"""Process start to the window's opening: imports, weights made on the device
from the seed, compile or cache hit, warm-up of the cell's own shapes, the
correctness comparison, any lead-in traffic."""


def read(obs):
    return obs["setup_s"]

"""Training tokens per second over whole steps: the clock runs from the
device-synchronised start of the first measured step to the moment the last
step's loss is ready, so the rate is not quantised by a step at the window's
edge."""
from benchmark import arith


def read(obs):
    if "steps" not in obs:
        return None
    return arith.whole_step_rate(obs["steps"], obs["tokens_per_step"],
                                 obs["t_open"], obs["t_close"])

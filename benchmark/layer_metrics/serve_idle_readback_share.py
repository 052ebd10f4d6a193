"""Share of the traced window in which the device ran nothing while the
engine waited for the step's tokens (``engine.step.wait``)."""
from benchmark import host_spans


def read(obs):
    return host_spans.idle_share_under(obs, ["engine.step.wait"])

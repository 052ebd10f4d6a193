"""Share of device busy time in the LSTM recurrence: the ``while`` loops of
the scan path (their bodies' operations included) or the fused Pallas
kernels, by name in the trace."""
from benchmark import trace_reduce

RECURRENCE = r"^%?while[.\d]* = |lstm"


def seconds(obs):
    t = obs.get("trace")
    if not t or "steps" not in obs:
        return None
    return trace_reduce.ops_seconds(t, RECURRENCE)


def read(obs):
    s = seconds(obs)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]

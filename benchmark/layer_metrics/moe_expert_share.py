"""Share of device busy time in the routed experts' grouped products:
``jax.lax.ragged_dot``, which XLA:TPU lowers to Mosaic calls named
``ragged-dot-...`` (the products and the group metadata before them)."""
from benchmark import trace_reduce

GROUPED = r"ragged-dot"


def seconds(obs):
    t = obs.get("trace")
    if not t or "kda_kernels" not in obs:
        return None
    return trace_reduce.ops_seconds(t, GROUPED) or None


def read(obs):
    s = seconds(obs)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]

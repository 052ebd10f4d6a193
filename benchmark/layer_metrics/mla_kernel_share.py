"""Share of device busy time in the ``mla_chunk`` kernel
(ops/pallas/mla.py), by the kernel's name in the trace."""
from benchmark import trace_reduce

KERNEL = r"mla_chunk"


def seconds(obs):
    t = obs.get("trace")
    if not t or "mla_kernels" not in obs:
        return None
    return trace_reduce.ops_seconds(t, KERNEL) or None


def read(obs):
    s = seconds(obs)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]

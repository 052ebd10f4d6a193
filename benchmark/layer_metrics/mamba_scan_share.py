"""Share of device busy time in the ``mamba_chunk`` kernel
(ops/pallas/mamba.py), by the kernel's name in the trace.  A program
without the kernel (the parent; a declined shape, whose scan is an XLA
``while``) gives nothing."""
from benchmark import trace_reduce

KERNEL = r"mamba_chunk"


def seconds(obs):
    t = obs.get("trace")
    if not t or "mamba_kernels" not in obs:
        return None
    return trace_reduce.ops_seconds(t, KERNEL) or None


def read(obs):
    s = seconds(obs)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]

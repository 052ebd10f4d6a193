"""Share of device busy time in the exact top-k kernel of the sparse layers
(ops/pallas/dsa.py, ``sparse_select``), by the kernel's name at the head of
an op's HLO text (the attention, which reads its picks, names it among its
operands, and is not counted).  A program whose step does not say which
path its sparse layers took gives nothing."""
from benchmark import trace_reduce

KERNEL = r"^%?sparse_select(\.\d+)? ="


def seconds(obs):
    t = obs.get("trace")
    if not t or "sparse_kernels" not in obs:
        return None
    return trace_reduce.ops_seconds(t, KERNEL) or None


def read(obs):
    s = seconds(obs)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]

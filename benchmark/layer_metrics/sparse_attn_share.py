"""Share of device busy time in the attention over the selected positions
(ops/pallas/dsa.py, ``sparse_attn_paged_chunk``), by the kernel's name at
the head of an op's HLO text (the ops that read its output name it among
their operands, and are not counted).  A program whose step does not say
which path its sparse layers took gives nothing."""
from benchmark import trace_reduce

KERNEL = r"^%?sparse_attn_paged_chunk(\.\d+)? ="


def seconds(obs):
    t = obs.get("trace")
    if not t or "sparse_kernels" not in obs:
        return None
    return trace_reduce.ops_seconds(t, KERNEL) or None


def read(obs):
    s = seconds(obs)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]

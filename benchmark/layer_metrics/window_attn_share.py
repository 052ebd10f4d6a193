"""Share of device busy time in the window kernel over the window layers'
per-slot rings (ops/pallas/decode_attention.py, ``decode_attn_window_chunk``),
by the kernel's name in the trace.  A program whose step does not say which
path its window layers took (one without them) gives nothing."""
from benchmark import trace_reduce

KERNEL = r"decode_attn_window_chunk"


def seconds(obs):
    t = obs.get("trace")
    if not t or "window_kernels" not in obs:
        return None
    return trace_reduce.ops_seconds(t, KERNEL) or None


def read(obs):
    s = seconds(obs)
    return None if s is None else 100.0 * s / obs["trace"]["busy_s"]

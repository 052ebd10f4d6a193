"""Share of device busy time in the paged decode-attention kernel
(ops/pallas/decode_attention.py, ``decode_attn_paged_chunk``) under a served
model's softmax attention layers, by the kernel's name in the trace (the
step holds other Mosaic calls, so the call target alone would not do).  A
program whose step does not say which attention path it took (the parent)
gives nothing."""
from benchmark import trace_reduce

KERNEL = r"decode_attn_paged_chunk"


def read(obs):
    t = obs.get("trace")
    if not t or "attn_kernels" not in obs:
        return None
    s = trace_reduce.ops_seconds(t, KERNEL)
    return 100.0 * s / t["busy_s"] if s else None

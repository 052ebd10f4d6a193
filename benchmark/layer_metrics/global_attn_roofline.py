"""The paged kernel under the full-attention layers of a model with window
layers, against its roofline: the least time their attention WORK allows
over the window (costs_laguna: the operations of every attended position of
every lane and head, from ``attended_positions_total``, over the bf16 peak,
or the positions each row reads once, ``read_positions_total``, with the
lanes' queries and results, over the HBM peak, whichever is larger), over
the time in ``decode_attn_paged_chunk``."""
from benchmark import costs_laguna, trace_reduce

KERNEL = r"decode_attn_paged_chunk"
COUNTERS = ("attended_positions_total", "read_positions_total",
            "active_slot_steps_total", "prefill_chunk_lanes_total")


def read(obs):
    t = obs.get("trace")
    if not t or "window_kernels" not in obs or not obs.get("peaks") \
            or not set(COUNTERS) <= set(obs["counters_after"]):
        return None
    s = trace_reduce.ops_seconds(t, KERNEL)
    if not s:
        return None
    d = {k: obs["counters_after"][k] - obs["counters_before"][k]
         for k in COUNTERS}
    lanes = d["active_slot_steps_total"] + d["prefill_chunk_lanes_total"]
    return 100.0 * costs_laguna.attn_least_seconds(
        obs["config"], obs["peaks"], "full", d["attended_positions_total"],
        d["read_positions_total"], lanes) / s

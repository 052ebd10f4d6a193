"""The sparse attention kernel against its roofline: the least time the
attention WORK allows over the window (costs_keye: every selected position
of every lane and query head, from ``sparse_selected_positions_total``, over
the bf16 peak, or the K and V of each row's selected positions once a layer,
``sparse_read_positions_total``, with the lanes' queries and results, over
the HBM peak, whichever is larger), over the kernel's device time: the
WORK, whatever implements it."""
from benchmark import costs_keye
from benchmark.layer_metrics import sparse_attn_share

COUNTERS = ("sparse_selected_positions_total", "sparse_read_positions_total",
            "active_slot_steps_total", "prefill_chunk_lanes_total")


def read(obs):
    s = sparse_attn_share.seconds(obs)
    if not s or not obs.get("peaks") \
            or not set(COUNTERS) <= set(obs["counters_after"]):
        return None
    d = {k: obs["counters_after"][k] - obs["counters_before"][k]
         for k in COUNTERS}
    lanes = d["active_slot_steps_total"] + d["prefill_chunk_lanes_total"]
    cfg = obs["config"]
    return 100.0 * costs_keye.least_seconds(
        obs["peaks"],
        costs_keye.attn_flops(cfg, d["sparse_selected_positions_total"]),
        costs_keye.attn_bytes(cfg, d["sparse_read_positions_total"],
                              lanes)) / s

"""The indexer's kernel against its roofline: the least time the scoring
WORK allows over the window (costs_keye: every scored position of every lane
and indexer head, from ``sparse_scored_positions_total``, over the bf16
peak, or each row's keys once a layer, ``read_positions_total``, with the
lanes' queries, over the HBM peak, whichever is larger), over the kernel's
device time."""
from benchmark import costs_keye
from benchmark.layer_metrics import indexer_share

COUNTERS = ("sparse_scored_positions_total", "read_positions_total",
            "active_slot_steps_total", "prefill_chunk_lanes_total")


def read(obs):
    s = indexer_share.seconds(obs)
    if not s or not obs.get("peaks") \
            or not set(COUNTERS) <= set(obs["counters_after"]):
        return None
    d = {k: obs["counters_after"][k] - obs["counters_before"][k]
         for k in COUNTERS}
    lanes = d["active_slot_steps_total"] + d["prefill_chunk_lanes_total"]
    cfg = obs["config"]
    return 100.0 * costs_keye.least_seconds(
        obs["peaks"],
        costs_keye.indexer_flops(cfg, d["sparse_scored_positions_total"]),
        costs_keye.indexer_bytes(cfg, d["read_positions_total"], lanes)) / s

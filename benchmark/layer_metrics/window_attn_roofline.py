"""The window kernel against its roofline: the least time the window
layers' attention WORK allows over the window (costs_laguna: the operations
of every attended position of every lane and head, from
``window_attended_positions_total``, over the bf16 peak, or the positions
each row reads once, ``window_read_positions_total``, with the lanes'
queries and results, over the HBM peak, whichever is larger), over the
kernel's device time."""
from benchmark import costs_laguna
from benchmark.layer_metrics import window_attn_share

COUNTERS = ("window_attended_positions_total", "window_read_positions_total",
            "active_slot_steps_total", "prefill_chunk_lanes_total")


def read(obs):
    s = window_attn_share.seconds(obs)
    if not s or not obs.get("peaks") \
            or not set(COUNTERS) <= set(obs["counters_after"]):
        return None
    d = {k: obs["counters_after"][k] - obs["counters_before"][k]
         for k in COUNTERS}
    lanes = d["active_slot_steps_total"] + d["prefill_chunk_lanes_total"]
    return 100.0 * costs_laguna.attn_least_seconds(
        obs["config"], obs["peaks"], "window",
        d["window_attended_positions_total"],
        d["window_read_positions_total"], lanes) / s

"""The grouped expert products against their roofline, which is memory at
these batches: the held experts' three matrices a step and expert layer,
as far as a step's live tokens touch them (costs_hybrid.moe_expert_bytes:
an expert no token chose is not read and not counted), over the HBM peak,
over the products' device time."""
from benchmark import costs_hybrid
from benchmark.layer_metrics import moe_expert_share


def read(obs):
    s = moe_expert_share.seconds(obs)
    if not s or not obs.get("peaks"):
        return None
    d = {k: obs["counters_after"][k] - obs["counters_before"][k]
         for k in ("decode_steps_total", "active_slot_steps_total",
                   "prefill_chunk_lanes_total")}
    lanes = d["active_slot_steps_total"] + d["prefill_chunk_lanes_total"]
    least = costs_hybrid.moe_expert_bytes(
        obs["config"], d["decode_steps_total"], lanes) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / s

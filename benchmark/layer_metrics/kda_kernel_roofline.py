"""``kda_chunk`` against its roofline, which is memory: the float32 state of
every SEATED slot read and written once a step and KDA layer, plus the live
lanes' operands (costs_hybrid.kda_kernel_bytes, from the window's counters),
over the HBM peak, over the kernel's device time.  Free slots' states move
too and are not counted, so skipping them could not read over 100%."""
from benchmark import costs_hybrid
from benchmark.layer_metrics import kda_kernel_share


def read(obs):
    s = kda_kernel_share.seconds(obs)
    if not s or not obs.get("peaks"):
        return None
    d = {k: obs["counters_after"][k] - obs["counters_before"][k]
         for k in ("active_slot_steps_total", "prefill_chunk_lanes_total")}
    lanes = d["active_slot_steps_total"] + d["prefill_chunk_lanes_total"]
    least = costs_hybrid.kda_kernel_bytes(
        obs["config"], d["active_slot_steps_total"], lanes) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / s

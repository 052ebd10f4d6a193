"""``mla_chunk`` against its roofline: the least time latent attention's
WORK allows over the window (costs_pangu: the operations of every attended
position of every lane, head and layer over the bf16 peak, or the latents,
queries and results over the HBM peak, whichever is larger; on a v5e the
operations), over the kernel's device time.  What the kernel computes or
moves beyond that (its zero padding, masked columns of a last tile, a tile
read once a lane group) is not counted."""
from benchmark import costs_pangu
from benchmark.layer_metrics import mla_kernel_share


def read(obs):
    s = mla_kernel_share.seconds(obs)
    if not s or not obs.get("peaks"):
        return None
    d = {k: obs["counters_after"][k] - obs["counters_before"][k]
         for k in ("attended_positions_total", "active_slot_steps_total",
                   "prefill_chunk_lanes_total")}
    lanes = d["active_slot_steps_total"] + d["prefill_chunk_lanes_total"]
    return 100.0 * costs_pangu.mla_kernel_least_seconds(
        obs["config"], obs["peaks"], d["attended_positions_total"],
        lanes) / s

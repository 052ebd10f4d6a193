"""``mamba_chunk`` against the least traffic of the selective scan's WORK:
the float32 state of every SEATED slot read and written once a step and
Mamba layer, plus each live lane's ``u``, ``dt``, ``B``, ``C`` in and ``y``
out (costs_jamba.mamba_kernel_bytes, from the window's counters, as
``kda_kernel_roofline`` counts them), over the HBM peak, over the kernel's
device time.  What the kernel moves beyond that (``B`` and ``C`` along 128
lanes, free slots' states) is not counted, so it cannot pass 100%.  The
scan's arithmetic (an ``exp`` and eight operations an element of state and
lane) is VPU and EUP work that no published peak bounds: where a step
prefills, the share reads low, and says how far from memory-bound the scan
is."""
from benchmark import costs_jamba
from benchmark.layer_metrics import mamba_scan_share


def read(obs):
    s = mamba_scan_share.seconds(obs)
    if not s or not obs.get("peaks"):
        return None
    d = {k: obs["counters_after"][k] - obs["counters_before"][k]
         for k in ("active_slot_steps_total", "prefill_chunk_lanes_total")}
    lanes = d["active_slot_steps_total"] + d["prefill_chunk_lanes_total"]
    least = costs_jamba.mamba_kernel_bytes(
        obs["config"], d["active_slot_steps_total"], lanes) \
        / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / s

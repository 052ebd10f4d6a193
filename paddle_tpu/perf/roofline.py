"""Roofline model: (FLOPs, bytes accessed) -> predicted step time / MFU.

The classic two-ceiling roofline (Williams et al.): a step whose
arithmetic intensity (FLOPs per HBM byte) sits below the chip's ridge
point is bandwidth-bound, above it compute-bound; predicted time is

    t = max(flops / peak_flops, bytes / hbm_bw)

and predicted MFU = (flops / peak_flops) / t = min(1, intensity/ridge).
This is an UPPER BOUND on achievable MFU — it assumes perfect overlap of
compute and HBM traffic and ignores per-step dispatch overhead, so tiny
steps (SmallNet at 2 ms/batch) will measure well below their prediction.
The bytes input comes from XLA's cost model on whatever backend compiled
the program (the CPU backend in the no-chip-window case), so it reflects
f32 traffic unless the program itself casts; on TPU the auto bf16 policy
roughly halves matmul operand bytes — the prediction is conservative for
bandwidth-bound families.

``SPECS`` is the program's peaks table: every consumer in the package —
the serving engine's restore/handoff routers, through
``perf/analytic.predicted_*`` — reads a chip's peaks from here, by the
``device_kind`` JAX reports (``for_device_kind``).  A device that is not
in the table is an error, never a default.
"""

import dataclasses


@dataclasses.dataclass(frozen=True)
class ChipSpec:
    name: str
    device_kind: str        # jax.devices()[0].device_kind on this chip
    peak_flops: float       # dense bf16 FLOP/s (f32 for the cpu row)
    hbm_bytes_per_s: float  # HBM (DRAM for cpu) bandwidth, bytes/s
    # host<->device link (PCIe) bandwidth: the third roofline ceiling
    # the hierarchical KV tier lives under (a restore streams spilled
    # bytes over THIS link instead of recomputing over HBM+MXU).  The
    # public TPU spec sheets don't quote it; PCIe Gen3 x16 (~16 GB/s
    # effective) is the conservative fleet floor, so restore-vs-
    # recompute routing errs toward recompute.
    host_link_bytes_per_s: float = 16e9

    @property
    def ridge_intensity(self):
        """FLOPs/byte where the roofline's two ceilings meet."""
        return self.peak_flops / self.hbm_bytes_per_s


# Keyed by the short names the snapshot JSON uses; ``device_kind`` is
# what the chip itself reports (read off libtpu 0.0.34's topology
# descriptions; the v5e says "TPU v5 lite").  Peaks: Google Cloud TPU
# documentation, system architecture pages "TPU v5e" (197 TFLOP/s bf16,
# 819 GB/s HBM), "TPU v5p" (459 TFLOP/s, 2765 GB/s) and "TPU v4"
# (275 TFLOP/s, 1228 GB/s).  The cpu row is a sanity anchor only (one
# NUMA node, AVX-512 class) for the CPU test lane.
SPECS = {
    "v5e": ChipSpec("TPU v5e", "TPU v5 lite", 197e12, 819e9),
    "v5p": ChipSpec("TPU v5p", "TPU v5", 459e12, 2765e9),
    "v4": ChipSpec("TPU v4", "TPU v4", 275e12, 1228e9),
    "cpu": ChipSpec("cpu (sanity anchor)", "cpu", 1e11, 50e9),
}


def for_device_kind(device_kind):
    """The peaks row of the chip that reports ``device_kind``.  Raises
    ``KeyError`` naming the stranger: a number computed against an
    assumed chip is worse than no number."""
    for spec in SPECS.values():
        if spec.device_kind == device_kind:
            return spec
    raise KeyError(
        f"device_kind {device_kind!r} is not in the peaks table "
        f"(perf/roofline.SPECS knows "
        f"{sorted(s.device_kind for s in SPECS.values())}); add its row "
        "with a source before computing against it")


def predict(flops, bytes_accessed, spec):
    """Roofline prediction for one compiled step on one chip spec.

    Returns a dict with compute_ms / memory_ms (the two ceilings),
    predicted_ms (their max), predicted_mfu, the step's arithmetic
    intensity vs the chip's ridge point, and the named bottleneck.
    """
    if isinstance(spec, str):
        spec = SPECS[spec]
    if flops < 0 or bytes_accessed < 0:
        raise ValueError("flops/bytes_accessed must be non-negative")
    compute_s = flops / spec.peak_flops
    memory_s = bytes_accessed / spec.hbm_bytes_per_s
    t = max(compute_s, memory_s)
    intensity = (flops / bytes_accessed) if bytes_accessed else float("inf")
    return {
        "chip": spec.name,
        "compute_ms": compute_s * 1e3,
        "memory_ms": memory_s * 1e3,
        "predicted_ms": t * 1e3,
        "predicted_mfu": (compute_s / t) if t > 0 else 0.0,
        "arithmetic_intensity": intensity,
        "ridge_intensity": spec.ridge_intensity,
        "bottleneck": "compute" if compute_s >= memory_s else "memory",
    }

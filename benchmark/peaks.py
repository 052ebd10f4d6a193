"""Published peaks of the chips the benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device that is not here is an error, never a
default.  (paddle_tpu/perf/roofline.py SPECS holds the same row for the
program's own use; this copy is the yardstick's.)"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB HBM2e at 819 GB/s per chip
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def for_device_kind(kind):
    try:
        return PEAKS[kind]
    except KeyError:
        raise KeyError(f"no published peaks for device_kind {kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

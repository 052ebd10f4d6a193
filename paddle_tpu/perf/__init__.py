"""Chip-independent analytic performance layer.

What can be said about a program without running it on the chip — counts,
not speeds (a device time, rate or utilization comes only from a chip
run: `benchmark/run.py`):

- `cost`     — extract XLA's own cost model (FLOPs, bytes accessed,
               arithmetic intensity) plus an HLO op histogram from any
               `jax.jit(...).lower(...).compile()` executable, on ANY
               backend.
- `roofline` — map (flops, bytes) through a peak-FLOP/s x HBM-bandwidth
               roofline parameterized by public TPU spec tables (v5e,
               v5p, v4, cpu) into a predicted step time / predicted MFU
               and the named bottleneck (compute- vs memory-bound).
- `analytic` — structure gates over lowered programs (does the step
               still materialize the buffer a fused kernel avoids?) and
               the `predicted_*` byte/time models the serving engine
               routes by.  See docs/perf.md.
"""

from paddle_tpu.perf import cost, roofline  # noqa: F401

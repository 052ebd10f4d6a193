"""Chip-independent analytic performance layer.

What can be said about a program without running it on the chip — counts,
not speeds (a device time, rate or utilization comes only from a chip
run):

- `cost`     — extract XLA's own cost model (FLOPs, bytes accessed,
               arithmetic intensity) plus an HLO op histogram from any
               `jax.jit(...).lower(...).compile()` executable, on ANY
               backend (the CPU backend works every round).
- `roofline` — map (flops, bytes) through a peak-FLOP/s x HBM-bandwidth
               roofline parameterized by public TPU spec tables (v5e,
               v5p, v4, cpu) into a predicted step time / predicted MFU
               and the named bottleneck (compute- vs memory-bound).
- `analytic` — run the extraction over every bench.py family and write
               the round's `BENCH_ANALYTIC_r06.json` snapshot;
               `scripts/perf_report.py --analytic-diff old new` then
               diffs two snapshots structurally and fails loudly on
               de-fusion / bytes-inflation regressions.

Entry points: `python bench.py --analytic`, `python -m
paddle_tpu.perf.analytic`, `python -m paddle_tpu.scripts.bench_sweep
--analytic`.  See docs/perf.md "Analytic roofline".
"""

from paddle_tpu.perf import cost, roofline  # noqa: F401

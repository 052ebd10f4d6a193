"""Static invariant analyzer (docs/analysis.md).

Three load-bearing disciplines hold this codebase together, and until
now only runtime tests and review hardening enforced them:

* **jit-purity** — host-side subsystems (obs/ tracing, resilience fault
  hooks, serving metrics, logging, wall clocks, stdlib RNG, threads)
  never run inside a jit body; the only FLAGS the jitted steps may read
  are the documented trace-time ones.
* **retrace discipline** — every serving/training step is 1-trace/
  0-retrace: all variation is fed as DATA, never as Python-level
  branching on runtime values, host syncs (`.item()`, `int(tracer)`),
  or shape keys built from non-static args.
* **lock order** — the threaded serving tier (batcher/engine/router/
  fleet/autoscaler/supervisor) acquires its locks in a consistent
  global order (no cycles), and attributes guarded by a lock are not
  also mutated outside it.

This package checks all three STATICALLY, by AST, on every commit —
before any chip or chaos test runs, the same way `perf/analytic.py`
gates HLO structure.  Nothing here imports jax: the gate costs a parse,
not a trace.

    python -m paddle_tpu.analysis --check all|jit|retrace|locks [--json]

Non-zero exit on findings not covered by the committed allow-list
(`paddle_tpu/analysis/baseline.json`).  Every rule is proven in
REVERSE against a seeded-violation fixture (`analysis/fixtures/`,
pinned by tests/test_analysis.py) — the analytic-gate discipline.

Modules:
  roots.py      the jitted-root registry (every jitted step's entry point)
  callgraph.py  AST project index + best-effort call/name resolution
  purity.py     jit-purity pass
  retrace.py    retrace-hazard pass (taint from the roots' data args)
  locks.py      lock-order + mixed-guard-mutation pass
  baseline.py   finding keys + committed allow-list round-trip
"""

from paddle_tpu.analysis.baseline import Finding  # noqa: F401

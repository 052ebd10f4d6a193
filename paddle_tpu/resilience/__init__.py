"""Resilience layer: deterministic fault injection + supervised recovery.

    faults.py      named fault points compiled into the hot paths
                   (serving execute/prefill/decode step, batcher submit,
                   prefetch H2D, trainer step, checkpoint write), driven
                   by seeded FaultPlan schedules — every chaos run
                   replays bit-for-bit; strict no-op when disabled
    supervisor.py  per-step watchdog (deadline -> rebuild from the AOT
                   cache), decode-slot re-prefill recovery (greedy
                   streams bit-identical across a mid-stream rebuild),
                   circuit breaker (fast 503 + Retry-After), bounded
                   retry with backoff+jitter for transient submits
    __main__.py    chaos smoke CLI: serving
                   under an injected decode fault + kill-9 trainer
                   resume, one JSON line

Docs: docs/serving.md §6.  Flags: resilience_* in utils/flags.py.
"""

from paddle_tpu.resilience.faults import (FAULT_POINTS, FaultPlan,
                                          InjectedFault, TransientError)
from paddle_tpu.resilience.supervisor import (BreakerOpenError,
                                              CircuitBreaker, Supervisor,
                                              WatchdogTimeout,
                                              retry_transient)

__all__ = [
    "FAULT_POINTS", "FaultPlan", "InjectedFault", "TransientError",
    "BreakerOpenError", "CircuitBreaker", "Supervisor",
    "WatchdogTimeout", "retry_transient",
]

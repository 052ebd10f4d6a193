"""Median over the device steps of the host time the generation loop
spends around one step outside the engine: ``gen.loop.iter``'s self time
plus its ``admit``, ``prepare`` and ``emit`` phases of the same ``step``."""
from benchmark import arith, host_spans
from benchmark.layer_metrics.serve_idle_sched_share import SCHED


def read(obs):
    hs = host_spans.load(obs)
    if not hs:
        return None
    per_step = hs.self_seconds("gen.loop.iter")
    for name in SCHED:
        for s, e, stats in hs.phases.get(name, ()):
            if stats.get("step") in per_step:
                per_step[stats["step"]] += e - s
    p50 = arith.percentile(per_step.values(), 50)
    return None if p50 is None else p50 * 1e3

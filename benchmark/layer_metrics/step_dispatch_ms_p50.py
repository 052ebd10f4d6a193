"""Median ``engine.step.dispatch`` phase (``DecodeEngine.step``: the input
snapshots and the compiled step's call until it returns), on the
profiler's clock."""
from benchmark import arith, host_spans


def read(obs):
    hs = host_spans.load(obs)
    p50 = arith.percentile(hs.durations("engine.step.dispatch"), 50) \
        if hs else None
    return None if p50 is None else p50 * 1e3

"""Median time between consecutive steps' losses becoming ready
(``block_until_ready`` on the host's clock).  The first ``lag`` intervals
hold the pipeline filling and are left out."""
from benchmark import arith


def read(obs):
    if "done_times" not in obs:
        return None
    gaps = arith.intervals(obs["done_times"])[3:]
    p50 = arith.percentile(gaps, 50)
    return None if p50 is None else p50 * 1e3

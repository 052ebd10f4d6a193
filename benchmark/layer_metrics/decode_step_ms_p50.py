"""Median of the server's ``tpot`` histogram over the window: host clock
round one engine step that ends in reading its tokens."""
from benchmark import arith


def read(obs):
    p50 = arith.percentile(obs.get("tpot_s") or [], 50)
    return None if p50 is None else p50 * 1e3

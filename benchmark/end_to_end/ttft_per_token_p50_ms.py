"""Median over the measured requests of (first token received - time the
request was DUE) / prompt tokens.  Per token, so that it does not move with
the lengths a seed happens to draw; from the due time, so that a stall's
cost to later requests counts.  A failed request has no first token and
counts as infinitely late."""
from benchmark import arith


def read(obs):
    if "requests" not in obs:
        return None
    vals = [(r["token_times"][0] - r["due"]) * 1e3 / r["prompt_tokens"]
            if r["ok"] and r["token_times"] else float("inf")
            for r in obs["requests"] if r["measured"]]
    return arith.percentile(vals, 50)

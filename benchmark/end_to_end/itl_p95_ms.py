"""95th percentile of all gaps between consecutive streamed tokens of the
measured requests, at the client, first token excluded: the tail users feel
while reading."""
from benchmark import arith


def read(obs):
    if "requests" not in obs:
        return None
    gaps = [g for r in obs["requests"] if r["measured"]
            for g in arith.token_gaps_ms(r["token_times"])]
    return arith.percentile(gaps, 95)

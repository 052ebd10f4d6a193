"""Median ``gen.queue_wait`` span (obs/trace.py, enabled in the traced run
only) of the requests that started waiting inside the window: submit to
seat.  Lead-in and drain are left out, as they are from every other number
of the window; the spans are on ``time.time()``."""
from benchmark import arith


def read(obs):
    if not obs.get("spans"):
        return None
    w_open, w_close = obs["window_wall"]
    waits = [(s["t_end"] - s["t_start"]) * 1e3 for s in obs["spans"]
             if s["name"] == "gen.queue_wait" and s["t_end"] is not None
             and w_open <= s["t_start"] < w_close]
    return arith.percentile(waits, 50)

"""Median, over the fresh admissions seated inside the window, of (the
``slot`` span's ``first_token`` event - the span's start) / its
``prompt_tokens``: the part of the time to first token per prompt token that
the engine's steps take, seat to emit, without the queue before and the
front after.  Beside it, ``decode_step_ms_p50`` / ``prefill_chunk`` is what a
whole chunk every step would read."""
from benchmark import request_path


def read(obs):
    rows = request_path.prefills(obs)
    if rows is None:
        return None
    return request_path.percentile_ms(
        [r["seconds"] / r["prompt_tokens"] for r in rows], 50)

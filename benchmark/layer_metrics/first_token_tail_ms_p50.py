"""Median, on the profiler's clock, from the start of the
``engine.step.dispatch`` phase of step n to the end of the ``gen.loop.emit``
phase whose ``of_step`` is n, over the steps n that produced some request's
first token (the ``of_step`` of its ``slot`` span's ``first_token`` event):
the last chunk's step, and the next iteration's hand-over that the loop puts
before the read since it keeps one step in flight."""
from benchmark import request_path


def read(obs):
    return request_path.percentile_ms(request_path.first_token_tails(obs), 50)

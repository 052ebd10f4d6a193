"""Median, per request by ``trace_id``, of what the HTTP front adds round
the engine on the way to the first token: ``server.request`` start to
``gen.queue_wait`` start (parse, validate, enqueue) plus the server's
``first_token`` event less the slot's (the hand-over to the handler thread
and the write).  Both clocks are ``time.time()``.  More than a millisecond or
two is the handler threads or the interpreter lock."""
from benchmark import request_path


def read(obs):
    return request_path.percentile_ms(request_path.front_seconds(obs), 50)

"""95th percentile of the gap the engine hands a decoding stream between
two tokens: the interval between consecutive ``engine.step.wait`` ends (by
``of_step``), each step counted once for every DECODING row it carried
(``rows - prefill_rows`` of its ``engine.step.dispatch`` phase).
``itl_p95_ms`` is the same tail at the client: a wider gap between the two is
the front's."""
from benchmark import request_path


def read(obs):
    steps = request_path.step_intervals(obs)
    if steps is None:
        return None
    return request_path.percentile_ms(
        [s for s, rows, prefill in steps for _ in range(rows - prefill)], 95)

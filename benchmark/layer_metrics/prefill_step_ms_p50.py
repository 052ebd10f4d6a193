"""Median interval between consecutive ``engine.step.wait`` ends of the
device steps that carried a prompt chunk (``prefill_rows`` >= 1): what a
step costs when it prefills, to read beside ``decode_step_ms_p50``, the
median over all steps."""
from benchmark import request_path


def read(obs):
    steps = request_path.step_intervals(obs)
    if steps is None:
        return None
    return request_path.percentile_ms(
        [s for s, _rows, prefill in steps if prefill >= 1], 50)

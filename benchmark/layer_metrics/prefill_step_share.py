"""Share of the device steps handed over inside the trace's window that
carried a prompt chunk for at least one row (``prefill_rows`` >= 1 on the
``engine.step.dispatch`` phase): how much of the decoding streams' time is
spent in steps that also prefill."""
from benchmark import request_path


def read(obs):
    stats = request_path.dispatches(obs)
    if not stats:
        return None
    return 100.0 * sum(st["prefill_rows"] >= 1 for st in stats) / len(stats)

"""Share of the steps a prefilling row held its slot for that its feed did
not need: 100 x (1 - steps needed at a whole chunk a step / steps from the
first that fed the row, or left it out, to its last chunk's), summed over the
fresh admissions seated inside the window (``slot`` spans: ``teacher_forced``,
``chunk``, and the ``step`` of every ``prefill_chunk`` and ``prefill_stall``
event).  0 for a request alone on an idle engine; above 0 where
``prefill_chunk_budget`` or the step's width cut a row's chunk or left it one
token through lane 0."""
from benchmark import request_path


def read(obs):
    rows = request_path.prefills(obs)
    taken = sum(r["taken"] for r in rows or ())
    if not taken:
        return None
    return 100.0 * (1.0 - sum(r["needed"] for r in rows) / taken)

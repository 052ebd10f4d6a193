"""Share of the traced window the trainer's batch loop spent in
``trainer.feed`` (the reader, the DataFeeder's conversion, the global
arrays' assembly) for batches it went on to train: ``feed_wait_share``
from inside the program, on the profiler's clock."""
from benchmark import host_spans


def read(obs):
    hs = host_spans.load(obs)
    fed = hs.in_window(["trainer.feed"], hs.steps_of("trainer.step")) \
        if hs else None
    return None if fed is None else 100.0 * fed / hs.window_s

"""1 if the fused Pallas RNN kernels were dispatched when the step was
traced (``ops.rnn.FUSED_DISPATCH_COUNT`` moved), 0 if the XLA scan path
trains.  A fact about the path, not part of ``correct``."""


def read(obs):
    if "fused_dispatches" not in obs:
        return None
    return 1.0 if obs["fused_dispatches"] > 0 else 0.0

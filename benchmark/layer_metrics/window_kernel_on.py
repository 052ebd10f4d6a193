"""1 if the engine's step took the window kernel over its window layers'
rings (``engine.window_kernels``), 0 if its guard declined and the XLA path
serves them.  A fact about the path, not part of ``correct``."""


def read(obs):
    if "window_kernels" not in obs:
        return None
    return 1.0 if obs["window_kernels"] else 0.0

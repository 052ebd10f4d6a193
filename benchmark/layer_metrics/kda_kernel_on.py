"""1 if the engine's step took the Pallas ``kda_chunk`` kernel
(``engine.kda_kernels``), 0 if its guard declined and the XLA scan serves.
A fact about the path, not part of ``correct``."""


def read(obs):
    if "kda_kernels" not in obs:
        return None
    return 1.0 if obs["kda_kernels"] else 0.0

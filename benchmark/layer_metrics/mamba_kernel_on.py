"""1 if the engine's step took the Pallas ``mamba_chunk`` kernel at every
width it is compiled at (``engine.mamba_kernels``), 0 if its guard declined
and the XLA scan serves.  A fact about the path, not part of ``correct``."""


def read(obs):
    if "mamba_kernels" not in obs:
        return None
    return 1.0 if obs["mamba_kernels"] else 0.0

"""1 if the engine's step took the Pallas ``mla_chunk`` kernel
(``engine.mla_kernels``), 0 if its guard declined and XLA gathers and
attends.  A fact about the path, not part of ``correct``."""


def read(obs):
    if "mla_kernels" not in obs:
        return None
    return 1.0 if obs["mla_kernels"] else 0.0

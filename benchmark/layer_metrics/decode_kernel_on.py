"""1 if the engine took the Pallas decode kernels (``engine.decode_kernels``),
0 if their guard declined and the XLA path serves.  A fact about the path,
not part of ``correct``."""


def read(obs):
    if "decode_kernels" not in obs:
        return None
    return 1.0 if obs["decode_kernels"] else 0.0

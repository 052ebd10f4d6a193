"""1 if the engine's step took the indexer, selection and sparse attention
kernels over its sparse layers (``engine.sparse_kernels``), 0 if their guard
declined and the XLA path serves them.  A fact about the path, not part of
``correct``."""


def read(obs):
    if "sparse_kernels" not in obs:
        return None
    return 1.0 if obs["sparse_kernels"] else 0.0

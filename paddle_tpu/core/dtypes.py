"""dtype policy.

The reference compiles with ``real`` = float or double (WITH_DOUBLE,
cmake flag; SURVEY.md §2.10).  On TPU the equivalent policy is: parameters
and optimizer state in float32, matmul/conv compute in bfloat16 (MXU-native),
reductions/softmax in float32.
"""

import jax.numpy as jnp

_param_dtype = jnp.float32
# None = auto: bfloat16 when the default backend is a TPU (MXU-native),
# float32 otherwise (XLA-CPU lacks bf16 kernels for some fused dots).
_compute_dtype = None

_NAMES = {
    "float32": jnp.float32,
    "float64": jnp.float64,
    "bfloat16": jnp.bfloat16,
    "float16": jnp.float16,
}


def set_policy(param_dtype="float32", compute_dtype=None):
    """compute_dtype=None restores the platform-auto policy."""
    global _param_dtype, _compute_dtype
    _param_dtype = _NAMES[str(param_dtype)] if isinstance(param_dtype, str) else param_dtype
    if compute_dtype is None:
        _compute_dtype = None
    else:
        _compute_dtype = _NAMES[str(compute_dtype)] if isinstance(compute_dtype, str) else compute_dtype


def param_dtype():
    return _param_dtype


def _auto_compute_dtype():
    import jax
    return jnp.bfloat16 if jax.default_backend() == "tpu" else jnp.float32


def compute_dtype():
    if _compute_dtype is None:
        return _auto_compute_dtype()
    return _compute_dtype


def to_compute(x):
    """Cast activations to the compute dtype (bf16 on the MXU path)."""
    if x.dtype in (jnp.float32, jnp.float64, jnp.bfloat16, jnp.float16):
        return x.astype(compute_dtype())
    return x


def to_param(x):
    return x.astype(_param_dtype)


def cast_tree(tree, dtype):
    """float32 leaves -> dtype; ids/lengths/masks (ints, bools) and other
    dtypes pass through.  The one shared implementation of the
    mixed-precision boundary cast (trainer step, eval, inference)."""
    import jax

    def cast(x):
        if hasattr(x, "dtype") and x.dtype == jnp.float32:
            return x.astype(dtype)
        return x
    return jax.tree_util.tree_map(cast, tree)

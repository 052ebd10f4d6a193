"""Dense/linear ops: fc, projections, mixed-layer combination.

Reference: FullyConnectedLayer (gserver/layers/FullyConnectedLayer.cpp),
projection zoo feeding MixedLayer (gserver/layers/Projection.h,
FullMatrixProjection, TransposedFullMatrixProjection, IdentityProjection,
DotMulProjection, ScalingProjection, DotMulOperator).  On TPU: keep matmuls
on the MXU in bfloat16, accumulate in f32 (preferred_element_type).
"""

import jax.numpy as jnp

from paddle_tpu.core import dtypes
from paddle_tpu.ops import activations


def matmul(x, w):
    """MXU-friendly matmul: bf16 inputs, >=f32 accumulation (f64 stays f64
    for the checkgrad sweeps)."""
    cd = dtypes.compute_dtype()
    acc = jnp.promote_types(cd, jnp.float32)
    return jnp.matmul(x.astype(cd), w.astype(cd),
                      preferred_element_type=acc)


def einsum(spec, x, w):
    """``matmul``'s policy for a two-operand einsum."""
    cd = dtypes.compute_dtype()
    return jnp.einsum(spec, x.astype(cd), w.astype(cd),
                      preferred_element_type=jnp.promote_types(
                          cd, jnp.float32))


def fc(x, w, b=None, act=None):
    """y = act(x @ w + b).  x: [..., in], w: [in, out], b: [out]."""
    y = matmul(x, w)
    if b is not None:
        y = y + b
    return activations.get(act)(y)


def full_matrix_projection(x, w):
    return matmul(x, w)


def trans_full_matrix_projection(x, w):
    """w stored [out, in] (reference TransposedFullMatrixProjection)."""
    return matmul(x, w.T)


def identity_projection(x, offset=0, size=None):
    if size is None:
        return x
    return x[..., offset:offset + size]


def dotmul_projection(x, w):
    """Elementwise scale by a learned vector: x * w, w: [size]."""
    return x * w


def scaling_projection(x, w):
    """Scale whole input by a learned scalar w: [1]."""
    return x * w.reshape(())


def dotmul_operator(a, b, scale=1.0):
    return scale * a * b


def linear_comb(x, w, size):
    """LinearCombinationLayer / convex_comb: weights [..., K] over K vectors
    [..., K*size] -> [..., size]."""
    k = w.shape[-1]
    xs = x.reshape(x.shape[:-1] + (k, size))
    return jnp.einsum("...k,...ks->...s", w, xs)

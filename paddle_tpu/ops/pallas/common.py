"""Shared Mosaic-layout helpers for the Pallas kernels.

Mosaic requires the last dimension of a block to be a multiple of the VPU
lane count (128) or the whole array dimension, so per-row statistics
(softmax running max/sum, sequence masks, saved lse) are stored
lane-REPLICATED in [rows, LANES] tiles and widened/narrowed with lanes().
"""

import jax.numpy as jnp

LANES = 128


def lanes(x, n):
    """[rows, LANES] lane-replicated -> [rows, n] (n <= LANES slices,
    multiples of LANES tile)."""
    if n == LANES:
        return x
    if n < LANES:
        return x[:, :n]
    return jnp.tile(x, (1, n // LANES))


# Mosaic's scoped-VMEM limit for a kernel that does not set its own: 16 MiB
# on every generation, whatever the core physically holds.
SCOPED_VMEM_DEFAULT_BYTES = 16 * 1024 * 1024


def _core_vmem_bytes():
    """Physical VMEM of one core of the device being compiled for, from
    Pallas's own table (128 MiB on a v5e): the default device or, in a
    chip-free compile, the ``abstract_device`` of the abstract mesh in use
    (``jax.sharding.use_abstract_mesh``; the verify skill has the recipe).
    The smallest generation's 16 MiB where that is no TPU (interpret
    mode)."""
    from jax.experimental.pallas import tpu as pltpu
    try:
        return pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:
        return SCOPED_VMEM_DEFAULT_BYTES


def vmem_budget_bytes(scoped_limit_raised=False):
    """VMEM a kernel may plan against: 7/8 of what it can be given, the
    rest is headroom for Mosaic's own buffers.  That is 14 MiB of the
    default scoped limit; a kernel that hands Mosaic its own limit
    (``vmem_limit_bytes``) passes ``scoped_limit_raised=True`` and plans
    against the core's physical VMEM instead (112 MiB on a v5e).  Override
    with PADDLE_TPU_KERNEL_VMEM_MB (or force the scan path by setting it
    tiny)."""
    import os
    mb = os.environ.get("PADDLE_TPU_KERNEL_VMEM_MB")
    if mb is not None:
        return int(float(mb) * 1024 * 1024)
    core = (_core_vmem_bytes() if scoped_limit_raised
            else SCOPED_VMEM_DEFAULT_BYTES)
    return core * 7 // 8


def vmem_limit_bytes(plan_bytes):
    """The scoped limit to hand Mosaic (``CompilerParams.vmem_limit_bytes``)
    for a kernel planned at ``plan_bytes``: the plan plus a sixteenth, and
    never under the default.  The margin is for what the plan cannot see:
    inside a larger program Mosaic's own count ran up to 2.3% OVER the LSTM's
    plan (d=1280, bt=128: docs/kernels.md, VMEM table).  A plan at the
    budget (7/8 of the core) gets 119/128 of the core."""
    return max(plan_bytes + plan_bytes // 16, SCOPED_VMEM_DEFAULT_BYTES)

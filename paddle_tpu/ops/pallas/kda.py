"""``kda_chunk``: the gated delta rule of a KDA layer (ops/kda.py) for the
serving step's ``[S, K]`` token lanes, with each slot's state held in VMEM.

Per head the state is ``S`` in ``R^{dk x dv}``, float32, and lane ``t`` does

    S <- Diag(a_t) S;  u = b_t (v_t - S^T k_t);  S <- S + k_t u^T;  o_t = S^T q_t

Plan.  Grid ``(slot, head group)``; a program holds ``hp`` states (64 KiB
each at dk = dv = 128), reads them from HBM once and writes them once (the
state operand is aliased to the state result), and walks the slot's OWN
lane count: the K lanes are unrolled and each sits under ``pl.when(t <
lengths[slot])``, so a decode row pays for one lane, not K.  ``lengths`` and
``fresh`` ride as scalar prefetch; a fresh slot (its chunk starts at
position 0) starts from zero instead of what the previous occupant left.
Everything is VPU work in float32: ``S^T k`` and ``S^T q`` are sublane
reductions of ``S * column``, the rank-one update is ``column * row``.

The per-key-channel operands (a, k, b k, q) therefore enter as COLUMNS:
``[S, H/hp, dk, hp*K]``, column ``h*K + t`` being head ``h`` of the group at
lane ``t`` (transposed by XLA outside: 4 x S*K*H*dk floats, small beside the
states), so that a lane's column is a static lane slice; v and the output
are rows ``[S, K, H*dv]``.  ``hp*K`` is a multiple of 128 on the chip (hp =
8 at K = 16): a narrower tile would be padded to 128 lanes in HBM and the
kernel would read 8 x the operands.

VMEM per program, double-buffered by the pipeline: 2 x (in + out state) x
hp x dk x dv x 4 B, plus 4 column tiles of dk x hp*K and two row tiles of
K x hp*dv: 2.8 MiB at hp = 8, far under the 16 MiB default.

``decline_reason`` is the one dispatch predicate (flag + shapes), shared by
``ops/kda.recurrence`` and by the engine's warm-up report."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import decode_attention as _dk

_LANES, _SUBLANES = 128, 8


def _kernel(lens_ref, fresh_ref, v_ref, a_ref, k_ref, kb_ref, q_ref, s_ref,
            o_ref, so_ref, *, kk, hp, dv):
    slot = pl.program_id(0)
    n = lens_ref[slot]
    fresh = fresh_ref[slot]
    o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(fresh != 0)
    def _():
        so_ref[...] = jnp.zeros_like(so_ref)

    @pl.when(fresh == 0)
    def _():
        so_ref[...] = s_ref[...]

    for h in range(hp):
        for t in range(kk):
            @pl.when(t < n)
            def _(h=h, t=t):
                lane, col = slice(t, t + 1), slice(h * kk + t, h * kk + t + 1)
                cols = slice(h * dv, (h + 1) * dv)
                st = so_ref[0, h] * a_ref[0, 0, :, col]
                r = jnp.sum(st * k_ref[0, 0, :, col], axis=0, keepdims=True)
                st = st + kb_ref[0, 0, :, col] * (v_ref[0, lane, cols] - r)
                o_ref[0, lane, cols] = jnp.sum(
                    st * q_ref[0, 0, :, col], axis=0, keepdims=True)
                so_ref[0, h] = st


def cost(slots, kk, heads, dk, dv):
    """``pl.CostEstimate`` of one call: every state read and written once,
    the lanes' operands once, 8 operations an element of state and lane."""
    state = slots * heads * dk * dv * 4
    lanes = slots * kk * heads * (4 * dk + 2 * dv) * 4
    return pl.CostEstimate(flops=8 * slots * kk * heads * dk * dv,
                           transcendentals=0,
                           bytes_accessed=2 * state + lanes)


def heads_per_program(kk, heads):
    """Heads a program holds: as many as fill 128 lanes with their K
    columns, and a divisor of ``heads``."""
    hp = max(1, min(heads, _LANES // kk))
    while heads % hp:
        hp -= 1
    return hp


@functools.partial(jax.jit, static_argnames=("hp", "interpret"))
def kda_chunk(q, k, v, a, beta, state, lengths, fresh, *, hp=None,
              interpret=None):
    """q, k, a ``[S, K, H, dk]``, v ``[S, K, H, dv]``, beta ``[S, K, H]``,
    state ``[S, H, dk, dv]``, all float32; lengths ``[S]`` in ``[1, K]``,
    fresh ``[S]`` bool -> (o ``[S, K, H, dv]``, new state).  Lanes at or
    past ``lengths`` leave the state alone and read 0 in ``o``.  Jitted so
    that a step's layers, and the widths a step is compiled at, share one
    trace and one Mosaic lowering (1.6 s and 0.5 s each at the serving
    shape, PERF.md 32.2)."""
    interpret = _dk._interpret(interpret)
    s, kk, heads, dk = q.shape
    dv = v.shape[-1]
    hp = hp or heads_per_program(kk, heads)
    problem = shape_problem(kk, heads, dk, dv, hp, interpret)
    if problem:
        raise ValueError(f"kda_chunk: {problem}")
    groups = heads // hp

    def cols(x):        # [S, K, H, dk] -> [S, H/hp, dk, hp*K]
        return x.reshape(s, kk, groups, hp, dk).transpose(0, 2, 4, 3, 1) \
            .reshape(s, groups, dk, hp * kk)

    col_spec = pl.BlockSpec((1, 1, dk, hp * kk),
                            lambda r, g, *_: (r, g, 0, 0))
    row_spec = pl.BlockSpec((1, kk, hp * dv), lambda r, g, *_: (r, 0, g))
    st_spec = pl.BlockSpec((1, hp, dk, dv), lambda r, g, *_: (r, g, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(s, groups),
        in_specs=[row_spec, col_spec, col_spec, col_spec, col_spec, st_spec],
        out_specs=[row_spec, st_spec])
    o, new_state = pl.pallas_call(
        functools.partial(_kernel, kk=kk, hp=hp, dv=dv),
        grid_spec=grid_spec, name="kda_chunk",
        out_shape=[jax.ShapeDtypeStruct((s, kk, heads * dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operand 7 (after the two prefetched scalars) is the state
        input_output_aliases={7: 1},
        cost_estimate=cost(s, kk, heads, dk, dv),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
    )(jnp.asarray(lengths, jnp.int32), jnp.asarray(fresh, jnp.int32),
      v.reshape(s, kk, heads * dv), cols(a), cols(k),
      cols(k * beta[..., None]), cols(q), state)
    return o.reshape(s, kk, heads, dv), new_state


def shape_problem(kk, heads, dk, dv, hp=None, interpret=False):
    """Why these shapes do not tile, or None."""
    hp = hp or heads_per_program(kk, heads)
    if heads % hp:
        return f"{heads} heads do not split into programs of {hp}"
    if interpret:
        return None
    if dk % _SUBLANES or dv % _LANES:
        return (f"state {dk} x {dv}: rows must be a multiple of {_SUBLANES} "
                f"and columns of {_LANES}")
    if (hp * kk) % _LANES or kk % _SUBLANES:
        return (f"{hp} heads x {kk} lanes do not fill {_LANES}-lane column "
                f"tiles, or {kk} lanes are not a multiple of {_SUBLANES}")
    return None


def decline_reason(kk, heads, dk, dv):
    """THE dispatch predicate: why ``kda_chunk`` will NOT serve these
    shapes (the ``pallas_decode`` flag, then the tiling), or None."""
    if not _dk.decode_kernels_enabled():
        return _dk.flag_decline_reason()
    return shape_problem(kk, heads, dk, dv, None, _dk._interpret(None))

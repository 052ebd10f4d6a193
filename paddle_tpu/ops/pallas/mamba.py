"""``mamba_chunk``: the selective scan of a Mamba-1 layer (ops/mamba.py) over
the serving step's PACKED lanes, with every slot's state held in VMEM.

Per slot the state is ``h`` in ``R^{n x d}`` (``n = d_state`` 16, ``d =
d_inner`` 5,120), float32, and a lane does

    h <- exp(dt A) * h + (dt u) B^T;    y = C^T h

with ``dt``, ``u``, ``y`` rows of ``d`` and ``B``, ``C`` vectors of ``n``:
an elementwise decay and one ``exp`` for every element of state and lane
(82 k a lane and layer), no matrix product anywhere.  All of it is VPU and
EUP work.

Plan.  The state lies ``[S, n, d]``: ``d_state`` on sublanes, ``d_inner`` on
lanes, two vregs for every 128 columns (with 16 minor it would be padded to
128 lanes: 8 x the bytes, PERF.md 27.1).  The kernel walks the step's packed
places, NOT ``[S, K]`` rows: a slot's lanes lie side by side in the packing
(``hybrid_lm.pack_lanes``), so place ``p`` continues the state of
``slot[p]``, and a step that feeds 180 lanes walks 180, whatever ``S x K``
is.  Grid ``(N / rows,)`` over chunks of ``rows`` places (64), sequential;
the whole state block ``[S, n, d]`` stays in VMEM across the grid (its
block index never changes), read from HBM once and written once (the state
operand is aliased to the state result).  ``u``, ``dt`` in and ``y`` out
are ``[rows, d]`` blocks of the packed arrays; chunks past the live count
map to the last live chunk, which the pipeline therefore neither fetches
nor writes again, and do nothing.  ``slot``, ``flags`` (``ZERO``: the place
is lane 0 of a row that starts at position 0, and starts from zero state
instead of what the previous occupant left; ``SKIP``: the place repeats a
lane, as the lanes past a row's length do where nothing is packed, and is
stepped over) and the live count ride as scalar prefetch.  A place's row of
``dt`` and ``u`` meets the state by a sublane broadcast; its ``B`` and ``C`` must lie along SUBLANES, constant along
lanes, and arrive so: XLA lays them out ``[N, n, 128]`` beforehand (16 KB a
place beside the 60 KB of ``u``, ``dt``, ``y``; a ``[n, 1]`` column would be
padded to the same tile in HBM anyway).  Mosaic loads no single row at a
dynamic offset, so the walk goes eight places at a time: an aligned ``[8,
128]`` tile of ``dt`` and ``u`` is loaded and a place's row picked out of it
statically, its ``y`` row is put into the tile of ``y`` by a select, and a
place past the live count (at most seven a step) computes and keeps nothing.
The body handles a place's 128 columns (two vregs of state) at a time,
statically unrolled, so that the chain mul, exp, mul, add, mul, sublane-sum
stays in registers and the 40 column pieces of a place are independent
work for the scheduler.

VMEM: 2 x (state in + out) x S x n x d x 4 B (21 MB at S = 16) + 2 x 3 x rows
x d x 4 B (7.9 MB) + 2 x 2 x rows x n x 128 x 4 B (2 MB) + A: 31 MB of the
v5e's 128, handed to Mosaic as its limit (``common.vmem_limit_bytes``).

Places at or past the live count are never written in ``y``:
``ops/mamba.mamba_chunk`` zeroes them where it costs nothing.

``decline_reason`` is the one dispatch predicate (flag + shapes), shared by
``ops/mamba.scan`` and by the engine's warm-up report."""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import common
from paddle_tpu.ops.pallas import decode_attention as _dk

_LANES, _SUBLANES = 128, 8
ROWS = 64           # packed places a grid step covers, at most
ZERO, SKIP = 1, 2   # a place's flags


def chunk_rows(n):
    """Places a grid step covers: the largest divisor of ``n`` within
    ``ROWS``."""
    rows = min(n, ROWS)
    while n % rows:
        rows -= 1
    return rows


def lane_width(d):
    """Lanes ``B`` and ``C`` are laid out along: a vreg's 128, or all of a
    narrower ``d`` (interpret mode)."""
    return _LANES if d % _LANES == 0 else d


def _kernel(slot_ref, flag_ref, live_ref, u_ref, dt_ref, b_ref, c_ref, a_ref,
            s_ref, y_ref, so_ref, *, rows, d, lw):
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _():
        so_ref[...] = s_ref[...]

    base = j * rows
    # places walked together: a sublane tile's 8 (fewer only where a chunk
    # is no multiple of 8, which compiles for no chip: interpret mode)
    gs = math.gcd(rows, _SUBLANES)
    row_of = jax.lax.broadcasted_iota(jnp.int32, (gs, lw), 0)

    def group(g, carry):
        first = pl.multiple_of(g * gs, gs)
        tile = pl.ds(first, gs)
        for r in range(gs):
            place = base + first + r
            flags = flag_ref[place]
            walked = jnp.logical_and(place < live_ref[0], flags & SKIP == 0)
            slot = slot_ref[place]
            zero = flags & ZERO != 0
            b, c = b_ref[first + r], c_ref[first + r]           # [n, lw]
            for col in range(0, d, lw):
                cols = slice(col, col + lw)
                old = so_ref[slot, :, cols]                     # [n, lw]
                dt = dt_ref[tile, cols][r:r + 1]                # [1, lw]
                u = u_ref[tile, cols][r:r + 1]
                h = jnp.exp(dt * a_ref[:, cols]) * jnp.where(zero, 0.0, old) \
                    + (dt * u) * b
                y = jnp.sum(h * c, axis=0, keepdims=True)
                y_ref[tile, cols] = jnp.where(row_of == r, y,
                                              y_ref[tile, cols])
                so_ref[slot, :, cols] = jnp.where(walked, h, old)
        return carry

    live_here = jnp.clip(live_ref[0] - base, 0, rows)
    jax.lax.fori_loop(0, (live_here + gs - 1) // gs, group, 0)


def cost(slots, n_places, d, n):
    """``pl.CostEstimate`` of one call at ``n_places`` live places: every
    state read and written once, the places' rows once, 8 operations and one
    ``exp`` an element of state and place."""
    state = slots * n * d * 4
    places = n_places * (3 * d + 2 * n * lane_width(d)) * 4
    return pl.CostEstimate(flops=8 * n_places * n * d,
                           transcendentals=n_places * n * d,
                           bytes_accessed=2 * state + places + n * d * 4)


def vmem_bytes(slots, n_places, d, n):
    """The plan's VMEM: every block double-buffered by the pipeline."""
    rows = chunk_rows(n_places)
    return 2 * 4 * (2 * slots * n * d + 3 * rows * d
                    + 2 * rows * n * lane_width(d) + n * d)


@functools.partial(jax.jit, static_argnames=("interpret",))
def mamba_chunk(u, dt, b, c, a, state, slot, flags, live, *, interpret=None):
    """u, dt ``[N, d]``, b, c ``[N, n]``, a ``[n, d]`` (negative), state
    ``[S, n, d]``, all float32; slot ``[N]`` int32 (the slot whose state
    place p continues), flags ``[N]`` int32 (``ZERO``: place p starts from
    zero state; ``SKIP``: it is stepped over), live int32 (places ``[0,
    live)`` are walked, in order) -> (y ``[N, d]``, new state).  Places
    skipped, or at or past ``live``, are left unwritten in ``y`` and touch
    no state.  Jitted so that a step's layers, and the widths a step
    is compiled at, share one trace and one Mosaic lowering each."""
    interpret = _dk._interpret(interpret)
    n_places, d = u.shape
    slots, n, _d = state.shape
    problem = shape_problem(n_places, slots, d, n, interpret)
    if problem:
        raise ValueError(f"mamba_chunk: {problem}")
    rows, lw = chunk_rows(n_places), lane_width(d)

    def chunk(j, _slot, _flags, live_ref):
        return jnp.minimum(j, jnp.maximum(live_ref[0] - 1, 0) // rows)

    row_spec = pl.BlockSpec((rows, d), lambda j, *m: (chunk(j, *m), 0))
    vec_spec = pl.BlockSpec((rows, n, lw),
                            lambda j, *m: (chunk(j, *m), 0, 0))
    a_spec = pl.BlockSpec((n, d), lambda j, *m: (0, 0))
    st_spec = pl.BlockSpec((slots, n, d), lambda j, *m: (0, 0, 0))
    wide = lambda x: jnp.broadcast_to(x[:, :, None], (n_places, n, lw))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(n_places // rows,),
        in_specs=[row_spec, row_spec, vec_spec, vec_spec, a_spec, st_spec],
        out_specs=[row_spec, st_spec])
    y, new_state = pl.pallas_call(
        functools.partial(_kernel, rows=rows, d=d, lw=lw),
        grid_spec=grid_spec, name="mamba_chunk",
        out_shape=[jax.ShapeDtypeStruct((n_places, d), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        # operand 8 (after the three prefetched scalars) is the state
        input_output_aliases={8: 1},
        cost_estimate=cost(slots, n_places, d, n),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=common.vmem_limit_bytes(
                vmem_bytes(slots, n_places, d, n))),
        interpret=interpret,
    )(jnp.asarray(slot, jnp.int32), jnp.asarray(flags, jnp.int32),
      jnp.asarray(live, jnp.int32).reshape(1), u, dt, wide(b), wide(c), a,
      state)
    return y, new_state


def shape_problem(n_places, slots, d, n, interpret=False):
    """Why these shapes do not tile or fit, or None."""
    if interpret:
        return None
    if d % _LANES or n % _SUBLANES:
        return (f"state {n} x {d}: rows must be a multiple of {_SUBLANES} "
                f"and columns of {_LANES}")
    if chunk_rows(n_places) % _SUBLANES:
        return (f"{n_places} packed places do not split into chunks of a "
                f"multiple of {_SUBLANES}")
    need = vmem_bytes(slots, n_places, d, n)
    budget = common.vmem_budget_bytes(scoped_limit_raised=True)
    if need > budget:
        return (f"{slots} states of {n} x {d} and the chunks need {need} "
                f"bytes of VMEM, over the budget of {budget}")
    return None


def decline_reason(n_places, slots, d, n):
    """THE dispatch predicate: why ``mamba_chunk`` will NOT serve these
    shapes (the ``pallas_decode`` flag, then the tiling and VMEM), or
    None."""
    if not _dk.decode_kernels_enabled():
        return _dk.flag_decline_reason()
    return shape_problem(n_places, slots, d, n, _dk._interpret(None))

"""``mla_chunk``: latent attention (ops/mla.py) in the absorbed form for the
serving step's ``[S, K]`` token lanes, walking each row's block table over
the shared latent pool.

Every head reads the SAME latent ``[RMSNorm(c) | k_r]`` of a position, so a
row's lanes x heads fold into the row dimension of one product: with the
up-projection absorbed into the query (``q_n W_uk``, done by XLA before the
call) a lane of a head is one row of ``W`` values, and a tile of ``T``
positions gives

    s = q lat^T            [rows, W] x [T, W]^T      W = pool width (640)
    o += softmax-weights(s) lat[:, :rank]            [rows, T] x [T, rank]

under the online softmax, float32 statistics, each row under its own causal
limit.  ``W_uv`` widens the result once a lane afterwards, in XLA.

Plan.  Grid ``(row, lane group)``; a lane group is ``lg`` consecutive lanes
of all ``H`` heads, ``lg * H`` query rows (1,024 at H = 128: 8 lanes; q is
``[S, K/lg, lg*H, W]``, a plain reshape of ``[S, K, H, W]``).  The pool
stays in HBM (``pl.ANY``); a program walks its group's tiles in a
``fori_loop``: the tile's live table entries are copied block by block into
a double-buffered ``[2, T, W]`` scratch, tile t + 1 in flight while tile t
is consumed.  What is skipped, not masked:

* a lane group past the row's length does nothing, and neither fetches its
  queries nor writes its output (the index maps hand it the block of the
  row's last live group, which the pipeline therefore keeps where it is);
* a row that feeds ONE lane (a decoding row, a free row) runs the same loop
  on the first ``H`` query rows alone: a 128-row product, not 1,024;
* tiles past the group's furthest position are neither copied nor
  computed, entries past it inside the last tile not copied (their rows
  keep finite data of an earlier tile under columns the mask removes: the
  scratch is zeroed once a call);
* tiles every lane of the group sees whole take no mask at all: only the
  last one or two do.

Rows of the output that no program wrote (lanes past a row's length) hold
whatever the buffer held: ``ops/mla.mla_chunk`` zeroes those lanes after the
output projection, where it costs nothing.

``decline_reason`` is the one dispatch predicate (flag + shapes), shared by
``ops/mla.mla_chunk`` and by the engine's warm-up report."""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import common
from paddle_tpu.ops.pallas import decode_attention as _dk
from paddle_tpu.ops.pallas.common import LANES, lanes

_NEG = -1e30
TILE_POSITIONS = 256    # positions a loop step covers (whole blocks)
QUERY_ROWS = 1024       # lanes x heads a program holds, at most


def lane_group(kk, heads):
    """Lanes a program holds: as many as keep ``lanes x heads`` within
    ``QUERY_ROWS``, and a divisor of ``kk``."""
    lg = max(1, min(kk, QUERY_ROWS // heads))
    while kk % lg:
        lg -= 1
    return lg


def _kernel(pos_ref, tbl_ref, q_ref, pool_hbm, o_ref, buf, sem, m_scr, l_scr,
            acc_scr, *, bs, g, kk, lg, heads, rank):
    r, grp = pl.program_id(0), pl.program_id(1)
    tile = g * bs
    # a row's lanes hold consecutive positions; lanes past its length
    # repeat the last live one's (transformer._chunk_lanes)
    p_first, p_last = pos_ref[r, 0], pos_ref[r, kk - 1]
    live = jnp.clip(p_last - p_first + 1 - grp * lg, 0, lg)
    g_first = pos_ref[r, grp * lg]
    g_last = jnp.minimum(g_first + lg - 1, p_last)
    n_tiles = g_last // tile + 1
    n_blocks = g_last // bs + 1
    # tiles whose every column every lane of the group may see
    n_whole = (g_first + 1) // tile

    @pl.when(jnp.logical_and(r == 0, grp == 0))
    def _():
        buf[...] = jnp.zeros_like(buf)

    def copies(t, slot, op):
        """Start, or wait for, the copies of tile t's live blocks: a loop
        over as many as are live, not g branches (the kernel is traced in
        every program that calls it, and g copies traced ten times over
        were most of that time)."""
        def one(i, carry):
            cp = pltpu.make_async_copy(
                pool_hbm.at[tbl_ref[r, t * g + i]],
                buf.at[slot, pl.ds(pl.multiple_of(i * bs, bs), bs)],
                sem.at[slot])
            getattr(cp, op)()
            return carry

        jax.lax.fori_loop(0, jnp.clip(n_blocks - t * g, 0, g), one, 0)

    def attend(rows):
        """The group's walk over its tiles, on its first ``rows`` query
        rows (row = lane * heads + head)."""
        m_scr[:rows] = jnp.full((rows, LANES), _NEG, jnp.float32)
        l_scr[:rows] = jnp.zeros((rows, LANES), jnp.float32)
        acc_scr[:rows] = jnp.zeros((rows, rank), jnp.float32)
        lim = jnp.minimum(
            g_first + jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 0)
            // heads, p_last)
        col = jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 1)
        copies(0, 0, "start")

        def body(t, carry, masked):
            slot = t % 2

            @pl.when(t + 1 < n_tiles)
            def _():
                copies(t + 1, 1 - slot, "start")

            copies(t, slot, "wait")
            q = q_ref[0, 0, :rows]
            lat = buf[slot].astype(q.dtype)                     # [tile, W]
            s = jax.lax.dot_general(
                q, lat, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)             # [rows, tile]
            if masked:
                s = jnp.where(col + t * tile <= lim, s, _NEG)
            m_prev = m_scr[:rows]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - lanes(m_new, tile))
            alpha = jnp.exp(m_prev - m_new)
            m_scr[:rows] = m_new
            l_scr[:rows] = l_scr[:rows] * alpha \
                + jnp.sum(p, axis=-1, keepdims=True)
            acc_scr[:rows] = acc_scr[:rows] * lanes(alpha, rank) \
                + jax.lax.dot_general(
                    p.astype(q.dtype), lat[:, :rank],
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)         # [rows, rank]
            return carry

        jax.lax.fori_loop(0, n_whole,
                          functools.partial(body, masked=False), 0)
        jax.lax.fori_loop(n_whole, n_tiles,
                          functools.partial(body, masked=True), 0)
        o_ref[0, 0, :rows] = (
            acc_scr[:rows] / lanes(jnp.maximum(l_scr[:rows], 1e-30), rank)
        ).astype(o_ref.dtype)

    if lg == 1:
        attend(heads)
        return

    @pl.when(live == 1)
    def _():
        attend(heads)

    @pl.when(live > 1)
    def _():
        attend(lg * heads)


def cost(s, kk, heads, width, rank, span, itemsize):
    """``pl.CostEstimate`` of one call whose lanes attend ``span``
    positions each: both products, the exponentials, each row's latents
    once a lane group, the queries in and the result out."""
    rows = s * kk * heads
    return pl.CostEstimate(
        flops=2 * rows * span * (width + rank),
        transcendentals=rows * span,
        bytes_accessed=itemsize * (s * (kk // lane_group(kk, heads)) * span
                                   * width + rows * (width + rank)))


def vmem_bytes(kk, heads, width, rank, tile, itemsize):
    """What a program plans to hold: the query and result blocks twice
    (the pipeline's), the accumulator and the statistics, the tile twice,
    and three score-sized temporaries."""
    m = lane_group(kk, heads) * heads
    return (2 * m * (width + rank) * itemsize + m * rank * 4
            + 2 * m * LANES * 4 + 2 * tile * width * itemsize
            + 3 * m * tile * 4)


@functools.partial(jax.jit, static_argnames=("rank", "lg", "interpret"))
def mla_attend(q, pool, qpos, tables, *, rank, lg=None, interpret=None):
    """q ``[S, K, H, W]`` the absorbed, scaled queries (``W`` the pool's
    width: latent columns, the rotated part, zero padding), pool
    ``[blocks, bs, W]``, qpos ``[S, K]`` the lanes' positions (consecutive
    in a row, lanes past its length repeating the last), tables
    ``[S, blocks_per_row]`` -> ``[S, K, H, rank]`` in q's dtype: the
    softmax-weighted latents of positions ``<= qpos``.  Lanes past a row's
    length may hold anything.  Jitted so that a step's layers share one
    trace and one Mosaic lowering.  ``lg``: lanes a program holds
    (``lane_group``'s unless a test says otherwise; a divisor of K)."""
    interpret = _dk._interpret(interpret)
    s, kk, heads, width = q.shape
    bs = pool.shape[1]
    problem = shape_problem(kk, heads, width, rank, bs, pool.dtype, interpret)
    if problem:
        raise ValueError(f"mla_chunk: {problem}")
    g = max(1, TILE_POSITIONS // bs)
    lg = lg or lane_group(kk, heads)
    groups, m = kk // lg, lg * heads

    def block(r, grp, pos, tbl):
        # a group past the row's length is handed the last live one's
        # block: nothing is fetched for it and nothing written back
        return r, jnp.minimum(grp, (pos[r, kk - 1] - pos[r, 0]) // lg), 0, 0

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(s, groups),
        in_specs=[pl.BlockSpec((1, 1, m, width), block),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec((1, 1, m, rank), block),
        scratch_shapes=[
            pltpu.VMEM((2, g * bs, width), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((m, LANES), jnp.float32),
            pltpu.VMEM((m, LANES), jnp.float32),
            pltpu.VMEM((m, rank), jnp.float32)])
    plan = vmem_bytes(kk, heads, width, rank, g * bs,
                      max(q.dtype.itemsize, pool.dtype.itemsize))
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, g=g, kk=kk, lg=lg, heads=heads,
                          rank=rank),
        grid_spec=grid_spec, name="mla_chunk",
        out_shape=jax.ShapeDtypeStruct((s, groups, m, rank), q.dtype),
        cost_estimate=cost(s, kk, heads, width, rank,
                           tables.shape[1] * bs // 4, q.dtype.itemsize),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=common.vmem_limit_bytes(plan)),
        interpret=interpret,
    )(jnp.asarray(qpos, jnp.int32), jnp.asarray(tables, jnp.int32),
      q.reshape(s, groups, m, width), pool)
    return out.reshape(s, kk, heads, rank)


def shape_problem(kk, heads, width, rank, bs, pool_dtype, interpret=False):
    """Why these shapes do not tile, or None."""
    if rank > width:
        return f"rank {rank} is wider than the pool's {width} columns"
    if interpret:
        return None
    sub = 8 * 4 // jnp.dtype(pool_dtype).itemsize     # rows of a tile
    if width % LANES or rank % LANES:
        return (f"pool width {width} and rank {rank} must be whole "
                f"{LANES}-lane tiles")
    if TILE_POSITIONS % bs or bs % sub:
        return (f"blocks of {bs} positions: a block must be whole "
                f"{sub}-row tiles of {jnp.dtype(pool_dtype).name} and "
                f"divide {TILE_POSITIONS}")
    if heads % 16:
        return f"{heads} heads: a lane's query rows must be whole tiles"
    plan = vmem_bytes(kk, heads, width, rank, TILE_POSITIONS, 4)
    if plan > common.vmem_budget_bytes(scoped_limit_raised=True):
        return f"the plan holds {plan} bytes of VMEM, over the budget"
    return None


def decline_reason(kk, heads, width, rank, bs, pool_dtype):
    """THE dispatch predicate: why ``mla_chunk`` will NOT serve these
    shapes (the ``pallas_decode`` flag, then the tiling), or None."""
    if not _dk.decode_kernels_enabled():
        return _dk.flag_decline_reason()
    return shape_problem(kk, heads, width, rank, bs, pool_dtype,
                         _dk._interpret(None))

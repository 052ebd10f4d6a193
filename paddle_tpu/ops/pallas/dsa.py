"""The three kernels of learned sparse attention over the paged pools
(DeepSeek Sparse Attention's lightning indexer, top-k and attention; the
layer that calls them is ``ops/dsa.py``, docs/kernels.md "Sparse
attention").  Each takes the step's rows ``[S, K]`` and walks a row's
positions up to its furthest lane, one grid step a row:

* ``indexer_paged_chunk`` (``indexer_paged_chunk`` in a trace): the
  indexer's scores ``I[t, s] = sum_j w_{t,j} ReLU(q_{t,j} . k_s)`` of every
  lane of a row against the row's positions, the keys copied block by block
  from their own paged leaf through the row's table, written to a ``[S, K,
  T]`` float32 buffer tile by tile.  A row that feeds one lane (a decoding
  row) computes that lane alone and writes it to all K of its lanes.
* ``sparse_select`` (``sparse_select``): an exact top-k of each lane's
  scores over the positions at or before its own, ties to the lower
  position, as a THRESHOLD: the order key of the k-th largest score and
  the position of the last tie taken (``picks``), found by bisection on the
  scores' order keys, eight lanes at a time, over the row's extent in VMEM;
  and the selection itself as bits (``plane_width``'s layout), which the
  step reports.
* ``sparse_attn_paged_chunk`` (``sparse_attn_paged_chunk``): softmax
  attention of the lanes over the positions they selected: the tiled paged
  walk of ``decode_attention._paged_tile_kernel`` (K and V blocks through
  the table, double-buffered by hand, query heads in panels, the one-lane
  path for a decoding row) under a mask that the tile's scores and the
  lane's threshold make.  It copies every block of a row: a position
  alone is less than a bfloat16 HBM tile of the leaf, which Mosaic cannot
  slice, and a decoding row's walk is not bound by what it copies (copying
  only the blocks and halves that hold a selected position left its time
  as it was; docs/kernels.md).

``decline_reason`` is the one predicate of the three (they share the score
buffer's layout); where it declines, ``ops/dsa.py`` runs them in XLA."""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas import decode_attention as da
from paddle_tpu.ops.pallas.common import LANES, lanes

INT_MIN = -2 ** 31
INDEX_TILE = 512        # positions an indexer tile scores
SELECT_CHUNK = 1024     # positions a pass of the selection reads at a time
SELECT_COPY = 4096      # positions a copy of the selection brings in


def order_keys(x):
    """float32 -> int32 whose signed order is the floats' (-0.0 just below
    +0.0)."""
    b = jax.lax.bitcast_convert_type(x, jnp.int32)
    return b ^ ((b >> 31) & 0x7FFFFFFF)


def plane_width(t):
    """Words a lane's selection bits take over a row of ``t`` positions:
    position p is bit ``p // width`` of word ``p % width`` (32 planes of
    ``width`` words, ``width`` a whole number of 128-lane tiles), so that a
    run of positions is a run of words in one plane."""
    return -(-(-(-t // 32)) // LANES) * LANES


def selected(keys, col, picks_thr, picks_w):
    """The threshold's selection: keys above it, and those equal to it up
    to the last tie taken."""
    return (keys > picks_thr) | ((keys == picks_thr) & (col <= picks_w))


def _each(n, fn, unroll=True):
    """fn(i) for i < n, traced once (unrolled as it lowers where ``n`` is
    static)."""
    def step(i, carry):
        fn(i)
        return carry
    jax.lax.fori_loop(0, n, step, 0, unroll=unroll)


# ------------------------------------------------------------ the indexer

def _indexer_kernel(pos_ref, tbl_ref, q_ref, w_ref, ik_hbm, o_hbm, kbuf,
                    obuf, sem, osem, state, *, bs, g, kk, heads):
    """One row: its tiles of ``g`` table entries in a loop, the next tile's
    key blocks in flight while one is scored (and during a row's last tile
    the next row's first: ``state[0]`` carries the buffer parity), each
    tile's ``[K, tile]`` scores copied out while the next is computed
    (``state[1]`` counts the tiles written)."""
    r = pl.program_id(0)
    tile = g * bs
    last = pos_ref[r, kk - 1]
    n_tiles = last // tile + 1

    def copies(row, t, slot, op):
        live = pos_ref[row, kk - 1] // bs + 1
        for i in range(g):
            @pl.when(t * g + i < live)
            def _():
                cp = pltpu.make_async_copy(
                    ik_hbm.at[tbl_ref[row, t * g + i]],
                    kbuf.at[slot, pl.ds(i * bs, bs)], sem.at[slot])
                getattr(cp, op)()

    def out_copy(slot, t):
        return pltpu.make_async_copy(
            obuf.at[slot], o_hbm.at[r, :, pl.ds(t * tile, tile)],
            osem.at[slot])

    @pl.when(r == 0)
    def _():
        # an entry past a row's furthest lane is never copied: its rows
        # keep finite keys of an earlier tile
        kbuf[...] = jnp.zeros_like(kbuf)
        state[0] = 0
        state[1] = 0
        copies(0, 0, 0, "start")

    first = state[0]
    written = state[1]
    one = last == pos_ref[r, 0]

    def body(t, carry):
        slot = (first + t) % 2

        @pl.when(t + 1 < n_tiles)
        def _():
            copies(r, t + 1, 1 - slot, "start")

        @pl.when(jnp.logical_and(t + 1 == n_tiles,
                                 r + 1 < pl.num_programs(0)))
        def _():
            copies(r + 1, 0, 1 - slot, "start")

        copies(r, t, slot, "wait")
        done = written + t
        oslot = done % 2

        @pl.when(done >= 2)
        def _():
            out_copy(oslot, 0).wait()

        k = kbuf[slot]

        @pl.when(one)
        def _():
            s = jax.lax.dot_general(
                q_ref[0, :heads], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [H, tile]
            i = jnp.sum(jnp.maximum(s, 0.0) * w_ref[0, :heads], axis=0,
                        keepdims=True)
            obuf[oslot] = jnp.broadcast_to(i, (kk, tile))

        @pl.when(jnp.logical_not(one))
        def _():
            s = jax.lax.dot_general(
                q_ref[0], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)        # [K H, tile]
            obuf[oslot] = (jnp.maximum(s, 0.0) * w_ref[0]).reshape(
                kk, heads, tile).sum(1)

        out_copy(oslot, t).start()
        return carry

    jax.lax.fori_loop(0, n_tiles, body, 0)
    state[0] = (first + n_tiles) % 2
    state[1] = written + n_tiles

    @pl.when(r == pl.num_programs(0) - 1)
    def _():
        done = state[1]
        for back in (1, 2):
            @pl.when(done >= back)
            def _():
                out_copy((done - back) % 2, 0).wait()


@functools.partial(jax.jit, static_argnames=("interpret",))
def indexer_paged_chunk(qi, w, ik, qpos, tables, *, interpret=None):
    """qi ``[S, K, H, D]`` (H indexer heads of D), w ``[S, K, H]`` float32,
    ik ``[blocks, block, D]`` (the keys' paged leaf, already written for the
    step), qpos ``[S, K]``, tables ``[S, E]`` -> scores ``[S, K, E x
    block]`` float32: lane (r, i)'s ``sum_j w_j ReLU(q_j . k_s)`` at each
    position s of the tiles (of ``INDEX_TILE`` positions) that reach row r's
    furthest lane.  Positions past a lane's own carry scores against
    whatever the pool holds there, and tiles past a row's furthest lane are
    left unwritten: whoever reads the buffer masks them."""
    interpret = da._interpret(interpret)
    s, kk, heads, dim = qi.shape
    bs = ik.shape[1]
    t = tables.shape[1] * bs
    g = _index_tile(bs, t) // bs
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[pl.BlockSpec((1, kk * heads, dim),
                               lambda r, pos, tbl: (r, 0, 0)),
                  pl.BlockSpec((1, kk * heads, 1),
                               lambda r, pos, tbl: (r, 0, 0)),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((2, g * bs, dim), ik.dtype),
            pltpu.VMEM((2, kk, g * bs), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((2,), jnp.int32),
        ],
    )
    kernel = functools.partial(_indexer_kernel, bs=bs, g=g, kk=kk,
                               heads=heads)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, name="indexer_paged_chunk",
        out_shape=jax.ShapeDtypeStruct((s, kk, t), jnp.float32),
        cost_estimate=pl.CostEstimate(
            flops=2 * s * kk * heads * dim * t,
            bytes_accessed=s * t * (dim * ik.dtype.itemsize + kk * 4),
            transcendentals=0),
        interpret=interpret,
    )(jnp.asarray(qpos, jnp.int32), jnp.asarray(tables, jnp.int32),
      qi.reshape(s, kk * heads, dim), w.reshape(s, kk * heads, 1)
      .astype(jnp.float32), ik)


def _index_tile(bs, t):
    """Positions an indexer tile scores: ``INDEX_TILE`` in whole blocks,
    at most the row."""
    return min(t, max(bs, INDEX_TILE // bs * bs))


# ------------------------------------------------------------- the top-k

def _select_kernel(pos_ref, i_hbm, o_ref, b_ref, fbuf, kscr, wv, bits, sem,
                   *, kk, topk, chunk, copy, nbits, width, piece):
    """One row, eight lanes at a time (one group where the row feeds one
    lane): the lanes' scores over the row's extent copied in, turned into
    order keys (``INT_MIN`` past each lane's position), then 32 passes that
    build the k-th largest key bit by bit, one that counts what lies above
    and at it, and, only where some lane has more ties at it than it takes,
    ``nbits`` passes that find the position of the last tie taken; the pass
    that counts what the lanes take also lays it out as bits, ``piece``
    positions (a run of words in one plane) at a time."""
    r = pl.program_id(0)
    last = pos_ref[r, kk - 1]
    n_copies = last // copy + 1
    n_chunks = last // chunk + 1
    groups = jnp.where(last == pos_ref[r, 0], 1, kk // 8)
    o_ref[...] = jnp.zeros_like(o_ref)
    b_ref[...] = jnp.zeros_like(b_ref)

    def count(pred):
        def body(c, acc):
            at = pl.multiple_of(c * chunk, chunk)
            col = jax.lax.broadcasted_iota(jnp.int32, (8, chunk), 1) + at
            return acc + pred(kscr[:, pl.ds(at, chunk)], col).astype(
                jnp.int32)
        acc = jax.lax.fori_loop(0, n_chunks, body,
                                jnp.zeros((8, chunk), jnp.int32))
        return jnp.sum(acc, axis=1, keepdims=True)              # [8, 1]

    def group(gi, carry):
        base = pl.multiple_of(gi * 8, 8)

        def cp(c):
            at = pl.multiple_of(c * copy, copy)
            return pltpu.make_async_copy(
                i_hbm.at[r, pl.ds(base, 8), pl.ds(at, copy)],
                fbuf.at[:, pl.ds(at, copy)], sem.at[0])

        _each(n_copies, lambda c: cp(c).start(), unroll=False)
        _each(n_copies, lambda c: cp(c).wait(), unroll=False)
        lane = jax.lax.broadcasted_iota(jnp.int32, (8, 1), 0)
        own = jax.lax.fori_loop(
            0, 8, lambda j, v: jnp.where(lane == j, pos_ref[r, base + j], v),
            jnp.zeros((8, 1), jnp.int32), unroll=True)

        def keys(c):
            at = pl.multiple_of(c * chunk, chunk)
            col = jax.lax.broadcasted_iota(jnp.int32, (8, chunk), 1) + at
            kscr[:, pl.ds(at, chunk)] = jnp.where(
                col <= own, order_keys(fbuf[:, pl.ds(at, chunk)]), INT_MIN)

        _each(n_chunks, keys, unroll=False)

        def bit(i, ans):        # the k-th largest key, unsigned, bit by bit
            cand = ans | jnp.left_shift(jnp.int32(1), 31 - i)
            hits = count(lambda x, col: x >= (cand ^ INT_MIN))
            return jnp.where(hits >= topk, cand, ans)

        ans = jax.lax.fori_loop(0, 32, bit, jnp.zeros((8, 1), jnp.int32))
        thr = ans ^ INT_MIN
        few = ans == 0          # no more than k positions: all are taken
        above = count(lambda x, col: x > thr)
        ties = count(lambda x, col: x == thr)
        need = topk - above

        def last_tie(c, v):
            at = pl.multiple_of(c * chunk, chunk)
            col = jax.lax.broadcasted_iota(jnp.int32, (8, chunk), 1) + at
            hit = jnp.where(kscr[:, pl.ds(at, chunk)] == thr, col, -1)
            return jnp.maximum(v, jnp.max(hit, axis=1, keepdims=True))

        wv[...] = jnp.broadcast_to(jax.lax.fori_loop(
            0, n_chunks, last_tie, jnp.full((8, 1), -1, jnp.int32)),
            wv.shape)
        extra = jnp.logical_and(ties > need, jnp.logical_not(few))

        @pl.when(jnp.max(extra.astype(jnp.int32)) > 0)
        def _():
            # the largest P with fewer than ``need`` ties before it: the
            # position of the need-th tie
            def pbit(i, p):
                cand = p | jnp.left_shift(jnp.int32(1), nbits - 1 - i)
                hits = count(lambda x, col: (x == thr) & (col < cand))
                return jnp.where(hits < need, cand, p)
            p = jax.lax.fori_loop(0, nbits, pbit,
                                  jnp.zeros((8, 1), jnp.int32))
            wv[...] = jnp.where(extra, p, wv[...])

        w = jnp.where(few, -1, wv[:, :1])
        bits[...] = jnp.zeros_like(bits)

        def take(c, acc):
            at = pl.multiple_of(c * chunk, chunk)
            col = jax.lax.broadcasted_iota(jnp.int32, (8, chunk), 1) + at
            sel = selected(kscr[:, pl.ds(at, chunk)], col, thr, w).astype(
                jnp.int32)
            for j in range(chunk // piece):
                lo = at + j * piece
                off = pl.ds(pl.multiple_of(lo % width, piece), piece)
                bits[:, off] = bits[:, off] | jnp.left_shift(
                    sel[:, j * piece:(j + 1) * piece], lo // width)
            return acc + sel

        taken = jnp.sum(jax.lax.fori_loop(
            0, n_chunks, take, jnp.zeros((8, chunk), jnp.int32)),
            axis=1, keepdims=True)
        col = jax.lax.broadcasted_iota(jnp.int32, (8, LANES), 1)
        o_ref[0, pl.ds(base, 8), :] = jnp.where(
            col == 0, thr, jnp.where(col == 1, w,
                                     jnp.where(col == 2, taken, 0)))
        b_ref[0, pl.ds(base, 8), :] = bits[...]
        return carry

    jax.lax.fori_loop(0, groups, group, 0)


@functools.partial(jax.jit, static_argnames=("topk", "interpret"))
def sparse_select(scores, qpos, topk, *, interpret=None):
    """scores ``[S, K, T]`` (``indexer_paged_chunk``'s), qpos ``[S, K]`` ->
    (picks ``[S, K, LANES]`` int32: lane 0 the order key (``order_keys``)
    of each lane's ``topk``-th largest score over the positions at or
    before its own, ``INT_MIN`` where it has no more than ``topk``; lane 1
    the position of its worst chosen, the last tie at that key it takes
    (ties go to the lower position), -1 where it takes every position; lane
    2 how many positions ``selected`` then takes; bits ``[S, K,
    plane_width(T)]`` int32: the positions taken).  Rows that feed one lane
    fill lanes 0-7 alike and leave the rest 0."""
    interpret = da._interpret(interpret)
    s, kk, t = scores.shape
    chunk, copy = min(SELECT_CHUNK, t), min(SELECT_COPY, t)
    width = plane_width(t)
    kernel = functools.partial(_select_kernel, kk=kk, topk=topk,
                               chunk=chunk, copy=copy,
                               nbits=int(t).bit_length(), width=width,
                               piece=math.gcd(width, chunk))
    row = lambda n: pl.BlockSpec((1, kk, n), lambda r, pos: (r, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s,),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[row(LANES), row(width)],
        scratch_shapes=[pltpu.VMEM((8, t), jnp.float32),
                        pltpu.VMEM((8, t), jnp.int32),
                        pltpu.VMEM((8, LANES), jnp.int32),
                        pltpu.VMEM((8, width), jnp.int32),
                        pltpu.SemaphoreType.DMA((1,))],
    )
    return pl.pallas_call(
        kernel, grid_spec=grid_spec, name="sparse_select",
        out_shape=[jax.ShapeDtypeStruct((s, kk, LANES), jnp.int32),
                   jax.ShapeDtypeStruct((s, kk, width), jnp.int32)],
        cost_estimate=pl.CostEstimate(
            flops=38 * s * kk * t,
            bytes_accessed=s * kk * (t + width) * 4, transcendentals=0),
        interpret=interpret,
    )(jnp.asarray(qpos, jnp.int32), scores)


# ---------------------------------------------------- attention over them

def _sparse_tile_kernel(pos_ref, tbl_ref, q_ref, pk_ref, k_hbm, v_hbm,
                        i_hbm, o_ref, kbuf, vbuf, ibuf, sem, first_slot,
                        m_scr, l_scr, acc_scr, lim_scr, q1_scr, *, bs, g, kk,
                        scale):
    """``decode_attention._paged_tile_kernel`` (no window) with each tile's
    scores ``[K, tile]`` copied in beside its K and V blocks (8 rows of them
    on the one-lane path) and the mask ``selected(order_keys(scores), col,
    threshold, last tie)`` of each lane laid over its causal one.  A tile
    none of whose positions a lane selected leaves that lane's running
    statistics as they were: its scores sit at -1e30, and until the lane's
    first selected position the masked weights are wiped by the exact 0.0
    of the first real tile's rescaling."""
    r = pl.program_id(0)
    tile = g * bs
    n_p, mp, wp = acc_scr.shape
    hg = mp // kk
    m1 = q1_scr.shape[1]
    last = pos_ref[r, kk - 1]
    n_tiles = last // tile + 1

    def one_lane(row):
        return pos_ref[row, kk - 1] == pos_ref[row, 0]

    def paths(fn):
        one = one_lane(r)
        pl.when(one)(lambda: fn(m1))
        pl.when(jnp.logical_not(one))(lambda: fn(mp))

    def copies(row, t, slot, op):
        live = pos_ref[row, kk - 1] // bs + 1
        for i in range(g):
            @pl.when(t * g + i < live)
            def _():
                bid = tbl_ref[row, t * g + i]
                for hbm, buf in ((k_hbm, kbuf), (v_hbm, vbuf)):
                    cp = pltpu.make_async_copy(
                        hbm.at[bid], buf.at[slot, pl.ds(i * bs, bs)],
                        sem.at[slot])
                    getattr(cp, op)()
        one = one_lane(row)
        for n, when in ((8, one), (kk, jnp.logical_not(one))):
            @pl.when(when)
            def _():
                cp = pltpu.make_async_copy(
                    i_hbm.at[row, pl.ds(0, n), pl.ds(t * tile, tile)],
                    ibuf.at[slot, pl.ds(0, n)], sem.at[slot])
                getattr(cp, op)()

    @pl.when(r == 0)
    def _():
        vbuf[...] = jnp.zeros_like(vbuf)
        kbuf[...] = jnp.zeros_like(kbuf)
        q1_scr[...] = jnp.zeros_like(q1_scr)
        first_slot[0] = 0
        copies(0, 0, 0, "start")

    first = first_slot[0]

    def init(rows):
        m_scr[:, :rows] = jnp.full((n_p, rows, LANES), da._NEG, jnp.float32)
        l_scr[:, :rows] = jnp.zeros((n_p, rows, LANES), jnp.float32)
        acc_scr[:, :rows] = jnp.zeros((n_p, rows, wp), jnp.float32)
        if rows < mp:
            def gather(h):      # lane 0 of head h: row h * K of each panel
                q1_scr[:, pl.ds(h, 1)] = \
                    q_ref[0, :, pl.ds(h * kk, 1)].astype(jnp.float32)

            _each(hg, gather)
            return
        lane = jax.lax.broadcasted_iota(jnp.int32, (mp, tile), 0) % kk
        lim_scr[...] = jax.lax.fori_loop(
            1, kk, lambda i, lim: jnp.where(lane >= i, pos_ref[r, i], lim),
            jnp.full((mp, tile), pos_ref[r, 0], jnp.int32), unroll=True)

    paths(init)

    def body(t, carry):
        slot = (first + t) % 2

        @pl.when(t + 1 < n_tiles)
        def _():
            copies(r, t + 1, 1 - slot, "start")

        @pl.when(jnp.logical_and(t + 1 == n_tiles,
                                 r + 1 < pl.num_programs(0)))
        def _():
            copies(r + 1, 0, 1 - slot, "start")

        copies(r, t, slot, "wait")

        def attend(rows):
            if rows < mp:
                col = jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1) \
                    + t * tile
                take = selected(order_keys(ibuf[slot, 0:1]), col,
                                pk_ref[0, 0:1, 0:1], pk_ref[0, 0:1, 1:2])
                seen = jnp.broadcast_to(
                    jnp.logical_and(take, col <= pos_ref[r, 0]),
                    (rows, tile))
            else:
                col = jax.lax.broadcasted_iota(jnp.int32, (kk, tile), 1) \
                    + t * tile
                take = selected(order_keys(ibuf[slot]), col,
                                pk_ref[0, :, 0:1], pk_ref[0, :, 1:2])
                # row (u, gq, i) of a panel is lane i
                take = jnp.tile(take.astype(jnp.int32), (hg, 1))
                col = jax.lax.broadcasted_iota(jnp.int32, (mp, tile), 1) \
                    + t * tile
                seen = jnp.logical_and(take != 0, col <= lim_scr[...])

            def panel(j):
                cols = pl.ds(pl.multiple_of(j * wp, wp), wp)
                q = q1_scr[j] if rows < mp \
                    else q_ref[0, j].astype(jnp.float32)
                s = jax.lax.dot_general(
                    q, kbuf[slot, :, cols], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale
                s = jnp.where(seen, s, da._NEG)
                m_prev, l_prev = m_scr[j, :rows], l_scr[j, :rows]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1,
                                                    keepdims=True))
                p = jnp.exp(s - lanes(m_new, tile))
                alpha = jnp.exp(m_prev - m_new)
                m_scr[j, :rows] = m_new
                l_scr[j, :rows] = l_prev * alpha + jnp.sum(p, axis=-1,
                                                           keepdims=True)
                acc_scr[j, :rows] = acc_scr[j, :rows] * lanes(alpha, wp) \
                    + jax.lax.dot_general(
                        p, vbuf[slot, :, cols], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)

            _each(n_p, panel)

        paths(attend)
        return carry

    jax.lax.fori_loop(0, n_tiles, body, 0)
    first_slot[0] = (first + n_tiles) % 2

    def finish(rows):
        def panel(j):
            o = acc_scr[j, :rows] / lanes(
                jnp.maximum(l_scr[j, :rows], 1e-30), wp)
            if rows == mp:
                o_ref[0, j] = o.astype(o_ref.dtype)
            else:
                acc_scr[j, :rows] = o

        _each(n_p, panel)
        if rows < mp:
            o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

            def put(h):
                o_ref[0, :, pl.ds(h * kk, 1)] = \
                    acc_scr[:, pl.ds(h, 1)].astype(o_ref.dtype)

            _each(hg, put)

    paths(finish)


@functools.partial(jax.jit, static_argnames=("num_heads", "interpret"))
def sparse_attn_paged_chunk(q, k, v, scores, picks, qpos, tables, *,
                            num_heads, interpret=None):
    """q ``[S, K, D]``, k / v ``[blocks, block, Dkv]`` (the pools, written
    for the step), scores ``[S, K, T]`` (``indexer_paged_chunk``'s), picks
    ``[S, K, LANES]`` (``sparse_select``'s), qpos ``[S, K]``, tables ``[S,
    E]`` -> ``[S, K, D]``: each lane's softmax attention over the
    positions ``selected`` takes at or before its own."""
    interpret = da._interpret(interpret)
    s, kk, d = q.shape
    bs, dkv = k.shape[1], k.shape[2]
    dh, hkv, group = da._head_split(d, dkv, num_heads)
    g = da.paged_chunk_tile(num_heads, d, dkv, bs, tables.shape[1], kk,
                            interpret=interpret)
    qp = da._to_panels(q, hkv, group, dh)
    _s, n_p, mp, wp = qp.shape
    m1 = da._one_lane_rows(da._panel_heads(hkv, dh), group, kk)
    row = pl.BlockSpec((1, n_p, mp, wp), lambda r, pos, tbl: (r, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[row, pl.BlockSpec((1, kk, LANES),
                                    lambda r, pos, tbl: (r, 0, 0)),
                  hbm, hbm, hbm],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((2, g * bs, dkv), k.dtype),
            pltpu.VMEM((2, g * bs, dkv), v.dtype),
            pltpu.VMEM((2, kk, g * bs), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((n_p, mp, LANES), jnp.float32),
            pltpu.VMEM((n_p, mp, LANES), jnp.float32),
            pltpu.VMEM((n_p, mp, wp), jnp.float32),
            pltpu.VMEM((mp, g * bs), jnp.int32),
            pltpu.VMEM((n_p, m1, wp), jnp.float32),
        ],
    )
    kernel = functools.partial(_sparse_tile_kernel, bs=bs, g=g, kk=kk,
                               scale=1.0 / math.sqrt(dh))
    t = tables.shape[1] * bs
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, name="sparse_attn_paged_chunk",
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=4 * s * kk * t * d,
            bytes_accessed=2 * s * t * dkv * k.dtype.itemsize
            + s * kk * t * 4 + 2 * s * kk * d * q.dtype.itemsize,
            transcendentals=s * kk * t),
        interpret=interpret,
    )(jnp.asarray(qpos, jnp.int32), jnp.asarray(tables, jnp.int32), qp,
      picks, k, v, scores)
    return da._from_panels(out, kk, hkv, group, dh)


# ------------------------------------------------------------ dispatch

def decline_reason(num_heads, d, dkv, block, entries, chunk, index_heads):
    """Why the three kernels will NOT serve a sparse layer of these shapes
    (None when they will): the paged kernel's flag and tiled form, a
    one-lane path, an indexer whose heads stack in whole sublane tiles and
    a row of positions the tiles divide."""
    why = da.decline_reason(num_heads, d, dkv, block, paged=True,
                            chunk=chunk)
    if why:
        return why
    split = da._head_split(d, dkv, num_heads)
    g = da.paged_chunk_tile(num_heads, d, dkv, block, entries, chunk)
    if g == 1:
        return (f"heads {num_heads} over Dkv {dkv} at block {block} take "
                "the block-a-grid-step kernel, which has no selection mask")
    if da._one_lane_rows(da._panel_heads(split[1], split[0]), split[2],
                         chunk) is None:
        return f"a chunk of {chunk} lanes has no one-lane path"
    t = entries * block
    if chunk % 8 or index_heads % 8:
        return (f"chunk {chunk} and indexer heads {index_heads} must be "
                "whole sublane tiles")
    if t % _index_tile(block, t) or t % (g * block) \
            or _index_tile(block, t) % (g * block) or t % LANES \
            or t % min(SELECT_COPY, t) or t % min(SELECT_CHUNK, t):
        return (f"a row of {t} positions is not a whole number of the "
                "kernels' tiles")
    return None

"""Fused decode-attention kernels (Pallas TPU): read the KV cache ONCE
per step.

A serving step moves every live row's K/V once a layer, and its time is
those bytes (docs/kernels.md "Decode-attention kernels"; PERF.md section
5 has the measured ``opt1.3b_chat`` step).  The reference XLA paths in
``models/transformer`` pay for the cache more than once:

* slab (``_cached_self_attn_chunk``): ``repeat_kv_heads`` widens the
  grouped K/V to full head width and the dense attention materializes
  the ``[S, K, H, T]`` score matrix in HBM before the softmax reads it
  back;
* paged (``_cached_self_attn_chunk_paged``): the per-row chain gather
  ``pool[tables]`` copies every row's blocks into a contiguous
  ``[S, T, Dkv]`` HBM buffer — a second full read AND a full write of
  the logical cache — before the same widened-score dance.

The TWO kernels here — slab and paged, each over the step's ``K`` token
lanes a row (``K = 1`` is a one-lane call of the same kernel) — delete
that traffic.  Per row the K/V stripe streams HBM -> VMEM exactly once;
the masked online softmax (flash-style running max/sum, the
``flash_attention.py`` recipe) and the grouped-KV -> full-head expansion
happen in VMEM/registers; neither the score matrix nor a second KV copy
ever exists in HBM.

* ``decode_attention_slab_chunk``: grid ``(S, T/blk)`` with the kv
  dimension innermost; per-lane ``qpos`` ride as SCALAR-PREFETCH data
  (``pltpu.PrefetchScalarGridSpec``) so the k-block index map CLAMPS at
  the row's furthest lane — blocks past a row's live prefix map to the
  same block id, which the Pallas pipeline recognizes and never
  re-fetches.

* ``decode_attention_paged_chunk``, the one attention of the paged
  serving step: the per-slot block TABLE is the second scalar-prefetch
  operand and the kernel walks it directly, so a row reads ONLY the
  physical blocks it owns and the chain gather disappears from the HLO
  entirely (perf/analytic.py's fusion-proof gate pins exactly that).  A
  TILE of ``G`` table entries (``paged_chunk_tile``: a lane row of
  positions, cut to the table and the VMEM budget) is one step of a
  loop over the row's LIVE tiles inside a grid of ``(S,)``; the pools
  stay in HBM and the row's blocks are copied into a double-buffered
  ``[2, G*bs, Dkv]`` scratch by hand (``_paged_tile_kernel``).  A table
  of 128 entries of 16 positions cost 1,024 grid steps a call, five in
  six of them dead at the serving contexts; tiled, the ``opt1.3b_chat``
  call went from 0.27 to 0.08 ms (docs/kernels.md has the table).  A
  row that feeds ONE lane (a decoding row) computes that lane's rows of
  each panel alone, not all K lanes' (the one-lane path).
  With a WINDOW W the same tile loop starts at the tile of ``qpos_0 - W +
  1`` and skips the entries before it (``decode_attention_window_chunk``,
  ``decode_attn_window_chunk`` in a trace): over a per-slot ring of W + K
  positions a window layer costs O(W), not O(position).
  ``G = 1`` — int8 K/V in a block under an s8 tile, shapes whose panels
  are not whole lane rows — is the block-a-grid-step form, grid ``(S,
  blocks_per_row)`` with ``[1, block_size, Dkv]`` k/v specs indexing
  ``pool[tables[r, j]]``, which the slab kernel also is.

Masking matches ``_attend`` exactly: cols > qpos[r, i] sit at -1e30,
whose exp is 0.0 — cache width beyond a lane's position never perturbs
its numerics, so greedy streams through the kernels stay token-for-token
identical to ``lm_generate`` (tests/test_pallas_decode.py pins it across
admission/eviction/CoW churn and supervisor recovery).

INT8 K/V (quant/kv.py; docs/serving.md "Quantized serving"): both
kernels take optional ``kscale``/``vscale`` per-(position, head) f32
sidecars marking a quantized cache.  The sidecar blocks ride the SAME
clamped/table-walked DMA stream as the int8 K/V blocks, and the
widening happens in REGISTERS inside ``_accumulate`` (one broadcast
multiply per KV-head group panel) — int8 is what streams from HBM and
the widened K/V never exists in any memory.  ``kernel_cost`` declares
the honest int8 byte counts (1-byte elements + the f32 sidecar).

Dispatch: callers go through ``maybe_slab_chunk`` /
``maybe_paged_chunk``, which return None (caller falls back to the
reference XLA path) unless the ``pallas_decode`` flag enables the
kernels — ``auto`` follows ``use_pallas()`` (TPU only; the CPU tier-1
default stays the reference path, preserving the greedy bit-identity
discipline), ``always`` forces them anywhere (interpret mode off-TPU —
the CPU test/smoke mode), ``off`` disables.  The flag is read at TRACE
time: set it before constructing the engine/jitting the step.
"""

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.common import (LANES as _LANES, lanes as _lanes,
                                          vmem_budget_bytes)

_NEG = -1e30

# test override for the pallas_decode flag: None = read FLAGS
# (utils/flags.py), else one of "auto" | "always" | "off" — same values
# the flag takes.  The FUSED_LSTM pattern (ops/rnn.py).
MODE = None


def _mode():
    if MODE is not None:
        return MODE
    from paddle_tpu.utils.flags import FLAGS
    return getattr(FLAGS, "pallas_decode", "auto")


@contextlib.contextmanager
def forced_mode(mode):
    """Temporarily force the kernel dispatch mode ("always" | "off" |
    "auto") — for tests.  The mode is read at TRACE time, so wrap the
    jit/lower call, not just the execution."""
    global MODE
    old = MODE
    MODE = mode
    try:
        yield
    finally:
        MODE = old


def decode_kernels_enabled():
    """True when the fused decode kernels should serve the slot/paged
    steps (read at trace time by ``models/transformer``)."""
    m = str(_mode()).lower()
    if m in ("0", "off", "false", "no"):
        return False
    if m in ("1", "on", "always", "true", "yes"):
        return True
    if m != "auto":
        raise ValueError(f"pallas_decode={m!r} (takes auto | always | off)")
    from paddle_tpu.ops import pallas as pk
    return pk.use_pallas()


def flag_decline_reason():
    """Why ``decode_kernels_enabled()`` is False, as a sentence (shared
    with ops/pallas/kda.py, which the same flag gates)."""
    m = str(_mode()).lower()
    if m == "auto":
        return (f"pallas_decode=auto and the backend is "
                f"{jax.default_backend()!r}, not 'tpu'")
    return f"pallas_decode={m}"


def _block_k_cap():
    from paddle_tpu.utils.flags import FLAGS
    return int(getattr(FLAGS, "pallas_decode_block_k", 512))


def _interpret(interpret):
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


def _head_split(d, dkv, num_heads):
    """(dh, hkv, group) from the projection widths, or None when the
    widths don't describe a grouped-head layout the kernels handle."""
    if num_heads < 1 or d % num_heads:
        return None
    dh = d // num_heads
    if dh < 1 or dkv % dh:
        return None
    hkv = dkv // dh
    if hkv < 1 or num_heads % hkv:
        return None
    return dh, hkv, num_heads // hkv


def _lane_tileable(n):
    """common.lanes() can slice (n <= LANES) or tile (n % LANES == 0)."""
    return n <= _LANES or n % _LANES == 0


def _vmem_bytes_per_position(dkv, quant):
    """VMEM one streamed KV position pins while a k-tile is resident:
    the K and V rows (f32, or int8 when ``quant``), each double-buffered
    by the Pallas pipeline; for int8 K/V also the two f32 images the body
    widens a block into and the two f32 scale-sidecar rows (lane-padded,
    double-buffered)."""
    if not quant:
        return 2 * 2 * dkv * 4
    return 2 * 2 * dkv + 2 * dkv * 4 + 2 * 2 * _LANES * 4


def _pick_block_k(t, cap, interpret, quant=False, dkv=None):
    """Largest k-tile <= cap dividing the slab length, compatible with
    the lane-replicated running-stat layout (<= LANES or a LANES
    multiple) and — given ``dkv`` — small enough that the streamed K/V
    blocks fit ``common.vmem_budget_bytes()`` (at Dkv=2048 f32 the flag's
    512 cap alone is 16 MiB of double-buffered blocks: the chip's whole
    scoped VMEM).  Single-block (blk == t) when the whole stripe fits —
    the common small-serving shape, where the online softmax degenerates
    to one plain masked softmax.  Compiled mode additionally wants
    8-sublane-divisible tiles — 32 for int8 K/V (``quant``; the s8 VMEM
    tile is (32, 128)), applied HERE so a 32-divisible tile is found
    whenever one exists rather than the largest-divisor pick being
    rejected downstream; interpret mode takes any shape."""
    if t < 1:
        return None
    if dkv is not None:
        cap = min(cap, vmem_budget_bytes()
                  // _vmem_bytes_per_position(dkv, quant))
    sublane = 32 if quant else 8
    b = min(t, cap)
    while b >= 1:
        if t % b == 0 and _lane_tileable(b) \
                and (interpret or b % sublane == 0):
            return b
        b -= 1
    return None


def _tile_problem(blk, dkv, dh, interpret, quant=False):
    """Why a k-tile of ``blk`` positions cannot run (None = it can).
    The lane-replicated running stats require a lane-tileable k-tile AND
    head dim in EVERY mode — ``_lanes`` can only slice (n <= LANES) or
    tile (n % LANES == 0), so e.g. a paged block_size of 136 must fall
    back to the reference path rather than fail mid-trace.  Compiled
    mode additionally wants 8-divisible sublane tiles and a lane-tileable
    Dkv; int8 K/V (``quant``) raises the sublane requirement to 32 — the
    s8 VMEM tile is (32, 128) — and the tile's streamed blocks must fit
    the VMEM budget."""
    if not _lane_tileable(blk):
        return f"k-tile {blk} is neither <= {_LANES} nor a multiple of it"
    if not _lane_tileable(dh):
        return f"head_dim {dh} is neither <= {_LANES} nor a multiple of it"
    if interpret:
        return None
    if quant and blk % 32:
        return (f"int8 K/V needs a k-tile that is a multiple of 32 (the "
                f"s8 VMEM tile is (32, {_LANES})), got {blk}")
    if blk % 8:
        return f"k-tile {blk} is not a multiple of 8 sublanes"
    if not _lane_tileable(dkv):
        return f"Dkv {dkv} is neither <= {_LANES} nor a multiple of it"
    need = blk * _vmem_bytes_per_position(dkv, quant)
    if need > vmem_budget_bytes():
        return (f"k-tile {blk} x Dkv {dkv} streams {need} bytes of "
                f"double-buffered K/V, over the {vmem_budget_bytes()}-byte "
                f"VMEM budget")
    return None


def _panel_heads(hkv, dh):
    """KV heads a panel of the tiled paged kernel holds: as many as fit
    one row of LANES (2 heads of 64), so K and V are read in whole lane
    tiles; 1 where a head fills the row."""
    return max(p for p in range(1, hkv + 1)
               if hkv % p == 0 and (p == 1 or p * dh <= _LANES))


def paged_chunk_tile(num_heads, d, dkv, bs, nb_row, chunk, quant=False,
                     interpret=None):
    """Table entries G a tile of the paged kernel covers (G x bs
    positions), from what the call sees; 1 = the block-a-grid-step kernel.
    One lane row of scores (LANES positions) is the aim — past it a tile
    only adds masked tail to a row's last one — cut to the table, to the
    ``pallas_decode_block_k`` cap and to the K/V tiles the VMEM budget
    holds double-buffered.  int8 K/V keeps G = 1 (a block of 16 is half
    an s8 tile); so does a compiled shape whose panels (``_panel_heads``)
    are not whole lane rows, or whose panel rows are not whole sublanes."""
    interpret = _interpret(interpret)
    split = _head_split(d, dkv, num_heads)
    if quant or split is None:
        return 1
    dh, hkv, group = split
    if not interpret:
        heads = _panel_heads(hkv, dh)
        if (heads * group * chunk) % 8 or (
                (heads * dh) % _LANES and heads != hkv):
            return 1
    cap = min(_LANES, _block_k_cap(), vmem_budget_bytes()
              // _vmem_bytes_per_position(dkv, False))
    g = max(1, min(nb_row, cap // bs))
    while g > 1 and _tile_problem(g * bs, dkv, dh, interpret):
        g -= 1
    return g


def _to_panels(q, hkv, group, dh):
    """q [S, K, D] -> [S, panels, heads*group*K, heads*dh]: panel j holds
    ``heads`` kv heads; row (u, gq, i) is lane i of query head (j*heads +
    u)*group + gq, its dh values in head u's lanes and exact zeros in the
    panel's other heads' (block-diagonal: one product with the panel's K
    lanes scores every head, a zero adds nothing)."""
    s, kk, _d = q.shape
    heads = _panel_heads(hkv, dh)
    x = q.reshape(s, kk, hkv // heads, heads, group, dh)
    x = x.transpose(0, 2, 3, 4, 1, 5).reshape(
        s, hkv // heads, heads, group * kk, dh)
    if heads > 1:
        eye = jnp.eye(heads, dtype=q.dtype)
        x = x[:, :, :, :, None, :] * eye[None, None, :, None, :, None]
    return x.reshape(s, hkv // heads, heads * group * kk, heads * dh)


def _from_panels(o, kk, hkv, group, dh):
    """``_to_panels``'s inverse on the kernel's output: each head's own
    lanes of its rows (the others hold its weights over a neighbour's
    values) -> [S, K, D]."""
    s = o.shape[0]
    heads = _panel_heads(hkv, dh)
    x = o.reshape(s, hkv // heads, heads, group * kk, heads, dh)
    x = jnp.stack([x[:, :, u, :, u] for u in range(heads)], axis=2)
    x = x.reshape(s, hkv // heads, heads, group, kk, dh)
    return x.transpose(0, 4, 1, 2, 3, 5).reshape(s, kk, hkv * group * dh)


def _one_lane_rows(heads, group, kk):
    """Rows of a panel the tiled kernel's one-lane path computes: lane 0's
    ``heads*group`` rounded up to a whole sublane tile of 8; None (no
    one-lane path) where that is no fewer than the panel's
    ``heads*group*K``, or where K is not a whole sublane tile (lane 0 of
    head h is read and written at row ``h*K``; the cells' K are 8 and
    64)."""
    m1 = -(-heads * group // 8) * 8
    return m1 if kk % 8 == 0 and m1 < heads * group * kk else None


# ------------------------------------------------------------ kernel body

def _accumulate(q, kb, vb, col0, blk, pos, m_scr, l_scr, acc_scr, *,
                num_heads, hkv, dh, scale, sl=slice(None), ks=None,
                vs=None):
    """One K/V block of the masked online softmax for one query lane.

    q: [H, dh] f32; kb/vb: [blk, Dkv] f32; col0: first global column of
    this block; pos: the LANE's position (cols > pos masked to -1e30).
    Grouped KV expands in REGISTERS: each kv head's [dh]-slice meets its
    query group's rows — no widened K/V ever exists in memory.  ``sl``
    selects this lane's running-stat rows inside scratch shaped
    [K*H, ...].
    A block entirely past ``pos`` is a BIT-EXACT no-op: every score
    masks to -1e30, so p underflows to exactly 0.0 and alpha is exactly
    1.0 — the chunk kernels rely on this for their shorter lanes.

    ks/vs: [blk, Hkv] f32 per-(position, head) scale panels for int8
    K/V (quant/kv.py): the caller hands kb/vb already CONVERTED s8 ->
    f32 and the per-head scale multiplies each group's panel here — the
    in-register dequant; the widened stripe never exists in memory,
    int8 is what streamed from HBM."""
    group = num_heads // hkv
    parts = []
    for g in range(hkv):
        qg = q[g * group:(g + 1) * group]              # [group, dh]
        kg = kb[:, g * dh:(g + 1) * dh]                # [blk, dh]
        if ks is not None:
            kg = kg * ks[:, g:g + 1]
        parts.append(jax.lax.dot_general(
            qg, kg, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32))       # [group, blk]
    s = (jnp.concatenate(parts, axis=0) if hkv > 1 else parts[0]) * scale
    cols = jax.lax.broadcasted_iota(jnp.int32, (num_heads, blk), 1) + col0
    s = jnp.where(cols <= pos, s, _NEG)
    m_prev, l_prev = m_scr[sl], l_scr[sl]              # [H, LANES]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - _lanes(m_new, blk))
    alpha = jnp.exp(m_prev - m_new)
    m_scr[sl] = m_new
    l_scr[sl] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
    parts = []
    for g in range(hkv):
        pg = p[g * group:(g + 1) * group]              # [group, blk]
        vg = vb[:, g * dh:(g + 1) * dh]                # [blk, dh]
        if vs is not None:
            vg = vg * vs[:, g:g + 1]
        parts.append(jax.lax.dot_general(
            pg, vg, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))       # [group, dh]
    av = jnp.concatenate(parts, axis=0) if hkv > 1 else parts[0]
    acc_scr[sl] = acc_scr[sl] * _lanes(alpha, dh) + av


def kernel_cost(s, t_span, d, dkv, itemsize=4, tq=1, kv_itemsize=None,
                scale_hkv=0):
    """The kernel's declared traffic/compute — the ``pl.CostEstimate``
    handed to Mosaic, and the number a TPU cost model reports for the
    fused custom call.  Bytes are the whole point: q in + out + each
    row's K AND V stripe read ONCE (worst case — the clamped index maps
    stop at each row's position, so the real stream is shorter), plus
    the scalar operands.  No score matrix, no second KV copy.  ``tq``:
    query lanes per row (1 = plain decode; K = the chunked-prefill
    step — the KV stream is UNCHANGED, every lane consumes it in
    VMEM).  ``kv_itemsize``/``scale_hkv``: the honest int8 accounting —
    1-byte K/V elements plus the f32 per-(position, head) scale sidecar
    (2 * s * t_span * scale_hkv * 4 bytes); 0 = no sidecar."""
    kv_itemsize = itemsize if kv_itemsize is None else kv_itemsize
    kv_bytes = 2 * s * t_span * dkv * kv_itemsize \
        + 2 * s * t_span * scale_hkv * 4
    io_bytes = 2 * s * tq * d * itemsize + s * tq * 4  # + int32 positions
    #           (the paged block table adds s * nb_row * 4 more — noise)
    heads_flops = 2 * 2 * s * tq * t_span * d   # qk^T + p@v
    return pl.CostEstimate(flops=heads_flops,
                           bytes_accessed=kv_bytes + io_bytes,
                           transcendentals=s * tq * t_span)


def _init_row(m_scr, l_scr, acc_scr):
    m_scr[:] = jnp.full_like(m_scr, _NEG)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)


def _finalize(o_ref, l_scr, acc_scr, dh):
    l = jnp.maximum(l_scr[:], 1e-30)
    o_ref[0] = (acc_scr[:] / _lanes(l, dh)).astype(o_ref.dtype)


def _chunk_kernel(pos_ref, q_ref, k_ref, v_ref, *rest, blk, kk,
                  num_heads, hkv, dh, scale):
    """The block-a-grid-step body: ``kk`` query lanes per row share each
    streamed K/V block.  pos_ref [S, K] carries every lane's own position (the
    engine's clamped ``qpos`` — non-decreasing per row, inactive lanes
    repeat the last active lane's), so lane i's mask is causal within
    the chunk AND clamped at the row's live prefix.  Lane stats live in
    [K*H, .]-shaped scratch, sliced per lane; the K/V stripe is read
    from HBM exactly once per row — the chunk consumes it in VMEM (and
    for int8 K/V every lane shares the same in-register dequant panels:
    the scale sidecars ride the same block stream)."""
    # int8 K/V adds two scale-sidecar operands between v and the output
    # (quantized dispatch appends their BlockSpecs); the f32 layout is
    # unchanged
    if len(rest) == 6:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_scr, l_scr, acc_scr = rest
    r = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _():
        _init_row(m_scr, l_scr, acc_scr)

    # the row's furthest lane gates the block (per-lane masking inside
    # _accumulate makes an out-of-range lane's visit a bit-exact no-op)
    @pl.when(j * blk <= pos_ref[r, kk - 1])
    def _():
        kb = k_ref[0].astype(jnp.float32)
        vb = v_ref[0].astype(jnp.float32)
        ks = None if ks_ref is None else ks_ref[0]
        vs = None if vs_ref is None else vs_ref[0]
        def _lane(i, sl):
            _accumulate(q_ref[0, sl].astype(jnp.float32), kb, vb,
                        j * blk, blk, pos_ref[r, i], m_scr, l_scr,
                        acc_scr, num_heads=num_heads, hkv=hkv, dh=dh,
                        scale=scale, sl=sl, ks=ks, vs=vs)

        _lane(0, slice(0, num_heads))    # lane 0 is always live

        # the decode-row fast path: live lanes have strictly increasing
        # positions and an inactive lane REPEATS the last live lane's
        # clamped qpos (engine ``_chunk_lanes``), so last == first means
        # the row has exactly ONE live lane — a plain decode row riding
        # the chunk step — and every other lane's accumulate is skipped
        # (their scratch keeps the _init_row zeros; _finalize's
        # max(l, eps) emits deterministic zeros nothing reads).  The
        # predicate is pos DATA — no retrace — and ONE conditional per
        # kernel keeps the step's HLO structurally flat for the
        # analytic-diff gate; partially-live rows (chunk-ingest tails,
        # spec verify) still visit every lane, where per-lane masking
        # makes the dead visits bit-exact no-ops.
        @pl.when(pos_ref[r, kk - 1] != pos_ref[r, 0])
        def _():
            for i in range(1, kk):
                _lane(i, slice(i * num_heads, (i + 1) * num_heads))

    @pl.when(j == pl.num_programs(1) - 1)
    def _():
        _finalize(o_ref, l_scr, acc_scr, dh)


def _paged_chunk_kernel(pos_ref, tbl_ref, *args, **kw):
    """Same body as the slab kernel — the block table shapes the DMA
    stream through the index maps, not the compute; ``tbl_ref`` is
    consumed entirely by the BlockSpecs."""
    del tbl_ref
    _chunk_kernel(pos_ref, *args, **kw)


def _paged_tile_kernel(pos_ref, tbl_ref, q_ref, k_hbm, v_hbm, o_ref, kbuf,
                       vbuf, sem, first_slot, m_scr, l_scr, acc_scr, lim_scr,
                       *q1, bs, g, kk, scale, window=None):
    """Paged body with a TILE of ``g`` table entries: one grid
    step is one ROW, and the row's live tiles are a loop inside it.

    ``window`` W: lane i attends only ``(qpos_i - W, qpos_i]``.  The row's
    tile loop then starts at the tile of ``max(0, qpos_0 - W + 1)`` and an
    entry wholly before that position is neither copied nor addressed, so
    a row costs O(W + K) positions whatever its context (None: every
    position from 0, the program as it was).

    The pools stay in HBM (``pl.ANY``).  Tile t of a row is its table
    entries ``[t*g, (t+1)*g)``: each LIVE entry's block is copied into its
    ``bs`` rows of ``kbuf/vbuf[slot]`` ([2, g*bs, Dkv], double-buffered by
    hand) — an entry past the row's furthest lane is neither copied nor
    addressed, its rows keep finite data of an earlier tile (``vbuf`` is
    zeroed once a call: a masked score is an exact 0.0, and 0.0 x NaN is
    not) under columns the mask removes.  While tile t is consumed tile
    t+1 is in flight, and during a row's LAST tile the next row's first:
    ``first_slot`` (SMEM) carries the buffer parity from row to row.

    q arrives PANEL-major (``_to_panels``): panel j is the ``wp`` lanes of
    K/V that hold its kv heads, and its query rows — every lane of every
    head of the panel, block-diagonal over the panel's heads — meet the
    tile in ONE ``[mp, wp] x [g*bs, wp]`` product, so K and V are read in
    whole lane tiles and a prefilling row's lanes share each product.
    The online softmax is ``_accumulate``'s, on ``[mp, g*bs]`` scores.

    The one-lane path: a row whose last lane repeats its first position
    feeds ONE live lane (a decoding row, or a free slot; the predicate of
    ``_chunk_kernel``'s fast path, read as data: no retrace).  Lane 0 of
    the panel's head h is its row ``h * K``: once a row those ``hg`` rows
    are copied into ``q1_scr`` (``[panels, m1, wp]``, ``m1`` = hg rounded
    up to a sublane tile, ``_one_lane_rows``; none where the panel is no
    larger), the row's tiles take those ``m1`` rows alone, in the first
    rows of the running stats, and its finish writes lane 0's results into
    an output of exact zeros.  Each query row's products and softmax are
    its own, so a live lane's output is bit-for-bit the whole panel's; the
    DMA walk is one code path for both.  Each loop over panels, heads and
    lanes is traced once (``fori_loop`` unrolled as it lowers): a kernel's
    trace is paid in every process's set-up."""
    r = pl.program_id(0)
    tile = g * bs
    n_p, mp, wp = acc_scr.shape
    hg = mp // kk
    q1_scr = q1[0] if q1 else None
    last = pos_ref[r, kk - 1]
    n_tiles = last // tile + 1

    def each(n, fn):            # fn(i) for i < n, one trace, unrolled
        def step(i, carry):
            fn(i)
            return carry
        jax.lax.fori_loop(0, n, step, 0, unroll=True)

    def paths(fn):
        """``fn(rows)`` on the row's path: ``fn(m1)``, lane 0's rows in
        ``q1_scr`` under its one position, or ``fn(mp)``, the whole panel
        under each lane's own (``lim_scr``)."""
        if q1_scr is None:
            fn(mp)
            return
        one = last == pos_ref[r, 0]
        pl.when(one)(lambda: fn(q1_scr.shape[1]))
        pl.when(jnp.logical_not(one))(lambda: fn(mp))

    def first_entry(row):       # the row's first entry its lanes reach
        return jnp.maximum(pos_ref[row, 0] - window + 1, 0) // bs

    def copies(row, t, slot, op):
        live = pos_ref[row, kk - 1] // bs + 1
        lo = None if window is None else first_entry(row)
        for i in range(g):
            wanted = t * g + i < live
            if lo is not None:
                wanted = jnp.logical_and(wanted, t * g + i >= lo)

            @pl.when(wanted)
            def _():
                bid = tbl_ref[row, t * g + i]
                for hbm, buf in ((k_hbm, kbuf), (v_hbm, vbuf)):
                    cp = pltpu.make_async_copy(
                        hbm.at[bid], buf.at[slot, pl.ds(i * bs, bs)],
                        sem.at[slot])
                    getattr(cp, op)()

    def start(row):             # the tile a row's loop starts at
        return 0 if window is None else first_entry(row) // g

    @pl.when(r == 0)
    def _():
        vbuf[...] = jnp.zeros_like(vbuf)
        if q1_scr is not None:
            # the rows past hg hold no head: finite, and never written out
            q1_scr[...] = jnp.zeros_like(q1_scr)
        first_slot[0] = 0
        copies(0, start(0), 0, "start")

    first = first_slot[0]

    def init(rows):
        m_scr[:, :rows] = jnp.full((n_p, rows, _LANES), _NEG, jnp.float32)
        l_scr[:, :rows] = jnp.zeros((n_p, rows, _LANES), jnp.float32)
        acc_scr[:, :rows] = jnp.zeros((n_p, rows, wp), jnp.float32)
        if rows < mp:
            def gather(h):      # lane 0 of head h: row h * K of each panel
                q1_scr[:, pl.ds(h, 1)] = \
                    q_ref[0, :, pl.ds(h * kk, 1)].astype(jnp.float32)

            each(hg, gather)
            return
        # row (u, gq, i) of a panel is lane i of one head: its own
        # position, once a row
        lane = jax.lax.broadcasted_iota(jnp.int32, (mp, tile), 0) % kk
        lim_scr[...] = jax.lax.fori_loop(
            1, kk, lambda i, lim: jnp.where(lane >= i, pos_ref[r, i], lim),
            jnp.full((mp, tile), pos_ref[r, 0], jnp.int32), unroll=True)

    paths(init)
    t0 = start(r)

    def body(t, carry):
        slot = (first + t) % 2 if window is None else (first + t - t0) % 2

        @pl.when(t + 1 < n_tiles)
        def _():
            copies(r, t + 1, 1 - slot, "start")

        @pl.when(jnp.logical_and(t + 1 == n_tiles,
                                 r + 1 < pl.num_programs(0)))
        def _():
            nxt = r + 1
            copies(nxt, start(nxt), 1 - slot, "start")

        copies(r, t, slot, "wait")

        def attend(rows):
            lim = pos_ref[r, 0] if rows < mp else lim_scr[...]
            col = jax.lax.broadcasted_iota(jnp.int32, (rows, tile), 1) \
                + t * tile
            seen = col <= lim
            if window is not None:
                seen = jnp.logical_and(seen, col > lim - window)

            def panel(j):
                cols = pl.ds(pl.multiple_of(j * wp, wp), wp)
                q = q1_scr[j] if rows < mp \
                    else q_ref[0, j].astype(jnp.float32)
                s = jax.lax.dot_general(
                    q, kbuf[slot, :, cols], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32) * scale  # [rows, tile]
                s = jnp.where(seen, s, _NEG)
                m_prev, l_prev = m_scr[j, :rows], l_scr[j, :rows]
                m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1,
                                                    keepdims=True))
                p = jnp.exp(s - _lanes(m_new, tile))
                alpha = jnp.exp(m_prev - m_new)
                m_scr[j, :rows] = m_new
                l_scr[j, :rows] = l_prev * alpha + jnp.sum(p, axis=-1,
                                                           keepdims=True)
                acc_scr[j, :rows] = acc_scr[j, :rows] * _lanes(alpha, wp) \
                    + jax.lax.dot_general(
                        p, vbuf[slot, :, cols], (((1,), (0,)), ((), ())),
                        preferred_element_type=jnp.float32)    # [rows, wp]

            each(n_p, panel)

        paths(attend)
        return carry

    jax.lax.fori_loop(t0, n_tiles, body, 0)
    first_slot[0] = (first + n_tiles) % 2 if window is None \
        else (first + n_tiles - t0) % 2

    def finish(rows):
        def panel(j):
            o = acc_scr[j, :rows] / _lanes(
                jnp.maximum(l_scr[j, :rows], 1e-30), wp)
            if rows == mp:
                o_ref[0, j] = o.astype(o_ref.dtype)
            else:
                acc_scr[j, :rows] = o

        each(n_p, panel)
        if rows < mp:
            # lane 0's results, and the exact 0.0 of the lanes no token fed
            o_ref[0] = jnp.zeros(o_ref.shape[1:], o_ref.dtype)

            def put(h):
                o_ref[0, :, pl.ds(h * kk, 1)] = \
                    acc_scr[:, pl.ds(h, 1)].astype(o_ref.dtype)

            each(hg, put)

    paths(finish)


# ------------------------------------------------------------ public API

def _check_scales(name, kscale, vscale, lead_shape, hkv):
    """Validate the int8 scale sidecars (both or neither; shapes match
    the K/V buffers with Hkv trailing).  Returns True when quantized."""
    if kscale is None and vscale is None:
        return False
    if kscale is None or vscale is None:
        raise ValueError(f"{name}: kscale and vscale come together")
    want = lead_shape + (hkv,)
    if tuple(kscale.shape) != want or tuple(vscale.shape) != want:
        raise ValueError(
            f"{name}: scale sidecars must be {want}, got "
            f"{kscale.shape}/{vscale.shape}")
    return True


def decode_attention_slab_chunk(q, k, v, qpos, num_heads, *,
                                block_k=None, interpret=None,
                                kscale=None, vscale=None):
    """Fused slab decode attention (the serving step's, K lanes a row;
    K = 1 is a one-lane call): q [S, K, D], k/v [S, T, Dkv] (the
    already-updated cache),
    qpos [S, K] int32 per-LANE positions (non-decreasing per row; the
    engine clamps inactive lanes to the last active one) -> [S, K, D].
    Lane (r, i) attends row r's stripe at cols <= qpos[r, i]; the
    stripe streams HBM -> VMEM once per row and every lane consumes it
    there — no [S, K, T] score matrix.  kscale/vscale [S, T, Hkv] f32
    mark an INT8 cache — in-register dequant, every lane sharing the
    widened panels.  Raises ValueError on shapes the kernel doesn't
    cover — callers use ``maybe_slab_chunk``."""
    interpret = _interpret(interpret)
    s, kk, d = q.shape
    t, dkv = k.shape[1], k.shape[2]
    split = _head_split(d, dkv, num_heads)
    blk = _pick_block_k(t, block_k or _block_k_cap(), interpret,
                        quant=kscale is not None, dkv=dkv)
    if split is None or blk is None or not _chunk_ok(kk, num_heads,
                                                    interpret):
        raise ValueError(
            f"decode_attention_slab_chunk: unsupported shape q={q.shape} "
            f"k={k.shape} heads={num_heads}")
    dh, hkv, _group = split
    quant = _check_scales("decode_attention_slab_chunk", kscale, vscale,
                          (s, t), hkv)
    problem = _tile_problem(blk, dkv, dh, interpret, quant=quant)
    if problem:
        raise ValueError(f"decode_attention_slab_chunk: {problem}")
    scale = 1.0 / math.sqrt(dh)
    kernel = functools.partial(_chunk_kernel, blk=blk, kk=kk,
                               num_heads=num_heads, hkv=hkv, dh=dh,
                               scale=scale)
    # clamp at the row's FURTHEST lane: blocks past it re-map to the
    # last needed block — same index, no re-fetch
    kv_map = lambda r, j, pos: (
        r, jnp.minimum(j, pos[r, kk - 1] // blk), 0)
    in_specs = [
        pl.BlockSpec((1, kk * num_heads, dh),
                     lambda r, j, pos: (r, 0, 0)),
        pl.BlockSpec((1, blk, dkv), kv_map),
        pl.BlockSpec((1, blk, dkv), kv_map),
    ]
    operands = [q.reshape(s, kk * num_heads, dh), k, v]
    if quant:
        in_specs += [pl.BlockSpec((1, blk, hkv), kv_map),
                     pl.BlockSpec((1, blk, hkv), kv_map)]
        operands += [kscale, vscale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(s, t // blk),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kk * num_heads, dh),
                               lambda r, j, pos: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kk * num_heads, _LANES), jnp.float32),
            pltpu.VMEM((kk * num_heads, _LANES), jnp.float32),
            pltpu.VMEM((kk * num_heads, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, name="decode_attn_slab_chunk",
        out_shape=jax.ShapeDtypeStruct((s, kk * num_heads, dh), q.dtype),
        cost_estimate=kernel_cost(
            s, t, d, dkv, q.dtype.itemsize, tq=kk,
            kv_itemsize=k.dtype.itemsize,
            scale_hkv=hkv if quant else 0),
        interpret=interpret,
    )(jnp.asarray(qpos, jnp.int32), *operands)
    return out.reshape(s, kk, d)


def decode_attention_paged_chunk(q, k, v, qpos, tables, num_heads, *,
                                 interpret=None, kscale=None,
                                 vscale=None):
    """Fused PAGED decode attention: q [S, K, D], k/v [num_blocks,
    block_size, Dkv] (the shared block POOL, already scatter-updated for
    the whole chunk span), qpos [S, K], tables [S, blocks_per_row] int32
    -> [S, K, D].

    The block table is the kernel's second scalar-prefetch operand: the
    k/v index maps read ``tables[r, j]`` directly, so row r's DMA stream
    is exactly the physical blocks it owns (clamped at its furthest
    lane) — the ``pool[tables]`` chain gather and its [S, T, Dkv] HBM
    buffer are gone, not fused.  kscale/vscale [num_blocks, block_size,
    Hkv] f32 mark an INT8 pool (quant/kv.py): the sidecar blocks ride
    the SAME table-walked stream and the widening happens in registers.
    Raises ValueError on shapes the kernel doesn't cover — callers use
    ``maybe_paged_chunk``."""
    interpret = _interpret(interpret)
    s, kk, d = q.shape
    bs, dkv = k.shape[1], k.shape[2]
    nb_row = tables.shape[1]
    split = _head_split(d, dkv, num_heads)
    if split is None or not _chunk_ok(kk, num_heads, interpret):
        raise ValueError(
            f"decode_attention_paged_chunk: unsupported shape "
            f"q={q.shape} pool={k.shape} heads={num_heads}")
    dh, hkv, _group = split
    quant = _check_scales("decode_attention_paged_chunk", kscale,
                          vscale, (k.shape[0], bs), hkv)
    problem = _tile_problem(bs, dkv, dh, interpret, quant=quant)
    if problem:
        raise ValueError(f"decode_attention_paged_chunk: {problem}")
    g = paged_chunk_tile(num_heads, d, dkv, bs, nb_row, kk, quant=quant,
                         interpret=interpret)
    if g > 1:
        return _paged_chunk_tiled(q, k, v, qpos, tables, g=g,
                                  num_heads=num_heads, interpret=interpret)
    scale = 1.0 / math.sqrt(dh)
    kernel = functools.partial(_paged_chunk_kernel, blk=bs, kk=kk,
                               num_heads=num_heads, hkv=hkv, dh=dh,
                               scale=scale)

    def _kv_map(r, j, pos, tbl):
        # walk the row's chain, clamped at its live prefix: entries past
        # the furthest lane (scratch/stale ids) are never even addressed
        return (tbl[r, jnp.minimum(j, pos[r, kk - 1] // bs)], 0, 0)

    in_specs = [
        pl.BlockSpec((1, kk * num_heads, dh),
                     lambda r, j, pos, tbl: (r, 0, 0)),
        pl.BlockSpec((1, bs, dkv), _kv_map),
        pl.BlockSpec((1, bs, dkv), _kv_map),
    ]
    operands = [q.reshape(s, kk * num_heads, dh), k, v]
    if quant:
        in_specs += [pl.BlockSpec((1, bs, hkv), _kv_map),
                     pl.BlockSpec((1, bs, hkv), _kv_map)]
        operands += [kscale, vscale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, nb_row),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, kk * num_heads, dh),
                               lambda r, j, pos, tbl: (r, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((kk * num_heads, _LANES), jnp.float32),
            pltpu.VMEM((kk * num_heads, _LANES), jnp.float32),
            pltpu.VMEM((kk * num_heads, dh), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, name="decode_attn_paged_chunk",
        out_shape=jax.ShapeDtypeStruct((s, kk * num_heads, dh), q.dtype),
        cost_estimate=kernel_cost(
            s, nb_row * bs, d, dkv, q.dtype.itemsize, tq=kk,
            kv_itemsize=k.dtype.itemsize,
            scale_hkv=hkv if quant else 0),
        interpret=interpret,
    )(jnp.asarray(qpos, jnp.int32),
      jnp.asarray(tables, jnp.int32), *operands)
    return out.reshape(s, kk, d)


@functools.partial(jax.jit, static_argnames=("g", "num_heads", "interpret",
                                             "window"))
def _paged_chunk_tiled(q, k, v, qpos, tables, *, g, num_heads, interpret,
                       window=None):
    """``decode_attention_paged_chunk`` at G > 1 (``_paged_tile_kernel``):
    grid (S,), q and the output panel-major, the pools left in HBM.
    Jitted so that the layers of a step, which call it with the same
    shapes, share ONE trace of the kernel and one Mosaic lowering (the
    step is traced a layer at a time; XLA inlines the calls).  With a
    ``window`` it is ``decode_attention_window_chunk``'s, under that
    kernel's own name."""
    s, kk, d = q.shape
    bs, dkv = k.shape[1], k.shape[2]
    dh, hkv, group = _head_split(d, dkv, num_heads)
    qp = _to_panels(q, hkv, group, dh)
    _s, n_p, mp, wp = qp.shape
    m1 = _one_lane_rows(_panel_heads(hkv, dh), group, kk)
    row = pl.BlockSpec((1, n_p, mp, wp), lambda r, pos, tbl: (r, 0, 0, 0))
    pool = pl.BlockSpec(memory_space=pl.ANY)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s,),
        in_specs=[row, pool, pool],
        out_specs=row,
        scratch_shapes=[
            pltpu.VMEM((2, g * bs, dkv), k.dtype),
            pltpu.VMEM((2, g * bs, dkv), v.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SMEM((1,), jnp.int32),
            pltpu.VMEM((n_p, mp, _LANES), jnp.float32),
            pltpu.VMEM((n_p, mp, _LANES), jnp.float32),
            pltpu.VMEM((n_p, mp, wp), jnp.float32),
            pltpu.VMEM((mp, g * bs), jnp.int32),
        ] + ([] if m1 is None else [pltpu.VMEM((n_p, m1, wp), jnp.float32)]),
    )
    kernel = functools.partial(_paged_tile_kernel, bs=bs, g=g, kk=kk,
                               scale=1.0 / math.sqrt(dh))
    name, span = "decode_attn_paged_chunk", tables.shape[1] * bs
    if window is not None:
        kernel = functools.partial(kernel, window=window)
        # a row's tiles reach W + K - 1 positions and two part-tiles
        name, span = "decode_attn_window_chunk", \
            min(span, window + kk - 1 + 2 * g * bs)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec, name=name,
        out_shape=jax.ShapeDtypeStruct(qp.shape, q.dtype),
        cost_estimate=kernel_cost(s, span, d, dkv,
                                  q.dtype.itemsize, tq=kk,
                                  kv_itemsize=k.dtype.itemsize),
        interpret=interpret,
    )(jnp.asarray(qpos, jnp.int32), jnp.asarray(tables, jnp.int32), qp, k, v)
    return _from_panels(out, kk, hkv, group, dh)


def ring_tables(slots, ring_blocks, entries):
    """The block table of a per-slot RING as the tiled kernel walks it:
    slot r's ring is blocks ``r * ring_blocks ..`` of the ring buffer laid
    ``[slots * ring_blocks, block, Dkv]``, and position p lives in its
    block ``(p // block) % ring_blocks`` -> ``[slots, entries]`` int32."""
    return (jnp.arange(slots, dtype=jnp.int32)[:, None] * ring_blocks
            + jnp.arange(entries, dtype=jnp.int32)[None, :] % ring_blocks)


def decode_attention_window_chunk(q, k_ring, v_ring, qpos, num_heads,
                                  window, *, block, entries,
                                  interpret=None):
    """Window attention over per-slot RINGS: q [S, K, D], k_ring / v_ring
    [S, R, Dkv] (row r's ring; position p at ``p % R``, already written
    for the chunk), qpos [S, K] -> [S, K, D].  Lane i attends ``(qpos_i -
    window, qpos_i]``.  The ring must hold ``window + K - 1`` positions so
    that the chunk's own writes never overwrite a position a lane reads;
    the kernel walks the ring in blocks of ``block`` positions through
    ``ring_tables`` of ``entries`` entries a row (enough for the largest
    position), so a row reads the O(window + K) positions behind it and
    nothing else, under its own name (``decode_attn_window_chunk``).
    Raises ValueError on shapes the tiled kernel does not cover — callers
    use ``maybe_window_chunk``."""
    interpret = _interpret(interpret)
    s, kk, d = q.shape
    ring, dkv = k_ring.shape[1], k_ring.shape[2]
    if ring % block or ring < window + kk - 1:
        raise ValueError(
            f"decode_attention_window_chunk: a ring of {ring} positions "
            f"does not hold window {window} + chunk {kk} - 1 in blocks of "
            f"{block}")
    split = _head_split(d, dkv, num_heads)
    g = paged_chunk_tile(num_heads, d, dkv, block, entries, kk,
                         interpret=interpret)
    if split is None or g == 1 or not _chunk_ok(kk, num_heads, interpret) \
            or _tile_problem(block, dkv, split[0], interpret):
        raise ValueError(
            f"decode_attention_window_chunk: unsupported shape q={q.shape} "
            f"ring={k_ring.shape} heads={num_heads} block={block}")
    nblk = ring // block
    as_pool = lambda x: x.reshape(s * nblk, block, dkv)
    return _paged_chunk_tiled(q, as_pool(k_ring), as_pool(v_ring), qpos,
                              ring_tables(s, nblk, entries), g=g,
                              num_heads=num_heads, interpret=interpret,
                              window=int(window))


# ------------------------------------------------------------ dispatch

def _chunk_ok(kk, num_heads, interpret):
    """Chunk-lane tiling: the lane-stacked scratch/q blocks are
    [K*H, .]-shaped — any K in interpret mode; the compiled backend
    wants an 8-divisible sublane dim."""
    if kk < 1:
        return False
    return interpret or (kk * num_heads) % 8 == 0


def decline_reason(num_heads, d, dkv, blk_len, paged=False, chunk=1,
                   quant=False, shards=1):
    """THE dispatch predicate (flag + shape support), as the reason the
    fused kernel will NOT serve these shapes — None when it will.  Shared
    by ``maybe_*`` (through ``covers``) and by ``DecodeEngine.warmup``'s
    resolved-path log — one definition, so the engine can never report a
    path its compiled step didn't take, and a reference path always has
    a sentence saying why.  ``blk_len``: the slab length (slab) or the
    pool block size (paged).  ``chunk``: query lanes per row (the step's
    K; 1 = a one-lane call).  ``quant``: int8 K/V (tighter sublane tiling
    on the compiled backend).

    ``shards``: a tensor-parallel mesh (docs/serving.md "Sharded
    decode") hands each chip the PER-CHIP stripe — ``num_heads/n``
    query heads, ``d/n``-wide q, ``dkv/n``-wide K/V — and coverage must
    be judged on THAT: a kernel that covers 8 KV heads may not cover
    the 4-head shard (lane-tiling of the narrower Dkv, the smaller
    ``chunk*H`` sublane dim).  The maybe_* call sites inside the
    shard_map see the local widths naturally; this localizes the
    warm-up prediction to match."""
    if not decode_kernels_enabled():
        return flag_decline_reason()
    shards = max(1, int(shards))
    if shards > 1:
        if num_heads % shards or d % shards or dkv % shards:
            return (f"heads {num_heads} / d {d} / Dkv {dkv} do not split "
                    f"evenly over {shards} shards")
        why = decline_reason(num_heads // shards, d // shards,
                             dkv // shards, blk_len, paged=paged,
                             chunk=chunk, quant=quant)
        return why and f"per-chip stripe (1/{shards} of the heads): {why}"
    interpret = _interpret(None)
    split = _head_split(d, dkv, num_heads)
    if split is None:
        return (f"d {d}, Dkv {dkv}, heads {num_heads} do not describe a "
                "grouped-head layout")
    if not _chunk_ok(chunk, num_heads, interpret):
        return (f"chunk {chunk} x heads {num_heads} is not a multiple of 8 "
                "sublanes")
    if paged:
        return _tile_problem(blk_len, dkv, split[0], interpret, quant=quant)
    blk = _pick_block_k(blk_len, _block_k_cap(), interpret, quant=quant,
                        dkv=dkv)
    if blk is None:
        return (f"no k-tile divides slab length {blk_len} under the "
                "sublane, lane and VMEM-budget constraints")
    return _tile_problem(blk, dkv, split[0], interpret, quant=quant)


def window_decline_reason(num_heads, d, dkv, block, entries, chunk):
    """``decline_reason`` for the window kernel over per-slot rings
    (``decode_attention_window_chunk``): the paged kernel's predicate, and
    its TILED form, since the lower bound lives in the tile loop (a shape
    that falls back to the block-a-grid-step kernel has no window)."""
    why = decline_reason(num_heads, d, dkv, block, paged=True, chunk=chunk)
    if why is None and paged_chunk_tile(num_heads, d, dkv, block, entries,
                                        chunk) == 1:
        why = (f"heads {num_heads} over Dkv {dkv} at block {block} take the "
               "block-a-grid-step kernel, which has no window bound")
    return why


def tile_positions(num_heads, d, dkv, blk_len, nb_row=1, paged=False,
                   chunk=1, quant=False, shards=1):
    """K/V positions ONE step of the kernel covers for shapes
    ``decline_reason`` accepts (same arguments; ``nb_row``: the table's
    entries a row), judged like it on the per-chip stripe: the slab
    kernel's k-tile, or G pool blocks (``paged_chunk_tile``).  ``DecodeEngine.warmup`` logs it beside the
    resolved path, so a run's output says which tile served it."""
    shards = max(1, int(shards))
    num_heads, d, dkv = num_heads // shards, d // shards, dkv // shards
    if not paged:
        return _pick_block_k(blk_len, _block_k_cap(), _interpret(None),
                             quant=quant, dkv=dkv)
    return blk_len * paged_chunk_tile(num_heads, d, dkv, blk_len, nb_row,
                                      chunk, quant=quant)


def covers(*args, **kw):
    """True when the fused kernel serves these shapes
    (``decline_reason`` is None)."""
    return decline_reason(*args, **kw) is None


def maybe_slab_chunk(q, k, v, qpos, num_heads, kscale=None, vscale=None):
    """Kernel output [S, K, D] when the fused slab kernel is enabled
    and covers these shapes; None -> the reference XLA path."""
    if not covers(num_heads, q.shape[2], k.shape[2], k.shape[1],
                  paged=False, chunk=q.shape[1],
                  quant=kscale is not None):
        return None
    return decode_attention_slab_chunk(q, k, v, qpos, num_heads,
                                       interpret=_interpret(None),
                                       kscale=kscale, vscale=vscale)


def maybe_paged_chunk(q, k, v, qpos, tables, num_heads, kscale=None,
                      vscale=None):
    """Kernel output [S, K, D] when the fused paged kernel is enabled
    and covers these shapes; None -> the chain-gather path."""
    if not covers(num_heads, q.shape[2], k.shape[2], k.shape[1],
                  paged=True, chunk=q.shape[1],
                  quant=kscale is not None):
        return None
    return decode_attention_paged_chunk(q, k, v, qpos, tables, num_heads,
                                        interpret=_interpret(None),
                                        kscale=kscale, vscale=vscale)


def maybe_window_chunk(q, k_ring, v_ring, qpos, num_heads, window, *, block,
                       entries):
    """Window-kernel output [S, K, D] over per-slot rings when the kernel
    is enabled and covers these shapes; None -> the caller's XLA path."""
    if window_decline_reason(num_heads, q.shape[2], k_ring.shape[2], block,
                             entries, q.shape[1]) is not None:
        return None
    return decode_attention_window_chunk(
        q, k_ring, v_ring, qpos, num_heads, window, block=block,
        entries=entries, interpret=_interpret(None))

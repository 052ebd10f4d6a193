"""Flash attention for TPU (Pallas): forward + backward kernels, custom_vjp.

Replaces the materialized [Tq, Tk] softmax of ops.attention.dot_product_
attention for long sequences: logits are computed block-by-block in VMEM
with a running (max, sum) softmax, so HBM traffic is O(T*D) not O(T^2)
(the reference's CUDA layer has no equivalent — pre-transformer era; this
is the TPU-native hot-op treatment its hl_lstm fused kernels got).

Streaming layout: grid (B*H, Tq/BLK_Q, Tk/BLK_K) with the kv dimension
innermost — TPU grids run sequentially per core, so Pallas pipelines the
per-block HBM->VMEM copies while VMEM scratch (acc, running max/sum)
persists across the kv iterations of one q block; only one (q, k, v)
block triple is resident at a time, so VMEM use is O(BLK^2) independent
of sequence length.  Causal blocks entirely above the diagonal are
skipped with @pl.when.  f32 accumulation throughout.

Backward = FlashAttention-2: delta = rowsum(do * o) precomputed (XLA);
one kernel streams q blocks per kv block for dk/dv, one streams kv blocks
per q block for dq, both recomputing p from (q, k, lse).
"""

import contextlib
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30

# test/bench override for the pallas_prefill flag: None = read FLAGS
# (utils/flags.py), else "auto" | "always" | "off" — the
# decode_attention.MODE pattern.  Gates the serving PREFILL routing
# (models/transformer.lm_prefill's batched causal pass) through this
# kernel so no serving path materializes the [Tp, Tp] score matrix;
# "auto" follows use_pallas() (TPU only — the CPU tier-1 default stays
# the masked XLA reference path, preserving greedy bit-identity),
# "always" forces it anywhere (interpret mode off-TPU — the test/smoke
# mode).  Read at TRACE time.
PREFILL_MODE = None


def _prefill_mode():
    if PREFILL_MODE is not None:
        return PREFILL_MODE
    from paddle_tpu.utils.flags import FLAGS
    return getattr(FLAGS, "pallas_prefill", "auto")


@contextlib.contextmanager
def forced_prefill_mode(mode):
    """Temporarily force the prefill-flash routing ("always" | "off" |
    "auto") — tests, the analytic gate, and the A/B bench.  Trace-time:
    wrap the jit/lower call, not just the execution."""
    global PREFILL_MODE
    old = PREFILL_MODE
    PREFILL_MODE = mode
    try:
        yield
    finally:
        PREFILL_MODE = old


def prefill_flash_enabled():
    """True when ``lm_prefill``'s batched causal pass should route
    through ``flash_attention`` (read at trace time by
    ``models/transformer``).  Shape coverage stays flash_attention's
    own: uncoverable blockings fall back to the masked path inside."""
    m = str(_prefill_mode()).lower()
    if m in ("0", "off", "false", "no"):
        return False
    if m in ("1", "on", "always", "true", "yes"):
        return True
    if m != "auto":
        raise ValueError(f"pallas_prefill={m!r} (takes auto | always | "
                         "off)")
    from paddle_tpu.ops import pallas as pk
    return pk.use_pallas()



def prefill_decline_reason(tp, head_dim):
    """Why ``lm_prefill``'s batched causal pass over a ``tp``-long prompt
    bucket will NOT run the flash kernel (None = it will): the routing
    flag, then ``flash_attention``'s own blocking — the same conditions
    under which it falls back to the masked XLA path inside.  For the
    engine's warm-up log: a reference path always has its sentence."""
    if not prefill_flash_enabled():
        m = str(_prefill_mode()).lower()
        if m == "auto":
            return (f"pallas_prefill=auto and the backend is "
                    f"{jax.default_backend()!r}, not 'tpu'")
        return f"pallas_prefill={m}"
    if _pick_block(512, tp) is None:
        return f"no 8-sublane block <= 512 tiles a prompt bucket of {tp}"
    if not _tileable(head_dim):
        return (f"head_dim {head_dim} is neither <= {_LANES} nor a "
                "multiple of it")
    return None


# Per-row statistics (running max/sum, lse, delta) live lane-REPLICATED in
# [rows, 128] tiles — the same layout
# jax.experimental.pallas.ops.tpu.flash_attention uses; see pallas/common.py.
from paddle_tpu.ops.pallas.common import LANES as _LANES, lanes as _lanes


# ------------------------------------------------------------------ forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, blk_q, blk_k, scale, causal):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)
    d = q_ref.shape[-1]

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: the block intersects the lower triangle iff
    # qi*blk_q + blk_q - 1 >= ki*blk_k
    needed = (qi * blk_q + blk_q - 1 >= ki * blk_k) if causal else True

    @pl.when(needed)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [blk_q, blk_k]
        if causal:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0) + qi * blk_q
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1) + ki * blk_k
            s = jnp.where(rows >= cols, s, _NEG)
        m_prev, l_prev = m_scr[:], l_scr[:]          # [blk_q, _LANES]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, blk_k))
        alpha = jnp.exp(m_prev - m_new)              # [blk_q, _LANES]
        m_scr[:] = m_new
        l_scr[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * _lanes(alpha, d) + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0] = (acc_scr[:] / _lanes(l, d)).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(l)           # lane-replicated


def _fwd(q, k, v, scale, causal, blk_q, blk_k, interpret):
    bh, tq, d = q.shape
    tk = k.shape[1]

    kernel = functools.partial(_fwd_kernel, blk_q=blk_q, blk_k=blk_k,
                               scale=scale, causal=causal)
    o, lse = pl.pallas_call(
        kernel,
        grid=(bh, tq // blk_q, tk // blk_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, tq, _LANES), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_q, _LANES), jnp.float32),
            pltpu.VMEM((blk_q, _LANES), jnp.float32),
            pltpu.VMEM((blk_q, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse[:, :, 0]


# ----------------------------------------------------------------- backward

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scr, dv_scr, *, blk_q, blk_k, scale,
                    causal):
    ki = pl.program_id(1)
    qi = pl.program_id(2)
    nq = pl.num_programs(2)

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    needed = (qi * blk_q + blk_q - 1 >= ki * blk_k) if causal else True

    @pl.when(needed)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                              # [blk_q, _LANES]
        delta = delta_ref[0]                          # [blk_q, _LANES]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [blk_q, blk_k]
        if causal:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0) + qi * blk_q
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1) + ki * blk_k
            s = jnp.where(rows >= cols, s, _NEG)
        p = jnp.exp(s - _lanes(lse, blk_k))
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [blk_k, d]
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)           # [blk_q, blk_k]
        ds = p * (dp - _lanes(delta, blk_k)) * scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)           # [blk_k, d]

    @pl.when(qi == nq - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   dq_scr, *, blk_q, blk_k, scale, causal):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    needed = (qi * blk_q + blk_q - 1 >= ki * blk_k) if causal else True

    @pl.when(needed)
    def _():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0]                              # [blk_q, _LANES]
        delta = delta_ref[0]                          # [blk_q, _LANES]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0) + qi * blk_q
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1) + ki * blk_k
            s = jnp.where(rows >= cols, s, _NEG)
        p = jnp.exp(s - _lanes(lse, blk_k))
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = p * (dp - _lanes(delta, blk_k)) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd(scale, causal, blk_q, blk_k, interpret, res, g):
    q, k, v, o, lse = res
    bh, tq, d = q.shape
    tk = k.shape[1]
    do = g
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    # lane-replicated [bh, t, _LANES] views for the kernels (see _LANES note)
    lse_r = jnp.broadcast_to(lse[:, :, None], (bh, tq, _LANES))
    delta_r = jnp.broadcast_to(delta[:, :, None], (bh, tq, _LANES))

    dkv_kernel = functools.partial(_bwd_dkv_kernel, blk_q=blk_q,
                                   blk_k=blk_k, scale=scale, causal=causal)
    dk, dv = pl.pallas_call(
        dkv_kernel,
        grid=(bh, tk // blk_k, tq // blk_q),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_q, d), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, blk_q, _LANES), lambda b, j, i: (b, i, 0)),
            pl.BlockSpec((1, blk_q, _LANES), lambda b, j, i: (b, i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, j, i: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, tk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, tk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((blk_k, d), jnp.float32),
            pltpu.VMEM((blk_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse_r, delta_r)

    dq_kernel = functools.partial(_bwd_dq_kernel, blk_q=blk_q, blk_k=blk_k,
                                  scale=scale, causal=causal)
    dq = pl.pallas_call(
        dq_kernel,
        grid=(bh, tq // blk_q, tk // blk_k),
        in_specs=[
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_k, d), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, _LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, blk_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, blk_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, tq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((blk_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse_r, delta_r)
    return dq, dk, dv


# -------------------------------------------------------------- public API

def _tileable(n):
    # _lanes() can slice (n < _LANES) or tile (n % _LANES == 0)
    return n <= _LANES or n % _LANES == 0


def _pick_block(want, n, sublane=8):
    """Largest b <= want that divides n, is sublane-divisible and
    lane-tileable; halve from `want` so a 128-multiple sequence that is
    not a 512-multiple (e.g. T=640) still gets the flash path with
    smaller blocks instead of the materialized-O(T^2) fallback.
    ``sublane``: 8 for f32 operands, 32 for int8 K/V (the s8 VMEM tile
    is (32, 128)) — the decode-side `_pick_block_k` convention."""
    b = min(want, n)
    while b >= sublane:
        if n % b == 0 and b % sublane == 0 and _tileable(b):
            return b
        b //= 2
    return None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_bhtd(q, k, v, scale, causal, blk_q, blk_k, interpret):
    o, _ = _fwd(q, k, v, scale, causal, blk_q, blk_k, interpret)
    return o


def _flash_fwd_rule(q, k, v, scale, causal, blk_q, blk_k, interpret):
    o, lse = _fwd(q, k, v, scale, causal, blk_q, blk_k, interpret)
    return o, (q, k, v, o, lse)


_flash_bhtd.defvjp(_flash_fwd_rule, _bwd)


def flash_attention(q, k, v, scale=None, causal=False, block_q=512,
                    block_k=512, interpret=None):
    """q: [B, H, Tq, D], k/v: [B, H, Tk, D] -> [B, H, Tq, D].

    Fast path requires Tq/Tk to be multiples of the block size (the model
    zoo pads/buckets sequences to 128-multiples for exactly this reason);
    other shapes fall back to the masked XLA implementation.

    Default 512x512 blocks: measured on a v5e chip at T=8192 causal they
    run 5x faster than 128x128 (grid-overhead-bound) and 2.1x faster than
    XLA's materialized attention — see docs/perf.md.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, h, tq, d = q.shape
    tk = k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)

    blk_q = _pick_block(block_q, tq)
    blk_k = _pick_block(block_k, tk)

    # causal block indexing assumes aligned sequence starts (tq == tk);
    # head width must be lane-tileable for the replicated-stat layout
    if (causal and tq != tk) or blk_q is None or blk_k is None \
            or not _tileable(d):
        from paddle_tpu.ops import attention as attn
        return attn.dot_product_attention(q, k, v, scale=scale,
                                          causal=causal, use_flash=False)

    qf = q.reshape(b * h, tq, d)
    kf = k.reshape(b * h, tk, d)
    vf = v.reshape(b * h, tk, d)
    o = _flash_bhtd(qf, kf, vf, scale, causal, blk_q, blk_k, interpret)
    return o.reshape(b, h, tq, d)


# ----------------------------------------- int8 K/V forward (quant prefill)
#
# The decode kernels' quant contract (ops/pallas/decode_attention.py),
# applied to the batched prefill pass: int8 K/V blocks plus their
# per-(position, KV-head) f32 scale sidecars ride the SAME block-indexed
# DMA stream as the values, and widening happens in REGISTERS —
# `k_i8.astype(f32) * scale` right before the qk dot, elementwise
# identical to quant/kv.dequantize_heads — so no f32 [Tp, Dkv] K/V
# buffer ever exists in HBM (perf/analytic.assert_prefill_kv_quantized
# pins its absence structurally).  Forward-only: prefill is inference;
# the training path keeps the f32 custom_vjp kernel above.
#
# GQA is handled by the index maps, not by widening: the grid carries the
# QUERY head h, and the K/V/scale BlockSpecs select kv-head h//group's
# dh-column stripe (block-unit indexing on the flat [B, Tk, Dkv] cache
# buffer), so repeat_kv_heads never materializes.

# test/bench override for the pallas_prefill_quant flag: None = read
# FLAGS, else "auto" | "always" | "off" — same trace-time contract as
# PREFILL_MODE above.
PREFILL_QUANT_MODE = None


def _prefill_quant_mode():
    if PREFILL_QUANT_MODE is not None:
        return PREFILL_QUANT_MODE
    from paddle_tpu.utils.flags import FLAGS
    return getattr(FLAGS, "pallas_prefill_quant", "auto")


@contextlib.contextmanager
def forced_prefill_quant_mode(mode):
    """Temporarily force the int8-prefill kernel routing ("always" |
    "off" | "auto") — tests, the analytic gate, and the A/B bench.
    Trace-time: wrap the jit/lower call, not just the execution."""
    global PREFILL_QUANT_MODE
    old = PREFILL_QUANT_MODE
    PREFILL_QUANT_MODE = mode
    try:
        yield
    finally:
        PREFILL_QUANT_MODE = old


def prefill_quant_enabled():
    """True when ``lm_prefill(kv_dtype="int8")``'s batched causal pass
    should stream the int8 cache bytes through ``flash_attention_quant``
    instead of dequantizing to a widened f32 K/V first (read at trace
    time by ``models/transformer``).  "auto" follows use_pallas() — the
    CPU tier-1 default stays the dequant + masked XLA reference path,
    preserving the batched-vs-sequential bit-exactness discipline."""
    m = str(_prefill_quant_mode()).lower()
    if m in ("0", "off", "false", "no"):
        return False
    if m in ("1", "on", "always", "true", "yes"):
        return True
    if m != "auto":
        raise ValueError(f"pallas_prefill_quant={m!r} (takes auto | "
                         "always | off)")
    from paddle_tpu.ops import pallas as pk
    return pk.use_pallas()


def _fwd_quant_kernel(q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                      m_scr, l_scr, acc_scr, *, blk_q, blk_k, scale,
                      causal):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)
    d = q_ref.shape[-1]

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    needed = (qi * blk_q + blk_q - 1 >= ki * blk_k) if causal else True

    @pl.when(needed)
    def _():
        q = q_ref[0, 0].astype(jnp.float32)
        # widen in registers: int8 block * per-(position, head) scale
        # column — the exact dequantize_heads product, so the kernel is
        # bit-identical to flash over the dequantized widened twin
        k = k_ref[0].astype(jnp.float32) * ks_ref[0, 0]   # [blk_k, dh]
        v = v_ref[0].astype(jnp.float32) * vs_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [blk_q, blk_k]
        if causal:
            rows = jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 0) + qi * blk_q
            cols = jax.lax.broadcasted_iota(
                jnp.int32, (blk_q, blk_k), 1) + ki * blk_k
            s = jnp.where(rows >= cols, s, _NEG)
        m_prev, l_prev = m_scr[:], l_scr[:]          # [blk_q, _LANES]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, blk_k))
        alpha = jnp.exp(m_prev - m_new)              # [blk_q, _LANES]
        m_scr[:] = m_new
        l_scr[:] = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * _lanes(alpha, d) + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _():
        l = jnp.maximum(l_scr[:], 1e-30)
        o_ref[0, 0] = (acc_scr[:] / _lanes(l, d)).astype(o_ref.dtype)


def flash_attention_quant(q, k, v, kscale, vscale, num_heads, scale=None,
                          causal=True, block_q=512, block_k=512,
                          interpret=None):
    """Int8-K/V flash prefill: q [B, Tq, D] f32 (flat projection), k/v
    [B, Tk, Dkv] int8 (the cache layout), kscale/vscale [B, Tk, Hkv]
    f32 per-(position, KV-head) sidecars -> [B, H, Tq, dh].

    The sidecars ride the same block-indexed stream as the int8 values
    (each k block pairs with its [blk_k, 1] scale column); widening is
    in-register.  Per-head the K/V stripe is re-streamed (grid is
    (B, H, Tq/blk, Tk/blk)) — the honest CostEstimate below — still
    ~4x fewer KV bytes than a widened f32 stream at dh=128.

    Shape contract (the caller pre-checks via ``prefill_quant_covers``):
    blocks divide Tq/Tk, dh lane-tileable, compiled mode wants
    32-sublane int8 k-tiles; interpret mode takes any divisor."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    b, tq, d = q.shape
    tk, dkv = k.shape[1], k.shape[2]
    from paddle_tpu.ops.pallas import decode_attention as _dk
    hs = _dk._head_split(d, dkv, num_heads)
    if hs is None:
        raise ValueError(
            f"flash_attention_quant: d={d}, dkv={dkv} do not describe a "
            f"grouped-head layout for num_heads={num_heads}")
    dh, hkv, group = hs
    if not _dk._check_scales("flash_attention_quant", kscale, vscale,
                             (b, tk), hkv):
        raise ValueError("flash_attention_quant: scale sidecars required")
    if k.dtype != jnp.int8 or v.dtype != jnp.int8:
        raise ValueError(
            f"flash_attention_quant: k/v must be int8, got "
            f"{k.dtype}/{v.dtype}")
    scale = scale if scale is not None else 1.0 / math.sqrt(dh)
    blk_q = _pick_block(block_q, tq)
    blk_k = _pick_block(block_k, tk, sublane=8 if interpret else 32)
    if blk_q is None or blk_k is None or not _tileable(dh) \
            or (causal and tq != tk):
        raise ValueError(
            f"flash_attention_quant: uncoverable shape tq={tq} tk={tk} "
            f"dh={dh} (use prefill_quant_covers before calling)")

    qh = q.reshape(b, tq, num_heads, dh).transpose(0, 2, 1, 3)
    kernel = functools.partial(_fwd_quant_kernel, blk_q=blk_q,
                               blk_k=blk_k, scale=scale, causal=causal)
    kv_map = lambda bb, hh, i, j: (bb, j, hh // group)
    # the sidecars go in head-major [B, Hkv, Tk, 1]: a [blk_k, 1] column
    # block of the cache layout [B, Tk, Hkv] has a last dim that is
    # neither 128-divisible nor the array's, which Mosaic rejects
    sc_map = lambda bb, hh, i, j: (bb, hh // group, j, 0)
    kscale, vscale = (sc.transpose(0, 2, 1)[..., None]
                      for sc in (kscale, vscale))
    o = pl.pallas_call(
        kernel,
        grid=(b, num_heads, tq // blk_q, tk // blk_k),
        in_specs=[
            pl.BlockSpec((1, 1, blk_q, dh),
                         lambda bb, hh, i, j: (bb, hh, i, 0)),
            pl.BlockSpec((1, blk_k, dh), kv_map),
            pl.BlockSpec((1, blk_k, dh), kv_map),
            pl.BlockSpec((1, 1, blk_k, 1), sc_map),
            pl.BlockSpec((1, 1, blk_k, 1), sc_map),
        ],
        out_specs=pl.BlockSpec((1, 1, blk_q, dh),
                               lambda bb, hh, i, j: (bb, hh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, num_heads, tq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((blk_q, _LANES), jnp.float32),
            pltpu.VMEM((blk_q, _LANES), jnp.float32),
            pltpu.VMEM((blk_q, dh), jnp.float32),
        ],
        cost_estimate=pl.CostEstimate(
            # 2 matmuls (qk, pv), per-head KV re-stream of int8 bytes +
            # f32 scale column, q in + o out
            flops=2 * 2 * b * num_heads * tq * tk * dh,
            bytes_accessed=(b * num_heads * 2 * tk * (dh * 1 + 4)
                            + 2 * b * num_heads * tq * dh * 4),
            transcendentals=b * num_heads * tq * tk),
        interpret=interpret,
    )(qh, k, v, kscale, vscale)
    return o


def prefill_quant_decline_reason(tq, tk, d, dkv, num_heads, interpret=None,
                                 block_q=512, block_k=512):
    """Why flash_attention_quant's blocking does NOT cover the shape
    (None = it does) — the dispatch predicate
    (decode_attention.decline_reason's twin)."""
    from paddle_tpu.ops.pallas import decode_attention as _dk
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    hs = _dk._head_split(d, dkv, num_heads)
    if hs is None:
        return (f"d {d}, Dkv {dkv}, heads {num_heads} do not describe a "
                "grouped-head layout")
    dh, _, _ = hs
    if not _tileable(dh):
        return f"head_dim {dh} is neither <= {_LANES} nor a multiple of it"
    if tq != tk:
        return f"causal prefill needs Tq == Tk, got {tq} != {tk}"
    if _pick_block(block_q, tq) is None:
        return f"no q block <= {block_q} tiles Tq {tq}"
    if _pick_block(block_k, tk, sublane=8 if interpret else 32) is None:
        return (f"no k block <= {block_k} tiles Tk {tk} in "
                f"{8 if interpret else 32}-sublane int8 tiles")
    return None


def prefill_quant_covers(b, tq, tk, d, dkv, num_heads, interpret,
                         block_q=512, block_k=512):
    """True when flash_attention_quant serves the shape."""
    del b
    return prefill_quant_decline_reason(tq, tk, d, dkv, num_heads,
                                        interpret, block_q, block_k) is None


def maybe_prefill_quant(q, k_set, v_set, sk, sv, num_heads):
    """lm_prefill's int8 dispatch: q [B, Tp, D] f32, k_set/v_set
    [B, Tp, Dkv] int8 (the just-quantized cache writes), sk/sv
    [B, Tp, Hkv] scales -> attention output [B, Tp, D], or None when
    the routing is off / the shape is uncoverable (caller falls back to
    the dequant + masked XLA reference path)."""
    if sk is None or not prefill_quant_enabled():
        return None
    interpret = jax.default_backend() != "tpu"
    b, tp, d = q.shape
    dkv = k_set.shape[-1]
    if not prefill_quant_covers(b, tp, tp, d, dkv, num_heads, interpret):
        return None
    o = flash_attention_quant(q, k_set, v_set, sk, sv, num_heads,
                              causal=True, interpret=interpret)
    return o.transpose(0, 2, 1, 3).reshape(b, tp, d)

"""Pallas kernel smoke checks: compile every kernel on the LIVE backend and
verify numerics against the pure-XLA oracle.

Interpret-mode passing is not a compile proof (Mosaic rejects layouts,
VMEM footprints and alignments the interpreter never sees), so every case
here runs through the backend's real path: on TPU a Mosaic compile —
``run_case`` records the ``interpret`` argument of every ``pl.pallas_call``
the case traces and ``expect_compiled=True`` fails the case unless at
least one ran and none was interpreted — and on CPU interpret mode
(tests/test_kernel_smoke.py keeps the harness itself honest).

Each case is built at one of two widths: ``SMALL`` (seconds on CPU) and
``SERVING`` (the widths chip_smoke.py serves and trains at: d_model 2048
as 16 heads of 128, slab length 2048, pool block 16, chunk 8; LSTM h=512
B=64 T=100, again at the benchmark's batch of 1,024, and at h=1280 B=256
x 25 — the widest row of the reference's own LSTM table
(benchmark/README.md: hidden 256/512/1280 x batch 64/128/256), which a
v5e core holds only as two batch tiles of 128; blocked LSTM h=1280).  A case whose
kernel's own guard declines the shape reports the guard's reason instead
of running — it never silently takes a reference path.

The oracle side always traces under ``f32_reference()`` (float32 compute
policy + ``jax.default_matmul_precision("highest")``): on the MXU a
default-precision f32 matmul is one bf16 pass, and an oracle that rounds
like that would hide — or fake — a kernel error.
"""

import contextlib
import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.core.sequence import SequenceBatch


@dataclasses.dataclass(frozen=True)
class Widths:
    """The shapes one smoke run builds its cases at."""
    heads: int          # query heads
    kv_heads: int       # KV heads (== heads: MHA; < heads: GQA)
    head_dim: int
    slots: int          # decode rows S
    slab_len: int       # slab layout: cache length T
    block_size: int     # paged layout: positions per pool block
    blocks_per_row: int
    chunk: int          # query lanes per row in the chunk cases
    flash_batch: int
    flash_len: int
    flash_block: int
    rnn_batch: int
    rnn_len: int
    rnn_hidden: int
    lstm_cell_batch: int    # the benchmark cell's batch, at rnn_len
    lstm_tiled_batch: int   # over one batch tile on the chip at
    lstm_tiled_hidden: int  # ... this width; the plan nears the budget
    lstm_tiled_len: int     # (short: keeps the case's arrays small)
    blocked_hidden: int     # lstm_blocked (the over-VMEM variant)
    blocked_len: int        # odd: exercises the t-parity pad
    kda_slots: int = 4      # kda_chunk: rows, heads of head_dim x head_dim
    kda_heads: int = 8      # state, lanes a row
    kda_chunk: int = 16
    # mamba_chunk: rows x lanes packed at the narrowest step width, states
    # of mamba_state x mamba_inner
    mamba_slots: int = 4
    mamba_chunk: int = 8
    mamba_inner: int = 256
    mamba_state: int = 8
    # mla_chunk: rows x lanes x heads over a latent pool of (rank + rope)
    # values a position, padded to whole lane tiles; blocks of block_size
    mla_slots: int = 4
    mla_heads: int = 8
    mla_chunk: int = 8
    mla_rank: int = 64
    mla_rope: int = 16
    mla_blocks_per_row: int = 4
    # the opt1.3b_chat cell's own paged chunk call: MHA heads of cell_head_dim
    # over a pool of block 16, rows at contexts drawn from cell_contexts
    # (lo, hi) around cell_mean_context
    cell_heads: int = 4
    cell_head_dim: int = 64
    cell_blocks_per_row: int = 16
    cell_contexts: tuple = (10, 200)
    cell_mean_context: int = 86
    # the window kernel over per-slot rings of window + chunk positions in
    # blocks of window_block, walked through a table of window_entries
    window_slots: int = 4
    window_heads: int = 6
    window_kv_heads: int = 2
    window_chunk: int = 8
    window: int = 64
    window_block: int = 16
    window_entries: int = 32


SMALL = Widths(heads=8, kv_heads=2, head_dim=128, slots=8, slab_len=256,
               block_size=32, blocks_per_row=4, chunk=8, flash_batch=2,
               flash_len=512, flash_block=256, rnn_batch=8, rnn_len=12,
               rnn_hidden=128, lstm_cell_batch=16, lstm_tiled_batch=24,
               lstm_tiled_hidden=128, lstm_tiled_len=5, blocked_hidden=256, blocked_len=9)

# chip_smoke.py's leg-2 trunk and leg-3 network; the serving CLI's own
# defaults for block size and prefill chunk (utils/flags.py)
SERVING = Widths(heads=16, kv_heads=16, head_dim=128, slots=8,
                 slab_len=2048, block_size=16, blocks_per_row=128, chunk=8,
                 flash_batch=2, flash_len=2048, flash_block=512,
                 rnn_batch=64, rnn_len=100, rnn_hidden=512,
                 lstm_cell_batch=1024, lstm_tiled_batch=256,
                 lstm_tiled_hidden=1280, lstm_tiled_len=25, blocked_hidden=1280, blocked_len=25,
                 # the kimilinear_reason cell's step: 32 slots of 32 heads
                 kda_slots=32, kda_heads=32, kda_chunk=16,
                 # the jamba3b_longctx cell's step: 16 slots of 16 x 5,120
                 mamba_slots=16, mamba_chunk=64, mamba_inner=5120,
                 mamba_state=16,
                 # the pangu_longdoc cell's step at four of its 16 rows and
                 # contexts to 1,024: 128 heads x 64 lanes over 512 + 64
                 mla_slots=4, mla_heads=128, mla_chunk=64, mla_rank=512,
                 mla_rope=64, mla_blocks_per_row=64,
                 # benchmark/configs/lm-opt-1.3b.json x chat_open.json
                 cell_heads=32, cell_head_dim=64, cell_blocks_per_row=128,
                 cell_contexts=(100, 700), cell_mean_context=330,
                 # the laguna_repoctx cell's window layers at four of its 16
                 # rows: 72 heads on 8 over rings of 576, table [4, 1024]
                 window_slots=4, window_heads=72, window_kv_heads=8,
                 window_chunk=64, window=512, window_block=32,
                 window_entries=1024)


class Case(NamedTuple):
    fn: Callable        # kernel side, jit-able: fn(*args) -> outputs
    oracle: Callable    # pure-XLA reference, same signature
    args: tuple
    err: Callable       # (got, want) -> float
    facts: dict = {}    # what the case knows of its path; joins its row
    tol: tuple = None   # (tolerance, why) of a case that computes in one
    #                     precision however it runs; else by what ran


class Declined(NamedTuple):
    """The kernel's own guard rejected the shape; ``reason`` is its."""
    reason: str


# Tolerances: one per way a kernel can run, because the two do not compute
# in the same precision.  Inputs are N(0, 0.5^2), so max|v| ~ 2.5;
# attention outputs are convex combinations of V rows and abs error is
# the right unit; fwd+bwd cases normalize by the oracle's max magnitude.
#
# Interpreted (CPU): the kernel body runs as f32 XLA ops; only summation
# order separates it from the f32 oracle.
_TOL_INTERPRETED = 1e-4
_WHY_INTERPRETED = ("interpret mode computes in f32: summation order is "
                    "all that differs from the f32 oracle")
# Compiled (Mosaic): an in-kernel f32 ``dot_general`` at default precision
# is ONE bf16 MXU pass with f32 accumulation (first measured on the v5e in
# PR 21: a row that attends a single position returns V rounded to bf16 —
# 3.9e-3 in every decode case, 4.1e-3..4.5e-3 in the causal flash cases —
# while rows averaging ~1000 positions land at 2e-4..6e-4; the recurrent
# cases at 2.1e-3..5.4e-3).  With unit roundoff u = 2^-9 the worst row is off by
# about 2 * u * max|v| = 1e-2 (V rounded, P rounded); the recurrent
# kernels compound u * sqrt(2) per step through the carry, about
# u * sqrt(2 T) at T = 100.  2e-2 covers both with 2x room, and an
# 8-bit-float pass (u = 2^-4, 16x every figure above) fails it.  Every
# decode case pins one row at position 0 so the single-position row —
# the one that shows the precision — is always in the comparison.
_TOL_COMPILED = 2e-2
_WHY_COMPILED = ("Mosaic's default-precision f32 matmul is one bf16 MXU "
                 "pass (u = 2^-9): <= 2*u*max|v| ~ 1e-2 on a row attending "
                 "one position, u*sqrt(2T) through a T=100 recurrence; an "
                 "8-bit-float pass is 16x off and fails")


@contextlib.contextmanager
def _fused_mode(mode):
    """Temporarily force the fused-RNN dispatch mode ('always' | '0')."""
    from paddle_tpu.ops import rnn
    old = rnn.FUSED_LSTM
    rnn.FUSED_LSTM = mode
    try:
        yield
    finally:
        rnn.FUSED_LSTM = old


@contextlib.contextmanager
def f32_reference():
    """Trace the enclosed computation as the float32 reference: float32
    compute policy (``linear.matmul`` otherwise casts to bf16 on TPU) and
    "highest" matmul precision (otherwise one bf16 MXU pass)."""
    from paddle_tpu.core import dtypes
    old = dtypes._compute_dtype
    dtypes.set_policy(dtypes.param_dtype(), "float32")
    try:
        with jax.default_matmul_precision("highest"):
            yield
    finally:
        dtypes._compute_dtype = old


@contextlib.contextmanager
def record_pallas_calls():
    """Yield a list that receives ``bool(interpret)`` for every
    ``pl.pallas_call`` traced inside the block — the observed answer to
    "did this go through Mosaic", instead of one inferred from the
    backend name."""
    from jax.experimental import pallas as pl
    seen = []
    real = pl.pallas_call

    def spy(*args, **kw):
        seen.append(bool(kw.get("interpret", False)))
        return real(*args, **kw)

    pl.pallas_call = spy
    try:
        yield seen
    finally:
        pl.pallas_call = real


def _max_err(a, b):
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


def _rel_err(a, b):
    """Max abs error normalized by the reference's max magnitude."""
    return _max_err(a, b) / max(1.0, float(jnp.max(jnp.abs(b))))


def _tree_rel_err(got, want):
    return max(_rel_err(g, w) for g, w in zip(
        jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)))


# ------------------------------------------------------------------ RNN

def _rnn_case(kind, w, batch=None, length=None, hidden=None, proj_in=None):
    """Fused-vs-scan equality (fwd + full BPTT grads) through the public
    rnn.{lstm,gru,simple_rnn} dispatch.  The dispatch mode is read at
    TRACE time, so each side sets it inside its own traced body.
    ``proj_in``: the LSTM is handed an input of that width and its
    projection (``lstm(proj=)``: the forward kernel forms the gate inputs),
    and ``wr`` is (w_r, W_x, the gate bias)."""
    from paddle_tpu.ops import rnn

    b, t, d = (batch or w.rnn_batch, length or w.rnn_len,
               hidden or w.rnn_hidden)
    facts = {}
    gates = {"lstm": 4, "gru": 3, "simple_rnn": 1}[kind]
    rng = np.random.RandomState(7)
    data = jnp.asarray(rng.randn(b, t, proj_in or gates * d) * 0.3,
                       jnp.float32)
    lengths = jnp.asarray(rng.randint(1, t + 1, (b,)), jnp.int32)
    probe = jnp.asarray(rng.randn(b, t, d), jnp.float32)
    scale = 1.0 / np.sqrt(d)

    if kind == "lstm":
        from paddle_tpu.ops.pallas import lstm as pl_lstm
        # the dispatcher's own rule, so the row says how the batch was cut
        facts = {"batch": b, "batch_tile": pl_lstm.batch_tile(b, d, proj_in)}
        wr = jnp.asarray(rng.randn(d, 4 * d) * scale, jnp.float32)
        checks = [jnp.asarray(rng.randn(d) * 0.1, jnp.float32)
                  for _ in range(3)]

        if proj_in:
            wr = (wr, jnp.asarray(rng.randn(proj_in, 4 * d)
                                  / np.sqrt(proj_in), jnp.float32),
                  jnp.asarray(rng.randn(4 * d) * 0.1, jnp.float32))

        def loss(data, wr):
            wr, *own = wr if proj_in else (wr,)
            out, final = rnn.lstm(SequenceBatch(data=data, lengths=lengths),
                                  wr, check_i=checks[0], check_f=checks[1],
                                  check_o=checks[2],
                                  **dict(zip(("proj", "bias"), own)))
            return (jnp.sum(out.data * probe) + jnp.sum(final.h)
                    + jnp.sum(final.c))
    elif kind == "gru":
        wr = jnp.asarray(rng.randn(d, 2 * d) * scale, jnp.float32)
        ws = jnp.asarray(rng.randn(d, d) * scale, jnp.float32)

        def loss(data, wr):
            out, final = rnn.gru(SequenceBatch(data=data, lengths=lengths),
                                 wr, ws)
            return jnp.sum(out.data * probe) + jnp.sum(final)
    else:
        wr = jnp.asarray(rng.randn(d, d) * scale, jnp.float32)

        def loss(data, wr):
            out, final = rnn.simple_rnn(
                SequenceBatch(data=data, lengths=lengths), wr)
            return jnp.sum(out.data * probe) + jnp.sum(final)

    vg = jax.value_and_grad(loss, argnums=(0, 1))

    def fn(data, wr):
        with _fused_mode("always"):
            return vg(data, wr)

    def oracle(data, wr):
        with _fused_mode("0"):
            return vg(data, wr)

    return Case(fn, oracle, (data, wr), _tree_rel_err, facts)


def _lstm_blocked_case(w):
    """Gate-blocked over-VMEM LSTM forward (lstm_blocked.py) + its
    saved-activation BPTT vs the scan oracle, via direct kernel call (the
    dispatch prefers the resident kernel whenever it fits)."""
    from paddle_tpu.ops import rnn
    from paddle_tpu.ops.pallas import lstm_blocked as blk

    b, t, d = w.rnn_batch, w.blocked_len, w.blocked_hidden
    rng = np.random.RandomState(11)
    data = jnp.asarray(rng.randn(b, t, 4 * d) * 0.3, jnp.float32)
    lengths = jnp.asarray(rng.randint(1, t + 1, (b,)), jnp.int32)
    probe = jnp.asarray(rng.randn(b, t, d), jnp.float32)
    wr = jnp.asarray(rng.randn(d, 4 * d) / np.sqrt(d), jnp.float32)
    checks = [jnp.asarray(rng.randn(d) * 0.1, jnp.float32)
              for _ in range(3)]
    if not blk.supported(b, d, "tanh", "sigmoid", "tanh", None):
        return Declined(f"lstm_blocked.supported(b={b}, d={d}) is False")

    def loss_blk(data, wr):
        seq = SequenceBatch(data=data, lengths=lengths)
        hs, (fh, fc) = blk.lstm_fused_blocked(
            data.transpose(1, 0, 2), seq.mask().transpose(1, 0), wr,
            *checks)
        out = hs.transpose(1, 0, 2) * seq.mask(hs.dtype)[..., None]
        return jnp.sum(out * probe) + jnp.sum(fh) + jnp.sum(fc)

    def loss_scan(data, wr):
        with _fused_mode("0"):
            out, final = rnn.lstm(SequenceBatch(data=data, lengths=lengths),
                                  wr, check_i=checks[0], check_f=checks[1],
                                  check_o=checks[2])
        return (jnp.sum(out.data * probe) + jnp.sum(final.h)
                + jnp.sum(final.c))

    return Case(jax.value_and_grad(loss_blk, argnums=(0, 1)),
                jax.value_and_grad(loss_scan, argnums=(0, 1)),
                (data, wr), _tree_rel_err)


# ---------------------------------------------------------------- flash

def _flash_case(causal, w):
    """Flash attention fwd+bwd vs materialized-softmax oracle."""
    import importlib
    # the pallas package re-exports the flash_attention FUNCTION under the
    # module's name; import the module itself explicitly
    fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")
    from paddle_tpu.ops import attention as attn

    b, h, t, d = w.flash_batch, w.heads, w.flash_len, w.head_dim
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(b, h, t, d) * 0.5, jnp.float32)
               for _ in range(3))
    probe = jnp.asarray(rng.randn(b, h, t, d), jnp.float32)

    def loss_flash(q, k, v):
        o = fa.flash_attention(q, k, v, causal=causal,
                               block_q=w.flash_block, block_k=w.flash_block)
        return jnp.sum(o * probe)

    def loss_oracle(q, k, v):
        o = attn.dot_product_attention(q, k, v, scale=1.0 / np.sqrt(d),
                                       causal=causal, use_flash=False)
        return jnp.sum(o * probe)

    return Case(jax.value_and_grad(loss_flash, argnums=(0, 1, 2)),
                jax.value_and_grad(loss_oracle, argnums=(0, 1, 2)),
                (q, k, v), _tree_rel_err)


def _quantize_kv(shape, hkv, seed):
    """Random f32 K/V quantized to (int8, per-(position, head) scales)
    — the int8 cases' shared input builder (quant/kv.py math)."""
    from paddle_tpu.quant import kv as kvq
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(*shape) * 0.5, jnp.float32)
    return kvq.quantize_heads(x, hkv)


def _flash_int8_case(w):
    """Int8 flash prefill kernel (flash_attention_quant): int8 K/V with
    their per-(position, head) scale sidecars riding the same
    block-indexed stream, widened in registers, vs the dequantize-then-
    attend oracle — causal, multi-position."""
    import importlib
    from paddle_tpu.models import transformer
    from paddle_tpu.quant import kv as kvq
    fa = importlib.import_module(
        "paddle_tpu.ops.pallas.flash_attention")

    b, h, hkv, dh, t = (w.flash_batch, w.heads, w.kv_heads, w.head_dim,
                        w.flash_len)
    d, dkv = h * dh, hkv * dh
    reason = fa.prefill_quant_decline_reason(t, t, d, dkv, h)
    if reason is not None:
        return Declined(reason)
    rng = np.random.RandomState(51)
    q = jnp.asarray(rng.randn(b, t, d) * 0.5, jnp.float32)
    qk, sk = _quantize_kv((b, t, dkv), hkv, seed=9)
    qv, sv = _quantize_kv((b, t, dkv), hkv, seed=10)

    def fn(q, k, v, ks, vs):
        o = fa.flash_attention_quant(q, k, v, ks, vs, h, causal=True)
        return o.transpose(0, 2, 1, 3).reshape(b, t, d)

    def oracle(q, k, v, ks, vs):
        pm = jnp.broadcast_to(jnp.tril(jnp.ones((t, t), bool))[None],
                              (b, t, t))
        return transformer._attend(q, kvq.dequantize_heads(k, ks),
                                   kvq.dequantize_heads(v, vs), h, pm)

    return Case(fn, oracle, (q, qk, qv, sk, sv), _max_err)


# --------------------------------------------------------------- decode

def build_private_tables(positions, nb_row, block_size, num_blocks):
    """Per-row PRIVATE block chains for decode-kernel drives: row r owns
    ``pos // block_size + 1`` distinct block ids from 1..num_blocks-1,
    unowned table slots stay 0 (the reserved scratch block) — the layout
    serving/kv_pool.py's allocator produces.  One definition for the
    smoke cases here and tests/test_pallas_decode.py."""
    tables = np.zeros((len(positions), nb_row), np.int32)
    nxt = 1
    for r, p in enumerate(positions):
        for j in range(int(p) // block_size + 1):
            if nxt >= num_blocks:
                raise ValueError(
                    f"pool of {num_blocks} blocks cannot hold private "
                    f"chains for positions {list(positions)}")
            tables[r, j] = nxt
            nxt += 1
    return tables


def _chunk_lanes_ref(positions, lengths, kk):
    li = np.minimum(np.arange(kk)[None, :], lengths[:, None] - 1)
    return (positions[:, None] + li).astype(np.int32)


def _cell(w):
    """``w`` with the opt1.3b_chat cell's paged chunk call in the decode
    fields: MHA, block 16, the cell's table length."""
    return dataclasses.replace(
        w, heads=w.cell_heads, kv_heads=w.cell_heads,
        head_dim=w.cell_head_dim, block_size=16,
        blocks_per_row=w.cell_blocks_per_row)


def _decode_case(w, *, paged, chunk, quant, seed, contexts=None):
    """One of the eight fused decode-attention calls (slab | paged) x
    (one lane | ``w.chunk`` lanes) x (f32 | int8 K/V) vs the masked-XLA oracle
    (models/transformer._attend over the gathered, dequantized rows) —
    forward only (the decode hot path has no backward).  Mixed decode
    rows (1 live lane) and chunking rows (all lanes) in the chunk cases;
    the oracle compares LIVE lanes only: dead tail lanes repeat the last
    live qpos and their output is unspecified (the decode-row fast path
    skips them; nothing downstream reads a dead lane).  ``contexts``
    (lo, hi): every row's position drawn from that range, none pinned."""
    from paddle_tpu.models import transformer
    from paddle_tpu.ops.pallas import decode_attention as dk
    from paddle_tpu.quant import kv as kvq

    h, hkv, dh, s = w.heads, w.kv_heads, w.head_dim, w.slots
    d, dkv = h * dh, hkv * dh
    kk = w.chunk if chunk else 1
    bs, nb_row = w.block_size, w.blocks_per_row
    t = nb_row * bs if paged else w.slab_len
    with dk.forced_mode("always"):
        reason = dk.decline_reason(h, d, dkv, bs if paged else t,
                                   paged=paged, chunk=kk, quant=quant)
    if reason is not None:
        return Declined(reason)

    rng = np.random.RandomState(seed)
    q = jnp.asarray(rng.randn(s, kk, d) * 0.5, jnp.float32)
    lens = rng.randint(1, kk + 1, s).astype(np.int32)
    lens[0], lens[-1] = 1, kk           # pin both extremes
    if contexts is not None:
        pos = rng.randint(contexts[0], contexts[1] + 1, s).astype(np.int32)
    else:
        pos = rng.randint(0, t - kk, s).astype(np.int32)
        pos[1] = t - kk                 # one row reaches the last block
        pos[2] = 0                      # one row attends <= kk positions:
        #                                 nothing averages its rounding away
    qpos = _chunk_lanes_ref(pos, lens, kk)
    kv_shape = ((s * nb_row + 1, bs, dkv) if paged else (s, t, dkv))
    if quant:
        k, ks = _quantize_kv(kv_shape, hkv, seed + 1)
        v, vs = _quantize_kv(kv_shape, hkv, seed + 2)
    else:
        k, v = (jnp.asarray(rng.randn(*kv_shape) * 0.5, jnp.float32)
                for _ in range(2))
        ks = vs = None
    tables = (jnp.asarray(build_private_tables(
        qpos[:, -1], nb_row, bs, kv_shape[0])) if paged else None)
    live = jnp.asarray(np.arange(kk)[None, :] < lens[:, None])
    qpos = jnp.asarray(qpos)

    def fn(q, k, v, ks, vs):
        with dk.forced_mode("always"):
            if paged:
                out = dk.maybe_paged_chunk(q, k, v, qpos, tables, h,
                                           kscale=ks, vscale=vs)
            else:
                out = dk.maybe_slab_chunk(q, k, v, qpos, h, kscale=ks,
                                          vscale=vs)
        assert out is not None, "kernel declined a shape its guard covers"
        return out

    def oracle(q, k, v, ks, vs):
        if quant:
            k, v = kvq.dequantize_heads(k, ks), kvq.dequantize_heads(v, vs)
        if paged:
            k = k[tables].reshape(s, -1, dkv)
            v = v[tables].reshape(s, -1, dkv)
        pm = jnp.arange(t)[None, None, :] <= qpos[:, :, None]
        return transformer._attend(q, k, v, h, pm)

    return Case(fn, oracle, (q, k, v, ks, vs),
                lambda got, want: _max_err(got[live], want[live]))


# the panels the tiled paged kernel serves, as the cells call it: (query
# heads, K/V heads, head dim, lanes a row, block, dtype, window).  Chat's
# two heads of 64 a block-diagonal panel, Laguna's full layers (group 6)
# and window layers (group 9, over rings), Jamba's group 20 on one K/V head
ONE_LANE_PANELS = {
    "opt1.3b_chat": (32, 32, 64, 8, 16, jnp.float32, None),
    "laguna_full": (48, 8, 128, 64, 32, jnp.bfloat16, None),
    "laguna_window": (72, 8, 128, 64, 32, jnp.bfloat16, 512),
    "jamba_attn": (20, 1, 128, 64, 32, jnp.bfloat16, None),
}


def _panel_call(panel, slots, entries, key):
    """``(q, call)``: queries and the tiled kernel's call ``call(q, qpos,
    tables)`` of ``panel`` (a row of ``ONE_LANE_PANELS``) over ``slots``
    rows' random K/V: private tables of ``entries`` blocks a row, or with
    a window the rows' rings (the tables are then the kernel's own)."""
    from paddle_tpu.ops.pallas import decode_attention as dk
    h, hkv, dh, kk, bs, dtype, window = panel
    keys = iter(jax.random.split(key, 3))
    rand = lambda *shp: 0.5 * jax.random.normal(next(keys), shp, dtype)
    q = rand(slots, kk, h * dh)
    if window is None:
        k, v = (rand(slots * entries + 1, bs, hkv * dh) for _ in range(2))
        return q, lambda q, qpos, tables: dk.decode_attention_paged_chunk(
            q, k, v, qpos, tables, h)
    ring = -(-(window + kk - 1) // bs) * bs
    k, v = (rand(slots, ring, hkv * dh) for _ in range(2))
    return q, lambda q, qpos, tables: dk.decode_attention_window_chunk(
        q, k, v, qpos, h, window, block=bs, entries=entries)


def one_lane_vs_panel(panel, positions, entries, seed=0):
    """The tiled kernel's output ``(one, two)`` [S, K, D] for rows at
    ``positions`` fed ONE lane — the one-lane path — and for the same rows
    fed a second lane at the next position, which takes the whole panel.
    Lane 0 reads the same positions in both (a tile past it is a bit-exact
    no-op), so ``one[:, 0]`` must equal ``two[:, 0]`` bit for bit, and
    ``one[:, 1:]`` is exact zeros.  ``panel``: a row of
    ``ONE_LANE_PANELS``."""
    pos = np.asarray(positions, np.int64)
    s, kk, bs = pos.size, panel[3], panel[4]
    q, call = _panel_call(panel, s, entries, jax.random.PRNGKey(seed))
    tables = jnp.asarray(build_private_tables(pos + 1, entries, bs,
                                              s * entries + 1))
    return tuple(np.asarray(jax.jit(call)(q, jnp.asarray(_chunk_lanes_ref(
        pos, np.full(s, n), kk)), tables)) for n in (1, 2))


# the laguna_repoctx cell's two attention calls (ONE_LANE_PANELS' laguna
# rows) over 16 rows and tables of 1,024 entries, timed at these positions
LAGUNA_CONTEXTS = (600, 2048, 8192, 16384, 30720)


def laguna_settings(slots=16, chunk=64, span=32768,
                    contexts=LAGUNA_CONTEXTS):
    """``time_paged_chunk_cell``'s settings at the laguna_repoctx cell:
    ``decode_at_<p>`` puts every row at position p feeding one lane;
    ``cell_mix`` is a step of the cell, six rows decoding at 12-24 k, one
    prefilling ``chunk`` lanes at 10 k, the other slots free at 0."""
    one = np.ones(slots, np.int64)
    settings = {f"decode_at_{p}": (np.full(slots, min(p, span - 1)), one)
                for p in contexts}
    last, lens = np.zeros(slots, np.int64), one.copy()
    last[:6] = np.minimum(np.linspace(12288, 24576, 6).astype(np.int64),
                          span - 1)
    last[6], lens[6] = min(10240, span - 1), chunk
    settings["cell_mix"] = (last, lens)
    return settings


def time_paged_chunk_cell(w, calls=24, reps=20, panel=None, slots=None,
                          entries=None, settings=None):
    """Wall ms a call of the tiled paged kernel ALONE, ``{setting: ms}``.
    By default at the opt1.3b_chat cell's shape: every row at position 0
    (all but a row's first tile dead), at the cell's mean context, and at
    the table's last position (none dead), decode rows (one live lane) and
    prefill rows (every lane) apart; then the cell's own mix, contexts
    drawn from ``cell_contexts`` with one row in eight prefilling.
    ``panel`` (a row of ``ONE_LANE_PANELS``; its window's form where it
    has one), ``slots``, ``entries`` (table entries a row) and
    ``settings`` (``{name: (last positions, lanes fed)}``, e.g.
    ``laguna_settings()``) time another cell's call.  ``calls`` kernels
    chained in one program, as the step chains its layers; positions and
    tables are data (one compile a kernel); a time only on the chip."""
    import time
    if panel is None:
        c = _cell(w)
        panel = (c.heads, c.kv_heads, c.head_dim, c.chunk, c.block_size,
                 jnp.float32, None)
        slots, entries = c.slots, c.blocks_per_row
    kk, bs = panel[3], panel[4]
    t = entries * bs
    if settings is None:
        rng = np.random.RandomState(5)
        lo, hi = w.cell_contexts
        one, full = np.ones(slots, np.int64), np.full(slots, kk)
        mix = one.copy()
        mix[-1] = kk
        settings = {"cell_mix": (rng.randint(lo, hi + 1, slots), mix)}
        for last in (0, w.cell_mean_context, t - 1):
            settings[f"decode_at_{last}"] = (np.full(slots, last), one)
            last = max(last, kk - 1)
            settings[f"prefill_at_{last}"] = (np.full(slots, last), full)
    q, call = _panel_call(panel, slots, entries, jax.random.PRNGKey(5))

    @jax.jit
    def chain(q, qpos, tables):
        for _ in range(calls):
            q = call(q, qpos, tables)
        return q

    out = {}
    for name, (last, lens) in settings.items():
        args = (q, jnp.asarray(_chunk_lanes_ref(last - lens + 1, lens, kk)),
                jnp.asarray(build_private_tables(last, entries, bs,
                                                 slots * entries + 1)))
        jax.block_until_ready(chain(*args))
        t0 = time.perf_counter()
        for _ in range(reps):
            got = chain(*args)
        jax.block_until_ready(got)
        out[name] = round((time.perf_counter() - t0) * 1e3 / (reps * calls), 4)
    return out


# --------------------------------------------------------- delta rule

_WHY_KDA = ("kda_chunk is float32 VPU arithmetic whether Mosaic compiles it "
            "or the interpreter runs it (no MXU pass): summation order is "
            "all that differs from the float32 scan, and a bfloat16 state "
            "or operand is 2^-9 * max|S| ~ 1e-2 off and fails")


def _kda_case(w):
    """``kda_chunk`` against the scan (ops/kda.recurrence_scan): unit q and
    k, decays in (0.3, 1), states N(0, 0.5^2); row 0 is a decode row (one
    lane), row 1 fills every lane, row 2 is fresh (its stale state must
    not leak), the rest draw their lane counts."""
    from paddle_tpu.ops import kda
    from paddle_tpu.ops.pallas import kda as kernel
    s, kk, h, d = w.kda_slots, w.kda_chunk, w.kda_heads, w.head_dim
    why = kernel.shape_problem(kk, h, d, d,
                               interpret=jax.default_backend() != "tpu")
    if why:
        return Declined(why)
    ks = jax.random.split(jax.random.PRNGKey(110), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (s, kk, h, d))) * d ** -0.5
    k = unit(jax.random.normal(ks[1], (s, kk, h, d)))
    v = 0.5 * jax.random.normal(ks[2], (s, kk, h, d))
    a = jax.random.uniform(ks[3], (s, kk, h, d), minval=0.3, maxval=1.0)
    beta = jax.random.uniform(ks[4], (s, kk, h))
    state = 0.5 * jax.random.normal(ks[5], (s, h, d, d))
    lengths = jax.random.randint(ks[6], (s,), 1, kk + 1) \
        .at[0].set(1).at[1].set(kk)
    fresh = jnp.zeros((s,), bool).at[2].set(True)
    return Case(fn=kernel.kda_chunk, oracle=kda.recurrence_scan,
                args=(q, k, v, a, beta, state, lengths, fresh),
                err=_tree_rel_err,
                facts={"state_bytes": int(state.size) * 4},
                tol=(_TOL_INTERPRETED, _WHY_KDA))


# ------------------------------------------------------ selective scan

_WHY_MAMBA = ("mamba_chunk is float32 VPU and EUP arithmetic whether Mosaic "
              "compiles it or the interpreter runs it (no MXU pass): "
              "summation order and the exp are all that differ from the "
              "float32 scan, and a bfloat16 state is 2^-9 * max|h| off and "
              "fails")


def _mamba_case(w):
    """``mamba_chunk`` over a step's packed places against the XLA scan
    over rows (ops/mamba.scan_xla): row 0 decodes (one lane), row 1 fills
    every lane, row 2 is fresh (its stale state must not leak) and feeds
    half, the rest decode; packed at the narrowest width that holds them,
    so the last places repeat a lane and must be stepped over."""
    from paddle_tpu.models import hybrid_lm
    from paddle_tpu.ops import mamba
    from paddle_tpu.ops.pallas import mamba as kernel
    s, kk, d, n = w.mamba_slots, w.mamba_chunk, w.mamba_inner, w.mamba_state
    lens = np.asarray([1, kk, kk // 2] + [1] * (s - 3))
    width = next(x for x in hybrid_lm.step_widths(s, kk) if x >= lens.sum())
    why = kernel.shape_problem(width, s, d, n,
                               interpret=jax.default_backend() != "tpu")
    if why:
        return Declined(why)
    src, back = hybrid_lm.pack_lanes(lens, kk)
    src, back = jnp.asarray(src[:width]), jnp.asarray(back)
    ks = jax.random.split(jax.random.PRNGKey(130), 6)
    u = 0.5 * jax.random.normal(ks[0], (width, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (width, d)) - 2.0)
    b, c = (0.5 * jax.random.normal(k, (width, n)) for k in ks[2:4])
    a = -jnp.broadcast_to(jnp.arange(1.0, n + 1)[:, None], (n, d))
    state = 0.5 * jax.random.normal(ks[4], (s, n, d))
    fresh = jnp.zeros((s,), bool).at[2].set(True)
    own = mamba.own_places(src, back)

    def fn(u, dt, b, c, a, state):
        y, new = kernel.mamba_chunk(u, dt, b, c, a, state,
                                    *mamba.walk(src, back, fresh))
        return jnp.where(own[:, None], y, 0.0), new

    def oracle(u, dt, b, c, a, state):
        y, new = mamba.scan_xla(u, dt, b, c, a, state, jnp.asarray(lens),
                                fresh, src, back)
        return jnp.where(own[:, None], y, 0.0), new

    return Case(fn=fn, oracle=oracle, args=(u, dt, b, c, a, state),
                err=_tree_rel_err,
                facts={"state_bytes": int(state.size) * 4,
                       "places": int(width), "live": int(lens.sum())},
                tol=(_TOL_INTERPRETED, _WHY_MAMBA))


# ---------------------------------------------------- latent attention

def _mla_case(w):
    """``mla_chunk`` (ops/pallas/mla.mla_attend) against gathered blocks and
    ``[S, K, H, T]`` scores: row 0 decodes deep in its context (one lane),
    row 1 fills every lane across tiles, row 2 ends inside a block, row 3
    starts at position 0.  Queries are scaled as attention's are; the lanes
    past a row's length, which the kernel leaves unwritten, are zeroed on
    both sides."""
    from paddle_tpu.ops import mla
    from paddle_tpu.ops.pallas import mla as kernel
    s, kk, h, rank = w.mla_slots, w.mla_chunk, w.mla_heads, w.mla_rank
    bs, nb_row = w.block_size, w.mla_blocks_per_row
    width = mla.pool_width(rank + w.mla_rope)
    why = kernel.shape_problem(kk, h, width, rank, bs, jnp.float32,
                               interpret=jax.default_backend() != "tpu")
    if why:
        return Declined(why)
    ks = jax.random.split(jax.random.PRNGKey(120), 3)
    blocks = s * nb_row + 1
    pool = (0.5 * jax.random.normal(ks[0], (blocks, bs, width))) \
        .at[..., rank + w.mla_rope:].set(0.0)
    q = 0.5 * jax.random.normal(ks[1], (s, kk, h, width)) * width ** -0.5
    tables = jax.random.permutation(ks[2], jnp.arange(1, blocks)) \
        .reshape(s, nb_row).astype(jnp.int32)
    span = nb_row * bs
    pos = jnp.asarray([span - 2, span // 2 - kk // 2, bs + 1, 0][:s]
                      + [0] * max(0, s - 4), jnp.int32)
    lens = jnp.asarray([1, kk, min(kk, bs // 2), max(1, kk - 1)][:s]
                       + [1] * max(0, s - 4), jnp.int32)
    lane = jnp.arange(kk)[None, :]
    qpos = pos[:, None] + jnp.minimum(lane, lens[:, None] - 1)
    live = (lane < lens[:, None])[:, :, None, None]

    def fn(q, pool):
        return jnp.where(live, kernel.mla_attend(q, pool, qpos, tables,
                                                 rank=rank), 0.0)

    def oracle(q, pool):
        lat = pool[tables].reshape(s, span, width)
        scores = jnp.einsum("skhc,stc->skht", q, lat)
        seen = jnp.arange(span)[None, None, :] <= qpos[:, :, None]
        probs = jax.nn.softmax(
            jnp.where(seen[:, :, None, :], scores, -jnp.inf), axis=-1)
        return jnp.where(live, jnp.einsum("skht,str->skhr", probs,
                                          lat[..., :rank]), 0.0)

    return Case(fn=fn, oracle=oracle, args=(q, pool), err=_max_err,
                facts={"pool_bytes": int(pool.size) * 4})


_WHY_WINDOW = ("the window kernel runs on bfloat16 rings and queries as "
               "served and returns bfloat16 (u = 2^-9) however it runs: "
               "2*u*max|v| ~ 1e-2 on a row attending one position, as a "
               "compiled decode case; an 8-bit-float pass is 16x off and "
               "fails")


def _window_case(w):
    """The window kernel over per-slot rings (decode_attention.
    decode_attention_window_chunk) against ``hybrid_lm``'s XLA path over
    the same rings, bfloat16 as served: row 0 decodes deep in its context
    (one lane), row 1 fills every lane with its window starting inside a
    tile, so the tiles before it are skipped, row 2 starts at position 0,
    row 3 ends inside a block with its window reaching back to 0.  LIVE
    lanes are compared, as in the decode cases: a one-lane row's other
    lanes are the zeros of the kernel's one-lane path."""
    from paddle_tpu.models import hybrid_lm
    from paddle_tpu.ops.pallas import decode_attention as kernel
    s, kk, h, hkv = w.window_slots, w.window_chunk, w.window_heads, \
        w.window_kv_heads
    dh, win, bs, entries = w.head_dim, w.window, w.window_block, \
        w.window_entries
    ring = -(-(win + kk - 1) // bs) * bs
    ks = jax.random.split(jax.random.PRNGKey(140), 3)
    rings = [(0.5 * jax.random.normal(k, (s, ring, hkv * dh)))
             .astype(jnp.bfloat16) for k in ks[:2]]
    q = (0.5 * jax.random.normal(ks[2], (s, kk, h * dh))).astype(jnp.bfloat16)
    span = entries * bs
    pos = [span - 2, 2 * win + 3 * bs + 7, 0, bs + 1][:s] + [0] * max(0, s - 4)
    lens = [1, kk, kk, max(1, kk // 2 + 3)][:s] + [1] * max(0, s - 4)
    lane = np.arange(kk)[None, :]
    qpos = jnp.asarray(np.asarray(pos)[:, None]
                       + np.minimum(lane, np.asarray(lens)[:, None] - 1),
                       jnp.int32)
    live = jnp.asarray(lane < np.asarray(lens)[:, None])

    def fn(q, k_ring, v_ring):
        return kernel.decode_attention_window_chunk(
            q, k_ring, v_ring, qpos, h, win, block=bs, entries=entries)

    def oracle(q, k_ring, v_ring):
        f32 = lambda x: x.astype(jnp.float32)
        return hybrid_lm._ring_attention(
            f32(q), f32(k_ring), f32(v_ring), qpos, hkv, dh, win) \
            .reshape(s, kk, h * dh)

    return Case(fn=fn, oracle=oracle, args=(q, *rings),
                err=lambda got, want: _max_err(got[live], want[live]),
                facts={"ring_positions": ring, "window": win,
                       "ring_bytes": 2 * int(rings[0].size) * 2},
                tol=(_TOL_COMPILED, _WHY_WINDOW))


def _decode(paged, chunk, quant, seed):
    return lambda w: _decode_case(w, paged=paged, chunk=chunk, quant=quant,
                                  seed=seed)


CASES = {
    "lstm_fused": lambda w: _rnn_case("lstm", w),
    "lstm_fused_cell_batch": lambda w: _rnn_case(
        "lstm", w, batch=w.lstm_cell_batch),
    "lstm_fused_projected": lambda w: _rnn_case(
        "lstm", w, batch=w.lstm_cell_batch, proj_in=w.rnn_hidden // 4),
    "lstm_fused_tiled": lambda w: _rnn_case(
        "lstm", w, batch=w.lstm_tiled_batch, length=w.lstm_tiled_len,
        hidden=w.lstm_tiled_hidden),
    "lstm_blocked": _lstm_blocked_case,
    "gru_fused": lambda w: _rnn_case("gru", w),
    "simple_rnn_fused": lambda w: _rnn_case("simple_rnn", w),
    "flash_attention": lambda w: _flash_case(False, w),
    "flash_attention_causal": lambda w: _flash_case(True, w),
    "flash_attention_int8": _flash_int8_case,
    "decode_attention_slab": _decode(False, False, False, 20),
    "decode_attention_paged": _decode(True, False, False, 30),
    "decode_attention_slab_chunk": _decode(False, True, False, 40),
    "decode_attention_paged_chunk": _decode(True, True, False, 50),
    "decode_attention_slab_int8": _decode(False, False, True, 60),
    "decode_attention_paged_int8": _decode(True, False, True, 70),
    "decode_attention_slab_chunk_int8": _decode(False, True, True, 80),
    "decode_attention_paged_chunk_int8": _decode(True, True, True, 90),
    "decode_attention_paged_chunk_cell": lambda w: _decode_case(
        _cell(w), paged=True, chunk=True, quant=False, seed=100,
        contexts=w.cell_contexts),
    "kda_chunk": _kda_case,
    "mla_chunk": _mla_case,
    "mamba_chunk": _mamba_case,
    "decode_attention_window_chunk": _window_case,
}


def run_all(widths=SMALL, expect_compiled=False):
    """Every case through ``run_case``; one broken kernel must not hide the
    verdict on the others, so a failure becomes ``{"ok": False, "error"}``
    in its row.  Returns ``(all_ok, {name: row})``; every row carries its
    wall ``secs``."""
    import time
    results = {}
    for name in CASES:
        t0 = time.perf_counter()
        try:
            row = run_case(name, widths, expect_compiled)
        except Exception as e:    # noqa: BLE001 — the row IS the report
            row = {"ok": False, "error": f"{type(e).__name__}: {e}"[:600]}
        row["secs"] = round(time.perf_counter() - t0, 1)
        results[name] = row
    return all(r["ok"] for r in results.values()), results


def run_case(name, widths=SMALL, expect_compiled=False):
    """Build, run and judge one case.  Returns a JSON-able dict:
    ``{"ok", "max_err", "tol", "why", "pallas_calls", "interpreted"}`` or
    ``{"ok": True, "declined": reason}`` when the kernel's guard rejects
    the shape.  The tolerance follows what was OBSERVED: compiled unless
    every pallas_call was interpreted.  Raises AssertionError when the
    error exceeds it, when no ``pallas_call`` was traced (a silent
    reference path), or — under ``expect_compiled`` — when any was
    interpreted."""
    case = CASES[name](widths)
    if isinstance(case, Declined):
        return {"ok": True, "declined": case.reason}
    with record_pallas_calls() as seen:
        got = jax.jit(case.fn)(*case.args)
        jax.block_until_ready(got)
    assert seen, f"{name}: no pallas_call traced — a reference path ran"
    if expect_compiled:
        assert not any(seen), \
            f"{name}: {sum(seen)}/{len(seen)} pallas_calls interpreted"
    tol, why = case.tol or (
        (_TOL_INTERPRETED, _WHY_INTERPRETED) if all(seen)
        else (_TOL_COMPILED, _WHY_COMPILED))
    with f32_reference():
        want = jax.jit(case.oracle)(*case.args)
        jax.block_until_ready(want)
    err = case.err(got, want)
    assert err == err and err <= tol, \
        f"{name}: max err {err:.3e} > tol {tol} ({why})"
    return {"ok": True, "max_err": err, "tol": tol, "why": why,
            "pallas_calls": len(seen), "interpreted": sum(seen),
            **case.facts}

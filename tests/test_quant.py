"""Quantized serving (paddle_tpu/quant/; docs/serving.md "Quantized
serving"): the quantize/dequant math pinned bit-exactly, the committed
quality budget pinned against the fp32 twins on seeded trunks, the
quantized engines' internal bit-identity discipline (slab == paged ==
chunked == the quantized lm_generate oracle, 1 warm-up trace / 0
retraces under admit/CoW churn), the 2x-blocks-at-equal-bytes paged
auto-sizing, and the perf/analytic structural gates in both directions.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.models import transformer
from paddle_tpu.quant import kv as kvq
from paddle_tpu.quant import weights as qw
from paddle_tpu.serving.decode_engine import DecodeEngine, GenerationBatcher
from paddle_tpu.serving.kv_pool import slab_equivalent_blocks
from paddle_tpu.testing import forbid_retrace

V, D, HEADS, LAYERS, MAXLEN = 64, 32, 2, 2, 48


def _trunk(seed=0, **kw):
    return transformer.init(jax.random.PRNGKey(seed), src_vocab=V,
                            trg_vocab=1, d_model=D, num_heads=HEADS,
                            dff=64, enc_layers=LAYERS, dec_layers=0,
                            max_len=MAXLEN, **kw)


def _prompts(seed=0, n=2, lo=3, hi=9):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, V, rng.randint(lo, hi)).astype(np.int32)
            for _ in range(n)]


_prefix = kvq.greedy_prefix_len    # THE budget comparison (one source)


# ------------------------------------------------ quantize/dequant math

def test_kv_identity_scale_roundtrip_bit_exact():
    """scale=1, values in int8 range -> dequant(quantize) BIT-exact:
    the quantize/dequant math itself (round half-to-even, clip,
    convert, multiply) carries no hidden bias."""
    rng = np.random.RandomState(0)
    # per-head amax exactly 127 in every head -> scale exactly 1.0
    x = rng.randint(-126, 127, (4, 6, 2, 16)).astype(np.float32)
    x[..., 0] = 127.0
    x = x.reshape(4, 6, 32)
    q, s = kvq.quantize_heads(jnp.asarray(x), 2)
    np.testing.assert_array_equal(np.asarray(s), np.ones((4, 6, 2)))
    back = np.asarray(kvq.dequantize_heads(q, s))
    np.testing.assert_array_equal(back, x)        # bit-exact


def test_weights_identity_scale_roundtrip_bit_exact():
    rng = np.random.RandomState(1)
    w = rng.randint(-126, 127, (64, 32)).astype(np.float32)
    w[0, :] = 127.0                    # per-column amax -> scale 1.0
    leaf = qw.quantize_leaf(jnp.asarray(w))
    np.testing.assert_array_equal(np.asarray(leaf["s"]),
                                  np.ones((1, 32)))
    np.testing.assert_array_equal(np.asarray(qw.dequantize_leaf(leaf)),
                                  w)


def test_kv_zero_head_roundtrip_and_shapes():
    x = jnp.zeros((3, 5, 32))
    q, s = kvq.quantize_heads(x, 2)
    assert q.dtype == jnp.int8 and q.shape == (3, 5, 32)
    assert s.shape == (3, 5, 2)
    np.testing.assert_array_equal(np.asarray(s), 0.0)   # amax 0 -> 0
    np.testing.assert_array_equal(
        np.asarray(kvq.dequantize_heads(q, s)), 0.0)


def test_quantize_lm_structure():
    params = _trunk()
    qp = qw.quantize_lm(params)
    assert qw.is_quantized_tree(qp) and not qw.is_quantized_tree(params)
    # the positional table is NOT a matmul weight: it stays f32
    assert not qw.is_quantized_leaf(qp["pos"])
    assert qw.weight_shape(qp["src_emb"]) == (V, D)
    shapes = qw.quantized_weight_shapes(qp)
    assert (V, D) in shapes and (D, D) in shapes
    # int8 data + f32 scales shrink the resident bytes close to 4x
    assert qw.param_bytes(qp) < 0.4 * qw.param_bytes(params)
    # maybe_dequant: identity object on a float tree, float on quantized
    assert qw.maybe_dequant(params) is params
    deq = qw.maybe_dequant(qp)
    assert deq["src_emb"].dtype == jnp.float32
    # dequant error bounded by half a quantization step per channel
    err = np.abs(np.asarray(deq["src_emb"])
                 - np.asarray(params["src_emb"]))
    step = np.asarray(qp["src_emb"]["s"])
    assert (err <= 0.5 * step + 1e-7).all()


@pytest.mark.slow
def test_export_leaf_format_interop():
    """``export.quantize_params``' ``{'__int8__','__scale__'}`` leaves
    (the artifact int8 format — same per-out-channel symmetric scheme)
    are recognized by every quant helper, so an exported int8 tree
    feeds the LM paths and the serving engine directly."""
    from paddle_tpu.export import quantize_params
    params = _trunk()
    qp, _dq = quantize_params(params)
    assert qw.is_quantized_tree(qp)
    assert qw.weight_shape(qp["src_emb"]) == (V, D)
    assert qw.param_bytes(qp) < qw.param_bytes(params)
    deq = qw.maybe_dequant(qp)
    assert deq["src_emb"].dtype == jnp.float32
    ids = transformer.lm_generate(qp, np.asarray([[3, 5, 7]], np.int32),
                                  12, HEADS, kv_dtype="int8")
    assert np.asarray(ids).shape == (1, 12)


# -------------------------------------------- prefill/step composition

@pytest.mark.slow
def test_quantized_prefill_equals_sequential_steps():
    """The quantized batched prefill attends over the SAME quantize ->
    dequantize round trip the incremental step applies, so the cached
    int8 values AND sidecar scales are bit-identical between the two
    ingestion orders — the property recovery/CoW/continuation replay
    rides."""
    params = _trunk()
    prompt = _prompts(2, n=1, lo=6, hi=7)[0][None]
    _h, cache = transformer.lm_prefill(params, prompt, MAXLEN, HEADS,
                                       kv_dtype="int8")
    cache2 = transformer.init_lm_cache(params, 1, MAXLEN,
                                       kv_dtype="int8", num_heads=HEADS)
    for t in range(prompt.shape[1]):
        _l, cache2 = transformer.lm_decode_step(params, prompt[:, t], t,
                                                cache2, HEADS)
    tp = prompt.shape[1]
    for key in ("k", "v", "ks", "vs"):
        np.testing.assert_array_equal(
            np.asarray(cache[0][key])[:, :tp],
            np.asarray(cache2[0][key])[:, :tp])


# ------------------------------------------------------ quality budget

@pytest.mark.parametrize("seed", [0, 1])
def test_quality_budget_greedy_prefix_and_logits(seed):
    """The COMMITTED quality budget on the pinned trunks: int8-KV
    greedy streams match the fp32 twin for >= GREEDY_PREFIX_MIN tokens,
    int8-KV + int8-weight streams for >= GREEDY_PREFIX_MIN_FULL, and
    the max |logit error| of a quantized prefill stays under
    LOGIT_ERR_BUDGET."""
    params = _trunk(seed)
    qp = qw.quantize_lm(params)
    n_tok = 2 * kvq.GREEDY_PREFIX_MIN
    for prompt in _prompts(seed, n=1):
        ml = prompt.size + n_tok
        ref = np.asarray(transformer.lm_generate(
            params, prompt[None], ml, HEADS))[0, prompt.size:]
        i8 = np.asarray(transformer.lm_generate(
            params, prompt[None], ml, HEADS,
            kv_dtype="int8"))[0, prompt.size:]
        full = np.asarray(transformer.lm_generate(
            qp, prompt[None], ml, HEADS,
            kv_dtype="int8"))[0, prompt.size:]
        assert _prefix(i8, ref) >= kvq.GREEDY_PREFIX_MIN
        assert _prefix(full, ref) >= kvq.GREEDY_PREFIX_MIN_FULL
        h32, _ = transformer.lm_prefill(params, prompt[None], MAXLEN,
                                        HEADS)
        l32 = transformer._lm_project(params, h32)
        for p, kvd in ((params, "int8"), (qp, "int8")):
            h, _ = transformer.lm_prefill(p, prompt[None], MAXLEN,
                                          HEADS, kv_dtype=kvd)
            lq = transformer._lm_project(p, h)
            err = float(kvq.logit_err(l32, lq).max())
            assert err <= kvq.LOGIT_ERR_BUDGET, err


# --------------------------------------------------- quantized engines

def _drive(engine, prompts, n_tok=10):
    bat = GenerationBatcher(engine, queue_size=64)
    futs = [bat.submit(p, max_tokens=n_tok) for p in prompts]
    outs = [f.result(120)["tokens"] for f in futs]
    bat.close()
    return outs


@pytest.mark.parametrize("layout,chunk", [("slab", 4), ("paged", 4)])
@pytest.mark.slow
def test_int8_engine_matches_quantized_oracle(layout, chunk):
    """Inside the int8 mode greedy decode stays fully deterministic:
    every engine layout reproduces the quantized ``lm_generate`` oracle
    token for token — the engine/oracle bit-identity discipline carries
    over to quantized serving unchanged (weights quantized too: the
    full-quant stack)."""
    params = qw.quantize_lm(_trunk())
    n_tok = 8
    eng = DecodeEngine(params, num_heads=HEADS, num_slots=4,
                       max_len=MAXLEN,
                       kv_layout=layout, kv_block_size=8,
                       kv_dtype="int8", prefill_chunk=chunk,
                       name=f"q_{layout}{chunk}")
    prompts = _prompts(3, n=4)
    with forbid_retrace(eng, what="int8 engine churn"):
        outs = _drive(eng, prompts, n_tok)
    for p, got in zip(prompts, outs):
        ids = np.asarray(transformer.lm_generate(
            params, p[None], p.size + n_tok, HEADS, kv_dtype="int8"))
        assert got == [int(t) for t in ids[0, p.size:]]


def test_int8_paged_churn_prefix_cow_no_retrace():
    """Admit/CoW/prefix-hit churn on the int8 paged engine: shared
    system-prompt clients must prefix-hit and copy-on-write fork int8
    blocks, streams identical to the int8 slab twin, and the step/
    write/fork executables trace exactly once at warm-up and never
    again."""
    params = _trunk()
    rng = np.random.RandomState(7)
    sys_prompt = rng.randint(1, V, 12).astype(np.int32)
    prompts = [np.concatenate([sys_prompt,
                               rng.randint(1, V, 3).astype(np.int32)])
               for _ in range(4)]
    prompts[1] = prompts[0].copy()          # exact duplicate: CoW fork
    # the churn exercises prefix-hit seating + span growth + CoW on
    # the ONE int8 step
    paged = DecodeEngine(params, num_heads=HEADS, num_slots=4,
                         max_len=MAXLEN,
                         kv_layout="paged", kv_block_size=8,
                         kv_dtype="int8", prefill_chunk=4,
                         name="q_churn")
    slab = DecodeEngine(params, num_heads=HEADS, num_slots=4,
                        max_len=MAXLEN,
                        kv_dtype="int8", prefill_chunk=4,
                        name="q_churn_slab")
    # leader first (registers the prefix chains), then the churners —
    # step/write/fork executables must all stay warm through the churn
    with forbid_retrace(paged, lambda: paged._write_traces[0],
                        lambda: paged._copy_traces[0],
                        what="int8 paged prefix/CoW churn"):
        outs = _drive(paged, prompts[:1]) + _drive(paged, prompts[1:])
    ref = _drive(slab, prompts)
    assert outs == ref
    snap = paged.metrics.snapshot()
    assert snap["prefix_cache_hits_total"] >= 2
    assert snap["cow_forks_total"] >= 1
    assert snap["kv_dtype"] == "int8"
    paged._paged.check()                    # full ledger audit


def test_int8_paged_auto_doubles_blocks_at_equal_bytes():
    params = _trunk()
    f32 = DecodeEngine(params, num_heads=HEADS, num_slots=4,
                       max_len=MAXLEN,
                       kv_layout="paged", kv_block_size=8, warm=False)
    i8 = DecodeEngine(params, num_heads=HEADS, num_slots=4,
                      max_len=MAXLEN,
                      kv_layout="paged", kv_block_size=8,
                      kv_dtype="int8", warm=False)
    assert i8._paged.pool.num_allocatable \
        == 2 * f32._paged.pool.num_allocatable
    # the doubled int8 pool + sidecars really fits the f32 byte budget
    def pool_bytes(eng):
        return sum(int(np.prod(l.shape)) * l.dtype.itemsize
                   for c in eng._cache for l in c.values())
    assert pool_bytes(i8) <= pool_bytes(f32)
    assert slab_equivalent_blocks(4, MAXLEN, 8, "int8") \
        == 2 * (slab_equivalent_blocks(4, MAXLEN, 8) - 1) + 1


def test_kv_dtype_validation():
    from paddle_tpu.utils.error import ConfigError
    with pytest.raises(ConfigError):
        DecodeEngine(_trunk(), num_heads=HEADS, kv_dtype="fp8",
                     warm=False)
    with pytest.raises(ValueError):
        transformer.init_lm_cache(_trunk(), 2, 16, kv_dtype="fp8")


@pytest.mark.slow
def test_recovery_replay_bit_identical_int8():
    """PR-6 supervised recovery on the int8 engine: an injected step
    fault rebuilds the slab and re-seats every stream (its context
    re-quantized on the way back in through the step) — recovered
    streams stay identical to the unfaulted int8 twin."""
    from paddle_tpu.resilience import faults
    from paddle_tpu.resilience.supervisor import Supervisor
    params = _trunk()
    prompts = _prompts(5, n=3)
    clean = DecodeEngine(params, num_heads=HEADS, num_slots=4,
                         max_len=MAXLEN,
                         kv_dtype="int8", name="q_clean")
    want = _drive(clean, prompts, n_tok=12)
    chaos = DecodeEngine(params, num_heads=HEADS, num_slots=4,
                         max_len=MAXLEN,
                         kv_dtype="int8", name="q_chaos")
    faults.install_spec("serving.decode_step:at=4")
    try:
        with forbid_retrace(chaos, what="int8 supervised recovery",
                            hint="the rebuild retraced the int8 step"):
            bat = GenerationBatcher(chaos, queue_size=64,
                                    supervisor=Supervisor())
            futs = [bat.submit(p, max_tokens=12) for p in prompts]
            got = [f.result(120)["tokens"] for f in futs]
            bat.close()
    finally:
        faults.install_spec("")
    assert got == want
    assert chaos.metrics.snapshot()["slot_reprefills_total"] >= 1


# ------------------------------------------------------ analytic gates

def test_analytic_quant_gates_both_directions():
    """assert_weights_quantized and assert_kv_quantized pass on the
    quantized kernel-forced step, and each FIRES on its twin (fp32
    weights / kernels-off reference) — plus the predicted-bytes model
    clears the 35% acceptance bar."""
    from paddle_tpu.ops.pallas import decode_attention as dk
    from paddle_tpu.perf import analytic as pa
    from paddle_tpu.testing.kernel_smoke import build_private_tables

    params = _trunk()
    qp = qw.quantize_lm(params, min_size=512)
    s, bs, nb_row = 4, 8, MAXLEN // 8
    num_blocks = s * nb_row + 1
    rng = np.random.RandomState(0)
    tokens = rng.randint(1, V, s).astype(np.int32)
    pos = rng.randint(1, MAXLEN - 1, s).astype(np.int32)
    tables = build_private_tables(pos, nb_row, bs, num_blocks)
    dkv = qw.weight_shape(params["enc"][0]["attn"]["wk"])[1]

    def staged(p, kv_dtype, mode):
        cache = transformer.init_lm_cache_paged(
            p, num_blocks, bs, max_len=MAXLEN, kv_dtype=kv_dtype,
            num_heads=HEADS)
        with dk.forced_mode(mode):
            def fn(pp, c, tok, po, tbl):      # a one-lane step
                logits, c = transformer.lm_decode_chunk_paged(
                    pp, tok[:, None], po, jnp.ones_like(po), c, tbl,
                    HEADS)
                return jnp.argmax(logits, axis=-1), c
            return jax.jit(fn).lower(p, cache, tokens, pos,
                                     tables).compile().as_text()

    shapes = qw.quantized_weight_shapes(qp)
    floats = qw.float_leaf_shapes(qp)
    assert shapes, "min_size=512 must quantize the test trunk"
    # the test trunk's pos table [MAXLEN, D] = [48, 32] deliberately
    # collides with no weight here, but the allow-list must exist so a
    # colliding trunk (max_len == dff) never false-positives
    t_span = nb_row * bs
    q_on = staged(qp, "int8", "always")
    pa.assert_weights_quantized(q_on, shapes, floats)
    pa.assert_kv_quantized(q_on, s, t_span, dkv)
    with pytest.raises(AssertionError):
        pa.assert_weights_quantized(staged(params, None, "off"), shapes,
                                    floats)
    with pytest.raises(AssertionError):
        pa.assert_kv_quantized(staged(qp, "int8", "off"), s, t_span,
                               dkv)
    b_f32 = pa.predicted_decode_step_bytes(params, s, t_span, HEADS)
    b_i8 = pa.predicted_decode_step_bytes(qp, s, t_span, HEADS, "int8")
    assert 1 - b_i8 / b_f32 >= 0.35


def test_weights_gate_tolerates_shape_collisions():
    """A non-weight f32 leaf whose shape collides with a quantized
    weight's (the positional table [max_len, d] vs FFN w2 [dff, d]
    when max_len == dff) must NOT read as a widened weight copy — the
    count-based gate allows exactly the tree's own float leaves."""
    from paddle_tpu.perf import analytic as pa
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=V,
                              trg_vocab=1, d_model=D, num_heads=HEADS,
                              dff=MAXLEN, enc_layers=1, dec_layers=0,
                              max_len=MAXLEN)
    qp = qw.quantize_lm(params, min_size=512)
    shapes = qw.quantized_weight_shapes(qp)
    assert (MAXLEN, D) in shapes        # w2 collides with pos
    cache = transformer.init_lm_cache(qp, 2, MAXLEN, kv_dtype="int8",
                                      num_heads=HEADS)
    tokens = np.zeros((2,), np.int32)
    pos = np.zeros((2,), np.int32)

    def fn(p, c, tok, po):                    # a one-lane step
        logits, c = transformer.lm_decode_chunk_slots(
            p, tok[:, None], po, jnp.ones_like(po), c, HEADS)
        return jnp.argmax(logits, axis=-1), c

    hlo = jax.jit(fn).lower(qp, cache, tokens,
                            pos).compile().as_text()
    pa.assert_weights_quantized(hlo, shapes, qw.float_leaf_shapes(qp))

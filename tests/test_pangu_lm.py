"""The hybrid trunk's second family (models/hybrid_lm.py as
``pangu_ultra_moe`` builds it: MLA in every layer with a low-rank query and a
rotated key part, sandwich norms, a dense layer and then a held share of
sigmoid-routed experts) at tiny widths on the CPU: the served path against
the plain reference (benchmark/reference/pangu_moe.py), the shares of the
experts against the uncut layer, the ``mla_chunk`` kernel interpreted against
the XLA path, and the engine's count of attended positions.

The tiny widths keep every ratio of the published ones: query rank 24 <
hidden 64, rope 8 < nope 16, both layer kinds, 4 held of 16 routed.

TOL: the program and the reference compute in float32 on the CPU and differ
in the ORDER of their sums only (the absorbed form against expanded keys
and values, grouped products against a loop over experts): a few 1e-6 on
logits of unit size; 2e-4 leaves two orders of room and is a thousandth of
what dropping any part of the block moves."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.drivers import serve_hybrid, serve_pangu  # noqa: E402
from benchmark.reference import pangu_moe as reference  # noqa: E402
from paddle_tpu.models import hybrid_lm  # noqa: E402
from paddle_tpu.models.transformer import _chunk_lanes  # noqa: E402
from paddle_tpu.ops import mla, moe  # noqa: E402
from paddle_tpu.ops.pallas import decode_attention as dk  # noqa: E402
from paddle_tpu.ops.pallas import mla as mla_kernel  # noqa: E402
from paddle_tpu.serving.decode_engine import (DecodeEngine,  # noqa: E402
                                              GenerationBatcher)

TOL = 2e-4
BLOCK = 4


def tiny(**over):
    """benchmark/testdata/configs/tiny-pangu.json with blocks of 4."""
    with open(os.path.join(ROOT, "benchmark", "testdata", "configs",
                           "tiny-pangu.json")) as f:
        hf = json.load(f)
    hf["serving"] = dict(hf["serving"], kv_block_size=BLOCK)
    hf.update(over)
    return hf


@pytest.fixture(scope="module")
def hf():
    return tiny()


@pytest.fixture(scope="module")
def params(hf):
    return serve_pangu.make_params(hf, 17)


def prompts(lengths, seed=0, vocab=128):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, n).tolist() for n in lengths]


def served_error(hf, params, seqs=None, n_decode=3):
    """Chunked prefill of uneven lengths, then decoding, through the paged
    latent pool at the engine's shape; the largest distance of a compared
    logits row from the reference's full forward (handed the program's
    expert choice) and the number of rows compared."""
    seqs, got, routes = serve_hybrid.served_logits(
        params, hf, seqs or prompts([21, 45, 7]), n_decode)
    want, _selects = serve_pangu.reference_forward(params, tiny(), seqs,
                                                   routes)
    return max(float(np.abs(row - want[i, p]).max())
               for i, rows in enumerate(got) for p, row in rows), \
        sum(map(len, got))


def test_config_from_hf_builds_the_second_family(hf):
    cfg = hybrid_lm.config_from_hf(hf)
    assert cfg.layers == (("mla", "dense"), ("mla", "moe"), ("mla", "moe"))
    assert (cfg.q_rank, cfg.rope_theta, cfg.post_norms) == (24, 25.6e6, True)
    assert (cfg.router_width, cfg.held, cfg.top_k) == (16, (4, 4), 4)
    # every leaf of the cache is block-addressed: no slot owns state
    kinds = jax.tree_util.tree_leaves(hybrid_lm.cache_kinds(cfg))
    assert kinds == [hybrid_lm.BLOCK_LEAF] * 3
    # the first family is built as before
    with open(os.path.join(ROOT, "benchmark", "testdata", "configs",
                           "tiny-hybrid.json")) as f:
        kimi = hybrid_lm.config_from_hf(json.load(f))
    assert (kimi.q_rank, kimi.rope_theta, kimi.post_norms) \
        == (None, None, False)
    assert [a for a, _f in kimi.layers] == ["kda", "kda", "kda", "mla"]
    # a published base that the file itself says is unused rotates nothing
    assert hybrid_lm.config_from_hf(
        dict(hf, mla_use_nope=True)).rope_theta is None
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "kimi-linear-48b-ep4-8l.json")) as f:
        published = json.load(f)
    assert (published["rope_theta"], published["mla_use_nope"]) \
        == (10000, True)
    assert hybrid_lm.config_from_hf(published).rope_theta is None


@pytest.mark.parametrize("kk", [1, 5, 8])
def test_served_path_matches_reference(hf, params, kk):
    chunked = dict(hf, serving=dict(hf["serving"], prefill_chunk=kk))
    err, rows = served_error(chunked, params)
    assert rows == 12 and err < TOL


@pytest.mark.parametrize("shares", [4, 16])
def test_shares_add_up_to_the_uncut_layer(shares):
    """The routed parts that all the ranks give, plus the shared expert
    counted once, are the uncut reference layer."""
    whole = tiny(n_routed_experts=16)
    whole.pop("expert_parallel")
    mc = hybrid_lm.config_from_hf(whole)
    layer = serve_pangu.make_params(whole, 23)["layers"][1]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(5), (24, mc.hidden_size))
    idx, w = moe.sigmoid_router(x, layer["router"], layer["router_bias"],
                                mc.top_k, mc.routed_scale)
    count = mc.router_width // shares
    total = moe.gated_ffn(x, *(layer["shared"][k] for k in ("wg", "wu",
                                                            "wd")))
    for rank in range(shares):
        held = {k: layer[k][rank * count:(rank + 1) * count]
                for k in ("wg", "wu", "wd")}
        total = total + moe.routed_experts(x, idx, w, held,
                                           (rank * count, count))
    ref_layer = {"router": layer["router"],
                 "router_bias": layer["router_bias"],
                 "shared": layer["shared"],
                 "experts": {k: layer[k] for k in ("wg", "wu", "wd")}}
    with jax.default_matmul_precision("highest"):
        want, _ = reference.moe(x, ref_layer, whole)
    np.testing.assert_allclose(total, want, atol=1e-4)


def _layer_case(rotation, query_rank, seed=0):
    """One MLA layer's parameters and a step of five rows: a decoding row
    deep in its context, a prefilling row that crosses blocks, a row whose
    chunk ends inside a block, a prefilling row at position 0 and a free
    row (one lane at position 0 on the scratch block)."""
    s, kk, d, heads, nope, rope, v, rank, nb_row, block = 5, 8, 32, 4, 16, \
        8, 16, 32, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    lin = lambda k, i, o: jax.random.normal(k, (i, o)) * i ** -0.5
    p = {"wkva": lin(ks[1], d, rank + rope), "kv_norm": jnp.ones((rank,)),
         "wkvb": lin(ks[2], rank, heads * (nope + v)),
         "wo": lin(ks[3], heads * v, d)}
    if query_rank:
        p.update(wqa=lin(ks[0], d, query_rank),
                 q_norm=jnp.ones((query_rank,)),
                 wqb=lin(ks[4], query_rank, heads * (nope + rope)))
    else:
        p["wq"] = lin(ks[0], d, heads * (nope + rope))
    width = mla.pool_width(rank + rope)
    blocks = s * nb_row + 1
    pool = jax.random.normal(ks[5], (blocks, block, width)) \
        .at[..., rank + rope:].set(0.0)
    tables = np.random.RandomState(seed).permutation(
        np.arange(1, blocks)).reshape(s, nb_row).astype(np.int32)
    tables[4] = 0
    lens = np.asarray([1, 8, 3, 5, 1])
    _li, qpos = _chunk_lanes(jnp.asarray([41, 13, 30, 0, 0]),
                             jnp.asarray(lens), kk)
    # the 18 live lanes packed into 24 places: the last six repeat a lane
    src, back = hybrid_lm.pack_lanes(lens, kk)
    src = jnp.asarray(src[:24])
    h = jax.random.normal(ks[6], (s * kk, d))[src]
    kw = dict(num_heads=heads, nope=nope, rope=rope, v_dim=v, rank=rank,
              eps=1e-5, rope_theta=1e4 if rotation else None)
    return (p, h, pool, qpos, jnp.asarray(tables), src,
            jnp.asarray(back)), kw


@pytest.mark.parametrize("lg", [8, 2], ids=["one_group", "groups_of_2"])
@pytest.mark.parametrize("query_rank", [None, 12], ids=["full_q", "low_rank_q"])
@pytest.mark.parametrize("rotation", [False, True], ids=["plain", "rotated"])
def test_mla_kernel_interpreted_matches_xla(monkeypatch, rotation,
                                            query_rank, lg):
    args, kw = _layer_case(rotation, query_rank)
    layer = lambda: jax.jit(lambda *a: mla.mla_chunk(*a, **kw))(*args)
    want, want_pool = layer()
    monkeypatch.setattr(mla_kernel, "lane_group", lambda kk, heads: lg)
    mla_kernel.mla_attend.clear_cache()
    with dk.forced_mode("always"):
        got, got_pool = layer()
    mla_kernel.mla_attend.clear_cache()
    np.testing.assert_array_equal(got_pool, want_pool)
    np.testing.assert_allclose(got, want, atol=1e-5)
    # every place holds its lane's result, the repeats their lane's too,
    # and the repeats wrote nothing (a poisoned one would have won a place)
    assert got.shape == (24, 32) and float(jnp.abs(got).min()) > 0.0
    np.testing.assert_array_equal(got[18:], jnp.broadcast_to(got[17], (6, 32)))
    poisoned = (args[0], args[1].at[18:].set(7.0)) + args[2:]
    _y, pool = jax.jit(lambda *a: mla.mla_chunk(*a, **kw))(*poisoned)
    np.testing.assert_array_equal(pool, want_pool)


def test_mla_kernel_guard_names_its_reason():
    bf16 = jnp.bfloat16
    assert "pallas_decode" in mla_kernel.decline_reason(64, 128, 640, 512,
                                                        16, bf16)
    assert mla_kernel.shape_problem(64, 128, 640, 512, 16, bf16) is None \
        or "VMEM" in mla_kernel.shape_problem(64, 128, 640, 512, 16, bf16)
    assert "lane tiles" in mla_kernel.shape_problem(64, 128, 576, 512, 16,
                                                    bf16)
    assert "blocks of 8" in mla_kernel.shape_problem(64, 128, 640, 512, 8,
                                                     bf16)
    assert "heads" in mla_kernel.shape_problem(16, 12, 640, 512, 16, bf16)
    assert mla_kernel.lane_group(64, 128) == 8
    assert mla_kernel.lane_group(16, 32) == 16


def _without_query_norm(monkeypatch, hf):
    q_rank = hf["q_lora_rank"]
    norm = mla.rms_norm
    monkeypatch.setattr(mla, "rms_norm", lambda x, g, eps: x
                        if g.shape[-1] == q_rank else norm(x, g, eps))
    return hf


@pytest.mark.parametrize("what", ["post_norm", "rotation", "query_norm"])
def test_dropping_a_part_moves_the_logits(monkeypatch, hf, params, what):
    """A program that leaves out the norms after the sublayers, the
    rotation or the query's norm is far outside the tolerance."""
    broken = {"post_norm": lambda: dict(hf, sandwich_norm=False),
              "rotation": lambda: dict(hf, rope_theta=None),
              "query_norm": lambda: _without_query_norm(monkeypatch, hf)}
    err, _rows = served_error(broken[what](), params)
    assert err > 1000 * TOL


@pytest.fixture(scope="module")
def served(hf, params):
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(hf))
    return DecodeEngine(
        params, model=model, num_slots=2, max_len=96, kv_layout="paged",
        kv_block_size=BLOCK, prefix_cache=False, prefill_chunk=8, name="pg")


def test_engine_serves_the_second_family(hf, params, served):
    """Through DecodeEngine -> GenerationBatcher with more requests than
    slots and a step in flight: every stream is the reference's greedy
    continuation, the step traced once, and the attended positions are
    what the requests' lengths give."""
    engine = served
    assert engine.mla_kernels is False and engine.kda_kernels is False
    assert "pallas_decode" in engine.mla_decline_reason
    assert engine.kda_decline_reason is None      # no KDA layer to decline
    reqs = prompts([21, 5, 30, 11], seed=2)
    outs_n = [4, 6, 3, 5]
    with GenerationBatcher(engine, default_max_tokens=4) as gen:
        outs = [f.result(120) for f in
                [gen.submit(p, max_tokens=n) for p, n in zip(reqs, outs_n)]]
    ref_params = serve_pangu.reference_params(params)
    for prompt, out, n in zip(reqs, outs, outs_n):
        assert len(out["tokens"]) == n
        seq = list(prompt)
        for tok in out["tokens"]:
            want, _ = reference.logits(ref_params,
                                       jnp.asarray([seq], jnp.int32), hf)
            row = np.asarray(want)[0, -1]
            assert row.max() - row[tok] < TOL
            seq.append(tok)
    m = engine.metrics
    assert engine.step_trace_count == 1
    assert m.decode_steps_overlapped_total > 0.5 * m.decode_steps_total
    # a request of P prompt tokens and O streamed ones feeds positions
    # 0 .. P + O - 2, and the lane at position q attends q + 1
    want = sum((len(p) + n - 1) * (len(p) + n) // 2
               for p, n in zip(reqs, outs_n))
    assert m.attended_positions_total == want
    snap = m.snapshot()
    assert snap["attended_positions_total"] == want
    assert snap["mla_kernels"] == 0 and snap["kda_kernels"] == 0
    assert m.recurrent_state_bytes == 0 and m.latent_pool_bytes > 0
    assert m.state_resets_total == 0            # no slot owns state
    text = m.render_prometheus()
    for name in ("attended_positions_total", "mla_kernels", "kda_kernels"):
        assert name in text

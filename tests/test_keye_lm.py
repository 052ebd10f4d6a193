"""The hybrid trunk's fifth family (models/hybrid_lm.py as ``keye`` builds
it: in every layer a lightning indexer whose keys have their own paged leaf,
an exact top-k of its scores a query lane, GQA softmax attention with
per-head q/k norms over the positions kept, a softmax router over a held
share of the experts and no shared expert) at tiny widths on the CPU: the
served path, prefilled in chunks and then decoding through its cache,
against the plain reference (benchmark/reference/keye.py) at contexts where
the selection drops positions; the selection against the reference's top-k
and the identity under k positions; the three kernels interpreted against
the XLA path; the held experts' shares against the uncut layer; and the
step programs of the four other families, unchanged.

TOL: the program and the reference compute in float32 on the CPU and differ
in the ORDER of their sums only: a few 1e-6 on logits of size 5, and a
selection that is the same set (the indexer's scores differ by rounding,
and a lane's 16th and 17th largest lie far further apart at these widths);
2e-4 leaves an order of room and is a thousandth of what attending every
position or dropping the q/k norms moves."""

import hashlib
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.drivers import serve_hybrid, serve_keye  # noqa: E402
from benchmark.reference import keye as reference  # noqa: E402
from paddle_tpu.models import hybrid_lm  # noqa: E402
from paddle_tpu.ops import dsa, moe  # noqa: E402
from paddle_tpu.ops.pallas import decode_attention as dk  # noqa: E402
from paddle_tpu.ops.pallas import dsa as dsa_kernels  # noqa: E402
from paddle_tpu.serving.decode_engine import (DecodeEngine,  # noqa: E402
                                              GenerationBatcher)

TOL = 2e-4


def tiny(**over):
    """benchmark/testdata/configs/tiny-keye.json: topk 16, chunk 8, blocks
    of 8, 256 positions a row."""
    with open(os.path.join(ROOT, "benchmark", "testdata", "configs",
                           "tiny-keye.json")) as f:
        hf = json.load(f)
    hf.update(over)
    return hf


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def hf():
    return tiny()


@pytest.fixture(scope="module")
def params(hf):
    return serve_keye.make_params(hf, 41)


def prompts(lengths, seed=0, vocab=128):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, n).tolist() for n in lengths]


def served_logits(params, hf, seqs, n_decode, kk=None):
    """The trunk's own step, ``Served.decode_chunk``, through its own cache:
    chunked prefill ``kk`` lanes at a time, then ``n_decode`` greedy decode
    steps, at the engine's shape (``serving.slots`` rows, those past
    ``seqs`` idling at position 0 as free slots do) and packed as the
    engine packs.  -> (sequences with the greedy tokens appended, per-row
    list of [position, logits row], per-row {position: chosen experts},
    per-row {position: the positions it took, [layers, T] bool})."""
    s = hf["serving"]
    bs, kk = s["kv_block_size"], kk or s["prefill_chunk"]
    live, n = len(seqs), max(len(seqs), s["slots"])
    nb_row = s["max_len"] // bs
    tables = jnp.asarray(np.arange(1, n * nb_row + 1, dtype=np.int32)
                         .reshape(n, nb_row))
    model = serve_hybrid.served_model(hf)
    cache = model.init_cache(n, n * nb_row + 1, bs, chunk=kk)
    jstep = jax.jit(lambda p, cache, *feed: model.decode_chunk(
        p, *feed[:3], cache, tables, *feed[3:]))
    seqs = [list(p) for p in seqs]
    cursor = [0] * live
    got = [[] for _ in range(live)]
    routes = [{} for _ in range(live)]
    picks = [{} for _ in range(live)]
    while any(len(g) <= n_decode for g in got):
        chunk = np.zeros((n, kk), np.int32)
        pos, lens = np.zeros(n, np.int32), np.ones(n, np.int32)
        for i in range(live):
            if len(got[i]) > n_decode:      # a finished row idles
                chunk[i, 0], pos[i] = seqs[i][-1], cursor[i]
                continue
            piece = seqs[i][cursor[i]:cursor[i] + kk]
            chunk[i, :len(piece)], pos[i], lens[i] = piece, cursor[i], \
                len(piece)
        logits, cache, (chosen, bits) = jstep(
            params, cache, chunk, pos, lens, *model.pack(lens, kk))
        logits, chosen = np.asarray(logits), np.asarray(chosen)
        picked = dsa.unpack(np.stack(bits), s["max_len"])
        for i in range(live):
            if len(got[i]) > n_decode:
                continue
            for j in range(int(lens[i])):
                routes[i][cursor[i] + j] = chosen[:, i, j]
                picks[i][cursor[i] + j] = picked[:, i, j]
            cursor[i] += int(lens[i])
            if cursor[i] == len(seqs[i]):
                got[i].append([cursor[i] - 1, logits[i]])
                seqs[i].append(int(logits[i].argmax()))
    return seqs, got, routes, picks


def reference_run(hf, params, served, hand=True):
    """The reference's full forward over the served sequences, handed the
    program's expert choice and (with ``hand``) its selection -> (its
    logits [B, T, V], its index facts per layer)."""
    seqs, _got, routes, picks = served
    layers, k = hf["num_hidden_layers"], hf["num_experts_per_tok"]
    ids = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    t = ids.shape[1]
    chosen = np.tile(np.arange(k, dtype=np.int32), ids.shape + (layers, 1))
    taken = np.tile(np.tri(t, dtype=bool), (len(seqs), layers, 1, 1))
    for i, seq in enumerate(seqs):
        ids[i, :len(seq)] = seq
        for p, c in routes[i].items():
            chosen[i, p] = c
            taken[i, :, p] = picks[i][p][:, :t]
    handed = [jnp.asarray(np.packbits(taken[:, l], -1, bitorder="little"))
              for l in range(layers)]
    want, _z, facts = reference.forward(
        serve_keye.reference_params(params, hf), jnp.asarray(ids), hf,
        routes=[jnp.asarray(chosen[:, :, l]) for l in range(layers)],
        selections=handed if hand else None)
    return np.asarray(want), facts


def served_error(hf, params, served, hand=True):
    want, _facts = reference_run(hf, params, served, hand)
    _seqs, got, _routes, _picks = served
    return max(float(np.abs(row - want[i, p]).max())
               for i, rows in enumerate(got) for p, row in rows), \
        sum(map(len, got))


# ------------------------------------------------------------ the family

def test_config_from_hf_builds_the_published_model():
    cfg = _config("keye-vl-2.0-30b-ep8-4l")
    full = dict(cfg, num_hidden_layers=48, num_experts=128,
                expert_parallel=None, vocab_size=151936)
    mc = hybrid_lm.config_from_hf(full)
    assert mc.layers == (("sparse", "moe"),) * 48
    assert (mc.attn_heads, mc.attn_kv_heads, mc.attn_head_dim) \
        == (32, 4, 128)
    assert (mc.index_heads, mc.index_dim, mc.index_topk) == (16, 64, 2048)
    assert mc.router == "softmax" and mc.shared_experts == 0
    assert (mc.top_k, mc.routed_scale, mc.router_width, mc.held,
            mc.expert_width) == (8, 1.0, 128, (0, 128), 768)
    assert mc.attn_rope[0] == 128 and mc.index_rope[0] == 32
    assert mc.attn_rope[1][1] == pytest.approx(1e7 ** (-2 / 128), rel=1e-6)
    cut = hybrid_lm.config_from_hf(cfg)
    assert len(cut.layers) == 4 and cut.held == (0, 16)
    assert cut.router_width == 128 and cut.vocab_size == 18992
    with pytest.raises(NotImplementedError, match="mrope"):
        hybrid_lm.config_from_hf(dict(cfg, rope_scaling={
            "mrope_section": [16, 24, 16]}))


def test_held_experts_add_up_to_the_uncut_layer(hf):
    """Four holders of four experts each: the parts their held experts give
    (``routed_experts`` after ``softmax_router``, no scale, no shared
    expert) add up to the uncut reference's layer."""
    d, e, f, k = 64, 16, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 5)
    x = jax.random.normal(ks[0], (24, d))
    w = jax.random.normal(ks[1], (d, e)) * d ** -0.5
    experts = {n: jax.random.normal(kk, shape) * shape[1] ** -0.5
               for n, kk, shape in (("wg", ks[2], (e, d, f)),
                                    ("wu", ks[3], (e, d, f)),
                                    ("wd", ks[4], (e, f, d)))}
    idx, weights = moe.softmax_router(x, w, k, 1.0)
    got = sum(moe.routed_experts(
        x, idx, weights, {n: v[4 * r:4 * r + 4] for n, v in experts.items()},
        (4 * r, 4)) for r in range(4))
    cfg = dict(hf, num_experts=e, num_experts_per_tok=k, expert_parallel=None)
    with jax.default_matmul_precision("highest"):
        want, z = reference.moe(x, {"router": w, "experts": experts}, cfg)
    np.testing.assert_allclose(got, want, atol=1e-4)
    np.testing.assert_allclose(z, x @ w, rtol=1e-5, atol=1e-6)


def test_no_shared_expert_has_no_parameters(hf, params):
    assert all("shared" not in lp["ffn"] for lp in params["layers"])
    attn = params["layers"][0]["attn"]
    assert attn["q_norm"].shape == attn["k_norm"].shape == (16,)
    assert attn["wq_index"].shape == (64, 8 * 16)
    assert attn["wk_index"].shape == (64, 16)
    assert attn["w_index"].shape == (64, 8)


# ------------------------------------------------------- the served path

@pytest.mark.parametrize("kk", [3, 8])
def test_served_path_matches_reference(hf, params, kk):
    """Prompts of 45, 120 and 9 prefilled in chunks of ``kk`` (the
    indexer keeps 16 positions: a lane past position 15 drops some), then
    three decode steps through the cache."""
    served = served_logits(params, hf, prompts([45, 120, 9]), 3, kk=kk)
    for hand in (True, False):
        err, rows = served_error(hf, params, served, hand)
        assert rows == 12 and err < TOL


def test_selection_is_the_references_top_k(hf, params):
    """Every fed lane of every layer takes min(16, t + 1) positions, none
    past t, and the reference, handed them, scores every position taken at
    least as high as every position left out: the program's selection is
    the reference's top-k where no tie is near."""
    served = served_logits(params, hf, prompts([70, 40], seed=3), 2)
    _want, facts = reference_run(hf, params, served)
    topk = hf["sa_config"]["topk"]
    for i, picks in enumerate(served[3]):
        for p, taken in picks.items():
            assert (taken.sum(-1) == min(topk, p + 1)).all(), p
            assert not taken[:, p + 1:].any()
            for l, f in enumerate(facts):
                gap = float(f["best_out"][i, p] - f["worst"][i, p])
                assert gap <= 1e-5 * float(f["std"][i, p]) + 1e-6, (i, p, l)
                if p + 1 > topk:
                    assert np.isfinite(gap)


def test_selection_is_the_identity_under_k_positions():
    """Under k positions every position at or before the lane is taken,
    whatever the scores, ties and all; past them exactly k, ties to the
    lower position."""
    s, kk, t, topk = 2, 8, 64, 16
    scores = jnp.asarray(np.random.RandomState(0).randint(
        0, 4, (s, kk, t)).astype(np.float32))
    qpos = jnp.asarray([[0, 1, 2, 3, 4, 5, 6, 7],
                        [20, 21, 22, 40, 41, 50, 60, 63]], jnp.int32)
    picks, bits = dsa.select(scores, qpos, topk, False)
    taken = dsa.mask(scores, picks, qpos)
    want = np.minimum(np.asarray(qpos) + 1, topk)
    np.testing.assert_array_equal(np.asarray(taken.sum(-1)), want)
    np.testing.assert_array_equal(np.asarray(picks[..., 2]), want)
    np.testing.assert_array_equal(np.asarray(dsa.unpack(bits, t)),
                                  np.asarray(taken))
    assert bool((taken[0] == (jnp.arange(t)[None] <= qpos[0][:, None])).all())
    # past k: the 16 largest, ties to the lower position (a stable sort)
    for i in range(kk):
        q = int(qpos[1, i])
        row = np.asarray(scores[1, i, :q + 1])
        order = np.argsort(-row, kind="stable")[:topk]
        np.testing.assert_array_equal(
            np.flatnonzero(np.asarray(taken[1, i])), np.sort(order))


@pytest.mark.parametrize("seed", [0, 1])
def test_select_kernel_matches_the_xla_path(seed):
    """``sparse_select`` interpreted against ``dsa.select`` on scores with
    many ties (small integers) and without (normals), rows that feed one
    lane and rows that feed eight."""
    rng = np.random.RandomState(seed)
    s, kk, t, topk = 3, 8, 256, 16
    for scores in (rng.randint(0, 6, (s, kk, t)).astype(np.float32),
                   rng.normal(size=(s, kk, t)).astype(np.float32)):
        scores = jnp.asarray(scores)
        qpos = jnp.asarray([[100] * kk, list(range(3, 11)),
                            list(range(190, 198))], jnp.int32)
        want, want_bits = dsa.select(scores, qpos, topk, False)
        got, bits = dsa_kernels.sparse_select(scores, qpos, topk,
                                              interpret=True)
        np.testing.assert_array_equal(np.asarray(got[0, 0, :3]),
                                      np.asarray(want[0, 0, :3]))
        np.testing.assert_array_equal(np.asarray(got[1:, :, :3]),
                                      np.asarray(want[1:, :, :3]))
        np.testing.assert_array_equal(np.asarray(bits[0, 0]),
                                      np.asarray(want_bits[0, 0]))
        np.testing.assert_array_equal(np.asarray(bits[1:]),
                                      np.asarray(want_bits[1:]))


def test_served_path_with_the_kernels_interpreted(hf, params):
    """The indexer, the selection and the sparse attention kernels, in
    interpret mode, through the same step."""
    with dk.forced_mode("always"):
        model = serve_hybrid.served_model(hf)
        report = model.kernel_report(8, 8, 4, entries=32)
        assert report["sparse_kernels"], report["sparse_decline_reason"]
        served = served_logits(params, hf, prompts([45, 120, 9]), 2)
    err, rows = served_error(hf, params, served)
    assert rows == 9 and err < TOL


@pytest.mark.parametrize("what", ["dense", "noqknorm"])
def test_each_wrong_program_moves_the_logits(hf, params, what):
    """A program that attends every position, or leaves q and k un-normed,
    is far outside the tolerance of the reference making its own
    selection."""
    from unittest import mock
    if what == "dense":
        select = dsa.select
        patch = mock.patch.object(dsa, "select", lambda sc, q, k, u: select(
            sc, q, 2 ** 30, u))
    else:
        patch = mock.patch.object(hybrid_lm, "head_norm",
                                  lambda x, gain, heads, head_dim, eps: x)
    with patch:
        served = served_logits(params, hf, prompts([45, 120, 9]), 2)
    err, _rows = served_error(hf, params, served, hand=False)
    assert err > 100 * TOL


# ------------------------------------------------------------ the engine

def test_engine_serves_the_fifth_family(hf, params):
    """Through DecodeEngine -> GenerationBatcher with more requests than
    slots and a step in flight: every stream is the reference's greedy
    continuation, the step traced once, the indexer's keys a block leaf
    beside K and V, the sparse counters and the kernels' fact set."""
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(hf))
    eng = DecodeEngine(
        params, model=model, num_slots=2, max_len=128, kv_layout="paged",
        kv_block_size=8, prefix_cache=False, prefill_chunk=8,
        report_logits=True, name="ky")
    assert eng.sparse_kernels is False and "pallas_decode" in \
        eng.sparse_decline_reason
    reqs = prompts([23, 5, 60, 11], seed=2)
    outs_n = [4, 6, 3, 5]
    with GenerationBatcher(eng, default_max_tokens=4) as gen:
        outs = [f.result(120) for f in
                [gen.submit(p, max_tokens=n) for p, n in zip(reqs, outs_n)]]
    ref_params = serve_keye.reference_params(params, hf)
    for prompt, out, n in zip(reqs, outs, outs_n):
        assert len(out["tokens"]) == n
        seq = prompt + out["tokens"][:-1]
        want, _z, _f = reference.forward(ref_params, jnp.asarray([seq]), hf)
        for j, tok in enumerate(out["tokens"]):
            row = np.asarray(want)[0, len(prompt) - 1 + j]
            assert row.max() - row[tok] < 1e-3
    assert eng.step_trace_count == 1
    assert sorted(eng.slot_state(0)[0]) == ["ik", "k", "v"]
    assert eng.slot_state(0)[0]["ik"] is None       # a block leaf
    m = eng.metrics
    fed = sum(len(p) + n - 1 for p, n in zip(reqs, outs_n))
    assert m.sparse_scored_positions_total == 4 * m.attended_positions_total
    assert m.sparse_selected_positions_total < \
        m.sparse_scored_positions_total
    assert m.sparse_selected_positions_total <= 4 * 16 * (fed + 8)
    assert 0 < m.sparse_read_positions_total \
        <= m.sparse_scored_positions_total
    snap = m.snapshot()
    assert snap["sparse_kernels"] == 0
    text = m.render_prometheus()
    for name in ("sparse_kernels", "sparse_scored_positions_total",
                 "sparse_selected_positions_total",
                 "sparse_read_positions_total"):
        assert name in text


def test_recorded_steps_keep_what_keep_makes_of_the_report(hf, params):
    """``record_steps(keep=fn)``: each step's report is what ``fn`` made of
    it as the step was read, here the seated row's selection bits on the
    host, one ``[layers, K, W]`` a step, whose lanes took what a lane at
    their position takes."""
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(hf))
    eng = DecodeEngine(
        params, model=model, num_slots=2, max_len=128, kv_layout="paged",
        kv_block_size=8, prefix_cache=False, prefill_chunk=8,
        report_logits=True, name="kp")
    seen = []

    def keep(tokens, pos, lens, report):
        (_routes, bits), _logits = report
        s = int(np.argmax(pos + lens))
        seen.append((int(pos[s]), int(lens[s])))
        return np.stack([np.asarray(b[s]) for b in bits])

    eng.record_steps(True, keep=keep)
    with GenerationBatcher(eng, default_max_tokens=3) as gen:
        gen.submit(prompts([30], seed=4)[0], max_tokens=3).result(120)
    steps = eng.recorded_steps()
    eng.record_steps(False)
    assert len(steps) == len(seen) >= 5
    for (_t, _p, _l, bits), (p, n) in zip(steps, seen):
        assert isinstance(bits, np.ndarray) and bits.shape == (4, 8, 128)
        taken = dsa.unpack(bits[:, :n], 128)
        np.testing.assert_array_equal(
            taken.sum(-1), np.minimum(p + np.arange(n) + 1, 16)[None]
            .repeat(4, 0))


def test_sparse_counts_follow_the_lanes(hf):
    """Lanes at 0..7 score 1..8 and keep the same; one lane at 40 scores
    41 and keeps 16; a row of eight lanes at 30..37 reads at most all 38
    of its positions, a decoding row its 16; four layers each."""
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(hf))
    assert (model.sparse_topk, model.sparse_layers) == (16, 4)
    scored, chosen, read = model.sparse_counts([0, 40, 30], [8, 1, 8])
    assert scored == 4 * (36 + 41 + sum(range(31, 39)))
    assert chosen == 4 * (36 + 16 + 8 * 16)
    assert read == 4 * (8 + 16 + 38)


# ------------------------------------------------ the other families

# Laguna's, Kimi's and Pangu's steps lay each expert layer's groups out on
# whole ``moe.ROW_ALIGN`` rows; Jamba's has no expert layer
PINNED = {
    "jamba2-3b":
        "3d5d788893aa74374f0d1fb1ec89a821c00959951682f1437b0cc912c205638b",
    "laguna-s-2.1-ep8-8l":
        "5a574a7fd91f1aa3293e5c0ccf4753faa9717ac4097abfd83f420aad3cadf32b",
    "kimi-linear-48b-ep4-8l":
        "8d9fa10b27c27167d069fe6c2e9c2ab6204a28ef3168039e5b418f3fa5b90d39",
    "openpangu-718b-ep16-5l":
        "7b724408071694faa517af2fe411a66b1dfb6b5814a8bd190885f46cfbebd609",
}


@pytest.mark.parametrize("name", sorted(PINNED))
def test_other_families_step_programs_are_unchanged(name):
    """The step of each other hybrid configuration, traced chip-free at its
    full widths (abstract parameters and cache, the narrowest width, the
    kernels forced on so that their bodies are in it), is the program it
    was before this family came: its jaxpr's hash, as the parent tree gave
    it."""
    cfg = _config(name)
    s = cfg["serving"]
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(cfg), s["kv_dtype"])
    slots, kk, bs = s["slots"], s["prefill_chunk"], s["kv_block_size"]
    entries = s["max_len"] // bs
    dtype = jnp.dtype(cfg.get("param_dtype", "float32"))
    with dk.forced_mode("always"):
        params = jax.eval_shape(lambda: hybrid_lm.init(
            jax.random.PRNGKey(0), model.cfg, dtype))
        cache = jax.eval_shape(lambda: model.init_cache(
            slots, slots * entries + 1, bs, chunk=kk))
        width = model.step_widths(slots, kk)[0]
        i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
        jaxpr = jax.make_jaxpr(model.decode_chunk)(
            params, i32(slots, kk), i32(slots), i32(slots), cache,
            i32(slots, entries), i32(width), i32(slots, kk))
    assert hashlib.sha256(str(jaxpr).encode()).hexdigest() == PINNED[name]


def test_kernels_match_the_xla_path_over_many_tiles():
    """The three kernels interpreted at a row of 2,048 positions in blocks
    of 32 (four indexer tiles, two selection passes' chunks, sixteen
    attention tiles), a row that feeds one lane at position 1,500 and one
    that feeds eight from 900, against ``ops/dsa.py``'s XLA path: the
    scores at every position a lane reaches, the picks, the attention."""
    s, kk, bs, entries, heads, dim, topk = 2, 8, 32, 64, 16, 128, 64
    num_heads, kv_heads, dh = 8, 2, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 6)
    blocks = s * entries + 1
    tables = jnp.arange(1, blocks, dtype=jnp.int32).reshape(s, entries)
    ik = jax.random.normal(ks[0], (blocks, bs, dim))
    k_pool = jax.random.normal(ks[1], (blocks, bs, kv_heads * dh))
    v_pool = jax.random.normal(ks[2], (blocks, bs, kv_heads * dh))
    qi = jax.random.normal(ks[3], (s, kk, heads, dim))
    w = jax.random.normal(ks[4], (s, kk, heads))
    q = jax.random.normal(ks[5], (s, kk, num_heads * dh))
    qpos = jnp.asarray([[1500] * kk, list(range(900, 908))], jnp.int32)
    qi = qi.at[0].set(qi[0, :1])           # a decoding row's lanes repeat
    w, q = w.at[0].set(w[0, :1]), q.at[0].set(q[0, :1])
    want = dsa.index_scores(qi, w, ik, qpos, tables, False)
    got = dsa_kernels.indexer_paged_chunk(qi, w, ik, qpos, tables,
                                          interpret=True)
    reach = jnp.arange(entries * bs) <= qpos[:, :, None]
    np.testing.assert_allclose(jnp.where(reach, got, 0.0),
                               jnp.where(reach, want, 0.0), rtol=1e-5,
                               atol=1e-4)
    picks, bits = dsa.select(want, qpos, topk, False)
    got_picks, got_bits = dsa_kernels.sparse_select(want, qpos, topk,
                                                    interpret=True)
    np.testing.assert_array_equal(np.asarray(got_picks[1, :, :3]),
                                  np.asarray(picks[1, :, :3]))
    np.testing.assert_array_equal(np.asarray(got_bits[1]),
                                  np.asarray(bits[1]))
    np.testing.assert_array_equal(np.asarray(got_bits[0, 0]),
                                  np.asarray(bits[0, 0]))
    want_o = dsa.attend(q, k_pool, v_pool, want, picks, qpos, tables,
                        num_heads, False)
    got_o = dsa_kernels.sparse_attn_paged_chunk(
        q, k_pool, v_pool, want, picks, qpos, tables, num_heads=num_heads,
        interpret=True)
    np.testing.assert_allclose(got_o[0, 0], want_o[0, 0], atol=1e-5)
    np.testing.assert_allclose(got_o[1], want_o[1], atol=1e-5)

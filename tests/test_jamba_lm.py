"""The hybrid trunk's third family (models/hybrid_lm.py as ``jamba`` builds
it: a Mamba-1 selective scan with per-slot state, softmax attention with one
K/V head over paged K and V pools once a period, dense FFNs, a tied head) at
tiny widths on the CPU: the served path, packed at each compiled width,
against the plain reference (benchmark/reference/jamba.py); the
``mamba_chunk`` kernel interpreted against ``scan_xla`` and against the
reference's own mixer; the packed convolution against ``kda.short_conv``;
and the engine's facts and gauges.

The tiny widths keep the published ones' relations: d_inner 2 x hidden, a dt
rank and a state far under hidden, 4 query heads on 1 K/V head, one
attention layer in a period of 3.

TOL: the program and the reference compute in float32 on the CPU and differ
in the ORDER of their sums only: a few 1e-6 on logits of size 6; 2e-4 leaves
an order of room and is a thousandth of what dropping any part of the mixer
moves."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.drivers import serve_hybrid, serve_jamba  # noqa: E402
from benchmark.reference import jamba as reference  # noqa: E402
from paddle_tpu.models import hybrid_lm  # noqa: E402
from paddle_tpu.ops import kda, mamba  # noqa: E402
from paddle_tpu.ops.pallas import decode_attention as dk  # noqa: E402
from paddle_tpu.ops.pallas import mamba as mamba_kernel  # noqa: E402
from paddle_tpu.serving.decode_engine import (DecodeEngine,  # noqa: E402
                                              GenerationBatcher)

TOL = 2e-4
BLOCK = 4


def tiny(**over):
    """benchmark/testdata/configs/tiny-jamba.json with blocks of 4."""
    with open(os.path.join(ROOT, "benchmark", "testdata", "configs",
                           "tiny-jamba.json")) as f:
        hf = json.load(f)
    hf["serving"] = dict(hf["serving"], kv_block_size=BLOCK)
    hf.update(over)
    return hf


@pytest.fixture(scope="module")
def hf():
    return tiny()


@pytest.fixture(scope="module")
def params(hf):
    return serve_hybrid.make_params(hf, 17)


def prompts(lengths, seed=0, vocab=128):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, n).tolist() for n in lengths]


def served_logits(params, hf, seqs, n_decode):
    """The trunk's own step, ``Served.decode_chunk``, through its own cache
    (per-slot state and tail, paged K and V pools): chunked prefill K lanes
    at a time, then ``n_decode`` greedy decode steps, at the engine's shape
    (``serving.slots`` rows, those past ``seqs`` idling at position 0 as
    free slots do) and packed as the engine packs (``Served.pack``).
    -> (sequences with the greedy tokens appended, per-row list of
    [position, logits row])."""
    s = hf["serving"]
    bs, kk = s["kv_block_size"], s["prefill_chunk"]
    live, n = len(seqs), max(len(seqs), s["slots"])
    nb_row = -(-(max(map(len, seqs)) + n_decode + 1) // bs)
    tables = jnp.asarray(np.arange(1, n * nb_row + 1, dtype=np.int32)
                         .reshape(n, nb_row))
    model = serve_hybrid.served_model(hf)
    cache = model.init_cache(n, n * nb_row + 1, bs)
    jstep = jax.jit(lambda p, cache, *feed: model.decode_chunk(
        p, *feed[:3], cache, tables, *feed[3:])[:2])
    seqs = [list(p) for p in seqs]
    cursor = [0] * live         # tokens of each row already in the cache
    got = [[] for _ in range(live)]
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
        logits, cache = jstep(params, cache, chunk, pos, lens,
                              *model.pack(lens, kk))
        logits = np.asarray(logits)
        for i in range(live):
            if len(got[i]) > n_decode:
                continue
            cursor[i] += int(lens[i])
            if cursor[i] == len(seqs[i]):
                got[i].append([cursor[i] - 1, logits[i]])
                seqs[i].append(int(logits[i].argmax()))
    return seqs, got


def served_error(hf, params, seqs=None, n_decode=3, served=None):
    """Chunked prefill of uneven lengths, then decoding, through the state,
    the tail and the paged K/V pools at the engine's shape and packing; the
    largest distance of a compared logits row from the reference's full
    forward and the number of rows compared."""
    seqs, got = served or served_logits(
        params, hf, seqs or prompts([21, 45, 7]), n_decode)
    ids = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, seq in enumerate(seqs):
        ids[i, :len(seq)] = seq
    want = np.asarray(reference.logits(
        serve_jamba.reference_params(params, tiny()), jnp.asarray(ids),
        tiny()))
    return max(float(np.abs(row - want[i, p]).max())
               for i, rows in enumerate(got) for p, row in rows), \
        sum(map(len, got))


def published_row():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "jamba2-3b.json")) as f:
        return json.load(f)


def test_config_from_hf_builds_the_third_family(hf):
    cfg = hybrid_lm.config_from_hf(published_row())
    kinds = [a for a, _f in cfg.layers]
    assert len(kinds) == 28 and kinds.count("mamba") == 26
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [7, 21]
    assert {f for _a, f in cfg.layers} == {"dense"}
    assert (cfg.mamba_inner, cfg.mamba_state, cfg.mamba_conv,
            cfg.mamba_dt_rank) == (5120, 16, 4, 160)
    assert (cfg.attn_heads, cfg.attn_kv_heads, cfg.attn_head_dim) \
        == (20, 1, 128)
    assert cfg.tie_embeddings and cfg.dense_width == 8192
    assert kinds == reference.layer_kinds(published_row())
    # the tiny twin: one attention layer in a period of 3
    small = hybrid_lm.config_from_hf(hf)
    assert [a for a, _f in small.layers] == ["mamba", "attn", "mamba",
                                             "mamba"]
    leaves = jax.tree_util.tree_leaves(hybrid_lm.cache_kinds(small))
    assert leaves.count(hybrid_lm.SLOT_LEAF) == 6
    assert leaves.count(hybrid_lm.BLOCK_LEAF) == 2
    # what the family does not serve says so
    with pytest.raises(NotImplementedError, match="routed experts"):
        hybrid_lm.config_from_hf(dict(hf, num_experts=16))
    with pytest.raises(NotImplementedError, match="window"):
        hybrid_lm.config_from_hf(dict(hf, sliding_window=4096))


@pytest.mark.parametrize("name,attn,flags", [
    ("tiny-hybrid.json", ["kda", "kda", "kda", "mla"], (None, None, False)),
    ("tiny-pangu.json", ["mla", "mla", "mla"], (24, 25.6e6, True))])
def test_config_from_hf_still_builds_the_first_two(name, attn, flags):
    with open(os.path.join(ROOT, "benchmark", "testdata", "configs",
                           name)) as f:
        cfg = hybrid_lm.config_from_hf(json.load(f))
    assert [a for a, _f in cfg.layers] == attn
    assert (cfg.q_rank, cfg.rope_theta, cfg.post_norms) == flags
    assert not cfg.tie_embeddings and cfg.mamba_inner == 0
    p = jax.eval_shape(lambda k: hybrid_lm.init(k, cfg),
                       jax.random.PRNGKey(0))
    assert "head" in p


def test_tied_head_has_no_leaf_and_reads_the_table(hf, params):
    cfg = hybrid_lm.config_from_hf(hf)
    assert "head" not in params and params["emb"].shape == (128, 32)
    tables = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(2, 4))
    cache = hybrid_lm.init_cache(cfg, 2, 9, BLOCK)
    tok = np.asarray(prompts([8, 8], seed=4), np.int32)
    pos, lens = np.zeros(2, np.int32), np.asarray([8, 8], np.int32)
    step = lambda p: hybrid_lm.decode_chunk(p, cfg, tok, pos, lens, cache,
                                            tables)[0]
    base = step(params)
    # a row of the table is that token's logit column: scaling one row
    # scales one column of the logits (and, as an input, nothing: token 127
    # is not in the prompts)
    assert 127 not in tok
    scaled = dict(params, emb=params["emb"].at[127].multiply(3.0))
    moved = step(scaled)
    np.testing.assert_allclose(moved[:, 127], 3.0 * base[:, 127], rtol=1e-5)
    np.testing.assert_allclose(moved[:, :127], base[:, :127], rtol=1e-5)


@pytest.mark.parametrize("kk", [1, 5, 8])
def test_served_path_matches_reference(hf, params, kk):
    chunked = dict(hf, serving=dict(hf["serving"], prefill_chunk=kk))
    err, rows = served_error(chunked, params)
    assert rows == 12 and err < TOL


def test_served_path_with_the_kernels_interpreted(hf, params):
    """The same comparison with ``mamba_chunk`` and the paged attention
    kernel in interpret mode, at blocks of 16."""
    blocks = dict(hf, serving=dict(hf["serving"], kv_block_size=16))
    with dk.forced_mode("always"):
        err, rows = served_error(blocks, params)
    mamba_kernel.mamba_chunk.clear_cache()
    assert rows == 12 and err < TOL


@pytest.mark.parametrize("width", [8, 16, 32])
def test_every_packed_width_computes_the_same_step(hf, params, width):
    """A step's logits and cache do not depend on the width it is packed
    at, nor on being packed at all."""
    cfg = hybrid_lm.config_from_hf(hf)
    model = hybrid_lm.Served(cfg)
    assert model.step_widths(4, 8) == (8, 16, 32)
    tables = jnp.asarray(np.arange(1, 17, dtype=np.int32).reshape(4, 4))
    cache = hybrid_lm.init_cache(cfg, 4, 17, BLOCK)
    tok = np.asarray(prompts([8] * 4, seed=6), np.int32)
    # two steps, so that the second starts from state the first left
    feeds = [(np.zeros(4, np.int32), np.asarray([3, 1, 2, 1], np.int32)),
             (np.asarray([3, 1, 2, 1], np.int32),
              np.asarray([1, 1, 4, 1], np.int32))]
    def run(pack):
        c, out = cache, []
        for pos, lens in feeds:
            logits, c = hybrid_lm.decode_chunk(
                params, cfg, tok, pos, lens, c, tables,
                packing=pack(lens))
            out.append(logits)
        return out, c
    want, want_cache = run(lambda lens: None)
    got, got_cache = run(lambda lens: model.pack(lens, 8, width))
    for a, b in zip(want, got):
        np.testing.assert_allclose(a, b, atol=1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(want_cache),
                    jax.tree_util.tree_leaves(got_cache)):
        np.testing.assert_allclose(a, b, atol=1e-5)


def _scan_case(lens, d=64, n=8, kk=8, seed=0, places=None):
    """The scan's operands over a packing of rows that feed ``lens`` lanes:
    row 1 starts at position 0 (fresh), the others carry state."""
    lens = np.asarray(lens)
    s = len(lens)
    src, back = hybrid_lm.pack_lanes(lens, kk)
    src = jnp.asarray(src[:places or s * kk])
    width = src.shape[0]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    u = jax.random.normal(ks[0], (width, d))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (width, d)) - 2.0)
    b, c = (jax.random.normal(k, (width, n)) for k in ks[2:4])
    a = -jnp.exp(0.3 * jax.random.normal(ks[4], (n, d)))
    state = jax.random.normal(ks[5], (s, n, d))
    fresh = jnp.zeros((s,), bool).at[1].set(True)
    return (u, dt, b, c, a, state, jnp.asarray(lens), fresh, src,
            jnp.asarray(back))


@pytest.mark.parametrize("lens,places", [
    ([1, 8, 3, 5, 1], 24),      # 18 live lanes in 24 places
    ([1, 1, 1, 1], 8),          # decoding rows only
    ([8, 8, 8], None),          # every lane live, nothing packed away
    ([2, 7, 1], 12)])           # a chunk of 12 places: groups of 4
def test_mamba_kernel_interpreted_matches_scan(lens, places):
    args = _scan_case(lens, places=places)
    want_y, want_state = mamba.scan_xla(*args)
    with dk.forced_mode("always"):
        got_y, got_state = jax.jit(mamba.scan)(*args)
    mamba_kernel.mamba_chunk.clear_cache()
    own = np.asarray(mamba.own_places(args[8], args[9]))
    np.testing.assert_allclose(np.asarray(got_y)[own],
                               np.asarray(want_y)[own], atol=1e-5)
    np.testing.assert_allclose(got_state, want_state, atol=1e-5)
    # a fresh row forgot what its slot held; a decoding row moved one lane
    u, dt, b, c, a, state = args[:6]
    first = int(args[9][1, 0])
    np.testing.assert_allclose(
        got_state[1] if lens[1] == 1 else got_y[first],
        (dt[first] * u[first])[None, :] * b[first][:, None]
        if lens[1] == 1 else
        jnp.sum((dt[first] * u[first])[None, :] * b[first][:, None]
                * c[first][:, None], 0), atol=1e-5)


def test_mamba_kernel_walks_unpacked_places_too():
    """Where nothing is packed (``N = S x K``) the lanes past a row's
    length repeat its last lane in place: the kernel steps over them, and
    gives what the packed walk gives."""
    lens, s, kk, d, n = np.asarray([3, 8, 1, 5]), 4, 8, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(9), 6)
    lanes = {"u": jax.random.normal(ks[0], (s * kk, d)),
             "dt": jax.nn.softplus(jax.random.normal(ks[1], (s * kk, d))),
             "b": jax.random.normal(ks[2], (s * kk, n)),
             "c": jax.random.normal(ks[3], (s * kk, n))}
    a = -jnp.exp(0.3 * jax.random.normal(ks[4], (n, d)))
    state = jax.random.normal(ks[5], (s, n, d))
    fresh = jnp.zeros((s,), bool).at[1].set(True)
    src, back = map(jnp.asarray, hybrid_lm.pack_lanes(lens, kk))
    li = jnp.minimum(jnp.arange(kk)[None, :], jnp.asarray(lens)[:, None] - 1)
    wide_back = jnp.arange(s)[:, None] * kk + li
    wide_src = wide_back.reshape(-1)

    def run(src, back, fn):
        return fn(*(lanes[k][src] for k in ("u", "dt", "b", "c")), a, state,
                  jnp.asarray(lens), fresh, src, back)

    want_y, want_state = run(src[:24], back, mamba.scan_xla)
    with dk.forced_mode("always"):
        y, got_state = run(wide_src, wide_back, jax.jit(mamba.scan))
    mamba_kernel.mamba_chunk.clear_cache()
    np.testing.assert_allclose(got_state, want_state, atol=1e-5)
    live = int(lens.sum())
    np.testing.assert_allclose(y[src[:live]], want_y[:live], atol=1e-5)


def test_mamba_kernel_against_the_reference_mixer(hf, params):
    """One Mamba layer, prefilled in chunks through ``mamba_chunk`` (the
    kernel interpreted), against ``reference.mamba`` over the whole
    sequence."""
    cfg = hybrid_lm.config_from_hf(hf)
    p = params["layers"][0]["attn"]
    t, kk = 21, 8
    x = jax.random.normal(jax.random.PRNGKey(3), (2, t, 32))
    ref_p = serve_jamba.reference_params(params, hf)["layers"][0]["mixer"]
    with jax.default_matmul_precision("highest"):
        want, want_state = reference.mamba(x, ref_p, hf)
        _y, state_at_9 = reference.mamba(x, ref_p, hf, jnp.asarray([9, t]))
    state = jnp.zeros((2, cfg.mamba_state, cfg.mamba_inner))
    tail = jnp.zeros((2, cfg.mamba_conv - 1, cfg.mamba_inner))
    got = []
    with dk.forced_mode("always"):
        for start in range(0, t, kk):
            n = min(kk, t - start)
            lens = np.asarray([n, n])
            src, back = hybrid_lm.pack_lanes(lens, kk)
            h = jnp.concatenate([x[0, start:start + n],
                                 x[1, start:start + n]])
            h = jnp.pad(h, ((0, 2 * kk - 2 * n), (0, 0)))
            y, state, tail = mamba.mamba_chunk(
                p, h, state, tail, jnp.full((2,), start), jnp.asarray(lens),
                jnp.asarray(src), jnp.asarray(back),
                dt_rank=cfg.mamba_dt_rank, eps=cfg.rms_norm_eps)
            got.append(jnp.stack([y[:n], y[n:2 * n]]))
    mamba_kernel.mamba_chunk.clear_cache()
    np.testing.assert_allclose(jnp.concatenate(got, 1), want, atol=1e-4)
    # the state the chunks carried is the reference's after all t
    # positions, laid out [n, d_inner]; asked after 9 it is the state a
    # shorter sequence ends with
    np.testing.assert_allclose(jnp.swapaxes(state, 1, 2), want_state,
                               atol=1e-4)
    np.testing.assert_allclose(state_at_9[1], want_state[1])
    _y, short = reference.mamba(x[:1, :9], ref_p, hf)
    np.testing.assert_allclose(state_at_9[0], short[0], atol=1e-6)


def test_mamba_kernel_guard_names_its_reason():
    assert "pallas_decode" in mamba_kernel.decline_reason(256, 16, 5120, 16)
    for width in (256, 512, 1024):
        assert mamba_kernel.shape_problem(width, 16, 5120, 16) is None \
            or "VMEM" in mamba_kernel.shape_problem(width, 16, 5120, 16)
    assert "columns" in mamba_kernel.shape_problem(256, 16, 5000, 16)
    assert "columns" in mamba_kernel.shape_problem(256, 16, 5120, 12)
    assert "chunks" in mamba_kernel.shape_problem(36, 16, 5120, 16)
    assert "VMEM" in mamba_kernel.shape_problem(256, 4096, 5120, 16)
    assert mamba_kernel.chunk_rows(256) == 64
    assert mamba_kernel.chunk_rows(24) == 24
    assert mamba_kernel.cost(16, 100, 5120, 16).transcendentals \
        == 100 * 16 * 5120


@pytest.mark.parametrize("lens", [[1, 8, 3, 5, 1], [2, 1, 1, 8, 4]])
def test_packed_convolution_matches_short_conv(lens):
    """``packed_conv`` on the packed lanes is ``kda.short_conv`` on the
    rows, bias apart: outputs of the live lanes and the new tail."""
    lens = np.asarray(lens)
    s, kk, width, ch = len(lens), 8, 4, 16
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    z_rows = jax.random.normal(ks[0], (s, kk, ch))
    tail = jax.random.normal(ks[1], (s, width - 1, ch))
    w = jax.random.normal(ks[2], (width, ch))
    bias = jax.random.normal(ks[3], (ch,))
    want, want_tail = kda.short_conv(z_rows, tail, w, jnp.asarray(lens))
    src, back = hybrid_lm.pack_lanes(lens, kk)
    src = jnp.asarray(src[:24])
    got, got_tail = mamba.packed_conv(
        z_rows.reshape(s * kk, ch)[src], tail, w, bias, src % kk,
        jnp.asarray(back)[:, 0], jnp.asarray(lens))
    live = int(lens.sum())
    np.testing.assert_allclose(
        got[:live], (want + bias).reshape(s * kk, ch)[src[:live]], atol=1e-5)
    np.testing.assert_allclose(got_tail, want_tail, atol=1e-6)


def test_lanes_past_a_rows_length_touch_nothing(hf, params):
    cfg = hybrid_lm.config_from_hf(hf)
    tables = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(2, 4))
    cache = hybrid_lm.init_cache(cfg, 2, 9, BLOCK)
    tok = np.asarray(prompts([8, 8], seed=4), np.int32)
    pos, lens = np.zeros(2, np.int32), np.asarray([3, 5], np.int32)
    step = jax.jit(lambda t: hybrid_lm.decode_chunk(params, cfg, t, pos,
                                                    lens, cache, tables))
    base_logits, base_cache = step(tok)
    other = tok.copy()
    other[0, 3:], other[1, 5:] = 99, 99
    logits, new_cache = step(other)
    np.testing.assert_array_equal(base_logits, logits)
    for a, b in zip(jax.tree_util.tree_leaves(base_cache),
                    jax.tree_util.tree_leaves(new_cache)):
        np.testing.assert_array_equal(a, b)
    assert float(jnp.abs(new_cache[0]["state"]).max()) > 0


def _without(monkeypatch, what, hf, params):
    if what == "norms":
        monkeypatch.setattr(mamba, "rms_norm", lambda x, g, eps: x)
        return params
    zero = lambda lp, key: dict(lp, attn=dict(
        lp["attn"], **{key: jnp.zeros_like(lp["attn"][key])}))
    key = {"d": "d", "conv_bias": "conv_bias", "dt_bias": "dt_bias"}[what]
    return dict(params, layers=[zero(lp, key) if key in lp["attn"] else lp
                                for lp in params["layers"]])


@pytest.mark.parametrize("what", ["norms", "d", "conv_bias", "dt_bias"])
def test_dropping_a_part_moves_the_logits(monkeypatch, hf, params, what):
    """A program that leaves out the norms of dt, B and C, the skip, or a
    bias is far outside the tolerance."""
    served = served_logits(
        _without(monkeypatch, what, hf, params), hf, prompts([21, 45, 7]), 3)
    err, _rows = served_error(hf, params, served=served)
    assert err > 100 * TOL


@pytest.fixture(scope="module")
def engine(hf, params):
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(hf))
    return DecodeEngine(
        params, model=model, num_slots=2, max_len=96, kv_layout="paged",
        kv_block_size=BLOCK, prefix_cache=False, prefill_chunk=8, name="jb")


def test_engine_serves_the_third_family(hf, params, engine):
    """Through DecodeEngine -> GenerationBatcher with more requests than
    slots and a step in flight: every stream is the reference's greedy
    continuation (so a slot seated again after another request started
    from zero), the step traced once at each width, the facts and the
    gauges set."""
    assert engine.mamba_kernels is False and engine.attn_kernels is False
    assert "pallas_decode" in engine.mamba_decline_reason
    assert "pallas_decode" in engine.attn_decline_reason
    assert engine.kda_decline_reason is None and not engine.kda_kernels
    assert engine.step_widths == (4, 8, 16)
    reqs = prompts([21, 5, 30, 11, 9], seed=2)
    outs_n = [4, 6, 3, 5, 4]
    with GenerationBatcher(engine, default_max_tokens=4) as gen:
        outs = [f.result(120) for f in
                [gen.submit(p, max_tokens=n) for p, n in zip(reqs, outs_n)]]
    ref_params = serve_jamba.reference_params(params, hf)
    for prompt, out, n in zip(reqs, outs, outs_n):
        assert len(out["tokens"]) == n
        seq = list(prompt)
        for tok in out["tokens"]:
            want = reference.logits(ref_params,
                                    jnp.asarray([seq], jnp.int32), hf)
            row = np.asarray(want)[0, -1]
            assert row.max() - row[tok] < TOL
            seq.append(tok)
    m = engine.metrics
    assert engine.step_trace_count == 1
    assert m.state_resets_total == 5            # five requests, two slots
    cfg = hybrid_lm.config_from_hf(hf)
    per_slot = 3 * (cfg.mamba_state + cfg.mamba_conv - 1) \
        * cfg.mamba_inner * 4
    assert m.slot_state_bytes == per_slot
    assert m.recurrent_state_bytes == 2 * per_slot
    assert m.latent_pool_bytes == 2 * engine._paged.pool.num_blocks \
        * BLOCK * cfg.attn_head_dim * 4
    snap = m.snapshot()
    assert snap["mamba_kernels"] == 0 and snap["slot_state_bytes"] == per_slot
    text = m.render_prometheus()
    for name in ("slot_state_bytes", "mamba_kernels",
                 "recurrent_state_bytes"):
        assert name in text


def test_kernel_report_names_every_kind(hf):
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(hf))
    report = model.kernel_report(8, BLOCK, 4)
    assert set(report) == {
        k + suffix for k in ("kda", "mla", "mamba", "attn", "window", "sparse")
        for suffix in ("_kernels", "_decline_reason")}
    assert report["window_decline_reason"] is None \
        and not report["window_kernels"]
    assert report["mamba_kernels"] is False and report["mamba_decline_reason"]
    assert report["kda_decline_reason"] is None
    with dk.forced_mode("always"):
        on = model.kernel_report(8, 16, 4)
    assert on["mamba_kernels"] and on["attn_kernels"]


def test_engine_reports_logits_and_slot_state(hf, params):
    """``report_logits``: the compiled step leaves its logits beside what
    the model reports, and their argmax is the token the batcher streams;
    ``slot_state``: what a slot owns when its request has left, the
    reference's state after the positions the request fed.  A budgeted
    engine compiles no width its steps cannot reach."""
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(hf))
    eng = DecodeEngine(
        params, model=model, num_slots=2, max_len=96, kv_layout="paged",
        kv_block_size=BLOCK, prefix_cache=False, prefill_chunk=8,
        prefill_chunk_budget=3, report_logits=True, name="jl")
    assert eng.step_widths == (4, 8)        # 2 rows' own lanes + 3 <= 8
    (prompt,) = prompts([21], seed=8)
    eng.record_steps(True)
    with GenerationBatcher(eng, default_max_tokens=4) as gen:
        out = gen.submit(prompt, max_tokens=4).result(120)
        steps = eng.recorded_steps()
        rows = [(int((pos + lens).max()), np.asarray(logits))
                for _t, pos, lens, (_routes, logits) in steps]
        slot = int(np.argmax(steps[-1][1] + steps[-1][2]))
        emitted = [int(l[slot].argmax()) for end, l in rows if end >= 21]
        assert emitted == out["tokens"] and rows[-1][0] == 21 + 3
        state = eng.slot_state(slot)
    assert [sorted(c) for c in state] == [
        ["conv", "state"], ["k", "v"], ["conv", "state"], ["conv", "state"]]
    assert state[1]["k"] is None            # block-addressed: no slot's
    fed = jnp.asarray([prompt + out["tokens"][:3]], jnp.int32)
    _logits, want = reference.forward(
        serve_jamba.reference_params(params, hf), fed, hf)
    for got, ref in zip([c["state"] for c in state if "state" in c], want):
        np.testing.assert_allclose(got.T, ref[0], atol=1e-5)
    with pytest.raises(Exception, match="report_logits"):
        DecodeEngine({"emb": params["emb"]}, report_logits=True, warm=False)

"""The hybrid trunk's fourth family (models/hybrid_lm.py as ``laguna``
builds it: window attention over per-slot rings beside full attention over
the paged pools, YaRN on half of each full head, a per-head output gate, a
softmax router over a held share of the experts) at tiny widths on the CPU:
the served path, packed at each compiled width and through the engine,
against the plain reference (benchmark/reference/laguna.py) past several
turns of the ring; the window kernel interpreted against the XLA path; the
YaRN frequencies against the published recipe; the router's shares against
the uncut layer; and ``config_from_hf`` on the published configuration.

The tiny widths keep the published ones' relations: window and full layers
3 to 1 with different query-head counts on the same K/V heads, half of a
full head turned, a held quarter of the experts.

TOL: the program and the reference compute in float32 on the CPU and differ
in the ORDER of their sums only: a few 1e-6 on logits of size 5; 2e-4 leaves
an order of room and is a thousandth of what dropping the window, the gate
or YaRN's scale moves."""

import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.drivers import serve_hybrid, serve_laguna  # noqa: E402
from benchmark.reference import laguna as reference  # noqa: E402
from paddle_tpu.models import hybrid_lm  # noqa: E402
from paddle_tpu.ops import moe  # noqa: E402
from paddle_tpu.ops.pallas import decode_attention as dk  # noqa: E402
from paddle_tpu.serving.decode_engine import (DecodeEngine,  # noqa: E402
                                              GenerationBatcher)

TOL = 2e-4


def tiny(**over):
    """benchmark/testdata/configs/tiny-laguna.json: window 8, chunk 4,
    blocks of 4, so a ring of 12 positions."""
    with open(os.path.join(ROOT, "benchmark", "testdata", "configs",
                           "tiny-laguna.json")) as f:
        hf = json.load(f)
    hf.update(over)
    return hf


def published():
    """The benchmark's configuration with the keys its cut changed set back
    to the published ones (``reduced``: depth, experts held, vocabulary)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-s-2.1-ep8-8l.json")) as f:
        cfg = json.load(f)
    cfg.update({k: v for k, v in cfg["published"].items()})
    cfg.pop("expert_parallel")
    return cfg


@pytest.fixture(scope="module")
def hf():
    return tiny()


@pytest.fixture(scope="module")
def params(hf):
    return serve_hybrid.make_params(hf, 39)


def prompts(lengths, seed=0, vocab=128):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, n).tolist() for n in lengths]


def served_logits(params, hf, seqs, n_decode, kk=None, width=None):
    """The trunk's own step, ``Served.decode_chunk``, through its own cache
    (rings and paged pools): chunked prefill ``kk`` lanes at a time, then
    ``n_decode`` greedy decode steps, at the engine's shape (``serving.
    slots`` rows, those past ``seqs`` idling at position 0 as free slots do)
    and packed as the engine packs (``Served.pack``, at ``width`` or the
    narrowest above it that holds the lanes).  -> (sequences with the greedy tokens
    appended, per-row list of [position, logits row], per-row {position:
    chosen experts})."""
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
        # the narrowest width at or above ``width`` that holds the lanes
        at = next(w for w in model.step_widths(n, kk)
                  if w >= max(width or 0, lens.sum()))
        logits, cache, aux = jstep(params, cache, chunk, pos, lens,
                                   *model.pack(lens, kk, at))
        logits, aux = np.asarray(logits), np.asarray(aux)
        for i in range(live):
            if len(got[i]) > n_decode:
                continue
            for j in range(int(lens[i])):
                routes[i][cursor[i] + j] = aux[:, i, j]
            cursor[i] += int(lens[i])
            if cursor[i] == len(seqs[i]):
                got[i].append([cursor[i] - 1, logits[i]])
                seqs[i].append(int(logits[i].argmax()))
    return seqs, got, routes


def served_error(hf, params, served):
    """The largest distance of a compared logits row from the reference's
    full forward, handed the program's expert choice, and the rows
    compared."""
    seqs, got, routes = served
    ids = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    n_moe = [f for _a, f in reference.layer_kinds(tiny())].count("moe")
    k = tiny()["num_experts_per_tok"]
    chosen = np.tile(np.arange(k, dtype=np.int32), ids.shape + (n_moe, 1))
    for i, seq in enumerate(seqs):
        ids[i, :len(seq)] = seq
        for p, c in routes[i].items():
            chosen[i, p] = c
    want, _shares = reference.forward(
        serve_laguna.reference_params(params, tiny()), jnp.asarray(ids),
        tiny(), routes=[jnp.asarray(chosen[:, :, l]) for l in range(n_moe)])
    want = np.asarray(want)
    return max(float(np.abs(row - want[i, p]).max())
               for i, rows in enumerate(got) for p, row in rows), \
        sum(map(len, got))


# ------------------------------------------------------------ the family

def test_config_from_hf_builds_the_published_model():
    cfg = hybrid_lm.config_from_hf(published())
    kinds = [a for a, _f in cfg.layers]
    assert len(kinds) == 48
    assert kinds.count("attn") == 12 and kinds.count("window") == 36
    assert [i for i, k in enumerate(kinds) if k == "attn"] \
        == list(range(0, 48, 4))
    assert [f for _a, f in cfg.layers] == ["dense"] + ["moe"] * 47
    assert (cfg.attn_heads, cfg.window_heads, cfg.attn_kv_heads,
            cfg.attn_head_dim) == (48, 72, 8, 128)
    assert (cfg.window, cfg.router, cfg.top_k, cfg.routed_scale) \
        == (512, "softmax", 10, 2.5)
    assert (cfg.router_width, cfg.held, cfg.expert_width,
            cfg.shared_experts, cfg.dense_width) \
        == (256, (0, 256), 1024, 1, 12288)
    assert cfg.attn_gate and not cfg.tie_embeddings
    assert cfg.attn_rope[0] == 64 and cfg.window_rope[0] == 128
    assert cfg.attn_rope[2] == pytest.approx(1.4852030263919618)
    assert cfg.window_rope[2] == 1.0
    # the cut: the first two periods, a held eighth of the experts
    cut = hybrid_lm.config_from_hf(_benchmark_config())
    assert [a for a, _f in cut.layers] == ["attn", "window", "window",
                                           "window"] * 2
    assert cut.held == (0, 32) and cut.router_width == 256
    assert cut.vocab_size == 12544
    # the reference reads the same kinds
    assert [("full" if a == "attn" else a, f) for a, f in cut.layers] \
        == reference.layer_kinds(_benchmark_config())


def _benchmark_config():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "laguna-s-2.1-ep8-8l.json")) as f:
        return json.load(f)


def test_yarn_frequencies_follow_the_published_recipe():
    """Hugging Face's ``_compute_yarn_parameters`` written out by hand for
    the full layers' entry (64 turned dimensions, theta 500,000, factor
    128 over 8,192 positions, beta 32 and 1): the ramp runs from dimension
    pair 9 (floor of 9.04) to 18 (ceil of 17.49); below it a frequency is
    the plain one, above it one 128th of that."""
    cfg = _benchmark_config()
    spec = cfg["rope_parameters"]["full_attention"]
    dim, inv, scale = hybrid_lm.rope_frequencies(128, spec)
    assert dim == 64 and len(inv) == 32
    fast = 64 * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(5e5))
    slow = 64 * math.log(8192 / (1 * 2 * math.pi)) / (2 * math.log(5e5))
    assert (math.floor(fast), math.ceil(slow)) == (9, 18)
    plain = [5e5 ** (-2 * i / 64) for i in range(32)]
    for i in range(32):
        ramp = min(max((i - 9) / 9, 0.0), 1.0)
        want = plain[i] / 128 * ramp + plain[i] * (1 - ramp)
        assert inv[i] == pytest.approx(want, rel=1e-6)
    assert inv[0] == 1.0 and inv[20] == pytest.approx(plain[20] / 128,
                                                     rel=1e-6)
    assert scale == pytest.approx(0.1 * math.log(128) + 1.0, rel=1e-9)
    # the reference's own transcription gives the same numbers
    ref_inv, ref_scale = reference.yarn_inv_freq(spec, 64)
    np.testing.assert_allclose(inv, ref_inv, rtol=1e-6)
    assert ref_scale == scale
    # the window layers: every dimension, theta 10,000, nothing scaled
    dim, inv, scale = hybrid_lm.rope_frequencies(
        128, cfg["rope_parameters"]["sliding_attention"])
    assert (dim, scale) == (128, 1.0)
    np.testing.assert_allclose(inv, [1e4 ** (-2 * i / 128)
                                     for i in range(64)], rtol=1e-6)


def test_softmax_router_shares_add_up_to_the_uncut_layer(hf):
    """Four holders of four experts each: the parts their held experts give
    (``routed_experts`` after ``softmax_router``), with the shared expert
    counted once, add up to the uncut reference's layer."""
    d, e, f, k = 64, 16, 32, 4
    ks = jax.random.split(jax.random.PRNGKey(5), 8)
    x = jax.random.normal(ks[0], (24, d))
    w = jax.random.normal(ks[1], (d, e)) * d ** -0.5
    experts = {n: jax.random.normal(kk, shape) * shape[1] ** -0.5
               for n, kk, shape in (("wg", ks[2], (e, d, f)),
                                    ("wu", ks[3], (e, d, f)),
                                    ("wd", ks[4], (e, f, d)))}
    shared = {n: jax.random.normal(kk, shape) * shape[0] ** -0.5
              for n, kk, shape in (("wg", ks[5], (d, f)),
                                   ("wu", ks[6], (d, f)),
                                   ("wd", ks[7], (f, d)))}
    idx, weights = moe.softmax_router(x, w, k, 2.5)
    s = jax.nn.softmax(x @ w, -1)
    np.testing.assert_allclose(
        weights, 2.5 * jnp.take_along_axis(s, idx, -1)
        / jnp.sort(s, -1)[:, -k:].sum(-1, keepdims=True), rtol=1e-5)
    parts = sum(moe.routed_experts(
        x, idx, weights, {n: v[4 * r:4 * r + 4] for n, v in experts.items()},
        (4 * r, 4)) for r in range(4))
    got = parts + moe.gated_ffn(x, shared["wg"], shared["wu"], shared["wd"])
    cfg = dict(hf, num_experts=e, num_experts_per_tok=k,
               moe_routed_scaling_factor=2.5, expert_parallel=None)
    with jax.default_matmul_precision("highest"):
        want, z = reference.moe(x, {"router": w, "experts": experts,
                                    "shared": shared}, cfg)
    np.testing.assert_allclose(got, want, atol=1e-4)
    np.testing.assert_allclose(jax.nn.softmax(z, -1), s, rtol=1e-5)


# ------------------------------------------------------- the served path

@pytest.mark.parametrize("kk", [1, 3, 4])
def test_served_path_matches_reference(hf, params, kk):
    """Prompts of 23, 45 and 7 (the ring of 12 turns three times) prefilled
    in chunks of ``kk``, then three decode steps."""
    served = served_logits(params, hf, prompts([23, 45, 7]), 3, kk=kk)
    err, rows = served_error(hf, params, served)
    assert rows == 12 and err < TOL


@pytest.mark.parametrize("width", [4, 8, 16])
def test_every_packed_width_computes_the_same(hf, params, width):
    served = served_logits(params, hf, prompts([23, 45, 7], seed=1), 2,
                           width=width)
    err, rows = served_error(hf, params, served)
    assert rows == 9 and err < TOL


def test_served_path_with_the_kernels_interpreted(hf, params):
    """The window kernel over the rings and the paged kernel over the
    pools, in interpret mode (blocks of 16: a ring of 32 in two blocks)."""
    blocks = dict(hf, serving=dict(hf["serving"], kv_block_size=16))
    with dk.forced_mode("always"):
        model = serve_hybrid.served_model(blocks)
        report = model.kernel_report(4, 16, 4, entries=8)
        assert report["window_kernels"] and report["attn_kernels"]
        served = served_logits(params, blocks, prompts([23, 45, 7]), 3)
    err, rows = served_error(hf, params, served)
    assert rows == 12 and err < TOL


@pytest.mark.parametrize("what", ["nowindow", "noyarn", "nogate"])
def test_each_wrong_program_moves_the_logits(hf, params, what):
    """A program without the window, YaRN's scale or the gate is far
    outside the tolerance."""
    wrong = serve_laguna.degraded_config(hf, what, 64)
    served = served_logits(params, wrong, prompts([23, 45, 7]), 3)
    err, _rows = served_error(hf, params, served)
    assert err > 100 * TOL


# ---------------------------------------------------------- the window

def _pool_case(lens, pos, window, bs=4, nb_row=16, heads=6, hkv=2, dh=16,
               seed=0):
    s, kk = len(lens), max(lens)
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    blocks = s * nb_row + 1
    k = jax.random.normal(ks[0], (blocks, bs, hkv * dh))
    v = jax.random.normal(ks[1], (blocks, bs, hkv * dh))
    q = jax.random.normal(ks[2], (s, kk, heads * dh))
    tables = jnp.arange(1, s * nb_row + 1, dtype=jnp.int32).reshape(s, nb_row)
    li = np.minimum(np.arange(kk)[None, :], np.asarray(lens)[:, None] - 1)
    qpos = jnp.asarray(np.asarray(pos)[:, None] + li, jnp.int32)
    return q, k, v, qpos, tables


def _window_reference(q, k, v, qpos, tables, window, hkv, dh):
    s, kk, d = q.shape
    keys = k[tables].reshape(s, -1, hkv, dh)
    vals = v[tables].reshape(s, -1, hkv, dh)
    col = jnp.arange(keys.shape[1])[None, None, :]
    live = (col <= qpos[:, :, None]) & (col > qpos[:, :, None] - window)
    qg = q.reshape(s, kk, hkv, -1, dh)
    sc = jnp.einsum("skvgd,stvd->skvgt", qg, keys) / math.sqrt(dh)
    p = jax.nn.softmax(jnp.where(live[:, :, None, None], sc, -jnp.inf), -1)
    return jnp.einsum("skvgt,stvd->skvgd", p, vals).reshape(s, kk, d)


def test_window_kernel_reads_no_tile_before_the_window():
    """The tiled kernel with a window, interpreted, over a plain pool: a
    row whose window starts mid-tile, one whose first tiles lie wholly
    before it, one at its first positions.  Every block of the pool wholly
    before a row's window has NaN in its V: copied into the kernel's
    buffer, a masked weight of 0 times NaN would turn the row to NaN, so
    finite rows equal to the masked reference mean those blocks were never
    copied."""
    window, bs, hkv, dh = 9, 4, 2, 16
    lens, pos = [4, 1, 3], [13, 41, 0]
    q, k, v, qpos, tables = _pool_case(lens, pos, window)
    poisoned = v
    for r, p in enumerate(pos):
        first = max(0, p - window + 1) // bs
        for j in range(first):
            poisoned = poisoned.at[tables[r, j]].set(jnp.nan)
    assert not bool(jnp.isfinite(poisoned).all())
    want = _window_reference(q, k, v, qpos, tables, window, hkv, dh)
    g = dk.paged_chunk_tile(6, 6 * dh, hkv * dh, bs, 16, 4, interpret=True)
    assert g > 1
    got = dk._paged_chunk_tiled(q, k, poisoned, qpos, tables, g=g,
                                num_heads=6, interpret=True, window=window)
    assert bool(jnp.isfinite(got).all())
    np.testing.assert_allclose(got, want, atol=1e-5)
    # the same call without a window reads the poisoned blocks
    full = dk._paged_chunk_tiled(q, k, poisoned, qpos, tables, g=g,
                                 num_heads=6, interpret=True)
    assert not bool(jnp.isfinite(full[1]).all())


@pytest.mark.parametrize("pos", [[0, 5, 30], [17, 38, 2]])
def test_window_kernel_over_rings_matches_the_xla_path(pos):
    """``decode_attention_window_chunk`` over per-slot rings (window 8,
    chunk 4, a ring of 12 in blocks of 4) against ``hybrid_lm``'s XLA path
    over the same rings, after each row wrote its chunk."""
    window, bs, kk, hkv, dh, heads = 8, 4, 4, 2, 16, 6
    lens = np.asarray([4, 1, 3])
    ring = 12
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    k_ring = jax.random.normal(ks[0], (3, ring, hkv * dh))
    v_ring = jax.random.normal(ks[1], (3, ring, hkv * dh))
    q = jax.random.normal(ks[2], (3, kk, heads * dh))
    li = np.minimum(np.arange(kk)[None, :], lens[:, None] - 1)
    qpos = jnp.asarray(np.asarray(pos)[:, None] + li, jnp.int32)
    want = hybrid_lm._ring_attention(q, k_ring, v_ring, qpos, hkv, dh,
                                     window)
    got = dk.decode_attention_window_chunk(q, k_ring, v_ring, qpos, heads,
                                           window, block=bs, entries=16,
                                           interpret=True)
    np.testing.assert_allclose(got.reshape(want.shape[:2] + (-1,)),
                               want.reshape(want.shape[:2] + (-1,)),
                               atol=1e-5)
    with pytest.raises(ValueError, match="ring"):
        dk.decode_attention_window_chunk(q, k_ring[:, :8], v_ring[:, :8],
                                         qpos, heads, window, block=bs,
                                         entries=16, interpret=True)


def test_window_kernel_guard_names_its_reason():
    assert "pallas_decode" in dk.window_decline_reason(72, 9216, 1024, 32,
                                                       1024, 64)
    with dk.forced_mode("always"):
        assert dk.window_decline_reason(6, 96, 32, 4, 16, 4) is None


# ------------------------------------------------------------ the engine

def test_engine_serves_the_fourth_family(hf, params):
    """Through DecodeEngine -> GenerationBatcher with more requests than
    slots and a step in flight: every stream is the reference's greedy
    continuation, the step traced once at each width, the rings the slot's
    own (window + chunk positions), the window's counter and gauges set."""
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(hf))
    eng = DecodeEngine(
        params, model=model, num_slots=2, max_len=96, kv_layout="paged",
        kv_block_size=4, prefix_cache=False, prefill_chunk=4,
        report_logits=True, name="lg")
    assert eng.window_kernels is False and "pallas_decode" in \
        eng.window_decline_reason
    reqs = prompts([23, 5, 30, 11], seed=2)
    outs_n = [4, 6, 3, 5]
    eng.record_steps(True)
    with GenerationBatcher(eng, default_max_tokens=4) as gen:
        outs = [f.result(120) for f in
                [gen.submit(p, max_tokens=n) for p, n in zip(reqs, outs_n)]]
    eng.record_steps(False)
    ref_params = serve_laguna.reference_params(params, hf)
    for prompt, out, n in zip(reqs, outs, outs_n):
        assert len(out["tokens"]) == n
        seq = prompt + out["tokens"][:-1]
        want, _s = reference.forward(ref_params, jnp.asarray([seq]), hf)
        for j, tok in enumerate(out["tokens"]):
            row = np.asarray(want)[0, len(prompt) - 1 + j]
            assert row.max() - row[tok] < 1e-3
    assert eng.step_trace_count == 1
    m = eng.metrics
    ring = hybrid_lm.ring_positions(model.cfg, 4, 4)
    assert ring == 12
    assert m.window_ring_bytes == 6 * 2 * 2 * ring * 32 * 4
    assert m.recurrent_state_bytes == m.window_ring_bytes
    assert [sorted(c) for c in eng.slot_state(0)][:2] == [["k", "v"]] * 2
    assert eng.slot_state(0)[0]["k"] is None            # the pool's
    assert eng.slot_state(0)[1]["k"].shape == (3, 4, 32)
    fed = sum(len(p) + n - 1 for p, n in zip(reqs, outs_n))
    assert m.window_attended_positions_total < m.attended_positions_total
    assert m.window_attended_positions_total <= 8 * fed
    snap = m.snapshot()
    assert snap["window_ring_bytes"] == m.window_ring_bytes
    text = m.render_prometheus()
    for name in ("window_ring_bytes", "window_kernels",
                 "window_attended_positions_total"):
        assert name in text


def test_window_counts_each_lane_up_to_the_window(hf):
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(hf))
    assert model.window == 8
    # lanes at 0..3 attend 1..4; at 20..23, 8 each; one lane at 6, 7.
    # The rows read 0..3, 13..23 and 0..6 once each
    assert model.window_counts([0, 20, 6], [4, 4, 1]) \
        == (10 + 32 + 7, 4 + 11 + 7)

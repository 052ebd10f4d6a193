"""The hybrid served trunk (models/hybrid_lm.py: KDA + MLA + held experts) at
tiny widths on the CPU: the served path against the plain reference
(benchmark/reference/kimi_linear.py), the share of the experts against the
uncut layer, and what a slot's state is owed (zero at position 0, nothing
from lanes past its length)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark.drivers import serve_hybrid  # noqa: E402
from benchmark.reference import kimi_linear as reference  # noqa: E402
from paddle_tpu.models import hybrid_lm  # noqa: E402
from paddle_tpu.ops import kda, moe  # noqa: E402
from paddle_tpu.ops.pallas import kda as kda_kernel  # noqa: E402
from paddle_tpu.serving.decode_engine import (DecodeEngine,  # noqa: E402
                                              GenerationBatcher)
from paddle_tpu.utils.error import ConfigError  # noqa: E402

BLOCK = 4


def tiny(**over):
    """hidden 64, layers KDA KDA KDA MLA, 16 experts top-4 (all held unless
    ``expert_parallel`` says otherwise), as a published config.json."""
    with open(os.path.join(ROOT, "benchmark", "testdata", "configs",
                           "tiny-hybrid.json")) as f:
        hf = json.load(f)
    hf.pop("expert_parallel")
    hf.update(num_experts=16, vocab_size=97)
    hf["serving"] = dict(hf["serving"], kv_block_size=BLOCK)
    hf.update(over)
    return hf


@pytest.fixture(scope="module")
def hf():
    return tiny()


@pytest.fixture(scope="module")
def params(hf):
    return serve_hybrid.make_params(hf, 11)


def run_chunks(hf, params, seqs, kk, cache=None, start=None):
    """Feed ``seqs`` through ``decode_chunk`` ``kk`` lanes at a time (row i
    one lane fewer, so that rows never move in step) -> (logits at every
    row's last position, cache)."""
    cfg = hybrid_lm.config_from_hf(hf)
    n = len(seqs)
    nb_row = -(-max(map(len, seqs)) // BLOCK) + 1
    tables = jnp.asarray(np.arange(1, n * nb_row + 1, dtype=np.int32)
                         .reshape(n, nb_row))
    if cache is None:
        cache = hybrid_lm.init_cache(cfg, n, n * nb_row + 1, BLOCK)
    step = jax.jit(lambda p, c, t, pos, l: hybrid_lm.decode_chunk(
        p, cfg, t, pos, l, c, tables))
    cursor = list(start or [0] * n)
    last = [None] * n
    while any(c < len(s) for c, s in zip(cursor, seqs)):
        tok = np.zeros((n, kk), np.int32)
        pos, lens = np.zeros(n, np.int32), np.ones(n, np.int32)
        fed = [0] * n
        for i, s in enumerate(seqs):
            if cursor[i] >= len(s):     # done: idle past the end
                tok[i, 0], pos[i] = s[-1], len(s)
                continue
            piece = s[cursor[i]:cursor[i] + max(1, kk - i % 2)]
            tok[i, :len(piece)], pos[i], lens[i] = piece, cursor[i], \
                len(piece)
            fed[i] = len(piece)
        logits, new = step(params, cache, tok, pos, lens)
        # a row that has nothing to feed must idle on SOME lane (lengths
        # are at least 1); the test puts its slot-addressed state back,
        # as the engine never idles a seated slot
        idle = jnp.asarray([f == 0 for f in fed])
        cache = jax.tree_util.tree_map(
            lambda kind, old, now: jnp.where(
                idle.reshape((n,) + (1,) * (now.ndim - 1)), old, now)
            if kind == "slot" else now,
            hybrid_lm.cache_kinds(cfg), cache, new)
        for i in range(n):
            cursor[i] += fed[i]
            if fed[i] and cursor[i] == len(seqs[i]):
                last[i] = np.asarray(logits[i])
    return last, cache


def prompts(lengths, seed=0, vocab=97):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, n).tolist() for n in lengths]


@pytest.mark.parametrize("kk", [1, 5, 8])
def test_served_path_matches_reference(hf, params, kk):
    """Prefill in chunks of K, then decode, through the state and the
    latent pool: logits against the reference's full forward."""
    seqs = prompts([21, 37])
    last, cache = run_chunks(hf, params, seqs, kk)
    ids = np.zeros((2, 40), np.int32)
    for step in range(3):           # the prompt's end, then two decode steps
        for i, s in enumerate(seqs):
            ids[i, :len(s)] = s
        want, _ = reference.logits(serve_hybrid.reference_params(params, hf),
                                   jnp.asarray(ids), hf)
        for i, s in enumerate(seqs):
            np.testing.assert_allclose(last[i], np.asarray(want)[i,
                                                                 len(s) - 1],
                                       atol=2e-4)
        grown = [s + [int(l.argmax())] for s, l in zip(seqs, last)]
        last, cache = run_chunks(hf, params, grown, 1, cache,
                                 [len(s) for s in seqs])
        seqs = grown


@pytest.mark.parametrize("kk", [3, 8])
def test_chunked_prefill_equals_one_pass(hf, params, kk):
    seqs = prompts([16, 16], seed=3)
    one, _ = run_chunks(hf, params, seqs, 17)
    many, _ = run_chunks(hf, params, seqs, kk)
    for a, b in zip(one, many):
        np.testing.assert_allclose(a, b, atol=2e-4)


@pytest.mark.parametrize("shares", [2, 4])
def test_shares_add_up_to_the_uncut_layer(hf, params, shares):
    """The routed parts that all the shares give, plus the shared expert
    counted once, are the uncut reference layer."""
    mc = hybrid_lm.config_from_hf(hf)
    layer = next(lp for lp, (_a, f) in zip(params["layers"], mc.layers)
                 if f == "moe")["ffn"]
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
        want, _ = reference.moe(x, ref_layer, hf)
    np.testing.assert_allclose(total, want, atol=1e-4)


@pytest.mark.parametrize("valid", [None, "half"])
def test_routed_layer_matches_all_experts_einsum(hf, params, valid):
    """Sorted by expert and grouped, against every expert on every token
    weighed by dense gates (the old ``moe_ffn`` formulation)."""
    mc = hybrid_lm.config_from_hf(hf)
    layer = params["layers"][1]["ffn"]
    x = jax.random.normal(jax.random.PRNGKey(6), (20, mc.hidden_size))
    idx, w = moe.sigmoid_router(x, layer["router"], layer["router_bias"],
                                mc.top_k, mc.routed_scale)
    mask = None if valid is None else jnp.arange(20) % 2 == 0
    got = moe.routed_experts(x, idx, w, layer, mc.held, valid=mask)
    gates = (jax.nn.one_hot(idx, mc.router_width) * w[..., None]).sum(1)
    h = jax.nn.silu(jnp.einsum("nd,edf->nef", x, layer["wg"])) \
        * jnp.einsum("nd,edf->nef", x, layer["wu"])
    want = jnp.einsum("nef,efd,ne->nd", h, layer["wd"], gates)
    if mask is not None:
        want = jnp.where(mask[:, None], want, 0.0)
    np.testing.assert_allclose(got, want, atol=1e-4)


@pytest.mark.parametrize("first", ["fresh", "stale"])
def test_reseated_slot_starts_from_zero_state(hf, params, first):
    """A row whose chunk starts at position 0 computes what a fresh cache
    computes, whatever the previous occupant left in the slot."""
    seqs = prompts([13, 9], seed=8)
    cache = None
    if first == "stale":
        _, cache = run_chunks(hf, params, prompts([19, 23], seed=9), 8)
        assert all(float(jnp.abs(c["state"]).max()) > 0
                   for c in cache if "state" in c)
    got, _ = run_chunks(hf, params, seqs, 8, cache)
    want, _ = run_chunks(hf, params, seqs, 8)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


@pytest.mark.parametrize("garbage", [1, 50])
def test_lanes_past_lengths_change_nothing(hf, params, garbage):
    """What sits in the lanes at or past a row's ``lengths`` reaches neither
    the logits nor the state, the convolution tail or the latent pool."""
    cfg = hybrid_lm.config_from_hf(hf)
    tables = jnp.asarray(np.arange(1, 9, dtype=np.int32).reshape(2, 4))
    cache = hybrid_lm.init_cache(cfg, 2, 9, BLOCK)
    tok = np.asarray(prompts([8, 8], seed=4), np.int32)
    pos, lens = np.zeros(2, np.int32), np.asarray([3, 5], np.int32)
    step = jax.jit(lambda t: hybrid_lm.decode_chunk(params, cfg, t, pos,
                                                    lens, cache, tables))
    base_logits, base_cache = step(tok)
    other = tok.copy()
    other[0, 3:], other[1, 5:] = garbage, garbage
    logits, new_cache = step(other)
    np.testing.assert_array_equal(base_logits, logits)
    for a, b in zip(jax.tree_util.tree_leaves(base_cache),
                    jax.tree_util.tree_leaves(new_cache)):
        np.testing.assert_array_equal(a, b)
    # and a state leaf moved at all only for rows that had lanes
    assert float(jnp.abs(new_cache[0]["state"]).max()) > 0


@pytest.mark.parametrize("s,kk,heads,dk,hp", [(3, 8, 4, 16, 2),
                                              (2, 16, 8, 8, 8),
                                              (5, 4, 2, 32, 1)])
def test_kda_kernel_interpreted_matches_scan(s, kk, heads, dk, hp):
    ks = jax.random.split(jax.random.PRNGKey(s), 8)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q, k = (unit(jax.random.normal(ks[i], (s, kk, heads, dk)))
            for i in (0, 1))
    v = jax.random.normal(ks[2], (s, kk, heads, dk))
    a = jax.random.uniform(ks[3], (s, kk, heads, dk), minval=0.3)
    beta = jax.random.uniform(ks[4], (s, kk, heads))
    state = jax.random.normal(ks[5], (s, heads, dk, dk))
    lengths = jax.random.randint(ks[6], (s,), 1, kk + 1).at[0].set(1)
    fresh = jnp.zeros((s,), bool).at[1].set(True)
    args = (q, k, v, a, beta, state, lengths, fresh)
    o, st = kda_kernel.kda_chunk(*args, hp=hp, interpret=True)
    o_want, st_want = kda.recurrence_scan(*args)
    np.testing.assert_allclose(o, o_want, atol=1e-5)
    np.testing.assert_allclose(st, st_want, atol=1e-5)
    # a decode row moved its state by one lane only; a fresh row forgot
    assert float(jnp.abs(o[0, 1:]).max()) == 0.0
    assert kda_kernel.heads_per_program(16, 32) == 8


def test_kda_kernel_guard_names_its_reason():
    assert "pallas_decode" in kda_kernel.decline_reason(16, 32, 128, 128)
    assert kda_kernel.shape_problem(16, 32, 128, 128) is None
    assert "lanes" in kda_kernel.shape_problem(12, 32, 128, 128)
    assert "heads" in kda_kernel.shape_problem(16, 6, 128, 128, hp=4)


REFUSED = {
    "prefix_cache": dict(prefix_cache=True),
    "speculate_k": dict(speculate_k=2, draft=object()),
    "kv_host_bytes": dict(kv_host_bytes=1 << 20),
    "mesh": dict(mesh=object()),
    "slab": dict(kv_layout="slab"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_state_holding_model_refuses(hf, params, what):
    """Each names what is missing instead of serving wrong tokens."""
    kw = dict(num_slots=2, max_len=32, kv_layout="paged",
              kv_block_size=BLOCK, prefix_cache=False, prefill_chunk=4,
              warm=False)
    kw.update(REFUSED[what])
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(hf))
    with pytest.raises(ConfigError) as e:
        DecodeEngine(params, model=model, **kw)
    needle = {"slab": "paged layout",
              "mesh": "placement rule"}.get(what, "state snapshot")
    assert needle in str(e.value)


@pytest.fixture(scope="module")
def served(hf, params):
    """(model, engine): the hybrid trunk behind a two-slot engine."""
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(hf))
    return model, DecodeEngine(
        params, model=model, num_slots=2, max_len=64, kv_layout="paged",
        kv_block_size=BLOCK, prefix_cache=False, prefill_chunk=8, name="hy")


def test_engine_serves_the_hybrid_trunk(hf, params, served):
    """Through DecodeEngine -> GenerationBatcher with more requests than
    slots: every stream is the reference's greedy continuation, the step
    traced once, slots reseated, the gauges and the counter set."""
    model, engine = served
    assert engine.kda_kernels is False
    assert "pallas_decode" in engine.kda_decline_reason
    reqs = prompts([21, 5, 30, 11], seed=2)
    engine.record_steps(True)
    with GenerationBatcher(engine, default_max_tokens=4) as gen:
        outs = [f.result(120) for f in
                [gen.submit(p, max_tokens=4) for p in reqs]]
    ref_params = serve_hybrid.reference_params(params, hf)
    for prompt, out in zip(reqs, outs):
        seq = list(prompt)
        for tok in out["tokens"]:
            want, _ = reference.logits(ref_params,
                                       jnp.asarray([seq], jnp.int32), hf)
            row = np.asarray(want)[0, -1]
            assert row.max() - row[tok] < 1e-4
            seq.append(tok)
    # what the server's own steps chose, for a check to hand its reference
    steps = engine.recorded_steps()
    assert len(steps) == engine.metrics.decode_steps_total
    tokens, pos, lens, chosen = steps[0]
    assert tokens.shape == (2, 8) and pos.shape == lens.shape == (2,)
    assert chosen.shape == (3, 2, 8, 4)         # expert layers, S, K, top_k
    engine.record_steps(False)
    assert engine.recorded_steps() == []
    m = engine.metrics
    assert engine.step_trace_count == 1
    assert m.state_resets_total == 4
    cache = model.init_cache(2, engine._paged.pool.num_blocks, BLOCK)
    assert m.recurrent_state_bytes == sum(
        c[k].size * 4 for c in cache for k in c if k != "latent")
    assert m.latent_pool_bytes == sum(c["latent"].size * 4 for c in cache
                                      if "latent" in c)
    text = m.render_prometheus()
    for name in ("recurrent_state_bytes", "latent_pool_bytes",
                 "state_resets_total"):
        assert name in text


def test_recorded_steps_hold_what_each_step_was_really_fed(served):
    """With a step in flight a decoding row's token goes from one step to
    the next on the device.  The log shows it all the same: one entry a
    device step, lane 0 as the device fed it, and that step's OWN report
    of its experts (a check hands both to its reference)."""
    from paddle_tpu.serving import ServingMetrics
    _model, engine = served
    engine.metrics = ServingMetrics()
    reqs = prompts([9, 19, 6], seed=5)
    engine.record_steps(True)
    with GenerationBatcher(engine) as gen:
        outs = [f.result(120)["tokens"] for f in
                [gen.submit(p, max_tokens=6) for p in reqs]]
    steps = engine.recorded_steps()
    engine.record_steps(False)
    m = engine.metrics
    assert len(steps) == m.decode_steps_total
    assert m.decode_steps_overlapped_total > 0.8 * m.decode_steps_total
    streams, seat = [], {}
    for tokens, pos, lens, chosen in steps:
        assert (tokens[:, 0] >= 0).all()        # no pick left unresolved
        assert np.asarray(chosen).shape == (3, 2, 8, 4)
        for slot in range(2):
            if pos[slot] + lens[slot] <= 1:
                continue                        # a free slot idling
            if pos[slot] == 0:
                seat[slot] = {}
                streams.append(seat[slot])
            for j in range(int(lens[slot])):
                seat[slot][int(pos[slot]) + j] = int(tokens[slot, j])
    # every stream was fed its prompt and then each token it was streamed
    # but the last, position by position
    fed = sorted([t for _p, t in sorted(s.items())] for s in streams)
    assert fed == sorted(list(p) + o[:-1] for p, o in zip(reqs, outs))
    assert len({id(chosen) for *_fed, chosen in steps}) == len(steps)
    assert engine.step_trace_count == 1


# ----------------------------------------------- the packed lanes (PR 32)

def _family(name):
    """(hf, parameters) of a tiny configuration under benchmark/testdata:
    ``tiny-hybrid`` (KDA + MLA + experts) or ``tiny-pangu`` (MLA in every
    layer, a low-rank query, rotation, post-norms)."""
    with open(os.path.join(ROOT, "benchmark", "testdata", "configs",
                           name + ".json")) as f:
        hf = json.load(f)
    return hf, serve_hybrid.make_params(hf, 11)


@pytest.fixture(scope="module", params=["tiny-hybrid", "tiny-pangu"])
def mixed_step(request):
    """One step of six rows of sixteen lanes over a cache that already holds
    something: a row prefilling all 16 lanes from position 0, a row whose
    chunk is three lanes ending inside a block, two rows decoding one lane
    deep in their contexts, two free rows (one lane, position 0, the scratch
    block).  23 live lanes of 96: every width of the ladder holds them."""
    hf, params = _family(request.param)
    cfg = hybrid_lm.config_from_hf(hf)
    s, kk, block, nb_row = 6, 16, 16, 3
    lens = np.asarray([16, 3, 1, 1, 1, 1], np.int32)
    pos = np.asarray([0, 16, 37, 5, 0, 0], np.int32)
    tables = np.zeros((s, nb_row), np.int32)
    tables[:4] = np.arange(1, 4 * nb_row + 1).reshape(4, nb_row)
    rng = np.random.RandomState(3)
    tok = rng.randint(1, cfg.vocab_size, (s, kk)).astype(np.int32)
    cache = jax.tree_util.tree_map(
        lambda x: jnp.asarray(0.1 * rng.randn(*x.shape), x.dtype),
        hybrid_lm.init_cache(cfg, s, 4 * nb_row + 1, block))
    args = (params, cfg, tok, pos, lens, cache, jnp.asarray(tables))
    whole = jax.jit(lambda: hybrid_lm.decode_chunk(*args, with_routes=True))()
    return args, whole


@pytest.mark.parametrize("width", [24, 48, 96])
def test_every_width_gives_what_the_whole_width_gives(mixed_step, width):
    """``decode_chunk`` packed at each width of the ladder (6 x 16 lanes: a
    quarter, a half, the whole) against the unpacked call: the seated rows'
    logits, what they wrote into the cache, the live lanes' experts."""
    args, (want, want_cache, want_routes) = mixed_step
    lens = args[4]
    assert hybrid_lm.step_widths(6, 16) == (24, 48, 96)
    src, back = hybrid_lm.pack_lanes(lens, 16)
    got, cache, routes = jax.jit(lambda s, b: hybrid_lm.decode_chunk(
        *args, with_routes=True, packing=(s, b)))(src[:width], back)
    np.testing.assert_allclose(got[:4], want[:4], atol=2e-5)
    for kinds, new, ref in zip(hybrid_lm.cache_kinds(args[1]), cache,
                               want_cache):
        for name, kind in kinds.items():
            # slot leaves by row, block leaves past the scratch block
            keep = slice(0, 4) if kind == "slot" else slice(1, None)
            np.testing.assert_allclose(new[name][keep], ref[name][keep],
                                       atol=2e-5)
    live = np.arange(16)[None, :] < lens[:, None]
    assert len(routes) == len(want_routes) > 0
    for r, w in zip(routes, want_routes):
        assert r.shape == (6, 16, args[1].top_k)
        np.testing.assert_array_equal(np.asarray(r)[live],
                                      np.asarray(w)[live])


def test_pack_lanes_lays_live_lanes_side_by_side():
    lens = np.asarray([3, 1, 4, 1])
    src, back = hybrid_lm.pack_lanes(lens, 4)
    # rows in order, a row's lanes in order, the tail repeating the last
    assert src.tolist() == [0, 1, 2, 4, 8, 9, 10, 11, 12] + [12] * 7
    assert back.tolist() == [[0, 1, 2, 2], [3, 3, 3, 3], [4, 5, 6, 7],
                             [8, 8, 8, 8]]
    # a place is a lane's own where the lane points back at it
    own = back.reshape(-1)[src] == np.arange(16)
    assert own.tolist() == [True] * 9 + [False] * 7


@pytest.mark.parametrize("live,width", [(4, 8), (8, 8), (9, 16), (16, 16),
                                        (17, 32), (32, 32)])
def test_step_takes_the_narrowest_width_that_holds_its_lanes(hf, live,
                                                             width):
    """4 rows x 8 lanes: a quarter, a half, the whole.  No row seated is
    four lanes (a free slot feeds one); the edges fall on either side."""
    widths = hybrid_lm.step_widths(4, 8)
    assert widths == (8, 16, 32)
    lens = np.ones(4, np.int32)
    for r in range(4):              # fill rows until ``live`` lanes are fed
        lens[r] += min(7, live - lens.sum())
    assert lens.sum() == live
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(hf))
    assert model.step_widths(4, 8) == widths
    src, back = model.pack(lens, 8)
    assert src.shape == (width,) and back.shape == (4, 8)
    # every live lane has its place, and points back at it
    assert sorted(set(src.tolist())) == np.flatnonzero(
        np.arange(8)[None, :] < lens[:, None]).tolist()
    assert (back.reshape(-1)[src[:live]] == np.arange(live)).all()
    assert model.pack(lens, 8, 32)[0].shape == (32,)
    # K under four lanes: a quarter would not hold a lane of every row
    assert hybrid_lm.step_widths(4, 2) == (4, 8)
    assert hybrid_lm.step_widths(4, 1) == (4,)


def _drive(engine, reqs, max_tokens=5):
    with GenerationBatcher(engine, default_max_tokens=max_tokens) as gen:
        return [f.result(120)["tokens"] for f in
                [gen.submit(p, max_tokens=max_tokens) for p in reqs]]


def test_narrow_steps_stream_what_wide_steps_stream(hf, params, monkeypatch):
    """The same requests through an engine whose steps take the ladder and
    through one held to the whole width: token for token; each width traced
    once, at warm-up, whatever the churn (five requests over three slots:
    admission, eviction, re-seating)."""
    from paddle_tpu.obs import trace as obstrace
    from paddle_tpu.serving import ServingMetrics
    kw = dict(num_slots=3, max_len=64, kv_layout="paged",
              kv_block_size=BLOCK, prefix_cache=False, prefill_chunk=8)
    reqs = prompts([21, 5, 30, 11, 2], seed=7)
    model = hybrid_lm.Served(hybrid_lm.config_from_hf(hf))
    engine = DecodeEngine(params, model=model, name="narrow", **kw)
    # each width traced once at warm-up: the discipline reads 1
    assert engine.step_widths == (6, 12, 24)
    assert engine.step_traces == {6: 1, 12: 1, 24: 1}
    assert engine.step_trace_count == 1
    engine.metrics = ServingMetrics()
    obstrace.enable(sample=1.0, capacity=65536)
    try:
        narrow = _drive(engine, reqs)
        phases = obstrace.debug_payload()["phases"]
    finally:
        obstrace.disable()
    assert engine.step_traces == {6: 1, 12: 1, 24: 1}
    m = engine.metrics
    # the counters and the phase say the same of every step
    stats = [ph["attrs"] for ph in phases
             if ph["name"] == "engine.step.dispatch"]
    assert len(stats) == m.decode_steps_total
    assert {st["lanes"] for st in stats} == {24}
    assert {st["width"] for st in stats} <= {6, 12, 24}
    assert all(st["live"] <= st["width"] and
               (st["width"] == 6 or st["live"] > st["width"] // 2)
               for st in stats)
    assert m.step_lanes_computed_total == sum(st["width"] for st in stats)
    assert m.step_lanes_live_total == sum(st["live"] for st in stats)
    assert m.step_lanes_live_total < m.step_lanes_computed_total \
        < 24 * m.decode_steps_total
    snap = m.snapshot()
    assert snap["step_lanes_computed_total"] == m.step_lanes_computed_total
    assert "step_lanes_live_total" in m.render_prometheus()

    monkeypatch.setattr(hybrid_lm, "STEP_WIDTH_FRACTIONS", (1,))
    wide_engine = DecodeEngine(params, model=model, name="wide", **kw)
    assert wide_engine.step_traces == {24: 1}
    wide_engine.metrics = ServingMetrics()
    assert _drive(wide_engine, reqs) == narrow
    wm = wide_engine.metrics
    assert wm.step_lanes_computed_total == 24 * wm.decode_steps_total

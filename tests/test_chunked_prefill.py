"""Chunked-prefill serving (DecodeEngine's one step, prefill_chunk = K).

Prompt ingestion rides the ONE jitted decode step: each step
advances a mix of decode rows (1 token) and admitting rows (up to K
prompt tokens, re-derived emissions swallowed until the last chunk).
The correctness bar is the slab engine's own: every greedy stream —
staggered admission, chunk boundaries, EOS, paged CoW churn, pool
pressure, supervisor recovery, continuation replay — must be
BIT-IDENTICAL to the single-request oracle
(``models/transformer.lm_generate``).  Trace discipline: ONE warm-up
trace for the chunked step (plus one block-fork executable on paged),
ZERO traces across any churn — tokens, positions, AND lane counts are
data, not shape, so the per-step chunk budget tunes without retracing.
"""

import threading
import time

import numpy as np
import pytest
import jax

from paddle_tpu.models import transformer
from paddle_tpu.ops.pallas import decode_attention as decode_kernels
from paddle_tpu.resilience import Supervisor, faults
from paddle_tpu.serving import (GenerationBatcher, InvalidRequestError,
                                ServingMetrics)
from paddle_tpu.serving.decode_engine import DecodeEngine
from paddle_tpu.testing import assert_no_retrace
from paddle_tpu.utils.error import ConfigError

VOCAB, D_MODEL, LAYERS, HEADS = 64, 32, 2, 2
MAX_LEN, SLOTS, BS, K = 48, 4, 8, 4


@pytest.fixture(autouse=True)
def _no_leaked_fault_plan():
    yield
    faults.clear()


@pytest.fixture(scope="module")
def params():
    return transformer.init(jax.random.PRNGKey(0), src_vocab=VOCAB,
                            trg_vocab=1, d_model=D_MODEL, num_heads=HEADS,
                            dff=64, enc_layers=LAYERS, dec_layers=0,
                            max_len=MAX_LEN)


@pytest.fixture(scope="module")
def rope_params():
    return transformer.init(jax.random.PRNGKey(1), src_vocab=VOCAB,
                            trg_vocab=1, d_model=D_MODEL, num_heads=HEADS,
                            dff=64, enc_layers=LAYERS, dec_layers=0,
                            max_len=MAX_LEN, pos_type="rope")


def _engine(params, **kw):
    kw.setdefault("prefill_chunk", K)
    return DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                        max_len=MAX_LEN, **kw)


def _prompt(rng, n=None):
    return rng.randint(1, VOCAB, n or rng.randint(1, 30)).astype(np.int32)


def _oracle(params, prompt, n_tokens, eos_id=None, pos_type="learned"):
    ids = np.asarray(transformer.lm_generate(
        params, prompt[None], max_len=MAX_LEN, num_heads=HEADS,
        eos_id=eos_id, prompt_lengths=np.asarray([prompt.size]),
        pos_type=pos_type))
    return ids[0, prompt.size:prompt.size + n_tokens].tolist()


def _drive(bat, cases, stagger_s=0.002):
    """Concurrent client threads (admissions land mid-decode)."""
    results, excs = [None] * len(cases), [None] * len(cases)

    def client(i):
        prompt, n = cases[i]
        try:
            time.sleep(stagger_s * i)
            results[i] = bat.submit(prompt, max_tokens=n).result(180)
        except Exception as e:      # noqa: BLE001
            excs[i] = e

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(cases))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(240)
        assert not t.is_alive(), "client thread wedged: DEADLOCK"
    return results, excs


# ----------------------------------------------------- step-level units


def test_chunk_step_matches_prefill(params):
    """Feeding a prompt through lm_decode_chunk_slots in K-token chunks
    produces the same K/V and last-position logits as the batched
    lm_prefill pass — the numerics fact the whole unified engine rests
    on.

    Bit for bit where the arithmetic is the same: layer 0's K/V are
    projections of identical inputs.  Past the first attention the two
    paths no longer round alike under the XLA of jax 0.9.0: the batched
    pass softmaxes over Tp columns, the chunk step over max_len columns
    whose masked tail contributes exp() == 0.0 exactly, and the CPU
    backend sums rows of different widths in different tree orders
    (measured: 1e-6 in layer-1 K/V, 1e-7 in the logits).  So deeper state
    and the logits are held to float32 rounding, 1e-5 — a wrong position,
    mask or lane lands at 1e-1.  The engine's own guarantee — streams
    token-identical to lm_generate — is pinned by the engine tests
    below."""
    rng = np.random.RandomState(0)
    prompt = _prompt(rng, 10)
    hidden, pc = transformer.lm_prefill(params, prompt[None], MAX_LEN,
                                        HEADS)
    h_last = np.asarray(hidden)[:, prompt.size - 1][:, None]
    ref_logits = np.asarray(transformer._lm_project(
        params, jax.numpy.asarray(h_last)))[:, 0]
    cache = transformer.init_lm_cache(params, SLOTS, MAX_LEN)
    p, out = 0, None
    while p < prompt.size:
        n = min(K, prompt.size - p)
        toks = np.zeros((SLOTS, K), np.int32)
        toks[0, :n] = prompt[p:p + n]
        lens = np.ones((SLOTS,), np.int32)
        lens[0] = n
        poss = np.zeros((SLOTS,), np.int32)
        poss[0] = p
        out, cache = transformer.lm_decode_chunk_slots(
            params, toks, poss, lens, cache, HEADS)
        p += n
    np.testing.assert_allclose(np.asarray(out)[0], ref_logits[0],
                               rtol=1e-5, atol=1e-5)
    for layer, (c, ref) in enumerate(zip(cache, pc)):
        for kv in "kv":
            got = np.asarray(c[kv])[0, :prompt.size]
            want = np.asarray(ref[kv])[0, :prompt.size]
            if layer == 0:
                assert np.array_equal(got, want), kv
            else:
                np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                           err_msg=f"layer {layer} {kv}")


# --------------------------------------------------------- engine parity


@pytest.mark.slow
def test_chunked_staggered_admissions_bit_identical(params):
    """The acceptance drive: more requests than slots, mixed prompt
    lengths (including chunk-boundary sizes 1 / K-1 / K / K+1 / 2K and
    prompts of several chunks) and mixed max_tokens,
    staggered so admissions land mid-decode — every stream equals the
    single-request oracle exactly."""
    eng = _engine(params, name="cp_slab")
    eng.metrics = ServingMetrics()
    bat = GenerationBatcher(eng, default_max_tokens=8)
    rng = np.random.RandomState(2)
    sizes = [1, K - 1, K, K + 1, 2 * K, 25, 30]
    cases = [(_prompt(rng, s), int(rng.randint(2, 10))) for s in sizes]
    cases += [(_prompt(rng), int(rng.randint(2, 10))) for _ in range(5)]
    results, excs = _drive(bat, cases)
    bat.close()
    assert all(e is None for e in excs), excs
    for (prompt, n), res in zip(cases, results):
        assert res["tokens"] == _oracle(params, prompt, n), \
            f"prompt len {prompt.size}, n {n}"
        assert res["finish_reason"] == "length"
    snap = eng.metrics.snapshot()
    assert snap["prefill_chunks_total"] >= 1
    assert snap["prefill_chunk_lanes_total"] > 0
    assert snap["prefill_chunk_size"] == K
    assert eng.free_slots == SLOTS


def test_chunked_eos_and_single_token(params):
    """EOS pinning (including an immediate first-token EOS) and
    max_tokens=1 — the finishes that land exactly at the feed-drain
    boundary."""
    eng = _engine(params, name="cp_eos")
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(3)
    prompt = _prompt(rng, 9)
    first = _oracle(params, prompt, 1)[0]
    res = bat.submit(prompt, max_tokens=20, eos_id=first).result(60)
    assert res["finish_reason"] == "eos" and res["tokens"] == [first]
    res = bat.submit(prompt, max_tokens=1).result(60)
    assert res["finish_reason"] == "length" and res["tokens"] == [first]
    want = _oracle(params, prompt, 12, eos_id=first + 1)
    res = bat.submit(prompt, max_tokens=12,
                     eos_id=first + 1).result(60)
    stop = want.index(first + 1) + 1 if first + 1 in want else 12
    assert res["tokens"] == want[:stop]
    bat.close()


def test_chunked_rope_trunk_bit_identical(rope_params):
    """The rope trunk chunks with per-lane rotary positions — streams
    stay bit-identical to the rope oracle."""
    eng = _engine(rope_params, name="cp_rope", pos_type="rope")
    bat = GenerationBatcher(eng, default_max_tokens=6)
    rng = np.random.RandomState(4)
    cases = [(_prompt(rng, s), 6) for s in (3, K, 13)]
    results, excs = _drive(bat, cases)
    bat.close()
    assert all(e is None for e in excs), excs
    for (prompt, n), res in zip(cases, results):
        assert res["tokens"] == _oracle(rope_params, prompt, n,
                                        pos_type="rope")


def test_chunked_continuation_replay_bit_identical(params):
    """PR-7 continuations ride chunks: a stream interrupted after k
    delivered tokens finishes emitting ONLY the remainder, bit-identical
    — including contexts of many chunks."""
    eng = _engine(params, name="cp_cont")
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(5)
    for plen, n, k in ((5, 10, 3), (16, 12, 7), (16, 24, 14)):
        prompt = _prompt(rng, plen)
        full = _oracle(params, prompt, n)
        res = bat.submit(prompt, replay=np.asarray(full[:k], np.int32),
                         max_tokens=n - k).result(60)
        assert res["tokens"] == full[k:], (plen, n, k)
    bat.close()


# ------------------------------------------------------------ paged


@pytest.mark.slow
def test_chunked_paged_prefix_cow_pressure_bit_identical(params):
    """The paged composition: chunked admission grows chains block by
    block, prompts register in the prefix index at first emission,
    duplicates seat by reference and CoW-fork on their first write,
    and a deliberately tight pool preempts + re-seats — every stream
    bit-identical, ledger balanced."""
    eng = _engine(params, name="cp_paged", kv_layout="paged",
                  kv_block_size=BS)
    eng.metrics = ServingMetrics()
    bat = GenerationBatcher(eng, default_max_tokens=6)
    rng = np.random.RandomState(6)
    sysp = _prompt(rng, BS + BS // 2)
    div = np.concatenate([sysp[:BS], _prompt(rng, 4)])
    lead = bat.submit(sysp, max_tokens=6).result(60)
    dup = bat.submit(sysp, max_tokens=6).result(60)
    dv = bat.submit(div, max_tokens=6).result(60)
    bat.close()
    assert lead["tokens"] == dup["tokens"] == _oracle(params, sysp, 6)
    assert dv["tokens"] == _oracle(params, div, 6)
    snap = eng.metrics.snapshot()
    assert snap["prefix_cache_hits_total"] == 2
    assert snap["cow_forks_total"] >= 1
    eng._paged.check()
    assert eng.free_slots == SLOTS

    # deterministic pool pressure (tight pool, tight-loop submits)
    eng2 = _engine(params, name="cp_tight", kv_layout="paged",
                   kv_block_size=BS, kv_num_blocks=10)
    bat2 = GenerationBatcher(eng2, default_max_tokens=16)
    cases = [(_prompt(rng, 16), 16) for _ in range(6)]
    futs = [bat2.submit(p, max_tokens=n) for p, n in cases]
    results = [f.result(300) for f in futs]
    bat2.close()
    for (prompt, n), res in zip(cases, results):
        assert res["tokens"] == _oracle(params, prompt, n)
    s2 = eng2.metrics.snapshot()
    assert s2["evictions"]["pool_exhausted"] >= 1, s2
    assert s2["slot_reprefills_total"] >= 1, s2
    eng2._paged.check()


# ----------------------------------------------------- trace discipline


def test_one_warmup_trace_zero_retraces_under_chunk_churn(params):
    """ONE step trace at warm-up (the engine compiles no admission
    write and no prefill program; paged adds only the block-fork
    executable), then ZERO traces across admission churn,
    varying chunk lane counts, budget throttling, prefix hits, CoW
    forks and pool preemption — lane counts are data, not shape."""
    for layout, extra in (("slab", {}),
                          ("paged", {"kv_block_size": BS,
                                     "kv_num_blocks": 12})):
        eng = _engine(params, name=f"cp_trace_{layout}",
                      kv_layout=layout, prefill_chunk_budget=5, **extra)
        assert eng.step_trace_count == 1
        rng = np.random.RandomState(7)
        shared = _prompt(rng, BS + 2)
        counters = [lambda: eng.step_trace_count]
        if layout == "paged":
            assert eng._copy_traces[0] == 1
            assert eng._write_traces[0] == 0    # never compiled
            counters.append(lambda: eng._copy_traces[0])
        with assert_no_retrace(
                lambda: sum(c() for c in counters),
                f"chunked churn ({layout}: admit/chunk/budget/CoW)"):
            bat = GenerationBatcher(eng, default_max_tokens=8)
            cases = [(shared, 8), (shared, 8)]
            cases += [(_prompt(rng), int(rng.randint(2, 13)))
                      for _ in range(6)]
            results, excs = _drive(bat, cases)
            bat.close()
        assert all(e is None for e in excs), excs


def test_chunk_budget_bounds_per_step_lanes(params):
    """prefill_chunk_budget=B: no step ever feeds more than B
    teacher-forced lanes across all slots (the per-step prefill bound
    that keeps TPOT flat), and streams stay bit-identical."""
    budget = 3

    class Spy(ServingMetrics):
        max_lanes = 0

        def observe_decode_step(self, n_active, n_slots, seconds,
                                prefill_lanes=0):
            Spy.max_lanes = max(Spy.max_lanes, prefill_lanes)
            super().observe_decode_step(n_active, n_slots, seconds,
                                        prefill_lanes)

    eng = _engine(params, name="cp_budget", prefill_chunk_budget=budget)
    eng.metrics = Spy()
    bat = GenerationBatcher(eng, default_max_tokens=5)
    rng = np.random.RandomState(8)
    cases = [(_prompt(rng, 20), 5) for _ in range(6)]
    results, excs = _drive(bat, cases, stagger_s=0.0)
    bat.close()
    assert all(e is None for e in excs), excs
    for (prompt, n), res in zip(cases, results):
        assert res["tokens"] == _oracle(params, prompt, n)
    assert 0 < Spy.max_lanes <= budget


# --------------------------------------------------- fused chunk kernels


@pytest.mark.slow
def test_chunked_with_fused_kernels_token_identical(params):
    """pallas_decode=always compiles the Tq=chunk kernels INTO the
    unified step (interpret mode on CPU): greedy streams must be
    TOKEN-identical to the oracle on both layouts, still 1 trace."""
    rng = np.random.RandomState(9)
    cases = [(_prompt(rng), int(rng.randint(2, 9))) for _ in range(6)]
    for layout in ("slab", "paged"):
        with decode_kernels.forced_mode("always"):
            eng = _engine(params, name=f"cp_k_{layout}",
                          kv_layout=layout, kv_block_size=BS)
            assert eng.decode_kernels
            bat = GenerationBatcher(eng, default_max_tokens=8)
            results, excs = _drive(bat, cases)
            bat.close()
        assert all(e is None for e in excs), excs
        for (prompt, n), res in zip(cases, results):
            assert res["tokens"] == _oracle(params, prompt, n), layout
        assert eng.step_trace_count == 1


# ------------------------------------------------- supervisor recovery


@pytest.mark.slow
def test_supervisor_recovery_rides_chunks_bit_identical(params):
    """PR-6 chaos on the chunked engine: an injected decode-step fault
    rebuilds the pool and re-seats every in-flight stream through
    CHUNKED seating (whole contexts as K-lane feeds, not one token a
    step) — all streams bit-identical, zero extra
    traces, ledger balanced."""
    eng = _engine(params, name="cp_chaos", kv_layout="paged",
                  kv_block_size=BS)
    eng.metrics = ServingMetrics()
    rng = np.random.RandomState(10)
    cases = [(_prompt(rng), 4 + (i % 5)) for i in range(8)]
    ref = [_oracle(params, p, n) for p, n in cases]
    sup = Supervisor(breaker_threshold=10)
    bat = GenerationBatcher(eng, supervisor=sup)
    faults.install_spec("serving.decode_step:at=6")
    with assert_no_retrace(lambda: eng.step_trace_count,
                           "chunked chaos recovery"):
        results, excs = _drive(bat, cases)
        bat.close()
    assert faults.fired_counts() == {"serving.decode_step": 1}
    faults.clear()
    assert all(e is None for e in excs), excs
    assert [r["tokens"] for r in results] == ref
    snap = eng.metrics.snapshot()
    assert snap["evictions"]["recovered"] >= 1
    assert snap["slot_reprefills_total"] >= 1
    eng._paged.check()


# ------------------------------------------------ one step in flight


def _nothing_in_flight(eng):
    return eng._last_step is None or eng._last_step.done


def test_pool_preemption_with_a_step_in_flight_bit_identical(params):
    """A pool too tight for its traffic takes slots from requests whose
    last token is still on the device; that token reaches the client, the
    request re-seats from what was delivered (with nothing in flight:
    the iteration that re-seats reads first), and every stream, chunk
    boundaries and all, is the oracle's."""
    eng = _engine(params, name="cp_tight_fly", kv_layout="paged",
                  kv_block_size=BS, kv_num_blocks=10)
    eng.metrics = ServingMetrics()
    bat = GenerationBatcher(eng, default_max_tokens=16)
    seen_at_reseat = []
    orig = bat._reseat_preempted

    def spy():
        if bat._preempted and eng.free_slots:
            seen_at_reseat.append(_nothing_in_flight(eng))
        return orig()
    bat._reseat_preempted = spy
    rng = np.random.RandomState(31)
    cases = [(_prompt(rng, n), 14) for n in (16, 13, 12, 9, 16)]
    futs = [bat.submit(p, max_tokens=n) for p, n in cases]
    results = [f.result(300) for f in futs]
    bat.close()
    for (prompt, n), res in zip(cases, results):
        assert res["tokens"] == _oracle(params, prompt, n), prompt.size
    snap = eng.metrics.snapshot()
    assert snap["evictions"]["pool_exhausted"] >= 1, snap
    assert snap["slot_reprefills_total"] >= 1, snap
    assert snap["decode_steps_overlapped_total"] > 0
    assert seen_at_reseat and all(seen_at_reseat)
    assert eng.step_trace_count == 1
    eng._paged.check()
    assert eng.free_slots == SLOTS


@pytest.mark.parametrize("what", ["speculating", "export", "restore"])
def test_host_work_on_the_cache_reads_the_step_in_flight_first(params, what):
    """Where host work needs committed tokens or a quiescent cache the
    loop reads the step in flight before going on — a draft trunk's
    acceptance, a cross-replica export, a host-tier restore commit — and
    hands steps over ahead of the read again once it is done.  Streams
    are the oracle's throughout."""
    kw = dict(name=f"cp_drain_{what}", kv_layout="paged", kv_block_size=BS,
              prefill_chunk=8)
    if what == "speculating":
        from paddle_tpu.serving.speculative import make_draft
        kw.update(speculate_k=2, draft=make_draft(params, layers=1))
    if what == "restore":
        kw.update(kv_num_blocks=2 * (MAX_LEN // BS) + 1,
                  kv_host_bytes=64 << 20)
    eng = _engine(params, **kw)
    eng.metrics = ServingMetrics()
    quiet = []
    if what == "export":
        orig = eng.export_chain
        eng.export_chain = lambda toks: (
            quiet.append(_nothing_in_flight(eng)), orig(toks))[1]
    if what == "restore":
        orig = eng._paged.commit_pending

        def commit(key, covered):
            quiet.append(_nothing_in_flight(eng))
            return orig(key, covered)
        eng._paged.commit_pending = commit
    bat = GenerationBatcher(eng, default_max_tokens=6)
    rng = np.random.RandomState(32)
    shared, long_prompt = _prompt(rng, 4 * BS), _prompt(rng, 4)
    first = bat.submit(shared, max_tokens=6).result(60)["tokens"]
    assert first == _oracle(params, shared, 6)
    if what == "restore":
        for _ in range(4):              # churn the shared chain out
            bat.submit(_prompt(rng, 28), max_tokens=4).result(60)
        assert eng._paged.lookup_prefix(shared)[0] == 0
        assert eng.host_tier.covers(tuple(int(t) for t in shared))
    streamed = []
    long_one = bat.submit(long_prompt, max_tokens=34,
                          on_token=streamed.append)
    while len(streamed) < 3:            # it decodes, a step in flight
        time.sleep(0.001)
    if what == "export":
        key, covered, blob = bat.export_chain(shared)
        assert covered == 4 * BS and blob
    again = bat.submit(shared, max_tokens=6).result(60)["tokens"]
    assert again == first
    assert long_one.result(60)["tokens"] == _oracle(params, long_prompt, 34)
    bat.close()
    snap = eng.metrics.snapshot()
    if what == "speculating":
        assert snap["decode_steps_overlapped_total"] == 0
        assert snap["spec_steps_total"] > 0
    else:
        assert quiet and all(quiet), quiet
        assert snap["decode_steps_overlapped_total"] \
            > 0.5 * snap["decode_steps_total"], snap
    if what == "restore":
        assert snap["kv_restore_hits_total"] == 1, snap
    assert eng.step_trace_count == 1
    eng._paged.check()
    assert eng.free_slots == SLOTS


# --------------------------------------------------------- validation


def test_chunked_validation_and_config(params):
    eng = _engine(params, name="cp_val", warm=False)
    # a prompt of many chunks is fine...
    eng.validate_request(np.arange(1, 31, dtype=np.int32), 8)
    # ...and max_len bounds prompt + emission
    with pytest.raises(InvalidRequestError, match="max_len"):
        eng.validate_request(np.arange(1, 41, dtype=np.int32), 10)
    with pytest.raises(ConfigError, match="prefill_chunk"):
        _engine(params, name="cp_bad", prefill_chunk=-1, warm=False)
    with pytest.raises(ConfigError, match="prefill_chunk"):
        _engine(params, name="cp_bad2", prefill_chunk=MAX_LEN + 1,
                warm=False)


# ----------------------------------------------------------- metrics


def test_chunked_metrics_surface(params):
    """The new /metrics surface: chunk counters, occupancy, TPOT jitter
    — in both the snapshot and the Prometheus rendering."""
    eng = _engine(params, name="cp_metrics")
    eng.metrics = ServingMetrics()
    bat = GenerationBatcher(eng, default_max_tokens=6)
    rng = np.random.RandomState(11)
    futs = [bat.submit(_prompt(rng, 20), max_tokens=6) for _ in range(4)]
    for f in futs:
        f.result(60)
    bat.close()
    snap = eng.metrics.snapshot()
    # each 20-token prompt feeds 19 tokens; at K-1 = 3 loaded lanes per
    # chunk that is >= 5 chunks and >= 10 loaded lanes per request
    assert snap["prefill_chunks_total"] >= 4 * 5
    assert snap["prefill_chunk_lanes_total"] >= 4 * 10
    assert snap["prefill_chunk_size"] == K
    assert snap["mean_prefill_chunk_occupancy"] > 0
    assert snap["tpot_jitter_p99_p50"] >= 1.0
    text = eng.metrics.render_prometheus()
    n = eng.metrics.name
    assert f"{n}_prefill_chunks_total " in text
    assert f"{n}_prefill_chunk_lanes_total " in text
    assert f"{n}_prefill_chunk_size {K}" in text
    assert f"{n}_prefill_chunk_occupancy_mean " in text
    assert f"{n}_tpot_jitter_p99_p50 " in text


# ------------------------------------------------- prefill flash gate


def test_prefill_flash_no_score_matrix_and_reverse():
    """The analytic acceptance gate's core: lm_prefill routed through
    flash holds NO [Tp, Tp] float buffer in its compiled HLO, and the
    masked XLA reference TRIPS the same detector (the gate works in
    both directions).  Tp is large enough that flash really blocks —
    a single-block run would legitimately hold a [Tp, Tp] tile."""
    import importlib

    import jax.numpy as jnp

    from paddle_tpu.perf import analytic

    flash_mod = importlib.import_module(
        "paddle_tpu.ops.pallas.flash_attention")
    tp = 640
    p = transformer.init(jax.random.PRNGKey(2), src_vocab=VOCAB,
                         trg_vocab=1, d_model=64, dff=64, enc_layers=1,
                         dec_layers=0, max_len=tp, num_heads=1)
    spec = jax.ShapeDtypeStruct((1, tp), jnp.int32)

    def lower():
        # fresh closure per mode: the routing is read at trace time and
        # jax caches traces on the function object
        def fn(prompt):
            return transformer.lm_prefill(p, prompt, tp, 1)
        return jax.jit(fn).lower(spec).compile().as_text()

    with flash_mod.forced_prefill_mode("always"):
        analytic.assert_prefill_flash(lower(), tp)
    with flash_mod.forced_prefill_mode("off"):
        hits = analytic.score_matrix_instrs(lower(), tp, tp)
    assert hits, "detector failed to flag the masked XLA prefill"
    with pytest.raises(AssertionError, match="score matrix"):
        with flash_mod.forced_prefill_mode("off"):
            analytic.assert_prefill_flash(lower(), tp)


def test_prefill_flash_numerics_close(params):
    """Flash-routed prefill is numerically equivalent to the masked
    reference (not bit-identical — the online softmax accumulates
    differently, which is why the CPU tier-1 default keeps the
    reference path and the flag is trace-time opt-in)."""
    import importlib
    flash_mod = importlib.import_module(
        "paddle_tpu.ops.pallas.flash_attention")
    rng = np.random.RandomState(12)
    prompt = _prompt(rng, 16)[None]
    with flash_mod.forced_prefill_mode("off"):
        h_ref, c_ref = transformer.lm_prefill(params, prompt, MAX_LEN,
                                              HEADS)
    with flash_mod.forced_prefill_mode("always"):
        h_fl, c_fl = transformer.lm_prefill(params, prompt, MAX_LEN,
                                            HEADS)
    np.testing.assert_allclose(np.asarray(h_ref), np.asarray(h_fl),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(c_ref[0]["k"]),
                               np.asarray(c_fl[0]["k"]),
                               rtol=2e-5, atol=2e-5)

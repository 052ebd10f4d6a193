"""Continuous-batching generation serving (serving/decode_engine.py).

The correctness bar mirrors test_serving.py's: a request served through
the full stack — queue, slot admission, chunked ingestion through the
shared step, eviction — must return EXACTLY the tokens the single-request
oracle (``models/transformer.lm_generate``, greedy) produces for that
prompt.  Every linear layer in the decode path is batched over the
leading slot axis, so a row's numerics do not depend on what the other
slots hold; greedy outputs are therefore bit-identical token for token,
across staggered admissions, mixed prompt lengths, and slot reuse after
eviction.

Trace discipline: the step traces exactly ONCE at warm-up and never
again across admission/eviction churn (the shared
``paddle_tpu.testing.trace`` assertion, same as ``InferenceEngine`` and
``SGD.precompile``).

Fault injection covers the GenerationBatcher's admission-control paths
(invalid prompt before the queue, overload, deadline), batch-failure
isolation (a step failure fails only the in-flight requests; the engine
resets and keeps serving), and both drain semantics.  The lifecycle
tests share one module-scoped engine a layout: they run on the slab and
on the paged pool, the engine every benchmark cell serves.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
import jax

from paddle_tpu.models import transformer
from paddle_tpu.resilience import faults
from paddle_tpu.serving import (BatchExecutionError, DeadlineExceededError,
                                GenerationBatcher, InvalidRequestError,
                                OverloadedError, ServingMetrics,
                                ShutdownError, make_server)
from paddle_tpu.serving.decode_engine import DecodeEngine
from paddle_tpu.testing import assert_no_retrace
from paddle_tpu.utils.error import ConfigError

VOCAB, D_MODEL, LAYERS, HEADS = 64, 32, 2, 2
MAX_LEN, SLOTS, PROMPT_TOP, BS = 48, 4, 16, 8


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


@pytest.fixture(scope="module", params=["slab", "paged"])
def engine(params, request):
    return DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                        max_len=MAX_LEN, kv_layout=request.param,
                        kv_block_size=BS, name=f"test_lm_{request.param}")


def _prompt(rng, n=None):
    return rng.randint(1, VOCAB, n or rng.randint(3, PROMPT_TOP + 1)
                       ).astype(np.int32)


def _oracle(params, prompt, n_tokens, eos_id=None, pos_type="learned"):
    """Single-request greedy lm_generate at the engine's cache width.
    The prompt is padded to a multiple of PROMPT_TOP so the oracle
    compiles a shape or two, not one per length (the pad value is
    irrelevant — lm_generate's own ragged-prompt contract)."""
    width = -(-prompt.size // PROMPT_TOP) * PROMPT_TOP
    padded = np.zeros((1, width), np.int32)
    padded[0, :prompt.size] = prompt
    ids = np.asarray(transformer.lm_generate(
        params, padded, max_len=MAX_LEN, num_heads=HEADS,
        eos_id=eos_id, prompt_lengths=np.asarray([prompt.size]),
        pos_type=pos_type))
    return ids[0, prompt.size:prompt.size + n_tokens].tolist()


# ------------------------------------------------------------ parity


def test_staggered_admissions_bit_identical_to_lm_generate(params, engine):
    """The acceptance drive: more requests than slots, mixed prompt
    lengths (under one chunk and several), mixed max_tokens, submitted in
    staggered waves so admissions land mid-decode and every slot is
    reused after eviction — each request's greedy tokens must equal the
    single-request oracle exactly."""
    engine.metrics = ServingMetrics()
    bat = GenerationBatcher(engine, default_max_tokens=8)
    rng = np.random.RandomState(1)
    cases = [(_prompt(rng), int(rng.randint(2, 13))) for _ in range(12)]
    futs = []
    for i, (prompt, n) in enumerate(cases):
        futs.append(bat.submit(prompt, max_tokens=n))
        if i % 3 == 2:
            time.sleep(0.01)        # let decode start; later admissions
            #                         churn slots mid-flight
    results = [f.result(120) for f in futs]
    bat.close()
    for (prompt, n), res in zip(cases, results):
        assert res["finish_reason"] == "length"
        assert len(res["tokens"]) == n
        assert res["tokens"] == _oracle(params, prompt, n), \
            f"prompt len {prompt.size}, n {n}"
    # 12 requests over 4 slots: every slot was reused after eviction
    snap = engine.metrics.snapshot()
    assert snap["evictions"]["length"] == 12
    assert engine.free_slots == SLOTS
    assert snap["mean_slot_occupancy"] > 1.0, snap    # real co-residency
    assert snap["ttft_ms"]["p50"] > 0
    assert snap["tpot_ms"]["p50"] > 0


def test_rope_trunk_bit_identical_to_lm_generate():
    """The per-row rope path (positions[:, None] through _rope_flat into
    rope()'s [B, T] branch) is the subtlest step code: pin the same
    bit-identity guarantee on a rope trunk (no learned table at all)."""
    rope_params = transformer.init(jax.random.PRNGKey(1), src_vocab=VOCAB,
                                   trg_vocab=1, d_model=D_MODEL,
                                   num_heads=HEADS, dff=64,
                                   enc_layers=LAYERS, dec_layers=0,
                                   max_len=MAX_LEN, pos_type="rope")
    eng = DecodeEngine(rope_params, num_heads=HEADS, num_slots=SLOTS,
                       max_len=MAX_LEN, pos_type="rope", name="rope_lm")
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(10)
    cases = [(_prompt(rng), int(rng.randint(2, 9))) for _ in range(6)]
    futs = [bat.submit(p, max_tokens=n) for p, n in cases]
    results = [f.result(120) for f in futs]
    bat.close()
    for (prompt, n), res in zip(cases, results):
        assert res["tokens"] == _oracle(rope_params, prompt, n,
                                        pos_type="rope")


def test_eos_early_finish_matches_oracle(params, engine):
    """A generated stop token finishes the request early (reason "eos",
    eos included), exactly where the oracle run with the same eos_id
    stops."""
    bat = GenerationBatcher(engine)
    rng = np.random.RandomState(2)
    prompt = _prompt(rng, 6)
    free = bat.submit(prompt, max_tokens=10).result(60)["tokens"]
    eos = free[4]
    res = bat.submit(prompt, max_tokens=10, eos_id=eos).result(60)
    bat.close()
    assert res["finish_reason"] == "eos"
    assert res["tokens"][-1] == eos
    k = free.index(eos) + 1             # first occurrence stops the run
    assert res["tokens"] == free[:k]
    assert res["tokens"] == _oracle(params, prompt, k, eos_id=eos)


def test_streaming_on_token_callback(params, engine):
    """on_token fires once per emitted token, in order, from the engine
    thread — and a crashing callback is dropped, never fatal."""
    bat = GenerationBatcher(engine)
    rng = np.random.RandomState(3)
    prompt = _prompt(rng, 5)
    seen = []
    res = bat.submit(prompt, max_tokens=7,
                     on_token=seen.append).result(60)
    assert seen == res["tokens"]

    def boom(tok):
        raise RuntimeError("client callback bug")
    res2 = bat.submit(prompt, max_tokens=7, on_token=boom).result(60)
    assert res2["tokens"] == res["tokens"]      # generation unharmed
    bat.close()


# ------------------------------------------------------------ trace


def test_one_warmup_trace_zero_retraces_across_churn(params):
    """The trace-count discipline, end to end: warm-up traces the step
    exactly once; an admission/eviction churn run (staggered requests,
    slot reuse, mixed prompt lengths) retraces NOTHING — scheduling is
    host-side by construction."""
    eng = DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                       max_len=MAX_LEN, name="trace_lm")
    assert eng.step_trace_count == 1           # exactly one warm-up trace
    rng = np.random.RandomState(4)
    with assert_no_retrace(lambda: eng.step_trace_count,
                           "decode churn over the warm step"):
        bat = GenerationBatcher(eng, default_max_tokens=6)
        futs = [bat.submit(_prompt(rng), max_tokens=int(rng.randint(2, 9)))
                for _ in range(10)]
        for f in futs:
            f.result(120)
        bat.close()


@pytest.mark.parametrize("layout", ["slab", "paged"])
def test_the_trunk_computes_every_lane_and_says_so(params, layout):
    """The transformer trunk has ONE width: each dispatch phase carries
    ``width == lanes == S x K`` beside the lanes rows fed (a free slot's
    one among them), and the two counters add the same up."""
    from paddle_tpu.obs import trace as obstrace
    eng = DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                       max_len=MAX_LEN, kv_layout=layout, kv_block_size=BS,
                       prefill_chunk=4, name=f"lanes_{layout}")
    eng.metrics = ServingMetrics()
    obstrace.enable(sample=1.0, capacity=4096)
    try:
        slot, _feed = eng.seat_chunked(np.arange(1, 8, dtype=np.int32))
        eng.load_chunk(slot, np.arange(2, 5, dtype=np.int32))
        eng.prepare_step()
        eng.step()                      # 4 lanes + three free slots' one
        eng.advance(slot, 5, consumed=4)
        eng.prepare_step()
        eng.step()                      # one lane a row
        phases = obstrace.debug_payload()["phases"]
    finally:
        obstrace.disable()
    stats = [ph["attrs"] for ph in phases
             if ph["name"] == "engine.step.dispatch"]
    assert [(st["width"], st["live"], st["lanes"]) for st in stats] \
        == [(16, 7, 16), (16, 4, 16)]
    m = eng.metrics
    assert (m.step_lanes_computed_total, m.step_lanes_live_total) == (32, 11)
    assert eng.step_trace_count == 1


def test_default_engine_is_chunked_and_the_ladder_is_gone():
    """``DecodeEngine(params)`` with no keywords is the engine the CLI
    builds (slab, 8 lanes a step); asking for the ladder is an error that
    says where it went."""
    trunk = transformer.init(jax.random.PRNGKey(2), src_vocab=VOCAB,
                             trg_vocab=1, d_model=D_MODEL, num_heads=8,
                             dff=64, enc_layers=1, dec_layers=0,
                             max_len=256)
    eng = DecodeEngine(trunk)
    assert (eng.prefill_chunk, eng.kv_layout) == (8, "slab")
    assert eng.step_trace_count == 1 and eng.ready
    prompt = np.arange(1, 21, dtype=np.int32)       # three chunks
    bat = GenerationBatcher(eng)
    got = bat.submit(prompt, max_tokens=4).result(60)["tokens"]
    bat.close()
    want = np.asarray(transformer.lm_generate(trunk, prompt[None],
                                              max_len=256))
    assert got == want[0, 20:24].tolist()
    with pytest.raises(ConfigError, match="ladder, which is gone"):
        DecodeEngine(trunk, prefill_chunk=0, warm=False)
    with pytest.raises(ConfigError, match="prefill_chunk"):
        DecodeEngine(trunk, prefill_chunk=257, warm=False)


# ------------------------------------------------------------ admission


def test_validate_request_rejects_before_queue(engine):
    bat = GenerationBatcher(engine)
    ok = np.arange(1, 5, dtype=np.int32)
    for bad, kw in [
        (np.zeros((2, 3), np.int32), {}),            # 2-D
        (np.zeros((0,), np.int32), {}),              # empty
        (np.zeros((3,), np.float32), {}),            # not ids
        (np.full((3,), VOCAB, np.int32), {}),        # out of vocab
        (ok, {"max_tokens": 0}),                     # no emission budget
        (ok, {"max_tokens": MAX_LEN}),               # overflows the slab
    ]:
        with pytest.raises(InvalidRequestError):
            bat.submit(bad, **kw)
    res = bat.submit(ok, max_tokens=3).result(60)    # still healthy
    assert len(res["tokens"]) == 3
    bat.close()


def test_prompt_bounded_by_max_len_alone(params, engine):
    """Only ``max_len`` caps a prompt: one of several chunks, longer than
    any prefill bucket the engine used to have, is served — and the
    first length that cannot emit a token inside ``max_len`` is refused
    before the queue."""
    bat = GenerationBatcher(engine)
    prompt = _prompt(np.random.RandomState(14), MAX_LEN - 3)
    res = bat.submit(prompt, max_tokens=3).result(120)
    assert res["tokens"] == _oracle(params, prompt, 3)
    with pytest.raises(InvalidRequestError, match="max_len"):
        bat.submit(_prompt(np.random.RandomState(15), MAX_LEN),
                   max_tokens=1)
    bat.close()


def _stall_engine(engine, stall_s):
    """Make each step slow to give its tokens — deterministic queue
    buildup."""
    orig = engine.collect_step

    def slow(*a, **kw):
        time.sleep(stall_s)
        return orig(*a, **kw)
    engine.collect_step = slow
    return orig


def test_overload_deadline_and_metrics(engine):
    engine.metrics = ServingMetrics()
    orig = _stall_engine(engine, 0.1)
    try:
        bat = GenerationBatcher(engine, queue_size=2,
                                default_max_tokens=6)
        rng = np.random.RandomState(5)
        first = bat.submit(_prompt(rng, 4))     # admitted immediately
        time.sleep(0.05)                        # loop now inside a
        #                                         stalled step: the next
        #                                         submits queue up
        q1 = bat.submit(_prompt(rng, 4), max_tokens=2)
        dead = bat.submit(_prompt(rng, 4), deadline_ms=5)
        with pytest.raises(OverloadedError):
            bat.submit(_prompt(rng, 4))         # queue_size=2 exceeded
        with pytest.raises(DeadlineExceededError):
            dead.result(60)
        assert len(q1.result(120)["tokens"]) == 2
        assert len(first.result(120)["tokens"]) == 6
        snap = engine.metrics.snapshot()
        assert snap["rejected"]["overload"] == 1
        assert snap["rejected"]["deadline"] == 1
        bat.close()
    finally:
        engine.collect_step = orig


# ------------------------------------------------------------ faults


def test_step_failure_isolated_and_engine_recovers(params, engine):
    """A decode-step failure fails exactly the in-flight requests with
    BatchExecutionError, the engine resets, and the next request serves
    with unchanged numerics."""
    engine.metrics = ServingMetrics()
    bat = GenerationBatcher(engine, default_max_tokens=30)
    rng = np.random.RandomState(6)
    prompt = _prompt(rng, 5)
    orig = _stall_engine(engine, 0.05)  # keep the victim in flight long
    #                                     enough to inject deterministically

    def boom():
        raise RuntimeError("injected step failure")
    victim = bat.submit(prompt)
    time.sleep(0.1)                     # it reaches a slot, mid-decode
    engine.dispatch_step = boom
    with pytest.raises(BatchExecutionError):
        victim.result(60)
    del engine.dispatch_step
    engine.collect_step = orig
    res = bat.submit(prompt, max_tokens=6).result(60)
    assert res["tokens"] == _oracle(params, prompt, 6)
    snap = engine.metrics.snapshot()
    assert snap["evictions"]["error"] >= 1
    assert snap["errors_total"] >= 1
    assert engine.free_slots == SLOTS
    bat.close()


def test_step_fault_mid_ingestion_isolated(params, engine):
    """A device-step fault that hits while a prompt is still being
    ingested (its second chunk of five) fails that step's requests alone
    — nothing was emitted yet — and the engine recovers: the request
    queued behind it is served with unchanged numerics."""
    engine.metrics = ServingMetrics()
    bat = GenerationBatcher(engine)
    rng = np.random.RandomState(16)
    long_prompt, prompt = _prompt(rng, 35), _prompt(rng, 5)
    seen = []
    faults.install_spec("serving.decode_step:at=2")
    victim = bat.submit(long_prompt, max_tokens=3, on_token=seen.append)
    with pytest.raises(BatchExecutionError, match="InjectedFault"):
        victim.result(60)
    assert faults.fired_counts() == {"serving.decode_step": 1}
    assert seen == []                   # failed before its first token
    snap = engine.metrics.snapshot()
    assert 0 < snap["prefill_chunk_lanes_total"] < long_prompt.size - 1
    assert snap["errors_total"] == 1
    res = bat.submit(prompt, max_tokens=6).result(60)
    assert res["tokens"] == _oracle(params, prompt, 6)
    # and the victim's prompt itself is servable on the rebuilt cache
    res = bat.submit(long_prompt, max_tokens=3).result(60)
    assert res["tokens"] == _oracle(params, long_prompt, 3)
    bat.close()
    assert engine.free_slots == SLOTS


def test_abandon_reclaims_slot_midflight(engine):
    """A disconnected caller's request stops burning decode steps: the
    slot is evicted at the next token boundary instead of running to
    max_tokens, and co-resident requests are untouched."""
    engine.metrics = ServingMetrics()
    orig = _stall_engine(engine, 0.03)
    try:
        bat = GenerationBatcher(engine, default_max_tokens=40)
        rng = np.random.RandomState(12)
        victim = bat.submit(_prompt(rng, 4))
        survivor = bat.submit(_prompt(rng, 4), max_tokens=8)
        time.sleep(0.1)             # both slotted, mid-decode
        bat.abandon(victim)
        assert len(survivor.result(120)["tokens"]) == 8
        deadline = time.time() + 10
        while engine.free_slots < SLOTS and time.time() < deadline:
            time.sleep(0.01)
        assert engine.free_slots == SLOTS   # reclaimed well before 40 toks
        assert engine.metrics.snapshot()["evictions"]["abandoned"] == 1
        bat.close()
    finally:
        engine.collect_step = orig


# ------------------------------------------------ one step in flight


def _gate_collect(engine):
    """Hold every read of a step's tokens until ``gate`` is set, and
    count the reads that have started: a test can act while a step is
    provably in flight.  Returns (gate, reads, undo)."""
    gate, reads = threading.Event(), []
    orig = engine.collect_step

    def held(*a, **kw):
        reads.append(time.perf_counter())
        assert gate.wait(60)
        return orig(*a, **kw)
    engine.collect_step = held

    def undo():
        gate.set()
        engine.collect_step = orig
    return gate, reads, undo


def _hold_admission(engine):
    """Keep the worker's next admission waiting until ``go`` is set, so
    that requests submitted meanwhile seat together.  Returns (go,
    undo)."""
    go, poll = threading.Event(), engine.poll_restores
    engine.poll_restores = lambda *a, **kw: (go.wait(30), poll(*a, **kw))[1]

    def undo():
        go.set()
        engine.poll_restores = poll
    return go, undo


def _wait_idle(engine):
    """Every slot free and no step in flight (the loop reads the last
    step's tokens after the slots have gone)."""
    _wait_for(lambda: engine.free_slots == SLOTS and engine.steps_dispatched
              == engine.metrics.decode_steps_total, "the loop to run dry")


def _wait_for(cond, what, timeout=30):
    deadline = time.time() + timeout
    while not cond():
        assert time.time() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


def test_one_step_in_flight_bit_identical_and_engages(params, engine):
    """Staggered admissions whose prompts end inside a chunk and exactly
    at a chunk boundary (the engine's K is 8): every stream is
    lm_generate's, nothing retraces, and in steady decoding nearly every
    step is handed over while the one before is still in flight."""
    engine.metrics = ServingMetrics()
    bat = GenerationBatcher(engine, default_max_tokens=24)
    rng = np.random.RandomState(21)
    cases = [(_prompt(rng, n), 24) for n in (8, 5, 16, 9, 17, 3, 15, 24)]
    with assert_no_retrace(lambda: engine.step_trace_count,
                           "decode with a step in flight"):
        futs = []
        for prompt, n in cases:
            futs.append(bat.submit(prompt, max_tokens=n))
            time.sleep(0.005)
        results = [f.result(120) for f in futs]
    bat.close()
    for (prompt, n), res in zip(cases, results):
        assert res["tokens"] == _oracle(params, prompt, n), prompt.size
    assert engine.step_trace_count == 1
    snap = engine.metrics.snapshot()
    assert snap["decode_steps_overlapped_total"] \
        > 0.9 * snap["decode_steps_total"], snap
    assert "decode_steps_overlapped_total" in engine.metrics.render_prometheus()
    assert engine.free_slots == SLOTS


def test_eos_with_a_step_in_flight_drops_the_surplus_lane(params, engine):
    """An EOS is not known until it is read, and by then the next step
    is on its way with one more lane for that row: its pick is never
    streamed, the stream ends at the EOS, the slot's next occupant
    streams right, and the pool's ledger holds (the surplus write went
    to a block of the slot's own, published nowhere)."""
    engine.metrics = ServingMetrics()
    bat = GenerationBatcher(engine)
    rng = np.random.RandomState(22)
    prompt = _prompt(rng, 11)
    free = bat.submit(prompt, max_tokens=12).result(60)["tokens"]
    eos = free[5]
    k = free.index(eos) + 1
    engine.record_steps(True)
    seen = []
    res = bat.submit(prompt, max_tokens=12, eos_id=eos,
                     on_token=seen.append).result(60)
    _wait_idle(engine)
    steps = engine.recorded_steps()
    engine.record_steps(False)
    assert res["finish_reason"] == "eos"
    assert seen == res["tokens"] == free[:k] \
        == _oracle(params, prompt, k, eos_id=eos)
    # the surplus lane ran, one step after the EOS was picked, and was
    # really fed the EOS the device had kept: position prompt + k - 1
    tokens, pos, lens, _aux = steps[-1]
    slot = int(np.argmax(pos))
    assert (int(pos[slot]), int(lens[slot])) == (prompt.size + k - 1, 1)
    assert int(tokens[slot, 0]) == eos
    # the slots' next occupants stream right
    others = [(_prompt(rng), 6) for _ in range(SLOTS + 1)]
    futs = [bat.submit(p, max_tokens=n) for p, n in others]
    for (p, n), f in zip(others, futs):
        assert f.result(60)["tokens"] == _oracle(params, p, n)
    bat.close()
    assert engine.free_slots == SLOTS
    if engine.kv_layout == "paged":
        engine._paged.check()
        # what the index holds of that stream ends with its prompt
        mine = [covered for key, (covered, _chain)
                in engine._paged.index._entries.items()
                if key[:BS] == tuple(prompt[:BS])]
        assert mine and max(mine) == prompt.size


def test_max_tokens_finish_takes_no_lane_in_the_next_step(params, engine):
    """A token that is the last by ``max_tokens`` is known to be before
    it is read: the row takes no lane in the step after, and its slot is
    free for the very next hand-over, as early as in the serial loop."""
    engine.metrics = ServingMetrics()
    bat = GenerationBatcher(engine)
    rng = np.random.RandomState(23)
    prompt, n = _prompt(rng, 10), 5
    engine.record_steps(True)
    res = bat.submit(prompt, max_tokens=n).result(60)
    _wait_idle(engine)
    steps = engine.recorded_steps()
    assert res["tokens"] == _oracle(params, prompt, n)
    # two steps of ingestion (8 + 2 lanes), then n - 1 decode lanes: the
    # last one fed token n - 1, and no step ran after it
    assert len(steps) == 2 + n - 1 == engine.metrics.decode_steps_total
    _tokens, pos, lens, _aux = steps[-1]
    assert int(pos.max()) == prompt.size + n - 2 and int(lens.max()) == 1
    # a full house of such requests and one more behind them: the queued
    # one is seated by the step right after their last
    engine.record_steps(True)
    wave = [(_prompt(rng, 6), 4) for _ in range(SLOTS)] \
        + [(_prompt(rng, 7), 3)]
    go, undo = _hold_admission(engine)
    try:
        time.sleep(0.1)                 # the worker is at the gate
        futs = [bat.submit(p, max_tokens=m) for p, m in wave]
        go.set()
        for (p, m), f in zip(wave, futs):
            assert f.result(60)["tokens"] == _oracle(params, p, m)
    finally:
        undo()
    _wait_idle(engine)
    steps = engine.recorded_steps()
    engine.record_steps(False)
    bat.close()
    fresh = [i for i, (_t, pos, lens, _a) in enumerate(steps)
             if ((pos == 0) & (lens > 1)).any()]
    # the wave's own first chunks, then the fifth request's: the wave
    # takes 1 + 3 steps from its last seat (6 lanes, then 3 decode lanes)
    assert fresh[-1] - fresh[-2] == 4, fresh


def test_read_failure_with_a_step_in_flight_fails_exactly_those(params,
                                                                engine):
    """A step whose tokens cannot be read, with the next one already
    handed over: both are void, exactly the requests in flight fail, and
    the ones queued behind them are served with unchanged numerics."""
    engine.metrics = ServingMetrics()
    bat = GenerationBatcher(engine, default_max_tokens=20)
    rng = np.random.RandomState(24)
    inflight = [_prompt(rng, 5) for _ in range(SLOTS)]
    queued = [_prompt(rng, 6) for _ in range(2)]
    orig, reads = engine.collect_step, []

    def third_read_fails(handle, *a, **kw):
        reads.append(handle.step)
        if len(reads) == 3:
            assert engine._last_step.step == handle.step + 1  # one behind
            handle.done = True
            raise RuntimeError("injected read failure")
        return orig(handle, *a, **kw)
    engine.collect_step = third_read_fails
    go, undo = _hold_admission(engine)
    try:
        time.sleep(0.1)                 # the worker is at the gate
        seen = [[] for _ in inflight]
        futs = [bat.submit(p, on_token=s.append)
                for p, s in zip(inflight, seen)]
        later = [bat.submit(p, max_tokens=6) for p in queued]
        go.set()
        for f in futs:
            with pytest.raises(BatchExecutionError, match="injected read"):
                f.result(60)
        for p, f in zip(queued, later):
            assert f.result(60)["tokens"] == _oracle(params, p, 6)
    finally:
        undo()
        engine.collect_step = orig
    bat.close()
    # what had been streamed before the failure was right
    for p, s in zip(inflight, seen):
        assert s == _oracle(params, p, len(s))
    snap = engine.metrics.snapshot()
    assert snap["errors_total"] == SLOTS
    assert engine.free_slots == SLOTS


def test_abandon_and_deadline_while_a_step_is_in_flight(params, engine):
    """With a step provably in flight (its read is held): a caller
    leaves, and a queued request outlives its deadline.  The one who left
    is streamed nothing more, the late one is refused, the survivor's
    stream and the slot's next occupant's are the oracle's."""
    engine.metrics = ServingMetrics()
    bat = GenerationBatcher(engine, default_max_tokens=30)
    rng = np.random.RandomState(25)
    gate, reads, undo = _gate_collect(engine)
    try:
        seen = []
        filler = [bat.submit(_prompt(rng, 4), max_tokens=30)
                  for _ in range(SLOTS - 2)]
        victim = bat.submit(_prompt(rng, 4), on_token=seen.append)
        keep = _prompt(rng, 4)
        survivor = bat.submit(keep, max_tokens=9)
        _wait_for(lambda: reads, "the first read")
        gate.set()                      # free running, then hold again
        _wait_for(lambda: len(seen) >= 3, "the victim to stream")
        gate.clear()
        n_reads = len(reads)
        _wait_for(lambda: len(reads) > n_reads, "a held read")
        assert engine.steps_dispatched > engine.metrics.decode_steps_total
        bat.abandon(victim)
        streamed = len(seen)
        dead = bat.submit(_prompt(rng, 4), deadline_ms=1)
        nxt_prompt = _prompt(rng, 9)
        nxt = bat.submit(nxt_prompt, max_tokens=5)
        time.sleep(0.02)
        gate.set()
        with pytest.raises(DeadlineExceededError):
            dead.result(60)
        assert victim.result(60)["finish_reason"] == "abandoned"
        assert len(seen) == streamed    # nothing after the caller left
        assert survivor.result(60)["tokens"] == _oracle(params, keep, 9)
        assert nxt.result(60)["tokens"] == _oracle(params, nxt_prompt, 5)
        for f in filler:
            assert len(f.result(60)["tokens"]) == 30
    finally:
        undo()
    bat.close()
    assert engine.metrics.snapshot()["evictions"]["abandoned"] == 1
    assert engine.free_slots == SLOTS


# ------------------------------------------------------------ drain


def test_drain_finishes_queued_and_inflight(engine):
    orig = _stall_engine(engine, 0.02)
    try:
        bat = GenerationBatcher(engine, default_max_tokens=6)
        rng = np.random.RandomState(7)
        futs = [bat.submit(_prompt(rng, 4)) for _ in range(8)]
        t = threading.Thread(target=bat.close, kwargs={"drain": True})
        t.start()
        time.sleep(0.01)
        with pytest.raises(ShutdownError):
            bat.submit(_prompt(rng, 4))     # draining: no new admissions
        t.join(120)
        for f in futs:
            assert len(f.result(0)["tokens"]) == 6  # all completed
        assert engine.free_slots == SLOTS
    finally:
        engine.collect_step = orig


@pytest.mark.parametrize("drain", [True, False])
def test_close_during_seating_resolves_submitter(engine, drain):
    """The batcher-close-during-admission race: close() while a request
    is inside the seating window (popped from the queue, not yet in a
    slot) must RESOLVE the submitter (result on drain=True, ShutdownError
    on drain=False) — never strand it.  The worker is provably inside
    the seat when close() lands."""
    orig = engine.seat_prefilled
    inside = threading.Event()

    def slow(fulls):
        inside.set()
        time.sleep(0.3)
        return orig(fulls)
    engine.seat_prefilled = slow
    try:
        bat = GenerationBatcher(engine, default_max_tokens=4)
        rng = np.random.RandomState(13)
        fut = bat.submit(rng.randint(1, VOCAB, 4).astype(np.int32))
        assert inside.wait(10)          # worker is mid-seat NOW
        closer = threading.Thread(target=bat.close,
                                  kwargs={"drain": drain})
        closer.start()
        if drain:
            assert len(fut.result(30)["tokens"]) == 4
        else:
            with pytest.raises((ShutdownError, BatchExecutionError)):
                fut.result(30)          # resolved, not stranded
        closer.join(30)
        assert not closer.is_alive(), "close() wedged on the seat"
        assert engine.free_slots == SLOTS
    finally:
        engine.seat_prefilled = orig


def test_close_without_drain_fails_inflight_and_queued(engine):
    orig = _stall_engine(engine, 0.1)
    try:
        bat = GenerationBatcher(engine, default_max_tokens=40)
        rng = np.random.RandomState(8)
        futs = [bat.submit(_prompt(rng, 4)) for _ in range(6)]
        time.sleep(0.05)                # some in slots, some queued
        bat.close(drain=False)
        failed = 0
        for f in futs:
            try:
                f.result(30)
            except ShutdownError:
                failed += 1
        assert failed == 6
        assert engine.free_slots == SLOTS       # slots reclaimed
    finally:
        engine.collect_step = orig


# ------------------------------------------------------------ HTTP


def test_http_generate_plain_stream_and_faults(params, engine):
    """/v1/generate end to end on a generation-only server: plain JSON,
    chunked NDJSON streaming (identical ids — greedy is deterministic),
    and the error mapping."""
    engine.metrics = ServingMetrics()
    bat = GenerationBatcher(engine, default_max_tokens=6)
    httpd = make_server(None, port=0, gen_batcher=bat)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.port}"
    try:
        prompt = np.random.RandomState(9).randint(1, VOCAB, 5).tolist()

        def post(body, path="/v1/generate"):
            req = urllib.request.Request(
                f"{base}{path}", data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as r:
                return r.status, r.read()

        status, raw = post({"prompt": prompt, "max_tokens": 6})
        plain = json.loads(raw)
        assert status == 200 and plain["finish_reason"] == "length"
        assert plain["tokens"] == _oracle(
            params, np.asarray(prompt, np.int32), 6)
        assert plain["ttft_ms"] >= 0

        _, raw = post({"prompt": prompt, "max_tokens": 6, "stream": True})
        lines = [json.loads(ln) for ln in raw.decode().splitlines() if ln]
        assert [ln["token"] for ln in lines if "token" in ln] \
            == plain["tokens"]
        assert lines[-1]["done"] and lines[-1]["tokens"] == plain["tokens"]

        def expect(code, body, path="/v1/generate"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                post(body, path=path)
            assert ei.value.code == code
            return json.loads(ei.value.read())

        assert "error" in expect(400, {"noprompt": 1})
        assert "error" in expect(400, {"prompt": []})
        assert "error" in expect(400, {"prompt": ["a", "b"]})
        assert "error" in expect(400, {"prompt": [2 ** 80]})  # > int64
        assert "error" in expect(400, {"prompt": prompt,
                                       "max_tokens": MAX_LEN + 9})
        assert "error" in expect(400, {"prompt": prompt,
                                       "deadline_ms": -1})
        # generation-only server: /v1/infer names the absent model
        assert "error" in expect(404, {"feed": {}}, path="/v1/infer")
        # the engine survived every fault
        status, raw = post({"prompt": prompt, "max_tokens": 3})
        assert status == 200 and len(json.loads(raw)["tokens"]) == 3

        # /metrics surfaces the generation section
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            text = r.read().decode()
        assert "gen_tokens_total" in text
        assert 'ttft_seconds{quantile="0.50"}' in text
        assert 'slot_evictions_total{reason="length"}' in text
    finally:
        httpd.shutdown()
        bat.close()


# ------------------------------------------------------------ load


@pytest.mark.slow
def test_generation_smoke_subprocess():
    """`python -m paddle_tpu.serving --smoke-generate` passes end to end
    in a fresh process."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.serving", "--smoke-generate"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["value"] == int(out["unit"].split("/")[1])
    assert out["eos_early_finish"] is True
    assert out["stream_ok"] is True
    assert out["metrics_sane"] is True

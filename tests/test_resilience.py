"""Resilience layer (paddle_tpu/resilience): the chaos matrix.

Every registered fault point fires under concurrent load and the stack
must: never deadlock, keep serving, keep recovered greedy streams
BIT-IDENTICAL to the single-request oracle, retrace nothing beyond the
rebuild, and count every recovery event into metrics.  The fault plans
are seeded/counted (resilience/faults.py), so every scenario here
replays bit-for-bit.

Training half: a trainer crash mid-pass (injected ``trainer.step``
fault in-process; a real subprocess SIGKILL mid-checkpoint-write in the
slow lane) must resume via ``train(resume=True)`` from the latest
COMPLETE pass dir to bit-identical final parameters — with a partial
``.tmp-`` checkpoint never picked up.
"""

import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
import jax

from paddle_tpu.models import transformer
from paddle_tpu.resilience import (FaultPlan, InjectedFault, Supervisor,
                                   faults, retry_transient)
from paddle_tpu.resilience.supervisor import BreakerOpenError
from paddle_tpu.serving import (BatchExecutionError, Batcher,
                                GenerationBatcher, InferenceEngine,
                                ServingMetrics, make_server)
from paddle_tpu.serving.decode_engine import DecodeEngine
from paddle_tpu.testing import assert_no_retrace, forbid_retrace
from paddle_tpu.utils.error import ConfigError

VOCAB, HEADS, MAX_LEN, SLOTS, PROMPT_TOP = 64, 2, 48, 4, 16


@pytest.fixture(autouse=True)
def _no_leaked_fault_plan():
    """A test's fault plan must never leak into the next test (or a
    crashed test leave the process poisoned)."""
    yield
    faults.clear()


@pytest.fixture(scope="module")
def params():
    return transformer.init(jax.random.PRNGKey(0), src_vocab=VOCAB,
                            trg_vocab=1, d_model=32, num_heads=HEADS,
                            dff=64, enc_layers=2, dec_layers=0,
                            max_len=MAX_LEN)


@pytest.fixture(scope="module")
def engine(params):
    return DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                        max_len=MAX_LEN, name="chaos_lm")


def _prompts(seed, n):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, VOCAB, rng.randint(3, PROMPT_TOP + 1)
                        ).astype(np.int32) for _ in range(n)]


def _reference(engine, cases):
    """Clean single-request runs through the batcher — greedy decode is
    deterministic, so these token lists are the oracle."""
    bat = GenerationBatcher(engine)
    ref = [bat.submit(p, max_tokens=n).result(120)["tokens"]
           for p, n in cases]
    bat.close()
    return ref


def _drive_concurrent(bat, cases, stagger_s=0.004):
    """8+ client threads, staggered submits; returns results (None on a
    failed request) + the per-request exceptions."""
    results, excs = [None] * len(cases), [None] * len(cases)

    def client(i):
        prompt, n = cases[i]
        try:
            time.sleep(stagger_s * i)
            results[i] = bat.submit(prompt, max_tokens=n).result(120)
        except Exception as e:      # noqa: BLE001
            excs[i] = e

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(cases))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(180)
        assert not t.is_alive(), "client thread wedged: DEADLOCK"
    return results, excs


# ---------------------------------------------------------------- plans


def test_fault_plan_spec_parsing_and_determinism():
    plan = FaultPlan.from_spec(
        "serving.decode_step:at=3; trainer.step:every=2,times=2; "
        "batcher.submit:p=0.5,seed=9,action=error")
    # at=3: one-shot on exactly the 3rd hit
    for i in range(1, 7):
        try:
            plan.hit("serving.decode_step")
            fired = False
        except InjectedFault as e:
            fired = True
            assert e.hit_index == 3
        assert fired == (i == 3)
    # every=2 capped at times=2: hits 2 and 4 fire, 6 does not
    fires = []
    for i in range(1, 7):
        try:
            plan.hit("trainer.step")
        except InjectedFault:
            fires.append(i)
    assert fires == [2, 4]
    # seeded p-mode replays bit-for-bit
    def pattern():
        p = FaultPlan.from_spec("batcher.submit:p=0.5,seed=9")
        out = []
        for _ in range(32):
            try:
                p.hit("batcher.submit")
                out.append(0)
            except InjectedFault:
                out.append(1)
        return out
    first = pattern()
    assert first == pattern()
    assert 0 < sum(first) < 32          # really probabilistic
    # unregistered points and bad specs fail loudly
    with pytest.raises(ConfigError):
        FaultPlan.from_spec("serving.decode_stepp:at=1")
    with pytest.raises(ConfigError):
        FaultPlan.from_spec("serving.decode_step:bogus=1")
    with pytest.raises(ConfigError):
        FaultPlan.from_spec("serving.decode_step:at=1,every=2")
    # no plan installed: hit() is a no-op
    faults.clear()
    faults.hit("serving.decode_step")
    assert faults.fired_counts() == {}


# ------------------------------------------------------------ decode step


def test_decode_step_fault_recovery_bit_identical_under_load(engine):
    """The chaos-matrix headline: a poisoned decode step under 12
    concurrent requests (8+ clients, slot churn) rebuilds the slab and
    re-prefills every in-flight stream — every request completes with
    tokens EXACTLY equal to its clean run, zero retraces, recovery
    events counted."""
    cases = [(p, 4 + (i % 5)) for i, p in enumerate(_prompts(1, 12))]
    ref = _reference(engine, cases)
    engine.metrics = ServingMetrics()
    sup = Supervisor(breaker_threshold=10)
    bat = GenerationBatcher(engine, supervisor=sup)
    faults.install_spec("serving.decode_step:at=6")
    with assert_no_retrace(lambda: engine.step_trace_count,
                           "decode chaos recovery"):
        results, excs = _drive_concurrent(bat, cases)
        bat.close()
    assert faults.fired_counts() == {"serving.decode_step": 1}
    faults.clear()
    assert all(e is None for e in excs), excs
    for i, r in enumerate(results):
        assert r["tokens"] == ref[i], f"stream {i} diverged after recovery"
    snap = engine.metrics.snapshot()
    assert snap["slot_reprefills_total"] >= 1
    assert snap["evictions"]["recovered"] >= 1
    assert engine.free_slots == SLOTS


def test_decode_step_hang_watchdog_rebuild_bit_identical(engine):
    """A HUNG step (injected hang past the watchdog deadline) is
    abandoned, the slab rebuilt, streams recovered bit-identically; the
    late-finishing stale thread is discarded by the epoch guard."""
    cases = [(p, 5) for p in _prompts(2, 6)]
    ref = _reference(engine, cases)
    engine.metrics = ServingMetrics()
    sup = Supervisor(step_deadline_s=0.25, breaker_threshold=10)
    bat = GenerationBatcher(engine, supervisor=sup)
    faults.install_spec("serving.decode_step:at=4,action=hang,hang_s=1.0")
    with forbid_retrace(engine, what="watchdog rebuild recovery",
                        hint="the slab rebuild retraced the step"):
        results, excs = _drive_concurrent(bat, cases)
        bat.close()
    faults.clear()
    assert all(e is None for e in excs), excs
    for i, r in enumerate(results):
        assert r["tokens"] == ref[i]
    assert sup.watchdog_trips == 1
    snap = engine.metrics.snapshot()
    assert snap["watchdog_trips_total"] == 1
    assert snap["slot_reprefills_total"] >= 1
    # the watchdog times one whole step: none is handed over ahead
    assert snap["decode_steps_overlapped_total"] == 0
    time.sleep(0.9)     # let the stale thread finish against the epoch
    #                     guard before the next test reuses the engine


def test_supervised_no_faults_is_zero_cost(engine):
    """Acceptance: with NO fault spec installed, a supervised batcher
    serves bit-identically to the oracle with zero extra traces and no
    recovery events — the resilience layer is free when nothing fails."""
    cases = [(p, 5) for p in _prompts(3, 6)]
    ref = _reference(engine, cases)
    engine.metrics = ServingMetrics()
    sup = Supervisor(breaker_threshold=3)
    bat = GenerationBatcher(engine, supervisor=sup)
    with assert_no_retrace(lambda: engine.step_trace_count,
                           "supervised serving without faults"):
        results, excs = _drive_concurrent(bat, cases)
        bat.close()
    assert all(e is None for e in excs)
    assert [r["tokens"] for r in results] == ref
    snap = engine.metrics.snapshot()
    assert snap["slot_reprefills_total"] == 0
    assert snap["watchdog_trips_total"] == 0
    assert snap["retries_total"] == 0
    assert snap["breaker_state"] == 0
    assert snap["faults_fired"] == {}
    # and without a step deadline it keeps a step in flight like any other
    assert snap["decode_steps_overlapped_total"] \
        > 0.5 * snap["decode_steps_total"], snap


@pytest.mark.parametrize("fail_read", [1, 2])
def test_read_failure_with_a_step_in_flight_recovers_bit_identical(
        engine, fail_read):
    """A step whose tokens cannot be read while the loop keeps one in
    flight: the step handed over behind it is void too, and recovery
    replays from the tokens DELIVERED.  Four requests of one prompt chunk
    and three tokens: read 1 fails with step 2 handed over for seated
    rows; read 2 fails after their third token, the last by count, had
    already cost them their slots.  Either way every stream completes
    with exactly its clean run's tokens."""
    cases = [(p[:4], 3) for p in _prompts(4, SLOTS)]
    ref = _reference(engine, cases)
    engine.metrics = ServingMetrics()
    # the worker's first admission waits until all four are queued, so
    # that they seat together and the reads below are the same every run
    go, poll = threading.Event(), engine.poll_restores
    engine.poll_restores = lambda *a, **kw: (go.wait(30), poll(*a, **kw))[1]
    bat = GenerationBatcher(engine, supervisor=Supervisor())
    orig, reads = engine.collect_step, []

    def flaky(handle, *a, **kw):
        reads.append((handle.step, engine._last_step.step,
                      engine.free_slots))
        if len(reads) == fail_read + 1:
            handle.done = True
            raise RuntimeError("injected read failure")
        return orig(handle, *a, **kw)
    engine.collect_step = flaky
    try:
        with assert_no_retrace(lambda: engine.step_trace_count,
                               "recovery with a step in flight"):
            futs = [bat.submit(p, max_tokens=n) for p, n in cases]
            go.set()
            results = [f.result(120) for f in futs]
            bat.close()
    finally:
        go.set()
        engine.collect_step, engine.poll_restores = orig, poll
    assert [r["tokens"] for r in results] == ref
    of_step, last, free = reads[fail_read]
    if fail_read == 1:
        assert (of_step, last, free) == (1, 2, 0)   # step 2 was behind it
    else:
        assert (of_step, last, free) == (2, 2, SLOTS)   # slots gone already
    snap = engine.metrics.snapshot()
    assert snap["slot_reprefills_total"] == SLOTS
    assert snap["errors_total"] == 0
    assert engine.free_slots == SLOTS


# ---------------------------------------------------- mid-ingestion


def test_step_fault_mid_ingestion_isolated_under_load(engine):
    """Unsupervised, under load: a device-step fault that lands while
    long prompts are still being ingested (their second chunk of five)
    fails the requests of THAT step alone — none of them had emitted a
    token — while the requests queued behind them complete, identical
    to their clean runs, and the engine keeps serving."""
    engine.metrics = ServingMetrics()
    rng = np.random.RandomState(4)
    long_prompts = [rng.randint(1, VOCAB, 35).astype(np.int32)
                    for _ in range(SLOTS)]
    cases = [(p, 3) for p in long_prompts] \
        + [(p, 4) for p in _prompts(4, 6)]
    ref = _reference(engine, cases)
    engine.metrics = ServingMetrics()
    bat = GenerationBatcher(engine)
    seen = [[] for _ in cases]
    faults.install_spec("serving.decode_step:at=2")
    # one thread, one tight loop: the long prompts are what is in the
    # slots at the second step (all four, unless the worker outran this
    # loop), the rest queue behind them
    futs = [bat.submit(p, max_tokens=n, on_token=seen[i].append)
            for i, (p, n) in enumerate(cases)]
    failed = []
    for i, f in enumerate(futs):
        try:
            assert f.result(120)["tokens"] == ref[i], i
        except BatchExecutionError:
            failed.append(i)
    assert faults.fired_counts() == {"serving.decode_step": 1}
    faults.clear()
    assert failed and failed[-1] < SLOTS    # the step's requests, alone
    assert all(seen[i] == [] for i in failed)
    snap = engine.metrics.snapshot()
    assert snap["errors_total"] == len(failed)
    assert snap["evictions"]["error"] == len(failed)
    ok = bat.submit(long_prompts[0], max_tokens=3).result(60)
    assert ok["tokens"] == ref[0]       # still serving, same numerics
    bat.close()
    assert engine.free_slots == SLOTS


# ------------------------------------------------------------ infer plane


def _mlp_engine(warm=True):
    from paddle_tpu.layers import api as L
    from paddle_tpu.layers.graph import Topology, reset_names
    reset_names()
    x = L.data_layer("rx", size=8)
    h = L.fc_layer(input=x, size=16, act="tanh")
    out = L.fc_layer(input=h, size=4, act="softmax")
    params = Topology([out]).init(jax.random.PRNGKey(0))
    spec = {"rx": jax.ShapeDtypeStruct((1, 8), np.float32)}
    return InferenceEngine.from_topology(out, params, spec, buckets=(4, 16),
                                         warm=warm)


def test_engine_execute_fault_isolated_and_keeps_serving():
    eng = _mlp_engine()
    bat = Batcher(eng, max_delay_ms=0.0, queue_size=64)
    row = {"rx": np.zeros((8,), np.float32)}
    faults.install_spec("serving.engine.execute:at=1")
    f = bat.submit(row)
    with pytest.raises(BatchExecutionError):
        f.result(30)
    faults.clear()
    assert np.asarray(bat.submit(row).result(30)).shape == (4,)
    assert eng.metrics.snapshot()["errors_total"] == 1
    bat.close()


# ------------------------------------------------------------ submit retry


def test_submit_retry_transient_with_idempotence(engine):
    """Transient submit failures are absorbed by the bounded retry, and
    a failed attempt admitted NOTHING (requests_total counts the one
    real admission only)."""
    engine.metrics = ServingMetrics()
    bat = GenerationBatcher(engine)
    prompt = _prompts(5, 1)[0]
    retried = []
    faults.install_spec("batcher.submit:every=1,times=2")   # hits 1+2 fail
    fut = retry_transient(lambda: bat.submit(prompt, max_tokens=3),
                          budget=3, base_delay_s=0.001, seed=0,
                          on_retry=lambda a, e: retried.append(a))
    assert len(fut.result(60)["tokens"]) == 3
    assert retried == [1, 2]
    snap = engine.metrics.snapshot()
    assert snap["requests_total"] == 1      # idempotent failed attempts
    # budget exhaustion: the transient error surfaces, still nothing
    # admitted by the failed attempts
    faults.install_spec("batcher.submit:every=1")
    with pytest.raises(InjectedFault):
        retry_transient(lambda: bat.submit(prompt, max_tokens=3),
                        budget=2, base_delay_s=0.001, seed=0)
    faults.clear()
    assert engine.metrics.snapshot()["requests_total"] == 1
    bat.close()


# ------------------------------------------------------------ breaker


def test_breaker_opens_sheds_and_recloses(engine):
    """M consecutive step failures open the breaker (fast shed with
    retry_after), the cooldown admits a half-open probe, and a healthy
    step closes it again — serving resumes bit-identically."""
    cases = [(p, 3) for p in _prompts(6, 1)]
    ref = _reference(engine, cases)
    engine.metrics = ServingMetrics()
    sup = Supervisor(breaker_threshold=2, breaker_cooldown_s=0.3,
                     max_request_recoveries=1)
    bat = GenerationBatcher(engine, supervisor=sup)
    prompt, n = cases[0]
    faults.install_spec("serving.decode_step:every=1")   # every step dies
    victim = bat.submit(prompt, max_tokens=n)
    with pytest.raises(BatchExecutionError):
        victim.result(60)       # recovery budget (1) exhausted
    deadline = time.time() + 5
    while sup.breaker.state != "open" and time.time() < deadline:
        time.sleep(0.01)
    assert sup.breaker.state == "open"
    with pytest.raises(BreakerOpenError) as ei:
        bat.submit(prompt, max_tokens=n)
    assert ei.value.retry_after_s > 0
    snap = engine.metrics.snapshot()
    assert snap["rejected"]["breaker"] == 1
    assert snap["breaker_state"] == 2
    assert snap["breaker_open_total"] == 1
    # cause clears; after the cooldown the half-open probe closes it
    faults.clear()
    time.sleep(0.35)
    probe = bat.submit(prompt, max_tokens=n)    # the half-open probe
    assert probe.result(60)["tokens"] == ref[0]
    deadline = time.time() + 5
    while sup.breaker.state != "closed" and time.time() < deadline:
        time.sleep(0.01)
    assert sup.breaker.state == "closed"
    assert bat.submit(prompt, max_tokens=n).result(60)["tokens"] == ref[0]
    bat.close()


def test_breaker_state_machine_units():
    """The documented open -> cooldown -> half-open -> close path, unit
    level: in-flight successes while OPEN do not bypass the cooldown
    (flapping engines keep shedding), probe failures re-open AND count,
    and half-open counts as ready (the probe must be routable)."""
    from paddle_tpu.resilience import CircuitBreaker
    b = CircuitBreaker(threshold=2, cooldown_s=0.25)
    b.record_failure()
    b.record_failure()
    assert b.state == "open" and b.opened_total == 1
    b.record_success()              # a recovered in-flight step
    assert b.state == "open"        # the cooldown stands
    time.sleep(0.3)
    assert b.state == "half_open"
    ok, _ = b.admit()               # the probe
    assert ok
    ok2, ra = b.admit()             # second caller sheds
    assert not ok2 and ra > 0
    b.record_failure()              # probe failed: re-open, counted
    assert b.state == "open" and b.opened_total == 2
    time.sleep(0.3)
    assert b.state == "half_open"
    assert b.seconds_until_probe() > 0
    b.record_success()              # post-cooldown success closes
    assert b.state == "closed"
    assert b.seconds_until_probe() == 0.0


# ------------------------------------------------------------ prefetch


def test_prefetch_h2d_fault_surfaces_in_consumer():
    from paddle_tpu.data.prefetch import ShardedPrefetcher

    def source():
        for i in range(4):
            yield {"x": np.full((2, 2), i, np.float32)}

    faults.install_spec("data.prefetch.h2d:at=2")
    pf = ShardedPrefetcher(source, depth=2)
    first = next(iter(pf))
    assert float(np.asarray(first["x"])[0, 0]) == 0.0
    with pytest.raises(InjectedFault):
        next(iter(pf))
    faults.clear()
    pf.close()          # clean close after the failure: no deadlock


# ------------------------------------------------------------ training


def _tiny_trainer(seed=7):
    import paddle_tpu.optim as optim
    from paddle_tpu.data import dense_vector, integer_value
    from paddle_tpu.layers import api as L
    from paddle_tpu.layers.graph import reset_names
    from paddle_tpu.trainer.trainer import SGD
    reset_names()
    x = L.data_layer("res_x", size=4)
    lab = L.data_layer("res_lab", size=1)
    h = L.fc_layer(input=x, size=8, act="tanh")
    y = L.fc_layer(input=h, size=2, act="softmax")
    cost = L.classification_cost(y, lab)
    tr = SGD(cost=cost,
             update_equation=optim.Momentum(learning_rate=0.1,
                                            momentum=0.9), seed=seed)
    feeding = {"res_x": dense_vector(4), "res_lab": integer_value(2)}

    def reader():
        rng = np.random.RandomState(0)      # identical batches every pass
        xs = rng.randn(24, 4).astype(np.float32)
        ys = (xs[:, 0] > 0).astype(np.int64)
        for i in range(0, 24, 8):
            yield [(xs[j], int(ys[j])) for j in range(i, i + 8)]

    return tr, feeding, reader


def _params_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    return len(la) == len(lb) and all(
        np.array_equal(np.asarray(x), np.asarray(y))
        for x, y in zip(la, lb))


def test_trainer_step_fault_then_resume_bit_identical(tmp_path):
    """A trainer crash mid-pass (injected trainer.step fault) resumes
    via train(resume=True) from the latest complete pass — final params
    bit-identical to an uninterrupted run (rng stream checkpointed)."""
    sd = str(tmp_path / "ckpt")
    t1, feeding, reader = _tiny_trainer()
    # 3 batches/pass: hit 5 = pass 1, batch 1 — mid-pass, after the
    # pass-0 checkpoint landed
    faults.install_spec("trainer.step:at=5")
    with pytest.raises(InjectedFault):
        t1.train(reader, num_passes=2, feeding=feeding, log_period=0,
                 buffered_batches=0, save_dir=sd)
    faults.clear()
    assert sorted(d for d in os.listdir(sd) if d.startswith("pass-")) \
        == ["pass-00000"]

    t2, feeding, reader = _tiny_trainer()
    t2.train(reader, num_passes=2, feeding=feeding, log_period=0,
             buffered_batches=0, save_dir=sd, resume=True)

    t3, feeding, reader = _tiny_trainer()
    t3.train(reader, num_passes=2, feeding=feeding, log_period=0,
             buffered_batches=0)
    assert _params_equal(jax.device_get(t2.parameters),
                         jax.device_get(t3.parameters)), \
        "resumed params diverged from the uninterrupted run"
    # resume with nothing to resume is a fresh run, not an error
    t4, feeding, reader = _tiny_trainer()
    t4.train(reader, num_passes=1, feeding=feeding, log_period=0,
             buffered_batches=0, save_dir=str(tmp_path / "fresh"),
             resume=True)


def test_preemption_midpass_resume_bit_identical(tmp_path):
    """A SIGTERM-style preemption checkpoint is MID-pass: its meta
    carries batches_done, and train(resume=True) re-enters that pass
    skipping exactly the trained prefix (no step, no rng split) — final
    params bit-identical to an uninterrupted run."""
    from paddle_tpu.trainer import events
    from paddle_tpu.trainer.checkpoint import load_checkpoint
    sd = str(tmp_path / "ckpt")
    t1, feeding, reader = _tiny_trainer()

    def preempt(e):
        # the graceful-stop path without a real signal: mid pass 1
        # (batch 0 of 3), exactly what a TPU maintenance TERM produces
        if isinstance(e, events.EndIteration) and e.pass_id == 1 \
                and e.batch_id == 0:
            t1._stop_signal = 15
    t1.train(reader, num_passes=3, feeding=feeding, log_period=0,
             buffered_batches=0, save_dir=sd, event_handler=preempt)
    _, _, _, meta = load_checkpoint(sd)
    assert meta["preempted"] is True and meta["pass_id"] == 1
    assert meta["batches_done"] == 1

    t2, feeding, reader = _tiny_trainer()
    t2.train(reader, num_passes=3, feeding=feeding, log_period=0,
             buffered_batches=0, save_dir=sd, resume=True)
    t3, feeding, reader = _tiny_trainer()
    t3.train(reader, num_passes=3, feeding=feeding, log_period=0,
             buffered_batches=0)
    assert _params_equal(jax.device_get(t2.parameters),
                         jax.device_get(t3.parameters)), \
        "mid-pass preemption resume diverged"


def test_checkpoint_write_fault_leaves_no_partial(tmp_path):
    """An injected failure mid-checkpoint-write surfaces to the caller,
    leaves NO partial pass dir or .tmp- droppings, and the next save
    succeeds."""
    from paddle_tpu.trainer.checkpoint import (load_checkpoint,
                                               save_checkpoint)
    params = {"w": np.arange(4, dtype=np.float32)}
    faults.install_spec("trainer.checkpoint.write:at=1")
    with pytest.raises(InjectedFault):
        save_checkpoint(str(tmp_path), 0, params, block=True)
    faults.clear()
    assert [d for d in os.listdir(tmp_path)] == []      # fully cleaned
    save_checkpoint(str(tmp_path), 0, params, block=True)
    p, _, _, meta = load_checkpoint(str(tmp_path))
    assert meta["pass_id"] == 0
    np.testing.assert_array_equal(np.asarray(p["w"]), params["w"])


def test_partial_tmp_checkpoint_never_picked_up(tmp_path):
    """resume/load skip a mid-write partial (the exact artifact a kill
    -9 inside the writer leaves: a hidden .tmp- dir, data but no
    rename) and take the latest COMPLETE pass instead."""
    from paddle_tpu.trainer.checkpoint import (load_checkpoint,
                                               save_checkpoint)
    save_checkpoint(str(tmp_path), 0, {"w": np.zeros(2, np.float32)},
                    block=True)
    partial = tmp_path / ".tmp-pass-00001-killed"
    partial.mkdir()
    np.savez(partial / "params.npz", w=np.ones(2, np.float32))  # no meta,
    #                                                             no rename
    _, _, _, meta = load_checkpoint(str(tmp_path))
    assert meta["pass_id"] == 0         # the partial was never eligible


@pytest.mark.slow
def test_kill9_mid_checkpoint_write_resumes_bit_identical(tmp_path):
    """The honest crash: a subprocess trainer's pass-1 checkpoint write
    HANGS mid-write (injected hang inside the .tmp- staging dir) and the
    process is SIGKILLed in that window.  On disk: complete pass-0, a
    partial .tmp- for pass 1.  train(resume=True) must pick pass-0 and
    finish to params bit-identical to an uninterrupted run."""
    import signal
    import subprocess
    import sys
    sd = str(tmp_path / "ckpt")
    script = tmp_path / "victim.py"
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    script.write_text(
        # the script runs from tmp_path: both the repo root (paddle_tpu)
        # and tests/ (this module) must be put on sys.path explicitly
        "import sys; sys.path[:0] = [%r, %r]\n"
        "from paddle_tpu.resilience import faults\n"
        "from test_resilience import _tiny_trainer\n"
        # pass-1's write hangs AFTER params.npz landed in the .tmp- dir
        "faults.install_spec("
        "'trainer.checkpoint.write:at=2,action=hang,hang_s=600')\n"
        "tr, feeding, reader = _tiny_trainer()\n"
        "tr.train(reader, num_passes=2, feeding=feeding, log_period=0,\n"
        "         buffered_batches=0, save_dir=%r)\n"
        % (os.path.dirname(tests_dir), tests_dir, sd))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen([sys.executable, str(script)], env=env,
                            stdout=subprocess.DEVNULL,
                            stderr=subprocess.DEVNULL)
    try:
        deadline = time.time() + 240
        partial = None
        while time.time() < deadline and partial is None:
            if os.path.isdir(sd):
                partial = next((d for d in os.listdir(sd)
                                if d.startswith(".tmp-pass-00001")), None)
            time.sleep(0.1)
        assert partial is not None, "pass-1 mid-write window never opened"
        proc.send_signal(signal.SIGKILL)
        proc.wait(30)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert proc.returncode == -signal.SIGKILL
    # the kill left exactly the crash artifacts the atomic writer promises
    assert sorted(d for d in os.listdir(sd) if d.startswith("pass-")) \
        == ["pass-00000"]
    assert any(d.startswith(".tmp-pass-00001") for d in os.listdir(sd))

    t2, feeding, reader = _tiny_trainer()
    t2.train(reader, num_passes=2, feeding=feeding, log_period=0,
             buffered_batches=0, save_dir=sd, resume=True)
    t3, feeding, reader = _tiny_trainer()
    t3.train(reader, num_passes=2, feeding=feeding, log_period=0,
             buffered_batches=0)
    assert _params_equal(jax.device_get(t2.parameters),
                         jax.device_get(t3.parameters))


# ------------------------------------------------------------ HTTP layer


def test_http_readyz_retry_after_and_liveness(engine):
    """The liveness/readiness split + Retry-After satellites, end to
    end: /healthz stays 200 through warming, breaker-open, and drain;
    /readyz flips 503 with the blocking reasons; 429/503 carry
    Retry-After."""
    engine.metrics = ServingMetrics()
    sup = Supervisor(breaker_threshold=1, breaker_cooldown_s=30.0)
    gen = GenerationBatcher(engine, supervisor=sup)
    httpd = make_server(None, port=0, gen_batcher=gen)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.port}"
    try:
        with urllib.request.urlopen(f"{base}/readyz", timeout=30) as r:
            assert json.loads(r.read())["status"] == "ready"
        # force the breaker open: readiness drops, liveness holds, and
        # a generate request sheds 503 + Retry-After fast
        sup.breaker.record_failure()
        assert sup.breaker.state == "open"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/readyz", timeout=30)
        assert ei.value.code == 503
        assert "breaker_open" in json.loads(ei.value.read())["reasons"]
        with urllib.request.urlopen(f"{base}/healthz", timeout=30) as r:
            body = json.loads(r.read())
            assert body["status"] == "ok" and body["draining"] is False
        req = urllib.request.Request(
            f"{base}/v1/generate",
            data=json.dumps({"prompt": [1, 2, 3],
                             "max_tokens": 3}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 503
        assert int(ei.value.headers["Retry-After"]) >= 1
        sup.breaker.record_success()        # close it again
        # drain begun: /readyz 503 draining, /healthz still 200
        gen.close()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/readyz", timeout=30)
        assert "draining" in json.loads(ei.value.read())["reasons"]
        with urllib.request.urlopen(f"{base}/healthz", timeout=30) as r:
            body = json.loads(r.read())
            assert body["status"] == "ok" and body["draining"] is True
    finally:
        httpd.shutdown()
        gen.close()


def test_http_readyz_warming_and_overload_retry_after():
    eng = _mlp_engine(warm=False)       # cold ladder: not ready yet
    bat = Batcher(eng, max_delay_ms=0.0, queue_size=2)
    httpd = make_server(bat, port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.port}"
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"{base}/readyz", timeout=30)
        assert ei.value.code == 503
        assert json.loads(ei.value.read())["reasons"] == ["warming"]
        eng.warmup()
        with urllib.request.urlopen(f"{base}/readyz", timeout=30) as r:
            assert json.loads(r.read())["status"] == "ready"
        # overload: stall the engine, fill the bounded queue, expect a
        # 429 with a queue-depth-derived Retry-After
        orig = eng.infer

        def slow(feed):
            time.sleep(0.4)
            return orig(feed)
        eng.infer = slow
        row = {"rx": np.zeros((8,), np.float32)}
        bat.submit(row)                 # occupies the worker
        time.sleep(0.05)
        bat.submit(row)
        bat.submit(row)                 # queue (size 2) now full
        req = urllib.request.Request(
            f"{base}/v1/infer",
            data=json.dumps({"feed": {"rx": [0.0] * 8}}).encode(),
            headers={"Content-Type": "application/json"})
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=30)
        assert ei.value.code == 429
        assert int(ei.value.headers["Retry-After"]) >= 1
        eng.infer = orig
    finally:
        httpd.shutdown()
        bat.close()


# ------------------------------------------------------------ drain


def test_drain_deadline_and_second_sigterm_unit():
    """Both forced-shutdown paths of the SIGTERM handler, without
    signals: (a) a drain that never completes force-exits at the hard
    deadline; (b) a second SIGTERM force-exits immediately; (c) a drain
    that completes in time never force-exits."""
    from paddle_tpu.serving.server import _make_drain_handler

    class FakeHttpd:
        def shutdown(self):
            pass

    exits = []
    state = {}
    handler = _make_drain_handler(FakeHttpd(), state, 0.2, exits.append)
    handler(15, None)                   # first SIGTERM: drain + watchdog
    assert exits == []
    handler(15, None)                   # second SIGTERM: immediate
    assert exits == [130]
    time.sleep(0.3)                     # wedged drain: deadline fires
    assert exits == [130, 3]

    exits2, state2 = [], {}
    handler2 = _make_drain_handler(FakeHttpd(), state2, 0.2, exits2.append)
    handler2(15, None)
    state2["drained"] = True            # the drain completed in time
    time.sleep(0.3)
    assert exits2 == []                 # watchdog disarmed


@pytest.mark.slow
def test_second_sigterm_forces_exit_subprocess():
    """Integration: a real server under a real double SIGTERM exits
    immediately with the forced-exit code and logs both paths."""
    import signal
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.serving", "--demo",
         "--port", "0", "--buckets", "1,4", "--drain-timeout-s", "60"],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        text=True,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    try:
        # wait for the server to be up (warm-up logged to stderr)
        deadline = time.time() + 240
        for line in proc.stderr:
            if "serving demo on" in line or time.time() > deadline:
                break
        # the startup log prints just BEFORE _serve() installs the
        # handlers; give installation a moment or the first SIGTERM
        # hits the default handler and simply terminates the process
        time.sleep(1.0)
        proc.send_signal(signal.SIGTERM)
        # wait until the FIRST handler observably ran (its drain log
        # line) before the second signal: two quick SIGTERMs can
        # coalesce into one handler invocation, and only after the line
        # is the serve_forever poll window (<=0.5s) reliably still open
        deadline = time.time() + 30
        for line in proc.stderr:
            if "SIGTERM: draining" in line or time.time() > deadline:
                break
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(30)
    finally:
        if proc.poll() is None:
            proc.kill()
    assert rc == 130, rc                # the forced-exit code


# ------------------------------------------------------------ metrics


def test_resilience_metrics_render():
    m = ServingMetrics(name="r")
    m.observe_retry()
    m.observe_watchdog_trip()
    m.observe_slot_reprefill(2)
    m.set_breaker_state("open", opened_total=1)
    m.reject("breaker")
    m.evict_slot("recovered")
    text = m.render_prometheus()
    assert "r_retries_total 1" in text
    assert "r_watchdog_trips_total 1" in text
    assert "r_slot_reprefills_total 2" in text
    assert "r_breaker_open_total 1" in text
    assert "r_breaker_state 2" in text
    assert 'r_rejected_total{reason="breaker"} 1' in text
    assert 'r_slot_evictions_total{reason="recovered"} 1' in text
    faults.install_spec("serving.decode_step:at=1")
    try:
        faults.hit("serving.decode_step")
    except InjectedFault:
        pass
    assert 'r_fault_injections_total{point="serving.decode_step"} 1' \
        in m.render_prometheus()
    faults.clear()
    snap = m.snapshot()
    assert snap["slot_reprefills_total"] == 2
    assert snap["breaker_state"] == 2

"""SLO-holding control plane (serving/autoscaler.py + serving/overload.py;
docs/serving.md §8).

Fast lane: the control LAW against scripted stub fleets on a SIMULATED
clock — scale-out on sustained TTFT-p99 breach, scale-in on sustained
slack with the idle-victim rule, flap-free hysteresis under oscillating
load, min/max bounds, `fleet.spawn`/`autoscaler.scale` chaos with
seeded-backoff retries, the brownout ladder's exact rung entry/exit
counter sequences, AIMD limiter + priority shed order + honest
Retry-After, router-level shedding/brownout effects over stub replicas,
and the headline determinism property: the full decision journal
replays BIT-FOR-BIT given the same seed and simulated clock.  No test
here sleeps for control-loop time — the injectable clock is the point.

Slow lane: the real-subprocess drive — `python -m
paddle_tpu.serving.autoscaler --smoke` (1 replica + seeded spike →
scale-out to 2 → recover → scale-in, zero failed requests).
"""

import json
import os
import random
import socket
import struct
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import pytest

from paddle_tpu.resilience import faults
from paddle_tpu.serving.autoscaler import Autoscaler
from paddle_tpu.serving.overload import (AIMDLimiter, BrownoutLadder,
                                         DrainRate, OverloadController,
                                         ShedError)
from paddle_tpu.serving.router import Router, RouterMetrics
from paddle_tpu.utils.stats import Histogram

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _no_leaked_fault_plan():
    yield
    faults.clear()


# --------------------------------------------------------------- harness


class SimClock:
    """A monotonic clock the test advances by hand."""

    def __init__(self, t=100.0):
        self.t = float(t)

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += float(dt)
        return self.t


class StubSupervisor:
    """A scripted ReplicaSupervisor: add/remove bookkeeping without
    subprocesses.  ``add_replica`` fires the same ``fleet.spawn`` fault
    point the real one does, so seeded chaos plans hit identically."""

    def __init__(self, n=1, ready=True):
        self.replicas = {f"r{i}": object() for i in range(n)}
        self._next = n
        self.added, self.removed = [], []
        self.ready = ready              # wait_ready verdict (scriptable)

    def add_replica(self):
        faults.hit("fleet.spawn")
        rid = f"r{self._next}"
        self._next += 1
        self.replicas[rid] = object()
        self.added.append(rid)
        return rid

    def remove_replica(self, rid, drain_timeout=60.0):
        self.replicas.pop(rid)
        self.removed.append(rid)

    def wait_ready(self, timeout=0.0, rids=None):
        return self.ready


class StubRouterView:
    """The router surface the autoscaler consumes: a real RouterMetrics
    (sim-clocked recent windows) + a scriptable replica_states()."""

    def __init__(self, clock, states=None):
        self.metrics = RouterMetrics(clock=clock)
        self.extra_render_fns = []
        self.states = states if states is not None else {
            "r0": {"ready": True, "queue_depth": 0, "inflight": 0,
                   "breaker": "closed"}}

    def replica_states(self):
        return {rid: dict(st) for rid, st in self.states.items()}

    def set_replica(self, rid, ready=True, queue_depth=0, inflight=0,
                    breaker="closed"):
        self.states[rid] = {"ready": ready, "queue_depth": queue_depth,
                            "inflight": inflight, "breaker": breaker}


def make_scaler(sup, router, clk, **kw):
    base = dict(poll_interval_s=1.0, target_ttft_ms=500.0, hysteresis=0.2,
                breach_polls=3, slack_polls=4, cooldown_out_s=5.0,
                cooldown_in_s=20.0, min_replicas=1, max_replicas=3,
                window_s=10.0, seed=7, ready_timeout_s=1.0,
                clock=clk)
    base.update(kw)
    return Autoscaler(sup, router, **base)


def feed_ttft(router, ms, n=5):
    for _ in range(n):
        router.metrics.observe_ttft(ms / 1e3)


# ------------------------------------------------- injectable clock plumbing


def test_histogram_windowed_percentiles_sim_clock():
    """The satellite clock threading: a sim-clocked Histogram's windowed
    p99 expires samples deterministically — no wall-clock sleeps — and
    a clockless Histogram rejects window_s while behaving exactly as
    before otherwise."""
    clk = SimClock(0.0)
    h = Histogram("t", keep="last", clock=clk)
    h.add(1.0)
    clk.advance(5)
    h.add(0.1)
    assert h.percentiles((99,))[99] > 0.9          # un-windowed: all
    assert h.percentiles((99,), window_s=3)[99] == pytest.approx(0.1)
    clk.advance(10)
    assert h.percentiles((99,), window_s=3)[99] == 0.0   # expired
    plain = Histogram("p")
    plain.add(2.0)
    assert plain.percentiles((50,))[50] == 2.0
    with pytest.raises(ValueError, match="clock"):
        plain.percentiles((50,), window_s=1)


def test_router_metrics_slo_signal_prefers_ttft():
    clk = SimClock()
    m = RouterMetrics(clock=clk)
    # EMPTY window = no signal, not "healthy 0ms"
    assert m.slo_p99_recent_s(10) is None
    m.observe_response(0.4)
    assert m.slo_p99_recent_s(10) == pytest.approx(0.4)   # latency fallback
    m.observe_ttft(0.05)
    assert m.slo_p99_recent_s(10) == pytest.approx(0.05)  # ttft wins
    assert "ttft_ms" in m.snapshot()
    # samples expiring out of the window bring the None back
    clk.advance(100)
    assert m.slo_p99_recent_s(10) is None


# ------------------------------------------------------------- control law


def test_scale_out_on_sustained_breach_only():
    """A breach must HOLD for breach_polls before anything moves; the
    scale-out lands exactly on the Nth breach poll and capacity follows
    spawn-to-readiness."""
    clk = SimClock()
    sup = StubSupervisor(1)
    router = StubRouterView(clk)
    a = make_scaler(sup, router, clk, breach_polls=3)
    feed_ttft(router, 2000)
    decisions = []
    for _ in range(4):
        decisions.append(a.tick()["decision"])
        clk.advance(1.0)
    assert decisions[:2] == ["hold", "hold"]    # streak building
    assert decisions[2] == "out"                # 3rd consecutive breach
    assert sup.added == ["r1"]
    assert len(sup.replicas) == 2
    assert a.scales_total["out"] == 1
    # one transient blip never scales: streak resets on a healthy poll
    sup2 = StubSupervisor(1)
    router2 = StubRouterView(clk)
    b = make_scaler(sup2, router2, clk, breach_polls=3, window_s=0.5)
    for i in range(6):
        # alternate: one breached poll, one healthy poll
        router2.metrics.observe_ttft(2.0 if i % 2 == 0 else 0.05)
        b.tick()
        clk.advance(1.0)
    assert sup2.added == []


def test_max_and_min_bounds_are_hard():
    clk = SimClock()
    sup = StubSupervisor(2)
    router = StubRouterView(clk)
    router.set_replica("r1")
    a = make_scaler(sup, router, clk, breach_polls=1, max_replicas=2,
                    cooldown_out_s=0.0)
    feed_ttft(router, 2000)
    e = a.tick()
    assert e["decision"] == "hold" and "max_replicas" in e["reason"]
    assert sup.added == []
    # and the floor: slack at min_replicas never scales in
    clk.advance(100)
    sup2 = StubSupervisor(1)
    router2 = StubRouterView(clk)
    b = make_scaler(sup2, router2, clk, slack_polls=1, min_replicas=1,
                    cooldown_in_s=0.0)
    feed_ttft(router2, 10)
    for _ in range(5):
        assert b.tick()["decision"] == "hold"
        clk.advance(1.0)
    assert sup2.removed == []


def test_scale_in_never_drains_active_when_idle_exists():
    """The small-fix satellite: the scale-in victim is the IDLE replica,
    even when the busy one sorts first by id."""
    clk = SimClock()
    sup = StubSupervisor(2)
    router = StubRouterView(clk)
    router.set_replica("r0", inflight=3)        # busy, lower id
    router.set_replica("r1", inflight=0)        # idle
    a = make_scaler(sup, router, clk, slack_polls=2, cooldown_in_s=0.0)
    feed_ttft(router, 10)
    a.tick()
    clk.advance(1.0)
    e = a.tick()
    assert e["decision"] == "in"
    assert sup.removed == ["r1"], "drained the busy replica instead " \
        "of the idle one"
    # with NO idle replica, the least-loaded one drains (graceful drain
    # finishes its streams; drain-then-death is pinned separately below)
    clk.advance(100)
    sup2 = StubSupervisor(2)
    router2 = StubRouterView(clk)
    router2.set_replica("r0", inflight=5)
    router2.set_replica("r1", inflight=1)
    b = make_scaler(sup2, router2, clk, slack_polls=1, cooldown_in_s=0.0)
    feed_ttft(router2, 10)
    b.tick()
    assert sup2.removed == ["r1"]


def test_scale_in_removes_dead_replica_before_draining_healthy():
    """Review hardening: the scale-in victim is a NOT-serving replica
    (dead/backoff) when one exists — draining the only healthy replica
    while a corpse stays counted would be a self-inflicted outage."""
    clk = SimClock()
    sup = StubSupervisor(2)
    router = StubRouterView(clk)
    router.set_replica("r0", ready=True, inflight=0)    # healthy + idle
    router.set_replica("r1", ready=False)               # dead/backoff
    a = make_scaler(sup, router, clk, slack_polls=1, cooldown_in_s=0.0)
    feed_ttft(router, 10)
    e = a.tick()
    assert e["decision"] == "in"
    assert sup.removed == ["r1"], "drained the healthy replica while " \
        "a dead one stayed counted"


def test_total_stall_no_signal_never_reads_as_slack():
    """Review hardening: an EMPTY SLO window (nothing completed) with
    work still in flight is a stall, not health — the loop holds; only
    a provably idle fleet (no queue, no inflight) shrinks on
    no-signal."""
    clk = SimClock()
    sup = StubSupervisor(2)
    router = StubRouterView(clk)
    router.set_replica("r0", inflight=3)        # stuck in-flight work
    router.set_replica("r1", inflight=2)
    a = make_scaler(sup, router, clk, slack_polls=1, cooldown_in_s=0.0)
    # no ttft/latency samples at all -> p99 is None
    for _ in range(5):
        e = a.tick()
        assert e["decision"] == "hold", e
        assert e["signals"]["ttft_p99_ms"] is None
        clk.advance(1.0)
    assert sup.removed == []
    # the same no-signal fleet, provably idle -> slack applies
    router.set_replica("r0", inflight=0)
    router.set_replica("r1", inflight=0)
    e = a.tick()
    assert e["decision"] == "in" and "no-signal" in e["reason"]


def test_flap_free_under_oscillating_load():
    """The acceptance bar: under oscillating load the replica count
    changes at most once per cooldown window — consecutive scale events
    are separated by at least the acting direction's cooldown."""
    clk = SimClock()
    sup = StubSupervisor(1)
    router = StubRouterView(clk)
    a = make_scaler(sup, router, clk, breach_polls=2, slack_polls=2,
                    cooldown_out_s=4.0, cooldown_in_s=10.0,
                    window_s=0.5, max_replicas=2)
    events = []
    for i in range(120):
        # square-wave load: 6 polls loud, 6 polls quiet — each phase is
        # long enough to fill either streak, so only the cooldowns damp
        router.metrics.observe_ttft(2.0 if (i // 6) % 2 == 0 else 0.01)
        # keep the router view in lockstep with the fleet (the real
        # poller's job)
        router.states = {rid: {"ready": True, "queue_depth": 0,
                               "inflight": 0, "breaker": "closed"}
                         for rid in sup.replicas}
        e = a.tick()
        if e["decision"] in ("out", "in"):
            events.append((e["t"], e["decision"]))
        clk.advance(1.0)
    assert events, "the oscillation never moved the fleet at all"
    for (t1, _d1), (t2, d2) in zip(events, events[1:]):
        need = 4.0 if d2 == "out" else 10.0
        assert t2 - t1 >= need, (events, "flapped faster than cooldown")


# ------------------------------------------------------------ chaos legs


def test_spawn_fault_retries_with_seeded_backoff():
    """fleet.spawn chaos: the injected spawn failure is retried with the
    EXACT seeded backoff delay, the failed attempt registers nothing,
    and the retry succeeds once the fault is spent."""
    clk = SimClock()
    sup = StubSupervisor(1)
    router = StubRouterView(clk)
    router.set_replica("r0", queue_depth=4, inflight=2)
    a = make_scaler(sup, router, clk, breach_polls=1, cooldown_out_s=0.0,
                    seed=13, retry_base_s=0.5, retry_max_s=4.0)
    feed_ttft(router, 2000)
    faults.install_spec("fleet.spawn:at=1")
    e = a.tick()
    assert e["decision"] == "out"
    assert e["actuation"]["ok"] is False
    assert "InjectedFault" in e["actuation"]["error"]
    assert sup.added == [] and len(sup.replicas) == 1
    assert a.scale_failures_total == 1
    # the retry delay replays the seeded stream exactly
    expect = round(0.5 * (0.5 + 0.5 * random.Random(13).random()), 4)
    assert e["actuation"]["retry_in_s"] == expect
    # before the backoff elapses: hold, no second attempt
    clk.advance(expect / 2)
    assert a.tick()["decision"] == "hold"
    assert sup.added == []
    # past the backoff: the retry fires and lands (fault was one-shot)
    clk.advance(expect)
    e = a.tick()
    assert e["decision"] == "out" and e["actuation"]["ok"] is True
    assert sup.added == ["r1"]
    assert faults.fired_counts()["fleet.spawn"] == 1


def test_unready_replica_never_counts_as_capacity():
    """A spawned replica that never reaches readiness is REMOVED and the
    attempt retried — the fleet never carries phantom capacity."""
    clk = SimClock()
    sup = StubSupervisor(1, ready=False)        # wait_ready times out
    router = StubRouterView(clk)
    router.set_replica("r0", queue_depth=4)
    a = make_scaler(sup, router, clk, breach_polls=1, cooldown_out_s=0.0)
    feed_ttft(router, 2000)
    e = a.tick()
    assert e["actuation"]["ok"] is False and "not ready" in \
        e["actuation"]["error"]
    assert sup.added == ["r1"] and sup.removed == ["r1"]
    assert len(sup.replicas) == 1
    assert a.scale_failures_total == 1


def test_autoscaler_scale_fault_point():
    """autoscaler.scale chaos: actuation fails BEFORE the supervisor is
    touched; the retry resolves it."""
    clk = SimClock()
    sup = StubSupervisor(1)
    router = StubRouterView(clk)
    a = make_scaler(sup, router, clk, breach_polls=1, cooldown_out_s=0.0,
                    retry_base_s=0.1, retry_max_s=0.1)
    feed_ttft(router, 2000)
    faults.install_spec("autoscaler.scale:at=1")
    e = a.tick()
    assert e["actuation"]["ok"] is False
    assert sup.added == [], "a failed decision must not touch the fleet"
    clk.advance(1.0)
    e = a.tick()
    assert e["actuation"]["ok"] is True and sup.added == ["r1"]
    assert faults.fired_counts()["autoscaler.scale"] == 1


def test_real_supervisor_spawn_fault_becomes_backoff_restart():
    """The REAL ReplicaSupervisor placement of fleet.spawn: an injected
    spawn failure on start() is accounted exactly like an instant crash
    — seeded backoff schedule, then the monitor retries and the replica
    comes up (no supervisor thread death, no unhandled exception)."""
    from paddle_tpu.serving.fleet import ReplicaSupervisor
    faults.install_spec("fleet.spawn:at=1")
    sup = ReplicaSupervisor(n_replicas=1,
                            cmd=["-c", "import time; time.sleep(60)"],
                            backoff_base_s=0.05, backoff_max_s=0.4,
                            seed=11, name="spawn_fault_t")
    sup.start()
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            snap = sup.snapshot()["r0"]
            if snap["pid"] is not None:
                break
            time.sleep(0.02)
        snap = sup.snapshot()["r0"]
        assert snap["pid"] is not None, snap
        assert snap["consecutive_failures"] == 1
        rng = random.Random(11 * 7919 + 0)
        expect = round(min(0.05, 0.4) * (0.5 + 0.5 * rng.random()), 4)
        assert snap["backoff_delays_s"] == [expect]
        assert faults.fired_counts()["fleet.spawn"] == 1
    finally:
        sup.stop()


def test_retry_abandoned_when_conditions_invert():
    """Review hardening: a pending scale-out retry is ABANDONED when the
    spike ends while the spawn was failing — the stale direction must
    not fire into a healthy fleet; the law re-decides from fresh
    streaks."""
    clk = SimClock()
    sup = StubSupervisor(1)
    router = StubRouterView(clk)
    a = make_scaler(sup, router, clk, breach_polls=1, cooldown_out_s=0.0,
                    window_s=2.0, retry_base_s=5.0, retry_max_s=5.0)
    feed_ttft(router, 2000)
    faults.install_spec("fleet.spawn:every=1")      # every spawn fails
    e = a.tick()
    assert e["decision"] == "out" and e["actuation"]["ok"] is False
    faults.clear()
    # the spike ends during the backoff: breach samples expire, healthy
    # ones land
    clk.advance(3.0)
    router.metrics.observe_ttft(0.01)
    clk.advance(3.0)                    # past the retry-at time
    router.metrics.observe_ttft(0.01)
    e = a.tick()
    assert e["decision"] == "hold", e    # retry dropped, law re-decides
    for _ in range(5):
        clk.advance(1.0)
        router.metrics.observe_ttft(0.01)
        e = a.tick()
        assert e["decision"] != "out", e
    assert sup.added == [], "stale retry scaled a healthy fleet"


# ----------------------------------------------------- bit-for-bit replay


def _scripted_run(seed):
    """One full scripted scenario (breach -> chaos -> recovery -> slack)
    on a fresh sim-clocked stub fleet; returns the journal lines."""
    faults.clear()
    faults.install_spec("fleet.spawn:at=2")
    clk = SimClock(50.0)
    sup = StubSupervisor(1)
    router = StubRouterView(clk)
    a = make_scaler(sup, router, clk, breach_polls=2, slack_polls=3,
                    cooldown_out_s=2.0, cooldown_in_s=6.0, seed=seed,
                    retry_base_s=0.5, window_s=4.0, max_replicas=3)
    script = [2000] * 8 + [100] * 4 + [2000] * 6 + [10] * 14
    for i, ms in enumerate(script):
        router.metrics.observe_ttft(ms / 1e3)
        for rid in list(sup.replicas):
            router.set_replica(rid, inflight=1 if ms > 500 and
                               rid == "r0" else 0)
        a.tick()
        clk.advance(1.0)
    lines = a.journal_lines()
    faults.clear()
    return lines


def test_decision_journal_replays_bit_for_bit():
    """THE determinism acceptance bar: same seed + same simulated clock
    + same scripted signals -> the SAME decision log, byte for byte —
    including the chaos retry timing; a different seed diverges."""
    run1 = _scripted_run(seed=21)
    run2 = _scripted_run(seed=21)
    assert run1 == run2
    assert any('"decision": "out"' in ln for ln in run1)
    assert any('"decision": "in"' in ln for ln in run1)
    assert any('"ok": false' in ln for ln in run1)    # the chaos leg
    run3 = _scripted_run(seed=22)
    assert run3 != run1                 # the seed is load-bearing


# ------------------------------------------------------- brownout ladder


def test_brownout_ladder_exact_rung_sequences():
    """Rung entry/exit counters, exactly: sustained breach climbs one
    rung per hold period (hedge_off -> token_cap -> shed_background),
    sustained health walks back down one rung per exit period, and a
    short blip moves nothing."""
    clk = SimClock(0.0)
    lad = BrownoutLadder(slo_ttft_s=0.5, enter_hold_s=2.0, exit_hold_s=3.0,
                         clock=clk)
    rungs = []
    for _ in range(9):                      # 9s of breach
        rungs.append(lad.observe(1.0))
        clk.advance(1.0)
    # t=0 arm, t=2 rung1, t=4 rung2, t=6 rung3, capped thereafter
    assert rungs == [0, 0, 1, 1, 2, 2, 3, 3, 3]
    assert lad.entries == {"hedge_off": 1, "token_cap": 1,
                           "shed_background": 1}
    assert lad.exits == {"hedge_off": 0, "token_cap": 0,
                         "shed_background": 0}
    assert not lad.hedging_allowed() and lad.shed_background()
    rungs = []
    for _ in range(11):                     # 11s of health
        rungs.append(lad.observe(0.1))
        clk.advance(1.0)
    assert rungs == [3, 3, 3, 2, 2, 2, 1, 1, 1, 0, 0]
    assert lad.exits == {"hedge_off": 1, "token_cap": 1,
                         "shed_background": 1}
    assert lad.hedging_allowed() and not lad.shed_background()
    # a 1s blip (under enter_hold) never enters a rung
    lad.observe(1.0)
    clk.advance(1.0)
    assert lad.observe(0.1) == 0
    assert lad.entries["hedge_off"] == 1
    # disabled ladder is inert
    off = BrownoutLadder(slo_ttft_s=0.0, clock=clk)
    for _ in range(10):
        assert off.observe(99.0) == 0
        clk.advance(5.0)


# -------------------------------------------- AIMD limiter + shed policy


def test_aimd_limiter_increase_decrease_and_class_order():
    clk = SimClock()
    # class slices of a limit of 3: background 1.8, standard 2.55,
    # interactive 3.0 — background saturates (sheds) first
    lim2 = AIMDLimiter(initial=3, min_limit=1, max_limit=8,
                       decrease_cooldown_s=1.0, clock=clk)
    for _ in range(2):
        assert lim2.try_acquire("standard")       # 0,1 < 2.55
    assert not lim2.try_acquire("background")     # 2 >= 1.8: shed first
    assert lim2.try_acquire("interactive")        # 2 < 3: still admitted
    assert not lim2.try_acquire("interactive")    # 3 >= 3: full
    # multiplicative decrease, once per cooldown window
    lim2.release(overloaded=True)
    assert lim2.limit == 1.5 and lim2.decreases_total == 1
    lim2.release(overloaded=True)                 # same congestion event
    assert lim2.limit == 1.5 and lim2.decreases_total == 1
    clk.advance(2.0)
    lim2.release(overloaded=True)
    assert lim2.limit == 1.0                      # floored at min_limit
    # additive increase on clean completions: +increase/limit each
    lim3 = AIMDLimiter(initial=2, increase=1.0, clock=clk)
    lim3.try_acquire()
    lim3.release()
    assert lim3.limit == pytest.approx(2.5)


def test_retry_after_is_honest_drain_rate():
    """Retry-After = excess in-flight over observed completions/s —
    derived, not a constant."""
    clk = SimClock(0.0)
    ctl = OverloadController(limiter=AIMDLimiter(initial=2, clock=clk),
                             drain_window_s=10.0, clock=clk)
    # 2 completions/second observed for 4s
    for _ in range(8):
        ctl.drain.observe()
        clk.advance(0.5)
    assert ctl.drain.rate() == pytest.approx(2.0, rel=0.3)
    ctl.limiter.inflight = 6        # 6 in flight over a limit of 2
    ra = ctl.retry_after_s()
    # excess = 6 - 2 + 1 = 5; 5 / ~2 per s -> ~3s
    assert 2 <= ra <= 4, ra
    # shed carries it
    ctl.limiter.inflight = int(ctl.limiter.limit) + 5
    with pytest.raises(ShedError) as ei:
        ctl.admit("standard")
    assert ei.value.retry_after_s == ra or ei.value.retry_after_s >= 1
    assert ctl.shed_reasons["limit"] == 1


def test_deadline_aware_shed():
    """A request whose deadline cannot survive the estimated QUEUE wait
    (the excess beyond the parallel-service limit over the drain rate)
    is shed immediately instead of timing out inside the fleet — and at
    healthy concurrency (no excess) a deadline is never shed."""
    clk = SimClock(0.0)
    ctl = OverloadController(limiter=AIMDLimiter(initial=4, clock=clk),
                             drain_window_s=10.0, clock=clk)
    for _ in range(10):                  # ~1 completion/s
        ctl.drain.observe()
        clk.advance(1.0)
    ctl.limiter.inflight = 8             # 4 beyond the limit: ~4s queue
    with pytest.raises(ShedError) as ei:
        ctl.admit("interactive", deadline_ms=2000)
    assert ei.value.reason == "deadline"
    # healthy concurrency: inflight under the limit, zero queue wait —
    # even a tight deadline is admitted (review hardening: the fleet
    # serves in parallel, inflight/rate is NOT the wait)
    ctl.limiter.inflight = 2
    ctl.admit("interactive", deadline_ms=100)


# ------------------------------------------------ router-level integration


class _Stub:
    """Minimal scripted replica for router-level tests: /readyz 200,
    /metrics depth, /v1/infer (settable delay), /v1/generate streaming a
    scripted token list (optional death mid-stream); captures the last
    generate request body."""

    def __init__(self, infer_delay_s=0.0, gen_tokens=(), die_after=None):
        self.infer_delay_s = infer_delay_s
        self.gen_tokens = list(gen_tokens)
        self.die_after = die_after
        self.ready = True
        self.gen_bodies = []
        stub = self

        class H(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def handle(self):
                try:
                    super().handle()
                except (ConnectionError, BrokenPipeError):
                    pass

            def _send(self, code, body):
                self.send_response(code)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/readyz":
                    self._send(200 if stub.ready else 503, b"{}")
                elif self.path == "/metrics":
                    self._send(200, b"stub_queue_depth 0\n")
                else:
                    self._send(404, b"{}")

            def do_POST(self):
                body = self.rfile.read(
                    int(self.headers.get("Content-Length") or 0))
                if self.path == "/v1/infer":
                    time.sleep(stub.infer_delay_s)
                    self._send(200, b'{"outputs": {"y": [1]}}')
                    return
                stub.gen_bodies.append(json.loads(body))
                if not self.path == "/v1/generate":
                    self._send(404, b"{}")
                    return
                req = stub.gen_bodies[-1]
                n = min(len(stub.gen_tokens),
                        int(req.get("max_tokens") or 64))
                if req.get("stream"):
                    self.send_response(200)
                    self.send_header("Transfer-Encoding", "chunked")
                    self.end_headers()
                    for i, t in enumerate(stub.gen_tokens[:n]):
                        if stub.die_after is not None \
                                and i >= stub.die_after:
                            self.connection.setsockopt(
                                socket.SOL_SOCKET, socket.SO_LINGER,
                                struct.pack("ii", 1, 0))
                            self.connection.close()
                            self.close_connection = True
                            return
                        data = (json.dumps({"token": int(t)})
                                + "\n").encode()
                        self.wfile.write(f"{len(data):X}\r\n".encode()
                                         + data + b"\r\n")
                    data = (json.dumps(
                        {"done": True, "tokens": stub.gen_tokens[:n],
                         "finish_reason": "length", "ttft_ms": 12.0})
                        + "\n").encode()
                    self.wfile.write(f"{len(data):X}\r\n".encode() + data
                                     + b"\r\n0\r\n\r\n")
                else:
                    self._send(200, json.dumps(
                        {"tokens": stub.gen_tokens[:n],
                         "finish_reason": "length",
                         "ttft_ms": 12.0}).encode())

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), H)
        self.httpd.daemon_threads = True
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"

    def close(self):
        self.httpd.shutdown()


def _wait(pred, timeout=15.0, every=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(every)
    return False


def _post_raw(port, path, body, headers=None, timeout=30):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json", **(headers or {})})
    try:
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return r.status, dict(r.getheaders()), r.read()
    except urllib.error.HTTPError as e:
        data = e.read()
        hd = dict(e.headers.items())
        e.close()
        return e.code, hd, data


def test_router_sheds_lowest_class_first_with_retry_after():
    """Admission through the AIMD limit: with the limit pinned low and
    held by in-flight standard traffic, a background request sheds 429
    + Retry-After while an interactive one still lands — and the shed
    is visible in rejected{shed} + overload_shed_total{priority}."""
    stub = _Stub(infer_delay_s=0.6)
    ctl = OverloadController(limiter=AIMDLimiter(initial=3, min_limit=1))
    router = Router(replicas=[stub.url], poll_interval_s=0.05, hedge_ms=0,
                    overload=ctl)
    httpd = router.start(port=0)
    try:
        assert _wait(router.ready, 10)
        results = {}

        def infer(tag, headers):
            results[tag] = _post_raw(httpd.port, "/v1/infer", {"feed": {}},
                                     headers)

        slow = [threading.Thread(target=infer,
                                 args=(f"s{i}", {"X-Priority": "standard"}))
                for i in range(2)]
        for t in slow:
            t.start()
        # both standard permits taken (limit 3 -> standard slice 2.55)
        assert _wait(lambda: ctl.limiter.inflight >= 2, 5)
        st, hd, data = _post_raw(httpd.port, "/v1/infer", {"feed": {}},
                                 {"X-Priority": "background"})
        assert st == 429
        assert "Retry-After" in hd and int(hd["Retry-After"]) >= 1
        assert json.loads(data)["priority"] == "background"
        st2, _hd2, _ = _post_raw(httpd.port, "/v1/infer", {"feed": {}},
                                 {"X-Priority": "interactive"})
        assert st2 == 200, "interactive must outlive background"
        for t in slow:
            t.join(30)
        assert all(r[0] == 200 for r in results.values())
        snap = router.metrics.snapshot()
        assert snap["rejected"]["shed"] == 1
        osnap = ctl.snapshot()
        assert osnap["shed_total"]["background"] == 1
        assert osnap["admitted_total"]["interactive"] == 1
        mtext = router.render_prometheus()
        assert 'overload_shed_total{priority="background"} 1' in mtext
        assert "overload_limit" in mtext and "brownout_rung" in mtext
    finally:
        router.close()
        stub.close()


def test_brownout_effects_in_router():
    """The three rungs, through the real router: rung 1 suppresses
    hedging, rung 2 caps a generate's max_tokens before it reaches the
    replica, rung 3 sheds background generates outright — and the
    priority field in the body is honored."""
    clk = SimClock()
    stub = _Stub(gen_tokens=list(range(40)))
    lad = BrownoutLadder(slo_ttft_s=0.1, enter_hold_s=1.0, exit_hold_s=1.0,
                         clock=clk)
    ctl = OverloadController(ladder=lad, brownout_max_tokens=5, clock=clk)
    router = Router(replicas=[stub.url], poll_interval_s=0.05,
                    hedge_ms=40, overload=ctl)
    httpd = router.start(port=0)
    try:
        assert _wait(router.ready, 10)
        # drive the ladder to rung 3 by hand (deterministic sim clock)
        for _ in range(8):
            lad.observe(1.0)
            clk.advance(1.0)
        assert lad.rung == 3
        # rung 2 effect: max_tokens capped at 5 on the wire
        st, _hd, data = _post_raw(httpd.port, "/v1/generate",
                                  {"prompt": [1, 2, 3],
                                   "max_tokens": 30})
        assert st == 200
        assert stub.gen_bodies[-1]["max_tokens"] == 5
        assert len(json.loads(data)["tokens"]) == 5
        assert ctl.token_caps_applied_total >= 1
        # rung 3 effect: background generate shed 429 despite free limit
        st, hd, data = _post_raw(httpd.port, "/v1/generate",
                                 {"prompt": [1], "max_tokens": 3,
                                  "priority": "background"})
        assert st == 429 and "Retry-After" in hd
        assert ctl.shed_reasons["brownout"] == 1
        # rung 1 effect: hedged infer suppressed (hedges_total stays 0)
        stub.infer_delay_s = 0.3
        st, _hd, _ = _post_raw(httpd.port, "/v1/infer", {"feed": {}})
        assert st == 200
        assert router.metrics.snapshot()["hedges_total"] == 0
        assert ctl.hedges_suppressed_total >= 1
        # walk the ladder back down: full service returns
        for _ in range(5):
            lad.observe(0.01)
            clk.advance(1.0)
        assert lad.rung == 0
        st, _hd, data = _post_raw(httpd.port, "/v1/generate",
                                  {"prompt": [1, 2], "max_tokens": 8,
                                   "priority": "background"})
        assert st == 200 and len(json.loads(data)["tokens"]) == 8
        assert lad.exits["shed_background"] == 1
    finally:
        router.close()
        stub.close()


def test_autoscaler_metrics_on_router_page():
    """The autoscaler's autoscaler_* lines land on the ROUTER's /metrics
    page through extra_render_fns."""
    clk = SimClock()
    stub = _Stub()
    router = Router(replicas=[stub.url], poll_interval_s=0.05, hedge_ms=0,
                    clock=clk)
    sup = StubSupervisor(1)
    a = make_scaler(sup, router, clk)
    try:
        feed_ttft_ms = router.metrics.observe_ttft
        feed_ttft_ms(0.01)
        a.tick()
        text = router.render_prometheus()
        assert "autoscaler_replicas 1" in text
        assert 'autoscaler_decisions_total{direction="hold"} 1' in text
        assert "autoscaler_ttft_p99_ms" in text
    finally:
        router.close()
        stub.close()


# ----------------------------- drain-then-death mid-stream (small fix #2)


@pytest.fixture(scope="module")
def lm_replica():
    """One real in-process generation replica (the failover target)."""
    import jax
    import numpy as np       # noqa: F401 — used by the test below
    from paddle_tpu.models import transformer
    from paddle_tpu.serving import (DecodeEngine, GenerationBatcher,
                                    make_server)
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=64,
                              trg_vocab=1, d_model=32, num_heads=2,
                              dff=64, enc_layers=2, dec_layers=0,
                              max_len=48)
    engine = DecodeEngine(params, num_heads=2, num_slots=4, max_len=48,
                          name="autoscale_lm")
    gen = GenerationBatcher(engine)
    httpd = make_server(None, port=0, gen_batcher=gen)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield params, httpd
    httpd.shutdown()
    gen.close()


def test_drained_replica_dies_midstream_failover_bit_identical(lm_replica):
    """Small-fix satellite, part 2: a replica being DRAINED for scale-in
    (unready, mid-stream still attached) that dies before its drain
    completes must not break the stream — the router's continuation
    failover finishes it bit-identical to lm_generate."""
    import numpy as np
    from paddle_tpu.models import transformer
    params, httpd_real = lm_replica
    rng = np.random.RandomState(5)
    prompt = rng.randint(1, 64, 6).astype(np.int32)
    ids = np.asarray(transformer.lm_generate(
        params, prompt[None], max_len=48, num_heads=2,
        prompt_lengths=np.asarray([prompt.size])))
    oracle = ids[0, prompt.size:prompt.size + 10].tolist()
    # the victim: starts ready (the stream lands on it), flips UNREADY
    # at drain start, then dies after 4 tokens — drain-then-death
    victim = _Stub(gen_tokens=oracle, die_after=4)
    router = Router(replicas=[victim.url,
                              f"http://127.0.0.1:{httpd_real.port}"],
                    poll_interval_s=0.05, retry_budget=2, hedge_ms=0)
    httpd = router.start(port=0)
    try:
        assert _wait(router.ready, 10)
        got = {}

        def stream():
            import http.client
            conn = http.client.HTTPConnection("127.0.0.1", httpd.port,
                                              timeout=60)
            conn.request("POST", "/v1/generate",
                         json.dumps({"prompt": prompt.tolist(),
                                     "max_tokens": 10,
                                     "stream": True}).encode(),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            toks, done = [], None
            while True:
                line = resp.readline()
                if not line:
                    break
                rec = json.loads(line)
                if "token" in rec:
                    toks.append(rec["token"])
                    if len(toks) == 1:
                        # the drain begins while the stream is live:
                        # the victim drops out of readiness (exactly
                        # what a SIGTERM'd replica's /readyz does)
                        victim.ready = False
                if rec.get("done"):
                    done = rec
                    break
            conn.close()
            got["toks"], got["done"] = toks, done

        t = threading.Thread(target=stream)
        t.start()
        t.join(60)
        assert not t.is_alive(), "stream wedged"
        # ... and then it died before the drain finished (die_after=4):
        # the stream must still have completed bit-identically
        assert got["toks"] == oracle, (got["toks"], oracle)
        assert got["done"] is not None and got["done"]["tokens"] == oracle
        snap = router.metrics.snapshot()
        assert snap["midstream_failovers_total"] == 1
        # and the router recorded a fleet-level TTFT sample for the SLO
        assert router.metrics.ttft.count >= 1
    finally:
        router.close()
        victim.close()


# ------------------------------------------------------------- slow lane


@pytest.mark.slow
def test_autoscale_smoke_real_subprocess_drive(tmp_path):
    """The real-2-subprocess scale-out drive: `--smoke` spawns 1 demo
    replica + router + autoscaler, breaches the TTFT target with a
    seeded spike, scales out to 2 to readiness, recovers under target,
    scales back in — zero failed requests, every completed stream
    bit-identical to lm_generate."""
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.setdefault("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "xla"))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.serving.autoscaler", "--smoke"],
        env=env, cwd=_ROOT, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["value"] == int(out["unit"].split("/")[1]), out
    assert out["scaled_out"] is True and out["scaled_in"] is True
    assert out["failed"] == 0 and out["completed"] > 0
    assert out["recovered_under_target"] is True
    assert out["decisions_out"] >= 1 and out["decisions_in"] >= 1


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))

"""Replicated serving tier (serving/fleet.py + serving/router.py): the
fleet-scope chaos matrix (docs/serving.md §7).

In-process half: the router's policies against scripted stub replicas
(readiness gating, least-loaded dispatch, outlier ejection + half-open
readmission, the ``router.dispatch`` fault point, hedging) and against a
REAL in-process replica (mid-stream failover bit-identity, client-
disconnect propagation to ``abandon()``, the continuation ``replay``
submit contract).

Subprocess half: a real 2-replica fleet behind the router — kill -9 one
replica under 8 concurrent streaming clients and every stream must
finish BIT-IDENTICAL to ``lm_generate``; the supervisor restarts the
victim with the exact seeded backoff; a rolling-drain sweep completes
with zero failed requests.
"""

import http.client
import json
import random
import signal
import socket
import struct
import sys
import threading
import time
import urllib.request
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import pytest
import jax

from paddle_tpu.models import transformer
from paddle_tpu.resilience import FaultPlan, faults
from paddle_tpu.serving import (DecodeEngine, GenerationBatcher,
                                ReplicaSupervisor, Router, ServingMetrics,
                                make_server)

VOCAB, HEADS, MAX_LEN, SLOTS = 64, 2, 48, 4

# the fleet replicas' demo-LM scale (server.py _demo_gen_batcher with the
# flags below); the decode-step hang paces tokens so kills land MID-stream
FLEET_VOCAB, FLEET_MAX_LEN, FLEET_TOKENS = 256, 64, 20
FLEET_ARGS = ["--gen-slots", "4", "--gen-max-len", str(FLEET_MAX_LEN),
              "--gen-max-tokens", str(FLEET_TOKENS),
              "--fault-spec",
              "serving.decode_step:every=1,action=hang,hang_s=0.02"]
FLEET_SEED = 0


@pytest.fixture(autouse=True)
def _no_leaked_fault_plan():
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
                        max_len=MAX_LEN, name="fleet_lm")


@pytest.fixture(scope="module")
def replica(engine):
    """One REAL in-process generation replica (engine + batcher + HTTP)."""
    gen = GenerationBatcher(engine)
    httpd = make_server(None, port=0, gen_batcher=gen)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    yield httpd, gen
    httpd.shutdown()
    gen.close()


def _oracle(params, prompt, n_tokens, max_len=MAX_LEN, heads=HEADS):
    ids = np.asarray(transformer.lm_generate(
        params, np.asarray(prompt, np.int32)[None], max_len=max_len,
        num_heads=heads, prompt_lengths=np.asarray([len(prompt)])))
    return ids[0, len(prompt):len(prompt) + n_tokens].tolist()


def _wait(pred, timeout=30.0, every=0.02):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(every)
    return False


def _stream(port, body, close_after=None, timeout=120):
    """Drive one streaming /v1/generate; returns (tokens, done_record).
    close_after=k drops the connection after k tokens (the disconnect
    test)."""
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    conn.request("POST", "/v1/generate", json.dumps(body).encode(),
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
            if close_after is not None and len(toks) >= close_after:
                # hard close (RST) — the router must notice and close the
                # upstream replica connection
                conn.sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                     struct.pack("ii", 1, 0))
                conn.close()
                return toks, None
        if rec.get("done"):
            done = rec
            break
    conn.close()
    return toks, done


# ------------------------------------------------------- continuation API


def test_replay_submit_bit_identical(engine):
    """The contract the router's failover rides on: submitting prompt +
    already-delivered replay tokens continues the greedy stream
    bit-identically, emitting only NEW tokens — including when the
    combined context takes several chunks to ingest."""
    engine.metrics = ServingMetrics()
    bat = GenerationBatcher(engine)
    rng = np.random.RandomState(3)
    try:
        for size, cut, total in ((5, 3, 12), (14, 9, 12), (16, 1, 20)):
            prompt = rng.randint(1, VOCAB, size).astype(np.int32)
            full = bat.submit(prompt, max_tokens=total).result(120)["tokens"]
            cont = bat.submit(prompt, replay=np.asarray(full[:cut],
                                                        np.int32),
                              max_tokens=total - cut).result(120)
            assert cont["tokens"] == full[cut:], (size, cut)
        with pytest.raises(Exception, match="replay"):
            bat.submit(np.asarray([1, 2], np.int32), replay=np.asarray(
                [], np.int32), max_tokens=2).result(5)
        with pytest.raises(Exception, match="max_len"):
            bat.submit(np.asarray([1] * 10, np.int32),
                       replay=np.asarray([2] * 30, np.int32),
                       max_tokens=20)
    finally:
        bat.close()


# ------------------------------------------------------------ stub router


class _StubReplica:
    """A scripted replica: /readyz, /metrics queue depth, /v1/infer with
    a settable mode, /v1/generate streaming a scripted token list with an
    optional abrupt death."""

    def __init__(self, ready=True, depth=0, infer_mode="ok",
                 infer_delay_s=0.0, gen_tokens=(), die_after=None):
        self.ready = ready
        self.depth = depth
        self.infer_mode = infer_mode
        self.infer_delay_s = infer_delay_s
        self.gen_tokens = list(gen_tokens)
        self.die_after = die_after
        self.infer_hits = 0
        stub = self

        class Handler(BaseHTTPRequestHandler):
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):
                pass

            def handle(self):
                try:
                    super().handle()
                except (ConnectionError, BrokenPipeError):
                    pass        # the death script RSTs its own socket

            def _send(self, code, body, headers=()):
                self.send_response(code)
                self.send_header("Content-Length", str(len(body)))
                for k, v in headers:
                    self.send_header(k, v)
                self.end_headers()
                self.wfile.write(body)

            def do_GET(self):
                if self.path == "/readyz":
                    if stub.ready:
                        self._send(200, b'{"status": "ready"}')
                    else:
                        self._send(503, b'{"status": "unready"}',
                                   [("Retry-After", "1")])
                elif self.path == "/metrics":
                    self._send(200, f"stub_queue_depth {stub.depth}\n"
                               .encode())
                else:
                    self._send(404, b"{}")

            def do_POST(self):
                self.rfile.read(int(self.headers.get("Content-Length")
                                    or 0))
                if self.path == "/v1/infer":
                    stub.infer_hits += 1
                    time.sleep(stub.infer_delay_s)
                    if stub.infer_mode == "fail":
                        self._send(500, b'{"error": "boom"}')
                    else:
                        self._send(200, b'{"outputs": {"y": [1]}}')
                    return
                # streaming generate: scripted tokens, optional death
                self.send_response(200)
                self.send_header("Transfer-Encoding", "chunked")
                self.end_headers()
                for i, t in enumerate(stub.gen_tokens):
                    if stub.die_after is not None \
                            and i >= stub.die_after:
                        self.connection.setsockopt(
                            socket.SOL_SOCKET, socket.SO_LINGER,
                            struct.pack("ii", 1, 0))
                        self.connection.close()
                        self.close_connection = True
                        return
                    data = (json.dumps({"token": int(t)}) + "\n").encode()
                    self.wfile.write(f"{len(data):X}\r\n".encode() + data
                                     + b"\r\n")
                    time.sleep(0.01)
                data = (json.dumps({"done": True,
                                    "tokens": stub.gen_tokens,
                                    "finish_reason": "length"})
                        + "\n").encode()
                self.wfile.write(f"{len(data):X}\r\n".encode() + data
                                 + b"\r\n0\r\n\r\n")

        self.httpd = ThreadingHTTPServer(("127.0.0.1", 0), Handler)
        self.httpd.daemon_threads = True
        threading.Thread(target=self.httpd.serve_forever,
                         daemon=True).start()
        self.url = f"http://127.0.0.1:{self.httpd.server_address[1]}"

    def close(self):
        self.httpd.shutdown()


def _post(port, path, body, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


def test_router_readiness_gating_and_least_loaded():
    """An unready replica is never dispatched to; among ready ones the
    smaller polled queue depth wins."""
    a = _StubReplica(ready=False)
    b = _StubReplica(depth=5)
    c = _StubReplica(depth=0)
    router = Router(replicas=[a.url, b.url, c.url], poll_interval_s=0.05,
                    hedge_ms=0)
    httpd = router.start(port=0)
    try:
        # both ready replicas polled (the router is ready after the first)
        assert _wait(lambda: all(router.replica_states()[r]["ready"]
                                 for r in ("r1", "r2")), 10)
        for _ in range(4):
            st, out = _post(httpd.port, "/v1/infer", {"feed": {}})
            assert st == 200 and "outputs" in out
        assert a.infer_hits == 0            # gated out by /readyz
        assert c.infer_hits == 4            # least-loaded (depth 0 vs 5)
        assert b.infer_hits == 0
        # the unready replica keeps /readyz-flagged; flipping it ready
        # admits it within a poll interval
        a.ready = True
        assert _wait(lambda: router.replica_states()["r0"]["ready"], 10)
    finally:
        router.close()
        for s in (a, b, c):
            s.close()


def test_router_ejection_and_halfopen_readmission():
    """Consecutive dispatch failures eject the replica (requests keep
    succeeding via retry on the healthy one); after the cooldown ONE
    half-open probe readmits it on success — counters count both
    transitions.  The cooldown elapses on the router's INJECTABLE clock
    (advanced by hand) instead of a wall-clock sleep."""

    class _Clock:
        def __init__(self):
            self.t = time.monotonic()

        def __call__(self):
            return self.t

        def advance(self, dt):
            self.t += dt

    clock = _Clock()
    a = _StubReplica(infer_mode="fail")     # r0 wins the load tie
    b = _StubReplica()
    router = Router(replicas=[a.url, b.url], poll_interval_s=0.05,
                    eject_threshold=2, eject_cooldown_s=0.4,
                    retry_budget=2, hedge_ms=0, clock=clock)
    httpd = router.start(port=0)
    try:
        assert _wait(router.ready, 10)
        for _ in range(3):
            st, _out = _post(httpd.port, "/v1/infer", {"feed": {}})
            assert st == 200                # retry absorbed the failure
        snap = router.metrics.snapshot()
        assert snap["ejections_total"].get("r0") == 1
        assert snap["retries_total"] >= 2
        assert router.replica_states()["r0"]["breaker"] != "closed"
        hits_after_eject = a.infer_hits
        _post(httpd.port, "/v1/infer", {"feed": {}})
        assert a.infer_hits == hits_after_eject    # ejected: not dialed
        # heal the replica; ADVANCE the injected clock past the cooldown
        # (no wall-clock sleep) — the half-open probe lands on it (load
        # tie -> r0 first) and recloses the breaker
        a.infer_mode = "ok"
        clock.advance(0.5)
        st, _out = _post(httpd.port, "/v1/infer", {"feed": {}})
        assert st == 200
        assert _wait(lambda: router.metrics.snapshot()
                     ["readmissions_total"].get("r0") == 1, 10)
        assert router.replica_states()["r0"]["breaker"] == "closed"
    finally:
        router.close()
        a.close()
        b.close()


def test_router_dispatch_fault_point():
    """The router-layer fault point: a seeded plan injects a dispatch
    error at the router->replica boundary; the bounded retry absorbs it
    and the fire count is exact.  Seeded p= schedules replay bit-for-bit
    at this point like the in-process seven."""
    plan_a = FaultPlan.from_spec("router.dispatch:p=0.5,seed=9")
    plan_b = FaultPlan.from_spec("router.dispatch:p=0.5,seed=9")
    fires_a, fires_b = [], []
    for plan, fires in ((plan_a, fires_a), (plan_b, fires_b)):
        for _ in range(64):
            try:
                plan.hit("router.dispatch")
                fires.append(0)
            except Exception:
                fires.append(1)
    assert fires_a == fires_b and sum(fires_a) > 0

    a = _StubReplica()
    router = Router(replicas=[a.url], poll_interval_s=0.05,
                    retry_budget=2, hedge_ms=0)
    httpd = router.start(port=0)
    try:
        assert _wait(router.ready, 10)
        faults.install_spec("router.dispatch:at=1")
        st, out = _post(httpd.port, "/v1/infer", {"feed": {}})
        assert st == 200 and "outputs" in out
        assert faults.fired_counts()["router.dispatch"] == 1
        snap = router.metrics.snapshot()
        assert snap["retries_total"] == 1
        assert snap["dispatch_errors_total"].get("r0") == 1
    finally:
        faults.clear()
        router.close()
        a.close()


def test_router_hedged_infer():
    """With hedging on, a slow primary is raced by a hedge on the other
    replica and the fast answer wins."""
    a = _StubReplica(infer_delay_s=0.6)     # r0: the slow primary
    b = _StubReplica()
    router = Router(replicas=[a.url, b.url], poll_interval_s=0.05,
                    hedge_ms=40, retry_budget=1)
    httpd = router.start(port=0)
    try:
        assert _wait(router.ready, 10)
        t0 = time.perf_counter()
        st, out = _post(httpd.port, "/v1/infer", {"feed": {}})
        dt = time.perf_counter() - t0
        assert st == 200 and "outputs" in out
        assert dt < 0.55, f"hedge did not cut the tail: {dt:.3f}s"
        snap = router.metrics.snapshot()
        assert snap["hedges_total"] == 1
        assert snap["hedge_wins_total"] == 1
    finally:
        router.close()
        a.close()
        b.close()


# ------------------------------------------- in-process failover + abandon


def test_midstream_failover_bit_identical(params, replica):
    """A replica that dies mid-stream (4 tokens out, then RST, no done
    record): the router resubmits prompt + delivered tokens as a
    continuation on the healthy replica and the client's stream finishes
    bit-identical to lm_generate."""
    httpd_real, gen = replica
    rng = np.random.RandomState(7)
    prompt = rng.randint(1, VOCAB, 6).astype(np.int32)
    oracle = _oracle(params, prompt, 10)
    # r0 = the dying stub (wins the idle load tie), r1 = the real engine
    stub = _StubReplica(gen_tokens=oracle, die_after=4)
    router = Router(replicas=[stub.url, f"http://127.0.0.1:"
                                        f"{httpd_real.port}"],
                    poll_interval_s=0.05, retry_budget=2, hedge_ms=0)
    httpd = router.start(port=0)
    try:
        assert _wait(router.ready, 10)
        toks, done = _stream(httpd.port, {"prompt": prompt.tolist(),
                                          "max_tokens": 10,
                                          "stream": True})
        assert toks == oracle, (toks, oracle)
        assert done is not None and done["tokens"] == oracle
        snap = router.metrics.snapshot()
        assert snap["midstream_failovers_total"] == 1
        assert snap["tokens_proxied_total"] == 10
    finally:
        router.close()
        stub.close()


def test_client_disconnect_propagates_abandon(engine, replica):
    """Satellite: a dropped downstream /v1/generate stream must close the
    upstream replica connection so the replica's abandon() slot
    reclamation fires (the slot frees at the next token boundary instead
    of decoding to max_tokens for nobody)."""
    httpd_real, gen = replica
    engine.metrics = gen.metrics = ServingMetrics()
    router = Router(replicas=[f"http://127.0.0.1:{httpd_real.port}"],
                    poll_interval_s=0.05, hedge_ms=0)
    httpd = router.start(port=0)
    # pace the in-process engine so the stream is still live when the
    # client drops (cleared by the autouse fixture)
    faults.install_spec("serving.decode_step:every=1,action=hang,"
                        "hang_s=0.02")
    try:
        assert _wait(router.ready, 10)
        prompt = np.random.RandomState(8).randint(1, VOCAB, 5)
        toks, done = _stream(httpd.port,
                             {"prompt": prompt.tolist(), "max_tokens": 30,
                              "stream": True}, close_after=2)
        assert done is None and len(toks) >= 2
        # the replica reclaims the slot instead of decoding to 30
        assert _wait(lambda: gen.metrics.snapshot()["evictions"]
                     ["abandoned"] >= 1, 30), \
            gen.metrics.snapshot()["evictions"]
        assert _wait(lambda: engine.free_slots == engine.num_slots, 30)
        assert _wait(lambda: router.metrics.snapshot()
                     ["client_disconnects_total"] >= 1, 10)
    finally:
        faults.clear()
        router.close()


# --------------------------------------------------- supervisor (no jax)


def test_supervisor_backoff_and_storm_breaker_exact():
    """A replica that dies instantly is restarted with the EXACT seeded
    exponential-backoff schedule until the restart-storm breaker trips;
    counters are exact."""
    sup = ReplicaSupervisor(
        n_replicas=1, cmd=["-c", "import sys; sys.exit(3)"],
        backoff_base_s=0.05, backoff_max_s=0.4, storm_threshold=4,
        storm_window_s=30.0, seed=11)
    sup.start()
    try:
        assert _wait(lambda: sup.snapshot()["r0"]["storm_tripped"], 30)
        snap = sup.snapshot()["r0"]
        assert snap["state"] == "failed"
        # threshold crashes -> threshold-1 restarts (the storm check
        # fires on the Nth crash, before scheduling another restart)
        assert snap["restarts_total"] == 3
        assert snap["consecutive_failures"] == 4
        # the jittered delays replay exactly from the seeded stream
        rng = random.Random(11 * 7919 + 0)
        expect = [round(min(0.05 * 2 ** k, 0.4)
                        * (0.5 + 0.5 * rng.random()), 4)
                  for k in range(3)]
        assert snap["backoff_delays_s"] == expect
        # tripped: no further restarts ever get scheduled
        time.sleep(0.3)
        assert sup.snapshot()["r0"]["restarts_total"] == 3
    finally:
        sup.stop()


# ------------------------------------------------- subprocess fleet chaos


@pytest.fixture(scope="module")
def fleet_params():
    return transformer.init(jax.random.PRNGKey(0), src_vocab=FLEET_VOCAB,
                            trg_vocab=1, d_model=32, num_heads=2, dff=64,
                            enc_layers=2, dec_layers=0,
                            max_len=FLEET_MAX_LEN)


@pytest.fixture(scope="module")
def fleet(tmp_path_factory):
    """One real 2-replica subprocess fleet + router, shared by the
    ordered chaos tests below (spawning replicas is the expensive part;
    a module-local persistent XLA cache makes the restarted replicas'
    warm-up a disk read instead of a recompile)."""
    import os
    env = dict(os.environ)
    env.setdefault("JAX_COMPILATION_CACHE_DIR",
                   str(tmp_path_factory.mktemp("xla_cache")))
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    sup = ReplicaSupervisor(n_replicas=2, extra_args=FLEET_ARGS,
                            backoff_base_s=0.3, seed=FLEET_SEED,
                            env=env, name="test_fleet")
    sup.start()
    if not sup.wait_ready(timeout=300):
        sup.stop()
        pytest.fail("fleet replicas never became ready")
    router = Router(supervisor=sup, poll_interval_s=0.1,
                    eject_threshold=2, eject_cooldown_s=1.0,
                    retry_budget=3, hedge_ms=0)
    httpd = router.start(port=0)
    assert _wait(router.ready, 30)
    yield sup, router, httpd.port
    router.close()
    sup.stop()


@pytest.mark.slow
def test_fleet_kill9_midstream_under_concurrent_load(fleet, fleet_params):
    """THE acceptance drive: kill -9 one replica while 8 concurrent
    clients stream — every stream must finish bit-identical to
    lm_generate (cross-replica continuation failover), with the router's
    failover counters as evidence."""
    sup, router, port = fleet
    n_clients = 8
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, FLEET_VOCAB, int(rng.randint(3, 17)))
               for _ in range(n_clients)]
    oracle = [_oracle(fleet_params, p, FLEET_TOKENS,
                      max_len=FLEET_MAX_LEN, heads=2) for p in prompts]
    results = [None] * n_clients
    errs = []
    seen2 = threading.Barrier(n_clients + 1, timeout=120)

    def hit(i):
        armed = True
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=120)
            conn.request("POST", "/v1/generate",
                         json.dumps({"prompt": prompts[i].tolist(),
                                     "max_tokens": FLEET_TOKENS,
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
                    if armed and len(toks) >= 2:
                        armed = False
                        seen2.wait()
                if rec.get("done"):
                    done = rec
                    break
            conn.close()
            if armed:
                seen2.wait()
            results[i] = (toks, done)
        except Exception as e:      # noqa: BLE001
            errs.append(f"client {i}: {type(e).__name__}: {e}")
            if armed:
                try:
                    seen2.wait()
                except threading.BrokenBarrierError:
                    pass

    threads = [threading.Thread(target=hit, args=(i,))
               for i in range(n_clients)]
    for t in threads:
        t.start()
    seen2.wait()                    # every stream is visibly mid-decode
    sup.kill("r0", signal.SIGKILL)
    for t in threads:
        t.join(180)
        assert not t.is_alive(), "client thread wedged: DEADLOCK"
    assert not errs, errs
    for i, (toks, done) in enumerate(results):
        assert toks == oracle[i], f"stream {i} diverged after the kill"
        assert done is not None and done["tokens"] == oracle[i]
    snap = router.metrics.snapshot()
    # half the streams lived on the victim: all of them failed over
    assert snap["midstream_failovers_total"] >= 1
    assert snap["failovers_total"] >= snap["midstream_failovers_total"]


@pytest.mark.slow   # reads the supervision evidence kill9 leaves behind
def test_fleet_victim_restarted_with_seeded_backoff(fleet):
    """Supervision evidence after the kill: exactly one crash-restart of
    r0, with the first backoff delay replaying the seeded schedule, and
    the replica back in rotation (router sees it ready again)."""
    sup, router, _port = fleet
    assert sup.wait_ready(timeout=300, rids=("r0",)), sup.snapshot()
    snap = sup.snapshot()["r0"]
    assert snap["restarts_total"] == 1
    assert snap["storm_tripped"] is False
    rng = random.Random(FLEET_SEED * 7919 + 0)
    expect = round(min(0.3, 10.0) * (0.5 + 0.5 * rng.random()), 4)
    assert snap["backoff_delays_s"] == [expect]
    assert _wait(lambda: router.replica_states().get("r0", {})
                 .get("ready", False), 30)


@pytest.mark.slow
def test_fleet_rolling_drain_zero_failed_requests(fleet, fleet_params):
    """Satellite: SIGTERM one replica at a time (rolling restart) while
    clients keep generating through the router — zero failed requests,
    every response still bit-identical (the router routes around the
    draining replica via /readyz)."""
    sup, router, port = fleet
    restarts_before = {rid: r["restarts_total"]
                       for rid, r in sup.snapshot().items()}
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, FLEET_VOCAB, int(rng.randint(3, 17)))
               for _ in range(4)]
    oracle = [_oracle(fleet_params, p, 6, max_len=FLEET_MAX_LEN, heads=2)
              for p in prompts]
    stop = threading.Event()
    failures, completed = [], [0]

    def client(i):
        while not stop.is_set():
            try:
                st, out = _post(port, "/v1/generate",
                                {"prompt": prompts[i].tolist(),
                                 "max_tokens": 6}, timeout=120)
                if st != 200 or out["tokens"] != oracle[i]:
                    failures.append((i, st, out))
                completed[0] += 1
            except Exception as e:      # noqa: BLE001
                failures.append((i, f"{type(e).__name__}: {e}"))

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    try:
        sup.rolling_restart(ready_timeout=300)
    finally:
        stop.set()
        for t in threads:
            t.join(120)
    assert not failures, failures[:5]
    assert completed[0] > 0
    fsnap = sup.snapshot()
    assert all(r["drains_total"] == 1 for r in fsnap.values()), fsnap
    # drains are deliberate: no crash-restart accounting moved
    for rid, r in fsnap.items():
        assert r["restarts_total"] == restarts_before[rid], fsnap


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))

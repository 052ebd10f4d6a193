"""Serving runtime (paddle_tpu/serving): bucketed AOT engine, dynamic
batcher, HTTP front-end, metrics.

The correctness bar: a request served through the full stack — queue,
dynamic batch formation, bucket padding, slicing — must return EXACTLY
what the direct forward returns for that row.  On the CPU test backend,
XLA gemm row results are bit-stable across batch sizes >= 2 (row dots
accumulate in the same order), so the tests pin bucket ladders with a
minimum bucket of 4 and assert BIT-IDENTICAL outputs, not allclose.

Fault injection covers each admission-control path: invalid feed
(rejected before the queue), queue overflow, per-request deadline, batch
execution failure (isolated to its batch) — and after every fault the
engine keeps serving.
"""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest
import jax

from paddle_tpu.layers import api as L
from paddle_tpu.layers.graph import Topology, reset_names
from paddle_tpu.serving import (BatchExecutionError, Batcher,
                                DeadlineExceededError, InferenceEngine,
                                InvalidRequestError, OverloadedError,
                                ServingMetrics, ShutdownError, make_server)


def setup_function(_):
    reset_names()


def _mlp(dim=8, hidden=16, classes=4, seed=0):
    x = L.data_layer("x", size=dim)
    h = L.fc_layer(input=x, size=hidden, act="tanh")
    out = L.fc_layer(input=h, size=classes, act="softmax")
    topo = Topology([out])
    params = topo.init(jax.random.PRNGKey(seed))
    return out, topo, params


def _engine(buckets=(4, 16), warm=True, dim=8):
    out, topo, params = _mlp(dim=dim)
    spec = {"x": jax.ShapeDtypeStruct((1, dim), np.float32)}
    eng = InferenceEngine.from_topology(out, params, spec, buckets=buckets,
                                        warm=warm)
    return eng, topo, params


# ---------------------------------------------------------------- engine


def test_engine_pads_to_bucket_and_slices_back():
    eng, topo, params = _engine(buckets=(4, 16))
    rng = np.random.RandomState(0)
    for b in (1, 3, 4, 5, 16):
        xb = rng.randn(b, 8).astype(np.float32)
        direct = np.asarray(topo.apply(params, {"x": xb.copy()},
                                       mode="test"))
        got = np.asarray(eng.infer({"x": xb}))
        assert got.shape == (b, 4)
        # bucket >= 4 executes every batch at M >= 4: bit-stable rows
        np.testing.assert_array_equal(got, direct)


def test_engine_chunks_batches_beyond_ladder_top():
    eng, topo, params = _engine(buckets=(4, 16))
    xb = np.random.RandomState(1).randn(37, 8).astype(np.float32)
    direct = np.asarray(topo.apply(params, {"x": xb.copy()}, mode="test"))
    got = np.asarray(eng.infer({"x": xb}))
    assert got.shape == (37, 4)
    np.testing.assert_array_equal(got, direct)


def test_engine_trace_count_stable_after_warmup():
    eng, _, _ = _engine(buckets=(4, 16), warm=True)
    assert eng.trace_count == 2          # one trace per bucket, exactly
    rng = np.random.RandomState(2)
    for b in (1, 2, 4, 9, 16, 33):
        eng.infer({"x": rng.randn(b, 8).astype(np.float32)})
    assert eng.trace_count == 2          # steady-state serving: no retrace


def test_engine_lazy_compile_on_first_use():
    eng, _, _ = _engine(buckets=(4, 16), warm=False)
    assert eng.trace_count == 0
    eng.infer({"x": np.zeros((3, 8), np.float32)})   # -> bucket 4 only
    assert eng.trace_count == 1
    eng.infer({"x": np.zeros((2, 8), np.float32)})   # same bucket: cached
    assert eng.trace_count == 1


def test_engine_validates_feeds():
    eng, _, _ = _engine()
    with pytest.raises(InvalidRequestError):
        eng.validate({"x": np.zeros((2, 5), np.float32)})   # wrong width
    with pytest.raises(InvalidRequestError):
        eng.validate({"x": np.zeros((2, 8), np.int32)})     # wrong dtype
    with pytest.raises(InvalidRequestError):
        eng.validate({"y": np.zeros((2, 8), np.float32)})   # wrong slot
    with pytest.raises(InvalidRequestError):
        eng.validate({"x": np.zeros((8,), np.float32)})     # no batch axis
    with pytest.raises(InvalidRequestError):
        eng.validate({"x": np.zeros((3, 8), np.float32)},
                     batch=False)                           # row API misuse
    assert eng.validate({"x": np.zeros((8,), np.float32)}, batch=False) == 1


def test_engine_lower_hook_exposes_bucket_cost():
    # the extras["lower"] analytic idiom: lower (never execute) a bucket's
    # program and read XLA's cost model from it (perf/analytic.py)
    from paddle_tpu.perf import cost
    eng, _, _ = _engine(buckets=(4, 16), warm=False)
    row = cost.extract(eng.lower(16).compile())
    assert row["flops"] > 0 and row["bytes_accessed"] > 0


# ---------------------------------------------------------------- batcher


def test_concurrent_clients_bit_identical_and_batched():
    """The acceptance drive: 16 threads hammer the batcher; every response
    is bit-identical to the direct forward of that request, and mean batch
    occupancy shows real cross-request batching."""
    eng, topo, params = _engine(buckets=(4, 16))
    xb = np.random.RandomState(3).randn(16, 8).astype(np.float32)
    direct = np.asarray(topo.apply(params, {"x": xb.copy()}, mode="test"))

    bat = Batcher(eng, max_delay_ms=100.0, queue_size=64)
    results = [None] * 16

    def client(i):
        results[i] = np.asarray(bat.submit({"x": xb[i]}).result(30))

    threads = [threading.Thread(target=client, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    bat.close()

    for i in range(16):
        np.testing.assert_array_equal(results[i], direct[i])
    snap = eng.metrics.snapshot()
    assert snap["responses_total"] == 16
    assert snap["mean_occupancy"] > 1.0, snap    # batching actually happened
    assert snap["errors_total"] == 0


def _stalled_engine(stall_s=0.15, buckets=(4, 16)):
    """Engine whose infer sleeps first — deterministic queue buildup."""
    eng, _, _ = _engine(buckets=buckets)
    orig = eng.infer

    def slow(feed):
        time.sleep(stall_s)
        return orig(feed)
    eng.infer = slow
    return eng


def test_fault_injection_all_paths_and_engine_stays_healthy():
    eng = _stalled_engine(stall_s=0.2)
    row = {"x": np.zeros((8,), np.float32)}
    bat = Batcher(eng, max_delay_ms=0.0, queue_size=3)

    # invalid feed: rejected synchronously, never queued
    with pytest.raises(InvalidRequestError):
        bat.submit({"x": np.zeros((5,), np.float32)})
    with pytest.raises(InvalidRequestError):
        bat.submit({"x": np.zeros((8,), np.int64)})

    # occupy the worker, then fill the bounded queue — the deadline'd
    # request sits behind the stall and must expire, the others succeed
    first = bat.submit(row)
    time.sleep(0.05)            # worker now inside the stalled infer
    q1, q2 = bat.submit(row), bat.submit(row)
    dead = bat.submit(row, deadline_ms=10)
    with pytest.raises(OverloadedError):
        bat.submit(row)         # queue_size=3 exceeded -> explicit 429 path
    with pytest.raises(DeadlineExceededError):
        dead.result(30)
    # the co-queued requests without deadlines still succeed
    assert np.asarray(first.result(30)).shape == (4,)
    assert np.asarray(q1.result(30)).shape == (4,)
    assert np.asarray(q2.result(30)).shape == (4,)

    snap = bat.metrics.snapshot()
    assert snap["rejected"]["invalid"] == 2
    assert snap["rejected"]["overload"] == 1
    assert snap["rejected"]["deadline"] == 1

    # batch execution failure: fails ONLY that batch's futures...
    def boom(feed):
        raise RuntimeError("injected batch failure")
    healthy_infer, eng.infer = eng.infer, boom
    f = bat.submit(row)
    with pytest.raises(BatchExecutionError):
        f.result(30)
    # ...and the engine keeps serving afterwards
    eng.infer = healthy_infer
    ok = bat.submit(row).result(30)
    assert np.asarray(ok).shape == (4,)
    assert bat.metrics.snapshot()["errors_total"] == 1
    bat.close()


def test_drain_on_shutdown():
    eng = _stalled_engine(stall_s=0.1)
    row = {"x": np.zeros((8,), np.float32)}
    bat = Batcher(eng, max_delay_ms=0.0, queue_size=64)
    futs = [bat.submit(row) for _ in range(6)]
    t = threading.Thread(target=bat.close, kwargs={"drain": True})
    t.start()
    time.sleep(0.02)
    # late submit while draining: rejected, not silently queued
    with pytest.raises(ShutdownError):
        bat.submit(row)
    t.join(30)
    # every in-flight future completed with a real result
    for f in futs:
        assert np.asarray(f.result(0)).shape == (4,)
    assert bat.metrics.snapshot()["rejected"]["shutdown"] == 1


def test_client_cancel_does_not_kill_the_worker():
    """A client-side fut.cancel() racing the batch must not raise
    InvalidStateError inside the worker thread (which would wedge the
    whole batcher): cancelled requests are dropped, later ones serve."""
    eng = _stalled_engine(stall_s=0.1)
    row = {"x": np.zeros((8,), np.float32)}
    bat = Batcher(eng, max_delay_ms=0.0, queue_size=64)
    bat.submit(row)             # occupies the worker
    time.sleep(0.02)
    victim = bat.submit(row)    # still PENDING in the queue
    assert victim.cancel()
    # worker processes the queue (dropping the cancelled future) and
    # must still be alive to serve this:
    ok = bat.submit(row).result(30)
    assert np.asarray(ok).shape == (4,)
    assert victim.cancelled()
    bat.close()


def test_zero_queue_size_rejected():
    # queue.Queue(0) would mean UNBOUNDED — refuse the footgun outright
    eng, _, _ = _engine()
    with pytest.raises(ValueError):
        Batcher(eng, queue_size=0)


def test_close_without_drain_fails_queued_requests():
    eng = _stalled_engine(stall_s=0.2)
    row = {"x": np.zeros((8,), np.float32)}
    bat = Batcher(eng, max_delay_ms=0.0, queue_size=64)
    bat.submit(row)             # occupies the worker
    time.sleep(0.05)
    queued = [bat.submit(row) for _ in range(3)]
    bat.close(drain=False)
    failed = 0
    for f in queued:
        try:
            f.result(30)
        except ShutdownError:
            failed += 1
    assert failed == 3


# ---------------------------------------------------------------- export


def test_export_bucketed_and_from_artifacts_roundtrip(tmp_path):
    out, topo, params = _mlp()
    from paddle_tpu import export as pexport
    spec = {"x": np.zeros((1, 8), np.float32)}
    paths = pexport.export_bucketed(out, params, spec, buckets=(2, 8),
                                    path_prefix=str(tmp_path / "mlp"))
    assert sorted(paths) == [2, 8]
    for n, p in paths.items():
        assert p.endswith(f".b{n}.shlo")    # the documented convention

    eng = InferenceEngine.from_artifacts(str(tmp_path / "mlp.b*.shlo"))
    assert eng.buckets == (2, 8)
    xb = np.random.RandomState(4).randn(5, 8).astype(np.float32)
    direct = np.asarray(topo.apply(params, {"x": xb.copy()}, mode="test"))
    got = np.asarray(eng.infer({"x": xb}))      # 5 -> bucket 8
    np.testing.assert_array_equal(got, direct)
    # artifacts hold serialized StableHLO: the analytic lower() hook is an
    # in-process-engine feature and must say so rather than mislead
    from paddle_tpu.utils.error import ConfigError
    with pytest.raises(ConfigError):
        eng.lower()


def test_from_artifact_single_bucket(tmp_path):
    out, topo, params = _mlp()
    from paddle_tpu import export as pexport
    path = str(tmp_path / "one.shlo")
    pexport.export_inference(out, params,
                             feed_spec={"x": np.zeros((4, 8), np.float32)},
                             path=path)
    eng = InferenceEngine.from_artifact(path)
    assert eng.buckets == (4,)
    xb = np.random.RandomState(5).randn(3, 8).astype(np.float32)
    direct = np.asarray(topo.apply(params, {"x": xb.copy()}, mode="test"))
    np.testing.assert_array_equal(np.asarray(eng.infer({"x": xb})), direct)


# ---------------------------------------------------------------- v2 API


def test_v2_infer_parity_with_direct_forward():
    """Satellite: v2.infer routes through the bucketed engine and must
    match the old direct-Inferencer path bit-for-bit."""
    import paddle_tpu.v2 as paddle
    from paddle_tpu.trainer.trainer import Inferencer
    out, topo, params = _mlp()
    xb = np.random.RandomState(6).randn(8, 8).astype(np.float32)
    direct = np.asarray(Inferencer(out, params).infer({"x": xb.copy()}))
    via_engine = np.asarray(paddle.infer(output_layer=out, parameters=params,
                                         input={"x": xb}))
    assert via_engine.shape == (8, 4)
    np.testing.assert_array_equal(via_engine, direct)

    # the class form reuses ONE engine across ragged batch sizes
    inf = paddle.inference.Inference(out, params)
    for b in (1, 3, 8, 70):     # 70 > ladder top: chunking path
        xi = np.random.RandomState(b).randn(b, 8).astype(np.float32)
        d = np.asarray(topo.apply(params, {"x": xi.copy()}, mode="test"))
        got = np.asarray(inf.infer({"x": xi}))
        assert got.shape == (b, 4)
        if b > 1:       # M=1 gemv accumulates differently on CPU XLA;
            np.testing.assert_array_equal(got, d)   # all M>=2 bit-match
        else:
            np.testing.assert_allclose(got, d, rtol=1e-6, atol=1e-7)


def test_v2_infer_sequence_feeds_across_padded_lengths():
    """Sequence slots pad per batch: a reused v2 Inference must serve
    DIFFERENT padded lengths (one engine per row signature), and the
    engine must pad/slice SequenceBatch pytrees correctly."""
    import paddle_tpu.v2 as paddle
    from paddle_tpu.core.sequence import SequenceBatch
    import jax.numpy as jnp
    ids = L.data_layer("ids", size=50)
    emb = L.embedding_layer(input=ids, size=8)
    pooled = L.pooling_layer(input=emb, pooling_type=None)
    out = L.fc_layer(input=pooled, size=2, act="softmax")
    topo = Topology([out])
    params = topo.init(jax.random.PRNGKey(0))
    inf = paddle.inference.Inference(out, params)
    rng = np.random.RandomState(8)
    for b, t in ((3, 7), (5, 12), (2, 7)):
        sb = SequenceBatch(
            data=jnp.asarray(rng.randint(0, 50, (b, t)), jnp.int32),
            lengths=jnp.asarray(rng.randint(1, t + 1, (b,)), jnp.int32))
        direct = np.asarray(topo.apply(params, {"ids": sb}, mode="test"))
        got = np.asarray(inf.infer({"ids": sb}))
        assert got.shape == (b, 2)
        np.testing.assert_allclose(got, direct, rtol=1e-6, atol=1e-7)


def test_v2_engine_cache_lru_bounded_with_eviction_counter():
    """Satellite: the per-row-signature engine table is a bounded LRU —
    under many distinct padded lengths it stops growing, counts its
    evictions (surfaced at /metrics as engine_cache_evictions_total),
    and an evicted signature that returns simply recompiles and still
    serves the right numbers."""
    import paddle_tpu.v2 as paddle
    from paddle_tpu.core.sequence import SequenceBatch
    import jax.numpy as jnp
    ids = L.data_layer("ids", size=50)
    emb = L.embedding_layer(input=ids, size=8)
    pooled = L.pooling_layer(input=emb, pooling_type=None)
    out = L.fc_layer(input=pooled, size=2, act="softmax")
    topo = Topology([out])
    params = topo.init(jax.random.PRNGKey(0))
    inf = paddle.inference.Inference(out, params, max_engines=3)
    rng = np.random.RandomState(11)

    def feed(t):
        return {"ids": SequenceBatch(
            data=jnp.asarray(rng.randint(0, 50, (2, t)), jnp.int32),
            lengths=jnp.asarray([t, max(1, t - 1)], jnp.int32))}

    for t in range(4, 11):          # 7 distinct signatures through cap 3
        inf.infer(feed(t))
    assert len(inf._engines) == 3
    assert inf.metrics.engine_cache_evictions == 4
    assert "engine_cache_evictions_total 4" \
        in inf.metrics.render_prometheus()
    # the evicted t=4 signature returns: recompiles, same numerics
    fd = feed(4)
    direct = np.asarray(topo.apply(params, dict(fd), mode="test"))
    np.testing.assert_allclose(np.asarray(inf.infer(fd)), direct,
                               rtol=1e-6, atol=1e-7)
    # most-recently-used signatures survived the round trip
    assert len(inf._engines) == 3

    # default bound: the ragged-length loop that used to grow without
    # limit now stays capped
    inf8 = paddle.inference.Inference(out, params)
    for t in range(3, 13):
        inf8.infer(feed(t))
    assert len(inf8._engines) <= 8


# ---------------------------------------------------------------- HTTP


def _start_server(buckets=(4, 16), **batcher_kw):
    eng, topo, params = _engine(buckets=buckets)
    bat = Batcher(eng, **{"max_delay_ms": 50.0, "queue_size": 64,
                          **batcher_kw})
    httpd = make_server(bat, port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    return httpd, bat, topo, params


def _post(port, payload, path="/v1/infer", raw=None):
    body = raw if raw is not None else json.dumps(payload).encode()
    req = urllib.request.Request(f"http://127.0.0.1:{port}{path}", data=body,
                                 headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as r:
        return r.status, json.loads(r.read())


def test_http_concurrent_clients_match_direct_forward():
    httpd, bat, topo, params = _start_server()
    try:
        xb = np.random.RandomState(7).randn(8, 8).astype(np.float32)
        direct = np.asarray(topo.apply(params, {"x": xb.copy()},
                                       mode="test"))
        results = [None] * 8

        def client(i):
            status, resp = _post(httpd.port,
                                 {"feed": {"x": xb[i].tolist()}})
            assert status == 200
            results[i] = np.asarray(resp["outputs"], np.float32)

        ts = [threading.Thread(target=client, args=(i,)) for i in range(8)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for i in range(8):
            # JSON round-trips float32 exactly (float -> shortest repr
            # double -> float32), so even HTTP responses are bit-identical
            np.testing.assert_array_equal(results[i], direct[i])

        # live metrics reflect the traffic
        with urllib.request.urlopen(
                f"http://127.0.0.1:{httpd.port}/metrics", timeout=30) as r:
            text = r.read().decode()
        assert "paddle_tpu_serving_requests_total 8" in text
        assert 'latency_seconds{quantile="0.99"}' in text
        with urllib.request.urlopen(
                f"http://127.0.0.1:{httpd.port}/healthz", timeout=30) as r:
            assert json.loads(r.read())["status"] == "ok"
    finally:
        httpd.shutdown()
        bat.close()


def test_http_fault_paths():
    httpd, bat, topo, params = _start_server()
    try:
        port = httpd.port

        def expect(code, payload=None, raw=None, path="/v1/infer"):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(port, payload, path=path, raw=raw)
            assert ei.value.code == code
            return json.loads(ei.value.read())

        assert "error" in expect(400, raw=b"{not json")
        assert "error" in expect(400, {"nofeed": 1})
        assert "error" in expect(400, {"feed": {"x": [1.0] * 5}})
        assert "error" in expect(400, {"feed": {"x": [1.0] * 8,
                                                "bogus": [1]}})
        assert "error" in expect(400, {"feed": {"x": [1.0] * 8},
                                       "deadline_ms": -5})
        assert "error" in expect(404, {"feed": {}}, path="/v1/nope")

        # the engine survived every fault: a good request still serves
        status, resp = _post(port, {"feed": {"x": [0.5] * 8}})
        assert status == 200 and len(resp["outputs"]) == 4
    finally:
        httpd.shutdown()
        bat.close()


# ---------------------------------------------------------------- metrics


def test_metrics_prometheus_render_and_waste():
    m = ServingMetrics(name="t")
    m.accepted()
    m.observe_batch(n_real=3, bucket=4, seconds=0.002)
    m.observe_response(0.010)
    m.reject("overload")
    assert m.mean_occupancy == 3.0
    assert m.padding_waste == pytest.approx(0.25)
    text = m.render_prometheus()
    assert "t_requests_total 1" in text
    assert 't_rejected_total{reason="overload"} 1' in text
    assert 't_latency_seconds{quantile="0.50"} 0.010000' in text
    assert "t_batch_occupancy_mean 3.000000" in text
    snap = m.snapshot()
    assert snap["latency_ms"]["p99"] == pytest.approx(10.0)


def test_histogram_keep_last_is_a_ring():
    from paddle_tpu.utils.stats import Histogram
    h = Histogram("x", max_samples=4, keep="last")
    for v in (1.0, 2.0, 3.0, 4.0, 5.0, 6.0):
        h.add(v)
    assert h.count == 6
    assert sorted(h.samples) == [3.0, 4.0, 5.0, 6.0]   # oldest evicted


# ---------------------------------------------------------------- load


@pytest.mark.slow
def test_serving_smoke_subprocess():
    """`python -m paddle_tpu.serving --smoke` passes end to end in a
    fresh process."""
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.serving", "--smoke"],
        capture_output=True, text=True, timeout=300, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert proc.returncode == 0, proc.stderr[-2000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["value"] == int(out["unit"].split("/")[1])
    assert out["metrics_sane"] is True
    assert out["mean_occupancy"] > 1.0

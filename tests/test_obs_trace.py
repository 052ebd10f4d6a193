"""End-to-end request tracing (obs/trace.py; docs/observability.md).

Units: span nesting / ring bound / deterministic sampling / the
disabled-path strict no-op / traceparent round-trip / Chrome export.
Integration: one trace_id propagated across a REAL router + replica
subprocess pair, and the no-retrace discipline — tracing enabled adds
ZERO jit traces to the decode engine (testing/trace.py
``assert_no_retrace``, the same counter every AOT surface pins).
"""

import json
import threading
import urllib.request

import numpy as np
import pytest
import jax

from paddle_tpu.obs import trace
from paddle_tpu.testing.trace import assert_no_retrace


@pytest.fixture(autouse=True)
def _clean_tracer():
    trace.disable()
    yield
    trace.disable()


# ------------------------------------------------------- correlated logs


def _fmt(formatter, msg="hello"):
    import logging
    rec = logging.LogRecord("paddle_tpu", logging.INFO, __file__, 1,
                            msg, (), None)
    return formatter.format(rec)


def test_json_log_format_carries_context():
    from paddle_tpu.utils import logging as ptlog
    with ptlog.log_context(trace_id="abc123", request_id="r-9"):
        line = _fmt(ptlog._JsonFormatter())
    obj = json.loads(line)
    assert obj["trace_id"] == "abc123" and obj["request_id"] == "r-9"
    assert obj["level"] == "INFO" and obj["logger"] == "paddle_tpu"
    # the greppable k=v tail rides in msg too, so ONE
    # `grep trace_id=<id>` crosses text- and json-format process logs
    assert "trace_id=abc123" in obj["msg"]
    # outside the context: clean line, no stale fields
    obj = json.loads(_fmt(ptlog._JsonFormatter()))
    assert "trace_id" not in obj and obj["msg"] == "hello"


def test_text_log_format_appends_context_tail():
    from paddle_tpu.utils import logging as ptlog
    fmt = ptlog._TextFormatter(ptlog._FMT, datefmt="%m%d %H:%M:%S")
    assert _fmt(fmt).endswith("hello")
    with ptlog.log_context(trace_id="abc123"):
        assert _fmt(fmt).endswith("hello trace_id=abc123")
    # nesting merges; falsy values are dropped
    with ptlog.log_context(trace_id="abc123"):
        with ptlog.log_context(request_id="r-1", empty=None):
            assert ptlog.context_fields() == {"trace_id": "abc123",
                                              "request_id": "r-1"}
        assert ptlog.context_fields() == {"trace_id": "abc123"}


def test_set_format_switches_installed_handlers():
    from paddle_tpu.utils import logging as ptlog
    log = ptlog.get_logger()
    try:
        ptlog.set_format("json")
        assert all(isinstance(h.formatter, ptlog._JsonFormatter)
                   for h in log.handlers)
    finally:
        ptlog.set_format("text")
    assert all(isinstance(h.formatter, ptlog._TextFormatter)
               for h in log.handlers)


# ------------------------------------------------------------------ units


def test_disabled_path_is_a_strict_noop():
    # no tracer installed: every entry point returns the ONE null
    # singleton — no allocation, no context mutation, empty ids
    assert trace.span("x", a=1) is trace.NULL
    assert trace.start_span("y") is trace.NULL
    assert trace.instant("z") is trace.NULL
    assert trace.NULL.trace_id == "" and not trace.NULL.recording
    with trace.span("x"):
        assert trace.current() is None      # NULL never touches the ctx
    # every mutator is inert and chainable
    assert trace.NULL.set(a=1).event("e").end() is trace.NULL
    assert trace.snapshot() == []
    assert trace.slowest() == {"wall": [], "ttft": []}
    assert trace.debug_payload()["enabled"] is False
    # inject with no context propagates nothing
    assert trace.inject({}) == {}


def test_span_nesting_parents_and_context():
    trace.enable(sample=1.0, capacity=64, process="unit")
    with trace.span("root", route="/x") as r:
        assert trace.current() == (r.trace_id, r.span_id)
        with trace.span("mid") as m:
            with trace.span("leaf") as leaf:
                assert leaf.trace_id == r.trace_id
                assert leaf.parent_id == m.span_id
            assert m.parent_id == r.span_id
        # context restored after each exit
        assert trace.current() == (r.trace_id, r.span_id)
    assert trace.current() is None
    spans = {s["name"]: s for s in trace.snapshot()}
    assert set(spans) == {"root", "mid", "leaf"}
    assert spans["root"]["parent_id"] is None
    assert spans["root"]["attrs"]["root"] is True
    # completed spans carry both timestamps
    for s in spans.values():
        assert s["t_end"] >= s["t_start"]


def test_start_span_is_context_free_and_async_endable():
    trace.enable(sample=1.0, capacity=64, process="unit")
    with trace.span("req") as r:
        seam = trace.start_span("queue_wait")
        assert seam.parent_id == r.span_id       # parented to current...
        assert trace.current() == (r.trace_id, r.span_id)  # ...but not
        #                                           made current itself
    done = []

    def other_thread():
        seam.event("picked")
        seam.end(batch_size=3)
        done.append(True)

    t = threading.Thread(target=other_thread)
    t.start()
    t.join(5)
    assert done
    s = next(s for s in trace.snapshot() if s["name"] == "queue_wait")
    assert s["attrs"]["batch_size"] == 3
    assert [e["name"] for e in s["events"]] == ["picked"]
    # double-end is idempotent
    first_end = s["t_end"]
    seam.end()
    s2 = next(s for s in trace.snapshot() if s["name"] == "queue_wait")
    assert s2["t_end"] == first_end


def test_ring_bound_drops_oldest():
    trace.enable(sample=1.0, capacity=5, process="unit")
    for i in range(12):
        trace.start_span(f"s{i}").end()
    spans = trace.snapshot()
    assert len(spans) == 5
    assert [s["name"] for s in spans] == [f"s{i}" for i in range(7, 12)]
    assert trace.get_tracer().dropped_total == 7
    assert trace.get_tracer().started_total == 12


def test_sampling_is_deterministic_on_trace_id_hash():
    ids = [trace.new_trace_id() for _ in range(400)]
    a = trace.Tracer(sample=0.5)
    b = trace.Tracer(sample=0.5)
    verdicts = [a.sampled(i) for i in ids]
    # the SAME ids get the SAME verdict in a different tracer/process
    assert verdicts == [b.sampled(i) for i in ids]
    assert 100 < sum(verdicts) < 300        # roughly the asked-for half
    assert all(trace.Tracer(sample=1.0).sampled(i) for i in ids)
    assert not any(trace.Tracer(sample=0.0).sampled(i) for i in ids)


def test_unsampled_spans_keep_ids_but_never_record():
    trace.enable(sample=0.0, capacity=64, process="unit")
    with trace.span("root") as r:
        assert len(r.trace_id) == 32        # ids exist: responses/logs
        assert not r.recording              # still correlate
        with trace.span("child") as c:
            assert c.trace_id == r.trace_id
        hdrs = trace.inject({})             # propagation stays coherent
        assert r.trace_id in hdrs["traceparent"]
    assert trace.snapshot() == []


def test_traceparent_round_trip_and_malformed():
    trace.enable(sample=1.0, capacity=8, process="unit")
    with trace.span("root") as r:
        hdr = trace.inject({})["traceparent"]
    assert trace.extract(hdr) == (r.trace_id, r.span_id)
    for bad in (None, "", "junk", "00-short-id-01",
                "00-" + "x" * 32 + "-" + "cd" * 8 + "-01"):
        assert trace.extract(bad) is None


def test_chrome_trace_export_shape():
    trace.enable(sample=1.0, capacity=64, process="replica:1")
    with trace.span("server.request", route="/v1/generate") as r:
        sl = trace.start_span("slot", slot=2, mode="prefill")
        sl.event("first_token")
        sl.end(reason="length")
    obj = trace.chrome_trace()
    json.loads(json.dumps(obj))             # valid JSON
    evs = obj["traceEvents"]
    procs = [e for e in evs if e["ph"] == "M"
             and e["name"] == "process_name"]
    assert [p["args"]["name"] for p in procs] == ["replica:1"]
    tracks = {e["args"]["name"] for e in evs if e["ph"] == "M"
              and e["name"] == "thread_name"}
    assert tracks == {"host", "slot 2"}
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"server.request", "slot"}
    assert xs["slot"]["tid"] == 102
    assert xs["slot"]["args"]["trace_id"] == r.trace_id
    instants = [e for e in evs if e["ph"] == "i"]
    assert [e["name"] for e in instants] == ["first_token"]


def test_chrome_trace_request_events_carry_their_step():
    """A request's chunk, stall and first-token instants say which device
    step they rode under ONE key, ``step``: the attr every loop phase of
    that step has, so the ``loop`` track's phase is one search away."""
    trace.enable(sample=1.0, capacity=64, process="replica:1")
    sl = trace.start_span("slot", slot=1, mode="prefill", step=7,
                          prompt_tokens=9, chunk=4)
    sl.event("prefill_chunk", step=7, lanes=3, wanted=3, pos=0)
    sl.event("prefill_stall", step=8)
    sl.event("prefill_chunk", step=9, lanes=1, wanted=3, pos=5)
    sl.event("first_token", of_step=9)
    sl.end(reason="length")
    with trace.phase("engine.step.dispatch", step=9, prefill_rows=1):
        pass
    evs = trace.chrome_trace()["traceEvents"]
    got = [(e["name"], e["args"]["step"]) for e in evs if e["ph"] == "i"]
    assert got == [("prefill_chunk", 7), ("prefill_stall", 8),
                   ("prefill_chunk", 9), ("first_token", 9)]
    first = [e for e in evs if e["name"] == "first_token"][0]
    assert first["args"]["of_step"] == 9 and first["tid"] == 101
    loop, = [e for e in evs if e.get("cat") == "loop"]
    assert loop["args"]["step"] == 9        # the phase the search finds
    # an event of a program from before the stamps is drawn as it was
    old = {"trace_id": "t", "span_id": "s", "name": "slot", "process": "p",
           "t_start": 1.0, "t_end": 2.0, "attrs": {"slot": 0},
           "events": [{"t": 1.5, "name": "first_token"}]}
    inst, = [e for e in trace.chrome_trace([old])["traceEvents"]
             if e["ph"] == "i"]
    assert inst["args"] == {"trace_id": "t"}


def test_slowest_surfaces_worst_roots():
    trace.enable(sample=1.0, capacity=64, process="unit")
    import time
    for i, dt in enumerate((0.0, 0.03, 0.01)):
        with trace.span(f"r{i}", route="/x") as s:
            s.set(ttft_ms=dt * 500)
            time.sleep(dt)
        # non-root spans never show up
        trace.start_span("noise").end()
    sl = trace.slowest(2)
    assert [r["name"] for r in sl["wall"]] == ["r1", "r2"]
    assert sl["wall"][0]["wall_ms"] >= sl["wall"][1]["wall_ms"]
    assert sl["ttft"][0]["ttft_ms"] == 15.0
    assert all(len(r["trace_id"]) == 32 for r in sl["wall"])


# ------------------------------------------------------ engine no-retrace


def test_tracing_enabled_adds_zero_jit_traces():
    from paddle_tpu.models import transformer
    from paddle_tpu.serving.decode_engine import (DecodeEngine,
                                                  GenerationBatcher)
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=64,
                              trg_vocab=1, d_model=16, num_heads=2,
                              dff=32, enc_layers=1, dec_layers=0,
                              max_len=32)
    # warm up with tracing DISABLED, then serve with it ENABLED: the
    # compiled step must not trace again
    engine = DecodeEngine(params, num_heads=2, num_slots=2, max_len=32,
                          name="obs_nr")
    trace.enable(sample=1.0, capacity=256, process="unit")
    gen = GenerationBatcher(engine, default_max_tokens=4)
    try:
        with assert_no_retrace(
                lambda: engine.step_trace_count,
                "decode under enabled tracing"):
            futs = [gen.submit(np.arange(1, 4 + 2 * i) % 60,
                               max_tokens=4) for i in range(3)]
            outs = [f.result(60) for f in futs]
        assert all(len(o["tokens"]) == 4 for o in outs)
    finally:
        gen.close()
    # a post-close submit is rejected — and must not leak a span
    from paddle_tpu.serving.batcher import ShutdownError
    with pytest.raises(ShutdownError):
        gen.submit(np.arange(1, 4), max_tokens=2)
    # the spans really recorded: every request has a slot lifetime span
    slots = [s for s in trace.snapshot() if s["name"] == "slot"]
    assert len(slots) == 3
    assert all(s["attrs"]["reason"] == "length" for s in slots)
    assert all(s["attrs"]["tokens"] == 4 for s in slots)
    # no span leaked into the live registry: every started span ended
    # (rejected submits and finished requests alike)
    assert trace.get_tracer()._active == {}


# ------------------------------------------- cross-process propagation


@pytest.mark.slow
def test_propagation_across_router_and_replica_subprocess(tmp_path):
    """One trace_id stitches the in-process router and a REAL replica
    subprocess: the replica's server.request span (fetched over its
    /debug/traces) must parent to the router's dispatch span."""
    from paddle_tpu.serving.fleet import ReplicaSupervisor
    from paddle_tpu.serving.router import Router

    import logging as pylogging
    from paddle_tpu.utils import logging as ptlog

    trace.enable(sample=1.0, capacity=1024, process="router")
    extra = ["--gen-slots", "2", "--gen-max-len", "48",
             "--gen-max-tokens", "6",
             "--obs-trace", "1"]
    sup = ReplicaSupervisor(n_replicas=1, extra_args=extra, seed=0,
                            name="obs_prop")
    router = Router(supervisor=sup, poll_interval_s=0.1,
                    name="obs_prop_router")
    httpd = None
    # capture the router's own log lines: the handler wraps each request
    # in log_context, so even debug access logs carry trace_id=<id>
    captured = []

    class _Cap(pylogging.Handler):
        def emit(self, rec):
            captured.append(self.format(rec))

    cap = _Cap(level=pylogging.DEBUG)
    cap.setFormatter(ptlog._TextFormatter(ptlog._FMT))
    shared = ptlog.get_logger()
    old_level = shared.level
    shared.addHandler(cap)
    shared.setLevel(pylogging.DEBUG)
    try:
        sup.start()
        assert sup.wait_ready(timeout=240), "replica never became ready"
        httpd = router.start(port=0)
        base = f"http://127.0.0.1:{httpd.port}"
        req = urllib.request.Request(
            f"{base}/v1/generate",
            data=json.dumps({"prompt": [3, 5, 7],
                             "max_tokens": 6}).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as r:
            out = json.loads(r.read())
            hdr_tid = r.headers.get("X-Trace-Id")
        tid = out["trace_id"]
        assert len(tid) == 32 and hdr_tid == tid
        # `grep trace_id=<id>` works on the router's process log
        assert any(f"trace_id={tid}" in line for line in captured), \
            captured[-5:]

        # router half: request root + a dispatch span on the same trace
        router_spans = {s["span_id"]: s for s in trace.snapshot()
                        if s["trace_id"] == tid}
        roots = [s for s in router_spans.values()
                 if s["name"] == "router.request"]
        dispatches = [s for s in router_spans.values()
                      if s["name"] == "router.dispatch"]
        assert len(roots) == 1 and dispatches
        assert all(d["parent_id"] == roots[0]["span_id"]
                   for d in dispatches)

        # replica half, over the wire: same trace_id, parented to the
        # router's dispatch span via the traceparent header
        (rid, url), = sup.endpoints()
        with urllib.request.urlopen(f"{url}/debug/traces",
                                    timeout=30) as r:
            payload = json.loads(r.read())
        assert payload["enabled"] is True
        assert payload["process"].startswith("replica:")
        rep = [s for s in payload["spans"] if s["trace_id"] == tid]
        byname = {s["name"]: s for s in rep}
        assert {"server.request", "gen.queue_wait", "slot"} <= set(byname)
        assert byname["server.request"]["parent_id"] in router_spans
        assert router_spans[byname["server.request"]["parent_id"]][
            "name"] == "router.dispatch"
        assert byname["slot"]["attrs"]["reason"] == "length"

        # a merged fleet dump parses and names both processes
        merged = list(router_spans.values()) + rep
        path = tmp_path / "chrome.json"
        trace.dump_chrome_trace(str(path), merged)
        with open(path) as f:
            chrome = json.load(f)
        procs = {e["args"]["name"] for e in chrome["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "process_name"}
        assert "router" in procs
        assert any(p.startswith("replica:") for p in procs)
    finally:
        shared.removeHandler(cap)
        shared.setLevel(old_level)
        router.close()
        sup.stop()

"""obs.trace.phase(): the per-step host phases of the two hot loops
(docs/observability.md).

A phase is ALWAYS a ``jax.profiler.TraceAnnotation`` (so a live profiler
session holds it on its own clock, tracer on or off) and, with the tracer
on, a record in a ring of its own that never evicts a request span.  The
generation loop and the trainer's batch loop are instrumented with it; the
shapes they must keep are pinned here: per counted decode step one
``admit -> prepare -> dispatch -> wait -> emit`` inside one
``gen.loop.iter``; per batch ``trainer.feed``, ``trainer.step`` and
``trainer.handler`` inside one ``trainer.iter`` — and not one new jit trace
for any of it."""

import glob
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


class _CountingLock:
    def __init__(self):
        self._lock, self.entered = threading.Lock(), 0

    def __enter__(self):
        self.entered += 1
        return self._lock.__enter__()

    def __exit__(self, *exc):
        return self._lock.__exit__(*exc)


def test_phase_with_tracer_off_touches_no_ring_lock_or_context():
    old = trace.enable(sample=1.0, capacity=8)
    old._lock = _CountingLock()
    trace.disable()
    with trace.phase("gen.loop.iter", step=3, active=1) as ph:
        assert trace.current() is None          # no context variable
        assert ph.set(admitted=2) is ph         # chainable, inert
    assert trace.get_tracer() is None
    assert len(old._phases) == 0 and old._lock.entered == 0
    assert trace.debug_payload()["phases"] == []
    assert trace.snapshot() == []


def test_phases_keep_a_ring_of_their_own():
    t = trace.enable(sample=1.0, capacity=8, process="unit")
    with trace.span("server.request", route="/v1/generate"):
        pass
    for i in range(50):                 # far more phases than capacity
        with trace.phase("gen.loop.emit", step=i) as ph:
            ph.set(emitted=i % 3)
    # the request span survived, and is still the slowest root
    assert [s["name"] for s in trace.snapshot()] == ["server.request"]
    assert [r["name"] for r in trace.slowest()["wall"]] == ["server.request"]
    assert t.dropped_total == 0
    held = t.phases()
    assert [p["step"] for p in held] == list(range(42, 50))   # bounded ring
    last = held[-1]
    assert last["name"] == "gen.loop.emit" and last["process"] == "unit"
    assert last["attrs"] == {"step": 49, "emitted": 49 % 3}
    assert last["t_start"] <= last["t_end"]


def _host_events(trace_dir):
    """{name: [stats dict]} of the written xplane's host planes."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    out.setdefault(e.name, []).append(dict(e.stats))
    return out


def test_phase_reaches_the_profilers_host_plane_with_tracer_off(tmp_path):
    assert not trace.enabled()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with trace.phase("gen.loop.admit", step=7) as ph:
            ph.set(admitted=2)
        with trace.phase("trainer.iter", _r=1, step_num=5):
            pass
    finally:
        jax.profiler.stop_trace()
    events = _host_events(tmp_path)
    assert events["gen.loop.admit"] == [{"step": 7, "admitted": 2}]
    # the keys jax.profiler.StepTraceAnnotation sets: a step event
    assert events["trainer.iter"] == [{"_r": 1, "step_num": 5}]


def test_chrome_trace_loop_track_and_debug_payload():
    trace.enable(sample=1.0, capacity=64, process="replica:1")
    with trace.span("server.request"):
        with trace.phase("gen.loop.iter", step=0, active=1):
            pass
    payload = trace.debug_payload()
    assert [p["name"] for p in payload["phases"]] == ["gen.loop.iter"]
    json.loads(json.dumps(payload))
    obj = trace.chrome_trace()
    tracks = {e["args"]["name"] for e in obj["traceEvents"]
              if e["ph"] == "M" and e["name"] == "thread_name"}
    assert tracks == {"host", "loop"}
    loop, = [e for e in obj["traceEvents"] if e.get("cat") == "loop"]
    assert loop["name"] == "gen.loop.iter" and loop["ph"] == "X"
    assert loop["args"] == {"step": 0, "active": 1}
    # a merged fleet dump takes the payloads' phases; spans alone, as
    # before, carry no loop track
    merged = trace.chrome_trace(payload["spans"], payload["phases"])
    assert sum(e.get("cat") == "loop" for e in merged["traceEvents"]) == 1
    alone = trace.chrome_trace(payload["spans"])
    assert not any(e.get("cat") == "loop" for e in alone["traceEvents"])


# ------------------------------------------------------ the serving loop

ITER_ORDER = ["gen.loop.iter", "gen.loop.admit", "gen.loop.prepare",
              "engine.step.dispatch", "engine.step.wait", "gen.loop.emit"]


def _lm_params():
    from paddle_tpu.models import transformer
    return transformer.init(jax.random.PRNGKey(0), src_vocab=64,
                            trg_vocab=1, d_model=16, num_heads=2, dff=32,
                            enc_layers=1, dec_layers=0, max_len=32)


@pytest.mark.parametrize("engine_kw", [
    dict(prefill_chunk=4),
    dict(kv_layout="paged", kv_block_size=8, prefill_chunk=4),
], ids=["slab", "paged"])
def test_generation_loop_one_phase_sequence_per_counted_step(engine_kw):
    from paddle_tpu.serving.decode_engine import (DecodeEngine,
                                                  GenerationBatcher)
    engine = DecodeEngine(_lm_params(), num_heads=2, num_slots=2,
                          max_len=32, name="obs_phase", **engine_kw)
    trace.enable(sample=1.0, capacity=4096, process="unit")
    gen = GenerationBatcher(engine, default_max_tokens=4)
    try:
        with assert_no_retrace(lambda: engine.step_trace_count,
                               "decode with phases recorded"):
            futs = [gen.submit(np.arange(1, 4 + 2 * i) % 60, max_tokens=4)
                    for i in range(3)]
            assert all(len(f.result(60)["tokens"]) == 4 for f in futs)
    finally:
        gen.close()
    assert engine.step_trace_count == 1
    steps = engine.metrics.decode_steps_total
    assert steps > 0
    phases = trace.get_tracer().phases()
    by_step = {}
    for p in phases:
        if p["name"] != "gen.loop.nowork":
            by_step.setdefault(p["step"], []).append(p)
    assert sorted(by_step) == list(range(steps))
    for step, rows in by_step.items():
        rows.sort(key=lambda p: (p["t_start"], -p["t_end"]))
        names = [p["name"] for p in rows]
        # the order of an iteration, as ever; what follows the hand-over
        # is the tokens of the step before (one step is in flight), none
        # (the first such step) or both (the loop runs dry): wait, emit
        assert names[:4] == ITER_ORDER[:4], step
        assert names[4:] == ITER_ORDER[4:] * (len(names[4:]) // 2), step
        it = rows[0]
        for a, b in zip(rows[1:], rows[2:]):
            assert a["t_end"] <= b["t_start"]       # one after the other
        assert it["t_start"] <= rows[1]["t_start"] \
            and rows[-1]["t_end"] <= it["t_end"]    # all inside the iter
        disp = rows[3]["attrs"]
        # what the step carried: seated rows, those fed a prompt chunk,
        # the chunks' lanes (what gen.loop.prepare armed) and, where the
        # pool counts them, the positions attended.  The host arrays are
        # fixed by the engine's shapes and said once, in its warm line
        assert "host_args" not in disp and "host_arg_bytes" not in disp
        assert 1 <= disp["rows"] <= 2
        assert 0 <= disp["prefill_rows"] <= disp["rows"]
        assert disp["prefill_lanes"] == rows[2]["attrs"]["chunk_lanes"] \
            == disp["live"] - 2
        assert (disp["prefill_rows"] > 0) == (disp["prefill_lanes"] > 0)
        assert (disp["attended"] > 0) \
            == (engine_kw.get("kv_layout") == "paged")
        assert disp["in_flight"] in (0, 1)
        of = [p["attrs"]["of_step"] for p in rows[4:]]
        assert of[0::2] == of[1::2]         # emit what was waited for
        if disp["in_flight"]:
            assert of[0] == step - 1        # read after the hand-over
        assert all(o <= step for o in of)
    # every device step's tokens are waited for and emitted once, in order
    for name in ITER_ORDER[4:]:
        assert [p["attrs"]["of_step"] for p in phases
                if p["name"] == name] == list(range(steps))
    overlapped = sum(p["attrs"]["in_flight"] for p in phases
                     if p["name"] == "engine.step.dispatch")
    assert overlapped == engine.metrics.decode_steps_overlapped_total
    assert overlapped >= steps - 3      # three runs of steps at most
    dispatches = [p["attrs"] for p in phases
                  if p["name"] == "engine.step.dispatch"]
    assert sum(d["prefill_rows"] > 0 for d in dispatches) \
        == engine.metrics.decode_steps_with_prefill_total > 0
    assert sum(d["prefill_lanes"] for d in dispatches) \
        == engine.metrics.prefill_lane_steps_total
    if engine_kw.get("kv_layout") == "paged":
        assert sum(d["attended"] for d in dispatches) \
            == engine.metrics.attended_positions_total
    emitted = sum(p["attrs"]["emitted"] for p in phases
                  if p["name"] == "gen.loop.emit")
    # every token, the first included, is delivered by an emit phase
    assert emitted == engine.metrics.gen_tokens_total
    assert sum(p["attrs"]["finished"] for p in phases
               if p["name"] == "gen.loop.emit") == 3
    # waiting for work lies outside every iteration
    iters = [(p["t_start"], p["t_end"]) for p in phases
             if p["name"] == "gen.loop.iter"]
    for p in phases:
        if p["name"] == "gen.loop.nowork":
            assert not any(s < p["t_end"] and p["t_start"] < e
                           for s, e in iters)
    # the request spans are what they were: no phase among them
    assert not {s["name"] for s in trace.snapshot()} & set(ITER_ORDER)


def _slot_events(span, name):
    return [e["attrs"] for e in span["events"] if e["name"] == name]


@pytest.mark.parametrize("engine_kw", [
    dict(), dict(kv_layout="paged", kv_block_size=8),
], ids=["slab", "paged"])
def test_a_request_alone_says_which_step_fed_each_chunk(engine_kw):
    """The join (docs/observability.md): request event -> ``step`` ->
    the ``engine.step.dispatch`` phase of that step.  Lane 0 holds the
    row's current token and ``load_chunk`` arms lanes 1..n, so a step
    consumes ``lanes + 1`` of the feed until its last chunk drains it."""
    from paddle_tpu.serving.decode_engine import (DecodeEngine,
                                                  GenerationBatcher)
    kk, prompt = 4, np.arange(1, 15) % 60           # 13 to feed: 4 chunks
    engine = DecodeEngine(_lm_params(), num_heads=2, num_slots=2,
                          max_len=32, prefill_chunk=kk, name="obs_join",
                          **engine_kw)
    trace.enable(sample=1.0, capacity=4096, process="unit")
    gen = GenerationBatcher(engine, default_max_tokens=3)
    try:
        assert len(gen.generate(prompt, timeout=60)["tokens"]) == 3
    finally:
        gen.close()
    slot, = [s for s in trace.snapshot() if s["name"] == "slot"]
    attrs = slot["attrs"]
    assert (attrs["mode"], attrs["prompt_tokens"], attrs["chunk"],
            attrs["teacher_forced"]) == ("prefill", 14, kk, 13)
    chunks = _slot_events(slot, "prefill_chunk")
    steps = [c["step"] for c in chunks]
    assert steps == list(range(attrs["step"], attrs["step"] + 4))
    left = attrs["teacher_forced"]
    for c in chunks:
        assert c["lanes"] == c["wanted"] == min(kk - 1, left)
        left -= min(c["lanes"] + 1, left)
    assert left == 0                        # the chunks use the feed up
    assert _slot_events(slot, "prefill_stall") == []
    first, = _slot_events(slot, "first_token")
    assert first == {"of_step": steps[-1]}
    by_step = {p["step"]: p["attrs"] for p in trace.get_tracer().phases()
               if p["name"] == "engine.step.dispatch"}
    for c in chunks:
        disp = by_step[c["step"]]
        assert (disp["rows"], disp["prefill_rows"],
                disp["prefill_lanes"]) == (1, 1, c["lanes"])
    # the steps after the first token decode: one row, no chunk
    assert all((d["rows"], d["prefill_rows"]) == (1, 0)
               for step, d in by_step.items() if step > steps[-1])
    m = engine.metrics
    assert m.decode_steps_with_prefill_total == 4
    assert m.prefill_stalled_row_steps_total == 0
    snap = m.snapshot()
    assert 0 < snap["prefill_ms"]["p50"] <= snap["ttft_ms"]["p50"]
    assert m.prefill.count == m.ttft.count == 1
    text = m.render_prometheus()
    assert 'prefill_seconds{quantile="0.50"}' in text
    assert "decode_steps_with_prefill_total 4" in text
    assert "prefill_stalled_row_steps_total 0" in text


def test_a_row_the_budget_cuts_leaves_a_stall_event_and_a_count():
    """``prefill_chunk_budget`` 3 is ONE row's chunk at K = 4: while two
    rows have prompt left, the second gets no lanes (one token through its
    lane 0) and says so, where it used to leave no mark at all."""
    from paddle_tpu.serving.decode_engine import (DecodeEngine,
                                                  GenerationBatcher)
    engine = DecodeEngine(_lm_params(), num_heads=2, num_slots=2,
                          max_len=32, prefill_chunk=4,
                          prefill_chunk_budget=3, name="obs_stall")
    trace.enable(sample=1.0, capacity=4096, process="unit")
    gen = GenerationBatcher(engine, default_max_tokens=2)
    try:
        futs = [gen.submit((np.arange(1, 29) + i) % 60, max_tokens=2)
                for i in range(2)]
        assert all(len(f.result(60)["tokens"]) == 2 for f in futs)
    finally:
        gen.close()
    slots = [s for s in trace.snapshot() if s["name"] == "slot"]
    assert len(slots) == 2
    stalls = [e for s in slots for e in _slot_events(s, "prefill_stall")]
    assert stalls and all(set(e) == {"step"} for e in stalls)
    assert len(stalls) == engine.metrics.prefill_stalled_row_steps_total
    by_step = {p["step"]: p["attrs"] for p in trace.get_tracer().phases()
               if p["name"] == "engine.step.dispatch"}
    fed = {c["step"] for s in slots
           for c in _slot_events(s, "prefill_chunk")}
    for e in stalls:
        # the step a row sat out fed another row its whole budget: two
        # rows seated, one of them prefilling
        assert e["step"] in fed
        disp = by_step[e["step"]]
        assert (disp["rows"], disp["prefill_rows"],
                disp["prefill_lanes"]) == (2, 1, 3)
    # a stalled row took more steps than its feed needs at a whole chunk
    # a step; every first token still names the step that produced it
    for s in slots:
        chunks = _slot_events(s, "prefill_chunk")
        assert all(c["lanes"] <= c["wanted"] <= 3 for c in chunks)
        first, = _slot_events(s, "first_token")
        assert chunks[-1]["step"] <= first["of_step"] \
            <= chunks[-1]["step"] + 1
    taken = [max(c["step"] for c in _slot_events(s, "prefill_chunk"))
             - s["attrs"]["step"] + 1 for s in slots]
    assert max(taken) > -(-27 // 4)


def test_step_stats_reach_the_host_plane_with_the_tracer_off(tmp_path):
    from paddle_tpu.serving.decode_engine import (DecodeEngine,
                                                  GenerationBatcher)
    engine = DecodeEngine(_lm_params(), num_heads=2, num_slots=2,
                          max_len=32, prefill_chunk=4, kv_layout="paged",
                          kv_block_size=8, name="obs_off")
    old = trace.enable(sample=1.0, capacity=8)
    old._lock = _CountingLock()
    trace.disable()
    gen = GenerationBatcher(engine, default_max_tokens=2)
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = gen.generate(np.arange(1, 11) % 60, timeout=60)
        assert len(out["tokens"]) == 2 and trace.current() is None
    finally:
        jax.profiler.stop_trace()
        gen.close()
    # no ring, no lock, no span of the request anywhere
    assert trace.get_tracer() is None and old._lock.entered == 0
    assert len(old._phases) == len(old._done) == len(old._active) == 0
    disp = _host_events(tmp_path)["engine.step.dispatch"]
    assert len(disp) == engine.metrics.decode_steps_total
    for st in disp:
        assert {"step", "in_flight", "width", "live", "lanes", "rows",
                "prefill_rows", "prefill_lanes", "attended",
                "one_lane_rows"} == set(st)
    # 9 to feed at K = 4: chunks of 3, 3 and 1 lanes, then decode steps
    assert [st["prefill_lanes"] for st in disp][:4] == [3, 3, 1, 0]
    assert [st["prefill_rows"] for st in disp][:4] == [1, 1, 1, 0]
    assert sum(st["attended"] for st in disp) \
        == engine.metrics.attended_positions_total > 0
    # the counters an operator has without a profiler moved all the same
    assert engine.metrics.decode_steps_with_prefill_total == 3
    assert engine.metrics.prefill.count == 1


def test_one_lane_rows_are_the_seated_rows_fed_one_lane():
    """``one_lane_rows`` on each ``engine.step.dispatch`` phase, and the
    ``one_lane_row_steps_total`` counter they sum to, are the seated rows
    of that step fed exactly ONE lane (``lens == 1``): the tiled attention
    kernel's one-lane predicate.  A free slot's armed lane takes that path
    too and is no row of the step.  Two slots, three requests at K = 4:
    steps with a row prefilling beside a row decoding, both decoding, one
    slot free."""
    from paddle_tpu.serving.decode_engine import (DecodeEngine,
                                                  GenerationBatcher)
    engine = DecodeEngine(_lm_params(), num_heads=2, num_slots=2,
                          max_len=32, prefill_chunk=4, kv_layout="paged",
                          kv_block_size=8, name="obs_one_lane")
    want = {}
    dispatch = engine.dispatch_step

    def counted():
        seated = [s for s in range(engine.num_slots)
                  if s not in engine._free]
        want[engine.steps_dispatched] = sum(
            int(engine._len[s]) == 1 for s in seated)
        return dispatch()

    engine.dispatch_step = counted
    trace.enable(sample=1.0, capacity=4096, process="unit")
    gen = GenerationBatcher(engine, default_max_tokens=3)
    try:
        futs = [gen.submit((np.arange(1, 4 + 5 * i) + i) % 60, max_tokens=3)
                for i in range(3)]
        assert all(len(f.result(60)["tokens"]) == 3 for f in futs)
    finally:
        gen.close()
    got = {p["step"]: p["attrs"]["one_lane_rows"]
           for p in trace.get_tracer().phases()
           if p["name"] == "engine.step.dispatch"}
    assert got == want and len(got) == engine.metrics.decode_steps_total
    disp = {p["step"]: p["attrs"] for p in trace.get_tracer().phases()
            if p["name"] == "engine.step.dispatch"}
    # the kinds of step the drive was built to hold
    kinds = {(d["rows"], d["prefill_rows"], d["one_lane_rows"])
             for d in disp.values()}
    assert {(2, 1, 1), (2, 0, 2), (1, 0, 1)} <= kinds, kinds
    for d in disp.values():     # no speculation: a row is one or the other
        assert d["one_lane_rows"] == d["rows"] - d["prefill_rows"]
    m = engine.metrics
    assert m.one_lane_row_steps_total == sum(want.values()) > 0
    assert m.snapshot()["one_lane_row_steps_total"] \
        == m.one_lane_row_steps_total
    assert (f"one_lane_row_steps_total {m.one_lane_row_steps_total}"
            in m.render_prometheus())


def test_debug_traces_endpoint_carries_phases():
    from paddle_tpu.serving.decode_engine import (DecodeEngine,
                                                  GenerationBatcher)
    from paddle_tpu.serving.server import make_server
    engine = DecodeEngine(_lm_params(), num_heads=2, num_slots=2,
                          max_len=32, name="obs_phase_http")
    trace.enable(sample=1.0, capacity=256, process="unit")
    gen = GenerationBatcher(engine, default_max_tokens=2)
    httpd = make_server(None, port=0, gen_batcher=gen)
    th = threading.Thread(target=httpd.serve_forever, daemon=True)
    th.start()
    try:
        assert len(gen.generate(np.arange(1, 5), timeout=60)["tokens"]) == 2
        with urllib.request.urlopen(
                f"http://127.0.0.1:{httpd.port}/debug/traces",
                timeout=30) as r:
            payload = json.loads(r.read())
    finally:
        httpd.shutdown()
        httpd.server_close()
        th.join(30)
        gen.close()
    assert {"gen.loop.iter", "engine.step.dispatch"} \
        <= {p["name"] for p in payload["phases"]}
    assert "slot" in {s["name"] for s in payload["spans"]}


# ------------------------------------------------------ the trainer loop


def test_trainer_loop_phases_once_a_batch():
    import paddle_tpu.layers as L
    from paddle_tpu import optim
    from paddle_tpu.data import dense_vector, integer_value
    from paddle_tpu.layers.graph import reset_names
    from paddle_tpu.trainer import SGD, events

    reset_names()
    x = L.data_layer("x", size=4)
    lbl = L.data_layer("lbl", size=2)
    out = L.fc_layer(x, size=2, act="softmax")
    tr = SGD(cost=L.classification_cost(out, lbl),
             update_equation=optim.Momentum(learning_rate=0.1))
    rng = np.random.RandomState(0)
    batches = [[(rng.rand(4).astype(np.float32), int(i % 2))
                for i in range(8)] for _ in range(5)]
    seen = []
    feeding = {"x": dense_vector(4), "lbl": integer_value(2)}

    def run():
        tr.train(lambda: iter(batches), num_passes=1, feeding=feeding,
                 event_handler=lambda e: seen.append(type(e)),
                 log_period=0, buffered_batches=0)

    run()                               # the step's one trace
    trace.enable(sample=1.0, capacity=256, process="unit")
    with assert_no_retrace(lambda: tr.trace_count,
                           "train() with phases recorded"):
        run()
    assert seen.count(events.EndIteration) == 10
    phases = trace.get_tracer().phases()
    by_batch = {}
    for p in phases:
        by_batch.setdefault(p["step"], []).append(p)
    # batch 5 is the read that found the reader empty: a feed, no step
    assert sorted(by_batch) == list(range(6))
    assert sorted(p["name"] for p in by_batch.pop(5)) \
        == ["trainer.feed", "trainer.iter"]
    for batch, rows in by_batch.items():
        names = [p["name"] for p in sorted(
            rows, key=lambda p: (p["t_start"], -p["t_end"]))]
        # the feed is the reader and the conversion, then (after the
        # BeginIteration handler) the global arrays' assembly
        assert names == ["trainer.iter", "trainer.feed", "trainer.handler",
                         "trainer.feed", "trainer.step",
                         "trainer.handler"], batch
        it = next(p for p in rows if p["name"] == "trainer.iter")
        assert all(it["t_start"] <= p["t_start"] and p["t_end"] <= it["t_end"]
                   for p in rows)
        step, = [p for p in rows if p["name"] == "trainer.step"]
        assert step["attrs"]["batch"] == batch
        assert step["attrs"]["pass_id"] == 0
        assert "h2d_wait_ms" in step["attrs"]
    # a training step is no request: nothing of it among the spans
    assert trace.snapshot() == []

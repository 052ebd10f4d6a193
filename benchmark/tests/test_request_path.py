"""request_path: the seven readers that follow a request to its first token
step by step (``prefill_ms_per_token_p50``, ``prefill_stall_share``,
``first_token_tail_ms_p50``, ``first_token_front_ms_p50``) and say what the
device steps carried (``row_step_ms_p95``, ``prefill_step_share``,
``prefill_step_ms_p50``).

On hand-built spans and a hand-built ``HostSpans`` with known answers, on
the records of a program from before the stamps (every reader gives None),
on a tiny engine (the count of steps a feed needs is what a request alone
takes), and the manifest's entries.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import copy
import importlib.util
import os
import sys

import numpy as np
import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.dirname(_HERE)
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmark import arith, harness, host_spans, request_path  # noqa: E402

_CELLS = ["opt1.3b_chat", "kimilinear_reason", "pangu_longdoc",
          "jamba3b_longctx"]
_ENTRIES = {
    "prefill_ms_per_token_p50": ("ms/token", "scheduler and KV manager",
                                 "ttft_per_token_p50_ms"),
    "prefill_stall_share": ("%", "scheduler and KV manager",
                            "ttft_per_token_p50_ms"),
    "first_token_tail_ms_p50": ("ms", "the one jitted step",
                                "ttft_per_token_p50_ms"),
    "first_token_front_ms_p50": ("ms", "HTTP front and batcher",
                                 "ttft_per_token_p50_ms"),
    "row_step_ms_p95": ("ms", "the one jitted step", "itl_p95_ms"),
    "prefill_step_share": ("%", "the one jitted step", "itl_p95_ms"),
    "prefill_step_ms_p50": ("ms", "the one jitted step", "itl_p95_ms"),
}
_SPAN_ONLY = ["prefill_ms_per_token_p50", "prefill_stall_share",
              "first_token_front_ms_p50"]


def _growth():
    spec = importlib.util.spec_from_file_location(
        "request_path_growth", os.path.join(_HERE,
                                            "test_manifest_growth.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# test_manifest_growth.py's manifest with a made-up cell and entry appended
grown_spec = _growth().grown_spec


# ------------------------------------------------- the hand-built records

def _span(name, tid, t0, t1, attrs=None, events=()):
    return {"trace_id": tid, "span_id": name + tid, "parent_id": None,
            "name": name, "process": "unit", "t_start": t0, "t_end": t1,
            "attrs": dict(attrs or {}),
            "events": [{"t": t, "name": n, **({"attrs": a} if a else {})}
                       for t, n, a in events]}


def _request(tid, t0, enqueue, seat, slot_attrs, slot_events, wrote):
    """One request's three spans: the handler's, the queue wait, the slot."""
    return [
        _span("server.request", tid, t0, seat + 1.0, {"root": True},
              [(wrote, "first_token", None)]),
        _span("gen.queue_wait", tid, t0 + enqueue, seat, {"root": False}),
        _span("slot", tid, seat, seat + 1.0, slot_attrs, slot_events)]


def _chunk(t, step, lanes, wanted=None):
    return (t, "prefill_chunk", {"step": step, "lanes": lanes,
                                 "wanted": wanted or lanes, "pos": 0})


# A: alone, 11 to feed at a chunk of 4: steps 1, 2, 3, first token of step 3
# B: left out of step 4 (one token through lane 0), fed in 5, cut to one
#    lane in 6 (which drains the feed), so its first token is step 7's
# C: seated before the window opened; D: a prefix hit, no fresh admission
_SPANS = (
    _request("a", 110.0, 0.001, 110.004,
             {"slot": 0, "mode": "prefill", "teacher_forced": 11,
              "step": 1, "prompt_tokens": 12, "chunk": 4},
             [_chunk(110.01, 1, 3), _chunk(110.1, 2, 3),
              _chunk(110.2, 3, 3), (110.5, "first_token", {"of_step": 3})],
             wrote=110.503)
    + _request("b", 120.0, 0.002, 120.01,
               {"slot": 1, "mode": "prefill", "teacher_forced": 7,
                "step": 4, "prompt_tokens": 8, "chunk": 4},
               [(120.02, "prefill_stall", {"step": 4}),
                _chunk(120.1, 5, 3), _chunk(120.2, 6, 1, wanted=2),
                (120.41, "first_token", {"of_step": 7})],
               wrote=120.4125)
    + _request("c", 49.9, 0.001, 50.0,
               {"slot": 2, "mode": "prefill", "teacher_forced": 3,
                "step": 0, "prompt_tokens": 4, "chunk": 4},
               [_chunk(50.0, 0, 3), (50.9, "first_token", {"of_step": 0})],
               wrote=50.95)
    + _request("d", 130.0, 0.5, 130.6,
               {"slot": 3, "mode": "prefix_hit", "teacher_forced": 2,
                "step": 8, "prompt_tokens": 40, "chunk": 4},
               [_chunk(130.6, 8, 2), (130.7, "first_token", {"of_step": 8})],
               wrote=130.9))
_WINDOW = (100.0, 200.0)

# the device ran from 0 to 10 on the profiler's clock
_BUSY = [[[0.0, 10.0]]]
# step: (rows, prefill_rows) it carried, and when its tokens were read
_CARRIED = {0: (1, 1), 1: (2, 1), 2: (2, 1), 3: (2, 1), 4: (3, 0),
            5: (3, 1), 6: (3, 1), 7: (3, 0), 8: (4, 1), 9: (4, 0)}
_READ = {0: 1.1, 1: 2.1, 2: 3.1, 3: 4.3, 4: 5.3, 5: 6.3, 6: 7.7, 7: 8.5,
         8: 9.5}                                # step 9's tokens: not read
_PHASES = {
    # step 0 was handed over before the window's first device operation
    "engine.step.dispatch": [
        (n - 0.5 if n == 0 else float(n), n + 0.2,
         {"step": n, "in_flight": 1, "rows": r, "prefill_rows": p,
          "prefill_lanes": 3 * p, "attended": 10 * r})
        for n, (r, p) in _CARRIED.items()],
    "engine.step.wait": [(t - 0.6, t, {"step": n + 1, "of_step": n})
                         for n, t in _READ.items()],
    "gen.loop.emit": [(t, t + 0.05, {"step": n + 1, "of_step": n,
                                     "emitted": 1, "finished": 0})
                      for n, t in _READ.items()],
}


def _strip(spans, phases):
    """The same run as a program from before the stamps recorded it."""
    spans, phases = copy.deepcopy(spans), copy.deepcopy(phases)
    for s in spans:
        for key in ("step", "prompt_tokens", "chunk"):
            s["attrs"].pop(key, None)
        s["events"] = [e for e in s["events"]
                       if e["name"] != "prefill_stall"]
        for e in s["events"]:
            if e["name"] == "first_token":
                e.pop("attrs", None)
            elif e["name"] == "prefill_chunk":
                e["attrs"] = {"lanes": e["attrs"]["lanes"], "pos": 0}
    for _s, _e, st in phases["engine.step.dispatch"]:
        for key in ("rows", "prefill_rows", "prefill_lanes", "attended"):
            del st[key]
        st.update(host_args=4, host_arg_bytes=4096)
    return spans, phases


def _read(name, spans, phases, monkeypatch, window=_WINDOW):
    hs = host_spans.HostSpans(phases, _BUSY) if phases is not None else None
    monkeypatch.setattr(host_spans, "load", lambda obs: hs)
    obs = {"spans": spans, "window_wall": window, "trace": {}}
    return harness.Spec().reader("per_layer", name).read(obs)


# ------------------------------------------------------ the known answers

def test_steps_needed_is_a_whole_chunk_a_step():
    # lanes + 1 of the feed a step, the chunk at most; the rest in the last
    assert [request_path.steps_needed(f, 4) for f in (1, 3, 4, 5, 8, 11)] \
        == [1, 1, 1, 2, 2, 3]
    assert request_path.steps_needed(0, 4) == 0
    assert request_path.steps_needed(8924, 64) == 140


def test_prefill_ms_per_token_p50_is_seat_to_first_token(monkeypatch):
    # A: 0.496 s over 12 tokens; B: 0.400 s over 8; C before the window
    # and D, a prefix hit, are no fresh admission of the window
    want = arith.percentile([496.0 / 12, 400.0 / 8], 50)
    got = _read("prefill_ms_per_token_p50", _SPANS, _PHASES, monkeypatch)
    assert got == pytest.approx(want)
    # spans alone are enough: it needs no device trace
    assert _read("prefill_ms_per_token_p50", _SPANS, None, monkeypatch) \
        == pytest.approx(want)


def test_prefill_stall_share_counts_the_steps_a_feed_did_not_need(
        monkeypatch):
    # A needs 3 and takes 3; B needs 2 (7 to feed) and takes steps 4..6
    assert _read("prefill_stall_share", _SPANS, _PHASES, monkeypatch) \
        == pytest.approx(100.0 * (1 - 5 / 6))
    alone = [s for s in _SPANS if s["trace_id"] == "a"]
    assert _read("prefill_stall_share", alone, _PHASES, monkeypatch) == 0.0


def test_first_token_tail_ms_p50_is_dispatch_to_emit_of_that_step(
        monkeypatch):
    # steps 3, 7 and 8 produced first tokens inside the trace's window:
    # 3.0 -> 4.35, 7.0 -> 8.55, 8.0 -> 9.55; step 0 was handed over before
    assert _read("first_token_tail_ms_p50", _SPANS, _PHASES, monkeypatch) \
        == pytest.approx(1550.0)
    tails = request_path.first_token_tails(
        {"spans": _SPANS, "window_wall": _WINDOW, "trace": {}})
    assert sorted(tails) == pytest.approx([1.35, 1.55, 1.55])


def test_first_token_front_ms_p50_is_handler_time_round_the_engine(
        monkeypatch):
    # A: 1 ms to the enqueue + 3 ms from the slot's event to the write;
    # B: 2 + 2.5; D: 500 + 200; C started before the window
    assert _read("first_token_front_ms_p50", _SPANS, _PHASES, monkeypatch) \
        == pytest.approx(4.5)
    ab = [s for s in _SPANS if s["trace_id"] in "ab"]
    assert _read("first_token_front_ms_p50", ab, None, monkeypatch) \
        == pytest.approx(4.25)


def test_row_step_ms_p95_weighs_each_gap_by_its_decoding_rows(monkeypatch):
    # gaps between reads of steps 1..8 (step 9 was not read), each once a
    # decoding row: rows - prefill_rows
    gaps = [1.0] * 1 + [1.0] * 1 + [1.2] * 1 + [1.0] * 3 + [1.0] * 2 \
        + [1.4] * 2 + [0.8] * 3 + [1.0] * 3
    assert _read("row_step_ms_p95", _SPANS, _PHASES, monkeypatch) \
        == pytest.approx(arith.percentile(gaps, 95) * 1e3)
    steps = request_path.step_intervals({"trace": {}})
    assert [(round(s, 6), r, p) for s, r, p in steps] == [
        (1.0, 2, 1), (1.0, 2, 1), (1.2, 2, 1), (1.0, 3, 0), (1.0, 3, 1),
        (1.4, 3, 1), (0.8, 3, 0), (1.0, 4, 1)]


def test_prefill_step_share_and_ms_p50(monkeypatch):
    # of the nine dispatches inside the window, steps 1, 2, 3, 5, 6, 8
    assert _read("prefill_step_share", _SPANS, _PHASES, monkeypatch) \
        == pytest.approx(100.0 * 6 / 9)
    # their gaps: 1.0, 1.0, 1.2, 1.0, 1.4, 1.0
    assert _read("prefill_step_ms_p50", _SPANS, _PHASES, monkeypatch) \
        == pytest.approx(1000.0)


# ------------------------------------------- a program without the stamps

@pytest.mark.parametrize("name", sorted(_ENTRIES))
def test_reader_is_none_on_the_records_of_a_program_without_stamps(
        name, monkeypatch):
    """The parent under this PR's benchmark files: ``slot`` spans without
    ``step``, events without ``step`` or ``of_step``, dispatch phases
    without ``prefill_rows``.  Its line leaves the metric out."""
    spans, phases = _strip(_SPANS, _PHASES)
    assert _read(name, spans, phases, monkeypatch) is None
    assert _read(name, [], {}, monkeypatch) is None
    assert _read(name, None, None, monkeypatch) is None


@pytest.mark.parametrize("name", sorted(_ENTRIES))
def test_reader_is_none_on_a_trace_recorded_before_the_stamps(
        name, tmp_path, monkeypatch):
    """A trace recorded on the v5e from the loop of PR 25
    (testdata/TRACES.md): dispatch phases with ``host_args`` and no
    ``prefill_rows``, beside that program's spans."""
    import gzip
    from benchmark import trace_reduce
    cell = "opt1.3b_chat"
    out = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    path = out / "host.xplane.pb"
    path.write_bytes(gzip.open(os.path.join(
        _BENCH, "testdata", "chat_phases.xplane.pb.gz")).read())
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    monkeypatch.setattr(host_spans, "_loaded", {})
    spec = harness.Spec()
    obs = {"trace": trace_reduce.reduce(trace_reduce.read_xplane(str(path))),
           "cell": spec.cell(cell), "spans": _strip(_SPANS, _PHASES)[0],
           "window_wall": _WINDOW}
    assert host_spans.load(obs).durations("engine.step.dispatch")
    assert spec.reader("per_layer", name).read(obs) is None


@pytest.mark.parametrize("name", sorted(_ENTRIES))
def test_reader_without_a_device_trace(name):
    """The CPU rehearsal: the driver's ``trace`` is None, the spans are
    there.  The three that read spans alone answer; the rest give None."""
    spec = harness.Spec()
    obs = {"trace": None, "cell": spec.cell("opt1.3b_chat"),
           "spans": _SPANS, "window_wall": _WINDOW}
    value = spec.reader("per_layer", name).read(obs)
    assert (value is not None) == (name in _SPAN_ONLY)
    assert spec.reader("per_layer", name).read(
        {"trace": None, "cell": spec.cell("opt1.3b_chat"),
         "spans": None}) is None


def test_a_request_without_its_first_token_is_left_out(monkeypatch):
    spans = copy.deepcopy(_SPANS)
    for s in spans:
        if s["trace_id"] == "b" and s["name"] == "slot":
            s["events"] = s["events"][:-1]
            s["t_end"] = None               # still seated at the snapshot
    assert _read("prefill_ms_per_token_p50", spans, _PHASES, monkeypatch) \
        == pytest.approx(496.0 / 12)
    assert _read("prefill_stall_share", spans, _PHASES, monkeypatch) == 0.0
    ab = [s for s in spans if s["trace_id"] in "ab"]
    assert _read("first_token_front_ms_p50", ab, None, monkeypatch) \
        == pytest.approx(4.0)


# -------------------------------------------------------- the tiny engine

def _tiny_engine(**kw):
    import jax
    from paddle_tpu.models import transformer
    from paddle_tpu.serving.decode_engine import DecodeEngine
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=64,
                              trg_vocab=1, d_model=16, num_heads=2, dff=32,
                              enc_layers=1, dec_layers=0, max_len=32)
    return DecodeEngine(params, num_heads=2, num_slots=2, max_len=32,
                        prefill_chunk=4, name="request_path", **kw)


@pytest.fixture
def tracer():
    from paddle_tpu.obs import trace
    trace.enable(sample=1.0, capacity=4096, process="unit")
    yield trace
    trace.disable()


def test_steps_needed_is_what_a_request_alone_takes(tracer):
    """The pin: on an idle engine every request takes exactly the steps its
    feed needs, whatever its length falls on, so the share reads 0."""
    from paddle_tpu.serving.decode_engine import GenerationBatcher
    gen = GenerationBatcher(_tiny_engine(), default_max_tokens=2)
    lengths = (2, 4, 5, 6, 9, 13, 14)
    try:
        for n in lengths:       # one after the other: each alone
            assert len(gen.generate(np.arange(1, n + 1) % 60,
                                    timeout=60)["tokens"]) == 2
    finally:
        gen.close()
    spans = tracer.snapshot()
    slots = [s for s in spans if s["name"] == "slot"]
    assert [s["attrs"]["prompt_tokens"] for s in slots] == list(lengths)
    for s in slots:
        fed = [e["attrs"]["step"] for e in s["events"]
               if e["name"] == "prefill_chunk"]
        assert len(fed) == request_path.steps_needed(
            s["attrs"]["teacher_forced"], s["attrs"]["chunk"])
        assert fed == list(range(s["attrs"]["step"],
                                 s["attrs"]["step"] + len(fed)))
    obs = {"spans": spans, "window_wall": (0.0, float("inf")),
           "trace": None}
    rows = request_path.prefills(obs)
    assert [(r["needed"], r["taken"]) for r in rows] \
        == [(request_path.steps_needed(n - 1, 4),) * 2 for n in lengths]
    spec = harness.Spec()
    assert spec.reader("per_layer", "prefill_stall_share").read(obs) == 0.0
    assert spec.reader("per_layer", "prefill_ms_per_token_p50").read(obs) > 0
    # no HTTP front in this drive: no request has a server.request span
    assert spec.reader("per_layer", "first_token_front_ms_p50").read(obs) \
        is None


def test_prefill_stall_share_rises_where_the_budget_cuts_a_row(tracer):
    from paddle_tpu.serving.decode_engine import GenerationBatcher
    engine = _tiny_engine(prefill_chunk_budget=3)   # ONE row's chunk
    gen = GenerationBatcher(engine, default_max_tokens=2)
    try:
        futs = [gen.submit((np.arange(1, 29) + i) % 60, max_tokens=2)
                for i in range(2)]
        assert all(len(f.result(60)["tokens"]) == 2 for f in futs)
    finally:
        gen.close()
    assert engine.metrics.prefill_stalled_row_steps_total > 0
    obs = {"spans": tracer.snapshot(),
           "window_wall": (0.0, float("inf")), "trace": None}
    share = harness.Spec().reader("per_layer", "prefill_stall_share").read(
        obs)
    assert 0.0 < share < 100.0


# ------------------------------------------------------------ the manifest

def request_path_entries_hold(spec):
    """Each entry, found by NAME, with the fields it was accepted with; its
    cells include the four serving cells, each reports it, and the training
    cell does not."""
    for name, (unit, layer, moves) in _ENTRIES.items():
        entry, = [m for m in spec.manifest["per_layer"]
                  if m["name"] == name]
        assert {k: v for k, v in entry.items() if k != "workloads"} == {
            "name": name, "unit": unit, "better": "lower",
            "source": "program_span", "layer": layer, "moves": moves}
        assert entry["workloads"][:4] == _CELLS
        for cell in entry["workloads"]:
            assert name in [m["name"] for m in spec.metrics_for(
                spec.cell(cell), "per_layer")]
        assert name not in [m["name"] for m in spec.metrics_for(
            spec.cell("lstm-h512_train"), "per_layer")]
        assert callable(spec.reader("per_layer", name).read)


def test_request_path_manifest_entries():
    spec = harness.Spec()
    request_path_entries_hold(spec)
    # every layer named is one the manifest already had, letter for letter
    had = {m["layer"] for m in spec.manifest["per_layer"]
           if m["name"] not in _ENTRIES}
    assert {layer for _u, layer, _m in _ENTRIES.values()} <= had
    assert len(spec.manifest["per_layer"]) >= 42


def test_request_path_entries_hold_on_a_grown_manifest(grown_spec):
    assert grown_spec.manifest["per_layer"][-1]["name"] == "made_up_share"
    request_path_entries_hold(grown_spec)

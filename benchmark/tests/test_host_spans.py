"""host_spans: the program's loop phases against the device's idle gaps.

On synthetic intervals (the join, the self time, a trace without phases),
on every reader without a device trace, and on two short traces recorded on
the v5e with the phases in their host planes (testdata/TRACES.md).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import gzip
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import harness, host_spans, trace_reduce  # noqa: E402

NEW = {"opt1.3b_chat": ["step_dispatch_ms_p50", "serve_idle_dispatch_share",
                        "serve_idle_readback_share", "serve_idle_sched_share",
                        "serve_idle_nowork_share", "sched_host_ms_p50"],
       "lstm-h512_train": ["feed_host_share", "train_idle_feed_share"]}
SERVE_IDLE = [["engine.step.dispatch"], ["engine.step.wait"],
              ["gen.loop.admit", "gen.loop.prepare", "gen.loop.emit"],
              ["gen.loop.nowork"]]


def test_intersect_seconds():
    a = [(0.0, 1.0), (2.0, 4.0), (6.0, 7.0)]
    b = [(0.5, 2.5), (3.0, 6.5)]
    assert host_spans.intersect_seconds(a, b) == pytest.approx(
        0.5 + 0.5 + 1.0 + 0.5)
    assert host_spans.intersect_seconds(a, []) == 0.0
    assert host_spans.intersect_seconds(a, a) == pytest.approx(4.0)


# the device ran in [0,1], [2,3] and [5,6]: idle in (1,2) and (3,5)
BUSY = [[[0.0, 1.0], [2.0, 3.0], [5.0, 6.0]]]
PHASES = {
    "gen.loop.iter": [(0.8, 2.4, {"step": 0, "active": 1})],
    "gen.loop.admit": [(0.8, 0.9, {"step": 0})],
    # the engine's two phases ran on another thread: only time and step
    # join them to the iteration
    "engine.step.dispatch": [(0.9, 1.6, {"step": 0}), (3.0, 3.5, {"step": 1})],
    "engine.step.wait": [(1.6, 2.2, {"step": 0})],
    "gen.loop.emit": [(2.2, 2.3, {"step": 0})],
    "gen.loop.nowork": [(3.5, 4.5, {})],
}


def test_idle_under_on_synthetic_intervals():
    hs = host_spans.HostSpans(PHASES, BUSY)
    assert hs.window_s == pytest.approx(6.0)
    assert hs.idle_s == pytest.approx(3.0)
    parts = [hs.idle_under(names) for names in SERVE_IDLE]
    assert parts == pytest.approx([0.6 + 0.5, 0.4, 0.0, 1.0])
    # the decomposition: the four parts and what no phase covers (4.5-5.0)
    # are the idle time
    covered = hs.idle_under([n for names in SERVE_IDLE for n in names])
    assert sum(parts) == pytest.approx(covered)
    assert hs.idle_s - covered == pytest.approx(0.5)
    assert hs.durations("engine.step.dispatch") == pytest.approx([0.7, 0.5])
    assert hs.durations("no.such.phase") == []


def test_self_seconds_joins_children_by_step_and_time():
    hs = host_spans.HostSpans(PHASES, BUSY)
    # 1.6 s of iteration less admit 0.1, dispatch 0.7, wait 0.6, emit 0.1;
    # step 1's dispatch is no child of step 0's iteration
    assert hs.self_seconds("gen.loop.iter") == pytest.approx({0: 0.1})
    assert hs.in_window(["engine.step.dispatch"], steps={1}) \
        == pytest.approx(0.5)
    assert hs.steps_of("engine.step.dispatch") == {0, 1}


def test_a_trace_without_phases_answers_nothing():
    """A program from before the phases existed (the parent of the PR that
    added them): every question gives None or nothing, and none raises."""
    hs = host_spans.HostSpans({}, BUSY)
    assert hs.idle_under(["engine.step.dispatch"]) is None
    assert hs.in_window(["trainer.feed"], hs.steps_of("trainer.step")) is None
    assert hs.durations("engine.step.dispatch") == []
    assert hs.self_seconds("gen.loop.iter") == {}


@pytest.mark.parametrize("cell,name", [(c, n) for c, ns in NEW.items()
                                       for n in ns])
def test_reader_gives_none_without_a_device_trace(cell, name):
    phase_reader_entry_holds(harness.Spec(), cell, name)


def phase_reader_entry_holds(spec, cell, name):
    """The entry reads the program's phases and lists the cell it was
    accepted for (later PRs may give it more); the CPU rehearsal, where the
    driver's ``trace`` is None, reads nothing."""
    entry, = [m for m in spec.manifest["per_layer"] if m["name"] == name]
    assert entry["source"] == "program_span" and cell in entry["workloads"]
    read = spec.reader("per_layer", name).read
    assert read({"trace": None, "cell": spec.cell(cell)}) is None


# ------------------------------------------------------ the recorded traces

RECORDED = {"opt1.3b_chat": "chat_phases.xplane.pb.gz",
            "lstm-h512_train": "lstm_phases.xplane.pb.gz"}


def _unpack(cell, tmp_path_factory):
    """(cell, root, reduced): the recorded trace unpacked where a traced run
    of the cell leaves its own, under a root of its own."""
    src = os.path.join(BENCH, "testdata", RECORDED[cell])
    root = tmp_path_factory.mktemp("root")
    out = root / ".bench_trace" / cell / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    path = out / "host.xplane.pb"
    path.write_bytes(gzip.open(src).read())
    reduced = trace_reduce.reduce(trace_reduce.read_xplane(str(path)))
    return cell, str(root), reduced


@pytest.fixture(scope="module")
def chat(tmp_path_factory):
    return _unpack("opt1.3b_chat", tmp_path_factory)


@pytest.fixture(scope="module")
def lstm(tmp_path_factory):
    return _unpack("lstm-h512_train", tmp_path_factory)


@pytest.fixture(params=["chat", "lstm"])
def recorded(request):
    return request.getfixturevalue(request.param)


def _read_all(cell, root, reduced, monkeypatch, capsys):
    monkeypatch.setattr(harness, "ROOT", root)
    spec = harness.Spec()
    obs = {"trace": reduced, "cell": spec.cell(cell)}
    values = {n: spec.reader("per_layer", n).read(obs) for n in NEW[cell]}
    said = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    return values, host_spans.load(obs), said


def test_recorded_trace_every_new_metric_reports(recorded, monkeypatch,
                                                 capsys):
    cell, root, reduced = recorded
    values, hs, said = _read_all(cell, root, reduced, monkeypatch, capsys)
    assert all(v is not None and v >= 0 for v in values.values()), values
    # the same window and the same idle time as trace_reduce's
    assert hs.window_s == pytest.approx(reduced["window_s"])
    assert hs.idle_s == pytest.approx(reduced["window_s"]
                                      - reduced["busy_s"])
    # one detail line, from the one read of the file
    assert len(said) <= 1 and all("host_spans" in l for l in said)


def test_recorded_chat_trace_idle_shares_add_up(chat, monkeypatch, capsys):
    cell, root, reduced = chat
    values, hs, _ = _read_all(cell, root, reduced, monkeypatch, capsys)
    idle_share = 100.0 * hs.idle_s / hs.window_s
    parts = [values[n] for n in NEW[cell] if n.startswith("serve_idle_")]
    assert len(parts) == 4
    # the four phases never overlap, so their shares are a decomposition
    covered = hs.idle_under([n for names in SERVE_IDLE for n in names])
    assert sum(parts) == pytest.approx(100.0 * covered / hs.window_s)
    rest = idle_share - sum(parts)
    assert 0.0 <= rest < 2.0, (idle_share, parts)
    # the engine's two phases hold nearly all of it, and each a real part:
    # the device waits while the step is handed over AND while the host
    # learns that it ended and reads its tokens (PERF.md section 5)
    engine = [values["serve_idle_dispatch_share"],
              values["serve_idle_readback_share"]]
    assert sum(engine) > 0.8 * idle_share
    assert min(engine) > 0.2 * idle_share
    assert 1.0 < values["step_dispatch_ms_p50"] < 10.0
    assert 0.0 < values["sched_host_ms_p50"] < values["step_dispatch_ms_p50"]
    # every dispatch inside the window lies in an iteration of its step
    its = {st["step"]: (s, e) for s, e, st in hs.phases["gen.loop.iter"]}
    inside = [its[st["step"]][0] <= s and e <= its[st["step"]][1]
              for s, e, st in hs.phases["engine.step.dispatch"]
              if st["step"] in its]
    assert inside and all(inside)


def test_recorded_lstm_trace_feed_is_hidden(lstm, monkeypatch, capsys):
    cell, root, reduced = lstm
    values, hs, _ = _read_all(cell, root, reduced, monkeypatch, capsys)
    assert 5.0 < values["feed_host_share"] < 25.0
    assert values["train_idle_feed_share"] < 1.0
    assert hs.steps_of("trainer.step") <= hs.steps_of("trainer.feed")
    assert len(hs.phases["trainer.handler"]) \
        == 2 * len(hs.phases["trainer.step"])

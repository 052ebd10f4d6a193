"""step_overlap_share: how many of the window's device steps were handed
over with the step before still in flight (``in_flight`` on the program's
``engine.step.dispatch`` phase).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import gzip
import os
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.dirname(_HERE)
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmark import harness, host_spans, trace_reduce  # noqa: E402

_NAME = "step_overlap_share"
_CELLS = ["opt1.3b_chat", "kimilinear_reason"]
# the device ran in [0,1], [2,3] and [5,6]: the window is [0, 6)
_BUSY = [[[0.0, 1.0], [2.0, 3.0], [5.0, 6.0]]]


def _read_on(phases, monkeypatch):
    hs = host_spans.HostSpans(phases, _BUSY)
    monkeypatch.setattr(host_spans, "load", lambda obs: hs)
    return harness.Spec().reader("per_layer", _NAME).read({"trace": {}})


def _dispatches(*stats):
    return {"engine.step.dispatch": [(0.5 + i, 0.7 + i, st)
                                     for i, st in enumerate(stats)]}


def test_step_overlap_share_counts_dispatches_with_a_step_in_flight(
        monkeypatch):
    phases = _dispatches({"step": 0, "in_flight": 0},
                         {"step": 1, "in_flight": 1},
                         {"step": 2, "in_flight": 1},
                         {"step": 3, "in_flight": 0})
    # one before the window opens and one after it closes: not counted
    phases["engine.step.dispatch"] += [
        (-0.5, -0.3, {"step": -1, "in_flight": 1}),
        (6.5, 6.7, {"step": 9, "in_flight": 1})]
    assert _read_on(phases, monkeypatch) == pytest.approx(50.0)
    every = _dispatches(*[{"step": i, "in_flight": 1} for i in range(5)])
    assert _read_on(every, monkeypatch) == pytest.approx(100.0)


def test_step_overlap_share_is_zero_for_a_program_without_the_stat(
        monkeypatch):
    """The parent: phases, but every step read before the next."""
    phases = _dispatches({"step": 0}, {"step": 1}, {"step": 2})
    assert _read_on(phases, monkeypatch) == 0.0


@pytest.mark.parametrize("phases", [
    {}, {"gen.loop.nowork": [(0.5, 0.9, {})]},
    _dispatches() | {"gen.loop.iter": [(0.5, 0.9, {"step": 0})]},
], ids=["no_phases", "no_dispatch", "empty_dispatch"])
def test_step_overlap_share_is_none_without_dispatch_phases(phases,
                                                            monkeypatch):
    assert _read_on(phases, monkeypatch) is None


@pytest.mark.parametrize("cell", _CELLS)
def test_step_overlap_share_is_none_without_a_device_trace(cell):
    """The CPU rehearsal: the driver's ``trace`` is None."""
    spec = harness.Spec()
    read = spec.reader("per_layer", _NAME).read
    assert read({"trace": None, "cell": spec.cell(cell)}) is None


def step_overlap_share_entry_holds(spec):
    """The entry, found by NAME wherever later entries have left it, with
    the fields it was accepted with; its cells include those committed, each
    of them reports it, and the training cell does not."""
    entry, = [m for m in spec.manifest["per_layer"] if m["name"] == _NAME]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": _NAME, "unit": "%", "better": "higher",
        "source": "program_span", "layer": "the one jitted step",
        "moves": "itl_p95_ms"}
    assert set(_CELLS) <= set(entry["workloads"])
    for cell in entry["workloads"]:
        assert _NAME in [m["name"] for m in spec.metrics_for(
            spec.cell(cell), "per_layer")]
    assert _NAME not in [m["name"] for m in spec.metrics_for(
        spec.cell("lstm-h512_train"), "per_layer")]


def test_step_overlap_share_manifest_entry():
    step_overlap_share_entry_holds(harness.Spec())


def test_step_overlap_share_reads_zero_from_a_trace_of_the_serial_loop(
        tmp_path, monkeypatch):
    """A trace recorded on the v5e from the loop that read every step
    before the next (testdata/TRACES.md): dispatch phases, none in flight."""
    cell = "opt1.3b_chat"
    out = tmp_path / ".bench_trace" / cell / "plugins" / "profile" / "t"
    out.mkdir(parents=True)
    path = out / "host.xplane.pb"
    path.write_bytes(gzip.open(os.path.join(
        _BENCH, "testdata", "chat_phases.xplane.pb.gz")).read())
    monkeypatch.setattr(harness, "ROOT", str(tmp_path))
    spec = harness.Spec()
    obs = {"trace": trace_reduce.reduce(trace_reduce.read_xplane(str(path))),
           "cell": spec.cell(cell)}
    assert host_spans.load(obs).durations("engine.step.dispatch")
    assert spec.reader("per_layer", _NAME).read(obs) == 0.0

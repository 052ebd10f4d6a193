"""The benchmark's own tests, CPU only: the manifest against the contract's
character sets, every file found by name, a dropped-in cell picked up with
no edit to an existing file, the arithmetic on synthetic timestamps, the
trace reduction, and each driver end to end at a tiny size.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q
"""

import gzip
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import arith, harness, trace_reduce, traffic  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
MANIFESTS = [os.path.join(ROOT, "BENCHMARK.json"),
             os.path.join(BENCH, "testdata", "BENCHMARK.json")]


@pytest.fixture(params=MANIFESTS, ids=["real", "rehearsal"])
def spec(request):
    return harness.Spec(request.param)


def test_manifest_fits_the_contract(spec):
    m = spec.manifest
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmark/")
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    cells = {w["name"] for w in m["workloads"]}
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.1
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert x["moves"] in e2e
        # the metric it moves is reported wherever this one is
        moved = next(e for e in m["end_to_end"] if e["name"] == x["moves"])
        assert set(x.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert set(x.get("workloads", [])) <= cells
    assert sum(w["chips"] == 4 for w in m["workloads"]) <= max(
        1, len(cells) // 4)
    # every cell reports setup_s, another end-to-end metric, a per-layer one
    for w in m["workloads"]:
        assert len(spec.metrics_for(w, "end_to_end")) >= 2
        assert len(spec.metrics_for(w, "per_layer")) >= 1


def test_every_file_is_found_by_name(spec):
    for cell in spec.manifest["workloads"]:
        cfg = spec.config(cell)
        assert spec.traffic(cell)["kind"]
        assert callable(spec.driver(cfg).run)
        for group in ("end_to_end", "per_layer"):
            for m in spec.metrics_for(cell, group):
                assert callable(spec.reader(group, m["name"]).read)


def test_a_dropped_in_cell_needs_no_edit(tmp_path):
    """A made-up cell, traffic file and per-layer metric: three new files and
    one entry each in the manifest, no existing file edited."""
    root = tmp_path / "copy"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    m = json.load(open(MANIFESTS[0]))
    base = m["workloads"][0]
    (root / "benchmark" / "traffic" / "made_up.json").write_text(
        json.dumps({"kind": "train_batches", "batch": 3}))
    (root / "benchmark" / "layer_metrics" / "made_up.metric.py").write_text(
        "def read(obs):\n    return obs['x'] * 2\n")
    m["workloads"].append(dict(base, name="made_up_cell", traffic="made_up"))
    m["per_layer"].append({"name": "made_up.metric", "unit": "count",
                           "better": "higher", "source": "program_counter",
                           "layer": "made up", "moves": "setup_s",
                           "workloads": ["made_up_cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(m))
    spec = harness.Spec(root=str(root))
    cell = spec.cell("made_up_cell")
    assert spec.traffic(cell)["batch"] == 3
    assert spec.config(cell) == spec.config(base)
    names = [x["name"] for x in spec.metrics_for(cell, "per_layer")]
    assert names == ["made_up.metric"]
    assert spec.reader("per_layer", "made_up.metric").read({"x": 21}) == 42
    assert "made_up.metric" not in [
        x["name"] for x in spec.metrics_for(base, "per_layer")]


def test_rate_over_whole_steps_has_no_window_edge():
    # 364 steps of 57 ms: the clock stops at the end of the last step, so a
    # window of 20 s and one of 20.05 s give the same rate
    n, step = 364, 0.057
    rate = arith.whole_step_rate(n, 102400, 10.0, 10.0 + n * step)
    assert rate == pytest.approx(102400 / step)
    assert arith.whole_step_rate(0, 1, 0.0, 1.0) is None


def test_percentiles_and_gaps():
    assert arith.percentile([], 50) is None
    assert arith.percentile([1, 2, 3, 4], 50) == 2.5
    assert arith.percentile(range(101), 95) == 95
    assert arith.token_gaps_ms([1.0, 1.5, 1.75]) == [500.0, 250.0]
    assert arith.token_gaps_ms([1.0]) == []


def test_open_loop_holds_the_same_work_for_every_seed():
    tr = json.load(open(os.path.join(BENCH, "traffic", "chat_open.json")))
    plans = [traffic.open_loop(tr, seed, 30.0, 50272)
             for seed in (1, 1, 2, 3000000001)]
    assert plans[0] == plans[1]                      # same seed, same inputs
    assert plans[0][-1]["prompt"] != plans[2][-1]["prompt"]

    def shape(p):
        return [(r["due"], len(r["prompt"]), r["max_tokens"], r["measured"])
                for r in p]

    # the seed moves no work and no arrival: only the token ids
    assert shape(plans[0]) == shape(plans[2]) == shape(plans[3])
    measured = [r for r in plans[3] if r["measured"]]
    assert len(measured) == round(tr["rate_rps"] * 30.0) == 72
    assert all(0.0 <= r["due"] < 30.0 for r in measured)
    assert sorted(r["due"] for r in measured) == [r["due"] for r in measured]
    # every pair of lengths three times over
    assert sorted([len(r["prompt"]), r["max_tokens"]] for r in measured) \
        == sorted(tr["lengths"] * 3)
    # the lead-in replays the end of the cycle before the window
    lead = [r for r in plans[3] if not r["measured"]]
    tail = [r for r in measured if r["due"] >= 30 - tr["lead_in_s"]]
    assert [(round(r["due"] + 30, 6), len(r["prompt"])) for r in lead] \
        == [(round(r["due"], 6), len(r["prompt"])) for r in tail]
    assert all(64 <= a <= 512 and 64 <= b <= 160 for a, b in tr["lengths"])
    # a longer window holds the same rate: 122 arrivals in 51 s
    assert sum(r["measured"] for r in
               traffic.open_loop(tr, 1, 51.0, 50272)) == 122


def test_train_batches_follow_the_rule():
    tr = json.load(open(os.path.join(BENCH, "testdata", "traffic",
                                     "tiny_batches.json")))
    a = traffic.train_batches(tr, 3000000001, 60)
    b = traffic.train_batches(tr, 3000000001, 60)
    assert len(a) == tr["distinct_batches"] and len(a[0]) == tr["batch"]
    for rows_a, rows_b in zip(a, b):
        for (ids, lab), (ids_b, lab_b) in zip(rows_a, rows_b):
            assert (ids == ids_b).all() and lab == lab_b
            assert ids.dtype.name == "int32" and len(ids) == tr["length"]
            n7 = int((ids == tr["positive_token"]).sum())
            assert n7 == (min(tr["positive_count"], tr["length"]) if lab
                          else 0)


def test_trace_reduction_on_synthetic_events():
    assert trace_reduce.union_seconds([(0, 2), (1, 3), (5, 6)])[0] == 4
    nested = [("%while.1 = (s32[]) while(...)", 0.0, 10.0),
              ("%fusion.2 = f32[8,128]{1,0} fusion(...)", 1.0, 4.0),
              ("%fusion.2 = f32[8,128]{1,0} fusion(...)", 5.0, 9.0),
              ("%copy.3 = f32[4]{0} copy(...)", 12.0, 13.0)]
    st = trace_reduce.self_times(nested)
    assert st["%while.1 = (s32[]) while(...)"] == [1, 10.0, 3.0]
    assert st["%fusion.2 = f32[8,128]{1,0} fusion(...)"] == [2, 7.0, 7.0]
    host = [("outer", 0.0, 20.0), ("inner", 10.0, 12.1), ("far", 30.0, 31.0)]
    red = trace_reduce.reduce(
        {"devices": {"/device:TPU:0": {"ops": nested, "modules": [
            ("jit_step(1)", 0.0, 10.0), ("jit_step(1)", 12.0, 13.0)]}},
         "host": host})
    assert red["busy_s"] == 11.0 and red["window_s"] == 13.0
    assert red["modules"]["jit_step(1)"] == [2, 11.0]
    assert red["breakdown"]["device_ops"][0] == ["fusion.2_f32_8_128_", 7.0]
    assert red["breakdown"]["idle_gaps"] == [["inner", 2.0]]
    assert trace_reduce.ops_seconds(red, r"^%?while") == 10.0
    long_name = "%fusion.9 = (f32[100,1024,512]{2,1,0:T(8,128)}, " * 40
    assert trace_reduce.short_name(long_name) == "fusion.9_f32_100_1024_512_"
    assert re.match(r"^[A-Za-z0-9_.\-]{1,80}$",
                    trace_reduce.short_name("weird name / with, stuff" * 9))


def test_trace_reduction_on_a_recorded_trace(tmp_path):
    """A trace recorded on the v5e (benchmark/testdata/README has its
    origin): busy union, idle share, short names, programs."""
    src = os.path.join(BENCH, "testdata", "lstm_steps.xplane.pb.gz")
    if not os.path.exists(src):
        pytest.skip("no recorded trace in testdata")
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(gzip.open(src).read())
    red = trace_reduce.reduce(trace_reduce.read_xplane(str(path)))
    assert red["devices"] == 1
    assert 0 < red["busy_s"] <= red["window_s"]
    assert len(red["breakdown"]["device_ops"]) == 10
    for name, sec in red["breakdown"]["device_ops"]:
        assert re.match(r"^[A-Za-z0-9_.\-]{1,80}$", name) and sec > 0
    assert any(k.startswith("jit_step") for k in red["modules"])
    share = trace_reduce.ops_seconds(red, r"^%?while[.\d]* = ") / red["busy_s"]
    assert 0.4 < share < 0.7        # the recurrence: four while loops


def _run(*args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("BENCH_RUN", None)
    return subprocess.run([sys.executable, os.path.join(BENCH, "run.py"),
                           *args], cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=300)


@pytest.mark.parametrize("cell,trace,expect", [
    ("tiny_train", 0, {"train_tok_s", "setup_s"}),
    ("tiny_chat", 0, {"ttft_per_token_p50_ms", "itl_p95_ms", "setup_s"}),
    ("tiny_chat", 1, {"decode_step_ms_p50", "decode_kernel_on",
                      "queue_wait_ms_p50"}),
    ("tiny_train", 1, {"feed_wait_share", "train_step_ms_p50",
                       "rnn_kernel_on"}),
])
def test_each_driver_end_to_end_at_a_tiny_size(cell, trace, expect):
    r = _run("--workload", cell, "--seed", "3000000001", "--seconds", "2",
             "--trace", str(trace), "--rehearsal")
    assert r.returncode == harness.RC_REHEARSAL_OK, r.stderr[-2000:]
    lines = [json.loads(ln) for ln in r.stdout.splitlines()
             if ln.startswith("{")]
    assert all(ln.get("rehearsal") is True for ln in lines)
    last = lines[-1]
    # a driver that says what it compared has it last in the line
    assert set(last) - {"compared"} == {"correct", "attempted", "failed",
                                        "metrics", "device", "rehearsal"}
    assert ("compared" in last) == (cell == "tiny_train")
    assert "compared" not in last or list(last)[-1] == "compared"
    assert last["correct"] is True and last["failed"] == 0
    assert last["attempted"] > 0
    assert set(last["metrics"]) == expect
    for m in last["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0 \
            or m["value"] == 0.0
    assert {"platform", "kind", "count", "memory_peak_bytes"} \
        <= set(last["device"])


def test_no_tpu_no_result():
    r = _run("--workload", "lstm-h512_train", "--seed", "1", "--seconds", "1",
             "--trace", "0")
    assert r.returncode == harness.RC_NO_DEVICE
    assert not [ln for ln in r.stdout.splitlines() if "metrics" in ln]
    assert "no TPU" in r.stderr

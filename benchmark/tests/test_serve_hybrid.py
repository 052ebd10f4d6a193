"""The hybrid driver and what came with it, CPU only: ``serve_hybrid.run`` end
to end on the tiny configuration and traffic of ``testdata/``, the bytes of
``costs_hybrid``, the five new readers on a trace recorded on the chip, and
the configuration file against the contract's rule for a cut.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_serve_hybrid.py -q
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

from benchmark import costs_hybrid, harness, peaks, trace_reduce  # noqa: E402

CELL = "kimilinear_reason"
HYBRID_READERS = ["kda_kernel_share", "kda_kernel_roofline", "kda_kernel_on",
                  "moe_expert_share", "moe_expert_roofline"]


def _testdata(*parts):
    with open(os.path.join(BENCH, "testdata", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def real_config():
    spec = harness.Spec()
    return spec.config(spec.cell(CELL))


@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_hybrid_driver_runs_tiny_cell(trace, tmp_path):
    """The driver's own ``run`` (no manifest names the tiny cell: the
    rehearsal's is not this PR's to edit) through engine, batcher and HTTP
    front, held to the reference, with the observation keys the existing
    readers use."""
    from benchmark.drivers import serve_hybrid
    obs = serve_hybrid.run({
        "cell": {"name": "tiny_reason", "chips": 1},
        "config": _testdata("configs", "tiny-hybrid.json"),
        "traffic": _testdata("traffic", "tiny_reason_open.json"),
        "seed": 3000000123, "seconds": 1.0, "trace": trace,
        "rehearsal": True, "phases": harness.Phases(),
        "trace_dir": str(tmp_path / "trace")})
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] >= 4
    assert obs["kda_kernels"] is False          # the CPU takes the scan
    after, before = obs["counters_after"], obs["counters_before"]
    assert after["state_resets_total"] > before["state_resets_total"]
    assert after["active_slot_steps_total"] > before[
        "active_slot_steps_total"]
    assert obs["weight_bytes"] > 0 and obs["tpot_s"]
    spec = harness.Spec()
    obs.update(cell={"name": CELL}, config=_testdata(
        "configs", "tiny-hybrid.json"), peaks=None)
    for name in ("ttft_per_token_p50_ms", "itl_p95_ms", "setup_s"):
        assert spec.reader("end_to_end", name).read(obs) > 0
    assert spec.reader("per_layer", "decode_step_ms_p50").read(obs) > 0
    assert spec.reader("per_layer", "kda_kernel_on").read(obs) == 0.0
    if trace:
        assert spec.reader("per_layer", "queue_wait_ms_p50").read(obs) \
            is not None
    # no device plane on the CPU: the trace-fed readers say nothing
    for name in HYBRID_READERS:
        if name != "kda_kernel_on":
            assert spec.reader("per_layer", name).read(obs) is None


def test_hybrid_readers_say_nothing_without_the_program():
    """On the parent the program has no such kernel and the observation no
    such key: every new reader returns None and does not raise."""
    spec = harness.Spec()
    for obs in ({}, {"trace": {"ops": {}, "devices": 1, "busy_s": 1.0},
                     "peaks": peaks.PEAKS["TPU v5 lite"]}):
        for name in HYBRID_READERS:
            assert spec.reader("per_layer", name).read(dict(obs)) is None


@pytest.fixture(scope="module")
def kimi_recorded(tmp_path_factory, real_config):
    """The cell's recorded second on the v5e (testdata/kimi_phases.md), reduced,
    with the window's counters as that run printed them."""
    src = os.path.join(BENCH, "testdata", "kimi_phases.xplane.pb.gz")
    path = tmp_path_factory.mktemp("kimi") / "t.xplane.pb"
    path.write_bytes(gzip.open(src).read())
    reduced = trace_reduce.reduce(trace_reduce.read_xplane(str(path)))
    window = _testdata("kimi_phases.counters.json")
    zero = {k: 0 for k in window}
    return {"trace": reduced, "kda_kernels": True, "config": real_config,
            "peaks": peaks.PEAKS["TPU v5 lite"],
            "counters_before": zero, "counters_after": window}


@pytest.mark.parametrize("name", HYBRID_READERS)
def test_hybrid_reader_on_recorded_trace(kimi_recorded, name):
    value = harness.Spec().reader("per_layer", name).read(dict(kimi_recorded))
    assert value is not None
    if name == "kda_kernel_on":
        assert value == 1.0
    else:
        assert 0.0 < value < 100.0, (name, value)


def test_recorded_trace_names_both_kernels(kimi_recorded):
    ops = kimi_recorded["trace"]["ops"]
    assert any("kda_chunk" in k for k in ops)
    assert any("ragged-dot" in k for k in ops)
    share = {n: harness.Spec().reader("per_layer", n).read(dict(kimi_recorded))
             for n in ("kda_kernel_share", "moe_expert_share")}
    assert share["moe_expert_share"] > share["kda_kernel_share"] > 0


def test_hybrid_costs_from_the_shapes(real_config):
    cfg = real_config
    assert costs_hybrid.kda_state_bytes_per_slot_layer(cfg) == 32 * 128 * 128 * 4
    assert costs_hybrid.held_expert_bytes_per_layer(cfg) \
        == 64 * 3 * 2304 * 1024 * 2
    # six KDA layers; a seated slot's state in and out, six operand rows
    # of 128 floats a head and lane
    assert costs_hybrid.kda_kernel_bytes(cfg, 1, 0) == 6 * 2 * 32 * 128 * 128 * 4
    assert costs_hybrid.kda_kernel_bytes(cfg, 0, 1) == 6 * 32 * 6 * 128 * 4
    assert costs_hybrid.experts_touched_share(cfg, 0) == 0.0
    assert 0.6 < costs_hybrid.experts_touched_share(cfg, 32) < 0.65
    assert costs_hybrid.experts_touched_share(cfg, 512) > 0.999
    assert costs_hybrid.moe_expert_bytes(cfg, 0, 0) == 0.0
    seven = 7 * costs_hybrid.held_expert_bytes_per_layer(cfg)
    assert costs_hybrid.moe_expert_bytes(cfg, 2, 1024) \
        == pytest.approx(2 * seven, rel=1e-3)


def test_hybrid_step_stream_bytes_leave_the_table_out():
    import numpy as np
    p = {"emb": np.zeros((10, 4), np.float32),
         "head": np.zeros((4, 10), np.float32),
         "layers": [{"w": np.zeros((4, 4), np.float16)}]}
    assert costs_hybrid.step_stream_bytes(p) == 160 + 32


def test_hybrid_configuration_states_its_cut(real_config):
    """Every number of the published row under its own key, but the two
    the manifest lists as reduced; the published counts, the deployment and
    the assumed sizes beside them."""
    cfg = real_config
    manifest = harness.Spec().manifest
    (entry,) = [c for c in manifest["configs"]
                if c["name"] == "kimi-linear-48b-ep4-8l"]
    assert entry["reduced"] == cfg["reduced"] == ["num_experts",
                                                  "num_hidden_layers"]
    assert cfg["published"] == {"num_experts": 256, "num_hidden_layers": 27}
    assert cfg["num_experts"] * cfg["expert_parallel"]["chips"] \
        == cfg["expert_parallel"]["num_experts_published"] == 256
    published = {
        "hidden_size": 2304, "intermediate_size": 9216,
        "moe_intermediate_size": 1024, "kv_lora_rank": 512,
        "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "v_head_dim": 128,
        "num_experts_per_token": 8, "vocab_size": 163840,
        "num_attention_heads": 32, "routed_scaling_factor": 2.446,
        "first_k_dense_replace": 1, "num_shared_experts": 1}
    assert {k: cfg[k] for k in published} == published
    la = cfg["linear_attn_config"]
    assert (la["num_heads"], la["head_dim"],
            la["short_conv_kernel_size"]) == (32, 128, 4)
    # layers 1-8: two whole periods, the dense layer once
    from benchmark.reference import kimi_linear
    kinds = kimi_linear.layer_kinds(cfg)
    assert [a for a, _f in kinds] == ["kda", "kda", "kda", "mla"] * 2
    assert [f for _a, f in kinds] == ["dense"] + ["moe"] * 7
    assert cfg["assumed"]["kda_gate_rank"] == 128 and cfg["departures"]
    assert cfg["serving"]["prefix_cache"] is False

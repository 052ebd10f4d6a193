"""The fourth hybrid driver and what came with it, CPU only: ``serve_laguna.
run`` end to end on the tiny configuration and traffic of ``testdata/``
(the rehearsal of ``laguna_repoctx``: every listed metric a number or
``None``), the check holding each wrong program NOT correct,
``costs_laguna`` against hand-counted numbers, the four new readers on
recorded observations and on a program without them, and the configuration
and traffic files against the contract.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_serve_laguna.py -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import costs_laguna, harness, peaks  # noqa: E402

CELL = "laguna_repoctx"
READERS = ["window_attn_share", "window_attn_roofline",
           "global_attn_roofline", "window_kernel_on"]
V5E = peaks.PEAKS["TPU v5 lite"]


def _testdata(*parts):
    with open(os.path.join(BENCH, "testdata", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def laguna_config():
    spec = harness.Spec()
    return spec.config(spec.cell(CELL))


@pytest.fixture(scope="module")
def laguna_traffic():
    spec = harness.Spec()
    return spec.traffic(spec.cell(CELL))


@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_laguna_driver_runs_tiny_cell(trace, tmp_path):
    """The driver's own ``run`` through engine, batcher and HTTP front, held
    to ``reference/laguna.py``; then every metric the manifest lists for the
    cell is read from what it observed: a number, or ``None`` where the CPU
    has no device plane."""
    from benchmark.drivers import serve_laguna
    cfg = _testdata("configs", "tiny-laguna.json")
    obs = serve_laguna.run({
        "cell": {"name": "tiny_repoctx", "chips": 1}, "config": cfg,
        "traffic": _testdata("traffic", "tiny_repoctx_open.json"),
        "seed": 3900000131, "seconds": 1.0, "trace": trace,
        "rehearsal": True, "phases": harness.Phases(),
        "trace_dir": str(tmp_path / "trace")})
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] >= 4
    assert obs["window_kernels"] is False and obs["attn_kernels"] is False
    after, before = obs["counters_after"], obs["counters_before"]
    moved = {k: after[k] - before[k] for k in after}
    # a window layer's lanes attend at most the window each
    assert 0 < moved["window_attended_positions_total"] \
        < moved["attended_positions_total"]
    assert 0 < moved["window_read_positions_total"] \
        <= moved["read_positions_total"]
    spec = harness.Spec()
    cell = spec.cell(CELL)
    obs.update(cell=cell, config=cfg, peaks=None)
    listed = {g: [m["name"] for m in spec.metrics_for(cell, g)]
              for g in ("end_to_end", "per_layer")}
    assert listed["end_to_end"] == ["ttft_per_token_p50_ms", "itl_p95_ms",
                                    "setup_s"]
    for name in listed["end_to_end"]:
        assert spec.reader("end_to_end", name).read(obs) > 0
    values = {name: spec.reader("per_layer", name).read(obs)
              for name in listed["per_layer"]}
    assert set(READERS) <= set(values) and len(values) == 18
    assert values["decode_step_ms_p50"] > 0
    assert values["window_kernel_on"] == 0.0
    for name in ("window_attn_share", "window_attn_roofline",
                 "global_attn_roofline", "paged_attn_share",
                 "moe_expert_share", "serve_device_idle_share"):
        assert values[name] is None
    for name in ("queue_wait_ms_p50", "prefill_ms_per_token_p50",
                 "first_token_front_ms_p50"):
        assert (values[name] is not None) == trace, name


@pytest.mark.parametrize("how,fails", [
    (None, set()),
    ("int8", {"logits_match_reference"}),
    ("nowindow", {"logits_match_reference"}),
    ("noyarn", {"logits_match_reference"}),
    ("nogate", {"logits_match_reference"})])
def test_laguna_check_reads_the_servers_own_step(how, fails):
    """Set-up's check has no program of its own: the requests go through
    the server, whose engine traced the (wrong) program while it was built,
    and the logits and the expert choice are the engine's.  Each wrong
    program comes out NOT correct, the sound one correct."""
    from benchmark.drivers import serve_jamba, serve_laguna
    cfg = _testdata("configs", "tiny-laguna.json")
    tr = _testdata("traffic", "tiny_repoctx_open.json")
    params = serve_laguna.make_params(cfg, 7)
    reqs = serve_jamba.check_requests(cfg, tr, 7)
    longest = max(len(r["prompt"]) + r["max_tokens"] for r in reqs)
    server = serve_laguna.degraded_server(cfg, params, how, longest)
    try:
        assert server.engine.report_logits
        assert serve_laguna.serve_recorded(server, reqs, 60)
    finally:
        server.close()
    assert [[len(r["prompt"]), len(r["rows"])] for r in reqs] \
        == [[13, 5], [21, 5], [37, 6]]
    # every fed position's choice: the prompt and all but the last token
    assert [len(r["routes"]) for r in reqs] == [17, 25, 42]
    if how == "int8":       # rounded in place
        params = serve_laguna.make_params(cfg, 7)
    checks, facts = serve_laguna.check_against_reference(params, cfg, reqs)
    failed = {k for k, ok in checks.items() if not ok}
    assert fails <= failed and (how or not failed)
    assert facts["compared_rows"] == 16


def test_laguna_readers_say_nothing_without_the_program():
    """On the parent the program has no window layer and the observation no
    such key: every new reader returns None and does not raise."""
    spec = harness.Spec()
    for obs in ({}, {"trace": {"ops": {}, "devices": 1, "busy_s": 1.0},
                     "peaks": V5E},
                {"trace": {"ops": {"%fusion.1 = fusion()": [3, 1.0, 1.0]},
                           "devices": 1, "busy_s": 1.0},
                 "attn_kernels": True, "peaks": V5E,
                 "counters_before": {}, "counters_after": {}}):
        for name in READERS:
            assert spec.reader("per_layer", name).read(dict(obs)) is None


@pytest.fixture(scope="module")
def laguna_recorded(laguna_config):
    """A reduced trace as ``trace_reduce.reduce`` shapes one, with the
    kernels under the names the chip's trace gives them, and a window's
    counters."""
    ops = {"%decode_attn_window_chunk.4 = custom-call(...)": [6000, 0.6, 0.6],
           "%decode_attn_window_chunk.5 = custom-call(...)": [6000, 0.4, 0.4],
           "%decode_attn_paged_chunk.2 = custom-call(...)": [2000, 0.5, 0.5],
           "%fusion.7 = fusion(...)": [9000, 8.5, 8.5]}
    window = {"attended_positions_total": 10 ** 9,
              "window_attended_positions_total": 4 * 10 ** 7,
              "read_positions_total": 10 ** 8,
              "window_read_positions_total": 6 * 10 ** 6,
              "active_slot_steps_total": 8_000,
              "prefill_chunk_lanes_total": 92_000}
    return {"trace": {"ops": ops, "devices": 1, "busy_s": 10.0,
                      "window_s": 10.1},
            "window_kernels": True, "attn_kernels": True,
            "config": laguna_config, "peaks": V5E,
            "counters_before": {k: 0 for k in window},
            "counters_after": window}


def _least(flops, nbytes):
    return max(flops / V5E["bf16_flops"], nbytes / V5E["hbm_bytes_per_s"])


@pytest.mark.parametrize("name,want", [
    ("window_kernel_on", 1.0), ("window_attn_share", 10.0),
    # six window layers: 4e7 x 72 heads x 128 x 4 = 1.47e12 operations
    # (7.48 ms); 6e6 positions x 4 KB + 1e5 lanes x 72 x 128 x 4 B =
    # 27.4 GB (33.5 ms): the bytes bound it, over the kernel's 1.0 s
    ("window_attn_roofline", 100 * _least(
        6 * 4e7 * 72 * 128 * 4, 6 * (6e6 * 4096 + 1e5 * 72 * 128 * 4))),
    # two full layers: 1e9 x 48 x 128 x 4 = 4.9e13 operations (0.249 s);
    # 1e8 positions x 4 KB + lanes: 0.82 TB (1.0 s), over 0.5 s
    ("global_attn_roofline", 100 * _least(
        2 * 1e9 * 48 * 128 * 4, 2 * (1e8 * 4096 + 1e5 * 48 * 128 * 4)) / 0.5)])
def test_laguna_reader_on_recorded_observations(laguna_recorded, name, want):
    value = harness.Spec().reader("per_layer", name).read(
        dict(laguna_recorded))
    assert value == pytest.approx(want, rel=1e-6)


def test_laguna_costs_from_the_shapes(laguna_config):
    cfg = laguna_config
    assert (costs_laguna.layers(cfg, "full"),
            costs_laguna.layers(cfg, "window")) == (2, 6)
    assert (costs_laguna.heads(cfg, "full"),
            costs_laguna.heads(cfg, "window")) == (48, 72)
    # one attended position: 72 heads x (128 + 128) x 2 operations a layer
    assert costs_laguna.attn_flops(cfg, "window", 1) == 6 * 72 * 128 * 4
    # one position read: K and V of 8 heads of 128 in bfloat16, a layer
    assert costs_laguna.attn_bytes(cfg, "full", 1, 0) == 2 * 4096


def test_laguna_configuration_keeps_the_published_numbers(laguna_config):
    """Every number of the catalog's row under its own key but the three
    ``reduced`` names, which ``published`` gives; the assumptions,
    departures and deployment stated; the parameter count the file
    gives."""
    cfg = laguna_config
    (entry,) = [c for c in harness.Spec().manifest["configs"]
                if c["name"] == "laguna-s-2.1-ep8-8l"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_experts": 256, "num_hidden_layers": 48,
                                "vocab_size": 100352}
    assert entry["source"] == cfg["source"] and "Laguna-S-2.1" \
        in cfg["source"]
    published = {
        "hidden_size": 3072, "intermediate_size": 12288,
        "num_attention_heads": 48, "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 1048576, "rms_norm_eps": 1e-06,
        "num_experts_per_tok": 10, "moe_intermediate_size": 1024,
        "shared_expert_intermediate_size": 1024, "sliding_window": 512,
        "moe_routed_scaling_factor": 2.5, "norm_topk_prob": True,
        "tie_word_embeddings": False, "gating": "per-head"}
    assert {k: cfg[k] for k in published} == published
    assert len(cfg["layer_types"]) == len(cfg["mlp_layer_types"]) == 48
    # the cut: 32 of 256 experts, an eighth of the vocabulary, two periods
    assert (cfg["num_experts"], cfg["vocab_size"],
            cfg["num_hidden_layers"]) == (32, 100352 // 8, 8)
    assert cfg["expert_parallel"] == {"chips": 8, "rank": 0,
                                      "num_experts_published": 256}
    for key in ("gate", "router", "shared_expert", "qk_norm", "window",
                "yarn"):
        assert cfg["assumed"][key]
    assert len(cfg["departures"]) >= 4 and cfg["param_dtype"] == "bfloat16"
    s = cfg["serving"]
    assert (s["slots"], s["max_len"], s["kv_block_size"], s["prefill_chunk"],
            s["prefix_cache"], s["kv_dtype"], s["report_logits"]) \
        == (16, 32768, 32, 64, False, "bfloat16", True)
    assert s["prefill_chunk_budget"] == 3 * (s["prefill_chunk"] - 1)
    assert s["prefill_chunk_budget"] + s["slots"] <= 256
    # the parameters: attention with per-layer heads, 32 experts held
    d, dh, kv = 3072, 128, 8
    attn = {h: d * (h + 2 * kv) * dh + h * dh * d + d * h for h in (48, 72)}
    expert = 3 * d * 1024
    moe_layer = 32 * expert + expert + d * 256
    total = attn[48] + 3 * d * 12288 + attn[48] + moe_layer \
        + 6 * (attn[72] + moe_layer) + 8 * 2 * d + d + 2 * 12544 * d
    assert attn[48] == pytest.approx(44.19e6, rel=1e-3)
    assert attn[72] == pytest.approx(63.14e6, rel=1e-3)
    assert total == 2_843_053_056
    assert "2,843,053,056" in cfg["parameters"]
    # each limit between the sound program's largest reading and the
    # nearest wrong program's, with room on both sides
    rc = cfg["reference_check"]
    u = 2.0 ** -9 * (2 * (rc["matmul_stages_per_layer"] * 8 + 1)) ** 0.5
    logit, router = rc["logit_readings"], rc["router_readings"]
    assert logit["limit"] == pytest.approx(rc["sigmas"] * u, rel=1e-3)
    assert 3 * logit["program_max"] <= logit["limit"] \
        <= logit["int8_weights"] / 1.7
    assert min(logit[k] for k in ("window_removed", "gate_dropped",
                                  "yarn_attention_factor_dropped")) \
        > 5 * logit["limit"]
    assert router["limit"] == logit["limit"]


def test_repoctx_traffic_holds_its_lengths_and_rule(laguna_traffic):
    tr = laguna_traffic
    assert tr["kind"] == "open_loop" and len(tr["lengths"]) == 24
    prompts = [p for p, _o in tr["lengths"]]
    outs = [o for _p, o in tr["lengths"]]
    assert min(prompts) == 8192 and max(prompts) == 30720
    assert prompts == sorted(prompts) and not any(p % 64 for p in prompts)
    assert sum(prompts) / 24 == pytest.approx(17149, rel=0.01)
    # log-uniform, so denser at the short end: the median under the mean
    assert prompts[12] < sum(prompts) / 24
    assert sorted(set(outs)) == [256, 384, 512, 640, 768, 896, 1024]
    assert sum(outs) / 24 == 640
    assert max(p + o for p, o in tr["lengths"]) <= 32768
    assert tr["rate_rps"] == pytest.approx(0.8 * tr["knee_rps"])
    # the lead-in holds two request lives: a mean request's prefill at the
    # budget's 189 lanes and its decoding, a 25 ms step each (the sweep's)
    life = (17149 / 189 + 640) * 0.025
    assert tr["lead_in_s"] >= 2 * life
    apart = {row["rate_rps"]: row["ttft_per_token_p50_ms_last_third"]
             / row["ttft_per_token_p50_ms_first_third"] - 1
             for row in tr["sweep"]}
    assert tr["knee_rps"] in apart
    assert all(row["failed"] == 0 for row in tr["sweep"])
    # the knee: the last rate whose whole-run median stays within a quarter
    # of the least loaded rate's; the next one's is twice it and more
    p50 = {row["rate_rps"]: row["ttft_per_token_p50_ms"]
           for row in tr["sweep"]}
    rates = sorted(p50)
    assert p50[tr["knee_rps"]] <= 1.25 * p50[rates[0]]
    assert p50[rates[rates.index(tr["knee_rps"]) + 1]] \
        >= 2 * p50[tr["knee_rps"]]
    assert round(tr["rate_rps"] * 51) == 18
    assert tr["warm_requests"] == [[8192, 8]]

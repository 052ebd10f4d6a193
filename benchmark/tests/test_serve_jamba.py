"""The third hybrid driver and what came with it, CPU only: ``serve_jamba.run``
end to end on the tiny configuration and traffic of ``testdata/`` (the
rehearsal of ``jamba3b_longctx``: every listed metric a number or ``None``),
``costs_jamba`` against hand-counted numbers, the four new readers on
recorded observations and on a program without them, and the configuration
and traffic files against the contract.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_serve_jamba.py -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import costs_jamba, harness, peaks  # noqa: E402

JAMBA_CELL = "jamba3b_longctx"
JAMBA_READERS = ["mamba_scan_share", "mamba_scan_roofline", "mamba_kernel_on",
                 "paged_attn_share"]
V5E = peaks.PEAKS["TPU v5 lite"]


def _testdata(*parts):
    with open(os.path.join(BENCH, "testdata", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def jamba_config():
    spec = harness.Spec()
    return spec.config(spec.cell(JAMBA_CELL))


@pytest.fixture(scope="module")
def jamba_traffic():
    spec = harness.Spec()
    return spec.traffic(spec.cell(JAMBA_CELL))


@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_jamba_driver_runs_tiny_cell(trace, tmp_path):
    """The driver's own ``run`` through engine, batcher and HTTP front, held
    to ``reference/jamba.py``; then every metric the manifest lists for the
    cell is read from what it observed: a number, or ``None`` where the CPU
    has no device plane."""
    from benchmark.drivers import serve_jamba
    cfg = _testdata("configs", "tiny-jamba.json")
    obs = serve_jamba.run({
        "cell": {"name": "tiny_longctx", "chips": 1}, "config": cfg,
        "traffic": _testdata("traffic", "tiny_longctx_open.json"),
        "seed": 3500000131, "seconds": 1.0, "trace": trace,
        "rehearsal": True, "phases": harness.Phases(),
        "trace_dir": str(tmp_path / "trace")})
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] >= 4
    assert obs["mamba_kernels"] is False        # the CPU scans in XLA
    assert obs["attn_kernels"] is False
    after, before = obs["counters_after"], obs["counters_before"]
    moved = {k: after[k] - before[k] for k in after}
    assert moved["attended_positions_total"] > moved[
        "prefill_chunk_lanes_total"] > 0
    assert moved["state_resets_total"] >= obs["attempted"]
    assert obs["weight_bytes"] > 0 and obs["tpot_s"]
    spec = harness.Spec()
    cell = spec.cell(JAMBA_CELL)
    obs.update(cell=cell, config=cfg, peaks=None)
    listed = {g: [m["name"] for m in spec.metrics_for(cell, g)]
              for g in ("end_to_end", "per_layer")}
    assert listed["end_to_end"] == ["ttft_per_token_p50_ms", "itl_p95_ms",
                                    "setup_s"]
    for name in listed["end_to_end"]:
        assert spec.reader("end_to_end", name).read(obs) > 0
    values = {name: spec.reader("per_layer", name).read(obs)
              for name in listed["per_layer"]}
    assert set(JAMBA_READERS) <= set(values) and len(values) == 10
    assert values["decode_step_ms_p50"] > 0
    assert values["mamba_kernel_on"] == 0.0
    # no device plane on the CPU: the trace-fed readers say nothing
    for name in ("mamba_scan_share", "mamba_scan_roofline",
                 "paged_attn_share", "serve_device_idle_share",
                 "weight_stream_roofline"):
        assert values[name] is None
    if trace:
        assert values["queue_wait_ms_p50"] is not None


@pytest.mark.parametrize("how,fails", [
    (None, set()),
    # the state's own precision: the check that reads it back sees it
    ("bf16state", {"state_matches_reference"}),
    ("int8", {"logits_match_reference", "state_matches_reference"}),
    ("norms", {"logits_match_reference", "state_matches_reference"}),
    ("d", {"logits_match_reference", "state_matches_reference"})])
def test_jamba_check_reads_the_servers_own_step(how, fails):
    """Set-up's check has no program of its own: the requests go through
    the server, whose engine traced the (wrong) program while it was built,
    and the logits and the state are the engine's.  Each wrong program
    comes out NOT correct by the limits it must, the sound one correct."""
    from benchmark.drivers import serve_jamba
    cfg = _testdata("configs", "tiny-jamba.json")
    tr = _testdata("traffic", "tiny_longctx_open.json")
    params = serve_jamba.make_params(cfg, 7)
    server = serve_jamba.degraded_server(cfg, params, how)
    reqs = serve_jamba.check_requests(cfg, tr, 7)
    try:
        assert server.engine.report_logits
        assert serve_jamba.serve_recorded(server, reqs, 60)
    finally:
        server.close()
    sizes = [[len(r["prompt"]), len(r["rows"])] for r in reqs]
    assert sizes == [[21, 5], [45, 5]] + tr["warm_requests"]
    assert [r["absorbed"] for r in reqs] == [sum(sz) - 1 for sz in sizes]
    if how == "int8":       # rounded in place
        params = serve_jamba.make_params(cfg, 7)
    checks, facts = serve_jamba.check_against_reference(params, cfg, reqs)
    # (in float32 on the CPU the logits' limit is 1e-3 x std and sees the
    # rounded state too; on the chip, under bfloat16 products, it does not)
    failed = {k for k, ok in checks.items() if not ok}
    assert fails <= failed and (how or not failed)
    assert facts["compared_rows"] == 28
    assert len(facts["state_rel_err_by_layer"]) == 3
    if how == "bf16state":
        # a float32 state reads 1e-6 here; rounded to 8 bits of mantissa a
        # position it reads thousands of times that
        assert 1e-3 < facts["state_rel_err"] < 0.1
    if how == "d":
        # the skip feeds nothing into its own layer's state: the first
        # Mamba layer's is still the reference's
        assert facts["state_rel_err_by_layer"][0] < 1e-5


def test_jamba_readers_say_nothing_without_the_program():
    """On the parent the program has no such kernel and the observation no
    such key: every new reader returns None and does not raise."""
    spec = harness.Spec()
    for obs in ({}, {"trace": {"ops": {}, "devices": 1, "busy_s": 1.0},
                     "peaks": V5E},
                {"trace": {"ops": {"%fusion.1 = fusion()": [3, 1.0, 1.0]},
                           "devices": 1, "busy_s": 1.0},
                 "mamba_kernels": False, "attn_kernels": False,
                 "peaks": V5E}):
        for name in JAMBA_READERS:
            value = spec.reader("per_layer", name).read(dict(obs))
            assert value is None or (name == "mamba_kernel_on"
                                     and value == 0.0)


@pytest.fixture(scope="module")
def jamba_recorded(jamba_config):
    """A reduced trace as ``trace_reduce.reduce`` shapes one ({HLO text:
    [count, seconds, self seconds]}), with the kernels under the names the
    chip's trace gives them, and a window's counters."""
    ops = {"%mamba_chunk.5 = custom-call(...)": [26000, 2.5, 2.5],
           "%decode_attn_paged_chunk.2 = custom-call(...)": [1000, 0.3, 0.3],
           "%decode_attn_paged_chunk.3 = custom-call(...)": [1000, 0.2, 0.2],
           "%fusion.7 = fusion(...)": [9000, 7.0, 7.0]}
    window = {"active_slot_steps_total": 5_000,
              "prefill_chunk_lanes_total": 95_000}
    return {"trace": {"ops": ops, "devices": 1, "busy_s": 10.0,
                      "window_s": 10.2},
            "mamba_kernels": True, "attn_kernels": True,
            "config": jamba_config, "peaks": V5E,
            "counters_before": {k: 0 for k in window},
            "counters_after": window}


@pytest.mark.parametrize("name,want", [
    ("mamba_kernel_on", 1.0), ("mamba_scan_share", 25.0),
    ("paged_attn_share", 5.0),
    # 26 layers x (2 x 5,000 slot-steps x 327,680 B + 100,000 lanes x
    # 61,568 B) = 245.3 GB / 819 GB/s = 0.2995 s of the kernel's 2.5
    ("mamba_scan_roofline", 11.98)])
def test_jamba_reader_on_recorded_observations(jamba_recorded, name, want):
    value = harness.Spec().reader("per_layer", name).read(
        dict(jamba_recorded))
    assert value == pytest.approx(want, rel=1e-3)


def test_jamba_costs_from_the_shapes(jamba_config):
    cfg = jamba_config
    assert costs_jamba.mamba_layers(cfg) == 26
    assert costs_jamba.d_inner(cfg) == 5120
    # a slot's state in one layer: 16 x 5120 float32; all it owns: 26 x
    # (state + 3 rows of the convolution's tail)
    assert costs_jamba.mamba_state_bytes_per_slot_layer(cfg) == 327_680
    assert costs_jamba.slot_state_bytes(cfg) == 26 * (327_680 + 61_440) \
        == 10_117_120
    # one seated slot, one step: its state in and out, a layer
    assert costs_jamba.mamba_kernel_bytes(cfg, 1, 0) == 26 * 2 * 327_680
    # one live lane: u, dt in and y out (5120 each), B and C (16 each)
    assert costs_jamba.mamba_kernel_bytes(cfg, 0, 1) \
        == 26 * (3 * 5120 + 32) * 4
    assert costs_jamba.mamba_kernel_exps(cfg, 1) == 26 * 81_920


def test_jamba_configuration_cuts_nothing(jamba_config):
    """Every number of the catalog's row under its own key, ``reduced``
    empty here and in the manifest, the assumptions and departures stated,
    and the parameter bytes the file gives."""
    cfg = jamba_config
    (entry,) = [c for c in harness.Spec().manifest["configs"]
                if c["name"] == "jamba2-3b"]
    assert entry["reduced"] == cfg["reduced"] == []
    assert entry["source"] == cfg["source"] and "AI21-Jamba2-3B" \
        in cfg["source"]
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
        "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    assert {k: cfg[k] for k in published} == published
    from benchmark.reference import jamba
    kinds = jamba.layer_kinds(cfg)
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [7, 21]
    for key in ("layer_order", "layer_order_why", "ffn", "head_dim",
                "mamba_start", "mamba_norms", "unused_keys"):
        assert cfg["assumed"][key]
    assert len(cfg["departures"]) >= 4 and cfg["param_dtype"] == "bfloat16"
    s = cfg["serving"]
    assert (s["slots"], s["max_len"], s["prefill_chunk"], s["prefix_cache"],
            s["kv_dtype"]) == (16, 32768, 64, False, "bfloat16")
    # three whole chunks of prompt a step and every other row's one lane
    # stay within the quarter width
    assert s["prefill_chunk_budget"] == 3 * (s["prefill_chunk"] - 1)
    assert s["prefill_chunk_budget"] + s["slots"] <= 256
    # the check reads the serving step's own logits
    assert s["report_logits"] is True
    # the block table a slot: scalar prefetch of the attention kernel
    assert s["slots"] * s["max_len"] // s["kv_block_size"] * 4 <= 64 * 1024
    rc = cfg["reference_check"]
    short, long = rc["prompt_lengths"]
    assert short % s["kv_block_size"] and short % s["prefill_chunk"]
    assert long > 1024 and rc["decode_steps"] == 4
    # each limit lies between the two readings the file gives, with room:
    # the logits' between the program and int8 weights (a bfloat16 state
    # passes it), the state's between the program and a bfloat16 state
    u = 2.0 ** -9 * (2 * (rc["matmul_stages_per_layer"] * 28 + 1)) ** 0.5
    logit, state = rc["logit_readings"], rc["state_readings"]
    assert logit["limit"] == pytest.approx(rc["sigmas"] * u, rel=1e-3)
    assert state["limit"] == pytest.approx(rc["state_sigmas"] * u, rel=1e-3)
    assert 2 * logit["program_max"] <= logit["limit"] \
        <= logit["int8_weights"] / 2
    assert logit["bf16_state"] < logit["limit"]
    assert 2 * state["program_max"] <= state["limit"] \
        <= state["bf16_state"] / 2
    # the arithmetic: nothing cut
    d, di, n, r = 2560, 5120, 16, 160
    mamba = d * 2 * di + di * (r + 2 * n) + r * di + di * d + n * di \
        + 4 * di + 3 * di + r + 2 * n
    attn = d * (20 + 2) * 128 + 20 * 128 * d
    ffn = 3 * d * 8192
    total = 26 * (mamba + ffn) + 2 * (attn + ffn) + 28 * 2 * d + d \
        + 65536 * d
    assert mamba == pytest.approx(41.24e6, rel=1e-3)
    assert attn == pytest.approx(13.76e6, rel=1e-3)
    assert total == 3_029_337_472
    assert 2 * total == pytest.approx(6.06e9, rel=2e-3)
    pools = s["slots"] * s["max_len"] * 2 * 2 * 128 * 2
    assert pools == pytest.approx(0.54e9, rel=1e-2)


def test_longctx_traffic_holds_its_lengths_and_rule(jamba_traffic):
    tr = jamba_traffic
    assert tr["kind"] == "open_loop" and len(tr["lengths"]) == 24
    prompts = [p for p, _o in tr["lengths"]]
    outs = [o for _p, o in tr["lengths"]]
    assert min(prompts) == 4096 and max(prompts) == 16384
    assert prompts == sorted(prompts) and not any(p % 64 for p in prompts)
    assert sum(prompts) / 24 == pytest.approx(8900, rel=0.01)
    # geometric, so denser at the short end: the median lies under the mean
    assert prompts[12] < sum(prompts) / 24
    assert sorted(set(outs)) == [128, 160, 192, 224, 256, 320, 352, 384]
    assert sum(outs) / 24 == 252
    assert max(p + o for p, o in tr["lengths"]) == 16768
    assert (tr["lead_in_s"], tr["trace_seconds"], tr["request_timeout_s"]) \
        == (16.0, 20, 120)
    assert tr["rate_rps"] == pytest.approx(0.8 * tr["knee_rps"])
    # the rule of the other cells, read off the file's own sweep: the knee
    # is the last rate at which TTFT per prompt token of the first and the
    # last third agree (theirs agree within 0-12% at their knees)
    apart = {row["rate_rps"]: row["ttft_per_token_p50_ms_last_third"]
             / row["ttft_per_token_p50_ms_first_third"] - 1
             for row in tr["sweep"]}
    rates = sorted(apart)
    assert tr["knee_rps"] in rates[:-1]
    assert all(abs(apart[r]) <= 0.12 for r in rates if r <= tr["knee_rps"])
    assert abs(apart[rates[rates.index(tr["knee_rps"]) + 1]]) > 0.3
    assert all(row["failed"] == 0 for row in tr["sweep"])
    # what a 51 s window holds at that rate
    assert round(tr["rate_rps"] * 51) == 41


def test_longctx_warm_requests_walk_state_and_pool_in_the_server(
        jamba_config, jamba_traffic):
    """Two short warm requests fit the padded forward the reference check
    pays for anyway; the long one crosses many chunks, blocks and tiles of
    positions in the server's own compiled step."""
    s, rc = jamba_config["serving"], jamba_config["reference_check"]
    t_pad = max(rc["prompt_lengths"]) + rc["decode_steps"] + 1
    (a, b), (prompt, outputs) = sorted(jamba_traffic["warm_requests"])[:2], \
        max(jamba_traffic["warm_requests"])
    assert sum(a) <= t_pad and sum(b) <= t_pad
    assert (prompt, outputs) == (8000, 12)
    for unit in (s["prefill_chunk"], s["kv_block_size"], 128):
        assert prompt // unit >= 60
    assert (prompt + outputs) % s["kv_block_size"]

"""The fifth hybrid driver and what came with it, CPU only: ``serve_keye.run``
end to end on the tiny configuration and traffic of ``testdata/`` (the
rehearsal of ``keye30b_longsparse``: every listed metric a number or
``None``), the check holding each wrong program NOT correct and reading the
selection of every fed lane, ``costs_keye`` against hand-counted numbers,
the six new readers on recorded observations and on a program without them,
and the configuration and traffic files against the contract.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_serve_keye.py -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import costs_keye, harness, peaks  # noqa: E402

CELL = "keye30b_longsparse"
READERS = ["indexer_share", "sparse_select_share", "sparse_attn_share",
           "indexer_roofline", "sparse_attn_roofline", "sparse_kernel_on"]
V5E = peaks.PEAKS["TPU v5 lite"]


def _testdata(*parts):
    with open(os.path.join(BENCH, "testdata", *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def keye_config():
    spec = harness.Spec()
    return spec.config(spec.cell(CELL))


@pytest.fixture(scope="module")
def keye_traffic():
    spec = harness.Spec()
    return spec.traffic(spec.cell(CELL))


@pytest.mark.parametrize("trace", [False, True], ids=["timed", "traced"])
def test_keye_driver_runs_tiny_cell(trace, tmp_path):
    """The driver's own ``run`` through engine, batcher and HTTP front, held
    to ``reference/keye.py``; then every metric the manifest lists for the
    cell is read from what it observed: a number, or ``None`` where the CPU
    has no device plane."""
    from benchmark.drivers import serve_keye
    cfg = _testdata("configs", "tiny-keye.json")
    obs = serve_keye.run({
        "cell": {"name": "tiny_longsparse", "chips": 1}, "config": cfg,
        "traffic": _testdata("traffic", "tiny_longsparse_open.json"),
        "seed": 4100000131, "seconds": 1.0, "trace": trace,
        "rehearsal": True, "phases": harness.Phases(),
        "trace_dir": str(tmp_path / "trace")})
    assert obs["correct"] and obs["failed"] == 0 and obs["attempted"] >= 4
    assert obs["sparse_kernels"] is False
    after, before = obs["counters_after"], obs["counters_before"]
    moved = {k: after[k] - before[k] for k in after}
    # a lane keeps at most 16 of the positions its indexer scored
    assert 0 < moved["sparse_selected_positions_total"] \
        < moved["sparse_scored_positions_total"]
    assert 0 < moved["sparse_read_positions_total"] \
        <= 4 * moved["read_positions_total"]
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
    assert set(READERS) <= set(values) and len(values) == 26
    assert values["decode_step_ms_p50"] > 0
    assert values["sparse_kernel_on"] == 0.0
    for name in ("indexer_share", "sparse_select_share", "sparse_attn_share",
                 "indexer_roofline", "sparse_attn_roofline",
                 "moe_expert_share", "serve_device_idle_share"):
        assert values[name] is None
    for name in ("queue_wait_ms_p50", "prefill_ms_per_token_p50",
                 "first_token_front_ms_p50"):
        assert (values[name] is not None) == trace, name


@pytest.mark.parametrize("how,fails", [
    (None, set()),
    ("int8", {"logits_match_reference"}),
    ("dense", {"selection_takes_topk"}),
    ("noqknorm", {"logits_match_reference"})])
def test_keye_check_reads_the_servers_own_step(how, fails):
    """Set-up's check has no program of its own: the requests go through
    the server all at once, whose engine traced the (wrong) program while
    it was built, and the logits, the expert choice and the selection are
    the engine's; steps that carry several requests' rows are compared.
    Each wrong program comes out NOT correct, the sound one correct; every
    fed position of every request reports its selection.  (A program that
    attends every position is matched in logits by a reference handed its
    selection: the count says it is wrong.)"""
    from benchmark.drivers import serve_jamba, serve_keye
    cfg = _testdata("configs", "tiny-keye.json")
    tr = _testdata("traffic", "tiny_longsparse_open.json")
    params = serve_keye.make_params(cfg, 7)
    reqs = serve_jamba.check_requests(cfg, tr, 7)
    server = serve_keye.degraded_server(cfg, params, how)
    try:
        assert server.engine.report_logits
        assert serve_keye.serve_recorded(server, reqs, 60)
    finally:
        server.close()
    assert [[len(r["prompt"]), len(r["rows"])] for r in reqs] \
        == [[13, 5], [45, 5], [60, 6]]
    # every fed position's choice: the prompt and all but the last token
    assert [len(r["routes"]) for r in reqs] == [17, 49, 65]
    assert [sum(b.shape[1] for _p, b in r["bits"]) for r in reqs] \
        == [17, 49, 65]
    if how == "int8":       # rounded in place
        params = serve_keye.make_params(cfg, 7)
    checks, facts = serve_keye.check_against_reference(params, cfg, reqs)
    failed = {k for k, ok in checks.items() if not ok}
    assert fails <= failed and (how or not failed)
    assert facts["compared_rows"] == 16
    assert facts["compared_rows_in_shared_steps"] >= 5
    # lanes past the 16th position of the three requests, four layers
    assert facts["selection_lanes_judged"] == 4 * (17 - 16 + 49 - 16
                                                   + 65 - 16)
    if how is None:
        assert checks["selection_takes_topk"]
        assert facts["selection_shortfall_max"] < 1e-4


def test_keye_check_sees_a_wrong_selection():
    """A program that takes the SMALLEST scores (the indexer's sign turned)
    still takes 16 positions, and the shortfall check says it is wrong."""
    from unittest import mock
    from benchmark.drivers import serve_jamba, serve_keye
    from paddle_tpu.ops import dsa
    cfg = _testdata("configs", "tiny-keye.json")
    tr = _testdata("traffic", "tiny_longsparse_open.json")
    params = serve_keye.make_params(cfg, 7)
    reqs = serve_jamba.check_requests(cfg, tr, 7)[:2]
    scores = dsa.index_scores
    with mock.patch.object(dsa, "index_scores", lambda *a: -scores(*a)):
        server = serve_keye.make_server(cfg, params)
    try:
        assert serve_keye.serve_recorded(server, reqs, 60)
    finally:
        server.close()
    checks, facts = serve_keye.check_against_reference(params, cfg, reqs)
    assert checks["selection_takes_topk"]
    assert not checks["selection_matches_reference"]
    assert facts["selection_shortfall_max"] > 10 * facts["selection_tol"]


def test_keye_readers_say_nothing_without_the_program():
    """On the parent the program has no sparse layer and the observation no
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
def keye_recorded(keye_config):
    """A reduced trace as ``trace_reduce.reduce`` shapes one, with the
    kernels under the names the chip's trace gives them (the ops that read
    their outputs name them among their operands, and count for nothing),
    and a window's counters."""
    ops = {"%indexer_paged_chunk.4 = custom-call(...)": [4000, 0.5, 0.5],
           "%sparse_select.5 = custom-call(...)": [4000, 1.0, 1.0],
           "%sparse_attn_paged_chunk.6 = custom-call(%indexer_paged_chunk.4, "
           "%sparse_select.5)": [4000, 2.0, 2.0],
           "%fusion.7 = fusion(%sparse_attn_paged_chunk.6)": [9000, 6.5, 6.5]}
    window = {"sparse_scored_positions_total": 4 * 10 ** 9,
              "sparse_selected_positions_total": 4 * 10 ** 8,
              "sparse_read_positions_total": 4 * 10 ** 7,
              "read_positions_total": 10 ** 8,
              "active_slot_steps_total": 8_000,
              "prefill_chunk_lanes_total": 92_000}
    return {"trace": {"ops": ops, "devices": 1, "busy_s": 10.0,
                      "window_s": 10.1},
            "sparse_kernels": True, "config": keye_config, "peaks": V5E,
            "counters_before": {k: 0 for k in window},
            "counters_after": window}


def _least(flops, nbytes):
    return max(flops / V5E["bf16_flops"], nbytes / V5E["hbm_bytes_per_s"])


@pytest.mark.parametrize("name,want", [
    ("sparse_kernel_on", 1.0), ("indexer_share", 5.0),
    ("sparse_select_share", 10.0), ("sparse_attn_share", 20.0),
    # 4e9 scored x 16 heads x 64 x 2 = 8.2e12 operations (41.6 ms); 1e8
    # positions x 4 layers x 128 B + 1e5 lanes x 4 x 2 KB of queries: 52 GB
    # (63.6 ms): the bytes bound it, over the kernel's 0.5 s
    ("indexer_roofline", 100 * _least(
        4e9 * 16 * 64 * 2, 4 * (1e8 * 128 + 1e5 * 16 * 64 * 2)) / 0.5),
    # 4e8 selected x 32 heads x 128 x 4 = 6.6e12 operations (33.3 ms);
    # 4e7 positions x 2 KB + 4 x 1e5 lanes x 16 KB: 88 GB (108 ms), over 2 s
    ("sparse_attn_roofline", 100 * _least(
        4e8 * 32 * 128 * 4, 4e7 * 2048 + 4 * 1e5 * 2 * 32 * 128 * 2) / 2.0)])
def test_keye_reader_on_recorded_observations(keye_recorded, name, want):
    value = harness.Spec().reader("per_layer", name).read(
        dict(keye_recorded))
    assert value == pytest.approx(want, rel=1e-6)


def test_keye_costs_from_the_shapes(keye_config):
    cfg = keye_config
    # one scored position: 16 heads x 64 x 2 operations
    assert costs_keye.indexer_flops(cfg, 1) == 2048
    # one position a row holds: 64 bfloat16 keys in each of four layers
    assert costs_keye.indexer_bytes(cfg, 1, 0) == 4 * 128
    # one selected position: 32 heads x (128 + 128) x 2 operations
    assert costs_keye.attn_flops(cfg, 1) == 32 * 128 * 4
    # one position read: K and V of 4 heads of 128 in bfloat16
    assert costs_keye.attn_bytes(cfg, 1, 0) == 2048


def test_keye_configuration_keeps_the_published_numbers(keye_config):
    """Every number of the catalog's row under its own key but the three
    ``reduced`` names, which ``published`` gives; the assumptions,
    departures, deployment and the vision tower stated; the parameter count
    the file gives."""
    cfg = keye_config
    (entry,) = [c for c in harness.Spec().manifest["configs"]
                if c["name"] == "keye-vl-2.0-30b-ep8-4l"]
    assert entry["reduced"] == cfg["reduced"] == [
        "num_experts", "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_experts": 128, "num_hidden_layers": 48,
                                "vocab_size": 151936}
    assert entry["source"] == cfg["source"] and "Keye-VL-2.0-30B-A3B" \
        in cfg["source"]
    published = {
        "hidden_size": 2048, "intermediate_size": 6144,
        "num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128,
        "max_position_embeddings": 262144, "rms_norm_eps": 1e-06,
        "num_experts_per_tok": 8, "moe_intermediate_size": 768,
        "num_local_experts": 128, "norm_topk_prob": True,
        "rope_theta": 10000000, "tie_word_embeddings": False,
        "mlp_only_layers": [], "decoder_sparse_step": 1,
        "sa_config": {"indexer_head_dim": 64, "indexer_num_heads": 16,
                      "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
                      "q_chunk_size": 512, "topk": 2048},
        "rope_scaling": {"mrope_section": [16, 24, 24],
                         "rope_type": "default", "type": "default"}}
    assert {k: cfg[k] for k in published} == published
    assert (cfg["num_experts"], cfg["vocab_size"],
            cfg["num_hidden_layers"]) == (16, 151936 // 8, 4)
    assert cfg["expert_parallel"] == {"chips": 8, "rank": 0,
                                      "num_experts_published": 128}
    for key in ("qk_norm", "rotation", "indexer", "indexer_rotation",
                "selection", "router"):
        assert cfg["assumed"][key]
    assert "vision" in cfg["vision_tower"] or "ViT" in cfg["vision_tower"]
    assert len(cfg["departures"]) >= 4 and cfg["param_dtype"] == "bfloat16"
    s = cfg["serving"]
    assert (s["slots"], s["max_len"], s["kv_block_size"], s["prefill_chunk"],
            s["prefix_cache"], s["kv_dtype"], s["report_logits"]) \
        == (16, 65536, 32, 64, False, "bfloat16", True)
    assert s["prefill_chunk_budget"] == 3 * (s["prefill_chunk"] - 1)
    # the parameters: attention, indexer, router and 16 held experts
    d = 2048
    layer = d * 40 * 128 + 32 * 128 * d + 2 * 128 \
        + d * (16 * 64 + 64 + 16) + 2 * 64 + d * 128 \
        + 16 * 3 * d * 768 + 2 * d
    total = 4 * layer + 2 * 18992 * d + d
    assert layer == 96_899_456 and total == 465_391_104
    assert "96,899,456" in cfg["parameters"]
    assert "465,391,104" in cfg["parameters"]
    # each limit between the sound program's largest reading and the
    # nearest wrong program's, with room on both sides; int8 weights come
    # out NOT correct by each of the three; a program that attends every
    # position is caught by the exact count, not by a limit
    rc = cfg["reference_check"]
    assert set(rc["limits"]) == {"logits", "router", "selection"}
    for name, limit in rc["limits"].items():
        r = rc["readings"][name]
        assert r["limit"] == limit
        worst = float(r["program"].split("-")[1])
        nearest = min(r[k] for k in ("int8_weights", "dense_selection",
                                     "qk_norms_dropped")
                      if isinstance(r[k], float) and r[k] > worst)
        assert nearest == r["int8_weights"], name
        assert 1.5 * worst <= limit <= nearest / 1.5, name
    # the chat form with 2 in place of 4.5: 12 stages of 4 layers
    assert rc["limits"]["logits"] == pytest.approx(
        2 * 2 ** -9 * (2 * (12 * 4 + 1)) ** 0.5, abs=5e-5)


def test_longsparse_traffic_holds_its_lengths_and_rule(keye_traffic):
    tr = keye_traffic
    assert tr["kind"] == "open_loop" and len(tr["lengths"]) == 24
    prompts = [p for p, _o in tr["lengths"]]
    outs = [o for _p, o in tr["lengths"]]
    assert min(prompts) == 16384 and max(prompts) == 61440
    assert prompts == sorted(prompts) and not any(p % 64 for p in prompts)
    assert sum(prompts) / 24 == pytest.approx(34291, rel=0.01)
    assert prompts[12] < sum(prompts) / 24
    assert sorted(set(outs)) == [256, 384, 512, 640, 768, 896, 1024]
    assert sum(outs) / 24 == 640
    assert max(p + o for p, o in tr["lengths"]) <= 65536
    assert tr["rate_rps"] == pytest.approx(0.8 * tr["knee_rps"])
    assert tr["warm_requests"] == [[16384, 8]]
    # the knee: the last rate, going up, whose thirds agree within a
    # quarter and whose whole-run median stays within a quarter of the
    # least loaded rate's
    rows = sorted(tr["sweep"], key=lambda r: r["rate_rps"])
    base, knee = rows[0]["ttft_per_token_p50_ms"], rows[0]["rate_rps"]
    for r in rows:
        if abs(r["ttft_per_token_p50_ms_last_third"]
               / r["ttft_per_token_p50_ms_first_third"] - 1) > 0.25 \
                or r["ttft_per_token_p50_ms"] > 1.25 * base:
            break
        knee = r["rate_rps"]
    assert tr["knee_rps"] == knee < rows[-1]["rate_rps"]
    assert all(r["failed"] == 0 for r in rows)
    assert round(tr["rate_rps"] * 51) >= 6

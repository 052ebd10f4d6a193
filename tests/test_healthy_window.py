"""CPU dry-run of the chip playbook ("zero chip minutes debugging the
harness").

Executes `healthy_window.sh` end-to-end with HW_DRYRUN=1 — every phase
runs its real command on the CPU backend at smoke scale — and asserts
each phase left its artifact behind.  A path typo, env-plumbing break, or
rc-logging bug in the playbook is caught here, not on the chip.

Slow lane only (several minutes of real subprocess work): run with
`pytest -m slow tests/test_healthy_window.py`.
"""

import json
import os
import subprocess

import pytest

pytestmark = pytest.mark.slow

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPT = os.path.join(_ROOT, "paddle_tpu", "scripts", "healthy_window.sh")


def test_dryrun_executes_every_phase(tmp_path):
    art = tmp_path / "window"
    env = dict(os.environ)
    env.update(HW_DRYRUN="1", JAX_PLATFORMS="cpu")
    # a dry run must be hermetic: no JAX persistent cache dir leaking in
    env.pop("BENCH_PROFILE_BASE", None)
    committed = [os.path.join(_ROOT, "BENCH_ANALYTIC_r06.json")]
    mtimes_before = {p: os.path.getmtime(p) for p in committed
                     if os.path.exists(p)}
    proc = subprocess.run(
        ["bash", _SCRIPT, str(art)], env=env, cwd=_ROOT,
        capture_output=True, text=True, timeout=3600)
    log = proc.stdout + proc.stderr
    assert proc.returncode == 0, log[-4000:]

    # every phase's artifact landed
    for name in ("smoke_kernels.json", "bench_sweep.json",
                 "bench_scan_baselines.json", "bench_bf16.json",
                 "bench_int8.json", "diff_cpu.npz", "diff_tpu.npz",
                 "tpu_differential_pytest.log", "nmt_scale.json",
                 "analytic.json",
                 "analytic_snapshot.json", "serving_smoke.json",
                 "serving_gen_smoke.json", "chaos_smoke.json",
                 "fleet_smoke.json", "paged_smoke.json",
                 "trace_smoke.json", "trace_chrome.json",
                 "decode_fused_smoke.json", "autoscale_smoke.json",
                 "chunked_smoke.json", "quant_smoke.json",
                 "analysis_gate.json", "spec_smoke.json",
                 "sharded_smoke.json", "spill_smoke.json",
                 "disagg_smoke.json", "quant_prefill_smoke.json",
                 "WINDOW_DONE"):
        assert (art / name).exists(), f"{name} missing; log tail:\n" \
            + log[-4000:]

    # the phases really ran (not just touched files): smoke reports every
    # kernel, the sweep reports its combos, the analytic snapshot holds
    # roofline rows
    smoke = json.loads((art / "smoke_kernels.json").read_text())
    assert smoke["value"] == int(smoke["unit"].split("/")[1]), smoke
    sweep = json.loads((art / "bench_sweep.json").read_text())
    assert set(sweep["sweep"]) == {"smallnet:8", "trainer_prefetch:8"}
    for combo, row in sweep["sweep"].items():
        assert row.get("value") is not None, (combo, row)
    snap = json.loads((art / "analytic_snapshot.json").read_text())
    assert set(snap["families"]) == {"smallnet", "trainer_prefetch",
                                     "serving", "serving_generate"}
    for fam, row in snap["families"].items():
        assert row.get("predicted_ms", 0) > 0, (fam, row)
    # the serving smoke really served: every request answered, the
    # malformed request 400'd, /metrics rendered sanely, and batching
    # happened (occupancy > 1 under the smoke's concurrent clients)
    smoke_srv = json.loads((art / "serving_smoke.json").read_text())
    assert smoke_srv["value"] == int(smoke_srv["unit"].split("/")[1]), \
        smoke_srv
    assert smoke_srv["bad_request_status"] == 400, smoke_srv
    assert smoke_srv["metrics_sane"] is True, smoke_srv
    assert smoke_srv["mean_occupancy"] > 1.0, smoke_srv
    # the generation smoke really generated: every staggered request
    # answered, the stream matched the plain response, the EOS probe
    # finished early, and the TTFT/slot metrics rendered
    smoke_gen = json.loads((art / "serving_gen_smoke.json").read_text())
    assert smoke_gen["value"] == int(smoke_gen["unit"].split("/")[1]), \
        smoke_gen
    assert smoke_gen["stream_ok"] is True, smoke_gen
    assert smoke_gen["eos_early_finish"] is True, smoke_gen
    assert smoke_gen["metrics_sane"] is True, smoke_gen
    assert smoke_gen["gen_tokens_total"] > 0, smoke_gen
    assert smoke_gen["readyz"] == "ready", smoke_gen
    # the chaos smoke really exercised the resilience layer: the injected
    # decode-step fault fired, recovered streams stayed bit-identical,
    # and the kill-9'd trainer resumed to bit-identical params
    chaos = json.loads((art / "chaos_smoke.json").read_text())
    assert chaos["value"] == int(chaos["unit"].split("/")[1]), chaos
    assert chaos["faults_fired"] >= 1, chaos
    assert chaos["bit_identical"] is True, chaos
    assert chaos["victim_killed"] is True, chaos
    assert chaos["resume_bit_identical"] is True, chaos
    # the fleet smoke really failed over: 2 replica subprocesses behind
    # the router, one kill -9'd mid-stream, every stream bit-identical
    # via the cross-replica continuation, and the supervisor restarted
    # the victim to readiness
    fleet = json.loads((art / "fleet_smoke.json").read_text())
    assert fleet["value"] == int(fleet["unit"].split("/")[1]), fleet
    assert fleet["bit_identical"] is True, fleet
    assert fleet["victim_killed"] is True, fleet
    assert fleet["midstream_failovers"] >= 1, fleet
    assert fleet["restarted_ready"] is True, fleet
    assert fleet["victim_restarts"] >= 1, fleet
    # the paged smoke really shared: the exact-duplicate and divergent
    # clients hit the leader's prefix chains, the duplicate's seat
    # copy-on-write forked the shared tail block, and every stream came
    # back bit-identical to the slab-layout twin
    paged = json.loads((art / "paged_smoke.json").read_text())
    assert paged["value"] == int(paged["unit"].split("/")[1]), paged
    assert paged["bit_identical"] is True, paged
    assert paged["prefix_cache_hits"] >= 2, paged
    assert paged["cow_forks"] >= 1, paged
    assert paged["metrics_sane"] is True, paged
    # the trace smoke really stitched: one trace_id crossed the router,
    # the kill -9'd replica, and the failover continuation on the
    # survivor, and the merged Chrome trace-event dump parsed with all
    # three process names
    tsm = json.loads((art / "trace_smoke.json").read_text())
    assert tsm["value"] == int(tsm["unit"].split("/")[1]), tsm
    assert tsm["victim_killed"] is True, tsm
    assert tsm["stitched"] is True, tsm
    assert tsm["chrome_parses"] is True, tsm
    assert tsm["chrome_processes"] >= 3, tsm
    chrome = json.loads((art / "trace_chrome.json").read_text())
    assert chrome["traceEvents"], "empty Chrome trace dump"
    # the decode-fused smoke really fused: both kernels (slab + paged)
    # compiled into the demo engines' steps, every staggered stream
    # bit-identical to the reference-path twin, zero retraces
    fused = json.loads((art / "decode_fused_smoke.json").read_text())
    assert fused["value"] == int(fused["unit"].split("/")[1]), fused
    for layout in ("slab", "paged"):
        assert fused[f"{layout}_kernel_engaged"] is True, fused
        assert fused[f"{layout}_bit_identical"] is True, fused
        assert fused[f"{layout}_retraces"] == 0, fused
    # the autoscale smoke really closed the loop: the seeded spike
    # breached the TTFT target, the control loop scaled 1 -> 2 to
    # readiness, the post-scale drive sat back under target, and the
    # fleet scaled back in — with zero failed requests
    asc = json.loads((art / "autoscale_smoke.json").read_text())
    assert asc["value"] == int(asc["unit"].split("/")[1]), asc
    assert asc["scaled_out"] is True, asc
    assert asc["scaled_in"] is True, asc
    assert asc["recovered_under_target"] is True, asc
    assert asc["failed"] == 0 and asc["completed"] > 0, asc
    assert asc["decisions_out"] >= 1 and asc["decisions_in"] >= 1, asc
    # the chunked-prefill smoke really unified: the long prompt chunked
    # through the shared decode step (>= ceil(15/(K-1)) chunks), the
    # in-flight stream kept emitting while it ingested, and both streams
    # came back bit-identical to the legacy-ladder twin
    chk = json.loads((art / "chunked_smoke.json").read_text())
    assert chk["value"] == int(chk["unit"].split("/")[1]), chk
    assert chk["bit_identical"] is True, chk
    assert chk["interleaved_tokens"] >= 1, chk
    assert chk["prefill_chunks_total"] >= 2, chk
    assert chk["prefill_chunk_lanes_total"] >= 15, chk
    # the quant smoke really quantized: every int8-KV stream inside the
    # committed quality budget vs the fp32 twin, the int8-KV+weights
    # engine token-exact vs the quantized lm_generate oracle, and the
    # int8 pool holding exactly DOUBLE the twin's blocks at equal bytes
    qsm = json.loads((art / "quant_smoke.json").read_text())
    assert qsm["value"] == int(qsm["unit"].split("/")[1]), qsm
    assert qsm["within_budget"] == qsm["value"], qsm
    assert qsm["full_quant_oracle_exact"] == qsm["value"], qsm
    assert qsm["kv_blocks_doubled"] is True, qsm
    assert qsm["kv_blocks_total"] == 2 * qsm["f32_twin_blocks"], qsm
    assert qsm["kv_dtype"] == "int8" and qsm["metrics_sane"] is True, qsm
    # the static invariant gate really gated: all three passes ran
    # against the committed baseline with ZERO new findings (a new
    # finding exits nonzero and withholds WINDOW_DONE — asserted above
    # via rc==0 + the file's existence)
    gate = json.loads((art / "analysis_gate.json").read_text())
    assert gate["check"] == "all", gate
    assert gate["new"] == 0, gate
    assert gate["roots"], "analysis gate ran with no jit roots"
    assert gate["stale_baseline_keys"] == [], gate
    # the speculative smoke really speculated: every staggered stream
    # bit-identical to the non-spec twin, draft lanes actually scored
    # (acceptance evidence on /metrics), every verify step netting
    # >= 1 token, and both engines at 1 warm-up trace / 0 retraces
    spc = json.loads((art / "spec_smoke.json").read_text())
    assert spc["value"] == int(spc["unit"].split("/")[1]), spc
    assert spc["bit_identical"] is True, spc
    assert spc["drafted_tokens_total"] > 0, spc
    assert spc["spec_tokens_per_step"] >= 1.0, spc
    assert spc["no_retrace"] is True, spc
    assert spc["metrics_sane"] is True, spc
    # the sharded smoke really sharded: a 2-device mesh actually backed
    # the step (the probe re-execs itself with the forcing flag on a
    # single-device machine), every staggered stream bit-identical to
    # the single-chip twin, the mesh_shards gauge on /metrics, and
    # exactly one warm-up trace per jitted function
    shd = json.loads((art / "sharded_smoke.json").read_text())
    assert shd["value"] == int(shd["unit"].split("/")[1]), shd
    assert shd["mesh_shards"] == 2, shd
    assert shd["devices"] >= 2, shd
    assert shd["bit_identical"] is True, shd
    assert shd["no_retrace"] is True, shd
    assert shd["metrics_sane"] is True, shd
    # the spill smoke really restored: churn evicted (and spilled) the
    # shared chain, the returning prompt restore-hit from the host tier
    # and seated by reference — ZERO prefill chunk lanes for the return
    # visit — bit-identical to the tier-less twin's recompute, with the
    # spill/restore counters on /metrics and one warm-up trace
    spl = json.loads((art / "spill_smoke.json").read_text())
    assert spl["kv_restore_hits"] >= 1, spl
    assert spl["kv_spill_blocks"] > 0, spl
    assert spl["chunk_lanes_return_visit"] == 0, spl
    assert spl["bit_identical"] is True, spl
    assert spl["step_traces"] == 1, spl
    assert spl["metrics_sane"] is True, spl
    # the disagg smoke really handed off: prompts prefilled on one pool,
    # the KV chain crossed the socket at first token and the decode pool
    # seated it (received counters on both replicas AND the router), a
    # sub-crossover prompt took the analytic recompute fallback, kill -9
    # of the prefill replica fell back to recompute — every stream
    # bit-identical to the single-replica oracle
    dsg = json.loads((art / "disagg_smoke.json").read_text())
    assert dsg["value"] == int(dsg["unit"].split("/")[1]), dsg
    assert dsg["disagg_active"] is True, dsg
    assert dsg["bit_identical"] is True, dsg
    assert dsg["prefill_sent"] >= 3, dsg
    assert dsg["decode_received"] >= 3, dsg
    assert dsg["decode_handoff_bytes"] > 0, dsg
    assert dsg["router_handoffs"]["received"] >= 3, dsg
    assert dsg["router_handoffs"]["fallback"] >= 1, dsg
    assert dsg["kill_fallback_outcome"]["outcome"] == "fallback", dsg
    assert dsg["post_kill_stream_ok"] is True, dsg
    # the quant-prefill smoke really went low-precision end to end:
    # every stream of the int8 flash prefill inside the committed logit
    # budget vs the fp32 twin, the kernel-fed int8 cache matching the
    # sequential-step round trip, and the int8 weight-streaming trainer
    # tracking its f32 twin within the committed training budget with a
    # non-empty int8 tree
    qpf = json.loads((art / "quant_prefill_smoke.json").read_text())
    assert qpf["value"] == int(qpf["unit"].split("/")[1]), qpf
    assert qpf["max_logit_err"] <= qpf["logit_err_budget"], qpf
    assert qpf["cache_matches_sequential"] is True, qpf
    assert qpf["trainer_loss_gap_max"] is not None, qpf
    assert qpf["trainer_loss_gap_max"] <= qpf["train_loss_budget"], qpf
    assert qpf["quant_tree_leaves"] >= 2, qpf
    assert "errors" not in qpf, qpf
    assert "dryrun=1" in (art / "WINDOW_DONE").read_text()

    # a dry run must never rewrite the committed analytic snapshot —
    # guarded by the dryrun-specific --out path above
    for p, before in mtimes_before.items():
        assert os.path.getmtime(p) == before, (
            f"dry run rewrote committed perf artifact {p}")

#!/usr/bin/env bash
# Chip playbook: run every phase that wants hardware evidence, in priority
# order, and leave its artifacts behind.  On the chip this is ONE command
# for the chip tool (chip_smoke.py is the quick proof; this is the long
# form).  Each phase writes its own artifact, so a failed phase keeps
# whatever already landed.
#
#   bash paddle_tpu/scripts/healthy_window.sh [artifacts_dir]
#
# Dry-run mode (round-6; tests/test_healthy_window.py):
#   HW_DRYRUN=1 bash paddle_tpu/scripts/healthy_window.sh [artifacts_dir]
# executes every phase end-to-end on the CPU backend with smoke-scale
# arguments and short timeouts, so the harness itself (paths, rcs, env
# plumbing, resume markers) is debugged with ZERO chip minutes.
# Dry runs never touch the committed analytic snapshot.
#
# Phases:
#  1. bench.py --smoke-kernels          (Mosaic compile canary, ~minutes)
#  2. bench_sweep                       (BASELINE rows + scaling column)
#  3. tpu_diff TPU dump + differential  (CPU-vs-TPU numerics evidence)
#  4. nmt_scale                         (verbatim-config NMT row + golden)
#  6. analytic snapshot refresh         (chip-INDEPENDENT cost/roofline —
#                                        last so it burns no chip time)
#  7. serving runtime smoke             (dynamic batcher + HTTP front-end
#                                        self-test on an ephemeral port)
#  8. generation serving smoke          (continuous-batching decode engine:
#                                        concurrent staggered /v1/generate,
#                                        streaming, EOS early-finish)
#  9. chaos smoke                       (resilience layer: server under an
#                                        injected decode-step fault, slot
#                                        re-prefill recovery bit-identical;
#                                        kill-9 trainer + resume)
# 10. fleet smoke                       (replicated serving tier: 2 replica
#                                        subprocesses behind the router,
#                                        kill-9 one mid-stream, streams
#                                        bit-identical via cross-replica
#                                        failover; supervisor restarts it)
# 11. paged KV smoke                    (paged block-pool KV cache: two
#                                        clients sharing a long system
#                                        prompt + one divergent -> prefix
#                                        hits + CoW fork recorded, streams
#                                        bit-identical to the slab twin)
# 12. trace smoke                       (end-to-end request tracing: 2
#                                        traced replicas behind the router,
#                                        kill -9 one mid-stream -> a single
#                                        trace_id stitches router + both
#                                        replicas; Chrome dump parses)
# 13. fused decode-kernel smoke         (pallas_decode generation drive,
#                                        slab + paged kernels compiled in:
#                                        streams bit-identical to the
#                                        reference-path twin, 0 retraces)
# 14. autoscale smoke                    (SLO-holding control plane: 1
#                                        replica + seeded load spike ->
#                                        scale-out to 2 and p99 TTFT back
#                                        under target, spike ends ->
#                                        rolling scale-in; zero failed
#                                        requests)
# 15. chunked-prefill smoke              (unified step vs legacy ladder:
#                                        long prompt chunked mid-decode,
#                                        in-flight streams keep emitting)
# 16. quantized serving smoke            (int8-KV paged engine within the
#                                        committed quality budget vs the
#                                        fp32 twin, int8+weights exact vs
#                                        the quantized oracle, blocks
#                                        doubled at equal bytes)
# 17. static invariant gate              (python -m paddle_tpu.analysis:
#                                        jit-purity + retrace-hazard +
#                                        lock-order passes vs the
#                                        committed baseline — findings
#                                        FAIL the window, no chip time
#                                        needed)
# 18. speculative serving smoke          (draft-ahead decode engine vs
#                                        its non-spec twin: streams
#                                        bit-identical, acceptance-rate
#                                        evidence in /metrics, zero
#                                        retraces — one JSON line)
# 19. sharded serving smoke              (tensor-parallel decode on an
#                                        n=2 forced host mesh vs the
#                                        single-chip twin: staggered
#                                        concurrent streams
#                                        bit-identical, mesh_shards on
#                                        /metrics, zero retraces — one
#                                        JSON line)
# 20. hierarchical KV smoke              (host-RAM spill tier: churn
#                                        evicts a long shared-prefix
#                                        chain, the returning prompt
#                                        restore-hits with zero chunk
#                                        lanes, bit-identical to the
#                                        tier-less twin, spill/restore
#                                        evidence on /metrics — one
#                                        JSON line)
# 21. disaggregated serving smoke        (prefill+decode replica pools
#                                        behind the router: streams
#                                        prefill on one pool, hand the
#                                        KV chain off over a real
#                                        socket at first token, decode
#                                        on the other — bit-identical
#                                        to the oracle, kill -9 of the
#                                        prefill replica mid-handoff
#                                        falls back to recompute,
#                                        kv_handoff counters on every
#                                        /metrics — one JSON line)
# 22. quantized prefill + int8 trainer   (int8 flash prefill within the
#                                        committed logit budget vs the
#                                        fp32 twin, cache matching Tp
#                                        sequential steps; 3-step int8
#                                        weight-streaming trainer loss
#                                        parity vs its f32 twin — one
#                                        JSON line)
set -u

DRY="${HW_DRYRUN:-0}"
if [ "$DRY" = "1" ]; then
    # smoke-scale everything: cpu backend, 2 timed steps, tiny model/
    # stream shapes, one small tpu_diff case, 200-word NMT
    export BENCH_PLATFORM=cpu JAX_PLATFORMS=cpu
    export BENCH_STEPS=2 BENCH_SERVING_TINY=1
    DIFF_PLATFORM=cpu
    T_SMOKE=900; T_SWEEP=900; T_COL=600; T_DIFF=600; T_NMT=600
    SWEEP_ARGS=(--combos "smallnet:8,trainer_prefetch:8" --steps 2)
    SCAN_ARGS=(--combos "smallnet:8" --steps 2)
    BF16_ARGS=(--combos "smallnet:8" --steps 2)
    INT8_ARGS=(--combos "transformer_serving:4" --steps 2)
    DIFF_CASES="embedding"
    NMT_ARGS=(--vocab 200 --steps 4 --gen-sents 4 --beam 2 --max-gen-len 20)
    ANALYTIC_FAMILIES="smallnet,trainer_prefetch,serving,serving_generate"
    T_SERVE=600
else
    T_SMOKE=1200; T_SWEEP=14400; T_COL=3600; T_DIFF=7200; T_NMT=7200
    DIFF_PLATFORM=tpu
    SWEEP_ARGS=()
    SCAN_ARGS=(--combos "lstm:64,lstm256:64,lstm1280:64,seq2seq:64")
    BF16_ARGS=(--combos "resnet50:256,transformer:128,lstm:64,googlenet:256")
    INT8_ARGS=(--combos "transformer_decode:32,transformer_serving:16")
    DIFF_CASES=""
    NMT_ARGS=(--vocab 30000 --steps 300 --gen-sents 32 --beam 5
              --max-gen-len 50)
    ANALYTIC_FAMILIES=""
    T_SERVE=600
fi

# every bench.py combo is a fresh subprocess; a shared persistent XLA
# compile cache means only the FIRST run of each program pays its compile.
# The cache is wherever JAX_COMPILATION_CACHE_DIR says; unset, every
# entry point uses the checkout's fixed .jax_cache
# (utils/flags.set_compilation_cache_dir), so the processes share it
# either way.  Phase 1's Mosaic canary must really compile (a cache hit
# would mask exactly the lowering regression it exists to catch), so it
# runs with the persistent cache disabled.

# an explicit dir resolves against the CALLER's cwd; the default stays
# repo-root-relative (resolved after the cd below)
if [ $# -ge 1 ]; then ART=$(realpath -m "$1"); else ART=""; fi
cd "$(dirname "$0")/../.."
ART="${ART:-$PWD/chiprun_out/window}"
mkdir -p "$ART"
log() { echo "[healthy_window $(date -u +%H:%M:%S)] $*" >&2; }
[ "$DRY" = "1" ] && log "DRY RUN: cpu backend, smoke-scale arguments"

log "phase 1: pallas kernel smoke"
JAX_ENABLE_COMPILATION_CACHE=false timeout "$T_SMOKE" \
    python bench.py --smoke-kernels \
    > "$ART/smoke_kernels.json" 2> "$ART/smoke_kernels.log"
log "smoke rc=$? -> $ART/smoke_kernels.json"

log "phase 2: bench sweep (BASELINE + scaling; per-combo xprof traces)"
BENCH_PROFILE_BASE="$ART/xprof" timeout "$T_SWEEP" \
    python -m paddle_tpu.scripts.bench_sweep "${SWEEP_ARGS[@]}" \
    > "$ART/bench_sweep.json" 2> "$ART/bench_sweep.log"
log "sweep rc=$? -> $ART/bench_sweep.json"
python -m paddle_tpu.scripts.xprof_report "$ART/xprof" \
    --write "$ART/xprof_report" 2> "$ART/xprof_report.log"
log "xprof attribution rc=$? -> $ART/xprof_report.{txt,json}"

log "phase 2b: scan baselines for the fused-kernel vs-scan column"
PADDLE_TPU_FUSED_RNN=0 BENCH_PROFILE_BASE="$ART/xprof_scan" \
    timeout "$T_COL" python -m paddle_tpu.scripts.bench_sweep \
    "${SCAN_ARGS[@]}" \
    > "$ART/bench_scan_baselines.json" 2> "$ART/bench_scan_baselines.log"
log "scan baselines rc=$? -> $ART/bench_scan_baselines.json"
python -m paddle_tpu.scripts.xprof_report "$ART/xprof_scan" \
    --write "$ART/xprof_scan_report" 2>> "$ART/xprof_report.log"
log "scan-trace attribution rc=$? (fused-vs-scan comparison inputs ready)"

log "phase 2c: bf16 column for the MFU-critical families"
BENCH_DTYPE=bfloat16 BENCH_PROFILE_BASE="$ART/xprof_bf16" \
    timeout "$T_COL" python -m paddle_tpu.scripts.bench_sweep \
    "${BF16_ARGS[@]}" \
    > "$ART/bench_bf16.json" 2> "$ART/bench_bf16.log"
log "bf16 sweep rc=$? (cached under model@bsN@bfloat16)"
python -m paddle_tpu.scripts.xprof_report "$ART/xprof_bf16" \
    --write "$ART/xprof_bf16_report" 2>> "$ART/xprof_report.log"
log "bf16-trace attribution rc=$?"

log "phase 2d: int8 weight-only serving column (vs the bf16/f32 rows)"
BENCH_QUANT=int8 timeout "$T_COL" python -m paddle_tpu.scripts.bench_sweep \
    "${INT8_ARGS[@]}" \
    > "$ART/bench_int8.json" 2> "$ART/bench_int8.log"
log "int8 sweep rc=$? -> $ART/bench_int8.json"

log "phase 3: TPU differential dump + compare"
# resumable per-case dumps.  Retry error/timeout records from an earlier
# partial run — a killed group leaves TimeoutExpired records for its
# missing sub-cases
export TPU_DIFF_RETRY_ERRORS=1
timeout "$T_DIFF" python -m paddle_tpu.testing.tpu_diff "$DIFF_PLATFORM" \
    "$ART/diff_tpu.npz" $DIFF_CASES 2> "$ART/diff_tpu.log"
log "tpu dump rc=$?"
JAX_PLATFORMS=cpu timeout "$T_COL" python -m paddle_tpu.testing.tpu_diff \
    cpu "$ART/diff_cpu.npz" $DIFF_CASES 2> "$ART/diff_cpu.log"
log "cpu dump rc=$?"
PADDLE_TPU_DIFF="$ART/diff_cpu.npz:$ART/diff_tpu.npz" \
    python -m pytest tests/test_tpu_differential.py -q \
    > "$ART/tpu_differential_pytest.log" 2>&1
log "differential pytest rc=$? -> $ART/tpu_differential_pytest.log"

log "phase 4: reference-scale NMT (verbatim configs, 30k vocab)"
timeout "$T_NMT" python -m paddle_tpu.scripts.nmt_scale \
    --out-dir "$ART/nmt" "${NMT_ARGS[@]}" \
    > "$ART/nmt_scale.json" 2> "$ART/nmt_scale.log"
log "nmt rc=$? -> $ART/nmt_scale.json"

log "phase 6: analytic cost/roofline snapshot (chip-independent, cpu)"
# the dry run writes into ART (never the committed round snapshot); the
# real window refreshes BENCH_ANALYTIC_r06.json at the repo root AFTER
# the chip phases, so the snapshot never competes for chip minutes
if [ "$DRY" = "1" ]; then
    timeout "$T_SWEEP" python bench.py --analytic \
        --families "$ANALYTIC_FAMILIES" --out "$ART/analytic_snapshot.json" \
        > "$ART/analytic.json" 2> "$ART/analytic.log"
else
    timeout 7200 python bench.py --analytic \
        > "$ART/analytic.json" 2> "$ART/analytic.log"
fi
log "analytic rc=$? -> $ART/analytic.json"

log "phase 7: serving runtime smoke (dynamic batcher + HTTP front-end)"
# self-contained: ephemeral port, concurrent requests, a malformed
# request, /healthz + /metrics sanity — one JSON line, nonzero rc on any
# failed check (serving/server.py --smoke)
timeout "$T_SERVE" python -m paddle_tpu.serving --smoke \
    > "$ART/serving_smoke.json" 2> "$ART/serving_smoke.log"
log "serving smoke rc=$? -> $ART/serving_smoke.json"

log "phase 8: generation serving smoke (continuous-batching decode engine)"
# concurrent STAGGERED /v1/generate requests (admissions land mid-decode,
# slots churn), one streaming request, EOS early-finish — one JSON line,
# nonzero rc on any failed check (serving/server.py --smoke-generate)
timeout "$T_SERVE" python -m paddle_tpu.serving --smoke-generate \
    > "$ART/serving_gen_smoke.json" 2> "$ART/serving_gen_smoke.log"
log "generation smoke rc=$? -> $ART/serving_gen_smoke.json"

log "phase 9: chaos smoke (fault injection + supervised recovery)"
# serving under an injected decode-step fault (recovered streams must be
# bit-identical to the clean run) + kill-9 trainer resume at smoke scale
# — one JSON line, nonzero rc on any failed check
# (python -m paddle_tpu.resilience --smoke; docs/serving.md §6)
timeout "$T_SERVE" python -m paddle_tpu.resilience --smoke \
    > "$ART/chaos_smoke.json" 2> "$ART/chaos_smoke.log"
log "chaos smoke rc=$? -> $ART/chaos_smoke.json"

log "phase 10: fleet smoke (replica supervisor + health-checked router)"
# 2 tiny replica subprocesses on ephemeral ports behind the router;
# concurrent streaming /v1/generate clients; kill -9 one replica
# MID-STREAM — every stream must finish bit-identical to lm_generate via
# the router's cross-replica continuation failover, /metrics must show
# it, and the supervisor must restart the victim to readiness — one JSON
# line (python -m paddle_tpu.serving.router --smoke; docs/serving.md §7)
timeout "$T_SERVE" python -m paddle_tpu.serving.router --smoke \
    > "$ART/fleet_smoke.json" 2> "$ART/fleet_smoke.log"
log "fleet smoke rc=$? -> $ART/fleet_smoke.json"

log "phase 11: paged KV smoke (block pool + prefix sharing + CoW)"
# kv_layout=paged demo server: one leader client registers a long
# system-prompt chain, an exact-duplicate client must hit + CoW-fork,
# a divergent client must hit the shared prefix — every stream
# bit-identical to the same prompts through a slab-layout twin — one
# JSON line (python -m paddle_tpu.serving --smoke-paged;
# docs/serving.md §5)
timeout "$T_SERVE" python -m paddle_tpu.serving --smoke-paged \
    > "$ART/paged_smoke.json" 2> "$ART/paged_smoke.log"
log "paged smoke rc=$? -> $ART/paged_smoke.json"

log "phase 12: trace smoke (end-to-end request tracing across the fleet)"
# 2 tracing-enabled replicas behind the router, concurrent paced streams,
# kill -9 one replica mid-stream: ONE trace_id must stitch router -> the
# dead replica (pre-kill /debug/traces snapshot) -> the continuation on
# the survivor, and the merged Chrome trace-event dump must parse with
# all three process names — one JSON line
# (python -m paddle_tpu.obs --smoke; docs/observability.md)
timeout "$T_SERVE" python -m paddle_tpu.obs --smoke \
    --chrome-out "$ART/trace_chrome.json" \
    > "$ART/trace_smoke.json" 2> "$ART/trace_smoke.log"
log "trace smoke rc=$? -> $ART/trace_smoke.json"

log "phase 13: fused decode-kernel smoke (pallas_decode vs reference twin)"
# the demo generation drive with the Pallas decode-attention kernels
# compiled into the slab AND paged steps (interpret mode on CPU, Mosaic
# on TPU): staggered streams must come back bit-identical to a
# reference-path twin engine with 0 retraces — one JSON line
# (python -m paddle_tpu.serving --smoke-decode-fused; docs/perf.md
# "Fused decode kernels")
timeout "$T_SERVE" python -m paddle_tpu.serving --smoke-decode-fused \
    > "$ART/decode_fused_smoke.json" 2> "$ART/decode_fused_smoke.log"
log "decode-fused smoke rc=$? -> $ART/decode_fused_smoke.json"

log "phase 14: autoscale smoke (SLO-holding control plane)"
# 1 tiny replica + router + autoscaler (min 1, max 2): a seeded load
# spike breaches the TTFT target -> the control loop scales out to 2
# (spawn-to-readiness), a post-scale steady drive sits back under
# target, the spike ends -> sustained slack scales back in through the
# rolling drain — ZERO failed requests, every completed stream
# bit-identical to lm_generate — one JSON line
# (python -m paddle_tpu.serving.autoscaler --smoke; docs/serving.md §8)
timeout "$T_SERVE" python -m paddle_tpu.serving.autoscaler --smoke \
    > "$ART/autoscale_smoke.json" 2> "$ART/autoscale_smoke.log"
log "autoscale smoke rc=$? -> $ART/autoscale_smoke.json"

log "phase 15: chunked-prefill smoke (unified step vs legacy ladder)"
# prompt ingestion folded into the ONE jitted decode step: a long prompt
# admitted MID-DECODE must chunk through the shared step while the
# in-flight stream keeps emitting (interleaved tokens >= 1), and every
# stream must be bit-identical to the legacy-ladder twin — one JSON line
# (python -m paddle_tpu.serving --smoke-chunked; docs/serving.md
# "Chunked prefill")
timeout "$T_SERVE" python -m paddle_tpu.serving --smoke-chunked \
    > "$ART/chunked_smoke.json" 2> "$ART/chunked_smoke.log"
log "chunked smoke rc=$? -> $ART/chunked_smoke.json"

log "phase 16: quantized serving smoke (int8 KV + int8 weights)"
# int8-KV paged engine (kv_num_blocks auto-DOUBLED at the slab-
# equivalent byte budget) vs a fp32 twin: every HTTP stream inside the
# committed quality budget, the int8-KV+weights engine token-EXACT vs
# the quantized lm_generate oracle, /metrics showing kv_blocks_total
# doubled at equal bytes + kv_cache_int8 1 — one JSON line
# (python -m paddle_tpu.serving --smoke-quant; docs/serving.md
# "Quantized serving")
timeout "$T_SERVE" python -m paddle_tpu.serving --smoke-quant \
    > "$ART/quant_smoke.json" 2> "$ART/quant_smoke.log"
log "quant smoke rc=$? -> $ART/quant_smoke.json"

log "phase 17: static invariant gate (jit-purity / retrace / lock-order)"
# chip-independent AST gate (docs/analysis.md): every finding must be
# either fixed or baselined with a reason — a NEW finding fails the
# whole window (rc propagated, WINDOW_DONE withheld) because a step
# that retraces or deadlocks would poison every phase above on the
# next revision.  Same command in dry-run and real windows: the
# analyzer never touches a chip.
timeout "$T_SERVE" python -m paddle_tpu.analysis --check all --json \
    > "$ART/analysis_gate.json" 2> "$ART/analysis_gate.log"
ANALYSIS_RC=$?
log "analysis gate rc=$ANALYSIS_RC -> $ART/analysis_gate.json"
if [ "$ANALYSIS_RC" != 0 ]; then
    log "STATIC INVARIANT GATE FAILED — fix or baseline the findings in"
    log "$ART/analysis_gate.json before trusting this window"
    exit "$ANALYSIS_RC"
fi

log "phase 18: speculative serving smoke (draft-ahead vs non-spec twin)"
# greedy speculative decoding on the slot engine: a k-lane draft rollout
# feeds the ONE chunked verify step; every stream must be bit-identical
# to the non-speculating twin regardless of draft quality, acceptance
# evidence (drafted/accepted counters, acceptance rate, tokens/step)
# must render on /metrics, and both engines must hold at 1 warm-up
# trace / 0 retraces — one JSON line
# (python -m paddle_tpu.serving --smoke-speculative; docs/serving.md
# "Speculative decoding")
timeout "$T_SERVE" python -m paddle_tpu.serving --smoke-speculative \
    > "$ART/spec_smoke.json" 2> "$ART/spec_smoke.log"
log "speculative smoke rc=$? -> $ART/spec_smoke.json"

log "phase 19: sharded serving smoke (n=2 host mesh vs single-chip twin)"
# tensor-parallel sharded decode: the ONE chunked step under a 2-chip
# model-axis mesh (head-striped attention + KV pool, vocab-striped
# embedding, speculation riding along) — the probe re-execs itself with
# XLA_FLAGS=--xla_force_host_platform_device_count=2 on a single-device
# machine, drives staggered concurrent clients, and every stream must be
# bit-identical to the single-chip twin at 1 warm-up trace / 0 retraces,
# with the mesh_shards gauge rendered on /metrics — one JSON line
# (python -m paddle_tpu.serving --smoke-sharded; docs/serving.md
# "Sharded decode")
timeout "$T_SERVE" python -m paddle_tpu.serving --smoke-sharded \
    > "$ART/sharded_smoke.json" 2> "$ART/sharded_smoke.log"
log "sharded smoke rc=$? -> $ART/sharded_smoke.json"

log "phase 20: hierarchical KV smoke (host spill tier + async restore)"
# tiny paged pool + host-RAM spill tier: churn traffic forces the pool
# to evict (and spill) a long block-aligned system-prompt chain, then
# the prompt RETURNS — the engine must restore-hit from the host tier
# and seat by reference with ZERO prefill chunk lanes, the stream
# bit-identical both to its first serving and to a tier-less twin's
# cold recompute, spill/restore counters + the host_tier_bytes gauge
# on /metrics, 1 warm-up trace — one JSON line
# (python -m paddle_tpu.serving --smoke-spill; docs/serving.md
# "Hierarchical KV")
timeout "$T_SERVE" python -m paddle_tpu.serving --smoke-spill \
    > "$ART/spill_smoke.json" 2> "$ART/spill_smoke.log"
log "spill smoke rc=$? -> $ART/spill_smoke.json"

log "phase 21: disaggregated serving smoke (prefill/decode KV handoff)"
# a 2-replica fleet split into a prefill pool and a decode pool behind
# the router: new prompts prefill on one replica, the KV chain crosses
# to the other as a trunk-signed wire blob over POST /v1/kv/export at
# first token, and the decode replica seats it through the existing
# restore pipeline (zero chunk lanes, zero new traces) — streams
# bit-identical to the single-replica oracle, a sub-crossover prompt
# proves the analytic recompute direction, kill -9 of the prefill
# replica mid-handoff falls back to continuation-replay recompute
# bit-identically, kv_handoff counters on both replicas' AND the
# router's /metrics — one JSON line
# (python -m paddle_tpu.serving.router --smoke-disagg; docs/serving.md
# "Disaggregated serving")
timeout "$T_SERVE" python -m paddle_tpu.serving.router --smoke-disagg \
    > "$ART/disagg_smoke.json" 2> "$ART/disagg_smoke.log"
log "disagg smoke rc=$? -> $ART/disagg_smoke.json"

log "phase 22: quantized prefill + int8 trainer smoke (end-to-end low precision)"
# the int8 flash prefill (pallas_prefill_quant forced ON — interpret
# mode off-TPU, the real kernel on-chip) against the fp32 prefill twin
# under the committed logit budget, its int8 cache matching Tp
# sequential decode steps; then 3 steps of the int8 weight-streaming
# trainer (SGD(quant_weights=True)) tracking the f32 twin within
# TRAIN_LOSS_BUDGET — one JSON line
# (python -m paddle_tpu.serving --smoke-quant-prefill; docs/perf.md
# "Int8 flash prefill" / "Int8 weight-streaming trainer")
timeout "$T_SERVE" python -m paddle_tpu.serving --smoke-quant-prefill \
    > "$ART/quant_prefill_smoke.json" 2> "$ART/quant_prefill_smoke.log"
log "quant-prefill smoke rc=$? -> $ART/quant_prefill_smoke.json"

cat > "$ART/WINDOW_DONE" <<EOF2
window completed $(date -u +%Y%m%dT%H%M%SZ) at revision $(git rev-parse --short HEAD 2>/dev/null || echo unknown) (dryrun=$DRY)
the sweep JSONs in this directory hold the live rows.
EOF2

log "done at $(date -u +%Y%m%dT%H%M%SZ); artifacts in $ART — review, update docs/perf.md, commit"

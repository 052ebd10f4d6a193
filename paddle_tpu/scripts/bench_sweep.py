"""Scaling sweep driver: run bench.py over (model, batch) combos.

The reference's benchmark table sweeps batch sizes per model
(benchmark/README.md:33-120); the TPU equivalent sweeps into MXU-saturating
batches (the round-2 verdict's scaling column: ResNet/GoogleNet at bs
256-1024, transformer at >=32k tokens/batch).  Each combo runs as its own
bench.py subprocess, one after another (fresh backend, own watchdog): this
parent never imports JAX, so the chip belongs to the one child that is
running.

Usage:
  python -m paddle_tpu.scripts.bench_sweep [--combos m:b,m:b,...]
      [--steps N] [--timeout S]
  python -m paddle_tpu.scripts.bench_sweep --analytic
      (chip-independent: write the analytic cost/roofline snapshot on the
      CPU backend instead of running live combos — see paddle_tpu/perf/)
Default combos cover the BASELINE.md families at their reference batch
plus the TPU scaling points.
"""

import argparse
import json
import os
import subprocess
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


DEFAULT_COMBOS = [
    # BASELINE.md reference points (bs 64 rows)
    "lstm:64", "lstm256:64", "lstm1280:64",
    "alexnet:64", "googlenet:64", "smallnet:64", "resnet50:32",
    # BASELINE.md batch-scaling rows (benchmark/README.md:33-58,115-135:
    # AlexNet 128/256/512, GoogleNet 128/256, SmallNet 512, LSTM h=256
    # bs128, h=512 bs256) — the TPU column for every published row, not
    # just the 2016 bs-64 points
    "alexnet:128", "alexnet:256", "alexnet:512",
    "googlenet:128", "smallnet:512",
    "lstm256:128", "lstm:256",
    # TPU scaling column
    "resnet50:256", "resnet50:512", "resnet50:1024",
    "googlenet:256", "googlenet:512",
    "lstm1280:256",
    "lstm2048:64",                                # MXU-scale recurrent row
    "transformer_packed_8k:2",                    # 8k-slot packed rows
    "transformer:32", "transformer:128",          # 128*256 = 32768 tok
    "transformer_long:2",                         # 8k-token sequences
    "transformer_packed:16",                      # padding-free packing
    "transformer_moe:16",                         # sparse-expert LM step
    "transformer_decode:32",                      # KV-cached serving path
    "transformer_lm_decode:32",                   # LM sampling throughput
    "transformer_serving:16",                     # bucketed-length stream
    "seq2seq:64",
    "trainer_prefetch:64",                        # input-pipeline overlap
]


def run_combo(model, batch, steps, timeout):
    env = dict(os.environ)
    env["BENCH_MODEL"] = model
    env["BENCH_BATCH"] = str(batch)
    if steps:
        env["BENCH_STEPS"] = str(steps)
    if os.environ.get("BENCH_PROFILE_BASE"):
        # one xprof trace dir per combo, so scripts/xprof_report.py can
        # attribute each family's step time separately
        env["BENCH_PROFILE_DIR"] = os.path.join(
            os.environ["BENCH_PROFILE_BASE"], f"{model}_bs{batch}")
    proc = subprocess.run(
        [sys.executable, os.path.join(_REPO, "bench.py")],
        env=env, cwd=_REPO, timeout=timeout, capture_output=True, text=True)
    line = proc.stdout.strip().splitlines()[-1] if proc.stdout.strip() else ""
    try:
        return json.loads(line)
    except ValueError:
        return {"error": "no_json", "rc": proc.returncode,
                "stderr": proc.stderr[-500:]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--combos", default=",".join(DEFAULT_COMBOS))
    ap.add_argument("--steps", type=int, default=None)
    ap.add_argument("--timeout", type=float, default=1500)
    ap.add_argument("--analytic", action="store_true",
                    help="run the chip-independent analytic snapshot "
                         "(paddle_tpu.perf.analytic, CPU backend) instead "
                         "of live combos")
    ap.add_argument("--analytic-out", default=None,
                    help="snapshot path for --analytic (default: "
                         "BENCH_ANALYTIC_r06.json at the repo root)")
    args = ap.parse_args(argv)

    if args.analytic:
        if _REPO not in sys.path:
            sys.path.insert(0, _REPO)
        from paddle_tpu.perf import analytic
        return analytic.main(["--out", args.analytic_out]
                             if args.analytic_out else [])

    results = {}
    for combo in args.combos.split(","):
        combo = combo.strip()
        if not combo:
            continue
        model, sep, batch = combo.partition(":")
        if not sep or not batch.isdigit() or int(batch) < 1:
            print(f"[sweep] bad combo {combo!r} (want model:batch) — "
                  "skipping", file=sys.stderr, flush=True)
            results[combo] = {"error": "bad_combo"}
            continue
        batch = int(batch)
        print(f"[sweep] {model} bs={batch} ...", file=sys.stderr, flush=True)
        try:
            r = run_combo(model, batch, args.steps, args.timeout)
        except subprocess.TimeoutExpired:
            r = {"error": "sweep_timeout"}
        row = {k: r.get(k) for k in
               ("value", "unit", "vs_baseline", "mfu",
                "tokens_per_s", "error")}
        # keep the diagnostics for failed runs — a crashed combo must stay
        # debuggable from the sweep's one JSON line
        if r.get("error"):
            for k in ("rc", "stderr", "phase", "detail"):
                if r.get(k) is not None:
                    row[k] = r[k]
        results[combo] = row
        print(f"[sweep] {combo}: {row}", file=sys.stderr, flush=True)
        # a backend that cannot come up fails every remaining combo the
        # same way; a combo-specific compile/steps timeout (e.g. an
        # oversized batch) moves on
        if r.get("error") in ("backend_unavailable_timeout",
                              "backend_unavailable"):
            print(f"[sweep] backend unavailable ({r['error']}) — stopping "
                  "sweep", file=sys.stderr)
            break
    print(json.dumps({"sweep": results}), flush=True)
    ok = sum(1 for r in results.values()
             if r.get("value") is not None and not r.get("error"))
    return 0 if ok else 2


if __name__ == "__main__":
    sys.exit(main())

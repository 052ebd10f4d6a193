#!/usr/bin/env python3
"""Run one cell of the benchmark once and print the contract's line.

    python3 benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Finds the cell in BENCHMARK.json, its configuration and traffic files by
name, and the driver the configuration names; refuses to run without the TPU
the cell asks for; measures end-to-end metrics (--trace 0) or per-layer
metrics under the profiler (--trace 1).  Detail goes on earlier lines; the
last line holds ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, traced ``breakdown`` and, where the driver gives them, last,
``compared``: each number compared with its limit — and nothing else.

``--rehearsal`` runs the tiny cells of benchmark/testdata/BENCHMARK.json on
whatever backend is there, marks every line ``"rehearsal": true`` and exits
10 on success, never 0."""

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    from benchmark import harness, peaks
    manifest = os.path.join(harness.HERE, "testdata", "BENCHMARK.json") \
        if args.rehearsal else None
    spec = harness.Spec(manifest)
    cell = spec.cell(args.workload)
    config, traffic = spec.config(cell), spec.traffic(cell)
    driver = spec.driver(config)
    seconds = args.seconds if args.seconds is not None \
        else float(spec.manifest["run_seconds"])

    device = harness.device_gate(cell["chips"], args.rehearsal)
    cache = harness.compile_cache()
    phases = harness.Phases()
    harness.say("run", args.rehearsal, workload=cell["name"], seed=args.seed,
                seconds=seconds, trace=args.trace, device=device,
                compile_cache=cache)
    obs = driver.run({
        "cell": cell, "config": config, "traffic": traffic,
        "seed": args.seed, "seconds": seconds, "trace": bool(args.trace),
        "rehearsal": args.rehearsal, "phases": phases,
        "trace_dir": os.path.join(harness.ROOT, ".bench_trace", cell["name"]),
    })
    harness.say("setup", args.rehearsal, **phases.rows)
    if obs.get("trace_cost"):
        harness.say("trace_cost", args.rehearsal, **obs["trace_cost"])
    # what every reader may want beside the driver's observations; a device
    # without published peaks (a rehearsal's CPU) has no roofline to report
    obs.update(cell=cell, config=config, traffic=traffic,
               peaks=peaks.PEAKS.get(device["kind"]))

    group = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_for(cell, group):
        value = spec.reader(group, m["name"]).read(obs)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    # a driver that runs its reference after the window reads the peak
    # itself, before it: a process's peak never falls again
    device["memory_peak_bytes"] = obs["memory_peak_bytes"] \
        if "memory_peak_bytes" in obs \
        else harness.memory_peak_bytes(obs["devices"])
    result = {"correct": bool(obs["correct"]), "attempted": obs["attempted"],
              "failed": obs["failed"], "metrics": metrics, "device": device}
    if args.trace and obs.get("trace"):
        device["busy_s"] = obs["trace"]["busy_s"]
        device["window_s"] = obs["trace"]["window_s"]
        result["breakdown"] = obs["trace"]["breakdown"]
    if args.rehearsal:
        result["rehearsal"] = True
    if obs.get("compared"):
        # each number the driver compared beside its limit (null: not judged
        # in this run): last in the line, and the last lines of stderr
        result["compared"] = obs["compared"]
        for name, (value, limit) in obs["compared"].items():
            print(f"compared {name}: {value!r} limit {limit!r}",
                  file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    if args.rehearsal:
        return harness.RC_REHEARSAL_OK if result["correct"] else 1
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Is a cell's ``correct`` a verdict on the program, or on the seed?  Run
the cell once a seed, each in a process of its own through ``run.py`` as the
driver runs it, and say how often it was correct and how near its limits.

    python3 benchmark/seed_sweep.py --workload lstm-h512_train --start 3400000001 --seeds 40 --seconds 1

One JSON line a seed: the run's exit code, ``correct``, its ``checks`` line
and the numbers it compared (the result line's ``compared``).  The last line
counts the seeds that were correct and gives, for each compared number, the
smallest margin (limit / reading) and the seed that read it.  ``--also``
adds seeds by name; ``--rehearsal`` runs the tiny cells on the CPU.  Exits 0
when every seed was correct."""

import argparse
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def run_one(workload, seed, seconds, trace, rehearsal, timeout):
    cmd = [sys.executable, os.path.join(HERE, "run.py"), "--workload",
           workload, "--seed", str(seed), "--seconds", str(seconds),
           "--trace", str(trace)] + (["--rehearsal"] if rehearsal else [])
    try:
        r = subprocess.run(cmd, cwd=os.path.dirname(HERE), text=True,
                           capture_output=True, timeout=timeout)
        rc, out, err = r.returncode, r.stdout, r.stderr
    except subprocess.TimeoutExpired as e:      # run() has killed the child
        rc, out, err = 124, e.stdout or "", e.stderr or ""
        out, err = (x.decode() if isinstance(x, bytes) else x
                    for x in (out, err))
    lines = [json.loads(ln) for ln in out.splitlines() if ln.startswith("{")]
    last = lines[-1] if lines and "correct" in lines[-1] else {}
    row = {"seed": seed, "rc": rc, "correct": last.get("correct", False),
           "checks": next((ln["checks"] for ln in lines if "checks" in ln),
                          None),
           "compared": last.get("compared")}
    if not last:
        row["stderr_tail"] = err[-600:]
    return row


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--start", type=int, default=1)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--also", default="", help="more seeds, comma-separated")
    ap.add_argument("--seconds", type=float, default=1.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--timeout", type=float, default=1200.0)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    seeds = [int(s) for s in args.also.split(",") if s] \
        + [args.start + i for i in range(args.seeds)]
    n_correct, smallest = 0, {}
    for seed in seeds:
        row = run_one(args.workload, seed, args.seconds, args.trace,
                      args.rehearsal, args.timeout)
        print(json.dumps(row), flush=True)
        n_correct += bool(row["correct"])
        for name, (value, limit) in (row["compared"] or {}).items():
            if limit is not None and value:
                margin = limit / value
                if name not in smallest or margin < smallest[name][0]:
                    smallest[name] = [margin, seed]
    print(json.dumps({"workload": args.workload, "seeds": len(seeds),
                      "correct": n_correct, "smallest_margin": smallest}),
          flush=True)
    return 0 if n_correct == len(seeds) else 1


if __name__ == "__main__":
    sys.exit(main())

"""xprof post-processor: attribute step time from a jax.profiler trace.

The MFU tuning loop needs to know WHERE a step's time goes before a chip
window opens (round-3 verdict: pre-stage the analysis so the window is
measure-only).  This reads the chrome-trace half of a profile directory
written by `jax.profiler.start_trace` — stdlib-only, no tensorboard
needed — and reports, per device track:

  - busy vs idle time over the traced span (MXU starvation shows as idle)
  - time by category: matmul/conv (MXU), fusion (VPU/elementwise),
    copy/layout, collective (ICI/DCN), infeed/outfeed + host transfer,
    scan/control, other
  - top ops by total duration (the concrete fusion names to chase in a
    real xprof UI)

Usage:
  python -m paddle_tpu.scripts.xprof_report PROFILE_DIR [--top N] [--json]
PROFILE_DIR may be a profile dir (contains plugins/profile/<run>/), a
run dir itself, or a parent of several profile dirs — every run found is
reported.
"""

import argparse
import collections
import glob
import gzip
import json
import os
import re
import sys

# category -> regex over the XLA op/event name (first match wins)
_CATEGORIES = [
    # custom-call first: Pallas kernels lower to it, and the fused-vs-scan
    # trace comparison needs them in their OWN bucket, not scan_control
    ("custom_kernel", re.compile(r"custom-call", re.I)),
    # "convolution" not "conv": the substring would swallow "convert"
    # (dtype casts), inflating the MXU bucket exactly when benching bf16
    ("matmul_conv", re.compile(
        r"dot|convolution|einsum|gemm|mxu", re.I)),
    ("collective", re.compile(
        r"all-reduce|all-gather|reduce-scatter|collective|ppermute|"
        r"all-to-all|send|recv", re.I)),
    ("infeed_host", re.compile(
        r"infeed|outfeed|transfer|h2d|d2h|host", re.I)),
    ("copy_layout", re.compile(
        r"copy|transpose|reshape|bitcast|pad|slice|concatenate", re.I)),
    ("scan_control", re.compile(
        r"while|conditional|\bbody\b|\bcall\b|tuple|scan", re.I)),
    ("fusion_elementwise", re.compile(
        r"fusion|add|multiply|tanh|exp|log|select|compare|reduce|rng|"
        r"broadcast|iota|convert", re.I)),
]

# host-side blocking waits: excluded from the op categories (they nest
# over real op events) but totted up separately — a large host_wait_us is
# the H2D-serialization signal docs/perf.md's tuning table points at
_WAIT = re.compile(
    r"Await|block_until_ready|try_to_block|wait for", re.I)

# host-runtime bookkeeping events that would double-count over the real op
# events nested under them (or alongside them on the same track).
# TfrtCpu* is the newer jax CPU runtime's name for the same executor
# events PjRtCpu* used to carry (TfrtCpuExecutable::Execute nests over
# every real op of the launch — counting it drowned the categories in
# "other" and broke the matmul-attribution assertion on newer jax).
_SKIP = re.compile(
    r"PjitFunction|ExecuteHelper|PjRtCpu|TfrtCpu|ParseArguments|"
    r"CollectGarbage|Handle inputs|holds|ThreadpoolListener|"
    r"CreateOutputs|TransferTo|BufferFromHost|^end: |^Thread |^run_|"
    # python frames ($file:line fn) and executor bookkeeping nest OVER the
    # real op events — counting both would double-book the time and drown
    # the categories in "other"
    r"^\$|ThunkExecutor|toarray",
    re.I)


def categorize(name):
    for cat, rx in _CATEGORIES:
        if rx.search(name):
            return cat
    return "other"


def find_runs(path):
    """Yield every plugins/profile/<run> dir under `path` (which may be the
    run dir itself, a profile dir, or a parent of per-combo profile dirs)."""
    if glob.glob(os.path.join(path, "*.trace.json.gz")):
        return [path]
    runs = sorted(glob.glob(
        os.path.join(path, "**", "plugins", "profile", "*"),
        recursive=True))
    return [r for r in runs if os.path.isdir(r)]


def load_events(run_dir):
    """All chrome-trace events of every host in the run, plus pid->track
    names."""
    events, tracks = [], {}
    for fn in sorted(glob.glob(os.path.join(run_dir, "*.trace.json.gz"))):
        with gzip.open(fn, "rt") as f:
            data = json.load(f)
        for e in data.get("traceEvents", []):
            if e.get("ph") == "M" and e.get("name") == "process_name":
                tracks[e["pid"]] = e["args"]["name"]
            elif e.get("ph") == "X" and e.get("dur") is not None:
                events.append(e)
    return events, tracks


def _merged_busy_us(spans):
    """Total covered time of possibly-overlapping [start, end) spans."""
    busy = 0.0
    last_end = None
    for s, e in sorted(spans):
        if last_end is None or s >= last_end:
            busy += e - s
            last_end = e
        elif e > last_end:
            busy += e - last_end
            last_end = e
    return busy


def report_run(run_dir, top=8):
    events, tracks = load_events(run_dir)
    per_track = collections.defaultdict(list)
    wait_us = collections.Counter()
    for e in events:
        name = e.get("name", "")
        if _WAIT.search(name):
            wait_us[e["pid"]] += e["dur"]
            continue
        if _SKIP.search(name):
            continue
        per_track[e["pid"]].append(e)

    out = {"run": run_dir, "tracks": {}}
    for pid, evs in sorted(per_track.items()):
        tname = tracks.get(pid, str(pid))
        spans = [(e["ts"], e["ts"] + e["dur"]) for e in evs]
        t0 = min(s for s, _ in spans)
        t1 = max(e for _, e in spans)
        wall = t1 - t0
        busy = _merged_busy_us(spans)
        by_cat = collections.Counter()
        by_op = collections.Counter()
        for e in evs:
            by_cat[categorize(e["name"])] += e["dur"]
            by_op[e["name"]] += e["dur"]
        out["tracks"][tname] = {
            "wall_us": round(wall, 1),
            "busy_us": round(busy, 1),
            "host_wait_us": round(wait_us.get(pid, 0.0), 1),
            "idle_pct": round(100.0 * max(wall - busy, 0.0)
                              / max(wall, 1e-9), 1),
            "by_category_us": {k: round(v, 1)
                               for k, v in by_cat.most_common()},
            "top_ops_us": {k: round(v, 1)
                           for k, v in by_op.most_common(top)},
        }
    return out


def render(rep):
    lines = [f"== {rep['run']}"]
    for tname, t in rep["tracks"].items():
        lines.append(f"  track {tname}: wall {t['wall_us'] / 1e3:.2f} ms, "
                     f"busy {t['busy_us'] / 1e3:.2f} ms, "
                     f"idle {t['idle_pct']}%, "
                     f"host waits {t.get('host_wait_us', 0) / 1e3:.2f} ms")
        total = sum(t["by_category_us"].values()) or 1.0
        for cat, us in t["by_category_us"].items():
            lines.append(f"    {cat:<20} {us / 1e3:9.2f} ms "
                         f"({100.0 * us / total:5.1f}%)")
        lines.append("    top ops:")
        for op, us in t["top_ops_us"].items():
            lines.append(f"      {us / 1e3:9.2f} ms  {op[:70]}")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("profile_dir")
    ap.add_argument("--top", type=int, default=8)
    ap.add_argument("--json", action="store_true",
                    help="machine-readable output (one JSON object)")
    ap.add_argument("--write", metavar="BASE",
                    help="write BASE.txt and BASE.json in one pass "
                         "(parse each trace once) instead of printing")
    args = ap.parse_args(argv)
    runs = find_runs(args.profile_dir)
    if not runs:
        print(f"no profile runs under {args.profile_dir}", file=sys.stderr)
        return 2
    reports = [report_run(r, args.top) for r in runs]
    if args.write:
        with open(args.write + ".json", "w") as f:
            json.dump({"reports": reports}, f)
        with open(args.write + ".txt", "w") as f:
            f.write("\n".join(render(r) for r in reports) + "\n")
        print(f"wrote {args.write}.txt + .json ({len(reports)} runs)")
    elif args.json:
        print(json.dumps({"reports": reports}))
    else:
        for r in reports:
            print(render(r))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BrokenPipeError:     # | head is a normal way to use this
        sys.exit(0)

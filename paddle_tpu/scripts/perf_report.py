"""Analytic snapshot tools (chip-independent: counts, not speeds).

  --analytic-diff OLD.json NEW.json   structural regression gate between
      two `bench.py --analytic` snapshots: exits non-zero when a family's
      bytes-accessed inflates, its FLOPs inflate, its HLO op mix shows a
      de-fusion (op counts ballooning / fusions collapsing), or a family
      disappears.  Identical snapshots always pass.
  --analytic-table SNAP.json          render the per-family roofline
      markdown table for docs/perf.md "Analytic roofline".
"""

import argparse
import json

# The gate's thresholds are deliberately loose enough to ride out XLA-
# version churn in op counts and tight enough that a real de-fusion (a
# matmul split into blocks, an elementwise chain falling out of its
# consumer) trips them — tests/test_perf_analytic.py pins both directions.

DIFF_TOLERANCES = {
    "flops_tol": 0.10,     # relative FLOP inflation allowed
    "bytes_tol": 0.25,     # relative bytes-accessed inflation allowed
    "op_total_tol": 0.30,  # relative HLO op-count growth allowed
    "op_abs_min": 4,       # per-op growth below this many ops is noise
    "op_rel_tol": 0.50,    # per-op relative growth allowed (with abs_min)
    "fusion_tol": 0.50,    # fusion-count collapse allowed (with flat total)
}


def _load_snapshot(path):
    with open(path) as f:
        snap = json.load(f)
    if "families" not in snap:
        raise SystemExit(f"{path}: not an analytic snapshot "
                         "(no 'families' key)")
    return snap


def analytic_diff(old, new, **tols):
    """Structural regressions between two analytic snapshots.

    Returns a list of human-readable regression strings (empty = pass).
    Improvements (fewer bytes, fewer ops, more fusion) never flag; only
    the regression direction does, so the gate stays quiet on wins.
    """
    t = dict(DIFF_TOLERANCES)
    t.update(tols)
    regs = []
    old_fams, new_fams = old["families"], new["families"]
    for name in sorted(old_fams):
        o, n = old_fams[name], new_fams.get(name)
        if o.get("error"):
            continue                 # no structural baseline to regress from
        if n is None:
            regs.append(f"{name}: family missing from new snapshot")
            continue
        if n.get("error"):
            regs.append(f"{name}: now fails to build/compile "
                        f"({n['error']})")
            continue
        def _growth(new, old):
            # cost.extract can report 0 for a metric XLA's table omits on
            # some backend/version; a 0 -> nonzero jump is still a
            # reportable regression, never a ZeroDivisionError
            return f"+{new / old - 1:.0%}" if old else "0 -> nonzero"

        if n["flops"] > o["flops"] * (1 + t["flops_tol"]):
            regs.append(
                f"{name}: flops inflated {o['flops']:.3g} -> "
                f"{n['flops']:.3g} ({_growth(n['flops'], o['flops'])} > "
                f"{t['flops_tol']:.0%})")
        if n["bytes_accessed"] > o["bytes_accessed"] * (1 + t["bytes_tol"]):
            regs.append(
                f"{name}: bytes accessed inflated {o['bytes_accessed']:.3g}"
                f" -> {n['bytes_accessed']:.3g} "
                f"({_growth(n['bytes_accessed'], o['bytes_accessed'])} > "
                f"{t['bytes_tol']:.0%})")
        oh, nh = o["hlo_op_histogram"], n["hlo_op_histogram"]
        o_total, n_total = sum(oh.values()), sum(nh.values())
        if n_total > o_total * (1 + t["op_total_tol"]) \
                and n_total - o_total >= t["op_abs_min"]:
            regs.append(
                f"{name}: HLO op count inflated {o_total} -> {n_total} "
                f"({_growth(n_total, o_total)} > {t['op_total_tol']:.0%})"
                " — likely de-fusion")
        # fusions collapsing with the op total flat: XLA materialized a
        # previously-fused chain (ops moved from fusion bodies to top
        # level, so the total barely moves and bytes may stay under
        # bytes_tol) — the third face of de-fusion.  A genuine
        # simplification shrinks the total too, and stays quiet.
        o_fus, n_fus = oh.get("fusion", 0), nh.get("fusion", 0)
        if o_fus - n_fus >= t["op_abs_min"] \
                and n_fus < o_fus * (1 - t["fusion_tol"]) \
                and n_total >= o_total * (1 - t["fusion_tol"]):
            regs.append(
                f"{name}: fusion count collapsed {o_fus} -> {n_fus} with "
                f"op total flat ({o_total} -> {n_total}) — de-fusion")
        for op in sorted(set(oh) | set(nh)):
            oc, nc = oh.get(op, 0), nh.get(op, 0)
            if nc - oc >= t["op_abs_min"] \
                    and nc > oc * (1 + t["op_rel_tol"]):
                regs.append(f"{name}: '{op}' ops {oc} -> {nc} "
                            "— structural change (split/de-fused kernel?)")
    return regs


def analytic_table(snap):
    """Markdown table for docs/perf.md 'Analytic roofline'.

    Rows follow the canonical analytic.FAMILIES order (the committed doc
    table's order), with any unknown names appended sorted — so the
    regeneration command reproduces the committed layout byte-for-byte."""
    try:
        from paddle_tpu.perf.analytic import FAMILIES
        order = [f[0] for f in FAMILIES]
    except ImportError:
        order = []
    names = [n for n in order if n in snap["families"]] \
        + sorted(n for n in snap["families"] if n not in order)
    lines = ["| family | batch | GFLOP/step | MB accessed | FLOP/B | "
             "v5e predicted ms | predicted MFU ≤ | #1 bottleneck |",
             "|---|---|---|---|---|---|---|---|"]
    for name in names:
        r = snap["families"][name]
        if r.get("error"):
            lines.append(f"| {name} | {r.get('batch', '?')} | "
                         f"(error: {r['error'][:60]}) | | | | | |")
            continue
        ai = r["arithmetic_intensity"]
        lines.append(
            f"| {name} | {r['batch']} | {r['flops'] / 1e9:.1f} | "
            f"{r['bytes_accessed'] / 1e6:.0f} | "
            f"{f'{ai:.0f}' if ai is not None else '—'} | "
            f"{r['predicted_ms']:.2f} | "
            f"{r['predicted_mfu'] * 100:.0f}% | {r['bottleneck']} |")
    return "\n".join(lines)


def main(argv=None):
    ap = argparse.ArgumentParser()
    mode = ap.add_mutually_exclusive_group(required=True)
    mode.add_argument("--analytic-diff", nargs=2,
                      metavar=("OLD", "NEW"), default=None)
    mode.add_argument("--analytic-table", default=None, metavar="SNAP")
    ap.add_argument("--bytes-tol", type=float, default=None)
    ap.add_argument("--flops-tol", type=float, default=None)
    args = ap.parse_args(argv)

    if args.analytic_diff:
        old, new = (_load_snapshot(p) for p in args.analytic_diff)
        tols = {}
        if args.bytes_tol is not None:
            tols["bytes_tol"] = args.bytes_tol
        if args.flops_tol is not None:
            tols["flops_tol"] = args.flops_tol
        regs = analytic_diff(old, new, **tols)
        for r in regs:
            print(f"ANALYTIC REGRESSION: {r}")
        if regs:
            print(f"{len(regs)} analytic regression(s) between "
                  f"{args.analytic_diff[0]} and {args.analytic_diff[1]}")
            return 1
        print(f"analytic diff clean: {len(old['families'])} famil"
              f"{'ies' if len(old['families']) != 1 else 'y'} compared")
        return 0

    print(analytic_table(_load_snapshot(args.analytic_table)))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())

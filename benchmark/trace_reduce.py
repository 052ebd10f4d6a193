"""From a profiler trace (.xplane.pb) to numbers: device busy union, idle
share, time per operation, time per program, idle gaps by host event.

The device planes are ``/device:TPU:<n>``; their ``XLA Ops`` line holds one
event per executed HLO operation (a ``while`` holds its body's operations as
nested events), ``XLA Modules`` one per executed program.  Host threads are
the lines of the ``/host:*`` planes, on the same clock."""

import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
_HEAD = re.compile(r"^%?([\w.\-]+)\s*=\s*\(?\s*(\w+\[[\d,]*\])?")


def short_name(hlo_text, limit=80):
    """``%fusion.209 = (f32[1024,512]{1,0:T(8,128)}, ...) fusion(...)`` ->
    ``fusion.209_f32_1024_512_``: the operation and its first output shape,
    in the characters a metric name may have."""
    m = _HEAD.match(hlo_text)
    text = f"{m.group(1)} {m.group(2) or ''}" if m else hlo_text
    return re.sub(r"[^A-Za-z0-9_.\-]", "_", text.strip())[:limit]


def union_seconds(spans):
    """Length of the union of (start, end) intervals, and the merged list."""
    merged = []
    for s, e in sorted(spans):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return sum(e - s for s, e in merged), merged


def nested(events):
    """[(name, start, end)] of one line -> [(name, start, end, seconds in
    directly nested events)].  An event is nested in the open one that holds
    it whole (a ``while`` holds its body's operations)."""
    out, open_ = [], []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while open_ and s >= open_[-1][2]:
            open_.pop()
        if open_ and e <= open_[-1][2]:
            open_[-1][3] += e - s
        row = [name, s, e, 0.0]
        open_.append(row)
        out.append(row)
    return out


def self_times(events):
    """{name: [count, inclusive seconds, self seconds]}; an event's self
    time is its duration minus its directly nested events'."""
    out = {}
    for name, s, e, child in nested(events):
        row = out.setdefault(name, [0, 0.0, 0.0])
        row[0] += 1
        row[1] += e - s
        row[2] += max(0.0, e - s - child)
    return out


def read_xplane(path):
    """{"devices": {plane: {"ops": [(name, s, e)], "modules": [...]}},
    "host": [(name, s, e)]}, seconds on the trace's clock."""
    from jax.profiler import ProfileData
    devices, host = {}, []
    for plane in ProfileData.from_file(path).planes:
        if DEVICE_PLANE.match(plane.name):
            rows = devices.setdefault(plane.name, {"ops": [], "modules": []})
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    rows[key] = [(e.name, e.start_ns * 1e-9,
                                  (e.start_ns + e.duration_ns) * 1e-9)
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events]
    return {"devices": devices, "host": host}


def attribute_gaps(gaps, host, top=2000):
    """Seconds of device idleness by the host event under each gap: the
    innermost (shortest) host event that covers at least half of the gap,
    else the one overlapping it most.  Only the ``top`` longest gaps are
    looked up (a 20 s serving trace holds some 1,300 gaps between steps,
    and they are the idleness that matters); the rest is summed under one
    name."""
    import numpy as np
    by = {}
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])
    rest = sum(e - s for s, e in gaps[top:])
    if rest:
        by["shorter_gaps"] = rest
    if host:
        hs = np.array([h[1] for h in host])
        he = np.array([h[2] for h in host])
    for s, e in gaps[:top]:
        name = "no_host_event"
        if host:
            ov = np.minimum(he, e) - np.maximum(hs, s)
            cand = np.flatnonzero(ov >= 0.5 * (e - s))
            if len(cand):
                name = host[int(cand[np.argmin((he - hs)[cand])])][0]
            elif ov.max() > 0:
                name = host[int(ov.argmax())][0]
        by[name] = by.get(name, 0.0) + (e - s)
    return by


def reduce(data, n_top=10):
    """The numbers the harness and the per-layer readers use.  Busy time and
    the window are averaged over the device planes that ran anything."""
    per_dev, ops, modules, gaps_by = [], {}, {}, {}
    for plane, rows in sorted(data["devices"].items()):
        if not rows["ops"]:
            continue
        spans = [(s, e) for _n, s, e in rows["ops"]]
        busy, merged = union_seconds(spans)
        start, end = merged[0][0], merged[-1][1]
        per_dev.append({"plane": plane, "busy_s": busy,
                        "window_s": end - start})
        for name, (n, inc, slf) in self_times(rows["ops"]).items():
            row = ops.setdefault(name, [0, 0.0, 0.0])
            row[0] += n; row[1] += inc; row[2] += slf
        for name, (n, inc, _s) in self_times(rows["modules"]).items():
            row = modules.setdefault(name, [0, 0.0])
            row[0] += n; row[1] += inc
        gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
        for name, sec in attribute_gaps(gaps, data["host"]).items():
            gaps_by[name] = gaps_by.get(name, 0.0) + sec
    if not per_dev:
        return None
    n = len(per_dev)
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1][2])[:n_top]
    top_gaps = sorted(gaps_by.items(), key=lambda kv: -kv[1])[:n_top]
    return {
        "devices": n,
        "busy_s": sum(d["busy_s"] for d in per_dev) / n,
        "window_s": sum(d["window_s"] for d in per_dev) / n,
        "per_device": per_dev,
        # name -> [count, inclusive s, self s], summed over devices
        "ops": ops,
        "modules": modules,
        "breakdown": {
            "device_ops": [[short_name(k), v[2] / n] for k, v in top_ops],
            "idle_gaps": [[short_name(k), v / n] for k, v in top_gaps]},
    }


def ops_seconds(reduced, pattern, self_time=False):
    """Seconds per device in operations whose HLO text matches ``pattern``;
    inclusive of nested operations unless ``self_time``."""
    rx = re.compile(pattern)
    i = 2 if self_time else 1
    return sum(v[i] for k, v in reduced["ops"].items()
               if rx.search(k)) / reduced["devices"]

"""The program's own loop phases, laid against the device's idle time.

The program annotates the per-step host phases of its two hot loops
(``paddle_tpu/obs/trace.py`` ``phase()``: ``gen.loop.*``, ``engine.step.*``,
``trainer.*``) as ``jax.profiler.TraceAnnotation``s, so a traced run's
``.xplane.pb`` holds them in its ``/host:*`` planes on the same clock as the
device operations.  This module reads them back, with their stats, beside
the intervals in which the device ran anything, and answers the readers in
``layer_metrics/``: how long a phase takes, and how much of the device's
idleness lies under it.

Phases are joined by time and by their ``step`` stat, never by thread: a
supervised serving step runs ``engine.step.*`` on a watchdog's thread.  The
window is ``trace_reduce``'s: first to last device operation of a plane.
A trace without device operations (the CPU rehearsal) gives None; a trace
without phases (a program from before they existed) gives empty answers,
which the readers turn into None."""

import glob
import os
import re
import time

from benchmark import harness, trace_reduce

PHASE = re.compile(r"^(gen\.loop|engine\.step|trainer)\.[a-z]+$")
_loaded = {}        # xplane path -> HostSpans or None, for this process


def intersect_seconds(a, b):
    """Seconds in which both of two sorted lists of disjoint (start, end)
    intervals hold."""
    total, i, j = 0.0, 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


class HostSpans:
    """``phases``: {name: [(start, end, stats)]} in seconds on the trace's
    clock.  ``busy``: per device plane, the merged intervals in which an
    operation ran."""

    def __init__(self, phases, busy):
        self.phases = {k: sorted(v, key=lambda p: p[:2])
                       for k, v in phases.items()}
        self.busy = busy
        self.lo = min(m[0][0] for m in busy)
        self.hi = max(m[-1][1] for m in busy)
        # a plane's idle gaps lie between its first and last operation
        self.gaps = [[(a[1], b[0]) for a, b in zip(m, m[1:])] for m in busy]
        self.window_s = sum(m[-1][1] - m[0][0] for m in busy) / len(busy)
        self.idle_s = sum(e - s for g in self.gaps for s, e in g) / len(busy)

    def durations(self, name):
        """Seconds of every phase ``name`` that started inside the
        window."""
        return [e - s for s, e, _st in self.phases.get(name, ())
                if self.lo <= s < self.hi]

    def union(self, names, steps=None):
        """Merged intervals in which the loop was in one of ``names``
        (of the given ``step``s only, if any are given)."""
        spans = [(s, e) for n in names for s, e, st in self.phases.get(n, ())
                 if steps is None or st.get("step") in steps]
        return trace_reduce.union_seconds(spans)[1]

    def idle_under(self, names):
        """Seconds, per device, in which no operation ran on the device and
        the loop was in one of ``names``.  None if the trace holds no phase
        of the program at all."""
        if not self.phases:
            return None
        held = self.union(names)
        return sum(intersect_seconds(g, held) for g in self.gaps) \
            / len(self.gaps)

    def in_window(self, names, steps=None):
        """Seconds, per device, of the window in which the loop was in one
        of ``names``.  None if the trace holds no phase at all."""
        if not self.phases:
            return None
        held = self.union(names, steps)
        return sum(intersect_seconds([(m[0][0], m[-1][1])], held)
                   for m in self.busy) / len(self.busy)

    def steps_of(self, name):
        return {st.get("step") for _s, _e, st in self.phases.get(name, ())}

    def self_seconds(self, name):
        """{step: seconds} of each phase ``name`` that started inside the
        window, less the other phases of the same ``step`` that lie inside
        it (its children, whatever thread they ran on)."""
        inner = {}
        for other, rows in self.phases.items():
            if other != name:
                for s, e, st in rows:
                    inner.setdefault(st.get("step"), []).append((s, e))
        out = {}
        for s, e, st in self.phases.get(name, ()):
            if self.lo <= s < self.hi:
                step = st.get("step")
                kids = [(a, b) for a, b in inner.get(step, ())
                        if s <= a and b <= e]
                out[step] = out.get(step, 0.0) + (e - s) \
                    - trace_reduce.union_seconds(kids)[0]
        return out


def read(path):
    """The HostSpans of one ``.xplane.pb``, or None if no device plane of
    it ran anything.  One pass: the device planes give intervals only, the
    host planes only the events whose names are the program's phases."""
    from jax.profiler import ProfileData
    phases, busy = {}, []
    for plane in ProfileData.from_file(path).planes:
        if trace_reduce.DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == trace_reduce.OPS_LINE:
                    merged = trace_reduce.union_seconds(
                        [(e.start_ns * 1e-9,
                          (e.start_ns + e.duration_ns) * 1e-9)
                         for e in line.events])[1]
                    if merged:
                        busy.append(merged)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if PHASE.match(e.name):
                        phases.setdefault(e.name, []).append(
                            (e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9,
                             dict(e.stats)))
    return HostSpans(phases, busy) if busy else None


def load(obs):
    """The HostSpans of the traced run that ``obs`` came from: the newest
    ``.xplane.pb`` under ``<root>/.bench_trace/<cell>/``, the directory
    run.py gives the driver.  None without a device trace.  Read once a
    process; the first read says on a detail line what it cost and found."""
    if not obs.get("trace"):
        return None
    files = glob.glob(os.path.join(harness.ROOT, ".bench_trace",
                                   obs["cell"]["name"], "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    path = max(files, key=os.path.getmtime)
    if path not in _loaded:
        t0 = time.perf_counter()
        hs = _loaded[path] = read(path)
        harness.say("host_spans", read_s=time.perf_counter() - t0,
                    phases={k: len(v) for k, v in hs.phases.items()}
                    if hs else None,
                    window_s=hs.window_s if hs else None,
                    idle_s=hs.idle_s if hs else None)
    return _loaded[path]


def idle_share_under(obs, names):
    """100 x idle_under(names) / window, or None: what the four
    ``serve_idle_*_share`` readers and ``train_idle_feed_share`` report."""
    hs = load(obs)
    idle = hs.idle_under(names) if hs else None
    return None if idle is None else 100.0 * idle / hs.window_s

"""What every cell shares: finding a cell's files by name, the device gate,
the compile cache, loading metric readers, tracing a window, the lines."""

import importlib.util
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
RC_NO_DEVICE = 2
RC_REHEARSAL_OK = 10
T_PROCESS_START = time.perf_counter()


class Spec:
    """BENCHMARK.json (or the tiny manifest the rehearsal uses) and the
    files its names lead to.  Nothing about a cell is written in code."""

    def __init__(self, manifest=None, root=ROOT):
        self.root = root
        path = manifest or os.path.join(root, "BENCHMARK.json")
        with open(path) as f:
            self.manifest = json.load(f)
        self.bench_dir = os.path.join(root, self.manifest["paths"][0])

    def _json(self, *parts):
        with open(os.path.join(self.root, *parts)) as f:
            return json.load(f)

    def cell(self, name):
        for c in self.manifest["workloads"]:
            if c["name"] == name:
                return c
        raise KeyError(f"no workload {name!r} in the manifest; it has "
                       f"{[c['name'] for c in self.manifest['workloads']]}")

    def config(self, cell):
        for c in self.manifest["configs"]:
            if c["name"] == cell["config"]:
                return self._json(c["file"])
        raise KeyError(f"workload {cell['name']!r} names config "
                       f"{cell['config']!r}, which the manifest lacks")

    def traffic(self, cell):
        """traffic/<name>.json, or testdata/traffic/<name>.json for the tiny
        mixes of the rehearsal."""
        for sub in ("traffic", os.path.join("testdata", "traffic")):
            path = os.path.join(self.bench_dir, sub, cell["traffic"] + ".json")
            if os.path.exists(path):
                with open(path) as f:
                    return json.load(f)
        raise FileNotFoundError(f"no traffic file {cell['traffic']}.json")

    def metrics_for(self, cell, group):
        """The manifest's ``end_to_end`` or ``per_layer`` entries that this
        cell reports: those that list it, or list no cells at all."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or cell["name"] in m["workloads"]]

    def reader(self, group, name):
        """The metric's own file, <group dir>/<name>.py, loaded by path (a
        metric name may hold dots and dashes)."""
        sub = {"end_to_end": "end_to_end", "per_layer": "layer_metrics"}[group]
        path = os.path.join(self.bench_dir, sub, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_metric_{abs(hash(path))}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def driver(self, config):
        return importlib.import_module(f"benchmark.drivers.{config['driver']}")


def say(kind, rehearsal=False, **fields):
    """One JSON line of detail; never the last line of a run."""
    line = {kind: fields}
    if rehearsal:
        line["rehearsal"] = True
    print(json.dumps(line), flush=True)


def device_gate(chips, rehearsal):
    """The device as JAX reports it, or exit: a run that finds no TPU in the
    peaks table, or fewer chips than the cell asks for, prints no result."""
    import jax
    from benchmark import peaks
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearsal:
        return info
    why = None
    if info["platform"] != "tpu":
        why = (f"no TPU: jax.devices()[0] is platform {info['platform']!r} "
               f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})")
    elif len(devs) < chips:
        why = f"the cell asks for {chips} chips and JAX finds {len(devs)}"
    else:
        try:
            peaks.for_device_kind(info["kind"])
        except KeyError as e:
            why = e.args[0]
    if why:
        print(f"benchmark: {why}", file=sys.stderr, flush=True)
        sys.exit(RC_NO_DEVICE)
    return info


def compile_cache():
    """JAX's persistent compile cache: where JAX_COMPILATION_CACHE_DIR says
    (JAX reads it itself), else the fixed, git-ignored .jax_cache/ of the
    checkout.  Programs of a second and less are cached too, so a warm run
    compiles nothing at all."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def memory_peak_bytes(devices):
    """Peak bytes on the fullest of ``devices``, from the allocator's own
    statistics: the peak of live buffers plus the peak of the region it
    reserves for the compiled programs' temporaries.  (On a TPU
    ``peak_bytes_in_use`` counts only buffers: under the h=512 trainer it
    reads 0.10 GB while 6.88 GB of activations live in the reserved region.)
    None on a backend that reports nothing."""
    per_device = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            per_device.append(stats["peak_bytes_in_use"]
                              + stats.get("peak_bytes_reserved", 0))
    return max(per_device) if per_device else None


class Phases:
    """Seconds of each set-up phase, in order."""

    def __init__(self):
        self._t = time.perf_counter()
        self.rows = {"imports_s": self._t - T_PROCESS_START}

    def mark(self, name):
        now = time.perf_counter()
        self.rows[name + "_s"] = self.rows.get(name + "_s", 0.0) \
            + now - self._t
        self._t = now


class TraceWindow:
    """``with TraceWindow(on, dir) as tw``: the profiler runs for the body;
    afterwards ``tw.reduced`` holds trace_reduce.reduce()'s numbers (None
    when tracing is off or the trace holds no device operation).  The
    profiler's Python tracer is off: it hooks every call of every thread,
    which slowed the host enough to move the host-side numbers (the feed
    read 22% of a step with it and 13% without), and the device planes and
    the runtime's own host events do not need it."""

    def __init__(self, on, out_dir):
        self.on, self.dir, self.reduced = on, out_dir, None
        self.cost = {}      # seconds to stop the profiler, read and reduce

    def __enter__(self):
        if self.on:
            import jax
            import shutil
            shutil.rmtree(self.dir, ignore_errors=True)
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(self.dir, profiler_options=options)
        return self

    def __exit__(self, *exc):
        if not self.on:
            return False
        import glob
        import jax
        from benchmark import trace_reduce
        t0 = time.perf_counter()
        jax.profiler.stop_trace()
        self.cost["stop_s"] = time.perf_counter() - t0
        if exc[0] is None:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            if files:
                t1 = time.perf_counter()
                data = trace_reduce.read_xplane(files[0])
                t2 = time.perf_counter()
                self.reduced = trace_reduce.reduce(data)
                self.cost.update(
                    trace_bytes=os.path.getsize(files[0]),
                    read_s=t2 - t1, reduce_s=time.perf_counter() - t2)
        return False

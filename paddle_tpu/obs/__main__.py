"""Trace smoke CLI: cross-process request tracing over a real fleet.

    python -m paddle_tpu.obs --smoke [--chrome-out PATH]

End-to-end proof of the tracing subsystem over the REAL fleet topology
(docs/observability.md): two tiny demo replicas (tracing enabled via
``--obs-trace``) behind an in-process router (tracing enabled), paced
concurrent streaming ``/v1/generate`` clients, then ``kill -9`` one
replica once every stream is visibly mid-decode.  The checks:

* every stream still finishes (the router's continuation failover);
* ONE trace_id stitches router -> the KILLED replica (its spans come
  from a ``/debug/traces`` snapshot taken while it was alive — the ring
  dies with the process) -> the failover continuation on the surviving
  replica (a ``slot`` span with ``mode="continuation"``);
* the merged Chrome trace-event dump ``json.load``s and names all three
  processes (router + both replicas).

ONE JSON line on stdout; nonzero rc on any failed check (the same
contract as the serving/chaos/fleet smokes).
"""

import argparse
import http.client
import json
import signal
import sys
import tempfile
import threading
import time
import urllib.request

from paddle_tpu.obs import trace
from paddle_tpu.utils.logging import logger


def _get_json(url, timeout=20):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def _merge_spans(snapshots):
    """Merge span lists from several /debug/traces payloads, newest
    completed version of each span_id winning (a pre-kill snapshot and a
    post-run snapshot overlap for the surviving replica)."""
    by_id = {}
    for spans in snapshots:
        for s in spans:
            cur = by_id.get(s["span_id"])
            if cur is None or (cur["t_end"] is None
                               and s["t_end"] is not None):
                by_id[s["span_id"]] = s
    return list(by_id.values())


def _smoke(chrome_out=None):
    from paddle_tpu.serving.fleet import ReplicaSupervisor
    from paddle_tpu.serving.router import Router

    errs = []
    out = {"metric": "trace smoke (cross-process request tracing, "
                     "kill -9 mid-stream)",
           "vs_baseline": None}
    n_clients, n_tokens = 4, 24
    # the injected decode-step hang paces tokens (~25ms each) so the
    # kill reliably lands MID-stream, exactly like the fleet smoke
    extra = ["--gen-slots", "4", "--gen-max-len", "64",
             "--gen-max-tokens", str(n_tokens),
             "--obs-trace", "1",
             "--fault-spec",
             "serving.decode_step:every=1,action=hang,hang_s=0.025"]
    trace.enable(sample=1.0, capacity=4096, process="router")
    sup = ReplicaSupervisor(n_replicas=2, extra_args=extra,
                            backoff_base_s=0.3, seed=0,
                            name="trace_smoke")
    router = Router(supervisor=sup, poll_interval_s=0.1,
                    eject_threshold=2, eject_cooldown_s=1.0,
                    retry_budget=3, name="router_trace_smoke")
    httpd = None
    checks = []
    try:
        sup.start()
        if not sup.wait_ready(timeout=240):
            raise RuntimeError("replicas never became ready")
        httpd = router.start(port=0)
        deadline = time.monotonic() + 30
        while not router.ready() and time.monotonic() < deadline:
            time.sleep(0.05)
        import numpy as np
        rng = np.random.RandomState(0)
        prompts = [rng.randint(1, 256, 3 + 2 * i).tolist()
                   for i in range(n_clients)]
        results = [None] * n_clients
        first_token = threading.Barrier(n_clients + 1, timeout=120)

        def hit(i):
            armed = True
            try:
                conn = http.client.HTTPConnection("127.0.0.1", httpd.port,
                                                  timeout=120)
                conn.request(
                    "POST", "/v1/generate",
                    json.dumps({"prompt": prompts[i],
                                "max_tokens": n_tokens,
                                "stream": True}).encode(),
                    {"Content-Type": "application/json"})
                resp = conn.getresponse()
                toks, done = [], None
                while True:
                    line = resp.readline()
                    if not line:
                        break
                    rec = json.loads(line)
                    if "token" in rec:
                        toks.append(rec["token"])
                        if armed and len(toks) >= 2:
                            armed = False
                            first_token.wait()
                    if rec.get("done"):
                        done = rec
                        break
                conn.close()
                if armed:
                    first_token.wait()
                results[i] = {"tokens": toks, "done": done}
            except Exception as e:      # noqa: BLE001
                errs.append(f"client {i}: {type(e).__name__}: {e}")
                if armed:
                    try:
                        first_token.wait()
                    except threading.BrokenBarrierError:
                        pass

        threads = [threading.Thread(target=hit, args=(i,))
                   for i in range(n_clients)]
        for t in threads:
            t.start()
        first_token.wait()      # every stream is mid-decode now

        # the victim's span ring dies with its process: snapshot every
        # replica's /debug/traces BEFORE the kill (in-flight spans show
        # with t_end null — the victim still holds its streams' slots)
        pre = {}
        for rid, url in sup.endpoints():
            try:
                pre[rid] = _get_json(f"{url}/debug/traces")
            except Exception as e:      # noqa: BLE001
                errs.append(f"pre-kill /debug/traces {rid}: {e}")
        sup.kill("r0", signal.SIGKILL)
        out["victim_killed"] = True

        for t in threads:
            t.join(180)
        streams_ok = sum(1 for r in results
                         if r is not None and r["done"])
        out["streams_ok"] = streams_ok

        # post-run snapshots: router (in-process) + whoever answers now
        snapshots = [trace.debug_payload()["spans"]]
        processes_seen = {"router"}
        for payload in pre.values():
            snapshots.append(payload.get("spans", []))
            if payload.get("process"):
                processes_seen.add(payload["process"])
        for rid, url in sup.endpoints():
            try:
                payload = _get_json(f"{url}/debug/traces")
            except Exception:   # noqa: BLE001 — a replica mid-restart
                continue
            snapshots.append(payload.get("spans", []))
            if payload.get("process"):
                processes_seen.add(payload["process"])
        merged = _merge_spans(snapshots)
        out["spans_merged"] = len(merged)

        # a stream that failed over mid-decode: its router root span
        # carries the midstream_failover event; the same trace_id must
        # show spans from the router AND (at least) both original
        # replicas — the kill victim's half from the pre-kill snapshot
        failover_tids = {
            s["trace_id"] for s in merged
            if s["process"] == "router" and s["name"] == "router.request"
            and any(e["name"] == "midstream_failover"
                    for e in s.get("events", ()))}
        out["failover_traces"] = len(failover_tids)
        stitched = False
        stitched_detail = {}
        for tid in failover_tids:
            tspans = [s for s in merged if s["trace_id"] == tid]
            procs = {s["process"] for s in tspans}
            router_names = {s["name"] for s in tspans
                            if s["process"] == "router"}
            # the FIRST replica held the original seat (a slot span with
            # mode="prefill", captured pre-kill); the survivor holds the
            # failover seat (mode="continuation")
            first_proc = next((s["process"] for s in tspans
                               if s["name"] == "slot"
                               and s["attrs"].get("mode") == "prefill"),
                              None)
            cont_proc = next((s["process"] for s in tspans
                              if s["name"] == "slot"
                              and s["attrs"].get("mode")
                              == "continuation"), None)
            first_names = {s["name"] for s in tspans
                           if s["process"] == first_proc}
            if (len(procs) >= 3 and first_proc and cont_proc
                    and first_proc != cont_proc
                    and {"router.request", "router.dispatch",
                         "router.leg"} <= router_names
                    and {"server.request", "gen.queue_wait",
                         "slot"} <= first_names):
                stitched = True
                stitched_detail = {
                    "trace_id": tid,
                    "processes": sorted(procs),
                    "n_spans": len(tspans),
                }
                break
        out["stitched"] = bool(stitched)
        out.update(stitched_detail)

        # the merged Chrome dump must parse and name all three processes
        if chrome_out is None:
            with tempfile.NamedTemporaryFile(
                    prefix="trace_smoke_", suffix=".json",
                    delete=False) as f:
                chrome_out = f.name
        trace.dump_chrome_trace(chrome_out, merged)
        with open(chrome_out) as f:
            chrome = json.load(f)
        proc_names = {e["args"]["name"] for e in chrome["traceEvents"]
                      if e.get("ph") == "M"
                      and e.get("name") == "process_name"}
        out["chrome_out"] = chrome_out
        out["chrome_parses"] = True
        out["chrome_processes"] = len(proc_names)
        checks = [
            streams_ok == n_clients,
            bool(stitched),
            len(proc_names) >= 3,
            bool(chrome["traceEvents"]),
        ]
    except Exception as e:      # noqa: BLE001 — a harness failure must
        errs.append(f"smoke: {type(e).__name__}: {e}")
        checks = [False]
    finally:
        try:
            router.close()
        finally:
            sup.stop()
    out["value"] = sum(bool(c) for c in checks)
    out["unit"] = f"checks_ok/{len(checks)}"
    if errs:
        out["errors"] = errs[:5]
    print(json.dumps(out), flush=True)
    return 0 if all(checks) else 2


def main(argv=None):
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.obs",
        description="trace smoke: cross-process request tracing over a "
                    "2-replica fleet with a kill -9 mid-stream failover")
    ap.add_argument("--smoke", action="store_true",
                    help="run the trace smoke, print one JSON line, exit")
    ap.add_argument("--chrome-out",
                    help="where the merged Chrome trace-event JSON is "
                         "written (default: a temp file)")
    args = ap.parse_args(argv)
    if args.smoke:
        return _smoke(chrome_out=args.chrome_out)
    ap.error("pass --smoke")


if __name__ == "__main__":
    logger.setLevel("WARNING")
    sys.exit(main())

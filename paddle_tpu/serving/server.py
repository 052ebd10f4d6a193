"""Stdlib HTTP front-end + CLI for the serving runtime.

The reference served models from C++ services over the C API; the
TPU-native runtime's front door is a dependency-free JSON/HTTP server on
``http.server.ThreadingHTTPServer`` — each connection thread blocks on its
request's Future while the single batcher thread forms engine batches, so
concurrency comes from the batcher, not from the HTTP layer.

Endpoints:
  POST /v1/infer   {"feed": {slot: array}, "deadline_ms": optional}
                   -> {"outputs": ..., "latency_ms": ...}
                   errors map to status codes: invalid feed/JSON 400,
                   overload 429, shutdown/breaker 503, deadline 504,
                   batch failure 500 — always a JSON body with "error";
                   429/503 carry a Retry-After header (breaker- and
                   queue-depth-derived; docs/serving.md §6).
  POST /v1/generate {"prompt": [ids], "max_tokens": N, "eos_id": opt,
                    "deadline_ms": opt, "stream": false}
                   -> {"tokens": [...], "finish_reason": "eos"|"length",
                       "ttft_ms": ..., "latency_ms": ...}
                   "stream": true streams newline-delimited JSON chunks
                   ({"token": id} per emitted token, then a {"done":
                   true, ...} record) over chunked transfer encoding —
                   continuous-batching generation (decode_engine.py,
                   docs/serving.md §4); same error-code mapping.
  GET  /healthz    LIVENESS: 200 while the process is alive (even
                   draining — a balancer uses /readyz to route)
  GET  /readyz     READINESS: 200 when warm-up is complete, the circuit
                   breaker is closed, and no drain has begun; 503 (with
                   the blocking reasons and Retry-After) otherwise
  POST /v1/kv/export {"tokens": [ids]}
                   -> the longest resident KV coverage of that prefix as
                   one length-prefixed spill-format blob (application/
                   octet-stream), serialized by the batcher worker
                   strictly BETWEEN decode steps; 404 = no coverage (the
                   peer recomputes).  The disaggregated-serving
                   transport (serving/transfer.py; docs/serving.md
                   "Disaggregated serving").
  GET  /metrics    Prometheus text (serving/metrics.py)
  GET  /debug/traces  recent request spans + slowest-request trace_ids
                   (obs/trace.py; {"enabled": false} when tracing is
                   off — enable with --obs-trace; docs/observability.md)

CLI (``python -m paddle_tpu.serving``):
  --artifact model.shlo            one-bucket exported artifact
  --artifacts 'model.b*.shlo'      bucket ladder (export.export_bucketed)
  --demo                           built-in tiny MLP (smoke/bring-up)
  --demo-generate                  built-in tiny LM trunk behind the
                                   continuous-batching /v1/generate
  --buckets 1,4,16 --port N --max-delay-ms --queue-size --deadline-ms
  --gen-slots --gen-max-len --gen-max-tokens
  --smoke                          self-test: ephemeral port, concurrent
                                   requests, /metrics sanity, ONE JSON
                                   line, exit code
  --smoke-generate                 generation self-test: concurrent
                                   staggered /v1/generate requests,
                                   streaming, EOS early-finish, ONE JSON
                                   line
  --kv-layout slab|paged           decode KV-cache layout (paged = block
                                   pool + prefix sharing, kv_pool.py)
  --kv-block-size --kv-num-blocks --kv-prefix-cache
  --kv-host-bytes N                host-RAM spill-tier cap: evicted
                                   prefix chains spill to host and
                                   restore asynchronously on the next
                                   hit (0 = tier off; docs/serving.md
                                   "Hierarchical KV")
  --smoke-paged                    paged-KV self-test: shared-system-
                                   prompt clients, prefix hits + CoW
                                   fork, streams bit-identical to the
                                   slab twin, ONE JSON line
  --smoke-spill                    hierarchical-KV self-test: churn
                                   evicts the shared chain, the
                                   returning prefix restore-hits with
                                   zero chunk lanes, bit-identical to
                                   the tier-less twin, ONE JSON line
  --role prefill|decode|mixed      disaggregated-serving role advertised
                                   on /metrics (serving_role{role=...}):
                                   the router prefers prefill replicas
                                   for new prompts and hands streams to
                                   decode replicas at the first token,
                                   shipping the KV chain over
                                   /v1/kv/export (serving/transfer.py;
                                   docs/serving.md "Disaggregated
                                   serving"); mixed (default) = both
  --prefill-chunk K                token lanes of the one decode step:
                                   prompt ingestion rides it as K-token
                                   chunks (docs/serving.md "Chunked
                                   prefill")
  --kv-dtype float32|int8          quantized KV cache (int8 + per-head
                                   scale sidecars; paged auto-sizing
                                   doubles the block count at equal
                                   bytes — docs/serving.md "Quantized
                                   serving")
  --quant-weights 1                per-channel int8 trunk weights
                                   (quant/weights.py)
  --smoke-quant                    quantized-serving self-test: int8-KV
                                   engine within the committed quality
                                   budget vs the fp32 twin, int8+weights
                                   exact vs the quantized oracle,
                                   kv_blocks_total doubled, ONE JSON
                                   line
  --smoke-quant-prefill            end-to-end low-precision self-test:
                                   int8 flash prefill within the logit
                                   budget vs the fp32 twin, int8 cache
                                   bit-exact vs sequential steps, int8
                                   trainer 3-step loss parity, ONE JSON
                                   line
  --speculate-k K                  speculative decoding: a truncated-
                                   trunk draft proposes K tokens per
                                   slot, the one chunked step scores
                                   every lane, each step nets >= 1
                                   token; streams stay token-identical
                                   (docs/serving.md "Speculative
                                   decoding")
  --draft-layers N                 trunk depth of the derived draft
                                   (embedding/vocab shared)
  --smoke-speculative              speculative-decoding self-test: spec
                                   engine vs a non-spec twin, streams
                                   bit-identical, acceptance evidence
                                   in /metrics, ONE JSON line
  --mesh-shards N                  tensor-parallel sharded decode: the
                                   one chunked step runs under an
                                   N-chip model-axis mesh (head-striped
                                   attention + KV pool, vocab-striped
                                   embedding; docs/serving.md "Sharded
                                   decode"); 0/1 = single-chip
  --smoke-sharded                  sharded-decode self-test: n=2 forced
                                   host mesh (re-execs itself with
                                   XLA_FLAGS when single-device),
                                   staggered concurrent streams
                                   bit-identical to the single-chip
                                   twin, mesh evidence in /metrics, ONE
                                   JSON line

The JSON front-end serves plain-array feed slots (dense/index vectors);
structured SequenceBatch slots are an in-process engine feature.
SIGTERM drains gracefully: stop admissions, finish queued requests,
answer in-flight connections, then exit.
"""

import argparse
import json
import queue as _queue
import signal
import sys
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

import numpy as np
import jax

from paddle_tpu.obs import trace as obstrace
from paddle_tpu.resilience.supervisor import (BreakerOpenError, Supervisor,
                                              retry_transient)
from paddle_tpu.serving.batcher import (Batcher, DeadlineExceededError,
                                        OverloadedError, ShutdownError)
from paddle_tpu.serving.engine import InferenceEngine, InvalidRequestError
from paddle_tpu.serving import transfer as kv_transfer
from paddle_tpu.utils.logging import log_context, logger

_STATUS = ((InvalidRequestError, 400), (OverloadedError, 429),
           (BreakerOpenError, 503), (ShutdownError, 503),
           (DeadlineExceededError, 504))


def _retry_after_for(e, metrics, drain_timeout_s=None):
    """Retry-After seconds for a shedding response (429/503), derived
    from the shedding cause: breaker -> its remaining cooldown; overload
    -> expected queue drain time (depth x recent p50 batch time);
    drain -> the EFFECTIVE drain deadline (the --drain-timeout-s the
    server was started with, not the raw flag — the process is going
    away within that window)."""
    if isinstance(e, BreakerOpenError):
        return max(1, int(round(e.retry_after_s + 0.5)))
    if isinstance(e, OverloadedError):
        p50 = depth = 0
        if metrics is not None:
            # inference plane: per-batch engine time.  Generation plane:
            # batch_time only sees prefill batches (decode time lands in
            # tpot), so fall back to the request WALL latency — an over-
            # estimate under load, which errs toward clients backing off
            # longer (the safe direction), capped below.
            p50 = metrics.batch_time.percentiles((50,)).get(50, 0.0) \
                or metrics.latency.percentiles((50,)).get(50, 0.0)
            depth = metrics.queue_depth()
        return max(1, min(30, int(round(depth * p50 + 0.5))))
    if isinstance(e, ShutdownError):
        if drain_timeout_s is None:
            from paddle_tpu.utils.flags import FLAGS
            drain_timeout_s = FLAGS.serving_drain_timeout_s
        return max(1, int(drain_timeout_s))
    return None


def _json_to_row(engine, obj):
    """JSON feed dict -> per-row numpy feed matching the engine spec
    (dtype cast here; shape checking is the engine's job)."""
    if not isinstance(obj, dict):
        raise InvalidRequestError("'feed' must be an object of "
                                  "{slot: array}")
    spec_row = engine.bucket_spec(1)
    if not isinstance(spec_row, dict):
        raise InvalidRequestError(
            "this model's feed is not a flat dict; the JSON front-end "
            "serves plain-array slots only")
    row = {}
    for name, sds in spec_row.items():
        if not isinstance(sds, jax.ShapeDtypeStruct):
            raise InvalidRequestError(
                f"feed slot {name!r} is structured (SequenceBatch); the "
                "JSON front-end serves plain-array slots only")
        if name not in obj:
            raise InvalidRequestError(f"missing feed slot {name!r}")
        try:
            row[name] = np.asarray(obj[name], dtype=sds.dtype)
        except (TypeError, ValueError) as e:
            raise InvalidRequestError(
                f"feed slot {name!r}: cannot convert to {sds.dtype}: {e}") \
                from e
    extra = sorted(set(obj) - set(spec_row))
    if extra:
        raise InvalidRequestError(f"unknown feed slot(s) {extra}")
    return row


def _to_jsonable(tree):
    return jax.tree_util.tree_map(lambda a: np.asarray(a).tolist(), tree)


class ServingHandler(BaseHTTPRequestHandler):
    # one server == one model; the batcher hangs off the server object
    protocol_version = "HTTP/1.1"
    # the request's root span (obs/trace.py), set by do_POST; GETs and
    # disabled tracing leave the NULL singleton (empty trace_id)
    _obs = obstrace.NULL

    def log_message(self, fmt, *args):   # route access logs to our logger
        logger.debug("http: " + fmt, *args)

    def _reply(self, code, payload, content_type="application/json",
               headers=None):
        body = (payload if isinstance(payload, bytes)
                else json.dumps(payload).encode())
        self.send_response(code)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        if self._obs.trace_id:
            self.send_header("X-Trace-Id", self._obs.trace_id)
        for k, v in (headers or {}).items():
            self.send_header(k, str(v))
        self.end_headers()
        self.wfile.write(body)

    # ------------------------------------------------------------ GET

    def do_GET(self):
        # keep-alive: one handler instance serves several requests, so
        # drop any previous POST's span before replying
        self._obs = obstrace.NULL
        # one server serves an inference batcher, a generation batcher,
        # or both; health/metrics report whichever exists.  Liveness vs
        # readiness (docs/serving.md §6): /healthz answers "is the
        # process alive" — 200 as long as we can answer at all, so an
        # orchestrator never kills a node that is merely draining or
        # warming; /readyz answers "should a balancer route here" — 503
        # while warm-up is incomplete, the circuit breaker is open, or a
        # drain has begun on EITHER plane.
        batchers = [b for b in (self.server.batcher,
                                self.server.gen_batcher) if b is not None]
        batcher = batchers[0]
        if self.path == "/healthz":
            draining = any(b.closed for b in batchers)
            engine = batcher.engine
            self._reply(200, {
                "status": "ok",
                "draining": draining,
                "model": engine.name,
                "buckets": list(getattr(engine, "buckets", ())),
                "queue_depth": batcher.metrics.queue_depth(),
            })
        elif self.path == "/readyz":
            reasons = []
            retry_after = 1.0
            for b in batchers:
                if b.closed:
                    reasons.append("draining")
                    # the process is going away within the drain window
                    retry_after = max(
                        retry_after,
                        getattr(self.server, "drain_timeout_s", None)
                        or 1.0)
                elif not b.engine.ready:
                    reasons.append("warming")
                elif not b.ready:
                    # warm and was accepting: either the breaker is open
                    # (supervised generation plane) or a close() raced
                    # these checks (any plane — report it as the drain
                    # it is)
                    sup = getattr(b, "supervisor", None)
                    if sup is not None \
                            and sup.breaker.state != "closed":
                        reasons.append("breaker_open")
                        retry_after = max(
                            retry_after,
                            sup.breaker.seconds_until_probe())
                    else:
                        reasons.append("draining")
            reasons = sorted(set(reasons))
            if reasons:
                self._reply(503, {"status": "unready", "reasons": reasons},
                            headers={"Retry-After":
                                     max(1, int(round(retry_after)))})
            else:
                self._reply(200, {"status": "ready"})
        elif self.path == "/metrics":
            self._reply(200, batcher.metrics.render_prometheus().encode(),
                        content_type="text/plain; version=0.0.4")
        elif self.path == "/debug/traces":
            # recent spans + the slowest recent requests' trace_ids
            # (obs/trace.py; {"enabled": false, ...} when tracing is off)
            self._reply(200, obstrace.debug_payload())
        else:
            self._reply(404, {"error": f"no route {self.path!r}"})

    # ------------------------------------------------------------ POST

    def _read_json(self):
        length = int(self.headers.get("Content-Length") or 0)
        try:
            req = json.loads(self.rfile.read(length) or b"")
        except ValueError as e:
            raise InvalidRequestError(f"malformed JSON: {e}") from e
        if not isinstance(req, dict):
            raise InvalidRequestError("body must be a JSON object")
        return req

    @staticmethod
    def _deadline_ms(req):
        deadline_ms = req.get("deadline_ms")
        if deadline_ms is not None and (
                not isinstance(deadline_ms, (int, float))
                or deadline_ms <= 0):
            raise InvalidRequestError("deadline_ms must be a positive "
                                      "number")
        return deadline_ms

    def _error_reply(self, e, metrics=None):
        for etype, code in _STATUS:
            if isinstance(e, etype):
                break
        else:
            code = 500
        headers = {}
        if code in (429, 503):
            ra = _retry_after_for(
                e, metrics,
                drain_timeout_s=getattr(self.server, "drain_timeout_s",
                                        None))
            if ra is not None:
                headers["Retry-After"] = ra
        self._reply(code, {"error": f"{type(e).__name__}: {e}"},
                    headers=headers)

    def _submit_retrying(self, batcher, fn):
        """Submit with the bounded transient-failure retry policy
        (resilience/supervisor.py): exponential backoff + jitter, budget
        from the resilience_retry_budget flag, retries counted into
        /metrics.  Safe because submit's fault point fires before any
        queue mutation (idempotent failed attempts)."""
        from paddle_tpu.utils.flags import FLAGS
        return retry_transient(
            fn, budget=FLAGS.resilience_retry_budget,
            on_retry=lambda _a, _e: batcher.metrics.observe_retry())

    def do_POST(self):
        # root span for this request (obs/trace.py): a propagated
        # traceparent (the router's dispatch) CONTINUES that trace — one
        # trace_id then stitches router, every failover leg, and the
        # slot timeline; a direct client starts a fresh trace.  The
        # trace_id doubles as the log correlation id (log_context), is
        # echoed in the response body and the X-Trace-Id header.
        ctx = obstrace.extract(self.headers.get("traceparent"))
        with obstrace.span("server.request", ctx=ctx, root=True,
                           route=self.path) as sp, \
                log_context(trace_id=sp.trace_id,
                            request_id=sp.span_id):
            self._obs = sp
            self._route_post()

    def _route_post(self):
        if self.path == "/v1/generate":
            self._post_generate()
            return
        if self.path == kv_transfer.EXPORT_PATH:
            self._post_kv_export()
            return
        if self.path != "/v1/infer":
            self._reply(404, {"error": f"no route {self.path!r}"})
            return
        t0 = time.perf_counter()
        batcher = self.server.batcher
        if batcher is None:
            self._reply(404, {"error": "no inference model is being "
                                       "served (generation-only server)"})
            return
        try:
            req = self._read_json()
            if "feed" not in req:
                raise InvalidRequestError('body must be {"feed": {...}}')
            deadline_ms = self._deadline_ms(req)
            row = _json_to_row(batcher.engine, req["feed"])
            fut = self._submit_retrying(
                batcher, lambda: batcher.submit(row,
                                                deadline_ms=deadline_ms))
            # bounded wait: batch errors surface here; the timeout is a
            # backstop against a wedged engine, not a policy knob (use
            # deadline_ms for per-request deadlines)
            out = fut.result(timeout=600)
            resp = {
                "outputs": _to_jsonable(out),
                "latency_ms": round((time.perf_counter() - t0) * 1e3, 3),
            }
            if self._obs.trace_id:
                resp["trace_id"] = self._obs.trace_id
            self._reply(200, resp)
        except Exception as e:    # noqa: BLE001 — every error is a response
            self._error_reply(e, metrics=batcher.metrics)

    # ----------------------------------------------------- POST kv export

    def _post_kv_export(self):
        """Disaggregated-serving SOURCE side (serving/transfer.py;
        docs/serving.md "Disaggregated serving"): a peer decode replica
        asks for our longest resident KV coverage of a token prefix.
        The gather reads the committed (donated) cache, which belongs to
        the batcher worker thread, so the worker serializes the chain
        strictly BETWEEN decode steps (``GenerationBatcher.
        export_chain``); this handler only ships the resulting blob —
        8-byte little-endian length prefix + payload, bounded chunks."""
        from paddle_tpu.utils.flags import FLAGS
        gen = self.server.gen_batcher
        if gen is None:
            self._reply(404, {"error": "no generation plane on this "
                                       "replica: nothing to export"})
            return
        try:
            req = self._read_json()
            tokens = req.get("tokens")
            if not isinstance(tokens, list) or not tokens \
                    or not all(isinstance(t, int) for t in tokens):
                raise InvalidRequestError(
                    "'tokens' must be a non-empty list of int token ids")
        except Exception as e:   # noqa: BLE001 — every error is a response
            self._error_reply(e, metrics=gen.metrics)
            return
        key, covered, blob = gen.export_chain(
            tokens, timeout=FLAGS.serving_handoff_timeout_s)
        if blob is None:
            # no resident coverage (evicted, never prefilled here, or
            # the export timed out behind a wedged step): the peer falls
            # back to recompute — this 404 is an outcome, not a failure
            self._reply(404, {"error": "no resident KV coverage for the "
                                       "requested tokens"})
            return
        self.send_response(200)
        self.send_header("Content-Type", "application/octet-stream")
        # the length prefix travels INSIDE the body so the framing is
        # transport-independent; read_blob re-checks the declared length
        # against the receiver's own bound before buffering toward it
        self.send_header("Content-Length", str(8 + len(blob)))
        self.send_header("X-KV-Covered", str(int(covered)))
        if self._obs.trace_id:
            self.send_header("X-Trace-Id", self._obs.trace_id)
        self.end_headers()
        kv_transfer.write_blob(self.wfile, blob)
        gen.metrics.observe_kv_handoff("sent", len(blob))

    def _receive_handoff(self, gen, hint):
        """Disaggregated-serving RECEIVE side: the router attached a
        ``{"source": url, "tokens": [ids]}`` hint naming the prefill
        replica that holds this stream's KV.  Fetch + verify + park the
        chain in the host tier BEFORE admission, so the request's
        ordinary seat probe restore-hits it through the existing
        claim/stage/commit pipeline.  ANY failure — dead peer, foreign
        or oversized blob, the analytic model preferring recompute, a
        malformed hint — is the recompute fallback, never a client
        error."""
        from paddle_tpu.utils.flags import FLAGS
        if not FLAGS.serving_handoff:
            gen.metrics.observe_kv_handoff("fallback")
            return {"outcome": "fallback", "bytes": 0, "covered": 0,
                    "ms": 0.0, "reason": "disabled"}
        source = hint.get("source") if isinstance(hint, dict) else None
        tokens = hint.get("tokens") if isinstance(hint, dict) else None
        if not isinstance(source, str) \
                or not isinstance(tokens, list) or not tokens \
                or not all(isinstance(t, int) for t in tokens):
            gen.metrics.observe_kv_handoff("fallback")
            return {"outcome": "fallback", "bytes": 0, "covered": 0,
                    "ms": 0.0, "reason": "malformed_hint"}
        return kv_transfer.receive_chain(
            gen.engine, source, tokens, metrics=gen.metrics,
            max_bytes=FLAGS.serving_handoff_max_bytes,
            timeout=FLAGS.serving_handoff_timeout_s)

    # ------------------------------------------------------- POST generate

    def _post_generate(self):
        t0 = time.perf_counter()
        gen = self.server.gen_batcher
        if gen is None:
            self._reply(404, {"error": "no generation model is being "
                                       "served (start with "
                                       "--demo-generate or wire a "
                                       "GenerationBatcher)"})
            return
        try:
            req = self._read_json()
            if "prompt" not in req:
                raise InvalidRequestError('body must be {"prompt": [ids]}')
            prompt = req["prompt"]
            if not isinstance(prompt, list) or not prompt \
                    or not all(isinstance(t, int) for t in prompt):
                raise InvalidRequestError(
                    "'prompt' must be a non-empty list of int token ids")
            try:
                prompt = np.asarray(prompt, np.int64)
            except (OverflowError, ValueError) as e:
                # Python ints are unbounded; an id past int64 is a
                # malformed request, not a server error
                raise InvalidRequestError(
                    f"prompt ids out of range: {e}") from e
            deadline_ms = self._deadline_ms(req)
            replay = req.get("replay")
            if replay is not None:
                # mid-stream continuation (a router failing over off a
                # dead replica, docs/serving.md §7): these tokens were
                # already delivered — teacher-forced, never re-emitted
                if not isinstance(replay, list) or not replay \
                        or not all(isinstance(t, int) for t in replay):
                    raise InvalidRequestError(
                        "'replay' must be a non-empty list of int token "
                        "ids")
                try:
                    replay = np.asarray(replay, np.int64)
                except (OverflowError, ValueError) as e:
                    raise InvalidRequestError(
                        f"replay ids out of range: {e}") from e
            # disaggregated handoff (serving/transfer.py): pull the
            # stream's KV off the named prefill replica before admission
            handoff = None
            if req.get("kv_handoff") is not None:
                handoff = self._receive_handoff(gen, req["kv_handoff"])
            kw = dict(max_tokens=req.get("max_tokens"),
                      eos_id=req.get("eos_id"), deadline_ms=deadline_ms,
                      replay=replay)
            if req.get("stream"):
                self._generate_stream(gen, prompt, kw, t0, handoff=handoff)
                return
            out = self._submit_retrying(
                gen, lambda: gen.submit(prompt, **kw)).result(timeout=600)
            out = dict(out)
            if handoff is not None:
                out["kv_handoff"] = handoff
            out["latency_ms"] = round((time.perf_counter() - t0) * 1e3, 3)
            if self._obs.trace_id:
                out["trace_id"] = self._obs.trace_id
            self._obs.set(ttft_ms=out.get("ttft_ms"))   # slowest(n) key
            self._reply(200, out)
        except Exception as e:    # noqa: BLE001 — every error is a response
            self._error_reply(e, metrics=gen.metrics)

    def _generate_stream(self, gen, prompt, kw, t0, handoff=None):
        """Chunked-transfer NDJSON stream: one {"token": id} record per
        emitted token (pushed from the decode loop as the slot advances),
        then a closing {"done": true, ...} record.  Admission errors are
        raised BEFORE any bytes go out, so they still map to their status
        codes; a failure mid-stream terminates with an {"error": ...}
        record instead (the status line is already on the wire)."""
        events = _queue.Queue()
        fut = self._submit_retrying(
            gen, lambda: gen.submit(
                prompt, on_token=lambda t: events.put(("token", t)), **kw))
        # the callback fires in the engine thread strictly before the
        # future resolves, so the queue orders tokens before done
        fut.add_done_callback(lambda f: events.put(("done", f)))
        try:
            self.send_response(200)
            self.send_header("Content-Type", "application/x-ndjson")
            self.send_header("Transfer-Encoding", "chunked")
            if self._obs.trace_id:
                self.send_header("X-Trace-Id", self._obs.trace_id)
            self.end_headers()
        except Exception as e:    # noqa: BLE001 — peer gone before the
            # status line finished: a second reply would corrupt the
            # connection; reclaim the slot and drop it
            logger.warning("generate stream: client gone before headers: "
                           "%s: %s", type(e).__name__, e)
            gen.abandon(fut)
            self.close_connection = True
            return

        def chunk(obj):
            data = (json.dumps(obj) + "\n").encode()
            self.wfile.write(f"{len(data):X}\r\n".encode() + data + b"\r\n")

        # the status line is on the wire: from here every failure must
        # terminate the chunk stream, never fall back to a second reply
        try:
            streamed = 0
            while True:
                kind, val = events.get(timeout=600)
                if kind == "token":
                    if streamed == 0:
                        self._obs.event("first_token")
                    streamed += 1
                    chunk({"token": int(val)})
                    continue
                exc = val.exception()
                if exc is not None:
                    chunk({"error": f"{type(exc).__name__}: {exc}"})
                else:
                    out = dict(val.result())
                    out["done"] = True
                    if handoff is not None:
                        out["kv_handoff"] = handoff
                    out["latency_ms"] = round(
                        (time.perf_counter() - t0) * 1e3, 3)
                    if self._obs.trace_id:
                        out["trace_id"] = self._obs.trace_id
                    self._obs.set(ttft_ms=out.get("ttft_ms"))
                    chunk(out)
                break
            self.wfile.write(b"0\r\n\r\n")
        except Exception as e:    # noqa: BLE001 — client gone / wedged
            logger.warning("generate stream aborted: %s: %s",
                           type(e).__name__, e)
            # the reader is gone: reclaim its decode slot instead of
            # generating to max_tokens for nobody
            gen.abandon(fut)
            # best-effort error record + terminator, then DROP the
            # connection: a keep-alive socket with an unterminated chunk
            # stream would block the client forever
            try:
                chunk({"error": f"stream aborted: {type(e).__name__}"})
                self.wfile.write(b"0\r\n\r\n")
            except Exception:   # noqa: BLE001 — socket already gone
                pass
            self.close_connection = True


def make_server(batcher, host="127.0.0.1", port=0, gen_batcher=None):
    """Bind (port 0 = ephemeral) and return the server; caller runs
    ``serve_forever()``.  ``server.port`` carries the bound port.

    batcher: the /v1/infer ``Batcher`` (None for a generation-only
    server); gen_batcher: the /v1/generate ``GenerationBatcher`` (None
    for an inference-only server).  At least one must be given."""
    if batcher is None and gen_batcher is None:
        raise ValueError("make_server needs a batcher, a gen_batcher, or "
                         "both")
    httpd = ThreadingHTTPServer((host, port), ServingHandler)
    httpd.daemon_threads = True
    httpd.batcher = batcher
    httpd.gen_batcher = gen_batcher
    httpd.port = httpd.server_address[1]
    # effective drain deadline (drives the ShutdownError Retry-After);
    # _serve overwrites it with the CLI's --drain-timeout-s
    httpd.drain_timeout_s = None
    return httpd


# ------------------------------------------------------------------- CLI


def _demo_engine(buckets, warm=True):
    """Built-in tiny MLP engine — bring-up/smoke without an artifact."""
    from paddle_tpu.layers import api as L
    from paddle_tpu.layers.graph import Topology, reset_names
    reset_names()
    x = L.data_layer("serving_demo_x", size=16)
    h = L.fc_layer(input=x, size=32, act="tanh")
    out = L.fc_layer(input=h, size=4, act="softmax")
    params = Topology([out]).init(jax.random.PRNGKey(0))
    spec = {"serving_demo_x": jax.ShapeDtypeStruct((1, 16), np.float32)}
    return InferenceEngine.from_topology(out, params, spec, buckets=buckets,
                                         warm=warm, name="demo")


def _demo_gen_batcher(args, tiny=False, metrics=None):
    """Built-in tiny decoder-only LM trunk behind the continuous-batching
    decode engine — /v1/generate bring-up and smoke without a trained
    model.  ``tiny=True`` shrinks the slots to smoke scale so the
    self-test warms in seconds.  ``metrics``: share the inference
    batcher's ServingMetrics on a combined server, so /metrics reports
    BOTH planes from the one object the handler renders."""
    from paddle_tpu.models import transformer
    from paddle_tpu.serving.decode_engine import (DecodeEngine,
                                                  GenerationBatcher)
    if tiny:
        slots, max_len = 4, 48
    else:
        slots = args.gen_slots
        max_len = args.gen_max_len
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=256,
                              trg_vocab=1, d_model=32, num_heads=2,
                              dff=64, enc_layers=2, dec_layers=0,
                              max_len=max_len)
    if getattr(args, "quant_weights", False):
        # per-channel int8 trunk weights (quant/weights.py): the engine
        # and every step variant accept the quantized tree directly
        from paddle_tpu.quant.weights import quantize_lm
        params = quantize_lm(params)
    speculate_k = int(getattr(args, "speculate_k", 0) or 0)
    draft = None
    if speculate_k:
        # the draft shares the target's embedding/vocab — a quantized
        # target hands the draft its quantized tree, which every step
        # variant dequantizes in place
        from paddle_tpu.serving.speculative import make_draft
        draft = make_draft(params,
                           layers=getattr(args, "draft_layers", 1))
    mesh = None
    mesh_shards = int(getattr(args, "mesh_shards", 0) or 0)
    if mesh_shards > 1:
        # tensor-parallel decode (docs/serving.md "Sharded decode"):
        # the demo trunk's heads/vocab divide any power-of-two mesh
        from paddle_tpu.parallel import sharding as _psh
        mesh = _psh.decode_mesh(mesh_shards)
    engine = DecodeEngine(params, num_heads=2, num_slots=slots,
                          max_len=max_len, name="demo_lm", metrics=metrics, mesh=mesh,
                          kv_layout=args.kv_layout,
                          kv_block_size=args.kv_block_size,
                          kv_num_blocks=args.kv_num_blocks,
                          prefix_cache=args.kv_prefix_cache,
                          kv_dtype=getattr(args, "kv_dtype", "float32"),
                          prefill_chunk=args.prefill_chunk,
                          prefill_chunk_budget=getattr(
                              args, "prefill_chunk_budget", 0),
                          speculate_k=speculate_k, draft=draft,
                          kv_host_bytes=getattr(args, "kv_host_bytes", 0))
    # supervision on by default for the generation plane: the breaker
    # and recovery are pure host bookkeeping (zero cost absent failures);
    # the step watchdog only arms when a deadline is configured
    sup = Supervisor(
        step_deadline_s=(args.step_deadline_ms / 1e3
                         if args.step_deadline_ms else None),
        breaker_threshold=args.breaker_threshold,
        breaker_cooldown_s=args.breaker_cooldown_s)
    return GenerationBatcher(engine, queue_size=args.queue_size,
                             default_deadline_ms=args.deadline_ms,
                             default_max_tokens=args.gen_max_tokens,
                             supervisor=sup)


def _build_engine(args):
    if args.artifact:
        return InferenceEngine.from_artifact(args.artifact)
    if args.artifacts:
        return InferenceEngine.from_artifacts(args.artifacts)
    if args.demo:
        buckets = tuple(int(b) for b in args.buckets.split(","))
        return _demo_engine(buckets)
    raise SystemExit("serving: pass one of --artifact PATH, "
                     "--artifacts GLOB, --demo, --demo-generate")


def _zeros_row_json(engine, fill=0.5):
    """A valid JSON feed for this engine's spec (smoke traffic)."""
    row = {}
    for name, sds in engine.bucket_spec(1).items():
        shape = tuple(sds.shape[1:])
        if np.issubdtype(sds.dtype, np.integer):
            row[name] = np.zeros(shape, sds.dtype).tolist()
        else:
            row[name] = np.full(shape, fill, sds.dtype).tolist()
    return row


def _smoke(batcher, n_requests=8):
    """Self-contained serving smoke: ephemeral port, n concurrent HTTP
    requests, a malformed request, /healthz + /metrics sanity.  Prints ONE
    JSON line; returns the process exit code."""
    import urllib.error
    import urllib.request

    httpd = make_server(batcher, port=0)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.port}"
    feed = _zeros_row_json(batcher.engine)
    ok = [0]
    errs = []

    def hit(i):
        body = json.dumps({"feed": feed}).encode()
        try:
            with urllib.request.urlopen(urllib.request.Request(
                    f"{base}/v1/infer", data=body,
                    headers={"Content-Type": "application/json"}),
                    timeout=30) as r:
                resp = json.loads(r.read())
                if "outputs" in resp:
                    ok[0] += 1
        except Exception as e:    # noqa: BLE001
            errs.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=hit, args=(i,))
               for i in range(n_requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)

    # malformed JSON must 400 without wounding the engine
    bad_status = None
    try:
        urllib.request.urlopen(urllib.request.Request(
            f"{base}/v1/infer", data=b"{not json",
            headers={"Content-Type": "application/json"}), timeout=30)
    except urllib.error.HTTPError as e:
        bad_status = e.code
    with urllib.request.urlopen(f"{base}/healthz", timeout=30) as r:
        health = json.loads(r.read())
    # readiness split (§5): a warm, serving, non-draining node is ready
    with urllib.request.urlopen(f"{base}/readyz", timeout=30) as r:
        ready = json.loads(r.read())
    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        metrics_text = r.read().decode()

    snap = batcher.metrics.snapshot()
    name = batcher.metrics.name
    metrics_sane = (
        f"{name}_requests_total {snap['requests_total']}" in metrics_text
        and f"{name}_batches_total" in metrics_text
        and 'latency_seconds{quantile="0.50"}' in metrics_text
        and snap["responses_total"] == ok[0]
        and snap["batches_total"] >= 1)
    out = {
        "metric": "serving smoke (dynamic batcher + HTTP front-end)",
        "value": ok[0], "unit": f"requests_ok/{n_requests}",
        "vs_baseline": None,
        "bad_request_status": bad_status,
        "healthz": health.get("status"),
        "readyz": ready.get("status"),
        "metrics_sane": bool(metrics_sane),
        "mean_occupancy": snap["mean_occupancy"],
        "p50_ms": snap["latency_ms"]["p50"],
        "p99_ms": snap["latency_ms"]["p99"],
    }
    if errs:
        out["errors"] = errs[:5]
    httpd.shutdown()
    batcher.close()
    print(json.dumps(out), flush=True)
    passed = (ok[0] == n_requests and bad_status == 400
              and health.get("status") == "ok"
              and ready.get("status") == "ready" and metrics_sane)
    return 0 if passed else 2


def _smoke_generate(gen, n_requests=6):
    """Generation-serving self-test: ephemeral
    port, concurrent STAGGERED /v1/generate requests with mixed prompt
    lengths and max_tokens (so admissions land mid-decode and slots churn),
    one streaming request, and an EOS early-finish probe (greedy decode is
    deterministic: replaying a prompt with eos_id set to one of its own
    continuation tokens must finish early with reason "eos").  Prints ONE
    JSON line; returns the process exit code."""
    import urllib.request

    httpd = make_server(None, port=0, gen_batcher=gen)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.port}"
    rng = np.random.RandomState(0)
    results = [None] * n_requests
    errs = []

    def post(body):
        req = urllib.request.Request(
            f"{base}/v1/generate", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, r.read()

    def hit(i):
        prompt = rng.randint(1, 256, 3 + 2 * i).tolist()
        n_tok = 10 + 3 * (i % 3)
        try:
            time.sleep(0.005 * i)       # staggered admissions: later
            # requests land while earlier ones are mid-decode, so slots
            # churn (admission between steps, never a retrace)
            status, raw = post({"prompt": prompt, "max_tokens": n_tok})
            resp = json.loads(raw)
            if status == 200 and len(resp["tokens"]) == n_tok \
                    and resp["finish_reason"] == "length":
                results[i] = resp
        except Exception as e:    # noqa: BLE001
            errs.append(f"{type(e).__name__}: {e}")

    threads = [threading.Thread(target=hit, args=(i,))
               for i in range(n_requests)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    ok = sum(1 for r in results if r is not None)

    # streaming: chunked NDJSON — tokens then a done record, and the
    # streamed ids must equal the non-streamed result for the same prompt
    # (greedy decode is deterministic).  EOS probe: replay stops AT the
    # first occurrence of the chosen stop token.  Guarded like hit(): a
    # probe failure must become a False flag in the ONE JSON line, never
    # a traceback that leaves phase 8's artifact empty.
    stream_ok = eos_ok = False
    try:
        probe = rng.randint(1, 256, 5).tolist()
        _, raw = post({"prompt": probe, "max_tokens": 6})
        plain = json.loads(raw)
        _, raw = post({"prompt": probe, "max_tokens": 6, "stream": True})
        lines = [json.loads(ln) for ln in raw.decode().splitlines() if ln]
        streamed = [ln["token"] for ln in lines if "token" in ln]
        done = [ln for ln in lines if ln.get("done")]
        stream_ok = (bool(done) and streamed == plain["tokens"]
                     and done[0]["tokens"] == plain["tokens"])
        eos = plain["tokens"][2]
        _, raw = post({"prompt": probe, "max_tokens": 6, "eos_id": eos})
        eos_probe = json.loads(raw)
        eos_ok = (eos_probe["finish_reason"] == "eos"
                  and eos_probe["tokens"][-1] == eos
                  and len(eos_probe["tokens"]) <= 3)
    except Exception as e:    # noqa: BLE001
        errs.append(f"probe: {type(e).__name__}: {e}")

    with urllib.request.urlopen(f"{base}/readyz", timeout=30) as r:
        ready = json.loads(r.read())
    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        metrics_text = r.read().decode()
    snap = gen.metrics.snapshot()
    name = gen.metrics.name
    metrics_sane = (
        f"{name}_gen_tokens_total {snap['gen_tokens_total']}" in metrics_text
        and f"{name}_decode_steps_total" in metrics_text
        and 'ttft_seconds{quantile="0.50"}' in metrics_text
        and snap["gen_tokens_total"] > 0
        and snap["decode_steps_total"] > 0)
    out = {
        "metric": "generation serving smoke (continuous batching + HTTP)",
        "value": ok, "unit": f"requests_ok/{n_requests}",
        "vs_baseline": None,
        "stream_ok": bool(stream_ok),
        "eos_early_finish": bool(eos_ok),
        "readyz": ready.get("status"),
        "metrics_sane": bool(metrics_sane),
        "mean_slot_occupancy": snap["mean_slot_occupancy"],
        "gen_tokens_total": snap["gen_tokens_total"],
        "evictions": snap["evictions"],
        "ttft_p50_ms": snap["ttft_ms"]["p50"],
        "tpot_p50_ms": snap["tpot_ms"]["p50"],
    }
    if errs:
        out["errors"] = errs[:5]
    httpd.shutdown()
    gen.close()
    print(json.dumps(out), flush=True)
    passed = (ok == n_requests and stream_ok and eos_ok and metrics_sane
              and ready.get("status") == "ready")
    return 0 if passed else 2


def _smoke_paged(args):
    """Paged-KV-cache self-test (docs/serving.md §5): serve the demo LM with ``kv_layout="paged"`` on an
    ephemeral port and drive the prefix-sharing scenario — one client
    establishes a long system-prompt context (prefix-cache miss, chains
    registered), then two clients sharing that system prompt (one the
    EXACT prompt — its seat lands inside the shared tail block and must
    copy-on-write fork it — one with a divergent question) admit by
    reference.  Every stream must be bit-identical to the SAME prompts
    served through a slab-layout twin engine (greedy decode — one
    compiled trunk, two memory layouts, same tokens), /metrics must
    show the hits, the fork, and the block-pool gauges.  Prints ONE
    JSON line; returns the process exit code."""
    import copy
    import urllib.request

    paged_args = copy.copy(args)
    paged_args.kv_layout = "paged"
    paged_args.kv_block_size = min(args.kv_block_size, 8)
    gen = _demo_gen_batcher(paged_args, tiny=True)
    slab_args = copy.copy(args)
    slab_args.kv_layout = "slab"
    slab = _demo_gen_batcher(slab_args, tiny=True)

    httpd = make_server(None, port=0, gen_batcher=gen)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.port}"
    bs = gen.engine.block_size
    rng = np.random.RandomState(0)
    # system prompt spanning one full block + a partial tail
    sys_prompt = rng.randint(1, 256, bs + bs // 2).tolist()
    qa = rng.randint(1, 256, 4).tolist()
    qb = rng.randint(1, 256, 4).tolist()
    prompts = [sys_prompt + qa,         # leader: miss, registers chains
               sys_prompt + qa,         # exact dup: hit + CoW fork
               sys_prompt + qb]         # divergent: shared-prefix hit
    n_tok = 8
    errs = []

    def post(body):
        req = urllib.request.Request(
            f"{base}/v1/generate", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return r.status, r.read()

    def generate(i, stream):
        try:
            if stream:
                _, raw = post({"prompt": prompts[i], "max_tokens": n_tok,
                               "stream": True})
                lines = [json.loads(ln) for ln in raw.decode().splitlines()
                         if ln]
                done = [ln for ln in lines if ln.get("done")]
                toks = [ln["token"] for ln in lines if "token" in ln]
                if not done or done[0]["tokens"] != toks:
                    errs.append(f"client {i}: stream/done mismatch")
                    return None
                return toks
            status, raw = post({"prompt": prompts[i],
                                "max_tokens": n_tok})
            resp = json.loads(raw)
            if status != 200 or resp["finish_reason"] != "length":
                errs.append(f"client {i}: {status} {resp}")
                return None
            return resp["tokens"]
        except Exception as e:    # noqa: BLE001 — a probe failure must
            # become a False flag in the ONE JSON line, never a traceback
            errs.append(f"client {i}: {type(e).__name__}: {e}")
            return None

    results = [None] * len(prompts)
    results[0] = generate(0, stream=False)      # leader registers first
    follower_threads = [
        threading.Thread(target=lambda i=i: results.__setitem__(
            i, generate(i, stream=i == 1)))
        for i in range(1, len(prompts))]
    for t in follower_threads:
        t.start()
    for t in follower_threads:
        t.join(120)
    ok = sum(1 for r in results if r is not None)

    # the slab twin serves the same prompts; greedy decode must agree
    # token for token across the two memory layouts
    bit_identical = False
    try:
        ref = [slab.submit(np.asarray(p, np.int64),
                           max_tokens=n_tok).result(120)["tokens"]
               for p in prompts]
        bit_identical = all(r is not None and r == e
                            for r, e in zip(results, ref))
    except Exception as e:    # noqa: BLE001
        errs.append(f"slab twin: {type(e).__name__}: {e}")

    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        metrics_text = r.read().decode()
    snap = gen.metrics.snapshot()
    name = gen.metrics.name
    metrics_sane = (
        f"{name}_prefix_cache_hits_total "
        f"{snap['prefix_cache_hits_total']}" in metrics_text
        and f"{name}_cow_forks_total {snap['cow_forks_total']}"
        in metrics_text
        and f"{name}_kv_blocks_total {snap['kv_blocks_total']}"
        in metrics_text
        and snap["kv_blocks_total"] > 0)
    out = {
        "metric": "paged KV serving smoke (prefix sharing + CoW + HTTP)",
        "value": ok, "unit": f"requests_ok/{len(prompts)}",
        "vs_baseline": None,
        "bit_identical": bool(bit_identical),
        "prefix_cache_hits": snap["prefix_cache_hits_total"],
        "prefix_cache_misses": snap["prefix_cache_misses_total"],
        "cow_forks": snap["cow_forks_total"],
        "kv_blocks_total": snap["kv_blocks_total"],
        "kv_blocks_used": snap["kv_blocks_used"],
        "pool_exhausted_evictions": snap["evictions"]["pool_exhausted"],
        "prefill_positions": gen.engine.prefill_positions_total,
        "metrics_sane": bool(metrics_sane),
    }
    if errs:
        out["errors"] = errs[:5]
    httpd.shutdown()
    gen.close()
    slab.close()
    print(json.dumps(out), flush=True)
    passed = (ok == len(prompts) and bit_identical and metrics_sane
              and snap["prefix_cache_hits_total"] >= 2
              and snap["cow_forks_total"] >= 1)
    return 0 if passed else 2


def _smoke_spill(args):
    """Hierarchical-KV self-test (docs/serving.md "Hierarchical KV"):
    serve the demo LM with a tiny paged pool plus a host-RAM spill tier on an ephemeral port.  A leader
    establishes a long block-aligned system-prompt context, churn
    traffic forces the pool to evict (and therefore spill) that chain,
    and then the leader's prompt RETURNS: the engine must restore-hit
    from the host tier and seat by reference — ZERO prefill chunk lanes
    for the covered prefix — with the stream bit-identical both to the
    first serving and to a tier-less twin's cold recompute.  /metrics
    must show the spill/restore counters and the host-tier gauge.
    Prints ONE JSON line; returns the process exit code."""
    import copy
    import urllib.request

    bs = 8
    spill_args = copy.copy(args)
    spill_args.kv_layout = "paged"
    spill_args.kv_block_size = bs
    # two slots' worth of blocks + 1: the shared chain cannot stay
    # resident once churn traffic claims seats
    spill_args.kv_num_blocks = 2 * (48 // bs) + 1
    spill_args.kv_prefix_cache = True
    spill_args.prefill_chunk = bs
    spill_args.kv_host_bytes = 64 << 20
    gen = _demo_gen_batcher(spill_args, tiny=True)
    twin_args = copy.copy(spill_args)
    twin_args.kv_host_bytes = 0
    twin = _demo_gen_batcher(twin_args, tiny=True)

    httpd = make_server(None, port=0, gen_batcher=gen)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.port}"
    rng = np.random.RandomState(0)
    # block-aligned system prompt: the registered chain covers every
    # prompt position, so the return visit needs no chunk lanes at all
    sys_prompt = rng.randint(1, 256, 4 * bs).tolist()
    churn = [rng.randint(1, 256, 28).tolist() for _ in range(4)]
    n_tok = 6
    errs = []

    def post(prompt):
        req = urllib.request.Request(
            f"{base}/v1/generate",
            data=json.dumps({"prompt": prompt,
                             "max_tokens": n_tok}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=60) as r:
                resp = json.loads(r.read())
                if r.status != 200 or resp["finish_reason"] != "length":
                    errs.append(f"{r.status} {resp}")
                    return None
                return resp["tokens"]
        except Exception as e:    # noqa: BLE001 — a probe failure must
            # become a False flag in the ONE JSON line, not a traceback
            errs.append(f"{type(e).__name__}: {e}")
            return None

    first = post(sys_prompt)                    # miss: registers chains
    for p in churn:                             # pool pressure -> spill
        post(p)
    snap_mid = gen.metrics.snapshot()
    lanes_before = snap_mid["prefill_chunk_lanes_total"]
    returned = post(sys_prompt)                 # must restore-hit
    snap = gen.metrics.snapshot()
    lanes_return = snap["prefill_chunk_lanes_total"] - lanes_before

    bit_identical = False
    try:
        ref = twin.submit(np.asarray(sys_prompt, np.int64),
                          max_tokens=n_tok).result(120)["tokens"]
        bit_identical = (first is not None and first == returned
                         and returned == ref)
    except Exception as e:    # noqa: BLE001
        errs.append(f"twin: {type(e).__name__}: {e}")

    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        metrics_text = r.read().decode()
    name = gen.metrics.name
    metrics_sane = (
        f"{name}_kv_restore_hits_total "
        f"{snap['kv_restore_hits_total']}" in metrics_text
        and f"{name}_kv_spill_blocks_total "
            f"{snap['kv_spill_blocks_total']}" in metrics_text
        and f"{name}_host_tier_bytes" in metrics_text
        and f"{name}_kv_restore_seconds_count" in metrics_text)
    out = {
        "metric": "hierarchical KV smoke (spill + async restore + HTTP)",
        "value": snap["kv_restore_hits_total"], "unit": "restore_hits",
        "vs_baseline": None,
        "bit_identical": bool(bit_identical),
        "kv_spill_blocks": snap["kv_spill_blocks_total"],
        "kv_restore_hits": snap["kv_restore_hits_total"],
        "kv_restore_bytes": snap["kv_restore_bytes_total"],
        "kv_restore_ms": snap["kv_restore_ms"],
        "host_tier_bytes": snap["host_tier_bytes"],
        "chunk_lanes_return_visit": lanes_return,
        "step_traces": gen.engine.step_trace_count,
        "metrics_sane": bool(metrics_sane),
    }
    if errs:
        out["errors"] = errs[:5]
    httpd.shutdown()
    gen.close()
    twin.close()
    print(json.dumps(out), flush=True)
    passed = (bit_identical and metrics_sane
              and snap["kv_spill_blocks_total"] > 0
              and snap["kv_restore_hits_total"] >= 1
              and lanes_return == 0
              and gen.engine.step_trace_count == 1)
    return 0 if passed else 2


def _smoke_decode_fused(args):
    """Fused decode-kernel self-test (docs/perf.md "Fused decode
    kernels"): the demo generation drive with
    ``pallas_decode=always`` — the Pallas decode-attention kernels
    compiled INTO the slab and paged steps (interpret mode on CPU, the
    real Mosaic kernels on TPU) — against a reference-path twin engine
    serving the same staggered prompts.  Every greedy stream must be
    bit-identical between the two steps, both fused engines must hold
    the 1-warm-up-trace/0-retrace discipline across the churn, and both
    kernels (slab + paged) must actually have engaged (engine
    ``decode_kernels`` resolution).  Prints ONE JSON line; returns the
    process exit code."""
    import copy

    from paddle_tpu.ops.pallas import decode_attention as decode_kernels

    rng = np.random.RandomState(0)
    n_tok = 8
    prompts = [rng.randint(1, 256, rng.randint(3, 17)).astype(np.int64)
               for _ in range(6)]
    errs = []
    out = {"metric": "fused decode-kernel smoke (pallas_decode vs "
                     "reference twin)",
           "vs_baseline": None}
    ok_layouts = 0
    for layout in ("slab", "paged"):
        a = copy.copy(args)
        a.kv_layout = layout
        a.kv_block_size = min(args.kv_block_size, 8)
        with decode_kernels.forced_mode("always"):
            fused = _demo_gen_batcher(a, tiny=True)
        # the twin must force the kernels OFF: on TPU the default
        # "auto" would fuse it too and the comparison would be
        # fused-vs-fused
        with decode_kernels.forced_mode("off"):
            ref = _demo_gen_batcher(a, tiny=True)
        engaged = bool(fused.engine.decode_kernels)
        traces0 = fused.engine.step_trace_count

        def drive(bat):
            futs, res = [], []
            for i, p in enumerate(prompts):
                futs.append(bat.submit(p, max_tokens=n_tok))
                if i % 2:
                    time.sleep(0.01)    # staggered: admissions land
                    #                     mid-decode, slots churn
            for f in futs:
                res.append(f.result(120)["tokens"])
            return res

        try:
            got = drive(fused)
            want = drive(ref)
            identical = got == want
        except Exception as e:  # noqa: BLE001 — a drive failure must
            # become a False flag in the ONE JSON line, not a traceback
            errs.append(f"{layout}: {type(e).__name__}: {e}")
            identical = False
        retraced = fused.engine.step_trace_count - traces0
        fused.close()
        ref.close()
        out[f"{layout}_kernel_engaged"] = engaged
        out[f"{layout}_bit_identical"] = bool(identical)
        out[f"{layout}_retraces"] = int(retraced)
        if engaged and identical and retraced == 0:
            ok_layouts += 1
    out["value"] = ok_layouts
    out["unit"] = "layouts_ok/2"
    if errs:
        out["errors"] = errs[:5]
    print(json.dumps(out), flush=True)
    return 0 if ok_layouts == 2 else 2


def _smoke_quant(args):
    """Quantized-serving self-test (docs/serving.md "Quantized
    serving"): the demo LM behind an INT8-KV paged engine (kv_num_blocks auto-DOUBLED at the slab-equivalent
    byte budget) serving HTTP /v1/generate, its streams compared
    against a fp32-twin engine under the COMMITTED quality budget
    (quant/kv.py: every stream's common prefix >= GREEDY_PREFIX_MIN_FULL
    and at least half the streams token-exact — the demo trunk is a
    random-init babbler with near-tied logits, so the budget, not
    bit-identity, is the fp32 contract).  An int8-KV + int8-WEIGHT
    engine must additionally reproduce the QUANTIZED ``lm_generate``
    oracle token-EXACTLY — inside one quantization mode greedy decode
    stays fully deterministic, so the engine/oracle bit-identity
    discipline carries over unchanged.  /metrics must show
    ``kv_blocks_total`` exactly DOUBLE the fp32 twin's at equal pool
    bytes and ``kv_cache_int8 1``.  Prints ONE JSON line; returns the
    process exit code."""
    import copy
    import urllib.request

    from paddle_tpu.quant.kv import (GREEDY_PREFIX_MIN_FULL,
                                     greedy_prefix_len)

    i8_args = copy.copy(args)
    i8_args.kv_layout = "paged"
    i8_args.kv_block_size = min(args.kv_block_size, 8)
    i8_args.kv_num_blocks = 0           # auto: slab-equivalent bytes
    i8_args.kv_dtype = "int8"
    gen = _demo_gen_batcher(i8_args, tiny=True)
    f32_args = copy.copy(i8_args)
    f32_args.kv_dtype = "float32"
    twin = _demo_gen_batcher(f32_args, tiny=True)
    full_args = copy.copy(i8_args)
    full_args.quant_weights = True
    full = _demo_gen_batcher(full_args, tiny=True)

    httpd = make_server(None, port=0, gen_batcher=gen)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.port}"
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 256, rng.randint(3, 15)).tolist()
               for _ in range(6)]
    n_tok = 10
    errs = []

    def post(body):
        req = urllib.request.Request(
            f"{base}/v1/generate", data=json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=60) as r:
            return json.loads(r.read())

    def hit(i, out):
        try:
            time.sleep(0.005 * i)       # staggered: slots churn
            out[i] = post({"prompt": prompts[i],
                           "max_tokens": n_tok})["tokens"]
        except Exception as e:    # noqa: BLE001 — a probe failure must
            errs.append(f"client {i}: {type(e).__name__}: {e}")

    results = [None] * len(prompts)
    threads = [threading.Thread(target=hit, args=(i, results))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(120)
    ok = sum(1 for r in results if r is not None)

    within_budget = exact = full_exact = 0
    try:
        from paddle_tpu.models import transformer
        for i, p in enumerate(prompts):
            want = twin.submit(np.asarray(p, np.int64),
                               max_tokens=n_tok).result(120)["tokens"]
            pre = greedy_prefix_len(results[i], want)
            within_budget += int(pre >= min(GREEDY_PREFIX_MIN_FULL,
                                            n_tok))
            exact += int(results[i] == want)
            # full-quant engine vs the QUANTIZED lm_generate oracle:
            # token-exact (bit-identity inside the int8 mode)
            fgot = full.submit(np.asarray(p, np.int64),
                               max_tokens=n_tok).result(120)["tokens"]
            arr = np.asarray(p, np.int32)[None]
            oracle = np.asarray(transformer.lm_generate(
                full.engine.params, arr, arr.size + n_tok, num_heads=2,
                kv_dtype="int8"))[0, arr.size:].tolist()
            full_exact += int(fgot == oracle)
    except Exception as e:    # noqa: BLE001
        errs.append(f"twin: {type(e).__name__}: {e}")

    with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
        metrics_text = r.read().decode()
    snap = gen.metrics.snapshot()
    twin_blocks = twin.engine._paged.pool.num_allocatable
    name = gen.metrics.name
    metrics_sane = (
        f"{name}_kv_blocks_total {snap['kv_blocks_total']}"
        in metrics_text
        and f"{name}_kv_cache_int8 1" in metrics_text
        and snap["kv_dtype"] == "int8")
    blocks_doubled = snap["kv_blocks_total"] == 2 * twin_blocks
    out = {
        "metric": "quantized serving smoke (int8 KV + int8 weights vs "
                  "fp32 twin)",
        "value": ok, "unit": f"requests_ok/{len(prompts)}",
        "vs_baseline": None,
        "within_budget": within_budget,
        "token_exact": exact,
        "full_quant_oracle_exact": full_exact,
        "kv_blocks_total": snap["kv_blocks_total"],
        "f32_twin_blocks": twin_blocks,
        "kv_blocks_doubled": bool(blocks_doubled),
        "kv_dtype": snap["kv_dtype"],
        "metrics_sane": bool(metrics_sane),
    }
    if errs:
        out["errors"] = errs[:5]
    httpd.shutdown()
    gen.close()
    twin.close()
    full.close()
    print(json.dumps(out), flush=True)
    passed = (ok == len(prompts) and blocks_doubled and metrics_sane
              and within_budget == len(prompts)
              and full_exact == len(prompts)
              and exact * 2 >= len(prompts))
    return 0 if passed else 2


def _smoke_quant_prefill(args):
    """End-to-end low-precision self-test (docs/perf.md "Int8 flash
    prefill" / "Int8 weight-streaming trainer").  Serving half: the demo trunk's batched causal prefill
    with ``kv_dtype="int8"`` THROUGH the int8 flash kernel
    (``pallas_prefill_quant=always`` — interpret mode off-TPU), its
    logits bounded against the fp32 prefill twin by the COMMITTED
    budget (quant/kv.logit_err vs LOGIT_ERR_BUDGET, the one comparison
    every quant surface shares), and the kernel-written cache checked
    against Tp sequential ``lm_decode_step`` calls — int8 codes
    BIT-EQUAL, f32 scale sidecars to float-epsilon (layer N>0's scales
    see layer N-1's kernel output, which is reference-equal only to
    ~1e-7; tests/test_flash_quant.py holds the per-layer bit-exact
    claim).  Training half: a 3-step int8 weight-streaming trainer
    (``SGD(quant_weights=True)``) must track its f32 twin's per-step
    cost within quant/weights.TRAIN_LOSS_BUDGET with a non-empty int8
    twin tree.  Prints ONE JSON line; returns the process exit code."""
    import importlib

    from paddle_tpu.models import transformer
    from paddle_tpu.quant import kv as quant_kv
    from paddle_tpu.quant import weights as quant_weights
    flash = importlib.import_module(
        "paddle_tpu.ops.pallas.flash_attention")

    b, tp, max_len, heads, vocab = 4, 16, 48, 2, 256
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=32, num_heads=heads,
                              dff=64, enc_layers=2, dec_layers=0,
                              max_len=max_len)
    rng = np.random.RandomState(0)
    tokens = jax.numpy.asarray(rng.randint(1, vocab, (b, tp)),
                               jax.numpy.int32)
    errs = []

    # ---- int8 flash prefill vs the fp32 twin (eager: the bit-exact
    # contract is defined eagerly; whole-program jit may reassociate
    # the scale divide by 1 ulp on any path — tests/test_flash_quant.py)
    with flash.forced_prefill_quant_mode("always"):
        h8, cache8 = transformer.lm_prefill(params, tokens, max_len,
                                            heads, kv_dtype="int8")
    h32, _ = transformer.lm_prefill(params, tokens, max_len, heads)
    l8 = transformer._lm_project(params, h8)
    l32 = transformer._lm_project(params, h32)
    per_stream = quant_kv.logit_err(l32, l8)
    max_err = float(per_stream.max())
    in_budget = int((np.asarray(per_stream)
                     <= quant_kv.LOGIT_ERR_BUDGET).sum())

    # the kernel-fed cache vs Tp sequential decode steps: bit-equal
    cache_seq = transformer.init_lm_cache(params, b, max_len,
                                          kv_dtype="int8",
                                          num_heads=heads)
    for t in range(tp):
        _lg, cache_seq = transformer.lm_decode_step(
            params, tokens[:, t], t, cache_seq, num_heads=heads)
    cache_exact = all(
        bool(np.array_equal(np.asarray(l8_[k])[:, :tp],
                            np.asarray(ls[k])[:, :tp]))
        for l8_, ls in zip(cache8, cache_seq)
        for k in ("k", "v")) and all(
        bool(np.allclose(np.asarray(l8_[k])[:, :tp],
                         np.asarray(ls[k])[:, :tp], rtol=1e-6, atol=0))
        for l8_, ls in zip(cache8, cache_seq)
        for k in ("ks", "vs"))

    # ---- int8 weight-streaming trainer: 3-step loss parity ----------
    import paddle_tpu.optim as optim
    from paddle_tpu.data import DataFeeder, dense_vector, integer_value
    from paddle_tpu.layers import api as L
    from paddle_tpu.layers.graph import reset_names
    from paddle_tpu.trainer.trainer import SGD

    def build(quant):
        reset_names()
        x = L.data_layer("qp_x", size=4)
        lab = L.data_layer("qp_lab", size=1)
        h = L.fc_layer(input=x, size=16, act="tanh")
        y = L.fc_layer(input=h, size=2, act="softmax")
        cost = L.classification_cost(y, lab)
        return SGD(cost=cost,
                   update_equation=optim.Momentum(learning_rate=0.1,
                                                  momentum=0.9),
                   seed=7, quant_weights=quant, quant_min_size=16)

    loss_gap = qtree_leaves = -1
    try:
        tq, tf = build(True), build(False)
        qtree_leaves = len(tq._qtree)
        feeder = DataFeeder({"qp_x": dense_vector(4),
                             "qp_lab": integer_value(2)})
        trng = np.random.RandomState(1)
        loss_gap = 0.0
        for _ in range(3):
            xs = trng.randn(8, 4).astype(np.float32)
            ys = (xs[:, 0] > 0).astype(np.int64)
            batch = [(xs[j], int(ys[j])) for j in range(8)]
            cq = float(tq.train_one_batch(batch, feeder))
            cf = float(tf.train_one_batch(batch, feeder))
            loss_gap = max(loss_gap, abs(cq - cf) / max(abs(cf), 1.0))
    except Exception as e:    # noqa: BLE001 — the probe must report
        errs.append(f"trainer: {type(e).__name__}: {e}")

    out = {
        "metric": "quantized prefill + int8 trainer smoke (int8 flash "
                  "prefill vs fp32 twin; quant trainer vs f32 twin)",
        "value": in_budget, "unit": f"streams_in_budget/{b}",
        "vs_baseline": None,
        "max_logit_err": round(max_err, 4),
        "logit_err_budget": quant_kv.LOGIT_ERR_BUDGET,
        "cache_matches_sequential": bool(cache_exact),
        "trainer_loss_gap_max": (round(loss_gap, 5)
                                 if loss_gap >= 0 else None),
        "train_loss_budget": quant_weights.TRAIN_LOSS_BUDGET,
        "quant_tree_leaves": qtree_leaves,
    }
    if errs:
        out["errors"] = errs[:5]
    print(json.dumps(out), flush=True)
    passed = (not errs and in_budget == b and cache_exact
              and 0 <= loss_gap <= quant_weights.TRAIN_LOSS_BUDGET
              and qtree_leaves >= 2)
    return 0 if passed else 2


def _smoke_speculative(args):
    """Speculative-decoding self-test (docs/serving.md "Speculative
    decoding"): the demo LM behind a
    speculating engine (1-layer draft riding the chunked step) serving
    concurrent staggered clients, every stream compared byte-for-byte
    against a NON-speculating twin of the same trunk — the draft may
    only ever change speed.  Acceptance evidence must land on the
    /metrics surface (drafted/accepted counters + the derived
    acceptance rate the snapshot carries), and both engines must hold
    the one-warm-up-trace discipline under acceptance churn.  Prints
    ONE JSON line; returns the process exit code."""
    import copy
    import threading

    spec_args = copy.copy(args)
    spec_args.prefill_chunk = min(4, args.prefill_chunk or 4) or 4
    spec_args.speculate_k = max(1, getattr(args, "speculate_k", 0) or 3)
    spec_args.draft_layers = max(1, getattr(args, "draft_layers", 1) or 1)
    gen = _demo_gen_batcher(spec_args, tiny=True)
    twin_args = copy.copy(spec_args)
    twin_args.speculate_k = 0
    twin = _demo_gen_batcher(twin_args, tiny=True)
    rng = np.random.RandomState(0)
    cases = [(rng.randint(1, 256, int(n)).astype(np.int64), int(m))
             for n, m in ((4, 12), (9, 8), (3, 14), (12, 10))]
    errs, results, ref = [], [None] * len(cases), [None] * len(cases)
    trace_spec = (gen.engine.step_trace_count,
                  gen.engine.draft.trace_count)
    try:
        def client(bat, out, i):
            p, mt = cases[i]
            time.sleep(0.002 * i)
            out[i] = bat.submit(p, max_tokens=mt).result(120)["tokens"]

        for bat, out in ((gen, results), (twin, ref)):
            ts = [threading.Thread(target=client, args=(bat, out, i))
                  for i in range(len(cases))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(180)
        requests_ok = sum(r is not None for r in results)
        bit_identical = results == ref and None not in results
    except Exception as e:      # noqa: BLE001 — a probe failure must
        # become a failed flag in the ONE JSON line, not a traceback
        errs.append(f"{type(e).__name__}: {e}")
        requests_ok, bit_identical = 0, False
    no_retrace = ((gen.engine.step_trace_count,
                   gen.engine.draft.trace_count) == trace_spec == (1, 1))
    snap = gen.metrics.snapshot()
    metrics_text = gen.metrics.render_prometheus()
    name = gen.metrics.name
    metrics_sane = (
        f"{name}_drafted_tokens_total "
        f"{snap['drafted_tokens_total']}" in metrics_text
        and f"{name}_accepted_tokens_total "
            f"{snap['accepted_tokens_total']}" in metrics_text
        and f"{name}_speculate_k {spec_args.speculate_k}" in metrics_text
        and "_spec_acceptance_rate " in metrics_text)
    out = {
        "metric": "speculative serving smoke (spec engine vs non-spec "
                  "twin)",
        "value": requests_ok, "unit": f"requests_ok/{len(cases)}",
        "vs_baseline": None,
        "speculate_k": spec_args.speculate_k,
        "draft_layers": spec_args.draft_layers,
        "bit_identical": bool(bit_identical),
        "drafted_tokens_total": snap["drafted_tokens_total"],
        "accepted_tokens_total": snap["accepted_tokens_total"],
        "spec_acceptance_rate": snap["spec_acceptance_rate"],
        "spec_tokens_per_step": snap["spec_tokens_per_step"],
        "no_retrace": bool(no_retrace),
        "metrics_sane": bool(metrics_sane),
    }
    if errs:
        out["errors"] = errs[:5]
    gen.close()
    twin.close()
    print(json.dumps(out), flush=True)
    passed = (requests_ok == len(cases) and bit_identical and no_retrace
              and metrics_sane and snap["drafted_tokens_total"] > 0
              and snap["spec_tokens_per_step"] >= 1.0)
    return 0 if passed else 2


def _smoke_sharded(args):
    """Tensor-parallel sharded-decode self-test (docs/serving.md
    "Sharded decode"): the demo LM's one
    chunked step under an n=2 model-axis mesh serving concurrent
    staggered clients, every stream compared byte-for-byte against the
    single-chip twin — sharding may only ever change WHERE bytes live,
    never a token.  Speculation rides along (the draft trunk shards
    with its target), so the probe composes chunked admission + spec
    churn over the mesh at exactly one warm-up trace per jitted
    function.  Mesh evidence must land on the /metrics surface (the
    mesh_shards gauge).  XLA's host device count is fixed at backend
    init, so on a single-device machine the probe RE-EXECS itself with
    the forcing flag and forwards the child's JSON line + exit code.
    Prints ONE JSON line; returns the process exit code."""
    import copy
    import os
    import subprocess
    import threading
    import jax

    shards = max(2, int(getattr(args, "mesh_shards", 0) or 2))
    if len(jax.devices()) < shards:
        env = dict(os.environ)
        env["XLA_FLAGS"] = (
            env.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={shards}").strip()
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.serving",
             "--smoke-sharded", "--mesh-shards", str(shards),
             "--kv-layout", args.kv_layout],
            env=env, capture_output=True, text=True, timeout=900)
        sys.stderr.write(proc.stderr[-2000:])
        lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
        print(lines[-1] if lines else json.dumps(
            {"metric": "sharded serving smoke", "value": 0,
             "errors": [f"child produced no output, rc="
                        f"{proc.returncode}"]}), flush=True)
        return proc.returncode

    sh_args = copy.copy(args)
    sh_args.mesh_shards = shards
    sh_args.prefill_chunk = min(4, args.prefill_chunk or 4) or 4
    sh_args.speculate_k = max(1, getattr(args, "speculate_k", 0) or 2)
    sh_args.draft_layers = max(1, getattr(args, "draft_layers", 1) or 1)
    gen = _demo_gen_batcher(sh_args, tiny=True)
    twin_args = copy.copy(sh_args)
    twin_args.mesh_shards = 0
    twin = _demo_gen_batcher(twin_args, tiny=True)
    rng = np.random.RandomState(0)
    cases = [(rng.randint(1, 256, int(n)).astype(np.int64), int(m))
             for n, m in ((4, 12), (9, 8), (3, 14), (12, 10))]
    errs, results, ref = [], [None] * len(cases), [None] * len(cases)
    traces = (gen.engine.step_trace_count, gen.engine.draft.trace_count)
    try:
        def client(bat, out, i):
            p, mt = cases[i]
            time.sleep(0.002 * i)
            out[i] = bat.submit(p, max_tokens=mt).result(120)["tokens"]

        for bat, out in ((gen, results), (twin, ref)):
            ts = [threading.Thread(target=client, args=(bat, out, i))
                  for i in range(len(cases))]
            for t in ts:
                t.start()
            for t in ts:
                t.join(180)
        requests_ok = sum(r is not None for r in results)
        bit_identical = results == ref and None not in results
    except Exception as e:      # noqa: BLE001 — a probe failure must
        # become a failed flag in the ONE JSON line, not a traceback
        errs.append(f"{type(e).__name__}: {e}")
        requests_ok, bit_identical = 0, False
    no_retrace = ((gen.engine.step_trace_count,
                   gen.engine.draft.trace_count) == traces == (1, 1))
    snap = gen.metrics.snapshot()
    metrics_text = gen.metrics.render_prometheus()
    name = gen.metrics.name
    metrics_sane = (snap["mesh_shards"] == shards
                    and f"{name}_mesh_shards {shards}" in metrics_text
                    and twin.metrics.snapshot()["mesh_shards"] == 1)
    out = {
        "metric": "sharded serving smoke (n-chip mesh vs single-chip "
                  "twin)",
        "value": requests_ok, "unit": f"requests_ok/{len(cases)}",
        "vs_baseline": None,
        "mesh_shards": snap["mesh_shards"],
        "devices": len(jax.devices()),
        "kv_layout": args.kv_layout,
        "speculate_k": sh_args.speculate_k,
        "bit_identical": bool(bit_identical),
        "no_retrace": bool(no_retrace),
        "metrics_sane": bool(metrics_sane),
    }
    if errs:
        out["errors"] = errs[:5]
    gen.close()
    twin.close()
    print(json.dumps(out), flush=True)
    passed = (requests_ok == len(cases) and bit_identical and no_retrace
              and metrics_sane)
    return 0 if passed else 2


def _write_port_file(path, port):
    """Publish the BOUND port (meaningful with --port 0) atomically —
    the fleet supervisor (serving/fleet.py) spawns replicas on ephemeral
    ports and discovers them here; a partial read must be impossible."""
    import os
    tmp = f"{path}.tmp-{os.getpid()}"
    with open(tmp, "w") as f:
        f.write(f"{port}\n")
    os.replace(tmp, path)


def main(argv=None):
    from paddle_tpu.utils.flags import FLAGS
    ap = argparse.ArgumentParser(
        prog="python -m paddle_tpu.serving",
        description="dynamic-batching inference server")
    ap.add_argument("--artifact", help="exported StableHLO artifact")
    ap.add_argument("--artifacts",
                    help="glob of bucketed artifacts (model.b*.shlo)")
    ap.add_argument("--demo", action="store_true",
                    help="serve the built-in tiny MLP")
    ap.add_argument("--demo-generate", action="store_true",
                    help="serve the built-in tiny LM behind the "
                         "continuous-batching /v1/generate")
    ap.add_argument("--buckets", default=FLAGS.serving_buckets,
                    help="batch bucket ladder for --demo (artifacts carry "
                         "their own)")
    ap.add_argument("--gen-slots", type=int, default=FLAGS.serving_gen_slots)
    ap.add_argument("--gen-max-len", type=int,
                    default=FLAGS.serving_gen_max_len)
    ap.add_argument("--gen-max-tokens", type=int,
                    default=FLAGS.serving_gen_max_tokens)
    # ---- paged KV cache (serving/kv_pool.py; docs/serving.md §5) ----
    ap.add_argument("--kv-layout", default=FLAGS.serving_kv_layout,
                    choices=("slab", "paged"),
                    help="decode KV-cache layout: slab reserves max_len "
                         "per slot; paged packs a shared block pool with "
                         "prefix sharing")
    ap.add_argument("--kv-block-size", type=int,
                    default=FLAGS.serving_kv_block_size)
    ap.add_argument("--kv-num-blocks", type=int,
                    default=FLAGS.serving_kv_num_blocks,
                    help="paged pool size incl. the scratch block "
                         "(0 = the slab-equivalent byte budget)")
    ap.add_argument("--kv-prefix-cache",
                    type=lambda v: v.lower() in ("1", "true", "yes"),
                    default=FLAGS.serving_kv_prefix_cache)
    ap.add_argument("--kv-host-bytes", type=int,
                    default=FLAGS.serving_kv_host_bytes,
                    help="host-RAM spill-tier byte cap (hierarchical "
                         "KV: evicted prefix chains spill to host and "
                         "restore asynchronously on the next hit when "
                         "the analytic model predicts restore beats "
                         "recompute; 0 = tier off; paged + "
                         "prefix-cache only)")
    # ---- disaggregated serving (serving/transfer.py; docs/serving.md
    # "Disaggregated serving") ----
    ap.add_argument("--role", default=FLAGS.serving_role,
                    choices=("prefill", "decode", "mixed"),
                    help="disaggregated-serving role, advertised on "
                         "/metrics as serving_role{role=...}: the "
                         "router sends new prompts to the prefill pool "
                         "and at the first token hands the stream to a "
                         "decode replica by shipping chain key + "
                         "continuation (KV blocks ride /v1/kv/export); "
                         "mixed (the default) serves both phases")
    # ---- quantized serving (quant/; docs/serving.md) ----
    ap.add_argument("--kv-dtype", default=FLAGS.serving_kv_dtype,
                    choices=("float32", "int8"),
                    help="KV-cache storage dtype: int8 stores quantized "
                         "K/V + per-head scale sidecars (halved+ KV "
                         "bytes; paged auto-sizing doubles the block "
                         "count at the same byte budget)")
    ap.add_argument("--quant-weights",
                    type=lambda v: v.lower() in ("1", "true", "yes"),
                    default=FLAGS.quant_weights,
                    help="serve per-channel int8 trunk weights "
                         "(quant/weights.py): int8 data + f32 scales "
                         "resident, dequant fused into each matmul")
    ap.add_argument("--pallas-decode", default=FLAGS.pallas_decode,
                    help="fused decode-attention kernels for the decode "
                         "step: auto (TPU only) | always (interpret "
                         "off-TPU) | off — docs/perf.md 'Fused decode "
                         "kernels'")
    # ---- chunked prefill (docs/serving.md "Chunked prefill") ---------
    ap.add_argument("--prefill-chunk", type=int,
                    default=FLAGS.serving_prefill_chunk,
                    help="token lanes K of the one decode step: prompt "
                         "ingestion rides it as up-to-K-token chunks "
                         "per slot per step (>= 1)")
    ap.add_argument("--prefill-chunk-budget", type=int,
                    default=FLAGS.serving_prefill_chunk_budget,
                    help="max teacher-forced chunk lanes per step "
                         "across all slots (bounds TPOT jitter; "
                         "0 = unbounded)")
    # ---- speculative decoding (docs/serving.md "Speculative decoding")
    ap.add_argument("--speculate-k", type=int,
                    default=FLAGS.serving_speculate_k,
                    help="draft tokens proposed per feeding slot per "
                         "step; the one chunked step scores every "
                         "drafted lane and each step nets 1 + accepted "
                         "tokens (0 = off; requires --prefill-chunk)")
    ap.add_argument("--draft-layers", type=int,
                    default=FLAGS.serving_draft_layers,
                    help="trunk depth of the draft derived from the "
                         "target (first N enc blocks; embedding/vocab "
                         "shared)")
    # ---- tensor-parallel sharded decode (docs/serving.md "Sharded
    # decode") ----
    ap.add_argument("--mesh-shards", type=int,
                    default=FLAGS.serving_mesh_shards,
                    help="run the one chunked step under an N-chip "
                         "model-axis mesh (heads/KV/vocab striped, "
                         "streams bit-identical to single-chip); 0/1 = "
                         "single-chip")
    ap.add_argument("--pallas-prefill", default=FLAGS.pallas_prefill,
                    help="route lm_prefill's causal pass (lm_generate; "
                         "the served step never runs it) through the "
                         "flash kernel (no [Tp, Tp] scores): auto (TPU "
                         "only) | always | off")
    ap.add_argument("--pallas-prefill-quant",
                    default=FLAGS.pallas_prefill_quant,
                    help="int8-cache prefill through the int8 flash "
                         "kernel (streams the quantized bytes + scale "
                         "sidecars, no f32 cache widen): auto (TPU "
                         "only) | always | off — docs/perf.md 'Int8 "
                         "flash prefill'")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=FLAGS.serving_port)
    ap.add_argument("--port-file",
                    help="write the BOUND port here once listening "
                         "(atomic; pairs with --port 0 for fleet-managed "
                         "replicas, serving/fleet.py)")
    ap.add_argument("--max-batch-size", type=int,
                    default=FLAGS.serving_max_batch_size or None)
    ap.add_argument("--max-delay-ms", type=float,
                    default=FLAGS.serving_max_delay_ms)
    ap.add_argument("--queue-size", type=int,
                    default=FLAGS.serving_queue_size)
    ap.add_argument("--deadline-ms", type=float,
                    default=FLAGS.serving_deadline_ms or None)
    ap.add_argument("--smoke", action="store_true",
                    help="self-test on an ephemeral port, print one JSON "
                         "line, exit")
    ap.add_argument("--smoke-generate", action="store_true",
                    help="generation self-test on an ephemeral port, "
                         "print one JSON line, exit")
    ap.add_argument("--smoke-paged", action="store_true",
                    help="paged-KV self-test: shared-system-prompt "
                         "clients over kv_layout=paged, prefix hits + "
                         "CoW fork recorded, streams bit-identical to "
                         "the slab layout; one JSON line, exit")
    ap.add_argument("--smoke-spill", action="store_true",
                    help="hierarchical-KV self-test: tiny paged pool + "
                         "host spill tier, churn forces eviction, the "
                         "returning shared prefix restore-hits with "
                         "zero prefill chunk lanes, bit-identical to a "
                         "tier-less twin, spill/restore evidence in "
                         "/metrics; one JSON line, exit")
    ap.add_argument("--smoke-decode-fused", action="store_true",
                    help="fused decode-kernel self-test: the demo "
                         "generation drive with pallas_decode=always "
                         "(slab + paged), streams bit-identical to a "
                         "reference-path twin, 0 retraces; one JSON "
                         "line, exit")
    ap.add_argument("--smoke-quant", action="store_true",
                    help="quantized-serving self-test: int8-KV paged "
                         "engine vs a fp32 twin within the committed "
                         "quality budget, int8-KV+weights engine exact "
                         "vs the quantized oracle, kv_blocks_total "
                         "doubled at equal bytes; one JSON line, exit")
    ap.add_argument("--smoke-quant-prefill", action="store_true",
                    help="end-to-end low-precision self-test: int8 "
                         "flash prefill within the committed logit "
                         "budget vs the fp32 twin with a bit-exact "
                         "int8 cache vs sequential steps, plus 3-step "
                         "int8-trainer loss parity; one JSON line, "
                         "exit")
    ap.add_argument("--smoke-speculative", action="store_true",
                    help="speculative-decoding self-test: spec engine "
                         "vs a non-spec twin under concurrent clients, "
                         "streams bit-identical, acceptance-rate "
                         "evidence in /metrics; one JSON line, exit")
    ap.add_argument("--smoke-sharded", action="store_true",
                    help="sharded-decode self-test: n=2 forced host "
                         "mesh (re-execs itself with XLA_FLAGS when "
                         "single-device), concurrent streams "
                         "bit-identical to the single-chip twin, "
                         "mesh_shards evidence in /metrics; one JSON "
                         "line, exit")
    # ---- resilience (docs/serving.md §6) ----
    ap.add_argument("--drain-timeout-s", type=float,
                    default=FLAGS.serving_drain_timeout_s,
                    help="hard deadline for the SIGTERM graceful drain")
    ap.add_argument("--step-deadline-ms", type=float,
                    default=FLAGS.resilience_step_deadline_ms or None,
                    help="decode-step watchdog deadline (0/unset = off)")
    ap.add_argument("--breaker-threshold", type=int,
                    default=FLAGS.resilience_breaker_threshold)
    ap.add_argument("--breaker-cooldown-s", type=float,
                    default=FLAGS.resilience_breaker_cooldown_s)
    ap.add_argument("--fault-spec", default=FLAGS.resilience_fault_spec,
                    help="deterministic fault-injection spec "
                         "(resilience/faults.py; chaos testing only)")
    # ---- request tracing (obs/trace.py; docs/observability.md) ----
    ap.add_argument("--obs-trace",
                    type=lambda v: v.lower() in ("1", "true", "yes"),
                    default=FLAGS.obs_trace_enable,
                    help="per-request span tracing: /debug/traces + "
                         "trace_id propagation/echo")
    ap.add_argument("--obs-trace-sample", type=float,
                    default=FLAGS.obs_trace_sample)
    ap.add_argument("--obs-trace-ring", type=int,
                    default=FLAGS.obs_trace_ring)
    args = ap.parse_args(argv)
    from paddle_tpu.utils.flags import set_compilation_cache_dir
    set_compilation_cache_dir()
    # kernel selection is read at TRACE time — push the flags before any
    # engine is constructed
    FLAGS.pallas_decode = args.pallas_decode
    FLAGS.pallas_prefill = args.pallas_prefill
    FLAGS.pallas_prefill_quant = args.pallas_prefill_quant
    if args.fault_spec:
        from paddle_tpu.resilience import faults
        faults.install_spec(args.fault_spec)
        logger.warning("fault injection ACTIVE: %s", args.fault_spec)
    if args.obs_trace:
        obstrace.enable(sample=args.obs_trace_sample,
                        capacity=args.obs_trace_ring)
    if args.smoke and not (args.artifact or args.artifacts):
        args.demo = True
    if args.smoke:
        # a generous batch window so the smoke's concurrent clients
        # reliably coalesce (the occupancy>1 assertion) even on a loaded
        # CI machine
        args.max_delay_ms = max(args.max_delay_ms, 50.0)

    if args.smoke_generate:
        return _smoke_generate(_demo_gen_batcher(args, tiny=True))
    if args.smoke_paged:
        return _smoke_paged(args)
    if args.smoke_spill:
        return _smoke_spill(args)
    if args.smoke_decode_fused:
        return _smoke_decode_fused(args)
    if args.smoke_quant:
        return _smoke_quant(args)
    if args.smoke_quant_prefill:
        return _smoke_quant_prefill(args)
    if args.smoke_speculative:
        return _smoke_speculative(args)
    if args.smoke_sharded:
        return _smoke_sharded(args)
    if args.demo_generate and not (args.artifact or args.artifacts
                                   or args.demo):
        # generation-only server: no /v1/infer batcher
        gen_batcher = _demo_gen_batcher(args)
        gen_batcher.metrics.set_serving_role(args.role)
        httpd = make_server(None, args.host, args.port,
                            gen_batcher=gen_batcher)
        # the bound port is the replica's identity in a merged fleet
        # Chrome trace (processes = router/replicas)
        obstrace.set_process(f"replica:{httpd.port}")
        if args.port_file:
            _write_port_file(args.port_file, httpd.port)
        logger.info("serving %s on http://%s:%d (/v1/generate: %d slots, "
                    "max_len %d)", gen_batcher.engine.name, args.host,
                    httpd.port, gen_batcher.engine.num_slots,
                    gen_batcher.engine.max_len)
        return _serve(httpd, None, gen_batcher,
                      drain_timeout_s=args.drain_timeout_s)

    engine = _build_engine(args)
    batcher = Batcher(engine, max_batch_size=args.max_batch_size,
                      max_delay_ms=args.max_delay_ms,
                      queue_size=args.queue_size,
                      default_deadline_ms=args.deadline_ms)
    if args.smoke:
        return _smoke(batcher)

    # combined server: the generation plane shares the inference
    # batcher's metrics, so the ONE /metrics page reports both
    gen_batcher = (_demo_gen_batcher(args, metrics=engine.metrics)
                   if args.demo_generate else None)
    engine.metrics.set_serving_role(args.role)
    httpd = make_server(batcher, args.host, args.port,
                        gen_batcher=gen_batcher)
    obstrace.set_process(f"replica:{httpd.port}")
    if args.port_file:
        _write_port_file(args.port_file, httpd.port)
    logger.info("serving %s on http://%s:%d (buckets %s, max_delay %.1fms, "
                "queue %d)", engine.name, args.host, httpd.port,
                list(engine.buckets), args.max_delay_ms, args.queue_size)
    return _serve(httpd, batcher, gen_batcher,
                  drain_timeout_s=args.drain_timeout_s)


def _make_drain_handler(httpd, state, drain_timeout_s, force_exit):
    """The SIGTERM/SIGINT handler with a HARD deadline (docs/serving.md
    §5): the first signal starts a graceful drain AND arms a watchdog —
    if the drain has not completed within ``drain_timeout_s`` (a wedged
    in-flight batch, a handler stuck on a dead socket), the process
    force-exits instead of hanging shutdown forever.  A SECOND signal
    force-exits immediately.  Factored out (and ``force_exit``
    injectable) so both paths are unit-testable without killing the
    test runner."""

    def _drain(signum, frame):
        state["signals"] = state.get("signals", 0) + 1
        if state["signals"] > 1:
            logger.warning("second SIGTERM: forcing immediate exit")
            force_exit(130)
            return
        logger.info("SIGTERM: draining (no new admissions, finishing "
                    "queued requests; hard deadline %.0fs, second "
                    "SIGTERM forces exit)", drain_timeout_s or 0.0)
        threading.Thread(target=httpd.shutdown, daemon=True).start()
        if drain_timeout_s and drain_timeout_s > 0:
            def watchdog():
                time.sleep(drain_timeout_s)
                if not state.get("drained"):
                    logger.warning("drain did not complete within %.0fs; "
                                   "forcing exit", drain_timeout_s)
                    force_exit(3)
            threading.Thread(target=watchdog, daemon=True,
                             name="drain-deadline").start()
    return _drain


def _serve(httpd, batcher, gen_batcher, drain_timeout_s=None):
    import os
    if drain_timeout_s is None:
        from paddle_tpu.utils.flags import FLAGS
        drain_timeout_s = FLAGS.serving_drain_timeout_s
    httpd.drain_timeout_s = drain_timeout_s
    state = {}
    _drain = _make_drain_handler(httpd, state, drain_timeout_s, os._exit)
    try:
        signal.signal(signal.SIGTERM, _drain)
        signal.signal(signal.SIGINT, _drain)
    except ValueError:
        pass        # not the main thread (embedded use)
    try:
        httpd.serve_forever()
    finally:
        # order matters: the drain resolves every in-flight future, THEN
        # server_close() joins the handler threads (block_on_close) so
        # their responses reach the sockets before the interpreter exits
        # — otherwise the work the drain completed is dropped on the wire
        if batcher is not None:
            batcher.close(drain=True)
        if gen_batcher is not None:
            gen_batcher.close(drain=True)
        state["drained"] = True     # disarms the drain-deadline watchdog
        httpd.server_close()
        metrics = (batcher or gen_batcher).metrics
        logger.info("serving stopped; %d responses served",
                    metrics.responses_total)
    return 0


if __name__ == "__main__":
    sys.exit(main())

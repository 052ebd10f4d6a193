"""Driver for a decoder-only LM served through the library's front door:
``DecodeEngine -> GenerationBatcher -> make_server`` on an ephemeral port of
this process, HTTP clients on ``/v1/generate``, every request streamed.

The benchmark owns the weights' seed, the traffic, the clients' clocks, the
reference check and the trace; the engine is built as the serving CLI builds
it, from the configuration's ``serving`` group."""

import http.client
import json
import math
import threading
import time

import numpy as np


# ------------------------------------------------------------- the system

def make_params(cfg, seed):
    """The trunk's parameters, made on the device in one jitted call from
    the seed.  ``transformer.init`` leaves LayerNorm gains at 1 and every
    bias at 0; they are perturbed here so that the reference check sees
    them."""
    import jax
    from paddle_tpu.models import transformer

    def init(key):
        k_init, k_noise = jax.random.split(key)
        p = transformer.init(
            k_init, src_vocab=cfg["vocab_size"], trg_vocab=8,
            d_model=cfg["hidden_size"], num_heads=cfg["num_attention_heads"],
            dff=cfg["ffn_dim"], enc_layers=cfg["num_hidden_layers"],
            dec_layers=0, max_len=cfg["max_position_embeddings"])
        leaves, treedef = jax.tree_util.tree_flatten(p)
        keys = jax.random.split(k_noise, len(leaves))
        leaves = [x + 0.02 * jax.random.normal(k, x.shape, x.dtype)
                  if x.ndim == 1 else x for x, k in zip(leaves, keys)]
        return jax.tree_util.tree_unflatten(treedef, leaves)

    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1))
    return jax.block_until_ready(jax.jit(init)(key))


def reference_params(p):
    """The program's parameter tree as the plain reference wants it."""
    return {"emb": p["src_emb"], "pos": p["pos"],
            "lnf_g": p["ln_f"]["g"], "lnf_b": p["ln_f"]["b"],
            "layers": [{"ln1_g": b["ln1"]["g"], "ln1_b": b["ln1"]["b"],
                        "ln2_g": b["ln2"]["g"], "ln2_b": b["ln2"]["b"],
                        **b["attn"], **b["ffn"]} for b in p["enc"]]}


class Server:
    """The engine behind the HTTP front, on an ephemeral local port."""

    def __init__(self, cfg, params):
        from paddle_tpu.serving.decode_engine import (DecodeEngine,
                                                      GenerationBatcher)
        from paddle_tpu.serving.server import make_server
        s = cfg["serving"]
        self.engine = DecodeEngine(
            params, num_heads=cfg["num_attention_heads"],
            num_slots=s["slots"], max_len=cfg["max_position_embeddings"],
            kv_layout=s["kv_layout"], kv_block_size=s["kv_block_size"],
            prefix_cache=s["prefix_cache"], prefill_chunk=s["prefill_chunk"],
            kv_dtype=s["kv_dtype"], name="bench")
        self.gen = GenerationBatcher(self.engine, default_max_tokens=64)
        self.httpd = make_server(None, port=0, gen_batcher=self.gen)
        self._thread = threading.Thread(target=self.httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        self.port = self.httpd.port

    def close(self):
        self.httpd.shutdown()
        self.httpd.server_close()
        self._thread.join(30)
        self.gen.close(drain=False, timeout=30)


# ------------------------------------------------------------ the clients

def stream_request(port, req, timeout):
    """One streamed /v1/generate request; fills ``req`` with the moment it
    was sent, the moment each token line arrived, and how it ended."""
    req["token_times"], req["tokens"], req["error"] = [], [], None
    body = json.dumps({"prompt": req["prompt"], "stream": True,
                       "max_tokens": req["max_tokens"]})
    conn = http.client.HTTPConnection("127.0.0.1", port, timeout=timeout)
    try:
        req["sent"] = time.perf_counter()
        conn.request("POST", "/v1/generate", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            req["error"] = f"http {resp.status}"
            return
        done = None
        for raw in resp:
            now = time.perf_counter()
            line = json.loads(raw) if raw.strip() else {}
            if "token" in line:
                req["token_times"].append(now)
                req["tokens"].append(line["token"])
            elif "error" in line:
                req["error"] = str(line["error"])[:200]
            elif line.get("done"):
                done = line
        if req["error"] is None and (
                done is None or done["tokens"] != req["tokens"]
                or len(req["tokens"]) != req["max_tokens"]):
            req["error"] = (f"asked {req['max_tokens']} tokens, streamed "
                            f"{len(req['tokens'])}, done={done is not None}")
    except Exception as e:      # noqa: BLE001 — a client failure is a result
        req["error"] = f"{type(e).__name__}: {e}"[:200]
    finally:
        req["finished"] = time.perf_counter()
        conn.close()


def run_open_loop(port, plan, t_open, timeout):
    """Send each request of ``plan`` when it is due (``t_open + due``) from
    one dispatcher, each on a thread of its own; returns the threads."""
    threads = []

    def dispatch():
        for req in plan:
            req["due_abs"] = t_open + req["due"]
            delay = req["due_abs"] - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            th = threading.Thread(target=stream_request,
                                  args=(port, req, timeout), daemon=True)
            th.start()
            threads.append(th)

    d = threading.Thread(target=dispatch, daemon=True)
    d.start()
    return d, threads


# ------------------------------------------------------- reference checks

def served_logits(params, cfg, prompts, n_decode):
    """Logits the served path computes for ``prompts`` (lists of ids): the
    engine's own step function, ``lm_decode_chunk_paged``, through a paged
    cache — chunked prefill K lanes at a time, then ``n_decode`` greedy
    decode steps through the cache.  Returns (sequences with the greedy
    tokens appended, per-row list of [position, logits row])."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import transformer
    s, heads = cfg["serving"], cfg["num_attention_heads"]
    bs, kk = s["kv_block_size"], s["prefill_chunk"]
    n = len(prompts)
    nb_row = -(-(max(map(len, prompts)) + n_decode + 1) // bs)
    tables = jnp.asarray(np.arange(1, n * nb_row + 1, dtype=np.int32)
                         .reshape(n, nb_row))
    cache = transformer.init_lm_cache_paged(
        params, n * nb_row + 1, bs, max_len=cfg["max_position_embeddings"],
        num_heads=heads)

    def step(p, cache, tokens, pos, lengths):
        return transformer.lm_decode_chunk_paged(
            p, tokens, pos, lengths, cache, tables, heads)

    jstep = jax.jit(step, donate_argnums=(1,))
    seqs = [list(p) for p in prompts]
    cursor = [0] * n            # tokens of each row already in the cache
    got = [[] for _ in range(n)]
    while any(len(g) <= n_decode for g in got):
        chunk = np.zeros((n, kk), np.int32)
        pos, lens = np.zeros(n, np.int32), np.ones(n, np.int32)
        for i in range(n):
            if len(got[i]) > n_decode:
                # a finished row idles on its last cached token
                chunk[i, 0], pos[i] = seqs[i][cursor[i] - 1], cursor[i] - 1
                continue
            piece = seqs[i][cursor[i]:cursor[i] + kk]
            chunk[i, :len(piece)], pos[i], lens[i] = piece, cursor[i], \
                len(piece)
        logits, cache = jstep(params, cache, chunk, pos, lens)
        logits = np.asarray(logits)
        for i in range(n):
            if len(got[i]) > n_decode:
                continue
            cursor[i] += int(lens[i])
            if cursor[i] == len(seqs[i]):
                got[i].append([cursor[i] - 1, logits[i]])
                seqs[i].append(int(logits[i].argmax()))
    del cache
    return seqs, got


def check_logits(params, cfg, seed, phases):
    """First-token logits and a few decode steps through the cache against
    the plain float32 reference's full forward pass.  Returns (ok, the
    jitted reference, the tolerance, the facts for the ``checks`` line)."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import lm as reference
    from paddle_tpu.core import dtypes
    rc = cfg["reference_check"]
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    prompts = [rng.randint(1, cfg["vocab_size"], n).tolist()
               for n in rc["prompt_lengths"]]
    seqs, got = served_logits(params, cfg, prompts, rc["decode_steps"])
    phases.mark("reference_served")
    t = max(map(len, seqs))
    ids = np.zeros((len(seqs), t), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    ref_fn = jax.jit(reference.logits, static_argnums=(2,))
    want = np.asarray(ref_fn(reference_params(params), jnp.asarray(ids),
                             cfg["num_attention_heads"]))
    err = max(float(np.abs(row - want[i, p]).max())
              for i, rows in enumerate(got) for p, row in rows)
    std = float(want.std())
    bf16 = dtypes.compute_dtype() == jnp.bfloat16
    stages = 4 * cfg["num_hidden_layers"] + 1
    tol = (4.5 * 2.0 ** -9 * math.sqrt(2 * stages) if bf16 else 1e-3) * std
    finite = all(np.isfinite(row).all() for rows in got for _p, row in rows)
    facts = dict(logits_max_abs_err=err, logits_tol=tol, ref_logit_std=std,
                 compute_dtype=jnp.dtype(dtypes.compute_dtype()).name,
                 compared_rows=sum(map(len, got)))
    return bool(finite and err <= tol), ref_fn, tol, facts


def check_served_tokens(params, cfg, ref_fn, tol, reqs, t_pad):
    """The tokens the server streamed for the warm-up requests, held to the
    reference: with random weights the largest logit can change on rounding,
    so a served token passes if the reference's logit for it is within
    2 x tol of the reference's largest at that position."""
    import jax.numpy as jnp
    ids = np.zeros((len(reqs), t_pad), np.int32)
    for i, r in enumerate(reqs):
        seq = r["prompt"] + r["tokens"]
        ids[i, :len(seq)] = seq
    want = np.asarray(ref_fn(reference_params(params), jnp.asarray(ids),
                             cfg["num_attention_heads"]))
    worst = 0.0
    for i, r in enumerate(reqs):
        for j, tok in enumerate(r["tokens"]):
            row = want[i, len(r["prompt"]) + j - 1]
            worst = max(worst, float(row.max() - row[tok]))
    return worst <= 2 * tol, worst


# ------------------------------------------------------------------- run

COUNTERS = ("errors_total", "gen_tokens_total", "decode_steps_total",
            "prefill_chunk_lanes_total")


def counters(engine):
    """The server's counters behind /metrics, read in one place so that a
    window's delta is of one moment."""
    return {name: getattr(engine.metrics, name) for name in COUNTERS}


def run(ctx):
    import jax
    from benchmark import arith, costs, harness, traffic
    from paddle_tpu.obs import trace as obstrace

    cfg, tr, phases = ctx["config"], ctx["traffic"], ctx["phases"]
    rehearsal = ctx["rehearsal"]
    devices = jax.devices()[:ctx["cell"]["chips"]]
    params = make_params(cfg, ctx["seed"])
    phases.mark("params")

    logits_ok, ref_fn, tol, facts = check_logits(params, cfg, ctx["seed"],
                                                 phases)
    phases.mark("reference_forward")

    server = Server(cfg, params)
    engine = server.engine
    phases.mark("engine")
    try:
        # warm-up: two small requests through the whole front, one after the
        # other, so every secondary program (admission writes, frees) exists
        # before the lead-in; their tokens are held to the reference
        rng = np.random.RandomState(int(ctx["seed"]) % (2 ** 32) ^ 0x5EED)
        warm = [{"prompt": rng.randint(1, cfg["vocab_size"], n).tolist(),
                 "max_tokens": m} for n, m in tr["warm_requests"]]
        for r in warm:
            stream_request(server.port, r, tr["request_timeout_s"])
        phases.mark("warm_requests")
        t_pad = max(cfg["reference_check"]["prompt_lengths"]) \
            + cfg["reference_check"]["decode_steps"] + 1
        warm_ok = all(r["error"] is None for r in warm)
        tokens_ok, margin = (False, None)
        if warm_ok:
            tokens_ok, margin = check_served_tokens(
                params, cfg, ref_fn, tol, warm, t_pad)
        phases.mark("reference_forward")

        seconds = ctx["seconds"]
        if ctx["trace"]:
            seconds = min(seconds, tr["trace_seconds"])
            obstrace.enable(sample=1.0, capacity=65536)
        plan = traffic.open_loop(tr, ctx["seed"], seconds, cfg["vocab_size"])
        t_open = time.perf_counter() + tr["lead_in_s"]
        dispatcher, threads = run_open_loop(server.port, plan, t_open,
                                            tr["request_timeout_s"])
        time.sleep(max(0.0, t_open - time.perf_counter()))
        phases.mark("lead_in")
        setup_s = time.perf_counter() - harness.T_PROCESS_START
        traces_at_open = engine.step_trace_count
        # the server's clocks: tpot samples are on time.monotonic(), the
        # obs/trace.py spans on time.time()
        before, m_open, w_open = counters(engine), time.monotonic(), \
            time.time()
        with harness.TraceWindow(ctx["trace"], ctx["trace_dir"]) as tw:
            time.sleep(max(0.0, t_open + seconds - time.perf_counter()))
            after, m_close, w_close = counters(engine), time.monotonic(), \
                time.time()
            traces_at_close = engine.step_trace_count
        t_close = t_open + seconds
        dispatcher.join()       # after it, ``threads`` is complete
        for th in threads:
            th.join(tr["request_timeout_s"])
        spans = obstrace.snapshot() if ctx["trace"] else None
        obstrace.disable()
        tpot = [s for s, t in zip(list(engine.metrics.tpot.samples),
                                  list(engine.metrics.tpot.times))
                if m_open <= t < m_close]
    finally:
        server.close()

    measured = [r for r in plan if r["measured"]]
    failed = [r for r in measured if r.get("error") is not None
              or "finished" not in r]
    late = [(r["sent"] - r["due_abs"]) * 1e3 for r in measured if "sent" in r]
    checks = {
        "logits_match_reference": logits_ok,
        "warm_requests_served": warm_ok,
        "served_tokens_match_reference": bool(tokens_ok),
        "no_compile_in_window": traces_at_open == traces_at_close == 1,
        "every_request_got_its_tokens": not failed,
        "no_server_errors": after["errors_total"] == before["errors_total"],
    }
    harness.say("checks", rehearsal, **checks, **facts,
                served_token_margin=margin,
                errors=[r["error"] for r in failed][:5],
                generator_late_ms_p95=arith.percentile(late, 95),
                requests_measured=len(measured), requests_lead_in=len(plan)
                - len(measured), drain_s=time.perf_counter() - t_close,
                decode_kernels=bool(engine.decode_kernels),
                decode_decline_reason=engine.decode_decline_reason,
                rate_rps=tr.get("rate_rps"), knee_rps=tr.get("knee_rps"),
                window_counters={k: after[k] - before[k] for k in COUNTERS},
                memory_stats=devices[0].memory_stats())
    return {
        "correct": all(checks.values()),
        "attempted": len(measured), "failed": len(failed),
        "setup_s": setup_s, "devices": devices,
        "requests": [{"due": r["due_abs"], "measured": r["measured"],
                      "prompt_tokens": len(r["prompt"]),
                      "token_times": r.get("token_times", []),
                      "ok": r.get("error") is None and "finished" in r}
                     for r in plan],
        "t_open": t_open, "t_close": t_close,
        "counters_before": before, "counters_after": after,
        "tpot_s": tpot, "spans": spans, "window_wall": (w_open, w_close),
        "decode_kernels": bool(engine.decode_kernels),
        "weight_bytes": costs.tree_bytes(params),
        "trace": tw.reduced, "trace_cost": tw.cost,
    }

#!/usr/bin/env python3
"""Driver for the hybrid trunk (models/hybrid_lm.py: KDA + MLA layers, a
routed expert layer that holds its share) served through the library's front
door, ``DecodeEngine(model=...) -> GenerationBatcher -> make_server``, as
``drivers/serve.py`` serves the transformer trunk: the same HTTP clients,
open loop, window, counters and observation keys; its own parameters,
engine, reference checks and ``run``.

Two entries beside ``run`` (``benchmark/sweep.py`` and ``run.py`` name
``drivers.serve`` and one check each, and are not this PR's to edit):

    python3 benchmark/drivers/serve_hybrid.py sweep --workload <cell> --rates 1,2,3
    python3 benchmark/drivers/serve_hybrid.py check --workload <cell> --seed <n> \\
        [--degrade int8|gate]

``sweep`` is ``benchmark/sweep.py`` with this driver's server; ``check`` is
the reference check alone, and with ``--degrade`` on a program that computes
in a lower precision (int8 weights) or drops a gate (the KDA output gate),
which has to come out as NOT correct."""

import json
import math
import os
import sys
import threading
import time

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.drivers.serve import (run_open_loop,  # noqa: E402
                                     stream_request)


# ------------------------------------------------------------- the system

def model_config(cfg):
    from paddle_tpu.models import hybrid_lm
    return hybrid_lm.config_from_hf(cfg)


def make_params(cfg, seed):
    """The trunk's parameters from the seed, on the device, in the
    configuration's ``param_dtype``.  ``hybrid_lm.init`` leaves gains at 1
    and the router bias at 0: every 1-D leaf (norm gains, A_log, dt_bias,
    the router bias) is perturbed here so that the reference check sees
    it."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import hybrid_lm
    k_init, k_noise = jax.random.split(
        jax.random.PRNGKey(int(seed) % (2 ** 31 - 1)))
    p = hybrid_lm.init(k_init, model_config(cfg),
                       jnp.dtype(cfg.get("param_dtype", "float32")),
                       emb_std=cfg.get("embedding_std", 0.02))
    leaves, treedef = jax.tree_util.tree_flatten(p)
    keys = jax.random.split(k_noise, len(leaves))
    noisy = jax.jit(lambda x, k: x + 0.02 * jax.random.normal(
        k, x.shape, x.dtype))
    leaves = [noisy(x, k) if x.ndim == 1 else x
              for x, k in zip(leaves, keys)]
    return jax.block_until_ready(jax.tree_util.tree_unflatten(treedef,
                                                              leaves))


def reference_params(p, cfg):
    """The program's parameter tree as the plain reference wants it: the
    fused q|k|v projection and convolution split, the held experts and the
    shared expert apart."""
    import jax.numpy as jnp
    layers = []
    for lp, (attn_kind, ffn_kind) in zip(p["layers"],
                                         model_config(cfg).layers):
        a = dict(lp["attn"])
        if attn_kind == "kda":
            for name, w, c in zip("qkv", jnp.split(a.pop("wqkv"), 3, axis=1),
                                  jnp.split(a.pop("conv"), 3, axis=1)):
                a["w" + name], a["conv_" + name] = w, c
        f = lp["ffn"]
        if ffn_kind == "moe":
            f = {"router": f["router"], "router_bias": f["router_bias"],
                 "shared": f["shared"],
                 "experts": {k: f[k] for k in ("wg", "wu", "wd")}}
        layers.append({"norm1": lp["norm1"], "norm2": lp["norm2"],
                       "attn": a, "ffn": f})
    return {"emb": p["emb"], "head": p["head"], "norm_f": p["norm_f"],
            "layers": layers}


def served_model(cfg):
    from paddle_tpu.models import hybrid_lm
    return hybrid_lm.Served(model_config(cfg), cfg["serving"]["kv_dtype"])


class Server:
    """The engine behind the HTTP front, on an ephemeral local port."""

    def __init__(self, cfg, params):
        from paddle_tpu.serving.decode_engine import (DecodeEngine,
                                                      GenerationBatcher)
        from paddle_tpu.serving.server import make_server
        s = cfg["serving"]
        self.engine = DecodeEngine(
            params, model=served_model(cfg), num_slots=s["slots"],
            max_len=s["max_len"], kv_layout=s["kv_layout"],
            kv_block_size=s["kv_block_size"],
            prefix_cache=s["prefix_cache"],
            prefill_chunk=s["prefill_chunk"], name="bench")
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


# ------------------------------------------------------- reference checks

def served_logits(params, cfg, seqs, n_decode):
    """What the served path computes for ``seqs`` (lists of ids): the
    engine's own step function, ``hybrid_lm.decode_chunk``, through its own
    cache (per-slot state, paged latent pool): chunked prefill K lanes at a
    time, then ``n_decode`` greedy decode steps.  The step has the ENGINE's
    shape, all ``serving.slots`` rows of it, the rows past ``seqs`` idling
    at position 0 as free slots do: the check is of the timed sizes.
    Returns (sequences with the greedy tokens appended, per-row list of
    [position, logits row], per-row {position: chosen experts [expert
    layers, top_k]})."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import hybrid_lm
    s, mc = cfg["serving"], model_config(cfg)
    bs, kk = s["kv_block_size"], s["prefill_chunk"]
    live, n = len(seqs), max(len(seqs), s["slots"])
    nb_row = -(-(max(map(len, seqs)) + n_decode + 1) // bs)
    tables = jnp.asarray(np.arange(1, n * nb_row + 1, dtype=np.int32)
                         .reshape(n, nb_row))
    cache = served_model(cfg).init_cache(n, n * nb_row + 1, bs)

    def step(p, cache, tokens, pos, lengths):
        return hybrid_lm.decode_chunk(p, mc, tokens, pos, lengths, cache,
                                      tables, with_routes=True)

    jstep = jax.jit(step, donate_argnums=(1,))
    seqs = [list(p) for p in seqs]
    cursor = [0] * live         # tokens of each row already in the cache
    got = [[] for _ in range(live)]
    routes = [{} for _ in range(live)]
    while any(len(g) <= n_decode for g in got):
        chunk = np.zeros((n, kk), np.int32)
        pos, lens = np.zeros(n, np.int32), np.ones(n, np.int32)
        for i in range(live):
            if len(got[i]) > n_decode:
                # a finished row idles; what it does to its own state is
                # never read again
                chunk[i, 0], pos[i] = seqs[i][-1], cursor[i]
                continue
            piece = seqs[i][cursor[i]:cursor[i] + kk]
            chunk[i, :len(piece)], pos[i], lens[i] = piece, cursor[i], \
                len(piece)
        logits, cache, chosen = jstep(params, cache, chunk, pos, lens)
        logits = np.asarray(logits)
        chosen = np.stack([np.asarray(c) for c in chosen], axis=2) \
            if chosen else np.zeros((n, kk, 0, 1), np.int32)
        for i in range(live):
            if len(got[i]) > n_decode:
                continue
            for j in range(int(lens[i])):
                routes[i][cursor[i] + j] = chosen[i, j]
            cursor[i] += int(lens[i])
            if cursor[i] == len(seqs[i]):
                got[i].append([cursor[i] - 1, logits[i]])
                seqs[i].append(int(logits[i].argmax()))
    del cache
    return seqs, got, routes


def reference_forward(params, cfg, seqs, routes, t_pad=None):
    """The reference's full forward over ``seqs`` padded to one length,
    handed the program's expert choice where it made one (padding and the
    last appended token are never fed to the program: any expert does
    there, the forward is causal).  -> (logits [B,T,V], selection scores
    per expert layer [B,T,E]) as numpy."""
    import jax.numpy as jnp
    from benchmark.reference import kimi_linear as reference
    t = max(t_pad or 0, max(map(len, seqs)))
    ids = np.zeros((len(seqs), t), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    n_moe = sum(1 for _a, f in reference.layer_kinds(cfg) if f == "moe")
    k = cfg["num_experts_per_token"]
    chosen = np.tile(np.arange(k, dtype=np.int32), (len(seqs), t, n_moe, 1))
    for i, by_pos in enumerate(routes):
        for p, c in by_pos.items():
            chosen[i, p] = c
    want, selects = reference.logits(
        reference_params(params, cfg), jnp.asarray(ids), cfg,
        routes=[jnp.asarray(chosen[:, :, l]) for l in range(n_moe)])
    return np.asarray(want), [np.asarray(s) for s in selects]


def tolerances(cfg, want_std, select_std):
    """(logit tolerance, router tolerance, the compute dtype's name).  See
    the configuration's ``reference_check`` for the reasons."""
    import jax.numpy as jnp
    from paddle_tpu.core import dtypes
    rc = cfg["reference_check"]
    bf16 = dtypes.compute_dtype() == jnp.bfloat16
    stages = rc["matmul_stages_per_layer"] * cfg["num_hidden_layers"] + 1
    u = rc["sigmas"] * 2.0 ** -9 * math.sqrt(2 * stages) if bf16 else 1e-3
    return u * want_std, u * select_std, \
        jnp.dtype(dtypes.compute_dtype()).name


def check_served(params, cfg, seed):
    """``served_logits`` of the check's own prompts (seeded lengths that
    cross several chunks and blocks)."""
    rc = cfg["reference_check"]
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    prompts = [rng.randint(1, cfg["vocab_size"], n).tolist()
               for n in rc["prompt_lengths"]]
    return served_logits(params, cfg, prompts, rc["decode_steps"])


def check_logits(params, cfg, seed, phases, served=None):
    """First-token logits and a few decode steps through the state and the
    latent pool against the plain float32 reference's full forward pass,
    and the program's expert choice against the reference's scores.
    ``served``: what ``check_served`` returned for the parameters the
    PROGRAM runs, where they differ from the ones the reference is given
    (``check --degrade``).  Returns (ok, the logit tolerance, the facts
    for the ``checks`` line)."""
    from benchmark.reference import kimi_linear as reference
    seqs, got, routes = served or check_served(params, cfg, seed)
    phases.mark("reference_served")
    want, selects = reference_forward(params, cfg, seqs, routes)
    err = max(float(np.abs(row - want[i, p]).max())
              for i, rows in enumerate(got) for p, row in rows)
    finite = all(np.isfinite(row).all() for rows in got for _p, row in rows)
    # every chosen expert's reference score against the reference's k-th
    # largest: a choice the reference would not have made by more than the
    # tolerance is a wrong router, not a rounding
    k = cfg["num_experts_per_token"]
    (first, count), _total = reference.held_experts(cfg)
    shortfall, load = 0.0, []
    for l, sel in enumerate(selects):
        picks = []
        for i, by_pos in enumerate(routes):
            for p, chosen in by_pos.items():
                kth = np.partition(sel[i, p], -k)[-k]
                shortfall = max(shortfall,
                                float(kth - sel[i, p][chosen[l]].min()))
                picks.append(chosen[l])
        local = np.concatenate(picks) - first
        held = np.bincount(local[(local >= 0) & (local < count)],
                           minlength=count)
        load.append(float(held.max() / max(held.mean(), 1e-9)))
    tol, router_tol, cd = tolerances(
        cfg, float(want.std()),
        float(np.std(selects[0])) if selects else 0.0)
    facts = dict(logits_max_abs_err=err, logits_tol=tol,
                 ref_logit_std=float(want.std()),
                 router_shortfall_max=shortfall, router_tol=router_tol,
                 expert_load_max_over_mean=max(load) if load else None,
                 compute_dtype=cd, compared_rows=sum(map(len, got)))
    ok = finite and err <= tol and shortfall <= router_tol
    return bool(ok), tol, facts


def served_routes(steps):
    """The expert choice the SERVER made, from ``engine.recorded_steps()``
    of requests served one after the other: one {position: chosen experts
    [expert layers, top_k]} a request.  The seated slot is the one that is
    not idling at position 0 on a single lane; a request starts where its
    position returns to 0."""
    out = []
    for tokens, pos, lens, chosen in steps:
        slot = int(np.argmax(pos + lens))
        if pos[slot] + lens[slot] <= 1:
            continue                        # nothing seated in this step
        if pos[slot] == 0:
            out.append({})
        chosen = np.asarray(chosen)
        for j in range(int(lens[slot])):
            out[-1][int(pos[slot]) + j] = chosen[:, slot, j]
    return out


def check_served_tokens(params, cfg, tol, reqs, routes, t_pad):
    """The tokens the server streamed for the warm-up requests, held to
    the reference as ``drivers/serve.py`` holds them: a served token passes
    if the reference's logit for it is within 2 x tol of the reference's
    largest at that position.  The reference is handed the expert choice the
    server's own steps made (``served_routes``): read from a replay instead,
    a router near a tie chose another expert than the server had in 5 runs
    of 12, and one run failed for it (PERF.md 27.3)."""
    seqs = [r["prompt"] + r["tokens"] for r in reqs]
    fed = all(set(range(len(s) - 1)) <= set(by_pos)
              for s, by_pos in zip(seqs, routes)) and len(routes) == len(reqs)
    if not fed:
        return False, None
    want, _selects = reference_forward(params, cfg, seqs, routes, t_pad)
    worst = 0.0
    for i, r in enumerate(reqs):
        for j, tok in enumerate(r["tokens"]):
            row = want[i, len(r["prompt"]) + j - 1]
            worst = max(worst, float(row.max() - row[tok]))
    return worst <= 2 * tol, worst


# ------------------------------------------------------------------- run

COUNTERS = ("errors_total", "gen_tokens_total", "decode_steps_total",
            "prefill_chunk_lanes_total", "active_slot_steps_total",
            "state_resets_total")


def counters(engine):
    return {name: getattr(engine.metrics, name) for name in COUNTERS}


def run(ctx):
    import jax
    from benchmark import arith, costs, costs_hybrid, harness, traffic
    from paddle_tpu.obs import trace as obstrace

    cfg, tr, phases = ctx["config"], ctx["traffic"], ctx["phases"]
    rehearsal = ctx["rehearsal"]
    devices = jax.devices()[:ctx["cell"]["chips"]]
    params = make_params(cfg, ctx["seed"])
    phases.mark("params")

    logits_ok, tol, facts = check_logits(params, cfg, ctx["seed"], phases)
    phases.mark("reference_forward")

    server = Server(cfg, params)
    engine = server.engine
    phases.mark("engine")
    try:
        # warm-up: two small requests through the whole front, one after
        # the other; their tokens are held to the reference
        rng = np.random.RandomState(int(ctx["seed"]) % (2 ** 32) ^ 0x5EED)
        warm = [{"prompt": rng.randint(1, cfg["vocab_size"], n).tolist(),
                 "max_tokens": m} for n, m in tr["warm_requests"]]
        engine.record_steps(True)
        for r in warm:
            stream_request(server.port, r, tr["request_timeout_s"])
        routes = served_routes(engine.recorded_steps())
        engine.record_steps(False)
        phases.mark("warm_requests")
        t_pad = max(cfg["reference_check"]["prompt_lengths"]) \
            + cfg["reference_check"]["decode_steps"] + 1
        warm_ok = all(r["error"] is None for r in warm)
        tokens_ok, margin = (False, None)
        if warm_ok:
            tokens_ok, margin = check_served_tokens(params, cfg, tol, warm,
                                                    routes, t_pad)
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
        gauges = {k: getattr(engine.metrics, k) for k in
                  ("recurrent_state_bytes", "latent_pool_bytes")}
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
    weight_bytes = costs_hybrid.step_stream_bytes(params)
    harness.say("checks", rehearsal, **checks, **facts,
                served_token_margin=margin,
                errors=[r["error"] for r in failed][:5],
                generator_late_ms_p95=arith.percentile(late, 95),
                requests_measured=len(measured), requests_lead_in=len(plan)
                - len(measured), drain_s=time.perf_counter() - t_close,
                kda_kernels=bool(engine.kda_kernels),
                kda_decline_reason=engine.kda_decline_reason,
                rate_rps=tr.get("rate_rps"), knee_rps=tr.get("knee_rps"),
                window_counters={k: after[k] - before[k] for k in COUNTERS},
                param_bytes=costs.tree_bytes(params),
                weight_stream_bytes=weight_bytes, **gauges,
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
        "kda_kernels": bool(engine.kda_kernels),
        "weight_bytes": weight_bytes,
        "trace": tw.reduced, "trace_cost": tw.cost,
    }


# --------------------------------------------------------------- entries

def _degraded(params, how):
    """The parameters a lower-precision or a gate-dropping program would
    run, made IN PLACE (the model nearly fills the device: the caller makes
    the true ones again from the seed): ``int8`` rounds every matrix to 8
    bits a value with one scale an output column; ``gate`` zeroes the KDA
    output gate's projection (the gate then reads sigmoid(0) everywhere)."""
    import jax
    import jax.numpy as jnp

    def int8(x):
        scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-2,
                        keepdims=True) / 127.0
        q = jnp.round(x.astype(jnp.float32) / jnp.maximum(scale, 1e-30))
        return (q * scale).astype(x.dtype)

    if how == "int8":
        rounded = jax.jit(int8, donate_argnums=(0,))
        return jax.tree_util.tree_map(
            lambda x: rounded(x) if x.ndim >= 2 else x, params)
    return dict(params, layers=[
        dict(lp, attn=dict(lp["attn"], wg2=jnp.zeros_like(lp["attn"]["wg2"])))
        if "wg2" in lp["attn"] else lp for lp in params["layers"]])


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("entry", choices=("sweep", "check"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--rates")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--degrade", choices=("int8", "gate"))
    args = ap.parse_args(argv)
    if args.entry == "sweep":
        # benchmark/sweep.py, whose server and parameters are
        # ``drivers.serve``'s by name, with this driver's in their place
        from unittest import mock
        from benchmark import sweep
        from benchmark.drivers import serve
        with mock.patch.multiple(serve, Server=Server,
                                 make_params=make_params):
            return sweep.main(["--workload", args.workload, "--rates",
                               args.rates, "--seconds", str(args.seconds),
                               "--seed", str(args.seed)])
    from benchmark import harness
    spec = harness.Spec()
    cell = spec.cell(args.workload)
    cfg = spec.config(cell)
    harness.device_gate(cell["chips"], False)
    harness.compile_cache()
    served = None
    if args.degrade:
        served = check_served(
            _degraded(make_params(cfg, args.seed), args.degrade), cfg,
            args.seed)
    params = make_params(cfg, args.seed)
    ok, _tol, facts = check_logits(params, cfg, args.seed, harness.Phases(),
                                   served=served)
    print(json.dumps({"check": dict(facts, ok=ok, degrade=args.degrade,
                                    seed=args.seed)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Driver for the hybrid trunk's second family (models/hybrid_lm.py as
``pangu_ultra_moe`` builds it: MLA in every layer with a low-rank query and a
rotated key part, sandwich norms, a leading dense layer, a held share of
sigmoid-routed experts) served through the library's front door,
``DecodeEngine(model=...) -> GenerationBatcher -> make_server``.  The
parameters, the server, the served path's replay, the tolerances and the
HTTP clients are ``drivers/serve_hybrid.py``'s and ``drivers/serve.py``'s;
the reference (``reference/pangu_moe.py``), what is counted and ``run`` are
this file's.

Two entries beside ``run``, as ``serve_hybrid`` has them:

    python3 benchmark/drivers/serve_pangu.py sweep --workload <cell> --rates 0.4,0.6
    python3 benchmark/drivers/serve_pangu.py check --workload <cell> --seed <n> \\
        [--degrade int8|postnorm|rope]

``check --degrade`` runs a program that computes in a lower precision (int8
matrices) or leaves a part of the block out (the norms after the sublayers;
the rotation), which has to come out as NOT correct."""

import json
import os
import sys
import time

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.drivers.serve import (run_open_loop,  # noqa: E402
                                     stream_request)
from benchmark.drivers.serve_hybrid import (Server,  # noqa: E402
                                            _degraded, check_served,
                                            make_params, served_routes,
                                            tolerances)
from benchmark.reference import pangu_moe as reference  # noqa: E402

# what a degraded program is built from: the configuration it runs
DEGRADED_CONFIG = {"postnorm": {"sandwich_norm": False},
                   "rope": {"rope_theta": None}}


def reference_params(p):
    """The program's parameter tree as the plain reference wants it: the
    held experts and the shared expert apart."""
    layers = []
    for lp in p["layers"]:
        f = lp["ffn"]
        if "router" in f:
            f = {"router": f["router"], "router_bias": f["router_bias"],
                 "shared": f["shared"],
                 "experts": {k: f[k] for k in ("wg", "wu", "wd")}}
        layers.append(dict(lp, ffn=f))
    return dict(p, layers=layers)


# ------------------------------------------------------- reference checks

def reference_forward(params, cfg, seqs, routes, t_pad=None):
    """The reference's full forward over ``seqs`` padded to one length,
    handed the program's expert choice where it made one (padding and the
    last appended token are never fed to the program: any expert does
    there, the forward is causal).  -> (logits [B,T,V], selection scores
    per expert layer [B,T,E]) as numpy."""
    import jax.numpy as jnp
    t = max(t_pad or 0, max(map(len, seqs)))
    ids = np.zeros((len(seqs), t), np.int32)
    for i, s in enumerate(seqs):
        ids[i, :len(s)] = s
    n_moe = reference.layer_kinds(cfg).count("moe")
    k = cfg["num_experts_per_tok"]
    chosen = np.tile(np.arange(k, dtype=np.int32), (len(seqs), t, n_moe, 1))
    for i, by_pos in enumerate(routes):
        for p, c in by_pos.items():
            chosen[i, p] = c
    want, selects = reference.logits(
        reference_params(params), jnp.asarray(ids), cfg,
        routes=[jnp.asarray(chosen[:, :, l]) for l in range(n_moe)])
    return np.asarray(want), [np.asarray(s) for s in selects]


def check_logits(params, cfg, seed, phases, served=None):
    """First-token logits and a few decode steps through the latent pool
    (chunked prefill at the engine's own shape, ``serve_hybrid.
    served_logits``) against the plain float32 reference's full forward
    pass, and the program's expert choice against the reference's scores.
    ``served``: what ``check_served`` returned for the PROGRAM that runs,
    where it differs from the one the reference describes (``check
    --degrade``).  Returns (ok, the logit tolerance, the facts for the
    ``checks`` line)."""
    seqs, got, routes = served or check_served(params, cfg, seed)
    phases.mark("reference_served")
    want, selects = reference_forward(params, cfg, seqs, routes)
    err = max(float(np.abs(row - want[i, p]).max())
              for i, rows in enumerate(got) for p, row in rows)
    finite = all(np.isfinite(row).all() for rows in got for _p, row in rows)
    # every chosen expert's reference score against the reference's k-th
    # largest: a choice the reference would not have made by more than the
    # tolerance is a wrong router, not a rounding
    k = cfg["num_experts_per_tok"]
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


def check_served_tokens(params, cfg, tol, reqs, routes, t_pad):
    """The tokens the server streamed for the warm-up requests: a served
    token passes if the reference's logit for it is within 2 x tol of the
    reference's largest at that position, the reference handed the expert
    choice the server's own steps made (``serve_hybrid.served_routes``)."""
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
            "attended_positions_total")


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
        latent_pool_bytes = engine.metrics.latent_pool_bytes
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
                mla_kernels=bool(engine.mla_kernels),
                mla_decline_reason=engine.mla_decline_reason,
                rate_rps=tr.get("rate_rps"), knee_rps=tr.get("knee_rps"),
                window_counters={k: after[k] - before[k] for k in COUNTERS},
                param_bytes=costs.tree_bytes(params),
                weight_stream_bytes=weight_bytes,
                latent_pool_bytes=latent_pool_bytes,
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
        # no KDA layer: False, and there so that moe_expert_share reads
        "kda_kernels": False,
        "mla_kernels": bool(engine.mla_kernels),
        "weight_bytes": weight_bytes,
        "trace": tw.reduced, "trace_cost": tw.cost,
    }


# --------------------------------------------------------------- entries

def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("entry", choices=("sweep", "check"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--rates")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--degrade", choices=("int8",) + tuple(DEGRADED_CONFIG))
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
    if args.degrade == "int8":
        served = check_served(
            _degraded(make_params(cfg, args.seed), "int8"), cfg, args.seed)
    params = make_params(cfg, args.seed)
    if args.degrade in DEGRADED_CONFIG:
        served = check_served(
            params, dict(cfg, **DEGRADED_CONFIG[args.degrade]), args.seed)
    ok, _tol, facts = check_logits(params, cfg, args.seed, harness.Phases(),
                                   served=served)
    print(json.dumps({"check": dict(facts, ok=ok, degrade=args.degrade,
                                    seed=args.seed)}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

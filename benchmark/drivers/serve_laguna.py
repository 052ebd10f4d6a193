#!/usr/bin/env python3
"""Driver for the hybrid trunk's fourth family (models/hybrid_lm.py as
``laguna`` builds it: window attention over per-slot rings beside full
attention over the paged pools, YaRN on half of each full head, a per-head
output gate, a softmax router over a held share of the experts and a shared
expert) served through the library's front door, ``DecodeEngine(model=...)
-> GenerationBatcher -> make_server``.  The parameters, the server (with the
engine options ``serve_jamba`` reads from the configuration), the requests
of the check, the tolerance's form and the HTTP clients are
``drivers/serve_hybrid.py``'s, ``drivers/serve_jamba.py``'s and
``drivers/serve.py``'s; the reference (``reference/laguna.py``), the check,
what is counted and ``run`` are this file's.

The check has no program of its own, as Jamba's: set-up serves a few
requests through the server, one after the other, and the ENGINE's compiled
step (``report_logits``) leaves each step's logits and expert choice: every
streamed token's logits row is held to the reference's full forward pass,
handed the choice the server's step made, and the choice is judged apart
against the reference's router.

Two entries beside ``run``:

    python3 benchmark/drivers/serve_laguna.py sweep --workload <cell> --rates 0.4,0.6
    python3 benchmark/drivers/serve_laguna.py check --workload <cell> --seed <n> \\
        [--degrade int8|nowindow|noyarn|nogate ...]

``check`` is set-up's check alone (exit 1 unless every program it ran read
correct); with ``--degrade`` the SERVER then runs, for each name given, a program
that computes in a lower precision (int8 matrices) or leaves a part of the
layer out (the window: window layers attend every position; YaRN's
attention factor; the output gate), which has to come out as NOT
correct."""

import gc
import json
import os
import sys
import time
from unittest import mock

import numpy as np

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))))

from benchmark.drivers.serve import (run_open_loop,  # noqa: E402
                                     stream_request)
from benchmark.drivers.serve_hybrid import (_degraded,  # noqa: E402
                                            make_params, model_config,
                                            tolerances)
from benchmark.drivers.serve_jamba import (check_requests,  # noqa: E402
                                           make_server)
from benchmark.reference import laguna as reference  # noqa: E402

DEGRADED = ("int8", "nowindow", "noyarn", "nogate")


def degraded_config(cfg, how, longest):
    """The configuration a wrong program is built from.  ``nowindow``: the
    window layers attend every position, over the paged pool, which then
    holds every layer's K and V: ``max_len`` is cut to the ``longest``
    request the check serves (the program is a control, not a
    deployment)."""
    if how == "nowindow":
        bs = cfg["serving"]["kv_block_size"]
        return dict(cfg, sliding_window=None, serving=dict(
            cfg["serving"], max_len=-(-longest // bs) * bs + bs))
    if how == "noyarn":
        rp = cfg["rope_parameters"]
        return dict(cfg, rope_parameters=dict(rp, full_attention=dict(
            rp["full_attention"], attention_factor=1.0)))
    if how == "nogate":
        return dict(cfg, gating=False)
    return cfg


def reference_params(p, cfg):
    """The program's parameter tree as the plain reference wants it: the
    fused q | k | v projection split, the held experts and the shared
    expert apart."""
    mc = model_config(cfg)
    layers = []
    for lp, (kind, ffn) in zip(p["layers"], mc.layers):
        a = dict(lp["attn"])
        d_q = mc.attention(kind)["num_heads"] * mc.attn_head_dim
        d_kv = mc.attn_kv_heads * mc.attn_head_dim
        w = a.pop("wqkv")
        a["wq"], a["wk"], a["wv"] = \
            w[:, :d_q], w[:, d_q:d_q + d_kv], w[:, d_q + d_kv:]
        f = lp["ffn"]
        if ffn == "moe":
            f = {"router": f["router"], "shared": f["shared"],
                 "experts": {k: f[k] for k in ("wg", "wu", "wd")}}
        layers.append({"norm1": lp["norm1"], "norm2": lp["norm2"],
                       "attn": a, "ffn": f})
    return {"emb": p["emb"], "head": p["head"], "norm_f": p["norm_f"],
            "layers": layers}


# ------------------------------------------------------- reference checks

def serve_recorded(server, reqs, timeout):
    """``reqs`` through the server's whole front, one after the other, the
    engine recording its steps.  Each request gains ``rows``: [position,
    the step's own logits row] of every token it streamed, and ``routes``:
    {position: chosen experts [expert layers, top_k]} of every position the
    server's steps fed for it.  False where a request failed or its steps do
    not account for its tokens."""
    engine = server.engine
    for r in reqs:
        engine.record_steps(True)
        stream_request(server.port, r, timeout)
        steps = engine.recorded_steps()
        engine.record_steps(False)
        if r["error"] is not None:
            return False
        n, rows, routes = len(r["prompt"]), [], {}
        for _tokens, pos, lens, (chosen, logits) in steps:
            # one request at a time: the seated slot is the one not idling
            # at position 0 on a single lane
            s = int(np.argmax(pos + lens))
            chosen = np.asarray(chosen)
            for j in range(int(lens[s])):
                routes[int(pos[s]) + j] = chosen[:, s, j]
            end = int(pos[s] + lens[s])
            if end >= n:
                rows.append([end - 1, np.asarray(logits[s])])
        del steps
        if [int(row.argmax()) for _p, row in rows] != r["tokens"]:
            return False
        r["rows"], r["routes"] = rows, routes
    return True


def check_against_reference(params, cfg, reqs):
    """Every recorded logits row against the plain float32 reference's full
    forward pass over the request's prompt and tokens, the reference handed
    the expert choice the server's steps made; that choice judged apart:
    each chosen expert's reference router logit against the reference's
    k-th largest (the softmax ranks as the logits do; a choice the
    reference would not have made by more than the router's tolerance is a
    wrong router, not a rounding).  Requests no
    longer than the ``reference_check`` prompts share one padded forward; a
    longer one pays its own.  Returns ({check: passed}, the facts for the
    ``checks`` line)."""
    import jax.numpy as jnp
    rc = cfg["reference_check"]
    k = cfg["num_experts_per_tok"]
    t_pad = max(rc["prompt_lengths"]) + rc["decode_steps"] + 1
    fits = lambda r: len(r["prompt"]) + len(r["tokens"]) <= t_pad
    groups = [[r for r in reqs if fits(r)]] \
        + [[r] for r in reqs if not fits(r)]
    ref_params = reference_params(params, cfg)
    n_moe = [f for _a, f in reference.layer_kinds(cfg)].count("moe")
    err, margin, shortfall, wants, logit_std = 0.0, 0.0, 0.0, [], []
    for group in filter(None, groups):
        seqs = [r["prompt"] + r["tokens"] for r in group]
        t = max(t_pad if fits(group[0]) else 0, max(map(len, seqs)))
        ids = np.zeros((len(seqs), t), np.int32)
        chosen = np.tile(np.arange(k, dtype=np.int32),
                         (len(seqs), t, n_moe, 1))
        for i, (seq, r) in enumerate(zip(seqs, group)):
            ids[i, :len(seq)] = seq
            for p, c in r["routes"].items():
                chosen[i, p] = c
        at = [[p for p, _row in r["rows"]] for r in group]
        at = np.asarray([a + a[-1:] * (max(map(len, at)) - len(a))
                         for a in at])
        want, router = reference.forward(
            ref_params, jnp.asarray(ids), cfg, positions=at,
            routes=[jnp.asarray(chosen[:, :, l]) for l in range(n_moe)])
        want = np.asarray(want)
        wants.append(want.reshape(-1, want.shape[-1]))
        for l, z in enumerate(router):
            z = np.asarray(z)
            logit_std.append(float(z.std()))
            for i, r in enumerate(group):
                fed = np.asarray(sorted(r["routes"]))
                rows = z[i, fed]
                kth = np.partition(rows, -k, axis=-1)[:, -k]
                picked = np.take_along_axis(rows, chosen[i, fed, l], -1)
                shortfall = max(shortfall, float((kth - picked.min(-1))
                                                 .max()))
        for i, r in enumerate(group):
            for j, (_p, row) in enumerate(r["rows"]):
                err = max(err, float(np.abs(row - want[i, j]).max()))
                margin = max(margin, float(want[i, j].max()
                                           - want[i, j, r["tokens"][j]]))
    std = float(np.concatenate(wants).std())
    tol, router_tol, cd = tolerances(cfg, std, float(np.mean(logit_std)))
    finite = all(np.isfinite(row).all() for r in reqs for _p, row in r["rows"])
    facts = dict(logits_max_abs_err=err, logits_tol=tol, ref_logit_std=std,
                 logits_err_over_std=err / std,
                 router_shortfall_max=shortfall, router_tol=router_tol,
                 served_token_margin=margin, compute_dtype=cd,
                 compared_rows=sum(len(r["rows"]) for r in reqs),
                 routed_positions=sum(len(r["routes"]) for r in reqs))
    checks = {"warm_requests_served": True,
              "logits_match_reference": bool(finite and err <= tol),
              "router_matches_reference": shortfall <= router_tol,
              "served_tokens_match_reference": margin <= 2 * tol}
    return checks, facts


def setup_check(server, params, cfg, tr, seed, phases):
    """-> ({check: passed}, facts) of set-up's requests through ``server``."""
    reqs = check_requests(cfg, tr, seed)
    served = serve_recorded(server, reqs, tr["request_timeout_s"])
    phases.mark("warm_requests")
    checks, facts = {"warm_requests_served": False}, {}
    if served:
        checks, facts = check_against_reference(params, cfg, reqs)
    phases.mark("reference_forward")
    return checks, facts


# ------------------------------------------------------------------- run

COUNTERS = ("errors_total", "gen_tokens_total", "decode_steps_total",
            "prefill_chunk_lanes_total", "active_slot_steps_total",
            "attended_positions_total", "window_attended_positions_total",
            "read_positions_total", "window_read_positions_total")


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
    server = make_server(cfg, params)
    engine = server.engine
    phases.mark("engine")
    try:
        # warm-up: a few requests through the whole front, one after the
        # other, each step's logits and expert choice held to the reference
        checks, facts = setup_check(server, params, cfg, tr, ctx["seed"],
                                    phases)

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
                  ("recurrent_state_bytes", "slot_state_bytes",
                   "latent_pool_bytes", "window_ring_bytes")}
    finally:
        server.close()

    measured = [r for r in plan if r["measured"]]
    failed = [r for r in measured if r.get("error") is not None
              or "finished" not in r]
    late = [(r["sent"] - r["due_abs"]) * 1e3 for r in measured if "sent" in r]
    checks.update({
        "no_compile_in_window": traces_at_open == traces_at_close == 1,
        "every_request_got_its_tokens": not failed,
        "no_server_errors": after["errors_total"] == before["errors_total"],
    })
    weight_bytes = costs_hybrid.step_stream_bytes(params)
    harness.say("checks", rehearsal, **checks, **facts,
                errors=[r["error"] for r in failed][:5],
                generator_late_ms_p95=arith.percentile(late, 95),
                requests_measured=len(measured), requests_lead_in=len(plan)
                - len(measured), drain_s=time.perf_counter() - t_close,
                window_kernels=bool(engine.window_kernels),
                window_decline_reason=engine.window_decline_reason,
                attn_kernels=bool(engine.attn_kernels),
                attn_decline_reason=engine.attn_decline_reason,
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
        # no KDA layer: False, and there so that moe_expert_share reads
        "kda_kernels": False,
        "attn_kernels": bool(engine.attn_kernels),
        "window_kernels": bool(engine.window_kernels),
        "weight_bytes": weight_bytes,
        "trace": tw.reduced, "trace_cost": tw.cost,
    }


# --------------------------------------------------------------- entries

def degraded_server(cfg, params, how, longest):
    """A server that runs a wrong program made of the true ``params``:
    ``int8`` rounds every matrix to 8 bits a value (``serve_hybrid.
    _degraded``, IN PLACE: the caller makes the true ones again); the others
    are built from ``degraded_config``.  The engine traces its step while it
    is built, so the wrong parts are in the step it serves with."""
    if how == "int8":
        params = _degraded(params, "int8")
    return make_server(degraded_config(cfg, how, longest), params)


def main(argv=None):
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("entry", choices=("sweep", "check"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=12345)
    ap.add_argument("--rates")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--degrade", choices=DEGRADED, nargs="+")
    args = ap.parse_args(argv)
    if args.entry == "sweep":
        # benchmark/sweep.py, whose server and parameters are
        # ``drivers.serve``'s by name, with this driver's in their place
        from benchmark import sweep
        from benchmark.drivers import serve
        with mock.patch.multiple(serve, Server=make_server,
                                 make_params=make_params):
            return sweep.main(["--workload", args.workload, "--rates",
                               args.rates, "--seconds", str(args.seconds),
                               "--seed", str(args.seed)])
    from benchmark import harness
    spec = harness.Spec()
    cell = spec.cell(args.workload)
    cfg, tr = spec.config(cell), spec.traffic(cell)
    harness.device_gate(cell["chips"], False)
    harness.compile_cache()
    params, all_ok = make_params(cfg, args.seed), True
    for how in [None] + (args.degrade or []):
        reqs = check_requests(cfg, tr, args.seed)
        longest = max(len(r["prompt"]) + r["max_tokens"] for r in reqs)
        server = degraded_server(cfg, params, how, longest)
        try:
            served = serve_recorded(server, reqs, tr["request_timeout_s"])
        finally:
            server.close()
        del server
        gc.collect()
        if how == "int8":
            # rounded in place, and two copies do not fit the chip: the
            # wrong ones go with their server before the true ones are
            # made again
            del params
            gc.collect()
            params = make_params(cfg, args.seed)
        checks, facts = {"warm_requests_served": False}, {}
        if served:
            checks, facts = check_against_reference(params, cfg, reqs)
        ok = all(checks.values())
        all_ok = all_ok and ok
        print(json.dumps({"check": dict(facts, **checks, ok=ok, degrade=how,
                                        seed=args.seed)}), flush=True)
    return 0 if all_ok else 1


if __name__ == "__main__":
    sys.exit(main())

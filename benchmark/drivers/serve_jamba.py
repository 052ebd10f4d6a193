#!/usr/bin/env python3
"""Driver for the hybrid trunk's third family (models/hybrid_lm.py as
``jamba`` builds it: a Mamba-1 selective scan with per-slot state in most
layers, softmax attention with one K/V head over paged K and V pools in one
layer a period, dense gated FFNs, a tied head) served through the library's
front door, ``DecodeEngine(model=...) -> GenerationBatcher -> make_server``.
The parameters, the server, the tolerance's form and the HTTP clients are
``drivers/serve_hybrid.py``'s and ``drivers/serve.py``'s; the reference
(``reference/jamba.py``), the check, what is counted and ``run`` are this
file's.

The check has no program of its own.  Set-up serves a few requests through
the server, one after the other, and the ENGINE's compiled step (the one
the window times, ``report_logits``) leaves each step's logits beside its
pick: every streamed token's logits row is held to the reference's full
forward pass, and when a request leaves, the state its slot holds in every
Mamba layer (``engine.slot_state``) to the reference's state after the same
positions.  The logits cannot see the state's precision (six bfloat16
products a layer drown it, PERF.md 35.3); the state read back can.

Two entries beside ``run``, as ``serve_hybrid`` has them:

    python3 benchmark/drivers/serve_jamba.py sweep --workload <cell> --rates 0.6,0.8
    python3 benchmark/drivers/serve_jamba.py check --workload <cell> --seed <n> \\
        [--degrade int8|norms|d|bf16state ...]

``check`` is set-up's check alone (exit 1 unless every program it ran read
correct); with ``--degrade`` the SERVER runs, for each name given, a
program that computes in a lower precision (int8 matrices; the scan's state
rounded to bfloat16 after every position) or leaves a part of the mixer out
(the norms of dt, B and C; the ``D`` skip), which has to come out as NOT
correct."""

import contextlib
import functools
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
from benchmark.drivers import serve_hybrid  # noqa: E402
from benchmark.drivers.serve_hybrid import (_degraded,  # noqa: E402
                                            make_params, model_config,
                                            tolerances)
from benchmark.reference import jamba as reference  # noqa: E402

DEGRADED = ("int8", "norms", "d", "bf16state")
# the engine's own options that ``serving`` may name beside the ones every
# hybrid cell gives (``serve_hybrid.Server``)
ENGINE_OPTIONS = ("prefill_chunk_budget", "report_logits")


def make_server(cfg, params):
    """``serve_hybrid.Server`` around an engine that is also given
    ``ENGINE_OPTIONS`` from the configuration: ``prefill_chunk_budget``, the
    prompt lanes ONE step may feed over all its rows (data, not shape: the
    rows seated first get whole chunks, the rest wait at a token a step),
    and ``report_logits``, which the check reads."""
    from paddle_tpu.serving import decode_engine
    more = {k: cfg["serving"][k] for k in ENGINE_OPTIONS
            if k in cfg["serving"]}
    with mock.patch.object(
            decode_engine, "DecodeEngine",
            functools.partial(decode_engine.DecodeEngine, **more)):
        return serve_hybrid.Server(cfg, params)


def reference_params(p, cfg):
    """The program's parameter tree as the plain reference wants it: the
    fused q | k | v projection split, ``A_log`` as published ([d_inner,
    n])."""
    mc = model_config(cfg)
    d_q = mc.attn_heads * mc.attn_head_dim
    d_kv = mc.attn_kv_heads * mc.attn_head_dim
    layers = []
    for lp, (kind, _ffn) in zip(p["layers"], mc.layers):
        a = dict(lp["attn"])
        if kind == "mamba":
            a["a_log"] = a["a_log"].T
        else:
            w = a.pop("wqkv")
            a["wq"], a["wk"], a["wv"] = \
                w[:, :d_q], w[:, d_q:d_q + d_kv], w[:, d_q + d_kv:]
        layers.append({"norm1": lp["norm1"], "norm2": lp["norm2"],
                       "mixer": a, "ffn": lp["ffn"]})
    return {"emb": p["emb"], "norm_f": p["norm_f"], "layers": layers}


# ------------------------------------------------------- reference checks

def check_requests(cfg, tr, seed):
    """What set-up serves and holds to the reference: the configuration's
    ``reference_check`` prompts (seeded lengths that end inside a chunk and
    a block, one over 1,024) with a few decode steps each, then the
    traffic's warm requests (one crosses a hundred chunks)."""
    rc = cfg["reference_check"]
    rng = np.random.RandomState(int(seed) % (2 ** 32))
    sizes = [(n, rc["decode_steps"] + 1) for n in rc["prompt_lengths"]] \
        + [tuple(w) for w in tr["warm_requests"]]
    return [{"prompt": rng.randint(1, cfg["vocab_size"], n).tolist(),
             "max_tokens": m} for n, m in sizes]


def serve_recorded(server, reqs, timeout):
    """``reqs`` through the server's whole front, one after the other, the
    engine recording its steps.  Each request gains ``rows``: [position,
    the step's own logits row] of every token it streamed (a step emits for
    the seated slot once its lanes reach the prompt's end), and
    ``states``: what its slot held in every Mamba layer when it left, as
    the reference lays it ([d_inner, n]), after ``absorbed`` positions (the
    last token is streamed and never fed).  False where a request failed or
    its steps do not account for its tokens."""
    engine = server.engine
    for r in reqs:
        engine.record_steps(True)
        stream_request(server.port, r, timeout)
        steps = engine.recorded_steps()
        engine.record_steps(False)
        if r["error"] is not None:
            return False
        n, slot, rows = len(r["prompt"]), None, []
        for _tokens, pos, lens, (_routes, logits) in steps:
            # one request at a time: the seated slot is the one not idling
            # at position 0 on a single lane
            s = int(np.argmax(pos + lens))
            end = int(pos[s] + lens[s])
            if end >= n:
                slot = s
                rows.append([end - 1, np.asarray(logits[s])])
        del steps
        if [int(row.argmax()) for _p, row in rows] != r["tokens"]:
            return False
        # the batcher idles once the request has left: nothing in flight
        r["rows"], r["absorbed"] = rows, n + len(rows) - 1
        r["states"] = [np.asarray(c["state"]).T
                       for c in engine.slot_state(slot) if "state" in c]
    return True


MEMORY_EDGES = (16, 64, 256)    # positions: the classes of state elements


def state_memory(mixer):
    """Each state element's nominal memory in positions, ``[d_inner, n]``:
    1 / (dt A) at the bias's own dt, ``softplus(dt_bias)`` (the data move dt
    about it), and ``A = exp(A_log)``.  Mamba's start spreads it from under
    one position to a thousand."""
    dt = np.logaddexp(0.0, np.asarray(mixer["dt_bias"], np.float32))
    return 1.0 / (dt[:, None] * np.exp(np.asarray(mixer["a_log"],
                                                  np.float32)))


def check_against_reference(params, cfg, reqs):
    """Every recorded logits row against the plain float32 reference's full
    forward pass over the request's prompt and tokens, and every Mamba
    layer's state against the reference's after the same positions: the
    distance's norm over the reference's, over the whole state a layer
    (``state_rel_err_by_layer``) and over the elements of each class of
    memory (``MEMORY_EDGES``), the largest over layers and requests.  The
    LIMIT is on the longest-lived class: what the bfloat16 products put
    into the scan's inputs reads alike in every class, a rounding of the
    state itself adds up over the positions an element remembers, so that
    is where the precision the configuration states for the state shows
    (PERF.md 35.3).  Requests no longer than the ``reference_check``
    prompts share one padded forward; a longer one pays its own.  Returns
    ({check: passed}, the facts for the ``checks`` line)."""
    import jax.numpy as jnp
    rc = cfg["reference_check"]
    t_pad = max(rc["prompt_lengths"]) + rc["decode_steps"] + 1
    fits = lambda r: len(r["prompt"]) + len(r["tokens"]) <= t_pad
    groups = [[r for r in reqs if fits(r)]] \
        + [[r] for r in reqs if not fits(r)]
    ref_params = reference_params(params, cfg)
    classes = [np.digitize(state_memory(lp["mixer"]), MEMORY_EDGES)
               for lp in ref_params["layers"] if "a_log" in lp["mixer"]]
    rel = lambda got, ref: float(np.linalg.norm(got - ref)
                                 / max(np.linalg.norm(ref), 1e-30))
    err, margin, wants = 0.0, 0.0, []
    by_layer = np.zeros(len(classes))
    by_memory = np.zeros(len(MEMORY_EDGES) + 1)
    for group in filter(None, groups):
        seqs = [r["prompt"] + r["tokens"] for r in group]
        ids = np.zeros((len(seqs), max(t_pad if fits(group[0]) else 0,
                                       max(map(len, seqs)))), np.int32)
        for i, seq in enumerate(seqs):
            ids[i, :len(seq)] = seq
        at = [[p for p, _row in r["rows"]] for r in group]
        at = np.asarray([a + a[-1:] * (max(map(len, at)) - len(a))
                         for a in at])
        want, states = reference.forward(
            ref_params, jnp.asarray(ids), cfg, positions=at,
            lengths=[r["absorbed"] for r in group])
        want = np.asarray(want)
        wants.append(want.reshape(-1, want.shape[-1]))
        for i, r in enumerate(group):
            for j, (_p, row) in enumerate(r["rows"]):
                err = max(err, float(np.abs(row - want[i, j]).max()))
                margin = max(margin, float(want[i, j].max()
                                           - want[i, j, r["tokens"][j]]))
            for l, (got, ref) in enumerate(zip(r["states"], states)):
                ref = np.asarray(ref[i])
                by_layer[l] = max(by_layer[l], rel(got, ref))
                for c in np.unique(classes[l]):
                    at_c = classes[l] == c
                    by_memory[c] = max(by_memory[c],
                                       rel(got[at_c], ref[at_c]))
    std = float(np.concatenate(wants).std())
    tol, _router_tol, cd = tolerances(cfg, std, 0.0)
    # the logits' form with fewer sigmas: a norm's ratio is a root mean
    # square, not the largest of 3 M values
    state_tol = tol / std * rc["state_sigmas"] / rc["sigmas"]
    finite = all(np.isfinite(row).all() for r in reqs for _p, row in r["rows"])
    short = lambda values: [float("%.3g" % v) for v in values]
    facts = dict(logits_max_abs_err=err, logits_tol=tol, ref_logit_std=std,
                 state_rel_err=float(by_memory[-1]),
                 state_rel_tol=state_tol,
                 state_rel_err_by_memory=short(by_memory),
                 state_rel_err_by_layer=short(by_layer),
                 served_token_margin=margin, compute_dtype=cd,
                 compared_rows=sum(len(r["rows"]) for r in reqs))
    checks = {"warm_requests_served": True,
              "logits_match_reference": bool(finite and err <= tol),
              "state_matches_reference":
                  bool(by_memory[-1] <= state_tol),
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
            "attended_positions_total", "state_resets_total")


def counters(engine):
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
    server = make_server(cfg, params)
    engine = server.engine
    phases.mark("engine")
    try:
        # warm-up: a few requests through the whole front, one after the
        # other, each step's logits and the state they leave held to the
        # reference
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
                   "latent_pool_bytes")}
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
    # what one step reads of the parameters: all of them, the table too,
    # once: the tied head streams it every step
    weight_bytes = costs.tree_bytes(params)
    harness.say("checks", rehearsal, **checks, **facts,
                errors=[r["error"] for r in failed][:5],
                generator_late_ms_p95=arith.percentile(late, 95),
                requests_measured=len(measured), requests_lead_in=len(plan)
                - len(measured), drain_s=time.perf_counter() - t_close,
                mamba_kernels=bool(engine.mamba_kernels),
                mamba_decline_reason=engine.mamba_decline_reason,
                attn_kernels=bool(engine.attn_kernels),
                attn_decline_reason=engine.attn_decline_reason,
                rate_rps=tr.get("rate_rps"), knee_rps=tr.get("knee_rps"),
                window_counters={k: after[k] - before[k] for k in COUNTERS},
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
        "mamba_kernels": bool(engine.mamba_kernels),
        "attn_kernels": bool(engine.attn_kernels),
        "weight_bytes": weight_bytes,
        "trace": tw.reduced, "trace_cost": tw.cost,
    }


# --------------------------------------------------------------- entries

def _scan_bf16_state(u, dt, b, c, a, state, lengths, fresh, src, back):
    """``ops/mamba.scan_xla`` with the state rounded to bfloat16 after every
    position: the wrong program of ``--degrade bf16state`` (a scratch copy
    on purpose: the program itself has no switch for its precision)."""
    import jax
    import jax.numpy as jnp
    (s, kk), d = back.shape, u.shape[1]
    state = jnp.where(fresh[:, None, None], 0.0, state)

    def lane(st, xs):
        t, u_t, dt_t, b_t, c_t = xs
        new = jnp.exp(dt_t[:, None, :] * a) * st \
            + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        # (not astype there and back: XLA may keep the excess precision,
        # and on the chip it does, PERF.md 35.3)
        new = jax.lax.reduce_precision(new, exponent_bits=8, mantissa_bits=7)
        y = jnp.sum(new * c_t[:, :, None], axis=1)
        return jnp.where((t < lengths)[:, None, None], new, st), y

    rows = lambda x: jnp.moveaxis(x[back], 1, 0)
    state, y = jax.lax.scan(
        lane, state, (jnp.arange(kk), rows(u), rows(dt), rows(b), rows(c)))
    return jnp.moveaxis(y, 0, 1).reshape(s * kk, d)[src], state


def degraded_server(cfg, params, how):
    """A server that runs a wrong program made of the true ``params``:
    ``int8`` rounds every matrix to 8 bits a value (``serve_hybrid.
    _degraded``, IN PLACE: the caller makes the true ones again); ``d``
    zeroes the skip; ``norms`` passes dt, B and C on un-normed;
    ``bf16state`` rounds the scan's state to bfloat16 after every position.
    The engine traces its step while it is built, so the wrong parts are in
    the step it serves with."""
    import jax.numpy as jnp
    from paddle_tpu.ops import mamba as ops
    patch = contextlib.nullcontext()
    if how == "int8":
        params = _degraded(params, "int8")
    elif how == "d":
        params = dict(params, layers=[
            dict(lp, attn=dict(lp["attn"], d=jnp.zeros_like(lp["attn"]["d"])))
            if "d" in lp["attn"] else lp for lp in params["layers"]])
    elif how == "norms":
        patch = mock.patch.object(ops, "rms_norm", lambda x, gain, eps: x)
    elif how == "bf16state":
        patch = mock.patch.object(ops, "scan", _scan_bf16_state)
    with patch:
        return make_server(cfg, params)


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
    for how in args.degrade or [None]:
        server = degraded_server(cfg, params, how)
        reqs = check_requests(cfg, tr, args.seed)
        try:
            served = serve_recorded(server, reqs, tr["request_timeout_s"])
        finally:
            server.close()
        del server
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

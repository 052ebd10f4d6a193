#!/usr/bin/env python3
"""Driver for the hybrid trunk's fifth family (models/hybrid_lm.py as
``keye`` builds it: in every layer a lightning indexer scores each earlier
position for each query lane from its own paged key leaf, an exact top-k
keeps ``topk`` positions a lane, and GQA softmax attention with per-head q/k
norms runs over those alone; a softmax router over a held share of the
experts, no shared expert) served through the library's front door,
``DecodeEngine(model=...) -> GenerationBatcher -> make_server``.  The
parameters, the server (``serve_jamba.make_server``, with the engine
options it reads from the configuration), the requests of the check, the
tolerance's form, the HTTP clients and the server-step recording are
``drivers/serve_hybrid.py``'s, ``drivers/serve_jamba.py``'s,
``drivers/serve_laguna.py``'s and ``drivers/serve.py``'s; the reference
(``reference/keye.py``), the check, what is counted and ``run`` are this
file's.

The check has no program of its own, as Jamba's and Laguna's: set-up serves
a few requests through the server, all at once, and the ENGINE's compiled
step (``report_logits``) leaves each step's logits, expert choice and the
positions each sparse layer's lanes took (as bits): every streamed token's
logits row is held to the reference's full forward pass, handed the expert
choice and the selection the server's step made; the expert choice is
judged apart, in router logits; and so is the selection: every fed lane of
every layer took ``min(topk, t + 1)`` positions, none past its own, and the
reference's best score at a position left out lies no further above its
worst at a position taken than the limit.

Two entries beside ``run``:

    python3 benchmark/drivers/serve_keye.py sweep --workload <cell> \\
        --rates 0.2,0.3
    python3 benchmark/drivers/serve_keye.py check --workload <cell> \\
        --seed <n> [--degrade int8|dense|noqknorm ...]

``check`` is set-up's check alone (exit 1 unless every program it ran read
correct); with ``--degrade`` the SERVER then runs, for each name given, a
program that computes in a lower precision (int8 matrices) or leaves a part
of the layer out (the selection: every position attended; the q/k norms),
which has to come out as NOT correct."""

import contextlib
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
                                            model_config)
from benchmark.drivers.serve_jamba import (check_requests,  # noqa: E402
                                           make_server)
from benchmark.reference import keye as reference  # noqa: E402

DEGRADED = ("int8", "dense", "noqknorm")
QK_GAIN_SIGMA = 0.5     # log-normal spread of the q and k norms' gains


def make_params(cfg, seed):
    """``serve_hybrid.make_params``, and each layer's q and k norm gains
    drawn log-normal (``QK_GAIN_SIGMA``) from the seed: at 1 (+ N(0,
    0.02)) a head's RMSNorm meets a head whose RMS is already about 1 and
    is nearly the identity, so no check could see it dropped; a trained
    model's gains are far from 1."""
    import jax
    import jax.numpy as jnp
    p = serve_hybrid.make_params(cfg, seed)
    key = jax.random.PRNGKey(int(seed) % (2 ** 31 - 1) + 1)
    for lp in p["layers"]:
        for name in ("q_norm", "k_norm"):
            key, k = jax.random.split(key)
            gain = lp["attn"][name]
            lp["attn"][name] = jnp.exp(QK_GAIN_SIGMA * jax.random.normal(
                k, gain.shape, gain.dtype))
    return p


def reference_params(p, cfg):
    """The program's parameter tree as the plain reference wants it: the
    fused q | k | v projection split, the held experts apart."""
    mc = model_config(cfg)
    d_q = mc.attn_heads * mc.attn_head_dim
    d_kv = mc.attn_kv_heads * mc.attn_head_dim
    layers = []
    for lp in p["layers"]:
        a = dict(lp["attn"])
        w = a.pop("wqkv")
        a["wq"], a["wk"], a["wv"] = \
            w[:, :d_q], w[:, d_q:d_q + d_kv], w[:, d_q + d_kv:]
        f = lp["ffn"]
        layers.append({"norm1": lp["norm1"], "norm2": lp["norm2"],
                       "attn": a, "ffn": {
                           "router": f["router"],
                           "experts": {k: f[k] for k in ("wg", "wu", "wd")}}})
    return {"emb": p["emb"], "head": p["head"], "norm_f": p["norm_f"],
            "layers": layers}


# ------------------------------------------------------- reference checks

LIMITS = ("logits", "router", "selection")


def limits(cfg):
    """({limit name: multiple}, the compute dtype's name): the
    configuration's ``reference_check.limits``, multiples of the
    reference's own scales (the std of its logits, of its router logits, of
    a lane's scores), each set from the readings the configuration records;
    where the program computes in float32, 1e-3 of each scale."""
    import jax.numpy as jnp
    from paddle_tpu.core import dtypes
    cd = dtypes.compute_dtype()
    lim = cfg["reference_check"]["limits"] if cd == jnp.bfloat16 \
        else dict.fromkeys(LIMITS, 1e-3)
    return {k: lim[k] for k in LIMITS}, jnp.dtype(cd).name


def _keep(tokens, pos, lens, report):
    """What the recording keeps of a step (``DecodeEngine.record_steps``'s
    ``keep``): the rows a request was seated in (a free slot idles at
    position 0 on one lane fed token 0, and ``check_requests`` draws ids
    from 1), and of each its chosen experts ``[layers, K, top_k]``, its
    lanes' selection bits ``[sparse layers, K, W]`` and its logits row, on
    the host."""
    import jax.numpy as jnp
    (routes, bits), logits = report
    rows = np.flatnonzero((pos + lens > 1) | (tokens[:, 0] != 0))
    at = jnp.asarray(rows, jnp.int32)
    take = lambda a, axis: np.asarray(jnp.take(a, at, axis=axis))
    return rows, take(routes, 1), np.stack([take(b, 0) for b in bits], 0), \
        take(logits, 0)


def serve_recorded(server, reqs, timeout):
    """``reqs`` through the server's whole front, all at once (so that
    steps carry several rows, as the window's do), the engine recording
    its steps.  Each request gains ``rows``: [position, the step's own
    logits row] of every token it streamed; ``routes``: {position: chosen
    experts [layers, top_k]}; ``bits``: [(first position, the fed lanes'
    selection bits [sparse layers, lanes, W])] of every step that fed it;
    and ``shared_rows``: how many of its ``rows`` came from a step that
    carried another request's row too.  A step's row is the request whose
    sequence it fed.  False where a request failed or its steps do not
    account for its tokens."""
    import threading
    engine = server.engine
    engine.record_steps(True, keep=_keep)
    threads = [threading.Thread(target=stream_request,
                                args=(server.port, r, timeout))
               for r in reqs]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    steps = engine.recorded_steps()
    engine.record_steps(False)
    if any(r["error"] is not None for r in reqs):
        return False
    seqs = [r["prompt"] + r["tokens"] for r in reqs]
    for r in reqs:
        r["rows"], r["routes"], r["bits"], r["shared_rows"] = [], {}, [], 0
    for tokens, pos, lens, (rows, routes, bits, logits) in steps:
        for i, s in enumerate(rows):
            p, n = int(pos[s]), int(lens[s])
            fed = list(tokens[s, :n])
            owner = [r for r, seq in zip(reqs, seqs) if seq[p:p + n] == fed]
            if len(owner) != 1:
                return False
            r = owner[0]
            for j in range(n):
                r["routes"][p + j] = routes[:, i, j]
            r["bits"].append((p, bits[:, i, :n]))
            if p + n >= len(r["prompt"]):
                r["rows"].append([p + n - 1, logits[i]])
                r["shared_rows"] += len(rows) > 1
    del steps
    for r, seq in zip(reqs, seqs):
        # every position the request fed (all but its last token), once
        if sorted(r["routes"]) != list(range(len(seq) - 1)) \
                or [int(row.argmax()) for _p, row in r["rows"]] \
                != r["tokens"]:
            return False
    return True


def selections(reqs, t, layers, topk):
    """The program's selections, as ``reference.forward`` takes them: one
    [B, t, ceil(t / 8)] uint8 array a sparse layer (the causal identity
    where no step fed a position: the sequence's last token and the
    padding), and the exact check: did every fed lane of every layer take
    ``min(topk, q + 1)`` positions, none past its own ``q``."""
    from paddle_tpu.ops import dsa
    ident = np.packbits(np.tri(t, dtype=bool), axis=-1, bitorder="little")
    out = [np.repeat(ident[None], len(reqs), 0) for _ in range(layers)]
    exact = True
    for i, r in enumerate(reqs):
        for p, bits in r["bits"]:
            n = bits.shape[1]
            q = p + np.arange(n)
            taken = dsa.unpack(bits, t)                 # [layers, n, t]
            count = taken.sum(-1)
            # every bit a lane set is one of its positions up to q
            total = np.unpackbits(bits.view(np.uint8), axis=-1).sum(-1)
            exact &= bool((count == np.minimum(q + 1, topk)).all()
                          and (total == count).all()
                          and not (taken & (np.arange(t) > q[:, None])).any())
            packed = np.packbits(taken, axis=-1, bitorder="little")
            for l in range(layers):
                out[l][i, p:p + n] = packed[l]
    return out, exact


def check_against_reference(params, cfg, reqs):
    """Every recorded logits row against the plain reference's full
    forward pass over the request's prompt and tokens, computed as the
    configuration states the program computes, and HANDED what the
    server's steps chose: the experts and each sparse layer's selection at
    every fed position; the expert choice judged apart in the reference's
    router logits (how far below the k-th largest a chosen expert's lies),
    as Laguna's is; the selection judged apart too: exact (every fed lane
    of every layer took ``min(topk, t + 1)`` positions, none past t) and
    by its shortfall (the reference's best score at a position NOT taken
    less its worst at a position taken, over the std of the lane's scores;
    at most 0 where the program took the reference's own top-k).  Requests
    no longer than the ``reference_check`` prompts share one padded
    forward; a longer one pays its own.  Returns ({check: passed}, the
    facts for the ``checks`` line)."""
    import jax.numpy as jnp
    from paddle_tpu.core import dtypes
    rc = cfg["reference_check"]
    k = cfg["num_experts_per_tok"]
    topk = cfg["sa_config"]["topk"]
    layers = cfg["num_hidden_layers"]
    t_pad = max(rc["prompt_lengths"]) + rc["decode_steps"] + 1
    fits = lambda r: len(r["prompt"]) + len(r["tokens"]) <= t_pad
    groups = [[r for r in reqs if fits(r)]] \
        + [[r] for r in reqs if not fits(r)]
    ref_params = reference_params(params, cfg)
    # the reference computes as the configuration states the program does
    stated = dtypes.compute_dtype() == jnp.bfloat16
    err, margin, wants, logit_std = 0.0, 0.0, [], []
    select_exact, judged = True, 0
    by_layer = {"router": np.zeros(layers), "selection": np.zeros(layers)}
    for group in filter(None, groups):
        seqs = [r["prompt"] + r["tokens"] for r in group]
        t = max(t_pad if fits(group[0]) else 0, max(map(len, seqs)))
        ids = np.zeros((len(seqs), t), np.int32)
        chosen = np.tile(np.arange(k, dtype=np.int32),
                         (len(seqs), t, layers, 1))
        for i, (seq, r) in enumerate(zip(seqs, group)):
            ids[i, :len(seq)] = seq
            for p, c in r["routes"].items():
                chosen[i, p] = c
        handed, exact = selections(group, t, layers, topk)
        select_exact &= exact
        at = [[p for p, _row in r["rows"]] for r in group]
        at = np.asarray([a + a[-1:] * (max(map(len, at)) - len(a))
                         for a in at])
        want, router, facts = reference.forward(
            ref_params, jnp.asarray(ids), cfg, positions=at,
            routes=[jnp.asarray(chosen[:, :, l]) for l in range(layers)],
            selections=[jnp.asarray(h) for h in handed],
            bf16_operands=stated)
        del handed
        want = np.asarray(want)
        wants.append(want.reshape(-1, want.shape[-1]))
        for l, (z, f) in enumerate(zip(router, facts)):
            z = np.asarray(z)
            logit_std.append(float(z.std()))
            worst, best_out, std = (np.asarray(f[name])
                                    for name in ("worst", "best_out", "std"))
            for i, r in enumerate(group):
                fed = np.asarray(sorted(r["routes"]))
                rows = z[i, fed]
                kk = np.partition(rows, -k, axis=-1)[:, -k]
                picked = np.take_along_axis(rows, chosen[i, fed, l], -1)
                by_layer["router"][l] = max(by_layer["router"][l], float(
                    (kk - picked.min(-1)).max()))
                # lanes that take every position leave none out to judge
                cut = fed[fed + 1 > topk]
                judged += cut.size
                if cut.size:
                    by_layer["selection"][l] = max(
                        by_layer["selection"][l], float(
                            ((best_out[i, cut] - worst[i, cut])
                             / std[i, cut]).max()))
        for i, r in enumerate(group):
            for j, (_p, row) in enumerate(r["rows"]):
                err = max(err, float(np.abs(row - want[i, j]).max()))
                margin = max(margin, float(want[i, j].max()
                                           - want[i, j, r["tokens"][j]]))
    std = float(np.concatenate(wants).std())
    shortfall, select_short = (float(by_layer[k].max())
                               for k in ("router", "selection"))
    lim, cd = limits(cfg)
    router_std = float(np.mean(logit_std))
    tol, router_tol = lim["logits"] * std, lim["router"] * router_std
    select_tol = lim["selection"]
    finite = all(np.isfinite(row).all() for r in reqs for _p, row in r["rows"])
    facts = dict(logits_max_abs_err=err, logits_tol=tol, ref_logit_std=std,
                 logits_err_over_std=err / std,
                 router_shortfall_max=shortfall, router_tol=router_tol,
                 router_shortfall_over_std=shortfall / router_std,
                 selection_shortfall_max=select_short,
                 selection_tol=select_tol, selection_lanes_judged=judged,
                 **{k + "_shortfall_by_layer": [float("%.3g" % v) for v in a]
                    for k, a in by_layer.items()},
                 served_token_margin=margin, compute_dtype=cd,
                 compared_rows=sum(len(r["rows"]) for r in reqs),
                 compared_rows_in_shared_steps=sum(
                     r["shared_rows"] for r in reqs),
                 routed_positions=sum(len(r["routes"]) for r in reqs))
    checks = {"warm_requests_served": True,
              "logits_match_reference": bool(finite and err <= tol),
              "router_matches_reference": bool(shortfall <= router_tol),
              "selection_takes_topk": bool(select_exact),
              "selection_matches_reference": bool(select_short <= select_tol),
              "served_tokens_match_reference": bool(margin <= 2 * tol)}
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
            "attended_positions_total", "sparse_scored_positions_total",
            "sparse_selected_positions_total", "sparse_read_positions_total",
            "read_positions_total")


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
        # other, each step's logits, expert choice and selection held to
        # the reference
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
                  ("recurrent_state_bytes", "latent_pool_bytes")}
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
                sparse_kernels=bool(engine.sparse_kernels),
                sparse_decline_reason=engine.sparse_decline_reason,
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
        "sparse_kernels": bool(engine.sparse_kernels),
        "weight_bytes": weight_bytes,
        "trace": tw.reduced, "trace_cost": tw.cost,
    }


# --------------------------------------------------------------- entries

def degraded_server(cfg, params, how):
    """A server that runs a wrong program made of the true ``params``:
    ``int8`` rounds every matrix to 8 bits a value (``serve_hybrid.
    _degraded``, IN PLACE: the caller makes the true ones again); ``dense``
    selects every position at or before each lane (attention over the whole
    context in place of the selection); ``noqknorm`` leaves q and k
    un-normed.  The engine traces its step while it is built, so the wrong
    parts are in the step it serves with."""
    from paddle_tpu.models import hybrid_lm
    from paddle_tpu.ops import dsa
    patch = contextlib.nullcontext()
    if how == "int8":
        params = _degraded(params, "int8")
    elif how == "dense":
        select = dsa.select
        patch = mock.patch.object(
            dsa, "select", lambda scores, qpos, topk, use_kernel: select(
                scores, qpos, 2 ** 30, use_kernel))
    elif how == "noqknorm":
        patch = mock.patch.object(hybrid_lm, "head_norm",
                                  lambda x, gain, heads, head_dim, eps: x)
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
    for how in [None] + (args.degrade or []):
        reqs = check_requests(cfg, tr, args.seed)
        server = degraded_server(cfg, params, how)
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

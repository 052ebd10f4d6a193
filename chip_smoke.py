#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

One process, no arguments: drives the two main paths through the entry
points a user calls, at the full width of models the repository supports,
with seeded random weights, and exits 0 only if every leg passed ON A TPU.

  leg 0  device gate: platform "tpu", device_kind in the peaks table
  leg 1  every Pallas kernel through Mosaic at the serving widths, against
         float32 "highest" oracles (paddle_tpu/testing/kernel_smoke.py)
  leg 2  DecodeEngine -> GenerationBatcher -> make_server, HTTP clients on
         /v1/generate: d_model 2048, 16 heads of 128, dff 8192, vocab 50304,
         max_len 2048, 24 layers (1.3 B parameters, float32), paged layout
         then slab layout; first-token logits against the float32 reference
  leg 3  the BASELINE.md LSTM (2 x lstmemory h=512 over a 128-wide embedding
         of a 30k vocabulary, batch 64, length 100) built with the layers
         DSL and trained by SGD(...).train(reader=...)
  leg 4  with four or more TPU devices: leg 3 data-parallel over 4 chips at
         batch 256 and leg-2 requests through decode_mesh(4); with fewer,
         "skipped" (which is not a pass of the leg)

One JSON line per leg on stdout, then a summary line (which legs passed,
``"claim": null``), then — the LAST line, and only outside a rehearsal — the
result ``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with exactly those keys, the device as JAX reports it.  Wall times are set-up
information, not performance metrics.  Without a TPU the gate names what it
found on stderr, prints no result, and exits 2.

``--rehearsal`` runs the same code at tiny sizes on whatever backend is
there (CPU, interpret-mode kernels), prints ``"rehearsal": true`` in every
line and exits 10 when everything passed — never 0, so a rehearsal cannot
be read as a pass.  ``--legs`` selects legs for partial runs (the builder's
four-chip call); the driver runs the file bare.
"""

import argparse
import contextlib
import gc
import json
import os
import sys
import threading
import time
import urllib.request

RC_NO_TPU = 2
RC_REHEARSAL_OK = 10

# leg 2: the widths of the first catalogue model the roadmap names (R1:
# d 2048, 16 heads of 128, vocab 50304) on the block the repository has
# today (LayerNorm, ReLU FFN dff 8192, learned positions, tied head).  No
# width is cut and, since 24 layers + one 8-slot cache are 10.9 GiB of the
# chip's 16, neither is depth.
FULL = dict(vocab=50304, d_model=2048, heads=16, dff=8192, layers=24,
            max_len=2048, slots=8,
            # (prompt length, max_tokens, stream); the first is the leader
            # that registers the shared 200-token system prefix
            leader=(300, 64, False),
            wave=[(300, 64, True),      # exact duplicate of the leader: CoW
                  (420, 96, False),     # shares the system prefix
                  (520, 96, True),      # shares the system prefix
                  (650, 128, False), (800, 64, True), (1000, 96, False),
                  (1250, 128, True), (1500, 128, False)],
            sys_prefix=200, ref_prompts=(200, 264),
            mesh_wave=[(300, 32, False), (420, 32, True), (650, 32, False),
                       (800, 32, True)],
            lstm=dict(vocab=30000, emb=128, hidden=512, batch=64, length=100,
                      batches=24))
TINY = dict(vocab=128, d_model=256, heads=4, dff=128, layers=2,
            max_len=128, slots=4,
            leader=(40, 6, False),
            wave=[(40, 6, True), (52, 8, False), (60, 8, True),
                  (70, 6, False)],
            sys_prefix=24, ref_prompts=(24, 40),
            mesh_wave=[(40, 4, False), (52, 4, True)],
            lstm=dict(vocab=60, emb=16, hidden=32, batch=16, length=12,
                      batches=24))


def emit(leg, ok, rehearsal, **fields):
    """One JSON line for a leg; ``ok=None`` is a leg that did not run."""
    line = {"leg": leg, "ok": None if ok is None else bool(ok)}
    if rehearsal:
        line["rehearsal"] = True
    line.update(fields)
    print(json.dumps(line), flush=True)
    return bool(ok)


# ------------------------------------------------------------------ leg 0

def leg0_device_gate(rehearsal):
    """The device as JAX reports it, or exit: a CPU — whether asked for by
    an inherited JAX_PLATFORMS or reached by JAX's quiet fall-back when
    libtpu cannot initialise — is not a chip."""
    import jax
    import jaxlib
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    versions = {"jax": jax.__version__, "jaxlib": jaxlib.__version__}
    try:
        from importlib.metadata import version
        versions["libtpu"] = version("libtpu")
    except Exception:     # noqa: BLE001 — absent metadata is itself the answer
        versions["libtpu"] = None
    if not rehearsal:
        if dev.platform != "tpu":
            print(f"chip_smoke: no TPU — jax.devices()[0] is platform "
                  f"{dev.platform!r}, device_kind {dev.device_kind!r} "
                  f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); "
                  "this script passes only on the chip",
                  file=sys.stderr, flush=True)
            sys.exit(RC_NO_TPU)
        from paddle_tpu.perf import roofline
        try:
            roofline.for_device_kind(dev.device_kind)
        except KeyError as e:
            print(f"chip_smoke: {e.args[0]}", file=sys.stderr, flush=True)
            sys.exit(RC_NO_TPU)
    emit(0, True, rehearsal, device=info, versions=versions)
    return info


# ------------------------------------------------------------------ leg 1

def leg1_kernels(rehearsal):
    """Every kernel_smoke case: compiled by Mosaic (``interpret=False``
    observed on every pallas_call, not inferred) at the serving widths and
    inside its tolerance, or declined by its own guard with the reason."""
    from paddle_tpu.testing import kernel_smoke
    widths = kernel_smoke.SMALL if rehearsal else kernel_smoke.SERVING
    ok, results = kernel_smoke.run_all(widths, expect_compiled=not rehearsal)
    # the opt1.3b_chat cell's attention call alone, outside a server: a
    # time only on the chip (the rehearsal runs the code and drops the clock)
    ms = kernel_smoke.time_paged_chunk_cell(
        widths, **(dict(calls=2, reps=1) if rehearsal else {}))
    if rehearsal:
        ms = dict.fromkeys(ms, "not measured")
    return emit(1, ok, rehearsal, widths=widths.__dict__, kernels=results,
                paged_chunk_cell_ms_a_call=ms)


# ------------------------------------------------------------------ leg 2

class CacheEvents:
    """Counts JAX's persistent-compile-cache events."""

    def __init__(self):
        import jax
        self.hits = self.misses = 0
        jax.monitoring.register_event_listener(self._on)

    def _on(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


def _lm_params(cfg, seed=0):
    import jax
    from paddle_tpu.models import transformer
    # trg_vocab: the seq2seq halves of init() the LM trunk never reads
    return transformer.init(
        jax.random.PRNGKey(seed), src_vocab=cfg["vocab"], trg_vocab=8,
        d_model=cfg["d_model"], num_heads=cfg["heads"], dff=cfg["dff"],
        enc_layers=cfg["layers"], dec_layers=0, max_len=cfg["max_len"])


def _prompts(cfg, specs, seed):
    """Seeded prompts: the leader and the first three of the wave open with
    one shared system prefix (longer than a block); wave[0] repeats the
    leader exactly."""
    import numpy as np
    rng = np.random.RandomState(seed)
    system = rng.randint(1, cfg["vocab"], cfg["sys_prefix"]).tolist()
    out = []
    for i, (n, _mt, _s) in enumerate(specs):
        if i < 4:
            out.append(system + rng.randint(1, cfg["vocab"],
                                            n - len(system)).tolist())
        else:
            out.append(rng.randint(1, cfg["vocab"], n).tolist())
    out[1] = list(out[0])
    return out


def _post(base, body, timeout):
    req = urllib.request.Request(
        f"{base}/v1/generate", data=json.dumps(body).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, r.read()


def _client(base, prompt, max_tokens, stream, out, i, timeout):
    """One HTTP client; records the token count it got back (or why not)."""
    try:
        status, raw = _post(base, {"prompt": prompt,
                                   "max_tokens": max_tokens,
                                   "stream": stream}, timeout)
        if stream:
            lines = [json.loads(ln) for ln in raw.decode().splitlines() if ln]
            toks = [ln["token"] for ln in lines if "token" in ln]
            done = [ln for ln in lines if ln.get("done")]
            if not done or done[0]["tokens"] != toks:
                out[i] = f"stream/done mismatch ({len(toks)} streamed)"
                return
        else:
            toks = json.loads(raw)["tokens"]
        out[i] = len(toks) if status == 200 else f"http {status}"
    except Exception as e:    # noqa: BLE001 — a client failure is a result
        out[i] = f"{type(e).__name__}: {e}"[:200]


def _metric(text, name):
    """Sum of a counter's samples in Prometheus text (0 when absent)."""
    total = 0.0
    for ln in text.splitlines():
        if ln.startswith("#"):
            continue
        head, _, val = ln.rpartition(" ")
        if head.split("{")[0].endswith("_" + name):
            total += float(val)
    return total


def _serve(engine, prompts, specs, timeout):
    """The library's front door: GenerationBatcher -> make_server on an
    ephemeral port, the leader alone, then every other request at once.
    Returns (per-request results, /metrics text)."""
    from paddle_tpu.serving.decode_engine import GenerationBatcher
    from paddle_tpu.serving.server import make_server
    gen = GenerationBatcher(engine, default_max_tokens=64)
    httpd = make_server(None, port=0, gen_batcher=gen)
    threading.Thread(target=httpd.serve_forever, daemon=True).start()
    base = f"http://127.0.0.1:{httpd.port}"
    got = [None] * len(specs)
    try:
        _client(base, prompts[0], specs[0][1], specs[0][2], got, 0, timeout)
        threads = [threading.Thread(
            target=_client,
            args=(base, prompts[i], specs[i][1], specs[i][2], got, i,
                  timeout)) for i in range(1, len(specs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout)
        with urllib.request.urlopen(f"{base}/metrics", timeout=30) as r:
            metrics = r.read().decode()
    finally:
        httpd.shutdown()
        httpd.server_close()
        gen.close(drain=False, timeout=30)
    return got, metrics


def _judge_serving(engine, got, specs, metrics, paged):
    want = [mt for _n, mt, _s in specs]
    facts = {
        "tokens_returned": got, "tokens_asked": want,
        "decode_kernels": bool(engine.decode_kernels),
        "decline_reason": engine.decode_decline_reason,
        "step_trace_count": engine.step_trace_count,
        "prefill_chunks_total": _metric(metrics, "prefill_chunks_total"),
        "errors_total": _metric(metrics, "errors_total"),
        "prefix_cache_hits_total": _metric(metrics,
                                           "prefix_cache_hits_total"),
        "cow_forks_total": _metric(metrics, "cow_forks_total"),
    }
    ok = (got == want and engine.decode_kernels
          and engine.step_trace_count == 1
          and facts["prefill_chunks_total"] > 0
          and facts["errors_total"] == 0)
    if paged:
        ok = ok and facts["prefix_cache_hits_total"] > 0 \
            and facts["cow_forks_total"] > 0
    return ok, facts


def _first_token_logits(params, cfg, rehearsal):
    """First-token logits of seeded prompts two ways: chunked prefill
    through a paged cache (the engine's own step function,
    ``lm_decode_chunk_paged``, K lanes at a time, kernel path as ``auto``
    resolves it) and a plain ``encode(causal=True)`` forward traced as the
    float32 reference.  Judged on logits, not tokens: with random weights
    the argmax flips on rounding.

    Tolerance, set from the dtype before the run: the served path rounds
    both operands of every matmul to bf16 (ops/linear.py, auto policy of
    core/dtypes.py; unit roundoff u = 2^-9) and accumulates in f32, so
    each of the M = 4 * layers + 1 matmul stages (qkv, wo, w1, w2, head)
    perturbs its output by about sqrt(2) * u relative; the perturbations
    add in quadrature along the residual stream, and the largest of the
    ~1e5 compared logits sits about 4.5 sigma out:
    tol = 4.5 * u * sqrt(2 * M) * std(reference logits).  On CPU the auto
    policy computes in f32 and only summation order differs: 1e-3 * std."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.core import dtypes
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import transformer
    from paddle_tpu.testing import kernel_smoke

    heads, bs, kk = cfg["heads"], _cli_default("serving_kv_block_size"), \
        _cli_default("serving_prefill_chunk")
    lens = np.asarray(cfg["ref_prompts"], np.int32)
    n, t = len(lens), int(lens.max())
    rng = np.random.RandomState(5)
    toks = rng.randint(1, cfg["vocab"], (n, t)).astype(np.int32)
    nb_row = -(-t // bs)
    tables = np.arange(1, n * nb_row + 1, dtype=np.int32).reshape(n, nb_row)
    cache = transformer.init_lm_cache_paged(
        params, n * nb_row + 1, bs, max_len=cfg["max_len"], num_heads=heads)

    def step(p, cache, tokens, pos, lengths):
        return transformer.lm_decode_chunk_paged(
            p, tokens, pos, lengths, cache, jnp.asarray(tables), heads)

    with kernel_smoke.record_pallas_calls() as seen:
        jstep = jax.jit(step, donate_argnums=(1,))
        first = np.zeros((n, cfg["vocab"]), np.float32)
        for c in range(-(-t // kk)):
            pos = np.full((n,), c * kk, np.int32)
            live = np.clip(lens - c * kk, 0, kk)
            chunk = np.zeros((n, kk), np.int32)
            chunk[:, :min(kk, t - c * kk)] = toks[:, c * kk:(c + 1) * kk]
            # a finished row idles on its last token (its logits were
            # already taken; lengths must stay >= 1)
            done = live == 0
            pos[done] = lens[done] - 1
            chunk[done, 0] = toks[done, lens[done] - 1]
            logits, cache = jstep(params, cache, chunk, pos,
                                  np.maximum(live, 1).astype(np.int32))
            ends = (~done) & (lens <= (c + 1) * kk)
            if ends.any():
                first[ends] = np.asarray(logits)[ends]
    del cache

    def reference(p, tokens, lengths):
        h = transformer.encode(p, SequenceBatch(tokens, lengths), heads,
                               causal=True)
        last = jnp.take_along_axis(h, (lengths - 1)[:, None, None], axis=1)
        return transformer._lm_project(p, last)[:, 0]

    with kernel_smoke.f32_reference():
        want = np.asarray(jax.jit(reference)(params, toks, lens))
    err = float(np.max(np.abs(first - want)))
    std = float(want.std())
    bf16 = dtypes.compute_dtype() == jnp.bfloat16
    stages = 4 * cfg["layers"] + 1
    tol = (4.5 * 2.0 ** -9 * (2 * stages) ** 0.5 if bf16 else 1e-3) * std
    facts = {"max_abs_err": err, "tol": tol, "ref_logit_std": std,
             "compute_dtype": jnp.dtype(dtypes.compute_dtype()).name,
             "prompt_lengths": lens.tolist(),
             "pallas_calls": len(seen), "interpreted": sum(seen)}
    ok = np.isfinite(first).all() and err <= tol
    if not rehearsal:
        # auto on a TPU must have taken the kernel, compiled (the layers
        # share ONE trace of the tiled kernel, so one call is recorded)
        ok = ok and bool(seen) and not any(seen)
    return bool(ok), facts


def _cli_default(flag):
    """A serving knob at the CLI's own default (utils/flags.py)."""
    from paddle_tpu.utils.flags import Flags
    return getattr(Flags(), flag)


def _engine(params, cfg, layout, mesh=None):
    """The engine as the serving CLI builds it: chunked prefill and pool
    block at the CLI defaults, prefix cache on, float32 KV,
    ``pallas_decode`` left at ``auto``."""
    from paddle_tpu.serving.decode_engine import DecodeEngine
    return DecodeEngine(
        params, num_heads=cfg["heads"], num_slots=cfg["slots"],
        max_len=cfg["max_len"], kv_layout=layout,
        kv_block_size=_cli_default("serving_kv_block_size"),
        prefix_cache=True,
        prefill_chunk=_cli_default("serving_prefill_chunk"),
        kv_dtype="float32", mesh=mesh, name=f"smoke_{layout}")


def _release(engine):
    """Free an engine's KV buffers now: the next engine's pool must not
    have to share the chip with this one's."""
    import jax
    for leaf in jax.tree_util.tree_leaves(engine._cache):
        leaf.delete()
    gc.collect()


def _tree_bytes(tree):
    import jax
    return sum(x.size * x.dtype.itemsize
               for x in jax.tree_util.tree_leaves(tree))


def _kernel_mode(rehearsal):
    """``pallas_decode`` stays at ``auto`` on the chip.  A rehearsal has no
    Mosaic: it forces the kernels through interpret mode so the same
    asserts (decode_kernels, one trace) run on the CPU."""
    from paddle_tpu.ops.pallas import decode_attention as dk
    return dk.forced_mode("always") if rehearsal else contextlib.nullcontext()


def leg2_server(rehearsal, cfg, cache_events):
    import jax
    timeout = 120 if rehearsal else 600
    facts, ok = {}, True
    with _kernel_mode(rehearsal):
        t0 = time.perf_counter()
        params = _lm_params(cfg)
        jax.block_until_ready(params)
        facts["params"] = sum(
            x.size for x in jax.tree_util.tree_leaves(params))
        facts["weight_bytes"] = _tree_bytes(params)
        facts["init_s"] = round(time.perf_counter() - t0, 1)

        lok, facts["logits"] = _first_token_logits(params, cfg, rehearsal)
        ok = ok and lok

        specs = [cfg["leader"]] + cfg["wave"]
        prompts = _prompts(cfg, specs, seed=11)

        # paged: built twice — the first construction compiles (or finds a
        # cache the machine came with), the second must hit the cache
        h0, m0 = cache_events.hits, cache_events.misses
        t0 = time.perf_counter()
        eng = _engine(params, cfg, "paged")
        facts["paged_warmup_first_s"] = round(time.perf_counter() - t0, 1)
        h1, m1 = cache_events.hits, cache_events.misses
        facts["pool_bytes"] = _tree_bytes(eng._cache)
        _release(eng)
        t0 = time.perf_counter()
        eng = _engine(params, cfg, "paged")
        facts["paged_warmup_second_s"] = round(time.perf_counter() - t0, 1)
        facts["compile_cache"] = {
            "dir": jax.config.jax_compilation_cache_dir,
            "first": {"hits": h1 - h0, "misses": m1 - m0},
            "second": {"hits": cache_events.hits - h1,
                       "misses": cache_events.misses - m1}}
        if not rehearsal:   # a tiny CPU compile is under JAX's 1 s floor
            #                     for persisting an executable
            ok = ok and facts["compile_cache"]["second"]["hits"] >= 1

        got, metrics = _serve(eng, prompts, specs, timeout)
        sok, facts["paged"] = _judge_serving(eng, got, specs, metrics, True)
        ok = ok and sok
        _release(eng)

        # the slab layout — the CLI's default — with the same requests,
        # after the paged engine's buffers are released
        t0 = time.perf_counter()
        eng = _engine(params, cfg, "slab")
        facts["slab_warmup_s"] = round(time.perf_counter() - t0, 1)
        got, metrics = _serve(eng, prompts, specs, timeout)
        sok, facts["slab"] = _judge_serving(eng, got, specs, metrics, False)
        ok = ok and sok
        _release(eng)

    stats = jax.devices()[0].memory_stats() or {}
    facts["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    if not rehearsal:
        # weights + ONE pool: a donation that did not take would hold two
        # pools (and at these sizes would not fit at all)
        budget = facts["weight_bytes"] + facts["pool_bytes"] + (1 << 30)
        facts["peak_budget_bytes"] = budget
        ok = ok and facts["peak_bytes_in_use"] is not None \
            and facts["peak_bytes_in_use"] <= budget
    return emit(2, ok, rehearsal, config={k: cfg[k] for k in (
        "vocab", "d_model", "heads", "dff", "layers", "max_len", "slots")},
        kv_block_size=_cli_default("serving_kv_block_size"),
        prefill_chunk=_cli_default("serving_prefill_chunk"),
        **facts), params


# ------------------------------------------------------------------ leg 3

def _lstm_reader(c, batch, n_batches):
    """The "does token 7 appear" rule: a positive sequence carries token 7
    at ten random positions, a negative one never."""
    import numpy as np

    def reader():
        rng = np.random.RandomState(0)
        for _ in range(n_batches):
            rows = []
            for _ in range(batch):
                seq = rng.randint(8, c["vocab"], c["length"])
                lab = int(rng.randint(0, 2))
                if lab:
                    seq[rng.choice(c["length"], min(10, c["length"]),
                                   replace=False)] = 7
                rows.append((seq.astype(np.int32), lab))
            yield rows
    return reader


def _lstm_trainer(c, mesh=None):
    """BASELINE.md's headline network through the layers DSL."""
    import paddle_tpu.layers as L
    from paddle_tpu import optim
    from paddle_tpu.layers import networks
    from paddle_tpu.layers.graph import reset_names
    from paddle_tpu.trainer import SGD
    reset_names()
    words = L.data_layer("w", size=c["vocab"], is_seq=True)
    label = L.data_layer("lab", size=1)
    emb = L.embedding_layer(words, size=c["emb"])
    h1 = networks.simple_lstm(emb, size=c["hidden"])
    h2 = networks.simple_lstm(h1, size=c["hidden"])
    pooled = L.pooling_layer(h2, pooling_type="max")
    probs = L.fc_layer(pooled, size=2, act="softmax")
    cost = L.classification_cost(probs, label)
    return SGD(cost=cost, mesh=mesh, update_equation=optim.Momentum(
        learning_rate=0.5, momentum=0.9))


def _train(trainer, c, batch, n_batches):
    from paddle_tpu.data import integer_value, integer_value_sequence
    from paddle_tpu.trainer import events
    losses = []
    trainer.train(
        _lstm_reader(c, batch, n_batches), num_passes=1,
        feeding={"w": integer_value_sequence(c["vocab"]),
                 "lab": integer_value(2)},
        event_handler=lambda e: losses.append(float(e.cost))
        if isinstance(e, events.EndIteration) else None,
        log_period=0, buffered_batches=0)
    return losses


def _judge_training(losses, trainer, dispatched, rehearsal):
    import numpy as np
    first, last = float(np.mean(losses[:5])), float(np.mean(losses[-5:]))
    facts = {"losses": [round(x, 4) for x in losses],
             "mean_first5": round(first, 4), "mean_last5": round(last, 4),
             "fused_dispatches": dispatched,
             "step_traces": trainer.trace_count}
    ok = (bool(np.isfinite(losses).all()) and last < 0.7 * first
          and trainer.trace_count == 1)
    if not rehearsal:
        ok = ok and dispatched > 0      # the fused kernel is what trained
    return ok, facts


def leg3_trainer(rehearsal, cfg):
    import jax.numpy as jnp
    from paddle_tpu import native
    from paddle_tpu.core import dtypes
    from paddle_tpu.ops import rnn
    c = cfg["lstm"]
    before = rnn.FUSED_DISPATCH_COUNT
    t0 = time.perf_counter()
    trainer = _lstm_trainer(c)
    losses = _train(trainer, c, c["batch"], c["batches"])
    ok, facts = _judge_training(losses, trainer,
                                rnn.FUSED_DISPATCH_COUNT - before, rehearsal)
    facts["secs"] = round(time.perf_counter() - t0, 1)
    facts["compute_dtype"] = jnp.dtype(dtypes.compute_dtype()).name
    facts["feeder"] = native.feeder_status()
    return emit(3, ok, rehearsal, network=c, **facts), losses[0]


# ------------------------------------------------------------------ leg 4

def leg4_four_chips(rehearsal, cfg, params):
    """Four chips: the data-parallel trainer and the decode_mesh(4) server,
    with the work really spread."""
    import jax
    import numpy as np
    n = 4
    if len(jax.devices()) < n:
        emit(4, None, rehearsal, skipped=f"{len(jax.devices())} device")
        return None
    from paddle_tpu.ops import rnn
    from paddle_tpu.parallel.mesh import MeshConfig, make_mesh
    from paddle_tpu.parallel.sharding import batch_shardings, decode_mesh
    devices = jax.devices()[:n]
    facts, ok = {}, True

    # (a) leg 3 over a data=4 mesh at 4x the batch
    c = cfg["lstm"]
    batch = 4 * c["batch"]
    mesh = make_mesh(MeshConfig(data=n), devices=devices)
    before = rnn.FUSED_DISPATCH_COUNT
    trainer = _lstm_trainer(c, mesh=mesh)
    losses = _train(trainer, c, batch, c["batches"])
    tok, facts["trainer"] = _judge_training(
        losses, trainer, rnn.FUSED_DISPATCH_COUNT - before, rehearsal)
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree_util.tree_leaves(trainer.parameters)}
    facts["trainer"]["param_sharding_devices"] = sorted(spans)
    feed_sh = batch_shardings(np.zeros((batch, c["length"]), np.int32), mesh)
    facts["trainer"]["feed_sharding"] = {
        "spec": str(feed_sh.spec), "devices": len(feed_sh.device_set)}
    spans.add(len(feed_sh.device_set))
    # the one-chip twin takes the SAME first batch from the same seed; its
    # first loss is the untrained network's, before any update.  Tolerance
    # 1e-3: both sides compute in bf16 per sample and differ only in how
    # the 256-row mean is split and summed (4 x 64 then a psum, vs 256).
    twin = _lstm_trainer(c)
    twin_first = _train(twin, c, batch, 1)[0]
    facts["trainer"]["first_loss"] = losses[0]
    facts["trainer"]["one_chip_first_loss"] = twin_first
    ok = ok and tok and spans == {n} \
        and abs(losses[0] - twin_first) <= 1e-3
    del trainer, twin

    # (b) leg-2 requests through the tensor-parallel engine.  The engine
    # places its own stripes; hand it a HOST copy so chip 0 does not hold
    # the whole trunk beside its share
    if params is None:
        params = _lm_params(cfg)
    host = jax.device_get(params)
    for leaf in jax.tree_util.tree_leaves(params):
        leaf.delete()
    params = host
    specs = cfg["mesh_wave"]
    prompts = _prompts(cfg, specs, seed=11)
    with _kernel_mode(rehearsal):
        eng = _engine(params, cfg, "paged",
                      mesh=decode_mesh(n, devices=devices))
    got, metrics = _serve(eng, prompts, specs, 120 if rehearsal else 600)
    sok, facts["server"] = _judge_serving(eng, got, specs, metrics, False)
    facts["server"]["mesh_shards"] = eng.mesh_shards
    cache_spans = {len(leaf.sharding.device_set)
                   for leaf in jax.tree_util.tree_leaves(eng._cache)}
    facts["server"]["cache_sharding_devices"] = sorted(cache_spans)
    in_use = [(d.memory_stats() or {}).get("bytes_in_use") for d in devices]
    facts["bytes_in_use"] = in_use
    ok = ok and sok and eng.mesh_shards == n and cache_spans == {n}
    if not rehearsal:       # the CPU backend reports no memory stats
        ok = ok and all(b is not None and b > 0 for b in in_use)
    _release(eng)
    return emit(4, ok, rehearsal, **facts)


# -------------------------------------------------------------------- main

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="tiny sizes on whatever backend is there; "
                         f"exits {RC_REHEARSAL_OK} on success, never 0")
    ap.add_argument("--legs", default="0,1,2,3,4",
                    help="comma-separated legs to run (leg 0 always runs)")
    args = ap.parse_args(argv)
    legs = {int(x) for x in args.legs.split(",")} | {0}
    rehearsal = args.rehearsal
    cfg = TINY if rehearsal else FULL

    device = leg0_device_gate(rehearsal)
    from paddle_tpu.utils.flags import set_compilation_cache_dir
    set_compilation_cache_dir()
    cache_events = CacheEvents()

    passed, params, t0 = {0: True}, None, time.perf_counter()
    if 1 in legs:
        passed[1] = leg1_kernels(rehearsal)
    if 2 in legs:
        passed[2], params = leg2_server(rehearsal, cfg, cache_events)
    if 3 in legs:
        passed[3], _first_loss = leg3_trainer(rehearsal, cfg)
    if 4 in legs:
        passed[4] = leg4_four_chips(rehearsal, cfg, params)
    # a skipped leg (None) neither passes nor fails the run
    ok = all(v for v in passed.values() if v is not None)
    summary = {"summary": True, "ok": ok,
               "legs": {str(k): "skipped" if v is None else v
                        for k, v in sorted(passed.items())},
               "secs": round(time.perf_counter() - t0, 1), "claim": None}
    if rehearsal:
        # a rehearsal ends on this line: it has no result to report
        summary.update(rehearsal=True, device=device)
        print(json.dumps(summary), flush=True)
        return RC_REHEARSAL_OK if ok else 1
    print(json.dumps(summary), flush=True)
    # the result: exactly these keys, the last line of standard output
    print(json.dumps({"ok": ok, "device": device}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark harness: prints ONE JSON line for the driver.

Primary metric mirrors the reference's headline RNN benchmark
(benchmark/paddle/rnn/rnn.py + BASELINE.md): LSTM text classifier,
2 stacked LSTM h=512, batch 64, seq len 100, vocab 30k — reference Paddle
on 1x K40m: 184 ms/batch (including parameter update; BASELINE.md line
"LSTM h=512 | 64 | 184").

value = our ms/batch for the full train step (fwd+bwd+momentum update) on
one TPU chip; vs_baseline = 184 / value (speedup, >1 is better).

Hardened (round-2): every phase — backend init, input build, compile,
timed steps — runs under a watchdog deadline and logs progress to stderr.
On any failure the harness still prints ONE JSON line whose "error" field
distinguishes backend-unavailable from compile-fail from slow-steps, so a
broken chip is distinguishable from a broken framework.  MFU is estimated
from analytic model FLOPs and the chip's peak (perf/roofline.SPECS).

Env overrides: BENCH_MODEL=lstm|lstm256|lstm1280|resnet50|alexnet|googlenet|
smallnet|seq2seq|transformer|transformer_decode (seq2seq/transformer report
tokens/sec — the reference never shipped an NMT row and predates
transformers; transformer_decode times the KV-cached serving beam search).
A bare family name also works positionally: `python bench.py serving`
drives the serving RUNTIME (paddle_tpu/serving dynamic batcher) at several
closed-loop load levels and reports batched vs batch-size-1 throughput,
tail latency, and mean batch occupancy; `python bench.py serving_generate`
drives the continuous-batching GENERATION engine (serving/decode_engine)
against the sequential whole-batch policy at 2/8/32 clients and reports
useful tokens/s, p99 TTFT, and slot occupancy for both;
`python bench.py serving_fleet` drives the REPLICATED tier (fleet
supervisor + health-checked router over replica subprocesses) at 1 vs 2
replicas with a kill-9 mid-stream failover latency probe;
`python bench.py serving_paged` drives the PAGED KV-cache layout
(serving/kv_pool.py block pool + prefix sharing) against the slab at a
fixed KV-byte budget on mixed-length and shared-prefix workloads and
reports useful tokens/s, p99 TTFT, effective concurrent streams, and the
prefill-compute elimination rate.  Other overrides:
BENCH_STEPS, BENCH_BATCH, BENCH_INIT_TIMEOUT, BENCH_BUILD_TIMEOUT (eager
param init), BENCH_COMPILE_TIMEOUT, BENCH_STEP_TIMEOUT (seconds),
BENCH_PLATFORM (e.g. cpu to force a platform for local testing), and
BENCH_PROFILE_DIR (capture an xprof trace of the timed steps).

A failed live run prints its failure and exits non-zero, always: nothing
is replayed from an earlier run.  The MFU denominator is the peaks table
in paddle_tpu/perf/roofline.py, keyed by the device_kind JAX reports; a
device that is not in it is an error.

Kernel smoke mode: `python bench.py --smoke-kernels` (or
BENCH_MODEL=smoke_kernels) compiles every Pallas kernel (flash attention
fwd+bwd, fused LSTM/GRU/simple-RNN fwd+bwd) on the real backend with small
shapes and checks numerics vs the scan oracle — a seconds-long canary that
detects Mosaic lowering regressions independently of a full bench.

Analytic mode (round-6): `python bench.py --analytic` never runs a step —
it AOT-compiles every family's jitted step on the CPU backend, extracts
XLA's cost model (FLOPs / bytes accessed / HLO op histogram) and a TPU-v5e
roofline prediction per family, and writes BENCH_ANALYTIC_r06.json.  The
perf evidence that cannot be chip-hostage; see paddle_tpu/perf/ and
docs/perf.md "Analytic roofline".
"""

import functools
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import numpy as np


def _log(msg):
    print(f"[bench +{time.perf_counter() - _T0:8.2f}s] {msg}",
          file=sys.stderr, flush=True)


_T0 = time.perf_counter()

def _emit_failure(stub):
    """Print the final JSON line for a failed live run and return the exit
    code to use: 3 for a watchdog timeout, 2 for any other failure — never
    0, and never an earlier run's numbers."""
    print(json.dumps(stub), flush=True)
    return 3 if stub.get("error", "").endswith("timeout") else 2


class Watchdog:
    """Daemon thread that force-exits with a diagnostic JSON line if a phase
    exceeds its deadline.  Needed because a hung backend call sits inside
    C++ where no Python exception can interrupt."""

    def __init__(self, result_stub):
        self._lock = threading.Lock()
        self._phase = None
        self._deadline = None
        self._stub = result_stub
        t = threading.Thread(target=self._run, daemon=True)
        t.start()

    def phase(self, name, timeout_s):
        with self._lock:
            self._phase = name
            self._deadline = time.perf_counter() + timeout_s
        _log(f"phase={name} (timeout {timeout_s:.0f}s)")

    def clear(self):
        with self._lock:
            self._phase, self._deadline = None, None

    def _run(self):
        while True:
            time.sleep(1.0)
            with self._lock:
                phase, deadline = self._phase, self._deadline
            if deadline is not None and time.perf_counter() > deadline:
                out = dict(self._stub)
                out["value"] = None
                out["vs_baseline"] = None
                out["error"] = {
                    "init": "backend_unavailable_timeout",
                    "build": "input_build_timeout",
                    "compile": "compile_timeout",
                    "steps": "steps_timeout",
                }.get(phase, f"{phase}_timeout")
                out["phase"] = phase
                out["detail"] = (f"watchdog: phase '{phase}' exceeded its "
                                 f"deadline; see stderr timeline")
                _log(f"WATCHDOG FIRED in phase={phase}")
                os._exit(_emit_failure(out))


_RNN_MODELS = ("lstm", "lstm256", "lstm1280", "lstm2048", "seq2seq")
# families whose bench process is a PARENT of serving replicas
# (ReplicaSupervisor): a chip belongs to one process, so the parent never
# initialises an accelerator backend
_SUPERVISING_FAMILIES = ("serving_fleet", "serving_autoscale")


def _env_remat(default):
    """BENCH_REMAT=1/0 overrides; anything else -> the model's heuristic."""
    v = os.environ.get("BENCH_REMAT", "")
    return v == "1" if v in ("0", "1") else default


def _device_info():
    """(platform, device_kind, device count, peak FLOP/s) of the live
    backend.  The peak is the peaks table's row for the reported
    ``device_kind``; a stranger raises (perf/roofline.for_device_kind)."""
    import jax
    from paddle_tpu.perf import roofline
    dev = jax.devices()[0]
    spec = roofline.for_device_kind(dev.device_kind)
    return dev.platform, dev.device_kind, len(jax.devices()), spec.peak_flops


# ---------------------------------------------------------------------------
# Benchmarks.  Each returns (setup_fn) -> (step, args, flops_per_step,
# baseline_ms_or_None, metric_name, unit, to_value).


def bench_lstm(batch=64, seq_len=100, hidden=512, vocab=30000,
               baseline_ms=184.0):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import text_lstm
    from paddle_tpu import optim

    params = text_lstm.init(jax.random.PRNGKey(0), vocab=vocab,
                            emb_dim=128, hidden=hidden, num_layers=2)
    opt = optim.Momentum(learning_rate=0.01, momentum=0.9)
    opt_state = opt.init(params)

    rng = np.random.RandomState(0)
    ids = SequenceBatch(
        data=jnp.asarray(rng.randint(0, vocab, (batch, seq_len)), jnp.int32),
        lengths=jnp.full((batch,), seq_len, jnp.int32))
    labels = jnp.asarray(rng.randint(0, 2, (batch,)), jnp.int32)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, ids, labels):
        loss, grads = jax.value_and_grad(text_lstm.loss)(
            params, ids, labels, 2, hidden)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, loss

    # Matmul FLOPs per train step: fwd = 2*B*T*4H*(emb + H + H + H) for the
    # two layers' input+recurrent projections; train ~= 3x fwd (bwd ~= 2x).
    emb_dim = 128
    fwd = 2.0 * batch * seq_len * 4 * hidden * (emb_dim + hidden + 2 * hidden)
    flops = 3.0 * fwd

    def run(s):
        nonlocal params, opt_state
        params, opt_state, loss = step(params, opt_state, ids, labels)
        return loss

    return run, flops, baseline_ms, (
        f"LSTM-textclass h={hidden} bs={batch} len={seq_len} ms/batch"), \
        {"lower": lambda: step.lower(params, opt_state, ids, labels)}


def bench_resnet50(batch=32):
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import resnet
    from paddle_tpu import optim

    params, state = resnet.init(jax.random.PRNGKey(0), depth=50,
                                num_classes=1000)
    opt = optim.Momentum(learning_rate=0.1, momentum=0.9)
    opt_state = opt.init(params)
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(batch, 224, 224, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, 1000, (batch,)), jnp.int32)

    # default: recompute activations once the batch is too big to keep
    # them resident (bs>=512)
    remat = _env_remat(batch >= 512)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, state, opt_state, images, labels):
        (loss, new_state), grads = jax.value_and_grad(
            resnet.loss, has_aux=True)(params, state, images, labels, 50,
                                       remat=remat)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_state, new_opt, loss

    st = {"params": params, "state": state, "opt": opt_state}

    def run(s):
        st["params"], st["state"], st["opt"], loss = step(
            st["params"], st["state"], st["opt"], images, labels)
        return loss

    flops = 3.0 * 4.1e9 * batch      # ~4.1 GFLOP fwd per 224x224 image
    return run, flops, None, f"ResNet-50 train ms/batch bs={batch}", \
        {"remat": remat,
         "lower": lambda: step.lower(st["params"], st["state"], st["opt"],
                                     images, labels)}


def bench_image(model_name, batch, baseline_ms, fwd_flops_per_image,
                image_hw, num_classes):
    import jax
    import jax.numpy as jnp
    from paddle_tpu import optim
    from paddle_tpu.models import alexnet, googlenet, smallnet
    mod = {"alexnet": alexnet, "googlenet": googlenet,
           "smallnet": smallnet}[model_name]

    params, state = mod.init(jax.random.PRNGKey(0), num_classes=num_classes)
    opt = optim.Momentum(learning_rate=0.01, momentum=0.9)
    opt_state = opt.init(params)
    rng = np.random.RandomState(0)
    images = jnp.asarray(rng.randn(batch, image_hw, image_hw, 3), jnp.float32)
    labels = jnp.asarray(rng.randint(0, num_classes, (batch,)), jnp.int32)

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def step(params, state, opt_state, images, labels):
        (loss, new_state), grads = jax.value_and_grad(
            mod.loss, has_aux=True)(params, state, images, labels)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_state, new_opt, loss

    st = {"params": params, "state": state, "opt": opt_state}

    def run(s):
        st["params"], st["state"], st["opt"], loss = step(
            st["params"], st["state"], st["opt"], images, labels)
        return loss

    flops = 3.0 * fwd_flops_per_image * batch
    return run, flops, baseline_ms, (
        f"{model_name} train ms/batch bs={batch} ({image_hw}x{image_hw})"), \
        {"lower": lambda: step.lower(st["params"], st["state"], st["opt"],
                                     images, labels)}


def bench_seq2seq(batch=64, src_len=30, trg_len=30, vocab=30000, hidden=512):
    """Attention-NMT train step (demo/seqToseq scale: vocab 30k, emb=h=512).
    The reference's benchmark README declares this row 'will be added later'
    (benchmark/README.md:141,168) and never shipped it — no baseline_ms;
    tokens/sec is the headline number here."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import seq2seq
    from paddle_tpu import optim

    h = e = hidden
    params = seq2seq.init(jax.random.PRNGKey(0), src_vocab=vocab,
                          trg_vocab=vocab, emb_dim=e, hidden=h)
    opt = optim.Momentum(learning_rate=0.01, momentum=0.9)
    opt_state = opt.init(params)

    rng = np.random.RandomState(0)
    src = SequenceBatch(
        data=jnp.asarray(rng.randint(3, vocab, (batch, src_len)), jnp.int32),
        lengths=jnp.full((batch,), src_len, jnp.int32))
    trg = SequenceBatch(
        data=jnp.asarray(rng.randint(3, vocab, (batch, trg_len)), jnp.int32),
        lengths=jnp.full((batch,), trg_len, jnp.int32))

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, src, trg):
        loss, grads = jax.value_and_grad(seq2seq.loss)(params, src, trg, trg)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, loss

    def run(s):
        nonlocal params, opt_state
        params, opt_state, loss = step(params, opt_state, src, trg)
        return loss

    # analytic matmul FLOPs, fwd (see models/seq2seq.py dims); train ~= 3x
    B, Ts, Tt, V = batch, src_len, trg_len, vocab
    enc = 2 * 2.0 * B * Ts * (3 * e * h + 3 * h * h) + 4.0 * B * Ts * h * h
    dec = 2.0 * B * Tt * ((e + 2 * h) * 3 * h + 4 * h * h
                          + (4 * h + e) * h + h * V) \
        + 2.0 * B * Tt * Ts * (h + 2 * h)
    flops = 3.0 * (enc + dec)
    return run, flops, None, (
        f"seq2seq attention-NMT train ms/batch bs={batch} "
        f"len={src_len} vocab={vocab}"), \
        {"tokens_per_step": B * Tt,
         "lower": lambda: step.lower(params, opt_state, src, trg)}


def bench_transformer(batch=32, seq_len=256, vocab=32000, d_model=512,
                      dff=2048, layers=6, heads=8):
    """Transformer-base MT train step (the framework's post-reference
    flagship; attention runs through the Pallas flash kernel).  No
    reference baseline exists (pre-transformer era); tokens/sec is the
    headline."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import transformer
    from paddle_tpu import optim

    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=vocab, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=layers,
                              max_len=seq_len)
    opt = optim.Adam(learning_rate=1e-4)
    opt_state = opt.init(params)
    rng = np.random.RandomState(0)
    mk = lambda: SequenceBatch(
        data=jnp.asarray(rng.randint(3, vocab, (batch, seq_len)), jnp.int32),
        lengths=jnp.full((batch,), seq_len, jnp.int32))
    src, trg = mk(), mk()

    # default: recompute per block once the token count reaches the 32k
    # scaling point (batch*seq >= 32768) OR the sequence itself is long
    # (transformer_long: per-layer [B, 8192, D] activations + the 32k-
    # vocab logits leave little HBM headroom without remat)
    remat = _env_remat(batch * seq_len >= 32768 or seq_len >= 4096)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, src, trg):
        # full_seq: every bench sequence is max-length, so masking drops
        # entirely and the Pallas flash kernel engages on TPU (a key_mask
        # would still be O(T)-memory via chunked_attention, but off the
        # flash fast path)
        loss, grads = jax.value_and_grad(transformer.loss)(
            params, src, trg, trg, heads, remat=remat, full_seq=True)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, loss

    def run(s):
        nonlocal params, opt_state
        params, opt_state, loss = step(params, opt_state, src, trg)
        return loss

    # 2*params*tokens matmul fwd; attention ~2*2*B*T^2*D per stack; x3 train
    # encoder layer: self-attn 4d^2 + mlp 2*d*dff; decoder layer adds a full
    # cross-attention block (another 4d^2)
    n_params = (2 * layers) * (4 * d_model ** 2 + 2 * d_model * dff) \
        + layers * 4 * d_model ** 2
    tok = batch * seq_len
    attn = 4.0 * 3 * layers * batch * seq_len * seq_len * d_model
    flops = 3.0 * (2.0 * n_params * tok + 2.0 * vocab * d_model * tok + attn)
    return run, flops, None, (
        f"transformer-base MT train ms/batch bs={batch} len={seq_len}"), \
        {"tokens_per_step": tok, "remat": remat,
         "lower": lambda: step.lower(params, opt_state, src, trg)}


def bench_transformer_packed(batch=16, max_len=512, vocab=32000,
                             d_model=512, dff=2048, layers=6, heads=8):
    """Padding-free packed training on the flagship encoder: ragged
    sequences (geometric-ish length mix, mean ~1/3 max_len) packed
    first-fit into [B, max_len] rows (core.sequence.pack_sequences),
    segment-ids attention keeping rows block-diagonal, within-segment
    positions.  The headline is REAL tokens/sec — the same ragged stream
    padded 1:1 would spend ~3x the step FLOPs per real token, which is
    the reference's Argument.sequenceStartPositions no-padding story at
    transformer scale.  extras carry pack_efficiency (real/slot tokens)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.sequence import SequenceBatch, pack_sequences
    from paddle_tpu.models import transformer
    from paddle_tpu import optim

    # encoder-only benchmark: no decoder stack and a 1-row target vocab,
    # so grad + Adam traffic covers exactly the params the loss trains
    # (a full trg_emb/out pair would add ~33M dead params to every step)
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=0,
                              max_len=max_len)
    opt = optim.Adam(learning_rate=1e-4)
    opt_state = opt.init(params)
    rng = np.random.RandomState(0)
    # estimate the sequence count up front and pack ONCE: mean real length
    # is ~max_len/3, so ~3 sequences fill a row; 2x slack covers first-fit
    # inefficiency + length-mix variance.  The rare shortfall doubles the
    # estimate and re-packs — O(log) attempts each packing a fresh list,
    # never the old quadratic re-pack of the whole accumulated list per
    # 64-sequence chunk.
    n_seqs = batch * 3 * 2
    while True:
        lens = np.clip(rng.geometric(1.0 / (max_len // 3), size=n_seqs),
                       8, max_len)
        seqs = [rng.randint(3, vocab, int(n)) for n in lens]
        rows = pack_sequences(seqs, max_len)
        if rows[0].shape[0] >= batch:
            break
        n_seqs *= 2
    data, seg, pos = (jnp.asarray(a[:batch]) for a in rows)
    src = SequenceBatch(data, jnp.full((batch,), max_len, jnp.int32))
    real_tokens = int(np.sum(np.asarray(seg) > 0))
    remat = _env_remat(batch * max_len >= 32768)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, src, seg, pos):
        def loss_fn(p):
            # the canonical packed causal-LM objective (next-token CE,
            # models/transformer.lm_loss) — the realistic workload, not
            # an ad-hoc re-prediction
            return transformer.lm_loss(p, src, heads, segment_ids=seg,
                                       positions=pos, remat=remat)
        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, loss

    def run(s):
        nonlocal params, opt_state
        params, opt_state, loss = step(params, opt_state, src, seg, pos)
        return loss

    # compute runs on every SLOT (padded) position; credit = real tokens
    tok_slots = batch * max_len
    n_params = layers * (4 * d_model ** 2 + 2 * d_model * dff)
    attn = 4.0 * layers * batch * max_len * max_len * d_model
    flops = 3.0 * (2.0 * n_params * tok_slots
                   + 2.0 * vocab * d_model * tok_slots + attn)
    return run, flops, None, (
        f"transformer packed-encoder train ms/batch bs={batch} "
        f"slots={max_len} real_tok/row={real_tokens / batch:.0f}"), \
        {"tokens_per_step": real_tokens, "remat": remat,
         "pack_efficiency": round(real_tokens / tok_slots, 3),
         "lower": lambda: step.lower(params, opt_state, src, seg, pos)}


def bench_transformer_moe(batch=16, seq_len=512, vocab=32000, d_model=512,
                          dff=2048, layers=6, heads=8, experts=8,
                          moe_top_k=2):
    """Sparse-expert causal-LM train step: the flagship trunk with every
    block's FFN an 8-expert top-2 mixture (models/transformer.init
    moe_experts=...).  E x the dense FFN parameters; the batched-einsum
    dispatch EXECUTES all E experts per token (dense dispatch — MXU-
    friendly, no gather/scatter), so the step genuinely pays ~E x the
    dense FFN FLOPs and the flops model counts it that way."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import transformer
    from paddle_tpu import optim

    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=0,
                              max_len=seq_len, moe_experts=experts)
    opt = optim.Adam(learning_rate=1e-4)
    opt_state = opt.init(params)
    rng = np.random.RandomState(0)
    tokens = SequenceBatch(
        jnp.asarray(rng.randint(3, vocab, (batch, seq_len)), jnp.int32),
        jnp.full((batch,), seq_len, jnp.int32))
    remat = _env_remat(batch * seq_len >= 32768)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, tokens):
        loss, grads = jax.value_and_grad(
            lambda p: transformer.lm_loss(p, tokens, heads, remat=remat,
                                          moe_top_k=moe_top_k))(params)
        new_params, new_opt = opt.update(grads, opt_state, params)
        return new_params, new_opt, loss

    def run(s):
        nonlocal params, opt_state
        params, opt_state, loss = step(params, opt_state, tokens)
        return loss

    tok = batch * seq_len
    # EXECUTED compute per token: attention stack + ALL E expert FFNs
    # (the batched einsum runs every expert; gating selects afterwards)
    # + router + tied projection; x3 train
    n_params = layers * (4 * d_model ** 2
                         + experts * 2 * d_model * dff
                         + d_model * experts)
    attn = 4.0 * layers * batch * seq_len * seq_len * d_model
    flops = 3.0 * (2.0 * n_params * tok + 2.0 * vocab * d_model * tok
                   + attn)
    return run, flops, None, (
        f"transformer MoE-LM train ms/batch bs={batch} len={seq_len} "
        f"E={experts} k={moe_top_k}"), \
        {"tokens_per_step": tok, "remat": remat,
         "lower": lambda: step.lower(params, opt_state, tokens)}


def _lm_kv_heads():
    """BENCH_LM_KV_HEADS parsed ONCE (int or None) — the bench body and
    must agree on what counts as 'GQA on'."""
    try:
        v = int(os.environ.get("BENCH_LM_KV_HEADS", "0"))
    except ValueError:
        return None
    return v if v > 0 else None


def bench_transformer_lm_decode(batch=32, prompt_len=32, max_len=160,
                                vocab=32000, d_model=512, dff=2048,
                                layers=6, heads=8):
    """LM sampling throughput: KV-cached greedy generation on the
    decoder-only trunk (models/transformer.lm_generate) — the modern
    serving workload the seq2seq beam families don't cover.  Emitted
    (post-prompt) tokens/sec is the headline.  BENCH_LM_KV_HEADS=K
    measures the grouped-query variant (KV cache + per-token HBM stream
    shrink heads/K-fold; cache row transformer_lm_decode@gqaK)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import transformer

    kv_heads = _lm_kv_heads()
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=0,
                              max_len=max_len, num_heads=heads,
                              num_kv_heads=kv_heads)
    rng = np.random.RandomState(0)
    prompt = jnp.asarray(rng.randint(3, vocab, (batch, prompt_len)),
                         jnp.int32)
    gen = jax.jit(lambda p, pr: transformer.lm_generate(
        p, pr, max_len=max_len, num_heads=heads))

    def run(s):
        # the harness float()s the return: a cheap device scalar while
        # the timed work is the whole generation scan
        return gen(params, prompt).sum()

    # EXECUTED compute per decoded position per row: q+o projections at
    # full width, k/v at the (possibly grouped) KV width, + ffn + the
    # d_model x vocab tied projection; attention reads the whole cache
    d_kv = (d_model // heads) * kv_heads if kv_heads else d_model
    per_tok = layers * (2 * d_model ** 2 + 2 * d_model * d_kv
                        + 2 * d_model * dff) + d_model * vocab
    # QK^T + AV = 4*d_model FLOPs per (query, cached position) — the
    # training benches' 4*d*T^2 convention, and like them added OUTSIDE
    # the 2.0 MAC->FLOP factor (which converts per_tok PARAM counts);
    # causal decode reads on average half the cache, hence the /2
    attn = layers * 4.0 * d_model * max_len * max_len / 2
    flops = 2.0 * batch * per_tok * (max_len - 1) + batch * attn
    extras = {"tokens_per_step": batch * (max_len - prompt_len),
              "lower": lambda: gen.lower(params, prompt)}
    tag = f" kv_heads={kv_heads}" if kv_heads else ""
    if kv_heads:
        extras["kv_heads"] = kv_heads
    return run, flops, None, (
        f"transformer LM decode ms/batch bs={batch} prompt={prompt_len} "
        f"T={max_len}" + tag), extras


def _decode_flops(batch, src_len, max_len, vocab, d_model, dff, layers,
                  beam):
    """Analytic FLOPs of one KV-cached beam decode of a batch: per decoded
    position per beam lane self-attn q/k/v/o (4d^2) + cross q/o only (2d^2
    — cross K/V are hoisted once per sequence by generate_cached) + ffn +
    the dominant d_model x vocab projection; encoder + cross-KV build run
    ONCE per sequence.  Shared by the decode and serving families so the
    model can only be fixed in one place."""
    dec_per_tok = layers * (6 * d_model ** 2 + 2 * d_model * dff) \
        + d_model * vocab
    per_seq = layers * (4 * d_model ** 2 + 2 * d_model * dff) * src_len \
        + layers * 2 * d_model ** 2 * src_len * beam      # cross-KV build
    return 2.0 * batch * (dec_per_tok * beam * max_len + per_seq)


def _maybe_quantize(params):
    """BENCH_QUANT=int8: weight-only int8 params with a jit-traceable
    dequant (export.quantize_params) — the decode then streams int8
    weights from HBM (~4x less weight bandwidth, the usual serving
    bottleneck) and the dequant fuses into the consuming matmuls.
    Returns (possibly-quantized params, dequant fn, quant tag or None)."""
    if os.environ.get("BENCH_QUANT") != "int8":
        return params, (lambda p: p), None
    from paddle_tpu.export import quantize_params
    q, dq = quantize_params(params)
    return q, dq, "int8"


def bench_transformer_decode(batch=32, src_len=128, max_len=128, vocab=32000,
                             d_model=512, dff=2048, layers=6, heads=8,
                             beam=4):
    """Serving decode throughput: KV-cached beam search on transformer-base
    (models/transformer.py generate_cached).  No reference baseline (the
    reference predates transformers); emitted tokens/sec is the headline.
    BENCH_QUANT=int8 measures the weight-only-quantized latency column
    (cache row transformer_decode@int8)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import transformer

    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=vocab, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=layers,
                              max_len=src_len + max_len)
    rng = np.random.RandomState(0)
    src = SequenceBatch(
        data=jnp.asarray(rng.randint(3, vocab, (batch, src_len)), jnp.int32),
        lengths=jnp.full((batch,), src_len, jnp.int32))

    # params as a jit ARGUMENT (closing over them would bake ~100MB of
    # weights into the executable as constants)
    params, dq, quant = _maybe_quantize(params)
    decode = jax.jit(lambda p, s: transformer.generate_cached(
        dq(p), s, beam_size=beam, max_len=max_len, num_heads=heads))

    def run(s):
        # the harness float()s the return for its log line: hand it the
        # mean beam score (scalar) while timing the whole decode
        return decode(params, src).scores.mean()

    flops = _decode_flops(batch, src_len, max_len, vocab, d_model, dff,
                          layers, beam)
    extras = {"tokens_per_step": batch * max_len,
              "lower": lambda: decode.lower(params, src)}
    if quant:
        extras["quant"] = quant
    return run, flops, None, (
        f"transformer decode ms/batch bs={batch} beam={beam} "
        f"T={max_len}" + (f" quant={quant}" if quant else "")), extras


def bench_transformer_serving(batch=16, n_requests=64, src_max=128,
                              buckets=(32, 64, 128), max_len=128,
                              vocab=32000, d_model=512, dff=2048, layers=6,
                              heads=8, beam=4, seed=0):
    """Serving-reality decode: a stream of requests with MIXED source
    lengths is bucketed (core.sequence.bucket_for), grouped into fixed
    batches per bucket, and batch-beam-decoded with the KV cache — one
    compiled program per bucket shape, padding waste included in the
    clock.  Headline: emitted tokens/sec over the whole stream.

    BENCH_SERVING_TINY=1 shrinks model + stream to smoke scale (harness
    canary on CPU, or a first-contact check in a TPU window)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.sequence import SequenceBatch, bucket_for
    from paddle_tpu.models import transformer

    if os.environ.get("BENCH_SERVING_TINY") == "1":
        n_requests, src_max, buckets, max_len = 6, 16, (8, 16), 8
        vocab, d_model, dff, layers, heads = 128, 32, 64, 1, 2

    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=vocab, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=layers,
                              max_len=src_max + max_len)
    rng = np.random.RandomState(seed)
    lengths = rng.randint(src_max // 8, src_max + 1, (n_requests,))

    # bucket + batch the stream (short final batches pad by repetition —
    # what a serving frontend does to keep shapes static)
    groups = {}
    for ln in lengths:
        groups.setdefault(bucket_for(int(ln), list(buckets)), []).append(
            int(ln))
    batches = []
    for blen, lens in sorted(groups.items()):
        for i in range(0, len(lens), batch):
            chunk = lens[i:i + batch]
            chunk = chunk + [chunk[-1]] * (batch - len(chunk))
            data = rng.randint(3, vocab, (batch, blen)).astype(np.int32)
            batches.append(SequenceBatch(
                data=jnp.asarray(data),
                lengths=jnp.asarray(np.asarray(chunk, np.int32))))

    params, dq, quant = _maybe_quantize(params)
    decode = jax.jit(lambda p, s: transformer.generate_cached(
        dq(p), s, beam_size=beam, max_len=max_len, num_heads=heads))

    def run(i):
        score = None
        for sb in batches:      # one step = serve the whole request stream
            score = decode(params, sb).scores.mean()
        return score

    # decode flop model summed over the stream's actual bucket shapes
    flops = sum(_decode_flops(batch, int(sb.data.shape[1]), max_len, vocab,
                              d_model, dff, layers, beam)
                for sb in batches)
    # real requests only: padding-duplicate rows burn clock (serving
    # reality) but must not be credited as served output
    emitted = n_requests * max_len
    # AOT hook costs ONE batch of the largest bucket (batches are built
    # in ascending bucket order) — the analytic row's scope, not the
    # whole stream
    extras = {"tokens_per_step": emitted,
              "lower": lambda: decode.lower(params, batches[-1])}
    if quant:
        extras["quant"] = quant
    return run, flops, None, (
        f"transformer serving ms/stream bs={batch} beam={beam} "
        f"{len(batches)} bucketed batches (src {src_max // 8}-{src_max}, "
        f"buckets {list(buckets)})"
        + (f" quant={quant}" if quant else "")), extras


def bench_serving_engine(batch=32, dim=256, hidden=1024, classes=32,
                         n_requests=256, max_delay_ms=2.0):
    """Dynamic-batching serving runtime (paddle_tpu/serving): closed-loop
    client threads hammer the Batcher with single-sample requests; the
    engine AOT-serves padded bucket batches.  extras carry the offered-
    load sweep — throughput / p50 / p99 / mean batch occupancy per client
    count — plus the batch-size-1 baseline (max_batch_size=1, same model,
    same engine) at saturating load, so the row IS the batched-vs-
    unbatched serving comparison.  run() serves one closed-loop burst
    (n_requests over 8 clients) for the timed phase."""
    import jax
    from paddle_tpu.layers import api as L
    from paddle_tpu.layers.graph import Topology, reset_names
    from paddle_tpu.serving import Batcher, InferenceEngine, ServingMetrics

    ladder = tuple(b for b in (1, 4, 16, 64) if b < batch) + (batch,)
    reset_names()
    x = L.data_layer("serving_x", size=dim)
    h = L.fc_layer(input=x, size=hidden, act="tanh")
    out_l = L.fc_layer(input=h, size=classes, act="softmax")
    params = Topology([out_l]).init(jax.random.PRNGKey(0))
    spec = {"serving_x": jax.ShapeDtypeStruct((1, dim), np.float32)}
    # warm=False: under --analytic nothing may execute (warmup runs each
    # bucket once); the load path below warms explicitly
    engine = InferenceEngine.from_topology(out_l, params, spec,
                                           buckets=ladder, warm=False,
                                           name="bench")
    rng = np.random.RandomState(0)
    rows = [{"serving_x": rng.randn(dim).astype(np.float32)}
            for _ in range(64)]

    def drive(n_clients, max_batch, n_req):
        """One closed-loop level: n_clients threads, back-to-back
        requests, fresh metrics; returns throughput + latency tails."""
        engine.metrics = ServingMetrics()
        bat = Batcher(engine, max_batch_size=max_batch,
                      max_delay_ms=max_delay_ms, queue_size=4096)
        lats, lock = [], threading.Lock()

        def client(k):
            my = []
            for i in range(n_req // n_clients):
                t0 = time.perf_counter()
                bat.submit(rows[(k * 7 + i) % len(rows)]).result(120)
                my.append(time.perf_counter() - t0)
            with lock:
                lats.extend(my)

        ts = [threading.Thread(target=client, args=(k,))
              for k in range(n_clients)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        bat.close()
        lats.sort()
        snap = engine.metrics.snapshot()
        return {"clients": n_clients, "max_batch": max_batch,
                "throughput_rps": round(len(lats) / dt, 1),
                "p50_ms": round(lats[len(lats) // 2] * 1e3, 2),
                "p99_ms": round(lats[min(len(lats) - 1,
                                         int(len(lats) * 0.99))] * 1e3, 2),
                "mean_occupancy": snap["mean_occupancy"],
                "padding_waste": snap["padding_waste"]}

    extras = {"lower": lambda: engine.lower(ladder[-1])}
    if os.environ.get("BENCH_ANALYTIC_BUILD") != "1":
        engine.warmup()
        # health probe (the /readyz readiness contract, docs/serving.md
        # §5): an unwarm ladder would put compiles on the timed clock
        assert engine.ready, "serving bench engine not ready after warmup"
        drive(8, batch, 64)             # warm the whole batched path
        sweep = [drive(c, batch, n_requests) for c in (2, 8, 32)]
        sat = sweep[-1]
        bs1 = drive(32, 1, n_requests)  # no-batching baseline, same load
        extras.update(
            load_sweep=sweep,
            batched_throughput_rps=sat["throughput_rps"],
            batched_p99_ms=sat["p99_ms"],
            mean_batch_occupancy=sat["mean_occupancy"],
            padding_waste=sat["padding_waste"],
            bs1_throughput_rps=bs1["throughput_rps"],
            bs1_p99_ms=bs1["p99_ms"],
            batching_speedup=round(sat["throughput_rps"]
                                   / bs1["throughput_rps"], 2))

    def run(s):
        r = drive(8, batch, n_requests)
        return np.float32(r["throughput_rps"])

    # fwd matmul FLOPs per request, over the burst run() serves
    flops = 2.0 * (dim * hidden + hidden * classes) * n_requests
    return run, flops, None, (
        f"serving dynamic-batch ms/burst ({n_requests} reqs, 8 clients, "
        f"buckets {list(ladder)}, delay {max_delay_ms:g}ms)"), extras


def bench_serving_generate(slots=8, n_requests=64, vocab=256, d_model=128,
                           dff=256, layers=3, heads=2,
                           prefill_buckets=(8, 16), gen_short=4,
                           gen_long=48, seed=0):
    """Continuous-batching generation serving (serving/decode_engine.py):
    closed-loop clients stream /v1/generate-shaped requests (mixed prompt
    lengths, mixed max_tokens — mostly short answers, some long ones)
    through the slot-based decode engine, against the SAME engine run
    under the sequential whole-batch policy (GenerationBatcher
    admission="gang": fill the slab, ride every row to the slowest one,
    only then admit more — what lm_generate's fixed-batch decode does).
    Same compiled slab step, same prefill ladder: the sweep isolates
    exactly what continuous admission/eviction buys.

    Headline: useful tokens/sec at 8 clients, continuous.  extras carry
    the 2/8/32-client sweep for BOTH policies (tokens/s, p50/p99 TTFT,
    slot occupancy), the continuous-vs-gang speedups, and the analytic
    AOT hook (extras["lower"]: the slab decode step's Lowered)."""
    import jax
    from paddle_tpu.models import transformer
    from paddle_tpu.serving import GenerationBatcher, ServingMetrics
    from paddle_tpu.serving.decode_engine import DecodeEngine

    gen_cap = gen_long
    max_len = prefill_buckets[-1] + gen_cap
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=0,
                              max_len=max_len, num_heads=heads)
    engine = DecodeEngine(params, num_heads=heads, num_slots=slots,
                          max_len=max_len, prefill_buckets=prefill_buckets,
                          name="bench_gen",
                          warm=os.environ.get("BENCH_ANALYTIC_BUILD") != "1")
    rng = np.random.RandomState(seed)
    # the serving-shaped mix: 3/4 short completions, 1/4 long ones — the
    # exact shape where whole-batch decode burns finished rows' steps
    reqs = [(rng.randint(1, vocab, rng.randint(3, prefill_buckets[-1] + 1)
                         ).astype(np.int32),
             gen_long if i % 4 == 0 else gen_short)
            for i in range(n_requests)]

    def drive(mode, n_clients, reqs):
        """One closed-loop level under one admission policy."""
        engine.metrics = ServingMetrics()
        bat = GenerationBatcher(engine, queue_size=4096, admission=mode)
        ttfts, lock, nxt = [], threading.Lock(), [0]
        tokens = [0]

        def client():
            while True:
                with lock:
                    i = nxt[0]
                    if i >= len(reqs):
                        return
                    nxt[0] += 1
                prompt, mt = reqs[i]
                out = bat.submit(prompt, max_tokens=mt).result(300)
                with lock:
                    ttfts.append(out["ttft_ms"])
                    tokens[0] += len(out["tokens"])

        ts = [threading.Thread(target=client) for _ in range(n_clients)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        bat.close()
        ttfts.sort()
        snap = engine.metrics.snapshot()
        return {"clients": n_clients, "mode": mode,
                "tokens_per_s": round(tokens[0] / dt, 1),
                "ttft_p50_ms": round(ttfts[len(ttfts) // 2], 2),
                "ttft_p99_ms": round(ttfts[min(len(ttfts) - 1,
                                               int(len(ttfts) * 0.99))], 2),
                "mean_slot_occupancy": snap["mean_slot_occupancy"]}

    def best_of(mode, n_clients, reqs, n=2):
        """Best throughput of n runs, for BOTH policies symmetrically:
        client threads contend with the decode worker for cores, so on a
        small host a single closed-loop run can lose a large slice of
        wall time to the scheduler; the best run is the one least
        distorted by that noise."""
        runs = [drive(mode, n_clients, reqs) for _ in range(n)]
        return max(runs, key=lambda r: r["tokens_per_s"])

    extras = {"lower": lambda: engine.lower()}
    if os.environ.get("BENCH_ANALYTIC_BUILD") != "1":
        drive("continuous", 8, reqs[:16])       # warm the whole path
        sweep = []
        for c in (2, 8, 32):
            cont = best_of("continuous", c, reqs)
            gang = best_of("gang", c, reqs)
            sweep.append({"clients": c, "continuous": cont, "gang": gang,
                          "speedup": round(cont["tokens_per_s"]
                                           / gang["tokens_per_s"], 2)})
        at8 = sweep[1]
        extras.update(
            load_sweep=sweep,
            continuous_tokens_per_s=at8["continuous"]["tokens_per_s"],
            continuous_ttft_p99_ms=at8["continuous"]["ttft_p99_ms"],
            gang_tokens_per_s=at8["gang"]["tokens_per_s"],
            gang_ttft_p99_ms=at8["gang"]["ttft_p99_ms"],
            mean_slot_occupancy=at8["continuous"]["mean_slot_occupancy"],
            continuous_speedup=at8["speedup"])

    def run(s):
        r = drive("continuous", 8, reqs)
        return np.float32(r["tokens_per_s"])

    # executed decode compute of one burst: every step runs the whole
    # [slots]-row slab; ideal-occupancy step count = useful tokens / slots
    total_tokens = sum(mt for _, mt in reqs)
    per_tok = layers * (6 * d_model ** 2 + 2 * d_model * dff) \
        + d_model * vocab
    attn = layers * 4.0 * d_model * max_len * max_len / 2
    flops = (2.0 * per_tok + attn / max_len) * slots \
        * (total_tokens / slots)
    return run, flops, None, (
        f"generation serving ms/burst ({n_requests} reqs, 8 clients, "
        f"{slots} slots, prefill {list(prefill_buckets)}, "
        f"max_tokens {gen_short}/{gen_long})"), extras


def bench_serving_paged(slots=8, n_requests=160, vocab=256, d_model=128,
                        dff=256, layers=3, heads=2, block_size=8, seed=0):
    """Paged KV-cache serving (serving/kv_pool.py + DecodeEngine
    kv_layout="paged") vs the PR-5 slab, at a FIXED KV-BYTE BUDGET:
    both layouts get exactly ``slots * max_len`` KV positions of memory;
    the slab spends them as ``slots`` fixed reservations while the paged
    pool commits blocks as streams actually grow (plus prefix sharing).
    Two workloads:

    * MIXED LENGTH (the reservation-waste case): mostly-short
      completions with a head of long ones (issued first, so their
      gen_long-step decode floor — neither layout can finish a stream
      in fewer steps than its token count — overlaps the short traffic
      instead of riding out alone), driven closed-loop at 48 clients.
      The paged engine opens 4x the slot count over the same bytes and
      packs by ACTUAL length — headline ``useful tokens/s`` plus
      ``effective_streams`` (mean active slots per decode step) for
      both layouts; the acceptance bar is paged >= 2x slab effective
      streams.
    * SHARED PREFIX (the duplicate-prefill case): every request is one
      long system prompt + a short divergent question.  The first
      request registers the prefix chains; the rest admit by reference,
      so ``prefill_elimination`` (1 - prefilled positions / total
      prompt positions) must clear 90%.

    Same compiled trunk for all engines; greedy streams are verified
    IDENTICAL between layouts inside the drive (any divergence fails
    the bench).  extras["lower"] is the paged slab step's Lowered — the
    analytic row gating the gather/scatter step structure."""
    import jax
    from paddle_tpu.models import transformer
    from paddle_tpu.serving import GenerationBatcher, ServingMetrics
    from paddle_tpu.serving.decode_engine import DecodeEngine

    prefill_buckets = (8, 16)
    gen_short, gen_long = 6, 48
    max_len = prefill_buckets[-1] + gen_long
    budget_positions = slots * max_len          # the fixed KV budget
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=0,
                              max_len=max_len, num_heads=heads)
    warm = os.environ.get("BENCH_ANALYTIC_BUILD") != "1"

    def make_engine(layout, n_slots, name):
        return DecodeEngine(
            params, num_heads=heads, num_slots=n_slots, max_len=max_len,
            prefill_buckets=prefill_buckets,
            prefill_batch_buckets=(1, 8), name=name, warm=warm,
            kv_layout=layout, kv_block_size=block_size,
            kv_num_blocks=(budget_positions // block_size + 1
                           if layout == "paged" else 0))

    # slab: budget / max_len slots.  paged: SAME bytes, 4x the slots —
    # concurrency is bounded by blocks actually used, not reservations
    slab = make_engine("slab", slots, "bench_paged_slab")
    paged = make_engine("paged", 4 * slots, "bench_paged_pool")
    rng = np.random.RandomState(seed)
    # the long completions go FIRST: closed-loop clients pull in order,
    # so the longs' decode floor (gen_long steps — neither layout can
    # finish sooner) overlaps the short traffic instead of riding out
    # alone at the tail of the drive
    mixed = [(rng.randint(1, vocab, rng.randint(3, 9)).astype(np.int32),
              gen_long if i < slots // 2 else gen_short)
             for i in range(n_requests)]
    # system prompt: one full block + a partial tail, question keeps the
    # total at the ladder top (the LEADER's whole-prompt prefill must fit
    # the ladder; followers seat by reference and never prefill)
    sys_prompt = rng.randint(1, vocab, block_size + block_size // 2) \
        .astype(np.int32)
    shared = [(np.concatenate([sys_prompt,
                               rng.randint(1, vocab, 4).astype(np.int32)]),
               gen_short) for _ in range(n_requests // 2)]

    def drive(engine, n_clients, reqs):
        engine.metrics = ServingMetrics()
        bat = GenerationBatcher(engine, queue_size=4096)
        lock, nxt, tokens, ttfts = threading.Lock(), [0], [0], []
        outs = [None] * len(reqs)

        def client():
            while True:
                with lock:
                    i = nxt[0]
                    if i >= len(reqs):
                        return
                    nxt[0] += 1
                prompt, mt = reqs[i]
                out = bat.submit(prompt, max_tokens=mt).result(300)
                outs[i] = out["tokens"]
                with lock:
                    ttfts.append(out["ttft_ms"])
                    tokens[0] += len(out["tokens"])

        ts = [threading.Thread(target=client) for _ in range(n_clients)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        bat.close()
        if not ttfts:
            raise RuntimeError(f"{engine.name}: no request completed")
        ttfts.sort()
        snap = engine.metrics.snapshot()
        return {"tokens_per_s": round(tokens[0] / dt, 1),
                "ttft_p99_ms": round(ttfts[min(len(ttfts) - 1,
                                               int(len(ttfts) * 0.99))], 2),
                "effective_streams": snap["mean_slot_occupancy"],
                "pool_exhausted": snap["evictions"]["pool_exhausted"],
                "outs": outs}

    extras = {"lower": lambda: paged.lower()}
    if warm:
        drive(paged, 8, mixed[:8])              # warm the whole path
        drive(slab, 8, mixed[:8])

        def best_of(engine, n_clients, reqs, n=2):
            runs = [drive(engine, n_clients, reqs) for _ in range(n)]
            return max(runs, key=lambda r: r["tokens_per_s"])

        pg = best_of(paged, 48, mixed)
        sl = best_of(slab, 48, mixed)
        if pg.pop("outs") != sl.pop("outs"):
            raise AssertionError("paged and slab greedy streams diverged")
        # shared-prefix leg: prefill-compute elimination via the
        # engine's prefilled-positions ledger (delta over the drive).
        # The leader request seats (and registers the prefix chains)
        # BEFORE the concurrent followers race the index.
        pre0 = paged.prefill_positions_total
        drive(paged, 1, shared[:1])
        ps = drive(paged, 8, shared[1:])
        ps.pop("outs")
        prefilled = paged.prefill_positions_total - pre0
        total_prompt = sum(p.size for p, _ in shared)
        hits = paged.metrics.snapshot()["prefix_cache_hits_total"]
        extras.update(
            paged_tokens_per_s=pg["tokens_per_s"],
            slab_tokens_per_s=sl["tokens_per_s"],
            paged_ttft_p99_ms=pg["ttft_p99_ms"],
            slab_ttft_p99_ms=sl["ttft_p99_ms"],
            paged_effective_streams=pg["effective_streams"],
            slab_effective_streams=sl["effective_streams"],
            effective_stream_gain=round(pg["effective_streams"]
                                        / sl["effective_streams"], 2),
            pool_exhausted_evictions=pg["pool_exhausted"],
            kv_budget_positions=budget_positions,
            shared_prefix_tokens_per_s=ps["tokens_per_s"],
            shared_prefix_hits=hits,
            prefill_positions=prefilled,
            prompt_positions=total_prompt,
            prefill_elimination=round(1.0 - prefilled / total_prompt, 4))

    def run(s):
        r = drive(paged, 48, mixed)
        return np.float32(r["tokens_per_s"])

    # decode compute of one mixed burst at ideal paged occupancy: every
    # step runs the whole [4*slots]-row gather step
    total_tokens = sum(mt for _, mt in mixed)
    per_tok = layers * (6 * d_model ** 2 + 2 * d_model * dff) \
        + d_model * vocab
    attn = layers * 4.0 * d_model * max_len * max_len / 2
    flops = (2.0 * per_tok + attn / max_len) * 4 * slots \
        * (total_tokens / (4 * slots))
    return run, flops, None, (
        f"paged KV serving ms/burst ({n_requests} reqs, 48 clients, "
        f"{4 * slots} paged slots vs {slots} slab slots at "
        f"{budget_positions} KV positions, block {block_size})"), extras


def bench_serving_decode_fused(slots=16, vocab=256, d_model=128, dff=256,
                               layers=3, heads=2, block_size=8,
                               max_len=64, seed=0):
    """Fused Pallas decode-attention kernels (ops/pallas/
    decode_attention.py) vs the reference XLA step — the per-token
    serving hot path A/B'd at the step level, slab AND paged layouts,
    16/64 slots, at the serving_paged model scale (d=128, 3 layers,
    block 8, max_len 64).

    The analytic leg is the headline: extras["lower"] is the FUSED
    paged step at the serving_paged slot scale, and extras["postcheck"]
    (run by perf/analytic.capture) asserts the fusion PROOF — the
    compiled fused HLO holds no full-chain [S, T, Dkv] gather buffer
    (perf.analytic.assert_decode_fused), the reference step FAILS the
    same gate, and the fused step's XLA-model bytes land strictly below
    the reference step's — recording the before/after bytes in the
    snapshot row before any chip time.  The timed leg runs one decode
    step per layout/mode at 16/64 slots (CPU runs the kernels in
    interpret mode; the real speed verdict needs a chip window, the
    bytes verdict does not)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import transformer
    from paddle_tpu.ops.pallas import decode_attention as decode_kernels
    from paddle_tpu.perf import analytic as perf_analytic
    from paddle_tpu.perf import cost as perf_cost

    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=0,
                              max_len=max_len, num_heads=heads)
    dkv = int(params["enc"][0]["attn"]["wk"].shape[1])
    nb_row = -(-max_len // block_size)
    rng = np.random.RandomState(seed)

    def step_inputs(s, layout):
        tokens = rng.randint(1, vocab, s).astype(np.int32)
        pos = rng.randint(1, max_len - 1, s).astype(np.int32)
        if layout == "slab":
            cache = transformer.init_lm_cache(params, s, max_len)
            return cache, tokens, pos, None
        num_blocks = s * nb_row + 1
        cache = transformer.init_lm_cache_paged(params, num_blocks,
                                                block_size,
                                                max_len=max_len)
        # each row owns a private chain covering its position (block 0
        # stays the reserved scratch block, exactly like the engine)
        from paddle_tpu.testing.kernel_smoke import build_private_tables
        tables = build_private_tables(pos, nb_row, block_size,
                                      num_blocks)
        return cache, tokens, pos, tables

    def staged(s, layout, mode):
        """jax.stages.Lowered of one decode step under one kernel mode
        (fresh jit per mode — the dispatch is read at trace time)."""
        cache, tokens, pos, tables = step_inputs(s, layout)
        with decode_kernels.forced_mode(mode):
            if layout == "slab":
                def fn(p, c, tok, po):
                    logits, c = transformer.lm_decode_step_slots(
                        p, tok, po, c, heads)
                    return jnp.argmax(logits, axis=-1), c
                return jax.jit(fn).lower(params, cache, tokens, pos), \
                    (params, cache, tokens, pos)
            def fn(p, c, tok, po, tbl):
                logits, c = transformer.lm_decode_step_paged(
                    p, tok, po, c, tbl, heads)
                return jnp.argmax(logits, axis=-1), c
            return jax.jit(fn).lower(params, cache, tokens, pos,
                                     tables), \
                (params, cache, tokens, pos, tables)

    paged_scale = 4 * 8     # the serving_paged family's paged slot count

    def attn_region_bytes(s, layout):
        """XLA-model bytes of ONE layer's reference attention region —
        a real, standalone XLA program (chain gather / slab stripe +
        the masked attend), so its cost numbers carry no interpreter
        artifacts."""
        rng2 = np.random.RandomState(1)
        q = jnp.asarray(rng2.randn(s, d_model), jnp.float32)
        cache, _tok, pos, tables = step_inputs(s, layout)
        kl, vl = cache[0]["k"], cache[0]["v"]
        t_span = nb_row * block_size if layout == "paged" else max_len

        if layout == "paged":
            def attn(q, kp, vp, po, tbl):
                k_rows = kp[tbl].reshape(s, -1, dkv)
                v_rows = vp[tbl].reshape(s, -1, dkv)
                pm = jnp.arange(t_span)[None, :] <= po[:, None]
                return transformer._attend(q[:, None], k_rows, v_rows,
                                           heads, pm)
            lowered = jax.jit(attn).lower(q, kl, vl, pos, tables)
        else:
            def attn(q, kc, vc, po):
                pm = jnp.arange(t_span)[None, :] <= po[:, None]
                return transformer._attend(q[:, None], kc, vc, heads, pm)
            lowered = jax.jit(attn).lower(q, kl, vl, pos)
        return perf_cost.extract(lowered.compile())["bytes_accessed"]

    def kernel_bytes(s, layout):
        t_span = nb_row * block_size if layout == "paged" else max_len
        est = decode_kernels.kernel_cost(s, t_span, d_model, dkv)
        return float(est.bytes_accessed)

    def bytes_ab(s, layout, ref_compiled=None):
        """Fused-vs-reference predicted step bytes at one (slots,
        layout) point.  The reference side is MEASURED (XLA cost model
        of the real reference step).  The fused side composes measured
        + declared: reference step minus its per-layer attention region
        (measured standalone) plus the kernel's ``pl.CostEstimate``
        traffic per layer — exactly what the TPU cost model reports for
        the Mosaic custom call (a CPU backend cannot compile Mosaic,
        and the interpret-mode emulation's loop bookkeeping would
        libel the kernel)."""
        if ref_compiled is None:
            ref_compiled = staged(s, layout, "off")[0].compile()
        ref_bytes = perf_cost.extract(ref_compiled)["bytes_accessed"]
        attn_bytes = attn_region_bytes(s, layout)
        kern_bytes = kernel_bytes(s, layout)
        fused = ref_bytes - layers * attn_bytes + layers * kern_bytes
        return {"reference_bytes": ref_bytes,
                "reference_attn_bytes_per_layer": attn_bytes,
                "kernel_bytes_per_layer": kern_bytes,
                "fused_bytes_predicted": fused,
                "bytes_saved_frac": round(1 - fused / ref_bytes, 4)}

    def postcheck(compiled):
        """The fusion-proof gate (perf/analytic.capture runs this on the
        fused lowered step): prove the chain gather's ABSENCE on the
        fused HLO, prove the same gate CATCHES the reference step, and
        record the fused-vs-reference bytes verdict at the
        serving_paged scale."""
        t_span = nb_row * block_size
        perf_analytic.assert_decode_fused(compiled.as_text(),
                                          paged_scale, t_span, dkv)
        ref_compiled = staged(paged_scale, "paged", "off")[0].compile()
        ref_hits = perf_analytic.chain_buffer_instrs(
            ref_compiled.as_text(), paged_scale, t_span, dkv)
        if not ref_hits:
            raise AssertionError(
                "fusion-proof gate failed to flag the reference "
                "chain-gather step — the detector is broken")
        ab = bytes_ab(paged_scale, "paged", ref_compiled=ref_compiled)
        if not ab["fused_bytes_predicted"] < ab["reference_bytes"]:
            raise AssertionError(
                f"fused paged step bytes "
                f"{ab['fused_bytes_predicted']:.3g} not below the "
                f"reference step's {ab['reference_bytes']:.3g}")
        ab.update(fusion_proof="pass",
                  reference_chain_gather_instrs=len(ref_hits))
        return ab

    extras = {"lower": lambda: staged(paged_scale, "paged", "always")[0],
              "postcheck": postcheck}
    if os.environ.get("BENCH_ANALYTIC_BUILD") != "1":
        # fused-vs-reference bytes matrix for docs/perf.md: 16/64
        # slots x slab/paged (no execution — lower + cost model only)
        extras["bytes_matrix"] = {
            f"{layout}@{s}": bytes_ab(s, layout)
            for s in (16, 64) for layout in ("slab", "paged")}

    def run(_s):
        """Wall-clock of one fused decode step at `slots` (paged) —
        interpret-mode on CPU, the real kernel through Mosaic on TPU."""
        lowered, args = staged(slots, "paged", "always")
        compiled = lowered.compile()
        jax.block_until_ready(compiled(*args))          # warm execute
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(*args))
        return np.float32((time.perf_counter() - t0) * 1e3)

    per_tok = layers * (6 * d_model ** 2 + 2 * d_model * dff) \
        + d_model * vocab
    attn = layers * 4.0 * d_model * max_len / 2
    flops = (2.0 * per_tok + attn) * slots
    return run, flops, None, (
        f"fused decode step ms ({slots} paged slots, block "
        f"{block_size}, d={d_model}, {layers} layers; analytic "
        f"fused-vs-reference bytes at 16/64 slots both layouts)"), extras


def bench_serving_chunked_prefill(slots=8, n_requests=36, vocab=256,
                                  d_model=128, dff=256, layers=3, heads=2,
                                  chunk=8, long_prompt=64, seed=0):
    """Unified chunked-prefill serving (decode_engine.py prefill_chunk)
    vs the legacy per-bucket prefill ladder, under MIXED long-prompt /
    decode traffic: a steady population of short-prompt decode streams
    plus periodic 64-token-prompt admissions.  The ladder runs each
    admission's prefill as one monolithic batched pass BETWEEN steps —
    every in-flight stream stalls for it (the TTFT/TPOT spikes in the
    PR-9 slot-lifetime traces); the unified engine feeds the same
    prompt as K-token chunks INSIDE the shared step, bounding per-step
    work.  Reported per mode: useful tokens/s, long-admission TTFT p99,
    the recent-window TPOT p99/p50 jitter ratio, and the worst decode
    stream's max/median inter-token gap (the stall, seen from one
    stream).

    The analytic leg is the acceptance bar: extras["lower"] is THE one
    unified chunked step (Tq=chunk kernels forced on) and
    extras["postcheck"] proves BOTH score matrices dead — no [K, T]
    buffer in the unified step's HLO, no [Tp, Tp] buffer in the
    flash-routed legacy prefill — with each detector also shown to fire
    on its reference twin (perf/analytic.score_matrix_instrs)."""
    import importlib

    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import transformer
    from paddle_tpu.ops.pallas import decode_attention as decode_kernels
    from paddle_tpu.perf import analytic as perf_analytic
    from paddle_tpu.serving import GenerationBatcher, ServingMetrics
    from paddle_tpu.serving.decode_engine import DecodeEngine

    flash_mod = importlib.import_module(
        "paddle_tpu.ops.pallas.flash_attention")
    max_len = long_prompt + 32
    buckets = (8, long_prompt)      # the twin's ladder covers the long
    #                                 prompts the unified engine chunks
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=0,
                              max_len=max_len, num_heads=heads)
    warm = os.environ.get("BENCH_ANALYTIC_BUILD") != "1"

    def make_engine(mode):
        return DecodeEngine(params, num_heads=heads, num_slots=slots,
                            max_len=max_len, prefill_buckets=buckets,
                            name=f"bench_cp_{mode}", warm=warm,
                            prefill_chunk=chunk if mode == "chunked"
                            else 0)

    rng = np.random.RandomState(seed)
    # the serving-shaped mix: 3 steady decode streams per 1 long-prompt
    # admission (short prompt + long emission vs long prompt + short
    # emission — the exact shape where the ladder's monolithic prefill
    # spikes every in-flight stream's TPOT)
    reqs = []
    for i in range(n_requests):
        if i % 4 == 3:
            reqs.append(("long",
                         rng.randint(1, vocab, long_prompt
                                     ).astype(np.int32), 4))
        else:
            reqs.append(("decode",
                         rng.randint(1, vocab, rng.randint(4, 9)
                                     ).astype(np.int32), 24))

    def drive(mode, n_clients=6):
        engine = make_engine(mode)
        engine.metrics = ServingMetrics()
        bat = GenerationBatcher(engine, queue_size=4096)
        lock, nxt, tokens = threading.Lock(), [0], [0]
        ttft_long, gaps_by_req = [], []

        def client():
            while True:
                with lock:
                    i = nxt[0]
                    if i >= len(reqs):
                        return
                    nxt[0] += 1
                klass, prompt, mt = reqs[i]
                times = []
                out = bat.submit(prompt, max_tokens=mt,
                                 on_token=lambda _t:
                                 times.append(time.perf_counter())
                                 ).result(300)
                with lock:
                    tokens[0] += len(out["tokens"])
                    if klass == "long":
                        ttft_long.append(out["ttft_ms"])
                    elif len(times) >= 8:
                        g = np.diff(np.asarray(times))
                        gaps_by_req.append(
                            float(np.max(g) / max(np.median(g), 1e-9)))

        ts = [threading.Thread(target=client) for _ in range(n_clients)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        snap = engine.metrics.snapshot()
        bat.close()
        ttft_long.sort()
        return {
            "mode": mode,
            "tokens_per_s": round(tokens[0] / dt, 1),
            "ttft_long_p99_ms": round(
                ttft_long[min(len(ttft_long) - 1,
                              int(len(ttft_long) * 0.99))], 2)
            if ttft_long else None,
            "tpot_jitter_p99_p50": snap["tpot_jitter_p99_p50"],
            "worst_stream_stall_ratio": round(max(gaps_by_req), 2)
            if gaps_by_req else None,
            "prefill_chunks_total": snap["prefill_chunks_total"],
            "mean_prefill_chunk_occupancy":
                snap["mean_prefill_chunk_occupancy"],
        }

    def lower_unified():
        engine = make_engine("chunked")
        with decode_kernels.forced_mode("always"):
            return engine.lower()

    def postcheck(compiled):
        """No serving path materializes a score matrix (the analytic
        acceptance gate): the unified step's HLO holds no [K, T] score
        buffer (chunk kernels on; the reference step must trip the same
        detector), and the legacy prefill routed through flash holds no
        [Tp, Tp] buffer (the masked reference must trip it too)."""
        hits = perf_analytic.score_matrix_instrs(compiled.as_text(),
                                                 chunk, max_len)
        if hits:
            raise AssertionError(
                f"unified chunked step materializes a [{chunk}, "
                f"{max_len}] score matrix — the Tq=chunk kernel did "
                "not engage:\n  " + "\n  ".join(hits[:4]))
        with decode_kernels.forced_mode("off"):
            ref_hlo = make_engine("chunked").lower().compile().as_text()
        if not perf_analytic.score_matrix_instrs(ref_hlo, chunk,
                                                 max_len):
            raise AssertionError(
                "score-matrix gate failed to flag the reference "
                "chunked step — the detector is broken")
        # legacy prefill half: Tp large enough that flash really blocks
        # (a single-block run would legitimately hold a [Tp, Tp] tile)
        tp = 640
        pf_params = transformer.init(
            jax.random.PRNGKey(1), src_vocab=vocab, trg_vocab=1,
            d_model=64, dff=64, enc_layers=1, dec_layers=0,
            max_len=tp, num_heads=1)

        spec = jax.ShapeDtypeStruct((1, tp), jnp.int32)

        def lower_prefill():
            # a FRESH closure per mode: the flash routing is read at
            # trace time, and jax caches traces on the function object
            # — reusing one closure would hand mode B mode A's trace
            def prefill_fn(prompt):
                return transformer.lm_prefill(pf_params, prompt, tp, 1)
            return jax.jit(prefill_fn).lower(spec).compile().as_text()

        with flash_mod.forced_prefill_mode("always"):
            flash_hlo = lower_prefill()
        perf_analytic.assert_prefill_flash(flash_hlo, tp)
        with flash_mod.forced_prefill_mode("off"):
            ref_pf_hlo = lower_prefill()
        if not perf_analytic.score_matrix_instrs(ref_pf_hlo, tp, tp):
            raise AssertionError(
                "prefill-flash gate failed to flag the masked XLA "
                "prefill — the detector is broken")
        return {"score_matrix_proof": "pass",
                "prefill_flash_proof": "pass",
                "prefill_flash_tp": tp}

    extras = {"lower": lower_unified, "postcheck": postcheck}
    if warm:
        chunked = drive("chunked")
        ladder = drive("ladder")
        extras.update(chunked=chunked, ladder=ladder,
                      ttft_long_p99_speedup=round(
                          (ladder["ttft_long_p99_ms"] or 0)
                          / max(chunked["ttft_long_p99_ms"] or 1e-9,
                                1e-9), 2),
                      jitter_ratio_ladder_over_chunked=round(
                          ladder["tpot_jitter_p99_p50"]
                          / max(chunked["tpot_jitter_p99_p50"], 1e-9),
                          2))

    def run(_s):
        return np.float32(drive("chunked")["tokens_per_s"])

    total_tokens = sum(mt for _k, _p, mt in reqs)
    prefill_tokens = sum(p.size for _k, p, _mt in reqs)
    per_tok = layers * (6 * d_model ** 2 + 2 * d_model * dff) \
        + d_model * vocab
    attn = layers * 4.0 * d_model * max_len / 2
    flops = (2.0 * per_tok + attn) * (total_tokens + prefill_tokens)
    return run, flops, None, (
        f"chunked-prefill serving tokens/s ({n_requests} reqs, 6 "
        f"clients, {slots} slots, chunk {chunk}, long prompts "
        f"{long_prompt}; unified step vs legacy ladder)"), extras


def bench_serving_kv_spill(slots=4, n_returns=4, vocab=256, d_model=128,
                           dff=256, layers=3, heads=2, block_size=8,
                           chunk=8, prefix_blocks=12, seed=0):
    """Hierarchical KV cache (serving/kv_pool.py HostTier +
    decode_engine kv_host_bytes; docs/serving.md "Hierarchical KV"):
    a long shared system prompt is registered, churn traffic forces
    the tiny paged pool to evict (and therefore SPILL) its chain, and
    the prompt keeps RETURNING.  With the tier on, each return visit
    restore-hits — the chain streams back over the host link and seats
    by reference, zero prefill chunk lanes — while the tier-less twin
    RECOMPUTES the whole prefix through chunked prefill every time.
    The warm drive measures the return-visit TTFT both ways (the
    measured half of the restore-vs-recompute story) and verifies
    every stream bit-identical between the two engines.

    The analytic leg is the acceptance bar: extras["lower"] is the one
    chunked paged step (the tier adds NO jitted code — spill gathers
    with NumPy, the restore lands through the already-warm block-write
    path) and extras["postcheck"] gates the routing model in BOTH
    directions — ``perf/analytic.predicted_restore_ms`` must beat
    ``predicted_recompute_ms`` for the long prefix and LOSE for a
    sub-chunk one, at the fleet chip spec and at this host's, with the
    live engine's router (``_restore_predicted_faster``) agreeing on
    both verdicts."""
    import jax
    from paddle_tpu.models import transformer
    from paddle_tpu.perf import analytic as perf_analytic
    from paddle_tpu.serving import GenerationBatcher, ServingMetrics
    from paddle_tpu.serving.decode_engine import DecodeEngine

    prefix_len = prefix_blocks * block_size         # 96: 12 full blocks
    max_len = prefix_len + 32
    # two slots' worth of blocks + 1: the shared chain cannot stay
    # resident once churn traffic claims seats
    num_blocks = 2 * (max_len // block_size) + 1
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=0,
                              max_len=max_len, num_heads=heads)
    warm = os.environ.get("BENCH_ANALYTIC_BUILD") != "1"

    def make_engine(host_bytes, name):
        return DecodeEngine(params, num_heads=heads, num_slots=slots,
                            max_len=max_len, prefill_buckets=(8, 16),
                            name=name, warm=warm, kv_layout="paged",
                            kv_block_size=block_size,
                            kv_num_blocks=num_blocks, prefill_chunk=chunk,
                            kv_host_bytes=host_bytes)

    rng = np.random.RandomState(seed)
    prefix = rng.randint(1, vocab, prefix_len).astype(np.int32)
    churn = [rng.randint(1, vocab, 56).astype(np.int32)
             for _ in range(4 * n_returns)]
    n_tok = 12

    def drive(host_bytes, name):
        engine = make_engine(host_bytes, name)
        engine.metrics = ServingMetrics()
        bat = GenerationBatcher(engine, queue_size=4096)
        t0 = time.perf_counter()
        lead = bat.submit(prefix, max_tokens=n_tok).result(300)
        ttfts, outs, tokens = [], [lead["tokens"]], len(lead["tokens"])
        for cycle in range(n_returns):
            # churn: 4 x 8-block admissions against the ~2-slot pool
            # evict the shared chain (tier on: spill; tier off: drop)
            for p in churn[4 * cycle:4 * cycle + 4]:
                tokens += len(bat.submit(p, max_tokens=8)
                              .result(300)["tokens"])
            out = bat.submit(prefix, max_tokens=n_tok).result(300)
            ttfts.append(out["ttft_ms"])
            outs.append(out["tokens"])
            tokens += len(out["tokens"])
        dt = time.perf_counter() - t0
        snap = engine.metrics.snapshot()
        bat.close()
        ttfts.sort()
        return {"ttft_return_p50_ms": round(ttfts[len(ttfts) // 2], 2),
                "ttft_return_max_ms": round(ttfts[-1], 2),
                "tokens_per_s": round(tokens / dt, 1),
                "restore_hits": snap["kv_restore_hits_total"],
                "spill_blocks": snap["kv_spill_blocks_total"],
                "restore_bytes": snap["kv_restore_bytes_total"],
                "kv_restore_ms": snap["kv_restore_ms"],
                "outs": outs}

    def lower():
        return make_engine(256 << 20, "bench_spill_aot").lower()

    def postcheck(_compiled):
        """The restore-vs-recompute router's model, gated in BOTH
        directions: the long registered prefix must be predicted
        cheaper to RESTORE (one host-link stream beats a dozen chunk
        steps), a sub-chunk prefix cheaper to RECOMPUTE (one cheap
        chunk step beats the restore's fixed scheduling cycles) — at
        the fleet chip spec AND this host's — and the live engine's
        router must return the same verdicts."""
        leaves = jax.tree_util.tree_leaves(params)
        pc = sum(l.size for l in leaves)
        pb = sum(l.size * l.dtype.itemsize for l in leaves)
        dkv = d_model // heads
        long_cov, short_cov = prefix_len, chunk // 2
        row = {}
        for chip in ("v5e", "cpu"):
            r_long = perf_analytic.predicted_restore_ms(
                long_cov, layers, dkv, heads, "float32", chip)
            c_long = perf_analytic.predicted_recompute_ms(
                long_cov, pc, pb, chunk, chip)
            if not r_long < c_long:
                raise AssertionError(
                    f"[{chip}] restore NOT predicted faster for the "
                    f"{long_cov}-position prefix: {r_long:.4f}ms vs "
                    f"recompute {c_long:.4f}ms")
            r_short = perf_analytic.predicted_restore_ms(
                short_cov, layers, dkv, heads, "float32", chip)
            c_short = perf_analytic.predicted_recompute_ms(
                short_cov, pc, pb, chunk, chip)
            if not c_short < r_short:
                raise AssertionError(
                    f"[{chip}] recompute NOT predicted faster for the "
                    f"{short_cov}-position prefix: {c_short:.4f}ms vs "
                    f"restore {r_short:.4f}ms")
            row[f"predicted_restore_long_ms_{chip}"] = round(r_long, 4)
            row[f"predicted_recompute_long_ms_{chip}"] = round(c_long, 4)
        engine = make_engine(256 << 20, "bench_spill_route")
        v_long = engine._restore_predicted_faster(long_cov)[0]
        v_short = engine._restore_predicted_faster(short_cov)[0]
        if not (v_long and not v_short):
            raise AssertionError(
                "the engine's restore router disagrees with the "
                f"analytic model: long->{v_long} short->{v_short} "
                "(want True/False)")
        return dict(row, restore_direction_proof="pass",
                    restore_route_agreement="pass")

    extras = {"lower": lower, "postcheck": postcheck}
    if warm:
        spill = drive(256 << 20, "bench_spill_tier")
        cold = drive(0, "bench_spill_twin")
        if spill.pop("outs") != cold.pop("outs"):
            raise AssertionError(
                "restored and recomputed greedy streams diverged")
        if spill["restore_hits"] < 1:
            raise AssertionError(
                "the spill drive never restore-hit — churn failed to "
                "evict the shared chain")
        extras.update(
            spill=spill, recompute=cold,
            ttft_return_speedup=round(
                cold["ttft_return_p50_ms"]
                / max(spill["ttft_return_p50_ms"], 1e-9), 2))

    def run(_s):
        return np.float32(drive(256 << 20, "bench_spill_timed")
                          ["tokens_per_s"])

    total_tokens = (n_returns + 1) * n_tok + 4 * n_returns * 8
    prompt_tokens = (n_returns + 1) * prefix_len + 4 * n_returns * 56
    per_tok = layers * (6 * d_model ** 2 + 2 * d_model * dff) \
        + d_model * vocab
    attn = layers * 4.0 * d_model * max_len / 2
    flops = (2.0 * per_tok + attn) * (total_tokens + prompt_tokens)
    return run, flops, None, (
        f"hierarchical-KV serving return-visit TTFT ({n_returns} "
        f"evict+return cycles, {prefix_len}-token shared prefix, "
        f"{num_blocks}-block pool, block {block_size}, chunk {chunk}; "
        "host spill tier vs cold recompute)"), extras


def bench_serving_disagg(slots=4, n_handoffs=4, vocab=256, d_model=128,
                         dff=256, layers=3, heads=2, block_size=8,
                         chunk=8, prefix_blocks=12, seed=0):
    """Disaggregated prefill/decode serving (serving/transfer.py;
    docs/serving.md "Disaggregated serving"): a prefill replica behind
    a REAL socket (`make_server` + ``POST /v1/kv/export``) prefills a
    long prompt to its first token, then a decode replica fetches the
    resident chain over HTTP (``transfer.receive_chain``), parks it in
    its host tier and seats the continuation by reference through the
    EXISTING restore pipeline — zero prefill chunk lanes, zero new
    traces.  The warm drive measures the handed-off continuation TTFT
    against a twin replica that recomputes the same context through
    plain continuation-replay, and verifies every stream bit-identical
    between the two.

    The analytic leg is the acceptance bar: extras["lower"] is the one
    chunked paged step (the handoff adds NO jitted code — export
    gathers with NumPy between steps, the delivered blob lands through
    the already-warm block-write path) and extras["postcheck"] gates
    the routing model in BOTH directions —
    ``perf/analytic.predicted_handoff_ms`` must beat
    ``predicted_recompute_ms`` for the long handed-off prefix and LOSE
    for a single-chunk one, at the fleet chip spec and at this host's,
    with the live engine's router (``_handoff_predicted_faster``)
    agreeing on both verdicts."""
    import threading
    import jax
    from paddle_tpu.models import transformer
    from paddle_tpu.perf import analytic as perf_analytic
    from paddle_tpu.serving import GenerationBatcher, ServingMetrics
    from paddle_tpu.serving import transfer as kv_transfer
    from paddle_tpu.serving.decode_engine import DecodeEngine
    from paddle_tpu.serving.server import make_server

    prefix_len = prefix_blocks * block_size         # 96: 12 full blocks
    max_len = prefix_len + 32
    num_blocks = slots * (max_len // block_size) + 1
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=0,
                              max_len=max_len, num_heads=heads)
    warm = os.environ.get("BENCH_ANALYTIC_BUILD") != "1"

    def make_engine(name):
        return DecodeEngine(params, num_heads=heads, num_slots=slots,
                            max_len=max_len, prefill_buckets=(8, 16),
                            name=name, warm=warm, kv_layout="paged",
                            kv_block_size=block_size,
                            kv_num_blocks=num_blocks, prefill_chunk=chunk,
                            kv_host_bytes=256 << 20)

    rng = np.random.RandomState(seed)
    prompts = [rng.randint(1, vocab, prefix_len).astype(np.int32)
               for _ in range(n_handoffs)]
    n_tok = 12

    def drive(tag):
        # prefill replica behind a real ephemeral-port HTTP server;
        # decode replica receives over the socket; twin recomputes
        eng_p = make_engine(f"bench_disagg_prefill_{tag}")
        eng_p.metrics = ServingMetrics()
        bat_p = GenerationBatcher(eng_p, queue_size=4096)
        srv = make_server(None, gen_batcher=bat_p)
        threading.Thread(target=srv.serve_forever, daemon=True).start()
        src = f"http://127.0.0.1:{srv.port}"
        eng_d = make_engine(f"bench_disagg_decode_{tag}")
        eng_d.metrics = ServingMetrics()
        bat_d = GenerationBatcher(eng_d, queue_size=4096)
        eng_t = make_engine(f"bench_disagg_twin_{tag}")
        eng_t.metrics = ServingMetrics()
        bat_t = GenerationBatcher(eng_t, queue_size=4096)
        try:
            ttft_hand, ttft_reco, tokens = [], [], 0
            t_start = time.perf_counter()
            for p in prompts:
                # prefill leg: one greedy token = the handoff boundary
                lead = bat_p.submit(p, max_tokens=1).result(300)
                boundary = lead["tokens"]
                ctx = [int(t) for t in p] + boundary
                hand = kv_transfer.receive_chain(
                    eng_d, src, ctx, metrics=eng_d.metrics)
                if hand["outcome"] != "received" or hand["bytes"] <= 0:
                    raise AssertionError(
                        f"socket handoff did not land: {hand}")
                out_h = bat_d.submit(p, max_tokens=n_tok - 1,
                                     replay=boundary).result(300)
                out_r = bat_t.submit(p, max_tokens=n_tok - 1,
                                     replay=boundary).result(300)
                if out_h["tokens"] != out_r["tokens"]:
                    raise AssertionError(
                        "handed-off and recomputed greedy streams "
                        "diverged")
                ttft_hand.append(out_h["ttft_ms"])
                ttft_reco.append(out_r["ttft_ms"])
                tokens += 1 + 2 * len(out_h["tokens"])
            dt = time.perf_counter() - t_start
            snap_p = eng_p.metrics.snapshot()
            snap_d = eng_d.metrics.snapshot()
            if snap_p["kv_handoffs_total"]["sent"] < n_handoffs:
                raise AssertionError(
                    "the prefill replica's sent counter is short: "
                    f"{snap_p['kv_handoffs_total']}")
            if snap_d["kv_handoffs_total"]["received"] < n_handoffs:
                raise AssertionError(
                    "the decode replica's received counter is short: "
                    f"{snap_d['kv_handoffs_total']}")
            if snap_d["kv_restore_hits_total"] < n_handoffs:
                raise AssertionError(
                    "handed-off chains did not seat through the "
                    "restore pipeline: "
                    f"{snap_d['kv_restore_hits_total']} hits")
            ttft_hand.sort()
            ttft_reco.sort()
            return {
                "ttft_handoff_p50_ms":
                    round(ttft_hand[len(ttft_hand) // 2], 2),
                "ttft_recompute_p50_ms":
                    round(ttft_reco[len(ttft_reco) // 2], 2),
                "handoffs_sent": snap_p["kv_handoffs_total"]["sent"],
                "handoffs_received":
                    snap_d["kv_handoffs_total"]["received"],
                "handoff_bytes": snap_d["kv_handoff_bytes_total"],
                "kv_handoff_ms": snap_d["kv_handoff_ms"],
                "tokens_per_s": round(tokens / dt, 1)}
        finally:
            srv.shutdown()
            srv.server_close()
            bat_p.close()
            bat_d.close()
            bat_t.close()

    def lower():
        return make_engine("bench_disagg_aot").lower()

    def postcheck(_compiled):
        """The handoff-vs-recompute router's model, gated in BOTH
        directions: the long prefill-side prefix must be predicted
        cheaper to HAND OFF (one socket stream + one host-link seat
        beats a dozen chunk steps), a single-chunk prefix cheaper to
        RECOMPUTE (one cheap chunk step beats the transfer's fixed
        scheduling cycles) — at the fleet chip spec AND this host's —
        and the live engine's router must return the same verdicts."""
        leaves = jax.tree_util.tree_leaves(params)
        pc = sum(l.size for l in leaves)
        pb = sum(l.size * l.dtype.itemsize for l in leaves)
        dkv = d_model // heads
        long_cov, short_cov = prefix_len, chunk
        row = {}
        for chip in ("v5e", "cpu"):
            h_long = perf_analytic.predicted_handoff_ms(
                long_cov, layers, dkv, heads, "float32", chip)
            c_long = perf_analytic.predicted_recompute_ms(
                long_cov, pc, pb, chunk, chip)
            if not h_long < c_long:
                raise AssertionError(
                    f"[{chip}] handoff NOT predicted faster for the "
                    f"{long_cov}-position prefix: {h_long:.4f}ms vs "
                    f"recompute {c_long:.4f}ms")
            h_short = perf_analytic.predicted_handoff_ms(
                short_cov, layers, dkv, heads, "float32", chip)
            c_short = perf_analytic.predicted_recompute_ms(
                short_cov, pc, pb, chunk, chip)
            if not c_short < h_short:
                raise AssertionError(
                    f"[{chip}] recompute NOT predicted faster for the "
                    f"{short_cov}-position prefix: {c_short:.4f}ms vs "
                    f"handoff {h_short:.4f}ms")
            row[f"predicted_handoff_long_ms_{chip}"] = round(h_long, 4)
            row[f"predicted_recompute_long_ms_{chip}"] = round(c_long, 4)
        engine = make_engine("bench_disagg_route")
        v_long = engine._handoff_predicted_faster(long_cov)[0]
        v_short = engine._handoff_predicted_faster(short_cov)[0]
        if not (v_long and not v_short):
            raise AssertionError(
                "the engine's handoff router disagrees with the "
                f"analytic model: long->{v_long} short->{v_short} "
                "(want True/False)")
        return dict(row, handoff_direction_proof="pass",
                    handoff_route_agreement="pass")

    extras = {"lower": lower, "postcheck": postcheck}
    if warm:
        d = drive("warm")
        extras.update(
            disagg=d,
            ttft_handoff_speedup=round(
                d["ttft_recompute_p50_ms"]
                / max(d["ttft_handoff_p50_ms"], 1e-9), 2))

    def run(_s):
        return np.float32(drive("timed")["tokens_per_s"])

    total_tokens = n_handoffs * (1 + 2 * (n_tok - 1))
    prompt_tokens = n_handoffs * 3 * prefix_len
    per_tok = layers * (6 * d_model ** 2 + 2 * d_model * dff) \
        + d_model * vocab
    attn = layers * 4.0 * d_model * max_len / 2
    flops = (2.0 * per_tok + attn) * (total_tokens + prompt_tokens)
    return run, flops, None, (
        f"disaggregated prefill->decode serving ({n_handoffs} real "
        f"socket KV handoffs, {prefix_len}-token prefix, block "
        f"{block_size}, chunk {chunk}; handed-off seat vs "
        "continuation-replay recompute)"), extras


def bench_serving_quant(slots=8, n_requests=48, vocab=256, d_model=128,
                        dff=256, layers=3, heads=2, block_size=8, seed=0):
    """Quantized serving (paddle_tpu/quant/; docs/serving.md "Quantized
    serving"): fp32 vs int8-KV vs int8-KV+int8-weights at a FIXED
    KV-BYTE budget.  The fp32 paged engine gets ``slots * ceil(max_len
    / block_size)`` blocks; the int8 engines get DOUBLE the block count
    — and 2x the slot count — inside the same bytes (an int8 block plus
    its f32 per-head scale sidecar costs (1/4 + 1/head_dim) of the f32
    block; serving/kv_pool.slab_equivalent_blocks).  Closed-loop
    mixed-length traffic at 48 clients reports per variant: useful
    tokens/s, p99 TTFT, effective streams (mean active slots/step), and
    the quality evidence — every int8 stream inside the COMMITTED
    quality budget vs the fp32 engine's stream for the same request
    (quant/kv.py GREEDY_PREFIX_MIN_FULL; exact-match counts recorded),
    and the full-quant engine TOKEN-EXACT against the quantized
    ``lm_generate`` oracle on a probe set (greedy determinism inside
    one quantization mode).

    The analytic leg is the acceptance bar (perf/analytic.capture runs
    extras["postcheck"] on extras["lower"] — the int8-KV+weights paged
    step with the fused kernels forced): (a) every quantized weight
    enters the compiled step as an s8 parameter and no float parameter
    of that shape exists (assert_weights_quantized — the fp32 twin must
    FAIL the same gate), (b) no widened-KV [S, T, Dkv] float buffer in
    the kernel-forced HLO (assert_kv_quantized — the kernels-off int8
    reference must TRIP the same detector: it dequantizes the gathered
    stripe), and (c) predicted decode-step bytes
    (perf/analytic.predicted_decode_step_bytes — first-principles: the
    XLA-CPU cost model materializes the dequant converts the TPU fuses,
    so like serving_decode_fused the prediction composes declared
    traffic) shrink >= 35% for int8-KV+weights vs fp32."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import transformer
    from paddle_tpu.ops.pallas import decode_attention as decode_kernels
    from paddle_tpu.perf import analytic as perf_analytic
    from paddle_tpu.quant import kv as quant_kv
    from paddle_tpu.quant import weights as quant_weights
    from paddle_tpu.serving import GenerationBatcher, ServingMetrics
    from paddle_tpu.serving.decode_engine import DecodeEngine

    prefill_buckets = (8, 16)
    gen_short, gen_long = 6, 48
    max_len = prefill_buckets[-1] + gen_long
    nb_row = -(-max_len // block_size)
    budget_blocks = slots * nb_row          # the fixed f32 byte budget
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=0,
                              max_len=max_len, num_heads=heads)
    qparams = quant_weights.quantize_lm(params)
    dkv = int(quant_weights.weight_shape(
        params["enc"][0]["attn"]["wk"])[1])
    warm = os.environ.get("BENCH_ANALYTIC_BUILD") != "1"

    def make_engine(name, p, kv_dtype, n_slots, n_blocks):
        return DecodeEngine(
            p, num_heads=heads, num_slots=n_slots, max_len=max_len,
            prefill_buckets=prefill_buckets,
            prefill_batch_buckets=(1, 8), name=name, warm=warm,
            kv_layout="paged", kv_block_size=block_size,
            kv_num_blocks=n_blocks + 1, kv_dtype=kv_dtype)

    # fp32: the budget as-is.  int8: 2x blocks AND 2x slots in the SAME
    # bytes — concurrency bounded by blocks actually used
    f32 = make_engine("bench_q_f32", params, "float32", slots,
                      budget_blocks)
    i8 = make_engine("bench_q_i8kv", params, "int8", 2 * slots,
                     2 * budget_blocks)
    i8w = make_engine("bench_q_i8kv_w", qparams, "int8", 2 * slots,
                      2 * budget_blocks)
    rng = np.random.RandomState(seed)
    mixed = [(rng.randint(1, vocab, rng.randint(3, 9)).astype(np.int32),
              gen_long if i < slots // 2 else gen_short)
             for i in range(n_requests)]

    def drive(engine, n_clients, reqs):
        engine.metrics = ServingMetrics()
        bat = GenerationBatcher(engine, queue_size=4096)
        lock, nxt, tokens, ttfts = threading.Lock(), [0], [0], []
        outs = [None] * len(reqs)

        def client():
            while True:
                with lock:
                    i = nxt[0]
                    if i >= len(reqs):
                        return
                    nxt[0] += 1
                prompt, mt = reqs[i]
                out = bat.submit(prompt, max_tokens=mt).result(300)
                outs[i] = out["tokens"]
                with lock:
                    ttfts.append(out["ttft_ms"])
                    tokens[0] += len(out["tokens"])

        ts = [threading.Thread(target=client) for _ in range(n_clients)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        bat.close()
        ttfts.sort()
        snap = engine.metrics.snapshot()
        return {"tokens_per_s": round(tokens[0] / dt, 1),
                "ttft_p99_ms": round(ttfts[min(len(ttfts) - 1,
                                               int(len(ttfts) * 0.99))],
                                     2),
                "effective_streams": snap["mean_slot_occupancy"],
                "kv_blocks_total": snap["kv_blocks_total"],
                "outs": outs}

    # ---- analytic leg: the standalone quantized paged step ----------
    s_an = 4 * slots
    t_span = nb_row * block_size
    an_rng = np.random.RandomState(1)
    an_tokens = an_rng.randint(1, vocab, s_an).astype(np.int32)
    an_pos = an_rng.randint(1, max_len - 1, s_an).astype(np.int32)
    an_blocks = s_an * nb_row + 1
    from paddle_tpu.testing.kernel_smoke import build_private_tables
    an_tables = build_private_tables(an_pos, nb_row, block_size,
                                     an_blocks)

    def staged(p, kv_dtype, mode):
        cache = transformer.init_lm_cache_paged(
            p, an_blocks, block_size, max_len=max_len,
            kv_dtype=kv_dtype, num_heads=heads)
        with decode_kernels.forced_mode(mode):
            def fn(pp, c, tok, po, tbl):
                logits, c = transformer.lm_decode_step_paged(
                    pp, tok, po, c, tbl, heads)
                return jnp.argmax(logits, axis=-1), c
            return jax.jit(fn).lower(p, cache, an_tokens, an_pos,
                                     an_tables)

    def predicted_bytes():
        b_f32 = perf_analytic.predicted_decode_step_bytes(
            params, s_an, t_span, heads, "float32")
        b_i8kv = perf_analytic.predicted_decode_step_bytes(
            params, s_an, t_span, heads, "int8")
        b_full = perf_analytic.predicted_decode_step_bytes(
            qparams, s_an, t_span, heads, "int8")
        return {"predicted_step_bytes_f32": b_f32,
                "predicted_step_bytes_i8kv": b_i8kv,
                "predicted_step_bytes_i8kv_w": b_full,
                "predicted_bytes_reduction_i8kv":
                    round(1 - b_i8kv / b_f32, 4),
                "predicted_bytes_reduction_i8kv_w":
                    round(1 - b_full / b_f32, 4)}

    def postcheck(compiled):
        """The quantization structural gates + the bytes verdict (see
        the factory docstring) — every detector also proven to fire on
        its unquantized/unfused twin."""
        txt = compiled.as_text()
        shapes = quant_weights.quantized_weight_shapes(qparams)
        floats = quant_weights.float_leaf_shapes(qparams)
        perf_analytic.assert_weights_quantized(txt, shapes, floats)
        f32_hlo = staged(params, "float32", "off").compile().as_text()
        try:
            perf_analytic.assert_weights_quantized(f32_hlo, shapes,
                                                   floats)
        except AssertionError:
            pass
        else:
            raise AssertionError(
                "weights-quantized gate failed to flag the fp32 step — "
                "the detector is broken")
        perf_analytic.assert_kv_quantized(txt, s_an, t_span, dkv)
        ref_hlo = staged(qparams, "int8", "off").compile().as_text()
        if not perf_analytic.widened_kv_instrs(ref_hlo, s_an, t_span,
                                               dkv):
            raise AssertionError(
                "widened-KV gate failed to flag the kernels-off int8 "
                "reference step — the detector is broken")
        out = predicted_bytes()
        if out["predicted_bytes_reduction_i8kv_w"] < 0.35:
            raise AssertionError(
                f"int8-KV+weights predicted step bytes shrink only "
                f"{out['predicted_bytes_reduction_i8kv_w']:.1%} "
                "(< the 35% acceptance bar)")
        out.update(weights_quantized_proof="pass",
                   kv_quantized_proof="pass",
                   widened_kv_instrs_reference=len(
                       perf_analytic.widened_kv_instrs(
                           ref_hlo, s_an, t_span, dkv)))
        return out

    extras = {"lower": lambda: staged(qparams, "int8", "always"),
              "postcheck": postcheck}
    if warm:
        drive(i8, 8, mixed[:8])             # warm the whole path
        drive(f32, 8, mixed[:8])
        drive(i8w, 8, mixed[:8])
        fp = drive(f32, 48, mixed)
        qv = drive(i8, 48, mixed)
        qw_ = drive(i8w, 48, mixed)
        ref_outs = fp.pop("outs")
        bar = quant_kv.GREEDY_PREFIX_MIN_FULL

        def quality(outs, p):
            """Served-stream quality vs the fp32 engine: exact-match
            and prefix>=bar counts (informational — a random-init trunk
            babbles with near-tied logits, so single-token argmax flips
            are expected), plus the COMMITTED budget check: teacher-
            force every served stream through both parameterizations
            and bound the max |logit error| (LOGIT_ERR_BUDGET) — tie-
            insensitive, so it must hold for EVERY driven stream."""
            within = exact = 0
            ctxs = np.zeros((len(outs), max_len), np.int32)
            lens = np.zeros((len(outs),), np.int32)
            for i, ((prompt, _mt), got, want) in enumerate(
                    zip(mixed, outs, ref_outs)):
                n = quant_kv.greedy_prefix_len(got, want)
                within += int(n >= min(bar, len(want)))
                exact += int(got == want)
                ctx = np.concatenate([prompt,
                                      np.asarray(got, np.int32)])
                ctxs[i, :ctx.size] = ctx
                lens[i] = ctx.size
            h32, _ = transformer.lm_prefill(params, ctxs, max_len,
                                            heads)
            l32 = transformer._lm_project(params, h32)
            h8, _ = transformer.lm_prefill(p, ctxs, max_len, heads,
                                           kv_dtype="int8")
            l8 = transformer._lm_project(p, h8)
            per_stream = quant_kv.logit_err(l32, l8, lens)
            in_budget = int((per_stream
                             <= quant_kv.LOGIT_ERR_BUDGET).sum())
            return within, exact, in_budget, float(per_stream.max())

        i8_within, i8_exact, i8_budget, i8_err = quality(
            qv.pop("outs"), params)
        w_within, w_exact, w_budget, w_err = quality(
            qw_.pop("outs"), qparams)
        # full-quant determinism probe: the engine must reproduce the
        # quantized lm_generate oracle token for token
        oracle_exact = 0
        probes = mixed[:4]
        bat = GenerationBatcher(i8w, queue_size=64)
        for prompt, mt in probes:
            got = bat.submit(prompt, max_tokens=mt).result(300)["tokens"]
            ids = np.asarray(transformer.lm_generate(
                qparams, prompt[None], prompt.size + mt, heads,
                kv_dtype="int8"))[0, prompt.size:]
            oracle_exact += int(got == [int(t) for t in ids])
        bat.close()
        extras.update(
            f32=fp, i8kv=qv, i8kv_w=qw_,
            kv_budget_blocks=budget_blocks,
            kv_blocks_doubled=qv["kv_blocks_total"]
            == 2 * fp["kv_blocks_total"],
            i8kv_streams_in_logit_budget=i8_budget,
            i8kv_max_logit_err=round(i8_err, 4),
            i8kv_prefix_ge_bar=i8_within,
            i8kv_exact=i8_exact,
            i8kv_w_streams_in_logit_budget=w_budget,
            i8kv_w_max_logit_err=round(w_err, 4),
            i8kv_w_prefix_ge_bar=w_within,
            i8kv_w_exact=w_exact,
            logit_err_budget=quant_kv.LOGIT_ERR_BUDGET,
            quality_prefix_bar=bar,
            full_quant_oracle_exact=f"{oracle_exact}/{len(probes)}",
            n_streams=len(ref_outs),
            **predicted_bytes())

    def run(_s):
        r = drive(i8w, 48, mixed)
        return np.float32(r["tokens_per_s"])

    total_tokens = sum(mt for _, mt in mixed)
    per_tok = layers * (6 * d_model ** 2 + 2 * d_model * dff) \
        + d_model * vocab
    attn = layers * 4.0 * d_model * max_len * max_len / 2
    flops = (2.0 * per_tok + attn / max_len) * total_tokens
    return run, flops, None, (
        f"quantized serving ms/burst ({n_requests} reqs, 48 clients, "
        f"fp32 {slots} slots vs int8 {2 * slots} slots at "
        f"{budget_blocks} f32-budget blocks, block {block_size})"), \
        extras


def bench_serving_quant_prefill(batch=8, tp=64, vocab=256, d_model=128,
                                dff=256, layers=3, heads=2, seed=0):
    """Int8 flash prefill (ops/pallas/flash_attention.flash_attention_
    quant; docs/serving.md "Quantized serving"): the batched causal
    prefill over an int8 cache + int8 weights, streaming the int8 K/V
    bytes and their per-(position, head) scale sidecars straight into
    the kernel, vs the reference path that widens each layer's whole
    just-quantized K/V set back to f32 before attending.

    The analytic leg is the acceptance bar (capture runs
    extras["postcheck"] on extras["lower"] — the int8-weights int8-KV
    ``lm_prefill`` with the quant kernel forced ON): (a) NO f32
    [b, tp, dkv]-element widen-the-cache convert exists in the
    kernel-forced HLO (assert_prefill_kv_quantized) while the
    kernels-off twin must TRIP the same detector — it dequantizes every
    layer's full set; (b) every quantized weight still enters as an s8
    parameter (assert_weights_quantized, fp32 twin must FAIL); and (c)
    predicted prefill bytes (perf/analytic.predicted_prefill_bytes —
    first-principles, the XLA-CPU cost model materializes the converts
    the TPU kernel keeps in registers) shrink >= 35% for int8 vs the
    fp32 prefill.  The quality leg bounds the max |logit error| of the
    quantized prefill vs the fp32 twin on mixed-length prompts under
    the COMMITTED budget (quant/kv.logit_err + LOGIT_ERR_BUDGET)."""
    import importlib
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models import transformer
    # the package re-exports the flash_attention FUNCTION, shadowing the
    # submodule — import the module itself for the mode controls
    flash = importlib.import_module(
        "paddle_tpu.ops.pallas.flash_attention")
    from paddle_tpu.perf import analytic as perf_analytic
    from paddle_tpu.quant import kv as quant_kv
    from paddle_tpu.quant import weights as quant_weights

    b = batch
    max_len = 2 * tp
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=0,
                              max_len=max_len, num_heads=heads)
    qparams = quant_weights.quantize_lm(params)
    dkv = int(quant_weights.weight_shape(
        params["enc"][0]["attn"]["wk"])[1])
    rng = np.random.RandomState(seed)
    tokens = rng.randint(1, vocab, (b, tp)).astype(np.int32)
    lens = rng.randint(tp // 2, tp + 1, b).astype(np.int32)

    def staged(p, mode):
        with flash.forced_prefill_quant_mode(mode):
            def fn(pp, toks):
                h, cache = transformer.lm_prefill(pp, toks, max_len,
                                                  heads,
                                                  kv_dtype="int8")
                return h, cache
            return jax.jit(fn).lower(p, tokens)

    def predicted_bytes():
        b_f32 = perf_analytic.predicted_prefill_bytes(
            params, b, tp, heads, "float32")
        b_i8 = perf_analytic.predicted_prefill_bytes(
            qparams, b, tp, heads, "int8")
        return {"predicted_prefill_bytes_f32": b_f32,
                "predicted_prefill_bytes_i8": b_i8,
                "predicted_prefill_bytes_reduction":
                    round(1 - b_i8 / b_f32, 4)}

    def postcheck(compiled):
        """The prefill quantization gates (see the factory docstring) —
        every detector also proven to fire on its widening/fp32 twin."""
        txt = compiled.as_text()
        perf_analytic.assert_prefill_kv_quantized(txt, b, tp, dkv)
        shapes = quant_weights.quantized_weight_shapes(qparams)
        floats = quant_weights.float_leaf_shapes(qparams)
        perf_analytic.assert_weights_quantized(txt, shapes, floats)
        f32_hlo = staged(params, "off").compile().as_text()
        try:
            perf_analytic.assert_weights_quantized(f32_hlo, shapes,
                                                   floats)
        except AssertionError:
            pass
        else:
            raise AssertionError(
                "weights-quantized gate failed to flag the fp32 "
                "prefill — the detector is broken")
        ref_hlo = staged(qparams, "off").compile().as_text()
        ref_hits = perf_analytic.widened_prefill_kv_instrs(
            ref_hlo, b, tp, dkv)
        if not ref_hits:
            raise AssertionError(
                "widened-prefill gate failed to flag the kernel-off "
                "int8 reference prefill — the detector is broken")
        out = predicted_bytes()
        if out["predicted_prefill_bytes_reduction"] < 0.35:
            raise AssertionError(
                f"int8 predicted prefill bytes shrink only "
                f"{out['predicted_prefill_bytes_reduction']:.1%} "
                "(< the 35% acceptance bar)")
        out.update(prefill_kv_quantized_proof="pass",
                   weights_quantized_proof="pass",
                   widened_prefill_instrs_reference=len(ref_hits))
        return out

    extras = {"lower": lambda: staged(qparams, "always"),
              "postcheck": postcheck}

    def prefill(p, mode):
        with flash.forced_prefill_quant_mode(mode):
            h, _cache = jax.jit(lambda pp, t: transformer.lm_prefill(
                pp, t, max_len, heads, kv_dtype="int8"))(p, tokens)
        return h

    if os.environ.get("BENCH_ANALYTIC_BUILD") != "1":
        # quality: quantized prefill (int8 KV + weights + kernel) vs
        # the fp32 twin, max |logit err| per stream over the VALID
        # positions of mixed-length prompts — the committed budget
        h32, _ = transformer.lm_prefill(params, tokens, max_len, heads)
        l32 = transformer._lm_project(params, h32)
        lq = transformer._lm_project(qparams, prefill(qparams, "always"))
        per_stream = quant_kv.logit_err(l32, lq, lens)
        # kernel-vs-reference: the SAME int8 cache attended through the
        # quant kernel vs the widen-then-flash reference path
        lref = transformer._lm_project(qparams, prefill(qparams, "off"))
        kernel_err = float(quant_kv.logit_err(lref, lq, lens).max())
        if float(per_stream.max()) > quant_kv.LOGIT_ERR_BUDGET:
            raise AssertionError(
                f"quantized prefill logit error {per_stream.max():.4f} "
                f"exceeds the committed budget "
                f"{quant_kv.LOGIT_ERR_BUDGET}")
        extras.update(
            streams_in_logit_budget=int(
                (per_stream <= quant_kv.LOGIT_ERR_BUDGET).sum()),
            n_streams=b,
            max_logit_err=round(float(per_stream.max()), 4),
            kernel_vs_reference_max_err=round(kernel_err, 6),
            logit_err_budget=quant_kv.LOGIT_ERR_BUDGET,
            **predicted_bytes())

    fn = jax.jit(lambda pp, t: transformer.lm_prefill(
        pp, t, max_len, heads, kv_dtype="int8")[0])

    def run(_s):
        with flash.forced_prefill_quant_mode("always"):
            return fn(qparams, tokens)

    per_tok = layers * (6.0 * d_model ** 2 + 2.0 * d_model * dff)
    attn = layers * 4.0 * d_model * tp * tp / 2
    flops = (2.0 * per_tok * tp + attn) * b
    return run, flops, None, (
        f"int8 flash prefill ms/batch ({b} prompts x {tp} positions, "
        f"int8 KV + int8 weights, quant kernel forced)"), extras


def bench_trainer_int8(batch=64, dim=64, hidden=128, n_batches=24,
                       seed=0):
    """Int8 weight-streaming training (trainer/trainer.py
    ``SGD(quant_weights=True)``; docs/perf.md "Int8 weight-streaming
    trainer"): the jitted step is fed the {master f32, q int8+scale}
    bundle, dequantizes at the matmul boundary, applies grads to the
    f32 masters and requantizes in-step — so the int8 tree, not a
    widened f32 copy, is what persists across steps.

    The analytic leg is the acceptance bar (capture runs
    extras["postcheck"] on extras["lower"] — the quant-mode
    ``lower_step``): every quantized weight enters the compiled step as
    an s8 ENTRY parameter with the f32 float params limited to the
    step's own legitimate leaves (masters + optimizer state), and the
    plain-f32 twin step must FAIL the same gate.  The quality leg
    trains the int8 trainer and its f32 twin from identical inits on
    identical batches and bounds the max per-step relative loss gap
    under the COMMITTED budget (quant/weights.TRAIN_LOSS_BUDGET)."""
    import jax
    import paddle_tpu.layers as L
    from paddle_tpu import optim
    from paddle_tpu.data import dense_vector, integer_value
    from paddle_tpu.data.feeder import DataFeeder
    from paddle_tpu.layers.graph import reset_names
    from paddle_tpu.perf import analytic as perf_analytic
    from paddle_tpu.quant import weights as quant_weights
    from paddle_tpu.trainer.trainer import SGD

    rng = np.random.RandomState(seed)
    xs = rng.randn(n_batches, batch, dim).astype(np.float32)
    ys = (xs.sum(-1) > 0).astype(np.int64)
    feeding = {"x": dense_vector(dim), "lab": integer_value(2)}
    feeder = DataFeeder(feeding)

    def build(quant):
        reset_names()
        x = L.data_layer("x", size=dim)
        lab = L.data_layer("lab", size=1)
        h = L.fc_layer(x, size=hidden, act="tanh")
        y = L.fc_layer(h, size=2, act="softmax")
        cost = L.classification_cost(y, lab)
        return SGD(cost=cost,
                   update_equation=optim.Momentum(learning_rate=0.01,
                                                  momentum=0.9),
                   quant_weights=quant, quant_min_size=1024)

    tr = build(True)
    assert tr._qtree, "the int8 trainer must quantize the fc weights"

    def batches():
        for i in range(n_batches):
            yield [(xs[i, j], int(ys[i, j])) for j in range(batch)]

    def postcheck(compiled):
        """The weight-streaming structural gate (see the factory
        docstring) — also proven to fire on the plain-f32 twin."""
        txt = compiled.as_text()
        shapes = [quant_weights.weight_shape(l)
                  for l in tr._qtree.values()]
        floats = [np.shape(l) for l in jax.tree_util.tree_leaves(
                      (tr.parameters, tr.opt_state, tr.model_state))
                  if hasattr(l, "dtype")
                  and np.issubdtype(l.dtype, np.floating)]
        perf_analytic.assert_weights_quantized(txt, shapes, floats)
        f32_hlo = build(False).lower_step(
            feeder.feed_specs(batch)[0]).compile().as_text()
        try:
            perf_analytic.assert_weights_quantized(f32_hlo, shapes,
                                                   floats)
        except AssertionError:
            pass
        else:
            raise AssertionError(
                "weights-quantized gate failed to flag the plain f32 "
                "train step — the detector is broken")
        return {"weights_quantized_proof": "pass",
                "quantized_weight_shapes": [list(s) for s in shapes]}

    extras = {"lower": lambda: tr.lower_step(feeder.feed_specs(batch)[0]),
              "postcheck": postcheck}

    if os.environ.get("BENCH_ANALYTIC_BUILD") != "1":
        f32 = build(False)
        gaps, qcost = [], None
        for bat in batches():
            qcost = float(tr.train_one_batch(bat, feeder))
            fcost = float(f32.train_one_batch(bat, feeder))
            gaps.append(abs(qcost - fcost) / max(abs(fcost), 1.0))
        gap = max(gaps)
        if gap > quant_weights.TRAIN_LOSS_BUDGET:
            raise AssertionError(
                f"int8 trainer loss gap {gap:.4f} exceeds the "
                f"committed budget {quant_weights.TRAIN_LOSS_BUDGET}")
        # bytes the FORWARD streams: the int8 tree replaces its f32
        # masters on the matmul path (masters stay optimizer-side and
        # are touched only by the update, like any opt-state slot)
        f32_w = quant_weights.param_bytes(tr.parameters)
        q_displaced = sum(
            int(np.prod(quant_weights.weight_shape(l))) * 4
            for l in tr._qtree.values())
        extras.update(
            loss_gap_max=round(gap, 5),
            loss_gap_budget=quant_weights.TRAIN_LOSS_BUDGET,
            final_loss_int8=round(qcost, 5),
            steps_compared=n_batches,
            fwd_weight_bytes_f32=f32_w,
            fwd_weight_bytes_int8=f32_w - q_displaced
            + quant_weights.param_bytes(tr._qtree))

    def run(_s):
        i = rng.randint(n_batches)
        return tr.train_one_batch(
            [(xs[i, j], int(ys[i, j])) for j in range(batch)], feeder)

    flops = 3.0 * 2.0 * (dim * hidden + hidden * 2) * batch
    return run, flops, None, (
        f"int8 weight-streaming trainer ms/batch bs={batch} "
        f"(master+q bundle, in-step requantize)"), extras


def bench_serving_speculative(slots=8, n_requests=32, vocab=256,
                              d_model=128, dff=192, layers=3, heads=2,
                              chunk=8, speculate_k=4, draft_layers=2,
                              seed=0):
    """Speculative decoding on the slot engine (serving/speculative.py;
    docs/serving.md "Speculative decoding") vs the same chunked engine
    without a draft, at 8 and 32 clients: a truncated-trunk draft
    proposes ``speculate_k`` tokens per feeding slot and the target's
    one chunk step scores every lane at once, so each target step nets
    1 + accepted tokens instead of exactly 1.  Reported per mode and
    client count: tokens/s, TTFT p99, TPOT p50/p99, and (spec only)
    the acceptance rate + effective tokens per target step.  An
    adversarial drive (a draft from a DIFFERENT seed — near-zero
    acceptance) pins the floor: every step still nets >= 1 token and
    the streams stay bit-identical, speculation only ever costs speed.

    The analytic leg: extras["lower"] is the spec-mode unified step
    (all_lanes projection live, Tq=chunk kernels forced on) and
    extras["postcheck"] proves (1) the spec step materializes the
    all-lanes [S, K, vocab] projection while the non-spec twin does
    NOT (detector shown firing in both directions), and (2) the
    predicted per-emitted-token bytes model
    (perf/analytic.predicted_spec_bytes_per_token — kernel_cost(tq=
    k+1) verify + k draft passes over expected emitted) shows a
    reduction at a serving-representative scale AND a regression in
    the adversarial direction (acceptance 0) — spec must never look
    free."""
    import jax
    from paddle_tpu.models import transformer
    from paddle_tpu.ops.pallas import decode_attention as decode_kernels
    from paddle_tpu.perf import analytic as perf_analytic
    from paddle_tpu.serving import GenerationBatcher, ServingMetrics
    from paddle_tpu.serving.decode_engine import DecodeEngine
    from paddle_tpu.serving.speculative import make_draft

    max_len = 96
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=0,
                              max_len=max_len, num_heads=heads)
    adv_params = transformer.init(jax.random.PRNGKey(7), src_vocab=vocab,
                                  trg_vocab=1, d_model=d_model, dff=dff,
                                  enc_layers=layers, dec_layers=0,
                                  max_len=max_len, num_heads=heads)
    warm = os.environ.get("BENCH_ANALYTIC_BUILD") != "1"

    def make_engine(mode, draft_params=None):
        spec = mode != "plain"
        draft = make_draft(draft_params or params,
                           layers=draft_layers) if spec else None
        return DecodeEngine(params, num_heads=heads, num_slots=slots,
                            max_len=max_len, prefill_chunk=chunk,
                            name=f"bench_spec_{mode}", warm=warm,
                            speculate_k=speculate_k if spec else 0,
                            draft=draft)

    rng = np.random.RandomState(seed)
    reqs = [(rng.randint(1, vocab, rng.randint(4, 12)).astype(np.int32),
             int(rng.randint(12, 21))) for _ in range(n_requests)]

    def drive(mode, n_clients, draft_params=None):
        engine = make_engine(mode, draft_params)
        engine.metrics = ServingMetrics()
        bat = GenerationBatcher(engine, queue_size=4096)
        lock, nxt, tokens = threading.Lock(), [0], [0]
        outs = {}

        def client():
            while True:
                with lock:
                    i = nxt[0]
                    if i >= len(reqs):
                        return
                    nxt[0] += 1
                prompt, mt = reqs[i]
                out = bat.submit(prompt, max_tokens=mt).result(300)
                with lock:
                    tokens[0] += len(out["tokens"])
                    outs[i] = out["tokens"]

        ts = [threading.Thread(target=client) for _ in range(n_clients)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        snap = engine.metrics.snapshot()
        bat.close()
        r = {"mode": mode, "clients": n_clients,
             "tokens_per_s": round(tokens[0] / dt, 1),
             "ttft_p99_ms": snap["ttft_ms"]["p99"],
             "tpot_p50_ms": snap["tpot_ms"]["p50"],
             "tpot_p99_ms": snap["tpot_ms"]["p99"],
             "outs": outs}
        if mode != "plain":
            r["spec_acceptance_rate"] = snap["spec_acceptance_rate"]
            r["spec_tokens_per_step"] = snap["spec_tokens_per_step"]
        return r

    def lower_spec():
        engine = make_engine("spec")
        with decode_kernels.forced_mode("always"):
            return engine.lower()

    kk = max(chunk, speculate_k + 1)

    def postcheck(compiled):
        """Both analytic gates, each proven in both directions."""
        import re

        def all_lanes_projection(hlo):
            # the spec verify's vocab projection over EVERY lane — the
            # [S, KK, vocab] buffer the non-spec step must not hold
            # (it projects only the selected last-position lane)
            pat = rf"f32\[{slots},{kk},{vocab}\]"
            return [ln.strip() for ln in hlo.splitlines()
                    if re.search(pat, ln)][:4]

        if not all_lanes_projection(compiled.as_text()):
            raise AssertionError(
                f"spec step holds no [{slots}, {kk}, {vocab}] all-lanes "
                "projection — the verify path is not scoring the "
                "drafted lanes")
        with decode_kernels.forced_mode("always"):
            plain_hlo = make_engine("plain").lower().compile().as_text()
        hits = all_lanes_projection(plain_hlo)
        if hits:
            raise AssertionError(
                "non-spec twin materializes the all-lanes projection — "
                "the detector (or the all_lanes gating) is broken:\n  "
                + "\n  ".join(hits))
        # bytes model, serving-representative scale (the toy bench dims
        # are embedding-dominated: a draft sharing the target embedding
        # is honestly predicted to LOSE there — recorded, not gated)
        rep = dict(layers=48, d=2048, dff=8192, vocab=32000, s=slots,
                   t_span=2048, num_heads=16,
                   draft_layers=draft_layers, k=speculate_k)
        spec_b, plain_b = perf_analytic.predicted_spec_bytes_per_token(
            acceptance=0.6, **rep)
        reduction = 1.0 - spec_b / plain_b
        if not reduction > 0:
            raise AssertionError(
                f"predicted per-emitted-token bytes show no reduction "
                f"at acceptance 0.6 ({reduction:+.2%}) — the spec "
                "bytes model lost its win")
        adv_b, _ = perf_analytic.predicted_spec_bytes_per_token(
            acceptance=0.0, **rep)
        if not adv_b > plain_b:
            raise AssertionError(
                "bytes model predicts a FREE lunch: acceptance-0 "
                "speculation must cost more per token than plain "
                "decode (draft passes + extra lanes are not free)")
        toy_spec, toy_plain = perf_analytic.predicted_spec_bytes_per_token(
            layers=layers, d=d_model, dff=dff, vocab=vocab, s=slots,
            t_span=max_len, num_heads=heads, draft_layers=draft_layers,
            k=speculate_k, acceptance=0.6)
        return {"all_lanes_projection_proof": "pass",
                "spec_bytes_reduction_rep": round(reduction, 4),
                "spec_bytes_regression_adversarial":
                    round(1.0 - adv_b / plain_b, 4),
                "spec_bytes_reduction_toy":
                    round(1.0 - toy_spec / toy_plain, 4)}

    extras = {"lower": lower_spec, "postcheck": postcheck}
    if warm:
        rows, plain_outs = [], {}
        for n_clients in (8, 32):
            spec_r = drive("spec", n_clients)
            plain_r = drive("plain", n_clients)
            if spec_r.pop("outs") != (po := plain_r.pop("outs")):
                raise AssertionError(
                    f"spec streams diverged from non-spec at "
                    f"{n_clients} clients — speculation changed OUTPUT")
            plain_outs = po
            if not spec_r["spec_tokens_per_step"] > 1.0:
                raise AssertionError(
                    "high-acceptance draft nets <= 1 token per target "
                    f"step: {spec_r}")
            rows += [spec_r, plain_r]
        adv = drive("adversarial", 8, draft_params=adv_params)
        if adv.pop("outs") != plain_outs:
            raise AssertionError("adversarial-draft streams diverged — "
                                 "speculation changed OUTPUT")
        if not adv["spec_tokens_per_step"] >= 1.0:
            raise AssertionError(
                f"adversarial draft broke the >= 1 token/step floor: "
                f"{adv}")
        rows.append(adv)
        extras.update(drives=rows)

    def run(_s):
        r = drive("spec", 8)
        r.pop("outs")
        return np.float32(r["tokens_per_s"])

    total_tokens = sum(mt for _p, mt in reqs)
    prefill_tokens = sum(p.size for p, _mt in reqs)
    per_tok = layers * (6 * d_model ** 2 + 2 * d_model * dff) \
        + d_model * vocab
    attn = layers * 4.0 * d_model * max_len / 2
    flops = (2.0 * per_tok + attn) * (total_tokens + prefill_tokens)
    return run, flops, None, (
        f"speculative serving tokens/s ({n_requests} reqs, 8/32 "
        f"clients, {slots} slots, k={speculate_k}, {draft_layers}-of-"
        f"{layers}-layer draft; spec vs plain vs adversarial)"), extras


def bench_serving_sharded(slots=8, n_requests=32, vocab=256, d_model=128,
                          dff=192, layers=3, heads=2, chunk=8, shards=2,
                          seed=0):
    """Tensor-parallel sharded decode (decode_engine.py ``mesh=`` +
    parallel/sharding.py; docs/serving.md "Sharded decode") vs the
    single-chip twin at a FIXED PER-CHIP KV-BYTE BUDGET: the sharded
    engine holds only its Hkv/n head stripe of every slot's K/V, so the
    same per-chip slab bytes carry ``shards`` x the slots.  Runs on an
    n=``shards`` forced host-CPU mesh (the snapshot refresh and this
    bench both need ``XLA_FLAGS=--xla_force_host_platform_device_count
    >= shards`` — the factory refuses to lie with a 1-device "mesh").
    Driven at 8/32 clients; the sharded streams are verified
    BIT-IDENTICAL to the twin's inside the drive (tensor parallelism
    may never change output) at exactly one step trace.

    The analytic leg: extras["lower"] is the sharded chunked step and
    postcheck proves (1) the compiled program holds EXACTLY the
    declared collective seams — one attention-output all-gather per
    layer plus the logits all-gather and the embedding psum — while
    the single-chip twin compiles to zero collectives (detector shown
    firing in both directions), and (2) the per-chip bytes model
    (perf/analytic.predicted_sharded_step_bytes) predicts a real
    reduction vs single-chip at a serving-representative scale, never
    beats the ideal 1/n floor, and a deliberately REPLICATED-WEIGHTS
    twin (same mesh, same collectives, full weight stream per chip)
    FAILS the reduction gate — sharding must never look free."""
    import jax
    from paddle_tpu.models import transformer
    from paddle_tpu.parallel import sharding as psh
    from paddle_tpu.perf import analytic as perf_analytic
    from paddle_tpu.serving import GenerationBatcher, ServingMetrics
    from paddle_tpu.serving.decode_engine import DecodeEngine

    if len(jax.devices()) < shards:
        raise RuntimeError(
            f"serving_sharded needs >= {shards} devices for the mesh, "
            f"got {len(jax.devices())} — run under XLA_FLAGS="
            f"--xla_force_host_platform_device_count={shards} (the "
            "tier-1 suite and healthy_window.sh already do)")
    max_len = 96
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, dff=dff,
                              enc_layers=layers, dec_layers=0,
                              max_len=max_len, num_heads=heads)
    mesh = psh.decode_mesh(shards)
    warm = os.environ.get("BENCH_ANALYTIC_BUILD") != "1"

    def make_engine(mode):
        sharded = mode == "sharded"
        # per-chip slab bytes: twin holds `slots` full-Dkv rows; the
        # sharded engine's rows are 1/shards as wide per chip, so the
        # SAME per-chip budget carries shards*slots rows
        return DecodeEngine(params, num_heads=heads,
                            num_slots=slots * shards if sharded else slots,
                            max_len=max_len, prefill_chunk=chunk,
                            name=f"bench_sharded_{mode}", warm=warm,
                            mesh=mesh if sharded else None)

    rng = np.random.RandomState(seed)
    reqs = [(rng.randint(1, vocab, rng.randint(4, 12)).astype(np.int32),
             int(rng.randint(12, 21))) for _ in range(n_requests)]

    def drive(mode, n_clients):
        engine = make_engine(mode)
        engine.metrics = ServingMetrics()
        bat = GenerationBatcher(engine, queue_size=4096)
        lock, nxt, tokens = threading.Lock(), [0], [0]
        outs = {}

        def client():
            while True:
                with lock:
                    i = nxt[0]
                    if i >= len(reqs):
                        return
                    nxt[0] += 1
                prompt, mt = reqs[i]
                out = bat.submit(prompt, max_tokens=mt).result(300)
                with lock:
                    tokens[0] += len(out["tokens"])
                    outs[i] = out["tokens"]

        ts = [threading.Thread(target=client) for _ in range(n_clients)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        snap = engine.metrics.snapshot()
        traces = engine.step_trace_count
        bat.close()
        return {"mode": mode, "clients": n_clients,
                "mesh_shards": snap["mesh_shards"],
                "slots": engine.num_slots,
                "step_traces": traces,
                "tokens_per_s": round(tokens[0] / dt, 1),
                "ttft_p99_ms": snap["ttft_ms"]["p99"],
                "tpot_p50_ms": snap["tpot_ms"]["p50"],
                "tpot_p99_ms": snap["tpot_ms"]["p99"],
                "outs": outs}

    def lower_sharded():
        return make_engine("sharded").lower()

    def postcheck(compiled):
        """Both analytic gates, each proven in both directions."""
        import re

        def collectives(hlo):
            ops = re.findall(r"= \S+ ([a-z][a-z0-9\-]*)\(", hlo)
            return (sum(1 for o in ops if o == "all-gather"),
                    sum(1 for o in ops
                        if o in ("all-reduce", "reduce-scatter")))

        gathers, reduces = collectives(compiled.as_text())
        if gathers != layers + 1 or reduces < 1:
            raise AssertionError(
                f"sharded step compiled to {gathers} all-gathers / "
                f"{reduces} reductions — expected exactly {layers + 1} "
                f"gathers (one per layer's attention output + the "
                "logits seam) and the embedding psum; the one-seam "
                "contract is broken")
        tg, tr = collectives(make_engine("plain").lower().compile()
                             .as_text())
        if tg or tr:
            raise AssertionError(
                f"single-chip twin holds {tg} gathers / {tr} reductions "
                "— the collective detector (or the mesh gating) is "
                "broken")
        # per-chip bytes model at a serving-representative, KV-bound
        # scale (long-context decode is where the head-stripe pool
        # pays); all three directions are pure-math, zero-noise gates
        rep = dict(layers=48, d=2048, dff=8192, vocab=32000, s=8,
                   t_span=4096, num_heads=16, chunk=8)
        single = perf_analytic.predicted_sharded_step_bytes(
            shards=1, **rep)
        sharded = perf_analytic.predicted_sharded_step_bytes(
            shards=shards, **rep)
        twin = perf_analytic.predicted_sharded_step_bytes(
            shards=shards, replicate_weights=True, **rep)
        ratio = sharded["total"] / single["total"]
        if not ratio <= 0.62:
            raise AssertionError(
                f"sharded per-chip bytes are {ratio:.1%} of single-chip "
                "at the representative scale — the >= 38% reduction is "
                "gone")
        if not sharded["total"] >= single["total"] / shards:
            raise AssertionError(
                f"model predicts BETTER than the ideal 1/{shards} floor "
                f"({ratio:.1%}) — replicated weights and collective "
                "seams cannot be free")
        twin_ratio = twin["total"] / single["total"]
        if not twin_ratio > 0.62:
            raise AssertionError(
                f"replicated-weights twin passes the reduction gate "
                f"({twin_ratio:.1%}) — the model stopped charging for "
                "the full per-chip weight stream")
        toy = perf_analytic.predicted_sharded_step_bytes(
            layers=layers, d=d_model, dff=dff, vocab=vocab, s=slots,
            t_span=max_len, num_heads=heads, chunk=chunk, shards=shards)
        return {"collective_seams_proof": "pass",
                "sharded_seams": {"all_gather": gathers,
                                  "reduce": reduces},
                "sharded_bytes_ratio_rep": round(ratio, 4),
                "sharded_bytes_ratio_twin": round(twin_ratio, 4),
                "per_chip_predicted_bytes_rep": round(sharded["total"]),
                "per_chip_collective_bytes_rep":
                    round(sharded["collective"]),
                "per_chip_predicted_bytes_toy": round(toy["total"]),
                "per_chip_collective_bytes_toy":
                    round(toy["collective"])}

    extras = {"lower": lower_sharded, "postcheck": postcheck}
    if warm:
        rows = []
        for n_clients in (8, 32):
            sh_r = drive("sharded", n_clients)
            pl_r = drive("plain", n_clients)
            if sh_r.pop("outs") != pl_r.pop("outs"):
                raise AssertionError(
                    f"sharded streams diverged from the single-chip "
                    f"twin at {n_clients} clients — tensor parallelism "
                    "changed OUTPUT")
            if sh_r["step_traces"] != 1:
                raise AssertionError(
                    f"sharded engine traced {sh_r['step_traces']}x "
                    "under the drive — the one-trace contract broke")
            if sh_r["mesh_shards"] != shards:
                raise AssertionError(
                    f"metrics report mesh_shards={sh_r['mesh_shards']}, "
                    f"engine built for {shards}")
            rows += [sh_r, pl_r]
        extras.update(drives=rows)

    def run(_s):
        r = drive("sharded", 8)
        r.pop("outs")
        return np.float32(r["tokens_per_s"])

    total_tokens = sum(mt for _p, mt in reqs)
    prefill_tokens = sum(p.size for p, _mt in reqs)
    per_tok = layers * (6 * d_model ** 2 + 2 * d_model * dff) \
        + d_model * vocab
    attn = layers * 4.0 * d_model * max_len / 2
    flops = (2.0 * per_tok + attn) * (total_tokens + prefill_tokens)
    return run, flops, None, (
        f"tensor-parallel sharded serving tokens/s ({n_requests} reqs, "
        f"8/32 clients, n={shards} host mesh, {slots * shards} sharded "
        f"vs {slots} single-chip slots at equal per-chip KV bytes; "
        "streams bit-identical)"), extras


def bench_serving_fleet(replicas=2, n_requests=16, vocab=256, max_len=64,
                        prefill_buckets=(8, 16), gen_short=8, gen_long=24,
                        seed=0):
    """Replicated serving tier (serving/fleet.py + serving/router.py):
    closed-loop clients drive /v1/generate through the health-checked
    ROUTER over 1 vs ``replicas`` fleet-supervised demo-LM replica
    SUBPROCESSES — the cross-process scaling the single-process
    serving_generate row cannot show.  extras carry the 8/32-client
    sweep for both fleet sizes (useful tokens/s, p99 TTFT, p99 wall),
    the 2-vs-1 replica speedup, and the FAILOVER-ADDED LATENCY probe:
    one streaming request whose replica is kill -9'd mid-stream, timed
    against the same stream uninterrupted (the router's continuation
    resubmit keeps it bit-identical; the delta is what the failover
    costs).

    The router is host-side only — its AOT hook is the SAME slab decode
    step the replicas run (a local DecodeEngine, never executed here),
    so the analytic row gates the serving hot path and the fleet adds
    zero new traces by construction."""
    import atexit
    import json as _json
    import signal as _signal
    import urllib.request
    import jax
    from paddle_tpu.models import transformer
    from paddle_tpu.serving.decode_engine import DecodeEngine

    d_model, heads, dff, layers = 32, 2, 64, 2   # the --demo-generate trunk
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, num_heads=heads,
                              dff=dff, enc_layers=layers, dec_layers=0,
                              max_len=max_len)
    slots = 8
    local = DecodeEngine(params, num_heads=heads, num_slots=slots,
                         max_len=max_len, prefill_buckets=prefill_buckets,
                         name="bench_fleet", warm=False)
    extras = {"lower": lambda: local.lower()}
    rng = np.random.RandomState(seed)
    reqs = [(rng.randint(1, vocab, rng.randint(3, prefill_buckets[-1] + 1)
                         ).tolist(),
             gen_long if i % 4 == 0 else gen_short)
            for i in range(n_requests)]
    replica_args = ["--gen-slots", str(slots), "--gen-max-len",
                    str(max_len), "--gen-prefill-buckets",
                    ",".join(str(b) for b in prefill_buckets),
                    "--gen-max-tokens", str(max_len - prefill_buckets[-1])]
    state = {}

    def _spawn(n_rep):
        from paddle_tpu.serving.fleet import ReplicaSupervisor
        from paddle_tpu.serving.router import Router
        sup = ReplicaSupervisor(n_replicas=n_rep, extra_args=replica_args,
                                name=f"bench_fleet{n_rep}").start()
        if not sup.wait_ready(timeout=300):
            sup.stop()
            raise RuntimeError(f"{n_rep}-replica fleet never became ready")
        router = Router(supervisor=sup, poll_interval_s=0.1)
        httpd = router.start(port=0)
        t0 = time.perf_counter()
        while not router.ready():
            if time.perf_counter() - t0 > 30:
                raise RuntimeError("router never saw a ready replica")
            time.sleep(0.05)
        return sup, router, httpd.port

    def _post(port, body, timeout=300):
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/generate",
            data=_json.dumps(body).encode(),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=timeout) as r:
            return _json.loads(r.read())

    def drive(port, n_clients, reqs):
        lats, ttfts, tokens = [], [], [0]
        lock, nxt = threading.Lock(), [0]

        def client():
            while True:
                with lock:
                    i = nxt[0]
                    if i >= len(reqs):
                        return
                    nxt[0] += 1
                prompt, mt = reqs[i]
                t0 = time.perf_counter()
                out = _post(port, {"prompt": prompt, "max_tokens": mt})
                with lock:
                    lats.append(time.perf_counter() - t0)
                    ttfts.append(out["ttft_ms"])
                    tokens[0] += len(out["tokens"])

        ts = [threading.Thread(target=client) for _ in range(n_clients)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        lats.sort()
        ttfts.sort()
        return {"clients": n_clients,
                "tokens_per_s": round(tokens[0] / dt, 1),
                "ttft_p99_ms": round(ttfts[min(len(ttfts) - 1,
                                               int(len(ttfts) * 0.99))], 2),
                "p99_ms": round(lats[min(len(lats) - 1,
                                         int(len(lats) * 0.99))] * 1e3, 2)}

    def _stream_ms(port, prompt, mt, kill=None):
        """Wall time of one streaming request; kill=(sup, router) fires
        kill -9 at the replica that OWNS the stream (the router's live
        in-flight gauge names it) after the first token — the failover
        probe."""
        import http.client
        conn = http.client.HTTPConnection("127.0.0.1", port, timeout=300)
        t0 = time.perf_counter()
        conn.request("POST", "/v1/generate",
                     _json.dumps({"prompt": prompt, "max_tokens": mt,
                                  "stream": True}).encode(),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        n = 0
        while True:
            line = resp.readline()
            if not line:
                break
            rec = _json.loads(line)
            if "token" in rec:
                n += 1
                if n == 1 and kill is not None:
                    sup_, router_ = kill
                    owner = [rid for rid, st
                             in router_.replica_states().items()
                             if st["inflight"] >= 1]
                    if owner:
                        sup_.kill(owner[0], _signal.SIGKILL)
            if rec.get("done"):
                break
        conn.close()
        return (time.perf_counter() - t0) * 1e3

    if os.environ.get("BENCH_ANALYTIC_BUILD") != "1":
        sweep = []
        fleet_sizes = (1,) if int(replicas) == 1 else (1, int(replicas))
        for n_rep in fleet_sizes:
            sup, router, port = _spawn(n_rep)
            try:
                drive(port, 8, reqs[:8])            # warm the whole path
                for c in (8, 32):
                    row = drive(port, c, reqs)
                    row["replicas"] = n_rep
                    sweep.append(row)
            finally:
                if n_rep != int(replicas):
                    router.close()
                    sup.stop()
        # the N-replica fleet stays up for run() and the failover probe
        state.update(sup=sup, router=router, port=port)
        atexit.register(lambda: (router.close(), sup.stop()))
        probe_prompt, probe_mt = reqs[0][0], max_len - prefill_buckets[-1]
        clean_ms = _stream_ms(port, probe_prompt, probe_mt)
        failover_ms = _stream_ms(port, probe_prompt, probe_mt,
                                 kill=(sup, router))
        snap = router.metrics.snapshot()
        at8 = {r["replicas"]: r for r in sweep if r["clients"] == 8}
        extras.update(
            load_sweep=sweep,
            fleet_tokens_per_s=at8[int(replicas)]["tokens_per_s"],
            fleet_ttft_p99_ms=at8[int(replicas)]["ttft_p99_ms"],
            single_tokens_per_s=at8[1]["tokens_per_s"],
            fleet_speedup=round(at8[int(replicas)]["tokens_per_s"]
                                / at8[1]["tokens_per_s"], 2),
            clean_stream_ms=round(clean_ms, 1),
            failover_stream_ms=round(failover_ms, 1),
            failover_added_ms=round(failover_ms - clean_ms, 1),
            midstream_failovers=snap["midstream_failovers_total"])
        # let the killed replica's restart settle before the timed runs
        sup.wait_ready(timeout=300)

    def run(s):
        r = drive(state["port"], 8, reqs)
        return np.float32(r["tokens_per_s"])

    total_tokens = sum(mt for _, mt in reqs)
    per_tok = layers * (6 * d_model ** 2 + 2 * d_model * dff) \
        + d_model * vocab
    attn = layers * 4.0 * d_model * max_len * max_len / 2
    flops = (2.0 * per_tok + attn / max_len) * slots \
        * (total_tokens / slots)
    return run, flops, None, (
        f"replicated serving ms/burst ({n_requests} reqs, 8 clients, "
        f"{replicas} replica subprocesses behind the router, "
        f"max_tokens {gen_short}/{gen_long})"), extras


def bench_serving_autoscale(replicas=2, n_requests=24, n_clients=8,
                            vocab=256, max_len=64, prefill_buckets=(8, 16),
                            gen_tokens=12, seed=0):
    """SLO-holding control plane (serving/autoscaler.py + serving/
    overload.py; docs/serving.md §8): the SAME seeded load spike driven
    through the router twice — once over a FIXED 1-replica fleet (what
    static provisioning gives you when the operator guessed low), once
    over an AUTOSCALED fleet (min 1, max ``replicas``) whose control
    loop watches the router's recent-window TTFT p99 and scales out
    mid-spike.  extras carry goodput (useful tokens/s), p99 TTFT, and
    the overload controller's shed rate for BOTH sides, plus the
    autoscaler's decision evidence (scale-outs, journal length).

    The autoscaler and overload controller are host-side only — the AOT
    hook is the SAME slab decode step the replicas run (a local
    DecodeEngine, never executed here), so the analytic row gates the
    serving hot path and the control plane adds zero new traces by
    construction."""
    import atexit
    import json as _json
    import urllib.error
    import urllib.request
    import jax
    from paddle_tpu.models import transformer
    from paddle_tpu.serving.autoscaler import Autoscaler
    from paddle_tpu.serving.decode_engine import DecodeEngine
    from paddle_tpu.serving.overload import AIMDLimiter, OverloadController

    d_model, heads, dff, layers = 32, 2, 64, 2   # the --demo-generate trunk
    slots = 4                                    # small slab: 16 clients queue
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=vocab,
                              trg_vocab=1, d_model=d_model, num_heads=heads,
                              dff=dff, enc_layers=layers, dec_layers=0,
                              max_len=max_len)
    local = DecodeEngine(params, num_heads=heads, num_slots=slots,
                         max_len=max_len, prefill_buckets=prefill_buckets,
                         name="bench_autoscale", warm=False)
    extras = {"lower": lambda: local.lower()}
    rng = np.random.RandomState(seed)
    reqs = [(rng.randint(1, vocab,
                         rng.randint(3, prefill_buckets[-1] + 1)).tolist(),
             gen_tokens) for _ in range(n_requests)]
    # the injected decode-step hang paces tokens (~20ms each): queue
    # pressure then comes from PACING, not CPU saturation, so the
    # 8-client spike breaches TTFT deterministically even on a 1-core
    # CI host (sleeping server threads don't starve the clients)
    replica_args = ["--gen-slots", str(slots), "--gen-max-len",
                    str(max_len), "--gen-prefill-buckets",
                    ",".join(str(b) for b in prefill_buckets),
                    "--gen-max-tokens", str(gen_tokens),
                    "--fault-spec",
                    "serving.decode_step:every=1,action=hang,hang_s=0.02"]
    state = {}

    def _controller():
        # a modest AIMD limit so the spike actually exercises the
        # shedding path on the under-provisioned side
        return OverloadController(limiter=AIMDLimiter(
            initial=6, min_limit=2, max_limit=64))

    def _spawn(autoscale):
        from paddle_tpu.serving.fleet import ReplicaSupervisor
        from paddle_tpu.serving.router import Router
        sup = ReplicaSupervisor(
            n_replicas=1, extra_args=replica_args,
            name=f"bench_autoscale{'_as' if autoscale else '_fixed'}"
        ).start()
        if not sup.wait_ready(timeout=300):
            sup.stop()
            raise RuntimeError("seed replica never became ready")
        router = Router(supervisor=sup, poll_interval_s=0.1,
                        overload=_controller())
        scaler = None
        if autoscale:
            scaler = Autoscaler(sup, router, poll_interval_s=0.25,
                                target_ttft_ms=150.0, hysteresis=0.2,
                                breach_polls=2, slack_polls=1 << 30,
                                cooldown_out_s=1.0, cooldown_in_s=1e9,
                                min_replicas=1, max_replicas=int(replicas),
                                window_s=5.0, seed=seed).start()
        httpd = router.start(port=0)
        t0 = time.perf_counter()
        while not router.ready():
            if time.perf_counter() - t0 > 30:
                raise RuntimeError("router never saw a ready replica")
            time.sleep(0.05)
        return sup, router, scaler, httpd.port

    def drive(port, reqs):
        """Closed-loop seeded spike: n_clients workers drain the request
        list.  A 429 shed is counted as BACKPRESSURE and the client
        honors its Retry-After (capped for bench scale) before retrying
        the same request; any other failure (5xx, starved socket) is
        counted separately as an error — so shed_rate measures real
        overload shedding, not restart-window noise, and a request that
        exhausts its retries is reported as LOST, never silently
        dropped from the goodput denominator."""
        ttfts, tokens, sheds, errors, lost = [], [0], [0], [0], [0]
        lock, nxt = threading.Lock(), [0]

        def client():
            while True:
                with lock:
                    i = nxt[0]
                    if i >= len(reqs):
                        return
                    nxt[0] += 1
                prompt, mt = reqs[i]
                body = _json.dumps({"prompt": prompt,
                                    "max_tokens": mt}).encode()
                req = urllib.request.Request(
                    f"http://127.0.0.1:{port}/v1/generate", data=body,
                    headers={"Content-Type": "application/json"})
                for attempt in range(50):
                    try:
                        with urllib.request.urlopen(req, timeout=300) as r:
                            out = _json.loads(r.read())
                    except urllib.error.HTTPError as e:
                        ra = e.headers.get("Retry-After") \
                            if e.code == 429 else None
                        with lock:
                            if e.code == 429:
                                sheds[0] += 1
                            else:
                                errors[0] += 1
                        e.read()
                        e.close()
                        try:
                            backoff = float(ra)
                        except (TypeError, ValueError):
                            backoff = 0.05
                        time.sleep(min(backoff, 0.25))
                        continue
                    except Exception:   # noqa: BLE001 — a starved socket
                        with lock:      # on a loaded CI host: brief
                            errors[0] += 1  # backoff, retry
                        time.sleep(0.05)
                        continue
                    with lock:
                        ttfts.append(out["ttft_ms"])
                        tokens[0] += len(out["tokens"])
                    break
                else:
                    with lock:
                        lost[0] += 1    # retries exhausted: visible,
                    #                     not silently dropped

        ts = [threading.Thread(target=client) for _ in range(n_clients)]
        t0 = time.perf_counter()
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        dt = time.perf_counter() - t0
        ttfts.sort()
        return {
            "tokens_per_s": round(tokens[0] / dt, 1),
            "ttft_p99_ms": round(
                ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))], 2)
            if ttfts else None,
            "completed": len(ttfts),
            "shed": sheds[0],
            "errors": errors[0],
            "lost": lost[0],
            "shed_rate": round(sheds[0] / max(1, sheds[0] + len(ttfts)),
                               3),
        }

    if os.environ.get("BENCH_ANALYTIC_BUILD") != "1":
        # ---- fixed 1-replica side: the same spike, nowhere to grow
        sup_f, router_f, _, port_f = _spawn(autoscale=False)
        try:
            drive(port_f, reqs[:8])             # warm the path
            fixed = drive(port_f, reqs)
        finally:
            router_f.close()
            sup_f.stop()
        # ---- autoscaled side: spike until the loop scales out, then
        # the measured drive runs on the adapted fleet
        sup, router, scaler, port = _spawn(autoscale=True)
        state.update(sup=sup, router=router, scaler=scaler, port=port)
        atexit.register(lambda: (scaler.close(), router.close(),
                                 sup.stop()))
        drive(port, reqs[:8])                   # warm
        t0 = time.perf_counter()
        while len(sup.replicas) < int(replicas) \
                and time.perf_counter() - t0 < 300:
            drive(port, reqs)                   # spike pressure
        sup.wait_ready(timeout=300)
        # let the router's poller actually see the new replica before
        # the measured drive, or the first batch still queues on r0
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < 30 and sum(
                1 for st in router.replica_states().values()
                if st["ready"]) < int(replicas):
            time.sleep(0.1)
        scaled = drive(port, reqs)
        snap = scaler.snapshot()
        extras.update(
            fixed_tokens_per_s=fixed["tokens_per_s"],
            fixed_ttft_p99_ms=fixed["ttft_p99_ms"],
            fixed_shed_rate=fixed["shed_rate"],
            fixed_errors=fixed["errors"],
            fixed_lost=fixed["lost"],
            autoscaled_tokens_per_s=scaled["tokens_per_s"],
            autoscaled_ttft_p99_ms=scaled["ttft_p99_ms"],
            autoscaled_shed_rate=scaled["shed_rate"],
            autoscaled_errors=scaled["errors"],
            autoscaled_lost=scaled["lost"],
            autoscaled_replicas=len(sup.replicas),
            goodput_speedup=round(scaled["tokens_per_s"]
                                  / max(fixed["tokens_per_s"], 1e-9), 2),
            scale_outs=snap["scales_total"]["out"],
            scale_failures=snap["scale_failures_total"],
            journal_len=snap["journal_len"])

    def run(s):
        r = drive(state["port"], reqs)
        return np.float32(r["tokens_per_s"])

    total_tokens = sum(mt for _, mt in reqs)
    per_tok = layers * (6 * d_model ** 2 + 2 * d_model * dff) \
        + d_model * vocab
    attn = layers * 4.0 * d_model * max_len * max_len / 2
    flops = (2.0 * per_tok + attn / max_len) * slots \
        * (total_tokens / slots)
    return run, flops, None, (
        f"autoscaled serving ms/burst ({n_requests} reqs, {n_clients} "
        f"clients, fixed 1 replica vs autoscaled 1->{replicas}, "
        f"max_tokens {gen_tokens})"), extras


def bench_trainer_prefetch(batch=64, dim=256, hidden=512, n_batches=24,
                           host_ms=4.0):
    """Trainer hot-loop input overlap: steps/s with the input pipeline
    synchronous (train(prefetch=0): reader + feeder conversion inline in
    the loop) vs overlapped device-resident (train(prefetch=2):
    data.prefetch.ShardedPrefetcher converts + device_puts on a bounded
    background thread).  The workload is deliberately INPUT-BOUND: each
    host batch costs ~host_ms of synthetic input latency against a small
    MLP step, so the row isolates exactly the overlap the prefetcher
    exists to buy.  run() trains a full pass; batches_per_step tells the
    harness to normalize the published value to ms/BATCH at prefetch=2.
    extras carry steps/s at both depths, the speedup, and the residual
    h2d_wait at depth 2."""
    import time as _time
    import jax
    import jax.numpy as jnp
    import paddle_tpu.layers as L
    from paddle_tpu import optim
    from paddle_tpu.layers.graph import reset_names
    from paddle_tpu.trainer import SGD, events
    from paddle_tpu.data import dense_vector, integer_value
    from paddle_tpu.utils.stats import global_stats

    rng = np.random.RandomState(0)
    xs = rng.randn(n_batches, batch, dim).astype(np.float32)
    ys = (xs.sum(-1) > 0).astype(np.int64)

    def reader():
        for i in range(n_batches):
            _time.sleep(host_ms * 1e-3)   # synthetic host-side input cost
            yield [(xs[i, j], int(ys[i, j])) for j in range(batch)]

    feeding = {"x": dense_vector(dim), "lab": integer_value(2)}
    reset_names()
    x = L.data_layer("x", size=dim)
    lab = L.data_layer("lab", size=1)
    h = L.fc_layer(x, size=hidden, act="tanh")
    y = L.fc_layer(h, size=2, act="softmax")
    cost = L.classification_cost(y, lab)
    tr = SGD(cost=cost,
             update_equation=optim.Momentum(learning_rate=0.01, momentum=0.9))

    last = {}

    def one_pass(prefetch):
        tr.train(reader, num_passes=1, feeding=feeding, log_period=0,
                 buffered_batches=0, prefetch=prefetch,
                 event_handler=lambda e: last.__setitem__("cost", e.cost)
                 if isinstance(e, events.EndIteration) else None)

    def steps_per_s(prefetch):
        t0 = _time.perf_counter()
        one_pass(prefetch)
        jax.block_until_ready(last["cost"])
        return n_batches / (_time.perf_counter() - t0)

    def run(s):
        one_pass(2)
        return last["cost"]

    # per-PASS analytic matmul FLOPs (run() trains a whole pass; the
    # harness divides both dt and flops by batches_per_step)
    flops = 3.0 * 2.0 * (dim * hidden + hidden * 2) * batch * n_batches
    from paddle_tpu.data.feeder import DataFeeder
    feeder = DataFeeder(feeding)
    extras = {"batches_per_step": n_batches,
              "lower": lambda: tr.lower_step(feeder.feed_specs(batch)[0])}

    # the analytic layer only consumes extras["lower"]; skip the warm-up/
    # measurement passes so `bench.py --analytic` keeps its nothing-
    # executes contract (paddle_tpu/perf/analytic.py sets the env var)
    if os.environ.get("BENCH_ANALYTIC_BUILD") != "1":
        steps_per_s(0)                  # compile + warm both code paths
        steps_per_s(2)
        sps0 = steps_per_s(0)
        global_stats.get("h2d_wait").reset()
        sps2 = steps_per_s(2)
        h2d_ms = global_stats.get("h2d_wait").avg * 1e3
        extras.update(steps_per_s_prefetch0=round(sps0, 1),
                      steps_per_s_prefetch2=round(sps2, 1),
                      prefetch_speedup=round(sps2 / sps0, 2),
                      h2d_wait_ms=round(h2d_ms, 2))

    return run, flops, None, (
        f"trainer hot-loop ms/batch bs={batch}, pass of {n_batches} "
        f"input-bound batches ({host_ms:g}ms host cost each), prefetch=2"), \
        extras


_BENCHES = {
    # name: (factory, default_batch)
    "transformer": (lambda b: bench_transformer(batch=b), 32),
    # long-context row: 8k tokens/sequence through the Pallas flash
    # kernel (O(T) memory — the materialized [T,T] softmax at this shape
    # would be 256 MB/head-batch); proves the long-context plane on chip
    "transformer_long": (lambda b: bench_transformer(batch=b,
                                                     seq_len=8192), 2),
    # padding-free packed training (real tokens/sec headline; the
    # reference's no-padding Argument story at transformer scale)
    "transformer_packed": (lambda b: bench_transformer_packed(batch=b), 16),
    # long-context packing row (round-5 verdict's "transformer 8k packed"):
    # 8192-slot rows through the O(T)-memory attention path
    "transformer_packed_8k": (lambda b: bench_transformer_packed(
        batch=b, max_len=8192), 2),
    # sparse-expert LM train step (router + expert dispatch on the clock)
    "transformer_moe": (lambda b: bench_transformer_moe(batch=b), 16),
    "transformer_decode": (lambda b: bench_transformer_decode(batch=b), 32),
    "transformer_lm_decode": (lambda b: bench_transformer_lm_decode(batch=b), 32),
    "transformer_serving": (lambda b: bench_transformer_serving(batch=b), 16),
    # the serving RUNTIME row (paddle_tpu/serving): dynamic batcher +
    # bucketed AOT engine under closed-loop load, batched vs batch-size-1
    "serving": (lambda b: bench_serving_engine(batch=b), 32),
    # continuous-batching GENERATION serving (serving/decode_engine.py):
    # slot-based KV-slab decode vs sequential whole-batch at 2/8/32
    # clients; b = the slot count
    "serving_generate": (lambda b: bench_serving_generate(slots=b), 8),
    # replicated serving tier (serving/fleet.py + router.py): router over
    # 1 vs b fleet-supervised replica subprocesses + the kill-9 failover
    # latency probe; b = the replica count
    "serving_fleet": (lambda b: bench_serving_fleet(replicas=b), 2),
    # SLO-holding control plane (serving/autoscaler.py + overload.py):
    # the same seeded spike over a fixed 1-replica fleet vs an
    # autoscaled 1->b fleet — goodput, p99 TTFT, shed rate; b = the
    # autoscaler's max_replicas
    "serving_autoscale": (lambda b: bench_serving_autoscale(replicas=b), 2),
    # paged KV-cache serving (serving/kv_pool.py): block-pool layout vs
    # the PR-5 slab at a fixed KV-byte budget — mixed-length packing +
    # shared-prefix prefill elimination; b = the slab slot count (the
    # paged engine gets 4*b slots over the same bytes)
    "serving_paged": (lambda b: bench_serving_paged(slots=b), 8),
    # fused Pallas decode-attention step vs the reference XLA step
    # (ops/pallas/decode_attention.py): analytic fused-vs-reference
    # bytes at 16/64 slots x slab/paged + the fusion-proof gate; b =
    # the timed paged slot count
    "serving_decode_fused": (lambda b: bench_serving_decode_fused(
        slots=b), 16),
    # unified chunked-prefill serving vs the legacy prefill ladder
    # under mixed long-prompt/decode traffic (decode_engine.py
    # prefill_chunk): TPOT jitter + long-admission TTFT both modes +
    # the no-score-matrix analytic proof; b = slots
    "serving_chunked_prefill": (lambda b: bench_serving_chunked_prefill(
        slots=b), 8),
    # quantized serving (paddle_tpu/quant/): fp32 vs int8-KV vs
    # int8-KV+weights at a fixed KV-byte budget — 2x slots at equal
    # bytes, committed quality budget, and the >= 35% predicted
    # step-bytes reduction gate; b = the fp32 slot count (int8 engines
    # get 2*b slots over the same bytes)
    "serving_quant": (lambda b: bench_serving_quant(slots=b), 8),
    # int8 flash prefill (ops/pallas/flash_attention_quant): the batched
    # causal prefill streaming int8 K/V bytes + scale sidecars straight
    # into the kernel vs the widen-to-f32 reference, the no-widened-
    # convert proof both directions, and the >= 35% predicted
    # prefill-bytes reduction gate; b = the prompt-batch size
    "serving_quant_prefill": (lambda b: bench_serving_quant_prefill(
        batch=b), 8),
    # int8 weight-streaming trainer (SGD(quant_weights=True)): the
    # {master, q} bundle step with in-step requantize, the s8-entry-
    # params proof both directions, and the committed loss-parity
    # budget vs the f32 twin; b = the batch size
    "trainer_int8": (lambda b: bench_trainer_int8(batch=b), 64),
    # speculative decoding (serving/speculative.py): draft-ahead +
    # chunk-kernel verify vs the same chunked engine without a draft at
    # 8/32 clients, the adversarial >= 1 token/step floor, and the
    # all-lanes-projection + predicted-bytes analytic proofs; b = slots
    "serving_speculative": (lambda b: bench_serving_speculative(
        slots=b), 8),
    # tensor-parallel sharded decode (decode_engine.py mesh= +
    # parallel/sharding.py): n=2 forced host-CPU mesh vs the single-chip
    # twin at equal per-chip KV bytes (2x slots), bit-identical streams,
    # the exact-collective-seams proof and the per-chip predicted-bytes
    # gates; b = the single-chip slot count (sharded gets shards*b)
    "serving_sharded": (lambda b: bench_serving_sharded(slots=b), 8),
    # hierarchical KV cache (serving/kv_pool.py HostTier): evicted
    # prefix chains spill to host RAM and restore on the next hit —
    # return-visit TTFT with the tier vs cold recompute, bit-identical
    # streams, and the both-directions restore-vs-recompute routing
    # gate; b = slots
    "serving_kv_spill": (lambda b: bench_serving_kv_spill(slots=b), 4),
    # disaggregated prefill/decode: real-socket KV handoff TTFT vs
    # continuation-replay recompute, bit-identical streams, and the
    # both-directions handoff-vs-recompute routing gate; b = slots
    "serving_disagg": (lambda b: bench_serving_disagg(slots=b), 4),
    "seq2seq": (lambda b: bench_seq2seq(batch=b), 64),
    # input-pipeline overlap row: steps/s at train(prefetch=0) vs 2 on a
    # synthetic input-bound workload (the ShardedPrefetcher's win)
    "trainer_prefetch": (lambda b: bench_trainer_prefetch(batch=b), 64),
    # baselines live ONLY in _BASELINE_MS (keyed per batch); factories
    # pass None so the published numbers have a single source of truth
    "lstm": (lambda b: bench_lstm(batch=b, hidden=512, baseline_ms=None), 64),
    "lstm256": (lambda b: bench_lstm(batch=b, hidden=256, baseline_ms=None), 64),
    "lstm1280": (lambda b: bench_lstm(batch=b, hidden=1280, baseline_ms=None), 64),
    # MXU-scale recurrent row (round-5 verdict's "LSTM h=2048"): each scan
    # step's recurrent matmul is [64,2048]x[2048,8192] — big enough to
    # tile the MXU, unlike the 2016-era hidden sizes
    "lstm2048": (lambda b: bench_lstm(batch=b, hidden=2048, baseline_ms=None), 64),
    "resnet50": (lambda b: bench_resnet50(batch=b), 32),
    "alexnet": (lambda b: bench_image("alexnet", b, None, 1.4e9, 227, 1000), 64),
    "googlenet": (lambda b: bench_image("googlenet", b, None, 3.0e9, 224, 1000), 64),
    "smallnet": (lambda b: bench_image("smallnet", b, None, 2.5e7, 32, 10), 64),
}


# published K40m ms/batch per (model, batch) — BASELINE.md single-GPU
# table (benchmark/README.md:33-58,115-135).  The factories carry the
# bs-64 default; this table corrects vs_baseline for the batch-scaling
# rows so each row compares against ITS published number, and batches
# the reference never published compare against nothing (vs_baseline
# null) rather than the wrong row.
_BASELINE_MS = {
    ("alexnet", 64): 195.0, ("alexnet", 128): 334.0,
    ("alexnet", 256): 602.0, ("alexnet", 512): 1629.0,
    ("googlenet", 64): 613.0, ("googlenet", 128): 1149.0,
    ("googlenet", 256): 2348.0,
    ("smallnet", 64): 10.463, ("smallnet", 512): 63.039,
    ("lstm256", 64): 83.0, ("lstm256", 128): 110.0,
    ("lstm", 64): 184.0, ("lstm", 256): 414.0,
    ("lstm1280", 64): 641.0,
}


def _resolve_baseline(model, batch, factory_baseline_ms):
    """vs_baseline denominator for (model, batch): the published row if
    one exists, the factory's number at its default batch, else None."""
    if (model, batch) in _BASELINE_MS:
        return _BASELINE_MS[(model, batch)]
    if batch == _BENCHES.get(model, (None, None))[1]:
        return factory_baseline_ms
    return None


def smoke_kernels(dog, stub):
    """Compile + numerics-check every Pallas kernel on the live backend at
    the small widths (paddle_tpu/testing/kernel_smoke.py; chip_smoke.py
    runs the same cases at the serving widths).  Prints ONE JSON line; rc
    0 iff every case passed or was declined by its own guard."""
    t_each = float(os.environ.get("BENCH_KERNEL_TIMEOUT", "180"))
    import jax
    from paddle_tpu.testing import kernel_smoke
    _ok, results = kernel_smoke.run_all(
        expect_compiled=jax.default_backend() == "tpu",
        before_case=lambda name: dog.phase(f"kernel:{name}", t_each))
    dog.clear()
    for name, row in results.items():
        _log(f"kernel {name}: {row}")
    n_ok = sum(1 for r in results.values() if r.get("ok"))
    out = {"metric": "pallas kernel smoke", "value": n_ok,
           "unit": f"kernels_ok/{len(results)}", "vs_baseline": None,
           "kernels": results,
           "device": stub.get("device"), "platform": stub.get("platform")}
    print(json.dumps(out), flush=True)
    sys.exit(0 if n_ok == len(results) else 2)


def main():
    if "--analytic" in sys.argv:
        # chip-independent analytic snapshot (cost_analysis + roofline on
        # the CPU backend): no watchdog, no timed steps, no TPU required
        from paddle_tpu.perf import analytic
        sys.exit(analytic.main(sys.argv[1:]))
    model = os.environ.get("BENCH_MODEL", "lstm")
    # positional family name: `python bench.py serving` == BENCH_MODEL=serving
    for a in sys.argv[1:]:
        if not a.startswith("-") and a in _BENCHES:
            model = a
            break
    if "--smoke-kernels" in sys.argv:
        model = "smoke_kernels"
    steps = int(os.environ.get("BENCH_STEPS", "20"))
    t_init = float(os.environ.get("BENCH_INIT_TIMEOUT", "240"))
    # build runs eager param init: every distinct shape is its own XLA
    # compile
    t_build = float(os.environ.get("BENCH_BUILD_TIMEOUT", "900"))
    t_compile = float(os.environ.get("BENCH_COMPILE_TIMEOUT", "600"))
    t_steps = float(os.environ.get("BENCH_STEP_TIMEOUT", "600"))
    if os.environ.get("BENCH_PLATFORM"):
        os.environ["JAX_PLATFORMS"] = os.environ["BENCH_PLATFORM"]

    if os.environ.get("BENCH_DTYPE"):
        # explicit compute-dtype policy for the run (core/dtypes auto
        # policy already picks bf16 on TPU; BENCH_DTYPE=float32 measures
        # the f32 column, bfloat16 forces bf16 off-TPU)
        from paddle_tpu.core import dtypes as _dtypes
        _dtypes.set_policy(compute_dtype=os.environ["BENCH_DTYPE"])

    if model == "smoke_kernels":
        factory, default_batch = None, 0
    else:
        factory, default_batch = _BENCHES[model]
    batch = int(os.environ.get("BENCH_BATCH", str(default_batch or 0)))

    stub = {"metric": f"{model} (pending)", "value": None, "unit": "ms/batch",
            "vs_baseline": None}
    dog = Watchdog(stub)

    # -- phase 1: backend init --
    dog.phase("init", t_init)
    try:
        if model in _SUPERVISING_FAMILIES:
            # this process supervises replica subprocesses that need the
            # chips: it stays on the CPU platform itself (its "device"
            # fields below describe the supervising host, not the replicas)
            from paddle_tpu.serving.fleet import pin_parent_to_cpu
            pin_parent_to_cpu()
        import jax
        import jax.numpy as jnp
        from paddle_tpu.utils.flags import set_compilation_cache_dir
        set_compilation_cache_dir()
        platform, kind, ndev, peak = _device_info()
        # touch the device with a tiny op so init failures surface here,
        # not inside the model build
        jnp.zeros((8, 8)).block_until_ready()
    except Exception as e:  # noqa: BLE001
        dog.clear()
        stub.update(error="backend_unavailable", phase="init",
                    detail=f"{type(e).__name__}: {e}"[:800])
        _log(f"backend init FAILED: {e}")
        sys.exit(_emit_failure(stub))
    _log(f"backend up: platform={platform} device_kind={kind} n={ndev} "
         f"peak={peak / 1e12:.0f} TF/s")

    if model == "smoke_kernels":
        stub.update(device=kind, platform=platform)
        smoke_kernels(dog, stub)
        return

    # -- phase 2: build model + inputs (host-side) --
    dog.phase("build", t_build)
    try:
        built = factory(batch)
        run, flops, baseline_ms, metric = built[:4]
        baseline_ms = _resolve_baseline(model, batch, baseline_ms)
        extras = built[4] if len(built) > 4 else {}
    except Exception as e:  # noqa: BLE001
        dog.clear()
        stub.update(error="build_failed", phase="build",
                    detail=f"{type(e).__name__}: {e}"[:800])
        _log(f"model build FAILED: {e}")
        sys.exit(_emit_failure(stub))
    stub["metric"] = metric
    _log(f"model built: {metric}, analytic {flops / 1e9:.1f} GFLOP/step")

    # -- phase 3: compile + warmup --
    dog.phase("compile", t_compile)
    # dispatch truth for RNN models: snapshot the dispatcher's fused-path
    # counter around the compile — whether the kernels actually ran is
    # read from ops/rnn, never re-derived here (docs/kernels.md "Dispatch
    # truthfulness").  A kernel that fails to compile fails the run: there
    # is no retry on the scan path.
    from paddle_tpu.ops import rnn as _rnn_dispatch
    fused_count0 = _rnn_dispatch.FUSED_DISPATCH_COUNT
    try:
        t0 = time.perf_counter()
        loss = run(0)
        jax.block_until_ready(loss)
        compile_s = time.perf_counter() - t0
        for i in range(3):
            loss = run(i)
        jax.block_until_ready(loss)
    except Exception as e:  # noqa: BLE001
        dog.clear()
        stub.update(error="compile_failed", phase="compile",
                    detail=f"{type(e).__name__}: {e}"[:800])
        _log(f"compile FAILED: {e}")
        sys.exit(_emit_failure(stub))
    _log(f"compiled + warm in {compile_s:.1f}s, loss={float(loss):.4f}")

    # -- phase 4: timed steps --
    dog.phase("steps", t_steps)
    profile_dir = os.environ.get("BENCH_PROFILE_DIR")
    tracing = False
    try:
        if profile_dir:
            # xprof trace of the timed window (the round-2 verdict's MFU
            # analysis wants per-family profiles); capture is ~free
            jax.profiler.start_trace(profile_dir)
            tracing = True
        t0 = time.perf_counter()
        for i in range(steps):
            loss = run(i)
        jax.block_until_ready(loss)
        dt = (time.perf_counter() - t0) / steps
        if tracing:
            jax.profiler.stop_trace()
            tracing = False
            _log(f"xprof trace written to {profile_dir}")
    except Exception as e:  # noqa: BLE001
        dog.clear()
        stub.update(error="step_failed", phase="steps",
                    detail=f"{type(e).__name__}: {e}"[:800])
        _log(f"steps FAILED: {e}")
        if tracing:
            # flush the partial trace — it profiles exactly the failing run
            try:
                jax.profiler.stop_trace()
            except Exception:   # noqa: BLE001
                pass
        sys.exit(_emit_failure(stub))
    dog.clear()

    bp = extras.get("batches_per_step")
    if bp:
        # run() executes several batches (e.g. trainer_prefetch trains a
        # whole pass): normalize so value/flops stay per-BATCH like every
        # other row — the published unit is hardcoded "ms/batch".
        # tokens_per_step scales too: it is per run() call, and the
        # tokens_per_s derivation below divides by the per-batch dt
        dt /= bp
        flops /= bp
        if extras.get("tokens_per_step"):
            extras["tokens_per_step"] /= bp
    ms = dt * 1e3
    mfu = flops / dt / peak
    _log(f"{steps} steps, {ms:.3f} ms/batch, MFU={mfu * 100:.1f}%")
    out = {"metric": metric, "value": round(ms, 3), "unit": "ms/batch",
           "vs_baseline": round(baseline_ms / ms, 2) if baseline_ms else None,
           "mfu": round(mfu, 4),
           "device": kind, "platform": platform,
           "compile_s": round(compile_s, 1), "steps": steps,
           "flops_per_step": flops}
    if extras.get("tokens_per_step"):
        out["tokens_per_s"] = round(extras["tokens_per_step"] / dt)
    # any other extras pass through verbatim (remat, pack_efficiency,
    # quant, the trainer_prefetch steps/s pair, ...) so a family can add
    # a column without touching the harness; keys the harness itself
    # consumed are not metrics and stay out of the row, and callables
    # ("lower" — the analytic AOT hook — and "postcheck", the analytic
    # acceptance gate) are hooks, not metrics
    for k, v in extras.items():
        if k not in ("tokens_per_step", "batches_per_step") \
                and not callable(v) and k not in out:
            out[k] = v
    if model in _RNN_MODELS:
        # the executed path, from the dispatcher's own counter: tracing the
        # compile entered _fused_seq_apply iff the kernels ran
        out["fused_rnn"] = _rnn_dispatch.FUSED_DISPATCH_COUNT > fused_count0
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()

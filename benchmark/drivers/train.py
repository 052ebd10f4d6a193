"""Driver for a Paddle-classic network trained by ``SGD(...).train(reader)``.

The network is built through the layers DSL as a user would; the benchmark
owns the batches (traffic.train_batches), the clock, the reference check and
the trace.  The step loop is the program's own ``train()``: the reader hands
it pre-generated rows, the feeder converts them, the jitted step runs."""

import collections
import time

import numpy as np


def build_trainer(cfg, seed):
    """BASELINE.md's IMDB network through the layers DSL."""
    import paddle_tpu.layers as L
    from paddle_tpu import optim
    from paddle_tpu.layers import networks
    from paddle_tpu.layers.graph import reset_names
    from paddle_tpu.trainer import SGD
    reset_names()
    words = L.data_layer("w", size=cfg["vocab"], is_seq=True)
    label = L.data_layer("lab", size=1)
    x = L.embedding_layer(words, size=cfg["emb"])
    for _ in range(cfg["lstm_layers"]):
        x = networks.simple_lstm(x, size=cfg["hidden"])
    pooled = L.last_seq(x) if cfg["pooling"] == "last" \
        else L.pooling_layer(x, pooling_type=cfg["pooling"])
    probs = L.fc_layer(pooled, size=cfg["classes"], act="softmax")
    cost = L.classification_cost(probs, label)
    opt = dict(cfg["optimizer"])
    return SGD(cost=cost, seed=int(seed) % (2 ** 31 - 1) + 1,
               update_equation=getattr(optim, opt.pop("kind"))(**opt))


def reference_params(tree, cfg):
    """The trainer's parameter tree (names as the DSL numbers its layers) as
    the plain reference wants it."""
    fcs = [tree[f"__fc_{i}__"] for i in range(cfg["lstm_layers"] + 1)]
    return {"emb": tree["__embedding_0__"]["w"],
            "lstm": [{"w_in": fcs[i]["w0"],
                      "w_r": tree[f"__lstmemory_{i}__"]["w"],
                      "b7": tree[f"__lstmemory_{i}__"]["b"]}
                     for i in range(cfg["lstm_layers"])],
            "w_out": fcs[-1]["w0"], "b_out": fcs[-1]["b"]}


class StepLoop:
    """The reader and the event handler of one ``train()`` call.

    The reader hands out batches until ``n_steps`` are out or the clock has
    passed ``seconds``; then it waits for the step in flight and stops the
    clock THERE, so the measured time holds whole steps only.  The handler
    lets the host run ``lag`` steps ahead of the device and no more, and
    notes when each step's loss became ready."""

    def __init__(self, batches, lag, n_steps=None, seconds=None,
                 snapshot_at=None, trainer=None):
        self.batches, self.lag = batches, lag
        self.n_steps, self.seconds = n_steps, seconds
        self.snapshot_at, self.trainer, self.snapshot = \
            snapshot_at, trainer, None
        self.pending = collections.deque()
        self.costs, self.done_times, self.feed_wait = [], [], []
        self.t_open = self.t_close = self._t_yield = None

    def reader(self):
        i = 0
        self.t_open = time.perf_counter()
        while (self.n_steps is None or i < self.n_steps) and (
                self.seconds is None
                or time.perf_counter() - self.t_open < self.seconds):
            self._t_yield = time.perf_counter()
            yield self.batches[i % len(self.batches)]
            i += 1
        while self.pending:
            self._retire()
        self.t_close = time.perf_counter()

    def _retire(self):
        cost = self.pending.popleft()
        cost.block_until_ready()
        self.done_times.append(time.perf_counter())
        self.costs.append(cost)

    def on_event(self, e):
        from paddle_tpu.trainer import events
        if isinstance(e, events.BeginIteration):
            self.feed_wait.append(time.perf_counter() - self._t_yield)
            if e.batch_id == self.snapshot_at:
                import jax
                self.snapshot = jax.device_get(self.trainer.parameters)
        elif isinstance(e, events.EndIteration):
            self.pending.append(e.cost)
            while len(self.pending) > self.lag:
                self._retire()

    def losses(self):
        return [float(c) for c in self.costs]


def _train(trainer, loop, cfg):
    from paddle_tpu.data import integer_value, integer_value_sequence
    trainer.train(loop.reader, num_passes=1,
                  feeding={"w": integer_value_sequence(cfg["vocab"]),
                           "lab": integer_value(cfg["classes"])},
                  event_handler=loop.on_event, log_period=0,
                  buffered_batches=0)


def run(ctx):
    import jax
    import jax.numpy as jnp
    from benchmark import arith, harness, traffic
    from benchmark.reference import lstm as reference
    from paddle_tpu.core import dtypes
    from paddle_tpu.ops import rnn

    cfg, tr, phases = ctx["config"], ctx["traffic"], ctx["phases"]
    chips = ctx["cell"]["chips"]
    devices = jax.devices()[:chips]
    batches = traffic.train_batches(tr, ctx["seed"], cfg["vocab"])
    phases.mark("batches")

    fused_before = rnn.FUSED_DISPATCH_COUNT
    trainer = build_trainer(cfg, ctx["seed"])
    phases.mark("build")

    # warm-up: the step compiles (or is found in the cache) and the
    # parameters move, so the loss compared below is not ln 2 whatever the
    # network computes
    k = tr["check_step"]
    warm = StepLoop(batches, tr["lag_steps"], n_steps=tr["warmup_steps"],
                    snapshot_at=k, trainer=trainer)
    _train(trainer, warm, cfg)
    warm_losses = warm.losses()
    traces_after_warmup = trainer.trace_count
    phases.mark("warmup")

    rows = batches[k % len(batches)]
    tokens = jnp.asarray(np.stack([r[0] for r in rows]))
    labels = jnp.asarray(np.array([r[1] for r in rows], np.int32))
    ref_loss = float(jax.jit(reference.loss, static_argnums=(3,))(
        reference_params(warm.snapshot, cfg), tokens, labels, cfg["pooling"]))
    loss_err = abs(warm_losses[k] - ref_loss)
    phases.mark("reference_check")

    seconds = ctx["seconds"]
    if ctx["trace"]:
        seconds = min(seconds, tr["trace_seconds"])
    loop = StepLoop(batches, tr["lag_steps"], seconds=seconds)
    setup_s = time.perf_counter() - harness.T_PROCESS_START
    with harness.TraceWindow(ctx["trace"], ctx["trace_dir"]) as tw:
        _train(trainer, loop, cfg)
    losses = loop.losses()

    tail = losses[-20:]
    # every run says how even its steps were: a slow run is then a few long
    # steps (the host) or all of them (the device), without a trace
    gaps = sorted(g * 1e3 for g in arith.intervals(loop.done_times)[3:])
    checks = {
        "losses_finite": bool(np.isfinite(warm_losses + losses).all()),
        "loss_fell": bool(np.mean(tail) < 0.7 * warm_losses[0]),
        "loss_matches_reference": bool(
            loss_err <= cfg["loss_tolerance"] * max(1.0, abs(ref_loss))),
        "no_compile_in_window": trainer.trace_count == traces_after_warmup,
    }
    harness.say("checks", ctx["rehearsal"], **checks,
                first_loss=warm_losses[0], checked_loss=warm_losses[k],
                reference_loss=ref_loss, loss_abs_err=loss_err,
                mean_last20_loss=float(np.mean(tail)),
                step_traces=trainer.trace_count,
                compute_dtype=jnp.dtype(dtypes.compute_dtype()).name,
                steps=len(losses), elapsed_s=loop.t_close - loop.t_open,
                step_ms_p50=arith.percentile(gaps, 50),
                step_ms_p99=arith.percentile(gaps, 99),
                step_ms_max=gaps[-1] if gaps else None,
                memory_stats=devices[0].memory_stats())
    return {
        "correct": all(checks.values()),
        "attempted": len(losses), "failed": 0,
        "setup_s": setup_s, "devices": devices,
        "steps": len(losses),
        "tokens_per_step": tr["batch"] * tr["length"],
        "t_open": loop.t_open, "t_close": loop.t_close,
        "done_times": loop.done_times, "feed_wait": loop.feed_wait,
        "fused_dispatches": rnn.FUSED_DISPATCH_COUNT - fused_before,
        "trace": tw.reduced, "trace_cost": tw.cost,
    }

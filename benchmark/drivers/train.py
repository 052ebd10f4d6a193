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
    # ``trainer``: further arguments of SGD, for a test's control (the
    # program's own int8 path, quant_weights); the cells' files give none
    return SGD(cost=cost, seed=int(seed) % (2 ** 31 - 1) + 1,
               update_equation=getattr(optim, opt.pop("kind"))(**opt),
               **cfg.get("trainer", {}))


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
    notes when each step's loss became ready.  ``hold`` names what to keep
    for the reference, as device copies taken before a batch's step:
    {batch_id: "parameters" or "first_moment"} -> ``held[batch_id]``."""

    def __init__(self, batches, lag, n_steps=None, seconds=None, hold=None,
                 trainer=None):
        self.batches, self.lag = batches, lag
        self.n_steps, self.seconds = n_steps, seconds
        self.hold, self.trainer, self.held = hold or {}, trainer, {}
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
            if e.batch_id in self.hold:
                self.held[e.batch_id] = held_copy(
                    self.trainer, self.hold[e.batch_id])
        elif isinstance(e, events.EndIteration):
            self.pending.append(e.cost)
            while len(self.pending) > self.lag:
                self._retire()

    def losses(self):
        return [float(c) for c in self.costs]


def held_copy(trainer, what):
    """A copy on the device (the step donates its operands) of the
    trainer's parameters, or of Adam's first moment, which after ONE step is
    (1 - beta1) x the gradient as the optimizer got it."""
    import jax
    import jax.numpy as jnp
    tree = trainer.parameters if what == "parameters" \
        else trainer.opt_state["slots"]["m"]
    return jax.tree_util.tree_map(jnp.copy, tree)


def _train(trainer, loop, cfg):
    from paddle_tpu.data import integer_value, integer_value_sequence
    trainer.train(loop.reader, num_passes=1,
                  feeding={"w": integer_value_sequence(cfg["vocab"]),
                           "lab": integer_value(cfg["classes"])},
                  event_handler=loop.on_event, log_period=0,
                  buffered_batches=0)


def _arrays(rows):
    import jax.numpy as jnp
    return (jnp.asarray(np.stack([r[0] for r in rows])),
            jnp.asarray(np.array([r[1] for r in rows], np.int32)))


def _norms(tree):
    from benchmark.reference import lstm as reference
    return {k: float(np.linalg.norm(np.asarray(v, np.float64)))
            for k, v in reference.leaves(tree).items()}


def _moved(after, before):
    import jax
    return jax.tree_util.tree_map(lambda a, b: a - b, after, before)


def compare_with_reference(cfg, batches, k, start, held, warm_losses,
                           stated):
    """The warm-up steps, which went through the timed call and feed, held
    to the plain reference: {name: [reading, limit]} (a limit of None: read,
    not judged) and the facts for the ``checks`` line.  The reference is
    computed as the configuration STATES the program computes (``stated``:
    both operands of every matrix product rounded to that type, float32
    sums and state; float32 throughout where it states float32), because
    the rounding of the weights is no noise: it moves the program's loss and
    the reference's alike, by up to 7e-3 on some seeds.  Two comparisons
    (``reference_check.why`` in the configuration has the readings behind
    every limit):

    - the reference FOLLOWS the first ``follow_steps`` steps from the
      program's initial parameters with its own gradients and its own Adam:
      each step's loss, the norm of the first gradient as the optimizer got
      it and the norm of the parameters' change after those steps, the last
      two by the worst leaf.  A leaf whose reference gradient is under
      ``dead_leaf_share`` of the median leaf's moves under Adam by round-off
      alone and is left out of the change;
    - the loss of step ``k``, late enough that the parameters have moved and
      the logits are no longer all but nought, against the reference's on the
      parameters held before it.  What is left between the two (the order of
      sums, the transcendentals) is amplified by the batch as the operands'
      rounding is, so the limit is a share of what that rounding does to
      this batch's rows (arith.loss_noise: the reference as stated against
      the reference in float32 at ``highest``), plus a few float32 steps of
      the loss itself."""
    import jax
    import jax.numpy as jnp
    from benchmark import arith
    from benchmark.reference import lstm as reference
    rc = cfg["reference_check"]
    opt = {name: v for name, v in cfg["optimizer"].items() if name != "kind"}
    n = rc["follow_steps"]
    operands = None if stated == jnp.float32 else stated
    grad_fn = jax.jit(reference.loss_and_grad, static_argnums=(3, 4, 5))
    adam_fn = jax.jit(lambda p, m, v, t, g:
                      reference.adam_step(p, m, v, t, g, opt))
    p0 = p = reference_params(start, cfg)
    m = v = jax.tree_util.tree_map(jnp.zeros_like, p)
    compared, first = {}, None
    for i in range(n):
        loss, grads = grad_fn(p, *_arrays(batches[i % len(batches)]),
                              cfg["pooling"], rc["row_blocks"], operands)
        first = grads if first is None else first
        compared[f"loss_err_step{i}"] = [
            abs(warm_losses[i] - float(loss)), rc["follow_loss_limits"][i]]
        p, m, v = adam_fn(p, m, v, float(i + 1), grads)
    want = _norms(first)
    scale = 1.0 / (1.0 - opt.get("beta1", 0.9))
    got = _norms(jax.tree_util.tree_map(
        lambda x: x * scale, reference_params(held[1], cfg)))
    gap, leaf = arith.worst_leaf_gap(got, want)
    compared["grad_norm_gap"] = [gap, rc["grad_norm_limit"]]
    floor = rc["dead_leaf_share"] * arith.percentile(list(want.values()), 50)
    live = [name for name, x in want.items() if x >= floor]
    facts = {"grad_norm_gap_leaf": leaf,
             "dead_leaves": sorted(set(want) - set(live))}
    gap, leaf = arith.worst_leaf_gap(
        _norms(_moved(reference_params(held[n], cfg), p0)),
        _norms(_moved(p, p0)), live)
    compared["change_norm_gap"] = [gap, rc["change_norm_limit"]]
    facts["change_norm_gap_leaf"] = leaf

    margins_fn = jax.jit(reference.loss_and_margins, static_argnums=(3, 4))
    at_k = reference_params(held[k], cfg), *_arrays(batches[k % len(batches)])
    ref_loss, as_stated = margins_fn(*at_k, cfg["pooling"], operands)
    exact_loss, noise = ref_loss, 0.0
    if operands is not None:
        exact_loss, exact = margins_fn(*at_k, cfg["pooling"], None)
        noise = arith.loss_noise(np.asarray(exact, np.float64).tolist(),
                                 np.asarray(as_stated, np.float64).tolist())
    compared["loss_abs_err"] = [
        abs(warm_losses[k] - float(ref_loss)),
        arith.loss_limit(rc, noise)]
    facts.update(checked_loss=warm_losses[k], reference_loss=float(ref_loss),
                 reference_loss_float32=float(exact_loss),
                 stated_precision_noise=noise)
    return compared, facts


def run(ctx):
    import jax
    import jax.numpy as jnp
    from benchmark import arith, harness, traffic
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

    # warm-up: the step compiles (or is found in the cache), and its first
    # steps are the ones the reference is held against once the window has
    # closed: what it needs of them is kept on the device meanwhile
    k, n = tr["check_step"], cfg["reference_check"]["follow_steps"]
    start = held_copy(trainer, "parameters")
    warm = StepLoop(batches, tr["lag_steps"], n_steps=tr["warmup_steps"],
                    hold={1: "first_moment", n: "parameters",
                          k: "parameters"}, trainer=trainer)
    _train(trainer, warm, cfg)
    warm_losses = warm.losses()
    traces_after_warmup = trainer.trace_count
    phases.mark("warmup")

    seconds = ctx["seconds"]
    if ctx["trace"]:
        seconds = min(seconds, tr["trace_seconds"])
    loop = StepLoop(batches, tr["lag_steps"], seconds=seconds)
    setup_s = time.perf_counter() - harness.T_PROCESS_START
    with harness.TraceWindow(ctx["trace"], ctx["trace_dir"]) as tw:
        _train(trainer, loop, cfg)
    losses = loop.losses()

    # the window has closed: read the peak and the program's counters, let
    # its state go, and only then run the reference
    memory_peak = harness.memory_peak_bytes(devices)
    memory_stats = devices[0].memory_stats()
    step_traces = trainer.trace_count
    fused = rnn.FUSED_DISPATCH_COUNT - fused_before
    stated = jnp.dtype(dtypes.compute_dtype())
    warm.trainer = trainer = None
    t_reference = time.perf_counter()
    compared, facts = compare_with_reference(
        cfg, batches, k, start, warm.held, warm_losses, stated)
    facts["reference_s"] = time.perf_counter() - t_reference

    tail = losses[-20:]
    # every run says how even its steps were: a slow run is then a few long
    # steps (the host) or all of them (the device), without a trace
    gaps = sorted(g * 1e3 for g in arith.intervals(loop.done_times)[3:])
    # a window with fewer steps than a seed may need to learn says nothing of
    # whether the loss fell: the check reads null there and is not counted
    fell_limit = 0.7 * warm_losses[0]
    fell_judged = len(losses) >= tr["loss_fell_min_steps"]
    compared["mean_last20_loss"] = [float(np.mean(tail)),
                                    fell_limit if fell_judged else None]
    compared["step_traces"] = [step_traces, traces_after_warmup]
    within = {name: bool(value <= limit)
              for name, (value, limit) in compared.items()
              if limit is not None}
    checks = {
        "losses_finite": bool(np.isfinite(warm_losses + losses).all()),
        "loss_fell": within.get("mean_last20_loss"),
        "first_steps_follow_reference": all(
            ok for name, ok in within.items()
            if name.startswith("loss_err_step") or name.endswith("_gap")),
        "loss_matches_reference": within["loss_abs_err"],
        "no_compile_in_window": step_traces == traces_after_warmup,
    }
    harness.say("checks", ctx["rehearsal"], **checks,
                loss_fell_judged=fell_judged,
                loss_fell_min_steps=tr["loss_fell_min_steps"],
                steps_to_fall=arith.steps_to_fall(losses, warm_losses[0]),
                first_loss=warm_losses[0], **facts, compared=compared,
                mean_last20_loss=float(np.mean(tail)),
                step_traces=step_traces, compute_dtype=stated.name,
                steps=len(losses), elapsed_s=loop.t_close - loop.t_open,
                step_ms_p50=arith.percentile(gaps, 50),
                step_ms_p99=arith.percentile(gaps, 99),
                step_ms_max=gaps[-1] if gaps else None,
                memory_stats=memory_stats)
    return {
        "correct": all(v for v in checks.values() if v is not None),
        "compared": compared,
        "attempted": len(losses), "failed": 0,
        "setup_s": setup_s, "devices": devices,
        "memory_peak_bytes": memory_peak,
        "steps": len(losses),
        "tokens_per_step": tr["batch"] * tr["length"],
        "t_open": loop.t_open, "t_close": loop.t_close,
        "done_times": loop.done_times, "feed_wait": loop.feed_wait,
        "fused_dispatches": fused,
        "trace": tw.reduced, "trace_cost": tw.cost,
    }

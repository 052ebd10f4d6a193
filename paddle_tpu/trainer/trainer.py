"""The training driver: v2-style `SGD.train(reader, event_handler)`.

Reference: python/paddle/v2/trainer.py:30-175 (SGD class, event loop),
trainer/Trainer.cpp:261-492 (pass/batch loops, periodic save/test),
trainer/TrainerInternal.cpp:66-170 (the hot loop: forward/backward/update +
eval + log).

TPU redesign: the entire hot loop — forward, backward, optimizer update,
evaluator statistics — is ONE jitted (and mesh-sharded) function.  The
reference's updater pipeline (grad-ready callbacks overlapping backward with
pserver sends, RemoteParameterUpdater.h:37-54) is subsumed by XLA scheduling
collectives inside the step; async host-side data feeding comes from
reader.buffered (the DoubleBuffer equivalent).
"""

import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.obs import trace as _obstrace
from paddle_tpu.core.sequence import (NestedSequenceBatch,
                                      SequenceBatch)
from paddle_tpu.resilience import faults as _faults
from paddle_tpu.data.feeder import DataFeeder
from paddle_tpu.data import reader as reader_mod
from paddle_tpu.layers.graph import Topology, LayerOutput
from paddle_tpu.optim.optimizers import Optimizer
from paddle_tpu.trainer import events
from paddle_tpu.trainer import hooks as param_hooks
from paddle_tpu.trainer.checkpoint import save_checkpoint, load_checkpoint
from paddle_tpu.utils.error import ConfigError
from paddle_tpu.utils.logging import logger
from paddle_tpu.utils.stats import timer, global_stats
from paddle_tpu.ops import rnn as _rnn_ops
from paddle_tpu.parallel import (
    make_mesh, param_shardings, batch_shardings, replicated_shardings,
    shard_params)
from paddle_tpu.parallel.mesh import AXIS_DATA



def _normalize_feed(feed):
    """Device-ready feed: (Nested)SequenceBatch pass through, everything
    else becomes a jnp array."""
    return {k: v if isinstance(v, (SequenceBatch, NestedSequenceBatch))
            else jnp.asarray(v)
            for k, v in feed.items()}


def _feed_signature(feed):
    """Hashable (treedef, leaf shapes/dtypes) key for a feed pytree — the
    dispatch key of the AOT-precompiled step executables (one per length
    bucket).  Works for concrete arrays and jax.ShapeDtypeStructs alike."""
    leaves, treedef = jax.tree_util.tree_flatten(feed)
    return (treedef,
            tuple((tuple(l.shape), np.dtype(l.dtype).name) for l in leaves))


def _abstract_feed(feed):
    """Feed pytree -> same pytree of jax.ShapeDtypeStructs (leaves that
    already are ShapeDtypeStructs pass through)."""
    return jax.tree_util.tree_map(
        lambda l: l if isinstance(l, jax.ShapeDtypeStruct)
        else jax.ShapeDtypeStruct(np.shape(l), l.dtype), feed)


class SGD:
    """paddle.v2.trainer.SGD equivalent.

    cost: LayerOutput (or list) whose value is a per-sample loss [B].
    update_equation: an optim.Optimizer.
    extra_layers: additional LayerOutputs to evaluate each batch (for
    metrics; reference SGD(extra_layers=) used for evaluators).
    mesh: jax Mesh (None = single device); sharding_rules: parallel.ShardingRules.
    """

    def __init__(self, cost, parameters=None, update_equation=None,
                 extra_layers=None, is_local=True, mesh=None,
                 sharding_rules=None, seed=1, donate=True, evaluators=None,
                 compute_dtype=None, grad_accum_steps=1,
                 quant_weights=False, quant_min_size=1024):
        self.costs = cost if isinstance(cost, (list, tuple)) else [cost]
        self.extra_layers = list(extra_layers or [])
        # evaluator specs (evaluators.dsl): fetch their bound layers as
        # extra outputs; labels/weights come straight from the feed
        self.evaluators = list(evaluators or [])
        self._eval_slots = []
        self._eval_extra_slots = []   # per spec: {kw: ('feed', name)|('extra', i)}

        def slot_for(layer):
            if layer.layer_type == "data":
                return ("feed", layer.name)
            if layer in self.extra_layers:
                return ("extra", self.extra_layers.index(layer))
            self.extra_layers.append(layer)
            return ("extra", len(self.extra_layers) - 1)

        for spec in self.evaluators:
            self._eval_slots.append(slot_for(spec.input))
            self._eval_extra_slots.append(
                {kw: slot_for(l) for kw, l in spec.extra_inputs.items()})
        self.topology = Topology(list(self.costs) + self.extra_layers)
        if update_equation is None:
            raise ValueError(
                "SGD needs update_equation=, e.g. "
                "optim.Momentum(learning_rate=0.01)")
        self.optimizer: Optimizer = update_equation
        # mixed precision, the TPU-native way: master params stay f32 (the
        # optimizer state/update precision), forward+backward run in
        # compute_dtype (jnp.bfloat16) — halves HBM traffic and feeds the
        # MXU its native input width.  bf16's f32-equal exponent range
        # makes loss scaling unnecessary (unlike fp16).  The cast happens
        # inside the loss, so autodiff returns f32 master grads.
        self.compute_dtype = compute_dtype
        self.mesh = mesh
        self.sharding_rules = sharding_rules
        rng = jax.random.PRNGKey(seed)
        self.rng, init_rng = jax.random.split(rng)
        self.parameters = parameters if parameters is not None \
            else self.topology.init(init_rng)
        self._sparse_specs = self._find_sparse_specs()
        # static pruning hooks (reference ParameterUpdaterHook.cpp:36):
        # mask values once at init, mask grads every step
        self._prune_masks = param_hooks.build_masks(
            self.topology, self.parameters)
        for k in self._prune_masks:
            if k in self._sparse_specs:
                raise ConfigError(
                    f"pruning hook on {k!r}: sparse_update tables can't be "
                    "statically pruned (the row path rewrites the table)")
        if self._prune_masks:
            self.parameters = param_hooks.apply_masks(
                self.parameters, self._prune_masks)
        # validate BEFORE allocating optimizer slots (a sparse-incompatible
        # setting must not first build full-vocab [V, D] slot tables)
        self.grad_accum_steps = int(grad_accum_steps)
        if self.grad_accum_steps < 1:
            raise ConfigError("grad_accum_steps must be >= 1")
        if self.grad_accum_steps > 1 and self._sparse_specs:
            raise ConfigError(
                "grad_accum_steps > 1 is unsupported with sparse_update "
                "embeddings (touched-row sets differ per micro-batch)")
        # int8 weight-streaming training (quant/weights.py, the serving
        # quant_weights scheme turned on the train step): the jitted
        # step is fed {"master": f32 tree, "q": int8+scale tree},
        # forward/backward run over the dequantized view (widening fuses
        # into each matmul's operand read), the optimizer updates the
        # f32 masters and the step requantizes them before returning —
        # so between steps the weight STREAM the forward pass reads is
        # int8 bytes + scale sidecars, and the f32 masters are touched
        # once, optimizer-side.  Deterministic requantization is what
        # makes kill-9 resume bit-identical.
        self._quant = bool(quant_weights)
        self._quant_min_size = int(quant_min_size)
        if self._quant:
            if self._sparse_specs:
                raise ConfigError(
                    "quant_weights=True is unsupported with sparse_update "
                    "embeddings (row-sliced tables have no per-out-channel "
                    "scale home)")
            if mesh is not None:
                raise ConfigError(
                    "quant_weights=True is single-chip for now (sharding "
                    "the int8+scale pair tree is the named residual)")
            if self.grad_accum_steps > 1:
                raise ConfigError(
                    "quant_weights=True with grad_accum_steps > 1 is "
                    "unsupported (the held-grads window would read stale "
                    "quantized weights)")
            if self.compute_dtype is not None:
                raise ConfigError(
                    "quant_weights=True already streams int8 weights; "
                    "combining it with compute_dtype is unsupported")
        dense_params = {k: v for k, v in self.parameters.items()
                        if k not in self._sparse_specs}
        self.opt_state = self.optimizer.init(dense_params) \
            if self.optimizer else None
        if self._sparse_specs and self.optimizer:
            # full-table optimizer slots for sparse embeddings; only touched
            # rows are gathered/updated/scattered each step (reference
            # SparseRowMatrix semantics)
            self.opt_state = {
                "dense": self.opt_state,
                "sparse": {k: self.optimizer.row_init(self.parameters[k])
                           for k in self._sparse_specs}}
        # gradient accumulation (reference num_batches_per_send_parameter's
        # local-accumulate, RemoteParameterUpdater.h:37-54): grads sum over
        # N micro-batches, the optimizer applies their mean every Nth —
        # still ONE jitted step (lax.cond-gated apply), so a big effective
        # batch fits any HBM.  Checkpointed with opt_state: resume keeps
        # mid-accumulation progress.
        if self.grad_accum_steps > 1:
            self.opt_state = {
                "inner": self.opt_state,
                "gsum": jax.tree_util.tree_map(jnp.zeros_like, dense_params),
                "tick": jnp.zeros((), jnp.int32)}
        self.model_state = self.topology.init_state()
        # multi-controller SPMD: the mesh spans devices owned by OTHER
        # processes (jax.distributed bring-up).  Every process must then
        # run the same program on the same host batches; feeds and rng are
        # assembled into global arrays (see _globalize) and checkpoints
        # gather-then-write on process 0 only.
        self._multiprocess = mesh is not None and any(
            d.process_index != jax.process_index()
            for d in np.asarray(mesh.devices).flat)
        # latest cross-rank straggler report (parallel.distributed.
        # step_skew_report), refreshed at each pass end in multi-process
        # runs (pass end is the only point every rank reaches
        # unconditionally, so the collective cannot deadlock there)
        self.last_skew_report = None
        if mesh is not None:
            rules = sharding_rules
            if self._multiprocess:
                # device_put cannot target non-addressable devices; build
                # global arrays from the (identical-per-process) host values
                ps = param_shardings(self.parameters, mesh, rules)
                self.parameters = self._globalize(self.parameters, ps)
            else:
                self.parameters = shard_params(self.parameters, mesh, rules)
        # the int8 twin of self.parameters: ONLY the quantized leaves
        # (masters carry the small f32 leaves — duplicating them in the
        # bundle would donate the same buffer twice).  Always the
        # masters' deterministic requantization; rebuilt by the step
        # every update.
        self._qtree = None
        if self._quant:
            self._qtree = self._requant(self.parameters)
        self._step_fn = None
        self._eval_fn = None
        self._gather_cache = {}   # jitted replicate-gathers (save path)
        self._compiled = {}       # feed signature -> AOT step executable
        # incremented each time the step's Python body is traced — the
        # trace-count hook: after precompile() covers every bucket, a
        # whole training pass must leave this unchanged
        self.trace_count = 0
        self._donate = donate

    # ------------------------------------------------------------ build

    def _find_sparse_specs(self):
        """Embedding layers flagged sparse_update=True whose ids come
        straight from a data layer (reference sparse-remote-update
        constraint: the sparse table's input slot).  Returns
        {param_key: {"feeds": [...], "vocab": V, "budget": K}}."""
        from paddle_tpu.ops.sparse import default_row_budget
        from paddle_tpu.utils.error import ConfigError
        specs = {}
        for node in self.topology.order:
            if node.layer_type != "embedding" \
                    or not node.cfg.get("sparse_update"):
                continue
            src = node.inputs[0]
            if src.layer_type != "data":
                raise ConfigError(
                    f"sparse_update embedding {node.name!r} needs a data "
                    "layer input (ids straight from the feed)")
            consumers = [n for n in self.topology.order
                         if src in n.inputs and n is not node]
            if consumers:
                raise ConfigError(
                    f"sparse_update embedding {node.name!r}: its id input "
                    f"{src.name!r} also feeds {consumers[0].name!r}; the "
                    "sparse path rewrites that feed and would corrupt it")
            key = self.topology._param_key(node)
            spec = specs.setdefault(
                key, {"feeds": [], "vocab": node.cfg["vocab"],
                      "budget": node.cfg.get("sparse_budget"),
                      "_nodes": set()})
            spec["feeds"].append(src.name)
            spec["_nodes"].add(id(node))
        # a sparse param key must not be shared with any NON-sparse layer:
        # sparse_step swaps params[key] for the gathered row block, which
        # would silently corrupt another reader of the full table
        for node in self.topology.order:
            key = self.topology._param_key(node)
            if key in specs and id(node) not in specs[key]["_nodes"] \
                    and node.layer_type != "data":
                raise ConfigError(
                    f"sparse_update table {key!r} is shared with layer "
                    f"{node.name!r} ({node.layer_type}), which would read "
                    "the gathered row block instead of the full table; "
                    "share only among sparse_update embeddings")
        return specs

    def _cast_compute(self, tree):
        """float32 leaves -> compute_dtype (ids, masks, lengths untouched).
        SequenceBatch data casts; lengths stay int."""
        from paddle_tpu.core.dtypes import cast_tree
        return cast_tree(tree, self.compute_dtype)

    def _loss_and_extras(self, params, state, feed, rng):
        if self.compute_dtype is not None:
            params = self._cast_compute(params)
            feed = self._cast_compute(feed)
        out, new_state = self.topology.apply(
            params, feed, mode="train", rng=rng, state=state,
            return_state=True)
        outs = out if isinstance(out, tuple) else (out,)
        n_cost = len(self.costs)
        cost_vals = outs[:n_cost]
        extra_vals = outs[n_cost:]
        # reductions in f32 regardless of compute dtype (bf16 has ~8 bits
        # of mantissa; a batch-mean in bf16 loses the loss signal)
        total = sum(jnp.mean(c.astype(jnp.float32)) for c in cost_vals)
        return total, (new_state, extra_vals)

    def _build_step(self, feed_example):
        specs = self._sparse_specs
        if specs:
            from paddle_tpu.ops import sparse as sparse_ops

            def budget_for(k, feed):
                """Static row budget derived from the TRACED feed shapes —
                jit retraces per batch shape, so a later, larger batch gets
                a larger budget instead of silently truncating the
                jnp.unique id set."""
                if specs[k]["budget"]:
                    return specs[k]["budget"]
                n = 0
                for f in specs[k]["feeds"]:
                    v = feed[f]
                    d = v.data if isinstance(v, SequenceBatch) else v
                    n += int(np.prod(d.shape))
                return sparse_ops.default_row_budget(n)

        prune_masks = self._prune_masks

        accum = self.grad_accum_steps

        def dense_step(params, opt_state, state, feed, rng):
            (loss, (new_state, extras)), grads = jax.value_and_grad(
                self._loss_and_extras, has_aux=True)(params, state, feed, rng)
            if prune_masks:
                grads = param_hooks.apply_masks(grads, prune_masks)
            if accum > 1:
                gsum = jax.tree_util.tree_map(
                    jnp.add, opt_state["gsum"], grads)
                tick = opt_state["tick"] + 1

                def apply(_):
                    mean_g = jax.tree_util.tree_map(
                        lambda s: s / accum, gsum)
                    p2, o2 = self.optimizer.update(
                        mean_g, opt_state["inner"], params)
                    return (p2, o2,
                            jax.tree_util.tree_map(jnp.zeros_like, gsum),
                            jnp.zeros((), jnp.int32))

                def hold(_):
                    return params, opt_state["inner"], gsum, tick

                new_params, inner, gsum, tick = jax.lax.cond(
                    tick >= accum, apply, hold, None)
                new_opt = {"inner": inner, "gsum": gsum, "tick": tick}
            else:
                new_params, new_opt = self.optimizer.update(
                    grads, opt_state, params)
            merged_state = {**state, **new_state}
            return new_params, new_opt, merged_state, loss, extras

        def sparse_step(params, opt_state, state, feed, rng):
            """The large-vocab path: differentiate w.r.t. the gathered
            touched-row blocks, not the [V, D] tables — the id feeds are
            rewritten to positions into those blocks so the graph runs
            unchanged (reference SparseRowMatrix + sparse remote update,
            RemoteParameterUpdater.h:265)."""
            feed = dict(feed)
            uids_map, rows_map = {}, {}
            for k, spec in specs.items():
                flats, places = [], []
                for f in spec["feeds"]:
                    v = feed[f]
                    d = v.data if isinstance(v, SequenceBatch) else v
                    flats.append(d.reshape(-1))
                    places.append((f, v, d.shape))
                allids = (jnp.concatenate(flats) if len(flats) > 1
                          else flats[0])
                uids, inv = sparse_ops.unique_touched(
                    allids, budget_for(k, feed), spec["vocab"])
                off = 0
                for f, v, shp in places:
                    n = int(np.prod(shp))
                    iv = inv[off:off + n].reshape(shp)
                    off += n
                    feed[f] = (SequenceBatch(data=iv, lengths=v.lengths)
                               if isinstance(v, SequenceBatch) else iv)
                uids_map[k] = uids
                rows_map[k] = jax.tree_util.tree_map(
                    lambda t, u=uids: sparse_ops.gather_rows(t, u),
                    params[k])

            dense_params = {k2: v for k2, v in params.items()
                            if k2 not in specs}

            def loss_fn(dp, rp):
                return self._loss_and_extras({**dp, **rp}, state, feed, rng)

            (loss, (new_state, extras)), (dg, rg) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(dense_params, rows_map)
            dstate = opt_state["dense"]
            # global-norm clipping must see ONE norm across the split grad
            # tree (dense + row blocks) or sparse/dense training diverge;
            # and like the dense path it measures AFTER the elementwise
            # clip_threshold (optim._clip applies threshold before norm)
            clip_scale = None
            if getattr(self.optimizer, "clip_norm", None):
                ct = getattr(self.optimizer, "clip_threshold", None)
                leaves = jax.tree_util.tree_leaves((dg, rg))
                if ct:
                    leaves = [jnp.clip(g, -ct, ct) for g in leaves]
                gn = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                  for g in leaves) + 1e-12)
                clip_scale = jnp.minimum(1.0, self.optimizer.clip_norm / gn)
            new_dense, new_dstate = self.optimizer.update(
                dg, dstate, dense_params, clip_scale=clip_scale)
            new_params = dict(new_dense)
            new_sparse = {}
            for k in specs:
                u = uids_map[k]
                slot_rows = jax.tree_util.tree_map(
                    lambda t, u=u: sparse_ops.gather_rows(t, u),
                    opt_state["sparse"][k])
                new_rows, new_slot_rows = self.optimizer.row_update(
                    rg[k], slot_rows, rows_map[k], dstate["step"],
                    clip_scale=clip_scale)
                new_params[k] = jax.tree_util.tree_map(
                    lambda t, nr, u=u: sparse_ops.scatter_rows(t, u, nr),
                    params[k], new_rows)
                new_sparse[k] = jax.tree_util.tree_map(
                    lambda t, nr, u=u: sparse_ops.scatter_rows(t, u, nr),
                    opt_state["sparse"][k], new_slot_rows)
            merged_state = {**state, **new_state}
            return (new_params, {"dense": new_dstate, "sparse": new_sparse},
                    merged_state, loss, extras)

        def quant_step(params, opt_state, state, feed, rng):
            """The int8 weight-streaming step: params is the {"master",
            "q"} bundle — q holds the int8+scale pairs for the big 2-D
            weights, master the f32 tree.  Forward/backward
            differentiate the DEQUANTIZED view (straight-through: the
            int8 grid is piecewise-constant, so grads at the dequantized
            values are the estimator — the mixed-precision master-weight
            recipe with int8 in place of bf16); the optimizer applies
            them to the f32 masters and the new masters requantize
            IN-step, so the returned bundle is self-consistent and
            checkpoint/resume carries both trees."""
            from paddle_tpu.quant import weights as qw
            from jax.tree_util import keystr, tree_map_with_path
            masters, qtree = params["master"], params["q"]
            # forward tree: the dequantized int8 view overlaid on the
            # masters' small f32 leaves (biases/norms — their bytes are
            # noise; this is what keeps the weight STREAM int8)
            fwd = tree_map_with_path(
                lambda path, x: qw.dequantize_leaf(qtree[keystr(path)])
                if keystr(path) in qtree else x, masters)
            (loss, (new_state, extras)), grads = jax.value_and_grad(
                self._loss_and_extras, has_aux=True)(fwd, state, feed, rng)
            if prune_masks:
                grads = param_hooks.apply_masks(grads, prune_masks)
            new_masters, new_opt = self.optimizer.update(
                grads, opt_state, masters)
            new_q = {}
            tree_map_with_path(
                lambda path, x: new_q.update(
                    {keystr(path): qw.quantize_leaf(x)})
                if keystr(path) in qtree else x, new_masters)
            merged_state = {**state, **new_state}
            return ({"master": new_masters, "q": new_q}, new_opt,
                    merged_state, loss, extras)

        base_step = quant_step if self._quant else (
            sparse_step if specs else dense_step)

        def step(params, opt_state, state, feed, rng):
            # Python body runs only under tracing: this is the trace-count
            # hook precompile()'s no-retrace guarantee is asserted against
            self.trace_count += 1
            # a multi-device mesh: the fused RNN kernels take their batch
            # shard through shard_map (GSPMD cannot partition them)
            with _rnn_ops.batch_sharded_over(self.mesh, AXIS_DATA):
                return base_step(params, opt_state, state, feed, rng)

        if self.mesh is None:
            self._step_fn = jax.jit(
                step, donate_argnums=(0, 1) if self._donate else ())
            return

        ps = param_shardings(self.parameters, self.mesh, self.sharding_rules)
        # optimizer slots are params-shaped: inherit the param shardings
        # (the reference keeps momentum etc. sharded in the pserver the same
        # way, ParameterServer2 block-indexed buffers)
        def dense_state_shardings(dstate, dense_ps):
            if isinstance(dstate, dict) and "gsum" in dstate:
                # grad-accumulation wrapper: the accumulator shards like
                # the grads it sums (= the params), the tick replicates
                return {"inner": dense_state_shardings(dstate["inner"],
                                                       dense_ps),
                        "gsum": dense_ps,
                        "tick": replicated_shardings(dstate["tick"],
                                                     self.mesh)}
            if isinstance(dstate, dict) and "slots" in dstate:
                return {"step": replicated_shardings(dstate["step"],
                                                     self.mesh),
                        "slots": {k: dense_ps for k in dstate["slots"]}}
            return replicated_shardings(dstate, self.mesh)

        if specs:
            dense_ps = {k: v for k, v in ps.items() if k not in specs}
            os_ = {"dense": dense_state_shardings(self.opt_state["dense"],
                                                  dense_ps),
                   "sparse": {k: {slot: ps[k]
                                  for slot in self.opt_state["sparse"][k]}
                              for k in specs}}
        else:
            os_ = dense_state_shardings(self.opt_state, ps)
        ss = replicated_shardings(self.model_state, self.mesh)
        fs = batch_shardings(feed_example, self.mesh)
        rs = replicated_shardings(jnp.zeros(2, jnp.uint32), self.mesh)
        if not self._multiprocess:
            # place the optimizer and model state where the step returns
            # them: under jax 0.9 an array's mesh is part of its traced
            # type, so fresh single-device state on the first call and
            # mesh-placed state on the second would trace the step twice
            self.opt_state = jax.device_put(self.opt_state, os_)
            self.model_state = jax.device_put(self.model_state, ss)
        self._step_fn = jax.jit(
            step,
            in_shardings=(ps, os_, ss, fs, rs),
            out_shardings=(ps, os_, ss,
                           replicated_shardings(0.0, self.mesh),
                           None),
            donate_argnums=(0, 1) if self._donate else ())

    # ------------------------------------------------------------ train

    def _globalize(self, tree, shardings):
        """Host pytree -> global jax.Arrays (parallel.sharding.
        globalize_pytree).  Already-global leaves (e.g. fresh-init params
        kept by a load_parameters 'rand' merge) are gathered to host
        first."""
        from paddle_tpu.parallel.sharding import globalize_pytree
        return globalize_pytree(tree, shardings,
                                gather=self._devget_replicated)

    def _globalize_step_inputs(self, feed, step_rng):
        if not self._multiprocess:
            return feed, step_rng
        feed = self._globalize(feed, batch_shardings(feed, self.mesh))
        return feed, self._globalize_rng(step_rng)

    def _globalize_rng(self, step_rng):
        """rng half of _globalize_step_inputs — the prefetch path already
        globalized the feed on the producer thread."""
        if not self._multiprocess:
            return step_rng
        return self._globalize(
            step_rng, replicated_shardings(step_rng, self.mesh))

    # ------------------------------------------------------------ warm-up

    def precompile(self, batch_specs):
        """AOT warm-up: compile the train step once per feed spec so a
        bucketed pass never pays an XLA compile inside the timed loop.

        batch_specs: iterable of feed dicts {data_layer_name: leaf} where
        a leaf is a concrete array, a ``jax.ShapeDtypeStruct``, or a
        SequenceBatch of either — one spec per length bucket.
        ``DataFeeder.feed_specs(batch_size, bucket_bounds)`` builds them
        from the feeding types + ``core.sequence.bucket_boundaries``.

        Each spec is lowered and compiled via ``jax.jit(step).lower(...)
        .compile()`` and the executable is dispatched by feed shape in
        ``train()``/``train_one_batch()`` — a subsequent pass over those
        buckets triggers no new traces (assert with ``trace_count``).
        Returns the number of NEW executables compiled.  Pair with the
        persistent compile cache (utils/flags.set_compilation_cache_dir) to keep
        the compilations across process restarts.
        """
        n_new = 0
        for spec in batch_specs:
            feed = _abstract_feed(spec)
            sig = _feed_signature(feed)
            if sig in self._compiled:
                continue
            self._compiled[sig] = self.lower_step(feed).compile()
            n_new += 1
        if n_new:
            logger.info("precompiled %d step executable(s) (%d cached)",
                        n_new, len(self._compiled))
        return n_new

    def lower_step(self, feed_spec):
        """Lower (not compile, never execute) the jitted train step for
        one feed spec — the AOT building block behind ``precompile`` and
        the hook the analytic perf layer (``paddle_tpu/perf``) uses to
        read XLA's cost model for a trainer step without a device run.

        feed_spec: one feed dict of concrete arrays or
        ``jax.ShapeDtypeStruct`` leaves (``DataFeeder.feed_specs``
        builds them).  Returns the ``jax.stages.Lowered``.
        """
        feed = _abstract_feed(feed_spec)
        if self._step_fn is None:
            self._build_step(feed)
        rng_spec = jax.ShapeDtypeStruct(np.shape(self.rng), self.rng.dtype)
        return self._step_fn.lower(
            self._step_params(), self.opt_state, self.model_state, feed,
            rng_spec)

    def _requant(self, params):
        """The masters' int8 twin: quantize every eligible 2-D f32
        weight (quant/weights.quantize_tree's predicate) into a
        path-keyed flat dict {tree path: {"q", "s"}} — ONLY the
        quantized leaves (the bundle must not duplicate the small f32
        leaves, or the step would donate the same buffer twice).
        Deterministic, so rebuilding it from loaded masters is
        bit-exact."""
        from paddle_tpu.quant import weights as qw
        from jax.tree_util import keystr, tree_map_with_path
        out = {}

        def visit(path, x):
            q = qw.quantize_tree(x, min_size=self._quant_min_size)
            if qw.is_quantized_leaf(q):
                out[keystr(path)] = q
            return x

        tree_map_with_path(visit, params)
        return out

    def _step_params(self):
        """The jitted step's first operand: the plain params tree, or —
        in quant_weights mode — the {"master": f32, "q": int8+scale}
        bundle (both donated together)."""
        if self._quant:
            return {"master": self.parameters, "q": self._qtree}
        return self.parameters

    def _absorb_step_params(self, p):
        """Unpack what the step returned back into self.parameters (+
        the int8 twin in quant mode) — `_step_params`' inverse."""
        if self._quant:
            self.parameters, self._qtree = p["master"], p["q"]
        else:
            self.parameters = p

    def _dispatch_step(self, feed):
        """The executable for this feed shape: a precompiled bucket
        program if one exists, else the jitted step (which traces on new
        shapes)."""
        if self._compiled:
            fn = self._compiled.get(_feed_signature(feed))
            if fn is not None:
                return fn
        return self._step_fn

    def log_parameter_stats(self):
        """Per-parameter value abs-max/avg dump (the reference's
        --show_parameter_stats_period, TrainerInternal.cpp:210-214)."""
        for path, leaf in jax.tree_util.tree_leaves_with_path(self.parameters):
            a = jnp.abs(leaf)
            logger.info("  param %s shape=%s absmax=%.5g absavg=%.5g",
                        jax.tree_util.keystr(path), tuple(leaf.shape),
                        float(jnp.max(a)), float(jnp.mean(a)))

    def train(self, reader, num_passes=1, event_handler=None, feeding=None,
              save_dir=None, saving_period=1, save_only_one=False,
              test_reader=None, test_period=0, log_period=100,
              buffered_batches=4, show_parameter_stats_period=0,
              save_on_signal=True, prefetch=0, progress_timeout_s=600.0,
              resume=False):
        """reader: callable -> iterator of batches (lists of samples).
        feeding: {data_layer_name: InputType} or a DataFeeder.

        resume: crash-resume (resilience layer).  When True and save_dir
        holds checkpoints, load the latest COMPLETE pass dir (the atomic
        writer guarantees a kill -9 mid-save can only ever leave a
        hidden ``.tmp-`` staging dir, which is never eligible), restore
        params/opt/model state AND the training rng stream from it, and
        continue at the following pass — so a killed-and-restarted run's
        final parameters are bit-identical to an uninterrupted one
        (tests/test_resilience.py pins it, kill -9 included).  A SIGTERM
        preemption checkpoint is MID-pass: its meta carries
        ``batches_done``, and resume re-enters that same pass skipping
        exactly those batches (no step, no rng split), so preemption
        resume is bit-identical too — provided the reader replays the
        same batches per pass (a deterministic reader, the same contract
        the pass loop already assumes).  With no checkpoint yet,
        training starts fresh — ``resume=True`` is safe as the default
        posture of a supervised job.

        prefetch: run feeder conversion AND the H2D transfer on a bounded
        background thread, `prefetch` batches ahead of the step
        (data.prefetch.ShardedPrefetcher — the DoubleBuffer story
        completed to the device side).  The hot loop then dequeues
        device-resident, mesh-sharded feeds, so step wall time excludes
        input time; the per-period log line's h2d_wait column shows the
        residual input wait (~0 when the pipeline keeps up).  Numerically
        identical to prefetch=0 (same batches, same order, donation-safe).
        Costs ~prefetch+1 extra batches of HBM; supersedes
        buffered_batches (the host-only half) when set.

        Multi-process note: every rank's reader must yield the SAME number
        of batches per pass — cross-rank collectives (the step's psums,
        the pass-end skew report) hang otherwise.  The pass-end
        equal-progress check (parallel.distributed.check_equal_progress)
        runs over the coordination service's HOST-side channel, so at
        PASS END a violation surfaces as a hard error — ConfigError
        naming each rank's count, or a barrier timeout when a rank is
        already wedged mid-pass — instead of a silent deadlock.  (A rank
        that stops mid-pass can still wedge peers at the next device
        sync point inside THEIR pass — e.g. the log-period cost mean —
        before they reach this guard; that is inherent to SPMD and the
        cluster runtime's reap timeout is the backstop there.)
        progress_timeout_s
        bounds that pass-end barrier: a rank stopping early on SIGTERM
        waits there for its peers to finish the pass, so on long passes
        raise it above the worst-case pass remainder or the preempted
        rank times out before the peers arrive (and before its
        checkpoint).

        save_on_signal: when save_dir is set and train() runs on the main
        thread, SIGTERM requests a graceful stop — the loop finishes the
        current batch, writes a checkpoint (meta carries preempted=true
        and the interrupted pass), and returns instead of dying mid-pass.
        That is the TPU-preemption story: the maintenance event's TERM
        becomes a resumable pass boundary (reference recovery was
        checkpoint/restart only, Trainer.cpp:245-249)."""
        event_handler = event_handler or (lambda e: None)
        feeder = feeding if isinstance(feeding, DataFeeder) else (
            DataFeeder(feeding) if feeding else None)

        first_pass = 0
        resume_skip_batches = 0
        if resume:
            if not save_dir:
                raise ConfigError("train(resume=True) needs save_dir=")
            try:
                meta = self.load(save_dir)
            except FileNotFoundError:
                meta = None     # nothing saved yet: a fresh run
            if meta is not None:
                if meta.get("preempted") and meta.get("batches_done") \
                        is not None:
                    # a preemption checkpoint is MID-pass: re-enter that
                    # pass and skip exactly the batches it already
                    # trained (no step, no rng split), so the remainder
                    # replays bit-identically
                    first_pass = int(meta["pass_id"])
                    resume_skip_batches = int(meta["batches_done"])
                else:
                    first_pass = int(meta["pass_id"]) + 1
                if meta.get("rng") is not None:
                    # the per-batch rng stream continues exactly where
                    # the checkpointed pass left it — resumed training
                    # is bit-identical to uninterrupted
                    self.rng = jnp.asarray(np.asarray(meta["rng"],
                                                      np.uint32))
                logger.info(
                    "resume: loaded pass %d from %s%s; continuing at "
                    "pass %d%s", meta["pass_id"], save_dir,
                    " (preemption checkpoint)" if meta.get("preempted")
                    else "", first_pass,
                    f" batch {resume_skip_batches}"
                    if resume_skip_batches else "")

        self._stop_signal = None
        prev_handler = None
        handler_armed = False
        # multi-process too, and there even WITHOUT save_dir: skewed
        # signal delivery diverges per-rank batch counts, but the
        # pass-end equal-progress gather coordinates the ranks — a
        # preempted rank reports its count as preempted, every rank
        # stops together, and host syncs/the checkpoint are skipped when
        # the decoded counts show wedged device queues.  An unhandled
        # SIGTERM would instead kill the rank instantly and strand its
        # peers at the barrier; only the checkpoint WRITE needs save_dir
        if save_on_signal and (save_dir or self._multiprocess):
            import signal as _signal

            def _request_stop(signum, frame):
                self._stop_signal = signum
                logger.info("SIGTERM: finishing current batch, then %s",
                            f"checkpointing to {save_dir}" if save_dir
                            else "stopping at pass end (no save_dir)")
            try:
                prev_handler = _signal.signal(_signal.SIGTERM, _request_stop)
                handler_armed = True
            except ValueError:      # not the main thread — feature off
                prev_handler = None

        def resolve(slot, extras, feed):
            kind, key = slot
            return feed.get(key) if kind == "feed" else extras[key]

        def update_evaluators(extras, feed):
            for spec, slot, eslots in zip(self.evaluators, self._eval_slots,
                                          self._eval_extra_slots):
                lab = feed.get(spec.label.name) if spec.label is not None else None
                wgt = feed.get(spec.weight.name) if spec.weight is not None else None
                extra = {kw: resolve(s, extras, feed)
                         for kw, s in eslots.items()}
                spec.update(resolve(slot, extras, feed), lab, wgt,
                            extra=extra)

        def eval_log_suffix():
            parts = []
            for spec in self.evaluators:
                r = spec.result()
                if r is not None:
                    parts.append(f"{spec.name}={r:.5f}" if isinstance(r, float)
                                 else f"{spec.name}={r}")
            return (" Eval: " + " ".join(parts)) if parts else ""

        try:
            for pass_id in range(first_pass, num_passes):
                event_handler(events.BeginPass(pass_id))
                for spec in self.evaluators:
                    spec.reset()
                batch_reader = reader
                if buffered_batches and not prefetch:
                    # host-only double buffering; with prefetch the device
                    # pipeline's own thread covers it
                    batch_reader = reader_mod.buffered(reader, buffered_batches)
                prefetcher = None
                # ONE conversion fn for both paths — the bit-identical
                # guarantee between prefetch=N and prefetch=0 rests on it
                convert = (lambda b: _normalize_feed(feeder(b)
                                                     if feeder else b))
                if prefetch:
                    from paddle_tpu.data.prefetch import (ShardedPrefetcher,
                                                          device_placer)
                    prefetcher = ShardedPrefetcher(
                        batch_reader, depth=prefetch, convert=convert,
                        place=device_placer(self.mesh, self._multiprocess))
                # running device-side sums: no host sync in the hot loop —
                # cost only crosses to the host every log_period (and for the
                # event stream, whose .cost is the device scalar; float() it
                # lazily in your handler if you need the number immediately)
                cost_sum = jnp.zeros(())
                if self._multiprocess:
                    # keep the accumulator global-replicated so per-step
                    # arithmetic stays on-device (no host sync in the hot loop)
                    cost_sum = self._globalize(
                        cost_sum, replicated_shardings(cost_sum, self.mesh))
                n_batches = 0
                # preemption resume: the first resumed pass consumes-and-
                # skips the batches the checkpoint already trained
                skip_left, resume_skip_batches = resume_skip_batches, 0
                pass_skip = skip_left   # already-trained prefix of this
                #                         pass (for a re-preemption's
                #                         batches_done accounting)
                window = []
                skew_window = []     # host-side step wall times this pass
                h2d_window = 0.0     # input wait this log period (seconds)
                t0 = time.time()
                feed_iter = iter(prefetcher) if prefetcher is not None \
                    else iter(batch_reader())
                batch_id = -1
                try:
                    while True:
                        # one step annotation a batch (obs/trace.py
                        # phase(); the keys are the ones jax.profiler.
                        # StepTraceAnnotation sets), the parent of the
                        # feed, step and handler phases below
                        with _obstrace.phase("trainer.iter", _r=1,
                                             step_num=batch_id + 1):
                            # h2d_wait: host time blocked acquiring the
                            # next device-ready feed — with prefetch this
                            # is the queue wait (~0 when the pipeline
                            # keeps up), without it the reader + feeder
                            # conversion run inline here
                            t_in = time.perf_counter()
                            with _obstrace.phase("trainer.feed",
                                                 step=batch_id + 1):
                                try:
                                    item = next(feed_iter)
                                except StopIteration:
                                    break
                                if skip_left > 0:
                                    # already trained before the
                                    # preemption: no step, no rng split,
                                    # no events — the checkpointed
                                    # rng/params sit exactly here
                                    skip_left -= 1
                                    batch_id += 1
                                    continue
                                feed = item if prefetcher is not None else \
                                    convert(item)
                            h2d_dt = time.perf_counter() - t_in
                            batch_id += 1
                            with _obstrace.phase("trainer.handler",
                                                 step=batch_id):
                                event_handler(events.BeginIteration(
                                    pass_id, batch_id))
                            self.rng, step_rng = jax.random.split(self.rng)
                            if self._step_fn is None:
                                self._build_step(feed)
                            # the rest of the feed: the global arrays'
                            # assembly (a no-op in one process)
                            t_g = time.perf_counter()
                            with _obstrace.phase("trainer.feed",
                                                 step=batch_id):
                                if prefetcher is None:
                                    # multi-process: the synchronous path's
                                    # global-array H2D assembly counts into
                                    # h2d_wait too — otherwise the prefetch
                                    # 0-vs-N comparison the column exists
                                    # for is apples-to-oranges.  (Single-
                                    # process this is a no-op; there the
                                    # sync path's transfer happens lazily
                                    # inside the jit call and lands in step
                                    # time.)
                                    feed, step_rng = \
                                        self._globalize_step_inputs(
                                            feed, step_rng)
                                else:
                                    # feed was placed on the producer
                                    # thread; rng assembly still runs here
                                    # and counts like the synchronous
                                    # path's (same per-step work on both
                                    # sides of the 0-vs-N comparison)
                                    step_rng = self._globalize_rng(step_rng)
                            h2d_dt += time.perf_counter() - t_g
                            global_stats.get("h2d_wait").add(h2d_dt)
                            h2d_window += h2d_dt
                            # chaos hook (resilience/faults.py), host-side so
                            # the compiled step is untouched; an injected
                            # fault unwinds like any real step crash (the
                            # finally blocks still close the prefetcher,
                            # land pending saves, restore the handler)
                            _faults.hit("trainer.step")
                            step_fn = self._dispatch_step(feed)
                            t_step = time.perf_counter()
                            # tracing hook (obs/trace.py), host-side like the
                            # chaos hook above: the phase wraps the step
                            # DISPATCH (the call returns long before the
                            # device is done) and carries this batch's
                            # input wait, so a trace shows train steps
                            # next to h2d stalls
                            with _obstrace.phase(
                                    "trainer.step", step=batch_id,
                                    pass_id=pass_id, batch=batch_id,
                                    h2d_wait_ms=round(h2d_dt * 1e3, 3)), \
                                    timer("train_step"):
                                (new_p, self.opt_state, self.model_state,
                                 cost, extras) = step_fn(
                                    self._step_params(), self.opt_state,
                                    self.model_state, feed, step_rng)
                                self._absorb_step_params(new_p)
                            # per-step distribution (BarrierStat skew-
                            # profiling role): record this step's own
                            # delta, not the cumulative timer
                            from paddle_tpu.utils.stats import step_histogram
                            step_dt = time.perf_counter() - t_step
                            step_histogram.add(step_dt)
                            cost_sum = cost_sum + cost
                            if self._multiprocess and len(skew_window) < 10000:
                                # consumed by the PASS-END cross-rank
                                # report (a collective can only live where
                                # every rank is guaranteed to arrive);
                                # bounded like step_histogram
                                skew_window.append(step_dt)
                            n_batches += 1
                            if log_period:  # only the log line consumes it;
                                window.append(cost)  # log_period=0 must not
                            if self.evaluators:  # pin a device scalar a batch
                                update_evaluators(extras, feed)
                            if log_period and (batch_id + 1) % log_period == 0:
                                c = float(jnp.mean(jnp.stack(window)))
                                window = []
                                dt = (time.time() - t0) / log_period
                                logger.info("Pass %d Batch %d Cost %.5f (%.1f ms/batch"
                                            " h2d_wait=%.2fms)%s",
                                            pass_id, batch_id + 1, c, dt * 1e3,
                                            h2d_window / log_period * 1e3,
                                            eval_log_suffix())
                                h2d_window = 0.0
                                t0 = time.time()
                            if (show_parameter_stats_period
                                    and (batch_id + 1) % show_parameter_stats_period == 0):
                                self.log_parameter_stats()
                            with _obstrace.phase("trainer.handler",
                                                 step=batch_id):
                                event_handler(events.EndIteration(
                                    pass_id, batch_id, cost=cost,
                                    evaluator_results={
                                        f"extra_{i}": e
                                        for i, e in enumerate(extras)}))
                            if self._stop_signal is not None:
                                break
                finally:
                    if prefetcher is not None:
                        prefetcher.close()
                sync_safe = True
                if self._multiprocess:
                    # pass end is the ONE point every rank reaches no
                    # matter how many batches its reader produced, so the
                    # cross-rank collectives live here: first the
                    # equal-progress guard (unequal batch counts raise a
                    # ConfigError instead of deadlocking the job), then
                    # the straggler/skew report (reference BarrierStat).
                    # On SIGTERM a rank still participates but marks its
                    # count preempted: signal delivery is not
                    # synchronized across ranks, so unequal counts are
                    # expected then, a silently-skipping rank would
                    # strand the others at the barrier, and the
                    # preemption checkpoint below must still run.  A
                    # preempted peer also means WE must stop after this
                    # pass — it will not join the next pass's collectives
                    from paddle_tpu.parallel.distributed import (
                        check_equal_progress, step_skew_report)
                    common, preempted = check_equal_progress(
                        n_batches, name=f"pass {pass_id}",
                        timeout_s=progress_timeout_s,
                        skip=self._stop_signal is not None)
                    # common=None: counts diverged (preempted mid-step
                    # skew) — a rank dispatched steps whose collectives
                    # will never complete, so ANY host sync on device
                    # values (pass cost, skew report, checkpoint gather)
                    # could hang; skip them all, consistently on every
                    # rank (all ranks see the same counts)
                    sync_safe = common is not None
                    if not preempted:
                        self.last_skew_report = step_skew_report(skew_window)
                    elif self._stop_signal is None:
                        import signal as _sig
                        logger.warning(
                            "a peer rank was preempted; stopping after "
                            "pass %d too (continuing would wedge on its "
                            "missing collectives)", pass_id)
                        self._stop_signal = int(_sig.SIGTERM)
                # sync_safe=False: evaluator results are device scalars from
                # the same possibly-wedged steps as cost_sum — no host syncs
                pass_cost = (float(cost_sum) / n_batches
                             if n_batches and sync_safe else float("nan"))
                logger.info("Pass %d done, mean cost %.5f%s", pass_id, pass_cost,
                            eval_log_suffix() if sync_safe else "")
                # per-pass step-time distribution (the BarrierStat successor:
                # in synchronous SPMD the skew diagnostic is p99/p50 spread)
                from paddle_tpu.utils.stats import step_histogram
                if step_histogram.samples:
                    logger.info("  %s", step_histogram.summary())
                    step_histogram.reset()
                if test_reader is not None and self._stop_signal is None and (
                        not test_period or (pass_id + 1) % test_period == 0):
                    tc = self.test(test_reader, feeding=feeder)
                    event_handler(events.EndTesting(pass_id, tc))
                if save_dir and self._stop_signal is not None:
                    if not sync_safe:
                        # parameters depend on dispatched steps whose
                        # collectives will never complete — the gather
                        # inside save() would hang, not checkpoint
                        logger.warning(
                            "preempted with unequal per-rank batch counts; "
                            "device state is unrecoverable — SKIPPING the "
                            "preemption checkpoint (last periodic "
                            "checkpoint remains the restart point)")
                    else:
                        # preemption checkpoint: blocking (the process is
                        # about to be reaped — there may be no later sync
                        # point)
                        # batches_done lets train(resume=True) re-enter
                        # THIS pass skipping exactly the trained prefix
                        # (bit-identical preemption resume)
                        path = self.save(save_dir, pass_id,
                                         save_only_one=save_only_one,
                                         block=True,
                                         extra={"preempted": True,
                                                "signal":
                                                int(self._stop_signal),
                                                "batches_done":
                                                pass_skip + n_batches})
                        if path:
                            logger.info("preemption checkpoint %s; stopping "
                                        "after pass %d", path, pass_id)
                elif save_dir and (pass_id + 1) % saving_period == 0:
                    # single-process saves overlap the disk write with the
                    # next pass (the snapshot itself is taken synchronously);
                    # multi-process stays blocking for the barrier guarantee
                    path = self.save(save_dir, pass_id,
                                     save_only_one=save_only_one,
                                     block=self._multiprocess)
                    if path:
                        # async schedule is not persistence yet; don't claim it
                        logger.info("saved checkpoint %s" if self._multiprocess
                                    else "saving checkpoint %s (async)", path)
                event_handler(events.EndPass(pass_id))
                if self._stop_signal is not None:
                    break
        finally:
            # durability + handler restoration even when an exception
            # unwinds out of the loop (a leaked handler would make the
            # process unkillable by SIGTERM)
            try:
                if save_dir:
                    from paddle_tpu.trainer import checkpoint as _ckpt
                    _ckpt.wait_pending(save_dir)
            finally:
                # restore even when wait_pending re-raises a save failure;
                # signal.signal() returns None when the prior handler was
                # installed outside Python, so gate on the armed flag, not
                # the returned value
                if handler_armed:
                    import signal as _signal
                    _signal.signal(_signal.SIGTERM,
                                   prev_handler if prev_handler is not None
                                   else _signal.SIG_DFL)


    def train_one_batch(self, batch, feeder=None):
        """One jitted train step on one host batch; returns the device
        cost scalar (reference TrainerInternal::trainOneBatch:66 at API
        level — the CLI `time` job and custom loops use this)."""
        feeder = feeder if isinstance(feeder, DataFeeder) else (
            DataFeeder(feeder) if feeder else None)
        feed = _normalize_feed(feeder(batch) if feeder else batch)
        self.rng, step_rng = jax.random.split(self.rng)
        if self._step_fn is None:
            self._build_step(feed)
        feed, step_rng = self._globalize_step_inputs(feed, step_rng)
        (new_p, self.opt_state, self.model_state,
         cost, _extras) = self._dispatch_step(feed)(
            self._step_params(), self.opt_state, self.model_state,
            feed, step_rng)
        self._absorb_step_params(new_p)
        return cost

    # ------------------------------------------------------------ test

    def _build_eval(self):
        def ev(params, state, feed):
            if self.compute_dtype is not None:
                params = self._cast_compute(params)
                feed = self._cast_compute(feed)
            with _rnn_ops.batch_sharded_over(self.mesh, AXIS_DATA):
                out = self.topology.apply(params, feed, mode="test",
                                          state=state)
            outs = out if isinstance(out, tuple) else (out,)
            cost_vals = outs[:len(self.costs)]
            # f32 reduction regardless of compute dtype (same rationale as
            # the train path: a bf16 batch-mean loses the cost signal)
            return (sum(jnp.mean(c.astype(jnp.float32))
                        for c in cost_vals), outs[len(self.costs):])
        self._eval_fn = jax.jit(ev)

    def test(self, reader, feeding=None):
        feeder = feeding if isinstance(feeding, DataFeeder) else (
            DataFeeder(feeding) if feeding else None)
        if self._eval_fn is None:
            self._build_eval()
        total, n = 0.0, 0
        for batch in reader():
            feed = _normalize_feed(feeder(batch) if feeder else batch)
            if self._multiprocess:
                feed = self._globalize(feed,
                                       batch_shardings(feed, self.mesh))
            cost, _ = self._eval_fn(self.parameters, self.model_state, feed)
            total += float(cost)
            n += 1
        mean = total / max(n, 1)
        logger.info("Test cost %.5f over %d batches", mean, n)
        return mean

    # ------------------------------------------------------------ io

    def save(self, save_dir, pass_id=0, save_only_one=False, block=True,
             extra=None):
        params, opt_state = self.parameters, self.opt_state
        if self._quant and self._qtree:
            # checkpoint BOTH trees (kill-9 resume must be
            # bit-identical; requantizing on load would also be exact —
            # quantize_tree is deterministic — but carrying the int8
            # twin keeps the resumed step operand byte-equal by
            # construction, no recompute in the restore path)
            params = {"master": self.parameters, "q": self._qtree}
        if self._multiprocess:
            block = True    # the barrier promise needs the file on disk
            # model-sharded leaves are not process-0-addressable: gather to
            # replicated (a jitted identity re-sharding), then only the
            # coordinator writes; everyone waits so a crash right after
            # the pass boundary can always resume from this checkpoint
            from paddle_tpu.parallel import barrier
            params = self._devget_replicated(params, "params")
            opt_state = self._devget_replicated(opt_state, "opt")
            if jax.process_index() != 0:
                barrier(f"save{pass_id}")
                return None
        extra = dict(extra or {})
        extra.setdefault("grad_accum_steps", self.grad_accum_steps)
        try:
            # the rng stream rides in meta so train(resume=True) can
            # continue it bit-identically (raw uint32 keys; typed-key
            # arrays would fail the cast and simply skip the field)
            extra.setdefault("rng", np.asarray(
                jax.device_get(self.rng), np.uint32).tolist())
        except (TypeError, ValueError):
            pass
        path = save_checkpoint(save_dir, pass_id, params,
                               opt_state, self.model_state, extra=extra,
                               save_only_one=save_only_one, block=block)
        if self._multiprocess:
            from paddle_tpu.parallel import barrier
            barrier(f"save{pass_id}")
        return path

    def _devget_replicated(self, tree, cache_key=None):
        if tree is None:
            return None
        gather = self._gather_cache.get(cache_key) if cache_key else None
        if gather is None:
            shardings = replicated_shardings(tree, self.mesh)
            gather = jax.jit(lambda t: t, out_shardings=shardings)
            if cache_key:
                self._gather_cache[cache_key] = gather
        return jax.device_get(gather(tree))

    def load(self, save_dir, pass_id=None):
        params, opt_state, model_state, meta = load_checkpoint(save_dir, pass_id)
        bundled = isinstance(params, dict) and set(params) == {"master", "q"}
        if self._quant:
            if bundled:
                self.parameters, self._qtree = params["master"], params["q"]
            else:
                # plain (f32) checkpoint into a quant trainer: adopt the
                # masters and requantize deterministically
                self.parameters = params
                self._qtree = self._requant(params)
        elif bundled:
            # quant checkpoint into a plain trainer: the masters ARE the
            # f32 params; the int8 twin is dropped
            self.parameters = params["master"]
        else:
            self.parameters = params
        if opt_state is not None:
            opt_state = self._adapt_accum_state(opt_state, meta)
            self.opt_state = opt_state
        if model_state is not None:
            self.model_state = model_state
        self._refresh_prune_masks()
        self._reglobalize_after_load()
        return meta

    def _adapt_accum_state(self, opt_state, meta):
        """Reconcile a checkpoint's grad-accumulation wrapper with THIS
        trainer's grad_accum_steps.  Clean boundaries (tick 0) convert
        freely in both directions — a test job or an accum-setting change
        just works; only a checkpoint holding genuinely mid-accumulation
        grads under a DIFFERENT accum value is an error (replaying those
        grads at another denominator would mis-scale the next step)."""
        wrapped = isinstance(opt_state, dict) and "gsum" in opt_state
        want = self.grad_accum_steps > 1
        tick = int(opt_state["tick"]) if wrapped else 0
        stored = meta.get("grad_accum_steps")
        if wrapped and not want:
            if tick:
                logger.warning(
                    "checkpoint holds %d accumulated micro-batch grads "
                    "(grad_accum_steps=%s) — discarded, this trainer "
                    "doesn't accumulate", tick, stored or ">1")
            return opt_state["inner"]
        if want and not wrapped:
            dense = {k: v for k, v in self.parameters.items()
                     if k not in self._sparse_specs}
            return {"inner": opt_state,
                    "gsum": jax.tree_util.tree_map(jnp.zeros_like, dense),
                    "tick": jnp.zeros((), jnp.int32)}
        if wrapped and want and stored and stored != self.grad_accum_steps:
            if tick:
                raise ConfigError(
                    f"checkpoint is mid-accumulation (tick={tick}) under "
                    f"grad_accum_steps={stored}; this trainer has "
                    f"{self.grad_accum_steps} — resume with the matching "
                    "setting (or from a pass boundary)")
            # clean boundary: gsum is zeros, the wrapper carries over
        return opt_state

    def _reglobalize_after_load(self):
        """Checkpoint leaves are host arrays; on a process-spanning mesh
        they must become global arrays again (jit cannot device_put host
        values onto non-addressable devices).  Params take their rule
        shardings; opt/model state re-enter replicated — the next step's
        explicit in_shardings reshards them to their true layout."""
        if not self._multiprocess:
            return
        ps = param_shardings(self.parameters, self.mesh,
                             self.sharding_rules)
        self.parameters = self._globalize(self.parameters, ps)
        if self.opt_state is not None:
            self.opt_state = self._globalize(
                self.opt_state,
                replicated_shardings(self.opt_state, self.mesh))
        if self.model_state:
            self.model_state = self._globalize(
                self.model_state,
                replicated_shardings(self.model_state, self.mesh))

    def load_parameters(self, save_dir, pass_id=None,
                        missing_strategy="fail"):
        """Warm-start parameters only (reference --init_model_path +
        --load_missing_parameter_strategy, ParamUtil.cpp loadParameters):
        params present in the checkpoint are taken; params absent follow
        missing_strategy = fail | rand | zero (rand keeps this trainer's
        fresh initialization, the reference's 'rand' semantics)."""
        params, _opt, model_state, _ = load_checkpoint(save_dir, pass_id)
        merged = {}
        for key, init_val in self.parameters.items():
            if key in params:
                merged[key] = params[key]
            elif missing_strategy == "rand":
                merged[key] = init_val
            elif missing_strategy == "zero":
                merged[key] = jax.tree_util.tree_map(jnp.zeros_like, init_val)
            else:
                raise ConfigError(
                    f"parameter {key!r} missing from {save_dir} "
                    "(load_missing_parameter_strategy=fail)")
        extra = set(params) - set(self.parameters)
        if extra:
            logger.warning("checkpoint parameters not in this model "
                           "(ignored): %s", sorted(extra))
        self.parameters = merged
        if model_state:
            self.model_state = {**self.model_state, **model_state}
        self._refresh_prune_masks()
        self._reglobalize_after_load()

    def _refresh_prune_masks(self):
        """Re-derive pruning masks after self.parameters was replaced
        (checkpoint load / warm start): a sparsity_ratio mask must reflect
        the LOADED weights, not the discarded random init (a resumed pruned
        model re-masks to exactly its checkpointed zeros), and the value
        mask is re-applied.  The cached step closure holds the old masks,
        so it is invalidated too."""
        if not self._prune_masks:
            return
        self._prune_masks = param_hooks.build_masks(
            self.topology, self.parameters)
        self.parameters = param_hooks.apply_masks(
            self.parameters, self._prune_masks)
        self._step_fn = None
        self._compiled = {}     # AOT executables hold the old masks too

    def log_layer_stats(self, feed):
        """Per-layer output abs-mean/abs-max on one batch (reference
        --show_layer_stat, TrainerInternal.cpp showParameterStats's layer
        twin: printAllStatus each log_period)."""
        from paddle_tpu.layers.graph import value_data
        feed = _normalize_feed(feed)
        vals = self.topology.apply(
            self.parameters, feed, mode="test", state=self.model_state,
            extra_outputs=[n for n in self.topology.order
                           if n.layer_type != "data"])
        vals = vals if isinstance(vals, tuple) else (vals,)
        nodes = [n for n in self.topology.order if n.layer_type != "data"]
        n_named = len(self.topology.outputs)
        for node, v in zip(nodes, vals[n_named:]):
            d = value_data(v)
            if hasattr(d, "astype"):
                a = jnp.abs(d.astype(jnp.float32))
                logger.info("  layer %s [%s] absavg=%.5g absmax=%.5g",
                            node.name, node.layer_type,
                            float(jnp.mean(a)), float(jnp.max(a)))


class Inferencer:
    """paddle.v2.inference equivalent: run a topology in test mode.

    compute_dtype=jnp.bfloat16 runs the forward in bf16 (params cast at
    the jit boundary; outputs returned in f32) — the serving-side half of
    the trainer's mixed-precision option.  quantize="int8" stores the
    weights int8 with per-channel scales (export.quantize_params): ~4x
    less weight-stream HBM per request, dequant fused into the matmuls."""

    def __init__(self, output_layer, parameters, model_state=None,
                 compute_dtype=None, quantize=None):
        outs = output_layer if isinstance(output_layer, (list, tuple)) \
            else [output_layer]
        self.topology = Topology(list(outs))
        dequant = None
        # .parameters stays the caller's FLOAT pytree in every mode (other
        # consumers — export_inference, a second Inferencer — rely on it);
        # the int8 representation is an internal execution detail
        self.parameters = parameters
        self._exec_params = parameters
        if quantize is not None:
            from paddle_tpu.export import quantize_params
            if quantize != "int8":
                raise ValueError(
                    f"quantize={quantize!r} (supported: None, 'int8')")
            self._exec_params, dequant = quantize_params(parameters)
        self.model_state = model_state or {}

        def fwd(p, s, feed):
            if dequant is not None:
                p = dequant(p)
            if compute_dtype is not None:
                from paddle_tpu.core.dtypes import cast_tree
                p = cast_tree(p, compute_dtype)
                feed = cast_tree(feed, compute_dtype)
            out = self.topology.apply(p, feed, mode="test", state=s)
            if compute_dtype is not None:
                out = jax.tree_util.tree_map(
                    lambda x: x.astype(jnp.float32)
                    if hasattr(x, "dtype") and x.dtype == compute_dtype
                    else x, out)
            return out
        # the raw (un-jitted) forward is the hook serving.InferenceEngine
        # wraps to AOT-compile one executable per batch bucket
        self._fwd = fwd
        self._fn = jax.jit(fwd)

    def infer(self, feed_or_batch, feeding=None):
        if feeding is not None and not isinstance(feed_or_batch, dict):
            feeder = feeding if isinstance(feeding, DataFeeder) else DataFeeder(feeding)
            feed = feeder(feed_or_batch)
        else:
            feed = feed_or_batch
        feed = _normalize_feed(feed)
        return self._fn(self._exec_params, self.model_state, feed)


def infer(output_layer, parameters, input, feeding=None):
    return Inferencer(output_layer, parameters).infer(input, feeding=feeding)


# the modern name for the training driver (SGD is the v2-compat spelling):
# Trainer.train(prefetch=...), Trainer.precompile(...) read naturally
Trainer = SGD

"""Recurrent DSL: lstmemory / grumemory / recurrent_layer whole-sequence
layers, and the recurrent_group / memory engine.

Reference surface: trainer_config_helpers layers.py lstmemory/grumemory/
recurrent_layer/recurrent_group/memory/lstm_step_layer/gru_step_layer/
get_output_layer + RecurrentLayerGroup lowering (config_parser.py sub_models,
gserver RecurrentLayerGroup.cpp:23-60, RecurrentGradientMachine engine).

TPU design: a recurrent_group's step sub-graph is built once at config time
(placeholders for step inputs and memories), compiled to a pure step
function, and driven by ops.rnn.recurrent_group — one lax.scan, static
shapes, masked carries (vs the reference's per-frame network instantiation
with batch shrinking).
"""

import math

import jax
import jax.numpy as jnp

from paddle_tpu.core import dtypes
from paddle_tpu.core.sequence import NestedSequenceBatch, SequenceBatch
from paddle_tpu.layers.graph import (
    LayerOutput, Topology, register_layer, auto_name, as_seq, value_data,
    Context, get_impl)
from paddle_tpu.layers.api import _winit, _maybe_bias
from paddle_tpu.ops import rnn as rnn_ops
from paddle_tpu.utils.error import ConfigError

__all__ = [
    "lstmemory", "grumemory", "recurrent_layer", "recurrent_group", "memory",
    "StaticInput", "SubsequenceInput", "lstm_step_layer", "gru_step_layer",
    "gru_step_naive_layer", "get_output_layer", "mdlstmemory",
]


# ----------------------------------------------------- whole-sequence RNNs

def _prev_batch_carry(ctx, cfg):
    """Reference --prev_batch_state (Flags.cpp:73: "batch is continue with
    next batch"): carry the RNN's final state into the next batch via the
    trainer's functional model_state thread (same channel as BN stats)."""
    if not cfg.get("prev_batch_state"):
        from paddle_tpu.utils.flags import FLAGS
        if not FLAGS.prev_batch_state:
            return False
    if cfg.get("reverse", False):
        if cfg.get("prev_batch_state"):
            # explicit per-layer request on a reversed scan is a config
            # contradiction — fail loudly instead of silently dropping it
            raise ConfigError(
                f"{cfg.get('name', '?')}: prev_batch_state cannot carry "
                "state for a reverse RNN (the final state of a reversed "
                "scan is the sequence START)")
        return False  # global flag: skip reversed layers, carry the rest
    return True


def _prev_batch_init(ctx, cfg):
    if not _prev_batch_carry(ctx, cfg):
        return None
    return ctx.state_in.get(cfg["name"] + "/carry")


def _prev_batch_save(ctx, cfg, final):
    if _prev_batch_carry(ctx, cfg):
        ctx.put_state(cfg["name"] + "/carry", final)

class _LstmImpl:
    def infer(self, cfg, in_sizes):
        return cfg["size"]

    def init(self, rng, cfg, in_sizes):
        d = cfg["size"]
        if in_sizes[0] != 4 * d:
            raise ConfigError(
                f"lstmemory input must be 4*size={4 * d} wide (a mixed/fc "
                f"projection), got {in_sizes[0]} — reference LstmLayer "
                "semantics")
        r1, r2 = jax.random.split(rng)
        p = {"w": _winit(cfg.get("param_attr"), 1.0 / math.sqrt(d))(r1, (d, 4 * d))}
        # bias layout (reference LstmLayer): 4*size gate bias + 3*size peepholes
        if cfg.get("bias_attr", True) is not False:
            p["b"] = jnp.zeros((7 * d,), dtypes.param_dtype())
        return p

    def apply(self, ctx, cfg, params, x, proj=None, hand_over=None):
        """``proj``: ``x`` is the input of the bias-free fc that feeds this
        layer and ``proj`` its weight (graph.Topology hands the pair over
        unapplied): the projection is the LSTM's own to compute, and ``x``
        may be a fused LSTM's ``LstmOutput``.  ``hand_over``: every reader
        takes the fused kernel's own form (``ops.rnn.lstm(hand_over=)``)."""
        d = cfg["size"]
        b = params.get("b")
        bias = b[:4 * d] if b is not None else None
        ci = b[4 * d:5 * d] if b is not None else None
        cf = b[5 * d:6 * d] if b is not None else None
        co = b[6 * d:] if b is not None else None
        init = _prev_batch_init(ctx, cfg)
        if init is not None:
            init = rnn_ops.LstmState(h=init[..., :d], c=init[..., d:])
        if _prev_batch_carry(ctx, cfg):
            hand_over = None
        if not isinstance(x, rnn_ops.LstmOutput):
            x = as_seq(x)
        out, final = rnn_ops.lstm(x, params["w"], bias=bias,
                                  check_i=ci, check_f=cf, check_o=co,
                                  init_state=init, proj=proj,
                                  hand_over=hand_over,
                                  reverse=cfg.get("reverse", False),
                                  act=cfg.get("act", "tanh"),
                                  gate_act=cfg.get("gate_act", "sigmoid"),
                                  state_act=cfg.get("state_act", "tanh"))
        _prev_batch_save(ctx, cfg,
                         jnp.concatenate([final.h, final.c], axis=-1))
        return out


register_layer("lstmemory")(_LstmImpl)


def lstmemory(input, size=None, reverse=False, act="tanh",
              gate_act="sigmoid", state_act="tanh", name=None,
              bias_attr=True, param_attr=None, prev_batch_state=False):
    d = size or input.size // 4
    nm = name or auto_name("lstmemory")
    return LayerOutput(nm, "lstmemory", d, [input],
                       {"size": d, "name": nm, "reverse": reverse,
                        "act": act, "gate_act": gate_act,
                        "state_act": state_act, "bias_attr": bias_attr,
                        "param_attr": param_attr,
                        "prev_batch_state": prev_batch_state},
                       is_seq=True)


class _GruImpl:
    def infer(self, cfg, in_sizes):
        return cfg["size"]

    def init(self, rng, cfg, in_sizes):
        d = cfg["size"]
        if in_sizes[0] != 3 * d:
            raise ConfigError(
                f"grumemory input must be 3*size={3 * d} wide, got {in_sizes[0]}")
        r1, r2, r3 = jax.random.split(rng, 3)
        wi = _winit(cfg.get("param_attr"), 1.0 / math.sqrt(d))
        p = {"w_gate": wi(r1, (d, 2 * d)), "w_state": wi(r2, (d, d))}
        if cfg.get("bias_attr", True) is not False:
            p["b"] = jnp.zeros((3 * d,), dtypes.param_dtype())
        return p

    def apply(self, ctx, cfg, params, x):
        out, final = rnn_ops.gru(as_seq(x), params["w_gate"],
                                 params["w_state"], bias=params.get("b"),
                                 init_state=_prev_batch_init(ctx, cfg),
                                 reverse=cfg.get("reverse", False),
                                 act=cfg.get("act", "tanh"),
                                 gate_act=cfg.get("gate_act", "sigmoid"))
        _prev_batch_save(ctx, cfg, final)
        return out


register_layer("grumemory")(_GruImpl)


def grumemory(input, size=None, reverse=False, act="tanh",
              gate_act="sigmoid", name=None, bias_attr=True, param_attr=None,
              prev_batch_state=False):
    d = size or input.size // 3
    nm = name or auto_name("grumemory")
    return LayerOutput(nm, "grumemory", d, [input],
                       {"size": d, "name": nm, "reverse": reverse,
                        "act": act, "gate_act": gate_act,
                        "bias_attr": bias_attr, "param_attr": param_attr,
                        "prev_batch_state": prev_batch_state}, is_seq=True)


class _SimpleRnnImpl:
    def infer(self, cfg, in_sizes):
        return cfg["size"]

    def init(self, rng, cfg, in_sizes):
        d = cfg["size"]
        p = {"w": _winit(cfg.get("param_attr"), 1.0 / math.sqrt(d))(rng, (d, d))}
        if cfg.get("bias_attr", True) is not False:
            p["b"] = jnp.zeros((d,), dtypes.param_dtype())
        return p

    def apply(self, ctx, cfg, params, x):
        out, final = rnn_ops.simple_rnn(as_seq(x), params["w"],
                                        bias=params.get("b"),
                                        init_state=_prev_batch_init(ctx, cfg),
                                        reverse=cfg.get("reverse", False),
                                        act=cfg.get("act", "tanh"))
        _prev_batch_save(ctx, cfg, final)
        return out


register_layer("recurrent")(_SimpleRnnImpl)


def recurrent_layer(input, act="tanh", reverse=False, name=None,
                    bias_attr=True, param_attr=None, prev_batch_state=False):
    """Reference RecurrentLayer: h_t = act(x_t + W h_{t-1})."""
    nm = name or auto_name("recurrent")
    return LayerOutput(nm, "recurrent", input.size, [input],
                       {"size": input.size, "name": nm, "act": act,
                        "reverse": reverse, "bias_attr": bias_attr,
                        "param_attr": param_attr,
                        "prev_batch_state": prev_batch_state},
                       is_seq=True)


# ----------------------------------------------------- recurrent_group

class StaticInput:
    """Whole-layer input visible unchanged at every step (reference
    StaticInput for recurrent_group; used for the encoder context in
    simple_attention)."""

    def __init__(self, input, is_seq=False):
        self.input = input
        self.is_seq = is_seq  # True: the step sees the whole sequence


class SubsequenceInput:
    """Marks a two-level sequence input for a nested recurrent_group
    (reference SubsequenceInput, RecurrentGradientMachine.cpp:642-712): the
    outer group iterates SUBSEQUENCES — the step function sees each
    subsequence as a whole SequenceBatch and can run an inner
    recurrent_group over it."""

    def __init__(self, input):
        self.input = input


def _in_v1_parse():
    """True while a reference v1 config script is being executed by the
    config compiler (there sequence-ness is a DataProvider property, not a
    layer property)."""
    try:
        from paddle_tpu.compat import config_parser
        return config_parser.in_parse()
    except Exception:
        return False


def _promote_seq(node, _seen=None):
    """Mark a layer chain as sequence-valued (v1 compat promotion)."""
    _seen = _seen if _seen is not None else set()
    if id(node) in _seen:
        return
    _seen.add(id(node))
    node.is_seq = True
    for dep in node.inputs:
        _promote_seq(dep, _seen)


class _GroupBuildCtx:
    current = None

    def __init__(self):
        self.memories = []  # list of (placeholder, link_name, boot, init_zero)


def resolve_memory_links(sub_topo, memories, extra_nodes=()):
    """Match memory() links to step-graph layers by name (shared by
    recurrent_group and the generation DSL).  extra_nodes: nodes created
    during step tracing that are NOT ancestors of the step outputs — the
    reference allows a memory to link a CONSUMER of the output (e.g.
    last_seq(inner_out, name="outer_rnn_state"), sequence_nest_rnn.conf)."""
    by_name = {n.name: n for n in extra_nodes}
    by_name.update({n.name: n for n in sub_topo.order})
    links = []
    for ph, link_name, boot, boot_const in memories:
        if link_name not in by_name:
            raise ConfigError(
                f"memory(name={link_name!r}) has no matching layer in the "
                f"step function (have {sorted(by_name)})")
        links.append((ph, by_name[link_name], boot, boot_const))
    return links


class _MemoryPlaceholder(LayerOutput):
    """memory() return value; supports the reference's late-link form
    `m = memory(name=None, size=...); ...; m.set_input(layer)`."""

    def set_input(self, layer):
        g = _GroupBuildCtx.current
        if g is None:
            raise ConfigError("set_input() must be called inside the step")
        for i, (ph, link, boot, boot_const) in enumerate(g.memories):
            if ph is self:
                g.memories[i] = (ph, layer.name, boot, boot_const)
                return
        raise ConfigError("set_input on a memory not in this group")


def memory(name, size, boot_layer=None, boot_with_const_id=None,
           is_seq=False):
    """Previous-step output of the layer called `name` (reference memory()
    with boot layers, RecurrentGradientMachine memory frames :715).  With
    name=None the link is bound later via .set_input(layer) (reference
    memory(name=None) + set_input)."""
    g = _GroupBuildCtx.current
    if g is None:
        raise ConfigError("memory() must be called inside recurrent_group's step")
    ph = _MemoryPlaceholder(auto_name(f"mem_{name}"), "__memory__", size, [],
                            {"link": name}, is_seq=False)
    g.memories.append((ph, name, boot_layer, boot_with_const_id))
    return ph


def recurrent_group(step, input, reverse=False, name=None):
    """Build the step sub-graph once, compile to a scan (see module doc).

    input: one or a list of sequence LayerOutputs and/or StaticInputs.
    step: fn(*step_inputs) -> LayerOutput or tuple of LayerOutputs.
    """
    ins = input if isinstance(input, (list, tuple)) else [input]
    seq_inputs, static_inputs, sub_inputs = [], [], []
    step_args = []
    for item in ins:
        if isinstance(item, StaticInput):
            ph = LayerOutput(auto_name("static_in"), "__static__",
                             item.input.size, [], {}, is_seq=item.is_seq)
            static_inputs.append((ph, item))
            step_args.append(ph)
        elif isinstance(item, SubsequenceInput):
            # the step sees each SUBSEQUENCE as a whole SequenceBatch
            ph = LayerOutput(auto_name("subseq_in"), "__step_input__",
                             item.input.size, [], {}, is_seq=True)
            sub_inputs.append((ph, item))
            step_args.append(ph)
        else:
            if not item.is_seq:
                if _in_v1_parse():
                    # v1 configs declare sequence-ness in the DataProvider,
                    # not on the layer (reference defers to runtime): a
                    # layer fed to a recurrent_group IS a sequence there.
                    # The native DSL keeps the strict check — its data
                    # layers carry is_seq explicitly.
                    _promote_seq(item)
                else:
                    raise ConfigError(
                        f"recurrent_group input {item.name} is not a "
                        "sequence; wrap non-sequence inputs in StaticInput")
            ph = LayerOutput(auto_name("step_in"), "__step_input__",
                             item.size, [], {}, is_seq=False)
            seq_inputs.append((ph, item))
            step_args.append(ph)
    if sub_inputs and seq_inputs:
        raise ConfigError("recurrent_group cannot mix SubsequenceInput with "
                          "flat sequence inputs (reference nested groups "
                          "iterate subsequences only)")

    from paddle_tpu.layers import graph as _graph
    g = _GroupBuildCtx()
    prev = _GroupBuildCtx.current
    _GroupBuildCtx.current = g
    created = []
    _graph._NODE_OBSERVERS.append(created.append)
    try:
        outs = step(*step_args)
    finally:
        _GroupBuildCtx.current = prev
        _graph._NODE_OBSERVERS.remove(created.append)
    outs = list(outs) if isinstance(outs, (list, tuple)) else [outs]

    # resolve memory links: each memory's `link` names a layer created
    # during the step trace (ancestor of the outputs or not)
    sub_topo = Topology(outs)
    links = resolve_memory_links(sub_topo, g.memories, extra_nodes=created)

    # link targets that are NOT ancestors of the outputs must still be
    # computed each step: make them additional sub-graph outputs
    in_graph = {id(n) for n in sub_topo.order}
    link_nodes = [ln for _, ln, _, _ in links]
    extra_outs = []
    for ln in link_nodes:
        if id(ln) not in in_graph and all(ln is not e for e in extra_outs):
            extra_outs.append(ln)
    if extra_outs:
        sub_topo = Topology(outs + extra_outs)

    group_inputs = ([real for _, real in seq_inputs]
                    + [s.input for _, s in sub_inputs]
                    + [s.input for _, s in static_inputs]
                    + [b for _, _, b, _ in links if isinstance(b, LayerOutput)])

    cfg = {
        "sub_topo": sub_topo,
        "outs": outs,
        "seq_phs": [ph for ph, _ in seq_inputs],
        "sub_phs": [ph for ph, _ in sub_inputs],
        "static_phs": [ph for ph, _ in static_inputs],
        "links": links,
        "reverse": reverse,
        "n_seq": len(seq_inputs),
        "n_sub": len(sub_inputs),
        "n_static": len(static_inputs),
    }
    node = LayerOutput(name or auto_name("recurrent_group"),
                       "recurrent_group", outs[0].size, group_inputs, cfg,
                       is_seq=True)
    node.cfg["self_name"] = node.name
    return node


# scan-invariant hoisting: step-graph layers that depend only on the
# per-step sequence inputs (not on memories/statics) and are row-wise can
# be computed ONCE over the whole padded sequence before the scan — one big
# MXU matmul instead of T small ones (the same trick the reference's
# SequenceToBatch plays for whole-sequence RNN layers, generalized to
# arbitrary step graphs).  Disable for A/B testing via this flag.
HOIST_SCAN_INVARIANTS = True

# layer types whose apply maps rows independently (safe on [B, T, ...] data
# exactly as on [B, ...] rows).  Anything sequence-aware (pooling, context,
# seq ops) must stay inside the scan.
_ROW_WISE_TYPES = {"fc", "embedding", "mixed", "addto", "concat",
                   "slope_intercept"}
_ROW_WISE_MIXED_PARTS = {"full_matrix", "trans_full_matrix", "identity",
                         "dotmul", "scaling", "table"}


def _hoistable_frontier(sub_topo, seq_phs, mode):
    """Maximal step-graph nodes computable before the scan: every ancestor
    path bottoms out in a per-step sequence placeholder and every node on it
    is row-wise (and dropout-free in train mode, so randomness stays
    per-step)."""
    seq_ph_ids = {id(ph) for ph in seq_phs}
    ok = {}
    for node in sub_topo.order:
        if id(node) in seq_ph_ids:
            ok[id(node)] = True
            continue
        if node.layer_type.startswith("__") or node.layer_type == "data":
            ok[id(node)] = False
            continue
        if not node.inputs or not all(ok.get(id(i), False)
                                      for i in node.inputs):
            ok[id(node)] = False
            continue
        row_wise = node.layer_type in _ROW_WISE_TYPES
        if node.layer_type == "mixed":
            row_wise = all(kind in _ROW_WISE_MIXED_PARTS
                           for kind, _ in node.cfg["parts"])
        if mode == "train" and (node.cfg.get("drop_rate")
                                or node.layer_type == "dropout"):
            row_wise = False
        ok[id(node)] = row_wise
    # frontier: hoistable nodes consumed by a non-hoistable node (or an
    # output) — computing deeper ancestors too would be redundant
    consumed_by_live = set()
    for node in sub_topo.order:
        if not ok.get(id(node), False):
            for i in node.inputs:
                consumed_by_live.add(id(i))
    for out in sub_topo.outputs:
        consumed_by_live.add(id(out))
    return [n for n in sub_topo.order
            if ok.get(id(n), False) and id(n) in consumed_by_live
            and id(n) not in seq_ph_ids]


class _RecurrentGroupImpl:
    def infer(self, cfg, in_sizes):
        return cfg["outs"][0].size

    def init(self, rng, cfg, in_sizes):
        # step-layer params are hoisted to the top level by
        # Topology._init_into (shared with generation mode by name)
        return {}

    def apply(self, ctx, cfg, params, *inputs):
        sub_topo: Topology = cfg["sub_topo"]
        n_seq, n_static = cfg["n_seq"], cfg["n_static"]
        n_sub = cfg.get("n_sub", 0)
        nested = n_sub > 0
        if nested:
            subs = []
            for v in inputs[:n_sub]:
                if not isinstance(v, NestedSequenceBatch):
                    raise ConfigError(
                        "SubsequenceInput needs a NestedSequenceBatch feed "
                        f"(got {type(v).__name__})")
                subs.append(v)
            n_lead = n_sub
        else:
            seqs = [as_seq(v) for v in inputs[:n_seq]]
            n_lead = n_seq
        statics = list(inputs[n_lead:n_lead + n_static])
        boots = list(inputs[n_lead + n_static:])
        sub_params = ctx.params

        ref = subs[0] if nested else seqs[0]
        bsz = ref.data.shape[0]

        # boot memories
        boot_vals = []
        bi = 0
        for ph, link_node, boot, boot_const in cfg["links"]:
            if isinstance(boot, LayerOutput):
                boot_vals.append(value_data(boots[bi]))
                bi += 1
            elif boot_const is not None:
                boot_vals.append(jnp.full((bsz, ph.size), float(boot_const)))
            else:
                boot_vals.append(jnp.zeros((bsz, ph.size)))

        mode = ctx.mode
        # independent key per scan step (folded in by rnn_ops.recurrent_group)
        # so per-step dropout masks decorrelate across time
        group_rng = ctx.next_rng() if ctx.rng is not None else None
        link_nodes = [ln for _, ln, _, _ in cfg["links"]]
        n_out = len(cfg["outs"])

        frame_phs = cfg["sub_phs"] if nested else cfg["seq_phs"]

        # scan-invariant hoist (flat groups): compute the memory-free,
        # row-wise prefix of the step graph over the WHOLE padded sequence
        # before the scan — big MXU matmuls instead of T small ones
        hoisted_names = []
        if not nested and HOIST_SCAN_INVARIANTS and seqs:
            frontier = _hoistable_frontier(sub_topo, cfg["seq_phs"], mode)
            if frontier:
                pre_topo = Topology(frontier)
                full_feed = {ph.name: s
                             for ph, s in zip(cfg["seq_phs"], seqs)}
                # no rng: the frontier is dropout-free by construction, and
                # skipping the split keeps the per-step rng stream identical
                # to the unhoisted graph
                pre_vals = pre_topo.apply(sub_params, full_feed, mode=mode)
                pre_vals = (pre_vals if isinstance(pre_vals, tuple)
                            and not isinstance(pre_vals, SequenceBatch)
                            else (pre_vals,))
                hoisted_names = [n.name for n in frontier]
                # hoisted values join the scanned inputs (engine slices
                # their time axis alongside the placeholders)
                seqs = list(seqs) + [as_seq(v) for v in pre_vals]

        def step_fn(mems, frames, step_rng=None):
            feed = {}
            for ph, frame in zip(frame_phs, frames):
                feed[ph.name] = frame
            pre = {name: frame for name, frame in
                   zip(hoisted_names, frames[len(frame_phs):])}
            for ph, s in zip(cfg["static_phs"], statics):
                feed[ph.name] = s
            for (ph, _, _, _), m in zip(cfg["links"], mems):
                feed[ph.name] = m
            # memory-link values come back as extra outputs of the SAME
            # apply — no per-link re-evaluation of the sub-graph
            vals = sub_topo.apply(sub_params, feed, mode=mode, rng=step_rng,
                                  extra_outputs=link_nodes, precomputed=pre)
            # NB: SequenceBatch/NestedSequenceBatch are NamedTuples — a
            # single sequence-valued output must not be unpacked fieldwise
            if not isinstance(vals, tuple) or isinstance(
                    vals, (SequenceBatch, NestedSequenceBatch)):
                vals = (vals,)
            # layout: [step outputs | consumer-link topo outputs (if any) |
            # link values appended by extra_outputs] — memories are always
            # the LAST len(links) entries
            n_links = len(cfg["links"])
            out_vals = vals[:n_out]
            new_mems = [value_data(v)
                        for v in (vals[len(vals) - n_links:]
                                  if n_links else ())]
            # nested groups keep sequence-valued step outputs whole so the
            # engine can stack them into a NestedSequenceBatch; flat groups
            # emit per-step rows
            if nested:
                outs_keep = tuple(v if isinstance(v, SequenceBatch)
                                  else value_data(v) for v in out_vals)
            else:
                outs_keep = tuple(value_data(v) for v in out_vals)
            return tuple(new_mems), outs_keep

        if group_rng is None:
            step = lambda mems, frames: step_fn(mems, frames)  # noqa: E731
        else:
            step = step_fn
        engine = (rnn_ops.nested_recurrent_group if nested
                  else rnn_ops.recurrent_group)
        outs, _ = engine(step, tuple(subs if nested else seqs),
                         tuple(boot_vals),
                         reverse=cfg["reverse"], rng=group_rng)
        # rnn_ops.recurrent_group maps over the input pytree; our step_fn
        # consumed a tuple of SequenceBatches and returned a tuple of outputs.
        # NB: SequenceBatch is itself a (named) tuple — test explicitly.
        def is_plain_tuple(v):
            return (isinstance(v, tuple)
                    and not isinstance(v, (SequenceBatch,
                                           NestedSequenceBatch)))

        result = outs[0] if (is_plain_tuple(outs) and len(outs) == 1) else outs
        ctx.aux[cfg["self_name"] + "/outputs"] = result
        return result[0] if is_plain_tuple(result) else result


register_layer("recurrent_group")(_RecurrentGroupImpl)


class _MemoryPlaceholderImpl:
    def infer(self, cfg, in_sizes):
        return 0

    def apply(self, ctx, cfg, params, *ins):
        raise RuntimeError("memory placeholders are fed by the group engine")


register_layer("__memory__")(_MemoryPlaceholderImpl)
register_layer("__step_input__")(_MemoryPlaceholderImpl)
register_layer("__static__")(_MemoryPlaceholderImpl)


def get_output_layer(input, arg_name=None, name=None, index=1):
    """Fetch a secondary output of a recurrent_group (reference
    GetOutputLayer).  index selects among the step function's outputs."""
    return LayerOutput(name or auto_name("get_output"), "get_output",
                       input.cfg["outs"][index].size, [input],
                       {"index": index, "group": input.cfg["self_name"]},
                       is_seq=True)


class _GetOutputImpl:
    def infer(self, cfg, in_sizes):
        return in_sizes[0]

    def apply(self, ctx, cfg, params, group_out):
        outs = ctx.aux.get(cfg["group"] + "/outputs")
        if not isinstance(outs, tuple):
            raise ConfigError("get_output_layer: group has a single output")
        return outs[cfg["index"]]


register_layer("get_output")(_GetOutputImpl)


# ----------------------------------------------------- step layers

class _LstmStepImpl:
    """One LSTM step as a layer (reference LstmStepLayer), for custom
    recurrent groups: inputs = (gate_input [B,4D], prev_state [B,D]);
    outputs h (primary); the cell state is exposed as output index 1 via
    a paired state node."""

    def infer(self, cfg, in_sizes):
        return cfg["size"]

    def init(self, rng, cfg, in_sizes):
        d = cfg["size"]
        if cfg.get("bias_attr", True) is False:
            return {}
        return {"b": jnp.zeros((7 * d,), dtypes.param_dtype())}

    def apply(self, ctx, cfg, params, x4, prev_state):
        d = cfg["size"]
        b = params.get("b")
        x4d, prev = value_data(x4), value_data(prev_state)
        if b is not None:
            x4d = x4d + b[:4 * d]
        ci = b[4 * d:5 * d] if b is not None else None
        cf = b[5 * d:6 * d] if b is not None else None
        co = b[6 * d:] if b is not None else None
        # prev_state carries [h | c] concatenated (2D wide)
        h_prev, c_prev = prev[..., :d], prev[..., d:]
        st = rnn_ops.lstm_cell(
            x4d, rnn_ops.LstmState(h=h_prev, c=c_prev),
            jnp.zeros((d, 4 * d), x4d.dtype),  # recurrence is in the mixed input
            check_i=ci, check_f=cf, check_o=co,
            act=cfg.get("act", "tanh"), gate_act=cfg.get("gate_act", "sigmoid"),
            state_act=cfg.get("state_act", "tanh"))
        return jnp.concatenate([st.h, st.c], axis=-1)


register_layer("lstm_step")(_LstmStepImpl)


def lstm_step_layer(input, state, size=None, act="tanh", gate_act="sigmoid",
                    state_act="tanh", name=None, bias_attr=True):
    d = size or input.size // 4
    return LayerOutput(name or auto_name("lstm_step"), "lstm_step", 2 * d,
                       [input, state],
                       {"size": d, "act": act, "gate_act": gate_act,
                        "state_act": state_act, "bias_attr": bias_attr})


class _GruStepImpl:
    def infer(self, cfg, in_sizes):
        return cfg["size"]

    def init(self, rng, cfg, in_sizes):
        d = cfg["size"]
        r1, r2 = jax.random.split(rng)
        wi = _winit(cfg.get("param_attr"), 1.0 / math.sqrt(d))
        p = {"w_gate": wi(r1, (d, 2 * d)), "w_state": wi(r2, (d, d))}
        if cfg.get("bias_attr", True) is not False:
            p["b"] = jnp.zeros((3 * d,), dtypes.param_dtype())
        return p

    def apply(self, ctx, cfg, params, x3, prev):
        x3d, h_prev = value_data(x3), value_data(prev)
        if "b" in params:
            x3d = x3d + params["b"]
        return rnn_ops.gru_cell(x3d, h_prev, params["w_gate"],
                                params["w_state"], act=cfg.get("act", "tanh"),
                                gate_act=cfg.get("gate_act", "sigmoid"))


register_layer("gru_step")(_GruStepImpl)


def gru_step_layer(input, output_mem, size=None, act="tanh",
                   gate_act="sigmoid", name=None, bias_attr=True,
                   param_attr=None):
    d = size or input.size // 3
    return LayerOutput(name or auto_name("gru_step"), "gru_step", d,
                       [input, output_mem],
                       {"size": d, "act": act, "gate_act": gate_act,
                        "bias_attr": bias_attr, "param_attr": param_attr})


def gru_step_naive_layer(input, output_mem, size=None, act="tanh",
                         gate_act="sigmoid", name=None, bias_attr=True,
                         param_attr=None, layer_attr=None):
    """Reference gru_step_naive_layer: gru_step built from mixed layers so
    error-clipping/dropout attrs apply.  XLA fuses the fused and naive
    formulations identically, so this is the same computation here."""
    return gru_step_layer(input, output_mem, size=size, act=act,
                          gate_act=gate_act, name=name, bias_attr=bias_attr,
                          param_attr=param_attr)


class _MDLstmImpl:
    """2-D multi-dimensional LSTM over image-shaped sequences (reference
    MDLstmLayer, REGISTER_LAYER(mdlstmemory); config_parser.py:3018)."""

    def infer(self, cfg, in_sizes):
        return cfg["size"] * cfg["h"] * cfg["w"]

    def init(self, rng, cfg, in_sizes):
        d = cfg["size"]
        r1, r2 = jax.random.split(rng)
        wi = _winit(cfg.get("param_attr"), 1.0 / math.sqrt(d))
        p = {"w_row": wi(r1, (d, 5 * d)), "w_col": wi(r2, (d, 5 * d))}
        if cfg.get("bias_attr", True) is not False:
            # 5d gate bias + 5d peepholes (i_row, i_col, f_row, f_col, o)
            p["b"] = jnp.zeros((10 * d,), dtypes.param_dtype())
        return p

    def apply(self, ctx, cfg, params, x):
        d, h, w = cfg["size"], cfg["h"], cfg["w"]
        xd = value_data(x).reshape(-1, h, w, 5 * d)
        b5 = params.get("b")
        checks = [None] * 5
        if b5 is not None:
            xd = xd + b5[:5 * d]
            checks = [b5[5 * d + k * d: 5 * d + (k + 1) * d]
                      for k in range(5)]
        out = rnn_ops.md_lstm_2d(
            xd, params["w_row"], params["w_col"],
            check_i_row=checks[0], check_i_col=checks[1],
            check_f_row=checks[2], check_f_col=checks[3], check_o=checks[4],
            act=cfg.get("act", "tanh"), gate_act=cfg.get("gate_act",
                                                         "sigmoid"),
            state_act=cfg.get("state_act", "tanh"))
        return out.reshape(out.shape[0], -1)


register_layer("mdlstmemory")(_MDLstmImpl)


def mdlstmemory(input, size=None, height=None, width=None, act="tanh",
                gate_act="sigmoid", state_act="tanh", name=None,
                bias_attr=True, param_attr=None):
    """input: image-shaped layer of 5*size channels (pre-projected gates);
    height/width default to the input's img_shape."""
    if height is None or width is None:
        if input.img_shape is None:
            raise ConfigError("mdlstmemory needs height/width (or an input "
                              "with img_shape)")
        height, width = input.img_shape
    d = size or input.size // (5 * height * width)
    node = LayerOutput(name or auto_name("mdlstm"), "mdlstmemory",
                       d * height * width, [input],
                       {"size": d, "h": height, "w": width, "act": act,
                        "gate_act": gate_act, "state_act": state_act,
                        "bias_attr": bias_attr, "param_attr": param_attr},
                       is_seq=False, num_filters=d, img_shape=(height, width))
    return node

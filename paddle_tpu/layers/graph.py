"""Layer-graph IR + compiler.

The reference's front-end is a Python DSL whose ctors register layer configs
into a global proto (config_parser.py:166-184 @config_layer registries,
emitting ModelConfig — "the protobuf IS the IR", SURVEY.md §1).  The
TPU-native redesign keeps the DSL surface but compiles to a *functional* IR:

  ctor (fc_layer, lstmemory, ...) -> LayerOutput node (name, type, size, inputs)
  Topology(outputs)               -> topological order over nodes
  Topology.init(rng)              -> params pytree {layer_name: {param: array}}
  Topology.apply(params, feed)    -> pure function, jit/grad/pjit-able

Values flowing between layers are either plain arrays [B, D] (one row per
sample) or SequenceBatch (padded [B, T, D] + lengths) — the reference's
Argument with sequenceStartPositions.  Layer kernels accept both via
row-mapping (the reference's layers see a flat row matrix either way).

Each layer type registers a LayerImpl:
  infer(cfg, in_sizes) -> output size
  init(rng, cfg, in_sizes) -> param dict (may be {})
  apply(ctx, cfg, params, *inputs) -> output value
"""

import collections
import dataclasses
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core.sequence import NestedSequenceBatch, SequenceBatch
from paddle_tpu.utils.error import ConfigError

_LAYER_IMPLS: Dict[str, "LayerImpl"] = {}
_NAME_COUNTERS: Dict[str, int] = {}

# observers notified of every LayerOutput constructed — the recurrent_group
# tracer uses this to see step-graph nodes that are CONSUMERS of the step
# outputs (e.g. `last_seq(inner_out, name="outer_rnn_state")` as a memory
# link target, the reference sequence_nest_rnn.conf pattern)
_NODE_OBSERVERS: List[Callable] = []


@dataclasses.dataclass
class LayerImpl:
    type: str
    infer: Callable            # (cfg, in_sizes) -> int
    init: Callable             # (rng, cfg, in_sizes) -> dict
    apply: Callable            # (ctx, cfg, params, *inputs) -> value


def register_layer(type_name):
    def deco(cls_or_fns):
        impl = cls_or_fns() if isinstance(cls_or_fns, type) else cls_or_fns
        _LAYER_IMPLS[type_name] = LayerImpl(
            type=type_name,
            infer=getattr(impl, "infer"),
            init=getattr(impl, "init", lambda rng, cfg, in_sizes: {}),
            apply=getattr(impl, "apply"))
        return cls_or_fns
    return deco


def get_impl(type_name) -> LayerImpl:
    try:
        return _LAYER_IMPLS[type_name]
    except KeyError:
        raise ConfigError(f"no layer impl registered for type {type_name!r}")


def auto_name(prefix):
    n = _NAME_COUNTERS.get(prefix, 0)
    _NAME_COUNTERS[prefix] = n + 1
    return f"__{prefix}_{n}__"


def reset_names():
    _NAME_COUNTERS.clear()


class LayerOutput:
    """A node in the layer graph (reference: the LayerOutput returned by every
    trainer_config_helpers ctor, wrapping a config_parser Layer)."""

    __slots__ = ("name", "layer_type", "size", "inputs", "cfg", "is_seq",
                 "num_filters", "img_shape")

    def __init__(self, name, layer_type, size, inputs=(), cfg=None,
                 is_seq=None, num_filters=None, img_shape=None):
        self.name = name
        self.layer_type = layer_type
        self.size = int(size)
        self.inputs: List[LayerOutput] = list(inputs)
        self.cfg = dict(cfg or {})
        # sequence-ness propagates: seq in -> seq out unless overridden
        if is_seq is None:
            is_seq = any(getattr(i, "is_seq", False) for i in self.inputs)
        self.is_seq = is_seq
        self.num_filters = num_filters      # conv image metadata
        self.img_shape = img_shape          # (h, w) after this layer
        for obs in _NODE_OBSERVERS:
            obs(self)

    def __repr__(self):
        return (f"LayerOutput({self.name}, {self.layer_type}, size={self.size}"
                f"{', seq' if self.is_seq else ''})")

    # arithmetic operators are installed by paddle_tpu.layers.layer_math
    # (the reference layer_math.py monkeypatches +,-,* the same way)


class Context:
    """Per-apply execution context: mode, rng, mutable-state collection
    (batch-norm moving stats thread through here, functionally)."""

    def __init__(self, mode="train", rng=None, state=None, params=None):
        self.mode = mode                  # "train" | "test"
        self.rng = rng
        self.state_in = state or {}       # {layer_name: pytree} (e.g. BN stats)
        self.state_out = {}
        self.aux = {}                     # scratch (e.g. recurrent_group outputs)
        # full top-level params dict: container layers (recurrent_group,
        # beam_search) apply their step sub-graphs against this, so step-layer
        # params live at top level under their own param-sharing keys and flow
        # between training groups and generation (reference shares by layer
        # name across sub-models the same way, config_parser.py sub_models)
        self.params = params

    def is_train(self):
        return self.mode == "train"

    def next_rng(self):
        if self.rng is None:
            raise ConfigError("this graph needs an rng (dropout/sampling); "
                              "pass rng= to Topology.apply")
        self.rng, sub = jax.random.split(self.rng)
        return sub

    def get_state(self, name, default_fn):
        if name in self.state_in:
            return self.state_in[name]
        return default_fn()

    def put_state(self, name, value):
        self.state_out[name] = value


# ---------------------------------------------------------------- helpers

from functools import partial


@partial(jax.custom_vjp, nondiff_argnums=(1,))
def _error_clip(x, threshold):
    """Identity forward; backward clips the incoming gradient to
    [-threshold, threshold] elementwise (reference ExtraLayerAttribute
    error_clipping_threshold, Layer.cpp backwardActivation clipping)."""
    return x


def _error_clip_fwd(x, threshold):
    return x, None


def _error_clip_bwd(threshold, _, g):
    return (jnp.clip(g, -threshold, threshold),)


_error_clip.defvjp(_error_clip_fwd, _error_clip_bwd)


def value_data(v):
    return v.data if isinstance(v, (SequenceBatch, NestedSequenceBatch)) \
        else v


def map_rows(fn, *values):
    """Apply a row-wise fn to values that may be SequenceBatch,
    NestedSequenceBatch, or arrays.  If any input is a (nested) sequence,
    output keeps its lengths structure."""
    seq = next((v for v in values
                if isinstance(v, (SequenceBatch, NestedSequenceBatch))), None)
    datas = [value_data(v) for v in values]
    out = fn(*datas)
    if isinstance(seq, NestedSequenceBatch):
        return NestedSequenceBatch(data=out,
                                   outer_lengths=seq.outer_lengths,
                                   inner_lengths=seq.inner_lengths)
    if isinstance(seq, SequenceBatch):
        return SequenceBatch(data=out, lengths=seq.lengths)
    return out


def as_seq(v) -> SequenceBatch:
    if not isinstance(v, SequenceBatch):
        raise ConfigError(f"expected a sequence input, got array {getattr(v, 'shape', v)}")
    return v


# ---------------------------------------------------------------- topology

class Topology:
    """Compiled graph over one or more output layers (reference:
    v2/topology.py Topology walking cost layers -> ModelConfig)."""

    def __init__(self, outputs, extra_feeds=()):
        if isinstance(outputs, LayerOutput):
            outputs = [outputs]
        self.outputs = list(outputs)
        self.order = self._topo_sort(self.outputs)
        self._lstm_projections = self._find_lstm_projections()
        self.data_layers = {n.name: n for n in self.order
                            if n.layer_type == "data"}
        for feed in extra_feeds:
            self.data_layers.setdefault(feed.name, feed)

    @staticmethod
    def _topo_sort(outputs):
        seen, order = set(), []

        def visit(node, stack):
            if id(node) in seen:
                return
            if id(node) in stack:
                raise ConfigError(f"cycle through layer {node.name}")
            stack = stack | {id(node)}
            for dep in node.inputs:
                visit(dep, stack)
            seen.add(id(node))
            order.append(node)

        for out in outputs:
            visit(out, frozenset())
        return order

    def _find_lstm_projections(self):
        """{id(lstmemory node): the fc that feeds it} for every pair built
        as ``networks.simple_lstm`` builds it: an ``fc`` of ONE input with
        no activation, dropout or error clipping, read by that lstmemory
        alone and not an output.  ``apply`` hands such an fc's input and
        weight to the lstmemory unapplied (``ops.rnn.lstm(proj=)``: the
        fused kernel forms the gate inputs in VMEM), unless the parameters
        hold a bias for the fc or the call wants its value."""
        readers = collections.Counter(
            id(i) for node in self.order for i in node.inputs)
        outputs = {id(o) for o in self.outputs}
        pairs = {}
        for node in self.order:
            if node.layer_type != "lstmemory" or len(node.inputs) != 1:
                continue
            fc = node.inputs[0]
            if (fc.layer_type == "fc" and len(fc.inputs) == 1
                    and fc.cfg.get("act") is None
                    and not fc.cfg.get("drop_rate")
                    and not fc.cfg.get("error_clipping_threshold")
                    and readers[id(fc)] == 1 and id(fc) not in outputs):
                pairs[id(node)] = fc
        return pairs

    def init(self, rng):
        """Initialize all parameters: {layer_name: {param_name: array}}.

        Layers with shared parameters (cfg['param_name']) alias the same
        entry keyed by that shared name.  Step sub-graphs of container layers
        (recurrent_group / beam_search) are initialized INTO the same
        top-level dict under their own param-sharing keys, so a decoder
        trained via recurrent_group and its generation-mode beam_search read
        the same weights when their step layers share names."""
        params = {}
        self._init_into(params, rng)
        return params

    def _init_into(self, params, rng):
        for node in self.order:
            sub = node.cfg.get("sub_topo")
            if isinstance(sub, Topology):
                rng, sk = jax.random.split(rng)
                sub._init_into(params, sk)
            impl = get_impl(node.layer_type)
            in_sizes = [i.size for i in node.inputs]
            rng, sub_rng = jax.random.split(rng)
            p = impl.init(sub_rng, node.cfg, in_sizes)
            if p:
                key = self._param_key(node)
                if key not in params:
                    params[key] = p
        return rng

    def _param_key(self, node):
        """Parameter-sharing key: explicit cfg['param_name'], else a
        ParamAttr name (the reference's ParameterAttribute(name=...) sharing
        mechanism), else the layer name."""
        if "param_name" in node.cfg:
            return node.cfg["param_name"]
        pa = node.cfg.get("param_attr")
        if isinstance(pa, dict) and pa.get("name"):
            return pa["name"]
        return node.name

    def apply(self, params, feed, mode="train", rng=None, state=None,
              return_state=False, extra_outputs=(), precomputed=None):
        """Run the graph.  feed: {data_layer_name: array|SequenceBatch}.
        precomputed: {node_name: value} — nodes whose values were computed
        elsewhere (the recurrent_group scan-invariant hoist) are taken as-is
        instead of re-applied."""
        ctx = Context(mode=mode, rng=rng, state=state, params=params)
        cache = {}
        wanted = {id(o) for o in extra_outputs}
        projections = {
            lstm: fc for lstm, fc in self._lstm_projections.items()
            if id(fc) not in wanted
            and not (precomputed and fc.name in precomputed)
            and "b" not in params.get(self._param_key(fc), {})}
        unapplied = {id(fc) for fc in projections.values()}
        for node in self.order:
            if id(node) in unapplied:
                continue
            if precomputed and node.name in precomputed:
                cache[id(node)] = precomputed[node.name]
                continue
            if node.layer_type == "data":
                if node.name not in feed:
                    raise ConfigError(f"missing feed for data layer {node.name!r}")
                cache[id(node)] = feed[node.name]
                continue
            # recurrent_group feeds its step/memory/static placeholders by
            # name on each scan step
            if node.layer_type.startswith("__") and node.name in feed:
                cache[id(node)] = feed[node.name]
                continue
            impl = get_impl(node.layer_type)
            fc = projections.get(id(node))
            ins = [cache[id(i)]
                   for i in (node if fc is None else fc).inputs]
            p = params.get(self._param_key(node), {})
            try:
                kwargs = {} if fc is None else {
                    "proj": params[self._param_key(fc)]["w0"]}
                val = impl.apply(ctx, node.cfg, p, *ins, **kwargs)
                # reference ExtraLayerAttribute(drop_rate=...) applies to any
                # layer's output; fc/mixed/dropout handle it inside their
                # impls, everything else gets it here
                rate = node.cfg.get("drop_rate", 0.0)
                if (rate and ctx.is_train()
                        and node.layer_type not in ("fc", "mixed", "dropout")):
                    def _drop(x, rate=rate):
                        keep = jax.random.bernoulli(ctx.next_rng(),
                                                    1.0 - rate, x.shape)
                        return jnp.where(keep, x / (1.0 - rate), 0.0)
                    val = map_rows(_drop, val)
                ect = node.cfg.get("error_clipping_threshold")
                if ect:
                    val = map_rows(lambda d: _error_clip(d, float(ect)), val)
                cache[id(node)] = val
            except Exception as e:
                # the reference dumps the active layer-name stack on FATAL
                # (utils/CustomStackTrace.h, pushed NeuralNetwork.cpp:247);
                # name the failing layer the same way
                if hasattr(e, "add_note"):
                    e.add_note(f"while applying layer {node.name!r} "
                               f"(type {node.layer_type!r})")
                raise
        outs = [cache[id(o)] for o in self.outputs]
        outs += [cache[id(o)] for o in extra_outputs if id(o) in cache]
        result = outs[0] if len(outs) == 1 else tuple(outs)
        if return_state:
            return result, ctx.state_out
        return result

    def init_state(self):
        """Initial mutable state (BN moving stats) for all layers that need it."""
        state = {}
        for node in self.order:
            if node.layer_type == "batch_norm":
                size = node.cfg["size"]
                state[node.name] = (jnp.zeros((size,)), jnp.ones((size,)))
        return state

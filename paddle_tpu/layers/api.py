"""v1-DSL layer constructors (dense / sequence / cost / util layers).

Reference surface: python/paddle/trainer_config_helpers/layers.py (~100
ctors, __all__ at :33-122) with size-inference semantics from
python/paddle/trainer/config_parser.py's @config_layer classes.  Vision
layers live in vision.py, recurrent machinery in recurrent.py.

Every ctor returns a LayerOutput graph node; compilation/execution is in
graph.py.  Hand-written C++ backward passes are replaced by jax.grad.
"""

import math

import jax
import jax.numpy as jnp

from paddle_tpu.core import dtypes
from paddle_tpu.core.sequence import SequenceBatch
from paddle_tpu.layers.graph import (
    LayerOutput, register_layer, auto_name, map_rows, as_seq, value_data)
from paddle_tpu.ops import (activations, linear, losses, math_ops, embedding as
                            emb_ops, sequence as seq_ops, crf as crf_ops,
                            ctc as ctc_ops, sampling as sampling_ops,
                            rnn as rnn_ops)
from paddle_tpu.utils.error import ConfigError

__all__ = [
    "data_layer", "fc_layer", "embedding_layer", "dropout_layer",
    "addto_layer", "concat_layer", "interpolation_layer", "power_layer",
    "scaling_layer", "slope_intercept_layer", "linear_comb_layer",
    "convex_comb_layer", "sum_to_one_norm_layer", "cos_sim",
    "out_prod_layer", "trans_layer", "rotate_layer", "tensor_layer",
    "multiplex_layer", "conv_shift_layer", "featmap_expand_layer",
    "resize_layer", "prelu_layer", "selective_fc_layer",
    "pooling_layer", "last_seq", "first_seq", "expand_layer",
    "seq_concat_layer", "seq_reshape_layer", "sub_seq_layer",
    "seq_slice_layer", "maxid_layer", "eos_layer", "sampling_id_layer",
    "print_layer", "mixed_layer", "full_matrix_projection",
    "trans_full_matrix_projection", "identity_projection", "table_projection",
    "dotmul_projection", "scaling_projection", "context_projection",
    "dotmul_operator",
    "classification_cost", "regression_cost", "mse_cost", "cross_entropy",
    "cross_entropy_with_selfnorm", "soft_binary_class_cross_entropy",
    "multi_binary_label_cross_entropy", "rank_cost", "lambda_cost",
    "huber_cost", "smooth_l1_cost", "sum_cost", "crf_layer",
    "crf_decoding_layer", "ctc_layer", "warp_ctc_layer", "nce_layer",
    "hsigmoid", "pooling", "slice_projection",
    "AggregateLevel", "ExpandLevel", "repeat_layer",
    "moe_layer",
]


# ---------------------------------------------------------------- helpers

def _winit(param_attr, default_std=None):
    """Weight initializer from a ParamAttr-style dict (reference
    ParameterAttribute: initial_mean/initial_std, default std=1/sqrt(fan_in)
    per config_parser Parameter defaults)."""
    attr = param_attr or {}
    if callable(attr.get("init")):
        return attr["init"]

    def init(rng, shape, dtype=None):
        dtype = dtype or dtypes.param_dtype()
        std = attr.get("initial_std", default_std)
        mean = attr.get("initial_mean", 0.0)
        if std is None:
            std = 1.0 / math.sqrt(max(shape[0], 1))
        if attr.get("initial_strategy", 0) == 1:  # uniform
            return jax.random.uniform(rng, shape, dtype, -std, std) + mean
        return mean + std * jax.random.normal(rng, shape, dtype)
    return init


def _maybe_bias(rng, bias_attr, size):
    if bias_attr is False or bias_attr is None:
        return None
    attr = bias_attr if isinstance(bias_attr, dict) else {}
    std = attr.get("initial_std", 0.0)
    mean = attr.get("initial_mean", 0.0)
    b = jnp.full((size,), mean, dtypes.param_dtype())
    if std:
        b = b + std * jax.random.normal(rng, (size,), dtypes.param_dtype())
    return b


def _dropout(ctx, cfg, value):
    rate = cfg.get("drop_rate", 0.0)
    if not rate or not ctx.is_train():
        return value
    def drop(x):
        keep = jax.random.bernoulli(ctx.next_rng(), 1.0 - rate, x.shape)
        return jnp.where(keep, x / (1.0 - rate), 0.0)
    return map_rows(drop, value)


def _inputs_list(input):
    return list(input) if isinstance(input, (list, tuple)) else [input]


# ---------------------------------------------------------------- data

class _DataImpl:
    def infer(self, cfg, in_sizes):
        return cfg["size"]

    def apply(self, ctx, cfg, params):
        raise RuntimeError("data layers are fed, not applied")


register_layer("data")(_DataImpl)


def data_layer(name, size, is_seq=False, height=None, width=None):
    """Reference: data_layer(name, size) (layers.py DataLayer); height/width
    carry image shape for the conv stack."""
    img = (height, width) if height and width else None
    return LayerOutput(name, "data", size, cfg={"size": size}, is_seq=is_seq,
                       img_shape=img)


# ---------------------------------------------------------------- fc

class _FcImpl:
    def infer(self, cfg, in_sizes):
        return cfg["size"]

    def init(self, rng, cfg, in_sizes):
        p = {}
        rngs = jax.random.split(rng, len(in_sizes) + 1)
        pa = cfg.get("param_attr")
        # reference fc_layer accepts one ParamAttr per input (sentiment's
        # stacked_lstm_net passes [fc_attr, lstm_attr])
        pas = (list(pa) if isinstance(pa, (list, tuple))
               else [pa] * len(in_sizes))
        for i, isz in enumerate(in_sizes):
            p[f"w{i}"] = _winit(pas[i % len(pas)])(rngs[i],
                                                   (isz, cfg["size"]))
        b = _maybe_bias(rngs[-1], cfg.get("bias_attr", True), cfg["size"])
        if b is not None:
            p["b"] = b
        return p

    def apply(self, ctx, cfg, params, *inputs):
        def fn(*datas):
            y = linear.matmul(datas[0], params["w0"])
            for i in range(1, len(datas)):
                y = y + linear.matmul(datas[i], params[f"w{i}"])
            if "b" in params:
                y = y + params["b"]
            return activations.get(cfg.get("act"))(y)
        return _dropout(ctx, cfg, map_rows(fn, *inputs))


register_layer("fc")(_FcImpl)


# ---------------------------------------------------------------- moe

class _MoeImpl:
    """Mixture-of-experts FFN over the row dimension (ops/moe.py) — a
    post-reference capability layer; experts shard over the 'expert' mesh
    axis under a mesh trainer (moe.expert_shardings)."""

    def infer(self, cfg, in_sizes):
        return in_sizes[0]

    def init(self, rng, cfg, in_sizes):
        from paddle_tpu.ops import moe
        return moe.init_moe(rng, in_sizes[0], cfg["expert_dim"],
                            cfg["n_experts"])

    def apply(self, ctx, cfg, params, x):
        from paddle_tpu.ops import moe

        def fn(d):
            # moe_ffn wants [B, T, D]; flatten any leading dims (dense
            # [B, D] and nested-sequence [B, S, T, D] included) and restore
            lead = d.shape[:-1]
            out = moe.moe_ffn(d.reshape(1, -1, d.shape[-1]), params,
                              top_k=cfg["top_k"])
            return out.reshape(*lead, d.shape[-1])
        return map_rows(fn, x)


register_layer("moe")(_MoeImpl)


def moe_layer(input, n_experts, expert_dim=None, top_k=2, name=None):
    """Gated mixture-of-experts FFN: `n_experts` experts of hidden width
    `expert_dim` (default 4x the input size), top_k-gated, residual-free
    (compose with addto_layer for a residual block).  Output size ==
    input size."""
    ins = _inputs_list(input)
    if len(ins) != 1:
        from paddle_tpu.utils.error import ConfigError
        raise ConfigError("moe_layer takes a single input (got "
                          f"{len(ins)}); concat upstream if needed")
    cfg = {"n_experts": n_experts, "top_k": top_k,
           "expert_dim": expert_dim or 4 * ins[0].size}
    return LayerOutput(name or auto_name("moe"), "moe", ins[0].size, ins, cfg)


def fc_layer(input, size, act="tanh", name=None, bias_attr=True,
             param_attr=None, layer_attr=None):
    ins = _inputs_list(input)
    cfg = {"size": size, "act": act, "bias_attr": bias_attr,
           "param_attr": param_attr}
    cfg.update(layer_attr or {})
    return LayerOutput(name or auto_name("fc"), "fc", size, ins, cfg)


# ---------------------------------------------------------------- embedding

class _EmbeddingImpl:
    def infer(self, cfg, in_sizes):
        return cfg["size"]

    def init(self, rng, cfg, in_sizes):
        return {"w": _winit(cfg.get("param_attr"),
                            default_std=1.0 / math.sqrt(cfg["vocab"]))(
            rng, (cfg["vocab"], cfg["size"]))}

    def apply(self, ctx, cfg, params, ids):
        def fn(d):
            return emb_ops.embedding_lookup(params["w"], d.astype(jnp.int32))
        return map_rows(fn, ids)


register_layer("embedding")(_EmbeddingImpl)


def embedding_layer(input, size, name=None, param_attr=None,
                    sparse_update=None, sparse_budget=None):
    """input: a data layer of integer ids (its .size = vocab size).

    sparse_update=True (reference ParameterAttribute sparse_update /
    SparseRowMatrix): the trainer gathers only the rows touched this batch,
    differentiates and optimizer-updates that [budget, D] block, and
    scatters it back — step cost scales with touched rows, not vocab.
    sparse_budget: static unique-row cap (default: batch token count rounded
    up to a power of two)."""
    if sparse_update is None and isinstance(param_attr, dict):
        sparse_update = param_attr.get("sparse_update", False)
    return LayerOutput(name or auto_name("embedding"), "embedding", size,
                       [input],
                       cfg={"size": size, "vocab": input.size,
                            "param_attr": param_attr,
                            "sparse_update": bool(sparse_update),
                            "sparse_budget": sparse_budget})


def table_projection(input, size=0, param_attr=None):
    """size=0 takes the enclosing mixed layer's width (reference
    table_projection default)."""
    return _Part("table", [input], {"param_attr": param_attr}, size)


# ---------------------------------------------------------------- mixed

class _MixedImpl:
    """MixedLayer: sum of projections/operators (reference MixedLayer.cpp).
    cfg['parts']: list of (kind, spec) aligned with the node's inputs list
    (one input per part; operators consume two)."""

    def infer(self, cfg, in_sizes):
        # config-time width check (reference MixedLayer asserts every
        # projection's output height/width against the layer size)
        size, idx = cfg["size"], 0
        for kind, spec in cfg["parts"]:
            isz = in_sizes[idx] if idx < len(in_sizes) else None
            out = None
            if kind == "identity":
                out = spec.get("size") or isz
            elif kind in ("dotmul", "scaling", "dotmul_op"):
                out = isz
            elif kind == "context":
                out = isz * spec["context_len"]
            if out is not None and out != size:
                raise ConfigError(
                    f"mixed_layer(size={size}): {kind} projection yields "
                    f"size {out} — all parts must produce the layer size")
            idx += 2 if kind in ("dotmul_op", "conv_op") else 1
        return size

    def init(self, rng, cfg, in_sizes):
        p = {}
        idx = 0
        rngs = jax.random.split(rng, len(cfg["parts"]) + 1)
        for k, (kind, spec) in enumerate(cfg["parts"]):
            isz = in_sizes[idx]
            if kind == "full_matrix":
                p[f"w{k}"] = _winit(spec.get("param_attr"))(rngs[k], (isz, cfg["size"]))
            elif kind == "trans_full_matrix":
                p[f"w{k}"] = _winit(spec.get("param_attr"))(rngs[k], (cfg["size"], isz))
            elif kind == "table":
                p[f"w{k}"] = _winit(spec.get("param_attr"))(
                    rngs[k], (spec["vocab"], cfg["size"]))
            elif kind == "dotmul":
                p[f"w{k}"] = jnp.ones((cfg["size"],), dtypes.param_dtype())
            elif kind == "scaling":
                p[f"w{k}"] = jnp.ones((1,), dtypes.param_dtype())
            elif kind == "context" and spec.get("trainable_padding"):
                pad_rows = max(0, -spec["context_start"]) + max(
                    0, spec["context_start"] + spec["context_len"] - 1)
                p[f"w{k}"] = _winit(spec.get("param_attr"))(rngs[k], (pad_rows, isz))
            elif kind == "conv_proj":
                fh, fw = spec["filter_size"]
                g = spec.get("groups", 1) or 1
                p[f"w{k}"] = _winit(spec.get("param_attr"))(
                    rngs[k], (fh, fw, spec["channels"] // g,
                              spec["num_filters"]))
            idx += 2 if kind in ("dotmul_op", "conv_op") else 1
        b = _maybe_bias(rngs[-1], cfg.get("bias_attr", False), cfg["size"])
        if b is not None:
            p["b"] = b
        return p

    def apply(self, ctx, cfg, params, *inputs):
        from paddle_tpu.ops import conv as conv_ops
        total = None
        idx = 0
        for k, (kind, spec) in enumerate(cfg["parts"]):
            if kind == "dotmul_op":
                a, b2 = inputs[idx], inputs[idx + 1]
                part = map_rows(lambda x, y: spec.get("scale", 1.0) * x * y, a, b2)
                idx += 2
            elif kind == "conv_op":
                # reference ConvOperator.cpp:58-83: per-sample conv, each
                # row of input(1) is that sample's own filter -> vmap
                img, filt = inputs[idx], inputs[idx + 1]
                idx += 2
                c, (h, w) = spec["channels"], spec["in_shape"]
                fh, fw = spec["filter_size"]
                nf = spec["num_filters"]

                def one(img_row, filt_row):
                    x = img_row.reshape(c, h, w).transpose(1, 2, 0)[None]
                    wgt = filt_row.reshape(nf, c, fh, fw).transpose(2, 3, 1, 0)
                    y = conv_ops.conv2d(x, wgt, stride=spec["stride"],
                                        padding=spec["padding"])
                    return y.transpose(0, 3, 1, 2).reshape(-1)

                part = map_rows(
                    lambda im, fl: jax.vmap(one)(im, fl), img, filt)
            else:
                v = inputs[idx]
                idx += 1
                if kind == "full_matrix":
                    part = map_rows(lambda d: linear.matmul(d, params[f"w{k}"]), v)
                elif kind == "trans_full_matrix":
                    part = map_rows(lambda d: linear.matmul(d, params[f"w{k}"].T), v)
                elif kind == "table":
                    part = map_rows(lambda d: emb_ops.embedding_lookup(
                        params[f"w{k}"], d.astype(jnp.int32)), v)
                elif kind == "identity":
                    off = spec.get("offset", 0)
                    sz = spec.get("size")
                    part = map_rows(
                        lambda d: d if sz is None else d[..., off:off + sz], v)
                elif kind == "dotmul":
                    part = map_rows(lambda d: d * params[f"w{k}"], v)
                elif kind == "scaling":
                    part = map_rows(lambda d: d * params[f"w{k}"].reshape(()), v)
                elif kind == "context":
                    part = seq_ops.context_projection(
                        as_seq(v), spec["context_len"], spec["context_start"],
                        params.get(f"w{k}"))
                elif kind == "conv_proj":
                    c, (h, w) = spec["channels"], spec["in_shape"]

                    def conv_rows(d):
                        x = d.reshape(d.shape[0], c, h, w).transpose(0, 2, 3, 1)
                        y = conv_ops.conv2d(x, params[f"w{k}"],
                                            stride=spec["stride"],
                                            padding=spec["padding"],
                                            groups=spec.get("groups", 1) or 1)
                        return y.transpose(0, 3, 1, 2).reshape(d.shape[0], -1)

                    part = map_rows(conv_rows, v)
                else:
                    raise ConfigError(f"unknown mixed part {kind}")
            total = part if total is None else map_rows(
                lambda a, b3: a + b3, total, part)
        if "b" in params:
            total = map_rows(lambda d: d + params["b"], total)
        out = map_rows(activations.get(cfg.get("act")), total)
        return _dropout(ctx, cfg, out)


register_layer("mixed")(_MixedImpl)


class _Part:
    """A projection/operator awaiting inclusion in mixed_layer."""

    def __init__(self, kind, input_nodes, spec, out_size):
        self.kind = kind
        self.inputs = input_nodes
        self.spec = spec
        self.out_size = out_size


def full_matrix_projection(input, size=0, param_attr=None):
    return _Part("full_matrix", [input], {"param_attr": param_attr}, size)


def trans_full_matrix_projection(input, size=0, param_attr=None):
    return _Part("trans_full_matrix", [input], {"param_attr": param_attr}, size)


def identity_projection(input, offset=None, size=None):
    if offset is None:
        return _Part("identity", [input], {}, input.size)
    out = size if size is not None else input.size - offset
    return _Part("identity", [input], {"offset": offset, "size": out}, out)


def slice_projection(input, slices):
    """Reference slice_projection: concat of [start, end) column slices."""
    parts = []
    for s, e in slices:
        parts.append(_Part("identity", [input], {"offset": s, "size": e - s}, e - s))
    return parts


def dotmul_projection(input, param_attr=None):
    return _Part("dotmul", [input], {"param_attr": param_attr}, input.size)


def scaling_projection(input, param_attr=None):
    return _Part("scaling", [input], {"param_attr": param_attr}, input.size)


def context_projection(input, context_len, context_start=None,
                       padding_attr=False):
    start = context_start if context_start is not None else -(context_len // 2)
    return _Part("context", [input],
                 {"context_len": context_len, "context_start": start,
                  "trainable_padding": bool(padding_attr),
                  "param_attr": padding_attr if isinstance(padding_attr, dict) else None},
                 input.size * context_len)


def dotmul_operator(a, b, scale=1.0):
    return _Part("dotmul_op", [a, b], {"scale": scale}, a.size)


def _collect_parts(input):
    parts = []
    for item in _inputs_list(input):
        if isinstance(item, list):
            parts.extend(item)
        elif isinstance(item, _Part):
            parts.append(item)
        elif isinstance(item, LayerOutput):
            parts.append(identity_projection(item))
        else:
            raise ConfigError(f"bad mixed_layer input {item!r}")
    return parts


def _finalize_mixed(node, parts, size):
    if size == 0:
        size = max(p.out_size for p in parts)
    nodes = []
    cfg_parts = []
    for p in parts:
        spec = dict(p.spec)
        if p.kind == "table":
            spec["vocab"] = p.inputs[0].size
        cfg_parts.append((p.kind, spec))
        nodes.extend(p.inputs)
    node.size = int(size)
    node.inputs = nodes
    # inputs arrive after construction: recompute sequence-ness propagation
    node.is_seq = any(getattr(n, "is_seq", False) for n in nodes)
    node.cfg.update({"size": size, "parts": cfg_parts})
    return node


class MixedLayer(LayerOutput):
    """Deferred mixed layer supporting the reference's builder protocol:

        with mixed_layer(size=d) as m:
            m += full_matrix_projection(input=a)
            m += identity_projection(input=b)

    The `as` target IS the LayerOutput (used downstream after the with);
    projections accumulate via += and the node finalizes on __exit__."""

    def __init__(self, size, name, act, bias_attr, layer_attr):
        super().__init__(name or auto_name("mixed"), "mixed", max(size, 1),
                         [], {"size": size, "act": act,
                              "bias_attr": bias_attr, "parts": []})
        self.cfg.update(layer_attr or {})
        self._parts = []
        self._decl_size = size
        self._finalized = False

    def __iadd__(self, part):
        if self._finalized:
            raise ConfigError("mixed_layer already finalized")
        self._parts.extend(_collect_parts(part))
        return self

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb):
        if exc_type is None:
            if not self._parts:
                raise ConfigError("empty mixed_layer: add projections "
                                  "with += inside the with block")
            _finalize_mixed(self, self._parts, self._decl_size)
            self._finalized = True
        return False


def mixed_layer(size=0, input=None, name=None, act=None, bias_attr=False,
                layer_attr=None):
    if input is None:
        return MixedLayer(size, name, act, bias_attr, layer_attr)
    parts = _collect_parts(input)
    cfg = {"size": size, "act": act, "bias_attr": bias_attr, "parts": []}
    cfg.update(layer_attr or {})
    node = LayerOutput(name or auto_name("mixed"), "mixed", max(size, 1),
                       [], cfg)
    return _finalize_mixed(node, parts, size)


# ------------------------------------------------------- elementwise layers

def _simple_layer(type_name, infer_fn, apply_fn, needs=None):
    class Impl:
        def infer(self, cfg, in_sizes):
            return infer_fn(cfg, in_sizes)

        def apply(self, ctx, cfg, params, *inputs):
            return apply_fn(ctx, cfg, *inputs)
    register_layer(type_name)(Impl)


_simple_layer("addto", lambda cfg, s: s[0],
              lambda ctx, cfg, *ins: map_rows(
                  lambda *ds: activations.get(cfg.get("act"))(
                      sum(ds[1:], ds[0])), *ins))


def addto_layer(input, act=None, name=None, bias_attr=False):
    ins = _inputs_list(input)
    return LayerOutput(name or auto_name("addto"), "addto", ins[0].size, ins,
                       {"act": act})


_simple_layer("concat", lambda cfg, s: sum(s),
              lambda ctx, cfg, *ins: map_rows(
                  lambda *ds: jnp.concatenate(ds, axis=-1), *ins))


def concat_layer(input, act=None, name=None, bias_attr=False,
                 layer_attr=None):
    # the reference concat accepts projections too (concat_layer(input=
    # [identity_projection(a), ...])) — realize each as a one-part mixed
    ins = [mixed_layer(size=item.out_size, input=[item], act=None)
           if isinstance(item, _Part) else item
           for item in _inputs_list(input)]
    return LayerOutput(name or auto_name("concat"), "concat",
                       sum(i.size for i in ins), ins, {"act": act})


_simple_layer("interpolation", lambda cfg, s: s[1],
              lambda ctx, cfg, w, a, b: map_rows(math_ops.interpolation, w, a, b))


def interpolation_layer(input, weight, name=None):
    a, b = input
    return LayerOutput(name or auto_name("interpolation"), "interpolation",
                       a.size, [weight, a, b], {})


_simple_layer("power", lambda cfg, s: s[1],
              lambda ctx, cfg, p, x: map_rows(math_ops.power, p, x))


def power_layer(input, weight, name=None):
    return LayerOutput(name or auto_name("power"), "power", input.size,
                       [weight, input], {})


_simple_layer("scaling", lambda cfg, s: s[1],
              lambda ctx, cfg, w, x: map_rows(math_ops.scaling, w, x))


def scaling_layer(input, weight, name=None):
    return LayerOutput(name or auto_name("scaling"), "scaling", input.size,
                       [weight, input], {})


_simple_layer("slope_intercept", lambda cfg, s: s[0],
              lambda ctx, cfg, x: map_rows(
                  lambda d: cfg["slope"] * d + cfg["intercept"], x))


def slope_intercept_layer(input, slope=1.0, intercept=0.0, name=None):
    return LayerOutput(name or auto_name("slope_intercept"), "slope_intercept",
                       input.size, [input],
                       {"slope": slope, "intercept": intercept})


_simple_layer("linear_comb", lambda cfg, s: cfg["size"],
              lambda ctx, cfg, w, x: map_rows(
                  lambda wd, xd: linear.linear_comb(xd, wd, cfg["size"]), w, x))


def linear_comb_layer(weights, vectors, size=None, name=None):
    if size is None:
        # reference default: vectors holds `weights.size` rows of width size
        if vectors.size % max(weights.size, 1) == 0:
            size = vectors.size // weights.size
        else:
            raise ConfigError("linear_comb_layer needs size")
    return LayerOutput(name or auto_name("linear_comb"), "linear_comb", size,
                       [weights, vectors], {"size": size})


convex_comb_layer = linear_comb_layer


_simple_layer("sum_to_one_norm", lambda cfg, s: s[0],
              lambda ctx, cfg, x: map_rows(math_ops.sum_to_one_norm, x))


def sum_to_one_norm_layer(input, name=None):
    return LayerOutput(name or auto_name("sum_to_one_norm"), "sum_to_one_norm",
                       input.size, [input], {})


_simple_layer("cos_sim", lambda cfg, s: 1,
              lambda ctx, cfg, a, b: map_rows(
                  lambda x, y: math_ops.cos_sim(x, y, cfg.get("scale", 1.0)), a, b))


def cos_sim(a, b, scale=1.0, size=1, name=None):
    if size > 1:
        return LayerOutput(name or auto_name("cos_vm"), "cos_sim_vec_mat", size,
                           [a, b], {"scale": scale, "size": size})
    return LayerOutput(name or auto_name("cos_sim"), "cos_sim", 1, [a, b],
                       {"scale": scale})


_simple_layer("cos_sim_vec_mat", lambda cfg, s: cfg["size"],
              lambda ctx, cfg, a, b: map_rows(
                  lambda v, m: math_ops.cos_sim_vec_mat(
                      v, m.reshape(m.shape[0], cfg["size"], -1),
                      cfg.get("scale", 1.0)), a, b))


_simple_layer("out_prod", lambda cfg, s: s[0] * s[1],
              lambda ctx, cfg, a, b: map_rows(math_ops.outer_prod, a, b))


def out_prod_layer(a, b, name=None):
    return LayerOutput(name or auto_name("out_prod"), "out_prod",
                       a.size * b.size, [a, b], {})


_simple_layer("trans", lambda cfg, s: s[0],
              lambda ctx, cfg, x: math_ops.trans(value_data(x)))


def trans_layer(input, name=None):
    return LayerOutput(name or auto_name("trans"), "trans", input.size,
                       [input], {})


_simple_layer("rotate", lambda cfg, s: s[0],
              lambda ctx, cfg, x: map_rows(
                  lambda d: math_ops.rotate(d, cfg["height"], cfg["width"]), x))


def rotate_layer(input, height, width, name=None):
    return LayerOutput(name or auto_name("rotate"), "rotate", input.size,
                       [input], {"height": height, "width": width})


class _TensorImpl:
    def infer(self, cfg, in_sizes):
        return cfg["size"]

    def init(self, rng, cfg, in_sizes):
        return {"w": _winit(cfg.get("param_attr"))(
            rng, (cfg["size"], in_sizes[0], in_sizes[1]))}

    def apply(self, ctx, cfg, params, a, b):
        return map_rows(lambda x, y: math_ops.tensor_product(
            x, y, params["w"], cfg.get("act")), a, b)


register_layer("tensor")(_TensorImpl)


def tensor_layer(a, b, size, act=None, name=None, param_attr=None,
                 bias_attr=False):
    return LayerOutput(name or auto_name("tensor"), "tensor", size, [a, b],
                       {"size": size, "act": act, "param_attr": param_attr})


_simple_layer("multiplex", lambda cfg, s: s[1],
              lambda ctx, cfg, idx, *xs: map_rows(
                  lambda i, *ds: math_ops.multiplex(i, *ds), idx, *xs))


def multiplex_layer(input, name=None):
    idx, *rest = input
    return LayerOutput(name or auto_name("multiplex"), "multiplex",
                       rest[0].size, [idx] + rest, {})


_simple_layer("conv_shift", lambda cfg, s: s[0],
              lambda ctx, cfg, a, b: map_rows(math_ops.conv_shift, a, b))


def conv_shift_layer(a, b, name=None):
    return LayerOutput(name or auto_name("conv_shift"), "conv_shift", a.size,
                       [a, b], {})


_simple_layer("featmap_expand", lambda cfg, s: s[0] * cfg["num_filters"],
              lambda ctx, cfg, x: map_rows(
                  lambda d: math_ops.feature_map_expand(
                      d, cfg["num_filters"], cfg.get("as_row_vector", True)), x))


def featmap_expand_layer(input, num_filters, as_row_vector=True, name=None):
    return LayerOutput(name or auto_name("featmap_expand"), "featmap_expand",
                       input.size * num_filters, [input],
                       {"num_filters": num_filters, "as_row_vector": as_row_vector})


_simple_layer("resize", lambda cfg, s: cfg["size"],
              lambda ctx, cfg, x: math_ops.resize(value_data(x), cfg["size"]))


_simple_layer("repeat", lambda cfg, s: s[0] * cfg["n"],
              lambda ctx, cfg, v: map_rows(
                  lambda d: jnp.tile(d, (1,) * (d.ndim - 1) + (cfg["n"],)), v))


class AggregateLevel:
    """Reference AggregateLevel (layers.py:227)."""
    EACH_TIMESTEP = "non-seq"
    EACH_SEQUENCE = "seq"
    TO_NO_SEQUENCE = "non-seq"
    TO_SEQUENCE = "seq"


class ExpandLevel:
    """Reference ExpandLevel (layers.py:1456)."""
    FROM_TIMESTEP = AggregateLevel.EACH_TIMESTEP
    FROM_SEQUENCE = AggregateLevel.EACH_SEQUENCE
    FROM_NO_SEQUENCE = AggregateLevel.EACH_TIMESTEP


def repeat_layer(input, num_repeats, name=None, layer_attr=None):
    """Reference repeat_layer: y = [x, x, ..., x] (concat num_repeats
    copies, layers.py:1514)."""
    return LayerOutput(name or auto_name("repeat"), "repeat",
                       input.size * num_repeats, [input], {"n": num_repeats})


def resize_layer(input, size, name=None):
    return LayerOutput(name or auto_name("resize"), "resize", size, [input],
                       {"size": size}, is_seq=False)


class _PreluImpl:
    def infer(self, cfg, in_sizes):
        return in_sizes[0]

    def init(self, rng, cfg, in_sizes):
        n = cfg.get("partial_sum", 1)
        return {"alpha": jnp.full((in_sizes[0] // n if n else in_sizes[0],),
                                  0.25, dtypes.param_dtype())}

    def apply(self, ctx, cfg, params, x):
        n = cfg.get("partial_sum", 1)
        def fn(d):
            alpha = jnp.repeat(params["alpha"], n) if n > 1 else params["alpha"]
            return math_ops.prelu(d, alpha)
        return map_rows(fn, x)


register_layer("prelu")(_PreluImpl)


def prelu_layer(input, partial_sum=1, name=None, param_attr=None):
    return LayerOutput(name or auto_name("prelu"), "prelu", input.size,
                       [input], {"partial_sum": partial_sum,
                                 "param_attr": param_attr})


class _SelectiveFcImpl:
    """Reference SelectiveFullyConnectedLayer: fc over the full class matrix,
    but only selected columns are computed/returned when a selection input is
    given.  Dense fallback multiplies then masks (MXU-friendly)."""

    def infer(self, cfg, in_sizes):
        return cfg["size"]

    def init(self, rng, cfg, in_sizes):
        r1, r2 = jax.random.split(rng)
        p = {"w": _winit(cfg.get("param_attr"))(r1, (in_sizes[0], cfg["size"]))}
        b = _maybe_bias(r2, cfg.get("bias_attr", True), cfg["size"])
        if b is not None:
            p["b"] = b
        return p

    def apply(self, ctx, cfg, params, x, sel=None):
        def fn(d):
            y = linear.matmul(d, params["w"])
            if "b" in params:
                y = y + params["b"]
            return activations.get(cfg.get("act"))(y)
        out = map_rows(fn, x)
        if sel is not None:
            out = map_rows(lambda o, s: o * s, out, sel)
        return out


register_layer("selective_fc")(_SelectiveFcImpl)


def selective_fc_layer(input, size, select=None, act="tanh", name=None,
                       param_attr=None, bias_attr=True):
    ins = [input] + ([select] if select is not None else [])
    return LayerOutput(name or auto_name("selective_fc"), "selective_fc", size,
                       ins, {"size": size, "act": act, "param_attr": param_attr,
                             "bias_attr": bias_attr})


# ------------------------------------------------------- dropout

def dropout_layer(input, dropout_rate, name=None):
    return LayerOutput(name or auto_name("dropout"), "dropout", input.size,
                       [input], {"drop_rate": dropout_rate})


class _DropoutImpl:
    def infer(self, cfg, in_sizes):
        return in_sizes[0]

    def apply(self, ctx, cfg, params, x):
        return _dropout(ctx, cfg, x)


register_layer("dropout")(_DropoutImpl)


# ------------------------------------------------------- sequence layers

class _SeqPoolImpl:
    def infer(self, cfg, in_sizes):
        return in_sizes[0]

    def apply(self, ctx, cfg, params, x):
        from paddle_tpu.core.sequence import NestedSequenceBatch
        if isinstance(x, rnn_ops.LstmOutput):
            # a plain last_seq of a fused LSTM (graph.Topology hands it
            # over): the row at lengths - 1 is the final carry
            return x.final.h
        if isinstance(x, NestedSequenceBatch):
            # reference sequence levels over sub-sequenced input:
            # TO_SEQUENCE pools within each sub-sequence (-> sequence of
            # pooled rows); default pools the whole (flattened) sequence
            each = cfg.get("agg_level") == "seq"
            return seq_ops.nested_seq_pool(x, cfg["pooling"],
                                           each_sequence=each)
        stride = cfg.get("stride", -1)
        if stride and stride > 0:
            return seq_ops.seq_strided_pool(as_seq(x), cfg["pooling"],
                                            int(stride))
        return seq_ops.seq_pool(as_seq(x), cfg["pooling"])


register_layer("seq_pool")(_SeqPoolImpl)


class pooling:
    """Pooling type markers (reference poolings.py MaxPooling/AvgPooling...)."""
    class Max:  # noqa: N801
        name = "max"

    class Avg:  # noqa: N801
        name = "avg"

    class Sum:  # noqa: N801
        name = "sum"

    class SqrtN:  # noqa: N801
        name = "sqrt"


def pooling_layer(input, pooling_type=None, name=None, agg_level=None):
    pt = getattr(pooling_type, "name", pooling_type) or "max"
    return LayerOutput(name or auto_name("seq_pool"), "seq_pool", input.size,
                       [input], {"pooling": pt, "agg_level": agg_level},
                       is_seq=agg_level == "seq")


def last_seq(input, name=None, agg_level=None, stride=-1):
    """stride > 0 (reference seqlastins stride): last instance of each
    non-overlapping stride window — output stays a (shorter) sequence.
    agg_level='seq' over a nested input pools each sub-sequence."""
    return LayerOutput(name or auto_name("last_seq"), "seq_pool", input.size,
                       [input], {"pooling": "last", "stride": stride,
                                 "agg_level": agg_level},
                       is_seq=stride > 0 or agg_level == "seq")


def first_seq(input, name=None, agg_level=None, stride=-1):
    return LayerOutput(name or auto_name("first_seq"), "seq_pool", input.size,
                       [input], {"pooling": "first", "stride": stride,
                                 "agg_level": agg_level},
                       is_seq=stride > 0 or agg_level == "seq")


_simple_layer("expand", lambda cfg, s: s[0],
              lambda ctx, cfg, vec, like: seq_ops.expand(
                  value_data(vec), as_seq(like)))


def expand_layer(input, expand_as, name=None, expand_level=None):
    out = LayerOutput(name or auto_name("expand"), "expand", input.size,
                      [input, expand_as], {}, is_seq=True)
    return out


_simple_layer("seq_concat", lambda cfg, s: s[0],
              lambda ctx, cfg, a, b: seq_ops.seq_concat(as_seq(a), as_seq(b)))


def seq_concat_layer(a, b, name=None):
    return LayerOutput(name or auto_name("seq_concat"), "seq_concat", a.size,
                       [a, b], {}, is_seq=True)


_simple_layer("seq_reshape", lambda cfg, s: cfg["size"],
              lambda ctx, cfg, x: seq_ops.seq_reshape(as_seq(x), cfg["size"]))


def seq_reshape_layer(input, reshape_size, name=None):
    return LayerOutput(name or auto_name("seq_reshape"), "seq_reshape",
                       reshape_size, [input], {"size": reshape_size},
                       is_seq=True)


class _SubSeqImpl:
    def infer(self, cfg, in_sizes):
        return in_sizes[0]

    def apply(self, ctx, cfg, params, x, offsets, sizes):
        sb = as_seq(x)
        off = value_data(offsets).reshape(-1).astype(jnp.int32)
        sz = value_data(sizes).reshape(-1).astype(jnp.int32)
        return seq_ops.sub_seq(sb, off, sz, sb.max_len)


register_layer("sub_seq")(_SubSeqImpl)


def sub_seq_layer(input, offsets, sizes, name=None):
    return LayerOutput(name or auto_name("sub_seq"), "sub_seq", input.size,
                       [input, offsets, sizes], {}, is_seq=True)


def seq_slice_layer(input, starts=None, ends=None, name=None):
    ins = [input] + [x for x in (starts, ends) if x is not None]
    return LayerOutput(name or auto_name("seq_slice"), "seq_slice", input.size,
                       ins, {"has_starts": starts is not None,
                             "has_ends": ends is not None}, is_seq=True)


class _SeqSliceImpl:
    def infer(self, cfg, in_sizes):
        return in_sizes[0]

    def apply(self, ctx, cfg, params, x, *rest):
        sb = as_seq(x)
        i = 0
        starts = ends = None
        if cfg["has_starts"]:
            starts = value_data(rest[i]).reshape(-1).astype(jnp.int32)
            i += 1
        if cfg["has_ends"]:
            ends = value_data(rest[i]).reshape(-1).astype(jnp.int32)
        return seq_ops.seq_slice(sb, starts, ends)


register_layer("seq_slice")(_SeqSliceImpl)


_simple_layer("maxid", lambda cfg, s: 1,
              lambda ctx, cfg, x: map_rows(seq_ops.max_id, x))


def maxid_layer(input, name=None):
    return LayerOutput(name or auto_name("maxid"), "maxid", 1, [input], {})


_simple_layer("eos", lambda cfg, s: 1,
              lambda ctx, cfg, x: map_rows(
                  lambda d: seq_ops.eos_check(d, cfg["eos_id"]), x))


def eos_layer(input, eos_id, name=None):
    return LayerOutput(name or auto_name("eos"), "eos", 1, [input],
                       {"eos_id": eos_id})


class _SamplingIdImpl:
    def infer(self, cfg, in_sizes):
        return 1

    def apply(self, ctx, cfg, params, x):
        return map_rows(lambda d: seq_ops.sampling_id(ctx.next_rng(), d), x)


register_layer("sampling_id")(_SamplingIdImpl)


def sampling_id_layer(input, name=None):
    return LayerOutput(name or auto_name("sampling_id"), "sampling_id", 1,
                       [input], {})


class _PrintImpl:
    def infer(self, cfg, in_sizes):
        return in_sizes[0]

    def apply(self, ctx, cfg, params, x):
        jax.debug.print(cfg.get("format", "{}"), value_data(x))
        return x


register_layer("print")(_PrintImpl)


def print_layer(input, format=None, name=None):
    return LayerOutput(name or auto_name("print"), "print", input.size,
                       [input], {"format": format or "{}"})


# ------------------------------------------------------- cost layers

def _seq_or_row_mean(loss, like):
    """Per-token losses on sequences average over valid tokens per sample."""
    if isinstance(like, SequenceBatch):
        return losses.masked_seq_mean(loss, like.mask(loss.dtype))
    return loss


class _CostImpl:
    def __init__(self, fn, needs_logits=True):
        self.fn = fn

    def infer(self, cfg, in_sizes):
        return 1

    def apply(self, ctx, cfg, params, *ins):
        return self.fn(ctx, cfg, *ins)


def _register_cost(type_name, fn):
    class Impl:
        def infer(self, cfg, in_sizes):
            return 1

        def apply(self, ctx, cfg, params, *ins):
            if cfg.get("weighted"):
                # reference: cost layers accept a per-sample weight input
                # (CostLayer::forward weights_, e.g. classification_cost
                # (input, label, weight))
                *core, w = ins
                val = fn(ctx, cfg, *core)
                wd = value_data(w)
                return val * wd.reshape(wd.shape[0], -1)[:, 0]
            return fn(ctx, cfg, *ins)
    register_layer(type_name)(Impl)


def _ce_cost(ctx, cfg, pred, label):
    pd, ld = value_data(pred), value_data(label)
    ids = ld.reshape(ld.shape[:-1] if ld.shape[-1] == 1 else ld.shape)
    per = losses.classification_cost(pd, ids, from_logits=cfg.get("from_logits", True))
    return _seq_or_row_mean(per, pred)


_register_cost("classification_cost", _ce_cost)


def _logits_view(node):
    """If `node` is a softmax-activated layer, build a logits alias: same
    type/inputs/params (shared via param_name) with act=None.  This fuses
    softmax+CE the way the reference's MultiClassCrossEntropy backward
    writes (p - y) straight into the softmax layer
    (gserver/layers/CostLayer.cpp) — the log(max(p, eps)) formulation has
    zero gradient once a probability underflows eps, which kills training;
    log_softmax(logits) never saturates."""
    if node.cfg.get("act") != "softmax" or node.cfg.get("drop_rate"):
        # dropout runs after the activation; CE(log_softmax(dropout(z)))
        # would differ from the documented CE over dropout(softmax(z)), so a
        # softmax layer with dropout keeps the unfused probability path.
        return None
    cfg = dict(node.cfg)
    cfg["act"] = None
    # alias key must match Topology._param_key exactly (explicit param_name,
    # else param_attr name, else layer name) or the alias layer inits and
    # trains a second parameter set while prediction reads the original
    if "param_name" in node.cfg:
        key = node.cfg["param_name"]
    else:
        pa = node.cfg.get("param_attr")
        key = pa["name"] if isinstance(pa, dict) and pa.get("name") else node.name
    cfg["param_name"] = key
    return LayerOutput(auto_name(node.name + "_logits"), node.layer_type,
                       node.size, node.inputs, cfg, is_seq=node.is_seq,
                       num_filters=node.num_filters, img_shape=node.img_shape)


def classification_cost(input, label, weight=None, name=None, evaluator=None,
                        from_logits=False):
    """Reference classification_cost: input is softmax output; here the
    graph usually ends with act='softmax', so from_logits defaults False.
    When the input is a softmax layer we rewire onto its logits (see
    _logits_view) for a numerically exact fused gradient.  weight: optional
    per-sample cost weight layer."""
    if not from_logits:
        logits = _logits_view(input)
        if logits is not None:
            input, from_logits = logits, True
    ins = [input, label] + ([weight] if weight is not None else [])
    return LayerOutput(name or auto_name("cost"), "classification_cost", 1,
                       ins, {"from_logits": from_logits,
                             "weighted": weight is not None},
                       is_seq=False)


def cross_entropy(input, label, name=None, from_logits=False):
    return classification_cost(input, label, name=name, from_logits=from_logits)


_register_cost("mse", lambda ctx, cfg, p, l: _seq_or_row_mean(
    losses.square_error(value_data(p), value_data(l)), p))


def regression_cost(input, label, weight=None, name=None):
    ins = [input, label] + ([weight] if weight is not None else [])
    return LayerOutput(name or auto_name("mse"), "mse", 1, ins,
                       {"weighted": weight is not None}, is_seq=False)


mse_cost = regression_cost


_register_cost("ce_selfnorm", lambda ctx, cfg, p, l: _seq_or_row_mean(
    losses.cross_entropy_with_selfnorm(
        value_data(p), value_data(l).reshape(value_data(p).shape[:-1]),
        cfg.get("alpha", 0.1)), p))


def cross_entropy_with_selfnorm(input, label, alpha=0.1, name=None):
    return LayerOutput(name or auto_name("ce_selfnorm"), "ce_selfnorm", 1,
                       [input, label], {"alpha": alpha}, is_seq=False)


_register_cost("soft_bce", lambda ctx, cfg, p, l: _seq_or_row_mean(
    losses.soft_binary_class_cross_entropy(value_data(p), value_data(l)), p))


def soft_binary_class_cross_entropy(input, label, name=None):
    return LayerOutput(name or auto_name("soft_bce"), "soft_bce", 1,
                       [input, label], {}, is_seq=False)


_register_cost("multi_bce", lambda ctx, cfg, p, l: _seq_or_row_mean(
    losses.multi_binary_label_cross_entropy(value_data(p), value_data(l)), p))


def multi_binary_label_cross_entropy(input, label, name=None):
    return LayerOutput(name or auto_name("multi_bce"), "multi_bce", 1,
                       [input, label], {}, is_seq=False)


_register_cost("rank", lambda ctx, cfg, left, right, label, *w:
               losses.rank_cost(value_data(left), value_data(right),
                                value_data(label),
                                value_data(w[0]) if w else None))


def rank_cost(left, right, label, weight=None, name=None):
    ins = [left, right, label] + ([weight] if weight is not None else [])
    return LayerOutput(name or auto_name("rank"), "rank", 1, ins, {},
                       is_seq=False)


_register_cost("lambda", lambda ctx, cfg, score, rel: losses.lambda_cost(
    value_data(score)[..., 0] if value_data(score).ndim == 3 else value_data(score),
    value_data(rel)[..., 0] if value_data(rel).ndim == 3 else value_data(rel),
    as_seq(score).mask(), cfg.get("ndcg_num", 5)))


def lambda_cost(input, score, NDCG_num=5, max_sort_size=-1, name=None):
    return LayerOutput(name or auto_name("lambda"), "lambda", 1,
                       [input, score], {"ndcg_num": NDCG_num}, is_seq=False)


def _huber_cost(ctx, cfg, p, l):
    return losses.huber_classification(value_data(p), value_data(l))


_register_cost("huber", _huber_cost)


def huber_cost(input, label, name=None):
    return LayerOutput(name or auto_name("huber"), "huber", 1, [input, label],
                       {}, is_seq=False)


_register_cost("smooth_l1", lambda ctx, cfg, p, l: _seq_or_row_mean(
    losses.smooth_l1(value_data(p), value_data(l)), p))


def smooth_l1_cost(input, label, name=None):
    return LayerOutput(name or auto_name("smooth_l1"), "smooth_l1", 1,
                       [input, label], {}, is_seq=False)


_register_cost("sum_cost", lambda ctx, cfg, x: losses.sum_cost(value_data(x)))


def sum_cost(input, name=None):
    return LayerOutput(name or auto_name("sum_cost"), "sum_cost", 1, [input],
                       {}, is_seq=False)


# structured costs ----------------------------------------------------------

class _CrfImpl:
    def infer(self, cfg, in_sizes):
        return 1

    def init(self, rng, cfg, in_sizes):
        n = cfg["size"]
        return {"w": _winit(cfg.get("param_attr"), default_std=0.1)(
            rng, (n + 2, n))}

    def apply(self, ctx, cfg, params, emissions, label):
        sb = as_seq(emissions)
        ld = value_data(label)
        tags = ld[..., 0] if ld.ndim == 3 else ld
        return crf_ops.crf_log_likelihood(sb.data, tags.astype(jnp.int32),
                                          sb.lengths, params["w"])


register_layer("crf")(_CrfImpl)


def crf_layer(input, label, size=None, param_attr=None, name=None,
              weight=None, layer_attr=None):
    n = size or input.size
    # transition weights share by ParamAttr(name=...) like any layer
    # (reference: crf + crf_decoding share 'crfw')
    pa_name = param_attr.get("name") if isinstance(param_attr, dict) else None
    return LayerOutput(name or auto_name("crf"), "crf", 1, [input, label],
                       {"size": n, "param_attr": param_attr,
                        "param_name": pa_name or name or auto_name("crf_w")},
                       is_seq=False)


class _CrfDecodingImpl:
    def infer(self, cfg, in_sizes):
        return 1

    def init(self, rng, cfg, in_sizes):
        n = cfg["size"]
        return {"w": _winit(cfg.get("param_attr"), default_std=0.1)(
            rng, (n + 2, n))}

    def apply(self, ctx, cfg, params, emissions, label=None):
        sb = as_seq(emissions)
        tags, _ = crf_ops.crf_decode(sb.data, sb.lengths, params["w"])
        if label is not None:
            # reference CRFDecodingLayer with a label input emits the
            # per-position 0/1 error indicator instead of the tags
            lab = as_seq(label)
            ld = lab.data.reshape(lab.data.shape[0], lab.data.shape[1], -1)
            err = (tags != ld[..., 0]).astype(jnp.float32)
            err = err * sb.mask(jnp.float32)
            return SequenceBatch(data=err[..., None], lengths=sb.lengths)
        return SequenceBatch(data=tags[..., None], lengths=sb.lengths)


register_layer("crf_decoding")(_CrfDecodingImpl)


def crf_decoding_layer(input, size=None, label=None, param_attr=None,
                       name=None, param_name=None, layer_attr=None):
    """param_name (or ParamAttr(name=...)) lets decode share the CRF weight
    learned by crf_layer."""
    n = size or input.size
    cfg = {"size": n, "param_attr": param_attr}
    pa_name = param_attr.get("name") if isinstance(param_attr, dict) else None
    if param_name or pa_name:
        cfg["param_name"] = param_name or pa_name
    ins = [input] + ([label] if label is not None else [])
    return LayerOutput(name or auto_name("crf_decoding"), "crf_decoding", 1,
                       ins, cfg, is_seq=True)


def _ctc_cost(ctx, cfg, probs, label):
    sb = as_seq(probs)
    lab = as_seq(label)
    logp = jnp.log(jnp.maximum(sb.data, 1e-20)) if not cfg.get("from_logits") \
        else jax.nn.log_softmax(sb.data, axis=-1)
    ids = lab.data[..., 0] if lab.data.ndim == 3 else lab.data
    return ctc_ops.ctc_loss(logp, sb.lengths, ids.astype(jnp.int32),
                            lab.lengths, blank=cfg.get("blank", 0))


_register_cost("ctc", _ctc_cost)


def ctc_layer(input, label, size=None, blank=None, norm_by_times=False,
              name=None):
    """Reference CTCLayer: blank = size-1 by default (warpctc uses 0)."""
    n = size or input.size
    return LayerOutput(name or auto_name("ctc"), "ctc", 1, [input, label],
                       {"blank": blank if blank is not None else n - 1},
                       is_seq=False)


def warp_ctc_layer(input, label, size=None, blank=0, norm_by_times=False,
                   name=None):
    return LayerOutput(name or auto_name("warp_ctc"), "ctc", 1, [input, label],
                       {"blank": blank, "from_logits": True}, is_seq=False)


class _NceImpl:
    def infer(self, cfg, in_sizes):
        return 1

    def init(self, rng, cfg, in_sizes):
        r1, r2 = jax.random.split(rng)
        return {"w": _winit(cfg.get("param_attr"))(
            r1, (cfg["num_classes"], in_sizes[0])),
            "b": jnp.zeros((cfg["num_classes"],), dtypes.param_dtype())}

    def apply(self, ctx, cfg, params, x, label):
        xd, ld = value_data(x), value_data(label)
        ids = ld.reshape(ld.shape[0]).astype(jnp.int32)
        k = cfg.get("num_neg_samples", 10)
        neg = sampling_ops.uniform_neg_samples(
            ctx.next_rng(), (xd.shape[0], k), cfg["num_classes"])
        return sampling_ops.nce_loss(xd, params["w"], params["b"], ids, neg,
                                     cfg["num_classes"])


register_layer("nce")(_NceImpl)


def nce_layer(input, label, num_classes, num_neg_samples=10, name=None,
              param_attr=None):
    return LayerOutput(name or auto_name("nce"), "nce", 1, [input, label],
                       {"num_classes": num_classes,
                        "num_neg_samples": num_neg_samples,
                        "param_attr": param_attr}, is_seq=False)


class _HsigmoidImpl:
    def infer(self, cfg, in_sizes):
        return 1

    def init(self, rng, cfg, in_sizes):
        return {"w": _winit(cfg.get("param_attr"))(
            rng, (cfg["num_classes"] - 1, in_sizes[0])),
            "b": jnp.zeros((cfg["num_classes"] - 1,), dtypes.param_dtype())}

    def apply(self, ctx, cfg, params, x, label):
        xd, ld = value_data(x), value_data(label)
        ids = ld.reshape(ld.shape[0]).astype(jnp.int32)
        return sampling_ops.hsigmoid_loss(xd, params["w"], params["b"], ids,
                                          cfg["num_classes"])


register_layer("hsigmoid")(_HsigmoidImpl)


def hsigmoid(input, label, num_classes, name=None, param_attr=None,
             bias_attr=True):
    return LayerOutput(name or auto_name("hsigmoid"), "hsigmoid", 1,
                       [input, label],
                       {"num_classes": num_classes, "param_attr": param_attr},
                       is_seq=False)

"""Laguna (poolside/Laguna-S-2.1, config.json, ``model_type`` ``laguna``) in
plain jax.numpy, float32, matrix products at ``highest`` precision: a full
causal forward over whole sequences.  No cache, no kernels, no packing, no
chunks, and nothing imported from the program under test.

    x_0 = E[ids]
    x += Attn_i(RMS(x));   x += FFN_i(RMS(x));   logits = RMS_f(x_L) W_head

Layer i's attention is ``layer_types[i]`` with ``num_attention_heads_per_
layer[i]`` query heads of ``head_dim`` on ``num_key_value_heads`` K/V heads,
no biases:
    q, k, v = h W_q, h W_k, h W_v;   q, k <- RoPE_kind(q), RoPE_kind(k)
    o_h = softmax_{j in J(t)}(q_h . k_j / sqrt(head_dim)) v_j
    g = sigmoid(h W_gate)   (one scalar a head);   y = concat_h(g_h o_h) W_o
"full_attention": J(t) = [0, t]; "sliding_attention": J(t) = [t - W + 1, t]
(``sliding_window`` W; an explicit mask, as Hugging Face's sliding mask has
it).  RoPE_kind follows ``rope_parameters[kind]``: the leading
``partial_rotary_factor`` of each head turns in rotate-half pairs (i, i +
d/2) by t x inv_freq_i, inv_freq_i = theta^(-2i/d); "yarn" blends each
frequency with its ``factor``-fold slower twin between the dimensions that
turn ``beta_fast`` and ``beta_slow`` times over
``original_max_position_embeddings`` (Hugging Face's
``_compute_yarn_parameters``) and scales cos and sin by
``attention_factor``.

FFN: ``mlp_layer_types[i]`` "dense" is (SiLU(h W_g) * h W_u) W_d of width
``intermediate_size``; "sparse" routes: s = softmax(h W_r) over all
experts; the chosen are the ``num_experts_per_tok`` largest (or ``routes``,
when the caller passes the choice: it is discontinuous, so a comparison
hands the program's own choice in and judges it apart); w_e =
``moe_routed_scaling_factor`` * s_e / sum of the chosen s; y = sum over
chosen e HELD HERE of w_e E_e(h) + E_shared(h) (width
``shared_expert_intermediate_size``, ungated).  Of the router's experts this
holder has ``held = (first, count)`` (``held_experts``); ``(0, E)`` is the
uncut layer.  The vocabulary is what ``vocab_size`` says (a slice of the
published one is a smaller vocabulary).

Departures from the published model, all in the configuration file too:
seeded random weights; the gate's form (the headwise sigmoid gate of
"Gated Attention for Large Language Models", from the normed input that q
reads), the router's (softmax over all experts, float32, no bias) and the
shared expert's (added ungated) are assumptions, the config names them
only.

``forward`` returns the rows asked for (``positions``).  Weights may arrive
in bfloat16: each layer widens its own inside its own jitted call, so the
whole model and its float32 copy never coexist; a layer's heads are
attended one at a time, so that the scores of thousands of positions fit a
chip that also holds the model."""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

KINDS = {"full_attention": "full", "sliding_attention": "window"}


def layer_kinds(cfg):
    """[(attention kind "full" | "window", FFN kind "dense" | "moe")] of
    layers 0..num_hidden_layers-1."""
    n = cfg["num_hidden_layers"]
    return [(KINDS[a], "dense" if f == "dense" else "moe")
            for a, f in zip(cfg["layer_types"][:n],
                            cfg["mlp_layer_types"][:n])]


def held_experts(cfg):
    """(first, count) of the experts this holder computes, and the router's
    width: ``num_experts`` are held of ``expert_parallel.
    num_experts_published`` (rank ``expert_parallel.rank``); without that
    group, all of them."""
    ep = cfg.get("expert_parallel") or {}
    count = cfg["num_experts"]
    return (ep.get("rank", 0) * count, count), \
        ep.get("num_experts_published", count)


def yarn_inv_freq(spec, dim):
    """(inv_freq [dim / 2] float64, cos/sin scale) of one
    ``rope_parameters`` entry over ``dim`` turned dimensions."""
    base = float(spec["rope_theta"])
    inv = base ** (-np.arange(0, dim, 2) / dim)
    if spec.get("rope_type", "default") != "yarn":
        return inv, 1.0
    factor = float(spec["factor"])
    orig = spec["original_max_position_embeddings"]

    def correction(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low = max(math.floor(correction(spec.get("beta_fast", 32))), 0)
    high = min(math.ceil(correction(spec.get("beta_slow", 1))), dim - 1)
    if low == high:
        high += 0.001
    interpolated = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = (inv / factor) * interpolated + inv * (1 - interpolated)
    scale = spec.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return inv, float(scale)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _ffn(x, p):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


def rope(x, spec, head_dim):
    """x [B, T, heads, head_dim] at positions 0..T-1, turned as ``spec``
    says."""
    dim = int(head_dim * spec.get("partial_rotary_factor", 1.0))
    inv, scale = yarn_inv_freq(spec, dim)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]             # [T, dim / 2]
    cos = (jnp.cos(ang) * scale)[None, :, None, :]
    sin = (jnp.sin(ang) * scale)[None, :, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., dim:]], -1)


def attention(x, p, cfg, kind, heads):
    """x [B, T, d] -> [B, T, d]; p: wq [d, H dh], wk, wv [d, Hkv dh], wgate
    [d, H], wo."""
    b, t, _ = x.shape
    kv, dh = cfg["num_key_value_heads"], cfg["head_dim"]
    layer_type = next(k for k, v in KINDS.items() if v == kind)
    spec = cfg["rope_parameters"][layer_type]
    q = rope((x @ p["wq"]).reshape(b, t, heads, dh), spec, dh)
    k = rope((x @ p["wk"]).reshape(b, t, kv, dh), spec, dh)
    v = (x @ p["wv"]).reshape(b, t, kv, dh)
    i, j = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    seen = j <= i
    if kind == "window" and cfg.get("sliding_window"):
        seen = seen & (j > i - cfg["sliding_window"])

    def head(args):
        q_h, k_h, v_h = args                                # [B, T, dh]
        s = jnp.einsum("bqd,bkd->bqk", q_h, k_h) / math.sqrt(dh)
        return jax.nn.softmax(jnp.where(seen[None], s, -jnp.inf), -1) @ v_h

    # query head h reads K/V head h // (H / Hkv)
    own = jnp.arange(heads) // (heads // kv)
    o = jax.lax.map(head, (jnp.moveaxis(q, 2, 0),
                           jnp.moveaxis(k, 2, 0)[own],
                           jnp.moveaxis(v, 2, 0)[own]))     # [H, B, T, dh]
    o = jnp.moveaxis(o, 0, 2)                               # [B, T, H, dh]
    if cfg.get("gating"):
        o = o * jax.nn.sigmoid(x @ p["wgate"])[..., None]
    return o.reshape(b, t, heads * dh) @ p["wo"]


def moe(x, p, cfg, routes=None):
    """-> (y, z): the layer's part held here, and the router's logits of
    every expert (the softmax ranks as they do), for whoever judges a
    handed-in choice."""
    (first, count), _total = held_experts(cfg)
    k = cfg["num_experts_per_tok"]
    z = x @ p["router"]
    s = jax.nn.softmax(z, -1)
    idx = jax.lax.top_k(s, k)[1] if routes is None else routes
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = cfg["moe_routed_scaling_factor"] * chosen \
        / chosen.sum(-1, keepdims=True)

    def one(y, xs):
        e, wg, wu, wd = xs
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        return y + w_e * _ffn(x, _f32({"wg": wg, "wu": wu, "wd": wd})), None

    ex = p["experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (first + jnp.arange(count), ex["wg"], ex["wu"],
                         ex["wd"]))
    return y + _ffn(x, _f32(p["shared"])), z


@functools.partial(jax.jit, static_argnames=("kinds", "heads", "cfg_json"))
def _layer(x, p, routes, kinds, heads, cfg_json):
    # jit wants hashable static arguments and a configuration is a nested
    # dict: it travels as its JSON text
    cfg = json.loads(cfg_json)
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        x = x + attention(_rms(x, p["norm1"].astype(jnp.float32), eps),
                          _f32(p["attn"]), cfg, kinds[0], heads)
        h = _rms(x, p["norm2"].astype(jnp.float32), eps)
        if kinds[1] == "dense":
            return x + _ffn(h, _f32(p["ffn"])), None
        ffn = dict(p["ffn"], router=p["ffn"]["router"].astype(jnp.float32),
                   shared=_f32(p["ffn"]["shared"]))
        y, z = moe(h, ffn, cfg, routes)
        return x + y, z


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, w, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, g.astype(jnp.float32), eps) @ w.astype(jnp.float32)


def forward(p, ids, cfg, positions=None, routes=None):
    """p: {"emb" [V, d], "head" [d, V], "norm_f" [d], "layers": [{"norm1",
    "norm2", "attn": {"wq", "wk", "wv", "wgate", "wo"}, "ffn": {"wg", "wu",
    "wd"} or {"router", "shared", "experts"}}]}; ids [B, T] int32;
    positions: None (every position) or [B, P] int32, the positions whose
    rows are wanted; routes: None, or one [B, T, k] int32 array of chosen
    experts per expert layer, in layer order.  -> (logits [B, T or P, V]
    float32, the router's logits [B, T, E] of each expert layer)."""
    key = json.dumps(cfg, sort_keys=True)
    x = p["emb"][ids].astype(jnp.float32)
    router, routes = [], list(routes) if routes is not None else None
    heads = cfg["num_attention_heads_per_layer"]
    for i, (lp, kinds) in enumerate(zip(p["layers"], layer_kinds(cfg))):
        r = routes.pop(0) if routes is not None and kinds[1] == "moe" \
            else None
        x, z = _layer(x, lp, r, kinds, heads[i], key)
        if z is not None:
            router.append(z)
    if positions is not None:
        x = jnp.take_along_axis(x, jnp.asarray(positions)[:, :, None],
                                axis=1)
    return _head(x, p["norm_f"], p["head"], cfg["rms_norm_eps"]), router

"""openPangu-Ultra-MoE (FreedomIntelligence/openPangu-Ultra-MoE-718B,
config.json, ``model_type`` ``pangu_ultra_moe``) in plain jax.numpy, float32,
matrix products at ``highest`` precision: a full causal forward over whole
sequences.  No cache, no kernels, no batching tricks, nothing absorbed, and
nothing imported from the program under test.

    x_0 = E[ids]
    a = RMS_post_attn(MLA(RMS_in(x)));       x += a
    f = RMS_post_mlp(FFN(RMS_pre_mlp(x)));   x += f        (sandwich_norm)
    logits = RMS_f(x_L) W_head                              (untied head)

MLA, every layer, H heads, the lane at position t:
    c_q = RMS_q(h W_qa);  q = c_q W_qb -> H x [q_n (nope) | q_r (rope)]
    [c | k_r] = h W_kva;  q_r, k_r <- RoPE_t (k_r is one for all heads)
    [k_n | v] = RMS_kv(c) W_kvb -> H x [nope | v];  k = [k_n | k_r]
    y = softmax_{j <= t}(q . k_j / sqrt(nope + rope)) v -> W_o
k and v are expanded per head and attended a group of heads at a time (so
that the scores of 128 heads over thousands of positions fit a chip that
also holds the model); that changes no number.

FFN: layers 1 .. ``first_k_dense_replace`` are (SiLU(h W_g) * h W_u) W_d of
width ``intermediate_size`` (summed over blocks of its columns, for the same
reason); the others route: s = sigmoid(h W_r); the
chosen experts are the ``num_experts_per_tok`` largest of ``s + bias`` (or
``routes``, when the caller passes the choice: it is discontinuous, so a
comparison hands the program's own choice in and judges it apart);
w_e = ``routed_scaling_factor`` * s_e / sum of the chosen s
(``norm_topk_prob``); y = sum over chosen e HELD HERE of w_e E_e(h) +
E_shared(h).  Of the router's experts this holder has ``held = (first,
count)`` (``held_experts``); ``(0, E)`` is the uncut layer.

Departures from the published model, all in the configuration file too:
seeded random weights; the router's scoring function (sigmoid, no group
limit) and the rotary layout (interleaved pairs (2i, 2i+1), angle
t * theta^(-2i/rope), no scaling) are the family's convention, the
config gives neither; the multi-token-prediction layer is left out (the
main model's forward does not use it).

Weights may arrive in bfloat16: each piece widens its own inside its own
jitted call, so the whole model and its float32 copy never coexist."""

import functools
import json
import math

import jax
import jax.numpy as jnp

HEAD_GROUP = 8      # heads attended at a time
DENSE_BLOCKS = 8    # column blocks the dense FFN is summed over


def layer_kinds(cfg):
    """["dense" | "moe"] of layers 1..num_hidden_layers (all attend by
    MLA)."""
    return ["dense" if l <= cfg["first_k_dense_replace"] else "moe"
            for l in range(1, cfg["num_hidden_layers"] + 1)]


def held_experts(cfg):
    """(first, count) of the experts this holder computes, and the router's
    width: ``n_routed_experts`` are held of
    ``expert_parallel.num_experts_published`` (rank ``expert_parallel.rank``);
    without that group, all of them."""
    ep = cfg.get("expert_parallel") or {}
    count = cfg["n_routed_experts"]
    return (ep.get("rank", 0) * count, count), \
        ep.get("num_experts_published", count)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _ffn(x, p):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


def _dense_ffn(x, p):
    """``_ffn`` summed over column blocks of its width, each widened to
    float32 alone: the same sum, without a float32 copy of 425 M weights."""
    nb = math.gcd(p["wg"].shape[1], DENSE_BLOCKS)
    cols = lambda w: jnp.moveaxis(w.reshape(w.shape[0], nb, -1), 1, 0)
    rows = p["wd"].reshape(nb, -1, p["wd"].shape[1])

    def block(y, ws):
        return y + _ffn(x, _f32(dict(zip(("wg", "wu", "wd"), ws)))), None

    return jax.lax.scan(block, jnp.zeros_like(x),
                        (cols(p["wg"]), cols(p["wu"]), rows))[0]


def rope(x, theta):
    """x [B, T, ..., n] at positions 0..T-1: the pair (x_2i, x_2i+1) of
    position t turns by t * theta^(-2i/n)."""
    t, n = x.shape[1], x.shape[-1]
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = ang.reshape((1, t) + (1,) * (x.ndim - 3) + (n // 2,))
    even, odd = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([even * jnp.cos(ang) - odd * jnp.sin(ang),
                     even * jnp.sin(ang) + odd * jnp.cos(ang)], -1)
    return out.reshape(x.shape)


def mla(x, p, cfg):
    b, t, _ = x.shape
    heads = cfg["num_attention_heads"]
    nope, rp = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    q = (_rms(x @ p["wqa"], p["q_norm"], eps) @ p["wqb"]) \
        .reshape(b, t, heads, nope + rp)
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], theta)], -1)
    kva = x @ p["wkva"]
    c, k_r = _rms(kva[..., :rank], p["kv_norm"], eps), \
        rope(kva[..., rank:], theta)
    causal = jnp.tril(jnp.ones((t, t), bool))[None, None]
    hg = math.gcd(heads, HEAD_GROUP)
    wkvb = p["wkvb"].reshape(rank, heads // hg, hg, nope + vd)

    def group(args):
        q_g, w_g = args                     # [B,T,hg,nope+rp], [rank,hg,.]
        kv = jnp.einsum("btr,rhd->bthd", c, w_g)
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_r[:, :, None, :], (b, t, hg, rp))], -1)
        s = jnp.einsum("bqhd,bkhd->bhqk", q_g, k) / math.sqrt(nope + rp)
        s = jnp.where(causal, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1),
                          kv[..., nope:])

    o = jax.lax.map(group, (
        jnp.moveaxis(q.reshape(b, t, heads // hg, hg, nope + rp), 2, 0),
        jnp.moveaxis(wkvb, 1, 0)))          # [G,B,T,hg,vd]
    return jnp.moveaxis(o, 0, 2).reshape(b, t, heads * vd) @ p["wo"]


def moe(x, p, cfg, routes=None):
    """-> (y, s + bias): the layer's part held here, and the selection
    scores of every expert, for whoever judges a handed-in choice."""
    (first, count), _total = held_experts(cfg)
    k = cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(x @ p["router"])
    select = s + p["router_bias"]
    idx = jax.lax.top_k(select, k)[1] if routes is None else routes
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = cfg["routed_scaling_factor"] * chosen / chosen.sum(-1, keepdims=True)

    def one(y, xs):
        e, wg, wu, wd = xs
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        return y + w_e * _ffn(x, _f32({"wg": wg, "wu": wu, "wd": wd})), None

    ex = p["experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (first + jnp.arange(count), ex["wg"], ex["wu"],
                         ex["wd"]))
    for _ in range(cfg["n_shared_experts"]):
        y = y + _ffn(x, _f32(p["shared"]))
    return y, select


@functools.partial(jax.jit, static_argnames=("cfg_json",))
def _attend(x, p, cfg_json):
    # jit wants hashable static arguments and a configuration is a nested
    # dict: it travels as its JSON text
    cfg = json.loads(cfg_json)
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        y = mla(_rms(x, p["norm1"].astype(jnp.float32), eps),
                _f32(p["attn"]), cfg)
        return x + _rms(y, p["post_attn"].astype(jnp.float32), eps)


@functools.partial(jax.jit, static_argnames=("kind", "cfg_json"))
def _feed(x, p, routes, kind, cfg_json):
    cfg = json.loads(cfg_json)
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        h = _rms(x, p["norm2"].astype(jnp.float32), eps)
        if kind == "dense":
            y, select = _dense_ffn(h, p["ffn"]), None
        else:
            ffn = dict(p["ffn"],
                       router=p["ffn"]["router"].astype(jnp.float32),
                       router_bias=p["ffn"]["router_bias"].astype(
                           jnp.float32))
            y, select = moe(h, ffn, cfg, routes)
        return x + _rms(y, p["post_ffn"].astype(jnp.float32), eps), select


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, w, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, g.astype(jnp.float32), eps) @ w.astype(jnp.float32)


def logits(p, ids, cfg, routes=None):
    """p: {"emb" [V,d], "head" [d,V], "norm_f" [d], "layers": [{"norm1",
    "post_attn", "norm2", "post_ffn", "attn": {"wqa", "q_norm", "wqb",
    "wkva", "kv_norm", "wkvb", "wo"}, "ffn": {"wg", "wu", "wd"} or
    {"router", "router_bias", "shared", "experts"}}]}; ids [B,T] int32;
    routes: None, or one [B,T,k] int32 array of chosen experts per expert
    layer, in layer order.  -> (logits [B,T,V] float32 at every position,
    [s + bias [B,T,E]] per expert layer)."""
    key = json.dumps(cfg, sort_keys=True)
    x = p["emb"][ids].astype(jnp.float32)
    selects, routes = [], list(routes) if routes is not None else None
    for lp, kind in zip(p["layers"], layer_kinds(cfg)):
        r = routes.pop(0) if routes is not None and kind == "moe" else None
        x = _attend(x, lp, key)
        x, select = _feed(x, lp, r, kind, key)
        if select is not None:
            selects.append(select)
    return _head(x, p["norm_f"], p["head"], cfg["rms_norm_eps"]), selects

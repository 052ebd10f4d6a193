"""Kimi-Linear (moonshotai/Kimi-Linear-48B-A3B-Instruct, config.json and the
flash-linear-attention implementation its model card points to) in plain
jax.numpy, float32, matrix products at ``highest`` precision: a full causal
forward over whole sequences.  No cache, no kernels, no batching tricks, and
nothing imported from the program under test.

    x_0 = E[ids]
    x  += Attn_l(RMSNorm(x));  x += FFN_l(RMSNorm(x))
    logits = RMSNorm(x_L) W_head                        (untied head)

Layer l (1-indexed) is MLA if ``linear_attn_config.full_attn_layers`` lists
it, else KDA; its FFN is dense for l <= ``first_k_dense_replace``, else the
expert layer.

KDA (H heads, d_k = d_v):  q, k, v = SiLU(conv_W(x W_q|k|v)), the convolution
causal and depthwise over time; q, k normalised to unit length per head, q
scaled by d_k^-0.5;  a_t = exp(-exp(A_log) softplus(x W_f1 W_f2 + dt_bias));
b_t = sigmoid(x W_b);  per head, S_0 = 0,
    S_t = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T,  o_t = S_t^T q_t
as a ``lax.scan`` over time;  y = (RMSNorm_head(o_t) * sigmoid(x W_g1 W_g2)) W_o.

MLA (``mla_use_nope``: no rotation):  q = x W_q, H heads of (nope + rope);
[c, k_r] = x W_kva;  [k_n, v] = RMSNorm(c) W_kvb;  k = [k_n, k_r], k_r shared by
the heads;  causal softmax(q k^T / sqrt(nope + rope)) v W_o.  Nothing absorbed,
nothing cached.

Expert FFN:  s = sigmoid(x W_r);  the chosen experts are the ``top_k`` of
``s + bias`` (or ``routes``, when the caller passes the choice: it is
discontinuous, so a comparison hands the program's own choice in and judges
it separately);  w_e = scale * s_e / sum of the chosen s;
y = sum_e w_e E_e(x) + E_shared(x),  E(x) = (SiLU(x W_gate) * x W_up) W_down.
Of the router's E experts this holder has ``held = (first, count)``: the sum
runs over the chosen experts it holds (a loop over those held), the shared
expert is computed in full, and that partial result goes on to the next
layer.  ``held = (0, E)`` is the uncut layer.

Departures from the published model, all in the configuration file too:
seeded random weights; the low-rank width of W_f1 / W_g1 and the shapes of
A_log and dt_bias are assumed (the config does not give them).

Weights may arrive in bfloat16: each layer widens its own inside its own
jitted call, so the whole model and its float32 copy never coexist."""

import functools
import json
import math

import jax
import jax.numpy as jnp

L2_EPS = 1e-6


def layer_kinds(cfg):
    """[(attention kind, ffn kind)] of layers 1..num_hidden_layers."""
    full = set(cfg["linear_attn_config"]["full_attn_layers"])
    return [("mla" if l in full else "kda",
             "dense" if l <= cfg["first_k_dense_replace"] else "moe")
            for l in range(1, cfg["num_hidden_layers"] + 1)]


def held_experts(cfg):
    """(first, count) of the experts this holder computes, and the router's
    width: ``num_experts`` are held of ``expert_parallel.num_experts_published``
    (rank ``expert_parallel.rank``); without that group, all of them."""
    ep = cfg.get("expert_parallel") or {}
    count = cfg["num_experts"]
    return (ep.get("rank", 0) * count, count), \
        ep.get("num_experts_published", count)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _ffn(x, p):
    return (jax.nn.silu(x @ p["wg"]) * (x @ p["wu"])) @ p["wd"]


def _conv(z, w):
    """Causal depthwise convolution over time: z [B,T,C], w [W,C];
    y_t = sum_j w_j z_{t - (W-1) + j}."""
    width, t = w.shape[0], z.shape[1]
    zp = jnp.pad(z, ((0, 0), (width - 1, 0), (0, 0)))
    return sum(w[j] * zp[:, j:j + t] for j in range(width))


def kda(x, p, cfg):
    b, t, _ = x.shape
    la = cfg["linear_attn_config"]
    heads, dk = la["num_heads"], la["head_dim"]
    split = lambda a: a.reshape(b, t, heads, dk)
    q, k, v = (split(jax.nn.silu(_conv(x @ p["w" + n], p["conv_" + n])))
               for n in "qkv")
    unit = lambda a: a / jnp.sqrt(jnp.sum(a * a, -1, keepdims=True) + L2_EPS)
    q, k = unit(q) * dk ** -0.5, unit(k)
    a = jnp.exp(-jnp.exp(p["a_log"])[:, None]
                * split(jax.nn.softplus(x @ p["wf1"] @ p["wf2"]
                                        + p["dt_bias"])))
    beta = jax.nn.sigmoid(x @ p["wb"])                       # [B,T,H]

    def step(s, xs):                                         # s [B,H,dk,dv]
        q_t, k_t, v_t, a_t, b_t = xs
        s = s * a_t[..., None]
        u = b_t[..., None] * (v_t - jnp.einsum("bhkv,bhk->bhv", s, k_t))
        s = s + k_t[..., None] * u[:, :, None, :]
        return s, jnp.einsum("bhkv,bhk->bhv", s, q_t)

    time_first = lambda arr: jnp.moveaxis(arr, 1, 0)
    _, o = jax.lax.scan(step, jnp.zeros((b, heads, dk, dk), jnp.float32),
                        tuple(map(time_first, (q, k, v, a, beta))))
    o = jnp.moveaxis(o, 0, 1)                                # [B,T,H,dv]
    gate = split(x @ p["wg1"] @ p["wg2"])
    o = _rms(o, p["o_norm"], cfg["rms_norm_eps"]) * jax.nn.sigmoid(gate)
    return o.reshape(b, t, heads * dk) @ p["wo"]


def mla(x, p, cfg):
    b, t, _ = x.shape
    heads = cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    rank, vd = cfg["kv_lora_rank"], cfg["v_head_dim"]
    q = (x @ p["wq"]).reshape(b, t, heads, nope + rope)
    kva = x @ p["wkva"]
    c, k_r = kva[..., :rank], kva[..., rank:]
    kv = (_rms(c, p["kv_norm"], cfg["rms_norm_eps"]) @ p["wkvb"]) \
        .reshape(b, t, heads, nope + vd)
    k = jnp.concatenate(
        [kv[..., :nope],
         jnp.broadcast_to(k_r[:, :, None, :], (b, t, heads, rope))], -1)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(nope + rope)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None], s, -jnp.inf)
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), kv[..., nope:])
    return o.reshape(b, t, heads * vd) @ p["wo"]


def moe(x, p, cfg, routes=None):
    """-> (y, s + bias): the layer's part held here, and the selection
    scores of every expert, for whoever judges a handed-in choice."""
    (first, count), _total = held_experts(cfg)
    k = cfg["num_experts_per_token"]
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
    for _ in range(cfg["num_shared_experts"]):
        y = y + _ffn(x, _f32(p["shared"]))
    return y, select


@functools.partial(jax.jit, static_argnames=("kinds", "cfg_json"))
def _layer(x, p, routes, kinds, cfg_json):
    # jit wants hashable static arguments and a configuration is a nested
    # dict: it travels as its JSON text
    cfg = json.loads(cfg_json)
    attn_kind, ffn_kind = kinds
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        attn = _f32(p["attn"])
        h = _rms(x, p["norm1"].astype(jnp.float32), eps)
        x = x + (kda if attn_kind == "kda" else mla)(h, attn, cfg)
        h = _rms(x, p["norm2"].astype(jnp.float32), eps)
        if ffn_kind == "dense":
            return x + _ffn(h, _f32(p["ffn"])), None
        ffn = dict(p["ffn"], router=p["ffn"]["router"].astype(jnp.float32),
                   router_bias=p["ffn"]["router_bias"].astype(jnp.float32))
        y, select = moe(h, ffn, cfg, routes)
        return x + y, select


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, w, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, g.astype(jnp.float32), eps) @ w.astype(jnp.float32)


def logits(p, ids, cfg, routes=None):
    """p: {"emb" [V,d], "head" [d,V], "norm_f" [d], "layers": [{"norm1",
    "norm2", "attn": {...}, "ffn": {...}}]} under the names the functions
    above read; ids [B,T] int32; routes: None, or one [B,T,k] int32 array of
    chosen experts per expert layer, in layer order.  -> (logits [B,T,V]
    float32 at every position, [s + bias [B,T,E]] per expert layer)."""
    key = json.dumps(cfg, sort_keys=True)
    x = p["emb"][ids].astype(jnp.float32)
    selects, routes = [], list(routes) if routes is not None else None
    for lp, kinds in zip(p["layers"], layer_kinds(cfg)):
        r = routes.pop(0) if routes is not None and kinds[1] == "moe" \
            else None
        x, select = _layer(x, lp, r, kinds, key)
        if select is not None:
            selects.append(select)
    return _head(x, p["norm_f"], p["head"], cfg["rms_norm_eps"]), selects

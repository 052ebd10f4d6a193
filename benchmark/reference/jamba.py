"""Jamba (ai21labs/AI21-Jamba2-3B, config.json, ``model_type`` ``jamba``) in
plain jax.numpy, float32, matrix products at ``highest`` precision: a full
causal forward over whole sequences.  No cache, no kernels, no packing, no
chunks, and nothing imported from the program under test.

    x_0 = E[ids]
    x += Mixer_i(RMS(x));   x += FFN_i(RMS(x));   logits = RMS_f(x_L) E^T

Layer i (from 0) attends when ``i % attn_layer_period == attn_layer_offset``
and is a Mamba layer otherwise; the head is the embedding table (tied);
nothing rotates and no position is embedded.

Mamba mixer (d_inner = ``mamba_expand`` x hidden, n = ``mamba_d_state``,
W = ``mamba_d_conv``, r = ``mamba_dt_rank``):
    [u, z] = h W_in;   u = SiLU(conv_W(u) + b_conv)   causal, depthwise
    [dt, B, C] = u W_x;  dt, B, C <- RMS_dt(dt), RMS_b(B), RMS_c(C)
    dt = softplus(dt W_dt + b_dt);   A = -exp(A_log)     [d_inner, n]
    h_t = exp(dt_t A) * h_{t-1} + (dt_t u_t) B_t^T;   y_t = h_t C_t + D u_t
    out = (y * SiLU(z)) W_out
the recurrence one position at a time from a zero state (``lax.scan``), which
also keeps the state as it stood after a given count of positions: what a
server that holds state carries from step to step, and the only place the
state's own precision shows undiluted.

Attention: H query heads and ``num_key_value_heads`` K/V heads of hidden / H
columns, no biases, softmax_{j <= t}(q . k_j / sqrt(head)) v -> W_o, a query
head at a time (so that the scores of thousands of positions fit a chip
that also holds the model); that changes no number.

FFN: (SiLU(h W_g) * h W_u) W_d of width ``intermediate_size`` (``num_experts``
is 1: the expert-layer keys select nothing).

``logits`` returns the rows asked for (``positions``), since all of them at
65,536 columns over thousands of positions would not fit.

Weights may arrive in bfloat16: each layer widens its own inside its own
jitted call, so the whole model and its float32 copy never coexist."""

import functools
import json
import math

import jax
import jax.numpy as jnp


def layer_kinds(cfg):
    """["attn" | "mamba"] of layers 0..num_hidden_layers-1."""
    return ["attn" if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
            else "mamba" for i in range(cfg["num_hidden_layers"])]


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def mamba(x, p, cfg, lengths=None):
    """x [B, T, d] -> ([B, T, d], the state h [B, d_inner, n] after
    ``lengths`` [B] positions, or after all T); p: w_in [d, 2 d_inner], conv
    [W, d_inner], conv_bias, w_x [d_inner, r + 2n], dt_norm [r], b_norm,
    c_norm [n], w_dt [r, d_inner], dt_bias, a_log [d_inner, n], d
    [d_inner], w_out."""
    t = x.shape[1]
    n, r, eps = cfg["mamba_d_state"], cfg["mamba_dt_rank"], \
        cfg["rms_norm_eps"]
    width = cfg["mamba_d_conv"]
    uz = x @ p["w_in"]
    u, z = jnp.split(uz, 2, axis=-1)
    padded = jnp.pad(u, ((0, 0), (width - 1, 0), (0, 0)))
    u = sum(p["conv"][j] * padded[:, j:j + t] for j in range(width))
    if cfg["mamba_conv_bias"]:
        u = u + p["conv_bias"]
    u = jax.nn.silu(u)
    low = u @ p["w_x"]
    dt = _rms(low[..., :r], p["dt_norm"], eps)
    b = _rms(low[..., r:r + n], p["b_norm"], eps)
    c = _rms(low[..., r + n:], p["c_norm"], eps)
    dt = jax.nn.softplus(dt @ p["w_dt"] + p["dt_bias"])
    a = -jnp.exp(p["a_log"])                                # [d_inner, n]

    lengths = jnp.full(x.shape[:1], t) if lengths is None else lengths

    def position(carry, xs):
        h, kept = carry
        i, u_t, dt_t, b_t, c_t = xs     # [B, d_inner] x 2, [B, n] x 2
        h = jnp.exp(dt_t[..., None] * a) * h \
            + (dt_t * u_t)[..., None] * b_t[:, None, :]
        kept = jnp.where((i < lengths)[:, None, None], h, kept)
        return (h, kept), jnp.sum(h * c_t[:, None, :], -1)

    time_first = lambda v: jnp.moveaxis(v, 1, 0)
    h0 = jnp.zeros((x.shape[0], u.shape[-1], n), jnp.float32)
    (_h, kept), y = jax.lax.scan(
        position, (h0, h0),
        (jnp.arange(t),) + tuple(map(time_first, (u, dt, b, c))))
    y = jnp.moveaxis(y, 0, 1) + p["d"] * u
    return (y * jax.nn.silu(z)) @ p["w_out"], kept


def attention(x, p, cfg):
    """x [B, T, d] -> [B, T, d]; p: wq [d, H dh], wk, wv [d, Hkv dh], wo."""
    b, t, d = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    dh = d // heads
    q = (x @ p["wq"]).reshape(b, t, heads, dh)
    k = (x @ p["wk"]).reshape(b, t, kv, dh)
    v = (x @ p["wv"]).reshape(b, t, kv, dh)
    causal = jnp.tril(jnp.ones((t, t), bool))[None]

    def head(args):
        q_h, k_h, v_h = args                                # [B, T, dh]
        s = jnp.einsum("bqd,bkd->bqk", q_h, k_h) / math.sqrt(dh)
        return jax.nn.softmax(jnp.where(causal, s, -jnp.inf), -1) @ v_h

    # query head i reads K/V head i // (H / Hkv)
    own = jnp.arange(heads) // (heads // kv)
    o = jax.lax.map(head, (jnp.moveaxis(q, 2, 0),
                           jnp.moveaxis(k, 2, 0)[own],
                           jnp.moveaxis(v, 2, 0)[own]))     # [H, B, T, dh]
    return jnp.moveaxis(o, 0, 2).reshape(b, t, heads * dh) @ p["wo"]


@functools.partial(jax.jit, static_argnames=("kind", "cfg_json"))
def _layer(x, p, lengths, kind, cfg_json):
    # jit wants hashable static arguments and a configuration is a nested
    # dict: it travels as its JSON text
    cfg = json.loads(cfg_json)
    with jax.default_matmul_precision("highest"):
        p, eps = _f32(p), cfg["rms_norm_eps"]
        h, state = _rms(x, p["norm1"], eps), None
        if kind == "attn":
            x = x + attention(h, p["mixer"], cfg)
        else:
            y, state = mamba(h, p["mixer"], cfg, lengths)
            x = x + y
        h, f = _rms(x, p["norm2"], eps), p["ffn"]
        return x + (jax.nn.silu(h @ f["wg"]) * (h @ f["wu"])) @ f["wd"], \
            state


@functools.partial(jax.jit, static_argnames=("eps",))
def _head(x, g, emb, eps):
    with jax.default_matmul_precision("highest"):
        return _rms(x, g.astype(jnp.float32), eps) \
            @ emb.astype(jnp.float32).T


def forward(p, ids, cfg, positions=None, lengths=None):
    """p: {"emb" [V, d], "norm_f" [d], "layers": [{"norm1", "norm2",
    "mixer": ``mamba``'s or ``attention``'s parameters, "ffn": {"wg", "wu",
    "wd"}}]}; ids [B, T] int32; positions: None (every position) or [B, P]
    int32, the positions whose rows are wanted; lengths: None (T) or [B]
    int32, the positions after which the scans' states are wanted.  ->
    (logits [B, T or P, V] float32, the state [B, d_inner, n] float32 of
    each Mamba layer, in layer order)."""
    key = json.dumps(cfg, sort_keys=True)
    x = p["emb"][ids].astype(jnp.float32)
    lengths = jnp.full(ids.shape[:1], ids.shape[1]) if lengths is None \
        else jnp.asarray(lengths)
    states = []
    for lp, kind in zip(p["layers"], layer_kinds(cfg)):
        x, state = _layer(x, lp, lengths, kind, key)
        if state is not None:
            states.append(state)
    if positions is not None:
        x = jnp.take_along_axis(x, jnp.asarray(positions)[:, :, None],
                                axis=1)
    return _head(x, p["norm_f"], p["emb"], cfg["rms_norm_eps"]), states


def logits(p, ids, cfg, positions=None):
    """``forward``'s logits."""
    return forward(p, ids, cfg, positions)[0]

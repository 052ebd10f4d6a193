"""Keye-VL-2.0's language model (Kwai-Keye/Keye-VL-2.0-30B-A3B, config.json:
Qwen3-MoE-shaped, with a DeepSeek-Sparse-Attention indexer in ``sa_config``)
in plain jax.numpy, float32, matrix products at ``highest`` precision: a
full causal forward over whole sequences, computed in blocks of queries.  No
cache, no kernels, no packing, no chunks, and nothing imported from the
program under test.

    x_0 = E[ids]
    x += Attn(RMS(x));   x += MoE(RMS(x));   logits = RMS_f(x_L) W_head

Attention, h the normed input (``num_attention_heads`` H query heads of
``head_dim`` d on ``num_key_value_heads`` K/V heads, no biases):
    q = RoPE(RMS_q(h W_q)),  k = RoPE(RMS_k(h W_k))  (a head's own RMSNorm,
    its [d] gain), v = h W_v; RoPE turns all d dims in rotate-half pairs
    (i, i + d/2) at ``rope_theta`` (``mrope_section`` covers half a head,
    and a text token's three positions are equal: the plain rotation).
The lightning indexer (``sa_config``: n heads of D on one key head):
    q^I_j = RoPE_I(h W_qI)_j,  k^I = RoPE_I(LayerNorm(h W_kI)),
    w = h W_w,  I[t, s] = (n D)^-1/2 sum_j w_{t,j} ReLU(q^I_{t,j} . k^I_s)
RoPE_I turns the leading D/2 dims, pairs (i, i + D/4).  S_t is the ``topk``
positions s <= t of largest I[t, s] (``jax.lax.top_k``: ties to the lower
position), every s <= t while t < topk; then
    o_t = softmax_{s in S_t}(q_t . k_s / sqrt(d)) v_s,   y = concat(o) W_o.
``q_chunk_size`` / ``kv_chunk_size`` tile the work and change nothing: the
queries are taken ``q_chunk_size`` at a time here.

FFN: s = softmax(h W_r) over all ``num_experts_published`` experts; the
``num_experts_per_tok`` largest (or ``routes``, when the caller passes the
program's choice: it is discontinuous, so a comparison hands it in and
judges it apart) are weighted s_e / sum of the chosen s
(``norm_topk_prob``); y = sum over chosen e HELD HERE of w_e (SiLU(h W_g)
* h W_u) W_d.  No shared expert.  Of the router's experts this holder has
``held = (first, count)`` (``held_experts``); ``(0, E)`` is the uncut
layer.  The vocabulary is what ``vocab_size`` says.

Departures from the published model, all in the configuration file too:
seeded random weights; the indexer's input (h: the model has no query
latent), its LayerNorm and its half-head rotation are assumptions, and its
keys are bfloat16 where DeepSeek keeps them in FP8 (the program's; here
float32).

``forward`` returns the rows asked for (``positions``), the router's logits
of each layer, and, where ``selections`` hands it the positions each query
of each layer attends (the program's choice: like the router's it is
discontinuous, so a comparison hands it in and judges it apart), what the
selection check needs of the indexer's scores there.

With ``bf16_operands`` the reference computes AS THE CONFIGURATION STATES
the served path computes (PERF.md 34.1 did so for the trainer): both
operands of every matrix product but the router's rounded to bfloat16, the
sums float32; the keys, values and indexer keys rounded to bfloat16 as the
pools hold them, the queries and indexer queries as the kernels read them,
and the attention's weights as its product with the values reads them (a
float32 product at the default precision is one bfloat16 pass, in a Mosaic
kernel as in XLA); everything else float32.  That is the form the
benchmark's check compares with: a choice of 2,048 positions among
thousands is discontinuous, and a float32 reference chooses differently
from the stated program at every near-tie (PERF.md 41.1)."""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np


def held_experts(cfg):
    """(first, count) of the experts this holder computes, and the router's
    width: ``num_experts`` are held of ``expert_parallel.
    num_experts_published`` (rank ``expert_parallel.rank``); without that
    group, all of them."""
    ep = cfg.get("expert_parallel") or {}
    count = cfg["num_experts"]
    return (ep.get("rank", 0) * count, count), \
        ep.get("num_experts_published", count)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def _rms(x, g, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rounder(bf16_operands):
    """The rounding of a product's operand: to bfloat16 (``lax.
    reduce_precision``: XLA may drop a cast there and back) or none."""
    if not bf16_operands:
        return lambda x: x
    return lambda x: jax.lax.reduce_precision(x, exponent_bits=8,
                                              mantissa_bits=7)


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, -1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def rope(x, theta, dim):
    """x [B, T, heads, head_dim] at positions 0..T-1, its leading ``dim``
    dims turned in rotate-half pairs (i, i + dim/2)."""
    inv = theta ** (-np.arange(0, dim, 2) / dim)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :dim // 2], x[..., dim // 2:dim]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., dim:]], -1)


def unpack(packed, t):
    """[..., ceil(t / 8)] uint8, position s at bit s % 8 of byte s // 8
    (``np.packbits(..., bitorder="little")``) -> [..., t] bool."""
    bits = (packed[..., None] >> jnp.arange(8, dtype=jnp.uint8)) & 1
    return bits.reshape(packed.shape[:-1] + (-1,))[..., :t] != 0


def attention(x, p, cfg, selection=None, bf16_operands=False):
    """x [B, T, d] (the normed input) -> (y [B, T, d], facts): p holds wq,
    wk, wv, wo, q_norm, k_norm, wq_index, wk_index, k_index_norm,
    k_index_bias, w_index.  ``selection`` [B, T, ceil(T / 8)] uint8
    (``unpack``'s), or None: the positions each query attends, in place of
    its own top-k; with it ``facts`` are {"worst": the least I over the
    positions handed, +inf where none; "best_out": the largest I over the
    positions s <= t NOT handed, -inf where none; "std": the std of its I
    over s <= t}, else None.  ``bf16_operands``: see the module's
    docstring."""
    b, t, _ = x.shape
    sa = cfg["sa_config"]
    heads, kv, dh = cfg["num_attention_heads"], \
        cfg["num_key_value_heads"], cfg["head_dim"]
    n, di, topk = sa["indexer_num_heads"], sa["indexer_head_dim"], sa["topk"]
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    r = _rounder(bf16_operands)
    mm = lambda a, w: r(a) @ r(w)
    q = r(rope(_rms(mm(x, p["wq"]).reshape(b, t, heads, dh), p["q_norm"],
                    eps), theta, dh))
    k = r(rope(_rms(mm(x, p["wk"]).reshape(b, t, kv, dh), p["k_norm"], eps),
               theta, dh))
    v = r(mm(x, p["wv"]).reshape(b, t, kv, dh))
    qi = r(rope(mm(x, p["wq_index"]).reshape(b, t, n, di), theta, di // 2))
    ki = r(rope(_layer_norm(mm(x, p["wk_index"]), p["k_index_norm"],
                            p["k_index_bias"], eps)[:, :, None, :],
                theta, di // 2)[:, :, 0])                       # [B, T, D]
    w = mm(x, p["w_index"]) / math.sqrt(n * di)                 # [B, T, n]
    own = jnp.arange(heads) // (heads // kv)
    kh, vh = jnp.moveaxis(k, 2, 0)[own], jnp.moveaxis(v, 2, 0)[own]
    qc = min(sa["q_chunk_size"], t)
    nb = -(-t // qc)
    pad = lambda a: jnp.pad(a, [(0, 0), (0, nb * qc - t)]
                            + [(0, 0)] * (a.ndim - 2))
    handed = selection is not None
    sel = pad(selection) if handed else jnp.zeros((b, nb * qc, 1), jnp.uint8)
    q, qi, w = pad(q), pad(qi), pad(w)

    def block(lo):
        rows = lo + jnp.arange(qc)
        take = lambda a: jax.lax.dynamic_slice_in_dim(a, lo, qc, axis=1)
        seen = jnp.arange(t)[None, :] <= rows[:, None]          # [Q, T]
        q_b, qi_b, w_b = take(q), take(qi), take(w)
        score = sum(w_b[:, :, j, None] * jax.nn.relu(jnp.einsum(
            "bqd,bsd->bqs", qi_b[:, :, j], ki)) for j in range(n))
        masked = jnp.where(seen[None], score, -jnp.inf)
        if handed:
            chosen = unpack(take(sel), t) & seen[None]
        else:
            idx = jax.lax.top_k(masked, min(topk, t))[1]
            chosen = jnp.zeros(masked.shape, bool).at[
                jnp.arange(b)[:, None, None], jnp.arange(qc)[None, :, None],
                idx].set(True) & seen[None]

        def head(args):
            q_h, k_h, v_h = args                               # [B, ., dh]
            s = jnp.einsum("bqd,bkd->bqk", q_h, k_h) / math.sqrt(dh)
            return r(jax.nn.softmax(jnp.where(chosen, s, -jnp.inf), -1)) @ v_h

        o = jax.lax.map(head, (jnp.moveaxis(q_b, 2, 0), kh, vh))
        cnt = seen.sum(-1)[None]
        mean = jnp.where(seen[None], score, 0.0).sum(-1) / cnt
        var = jnp.where(seen[None], (score - mean[..., None]) ** 2,
                        0.0).sum(-1) / cnt
        worst = jnp.where(chosen, score, jnp.inf).min(-1)
        best_out = jnp.where(seen[None] & ~chosen, score, -jnp.inf).max(-1)
        return o, (worst, best_out, jnp.sqrt(var))  # o [H, B, Q, dh]

    o, facts = jax.lax.map(block, jnp.arange(nb) * qc)
    o = jnp.moveaxis(o, 0, 2).reshape(heads, b, nb * qc, dh)[:, :, :t]
    y = mm(jnp.moveaxis(o, 0, 2).reshape(b, t, heads * dh), p["wo"])
    facts = {name: jnp.moveaxis(f, 0, 1).reshape(b, nb * qc)[:, :t]
             for name, f in zip(("worst", "best_out", "std"), facts)}
    return y, facts if handed else None


def moe(x, p, cfg, routes=None, bf16_operands=False):
    """-> (y, z): the layer's part held here, and the router's logits of
    every expert (the softmax ranks as they do).  The router's product is
    float32 in either form, as the served router's is."""
    r = _rounder(bf16_operands)
    (first, count), _total = held_experts(cfg)
    k = cfg["num_experts_per_tok"]
    z = x @ p["router"]
    s = jax.nn.softmax(z, -1)
    idx = jax.lax.top_k(s, k)[1] if routes is None else routes
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = chosen / chosen.sum(-1, keepdims=True)

    def one(y, xs):
        e, wg, wu, wd = xs
        w_e = jnp.sum(jnp.where(idx == e, w, 0.0), -1, keepdims=True)
        wg, wu, wd = (a.astype(jnp.float32) for a in (wg, wu, wd))
        return y + w_e * (r(jax.nn.silu(r(x) @ r(wg)) * (r(x) @ r(wu)))
                          @ r(wd)), None

    ex = p["experts"]
    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        (first + jnp.arange(count), ex["wg"], ex["wu"],
                         ex["wd"]))
    return y, z


@functools.partial(jax.jit, static_argnames=("cfg_json", "bf16_operands"))
def _layer(x, p, routes, selection, cfg_json, bf16_operands):
    # jit wants hashable static arguments and a configuration is a nested
    # dict: it travels as its JSON text
    cfg = json.loads(cfg_json)
    with jax.default_matmul_precision("highest"):
        eps = cfg["rms_norm_eps"]
        y, facts = attention(_rms(x, p["norm1"].astype(jnp.float32), eps),
                             _f32(p["attn"]), cfg, selection, bf16_operands)
        x = x + y
        h = _rms(x, p["norm2"].astype(jnp.float32), eps)
        ffn = dict(p["ffn"], router=p["ffn"]["router"].astype(jnp.float32))
        y, z = moe(h, ffn, cfg, routes, bf16_operands)
        return x + y, z, facts


@functools.partial(jax.jit, static_argnames=("eps", "bf16_operands"))
def _head(x, g, w, eps, bf16_operands):
    r = _rounder(bf16_operands)
    with jax.default_matmul_precision("highest"):
        return r(_rms(x, g.astype(jnp.float32), eps)) \
            @ r(w.astype(jnp.float32))


def forward(p, ids, cfg, positions=None, routes=None, selections=None,
            bf16_operands=False):
    """p: {"emb" [V, d], "head" [d, V], "norm_f" [d], "layers": [{"norm1",
    "norm2", "attn": {...}, "ffn": {"router", "experts": {"wg", "wu",
    "wd"}}}]}; ids [B, T] int32; positions: None (every position) or [B, P]
    int32, the rows wanted; routes: None, or one [B, T, k] int32 array of
    chosen experts a layer; selections: None, or one [B, T, ceil(T / 8)]
    uint8 array a layer (``attention``'s ``selection``); ``bf16_operands``:
    the configuration's stated precision (the module's docstring).  ->
    (logits [B, T or P, V] float32, the router's logits [B, T, E] of each
    layer, the indexer's facts of each layer or None)."""
    key = json.dumps(cfg, sort_keys=True)
    x = p["emb"][ids].astype(jnp.float32)
    router, facts = [], []
    for i, lp in enumerate(p["layers"]):
        r = None if routes is None else routes[i]
        sel = None if selections is None else selections[i]
        x, z, f = _layer(x, lp, r, sel, key, bf16_operands)
        router.append(z)
        facts.append(f)
    if positions is not None:
        x = jnp.take_along_axis(x, jnp.asarray(positions)[:, :, None],
                                axis=1)
    return _head(x, p["norm_f"], p["head"], cfg["rms_norm_eps"],
                 bf16_operands), router, facts

"""Decoder-only pre-LayerNorm transformer (OPT, arXiv:2205.01068) in plain
jax.numpy, with the departures the configuration file lists: no biases on
the four attention projections, embeddings scaled by sqrt(d), learned
positions without OPT's offset of 2, LayerNorm eps 1e-6.

    x_0 = E[ids] * sqrt(d) + P[0..T)
    x  += softmax(causal(q k^T / sqrt(d_head))) v W_o,  q,k,v = LN1(x) W_{q,k,v}
    x  += relu(LN2(x) W_1 + b_1) W_2 + b_2
    logits = LN_f(x_L) E^T                       (tied output head)

Full causal forward over whole sequences: no cache, no kernels."""

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-6


def _ln(x, g, b):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * g + b


def logits(p, ids, num_heads):
    """p: {"emb" [V,d], "pos" [P,d], "layers": [{ln1_g, ln1_b, wq, wk, wv,
    wo, ln2_g, ln2_b, w1, b1, w2, b2}], "lnf_g", "lnf_b"}; ids [B,T] int32
    -> logits [B,T,V] float32 at every position."""
    with jax.default_matmul_precision("highest"):
        b, t = ids.shape
        d = p["emb"].shape[1]
        hd = d // num_heads
        x = p["emb"][ids] * math.sqrt(d) + p["pos"][:t][None]
        causal = jnp.tril(jnp.ones((t, t), bool))
        for lyr in p["layers"]:
            h = _ln(x, lyr["ln1_g"], lyr["ln1_b"])
            q, k, v = (jnp.reshape(h @ lyr[w], (b, t, num_heads, hd))
                       for w in ("wq", "wk", "wv"))
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(hd)
            s = jnp.where(causal[None, None], s, -jnp.inf)
            a = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)
            x = x + a.reshape(b, t, d) @ lyr["wo"]
            h = _ln(x, lyr["ln2_g"], lyr["ln2_b"])
            x = x + jax.nn.relu(h @ lyr["w1"] + lyr["b1"]) @ lyr["w2"] \
                + lyr["b2"]
        return _ln(x, p["lnf_g"], p["lnf_b"]) @ p["emb"].T

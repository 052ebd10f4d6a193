"""Multi-head latent attention (MLA) without rotation, served in the
absorbed form over a paged latent cache.

    q        = x W_q                     H heads of (nope + rope) columns
    [c, k_r] = x W_kva                   latent (rank) + one shared key part
    [k_n, v] = RMSNorm(c) W_kvb          H heads of (nope + v) columns
    k        = [k_n, k_r]                k_r shared by every head
    y        = softmax(causal(q k^T / sqrt(nope + rope))) v W_o

The cache holds ``[RMSNorm(c), k_r]`` a position (``rank + rope`` values,
one leaf ``[blocks, block, pool_width(rank + rope)]`` a layer) instead of H
heads of K and V.  ``W_kvb`` is absorbed: its key half into the query
(``q_n W_uk^T`` meets the latent directly), its value half after the softmax
(the probabilities weigh latents, ``W_uv`` widens the result once a lane).
``mla_use_nope``: no rotary anywhere, the "rope" columns are plain ones.

Runs in XLA: each row's latent blocks are gathered through its block table
and every lane attends under its own causal mask (``cols <= qpos``).  Two
things the chip taught (PR 27):

* the pool's last dimension is padded with zeros to a multiple of 128.  At
  576 the compiler laid the pool out with the BLOCK dimension minor (least
  padding), and every step copied both 151 MB pools into the layout the
  scatter wants and back: 2 ms of a 33 ms step;
* the gather, the ``[S, K, H, T]`` scores and the softmax run over the
  shortest of a few static spans (an eighth, a quarter, a half, all of the
  table) that holds every row's furthest position, chosen by ``lax.switch``
  on data: one program, no retrace, and contexts a quarter of ``max_len``
  long do not pay for the whole table."""

import math

import jax
import jax.numpy as jnp

from paddle_tpu.ops import linear
from paddle_tpu.ops.kda import rms_norm


LANES = 128
SPAN_FRACTIONS = (8, 4, 2, 1)   # of the block table, shortest first


def pool_width(latent_width):
    """The stored width of a latent: padded to whole 128-lane tiles."""
    return -(-latent_width // LANES) * LANES


def mla_chunk(p, h, pool, li, qpos, tables, *, num_heads, nope, rope, v_dim,
              rank, eps):
    """One MLA layer over the lanes.  p: the layer's ``attn`` parameters
    (models/hybrid_lm.py), h ``[S, K, d]`` the normed input, pool
    ``[blocks, block, pool_width(rank + rope)]``, li/qpos ``[S, K]`` the clamped lane
    indices and their positions (``transformer._chunk_lanes``), tables
    ``[S, blocks_per_row]`` -> (y ``[S, K, d]``, new pool).  The lanes'
    latents are written BEFORE the read, so causality inside the chunk is
    the ordinary mask."""
    s, kk, _d = h.shape
    block, width = pool.shape[1], pool.shape[2]
    pad = jnp.zeros((s, kk, width - rank - rope), jnp.float32)
    kva = linear.matmul(h, p["wkva"])
    new = jnp.concatenate(
        [rms_norm(kva[..., :rank], p["kv_norm"], eps), kva[..., rank:], pad],
        -1)
    # lanes past a row's length rewrite its last live lane's latent
    new = jnp.take_along_axis(new, li[:, :, None], axis=1)
    rows = jnp.arange(s)[:, None]
    pool = pool.at[tables[rows, qpos // block], qpos % block].set(
        new.astype(pool.dtype))

    q = linear.matmul(h, p["wq"]).reshape(s, kk, num_heads, nope + rope)
    wkvb = p["wkvb"].reshape(rank, num_heads, nope + v_dim)
    q_lat = linear.einsum("skhn,rhn->skhr", q[..., :nope], wkvb[..., :nope])
    q_all = jnp.concatenate(
        [q_lat, q[..., nope:],
         jnp.broadcast_to(pad[:, :, None, :],
                          (s, kk, num_heads, pad.shape[-1]))], -1)

    def attend(nb):
        """Over the first ``nb`` blocks of every row's table."""
        lat = pool[tables[:, :nb]].reshape(s, nb * block, width)
        scores = linear.einsum("skhc,stc->skht", q_all, lat) \
            / math.sqrt(nope + rope)
        live = jnp.arange(nb * block)[None, None, :] <= qpos[:, :, None]
        probs = jax.nn.softmax(
            jnp.where(live[:, :, None, :], scores, -jnp.inf), axis=-1)
        return linear.einsum("skht,str->skhr", probs, lat[..., :rank])

    nb_row = tables.shape[1]
    spans = sorted({max(1, nb_row // f) for f in SPAN_FRACTIONS})
    need = jnp.max(qpos) // block + 1
    which = sum((need > nb).astype(jnp.int32) for nb in spans[:-1])
    o_lat = jax.lax.switch(which, [lambda nb=nb: attend(nb) for nb in spans])
    o = linear.einsum("skhr,rhv->skhv", o_lat, wkvb[..., nope:])
    return linear.matmul(o.reshape(s, kk, num_heads * v_dim), p["wo"]), pool

"""Multi-head latent attention (MLA), served in the absorbed form over a
paged latent cache.

    q        = x W_q                     H heads of (nope + rope) columns,
               or RMSNorm(x W_qa) W_qb   through a low-rank query (``wqa``)
    [c, k_r] = x W_kva                   latent (rank) + one shared key part
    q_r, k_r <- RoPE_t(q_r), RoPE_t(k_r) where ``rope_theta`` is given
    [k_n, v] = RMSNorm(c) W_kvb          H heads of (nope + v) columns
    k        = [k_n, k_r]                k_r shared by every head
    y        = softmax(causal(q k^T / sqrt(nope + rope))) v W_o

The cache holds ``[RMSNorm(c), k_r]`` a position (``rank + rope`` values,
one leaf ``[blocks, block, pool_width(rank + rope)]`` a layer; ``k_r`` is
stored ROTATED, so old keys never turn again) instead of H heads of K and
V.  ``W_kvb`` is absorbed: its key half into the query (``q_n W_uk^T`` meets
the latent directly), its value half after the softmax (the probabilities
weigh latents, ``W_uv`` widens the result once a lane).  Without
``rope_theta`` nothing rotates and the "rope" columns are plain ones
(``mla_use_nope``).

There is one way to attend a latent pool, ``mla_chunk``; how it attends is
decided from static shapes by ``ops/pallas/mla.decline_reason``: the Pallas
kernel ``mla_chunk`` that walks each row's block table (a TPU at tiling
shapes), or XLA: each row's latent blocks gathered through its block table,
every lane under its own causal mask (``cols <= qpos``), ``[S, K, H, T]``
float32 scores (the CPU, a declined shape, the tests' oracle).  Two things
the chip taught the XLA path (PR 27):

* the pool's last dimension is padded with zeros to a multiple of 128.  At
  576 the compiler laid the pool out with the BLOCK dimension minor (least
  padding), and every step copied both 151 MB pools into the layout the
  scatter wants and back: 2 ms of a 33 ms step;
* the gather, the scores and the softmax run over the shortest of a few
  static spans (an eighth, a quarter, a half, all of the table) that holds
  every row's furthest position, chosen by ``lax.switch`` on data: one
  program, no retrace, and contexts a quarter of ``max_len`` long do not
  pay for the whole table.

The layer's products run on the step's PACKED lanes ``[N, ...]`` (the lanes
rows really feed, ``hybrid_lm.pack_lanes``), and so does the pool's write;
only the attention keeps ``[S, K]`` rows, on either path (docs/serving.md
"The packed lanes")."""

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


def rotate(x, pos, theta):
    """RoPE on the last dimension, pairs interleaved: columns ``(2i, 2i+1)``
    of the lane at position ``pos`` turn by ``pos * theta^(-2i/n)``.  x
    ``[..., n]`` float32, pos broadcastable to ``x.shape[:-1]``."""
    n = x.shape[-1]
    freq = theta ** (-jnp.arange(0, n, 2, dtype=jnp.float32) / n)
    ang = pos[..., None].astype(jnp.float32) * freq
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(x.shape[:-1] + (n // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1) \
        .reshape(x.shape)


def own_places(src, back):
    """Which places of a packing (``hybrid_lm.pack_lanes``: src ``[N]``,
    back ``[S, K]``) hold a lane of their own, ``[N]`` bool: those their
    lane points back at.  The rest repeat a lane (the packed tail; without a
    packing, the lanes past a row's length): no expert sees them and they
    write nothing."""
    return back.reshape(-1)[src] == jnp.arange(src.shape[0])


def mla_chunk(p, h, pool, qpos, tables, src, back, *, num_heads, nope, rope,
              v_dim, rank, eps, rope_theta=None):
    """One MLA layer over the step's packed lanes.  p: the layer's ``attn``
    parameters (models/hybrid_lm.py: ``wq``, or ``wqa`` / ``q_norm`` /
    ``wqb`` for a low-rank query), h ``[N, d]`` the normed input of the
    packed lanes, pool ``[blocks, block, pool_width(rank + rope)]``, qpos
    ``[S, K]`` the lanes' positions (``transformer._chunk_lanes``), tables
    ``[S, blocks_per_row]``, src ``[N]`` / back ``[S, K]`` the packing
    (``hybrid_lm.pack_lanes``) -> (y ``[N, d]``, new pool).

    Every product runs on the ``N`` packed lanes and the latents are written
    from them, position by position, BEFORE the read, so causality inside the
    chunk is the ordinary mask.  Only the attention itself keeps rows: the
    absorbed queries are laid out ``[S, K, H, W]`` through ``back`` (a lane
    past its row's length repeats the row's last), and the lanes' results
    are picked out of its ``[S, K, H, rank]`` through ``src``."""
    from paddle_tpu.core import dtypes
    from paddle_tpu.ops.pallas import mla as kernel
    n, (s, kk) = h.shape[0], qpos.shape
    block, width = pool.shape[1], pool.shape[2]
    row, pos = src // kk, qpos.reshape(-1)[src]
    kva = linear.matmul(h, p["wkva"])
    k_r = kva[..., rank:]
    if rope_theta is not None:
        k_r = rotate(k_r, pos, rope_theta)
    pad = jnp.zeros((n, width - rank - rope), jnp.float32)
    new = jnp.concatenate(
        [rms_norm(kva[..., :rank], p["kv_norm"], eps), k_r, pad], -1)
    # a place that repeats a lane writes nothing: past an expert layer it
    # no longer holds what its lane holds (``routed_experts`` skips it)
    blk = jnp.where(own_places(src, back), tables[row, pos // block],
                    pool.shape[0])
    pool = pool.at[blk, pos % block].set(new.astype(pool.dtype), mode="drop")

    if "wqa" in p:
        q = linear.matmul(rms_norm(linear.matmul(h, p["wqa"]), p["q_norm"],
                                   eps), p["wqb"])
    else:
        q = linear.matmul(h, p["wq"])
    q = q.reshape(n, num_heads, nope + rope) / math.sqrt(nope + rope)
    q_r = q[..., nope:]
    if rope_theta is not None:
        q_r = rotate(q_r, pos[:, None], rope_theta)
    wkvb = p["wkvb"].reshape(rank, num_heads, nope + v_dim)
    q_lat = linear.einsum("nhd,rhd->nhr", q[..., :nope], wkvb[..., :nope])
    q_all = jnp.concatenate(
        [q_lat, q_r,
         jnp.broadcast_to(pad[:, None, :], (n, num_heads, pad.shape[-1]))],
        -1)

    if kernel.decline_reason(kk, num_heads, width, rank, block,
                             pool.dtype) is None:
        o_lat = kernel.mla_attend(q_all.astype(dtypes.compute_dtype())[back],
                                  pool, qpos, tables, rank=rank)
    else:
        q_rows = q_all[back]

        def attend(nb):
            """Over the first ``nb`` blocks of every row's table."""
            lat = pool[tables[:, :nb]].reshape(s, nb * block, width)
            scores = linear.einsum("skhc,stc->skht", q_rows, lat)
            live = jnp.arange(nb * block)[None, None, :] <= qpos[:, :, None]
            probs = jax.nn.softmax(
                jnp.where(live[:, :, None, :], scores, -jnp.inf), axis=-1)
            return linear.einsum("skht,str->skhr", probs, lat[..., :rank])

        nb_row = tables.shape[1]
        spans = sorted({max(1, nb_row // f) for f in SPAN_FRACTIONS})
        need = jnp.max(qpos) // block + 1
        which = sum((need > nb).astype(jnp.int32) for nb in spans[:-1])
        o_lat = jax.lax.switch(which,
                               [lambda nb=nb: attend(nb) for nb in spans])
    # (the kernel leaves the lanes past a row's length unwritten: src names
    # none of them)
    o_lat = o_lat.reshape(s * kk, num_heads, rank)[src]
    o = linear.einsum("nhr,rhv->nhv", o_lat, wkvb[..., nope:])
    return linear.matmul(o.reshape(n, num_heads * v_dim), p["wo"]), pool

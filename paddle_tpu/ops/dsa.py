"""Learned sparse attention (DeepSeek Sparse Attention, DeepSeek-V3.2's
lightning indexer) over the paged pools, in three steps a layer, each a
Pallas kernel (ops/pallas/dsa.py) or, where its ``decline_reason`` says the
kernels do not serve, the XLA path here that computes the same:

    I[t, s] = sum_j w_{t,j} ReLU(q^I_{t,j} . k^I_s)          (index_scores)
    S_t     = the topk positions s <= t of largest I[t, s],
              ties to the lower position                     (select)
    o_t     = softmax_{s in S_t}(q_t . k_s / sqrt(d)) v_s     (attend)

A step's rows are ``[S, K]``: qpos ``[S, K]`` the lanes' positions, tables
``[S, E]`` the rows' block tables over ``E x block`` positions.  The scores
live in a ``[S, K, E x block]`` float32 buffer; the selection is a
THRESHOLD a lane (``picks``, ``[S, K, LANES]`` int32: the order key of its
topk-th largest score, the position of the last tie it takes, the count it
takes), which the attention turns back into a mask over any tile of
positions, and the same selection as BITS (``[S, K, W]`` int32, ``W =
kernels.plane_width(T)``: position p is bit ``p // W`` of word ``p % W``),
which the step reports."""

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.ops import linear
from paddle_tpu.ops.pallas import dsa as kernels
from paddle_tpu.ops.pallas.common import LANES


def index_scores(qi, w, ik_pool, qpos, tables, use_kernel):
    """qi ``[S, K, H, D]``, w ``[S, K, H]`` float32 (the heads' weights,
    scale included), ik_pool ``[blocks, block, D]`` -> ``[S, K, T]``
    float32, a lane's scores at every position up to its own (what lies
    past it is undefined: ``select`` masks it)."""
    if use_kernel:
        return kernels.indexer_paged_chunk(qi, w, ik_pool, qpos, tables)
    s, kk, heads, dim = qi.shape
    keys = ik_pool[tables].reshape(s, -1, dim)                 # [S, T, D]
    sc = linear.einsum("skhd,std->skht", qi, keys)
    return jnp.einsum("skht,skh->skt", jnp.maximum(sc, 0.0),
                      w.astype(jnp.float32),
                      precision=jax.lax.Precision.HIGHEST)


def pack(taken):
    """``[..., T]`` bool -> ``[..., plane_width(T)]`` int32, the bits'
    layout."""
    t = taken.shape[-1]
    width = kernels.plane_width(t)
    planes = jnp.pad(taken, [(0, 0)] * (taken.ndim - 1)
                     + [(0, 32 * width - t)]).reshape(
        taken.shape[:-1] + (32, width)).astype(jnp.uint32)
    shift = jnp.arange(32, dtype=jnp.uint32)[:, None]
    return jax.lax.bitcast_convert_type(
        jnp.sum(planes << shift, axis=-2, dtype=jnp.uint32), jnp.int32)


def unpack(bits, t):
    """``pack``'s inverse over the first ``t`` positions: ``[..., W]``
    int32 -> ``[..., t]`` bool (the planes past ``t`` are not read), in
    numpy or jax.numpy as ``bits`` is."""
    xp = np if isinstance(bits, np.ndarray) else jnp
    planes = min(32, -(-t // bits.shape[-1]))
    taken = (bits[..., None, :]
             >> xp.arange(planes, dtype=xp.int32)[:, None]) & 1
    return taken.reshape(bits.shape[:-1] + (-1,))[..., :t] != 0


def select(scores, qpos, topk, use_kernel):
    """``[S, K, T]`` scores -> (picks ``[S, K, LANES]`` int32 (lane 0: the
    order key of the topk-th largest score at or before the lane's
    position, ``INT_MIN`` where it has no more than topk positions; lane 1:
    the position of the last tie at that key it takes, -1 where it takes
    every position; lane 2: how many it takes), bits ``[S, K, W]``: the
    positions it takes)."""
    if use_kernel:
        return kernels.sparse_select(scores, qpos, topk)
    s, kk, t = scores.shape
    col = jnp.arange(t, dtype=jnp.int32)
    valid = col <= qpos[:, :, None]
    keys = jnp.where(valid, kernels.order_keys(scores), kernels.INT_MIN)
    kth = jax.lax.top_k(keys, min(topk, t))[0][..., -1]
    few = qpos + 1 <= topk
    thr = jnp.where(few, kernels.INT_MIN, kth)
    ties = valid & (keys == thr[..., None])
    need = topk - jnp.sum(keys > thr[..., None], axis=-1)
    rank = jnp.cumsum(ties, axis=-1)
    w = jnp.max(jnp.where(ties & (rank == need[..., None]), col, -1),
                axis=-1)
    w = jnp.where(few, -1, w)
    taken = valid & kernels.selected(keys, col, thr[..., None], w[..., None])
    picks = jnp.zeros((s, kk, LANES), jnp.int32).at[..., 0].set(thr) \
        .at[..., 1].set(w).at[..., 2].set(
            jnp.sum(taken, axis=-1, dtype=jnp.int32))
    return picks, pack(taken)


def mask(scores, picks, qpos):
    """The selection ``picks`` makes of ``scores``: ``[S, K, T]`` bool."""
    col = jnp.arange(scores.shape[-1], dtype=jnp.int32)
    return (col <= qpos[:, :, None]) & kernels.selected(
        kernels.order_keys(scores), col, picks[..., :1], picks[..., 1:2])


def attend(q, k_pool, v_pool, scores, picks, qpos, tables, num_heads,
           use_kernel):
    """q ``[S, K, H x dh]`` -> ``[S, K, H x dh]``: each lane's softmax
    attention over the positions it selected, K and V from the pools."""
    if use_kernel:
        return kernels.sparse_attn_paged_chunk(
            q, k_pool, v_pool, scores, picks, qpos, tables,
            num_heads=num_heads)
    s, kk, d = q.shape
    dkv = k_pool.shape[2]
    head_dim = d // num_heads
    kv_heads = dkv // head_dim
    rows = lambda pool: pool[tables].reshape(s, -1, kv_heads, head_dim)
    sc = linear.einsum(
        "skvgd,stvd->skvgt",
        q.reshape(s, kk, kv_heads, num_heads // kv_heads, head_dim),
        rows(k_pool)) * head_dim ** -0.5
    live = mask(scores, picks, qpos)[:, :, None, None, :]
    probs = jax.nn.softmax(jnp.where(live, sc, -jnp.inf), axis=-1)
    return linear.einsum("skvgt,stvd->skvgd", probs, rows(v_pool)) \
        .reshape(s, kk, d)

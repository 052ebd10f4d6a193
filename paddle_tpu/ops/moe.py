"""Mixture-of-experts FFN with expert parallelism.

A post-reference capability (the reference predates MoE) backing the mesh's
'expert' axis (parallel/mesh.py AXIS_EXPERT).  TPU-first shape: experts are
one batched [E, D, F] einsum, so sharding the E dim over the 'expert' axis
makes every device compute ONLY its local experts over all tokens and XLA
inserts the psum that combines partial expert outputs — expert parallelism
derived from shardings, no hand-written all-to-all.  Gating is dense
top-k with renormalization (Switch/GShard style): no dynamic shapes, no
scatter — everything stays MXU-friendly einsums under jit.

That all-experts einsum (``moe_ffn``) serves the 2017 trunk and is the
tests' oracle.  The SERVED expert layer is below it (``sigmoid_router`` or
``softmax_router``, ``routed_experts``): tokens sorted by expert, grouped
products over the experts this holder was told it holds, the rest of the
experts' part left to whoever holds them (models/hybrid_lm.py;
docs/serving.md).
"""

import jax
import jax.numpy as jnp

# Rows a held expert's group of (token, expert) pairs is padded to in
# ``routed_experts``: one bfloat16 sublane tile.  XLA's grouped matmul on a
# TPU runs about twice as fast over groups that start on such a row.
ROW_ALIGN = 16


def init_moe(rng, d_model, d_ff, n_experts, dtype=jnp.float32):
    kg, k1, k2 = jax.random.split(rng, 3)
    scale = d_model ** -0.5
    return {
        "wg": (jax.random.normal(kg, (d_model, n_experts)) * scale
               ).astype(dtype),
        "w1": (jax.random.normal(k1, (n_experts, d_model, d_ff)) * scale
               ).astype(dtype),
        "w2": (jax.random.normal(k2, (n_experts, d_ff, d_model))
               * d_ff ** -0.5).astype(dtype),
    }


def router_probs(x, wg):
    """Softmax router probabilities: x [..., D], wg [D, E] -> [..., E]."""
    return jax.nn.softmax(x @ wg, axis=-1)


def moe_gates(probs, top_k):
    """Top-k gates from router probs, renormalized over the kept experts;
    EXACTLY top_k experts stay nonzero even on tied probabilities (index-
    based mask, not a >=threshold)."""
    e = probs.shape[-1]
    if top_k >= e:
        return probs
    _, idx = jax.lax.top_k(probs, top_k)            # [..., top_k]
    mask = jax.nn.one_hot(idx, e, dtype=probs.dtype).sum(-2)
    kept = probs * mask
    return kept / jnp.maximum(kept.sum(-1, keepdims=True), 1e-9)


def aux_load_balance_loss(probs, gates, top_k, valid=None):
    """GShard/Switch auxiliary loss over precomputed router tensors:
    E * sum_e(frac_tokens_picking_e * mean_prob_e); minimized (=1) at
    uniform expert utilization.  valid: optional [...] token mask — the
    statistics count REAL tokens only, so padding (which routes
    identically everywhere) can't skew the balance pressure."""
    e = probs.shape[-1]
    picked = (gates > 0).astype(probs.dtype)
    if valid is None:
        frac = picked.reshape(-1, e).mean(0) / max(top_k, 1)
        mean_prob = probs.reshape(-1, e).mean(0)
    else:
        w = valid.astype(probs.dtype).reshape(-1, 1)
        n = jnp.maximum(w.sum(), 1.0)
        frac = (picked.reshape(-1, e) * w).sum(0) / n / max(top_k, 1)
        mean_prob = (probs.reshape(-1, e) * w).sum(0) / n
    return e * jnp.sum(frac * mean_prob)


def moe_ffn(x, params, top_k=2, act=jax.nn.gelu, return_aux=False,
            valid=None):
    """x: [B, T, D] -> [B, T, D] through E gated FFN experts.

    All experts run as one batched einsum over the E dim; under a mesh with
    w1/w2 sharded P('expert', ...) each device computes its local experts'
    partial output and the gate-weighted combine psums across the axis.
    The router runs ONCE; return_aux=True additionally returns the
    load-balance loss built from the same probs/gates, restricted to
    `valid` [B, T] tokens when given (padding must not train the
    router)."""
    probs = router_probs(x, params["wg"])              # [B, T, E]
    gates = moe_gates(probs, top_k)
    h = act(jnp.einsum("btd,edf->btef", x, params["w1"]))
    y = jnp.einsum("btef,efd->bted", h, params["w2"])
    out = jnp.einsum("bted,bte->btd", y, gates)
    if return_aux:
        return out, aux_load_balance_loss(probs, gates, top_k, valid)
    return out


def expert_shardings(mesh, axis="expert"):
    """NamedShardings for an init_moe params dict: experts sharded over the
    expert axis, gate replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    return {
        "wg": NamedSharding(mesh, P(None, None)),
        "w1": NamedSharding(mesh, P(axis, None, None)),
        "w2": NamedSharding(mesh, P(axis, None, None)),
    }


# ------------------------------------------------- routed, held experts

def sigmoid_router(x, w, bias, top_k, scale):
    """A sigmoid router with a selection bias and a scale, in float32:
    ``s = sigmoid(x W_r)``; the ``top_k`` experts are the largest of
    ``s + bias`` (the bias chooses, it does not weigh); the weights are
    ``scale * s_e / sum of the chosen s``.  x ``[N, D]``, w ``[D, E]``,
    bias ``[E]`` -> (idx ``[N, top_k]`` int32, weights ``[N, top_k]``).
    The product runs at ``highest`` precision: on a TPU a float32 product
    is otherwise one bfloat16 pass, and the choice is discontinuous."""
    s = jax.nn.sigmoid(jnp.dot(x.astype(jnp.float32),
                               w.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST))
    _, idx = jax.lax.top_k(s + bias.astype(jnp.float32), top_k)
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    return idx.astype(jnp.int32), \
        scale * chosen / chosen.sum(-1, keepdims=True)


def softmax_router(x, w, top_k, scale):
    """A softmax router with a scale, in float32, ``sigmoid_router``'s
    interface without a bias: ``s = softmax(x W_r)`` over ALL the experts
    (the router keeps its published width); the ``top_k`` largest are
    chosen and weighted ``scale * s_e / sum of the chosen s``.  x ``[N,
    D]``, w ``[D, E]`` -> (idx ``[N, top_k]`` int32, weights ``[N,
    top_k]``).  The product runs at ``highest`` precision, as there."""
    s = jax.nn.softmax(jnp.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST), axis=-1)
    chosen, idx = jax.lax.top_k(s, top_k)
    return idx.astype(jnp.int32), \
        scale * chosen / chosen.sum(-1, keepdims=True)


def routed_experts(x, idx, weights, params, held, valid=None):
    """The part of a routed expert layer that THIS holder's experts give:
    expert parallelism as one chip sees it, without the exchange.

    x ``[N, D]``; idx/weights ``[N, k]`` over ALL experts (the router keeps
    its published width); params ``{"wg", "wu" [C, D, F], "wd" [C, F, D]}``
    hold the ``C`` experts ``first .. first + C - 1`` (``held = (first,
    C)``); valid ``[N]`` marks real tokens (padding lanes are routed
    nowhere).  Returns ``sum over held chosen e of w_e E_e(x)``, ``[N, D]``
    float32, ``E(x) = (SiLU(x W_gate) * x W_up) W_down``.

    The ``N * k`` (token, expert) pairs are sorted by expert and laid out
    in groups that each start on a whole ``ROW_ALIGN`` rows; pairs of
    experts held elsewhere, and of padding, have no row and are never
    computed.  The three grouped products are ``jax.lax.ragged_dot`` over
    that layout (on a TPU: XLA's Mosaic grouped matmul, ``ragged-dot`` in
    the trace), which reads only the experts that have rows."""
    from paddle_tpu.core import dtypes
    first, count = held
    n, k = idx.shape
    m = n * k
    cd = dtypes.compute_dtype()
    local = idx - first
    mine = (local >= 0) & (local < count)
    if valid is not None:
        mine &= valid[:, None]
    mine = mine.reshape(-1)
    key = jnp.where(mine, local.reshape(-1), count)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
    # each pair's place in the sorted order, and its row in the layout
    rank = jnp.zeros((m,), jnp.int32).at[order].set(
        jnp.arange(m, dtype=jnp.int32))
    padded = -(-sizes // ROW_ALIGN) * ROW_ALIGN
    rows_total = -(-(m + (ROW_ALIGN - 1) * min(count, m)) // ROW_ALIGN) \
        * ROW_ALIGN
    e = jnp.minimum(key, count - 1)
    row = jnp.where(mine, (jnp.cumsum(padded) - padded)[e] + rank
                    - (jnp.cumsum(sizes) - sizes)[e], rows_total)
    token = jnp.zeros((rows_total,), jnp.int32).at[row].set(
        jnp.arange(m, dtype=jnp.int32) // k, mode="drop")
    rows = x.astype(cd)[token]
    dot = lambda a, w: jax.lax.ragged_dot(
        a.astype(cd), w.astype(cd), padded,
        preferred_element_type=jnp.float32)
    y = dot(jax.nn.silu(dot(rows, params["wg"])) * dot(rows, params["wu"]),
            params["wd"])
    # a pair with no row reads some row and drops it: rows past the last
    # group hold whatever the grouped product left there
    y = jnp.where(mine[:, None],
                  y[jnp.minimum(row, rows_total - 1)]
                  * weights.reshape(-1)[:, None], 0.0)
    return y.reshape(n, k, -1).sum(1)


def gated_ffn(x, wg, wu, wd):
    """``(SiLU(x W_gate) * x W_up) W_down`` under ``linear.matmul``'s
    policy: the dense FFN and the shared expert."""
    from paddle_tpu.ops import linear
    return linear.matmul(
        jax.nn.silu(linear.matmul(x, wg)) * linear.matmul(x, wu), wd)

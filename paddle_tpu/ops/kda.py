"""Kimi Delta Attention (KDA): a gated delta rule with a per-channel decay,
short causal convolutions on q, k, v and a gated head-wise RMSNorm, for the
serving step's token lanes: the projections, the gates and the norm over
the step's PACKED lanes ``[N, ...]`` (``hybrid_lm.pack_lanes``), the
convolution and the recurrence over ``[S, K]`` rows.

    q, k, v = SiLU(conv_W(x W_qkv))             causal depthwise over time
    q, k    = q / |q| * dk^-0.5,  k / |k|       per head
    a_t     = exp(-exp(A_log) softplus(x W_f1 W_f2 + dt_bias))   in (0,1)^dk
    b_t     = sigmoid(x W_b)                    per head
    S_t     = (I - b_t k_t k_t^T) Diag(a_t) S_{t-1} + b_t k_t v_t^T
    o_t     = S_t^T q_t
    y       = (RMSNorm_head(o_t) * sigmoid(x W_g1 W_g2)) W_o

A slot owns two pieces of state that no position addresses: the float32
``S`` (``[H, dk, dv]``) and the convolution's tail (the last ``W - 1`` rows
of ``x W_qkv``).  Both start at zero when a row's chunk starts at position
0 and are left alone by lanes at or past the row's ``lengths``.  Matrix
products follow ``ops/linear.matmul`` (bfloat16 operands on the MXU,
float32 accumulation); the recurrence is float32 throughout."""

import jax
import jax.numpy as jnp

from paddle_tpu.ops import linear

L2_EPS = 1e-6


def rms_norm(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * gain


def short_conv(z, tail, w, lengths):
    """Causal depthwise convolution over the lanes, carried across steps:
    z ``[S, K, C]`` (this step's inputs), tail ``[S, W-1, C]`` (the inputs
    just before lane 0), w ``[W, C]`` -> (y ``[S, K, C]``, new tail: the
    ``W - 1`` inputs that end at lane ``lengths - 1``)."""
    width, kk = w.shape[0], z.shape[1]
    zc = jnp.concatenate([tail.astype(z.dtype), z], axis=1)
    y = sum(w[j] * zc[:, j:j + kk] for j in range(width))
    keep = lengths[:, None] + jnp.arange(width - 1)[None, :]
    return y, jnp.take_along_axis(zc, keep[:, :, None], axis=1)


def recurrence_scan(q, k, v, a, beta, state, lengths, fresh):
    """The gated delta rule lane by lane in XLA: the oracle of the Pallas
    kernel and its fallback (each lane rewrites the whole state).  Shapes
    as ``ops/pallas/kda.kda_chunk``."""
    state = jnp.where(fresh[:, None, None, None], 0.0, state)

    def lane(st, xs):
        t, q_t, k_t, v_t, a_t, b_t = xs
        new = st * a_t[..., None]
        r = jnp.einsum("shkv,shk->shv", new, k_t)
        new = new + (k_t * b_t[..., None])[..., None] \
            * (v_t - r)[:, :, None, :]
        o = jnp.einsum("shkv,shk->shv", new, q_t)
        live = (t < lengths)[:, None, None]
        return (jnp.where(live[..., None], new, st),
                jnp.where(live, o, 0.0))

    lanes = lambda x: jnp.moveaxis(x, 1, 0)
    state, o = jax.lax.scan(
        lane, state, (jnp.arange(q.shape[1]), lanes(q), lanes(k), lanes(v),
                      lanes(a), lanes(beta)))
    return jnp.moveaxis(o, 0, 1), state


def recurrence(q, k, v, a, beta, state, lengths, fresh):
    """``kda_chunk`` where its guard allows (ops/pallas/kda.py), else the
    scan."""
    from paddle_tpu.ops.pallas import kda as kernel
    s, kk, heads, dk = q.shape
    if kernel.decline_reason(kk, heads, dk, v.shape[-1]) is None:
        return kernel.kda_chunk(q, k, v, a, beta, state, lengths, fresh)
    return recurrence_scan(q, k, v, a, beta, state, lengths, fresh)


def kda_chunk(p, h, state, tail, positions, lengths, src, back, *, num_heads,
              head_dim, eps):
    """One KDA layer over the step's packed lanes.  p: the layer's ``attn``
    parameters (models/hybrid_lm.py), h ``[N, d]`` the normed input of the
    packed lanes, state ``[S, H, dk, dv]`` float32, tail
    ``[S, W-1, 3*H*dk]``, positions ``[S]`` (lane 0's), lengths ``[S]``, src
    ``[N]`` / back ``[S, K]`` the packing (``hybrid_lm.pack_lanes``) ->
    (y ``[N, d]``, new state, new tail).

    The projections, the gates, the head-wise norm and ``wo`` run on the
    ``N`` packed lanes.  The convolution and the recurrence need a row's
    lanes side by side: their operands are laid out ``[S, K, ...]`` through
    ``back`` (a lane past its row's length repeats the row's last; neither
    reads it into the state or the tail), and the lanes' outputs are picked
    out of the recurrence's ``[S, K, H, dv]`` through ``src``."""
    n, (s, kk) = h.shape[0], back.shape
    heads, dk = num_heads, head_dim
    fresh = positions == 0
    tail = jnp.where(fresh[:, None, None], 0.0, tail)
    z, tail = short_conv(linear.matmul(h, p["wqkv"])[back], tail,
                         p["conv"].astype(jnp.float32), lengths)
    q, k, v = (x.reshape(s, kk, heads, dk)
               for x in jnp.split(jax.nn.silu(z), 3, axis=-1))
    unit = lambda x: x * jax.lax.rsqrt(
        jnp.sum(x * x, -1, keepdims=True) + L2_EPS)
    q, k = unit(q) * dk ** -0.5, unit(k)
    f = linear.matmul(linear.matmul(h, p["wf1"]), p["wf2"]) + p["dt_bias"]
    decay = jnp.exp(-jnp.exp(p["a_log"].astype(jnp.float32))[:, None]
                    * jax.nn.softplus(f).reshape(n, heads, dk))
    beta = jax.nn.sigmoid(linear.matmul(h, p["wb"]))
    o, state = recurrence(q, k, v, decay[back], beta[back], state, lengths,
                          fresh)
    o = o.reshape(s * kk, heads, dk)[src]
    gate = linear.matmul(linear.matmul(h, p["wg1"]), p["wg2"])
    o = rms_norm(o, p["o_norm"], eps) \
        * jax.nn.sigmoid(gate.reshape(n, heads, dk))
    return linear.matmul(o.reshape(n, heads * dk), p["wo"]), state, tail

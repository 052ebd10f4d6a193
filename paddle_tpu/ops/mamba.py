"""The Mamba-1 mixer as Jamba has it (a selective state-space layer with a
norm on each of the scan's three data-dependent inputs), for the serving
step's token lanes: every product, the convolution, the gate AND the scan
over the step's PACKED lanes ``[N, ...]`` (``hybrid_lm.pack_lanes``).

    [u, z]     = x W_in                          d -> 2 x d_inner
    u          = SiLU(conv_W(u) + b_conv)        causal depthwise over time
    [dt, B, C] = u W_x                           d_inner -> dt_rank + 2 n
    dt, B, C   = RMSNorm_dt(dt), RMSNorm_b(B), RMSNorm_c(C)    (Jamba's)
    dt         = softplus(dt W_dt + b_dt)        dt_rank -> d_inner
    h_t        = exp(dt_t A) * h_{t-1} + (dt_t u_t) B_t^T      A = -exp(A_log)
    y_t        = C_t^T h_t + D * u_t
    out        = (y * SiLU(z)) W_out             d_inner -> d

A slot owns two pieces of state that no position addresses: the float32
``h`` (``[n, d_inner]``: ``d_state`` on sublanes, ``d_inner`` on lanes) and
the convolution's tail (the last ``W - 1`` rows of ``u`` before the
convolution).  Both start at zero when a row's chunk starts at position 0
and are left alone by lanes at or past the row's ``lengths``.  Matrix
products follow ``ops/linear.matmul`` (bfloat16 operands on the MXU, float32
accumulation); ``A``, ``D``, ``dt``, the decay, the state and the scan are
float32 throughout.

Nothing here is laid out ``[S, K]``: a row's lanes lie side by side in the
packing, so the convolution reads a lane's predecessors from the places
before it (or from the tail), and the kernel walks the places in order
(ops/pallas/mamba.py).  At 5,120 columns a gather of ``u``, ``dt`` and ``y``
to ``[S, K]`` and back would move 150 MB a layer whatever the step feeds.
Only ``scan_xla``, the kernel's oracle and fallback, unpacks."""

import jax
import jax.numpy as jnp

from paddle_tpu.ops import linear
from paddle_tpu.ops.kda import rms_norm
from paddle_tpu.ops.mla import own_places


def packed_conv(z, tail, w, bias, lane, first, lengths):
    """``kda.short_conv`` on packed lanes: z ``[N, C]`` (this step's
    inputs, a row's lanes side by side), tail ``[S, W-1, C]`` (each row's
    inputs just before its lane 0), w ``[W, C]``, bias ``[C]``; lane ``[N]``
    (the lane of its row that place p holds), first ``[S]`` (the place of
    each row's lane 0), lengths ``[S]`` -> (y ``[N, C]``, new
    tail: the ``W - 1`` inputs that end at lane ``lengths - 1``).  A place
    that repeats a lane (the packed tail) reads neighbours that are not its
    own: its y means nothing, and nothing reads it."""
    width, n = w.shape[0], z.shape[0]
    # a place's predecessors are the places before it (a static shift, no
    # gather of 5,120 columns a place) as far as its row reaches back ...
    y = w[width - 1] * z + bias
    for k in range(1, width):
        shifted = jnp.pad(z, ((k, 0), (0, 0)))[:n]
        y = y + w[width - 1 - k] * jnp.where((lane >= k)[:, None], shifted,
                                             0.0)
    # ... and the tail before that, which only a row's first W - 1 lanes
    # see: [S, W-1, C] of corrections, added at those places
    j = jnp.arange(width - 1)
    head = sum(
        jnp.where((j < k)[None, :, None], w[width - 1 - k], 0.0)
        * tail[:, jnp.minimum(width - 1 - k + j, width - 2)]
        for k in range(1, width))
    at = jnp.where(j[None, :] < lengths[:, None], first[:, None] + j, n)
    y = y.at[at].add(head, mode="drop")
    # of [tail | the row's lanes], the W - 1 entries from ``lengths`` on
    keep = lengths[:, None] + jnp.arange(width - 1)[None, :]
    in_row = keep >= width - 1
    from_row = z[first[:, None] + jnp.maximum(keep - (width - 1), 0)]
    from_tail = jnp.take_along_axis(
        tail, jnp.minimum(keep, width - 2)[:, :, None], axis=1)
    return y, jnp.where(in_row[:, :, None], from_row, from_tail)


def scan_xla(u, dt, b, c, a, state, lengths, fresh, src, back):
    """The selective scan lane by lane in XLA, over ``[S, K]`` rows laid
    out through ``back``: the oracle of the Pallas kernel and its fallback
    (each lane rewrites every state).  u, dt ``[N, d]``, b, c ``[N, n]``, a
    ``[n, d]``, state ``[S, n, d]``, fresh ``[S]`` bool -> (y ``[N, d]``,
    new state)."""
    (s, kk), d = back.shape, u.shape[1]
    state = jnp.where(fresh[:, None, None], 0.0, state)

    def lane(st, xs):
        t, u_t, dt_t, b_t, c_t = xs
        new = jnp.exp(dt_t[:, None, :] * a) * st \
            + (dt_t * u_t)[:, None, :] * b_t[:, :, None]
        y = jnp.sum(new * c_t[:, :, None], axis=1)
        return jnp.where((t < lengths)[:, None, None], new, st), y

    rows = lambda x: jnp.moveaxis(x[back], 1, 0)
    state, y = jax.lax.scan(
        lane, state, (jnp.arange(kk), rows(u), rows(dt), rows(b), rows(c)))
    return jnp.moveaxis(y, 0, 1).reshape(s * kk, d)[src], state


def walk(src, back, fresh):
    """What ``mamba_chunk`` is told of a packing's places: (slot ``[N]``,
    flags ``[N]``: ``SKIP`` where the place repeats a lane, ``ZERO`` where
    it is lane 0 of a fresh row; the places to walk: up to the last that
    holds a lane of its own)."""
    from paddle_tpu.ops.pallas import mamba as kernel
    n, kk = src.shape[0], back.shape[1]
    slot, own = src // kk, own_places(src, back)
    flags = jnp.where(own, 0, kernel.SKIP) \
        + jnp.where(fresh[slot] & (src % kk == 0), kernel.ZERO, 0)
    return slot, flags, jnp.max(jnp.where(own, jnp.arange(n), 0)) + 1


def scan(u, dt, b, c, a, state, lengths, fresh, src, back):
    """``mamba_chunk`` where its guard allows (ops/pallas/mamba.py), else
    ``scan_xla``; same arguments and results.  Places that repeat a lane
    hold anything in ``y``."""
    from paddle_tpu.ops.pallas import mamba as kernel
    slots, n_state, d = state.shape
    if kernel.decline_reason(u.shape[0], slots, d, n_state) is not None:
        return scan_xla(u, dt, b, c, a, state, lengths, fresh, src, back)
    return kernel.mamba_chunk(u, dt, b, c, a, state,
                              *walk(src, back, fresh))


def mamba_chunk(p, h, state, tail, positions, lengths, src, back, *,
                dt_rank, eps):
    """One Mamba layer over the step's packed lanes.  p: the layer's
    ``attn`` parameters (models/hybrid_lm.py), h ``[N, d]`` the normed input
    of the packed lanes, state ``[S, n, d_inner]`` float32, tail
    ``[S, W-1, d_inner]``, positions ``[S]`` (lane 0's), lengths ``[S]``, src
    ``[N]`` / back ``[S, K]`` the packing (``hybrid_lm.pack_lanes``) ->
    (y ``[N, d]``, new state, new tail)."""
    kk, n_state = back.shape[1], state.shape[1]
    fresh = positions == 0
    tail = jnp.where(fresh[:, None, None], 0.0, tail)
    uz = linear.matmul(h, p["w_in"])
    d_inner = uz.shape[1] // 2
    u, tail = packed_conv(
        uz[:, :d_inner], tail, p["conv"].astype(jnp.float32),
        p["conv_bias"], src % kk, back[:, 0], lengths)
    u = jax.nn.silu(u)
    low = linear.matmul(u, p["w_x"])
    dt = rms_norm(low[:, :dt_rank], p["dt_norm"], eps)
    b = rms_norm(low[:, dt_rank:dt_rank + n_state], p["b_norm"], eps)
    c = rms_norm(low[:, dt_rank + n_state:], p["c_norm"], eps)
    dt = jax.nn.softplus(linear.matmul(dt, p["w_dt"]) + p["dt_bias"])
    y, state = scan(u, dt, b, c, -jnp.exp(p["a_log"].astype(jnp.float32)),
                    state, lengths, fresh, src, back)
    # (the kernel leaves the places that repeat a lane unwritten)
    y = jnp.where(own_places(src, back)[:, None], y + p["d"] * u, 0.0)
    return linear.matmul(y * jax.nn.silu(uz[:, d_inner:]), p["w_out"]), \
        state, tail

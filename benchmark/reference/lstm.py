"""The reference's ``benchmark/paddle/rnn/rnn.py`` network, written from the
published ``lstmemory`` equations (Paddle's LstmLayer: peephole connections,
gate order [candidate, input, forget, output]):

    g_t = x_t W_in + h_{t-1} W_r + b
    a = tanh(g_a)
    i = sigmoid(g_i + c_{t-1} * p_i)      f = sigmoid(g_f + c_{t-1} * p_f)
    c_t = a * i + c_{t-1} * f
    o = sigmoid(g_o + c_t * p_o)          h_t = o * tanh(c_t)

then the last step's output (rnn.py's ``last_seq``; ``pooling`` "max" is
the maximum over time instead), a softmax layer and the mean cross-entropy.
Every sequence has full length (the cells pad nothing), so there is no
mask."""

import jax
import jax.numpy as jnp


def lstm_layer(x4, w_r, bias, p_i, p_f, p_o):
    """x4: [B, T, 4h] projected inputs -> h: [B, T, h]."""
    b, _t, h4 = x4.shape
    h = h4 // 4

    def step(carry, g_in):
        h_prev, c_prev = carry
        g = g_in + h_prev @ w_r + bias
        a, gi, gf, go = jnp.split(g, 4, axis=-1)
        i = jax.nn.sigmoid(gi + c_prev * p_i)
        f = jax.nn.sigmoid(gf + c_prev * p_f)
        c = jnp.tanh(a) * i + c_prev * f
        o = jax.nn.sigmoid(go + c * p_o)
        hh = o * jnp.tanh(c)
        return (hh, c), hh

    zero = jnp.zeros((b, h), jnp.float32)
    _, hs = jax.lax.scan(step, (zero, zero), x4.transpose(1, 0, 2))
    return hs.transpose(1, 0, 2)


def loss(p, tokens, labels, pooling="last"):
    """p: {"emb": [V, E], "lstm": [{"w_in", "w_r", "b7"}, ...], "w_out",
    "b_out"}; ``b7`` is Paddle's 7h bias: 4h gate bias then the three
    peepholes.  tokens [B, T] int32, labels [B] int32 -> scalar loss."""
    with jax.default_matmul_precision("highest"):
        x = p["emb"][tokens].astype(jnp.float32)
        for lyr in p["lstm"]:
            h = lyr["w_r"].shape[0]
            b7 = lyr["b7"]
            x = lstm_layer(x @ lyr["w_in"], lyr["w_r"], b7[:4 * h],
                           b7[4 * h:5 * h], b7[5 * h:6 * h], b7[6 * h:])
        pooled = x[:, -1] if pooling == "last" else x.max(axis=1)
        logits = pooled @ p["w_out"] + p["b_out"]
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, labels[:, None], axis=1)
        return -picked.mean()

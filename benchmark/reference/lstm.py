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
mask.  The products are float32 at ``highest``, or, with ``operands``, as a
configuration states a program computes them; ``loss_and_grad`` and
``adam_step`` let the reference follow a trainer's first steps."""

import jax
import jax.numpy as jnp


def _mm(x, w, operands):
    """x @ w: float32 at ``highest``, or, given a narrower ``operands``
    type, both sides rounded to it and the sum kept in float32."""
    if operands is None:
        return jnp.matmul(x, w, precision="highest")
    return jnp.matmul(x.astype(operands), w.astype(operands),
                      preferred_element_type=jnp.float32)


def lstm_layer(x4, w_r, bias, p_i, p_f, p_o, operands=None):
    """x4: [B, T, 4h] projected inputs -> h: [B, T, h]."""
    b, _t, h4 = x4.shape
    h = h4 // 4

    def step(carry, g_in):
        h_prev, c_prev = carry
        g = g_in + _mm(h_prev, w_r, operands) + bias
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


def loss_and_margins(p, tokens, labels, pooling="last", operands=None):
    """p: {"emb": [V, E], "lstm": [{"w_in", "w_r", "b7"}, ...], "w_out",
    "b_out"}; ``b7`` is Paddle's 7h bias: 4h gate bias then the three
    peepholes.  tokens [B, T] int32, labels [B] int32 -> (scalar loss,
    margins [B]).  A row's margin is its label's logit less the log-sum-exp
    of the other classes' (for two classes, the label's logit less the
    other's), so the row's loss is exactly softplus(-margin).

    ``operands``: None is the reference proper, float32 products at
    ``highest``.  A narrower type (bfloat16) gives the same network as the
    configuration STATES it is computed: both operands of every matrix
    product rounded to that type, float32 sums, float32 state.  The two
    together say what the stated precision alone does to each row of THIS
    batch, which is what a limit on the program's error has to follow."""
    x = p["emb"][tokens].astype(jnp.float32)
    for lyr in p["lstm"]:
        h = lyr["w_r"].shape[0]
        b7 = lyr["b7"]
        x = lstm_layer(_mm(x, lyr["w_in"], operands), lyr["w_r"], b7[:4 * h],
                       b7[4 * h:5 * h], b7[5 * h:6 * h], b7[6 * h:], operands)
    pooled = x[:, -1] if pooling == "last" else x.max(axis=1)
    logits = _mm(pooled, p["w_out"], operands) + p["b_out"]
    own = jnp.arange(logits.shape[-1])[None, :] == labels[:, None]
    picked = jnp.sum(jnp.where(own, logits, 0.0), axis=-1)
    others = jax.nn.logsumexp(jnp.where(own, -jnp.inf, logits), axis=-1)
    margins = picked - others
    return jax.nn.softplus(-margins).mean(), margins


def loss(p, tokens, labels, pooling="last", operands=None):
    """The scalar alone."""
    return loss_and_margins(p, tokens, labels, pooling, operands)[0]


def loss_and_grad(p, tokens, labels, pooling="last", blocks=1,
                  operands=None):
    """The loss and its gradient over the batch, ``blocks`` equal blocks of
    rows at a time (the mean of the blocks' means), so that the float32
    residuals of one block are what the device has to hold."""
    t = tokens.shape[-1]
    tb, lb = tokens.reshape(blocks, -1, t), labels.reshape(blocks, -1)

    def body(acc, xs):
        got = jax.value_and_grad(loss)(p, xs[0], xs[1], pooling, operands)
        return jax.tree_util.tree_map(jnp.add, acc, got), None

    zero = (jnp.zeros((), jnp.float32),
            jax.tree_util.tree_map(jnp.zeros_like, p))
    total, _ = jax.lax.scan(body, zero, (tb, lb))
    return jax.tree_util.tree_map(lambda x: x / blocks, total)


def adam_step(p, m, v, t, grads, opt):
    """Adam as published (Kingma & Ba, algorithm 1), after the reference's
    ``gradient_clipping_threshold``: each element of the gradient held to
    +-clip_threshold.  ``t`` counts from 1."""
    b1, b2 = opt.get("beta1", 0.9), opt.get("beta2", 0.999)
    eps, lr = opt.get("epsilon", 1e-8), opt["learning_rate"]
    clip = opt.get("clip_threshold")
    tm = jax.tree_util.tree_map
    if clip:
        grads = tm(lambda g: jnp.clip(g, -clip, clip), grads)
    m = tm(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = tm(lambda a, g: b2 * a + (1 - b2) * g * g, v, grads)
    p = tm(lambda w, a, b: w - lr * (a / (1 - b1 ** t))
           / (jnp.sqrt(b / (1 - b2 ** t)) + eps), p, m, v)
    return p, m, v


def leaves(p):
    """``p`` (parameters, a gradient or a moment in their form) as named
    leaves, Paddle's 7h bias split into the gates' bias and the peepholes:
    each is held by its own norm (at the published initial values the
    peepholes' gradient is all but nought, the bias's is not)."""
    out = {"emb": p["emb"], "w_out": p["w_out"], "b_out": p["b_out"]}
    for i, lyr in enumerate(p["lstm"]):
        h = lyr["w_r"].shape[0]
        out[f"lstm{i}.w_in"], out[f"lstm{i}.w_r"] = lyr["w_in"], lyr["w_r"]
        out[f"lstm{i}.bias"] = lyr["b7"][:4 * h]
        out[f"lstm{i}.peepholes"] = lyr["b7"][4 * h:]
    return out

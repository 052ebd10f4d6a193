"""Registry-driven padding-invariance sweep (SURVEY hard part c).

The reference NEVER pads: every sequence op walks
`Argument::sequenceStartPositions` (parameter/Argument.h:84-93), so its
results cannot depend on anything past a sequence's end.  The TPU rebuild
pads to static shapes and masks — meaning every sequence op must produce
IDENTICAL results when the same sequences are padded longer.  This module
enforces that property for EVERY sweep case with a sequence input, driven
off the same CASES registry as the gradient sweep (new layers get the check
for free).

Method: build the case feed at T, extend every SequenceBatch's data with
EXTRA garbage timesteps (nonzero, so any op that reads past lengths is
caught — zeros would hide mean/sum leaks), keep lengths unchanged, and
compare the scalar loss over all outputs.
"""

import zlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.core.sequence import SequenceBatch
from paddle_tpu.layers.graph import Topology, reset_names, value_data

from tests.test_layer_grad_sweep import CASES, B0, T0

# scan-heavy sweep (every sequence case re-built at two padded lengths);
# nightly lane — README "Running the tests"
pytestmark = pytest.mark.slow

EXTRA = 3          # appended timesteps
GARBAGE = 7.5      # pad payload: loud, not zero

# cases whose outputs legitimately depend on the padded length
EXCLUDED = {
    # none known — an entry here needs a comment citing the reference
    # semantics that make the op max_len-dependent
}


def _seq_cases():
    return sorted(n for n in CASES if n not in EXCLUDED)


def _extend(v):
    """SequenceBatch [B, T, ...] -> [B, T+EXTRA, ...] with garbage pad and
    unchanged lengths."""
    data = np.asarray(v.data)
    pad_shape = (data.shape[0], EXTRA) + data.shape[2:]
    if np.issubdtype(data.dtype, np.floating):
        pad = np.full(pad_shape, GARBAGE, data.dtype)
    else:
        pad = np.ones(pad_shape, data.dtype)   # in-vocab garbage ids
    return SequenceBatch(data=jnp.asarray(np.concatenate([data, pad], 1)),
                         lengths=v.lengths)


def _loss(topo, params, feed):
    out = topo.apply(params, feed, mode="test", rng=jax.random.PRNGKey(7))
    vals = out if isinstance(out, tuple) else (out,)
    total = 0.0
    for v in vals:
        d = value_data(v)
        total = total + jnp.sum(jnp.abs(d.astype(jnp.float32)))
    return total


@pytest.mark.parametrize("name", _seq_cases())
def test_padding_invariant(name):
    build, _ = CASES[name]
    reset_names()
    r = np.random.RandomState(zlib.crc32(name.encode()) % (2 ** 31))
    outs, feed = build(r, B0, T0)
    outs = outs if isinstance(outs, list) else [outs]
    if not any(isinstance(v, SequenceBatch) for v in feed.values()):
        pytest.skip("no sequence inputs")
    topo = Topology(outs)
    params = topo.init(jax.random.PRNGKey(0))

    base = float(_loss(topo, params, feed))
    wide = {k: _extend(v) if isinstance(v, SequenceBatch) else v
            for k, v in feed.items()}
    padded = float(_loss(topo, params, wide))
    np.testing.assert_allclose(
        padded, base, rtol=1e-5,
        err_msg=f"{name}: output depends on padding beyond lengths")

    # gradient side: d(loss)/d(param) must not see the padding either
    g_base = jax.grad(lambda p: _loss(topo, p, feed))(params)
    g_wide = jax.grad(lambda p: _loss(topo, p, wide))(params)
    for (path, ga), (_, gw) in zip(
            jax.tree_util.tree_flatten_with_path(g_base)[0],
            jax.tree_util.tree_flatten_with_path(g_wide)[0]):
        if np.issubdtype(np.asarray(ga).dtype, np.floating):
            np.testing.assert_allclose(
                np.asarray(gw), np.asarray(ga), rtol=1e-4, atol=1e-6,
                err_msg=f"{name}: param grad {jax.tree_util.keystr(path)} "
                        "depends on padding")


def test_padding_invariant_lstm_outputs_handed_over(monkeypatch):
    """The benchmark's two ``simple_lstm -> last_seq`` layers on the fused
    kernels (interpret mode here), their outputs handed over in the
    kernels' own form: layer 2 reads layer 1's UNMASKED time-major stream,
    whose padded steps hold the frozen carry, and ``last_seq`` the final
    carry.  Loss and gradients do not see the padding."""
    from benchmark.drivers import train as driver
    from paddle_tpu.ops import rnn
    monkeypatch.setattr(rnn, "FUSED_LSTM", "always")
    b, t, v, h = 8, 5, 40, 128
    topo = driver.build_trainer(
        {"vocab": v, "emb": h, "hidden": h, "lstm_layers": 2, "classes": 2,
         "pooling": "last", "optimizer": {"kind": "Adam",
                                          "learning_rate": 2e-3}},
        seed=3).topology
    params = topo.init(jax.random.PRNGKey(0))
    r = np.random.RandomState(45)
    feed = {"w": SequenceBatch(
                data=jnp.asarray(r.randint(0, v, (b, t)), jnp.int32),
                lengths=jnp.asarray([1, t, 2, 3, 0, 4, t, 1], jnp.int32)),
            "lab": jnp.asarray(r.randint(0, 2, (b,)), jnp.int32)}
    wide = dict(feed, w=_extend(feed["w"]))
    before = rnn.HANDED_OVER_COUNT
    base, g_base = jax.value_and_grad(
        lambda p: _loss(topo, p, feed))(params)
    assert rnn.HANDED_OVER_COUNT == before + 2
    padded, g_wide = jax.value_and_grad(
        lambda p: _loss(topo, p, wide))(params)
    np.testing.assert_allclose(float(padded), float(base), rtol=1e-5)
    for (path, ga), (_, gw) in zip(
            jax.tree_util.tree_flatten_with_path(g_base)[0],
            jax.tree_util.tree_flatten_with_path(g_wide)[0], strict=True):
        np.testing.assert_allclose(
            np.asarray(gw), np.asarray(ga), rtol=1e-4, atol=1e-6,
            err_msg=f"param grad {jax.tree_util.keystr(path)} depends on "
                    "padding")

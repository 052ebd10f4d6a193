"""The ``fc -> lstmemory`` pair that ``networks.simple_lstm`` builds is
applied as ONE layer (``graph.Topology`` hands the fc's input and weight to
``ops.rnn.lstm(proj=)``, so the fused forward kernel forms the gate inputs
in VMEM): same loss and gradients as with the fc materialised, the same
parameter tree, and any other shape of graph runs as it did."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import paddle_tpu.layers as L
from paddle_tpu.core.sequence import SequenceBatch
from paddle_tpu.layers import networks, recurrent
from paddle_tpu.layers.graph import Topology, reset_names
from paddle_tpu.ops import rnn

B, T, V, H = 8, 6, 40, 128
CFG = {"vocab": V, "emb": H, "hidden": H, "lstm_layers": 2, "classes": 2,
       "pooling": "last", "optimizer": {"kind": "Adam",
                                        "learning_rate": 2e-3}}


def _feed(np_rng):
    return {"w": SequenceBatch(
                data=jnp.asarray(np_rng.randint(0, V, (B, T)), jnp.int32),
                lengths=jnp.asarray(np_rng.randint(1, T + 1, (B,)),
                                    jnp.int32)),
            "lab": jnp.asarray(np_rng.randint(0, 2, (B,)), jnp.int32)}


def _mean_cost(topo, params, feed, **kw):
    return jnp.mean(topo.apply(params, feed, mode="train", **kw))


@pytest.fixture
def projections_handed(monkeypatch):
    """The ``proj`` of every ``rnn.lstm`` call a layer makes."""
    seen, real = [], rnn.lstm

    def spy(*a, proj=None, **kw):
        seen.append(proj)
        return real(*a, proj=proj, **kw)

    monkeypatch.setattr(recurrent.rnn_ops, "lstm", spy)
    return seen


@pytest.mark.parametrize("mode", ["always", "0"], ids=["kernel", "scan"])
def test_simple_lstm_pairs_train_as_with_the_fc_materialised(
        np_rng, monkeypatch, mode):
    """BASELINE.md's network as the benchmark's driver builds it, two
    ``simple_lstm`` layers: loss and every gradient with the pairs handed
    over against the same graph with each fc applied as a layer; on the
    kernel path both projections are the forward kernel's, on the scan
    path ``lstm`` forms the same product, bit for bit."""
    from benchmark.drivers import train as driver
    trainer = driver.build_trainer(CFG, seed=3)
    topo, params, feed = trainer.topology, trainer.parameters, _feed(np_rng)
    assert len(topo._lstm_projections) == 2
    monkeypatch.setattr(rnn, "FUSED_LSTM", mode)
    grad = jax.value_and_grad(lambda p: _mean_cost(topo, p, feed))
    before = rnn.FUSED_DISPATCH_COUNT, rnn.PROJECTED_DISPATCH_COUNT
    got = grad(params)
    on = 2 * (mode == "always")
    assert (rnn.FUSED_DISPATCH_COUNT, rnn.PROJECTED_DISPATCH_COUNT) \
        == (before[0] + on, before[1] + on)
    monkeypatch.setattr(topo, "_lstm_projections", {})
    want = grad(params)
    assert rnn.PROJECTED_DISPATCH_COUNT == before[1] + on
    flat = lambda t: jax.tree_util.tree_leaves_with_path(t)
    for (path, g), (_, w) in zip(flat(got), flat(want), strict=True):
        if mode == "0":
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                          err_msg=str(path))
        else:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-4, atol=2e-6,
                                       err_msg=str(path))


def test_the_parameter_tree_is_what_it_was():
    """Keys and shapes as the DSL numbers them: checkpoints hold them, and
    ``benchmark/drivers/train.py`` ``reference_params`` reads them by
    name."""
    from benchmark.drivers import train as driver
    tree = driver.build_trainer(CFG, seed=3).parameters
    shapes = {f"{k}/{n}": tuple(v.shape)
              for k, sub in tree.items() for n, v in sub.items()}
    assert shapes == {
        "__embedding_0__/w": (V, H),
        "__fc_0__/w0": (H, 4 * H), "__fc_1__/w0": (H, 4 * H),
        "__lstmemory_0__/w": (H, 4 * H), "__lstmemory_0__/b": (7 * H,),
        "__lstmemory_1__/w": (H, 4 * H), "__lstmemory_1__/b": (7 * H,),
        "__fc_2__/w0": (H, 2), "__fc_2__/b": (2,)}
    ref = driver.reference_params(tree, CFG)
    assert [sorted(lyr) for lyr in ref["lstm"]] == [["b7", "w_in", "w_r"]] * 2
    assert ref["lstm"][1]["w_in"] is tree["__fc_1__"]["w0"]


def _net(fc_kw=None, also=None):
    """words -> embedding -> fc (4H) -> lstmemory -> last_seq -> cost, the
    fc built with ``fc_kw``; ``also``: what else reads the fc."""
    reset_names()
    words = L.data_layer("w", size=V, is_seq=True)
    label = L.data_layer("lab", size=1)
    emb = L.embedding_layer(words, size=H)
    fc = L.fc_layer(emb, **{"size": 4 * H, "act": None, "bias_attr": False,
                            **(fc_kw or {})})
    lstm = L.lstmemory(fc, size=H)
    pooled = L.last_seq(lstm)
    if also == "second_reader":
        pooled = L.concat_layer([pooled, L.last_seq(fc)])
    probs = L.fc_layer(pooled, size=2, act="softmax")
    cost = L.classification_cost(probs, label)
    outputs = [cost, fc] if also == "an_output" else [cost]
    return Topology(outputs), fc


@pytest.mark.parametrize("what,fc_kw,also", [
    pytest.param(what, fc_kw, also, id=what) for what, fc_kw, also in [
        ("the_pair", None, None),
        ("bias", {"bias_attr": True}, None),
        ("activation", {"act": "tanh"}, None),
        ("dropout", {"layer_attr": {"drop_rate": 0.3}}, None),
        ("error_clipping",
         {"layer_attr": {"error_clipping_threshold": 1.0}}, None),
        ("second_reader", None, "second_reader"),
        ("an_output", None, "an_output"),
        ("extra_output", None, "extra_output"),
        ("precomputed", None, "precomputed")]])
def test_only_the_plain_pair_is_handed_over(np_rng, projections_handed,
                                            what, fc_kw, also):
    """An fc with a bias, an activation, dropout, error clipping, a second
    reader, or one whose value the call wants (an output, an extra output,
    a precomputed value) is applied as the layer it is; either way the
    graph's cost is the one the fc-as-a-layer graph gives, bit for bit (no
    kernel here: ``lstm`` forms the product the fc would have)."""
    topo, fc = _net(fc_kw, also)
    params, feed = topo.init(jax.random.PRNGKey(1)), _feed(np_rng)
    kw = {"rng": jax.random.PRNGKey(2)}
    if also == "extra_output":
        kw["extra_outputs"] = [fc]
    if also == "precomputed":
        kw["precomputed"] = {fc.name: SequenceBatch(
            data=jnp.asarray(np_rng.randn(B, T, 4 * H), jnp.float32),
            lengths=feed["w"].lengths)}
    got = topo.apply(params, feed, mode="train", **kw)
    handed = what == "the_pair"
    assert [p is not None for p in projections_handed] == [handed]
    if handed:
        assert projections_handed[0] is params[fc.name]["w0"]
    topo._lstm_projections = {}
    want = topo.apply(params, feed, mode="train", **kw)
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))

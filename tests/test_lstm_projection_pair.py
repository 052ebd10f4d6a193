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


# ------------------------------------- the LSTM's output in the kernel's form

def _lstm_nodes(topo):
    return [n for n in topo.order if n.layer_type == "lstmemory"]


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
@pytest.mark.parametrize("lengths", [[1, T, 3, 5, 2, T, 4, 1],
                                     [0, T, 3, 1, 5, 2, T, 4]],
                         ids=["ragged", "an_empty_row"])
def test_the_cells_network_hands_its_outputs_over_bit_for_bit(
        np_rng, monkeypatch, lengths, compute):
    """The benchmark's network on the kernels: layer 1's time-major stream
    is layer 2's input and layer 2's final carry is ``last_seq``'s row.
    Loss and every gradient leaf are those of the same graph with both
    ``lstmemory`` nodes asked for in ``extra_outputs`` (each then a masked
    batch-major sequence, as every reader had it before), bit for bit, at
    lengths from 1 to T and with a row of length 0, under both compute
    dtypes (bfloat16: layer 2's ``dx`` is rounded in its kernel)."""
    from benchmark.drivers import train as driver
    from paddle_tpu.core import dtypes
    monkeypatch.setattr(dtypes, "_compute_dtype", getattr(jnp, compute))
    monkeypatch.setattr(rnn, "FUSED_LSTM", "always")
    trainer = driver.build_trainer(CFG, seed=3)
    topo, params = trainer.topology, trainer.parameters
    feed = {"w": SequenceBatch(
                data=jnp.asarray(np_rng.randint(0, V, (B, T)), jnp.int32),
                lengths=jnp.asarray(lengths, jnp.int32)),
            "lab": jnp.asarray(np_rng.randint(0, 2, (B,)), jnp.int32)}

    def grad(extra):
        def cost(p):
            out = topo.apply(p, feed, mode="train", extra_outputs=extra)
            return jnp.mean(out[0] if extra else out)
        before = rnn.HANDED_OVER_COUNT
        got = jax.value_and_grad(cost)(params)
        return got, rnn.HANDED_OVER_COUNT - before

    got, handed = grad(())
    assert handed == 2
    want, handed = grad(_lstm_nodes(topo))
    assert handed == 0
    flat = lambda t: jax.tree_util.tree_leaves_with_path(t)
    for (path, g), (_, w) in zip(flat(got), flat(want), strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w),
                                      err_msg=jax.tree_util.keystr(path))


def _one_layer(what):
    """words -> embedding -> simple_lstm -> a pooling -> cost, as ``what``
    asks; returns (Topology, the lstmemory node)."""
    reset_names()
    words = L.data_layer("w", size=V, is_seq=True)
    label = L.data_layer("lab", size=1)
    emb = L.embedding_layer(words, size=H)
    if what == "bidirectional_lstm":
        seq = networks.bidirectional_lstm(emb, H, return_seq=True)
        pooled, lstm = L.last_seq(seq), None
    elif what == "bidirectional_last_and_first":
        pooled, lstm = networks.bidirectional_lstm(emb, H), None
    elif what == "prev_batch_state":
        lstm = L.lstmemory(L.fc_layer(emb, size=4 * H, act=None,
                                      bias_attr=False),
                           size=H, prev_batch_state=True)
        pooled = L.last_seq(lstm)
    else:
        lstm = networks.simple_lstm(emb, H, reverse=what == "reversed")
        pooled = {"max_pooling": lambda: L.pooling_layer(
                      lstm, pooling_type=L.pooling.Max),
                  "strided_last_seq": lambda: L.pooling_layer(
                      L.last_seq(lstm, stride=2), pooling_type=L.pooling.Max),
                  "two_readers": lambda: L.concat_layer(
                      [L.last_seq(lstm), L.first_seq(lstm)])}.get(
                          what, lambda: L.last_seq(lstm))()
    probs = L.fc_layer(pooled, size=2, act="softmax")
    return Topology(L.classification_cost(probs, label)), lstm


@pytest.mark.parametrize("what,want", [
    ("the_cells_network", 2), ("the_scan", 0), ("one_layer", 1),
    ("bidirectional_lstm", 0), ("reversed", 0), ("max_pooling", 0),
    ("strided_last_seq", 0), ("two_readers", 0), ("extra_output", 0),
    ("prev_batch_state", 0),
    # the forward half's one reader is a plain last_seq (the reversed
    # half's a first_seq): the rule hands the forward half over
    ("bidirectional_last_and_first", 1)])
def test_only_kernel_form_readers_get_the_kernels_form(
        np_rng, monkeypatch, what, want):
    """``rnn.HANDED_OVER_COUNT`` over one forward pass on the kernels: 2
    for the benchmark's network, 1 for one layer read by ``last_seq``, and
    0 for the scan, both layers of ``bidirectional_lstm`` returning the
    sequence, a reversed layer, any other pooling, a strided ``last_seq``,
    a second reader, a node the call asks for, a state carried to the next
    batch.  Each graph's cost is the same either way (the second pass
    hands nothing over)."""
    from benchmark.drivers import train as driver
    lstm, kw = None, {}
    if what in ("the_cells_network", "the_scan"):
        topo = driver.build_trainer(CFG, seed=3).topology
    else:
        topo, lstm = _one_layer(
            "one_layer" if what == "extra_output" else what)
    if what == "extra_output":
        kw = {"extra_outputs": [lstm]}
    monkeypatch.setattr(rnn, "FUSED_LSTM",
                        "0" if what == "the_scan" else "always")
    params, feed = topo.init(jax.random.PRNGKey(1)), _feed(np_rng)
    before = rnn.HANDED_OVER_COUNT
    got = topo.apply(params, feed, mode="test", **kw)
    assert rnn.HANDED_OVER_COUNT - before == want
    monkeypatch.setattr(topo, "_lstm_handoffs", {})
    want_cost = topo.apply(params, feed, mode="test", **kw)
    assert rnn.HANDED_OVER_COUNT - before == want
    np.testing.assert_allclose(
        np.asarray(jax.tree_util.tree_leaves(got)[0]),
        np.asarray(jax.tree_util.tree_leaves(want_cost)[0]),
        rtol=1e-6, atol=1e-7)

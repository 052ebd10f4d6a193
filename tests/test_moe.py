"""MoE FFN + expert parallelism (ops/moe.py, the 'expert' mesh axis)."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.ops import moe


def _params(d=8, f=16, e=4, seed=0):
    return moe.init_moe(jax.random.PRNGKey(seed), d, f, e)


def test_gates_topk_renormalized():
    p = _params()
    x = jnp.asarray(np.random.RandomState(0).randn(2, 5, 8), jnp.float32)
    probs = moe.router_probs(x, p["wg"])
    g = np.asarray(moe.moe_gates(probs, top_k=2))
    assert ((g > 0).sum(-1) == 2).all()
    np.testing.assert_allclose(g.sum(-1), 1.0, rtol=1e-5)
    # top_k >= E degrades to plain softmax
    g_all = np.asarray(moe.moe_gates(probs, top_k=4))
    assert (g_all > 0).all()


def test_gates_exactly_topk_on_ties():
    # uniform router: every prob tied — the index mask must STILL keep
    # exactly top_k experts
    probs = jnp.full((3, 7, 4), 0.25, jnp.float32)
    g = np.asarray(moe.moe_gates(probs, top_k=2))
    assert ((g > 0).sum(-1) == 2).all()
    np.testing.assert_allclose(g.sum(-1), 1.0, rtol=1e-5)


def test_moe_ffn_matches_per_expert_loop():
    """The batched-einsum formulation == explicit per-expert computation."""
    p = _params()
    x = jnp.asarray(np.random.RandomState(1).randn(2, 6, 8), jnp.float32)
    out = np.asarray(moe.moe_ffn(x, p, top_k=2))

    gates = np.asarray(moe.moe_gates(moe.router_probs(x, p["wg"]), top_k=2))
    ref = np.zeros_like(out)
    for e in range(4):
        h = jax.nn.gelu(x @ p["w1"][e])
        ye = np.asarray(h @ p["w2"][e])
        ref += ye * gates[..., e:e + 1]
    np.testing.assert_allclose(out, ref, rtol=2e-5, atol=2e-5)


def test_aux_loss_uniform_is_one():
    """Perfectly uniform router -> aux loss == 1 (its minimum), at any
    top_k now that tied probs keep exactly top_k experts."""
    d, e = 8, 4
    wg = jnp.zeros((d, e), jnp.float32)    # uniform probs everywhere
    x = jnp.asarray(np.random.RandomState(2).randn(2, 10, d), jnp.float32)
    probs = moe.router_probs(x, wg)
    for k in (1, 2, e):
        gates = moe.moe_gates(probs, k)
        val = float(moe.aux_load_balance_loss(probs, gates, k))
        assert val == pytest.approx(1.0, rel=1e-5), k


def test_expert_parallel_matches_single_device():
    devs = jax.devices()
    if len(devs) < 4:
        pytest.skip("needs the 8-device virtual CPU mesh")
    p = _params(e=4)
    x = jnp.asarray(np.random.RandomState(3).randn(4, 6, 8), jnp.float32)
    single = np.asarray(moe.moe_ffn(x, p, top_k=2))

    mesh = Mesh(np.asarray(devs[:4]).reshape(2, 2), ("data", "expert"))
    psh = moe.expert_shardings(mesh)
    xsh = NamedSharding(mesh, P("data", None, None))
    f = jax.jit(lambda p, x: moe.moe_ffn(x, p, top_k=2),
                in_shardings=(psh, xsh), out_shardings=xsh)
    with mesh:
        sharded = np.asarray(f(jax.device_put(p, psh),
                               jax.device_put(x, xsh)))
    np.testing.assert_allclose(single, sharded, rtol=2e-5, atol=2e-5)


def test_moe_trains():
    p = _params()
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(8, 6, 8), jnp.float32)
    y = jnp.asarray(rng.randn(8, 6, 8) * 0.1, jnp.float32)

    @jax.jit
    def step(p):
        def loss_fn(p):
            out, aux = moe.moe_ffn(x, p, top_k=2, return_aux=True)
            return jnp.mean((out - y) ** 2) + 0.01 * aux
        l, g = jax.value_and_grad(loss_fn)(p)
        return jax.tree_util.tree_map(lambda w, gw: w - 0.2 * gw, p, g), l

    losses = []
    for _ in range(40):
        p, l = step(p)
        losses.append(float(l))
    assert losses[-1] < 0.6 * losses[0]


def test_moe_layer_dsl():
    """moe_layer in the graph DSL: dense and sequence inputs, output size
    preserved, trains through the SGD trainer."""
    from paddle_tpu.layers import api as L
    from paddle_tpu.layers.graph import Topology
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu import optim
    from paddle_tpu.trainer.trainer import SGD

    x = L.data_layer("x", size=8)
    y = L.data_layer("y", size=1)
    m = L.moe_layer(x, n_experts=4, top_k=2, expert_dim=16, name="moe1")
    out = L.fc_layer(input=m, size=1, act="sigmoid")
    from paddle_tpu.layers.api import mse_cost
    tr = SGD(cost=mse_cost(input=out, label=y),
             update_equation=optim.Adam(learning_rate=0.01))
    assert set(tr.parameters["moe1"]) == {"wg", "w1", "w2"}
    rng = np.random.RandomState(0)

    def batch():
        xb = rng.randn(32, 8).astype(np.float32)
        yb = (xb[:, :3].sum(1, keepdims=True) > 0).astype(np.float32)
        return {"x": jnp.asarray(xb), "y": jnp.asarray(yb)}

    costs = []
    tr.train(lambda: iter([batch() for _ in range(25)]), num_passes=1,
             event_handler=lambda e: costs.append(float(e.cost))
             if hasattr(e, "cost") else None)
    assert costs[-1] < 0.6 * costs[0]

    # sequence input keeps lengths
    s = L.data_layer("s", size=8, is_seq=True)
    mseq = L.moe_layer(s, n_experts=2, top_k=1, expert_dim=8)
    topo = Topology([mseq])
    params = topo.init(jax.random.PRNGKey(0))
    sb = SequenceBatch(
        data=jnp.asarray(np.random.RandomState(1).randn(2, 5, 8),
                         jnp.float32),
        lengths=jnp.asarray([5, 3], jnp.int32))
    o = topo.apply(params, {"s": sb}, mode="test")
    assert o.data.shape == (2, 5, 8)
    assert (np.asarray(o.lengths) == [5, 3]).all()


def test_moe_layer_nested_and_multi_input():
    from paddle_tpu.layers import api as L
    from paddle_tpu.layers.graph import Topology
    from paddle_tpu.core.sequence import NestedSequenceBatch
    from paddle_tpu.utils.error import ConfigError

    # nested sequences flow through (4-d data flattened internally)
    s = L.data_layer("ns", size=8, is_seq=True)
    m = L.moe_layer(s, n_experts=2, top_k=1, expert_dim=8)
    topo = Topology([m])
    params = topo.init(jax.random.PRNGKey(0))
    nb = NestedSequenceBatch(
        data=jnp.asarray(np.random.RandomState(0).randn(2, 3, 4, 8),
                         jnp.float32),
        outer_lengths=jnp.asarray([3, 2], jnp.int32),
        inner_lengths=jnp.asarray([[4, 2, 1], [3, 4, 0]], jnp.int32))
    o = topo.apply(params, {"ns": nb}, mode="test")
    assert o.data.shape == (2, 3, 4, 8)

    # multi-input is a config error at construction time
    a = L.data_layer("a", size=8)
    b = L.data_layer("b", size=8)
    with pytest.raises(ConfigError, match="single input"):
        L.moe_layer([a, b], n_experts=2)


@pytest.mark.parametrize("places", [12, 16, 32])
def test_routed_experts_skip_the_places_that_repeat_a_lane(places):
    """The served layer over a packed axis (models/hybrid_lm.py): 12 live
    tokens and a tail that repeats the last one, marked not ``valid``.  The
    live tokens get what they get alone whatever the width, the repeats get
    nothing, and the grouped products see the live pairs only."""
    d, f, experts, k = 16, 8, 8, 2
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    layer = {"wg": jax.random.normal(ks[0], (4, d, f)) * d ** -0.5,
             "wu": jax.random.normal(ks[1], (4, d, f)) * d ** -0.5,
             "wd": jax.random.normal(ks[2], (4, f, d)) * f ** -0.5}
    router = jax.random.normal(ks[3], (d, experts))
    x = jax.random.normal(ks[4], (12, d))
    held = (2, 4)                   # experts 2..5 of 8 are held here

    def layer_over(x, valid):
        idx, w = moe.sigmoid_router(x, router, jnp.zeros((experts,)), k, 2.5)
        return moe.routed_experts(x, idx, w, layer, held, valid=valid), idx

    want, idx = layer_over(x, None)
    packed = jnp.concatenate([x, jnp.broadcast_to(x[-1], (places - 12, d))])
    got, _ = layer_over(packed, jnp.arange(places) < 12)
    np.testing.assert_allclose(got[:12], want, atol=1e-5)
    assert float(jnp.abs(got[12:]).max(initial=0.0)) == 0.0
    mine = (np.asarray(idx) >= 2) & (np.asarray(idx) < 6)
    assert 0 < mine.sum() < 12 * k and float(jnp.abs(want).max()) > 0

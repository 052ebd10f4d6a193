"""Fused Pallas LSTM vs the lax.scan reference path: forward and every
gradient must agree (the dual-implementation discipline the reference
applies to its fused CUDA LSTM in test_LayerGrad + test_RecurrentLayer).

Runs the kernel in interpret mode on the CPU mesh; the same code lowers to
Mosaic on a real chip (exercised by chip_smoke.py and the
``lstm-h512_train`` benchmark cell)."""

import collections

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.core.sequence import SequenceBatch
from paddle_tpu.ops import rnn

B, T, D = 8, 7, 128          # kernel needs B%8==0, D%128==0


def _mk(np_rng, ragged=True):
    x = jnp.asarray(np_rng.randn(B, T, 4 * D) * 0.3, jnp.float32)
    lengths = (np_rng.randint(1, T + 1, (B,)) if ragged
               else np.full((B,), T))
    seq = SequenceBatch(data=x, lengths=jnp.asarray(lengths, jnp.int32))
    w_r = jnp.asarray(np_rng.randn(D, 4 * D) * 0.1, jnp.float32)
    checks = [jnp.asarray(np_rng.randn(D) * 0.1, jnp.float32)
              for _ in range(3)]
    bias = jnp.asarray(np_rng.randn(4 * D) * 0.1, jnp.float32)
    return seq, w_r, checks, bias


def _run(seq, w_r, checks, bias, fused, use_final=False, peephole=True):
    prior = rnn.FUSED_LSTM
    rnn.FUSED_LSTM = "always" if fused else "0"
    try:
        ci, cf, co = checks if peephole else (None, None, None)
        out, final = rnn.lstm(seq, w_r, bias=bias,
                              check_i=ci, check_f=cf, check_o=co)
        if use_final:
            return jnp.sum(out.data ** 2) + jnp.sum(final.c ** 2) \
                + jnp.sum(final.h)
        return jnp.sum(out.data ** 2)
    finally:
        rnn.FUSED_LSTM = prior


@pytest.mark.parametrize("ragged", [False, True], ids=["full", "ragged"])
@pytest.mark.parametrize("peephole", [True, False], ids=["peep", "nopeep"])
def test_fused_matches_scan_forward(np_rng, ragged, peephole):
    seq, w_r, checks, bias = _mk(np_rng, ragged)
    a = _run(seq, w_r, checks, bias, fused=True, peephole=peephole)
    b = _run(seq, w_r, checks, bias, fused=False, peephole=peephole)
    np.testing.assert_allclose(float(a), float(b), rtol=2e-5)


@pytest.mark.parametrize("use_final", [False, True], ids=["hs", "hs+final"])
def test_fused_matches_scan_grads(np_rng, use_final):
    seq, w_r, checks, bias = _mk(np_rng, ragged=True)

    def loss(fused, xdata, w_r, checks, bias):
        s = SequenceBatch(data=xdata, lengths=seq.lengths)
        return _run(s, w_r, checks, bias, fused, use_final=use_final)

    args = (seq.data, w_r, checks, bias)
    ga = jax.grad(lambda *a: loss(True, *a), argnums=(0, 1, 2, 3))(*args)
    gb = jax.grad(lambda *a: loss(False, *a), argnums=(0, 1, 2, 3))(*args)
    labels = ["dx", "dw_r", "dchecks", "dbias"]
    for la, (a, b) in zip(labels, zip(jax.tree_util.tree_leaves(ga),
                                      jax.tree_util.tree_leaves(gb))):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5, err_msg=la)


def test_fused_zero_length_sequence(np_rng):
    seq, w_r, checks, bias = _mk(np_rng, ragged=True)
    seq = SequenceBatch(data=seq.data,
                        lengths=seq.lengths.at[0].set(0))
    a = _run(seq, w_r, checks, bias, fused=True)
    b = _run(seq, w_r, checks, bias, fused=False)
    np.testing.assert_allclose(float(a), float(b), rtol=2e-5)


def test_fused_reverse_matches_scan(np_rng):
    seq, w_r, checks, bias = _mk(np_rng, ragged=True)

    def loss(fused, xdata):
        s = SequenceBatch(data=xdata, lengths=seq.lengths)
        prior = rnn.FUSED_LSTM
        rnn.FUSED_LSTM = "always" if fused else "0"
        try:
            out, final = rnn.lstm(s, w_r, bias=bias, check_i=checks[0],
                                  check_f=checks[1], check_o=checks[2],
                                  reverse=True)
            return (jnp.sum(out.data ** 2) + jnp.sum(final.c ** 2)
                    + jnp.sum(final.h))
        finally:
            rnn.FUSED_LSTM = prior

    a, ga = jax.value_and_grad(lambda x: loss(True, x))(seq.data)
    b, gb = jax.value_and_grad(lambda x: loss(False, x))(seq.data)
    np.testing.assert_allclose(float(a), float(b), rtol=2e-5)
    np.testing.assert_allclose(np.asarray(ga), np.asarray(gb),
                               rtol=2e-4, atol=2e-5)


def test_vmem_guard_routes_oversized_to_scan(monkeypatch):
    """d=1280's w_r (26 MB f32) cannot be VMEM-resident on a ~16 MB core:
    supported() must say no BEFORE Mosaic discovers it the hard way, and
    the budget must be overridable for bigger chips."""
    from paddle_tpu.ops.pallas import lstm as pl
    monkeypatch.delenv("PADDLE_TPU_KERNEL_VMEM_MB", raising=False)
    assert pl.supported(64, 512, "tanh", "sigmoid", "tanh", None)
    assert not pl.supported(64, 1280, "tanh", "sigmoid", "tanh", None)
    monkeypatch.setenv("PADDLE_TPU_KERNEL_VMEM_MB", "128")
    assert pl.supported(64, 1280, "tanh", "sigmoid", "tanh", None)
    monkeypatch.setenv("PADDLE_TPU_KERNEL_VMEM_MB", "1")
    assert not pl.supported(64, 512, "tanh", "sigmoid", "tanh", None)


# ------------------------------------------------------------ batch tiles

TILED_B = 32            # tiles of 32, 16 and 8 rows: one, two, four


def _force_tile(monkeypatch, b, d, bt, d_in=None):
    """Make ``batch_tile(b, d, d_in)`` come out as ``bt`` the way a small
    core would: through the budget the guard already reads."""
    from paddle_tpu.ops.pallas import lstm as pl
    monkeypatch.setenv("PADDLE_TPU_KERNEL_VMEM_MB",
                       repr((pl.plan_bytes(bt, d, d_in) + 512) / 2 ** 20))
    assert pl.batch_tile(b, d, d_in) == bt


@pytest.fixture
def pallas_grids(monkeypatch):
    """The ``(name, grid)`` of every pallas_call traced in the test."""
    from jax.experimental import pallas
    seen, real = [], pallas.pallas_call

    def spy(*a, **kw):
        seen.append((kw.get("name"), tuple(kw["grid"])))
        return real(*a, **kw)

    monkeypatch.setattr(pallas, "pallas_call", spy)
    return seen


@pytest.mark.parametrize("tiles", [1, 2, 4])
@pytest.mark.parametrize("reverse", [False, True], ids=["fwd", "rev"])
@pytest.mark.parametrize("peephole", [True, False], ids=["peep", "nopeep"])
def test_tiled_matches_scan(np_rng, monkeypatch, pallas_grids, tiles,
                            reverse, peephole):
    """One, two and four batch tiles: outputs, final state and EVERY
    gradient equal the scan's on ragged rows, with a cotangent on the
    final cell (``dcfin`` enters each tile's chain at its own last step)
    and ``dW_r`` / ``dchecks`` summed across the tiles."""
    _force_tile(monkeypatch, TILED_B, D, TILED_B // tiles)
    x = jnp.asarray(np_rng.randn(TILED_B, T, 4 * D) * 0.3, jnp.float32)
    lengths = jnp.asarray(np_rng.randint(1, T + 1, (TILED_B,)), jnp.int32)
    _, w_r, checks, bias = _mk(np_rng)
    probe = jnp.asarray(np_rng.randn(TILED_B, T, D), jnp.float32)
    probe_c = jnp.asarray(np_rng.randn(TILED_B, D), jnp.float32)

    def loss(fused, x, w_r, checks, bias):
        prior = rnn.FUSED_LSTM
        rnn.FUSED_LSTM = "always" if fused else "0"
        try:
            ci, cf, co = checks if peephole else (None, None, None)
            out, final = rnn.lstm(SequenceBatch(data=x, lengths=lengths),
                                  w_r, bias=bias, check_i=ci, check_f=cf,
                                  check_o=co, reverse=reverse)
        finally:
            rnn.FUSED_LSTM = prior
        return (jnp.sum(out.data * probe) + jnp.sum(final.c * probe_c)
                + jnp.sum(final.h))

    args = (x, w_r, checks, bias)
    got = jax.value_and_grad(lambda *a: loss(True, *a),
                             argnums=(0, 1, 2, 3))(*args)
    assert pallas_grids == [("lstm_fwd", (tiles, T)),
                            ("lstm_bwd", (tiles, T))]
    want = jax.value_and_grad(lambda *a: loss(False, *a),
                              argnums=(0, 1, 2, 3))(*args)
    labels = ["loss", "dx", "dw_r", "dci", "dcf", "dco", "dbias"]
    for la, g, w in zip(labels, jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5, err_msg=la)


V5E_BUDGET_MB = "112"       # 7/8 of the v5e core's 128 MiB


@pytest.mark.parametrize("budget_mb,b,d,want", [
    (V5E_BUDGET_MB, 1024, 512, 1024),   # the benchmark's cell: one tile
    (V5E_BUDGET_MB, 4096, 512, 1024),
    (V5E_BUDGET_MB, 2048, 1024, 256),
    (V5E_BUDGET_MB, 64, 1280, 64),      # resident on a 128 MiB core
    ("24", 1024, 512, 128),
    ("14", 1024, 512, 16),              # a 16 MiB core: 13.2 MB at 16 rows
    ("14", 64, 1280, 0),                # ... cannot hold d=1280's weights
    ("14", 168, 512, 24),
    ("14", 1000, 512, 8),
    ("14", 1024, 128, 512),
], ids=lambda v: str(v))
def test_batch_tile_rule(monkeypatch, budget_mb, b, d, want):
    """The largest multiple of 8 that divides the batch and fits."""
    from paddle_tpu.ops.pallas import lstm as pl
    monkeypatch.setenv("PADDLE_TPU_KERNEL_VMEM_MB", budget_mb)
    bt = pl.batch_tile(b, d)
    assert bt == want
    if bt:
        assert b % bt == 0 and bt % 8 == 0
        assert pl.vmem_bytes(bt, d) <= float(budget_mb) * 2 ** 20
    assert pl.supported(b, d, "tanh", "sigmoid", "tanh", None) == (bt > 0)


@pytest.mark.parametrize("d", [128, 256, 384, 512, 640])
def test_batches_that_dispatched_before_are_one_tile_on_the_v5e(
        monkeypatch, d):
    """Every (b, d) the whole-batch guard admitted (its own estimate
    against 14 MiB) is ONE tile under the v5e's budget: the grid is
    (1, T), the program the kernel always was."""
    from paddle_tpu.ops.pallas import lstm as pl
    monkeypatch.setenv("PADDLE_TPU_KERNEL_VMEM_MB", V5E_BUDGET_MB)

    def admitted_before(b):
        return 4 * (8 * d * d + 3 * d + 18 * b * d + 128 * b) <= 14 * 2 ** 20

    bs = [b for b in range(8, 4096, 8) if admitted_before(b)]
    assert bs, "the old guard admitted no batch at this width"
    assert [pl.batch_tile(b, d) for b in bs] == bs


@pytest.mark.parametrize("b", [0, 4, 12, 100, 1001])
def test_no_tile_when_no_multiple_of_8_divides_the_batch(monkeypatch, b):
    from paddle_tpu.ops.pallas import lstm as pl
    monkeypatch.setenv("PADDLE_TPU_KERNEL_VMEM_MB", V5E_BUDGET_MB)
    assert pl.batch_tile(b, 128) == 0
    assert not pl.supported(b, 128, "tanh", "sigmoid", "tanh", None)


@pytest.mark.parametrize("what", ["activation", "init_state", "width"])
def test_shapes_declined_for_other_reasons_still_scan(monkeypatch, what):
    from paddle_tpu.ops.pallas import lstm as pl
    monkeypatch.setenv("PADDLE_TPU_KERNEL_VMEM_MB", V5E_BUDGET_MB)
    args = {"activation": (64, 128, "relu", "sigmoid", "tanh", None),
            "init_state": (64, 128, "tanh", "sigmoid", "tanh", object()),
            "width": (64, 192, "tanh", "sigmoid", "tanh", None)}[what]
    assert not pl.supported(*args)


def test_budget_follows_the_core_only_for_a_kernel_that_sets_its_limit(
        monkeypatch):
    """``vmem_budget_bytes()`` stays 14 MiB for the kernels that live
    under Mosaic's default scoped limit; the LSTM, which hands Mosaic its
    own limit, plans against 7/8 of the core's physical VMEM — the same
    14 MiB where the device is no TPU."""
    from jax.sharding import AbstractDevice, AbstractMesh, use_abstract_mesh
    from paddle_tpu.ops.pallas import common, lstm as pl
    monkeypatch.delenv("PADDLE_TPU_KERNEL_VMEM_MB", raising=False)
    mib = 2 ** 20
    assert common.vmem_budget_bytes() == 14 * mib
    assert common.vmem_budget_bytes(scoped_limit_raised=True) == 14 * mib
    assert pl.batch_tile(1024, 512) == 16
    # a chip-free compile names its chip the way JAX itself reads it
    v5e = AbstractMesh((), (), abstract_device=AbstractDevice(
        device_kind="TPU v5 lite", num_cores=1))
    with use_abstract_mesh(v5e):
        assert common.vmem_budget_bytes() == 14 * mib
        assert common.vmem_budget_bytes(scoped_limit_raised=True) == 112 * mib
        assert pl.batch_tile(1024, 512) == 1024
        assert pl.batch_tile(256, 1280) == 128
    assert pl.batch_tile(1024, 512) == 16


@pytest.mark.parametrize("bt,d,in_context_mib", [
    (1024, 512, 82.12), (1352, 512, 111.56), (256, 1024, 78.08),
    (64, 1280, 84.22), (128, 1280, 100.58)])
def test_limit_handed_to_mosaic_covers_its_own_count(bt, d, in_context_mib):
    """The smallest ``vmem_limit_bytes`` under which fwd+bwd through
    ``rnn.lstm`` compiled for the v5e (bisected chip-free, T=25, PR 26): at
    d=1280 it is OVER the plan, so the limit is the plan plus a sixteenth —
    inside the core's 128 MiB for any plan the budget admits."""
    from paddle_tpu.ops.pallas import common, lstm as pl
    mib = 2 ** 20
    plan = pl.vmem_bytes(bt, d)
    assert plan <= 112 * mib
    assert in_context_mib * mib * 1.02 <= common.vmem_limit_bytes(plan) \
        <= 119 * mib
    assert common.vmem_limit_bytes(pl.vmem_bytes(8, 128)) == 16 * mib


@pytest.mark.parametrize("projected,tiles", [(False, 1), (True, 1),
                                              (True, 2)],
                         ids=["gate_inputs", "projected", "projected-2tiles"])
def test_fused_kernel_takes_its_batch_shard_under_a_data_mesh(
        np_rng, monkeypatch, pallas_grids, projected, tiles):
    """GSPMD cannot partition a Mosaic kernel (on the chip a batch-sharded
    jit raises "Mosaic kernels cannot be automatically partitioned"), so
    under ``rnn.batch_sharded_over`` the kernels run per batch shard in a
    shard_map: forward and every gradient — the replicated weights'
    included, summed over the shards; with ``proj=`` W_x and the bias ride
    among them, their gradients formed by the backward kernel across the
    shard's tiles — equal the single-device scan."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel.mesh import AXIS_DATA, MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(data=2), devices=jax.devices()[:2])
    if tiles > 1:
        _force_tile(monkeypatch, tiles * B, D, B, 4 * D)
    x = jnp.asarray(np_rng.randn(2 * tiles * B, T, 4 * D) * 0.3, jnp.float32)
    lengths = jnp.asarray(np_rng.randint(1, T + 1, (2 * tiles * B,)),
                          jnp.int32)
    w_r = jnp.asarray(np_rng.randn(D, 4 * D) * 0.1, jnp.float32)
    checks = [jnp.asarray(np_rng.randn(D) * 0.1, jnp.float32)
              for _ in range(3)]
    own = {}
    if projected:
        own = {"proj": jnp.asarray(np_rng.randn(4 * D, 4 * D) * 0.05,
                                   jnp.float32),
               "bias": jnp.asarray(np_rng.randn(4 * D) * 0.1, jnp.float32)}

    def loss(x, w_r, checks, own):
        out, final = rnn.lstm(SequenceBatch(data=x, lengths=lengths), w_r,
                              check_i=checks[0], check_f=checks[1],
                              check_o=checks[2], **own)
        return jnp.sum(out.data ** 2) + jnp.sum(final.c ** 2)

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2, 3))
    prior = rnn.FUSED_LSTM
    try:
        rnn.FUSED_LSTM = "always"
        before = rnn.FUSED_DISPATCH_COUNT, rnn.PROJECTED_DISPATCH_COUNT

        def sharded(x, w_r, checks, own):
            with rnn.batch_sharded_over(mesh, AXIS_DATA):
                return grad(x, w_r, checks, own)

        whole = NamedSharding(mesh, P())
        got = jax.jit(sharded, in_shardings=(
            NamedSharding(mesh, P(AXIS_DATA)), whole, whole, whole))(
                x, w_r, checks, own)
        assert (rnn.FUSED_DISPATCH_COUNT, rnn.PROJECTED_DISPATCH_COUNT) \
            == (before[0] + 1, before[1] + projected)
        assert pallas_grids == [("lstm_fwd", (tiles, T)),
                                ("lstm_bwd", (tiles, T))]
        rnn.FUSED_LSTM = "0"
        want = jax.jit(grad)(x, w_r, checks, own)
    finally:
        rnn.FUSED_LSTM = prior
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)
    # the guard judges the PER-SHARD batch: 8 rows a shard is supported,
    # a batch that does not split evenly is not
    with rnn.batch_sharded_over(mesh, AXIS_DATA):
        assert rnn._local_batch(2 * B) == B
        assert rnn._local_batch(2 * B + 1) == 0
    assert rnn._local_batch(2 * B) == 2 * B


# ------------------------------------------- the forward's own projection

def _projected_case(np_rng, b, d_in, d):
    x = jnp.asarray(np_rng.randn(b, T, d_in) * 0.3, jnp.float32)
    lengths = jnp.asarray(np_rng.randint(1, T + 1, (b,)), jnp.int32)
    w_x = jnp.asarray(np_rng.randn(d_in, 4 * d) * 0.1, jnp.float32)
    w_r = jnp.asarray(np_rng.randn(d, 4 * d) * 0.1, jnp.float32)
    bias = jnp.asarray(np_rng.randn(4 * d) * 0.1, jnp.float32)
    checks = [jnp.asarray(np_rng.randn(d) * 0.1, jnp.float32)
              for _ in range(3)]
    return x, lengths, w_x, w_r, bias, checks


def _lstm_of_input(x, lengths, w_x, w_r, bias, checks, projected, mode,
                   **kw):
    """``rnn.lstm`` of the layer's INPUT: handed the projection
    (``proj=``), or the gate inputs formed outside as an fc layer would."""
    from paddle_tpu.ops.linear import matmul
    prior = rnn.FUSED_LSTM
    rnn.FUSED_LSTM = mode
    try:
        data = x if projected else matmul(x, w_x)
        return rnn.lstm(SequenceBatch(data=data, lengths=lengths), w_r,
                        bias=bias, check_i=checks[0], check_f=checks[1],
                        check_o=checks[2],
                        proj=w_x if projected else None, **kw)
    finally:
        rnn.FUSED_LSTM = prior


@pytest.mark.parametrize("tiles", [1, 2])
@pytest.mark.parametrize("reverse", [False, True], ids=["fwd", "rev"])
@pytest.mark.parametrize("d_in,d", [(128, 128), (128, 256)],
                         ids=["in==d", "in<d"])
def test_projected_kernel_matches_the_unprojected(
        np_rng, monkeypatch, pallas_grids, d_in, d, reverse, tiles):
    """The forward kernel that forms x_t W_x + b itself against the same
    kernel fed the gate inputs: ``hs``, the final (h, c) and the gradients
    of x, W_x, w_r, the gate bias and the three peepholes, on ragged rows
    (masked steps), both directions, one and two batch tiles."""
    b = 16
    _force_tile(monkeypatch, b, d, b // tiles, d_in)
    args = _projected_case(np_rng, b, d_in, d)
    lengths = args[1]
    probe = jnp.asarray(np_rng.randn(b, T, d), jnp.float32)
    probe_c = jnp.asarray(np_rng.randn(b, d), jnp.float32)

    def loss(projected, x, w_x, w_r, bias, checks):
        out, final = _lstm_of_input(x, lengths, w_x, w_r, bias, checks,
                                    projected, "always", reverse=reverse)
        return (jnp.sum(out.data * probe) + jnp.sum(final.c * probe_c)
                + jnp.sum(final.h)), (out.data, final.h, final.c)

    diff = (args[0],) + args[2:]
    before = (rnn.FUSED_DISPATCH_COUNT, rnn.PROJECTED_DISPATCH_COUNT)
    got = jax.value_and_grad(lambda *a: loss(True, *a), has_aux=True,
                             argnums=(0, 1, 2, 3, 4))(*diff)
    assert (rnn.FUSED_DISPATCH_COUNT, rnn.PROJECTED_DISPATCH_COUNT) \
        == (before[0] + 1, before[1] + 1)
    assert pallas_grids == [("lstm_fwd", (tiles, T)),
                            ("lstm_bwd", (tiles, T))]
    want = jax.value_and_grad(lambda *a: loss(False, *a), has_aux=True,
                              argnums=(0, 1, 2, 3, 4))(*diff)
    assert (rnn.FUSED_DISPATCH_COUNT, rnn.PROJECTED_DISPATCH_COUNT) \
        == (before[0] + 2, before[1] + 1)
    labels = ["loss", "hs", "h", "c", "dx", "dw_x", "dw_r", "dbias",
              "dci", "dcf", "dco"]
    for la, g, w in zip(labels, jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5, err_msg=la)


def _pallas_calls(jaxpr):
    """``(name, [operand avals], [output avals])`` of every pallas_call in
    a jaxpr."""
    from jax.extend.core import ClosedJaxpr, Jaxpr
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append((eqn.params["name"], [v.aval for v in eqn.invars],
                          list(eqn.params["out_avals"])))
            continue
        for p in eqn.params.values():
            for sub in p if isinstance(p, (list, tuple)) else [p]:
                if isinstance(sub, (ClosedJaxpr, Jaxpr)):
                    found += _pallas_calls(getattr(sub, "jaxpr", sub))
    return found


def _pallas_outputs(jaxpr):
    """``(name, [output shapes])`` of every pallas_call in a jaxpr."""
    return [(name, [a.shape for a in outs])
            for name, _, outs in _pallas_calls(jaxpr)]


@pytest.mark.parametrize("projected", [True, False],
                         ids=["projected", "gate_inputs"])
def test_backward_writes_the_gate_gradient_only_where_it_was_handed_them(
        np_rng, projected):
    """The projected ``lstm_bwd`` finishes the projection's gradients
    itself: its outputs are dx [T, B, in], dW_x [in, 4D] and the bias's
    sums [1, 4D] beside dW_r and the peephole rows, and NO [T, B, 4D]
    ``dgates``; handed gate inputs, it still emits their gradient."""
    d_in = 256                       # neither D nor 4D: shapes tell apart
    x, lengths, w_x, w_r, bias, checks = _projected_case(np_rng, B, d_in, D)

    def loss(x, w_x, w_r, bias):
        out, final = _lstm_of_input(x, lengths, w_x, w_r, bias, checks,
                                    projected, "always")
        return jnp.sum(out.data ** 2) + jnp.sum(final.c)

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2, 3)))(
        x, w_x, w_r, bias).jaxpr
    calls = _pallas_outputs(jaxpr)
    assert [name for name, _ in calls] == ["lstm_fwd", "lstm_bwd"]
    outputs = calls[1][1]
    assert ((T, B, 4 * D) in outputs) == (not projected)
    if projected:
        assert outputs == [(T, B, d_in), (d_in, 4 * D), (1, 4 * D),
                           (D, 4 * D), (B, 3 * D)]


@pytest.mark.parametrize("tiles", [2, 4])
def test_projection_gradients_sum_over_every_tile(
        np_rng, monkeypatch, pallas_grids, tiles):
    """dW_x and the bias's gradient live in ONE accumulator across all
    batch tiles and time steps: on ragged rows (masked steps add nothing)
    they equal x^T dgates and the sum of dgates, where dgates is the
    gradient of the gate inputs the unprojected path reports."""
    from paddle_tpu.ops.linear import matmul
    b, d_in = 32, 128
    _force_tile(monkeypatch, b, D, b // tiles, d_in)
    x, lengths, w_x, w_r, bias, checks = _projected_case(np_rng, b, d_in, D)
    probe = jnp.asarray(np_rng.randn(b, T, D), jnp.float32)

    def loss(data, w_x, bias, projected):
        out, final = rnn.lstm(SequenceBatch(data=data, lengths=lengths), w_r,
                              bias=bias, check_i=checks[0],
                              check_f=checks[1], check_o=checks[2],
                              proj=w_x if projected else None)
        return jnp.sum(out.data * probe) + jnp.sum(final.c)

    prior = rnn.FUSED_LSTM
    rnn.FUSED_LSTM = "always"
    try:
        dw_x, db = jax.grad(loss, argnums=(1, 2))(x, w_x, bias, True)
        assert pallas_grids == [("lstm_fwd", (tiles, T)),
                                ("lstm_bwd", (tiles, T))]
        dgates = jax.grad(loss)(matmul(x, w_x), None, bias, False)
    finally:
        rnn.FUSED_LSTM = prior
    mask = np.arange(T)[None, :] < np.asarray(lengths)[:, None]
    assert not np.asarray(dgates)[~mask].any()
    np.testing.assert_allclose(
        np.asarray(dw_x), np.einsum("bti,btg->ig", np.asarray(x),
                                    np.asarray(dgates)),
        rtol=2e-4, atol=2e-5, err_msg="dw_x")
    np.testing.assert_allclose(np.asarray(db),
                               np.asarray(dgates).sum(axis=(0, 1)),
                               rtol=2e-4, atol=2e-5, err_msg="dbias")


def test_projection_gradients_keep_the_compute_dtypes_rounding(
        np_rng, monkeypatch):
    """Under a bfloat16 compute dtype the kernel's dx and dW_x are what
    autodiff of ``linear.matmul`` gives the step — bfloat16 operands,
    float32 sums, each product rounded to bfloat16 as the cotangent of an
    operand cast to it — so every value is a bfloat16 one and they equal
    the gate inputs' path to within the order of the sums; the bias's sum
    stays float32."""
    from paddle_tpu.core import dtypes
    monkeypatch.setattr(dtypes, "_compute_dtype", jnp.bfloat16)
    x, lengths, w_x, w_r, bias, checks = _projected_case(np_rng, B, D, D)

    def grads(projected):
        def loss(x, w_x, bias):
            out, final = _lstm_of_input(x, lengths, w_x, w_r, bias, checks,
                                        projected, "always")
            return jnp.sum(out.data ** 2) + jnp.sum(final.c)
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(x, w_x, bias)

    got, want = grads(True), grads(False)
    # dx leaves the kernel in x's own dtype, already rounded
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda x: jnp.sum(_lstm_of_input(x, lengths, w_x, w_r, bias, checks,
                                         True, "always")[1].c)))(x).jaxpr
    (_, _, bwd_outputs), = [c for c in _pallas_calls(jaxpr)
                            if c[0] == "lstm_bwd"]
    assert bwd_outputs[0].dtype == x.dtype == jnp.float32
    for label, g, w in zip(["dx", "dw_x", "dbias"], got, want, strict=True):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == np.float32, label
        if label != "dbias":
            rounded = np.asarray(jnp.asarray(g).astype(jnp.bfloat16)
                                 .astype(jnp.float32))
            np.testing.assert_array_equal(g, rounded, err_msg=label)
        np.testing.assert_allclose(g, w, rtol=0,
                                   atol=2 ** -7 * np.abs(w).max(),
                                   err_msg=label)


def test_in_kernel_rounding_is_the_bfloat16_conversion(np_rng):
    """The projected backward rounds dx to bfloat16 in the integers; that
    is the conversion's round-to-nearest-even bit for bit, ties, the
    largest finite values and infinities included, and a NaN of any
    payload stays a NaN (the integer carry must not make it 0 or inf)."""
    from paddle_tpu.ops.pallas.lstm import _rounded_as
    edges = np.array([0, 0x80000000, 0x00008000, 0x00018000, 0x3F808000,
                      0x3F818000, 0x7F7FFFFF, 0x7F7F8000, 0x7F800000,
                      0xFF800000, 0x00000001, 0x7F800001, 0x7FC00000,
                      0x7FFFFFFF, 0xFFFFFFFF, 0xFF800001], np.uint32)
    bits = np.concatenate([edges, np_rng.randint(0, 2 ** 32, 20000,
                                                 dtype=np.uint64)
                           .astype(np.uint32)])
    v = jnp.asarray(bits.view(np.float32))
    got = np.asarray(_rounded_as(v, jnp.bfloat16))
    want = np.asarray(v.astype(jnp.bfloat16).astype(jnp.float32))
    nan = np.isnan(want)
    np.testing.assert_array_equal(np.isnan(got), nan)
    np.testing.assert_array_equal(got[~nan].view(np.uint32),
                                  want[~nan].view(np.uint32))


@pytest.mark.parametrize("why", ["scan", "init_state", "activation",
                                 "w_x_over_vmem", "dw_x_over_vmem",
                                 "blocked", "no_bias"])
def test_projection_is_the_kernels_only_where_it_can_be(
        np_rng, monkeypatch, why):
    """``lstm(proj=)`` is ONE entry: where the fused kernels cannot take
    the projection (the scan, a carried state, another activation, a W_x
    that does not fit beside w_r in the forward, or its gradient's
    accumulator and streams beside dW_r in the backward, the gate-blocked
    kernel of a w_r that does not fit at all) ``lstm`` forms the gate
    inputs itself,
    bit for bit what the fc layer outside would have handed it, and
    ``PROJECTED_DISPATCH_COUNT`` stays; ``FUSED_DISPATCH_COUNT`` moves
    whenever a kernel ran at all."""
    from paddle_tpu.ops.pallas import lstm as pl
    d_in = {"w_x_over_vmem": 2048, "dw_x_over_vmem": 1024}.get(why, D)
    x, lengths, w_x, w_r, bias, checks = _projected_case(np_rng, B, d_in, D)
    kw, mode, fused, projected = {}, "always", 1, 0
    if why == "scan":
        mode, fused = "0", 0
    elif why == "init_state":
        zero = jnp.zeros((B, D), jnp.float32)
        kw, fused = {"init_state": rnn.LstmState(h=zero + 0.1, c=zero)}, 0
    elif why == "activation":
        kw, fused = {"act": "relu"}, 0
    elif why == "w_x_over_vmem":
        # the backward's plan fits, the forward with W_x resident does not
        monkeypatch.setenv("PADDLE_TPU_KERNEL_VMEM_MB", "2")
        assert pl.vmem_bytes(B, D) < 2 * 2 ** 20 \
            < pl.fwd_vmem_bytes(B, D, d_in)
    elif why == "dw_x_over_vmem":
        # both forwards fit, and so does the backward handed gate inputs:
        # the one that also forms dx and dW_x does not
        monkeypatch.setenv("PADDLE_TPU_KERNEL_VMEM_MB", "4")
        assert max(pl.vmem_bytes(B, D), pl.fwd_vmem_bytes(B, D, d_in)) \
            < 4 * 2 ** 20 < pl.bwd_vmem_bytes(B, D, d_in)
    elif why == "blocked":
        from paddle_tpu.ops.pallas import lstm_blocked as blk
        monkeypatch.setenv("PADDLE_TPU_KERNEL_VMEM_MB",
                           repr(1.2 * blk.vmem_bytes(B, D) / 2 ** 20))
        assert not pl.supported(B, D, "tanh", "sigmoid", "tanh", None)
        assert blk.supported(B, D, "tanh", "sigmoid", "tanh", None)
    else:
        bias, projected = None, 1

    def run(handed):
        before = (rnn.FUSED_DISPATCH_COUNT, rnn.PROJECTED_DISPATCH_COUNT)
        out, final = _lstm_of_input(x, lengths, w_x, w_r, bias, checks,
                                    handed, mode, **kw)
        moved = (rnn.FUSED_DISPATCH_COUNT - before[0],
                 rnn.PROJECTED_DISPATCH_COUNT - before[1])
        return moved, (out.data, final.h, final.c)

    moved, got = run(True)
    assert moved == (fused, projected)
    moved, want = run(False)
    assert moved == (fused, 0)
    for g, w in zip(got, want, strict=True):
        if projected:
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       rtol=2e-5, atol=2e-6)
        else:
            np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("bt,d,d_in,mosaic_mib", [
    (1024, 512, 512, 42.34), (1024, 512, 128, 44.07), (512, 512, 512, 23.15),
    (1024, 128, 128, 11.71), (1024, 256, 256, 25.12), (1024, 384, 384, 34.59),
    (1024, 640, 640, 53.29), (1024, 128, 2048, 29.05),
    (1024, 128, 4096, 48.99), (512, 256, 4096, 29.30)])
def test_projected_forward_is_planned_with_w_x_resident(
        monkeypatch, bt, d, d_in, mosaic_mib):
    """``fwd_vmem_bytes`` against the smallest ``vmem_limit_bytes`` under
    which the projected forward (residuals saved, bfloat16 W_x) compiled
    for the v5e (bisected chip-free, T=4, PR 36): the limit handed to
    Mosaic, the larger of the two passes' plans plus a sixteenth, covers
    it."""
    from paddle_tpu.core import dtypes
    from paddle_tpu.ops.pallas import common, lstm as pl
    monkeypatch.setattr(dtypes, "_compute_dtype", jnp.bfloat16)
    mib = 2 ** 20
    assert mosaic_mib * mib <= common.vmem_limit_bytes(
        pl.plan_bytes(bt, d, d_in))
    assert pl.fwd_vmem_bytes(bt, d, d_in) >= 0.99 * mosaic_mib * mib


@pytest.mark.parametrize("bt,d,d_in,mosaic_mib", [
    (1024, 512, 512, 81.75), (1024, 512, 128, 78.01), (512, 512, 512, 46.51),
    (1024, 128, 128, 14.92), (1024, 256, 256, 37.51), (1024, 384, 384, 58.44),
    (1024, 640, 640, 114.66), (1024, 128, 2048, 52.78),
    (1024, 128, 4096, 95.79), (512, 256, 4096, 78.01),
    (256, 1024, 1024, 115.5), (8, 1536, 128, 112.79)])
def test_projected_backward_is_planned_with_dw_x_resident(
        monkeypatch, bt, d, d_in, mosaic_mib):
    """``bwd_vmem_bytes`` against the smallest ``vmem_limit_bytes`` under
    which the projected backward (bfloat16 W_x and dW_x out, dx in its
    float32 input's dtype, the forward at its own limit) compiled for the
    v5e inside the gradient of ``sum(hs) + sum(c_final)`` through
    ``lstm_fused(proj=)`` (bisected chip-free to 0.05 MiB, T=100: at T=4
    XLA holds whole operands in VMEM and Mosaic counts less; the kernel
    with float32 dx, docs/kernels.md): the plan is over it, the limit
    handed over covers it, and the benchmark's batch is ONE tile of both
    of its layers at the v5e's budget."""
    from paddle_tpu.core import dtypes
    from paddle_tpu.ops.pallas import common, lstm as pl
    monkeypatch.setattr(dtypes, "_compute_dtype", jnp.bfloat16)
    mib = 2 ** 20
    assert pl.bwd_vmem_bytes(bt, d, d_in) >= mosaic_mib * mib
    assert mosaic_mib * mib <= common.vmem_limit_bytes(
        pl.plan_bytes(bt, d, d_in))
    monkeypatch.setenv("PADDLE_TPU_KERNEL_VMEM_MB", V5E_BUDGET_MB)
    for layer_in in (128, 512):            # lstm-h512_train's two layers
        assert pl.batch_tile(1024, 512, layer_in) == 1024
        assert pl.supported(1024, 512, "tanh", "sigmoid", "tanh", None,
                            d_in=layer_in)
    # at d=640 the plan (118.7 MiB at 1,024 rows) halves a tile that
    # Mosaic's count (98.4) would take whole: the plan errs to the safe side
    assert pl.batch_tile(1024, 640, 640) == 512


# ------------------------------------------ the output in the kernel's form

@pytest.mark.parametrize("tiles", [1, 2])
@pytest.mark.parametrize("projected", [False, True],
                         ids=["gate_inputs", "projected"])
def test_final_carry_entry_matches_scan(np_rng, monkeypatch, pallas_grids,
                                        projected, tiles):
    """``lstm(hand_over="final")`` on the kernels is the entry whose
    outputs are the final (h, c) alone: their values and the gradients of
    x, W_x, w_r, the bias and the peepholes against the scan's, on ragged
    rows with one of length 0 (its carry is 0 either way), one and two
    batch tiles.  The backward is handed no [T, B, D] cotangent: its
    operands of that shape are the residuals c_t, c_{t-1} and h_{t-1}
    alone, and the final h's cotangent enters as a [1, B, D] block."""
    b, d_in = 16, 256                # d_in neither D nor 4D
    _force_tile(monkeypatch, b, D, b // tiles, d_in if projected else None)
    x, lengths, w_x, w_r, bias, checks = _projected_case(np_rng, b, d_in, D)
    lengths = lengths.at[0].set(0).at[1].set(T).at[2].set(1)
    probe_h = jnp.asarray(np_rng.randn(b, D), jnp.float32)
    probe_c = jnp.asarray(np_rng.randn(b, D), jnp.float32)

    def loss(mode, x, w_x, w_r, bias, checks):
        out, final = _lstm_of_input(x, lengths, w_x, w_r, bias, checks,
                                    projected, mode, hand_over="final")
        assert isinstance(out, rnn.LstmOutput) == (mode == "always")
        return (jnp.sum(final.h * probe_h) + jnp.sum(final.c * probe_c),
                (final.h, final.c))

    args = (x, w_x, w_r, bias, checks)
    grad = lambda mode: jax.value_and_grad(
        lambda *a: loss(mode, *a), has_aux=True, argnums=(0, 1, 2, 3, 4))
    before = rnn.HANDED_OVER_COUNT
    got = grad("always")(*args)
    assert rnn.HANDED_OVER_COUNT == before + 1
    assert pallas_grids == [("lstm_fwd", (tiles, T)),
                            ("lstm_bwd", (tiles, T))]
    want = grad("0")(*args)
    assert rnn.HANDED_OVER_COUNT == before + 1
    labels = ["loss", "h", "c", "dx", "dw_x", "dw_r", "dbias",
              "dci", "dcf", "dco"]
    for la, g, w in zip(labels, jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=2e-4, atol=2e-5, err_msg=la)
    assert not np.asarray(got[0][1][0])[0].any()     # the empty row's h

    def bwd_operands(hand_over):
        def loss(x):
            out, final = _lstm_of_input(x, lengths, w_x, w_r, bias, checks,
                                        projected, "always",
                                        hand_over=hand_over)
            every_h = 0.0 if hand_over else jnp.sum(out.data)
            return every_h + jnp.sum(final.h) + jnp.sum(final.c)
        jaxpr = jax.make_jaxpr(jax.grad(loss))(x).jaxpr
        (_, operands, _), = [c for c in _pallas_calls(jaxpr)
                             if c[0] == "lstm_bwd"]
        return collections.Counter(a.shape for a in operands)

    streamed, final = bwd_operands(None), bwd_operands("final")
    assert streamed - final == {(T, b, D): 1}        # dh_out
    assert final - streamed == {(1, b, D): 1}        # dh_final


@pytest.mark.parametrize("why", ["forward", "reverse", "scan"])
def test_the_stream_is_the_sequence_in_the_kernels_form(np_rng, why):
    """``lstm(hand_over="stream")``: on the kernels, forward, an
    ``LstmOutput`` whose ``sequence()`` is the masked batch-major output,
    bit for bit, and whose row at ``lengths - 1`` of the stream is the
    final h (a masked step holds the carry); reversed or on the scan the
    output is that sequence itself, and nothing is counted."""
    seq, w_r, checks, bias = _mk(np_rng, ragged=True)
    kw = dict(bias=bias, check_i=checks[0], check_f=checks[1],
              check_o=checks[2], reverse=why == "reverse")
    prior = rnn.FUSED_LSTM
    rnn.FUSED_LSTM = "0" if why == "scan" else "always"
    try:
        before = rnn.HANDED_OVER_COUNT
        out, final = rnn.lstm(seq, w_r, hand_over="stream", **kw)
        handed = rnn.HANDED_OVER_COUNT - before
        want, want_final = rnn.lstm(seq, w_r, **kw)
    finally:
        rnn.FUSED_LSTM = prior
    assert handed == (why == "forward")
    assert isinstance(out, rnn.LstmOutput) == (why == "forward")
    if why == "forward":
        np.testing.assert_array_equal(np.asarray(out.final.h),
                                      np.asarray(final.h))
        last = np.asarray(out.hs)[np.maximum(np.asarray(seq.lengths) - 1, 0),
                                  np.arange(B)]
        np.testing.assert_array_equal(last, np.asarray(final.h))
        out = out.sequence()
    np.testing.assert_array_equal(np.asarray(out.data), np.asarray(want.data))
    for g, w in zip(final, want_final, strict=True):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))


def test_handed_over_stack_under_a_data_mesh(np_rng):
    """Two projected layers as the benchmark's network stacks them, each
    kernel on its batch shard (``batch_sharded_over``): layer 1 hands its
    time-major stream to layer 2's kernels, layer 2 its final carry alone.
    The loss of the final h and every gradient equal the single-device
    scan of the same layers."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel.mesh import AXIS_DATA, MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(data=2), devices=jax.devices()[:2])
    b = 2 * B
    x, lengths, w_x, w_r, bias, checks = _projected_case(np_rng, b, D, D)
    w_x2, w_r2 = w_x[:, ::-1] * 0.9, w_r[::-1] * 1.1
    probe = jnp.asarray(np_rng.randn(b, D), jnp.float32)

    def loss(x, w_x, w_r, w_x2, w_r2):
        layer = dict(bias=bias, check_i=checks[0], check_f=checks[1],
                     check_o=checks[2])
        out, _ = rnn.lstm(SequenceBatch(data=x, lengths=lengths), w_r,
                          proj=w_x, hand_over="stream", **layer)
        _, final = rnn.lstm(out, w_r2, proj=w_x2, hand_over="final", **layer)
        return jnp.sum(final.h * probe)

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))
    args = (x, w_x, w_r, w_x2, w_r2)
    prior = rnn.FUSED_LSTM
    try:
        rnn.FUSED_LSTM = "always"
        before = rnn.HANDED_OVER_COUNT

        def sharded(*a):
            with rnn.batch_sharded_over(mesh, AXIS_DATA):
                return grad(*a)

        whole = NamedSharding(mesh, P())
        got = jax.jit(sharded, in_shardings=(
            NamedSharding(mesh, P(AXIS_DATA)),) + (whole,) * 4)(*args)
        assert rnn.HANDED_OVER_COUNT == before + 2
        rnn.FUSED_LSTM = "0"
        want = jax.jit(grad)(*args)
    finally:
        rnn.FUSED_LSTM = prior
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want), strict=True):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)

"""Fused Pallas LSTM vs the lax.scan reference path: forward and every
gradient must agree (the dual-implementation discipline the reference
applies to its fused CUDA LSTM in test_LayerGrad + test_RecurrentLayer).

Runs the kernel in interpret mode on the CPU mesh; the same code lowers to
Mosaic on a real chip (exercised by bench.py and the TPU differential
sweep)."""

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


def test_fused_kernel_takes_its_batch_shard_under_a_data_mesh(np_rng):
    """GSPMD cannot partition a Mosaic kernel (on the chip a batch-sharded
    jit raises "Mosaic kernels cannot be automatically partitioned"), so
    under ``rnn.batch_sharded_over`` the kernels run per batch shard in a
    shard_map: forward and every gradient — the replicated weights'
    included, summed over the shards — equal the single-device scan."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel.mesh import AXIS_DATA, MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(data=2), devices=jax.devices()[:2])
    x = jnp.asarray(np_rng.randn(2 * B, T, 4 * D) * 0.3, jnp.float32)
    lengths = jnp.asarray(np_rng.randint(1, T + 1, (2 * B,)), jnp.int32)
    w_r = jnp.asarray(np_rng.randn(D, 4 * D) * 0.1, jnp.float32)
    checks = [jnp.asarray(np_rng.randn(D) * 0.1, jnp.float32)
              for _ in range(3)]

    def loss(x, w_r, checks):
        out, final = rnn.lstm(SequenceBatch(data=x, lengths=lengths), w_r,
                              check_i=checks[0], check_f=checks[1],
                              check_o=checks[2])
        return jnp.sum(out.data ** 2) + jnp.sum(final.c ** 2)

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2))
    prior = rnn.FUSED_LSTM
    try:
        rnn.FUSED_LSTM = "always"
        before = rnn.FUSED_DISPATCH_COUNT

        def sharded(x, w_r, checks):
            with rnn.batch_sharded_over(mesh, AXIS_DATA):
                return grad(x, w_r, checks)

        got = jax.jit(sharded, in_shardings=(
            NamedSharding(mesh, P(AXIS_DATA)), NamedSharding(mesh, P()),
            NamedSharding(mesh, P())))(x, w_r, checks)
        assert rnn.FUSED_DISPATCH_COUNT == before + 1
        rnn.FUSED_LSTM = "0"
        want = jax.jit(grad)(x, w_r, checks)
    finally:
        rnn.FUSED_LSTM = prior
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   rtol=1e-4, atol=1e-4)
    # the guard judges the PER-SHARD batch: 8 rows a shard is supported,
    # a batch that does not split evenly is not
    with rnn.batch_sharded_over(mesh, AXIS_DATA):
        assert rnn._local_batch(2 * B) == B
        assert rnn._local_batch(2 * B + 1) == 0
    assert rnn._local_batch(2 * B) == 2 * B

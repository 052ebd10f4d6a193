"""``ops/moe.routed_experts`` over its aligned row layout (each held
expert's group starts on a whole ``ROW_ALIGN`` rows): against the same
products over the unpadded sorted rows and a float32 einsum over every
expert, for skewed, empty and padded groups."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.core import dtypes
from paddle_tpu.ops import moe

# tokens N, chosen k, experts E, held (first, C), D, F; and what the case is
CASES = {
    # tiny twins of the four expert cells' (D, F, held share, k)
    "laguna": (24, 10, 64, (8, 8), 384, 128, "routed"),
    "kimi": (16, 8, 32, (0, 8), 288, 128, "routed"),
    "pangu": (24, 8, 64, (16, 4), 480, 128, "routed"),
    "keye": (24, 8, 32, (4, 4), 256, 96, "routed"),
    # a held expert that no pair chose
    "empty_expert": (16, 4, 16, (0, 8), 128, 128, "skip_one"),
    # every pair to one expert: 48 x 4 rows in one group
    "one_expert_takes_all": (48, 4, 16, (0, 4), 128, 128, "all_to_one"),
    # the tail of the lanes is padding, routed nowhere
    "padding_lanes": (20, 4, 16, (0, 8), 128, 128, "padded"),
    # every pair to experts held elsewhere: nothing to compute
    "held_elsewhere": (12, 4, 16, (8, 8), 128, 128, "elsewhere"),
    # N * k = 39 pairs: not a whole number of ROW_ALIGN rows
    "odd_pairs": (13, 3, 16, (4, 8), 128, 128, "routed"),
}


@pytest.fixture
def serving_policy():
    """Operands in bfloat16 with float32 sums, as the served configurations
    state; the auto policy back afterwards."""
    dtypes.set_policy("float32", "bfloat16")
    try:
        yield
    finally:
        dtypes.set_policy("float32", None)


def _layer(case):
    n, k, e, held, d, f, kind = CASES[case]
    first, count = held
    ks = jax.random.split(jax.random.PRNGKey(len(case)), 6)
    params = {
        "wg": (jax.random.normal(ks[0], (count, d, f)) * d ** -0.5
               ).astype(jnp.bfloat16),
        "wu": (jax.random.normal(ks[1], (count, d, f)) * d ** -0.5
               ).astype(jnp.bfloat16),
        "wd": (jax.random.normal(ks[2], (count, f, d)) * f ** -0.5
               ).astype(jnp.bfloat16)}
    x = jax.random.normal(ks[3], (n, d))
    idx = jnp.argsort(jax.random.uniform(ks[4], (n, e)), axis=1)[:, :k]
    if kind == "skip_one":
        idx = jnp.where(idx == first + 1, first + count, idx)
    elif kind == "all_to_one":
        idx = jnp.full((n, k), first)
    elif kind == "elsewhere":
        idx = idx % first
    weights = jax.random.uniform(ks[5], (n, k))
    valid = jnp.arange(n) < n - 6 if kind == "padded" else None
    return x, idx.astype(jnp.int32), weights, params, held, valid


def _float32_reference(x, idx, weights, params, held, valid):
    """Every held expert on every token in float32 at ``highest``, each
    token's sum over the pairs that chose a held expert."""
    first, count = held
    hp = jax.lax.Precision.HIGHEST
    w = {k: v.astype(jnp.float32) for k, v in params.items()}
    xb = x.astype(jnp.bfloat16).astype(jnp.float32)
    gate = jnp.einsum("nd,cdf->ncf", xb, w["wg"], precision=hp)
    up = jnp.einsum("nd,cdf->ncf", xb, w["wu"], precision=hp)
    y = jnp.einsum("ncf,cfd->ncd", jax.nn.silu(gate) * up, w["wd"],
                   precision=hp)
    pick = jax.nn.one_hot(idx - first, count) \
        * ((idx >= first) & (idx < first + count))[..., None]
    if valid is not None:
        pick = pick * valid[:, None, None]
    return jnp.einsum("nkc,ncd,nk->nd", pick, y, weights, precision=hp)


def _unaligned(x, idx, weights, params, held, valid):
    """The layer over the sorted rows with no padding between groups: the
    same three grouped products at the same roundings."""
    first, count = held
    n, k = idx.shape
    local = idx - first
    mine = (local >= 0) & (local < count)
    if valid is not None:
        mine &= valid[:, None]
    key = jnp.where(mine, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True)
    sizes = jnp.bincount(key, length=count + 1)[:count].astype(jnp.int32)
    dot = lambda a, w: jax.lax.ragged_dot(
        a.astype(jnp.bfloat16), w, sizes, preferred_element_type=jnp.float32)
    rows = x.astype(jnp.bfloat16)[order // k]
    y = dot(jax.nn.silu(dot(rows, params["wg"])) * dot(rows, params["wu"]),
            params["wd"])
    y = jnp.where((jnp.arange(n * k) < sizes.sum())[:, None],
                  y * weights.reshape(-1)[order][:, None], 0.0)
    back = jnp.zeros((n * k,), jnp.int32).at[order].set(
        jnp.arange(n * k, dtype=jnp.int32))
    return y[back].reshape(n, k, -1).sum(1)


@pytest.mark.parametrize("case", sorted(CASES))
def test_aligned_layout_matches_unaligned_and_float32(case, serving_policy):
    """``routed_experts`` jitted as the served step runs it, against the
    same products over unpadded groups (row by row the same sums) and
    against float32 (bfloat16 operands: a few units of 2^-9 of the
    result's scale); padding lanes and pairs held elsewhere give 0."""
    args = _layer(case)
    x, idx, weights, params, held, valid = args
    got = jax.jit(lambda *a: moe.routed_experts(*a, held, valid))(
        x, idx, weights, params)
    want = _float32_reference(*args)
    scale = float(jnp.abs(want).max())
    if CASES[case][-1] == "elsewhere":
        assert scale == 0.0 and float(jnp.abs(got).max()) == 0.0
        return
    np.testing.assert_allclose(got, _unaligned(*args), atol=2 ** -12 * scale)
    np.testing.assert_allclose(got, want, atol=4 * 2 ** -9 * scale)
    if valid is not None:
        assert float(jnp.abs(got[~valid]).max()) == 0.0


def test_products_run_over_whole_row_tiles():
    """The three grouped products take rows of the aligned layout: a whole
    number of ``ROW_ALIGN`` rows, at most ``N k + (ROW_ALIGN - 1) min(C,
    N k)`` rounded up, so every group can start on a whole tile."""
    x, idx, weights, params, held, valid = _layer("laguna")
    n, k = idx.shape
    jaxpr = jax.make_jaxpr(lambda *a: moe.routed_experts(*a, held, valid))(
        x, idx, weights, params)
    dots = [e for e in jaxpr.jaxpr.eqns
            if "ragged_dot" in e.primitive.name]
    assert len(dots) == 3
    bound = n * k + (moe.ROW_ALIGN - 1) * min(held[1], n * k)
    for eqn in dots:
        rows = eqn.invars[0].aval.shape[0]
        assert rows % moe.ROW_ALIGN == 0 and bound <= rows \
            < bound + moe.ROW_ALIGN

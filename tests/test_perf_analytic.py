"""Analytic perf layer (paddle_tpu/perf): roofline math and cost/HLO
extraction.  Chip-independent counts: every assertion here runs on the
CPU backend.  (The structure gates of perf/analytic.py are tested where
they are used: test_pallas_decode.py, test_quant.py, test_flash_quant.py,
test_chunked_prefill.py.)
"""

import jax
import jax.numpy as jnp
import pytest

from paddle_tpu.perf import cost, roofline

_S = jax.ShapeDtypeStruct


# ------------------------------------------------------------- roofline

def test_roofline_compute_bound():
    # exactly one second of v5e MXU work, negligible bytes
    r = roofline.predict(197e12, 1.0, "v5e")
    assert r["bottleneck"] == "compute"
    assert r["predicted_ms"] == pytest.approx(1000.0)
    assert r["predicted_mfu"] == pytest.approx(1.0)


def test_roofline_memory_bound():
    # exactly one second of v5e HBM traffic, negligible FLOPs
    r = roofline.predict(1.0, 819e9, "v5e")
    assert r["bottleneck"] == "memory"
    assert r["predicted_ms"] == pytest.approx(1000.0)
    assert r["predicted_mfu"] == pytest.approx(0.0, abs=1e-9)


def test_roofline_mixed_known_numbers():
    # 1 ms of compute vs 2 ms of memory -> memory-bound at 50% MFU
    flops = 197e12 * 1e-3
    nbytes = 819e9 * 2e-3
    r = roofline.predict(flops, nbytes, "v5e")
    assert r["predicted_ms"] == pytest.approx(2.0)
    assert r["predicted_mfu"] == pytest.approx(0.5)
    assert r["compute_ms"] == pytest.approx(1.0)
    assert r["memory_ms"] == pytest.approx(2.0)
    assert r["arithmetic_intensity"] == pytest.approx(flops / nbytes)


def test_roofline_ridge_point():
    spec = roofline.SPECS["v5e"]
    assert spec.ridge_intensity == pytest.approx(197e12 / 819e9)
    # at exactly the ridge intensity both ceilings agree
    r = roofline.predict(spec.peak_flops, spec.hbm_bytes_per_s, spec)
    assert r["compute_ms"] == pytest.approx(r["memory_ms"])
    assert r["predicted_mfu"] == pytest.approx(1.0)


def test_roofline_rejects_negative():
    with pytest.raises(ValueError):
        roofline.predict(-1.0, 10.0, "v5e")


def test_unknown_device_kind_is_an_error():
    """The MFU denominator comes from the one peaks table; a device that is
    not in it raises instead of returning peak=None or a guessed row."""
    assert roofline.for_device_kind("TPU v5 lite") is roofline.SPECS["v5e"]
    assert roofline.for_device_kind("cpu") is roofline.SPECS["cpu"]
    with pytest.raises(KeyError, match="TPU v99"):
        roofline.for_device_kind("TPU v99")


# ------------------------------------------------------ cost extraction

def test_op_histogram_parses_tuple_types_and_skips_bookkeeping():
    hlo = "\n".join([
        "ENTRY %main (p0: f32[2,2]) -> f32[] {",
        "  %p0 = f32[2,2]{1,0} parameter(0)",
        "  %c = f32[] constant(0)",
        "  %t = (f32[2]{0}, s32[]) while(%p0), condition=%cond, body=%b",
        "  ROOT %d = f32[2,2]{1,0} dot(%p0, %p0), lhs_contracting_dims={1}",
        "}",
    ])
    hist = cost.op_histogram(hlo)
    assert hist == {"dot": 1, "while": 1}   # parameter/constant skipped


def test_extract_on_compiled_step():
    def f(x, w):
        return jnp.tanh(x @ w).sum()

    c = jax.jit(f).lower(_S((64, 128), jnp.float32),
                         _S((128, 256), jnp.float32)).compile()
    row = cost.extract(c)
    # 2*M*K*N matmul MACs dominate XLA's flop count
    assert row["flops"] >= 2 * 64 * 128 * 256
    assert row["bytes_accessed"] > 0
    assert row["dot_count"] == 1
    assert row["arithmetic_intensity"] == pytest.approx(
        row["flops"] / row["bytes_accessed"])
    assert row["hlo_op_total"] == sum(row["hlo_op_histogram"].values())

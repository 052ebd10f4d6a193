"""The kernel smoke cases run (interpret mode) on CPU.

The same CASES dict is what chip_smoke.py runs through a real Mosaic
compile at the serving widths; this test keeps the harness itself honest
(oracle wiring, fresh-trace dispatch, tolerances, the pallas_call
recorder) so an on-chip failure can only mean a lowering/numerics problem.
"""

import pytest

from paddle_tpu.testing import kernel_smoke


@pytest.mark.parametrize("name", sorted(kernel_smoke.CASES))
def test_kernel_smoke_case(name):
    res = kernel_smoke.run_case(name)
    assert res["ok"] and "declined" not in res
    assert res["max_err"] <= res["tol"]
    # CPU: every kernel ran, and ran interpreted
    assert res["pallas_calls"] >= 1
    assert res["interpreted"] == res["pallas_calls"]


def test_expect_compiled_rejects_interpret_mode():
    """``expect_compiled`` is what stops an interpreted run from passing
    as a Mosaic compile."""
    with pytest.raises(AssertionError, match="interpreted"):
        kernel_smoke.run_case("decode_attention_slab", expect_compiled=True)


def test_serving_width_declines_carry_the_guards_reason():
    """int8 K/V at the CLI's default pool block (16) is declined by the
    kernel's own guard on the compiled backend — with its sentence."""
    from paddle_tpu.ops.pallas import decode_attention as dk
    w = kernel_smoke.SERVING
    reason = dk._tile_problem(w.block_size, w.kv_heads * w.head_dim,
                              w.head_dim, interpret=False, quant=True)
    assert "multiple of 32" in reason
    # the slab kernel picks its tile from the VMEM budget: at Dkv=2048 the
    # flag's 512 cap alone would be the chip's whole scoped VMEM
    blk = dk._pick_block_k(w.slab_len, 512, False, dkv=2048)
    assert blk == 256
    assert dk._tile_problem(512, 2048, 128, interpret=False) is not None

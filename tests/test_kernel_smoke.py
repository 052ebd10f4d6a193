"""The kernel smoke cases run (interpret mode) on CPU.

The same CASES dict is what chip_smoke.py runs through a real Mosaic
compile at the serving widths; this test keeps the harness itself honest
(oracle wiring, fresh-trace dispatch, tolerances, the pallas_call
recorder) so an on-chip failure can only mean a lowering/numerics problem.
"""

import jax.numpy as jnp
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


# the laguna_repoctx cell's two calls (kernel_smoke.ONE_LANE_PANELS) cut to
# the interpreter, each panel's layout kept
TINY_LAGUNA = {"laguna_full": (12, 2, 128, 8, 8, jnp.bfloat16, None),
               "laguna_window": (18, 2, 128, 8, 8, jnp.bfloat16, 16)}


@pytest.mark.parametrize("cell", ["opt1.3b_chat", *sorted(TINY_LAGUNA)])
def test_paged_chunk_timer_runs_every_setting(cell):
    """``time_paged_chunk_cell`` (the tiled kernel alone, timed on the
    chip) runs every setting of the chat cell's call and, given a panel
    and ``laguna_settings``, of Laguna's full and window calls, here at a
    shape cut to the interpreter: a timer nobody runs rots.  The numbers
    are the CPU's and mean nothing."""
    if cell == "opt1.3b_chat":
        got = kernel_smoke.time_paged_chunk_cell(kernel_smoke.SMALL,
                                                 calls=1, reps=1)
        want = {"cell_mix", "decode_at_0", "prefill_at_7"}
    else:
        got = kernel_smoke.time_paged_chunk_cell(
            None, calls=1, reps=1, panel=TINY_LAGUNA[cell], slots=8,
            entries=16, settings=kernel_smoke.laguna_settings(
                slots=8, chunk=8, span=128, contexts=(5, 60)))
        want = {"decode_at_5", "decode_at_60", "cell_mix"}
    assert want <= set(got) and min(got.values()) > 0

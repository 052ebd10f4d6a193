"""Fused whole-sequence LSTM (Pallas) — the TPU-native answer to the
reference's fused CUDA LSTM kernels (cuda/src/hl_cuda_lstm.cu +
hl_lstm_ops.cuh:46-66, dispatched from LstmCompute).

Why a kernel when lax.scan works: XLA's scan round-trips the carry (h, c)
and the per-step gate tensor through HBM every timestep and re-fetches the
recurrent weights.  Here the grid IS the time loop (TPU grids execute
sequentially per core, the same property the flash-attention kernel uses):
w_r and the peephole vectors stay resident in VMEM across all T steps,
h/c live in VMEM scratch, and each step streams only its [bt, 4D] gate
input in and its [bt, D] output out.

The forward forms its own gate inputs where the caller hands it the layer's
INPUT and the projection's weight (``lstm_fused(..., proj=W_x, bias=b)``):
W_x [in, 4D] sits in VMEM beside w_r, each step streams x_t [bt, in] and
computes (x_t W_x + b) + h_{t-1} W_r, so the [T, B, 4D] pre-activations
are neither written to HBM by a projection nor read back here (4D values a
token become ``in``).  The backward then finishes the projection's
gradients where it forms ``dgates``: each reversed step streams x_t in and
dx_t = dgates_t W_x^T out, and accumulates dW_x += x_t^T dgates_t and the
bias's row sums in VMEM across every step and tile, so ``dgates`` leaves
the kernel in no form.  Given gate inputs, it emits ``dgates`` as their
gradient instead.  One plan decides both passes: the projection is the
kernels' in both or in neither (``supported(..., d_in=)``).

The batch is tiled: grid = (B // bt, T), time innermost, both axes
sequential.  Rows of a batch never interact in the recurrence, so a tile
is a whole LSTM over bt rows; w_r and the peepholes keep a constant block
index and stay resident across every tile, the h/c carry restarts at the
first step of each tile, and the backward's dW_r accumulator lives across
ALL tiles (one [D, 4D] output, no per-tile partials in HBM).  bt is
``batch_tile(B, D)``: the whole batch when it fits the VMEM budget (one
tile), otherwise the largest multiple of 8 dividing B that does.

Semantics match ops.rnn.lstm exactly (reference gate order
[a, in_gate, forget_gate, out_gate], peepholes on i/f from c_prev and on o
from c_new, masked steps freeze the carry): tests/test_pallas_lstm.py
proves forward+grad equality against the scan path.

Backward is a second time-reversed kernel (BPTT): recomputes nothing,
reads the forward-saved activations, accumulates dW_r (and dW_x, db) in
VMEM f32 accumulators and streams the input's gradient out.  Per-batch
peephole partials are reduced outside the kernel.

A second entry (``lstm_fused(..., final_only=True)``) has the final carry
(h, c) as its only outputs, for a reader that takes the last valid step
alone: the residuals are the same, and its backward takes no [T, B, D]
cotangent: the final h's enters the chain at the last step as the final
c's does, and masked steps carry both to each row's last valid step.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.core import dtypes
from paddle_tpu.ops.pallas.common import (
    LANES as _LANES, lanes as _lanes, vmem_budget_bytes, vmem_limit_bytes)


def _fwd_kernel(x_ref, wx_ref, b_ref, wr_ref, chk_ref, mask_ref,
                hs_ref, cfin_ref, cs_ref, acts_ref, h_scr, c_scr,
                *, d, nt, save_residuals):
    """cs_ref/acts_ref are None in the lean (inference) variant — the
    residual tensors are ~5x the HBM traffic of the h output, so
    forward-only calls must not pay for them.  wx_ref/b_ref are None where
    x_ref already holds the gate inputs [bt, 4D]."""
    t = pl.program_id(1)          # axis 0 walks the batch tiles

    @pl.when(t == 0)
    def _():
        h_scr[:] = jnp.zeros_like(h_scr)
        c_scr[:] = jnp.zeros_like(c_scr)

    h, c = h_scr[:], c_scr[:]
    if wx_ref is None:
        x4 = x_ref[0].astype(jnp.float32)
    else:
        # the input projection as linear.matmul computes it (operands in
        # the compute dtype, which W_x arrives in; float32 sums), then the
        # bias, then the recurrent product: the order of the sums outside
        x4 = jax.lax.dot_general(
            x_ref[0].astype(wx_ref.dtype), wx_ref[:],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32) + b_ref[:]
    wr = wr_ref[:].astype(jnp.float32)
    gates = x4 + jax.lax.dot_general(
        h, wr, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    a, ig, fg, og = (gates[:, 0:d], gates[:, d:2 * d],
                     gates[:, 2 * d:3 * d], gates[:, 3 * d:4 * d])
    ci, cf, co = chk_ref[0:1], chk_ref[1:2], chk_ref[2:3]   # [1, D]
    a = jnp.tanh(a)
    i = jax.nn.sigmoid(ig + c * ci)
    f = jax.nn.sigmoid(fg + c * cf)
    c_new = a * i + c * f
    o = jax.nn.sigmoid(og + c_new * co)
    h_new = o * jnp.tanh(c_new)

    m = _lanes(mask_ref[0], d)                               # [B, D] 0/1
    h = m * h_new + (1.0 - m) * h
    c = m * c_new + (1.0 - m) * c
    h_scr[:], c_scr[:] = h, c

    hs_ref[0] = h.astype(hs_ref.dtype)
    if save_residuals:
        cs_ref[0] = c.astype(cs_ref.dtype)
        acts_ref[0, :, 0:d] = a
        acts_ref[0, :, d:2 * d] = i
        acts_ref[0, :, 2 * d:3 * d] = f
        acts_ref[0, :, 3 * d:4 * d] = o

    @pl.when(t == nt - 1)
    def _():
        cfin_ref[0] = c_scr[:].astype(cfin_ref.dtype)


def _bwd_kernel(acts_ref, cs_ref, csp_ref, hsp_ref, x_ref, wx_ref, wr_ref,
                chk_ref, mask_ref, dh_out_ref, dhfin_ref, dcfin_ref,
                dx_ref, dwx_ref, db_ref, dwr_ref, dchk_ref,
                dh_scr, dc_scr, dwr_scr, dchk_scr, dwx_scr, db_scr, *, d, nt):
    """x_ref/wx_ref, dwx_ref/db_ref and their scratch are None where the
    forward was handed gate inputs: dx_ref then takes ``dgates`` itself.
    One of dh_out_ref (every step's h an output) and dhfin_ref (the final
    carry's h alone) is None."""
    ib = pl.program_id(0)         # batch tile
    j = pl.program_id(1)          # reversed: actual time t = nt - 1 - j
    t = nt - 1 - j
    projected = wx_ref is not None

    @pl.when(j == 0)
    def _():
        # the final carry's cotangents enter the chain at the last (first-
        # reversed) step, exactly where the scan's carry cotangent starts;
        # masked steps then carry them to each row's last valid step
        dh_scr[:] = (jnp.zeros_like(dh_scr) if dhfin_ref is None
                     else dhfin_ref[0].astype(jnp.float32))
        dc_scr[:] = dcfin_ref[0].astype(jnp.float32)
        dchk_scr[:] = jnp.zeros_like(dchk_scr)

    # the weights' gradients sum over every row of the batch: one
    # accumulator each for all tiles
    @pl.when((ib == 0) & (j == 0))
    def _():
        dwr_scr[:] = jnp.zeros_like(dwr_scr)
        if projected:
            dwx_scr[:] = jnp.zeros_like(dwx_scr)
            db_scr[:] = jnp.zeros_like(db_scr)

    a = acts_ref[0, :, 0:d]
    i = acts_ref[0, :, d:2 * d]
    f = acts_ref[0, :, 2 * d:3 * d]
    o = acts_ref[0, :, 3 * d:4 * d]
    c_t = cs_ref[0].astype(jnp.float32)
    zero_prev = (t == 0)
    c_prev = jnp.where(zero_prev, 0.0, csp_ref[0].astype(jnp.float32))
    h_prev = jnp.where(zero_prev, 0.0, hsp_ref[0].astype(jnp.float32))
    ci, cf, co = chk_ref[0:1], chk_ref[1:2], chk_ref[2:3]
    m = _lanes(mask_ref[0], d)

    dh = dh_scr[:]
    if dh_out_ref is not None:
        dh = dh + dh_out_ref[0].astype(jnp.float32)
    dc_merged = dc_scr[:]
    tc = jnp.tanh(c_t)
    do_ = dh * tc
    dog = do_ * o * (1.0 - o)
    dc = dh * o * (1.0 - tc * tc) + dc_merged + dog * co
    da = dc * i
    di = dc * a
    dag = da * (1.0 - a * a)
    dig = di * i * (1.0 - i)
    dfg = dc * c_prev * f * (1.0 - f)
    # masked step: carry passes through untouched
    dgates = (jnp.concatenate([dag, dig, dfg, dog], axis=1)
              * _lanes(mask_ref[0], 4 * d))
    wr = wr_ref[:].astype(jnp.float32)
    dh_prev = jax.lax.dot_general(
        dgates, wr, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    dc_prev = dc * f + dig * ci + dfg * cf

    # pass-through on masked steps carries the MERGED cotangents (the cell
    # terms in dc only exist on active steps)
    dh_scr[:] = m * dh_prev + (1.0 - m) * dh
    dc_scr[:] = m * dc_prev + (1.0 - m) * dc_merged
    dwr_scr[:] = dwr_scr[:] + jax.lax.dot_general(
        h_prev, dgates, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    dchk_scr[:, 0:d] = dchk_scr[:, 0:d] + m * dig * c_prev
    dchk_scr[:, d:2 * d] = dchk_scr[:, d:2 * d] + m * dfg * c_prev
    dchk_scr[:, 2 * d:3 * d] = dchk_scr[:, 2 * d:3 * d] + m * dog * c_t

    if projected:
        # the projection's gradients as autodiff of linear.fc forms them:
        # operands in the compute dtype W_x arrives in, float32 sums, each
        # product rounded to that dtype, for it is the cotangent of an
        # operand cast to it; dx then leaves in the input's own dtype (the
        # cast's transpose), so the layer below reads it as it is
        cd = wx_ref.dtype
        dg = dgates.astype(cd)
        dx_ref[0] = _rounded_as(jax.lax.dot_general(
            dg, wx_ref[:], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), cd).astype(dx_ref.dtype)
        dwx_scr[:] = dwx_scr[:] + jax.lax.dot_general(
            x_ref[0].astype(cd), dg, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        db_scr[:] = db_scr[:] + jnp.sum(dgates, axis=0, keepdims=True)
    else:
        dx_ref[0] = dgates.astype(dx_ref.dtype)

    @pl.when(j == nt - 1)
    def _():
        dchk_ref[:] = dchk_scr[:]

    @pl.when((ib == pl.num_programs(0) - 1) & (j == nt - 1))
    def _():
        dwr_ref[:] = dwr_scr[:]
        if projected:
            dwx_ref[:] = dwx_scr[:].astype(dwx_ref.dtype)
            db_ref[:] = db_scr[:]


def _rounded_as(v, dtype):
    """float32 ``v`` rounded to nearest-even at ``dtype``'s precision and
    kept in float32: ``v.astype(dtype).astype(float32)``, which XLA may
    drop on the chip, where ``lax.reduce_precision`` (which it keeps) has
    no Mosaic lowering.  bfloat16 is float32's upper half: round the lower
    16 bits away in the integers.  A NaN is kept as it is: the carry into
    its upper half would make some payloads infinite or zero."""
    if jnp.dtype(dtype) == jnp.float32:
        return v
    if jnp.dtype(dtype) != jnp.bfloat16:
        return v.astype(dtype).astype(jnp.float32)
    bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
    bits = bits + (jnp.uint32(0x7FFF) + ((bits >> 16) & jnp.uint32(1)))
    rounded = jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                           jnp.float32)
    return jnp.where(v != v, v, rounded)


def _compiler_params(bt, d, d_in):
    """grid = (batch tiles, time): the carry makes time sequential, the
    shared weight-gradient accumulators make the tiles sequential.  The
    scoped-VMEM limit follows the plan ``batch_tile`` chose ``bt`` by
    (``plan_bytes``): at d=512 even 64 rows are over Mosaic's default
    16 MiB (docs/kernels.md, VMEM table)."""
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),
        vmem_limit_bytes=vmem_limit_bytes(plan_bytes(bt, d, d_in)))


def _fwd(x, w_x, bias, w_r, checks, mask, interpret, bt, save_residuals):
    """x: the gate inputs [T, B, 4D] (``w_x`` None), or the layer's input
    [T, B, in] with its projection ``w_x`` [in, 4D] and ``bias`` [4D]."""
    nt, b, d_in = x.shape
    d = w_r.shape[0]
    g = 4 * d
    projected = w_x is not None

    def const(ib, t):
        return (0, 0)

    in_specs = [pl.BlockSpec((1, bt, d_in), lambda ib, t: (t, ib, 0))]
    operands = [x]
    out_dtype = x.dtype
    if projected:
        cd = dtypes.compute_dtype()
        out_dtype = jnp.promote_types(cd, jnp.float32)
        in_specs += [pl.BlockSpec((d_in, g), const),
                     pl.BlockSpec((1, g), const)]
        operands += [w_x.astype(cd), bias.astype(jnp.float32).reshape(1, g)]
    in_specs += [
        pl.BlockSpec((d, g), const),
        pl.BlockSpec((3, d), const),
        pl.BlockSpec((1, bt, _LANES), lambda ib, t: (t, ib, 0)),
    ]
    operands += [w_r, checks, mask]
    out_specs = [
        pl.BlockSpec((1, bt, d), lambda ib, t: (t, ib, 0)),    # hs
        pl.BlockSpec((1, bt, d), lambda ib, t: (0, ib, 0)),    # c_final
    ]
    out_shape = [
        jax.ShapeDtypeStruct((nt, b, d), out_dtype),
        jax.ShapeDtypeStruct((1, b, d), jnp.float32),
    ]
    if save_residuals:
        out_specs += [
            pl.BlockSpec((1, bt, d), lambda ib, t: (t, ib, 0)),    # cs
            pl.BlockSpec((1, bt, g), lambda ib, t: (t, ib, 0)),    # acts
        ]
        out_shape += [
            jax.ShapeDtypeStruct((nt, b, d), jnp.float32),
            jax.ShapeDtypeStruct((nt, b, g), jnp.float32),
        ]

    def kernel(x_ref, *refs):
        refs = list(refs)
        wx_ref, b_ref = (refs.pop(0), refs.pop(0)) if projected \
            else (None, None)
        wr_ref, chk_ref, mask_ref, hs_ref, cfin_ref = refs[:5]
        cs_ref, acts_ref = refs[5:7] if save_residuals else (None, None)
        h_scr, c_scr = refs[-2:]
        _fwd_kernel(x_ref, wx_ref, b_ref, wr_ref, chk_ref, mask_ref,
                    hs_ref, cfin_ref, cs_ref, acts_ref, h_scr, c_scr,
                    d=d, nt=nt, save_residuals=save_residuals)

    outs = pl.pallas_call(
        kernel,
        name="lstm_fwd",
        grid=(b // bt, nt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((bt, d), jnp.float32),
            pltpu.VMEM((bt, d), jnp.float32),
        ],
        compiler_params=_compiler_params(
            bt, d, d_in if projected else None),
        interpret=interpret,
    )(*operands)
    if save_residuals:
        hs, cfin, cs, acts = outs
        return hs, cfin, cs, acts
    hs, cfin = outs
    return hs, cfin, None, None


def _bwd(interpret, bt, res, dh_out, dhfin, dcfin):
    """``dh_out`` [T, B, D]: the cotangent of every step's h, or None where
    the final carry alone was an output: then ``dhfin`` [1, B, D], its h's
    cotangent, enters the chain at the last step as ``dcfin`` does."""
    x, w_x, bias, w_r, checks, mask, hs, cs, acts = res
    nt, b, d = hs.shape
    g = 4 * d
    projected = w_x is not None

    def now(ib, j):               # the step being differentiated
        return (nt - 1 - j, ib, 0)

    def prev(ib, j):              # its predecessor (zeroed in-kernel at t=0)
        return (jnp.maximum(nt - 2 - j, 0), ib, 0)

    def const(ib, j):
        return (0, 0)

    in_specs = [
        pl.BlockSpec((1, bt, g), now),                     # acts
        pl.BlockSpec((1, bt, d), now),                     # cs
        pl.BlockSpec((1, bt, d), prev),                    # c_{t-1}
        pl.BlockSpec((1, bt, d), prev),                    # h_{t-1}
    ]
    operands = [acts, cs, cs, hs]
    if projected:
        d_in = x.shape[-1]
        cd = dtypes.compute_dtype()
        in_specs += [pl.BlockSpec((1, bt, d_in), now),     # x
                     pl.BlockSpec((d_in, g), const)]       # W_x
        operands += [x, w_x.astype(cd)]
        out_specs = [pl.BlockSpec((1, bt, d_in), now),     # dx
                     pl.BlockSpec((d_in, g), const),       # dW_x
                     pl.BlockSpec((1, g), const)]          # db
        out_shape = [jax.ShapeDtypeStruct((nt, b, d_in), x.dtype),
                     jax.ShapeDtypeStruct((d_in, g), cd),
                     jax.ShapeDtypeStruct((1, g), jnp.float32)]
        own_scratch = [pltpu.VMEM((d_in, g), jnp.float32),
                       pltpu.VMEM((1, g), jnp.float32)]
    else:
        d_in = None
        # hs was emitted in the gate inputs' dtype
        out_specs = [pl.BlockSpec((1, bt, g), now)]        # dgates
        out_shape = [jax.ShapeDtypeStruct((nt, b, g), hs.dtype)]
        own_scratch = []
    streamed = dh_out is not None

    def final(ib, j):
        return (0, ib, 0)

    in_specs += [
        pl.BlockSpec((d, g), const),
        pl.BlockSpec((3, d), const),
        pl.BlockSpec((1, bt, _LANES), now),                # mask
        pl.BlockSpec((1, bt, d), now if streamed else final),  # dh_out | dhfin
        pl.BlockSpec((1, bt, d), final),                   # dcfin
    ]
    operands += [w_r, checks, mask,
                 dh_out if streamed else dhfin.astype(jnp.float32),
                 dcfin.astype(jnp.float32)]
    out_specs += [
        pl.BlockSpec((d, g), const),                       # dW_r
        pl.BlockSpec((bt, 3 * d), lambda ib, j: (ib, 0)),  # dchk rows
    ]
    out_shape += [
        jax.ShapeDtypeStruct((d, g), jnp.float32),
        jax.ShapeDtypeStruct((b, 3 * d), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((bt, d), jnp.float32),
        pltpu.VMEM((bt, d), jnp.float32),
        pltpu.VMEM((d, g), jnp.float32),
        pltpu.VMEM((bt, 3 * d), jnp.float32),
    ] + own_scratch

    def kernel(*refs):
        refs = list(refs)

        def take(n, present=True):
            return [refs.pop(0) for _ in range(n)] if present else [None] * n

        _bwd_kernel(*take(4), *take(2, projected), *take(3),        # inputs
                    *take(1, streamed), *take(1, not streamed), *take(1),
                    *take(1), *take(2, projected), *take(2),        # outputs
                    *take(4), *take(2, projected), d=d, nt=nt)      # scratch

    outs = pl.pallas_call(
        kernel,
        name="lstm_bwd",
        grid=(b // bt, nt),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=scratch,
        compiler_params=_compiler_params(bt, d, d_in),
        interpret=interpret,
    )(*operands)
    dx, dwx, db = outs[0], None, None
    if projected:
        dwx = outs[1].astype(w_x.dtype)
        db = outs[2][0].astype(bias.dtype)
    dwr, dchk = outs[-2:]
    dchecks = dchk.sum(axis=0).reshape(3, d).astype(checks.dtype)
    return dx, dwx, db, dwr.astype(w_r.dtype), dchecks, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _fused(x, w_x, bias, w_r, checks, mask, interpret, bt):
    hs, cfin, _, _ = _fwd(x, w_x, bias, w_r, checks, mask, interpret, bt,
                          save_residuals=False)
    return hs, cfin


def _fused_fwd_rule(x, w_x, bias, w_r, checks, mask, interpret, bt):
    hs, cfin, cs, acts = _fwd(x, w_x, bias, w_r, checks, mask, interpret,
                              bt, save_residuals=True)
    # the gate inputs themselves are no residual: the backward reads acts
    x_res = None if w_x is None else x
    return (hs, cfin), (x_res, w_x, bias, w_r, checks, mask, hs, cs, acts)


_fused.defvjp(_fused_fwd_rule, lambda interpret, bt, res, g: _bwd(
    interpret, bt, res, g[0], None, g[1]))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _fused_final(x, w_x, bias, w_r, checks, mask, interpret, bt):
    """``_fused`` whose outputs are the final carry (h, c) alone: the
    residuals are the same, and the backward takes no [T, B, D]
    cotangent."""
    hs, cfin, _, _ = _fwd(x, w_x, bias, w_r, checks, mask, interpret, bt,
                          save_residuals=False)
    return hs[-1], cfin[0]


def _fused_final_fwd_rule(x, w_x, bias, w_r, checks, mask, interpret, bt):
    (hs, cfin), res = _fused_fwd_rule(x, w_x, bias, w_r, checks, mask,
                                      interpret, bt)
    return (hs[-1], cfin[0]), res


_fused_final.defvjp(_fused_final_fwd_rule, lambda interpret, bt, res, g: _bwd(
    interpret, bt, res, None, g[0][None], g[1][None]))


def vmem_bytes(bt, d):
    """Planning estimate of the BACKWARD kernel's VMEM footprint (the
    larger pass) at a batch tile of ``bt`` rows, as Mosaic allocates it:
    w_r, the dW_r accumulator and dW_r's output block (4dd each, f32; a
    block whose index never moves gets ONE buffer), the peepholes, and per
    row the dh/dc/dchk scratch (5d) plus TWO buffers — the pipeline's —
    of every streamed block: acts and dxs (4d each), cs, csp, hsp, dh_out,
    dcfin (d each), dchk (3d), the mask (128).  Against the v5e compiler's
    own count the plan is 2-7% over at d = 128..512 and within 3% either
    way at d = 1024..1536 (docs/kernels.md carries the table);
    ``vmem_limit_bytes`` adds the margin."""
    resident = 12 * d * d + 6 * d
    per_row = 5 * d + 2 * (16 * d + _LANES)
    return 4 * (resident + bt * per_row)


def fwd_vmem_bytes(bt, d, d_in):
    """The projected FORWARD's footprint, residuals saved: w_r (f32) and
    W_x (compute dtype) resident in one buffer each, the bias and the
    peepholes, and per row the h/c scratch (2d), the gate pre-activations
    as a value (4d), x in the compute dtype, plus two buffers of every
    streamed block: x (d_in), hs, cs and c_final (d each), acts (4d), the
    mask (128).  From 0.1% under to 26% over the v5e compiler's own count
    at d = 128..640 (docs/kernels.md carries the table)."""
    cd = jnp.dtype(dtypes.compute_dtype()).itemsize
    resident = 4 * (4 * d * d + 7 * d) + cd * d_in * 4 * d
    per_row = 4 * (6 * d + 2 * (7 * d + d_in + _LANES)) + cd * d_in
    return resident + bt * per_row


def bwd_vmem_bytes(bt, d, d_in):
    """The projected BACKWARD's footprint: ``vmem_bytes``'s without the
    two buffers of the [bt, 4D] ``dxs`` block, with W_x resident, the dW_x
    accumulator (f32) and its output block (compute dtype), the bias's
    sums, and per row two buffers each of x_t and dx_t (f32, dx leaving
    in its input's dtype) and the compute-dtype copies of dgates and
    x_t."""
    cd = jnp.dtype(dtypes.compute_dtype()).itemsize
    resident = (4 * (12 * d * d + 6 * d + d_in * 4 * d + 2 * 8 * 4 * d)
                + 2 * cd * d_in * 4 * d)
    per_row = (4 * (5 * d + 2 * (12 * d + 2 * d_in + _LANES))
               + cd * (4 * d + d_in))
    return resident + bt * per_row


def plan_bytes(bt, d, d_in=None):
    """The larger of the two passes' plans at a tile of ``bt`` rows: the
    tile is chosen by it and both calls hand Mosaic it plus a sixteenth.
    ``d_in``: the kernels project an input of that width themselves."""
    if d_in is None:
        return vmem_bytes(bt, d)
    return max(fwd_vmem_bytes(bt, d, d_in), bwd_vmem_bytes(bt, d, d_in))


def batch_tile(b, d, d_in=None):
    """Rows per batch tile: the largest multiple of 8 that divides ``b``
    and whose ``plan_bytes`` fits the budget — ``b`` itself (one tile)
    whenever the whole batch fits; 0 when no such tile exists (the weights
    alone are over the budget, or no multiple of 8 divides ``b``)."""
    budget = vmem_budget_bytes(scoped_limit_raised=True)
    for bt in range(b - b % 8, 0, -8):
        if b % bt == 0 and plan_bytes(bt, d, d_in) <= budget:
            return bt
    return 0


def supported(b, d, act, gate_act, state_act, init_state, d_in=None):
    """Kernel path preconditions; callers fall back to the scan otherwise.
    reverse is handled by the caller's time-flip (see rnn._fused_dispatch).
    The VMEM guard keeps weights that cannot be resident off the kernel
    path (d=1280: w_r, its gradient's accumulator and output block are
    79 MB f32 — over a 16 MiB core, inside a v5e's 128 MiB); a batch too
    large for one block is tiled, not declined.  ``d_in``: the kernels are
    to project their own input of that width (``lstm_fused(proj=)``) in
    BOTH passes, so W_x, dW_x and the x / dx streams have to fit beside
    w_r at some tile."""
    return (act == "tanh" and gate_act == "sigmoid" and state_act == "tanh"
            and init_state is None
            and d % _LANES == 0 and batch_tile(b, d, d_in) > 0)


def lstm_fused(xs_tm, mask_tm, w_r, check_i, check_f, check_o,
               proj=None, bias=None, interpret=None, final_only=False):
    """Whole-sequence fused LSTM.

    xs_tm: [T, B, 4D] time-major pre-projected gate inputs (bias included);
    or, with ``proj`` [in, 4D], the layer's input [T, B, in] itself, which
    the forward kernel projects and adds ``bias`` [4D] (or nothing) to, and
    whose gradients the backward kernel forms (dx in xs_tm's dtype).
    mask_tm: [T, B] float 0/1.  Returns (hs_tm [T, B, D], final (h, c));
    with ``final_only``, (None, final (h, c)): the final carry is the only
    output, so no [T, B, D] cotangent exists and the backward starts from
    the carry's.
    """
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    nt, b, d_x = xs_tm.shape
    d = w_r.shape[0]
    bt = batch_tile(b, d, None if proj is None else d_x)
    assert bt, f"lstm_fused: no batch tile for b={b}, d={d} (supported())"
    if proj is None and bias is not None:
        raise ValueError("lstm_fused: a bias without proj= is already part "
                         "of the gate inputs")
    if proj is not None and bias is None:
        bias = jnp.zeros((4 * d,), jnp.float32)
    checks = jnp.stack([
        jnp.zeros((d,), jnp.float32) if v is None else v.astype(jnp.float32)
        for v in (check_i, check_f, check_o)])
    mask_r = jnp.broadcast_to(
        mask_tm.astype(jnp.float32)[:, :, None], (nt, b, _LANES))
    args = (xs_tm, proj, bias, w_r, checks, mask_r, interpret, bt)
    if final_only:
        h, c = _fused_final(*args)
        return None, (h, c.astype(h.dtype))
    hs, cfin = _fused(*args)
    return hs, (hs[-1], cfin[0].astype(hs.dtype))

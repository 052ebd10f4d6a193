"""Gate-blocked fused LSTM forward (Pallas) for over-VMEM hidden sizes.

The resident-weight kernel (ops/pallas/lstm.py) needs w_r `[D, 4D]` in
VMEM for all T steps — impossible at d=1280 (26 MB f32 on a ~16 MB core;
docs/kernels.md audit).  This variant blocks the GATE dimension instead:

  grid = (T, D/blk), block-j innermost.  Each (t, j) step streams
  w_r[:, :, j] (`[D, 4, blk]`) from HBM — the same weight traffic as
  lax.scan — but the carried state stays in VMEM (h double-buffered A/B
  by t-parity so every block of step t reads the INTACT h_{t-1}; c is
  updated in place, its cell math being columnwise) and the whole cell
  fuses into the matmul.  What the scan pays per step and this kernel
  does not: h+c round-trips through HBM and separate elementwise ops.

The t-parity double buffer uses two STATIC scratch refs selected with
@pl.when (Mosaic cannot dynamically index a scratch ref's leading dim by
a traced value).  T is padded to even in the wrapper; the pad step gets
mask 0, which freezes the carry, so it is a no-op (same trick the ragged
path uses for short sequences).

Backward: pure-JAX BPTT over the forward-saved activations (a, i, f, o,
c per step) — no Pallas kernel and NO forward recompute; the two matmuls
per step (dgates @ w_r^T, h^T @ dgates) are exactly what XLA tiles well
at this size.  Saved-activation layout matches the resident kernel so
the scan oracle tests can share machinery.

Reference anchor: cuda/src/hl_cuda_lstm.cu (the fused production RNN
path this family replaces).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from paddle_tpu.ops.pallas.common import LANES as _LANES, lanes as _lanes

_BLK = _LANES     # gate-column block width; 128 = one lane tile


def _cell_block(x4, h_prev, wblk, ci, cf, co, c_prev_blk):
    """One timestep's cell math for one gate-column block.  x4 [B,4,blk],
    h_prev [B,D] (full), wblk [D,4,blk].  Returns (a,i,f,o,c_new,h_new)
    for the block's columns."""
    r = jax.lax.dot_general(
        h_prev, wblk.reshape(wblk.shape[0], -1), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)            # [B, 4*blk]
    blk = x4.shape[-1]
    g = x4.reshape(x4.shape[0], -1) + r                # [B, 4*blk]
    a = jnp.tanh(g[:, 0:blk])
    i = jax.nn.sigmoid(g[:, blk:2 * blk] + c_prev_blk * ci)
    f = jax.nn.sigmoid(g[:, 2 * blk:3 * blk] + c_prev_blk * cf)
    c_new = a * i + c_prev_blk * f
    o = jax.nn.sigmoid(g[:, 3 * blk:4 * blk] + c_new * co)
    h_new = o * jnp.tanh(c_new)
    return a, i, f, o, c_new, h_new


def _fwd_kernel(xs_ref, wr_ref, chk_ref, mask_ref,
                hs_ref, cfin_ref, cs_ref, acts_ref,
                ha_scr, hb_scr, c_scr, *, nt, save_residuals):
    t = pl.program_id(0)
    j = pl.program_id(1)
    blk = _BLK

    @pl.when((t == 0) & (j == 0))
    def _():
        ha_scr[:] = jnp.zeros_like(ha_scr)
        c_scr[:] = jnp.zeros_like(c_scr)

    x4 = xs_ref[0].astype(jnp.float32)                 # [B, 4, blk]
    wblk = wr_ref[:].astype(jnp.float32)               # [D, 4, blk]
    ci, cf, co = chk_ref[0:1], chk_ref[1:2], chk_ref[2:3]   # [1, blk]
    m = _lanes(mask_ref[0], blk)                       # [B, blk]
    c_prev = c_scr[:, pl.ds(j * blk, blk)]

    def run(prev_ref, new_ref):
        h_prev = prev_ref[:]                           # full [B, D]
        a, i, f, o, c_new, h_new = _cell_block(
            x4, h_prev, wblk, ci, cf, co, c_prev)
        # block read straight off the ref: Mosaic lowers dynamic slices
        # on REFS but not the dynamic_slice primitive on values
        h_prev_blk = prev_ref[:, pl.ds(j * blk, blk)]
        h_out = m * h_new + (1.0 - m) * h_prev_blk
        c_out = m * c_new + (1.0 - m) * c_prev
        new_ref[:, pl.ds(j * blk, blk)] = h_out
        c_scr[:, pl.ds(j * blk, blk)] = c_out
        hs_ref[0] = h_out.astype(hs_ref.dtype)
        if save_residuals:
            cs_ref[0] = c_out
            acts_ref[0, :, 0, :] = a
            acts_ref[0, :, 1, :] = i
            acts_ref[0, :, 2, :] = f
            acts_ref[0, :, 3, :] = o

    # static A/B selection by t-parity: even t reads A writes B, odd t
    # reads B writes A
    @pl.when(t % 2 == 0)
    def _():
        run(ha_scr, hb_scr)

    @pl.when(t % 2 == 1)
    def _():
        run(hb_scr, ha_scr)

    @pl.when(t == nt - 1)
    def _():
        cfin_ref[0] = c_scr[:, pl.ds(j * blk, blk)].astype(cfin_ref.dtype)


def _fwd(xs4, w_r4, checks, mask, interpret, save_residuals):
    nt, b, d = xs4.shape[0], xs4.shape[1], xs4.shape[3]
    nblk = d // _BLK

    out_specs = [
        pl.BlockSpec((1, b, _BLK), lambda t, j: (t, 0, j)),   # hs
        pl.BlockSpec((1, b, _BLK), lambda t, j: (0, 0, j)),   # c_final
    ]
    out_shape = [
        jax.ShapeDtypeStruct((nt, b, d), xs4.dtype),
        jax.ShapeDtypeStruct((1, b, d), jnp.float32),
    ]
    if save_residuals:
        out_specs += [
            pl.BlockSpec((1, b, _BLK), lambda t, j: (t, 0, j)),      # cs
            pl.BlockSpec((1, b, 4, _BLK), lambda t, j: (t, 0, 0, j)),  # acts
        ]
        out_shape += [
            jax.ShapeDtypeStruct((nt, b, d), jnp.float32),
            jax.ShapeDtypeStruct((nt, b, 4, d), jnp.float32),
        ]

    def kernel(xs_ref, wr_ref, chk_ref, mask_ref, hs_ref, cfin_ref, *rest):
        if save_residuals:
            cs_ref, acts_ref, ha, hb, c = rest
        else:
            (ha, hb, c), cs_ref, acts_ref = rest, None, None
        _fwd_kernel(xs_ref, wr_ref, chk_ref, mask_ref, hs_ref, cfin_ref,
                    cs_ref, acts_ref, ha, hb, c,
                    nt=nt, save_residuals=save_residuals)

    outs = pl.pallas_call(
        kernel,
        name="lstm_blocked",
        grid=(nt, nblk),
        in_specs=[
            pl.BlockSpec((1, b, 4, _BLK), lambda t, j: (t, 0, 0, j)),
            pl.BlockSpec((d, 4, _BLK), lambda t, j: (0, 0, j)),
            pl.BlockSpec((3, _BLK), lambda t, j: (0, j)),
            pl.BlockSpec((1, b, _LANES), lambda t, j: (t, 0, 0)),
        ],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((b, d), jnp.float32),   # h parity buffer A
            pltpu.VMEM((b, d), jnp.float32),   # h parity buffer B
            pltpu.VMEM((b, d), jnp.float32),   # c (in-place per block)
        ],
        interpret=interpret,
    )(xs4, w_r4, checks, mask)
    if save_residuals:
        return outs
    return outs[0], outs[1], None, None


def _bwd_scan(res, g_out):
    """Saved-activation BPTT in plain JAX (reversed lax.scan): the
    recurrent matmuls XLA-tile fine at over-VMEM sizes; what the forward
    kernel bought (fused cell, VMEM carry) the backward buys back by not
    recomputing any activation."""
    w_r, checks, mask, hs, cs, acts = res
    dh_out, dcfin = g_out
    nt, b, d = dh_out.shape
    ci, cf, co = checks[0], checks[1], checks[2]
    wr = w_r.astype(jnp.float32)

    hs_prev = jnp.concatenate(
        [jnp.zeros_like(hs[:1]), hs[:-1]], axis=0).astype(jnp.float32)
    cs_prev = jnp.concatenate(
        [jnp.zeros_like(cs[:1]), cs[:-1]], axis=0)

    def step(carry, inp):
        dh_acc, dc_acc, dwr_acc, dchk_acc = carry
        a, i, f, o, c_t, c_prev, h_prev, m, dh_t = inp
        dh = dh_acc + dh_t.astype(jnp.float32)
        tc = jnp.tanh(c_t)
        dog = dh * tc * o * (1.0 - o)
        dc = dh * o * (1.0 - tc * tc) + dc_acc + dog * co
        dag = dc * i * (1.0 - a * a)
        dig = dc * a * i * (1.0 - i)
        dfg = dc * c_prev * f * (1.0 - f)
        dgates = jnp.concatenate([dag * m, dig * m, dfg * m, dog * m],
                                 axis=1)
        dh_prev = jax.lax.dot_general(
            dgates, wr, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dc_prev = dc * f + dig * ci + dfg * cf
        new_dh = m * dh_prev + (1.0 - m) * dh
        new_dc = m * dc_prev + (1.0 - m) * dc_acc
        dwr_acc = dwr_acc + jax.lax.dot_general(
            h_prev, dgates, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dchk_acc = dchk_acc + jnp.stack([
            jnp.sum(m * dig * c_prev, axis=0),
            jnp.sum(m * dfg * c_prev, axis=0),
            jnp.sum(m * dog * c_t, axis=0)])
        return (new_dh, new_dc, dwr_acc, dchk_acc), dgates

    m_t = mask[:, :, :1]                      # [T, B, 1] lane 0
    m_full = jnp.broadcast_to(m_t, (nt, b, d))
    init = (jnp.zeros((b, d), jnp.float32),
            dcfin[0].astype(jnp.float32),
            jnp.zeros((d, 4 * d), jnp.float32),
            jnp.zeros((3, d), jnp.float32))
    acts_flat = acts.reshape(nt, b, 4, d)
    (dh0, dc0, dwr, dchk), dxs = jax.lax.scan(
        step, init,
        (acts_flat[:, :, 0], acts_flat[:, :, 1], acts_flat[:, :, 2],
         acts_flat[:, :, 3], cs.astype(jnp.float32), cs_prev, hs_prev,
         m_full, dh_out),
        reverse=True)
    return (dxs.astype(hs.dtype), dwr.astype(w_r.dtype),
            dchk.astype(checks.dtype), None)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _fused(xs4, w_r4, checks, mask, interpret):
    hs, cfin, _, _ = _fwd(xs4, w_r4, checks, mask, interpret,
                          save_residuals=False)
    return hs, cfin


def _fused_fwd_rule(xs4, w_r4, checks, mask, interpret):
    hs, cfin, cs, acts = _fwd(xs4, w_r4, checks, mask, interpret,
                              save_residuals=True)
    d = xs4.shape[3]
    w_r = w_r4.reshape(w_r4.shape[0], 4 * d)
    return (hs, cfin), (w_r, checks, mask, hs, cs, acts)


def _fused_bwd_rule(interpret, res, g_out):
    dxs, dwr, dchk, _ = _bwd_scan(res, g_out)
    nt, b, d = dxs.shape[0], dxs.shape[1], dxs.shape[2] // 4
    return (dxs.reshape(nt, b, 4, d), dwr.reshape(dwr.shape[0], 4, d),
            dchk, None)


_fused.defvjp(_fused_fwd_rule, _fused_bwd_rule)


def vmem_bytes(b, d):
    """Training-path footprint (the larger, save_residuals forward): three
    [B, D] f32 carry scratches + two pipelined weight blocks [D, 4, 128]
    + double-buffered streamed blocks INCLUDING the residual outputs
    (cs [B, 128] + acts [B, 4, 128]) the VJP variant emits."""
    resident = 3 * b * d + 2 * d * 4 * _BLK
    streamed = 2 * (b * 4 * _BLK + b * _LANES + 2 * b * _BLK
                    + b * _BLK + b * 4 * _BLK)
    return 4 * (resident + streamed)


def supported(b, d, act, gate_act, state_act, init_state):
    from paddle_tpu.ops.pallas.common import vmem_budget_bytes
    return (act == "tanh" and gate_act == "sigmoid" and state_act == "tanh"
            and init_state is None
            and b % 8 == 0 and d % _BLK == 0
            and vmem_bytes(b, d) <= vmem_budget_bytes())


def lstm_fused_blocked(xs_tm, mask_tm, w_r, check_i, check_f, check_o,
                       interpret=None):
    """Whole-sequence gate-blocked LSTM; same contract as
    lstm.lstm_fused: xs_tm [T, B, 4D] pre-projected gate inputs, mask
    [T, B] -> (hs_tm [T, B, D], final (h, c))."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    nt, b, g = xs_tm.shape
    d = g // 4
    checks = jnp.stack([
        jnp.zeros((d,), jnp.float32) if v is None else v.astype(jnp.float32)
        for v in (check_i, check_f, check_o)])
    # pad T to even for the parity double-buffer; the pad step's mask is 0,
    # which freezes the carry (a no-op step)
    pad = nt % 2
    if pad:
        xs_tm = jnp.concatenate(
            [xs_tm, jnp.zeros_like(xs_tm[:1])], axis=0)
        mask_tm = jnp.concatenate(
            [mask_tm, jnp.zeros_like(mask_tm[:1])], axis=0)
    ntp = nt + pad
    xs4 = xs_tm.reshape(ntp, b, 4, d)
    w_r4 = w_r.reshape(d, 4, d)
    mask_r = jnp.broadcast_to(
        mask_tm.astype(jnp.float32)[:, :, None], (ntp, b, _LANES))
    hs, cfin = _fused(xs4, w_r4, checks, mask_r, interpret)
    hs = hs[:nt]
    return hs, (hs[-1], cfin[0].astype(hs.dtype))

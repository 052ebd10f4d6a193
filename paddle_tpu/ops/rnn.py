"""Recurrent ops: fused LSTM/GRU cells + whole-sequence scans + the generic
recurrent-group engine.

Reference: LstmLayer/LstmCompute + hl_lstm fused kernels
(cuda/include/hl_lstm_ops.cuh:46-66: gate order [input, input_gate,
forget_gate, output_gate], peephole checkI/F/O), GatedRecurrentLayer /
GruCompute (cuda/include/hl_gru_ops.cuh:37-80: h = prev - u*prev + u*c),
RecurrentLayer, and the per-step unrolled engine
RecurrentGradientMachine.cpp:379-712.

TPU design: whole-sequence compute is one `lax.scan` whose body is a fused
(gate-matmul + elementwise) step — XLA fuses the elementwise block; the
input-to-hidden projection for ALL timesteps is hoisted out of the scan as a
single big MXU matmul (the same trick as the reference's SequenceToBatch
batching, but in time-major form).  Padding is handled by carrying state
through masked steps unchanged, so results match the reference's padding-free
semantics exactly.
"""

import contextlib
import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from paddle_tpu.core.sequence import NestedSequenceBatch, SequenceBatch
from paddle_tpu.ops import activations
from paddle_tpu.ops.linear import matmul


class LstmState(NamedTuple):
    h: jnp.ndarray  # [B, D] hidden (output)
    c: jnp.ndarray  # [B, D] cell state


def _batch_major(hs_tm, lengths) -> SequenceBatch:
    """A time-major [T, B, D] output as the masked batch-major sequence
    every reader of a recurrent layer takes by default."""
    sb = SequenceBatch(data=hs_tm.transpose(1, 0, 2), lengths=lengths)
    return sb.with_data(sb.data * sb.mask(hs_tm.dtype)[..., None])


class LstmOutput(NamedTuple):
    """A fused LSTM's output in the kernel's own form, for readers that
    need no masked batch-major sequence (``graph.Topology`` decides which):
    ``hs`` the time-major [T, B, D] stream as the kernel wrote it, a masked
    step holding the carry frozen there, or None where only ``final`` was
    asked for; ``final`` the carry after each row's last valid step, which
    is the row at ``lengths - 1`` (0 for a row of length 0)."""
    hs: Optional[jnp.ndarray]
    final: LstmState
    lengths: jnp.ndarray

    def sequence(self) -> SequenceBatch:
        return _batch_major(self.hs, self.lengths)


def lstm_cell(x4, state: LstmState, w_r, check_i=None, check_f=None,
              check_o=None, act="tanh", gate_act="sigmoid", state_act="tanh"):
    """One LSTM step.

    x4: [B, 4D] input already projected to the 4 gates in reference order
        [in, input_gate, forget_gate, output_gate] (hl_lstm_ops.cuh:46).
    w_r: [D, 4D] recurrent weights.  check_*: [D] peepholes (optional).
    """
    d = state.h.shape[-1]
    gates = x4 + matmul(state.h, w_r)
    a, ig, fg, og = jnp.split(gates, 4, axis=-1)
    act_f = activations.get(act)
    gate_f = activations.get(gate_act)
    state_f = activations.get(state_act)
    a = act_f(a)
    if check_i is not None:
        ig = ig + state.c * check_i
    if check_f is not None:
        fg = fg + state.c * check_f
    i = gate_f(ig)
    f = gate_f(fg)
    c = a * i + state.c * f
    if check_o is not None:
        og = og + c * check_o
    o = gate_f(og)
    h = o * state_f(c)
    return LstmState(h=h, c=c)


def gru_cell(x3, h_prev, w_gate, w_state, act="tanh", gate_act="sigmoid"):
    """One GRU step (reference hl_gru_ops.cuh:37-80).

    x3: [B, 3D] projected input, layout [update, reset, candidate].
    w_gate: [D, 2D] recurrent weights for update/reset;
    w_state: [D, D] recurrent weights for the candidate.
    h = prev - u*prev + u*c~,  c~ = act(x_c + (r*prev) @ w_state)
    """
    d = h_prev.shape[-1]
    xu, xr, xc = x3[..., :d], x3[..., d:2 * d], x3[..., 2 * d:]
    ru = matmul(h_prev, w_gate)
    gate_f = activations.get(gate_act)
    u = gate_f(xu + ru[..., :d])
    r = gate_f(xr + ru[..., d:])
    c = activations.get(act)(xc + matmul(r * h_prev, w_state))
    return h_prev - u * h_prev + u * c


def simple_rnn_cell(x, h_prev, w_r, act="tanh"):
    """Reference RecurrentLayer: h = act(x + h_prev @ w_r)."""
    return activations.get(act)(x + matmul(h_prev, w_r))


# lax.scan unroll factor for the sequence loops: >1 lets XLA pipeline
# consecutive steps (fewer loop-carried syncs on the TPU scalar core) at the
# cost of compile time.  Overridable via PADDLE_TPU_SCAN_UNROLL.
import os as _os

SCAN_UNROLL = int(_os.environ.get("PADDLE_TPU_SCAN_UNROLL", "1"))

# Fused whole-sequence Pallas RNN kernels (ops/pallas/{lstm,gru,
# simple_rnn}.py): weights + state stay VMEM-resident across the time loop
# instead of round-tripping HBM every scan step.  Gates ALL THREE kernels.
# Values: "auto" (default; kernels on real TPU, scan elsewhere — interpret
# mode is slower than the scan and only useful for testing), "always"
# (kernels everywhere, interpret off-TPU), "0"/"off" (scan everywhere);
# "1" is a legacy alias for auto.
# PADDLE_TPU_FUSED_RNN is the primary env var; PADDLE_TPU_FUSED_LSTM is an
# accepted alias from before the GRU kernel existed.
FUSED_LSTM = _os.environ.get(
    "PADDLE_TPU_FUSED_RNN",
    _os.environ.get("PADDLE_TPU_FUSED_LSTM", "auto"))


def _fused_lstm_enabled():
    if FUSED_LSTM == "always":
        return True
    if FUSED_LSTM in ("0", "off", "false", "no"):
        return False
    # "1" keeps its legacy meaning: enabled-with-auto-gating (kernel on
    # real TPU only) — NOT force-on, which would switch CPU boxes to the
    # slow interpret path
    if FUSED_LSTM not in ("auto", "1", ""):
        from paddle_tpu.utils.logging import logger
        logger.warning("PADDLE_TPU_FUSED_RNN=%r not recognized "
                       "(auto|always|0); treating as auto", FUSED_LSTM)
    return jax.default_backend() == "tpu"


#: incremented on every fused-kernel dispatch (trace time).  Observers
#: (benchmark/drivers/train.py, chip_smoke.py) snapshot it around a
#: compile to learn whether the fused path was ACTUALLY taken for a given
#: model/shape — the one source of truth, instead of re-deriving
#: supported()'s decision externally.
FUSED_DISPATCH_COUNT = 0

#: of those, the LSTM dispatches in which the kernels took the layer's
#: input and its projection (``lstm(proj=)``) in both passes: the forward
#: formed the gate inputs itself, the backward the projection's gradients;
#: counted at trace time like its neighbour.
PROJECTED_DISPATCH_COUNT = 0

#: the LSTM outputs handed to their readers in the fused kernel's own form
#: (``LstmOutput``) instead of a masked batch-major sequence; counted at
#: trace time like its neighbours.
HANDED_OVER_COUNT = 0


# The mesh axis a multi-device jit shards the batch over, as (mesh, axis) —
# set by the trainer around its step's trace (``batch_sharded_over``).
# GSPMD cannot partition a Mosaic kernel ("Mosaic kernels cannot be
# automatically partitioned"), so under such a jit the fused kernels are
# handed their batch shard through ``shard_map`` instead.  Read at TRACE
# time, like FUSED_LSTM.
_BATCH_MESH = None


@contextlib.contextmanager
def batch_sharded_over(mesh, axis):
    """Trace the enclosed model code as data-parallel over ``axis`` of
    ``mesh``: the fused RNN kernels run per batch shard under shard_map.
    A no-op for ``mesh=None`` or an axis of size 1."""
    global _BATCH_MESH
    old = _BATCH_MESH
    if mesh is not None and dict(mesh.shape).get(axis, 1) > 1:
        _BATCH_MESH = (mesh, axis)
    try:
        yield
    finally:
        _BATCH_MESH = old


def _local_batch(b):
    """The batch ONE kernel instance sees: the per-shard batch under
    ``batch_sharded_over`` (what the kernels' VMEM guards must judge)."""
    if _BATCH_MESH is None:
        return b
    mesh, axis = _BATCH_MESH
    n = mesh.shape[axis]
    # an uneven batch cannot be sharded: 0 fails every supported() guard
    return b // n if b % n == 0 else 0


def _fused_dispatch(xs, ms, reverse, kernel_fn, weights):
    """Shared fused-kernel dispatch: reverse = forward kernel over
    time-flipped arrays, flipped back (valid because sequences are
    left-aligned; masked steps freeze the carry identically either way).
    Returns (time-major hs or None, final-state) from
    ``kernel_fn(xs_tm, ms_tm, weights)``; ``weights`` (a tuple, None
    entries allowed) is passed explicitly so that under
    ``batch_sharded_over`` it enters the shard_map as a replicated operand
    whose cotangent is summed over the batch shards."""
    global FUSED_DISPATCH_COUNT
    FUSED_DISPATCH_COUNT += 1
    xs_k = jnp.flip(xs, 0) if reverse else xs
    ms_k = jnp.flip(ms, 0) if reverse else ms
    if _BATCH_MESH is not None:
        from jax.sharding import PartitionSpec as P
        mesh, axis = _BATCH_MESH
        kernel_fn = jax.shard_map(
            kernel_fn, mesh=mesh,
            in_specs=(P(None, axis), P(None, axis), P()),
            out_specs=(P(None, axis), P(axis)), check_vma=False)
    hs_tm, final = kernel_fn(xs_k, ms_k, weights)
    if reverse:
        hs_tm = jnp.flip(hs_tm, 0)
    return hs_tm, final


def _fused_seq_apply(seq, xs, ms, reverse, kernel_fn, weights):
    """``_fused_dispatch`` whose output is a masked batch-major sequence:
    returns (SequenceBatch, final-state)."""
    hs_tm, final = _fused_dispatch(xs, ms, reverse, kernel_fn, weights)
    return _batch_major(hs_tm, seq.lengths), final


def _masked_scan(step, init_carry, xs_time_major, mask_time_major, reverse=False):
    """Scan over time; where mask==0 the carry passes through unchanged."""
    def body(carry, inp):
        x, m = inp
        new_carry = step(carry, x)
        merged = jax.tree_util.tree_map(
            lambda new, old: jnp.where(
                m.reshape((-1,) + (1,) * (new.ndim - 1)) > 0, new, old),
            new_carry, carry)
        return merged, merged
    return jax.lax.scan(body, init_carry, (xs_time_major, mask_time_major),
                        reverse=reverse, unroll=SCAN_UNROLL)


def lstm(seq, w_r, bias=None, check_i=None, check_f=None,
         check_o=None, reverse=False, act="tanh", gate_act="sigmoid",
         state_act="tanh", init_state=None, proj=None, hand_over=None):
    """Whole-sequence LSTM (reference LstmLayer + SequenceToBatch).

    seq.data: [B, T, 4D] pre-projected gate inputs (the reference's lstmemory
    also expects a 4*size mixed input); or, with ``proj`` [in, 4D], the
    [B, T, in] input of that bias-free projection, which the fused kernels
    then compute, and differentiate, themselves (neither the [T, B, 4D]
    gate inputs nor their gradient reaches HBM) and every other path forms
    first.  With ``proj``, ``seq`` may also be the ``LstmOutput`` of a
    fused LSTM below, whose time-major stream the projecting kernels read
    as it is (they ignore a masked step's input; every other path reads
    its ``sequence()``).  bias: [4D].

    ``hand_over`` ("stream" or "final", forward only): the caller's readers
    take the fused kernel's own form, so where that kernel runs the output
    is an ``LstmOutput`` with the time-major stream, or with the final
    carry alone (the kernel's entry whose backward takes no [T, B, D]
    cotangent).  Returns (SequenceBatch of h [B, T, D] or LstmOutput,
    final LstmState).
    """
    global PROJECTED_DISPATCH_COUNT, HANDED_OVER_COUNT
    stream = isinstance(seq, LstmOutput)
    if stream:
        t, b = seq.hs.shape[:2]
    else:
        b, t = seq.data.shape[:2]
    d = w_r.shape[0]
    ms = (jnp.arange(t, dtype=jnp.int32)[:, None]
          < seq.lengths[None, :]).astype(jnp.float32)       # [T, B]
    fused = blocked = projected = False
    if _fused_lstm_enabled():
        # import inside the branch: a broken pallas install must not take
        # the scan fallback down with it
        from paddle_tpu.ops.pallas import lstm as pl_lstm
        from paddle_tpu.ops.pallas import lstm_blocked as pl_lstm_blk
        guard = (_local_batch(b), d, act, gate_act, state_act, init_state)
        projected = proj is not None and pl_lstm.supported(
            *guard, d_in=proj.shape[0])
        fused = projected or pl_lstm.supported(*guard)
        # over-VMEM hidden sizes: the gate-blocked forward keeps the carry
        # in VMEM and fuses the cell while streaming weight blocks (scan-
        # equivalent weight traffic; docs/kernels.md blocked-variant notes)
        blocked = not fused and pl_lstm_blk.supported(*guard)

    if stream and projected:
        xs = seq.hs                 # time-major [T, B, in] already
    else:
        if stream:
            seq = seq.sequence()
        x = seq.data
        if not projected:
            if proj is not None:
                x = matmul(x, proj)
            if bias is not None:
                x = x + bias
        xs = x.transpose(1, 0, 2)   # time-major [T, B, 4D], or [T, B, in]

    if fused or blocked:
        PROJECTED_DISPATCH_COUNT += projected
        hand_over = hand_over if fused and not reverse else None
        if fused:
            kernel = functools.partial(pl_lstm.lstm_fused,
                                       final_only=hand_over == "final")
        else:
            kernel = pl_lstm_blk.lstm_fused_blocked
        weights = (w_r, check_i, check_f, check_o)
        hs_tm, (fh, fc) = _fused_dispatch(
            xs, ms, reverse, lambda x, m, w: kernel(x, m, *w),
            (weights + (proj, bias)) if projected else weights)
        final = LstmState(h=fh, c=fc)
        if hand_over:
            HANDED_OVER_COUNT += 1
            return LstmOutput(hs=hs_tm, final=final,
                              lengths=seq.lengths), final
        return _batch_major(hs_tm, seq.lengths), final

    if init_state is None:
        init_state = LstmState(h=jnp.zeros((b, d), x.dtype),
                               c=jnp.zeros((b, d), x.dtype))

    def step(state, x4):
        return lstm_cell(x4, state, w_r, check_i, check_f, check_o,
                         act, gate_act, state_act)

    final, hs = _masked_scan(step, init_state, xs, ms, reverse=reverse)
    return _batch_major(hs.h, seq.lengths), final


def gru(seq: SequenceBatch, w_gate, w_state, bias=None, reverse=False,
        act="tanh", gate_act="sigmoid", init_state=None):
    """Whole-sequence GRU (reference GatedRecurrentLayer).

    seq.data: [B, T, 3D] pre-projected [update|reset|candidate] inputs.
    """
    b, t, d3 = seq.data.shape
    d = d3 // 3
    x = seq.data if bias is None else seq.data + bias
    xs = x.transpose(1, 0, 2)
    ms = seq.mask().transpose(1, 0)

    if _fused_lstm_enabled():
        from paddle_tpu.ops.pallas import gru as pl_gru
        if pl_gru.supported(_local_batch(b), d, act, gate_act, init_state):
            return _fused_seq_apply(
                seq, xs, ms, reverse,
                lambda x, m, w: pl_gru.gru_fused(x, m, *w),
                (w_gate, w_state))

    if init_state is None:
        init_state = jnp.zeros((b, d), x.dtype)

    def step(h, x3):
        return gru_cell(x3, h, w_gate, w_state, act, gate_act)

    final, hs = _masked_scan(step, init_state, xs, ms, reverse=reverse)
    return _batch_major(hs, seq.lengths), final


def simple_rnn(seq: SequenceBatch, w_r, bias=None, reverse=False, act="tanh",
               init_state=None):
    """Reference RecurrentLayer over a whole sequence; input pre-projected [B,T,D]."""
    b, t, d = seq.data.shape
    x = seq.data if bias is None else seq.data + bias
    xs = x.transpose(1, 0, 2)
    ms = seq.mask().transpose(1, 0)

    if _fused_lstm_enabled():
        from paddle_tpu.ops.pallas import simple_rnn as pl_rnn
        if pl_rnn.supported(_local_batch(b), d, act, init_state):
            return _fused_seq_apply(
                seq, xs, ms, reverse,
                lambda x, m, w: pl_rnn.simple_rnn_fused(x, m, *w), (w_r,))

    if init_state is None:
        init_state = jnp.zeros((b, d), x.dtype)
    final, hs = _masked_scan(lambda h, xt: simple_rnn_cell(xt, h, w_r, act),
                             init_state, xs, ms, reverse=reverse)
    return _batch_major(hs, seq.lengths), final


def recurrent_group(step_fn, inputs, boot_memories, reverse=False, rng=None):
    """The generic dynamic-RNN engine (reference RecurrentGradientMachine
    forward :379 / createInFrameInfo :642).

    step_fn(memories, frame_inputs) -> (new_memories, frame_outputs), where
    `memories` is any pytree of [B, ...] arrays (the reference's memory()
    links with boot layers) and frame_inputs is a pytree of per-step slices.
    With rng= given, step_fn is called as step_fn(memories, frame_inputs,
    step_rng) where step_rng is an INDEPENDENT key per timestep (so dropout
    masks inside the step decorrelate across time).

    inputs: pytree of SequenceBatch sharing lengths; scanned time-major.
    Returns (pytree of SequenceBatch outputs, final memories).

    The reference shrinks the batch as short sequences finish (dynamic
    shapes); here finished sequences' memories are frozen by masking, which
    is numerically identical and keeps shapes static for XLA.
    """
    leaves = jax.tree_util.tree_leaves(inputs, is_leaf=lambda x: isinstance(x, SequenceBatch))
    ref = leaves[0]
    mask_tm = ref.mask().transpose(1, 0)

    xs_tm = jax.tree_util.tree_map(
        lambda sb: sb.data.transpose((1, 0) + tuple(range(2, sb.data.ndim))),
        inputs, is_leaf=lambda x: isinstance(x, SequenceBatch))

    def merge(mem, new_mem, m):
        return jax.tree_util.tree_map(
            lambda new, old: jnp.where(
                m.reshape((-1,) + (1,) * (new.ndim - 1)) > 0, new, old),
            new_mem, mem)

    if rng is not None:
        keys_tm = jax.random.split(rng, ref.data.shape[1])   # [T, 2]

        def body(mem, scanned):
            x, m, k = scanned
            new_mem, out = step_fn(mem, x, k)
            return merge(mem, new_mem, m), out

        final_mem, outs_tm = jax.lax.scan(
            body, boot_memories, (xs_tm, mask_tm, keys_tm), reverse=reverse,
            unroll=SCAN_UNROLL)
    else:
        def body(mem, scanned):
            x, m = scanned
            new_mem, out = step_fn(mem, x)
            return merge(mem, new_mem, m), out

        final_mem, outs_tm = jax.lax.scan(
            body, boot_memories, (xs_tm, mask_tm), reverse=reverse,
            unroll=SCAN_UNROLL)
    outs = jax.tree_util.tree_map(
        lambda o: SequenceBatch(
            data=o.transpose((1, 0) + tuple(range(2, o.ndim)))
            * ref.mask(o.dtype).reshape(ref.mask().shape + (1,) * (o.ndim - 2)),
            lengths=ref.lengths),
        outs_tm)
    return outs, final_mem


def nested_recurrent_group(step_fn, inputs, boot_memories, reverse=False,
                           rng=None):
    """Two-level (sub-sequence) recurrent engine: the OUTER scan iterates
    subsequences (reference RecurrentGradientMachine createInFrameInfo with
    subsequence inputs, RecurrentGradientMachine.cpp:642-712); at outer step
    j, step_fn sees each input's j-th subsequence as a whole SequenceBatch —
    an inner recurrent_group inside the step scans it as usual, so the pair
    compiles to a nested lax.scan with fully static shapes.

    step_fn(memories, frames[, step_rng]) -> (new_memories, outputs) where
    frames is a tuple of SequenceBatch (one per NestedSequenceBatch input).
    Outer memories are [B, ...] arrays frozen at padded outer steps (the
    masking equivalent of the reference's batch shrinking).

    Step outputs that are [B, ...] arrays stack into a SequenceBatch over the
    outer axis (one row per subsequence); step outputs that are themselves
    SequenceBatch stack into a NestedSequenceBatch — the reference's
    seq-level-output-in-nested-group semantics.
    """
    inputs = tuple(inputs)
    ref = inputs[0]
    outer_mask_sm = ref.outer_mask().transpose(1, 0)          # [S, B]
    datas_sm = tuple(
        n.data.transpose((1, 0) + tuple(range(2, n.data.ndim)))
        for n in inputs)                                       # each [S, B, T, ...]
    ilens_sm = tuple(n.inner_lengths.transpose(1, 0) for n in inputs)

    def merge(mem, new_mem, m):
        return jax.tree_util.tree_map(
            lambda new, old: jnp.where(
                m.reshape((-1,) + (1,) * (new.ndim - 1)) > 0, new, old),
            new_mem, mem)

    def body(mem, scanned):
        if rng is not None:
            datas, ilens, m, k = scanned
            frames = tuple(SequenceBatch(data=d, lengths=l)
                           for d, l in zip(datas, ilens))
            new_mem, out = step_fn(mem, frames, k)
        else:
            datas, ilens, m = scanned
            frames = tuple(SequenceBatch(data=d, lengths=l)
                           for d, l in zip(datas, ilens))
            new_mem, out = step_fn(mem, frames)
        return merge(mem, new_mem, m), out

    S = ref.data.shape[1]
    if rng is not None:
        xs = (datas_sm, ilens_sm, outer_mask_sm, jax.random.split(rng, S))
    else:
        xs = (datas_sm, ilens_sm, outer_mask_sm)
    final_mem, outs_sm = jax.lax.scan(body, boot_memories, xs,
                                      reverse=reverse)

    omask = ref.outer_mask()                                   # [B, S]

    def collect(o):
        # after scan-stacking, a per-step SequenceBatch output has fields
        # data [S, B, T, ...], lengths [S, B]
        if isinstance(o, SequenceBatch):
            data = o.data.transpose((1, 0) + tuple(range(2, o.data.ndim)))
            inner = (o.lengths.transpose(1, 0)
                     * ref.outer_mask(o.lengths.dtype))
            nsb = NestedSequenceBatch(data=data,
                                      outer_lengths=ref.outer_lengths,
                                      inner_lengths=inner)
            return NestedSequenceBatch(
                data=data * nsb.inner_mask(data.dtype).reshape(
                    nsb.inner_mask().shape + (1,) * (data.ndim - 3)),
                outer_lengths=ref.outer_lengths, inner_lengths=inner)
        data = o.transpose((1, 0) + tuple(range(2, o.ndim)))   # [B, S, ...]
        data = data * omask.astype(data.dtype).reshape(
            omask.shape + (1,) * (data.ndim - 2))
        return SequenceBatch(data=data, lengths=ref.outer_lengths)

    outs = jax.tree_util.tree_map(
        collect, outs_sm, is_leaf=lambda x: isinstance(x, SequenceBatch))
    return outs, final_mem


def bidirectional(fwd_out: SequenceBatch, bwd_out: SequenceBatch) -> SequenceBatch:
    """Concat forward and reverse passes (reference bidirectional_lstm)."""
    return SequenceBatch(
        data=jnp.concatenate([fwd_out.data, bwd_out.data], axis=-1),
        lengths=fwd_out.lengths)


# ------------------------------------------------- multi-dimensional LSTM

def md_lstm_2d(x5, w_r_row, w_r_col, check_i_row=None, check_i_col=None,
               check_f_row=None, check_f_col=None, check_o=None,
               act="tanh", gate_act="sigmoid", state_act="tanh"):
    """2-D multi-dimensional LSTM (reference MDLstmLayer.cpp:158-178,
    REGISTER_LAYER(mdlstmemory)): each cell sees two predecessors (top and
    left), each with its own forget gate and recurrent weights:

      state = actIn(a)*actGate(ig) + sum_j actGate(fg_j)*state_prev_j
      gates = x5 + sum_j h_prev_j @ w_r_j (+ peepholes)

    x5: [B, H, W, 5*D] pre-projected (a, ig, fg_row, fg_col, og — the
    reference's size*(3+numDims) IG layout for numDims=2).
    w_r_row/w_r_col: [D, 5*D] recurrent weights for the top/left neighbor.

    TPU mapping: scan over rows carrying the previous row's (h, c)
    [B, W, D]; the inner column scan carries (h_left, c_left).  XLA
    unrolls both into static-shape loops (no dynamic control flow).
    """
    b, h, w, d5 = x5.shape
    d = d5 // 5
    act_f, gate_f, state_f = (activations.get(act), activations.get(gate_act),
                              activations.get(state_act))
    zeros_bd = jnp.zeros((b, d), x5.dtype)

    def cell(x, h_top, c_top, h_left, c_left):
        gates = (x + matmul(h_top, w_r_row) + matmul(h_left, w_r_col))
        a, ig, fg_r, fg_c, og = jnp.split(gates, 5, axis=-1)
        if check_i_row is not None:
            ig = ig + c_top * check_i_row
        if check_i_col is not None:
            ig = ig + c_left * check_i_col
        if check_f_row is not None:
            fg_r = fg_r + c_top * check_f_row
        if check_f_col is not None:
            fg_c = fg_c + c_left * check_f_col
        c = (act_f(a) * gate_f(ig) + gate_f(fg_r) * c_top
             + gate_f(fg_c) * c_left)
        if check_o is not None:
            og = og + c * check_o
        hh = gate_f(og) * state_f(c)
        return hh, c

    def row_step(prev_row, x_row):
        # prev_row: (h_top [B, W, D], c_top [B, W, D]); x_row: [B, W, 5D]
        h_top, c_top = prev_row

        def col_step(carry, inp):
            h_left, c_left = carry
            x, ht, ct = inp
            hh, cc = cell(x, ht, ct, h_left, c_left)
            return (hh, cc), (hh, cc)

        xs = (x_row.transpose(1, 0, 2), h_top.transpose(1, 0, 2),
              c_top.transpose(1, 0, 2))
        _, (hs, cs) = jax.lax.scan(col_step, (zeros_bd, zeros_bd), xs)
        h_row = hs.transpose(1, 0, 2)       # [B, W, D]
        c_row = cs.transpose(1, 0, 2)
        return (h_row, c_row), h_row

    zeros_row = jnp.zeros((b, w, d), x5.dtype)
    _, out = jax.lax.scan(row_step, (zeros_row, zeros_row),
                          x5.transpose(1, 0, 2, 3))
    return out.transpose(1, 0, 2, 3)        # [B, H, W, D]

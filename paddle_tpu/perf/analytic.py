"""Analytic bench runner: cost + roofline snapshot for every bench family.

For each family in bench.py the factory's AOT hook (`extras["lower"]`,
a zero-arg callable returning the jitted step's `jax.stages.Lowered`) is
compiled on the CURRENT backend — the CPU backend when no TPU answers —
and fed through `perf.cost.extract` and `perf.roofline.predict`.  The
result is one JSON snapshot (`BENCH_ANALYTIC_r06.json`) holding, per
family: XLA-model FLOPs, bytes accessed, arithmetic intensity, the HLO
op histogram / fusion count, and the v5e-roofline predicted step time,
predicted MFU and named bottleneck.  No program is ever executed, so a
wedged chip cannot block the snapshot ("no chip window -> partial
evidence").

`scripts/perf_report.py --analytic-diff old.json new.json` diffs two
snapshots structurally and exits non-zero when a change de-fuses a step
or inflates bytes-accessed beyond threshold (see `analytic_diff` there).

Usage:
  python bench.py --analytic [--families a,b] [--out PATH]
  python -m paddle_tpu.perf.analytic [...]
  python -m paddle_tpu.scripts.bench_sweep --analytic   (same snapshot)
"""

import argparse
import gc
import json
import os
import sys
import time

from paddle_tpu.perf import cost, roofline

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DEFAULT_OUT = os.path.join(_REPO, "BENCH_ANALYTIC_r06.json")

# The family registry moved to paddle_tpu/analysis/roots.py — ONE list
# shared with the static invariant analyzer, so a new bench family
# cannot add a jitted step the analyzer doesn't see (FAMILY_ROOTS maps
# every family to the jit roots its extras["lower"] traces; the drift
# test in tests/test_analysis.py keeps registry and code joined).  The
# name stays importable from here for every existing consumer
# (scripts/perf_report.py, tests/test_perf_analytic.py).
from paddle_tpu.analysis.roots import FAMILIES  # noqa: E402,F401


def _log(msg):
    print(f"[analytic] {msg}", file=sys.stderr, flush=True)


# ----------------------------------------------------- fusion-proof gate

def chain_buffer_instrs(hlo_text, num_rows, t_span, dkv):
    """Instructions whose RESULT materializes a full-chain KV buffer —
    the PR-3 de-fusion detector run in REVERSE.

    The reference paged-decode step gathers every row's block chain into
    a contiguous ``[S, blocks_per_row, bs, Dkv]`` HBM buffer (and its
    ``[S, T, Dkv]`` reshape) before attending; the fused Pallas kernel
    walks the block table in place and that buffer must not exist.  An
    instruction matches when its result shape leads with ``num_rows``
    and holds exactly ``num_rows * t_span * dkv`` elements — the chain
    buffer's signature under any dim factoring (the per-layer block
    POOL never matches: it leads with num_blocks, not S).  Returns the
    offending instruction lines (empty = fusion proven).
    """
    import re
    from paddle_tpu.perf import cost as _cost
    target = int(num_rows) * int(t_span) * int(dkv)
    shape_re = re.compile(r"\b[a-z][a-z0-9]*\[([0-9,]+)\]")
    hits = []
    for line in hlo_text.splitlines():
        m = _cost._INSTR_RE.match(line)
        if not m:
            continue
        rhs = m.group(1)
        op = _cost._op_of(rhs)
        if op is None or op in _cost._SKIP_OPS:
            continue
        # result type: the leading whitespace-free token, or the
        # balanced-paren tuple type for multi-result instructions
        if rhs.startswith("("):
            depth, ty = 0, rhs
            for i, ch in enumerate(rhs):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    ty = rhs[:i + 1]
                    break
        else:
            ty = rhs.split(None, 1)[0]
        for dims in shape_re.findall(ty):
            shape = [int(d) for d in dims.split(",")]
            n = 1
            for d in shape:
                n *= d
            if shape and shape[0] == int(num_rows) and n == target:
                hits.append(line.strip())
                break
    return hits


def score_matrix_instrs(hlo_text, tq, tk):
    """Instructions whose RESULT materializes an attention SCORE matrix:
    a float-typed buffer whose trailing two dims are exactly
    ``(tq, tk)`` — ``[.., Tp, Tp]`` for the batched causal prefill,
    ``[.., K, T]`` for the unified chunked step's reference path.  The
    flash/chunk kernels compute scores block-by-block in VMEM, so with
    them engaged NO such buffer may exist in the HLO (and the reference
    path must trip this same detector — the gate is tested in reverse).
    Returns the offending instruction lines (empty = proven)."""
    import re
    from paddle_tpu.perf import cost as _cost
    shape_re = re.compile(r"\b(f32|bf16|f16|f64)\[([0-9,]+)\]")
    hits = []
    for line in hlo_text.splitlines():
        m = _cost._INSTR_RE.match(line)
        if not m:
            continue
        rhs = m.group(1)
        op = _cost._op_of(rhs)
        if op is None or op in _cost._SKIP_OPS:
            continue
        if rhs.startswith("("):
            depth, ty = 0, rhs
            for i, ch in enumerate(rhs):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    ty = rhs[:i + 1]
                    break
        else:
            ty = rhs.split(None, 1)[0]
        for _dt, dims in shape_re.findall(ty):
            shape = [int(d) for d in dims.split(",")]
            if len(shape) >= 2 and shape[-2] == int(tq) \
                    and shape[-1] == int(tk):
                hits.append(line.strip())
                break
    return hits


def assert_prefill_flash(hlo_text, tp):
    """Raise AssertionError when a batched causal prefill HLO still
    materializes the ``[Tp, Tp]`` score matrix (the flash routing was
    supposed to be ON)."""
    hits = score_matrix_instrs(hlo_text, tp, tp)
    if hits:
        raise AssertionError(
            f"prefill materializes a [{tp}, {tp}] score matrix — the "
            f"flash routing did not engage:\n  " + "\n  ".join(hits[:4]))


def assert_decode_fused(hlo_text, num_rows, t_span, dkv):
    """Raise AssertionError when the paged-decode HLO still materializes
    the full-chain gather buffer (kernels were supposed to be ON)."""
    hits = chain_buffer_instrs(hlo_text, num_rows, t_span, dkv)
    if hits:
        raise AssertionError(
            f"paged decode step materializes a full-chain "
            f"[{num_rows}, {t_span}, {dkv}]-element KV buffer — the "
            f"fused kernel did not engage:\n  " + "\n  ".join(hits[:4]))


# -------------------------------------------------- quantized-serving gates

def widened_kv_instrs(hlo_text, num_rows, t_span, dkv):
    """Instructions whose RESULT materializes a widened (FLOAT) full
    KV view of an int8 cache: a float-typed buffer leading with
    ``num_rows`` and holding exactly ``num_rows * t_span * dkv``
    elements.  The int8-KV reference path dequantizes the whole
    gathered stripe into exactly such a buffer before attending; the
    fused kernels widen block-by-block in registers, so with them
    engaged NO such buffer may exist.  (The int8 cache itself never
    matches: the dtype filter is float-only, and the paged pool leads
    with num_blocks, not S.)  Returns the offending lines."""
    import re
    from paddle_tpu.perf import cost as _cost
    target = int(num_rows) * int(t_span) * int(dkv)
    shape_re = re.compile(r"\b(f32|bf16|f16|f64)\[([0-9,]+)\]")
    hits = []
    for line in hlo_text.splitlines():
        m = _cost._INSTR_RE.match(line)
        if not m:
            continue
        rhs = m.group(1)
        op = _cost._op_of(rhs)
        if op is None or op in _cost._SKIP_OPS:
            continue
        if rhs.startswith("("):
            depth, ty = 0, rhs
            for i, ch in enumerate(rhs):
                depth += ch == "("
                depth -= ch == ")"
                if depth == 0:
                    ty = rhs[:i + 1]
                    break
        else:
            ty = rhs.split(None, 1)[0]
        for _dt, dims in shape_re.findall(ty):
            shape = [int(d) for d in dims.split(",")]
            n = 1
            for d in shape:
                n *= d
            if shape and shape[0] == int(num_rows) and n == target:
                hits.append(line.strip())
                break
    return hits


def assert_kv_quantized(hlo_text, num_rows, t_span, dkv):
    """Raise AssertionError when an int8-KV decode HLO still widens the
    whole cache into a float [num_rows, t_span, dkv]-element buffer
    (the kernels were supposed to dequantize in registers)."""
    hits = widened_kv_instrs(hlo_text, num_rows, t_span, dkv)
    if hits:
        raise AssertionError(
            f"int8-KV decode step materializes a widened float "
            f"[{num_rows}, {t_span}, {dkv}]-element KV buffer — the "
            f"in-register dequant did not engage:\n  "
            + "\n  ".join(hits[:4]))


def widened_prefill_kv_instrs(hlo_text, b, tp, dkv):
    """``convert`` instructions that widen the WHOLE just-quantized
    prefill cache back to float: an f32-result convert with an s8
    operand holding exactly ``b * tp * dkv`` elements and leading with
    ``b``.  The int8-KV reference prefill dequantizes each layer's full
    K and V set (``_kv_view``) into exactly such a buffer before
    attending; ``flash_attention_quant`` widens int8 blocks in
    registers, so with it engaged NO such convert may exist.  (The
    quantize direction never matches — those converts RESULT in s8; the
    in-kernel interpret-mode converts never match — they are
    block-shaped, leading with 1, holding blk_k * dh < b * tp * dkv
    elements.)  Returns the offending lines."""
    import re
    from paddle_tpu.perf import cost as _cost
    target = int(b) * int(tp) * int(dkv)
    shape_re = re.compile(r"^f32\[([0-9,]+)\]")
    # the XLA of jax 0.9.0 prints operands by NAME only
    # (``convert(%bitcast.57)``), so the operand's element type comes from
    # the instruction that defines it
    def_re = re.compile(r"^\s*(?:ROOT\s+)?(%[^\s=]+) = (\w+)\[")
    operand_re = re.compile(r"convert\((%[^\s,)]+)\)")
    lines = hlo_text.splitlines()
    dtype_of = {m.group(1): m.group(2)
                for m in map(def_re.match, lines) if m}
    hits = []
    for line in lines:
        m = _cost._INSTR_RE.match(line)
        if not m:
            continue
        rhs = m.group(1)
        if _cost._op_of(rhs) != "convert":
            continue
        om = operand_re.search(rhs)
        if not om or dtype_of.get(om.group(1)) != "s8":
            continue
        sm = shape_re.match(rhs)
        if not sm:
            continue
        shape = [int(d) for d in sm.group(1).split(",")]
        n = 1
        for d in shape:
            n *= d
        if shape[0] == int(b) and n == target:
            hits.append(line.strip())
    return hits


def assert_prefill_kv_quantized(hlo_text, b, tp, dkv):
    """Raise AssertionError when an int8-KV batched prefill HLO still
    widens the whole per-layer cache into a float [b, tp, dkv]-element
    buffer (``flash_attention_quant`` was supposed to stream the int8
    bytes and widen block-by-block in registers)."""
    hits = widened_prefill_kv_instrs(hlo_text, b, tp, dkv)
    if hits:
        raise AssertionError(
            f"int8-KV prefill widens the whole cache into float "
            f"[{b}, {tp}, {dkv}]-element buffers before attending — "
            f"the quantized flash prefill did not engage:\n  "
            + "\n  ".join(hits[:4]))


def entry_param_types(hlo_text):
    """(dtype, dims-tuple) of every ENTRY parameter, parsed from the
    module's ``entry_computation_layout`` — the program's resident
    interface (what is fed and carried between steps)."""
    import re
    m = re.search(r"entry_computation_layout=\{\((.*?)\)->", hlo_text,
                  re.S)
    if not m:
        return []
    out = []
    for dt, dims in re.findall(r"([a-z][a-z0-9]*)\[([0-9,]*)\]",
                               m.group(1)):
        out.append((dt, tuple(int(d) for d in dims.split(",") if d)))
    return out


def assert_weights_quantized(hlo_text, weight_shapes, float_shapes=()):
    """Raise AssertionError unless every quantized weight enters the
    compiled step as an s8 ENTRY PARAMETER and no EXTRA float parameter
    of that shape exists — i.e. no fp32 (or bf16) weight copy is ever
    RESIDENT across steps; the dequantized view lives only inside the
    step, fused into each consuming matmul's operand read on TPU.
    COUNT-based per shape: ``weight_shapes``
    (quant.weights.quantized_weight_shapes) sets how many s8 params a
    shape needs, and ``float_shapes``
    (quant.weights.float_leaf_shapes) allows the tree's legitimate
    float leaves — so a non-weight f32 param whose shape collides with
    a quantized weight's (e.g. the positional table vs an FFN weight
    at max_len == dff) never reads as a widened copy.  The fp32 twin
    step must FAIL this gate (its weights enter f32, no s8 params) —
    the reverse test the serving_quant postcheck runs."""
    import collections
    params = entry_param_types(hlo_text)
    s8 = collections.Counter(dims for dt, dims in params if dt == "s8")
    fl = collections.Counter(dims for dt, dims in params
                             if dt in ("f32", "bf16", "f16", "f64"))
    need = collections.Counter(tuple(int(d) for d in s)
                               for s in weight_shapes)
    allow = collections.Counter(tuple(int(d) for d in s)
                                for s in float_shapes)
    for shape, n in need.items():
        if s8[shape] < n:
            raise AssertionError(
                f"only {s8[shape]} of {n} quantized weights of shape "
                f"{list(shape)} enter the step as s8 parameters — the "
                "int8 tree was not threaded through")
        if fl[shape] > allow[shape]:
            raise AssertionError(
                f"{fl[shape]} float parameter(s) of quantized-weight "
                f"shape {list(shape)} exist but only {allow[shape]} "
                "float leaf(s) of that shape are in the tree — a "
                "widened weight copy is being fed to the step")


def predicted_decode_step_bytes(params, s, t_span, num_heads,
                                kv_dtype="float32"):
    """First-principles HBM traffic of ONE serving decode step — the
    quantized-serving bytes model (the XLA-CPU cost model cannot show
    the int8 win: it materializes the dequant converts the TPU backend
    fuses into the MXU/kernel operand reads, so like PR 10's fused-
    kernel row the prediction composes declared traffic instead).

    Terms, each read/written exactly once per step on the memory-bound
    path: every trunk weight as STORED (int8 data + f32 scales for a
    quantized tree — quant.weights.param_bytes), each of the S rows'
    K/V stripe streamed once per layer (the fused kernels' declared
    stream, including the int8 scale sidecar), one position's K/V
    written per row per layer, the inter-layer activations, and the
    token-ids-in / logits-out io.  Returns the byte total; the
    serving_quant postcheck gates int8 vs f32 at >= 35% reduction."""
    from paddle_tpu.quant import kv as kvq
    from paddle_tpu.quant import weights as qw
    enc = params["enc"]
    layers = len(enc)
    vocab, d = qw.weight_shape(params["src_emb"])
    dkv = qw.weight_shape(enc[0]["attn"]["wk"])[1]
    hkv = dkv // (d // num_heads)
    kv_isz = 1 if kv_dtype == "int8" else 4
    sidecar = 2 * s * t_span * hkv * 4 if kv_dtype == "int8" else 0
    kv_read = layers * (2 * s * t_span * dkv * kv_isz + sidecar)
    kv_write = layers * s * kvq.kv_bytes_per_position(dkv, hkv, kv_dtype)
    acts = layers * 2 * s * d * 4          # residual stream in/out
    io = s * 4 + s * vocab * 4             # ids in, logits out
    return qw.param_bytes(params) + kv_read + kv_write + acts + io


def predicted_prefill_bytes(params, b, tp, num_heads,
                            kv_dtype="float32"):
    """First-principles HBM traffic of ONE batched causal prefill of
    ``b`` prompts x ``tp`` positions — the serving_quant_prefill bytes
    model, ``predicted_decode_step_bytes``'s ingestion-side twin.

    Terms: every trunk weight as STORED (int8 data + f32 scales for a
    quantized tree), each layer's freshly written K/V set streamed back
    through attention once per QUERY head (the flash kernels' declared
    stream — GQA re-reads the kv head's stripe per group member; int8
    streams 1 byte/value + the f32 per-(position, head) scale sidecar
    per block row, f32 streams 4), the per-position K/V cache write as
    stored, the inter-layer activations, and the ids-in / hidden-out
    io.  The int8 win the >= 35% acceptance bar gates: the attention
    re-stream — the term that grows with Tp^0 * heads — drops ~4x, and
    the cache write drops ~4x, while weights (int8 tree) drop ~4x too.
    (The XLA-CPU cost model cannot show any of this: it materializes
    the widened converts the quant kernel keeps in registers.)"""
    from paddle_tpu.quant import kv as kvq
    from paddle_tpu.quant import weights as qw
    enc = params["enc"]
    layers = len(enc)
    _vocab, d = qw.weight_shape(params["src_emb"])
    dkv = qw.weight_shape(enc[0]["attn"]["wk"])[1]
    dh = d // num_heads
    hkv = dkv // dh
    # per query head, per position: int8 value bytes + the f32 scale
    # rides the same block stream (flash_attention_quant CostEstimate)
    per_pos = (dh * 1 + 4) if kv_dtype == "int8" else dh * 4
    kv_stream = layers * 2 * b * num_heads * tp * per_pos
    kv_write = layers * b * tp * kvq.kv_bytes_per_position(
        dkv, hkv, kv_dtype)
    acts = layers * 2 * b * tp * d * 4     # residual stream in/out
    io = b * tp * 4 + b * tp * d * 4       # ids in, hidden out
    return qw.param_bytes(params) + kv_stream + kv_write + acts + io


def predicted_spec_bytes_per_token(layers, d, dff, vocab, s, t_span,
                                   num_heads, draft_layers, k,
                                   acceptance, dkv=None):
    """First-principles HBM traffic per EMITTED token, speculative vs
    plain decode — the serving_speculative bytes model (docs/serving.md
    "Speculative decoding").  Returns ``(spec, nonspec)`` byte totals.

    The target's verify step streams each row's K/V stripe ONCE no
    matter how many query lanes ride it (the Tq=chunk kernels —
    ``kernel_cost(tq=k+1)`` differs from ``tq=1`` only by the extra
    q/o lanes and the all-lanes vocab projection), so verifying k
    drafts costs nearly the same bytes as decoding one token.  The
    draft rollout is the price: k sequential passes, each streaming
    the draft's weights and its own K/V.  With expected emitted tokens
    ``E = sum(a^i, i=0..k) = (1 - a^(k+1)) / (1 - a)`` per verify
    step, spec wins iff ``(target_step + k * draft_pass) / E <
    target_step`` — a cheap-enough draft and a real acceptance rate,
    which is why the adversarial direction (a = 0, E = 1) must predict
    a REGRESSION: the model is gated in both directions by the
    serving_speculative postcheck."""
    from paddle_tpu.ops.pallas.decode_attention import kernel_cost
    dkv = d if dkv is None else dkv

    def weight_bytes(n_layers, with_embed=True):
        trunk = n_layers * (4 * d * d + 2 * d * dff + 9 * d) * 4
        emb = (2 * vocab * d + t_span * d + 2 * d) * 4 if with_embed \
            else 0
        return trunk + emb

    def step_bytes(n_layers, tq, vocab_lanes):
        attn = n_layers * kernel_cost(s, t_span, d, dkv,
                                      tq=tq).bytes_accessed
        kv_write = n_layers * 2 * s * tq * dkv * 4
        acts = n_layers * 2 * s * tq * d * 4
        io = s * tq * 4 + s * vocab_lanes * vocab * 4
        return weight_bytes(n_layers) + attn + kv_write + acts + io

    a = min(max(float(acceptance), 0.0), 1.0 - 1e-9)
    emitted = (1.0 - a ** (k + 1)) / (1.0 - a)
    verify = step_bytes(layers, k + 1, k + 1)
    draft = k * step_bytes(draft_layers, 1, 1)
    nonspec = step_bytes(layers, 1, 1)
    return (verify + draft) / emitted, float(nonspec)


def predicted_sharded_step_bytes(layers, d, dff, vocab, s, t_span,
                                 num_heads, shards, dkv=None,
                                 kv_dtype="float32",
                                 weight_dtype="float32", chunk=1,
                                 replicate_weights=False):
    """First-principles PER-CHIP HBM traffic of one tensor-parallel
    chunked decode step — the serving_sharded bytes model
    (docs/serving.md "Sharded decode").  Returns a breakdown dict:
    ``total`` (per-chip bytes), ``weights``, ``kv``, ``acts_io``, and
    ``collective`` (the wire bytes of the gather seams).

    The sharding policy is ``parallel.sharding.lm_decode_param_specs``'s,
    priced term by term: wq/wk/wv shard their out-feature axis and
    src_emb its vocab axis (each chip streams 1/n of those weights);
    the K/V pool shards its trailing Dkv axis (1/n of the read/write
    stream per chip).  Everything bit-exactness forces to stay
    REPLICATED — wo, the FFN, LNs/biases, the positional table — is
    streamed in full on every chip: the model never pretends the whole
    step scales 1/n.  The collective term prices the seams honestly as
    ring traffic (in + out ~= 2 * (n-1)/n * payload per chip): one
    attention-output all-gather of [s, chunk, d] per layer, one logits
    all-gather of [s, vocab], one embedding psum of [s, chunk, d].

    ``replicate_weights=True`` is the adversarial twin: same mesh, same
    collectives, but every weight streamed in full on every chip — the
    serving_sharded postcheck requires THAT prediction to FAIL the
    reduction gate (weight replication must never look like a win), and
    ``shards=1`` collapses to the single-chip step (no collectives) the
    sharded prediction is gated against in the other direction."""
    n = max(1, int(shards))
    dkv = d if dkv is None else dkv
    hkv = dkv // (d // num_heads)
    wsz = 1 if weight_dtype == "int8" else 4
    # int8 weights carry a per-out-channel f32 scale; the scale shards
    # with its weight's out axis (the emb scale [1, d] is replicated)
    ssz = 4 if weight_dtype == "int8" else 0
    w_shard = layers * ((d * d + 2 * d * dkv) * wsz
                        + (d + 2 * dkv) * ssz) \
        + vocab * d * wsz + vocab * 0 * ssz
    w_repl = layers * ((d * d + 2 * d * dff) * wsz
                       + (d + 2 * dff) * ssz + 9 * d * 4) \
        + t_span * d * 4 + 2 * d * 4 + d * ssz
    if replicate_weights or n == 1:
        weights = w_shard + w_repl
    else:
        weights = w_shard / n + w_repl
    kv_isz = 1 if kv_dtype == "int8" else 4
    sidecar = 2 * s * t_span * hkv * 4 if kv_dtype == "int8" else 0
    kv_read = layers * (2 * s * t_span * dkv * kv_isz + sidecar)
    kv_write = layers * s * chunk * (2 * dkv * kv_isz
                                     + (2 * hkv * 4 if kv_isz == 1
                                        else 0))
    kv = (kv_read + kv_write) / n      # the pool ALWAYS shards its Dkv
    acts = layers * 2 * s * chunk * d * 4
    io = s * chunk * 4 + s * vocab * 4
    ring = 2.0 * (n - 1) / n if n > 1 else 0.0
    collective = ring * (layers * s * chunk * d * 4      # att gathers
                         + s * vocab * 4                 # logits gather
                         + s * chunk * d * 4)            # embed psum
    total = weights + kv + acts + io + collective
    return {"total": float(total), "weights": float(weights),
            "kv": float(kv), "acts_io": float(acts + io),
            "collective": float(collective)}


# ------------------------------------------------ hierarchical-KV model

# Scheduling cycles a host-tier restore spends off the device: the
# probe-and-claim admission pass that defers the request, the transfer
# landing between two steps, and the commit-and-reseat pass.  Priced in
# dispatch floors (below) — the restore never runs device compute.
RESTORE_CYCLES = 3
# Per-step host dispatch floor (ms): the irreducible Python/runtime cost
# of launching one jitted step, which the pure FLOPs/bytes roofline
# ignores.  Dominant for tiny chunk steps, noise for real trunks — which
# is exactly why a SHORT prefix should recompute (a couple of cheap
# chunk steps) while a LONG one should restore (dozens of steps vs one
# host-link stream).
STEP_DISPATCH_MS = 0.05


def predicted_restore_ms(covered, layers, dkv, kv_heads,
                         kv_dtype="float32", chip="v5e"):
    """First-principles wall cost of restoring a ``covered``-position
    spilled prefix chain from the host tier (docs/serving.md
    "Hierarchical KV"): the chain's serialized payload — int8 data plus
    f32 scale sidecars on a quantized engine
    (``quant.kv.kv_bytes_per_position``), times ``layers`` — streamed
    once over the host link (``ChipSpec.host_link_bytes_per_s``), plus
    ``RESTORE_CYCLES`` scheduling cycles at the dispatch floor.  The
    restore-vs-recompute router compares this against
    ``predicted_recompute_ms`` at the SAME chip spec; the
    serving_kv_spill postcheck gates the comparison in both
    directions."""
    from paddle_tpu.quant import kv as kvq
    spec = roofline.SPECS[chip] if isinstance(chip, str) else chip
    payload = float(covered) * int(layers) \
        * kvq.kv_bytes_per_position(dkv, kv_heads, kv_dtype)
    return RESTORE_CYCLES * STEP_DISPATCH_MS \
        + payload / spec.host_link_bytes_per_s * 1e3


# Effective socket bandwidth for a cross-replica KV handoff blob
# (serving/transfer.py).  Datacenter 25GbE at ~realistic goodput is the
# conservative fleet floor (loopback in the smoke is far faster), so
# the handoff-vs-recompute router errs toward recompute — same bias the
# host-link constant gives the local restore pair.
HANDOFF_LINK_BYTES_PER_S = 3e9
# Scheduling cycles a handoff spends beyond the restore's three: the
# source-side export waiting for its between-steps seam, and the HTTP
# round trip's request leg.
HANDOFF_CYCLES = RESTORE_CYCLES + 2


def predicted_handoff_ms(covered, layers, dkv, kv_heads,
                         kv_dtype="float32", chip="v5e"):
    """First-principles wall cost of HANDING OFF a ``covered``-position
    prefix chain from a peer replica (docs/serving.md "Disaggregated
    serving"): the same serialized payload as a local restore, streamed
    once over the handoff socket (``HANDOFF_LINK_BYTES_PER_S``) AND
    once over the receiver's host link, plus ``HANDOFF_CYCLES``
    scheduling cycles at the dispatch floor.  The receive path compares
    this against ``predicted_recompute_ms`` at the SAME chip spec
    before fetching anything — the serving_disagg postcheck gates the
    comparison in both directions, exactly as serving_kv_spill gates
    the local restore pair."""
    from paddle_tpu.quant import kv as kvq
    spec = roofline.SPECS[chip] if isinstance(chip, str) else chip
    payload = float(covered) * int(layers) \
        * kvq.kv_bytes_per_position(dkv, kv_heads, kv_dtype)
    return HANDOFF_CYCLES * STEP_DISPATCH_MS \
        + payload / HANDOFF_LINK_BYTES_PER_S * 1e3 \
        + payload / spec.host_link_bytes_per_s * 1e3


def predicted_recompute_ms(covered, param_count, param_bytes,
                           prefill_chunk, chip="v5e"):
    """First-principles wall cost of RECOMPUTING a ``covered``-position
    prefix through the unified chunked-prefill step: ``ceil(covered /
    (K-1))`` chunk steps, each streaming the trunk's stored weight
    bytes (``param_bytes`` — int8 data + scales on a quantized tree)
    and together spending ``2 * covered * param_count`` FLOPs, priced
    by the roofline's two ceilings plus the per-step dispatch floor.
    The companion term ``predicted_restore_ms`` replaces all of this
    with one host-link stream — long prefixes amortize the restore's
    fixed cycles over dozens of avoided chunk steps, short ones
    don't."""
    lanes = max(1, int(prefill_chunk) - 1)
    steps = -(-int(covered) // lanes)
    r = roofline.predict(2.0 * float(covered) * float(param_count),
                         float(steps) * float(param_bytes), chip)
    return steps * STEP_DISPATCH_MS + r["predicted_ms"]


def _import_bench():
    if _REPO not in sys.path:
        sys.path.insert(0, _REPO)
    import bench
    return bench


# Families whose capture needs a multi-device host platform (the
# sharded-serving mesh).  XLA's CPU device count is fixed at backend
# init, and forcing it for the WHOLE snapshot perturbs every
# single-device family's HLO (the CPU backend re-partitions its thread
# pool per device — alexnet grows `call` ops under a 2-device flag), so
# when THIS process lacks the devices these families are captured in a
# subprocess that sets the flag for itself alone.
MESH_FAMILIES = {"serving_sharded": 2}


def _capture_subprocess(name, model, batch, devices):
    """Run one family's capture under a forced ``devices``-way host
    platform in a child ``bench.py --analytic`` process and return its
    row (an error row on any child failure — same isolation contract
    as ``capture``)."""
    import subprocess
    import tempfile
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count="
                        f"{devices}").strip()
    fd, out = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    try:
        proc = subprocess.run(
            [sys.executable, os.path.join(_REPO, "bench.py"),
             "--analytic", "--families", name, "--out", out],
            env=env, capture_output=True, text=True, timeout=1800)
        with open(out) as f:
            snap = json.load(f)
        return snap["families"][name]
    except Exception as e:   # noqa: BLE001 — per-family isolation
        tail = ""
        try:
            tail = proc.stderr[-300:]
        except Exception:    # noqa: BLE001
            pass
        return {"model": model, "batch": batch,
                "error": f"mesh-capture subprocess failed: "
                         f"{type(e).__name__}: {e} {tail}"[:500]}
    finally:
        if os.path.exists(out):
            os.unlink(out)


def capture(name, model, batch=None, chips=("v5e", "v5p")):
    """Build one bench family, AOT-compile its step, extract cost +
    roofline rows.  Returns the snapshot row (with an "error" key instead
    of numbers if the family fails — partial evidence beats none)."""
    bench = _import_bench()
    factory, default_batch = bench._BENCHES[model]
    batch = int(batch if batch is not None else default_batch)
    t0 = time.perf_counter()
    # tell build-time-measuring factories (trainer_prefetch) that only the
    # AOT hook will be consumed — nothing may execute during the snapshot
    prev = os.environ.get("BENCH_ANALYTIC_BUILD")
    os.environ["BENCH_ANALYTIC_BUILD"] = "1"
    try:
        built = factory(batch)
        run, model_flops, _baseline, metric = built[:4]
        extras = built[4] if len(built) > 4 else {}
        lower = extras.get("lower")
        if lower is None:
            raise RuntimeError(f"bench family {model!r} exposes no "
                               "extras['lower'] AOT hook")
        compiled = lower().compile()
        # inside the isolation net: cost_analysis()/as_text() raise
        # Unimplemented on some backend/jax combinations (the documented
        # BENCH_PLATFORM override), and one family's extraction failure
        # must degrade to an error row, not kill the snapshot
        row = cost.extract(compiled)
        # structural acceptance gate hook: a family may ship a
        # postcheck(compiled) -> dict that ASSERTS on the compiled
        # program (e.g. serving_decode_fused's fusion proof) and
        # returns extra row fields; a failed assertion degrades this
        # family to an error row like any other capture failure
        postcheck = extras.get("postcheck")
        if postcheck is not None:
            row.update(postcheck(compiled))
    except Exception as e:    # noqa: BLE001 — per-family isolation
        return {"model": model, "batch": batch,
                "error": f"{type(e).__name__}: {e}"[:500]}
    finally:
        if prev is None:
            os.environ.pop("BENCH_ANALYTIC_BUILD", None)
        else:
            os.environ["BENCH_ANALYTIC_BUILD"] = prev
    row.update(model=model, batch=batch, metric=metric,
               compile_s=round(time.perf_counter() - t0, 1))
    # bench.py's hand-derived FLOPs model, normalized to the same scope
    # as the lowered program (one step); trainer_prefetch's model covers
    # a whole pass, the serving families' covers the whole request
    # stream/burst — the lowered program there is one batch, so scopes
    # differ and the cross-check is omitted for them.
    bps = extras.get("batches_per_step")
    if model in ("transformer_serving", "serving", "serving_generate",
                 "serving_fleet", "serving_paged",
                 "serving_decode_fused", "serving_autoscale",
                 "serving_chunked_prefill", "serving_quant",
                 "serving_quant_prefill",
                 "serving_speculative", "serving_sharded",
                 "serving_kv_spill", "serving_disagg"):
        # the lowered program is one batch/slab step while the bench FLOPs
        # model covers the whole stream/burst — scopes differ, no cross-check
        row["bench_model_flops"] = None
    else:
        row["bench_model_flops"] = model_flops / (bps or 1)
    row["roofline"] = {c: roofline.predict(row["flops"],
                                           row["bytes_accessed"], c)
                       for c in chips}
    head = row["roofline"][chips[0]]
    row["predicted_ms"] = head["predicted_ms"]
    row["predicted_mfu"] = head["predicted_mfu"]
    row["bottleneck"] = head["bottleneck"]
    return row


def snapshot(families=None, chips=("v5e", "v5p")):
    """Full snapshot dict for the given family names (default: all)."""
    import jax
    sel = [f for f in FAMILIES if families is None or f[0] in families]
    unknown = set(families or ()) - {f[0] for f in sel}
    if unknown:
        raise SystemExit(f"unknown analytic families: {sorted(unknown)} "
                         f"(known: {[f[0] for f in FAMILIES]})")
    rows = {}
    for name, model, batch in sel:
        _log(f"{name} (model={model} batch={batch or 'default'}) ...")
        need = MESH_FAMILIES.get(name, 0)
        if need and len(jax.devices()) < need:
            _log(f"{name}: needs a {need}-device mesh, forcing it in a "
                 "subprocess (this process stays single-device)")
            rows[name] = _capture_subprocess(name, model, batch, need)
        else:
            rows[name] = capture(name, model, batch, chips=chips)
        if "error" in rows[name]:
            _log(f"{name}: FAILED {rows[name]['error']}")
        else:
            _log(f"{name}: {rows[name]['flops'] / 1e9:.1f} GFLOP, "
                 f"{rows[name]['bytes_accessed'] / 1e6:.0f} MB, "
                 f"predicted {rows[name]['predicted_ms']:.2f} ms "
                 f"({rows[name]['bottleneck']}-bound, "
                 f"MFU<={rows[name]['predicted_mfu'] * 100:.0f}%)")
        gc.collect()
    try:
        from paddle_tpu.utils.revision import code_revision
        rev = code_revision()
    except Exception:   # noqa: BLE001
        rev = "unknown"
    return {
        "schema": 1,
        "kind": "paddle_tpu analytic perf snapshot",
        "generated_at": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "revision": rev,
        "backend": jax.default_backend(),
        "jax_version": jax.__version__,
        "roofline_chips": list(chips),
        "families": rows,
    }


def write(path, snap):
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(snap, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return path


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="chip-independent analytic perf snapshot")
    ap.add_argument("--analytic", action="store_true",
                    help="accepted for bench.py passthrough; implied")
    ap.add_argument("--families", default=None,
                    help="comma-separated subset (default: all)")
    ap.add_argument("--out", default=os.environ.get("BENCH_ANALYTIC_OUT",
                                                    DEFAULT_OUT))
    args = ap.parse_args(argv)

    # the snapshot is defined on the CPU backend (works every round); an
    # explicit BENCH_PLATFORM still overrides for A/B-ing backends
    platform = os.environ.get("BENCH_PLATFORM", "cpu")
    os.environ["JAX_PLATFORMS"] = platform
    import jax
    jax.config.update("jax_platforms", platform)

    fams = ([f.strip() for f in args.families.split(",") if f.strip()]
            if args.families else None)
    snap = snapshot(families=fams)
    write(args.out, snap)
    errors = sorted(n for n, r in snap["families"].items() if "error" in r)
    out = {"metric": "analytic perf snapshot (roofline v5e)",
           "value": len(snap["families"]) - len(errors),
           "unit": f"families_ok/{len(snap['families'])}",
           "vs_baseline": None, "out": args.out, "backend": snap["backend"]}
    if errors:
        out["errors"] = errors
    print(json.dumps(out), flush=True)
    return 2 if errors else 0


if __name__ == "__main__":
    sys.exit(main())

"""Structure gates and first-principles byte/time models.

Two kinds of chip-independent statement about a serving program, neither
of which executes anything:

- Structure gates over lowered/compiled HLO text (``assert_decode_fused``,
  ``assert_prefill_flash``, ``assert_kv_quantized``,
  ``assert_prefill_kv_quantized``, ``assert_weights_quantized`` and the
  ``*_instrs`` detectors behind them): does the program materialize the
  buffer a fused kernel exists to avoid?  The tests run them both ways
  (the reference path must trip them, the kernel path must not).
- ``predicted_*``: bytes and milliseconds from shapes and spec-sheet
  peaks (``perf.roofline``).  ``DecodeEngine`` routes host-tier restores
  and cross-replica handoffs by them; they are counts, not measurements
  (a device time comes only from a chip run — ``benchmark/run.py``).
"""

from paddle_tpu.perf import roofline

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

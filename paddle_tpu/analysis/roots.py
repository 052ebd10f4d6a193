"""The jitted-root registry: ONE place that names every jitted step.

Two consumers, kept joined by tests/test_analysis.py's drift test:

* `perf/analytic.py` iterates ``FAMILIES`` (moved here from there) to
  AOT-compile every bench family's step and gate its HLO structure;
* the static analyzer (`python -m paddle_tpu.analysis`) walks the call
  graph reachable from ``JIT_ROOTS`` — the Python functions those same
  lowered steps trace — and enforces jit-purity + retrace discipline.

``FAMILY_ROOTS`` maps every FAMILIES name to the JIT_ROOTS entries its
``extras["lower"]`` hook traces, so a NEW bench family cannot add a
jitted step the analyzer doesn't see: the drift test fails until the
family is mapped here (and its roots exist in the AST index).

Nothing here imports jax or bench machinery — the analyzer must stay a
parse-only gate, and analytic.py imports FAMILIES from here (never the
other way around).
"""

import dataclasses

# ---------------------------------------------------------------- FAMILIES
# snapshot name -> (bench.py model, batch override or None = family
# default).  Covers every bench family class (RNN, conv/image, seq2seq,
# transformer train/packed/moe, LM + beam decode, serving, trainer loop)
# plus the large-batch rows the round-5 verdict asked for: ResNet-50 at
# bs 256, the 8k-slot packed transformer, LSTM h=2048.  (The per-family
# commentary lives with the FAMILY_ROOTS mapping below and in
# perf/analytic.py's capture docstring.)
FAMILIES = [
    ("lstm", "lstm", None),
    ("lstm2048", "lstm2048", None),
    ("smallnet", "smallnet", None),
    ("alexnet", "alexnet", None),
    ("resnet50", "resnet50", None),
    ("resnet50@bs256", "resnet50", 256),
    ("seq2seq", "seq2seq", None),
    ("transformer", "transformer", None),
    ("transformer_packed", "transformer_packed", None),
    ("transformer_packed_8k", "transformer_packed_8k", None),
    ("transformer_moe", "transformer_moe", None),
    ("transformer_lm_decode", "transformer_lm_decode", None),
    ("transformer_decode", "transformer_decode", None),
    ("transformer_serving", "transformer_serving", None),
    ("serving", "serving", None),
    ("serving_generate", "serving_generate", None),
    ("serving_fleet", "serving_fleet", None),
    ("serving_autoscale", "serving_autoscale", None),
    ("serving_paged", "serving_paged", None),
    ("serving_decode_fused", "serving_decode_fused", None),
    ("serving_chunked_prefill", "serving_chunked_prefill", None),
    ("serving_quant", "serving_quant", None),
    ("serving_quant_prefill", "serving_quant_prefill", None),
    ("serving_speculative", "serving_speculative", None),
    ("serving_sharded", "serving_sharded", None),
    ("serving_kv_spill", "serving_kv_spill", None),
    ("serving_disagg", "serving_disagg", None),
    ("trainer_prefetch", "trainer_prefetch", None),
    ("trainer_int8", "trainer_int8", None),
]


# ---------------------------------------------------------------- JIT roots

@dataclasses.dataclass(frozen=True)
class Root:
    """One jitted step's Python entry point.

    ``ref`` is ``"dotted.module:qualname"`` with ``<locals>`` segments
    for closures (e.g. the trainer step).  ``static_args`` names the
    parameters that are TRACE-TIME constants (shapes, head counts,
    mode strings) — every other parameter is DATA (a tracer), and the
    retrace pass taints from exactly those.
    """
    name: str
    ref: str
    static_args: tuple = ()
    note: str = ""


JIT_ROOTS = {r.name: r for r in [
    # ---- training: the ONE jitted train step (SGD._build_step wraps
    # dense_step/sparse_step in the trace-counting `step` closure)
    Root("trainer_step",
         "paddle_tpu.trainer.trainer:SGD._build_step.<locals>.step",
         static_args=(),
         note="the jitted train step (loss + grads + optimizer update)"),
    # ---- LM trunk entry points (models/transformer.py) — what the
    # serving engines' _step_fn closures and lm_generate trace
    Root("lm_logits", "paddle_tpu.models.transformer:lm_logits",
         static_args=("num_heads", "return_aux", "encode_kw"),
         note="batched LM forward (training families + serving infer)"),
    Root("lm_prefill", "paddle_tpu.models.transformer:lm_prefill",
         static_args=("max_len", "num_heads", "moe_top_k", "pos_type",
                      "kv_dtype"),
         note="batched causal prefill writing the decode cache"),
    Root("lm_decode_step", "paddle_tpu.models.transformer:lm_decode_step",
         static_args=("num_heads", "moe_top_k", "pos_type"),
         note="single-stream incremental decode step"),
    Root("lm_decode_step_slots",
         "paddle_tpu.models.transformer:lm_decode_step_slots",
         static_args=("num_heads", "moe_top_k", "pos_type",
                      "shard_axis"),
         note="slab continuous-batching decode step (DecodeEngine); "
              "shard_axis is the tensor-parallel mesh-axis name — a "
              "trace-time constant like num_heads"),
    Root("lm_decode_step_paged",
         "paddle_tpu.models.transformer:lm_decode_step_paged",
         static_args=("num_heads", "moe_top_k", "pos_type"),
         note="paged-KV decode step (block tables fed as data)"),
    Root("lm_decode_chunk_slots",
         "paddle_tpu.models.transformer:lm_decode_chunk_slots",
         static_args=("num_heads", "moe_top_k", "pos_type", "all_lanes",
                      "shard_axis"),
         note="unified chunked-prefill step, slab layout (all_lanes is "
              "the spec-verify projection switch, shard_axis the "
              "tensor-parallel mesh axis — both trace-time only)"),
    Root("lm_decode_chunk_paged",
         "paddle_tpu.models.transformer:lm_decode_chunk_paged",
         static_args=("num_heads", "moe_top_k", "pos_type", "all_lanes",
                      "shard_axis"),
         note="unified chunked-prefill step, paged layout (all_lanes is "
              "the spec-verify projection switch, shard_axis the "
              "tensor-parallel mesh axis — both trace-time only)"),
    Root("hybrid_decode_chunk",
         "paddle_tpu.models.hybrid_lm:decode_chunk",
         static_args=("cfg", "with_routes"),
         note="the hybrid trunk's chunked paged step (KDA state and MLA "
              "latents; DecodeEngine(model=...) reaches it through "
              "hybrid_lm.Served, which the call graph cannot follow)"),
    # ---- engine-side jitted closures (serving/): the slot-step wrapper
    # plus the admission/write/fork device ops around it
    Root("decode_engine_step",
         "paddle_tpu.serving.decode_engine:"
         "DecodeEngine.__init__.<locals>._step_fn",
         static_args=(),
         note="DecodeEngine's jitted step wrapper (all 4 layout/chunk "
              "variants share the qualname; every variant is analyzed)"),
    Root("draft_rollout",
         "paddle_tpu.serving.speculative:"
         "DraftTrunk.__init__.<locals>._draft_fn",
         static_args=(),
         note="DraftTrunk's jitted k-token rollout (speculative "
              "decoding); k/chunk are constructor constants baked into "
              "the closure, feed lengths/positions are data"),
    Root("serving_fwd",
         "paddle_tpu.serving.engine:"
         "InferenceEngine.from_inferencer.<locals>.fwd",
         static_args=(),
         note="InferenceEngine's jitted bucket forward"),
    # ---- fused Pallas kernels (ops/pallas/): what `maybe_*` dispatches
    # into — the kernel WRAPPERS trace host Python around pallas_call
    Root("decode_attention_slab",
         "paddle_tpu.ops.pallas.decode_attention:decode_attention_slab",
         static_args=("num_heads", "block_k", "interpret"),
         note="fused slab decode-attention kernel"),
    Root("decode_attention_paged",
         "paddle_tpu.ops.pallas.decode_attention:decode_attention_paged",
         static_args=("num_heads", "interpret"),
         note="fused paged decode-attention kernel"),
    Root("decode_attention_slab_chunk",
         "paddle_tpu.ops.pallas.decode_attention:"
         "decode_attention_slab_chunk",
         static_args=("num_heads", "block_k", "interpret"),
         note="Tq=chunk slab kernel (unified chunked prefill)"),
    Root("decode_attention_paged_chunk",
         "paddle_tpu.ops.pallas.decode_attention:"
         "decode_attention_paged_chunk",
         static_args=("num_heads", "interpret"),
         note="Tq=chunk paged kernel (unified chunked prefill)"),
    Root("kda_chunk",
         "paddle_tpu.ops.pallas.kda:kda_chunk",
         static_args=("hp", "interpret"),
         note="gated delta rule with the state in VMEM (hybrid trunk)"),
    Root("flash_attention",
         "paddle_tpu.ops.pallas.flash_attention:flash_attention",
         static_args=("scale", "causal", "block_q", "block_k",
                      "interpret"),
         note="flash prefill kernel (pallas_prefill routing)"),
    Root("flash_attention_quant",
         "paddle_tpu.ops.pallas.flash_attention:flash_attention_quant",
         static_args=("num_heads", "scale", "causal", "block_q",
                      "block_k", "interpret"),
         note="int8 flash prefill kernel (pallas_prefill_quant "
              "routing): int8 K/V + per-(position, head) scale "
              "sidecars stream block-by-block, widen in registers"),
    # ---- int8 weight-streaming train step (SGD quant_weights=True):
    # a SEPARATE closure from dense_step — the {master, q} bundle step
    # with the in-step requantize
    Root("trainer_quant_step",
         "paddle_tpu.trainer.trainer:"
         "SGD._build_step.<locals>.quant_step",
         static_args=(),
         note="the int8 weight-streaming train step (dequant at the "
              "matmul boundary, f32 masters optimizer-side, in-step "
              "requantize)"),
]}


# Every FAMILIES name -> the JIT_ROOTS its extras["lower"] hook traces.
# Training families all lower SGD.lower_step -> the trainer step; the
# serving families lower the engine step for their layout.  The drift
# test (tests/test_analysis.py) fails when a FAMILIES entry is missing
# here, when a mapping names an unknown root, or when a root's ref no
# longer resolves in the AST index.
FAMILY_ROOTS = {
    "lstm": ("trainer_step",),
    "lstm2048": ("trainer_step",),
    "smallnet": ("trainer_step",),
    "alexnet": ("trainer_step",),
    "resnet50": ("trainer_step",),
    "resnet50@bs256": ("trainer_step",),
    "seq2seq": ("trainer_step",),
    "transformer": ("trainer_step",),
    "transformer_packed": ("trainer_step",),
    "transformer_packed_8k": ("trainer_step",),
    "transformer_moe": ("trainer_step",),
    "transformer_lm_decode": ("lm_prefill", "lm_decode_step"),
    "transformer_decode": ("trainer_step",),
    "transformer_serving": ("lm_logits",),
    "serving": ("serving_fwd", "lm_logits"),
    "serving_generate": ("decode_engine_step", "lm_decode_step_slots",
                         "lm_prefill"),
    "serving_fleet": ("decode_engine_step", "lm_decode_step_slots",
                      "lm_prefill"),
    "serving_autoscale": ("decode_engine_step", "lm_decode_step_slots",
                          "lm_prefill"),
    "serving_paged": ("decode_engine_step", "lm_decode_step_paged",
                      "lm_prefill"),
    "serving_decode_fused": ("decode_engine_step", "lm_decode_step_paged",
                             "decode_attention_paged",
                             "decode_attention_slab"),
    "serving_chunked_prefill": ("decode_engine_step",
                                "lm_decode_chunk_slots",
                                "lm_decode_chunk_paged", "lm_prefill",
                                "decode_attention_slab_chunk",
                                "decode_attention_paged_chunk",
                                "flash_attention"),
    "serving_quant": ("decode_engine_step", "lm_decode_step_paged",
                      "decode_attention_paged", "lm_prefill"),
    # serving_quant_prefill lowers the int8-KV lm_prefill with the
    # quant kernel forced ON — the per-layer seam dispatches into
    # flash_attention_quant (the f32 twin it gates falls back through
    # flash_attention).
    "serving_quant_prefill": ("lm_prefill", "flash_attention_quant",
                              "flash_attention"),
    "serving_speculative": ("decode_engine_step", "draft_rollout",
                            "lm_decode_chunk_slots",
                            "lm_decode_chunk_paged",
                            "lm_decode_step_slots", "lm_prefill",
                            "decode_attention_slab_chunk",
                            "decode_attention_paged_chunk",
                            "flash_attention"),
    # serving_sharded traces the SAME engine/draft closures as the
    # speculative family — the shard_map wrapper lives inside
    # decode_engine_step/draft_rollout's `_model` body, so the analyzer
    # walks it through the existing refs; no new qualnames appear.
    "serving_sharded": ("decode_engine_step", "draft_rollout",
                        "lm_decode_chunk_slots",
                        "lm_decode_chunk_paged",
                        "lm_decode_step_slots", "lm_prefill",
                        "decode_attention_slab_chunk",
                        "decode_attention_paged_chunk",
                        "flash_attention"),
    # serving_kv_spill runs the SAME one chunked step as
    # serving_chunked_prefill — the host tier adds no jitted code (spill
    # gathers with NumPy on the worker thread; the restore lands through
    # the already-warm block-write donation path), so the family traces
    # exactly the chunked-prefill root set.
    "serving_kv_spill": ("decode_engine_step",
                         "lm_decode_chunk_slots",
                         "lm_decode_chunk_paged", "lm_prefill",
                         "decode_attention_slab_chunk",
                         "decode_attention_paged_chunk",
                         "flash_attention"),
    # serving_disagg (cross-replica KV handoff, serving/transfer.py)
    # adds NO jitted code either: the export gathers with NumPy on the
    # source's worker thread, the blob crosses a plain socket, and the
    # receive lands through the SAME claim/stage/commit restore pipeline
    # serving_kv_spill exercises — so the receive/commit path traces
    # exactly the chunked-prefill root set, and the analyzer covers the
    # handoff by covering these.
    "serving_disagg": ("decode_engine_step",
                       "lm_decode_chunk_slots",
                       "lm_decode_chunk_paged", "lm_prefill",
                       "decode_attention_slab_chunk",
                       "decode_attention_paged_chunk",
                       "flash_attention"),
    "trainer_prefetch": ("trainer_step",),
    # trainer_int8 lowers SGD(quant_weights=True).lower_step — the
    # quant_step closure (NOT dense_step) wrapped by the same
    # trace-counting `step`.
    "trainer_int8": ("trainer_step", "trainer_quant_step"),
}


# FLAGS fields the jitted paths may legitimately read AT TRACE TIME
# (each is documented "read at trace time" in utils/flags.py): kernel
# dispatch + tiling.  Any other FLAGS read reachable from a root is a
# jit-purity finding — runtime flag reads inside a traced body are
# invisible to the compiled program (the trace bakes one value in) and
# a classic source of "works until the flag changes" bugs.
TRACE_TIME_FLAGS = frozenset({
    "pallas_decode",
    "pallas_decode_block_k",
    "pallas_prefill",
    "pallas_prefill_quant",
})


def all_roots():
    """Every registered Root, in a stable order."""
    return [JIT_ROOTS[k] for k in sorted(JIT_ROOTS)]


def roots_for_family(name):
    """The Root entries a FAMILIES name traces (drift test's subject)."""
    return [JIT_ROOTS[r] for r in FAMILY_ROOTS[name]]

"""The jitted-root registry: ONE place that names every jitted step.

The static analyzer (`python -m paddle_tpu.analysis`) walks the call
graph reachable from ``JIT_ROOTS`` — the Python functions the program's
jitted steps trace — and enforces jit-purity + retrace discipline.  A
new jitted step is registered here; tests/test_analysis.py checks that
every root's ``ref`` still resolves in the AST index and that its
``static_args`` are real parameters, so a rename cannot silently drop a
step out of the analysis.

Nothing here imports jax — the analyzer must stay a parse-only gate.
"""

import dataclasses


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
         note="batched LM forward (training + serving infer)"),
    Root("lm_prefill", "paddle_tpu.models.transformer:lm_prefill",
         static_args=("max_len", "num_heads", "moe_top_k", "pos_type",
                      "kv_dtype"),
         note="batched causal prefill writing the decode cache"),
    Root("lm_decode_step", "paddle_tpu.models.transformer:lm_decode_step",
         static_args=("num_heads", "moe_top_k", "pos_type"),
         note="single-stream incremental decode step"),
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
         note="DecodeEngine's jitted step wrapper (the model, paged and "
              "slab variants share the qualname; every one is analyzed)"),
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
    Root("decode_attention_slab_chunk",
         "paddle_tpu.ops.pallas.decode_attention:"
         "decode_attention_slab_chunk",
         static_args=("num_heads", "block_k", "interpret"),
         note="fused slab decode-attention kernel, Tq = the step's lanes"),
    Root("decode_attention_paged_chunk",
         "paddle_tpu.ops.pallas.decode_attention:"
         "decode_attention_paged_chunk",
         static_args=("num_heads", "interpret"),
         note="fused paged decode-attention kernel, Tq = the step's "
              "lanes (block tables fed as data)"),
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

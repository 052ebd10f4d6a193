"""Transformer-base MT (BASELINE.json stretch config: "Transformer-base MT —
stretch gserver layers to attention stack").  The reference predates
attention; this is the TPU-era flagship: pre-LN encoder-decoder, bf16 MXU
matmuls, f32 softmax/layernorm, causal+padding masks, beam-search decode
sharing ops.beam with seq2seq.
"""

import math

import jax
import jax.numpy as jnp

from paddle_tpu.core.sequence import SequenceBatch
from paddle_tpu.ops import linear, losses, embedding as emb_ops
from paddle_tpu.ops import attention as attn_ops
from paddle_tpu.ops import beam as beam_ops
from paddle_tpu.ops.norm import layer_norm
from paddle_tpu.quant import kv as kvq
from paddle_tpu.quant.weights import (is_quantized_leaf as _w_quantized,
                                      maybe_dequant as _maybe_dequant,
                                      weight_shape as _w_shape)


def _dense(rng, din, dout, scale=None):
    s = scale or (1.0 / math.sqrt(din))
    return s * jax.random.normal(rng, (din, dout), jnp.float32)


def _block_init(ks, d, dff, cross=False, moe_experts=0, d_kv=None):
    dkv = d_kv or d
    blk = {
        "ln1": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
        "attn": {"wq": _dense(next(ks), d, d),
                 "wk": _dense(next(ks), d, dkv),
                 "wv": _dense(next(ks), d, dkv),
                 "wo": _dense(next(ks), d, d)},
        "ln2": {"g": jnp.ones((d,)), "b": jnp.zeros((d,))},
    }
    if moe_experts and moe_experts > 1:
        from paddle_tpu.ops import moe as moe_ops
        blk["moe"] = moe_ops.init_moe(next(ks), d, dff, moe_experts)
    else:
        blk["ffn"] = {"w1": _dense(next(ks), d, dff),
                      "b1": jnp.zeros((dff,)),
                      "w2": _dense(next(ks), dff, d),
                      "b2": jnp.zeros((d,))}
    if cross:
        blk["ln_x"] = {"g": jnp.ones((d,)), "b": jnp.zeros((d,))}
        blk["xattn"] = {"wq": _dense(next(ks), d, d),
                        "wk": _dense(next(ks), d, d),
                        "wv": _dense(next(ks), d, d),
                        "wo": _dense(next(ks), d, d)}
    return blk


def init(rng, src_vocab=30000, trg_vocab=30000, d_model=512, num_heads=8,
         dff=2048, enc_layers=6, dec_layers=6, max_len=512,
         moe_experts=0, pos_type="learned", num_kv_heads=None):
    """moe_experts > 1 replaces every ENC block's dense FFN with a
    top-k-gated mixture of that many expert FFNs (ops/moe.py: batched
    einsum over the expert dim, shardable over the 'expert' mesh axis
    via moe.expert_shardings) — the modern sparse-LM trunk.  Decoder
    blocks keep dense FFNs (the MoE plane targets the causal/encoder
    trunk lm_loss trains).

    num_kv_heads < num_heads gives the ENC/causal blocks grouped-query
    attention (GQA): wk/wv project to num_kv_heads*head_dim, each KV
    head serving a group of query heads — the KV cache (and its HBM
    stream at decode) shrinks by the same factor, the standard serving
    lever.  Carried entirely by the weight shapes; every path infers it.

    pos_type="rope" drops the learned positional table entirely: the
    trunk rotates q/k per position instead (ops.attention.rope), so
    max_len stops being a hard cap — a rope trunk can run sequences
    longer than anything trained on (relative-position attention).
    Callers pass the same pos_type to encode/lm_* (static config, like
    depth in models/resnet).  rope is a decoder-only-trunk feature:
    the seq2seq decoder stack needs the learned table, so
    pos_type='rope' requires dec_layers=0."""
    ks = iter(jax.random.split(rng, 16 + 9 * (enc_layers + dec_layers)))
    params = {
        "src_emb": _dense(next(ks), src_vocab, d_model, scale=0.02),
        "trg_emb": _dense(next(ks), trg_vocab, d_model, scale=0.02),
    }
    # the pos key is drawn in its historical slot EITHER WAY so a given
    # seed yields byte-identical weights for every other parameter
    # (golden generation tests pin exactly that)
    pos_key = next(ks)
    if pos_type == "rope" and dec_layers:
        raise ValueError(
            "pos_type='rope' is the decoder-only trunk configuration "
            "(lm_loss/lm_generate); the seq2seq decoder stack needs the "
            "learned table — use dec_layers=0 or pos_type='learned'")
    if pos_type == "learned":
        params["pos"] = 0.02 * jax.random.normal(pos_key,
                                                 (max_len, d_model))
    elif pos_type != "rope":
        raise ValueError(f"pos_type must be 'learned' or 'rope', got "
                         f"{pos_type!r}")
    d_kv = None
    if num_kv_heads is not None:
        if num_heads % num_kv_heads:
            raise ValueError(f"num_heads={num_heads} not divisible by "
                             f"num_kv_heads={num_kv_heads}")
        d_kv = (d_model // num_heads) * num_kv_heads
    params["enc"] = [_block_init(ks, d_model, dff, moe_experts=moe_experts,
                                 d_kv=d_kv)
                     for _ in range(enc_layers)]
    params["dec"] = [_block_init(ks, d_model, dff, cross=True)
                     for _ in range(dec_layers)]
    params["ln_f"] = {"g": jnp.ones((d_model,)), "b": jnp.zeros((d_model,))}
    params["out"] = _dense(next(ks), d_model, trg_vocab)
    return params


def moe_lm_shardings(mesh, params):
    """NamedShardings for a moe_experts trunk: everything replicated
    except each block's expert weights, which take the canonical
    moe.expert_shardings layout (wg replicated, w1/w2 over 'expert') —
    THE recipe the dryrun leg and the parity tests share."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.ops import moe as moe_ops
    repl = NamedSharding(mesh, P())
    sh = jax.tree_util.tree_map(lambda _: repl, params)
    for blk in sh["enc"]:
        if "moe" in blk:
            blk["moe"] = moe_ops.expert_shardings(mesh)
    return sh


def _mha(blk, xq, xkv, num_heads, key_mask=None, causal=False, mesh=None,
         zigzag=False, q_segment_ids=None, rope_positions=None):
    return attn_ops.multi_head_attention(
        xq, xkv, blk["wq"], blk["wk"], blk["wv"], blk["wo"], num_heads,
        key_mask=key_mask, causal=causal, mesh=mesh, zigzag=zigzag,
        q_segment_ids=q_segment_ids, rope_positions=rope_positions)


def _ffn(blk, x):
    h = jax.nn.relu(linear.matmul(x, blk["w1"]) + blk["b1"])
    return linear.matmul(h, blk["w2"]) + blk["b2"]


def _ln(p, x):
    return layer_norm(x, p["g"], p["b"])


def _zigzag_idx(t, mesh):
    """THE permutation decode's logits and loss's labels share — one
    definition so they can never misalign."""
    from paddle_tpu.parallel.ring_attention import zigzag_order
    return jnp.asarray(zigzag_order(t, mesh.shape["seq"]))


def _check_full(seq: SequenceBatch):
    """full_seq=True promises no padding; catch a broken promise when the
    lengths are concrete (outside jit) instead of silently attending
    padded keys."""
    lengths = seq.lengths
    if isinstance(lengths, jax.core.Tracer):
        return
    t = seq.data.shape[1]
    if bool(jnp.any(lengths != t)):
        import numpy as _np
        a = _np.asarray(lengths)
        raise ValueError(
            f"full_seq=True but batch has lengths "
            f"{(int(a.min()), int(a.max()))} < T={t}; drop full_seq or "
            "pack the batch")


def _block_ffn(blk, h, moe_top_k=2, valid=None):
    """Dense or mixture FFN, depending on how the block was initialized;
    returns (output, load-balance aux) with aux == 0 for dense.  relu
    for both so an identical-experts mixture reproduces the dense block
    exactly (the MoE equivalence test relies on it).  valid: [B, T] real-
    token mask — the aux statistics must not be skewed by padding rows
    that all route identically."""
    if "moe" in blk:
        from paddle_tpu.ops import moe as moe_ops
        return moe_ops.moe_ffn(h, blk["moe"], top_k=moe_top_k,
                               act=jax.nn.relu, return_aux=True,
                               valid=valid)
    return _ffn(blk["ffn"], h), jnp.zeros(())


def _enc_block(blk, x, key_mask, num_heads, mesh=None, segment_ids=None,
               causal=False, zigzag=False, moe_top_k=2, rope_pos=None):
    h = _ln(blk["ln1"], x)
    x = x + _mha(blk["attn"], h, h, num_heads, key_mask=key_mask,
                 causal=causal, mesh=mesh, zigzag=zigzag,
                 q_segment_ids=segment_ids, rope_positions=rope_pos)
    # real-token mask for the MoE aux: packed rows label padding 0,
    # unpacked rows carry key_mask; full_seq has no padding at all
    valid = (segment_ids > 0 if segment_ids is not None
             else (key_mask > 0 if key_mask is not None else None))
    y, aux = _block_ffn(blk, _ln(blk["ln2"], x), moe_top_k, valid)
    return x + y, aux


def _dec_block(blk, x, enc_out, self_km, cross_km, num_heads, mesh=None,
               zigzag=False):
    h = _ln(blk["ln1"], x)
    x = x + _mha(blk["attn"], h, h, num_heads, key_mask=self_km,
                 causal=True, mesh=mesh, zigzag=zigzag)
    x = x + _mha(blk["xattn"], _ln(blk["ln_x"], x), enc_out, num_heads,
                 key_mask=cross_km, mesh=mesh)
    return x + _ffn(blk["ffn"], _ln(blk["ln2"], x))


def encode(params, src: SequenceBatch, num_heads=8, remat=False,
           full_seq=False, mesh=None, segment_ids=None, positions=None,
           causal=False, zigzag=False, moe_top_k=2, return_aux=False,
           pos_type="learned"):
    """remat=True checkpoints each block (jax.checkpoint): backward
    recomputes activations instead of storing them — the HBM headroom for
    >=32k-token batches.

    mesh: a mesh whose `seq` axis is >1 runs every attention sequence-
    parallel via the ppermute ring (callers shard the T dim of the feeds
    over that axis) — long-context training across chips.

    segment_ids/positions: PACKED rows (core.sequence.pack_sequences —
    several short sequences per row): attention stays block-diagonal per
    segment and each token's positional row is its within-segment index,
    so the encoder behaves exactly as if every sequence ran alone.

    causal=True turns the stack into a decoder-only (GPT-style) trunk:
    every self-attention is causal — combined with segment_ids this is
    packed causal-LM training (see lm_loss).  zigzag=True (causal +
    seq>1 mesh only) processes the stream in zigzag storage order so the
    causal self-attention rides the balanced ring; the returned hidden
    states are in zigzag order (lm_loss aligns its labels the same way)."""
    # quantized trunks (quant/weights.py) dequantize at the matmul
    # boundary: XLA fuses convert(int8)*scale into each consuming
    # matmul's operand read — a float tree passes through untouched
    params = _maybe_dequant(params)
    t = src.data.shape[1]
    if (pos_type == "learned") != ("pos" in params):
        raise ValueError(
            f"pos_type={pos_type!r} but params were initialized "
            f"{'with' if 'pos' in params else 'without'} a learned "
            "positional table — pass the SAME pos_type used at init")
    block = (jax.checkpoint(_enc_block, static_argnums=(3, 4, 6, 7, 8))
             if remat else _enc_block)
    if (segment_ids is None) != (positions is None):
        raise ValueError("packed encode needs BOTH segment_ids and "
                         "positions (pack_sequences returns them "
                         "together)")
    ids, order = src.data, None
    if zigzag:
        if not causal or mesh is None or mesh.shape.get("seq", 1) <= 1:
            raise ValueError("zigzag encode needs causal=True and a mesh "
                             "with seq > 1")
        order = _zigzag_idx(t, mesh)
        ids = ids[:, order]
        if segment_ids is not None:
            segment_ids = segment_ids[:, order]
            positions = positions[:, order]
    x = emb_ops.embedding_lookup(params["src_emb"], ids)
    if positions is not None and pos_type == "learned" \
            and not isinstance(positions, jax.core.Tracer):
        try:
            max_pos = int(jnp.max(positions))
        except jax.errors.ConcretizationTypeError:
            # inside a jit trace even closed-over constants are staged;
            # the eager-path check below is best-effort only
            max_pos = -1
        if max_pos >= params["pos"].shape[0]:
            # fail fast like the unpacked path and init_decode_cache do;
            # the gather would otherwise silently clamp to the last row
            raise ValueError(
                f"packed position {max_pos} exceeds the positional table "
                f"({params['pos'].shape[0]}); re-init with a larger "
                "max_len or pack shorter rows")
    rope_pos = None
    if pos_type == "rope":
        # rotary positions ride q/k inside attention; nothing is added
        # to the embeddings and no table caps the length.  Packed rows
        # use within-segment positions (relative attention per segment);
        # zigzag uses the permuted global positions.
        x = x * math.sqrt(x.shape[-1])
        if positions is not None:
            rope_pos = positions
        else:
            rope_pos = jnp.arange(t)
            if order is not None:
                rope_pos = rope_pos[order]
    elif positions is not None:
        pos_rows = params["pos"][positions]
        x = x * math.sqrt(x.shape[-1]) + pos_rows
    else:
        pos_rows = params["pos"][:t]
        if order is not None:
            pos_rows = pos_rows[order]
        x = x * math.sqrt(x.shape[-1]) + pos_rows[None]
    # key validity stays O(T) ([B, T]); full_seq=True promises every
    # sequence is max-length (packed/bucketed batches) and drops the mask
    # entirely so the flash/chunked O(T)-memory paths engage — validated
    # when lengths are concrete (a jit-traced batch is trusted)
    key_mask = None if full_seq or segment_ids is not None else src.mask()
    if key_mask is not None and order is not None:
        key_mask = key_mask[:, order]
    if full_seq:
        _check_full(src)
    aux_total = jnp.zeros(())
    for blk in params["enc"]:
        x, aux = block(blk, x, key_mask, num_heads, mesh, segment_ids,
                       causal, zigzag, moe_top_k, rope_pos)
        aux_total = aux_total + aux
    return (x, aux_total) if return_aux else x


def decode(params, enc_out, src_mask, trg_in: SequenceBatch, num_heads=8,
           pos_offset=0, remat=False, full_seq=False, mesh=None,
           zigzag=False):
    """zigzag=True (mesh with seq>1 only): the decoder stream — ids,
    positions, masks — is processed in zigzag storage order so the causal
    self-attention rides the BALANCED ring (ring_attention_zigzag); the
    non-causal cross-attention doesn't care about q order.  Returned
    logits are in zigzag order: permute labels the same way (loss() does)
    rather than unpermuting — masked CE is permutation-invariant."""
    t = trg_in.data.shape[1]
    block = (jax.checkpoint(_dec_block, static_argnums=(5, 6, 7)) if remat
             else _dec_block)
    ids, pos_rows = trg_in.data, params["pos"][pos_offset:pos_offset + t]
    self_km = None if full_seq else trg_in.mask()
    if zigzag:
        if mesh is None or mesh.shape.get("seq", 1) <= 1:
            raise ValueError("zigzag decode needs a mesh with seq > 1")
        if pos_offset:
            raise ValueError("zigzag is a training-path layout; "
                             "incremental decode uses the cache path")
        order = _zigzag_idx(t, mesh)
        ids = ids[:, order]
        pos_rows = pos_rows[order]
        if self_km is not None:
            self_km = self_km[:, order]
    x = emb_ops.embedding_lookup(params["trg_emb"], ids)
    x = x * math.sqrt(x.shape[-1]) + pos_rows[None]
    cross_km = None if full_seq else src_mask
    if full_seq:
        _check_full(trg_in)
    for blk in params["dec"]:
        x = block(blk, x, enc_out, self_km, cross_km, num_heads, mesh,
                  zigzag)
    x = _ln(params["ln_f"], x)
    return linear.matmul(x, params["out"])


def forward(params, src: SequenceBatch, trg_in: SequenceBatch, num_heads=8,
            remat=False, full_seq=False, mesh=None, zigzag=False,
            return_aux=False, moe_top_k=2):
    enc = encode(params, src, num_heads, remat=remat,
                 full_seq=full_seq, mesh=mesh, return_aux=return_aux,
                 moe_top_k=moe_top_k)
    enc_out, aux = enc if return_aux else (enc, None)
    logits = decode(params, enc_out, src.mask(), trg_in, num_heads,
                    remat=remat, full_seq=full_seq, mesh=mesh,
                    zigzag=zigzag)
    return (logits, aux) if return_aux else logits


def loss(params, src, trg_in, trg_next, num_heads=8, label_smoothing=0.1,
         remat=False, full_seq=False, mesh=None, zigzag=False,
         moe_aux_weight=0.01, moe_top_k=2):
    logits, aux = forward(params, src, trg_in, num_heads, remat=remat,
                          full_seq=full_seq, mesh=mesh, zigzag=zigzag,
                          return_aux=True, moe_top_k=moe_top_k)
    labels = trg_next.data
    if labels.ndim == 3:
        labels = labels[..., 0]
    tok_mask = trg_in.mask(jnp.float32)
    if zigzag:
        # logits are in zigzag order; align labels + mask the same way
        # (masked CE is permutation-invariant, so no unpermute needed)
        order = _zigzag_idx(labels.shape[1], mesh)
        labels = labels[:, order]
        tok_mask = tok_mask[:, order]
    per_tok = _token_ce(logits, labels, label_smoothing)
    per_seq = losses.masked_seq_mean(per_tok, tok_mask.astype(per_tok.dtype))
    return jnp.mean(per_seq) + moe_aux_weight * aux


def _token_ce(logits, labels, label_smoothing):
    """Per-token (optionally label-smoothed) cross-entropy — the ONE
    definition loss() and lm_loss() share."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    if label_smoothing:
        v = logits.shape[-1]
        onehot = jax.nn.one_hot(labels, v)
        smoothed = onehot * (1 - label_smoothing) + label_smoothing / v
        return -jnp.sum(smoothed * logp, axis=-1)
    return -jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]


def lm_loss(params, tokens: SequenceBatch, num_heads=8, segment_ids=None,
            positions=None, mesh=None, zigzag=False, remat=False,
            label_smoothing=0.0, moe_aux_weight=0.01, moe_top_k=2,
            pos_type="learned"):
    """Decoder-only (GPT-style) causal LM: the encoder stack run causal,
    next-token cross-entropy with the input embedding tied as the output
    projection.  Token-mean objective (the standard LM loss — every real
    token weighs the same regardless of how rows were packed).

    segment_ids/positions (pack_sequences layout) train PACKED rows with
    every segment isolated: label t is token t+1 of the SAME segment, so
    each segment's last token — and padding — carries no label.  mesh
    (seq>1) runs the causal attention sequence-parallel; zigzag=True
    additionally balances the causal ring (labels are aligned to the
    zigzag order internally — masked token-mean is permutation-
    invariant).  The modern training plane the reference's
    Argument.sequenceStartPositions pointed toward: no-padding batches,
    long-context sharding, one loss call."""
    ids = tokens.data
    b, t = ids.shape
    if segment_ids is not None:
        seg = segment_ids
        valid = jnp.concatenate(
            [(seg[:, :-1] > 0) & (seg[:, :-1] == seg[:, 1:]),
             jnp.zeros((b, 1), bool)], axis=1)
    else:
        m = tokens.mask() > 0
        # label for position t exists iff position t+1 is a real token
        valid = jnp.concatenate([m[:, 1:], jnp.zeros((b, 1), bool)],
                                axis=1)
    labels = jnp.roll(ids, -1, axis=1)      # wrap at T-1 is masked out
    logits, aux = lm_logits(params, tokens, num_heads, remat=remat,
                            mesh=mesh, segment_ids=segment_ids,
                            positions=positions, zigzag=zigzag,
                            moe_top_k=moe_top_k, pos_type=pos_type,
                            return_aux=True)
    if zigzag:
        order = _zigzag_idx(t, mesh)
        labels, valid = labels[:, order], valid[:, order]
    per_tok = _token_ce(logits, labels, label_smoothing)
    w = valid.astype(per_tok.dtype)
    ce = jnp.sum(per_tok * w) / jnp.maximum(jnp.sum(w), 1.0)
    # MoE load-balance aux (exactly 0 for a dense trunk, so the weight
    # is inert there)
    return ce + moe_aux_weight * aux


def _lm_project(params, h, shard_axis=None):
    """Final LN + tied-embedding projection (the GPT/pre-LN convention,
    same ln_f as decode): without the LN the un-normalized residual
    stream's depth-growing magnitude would set the softmax temperature.
    Accepts a quantized tree too (idempotent dequant — external callers
    like the prefill ladder hand it raw engine params).

    shard_axis (trace-time, like num_heads): inside the serving
    shard_map, src_emb is a LOCAL vocab stripe [V/n, d] — each chip
    computes its logit columns exactly as the single chip would (a
    column slice of a matmul touches no other column's contraction) and
    the tiled all-gather concatenates them back in device order, i.e.
    the original column order.  This is the LOGITS seam of the sharded
    decode step (docs/serving.md "Sharded decode")."""
    params = _maybe_dequant(params)
    local = linear.matmul(_ln(params["ln_f"], h), params["src_emb"].T)
    if shard_axis is None:
        return local
    return jax.lax.all_gather(local, shard_axis, axis=-1, tiled=True)


def _lm_embed(params, ids, shard_axis=None):
    """Input-embedding gather, vocab-sharded under ``shard_axis``: each
    chip looks up ``ids - its_stripe_offset`` against its local [V/n, d]
    stripe — ``embedding_lookup`` returns EXACT zero rows for the
    out-of-stripe (now out-of-range) ids, so the psum adds ``n-1`` exact
    zeros to the one real row and reproduces the replicated gather
    bit-for-bit (x + 0.0 == x).  The single-chip convention that
    out-of-vocab ids embed to zeros is preserved: such ids miss EVERY
    stripe.  This is the (cheap) third collective of the sharded step,
    [tokens, d]-sized."""
    emb = params["src_emb"]
    if shard_axis is None:
        return emb_ops.embedding_lookup(emb, ids)
    off = jax.lax.axis_index(shard_axis) * emb.shape[0]
    return jax.lax.psum(emb_ops.embedding_lookup(emb, ids - off),
                        shard_axis)


def lm_logits(params, tokens: SequenceBatch, num_heads=8,
              return_aux=False, **encode_kw):
    """Full-sequence LM logits [B, T, V]: the lm_generate oracle and the
    building block lm_loss uses via encode(causal=True) + _lm_project.
    return_aux=True additionally returns the MoE load-balance aux (0 for
    a dense trunk)."""
    out = encode(params, tokens, num_heads, causal=True,
                 return_aux=return_aux, **encode_kw)
    if return_aux:
        h, aux = out
        return _lm_project(params, h), aux
    return _lm_project(params, out)


# --------------------------------------------------------- cached decode

def init_decode_cache(params, enc_out, max_len):
    """Per-decoder-layer self-attention K/V buffers ([B, max_len, D],
    written one position per step).  A plain pytree, so beam search's lane
    reordering (ops/beam.py gather_state) reindexes it for free.  The
    cross-attention K/V are NOT here — they never change during decode, so
    they stay out of the scan state (see cross_kv) and are closed over
    instead of being re-gathered every step."""
    if max_len > params["pos"].shape[0]:
        # fail fast like the full-decode oracle would; dynamic_slice would
        # otherwise silently clamp and reuse the last position row
        raise ValueError(
            f"decode max_len {max_len} exceeds the positional table "
            f"({params['pos'].shape[0]}); re-init the model with a larger "
            "max_len")
    b, _, d = enc_out.shape
    return [{"k": jnp.zeros((b, max_len, d), enc_out.dtype),
             "v": jnp.zeros((b, max_len, d), enc_out.dtype)}
            for _ in params["dec"]]


def cross_kv(params, enc_out):
    """Per-decoder-layer cross-attention K/V, computed once per source."""
    return [{"xk": linear.matmul(enc_out, blk["xattn"]["wk"]),
             "xv": linear.matmul(enc_out, blk["xattn"]["wv"])}
            for blk in params["dec"]]


def _attend(q, k, v, num_heads, mask):
    """q: [B, Tq, D] against k/v: [B, T, Dkv] with mask [B, T] (shared
    by every query lane) or [B, Tq, T] (per-lane — the chunked-prefill
    step, where lane i of row r attends cols <= positions[r] + i) ->
    [B, Tq, D].  Tiny-Tq attention: always the masked XLA path (flash
    needs big tiles).  Dkv < D means grouped KV heads (GQA) — repeated
    up to full heads here, so the CACHE stays small."""
    b, tq, d = q.shape
    tk, dkv = k.shape[1], k.shape[2]
    dh = d // num_heads
    hkv = dkv // dh
    qh = q.reshape(b, tq, num_heads, dh).transpose(0, 2, 1, 3)
    kh = attn_ops.repeat_kv_heads(
        k.reshape(b, tk, hkv, dh).transpose(0, 2, 1, 3), num_heads)
    vh = attn_ops.repeat_kv_heads(
        v.reshape(b, tk, hkv, dh).transpose(0, 2, 1, 3), num_heads)
    mh = (mask[:, None, None, :] if mask.ndim == 2
          else mask[:, None, :, :])
    out = attn_ops.dot_product_attention(
        qh, kh, vh, mask=mh, use_flash=False)
    return out.transpose(0, 2, 1, 3).reshape(b, tq, d)


def decode_step_cached(params, src_mask, prev_ids, t, cache, cross,
                       num_heads=8):
    """One incremental decode position.

    prev_ids: [B] token at position t; t: scalar int32; cross: cross_kv()
    output; returns (logits [B, V], updated cache).  Equivalent to column
    t of the full decode() — proven by tests/test_transformer_decode.py."""
    b = prev_ids.shape[0]
    max_len = cache[0]["k"].shape[1]
    x = emb_ops.embedding_lookup(params["trg_emb"], prev_ids)[:, None]
    x = x * math.sqrt(x.shape[-1]) \
        + jax.lax.dynamic_slice_in_dim(params["pos"], t, 1)[None]
    pos_mask = jnp.arange(max_len)[None, :] <= t          # [1, max_len]
    pos_mask = jnp.broadcast_to(pos_mask, (b, max_len))
    new_cache = []
    for blk, c, cx in zip(params["dec"], cache, cross):
        x, nc = _cached_self_attn(blk, x, c, t, pos_mask, num_heads)
        hx = _ln(blk["ln_x"], x)
        xq = linear.matmul(hx, blk["xattn"]["wq"])
        xat = _attend(xq, cx["xk"], cx["xv"], num_heads, src_mask > 0)
        x = x + linear.matmul(xat, blk["xattn"]["wo"])
        x = x + _ffn(blk["ffn"], _ln(blk["ln2"], x))
        new_cache.append(nc)
    x = _ln(params["ln_f"], x)
    return linear.matmul(x, params["out"])[:, 0], new_cache


def _beam_setup(params, src, beam_size, num_heads, moe_top_k=2):
    """Shared oracle/serving preamble: encode once, tile lane-major."""
    b = src.data.shape[0]
    enc_out = encode(params, src, num_heads, moe_top_k=moe_top_k)
    enc_l = jnp.repeat(enc_out, beam_size, axis=0)
    src_mask_l = jnp.repeat(src.mask(), beam_size, axis=0)
    return b, b * beam_size, enc_l, src_mask_l


def generate_cached(params, src: SequenceBatch, beam_size=4, max_len=64,
                    bos_id=0, eos_id=1, num_heads=8, length_penalty=0.6,
                    moe_top_k=2):
    """Beam decode with KV-cached incremental steps: O(T) attention per new
    token instead of re-running the full decoder stack over the whole
    prefix (O(T^2) per token) — the serving-path decoder."""
    b, bk, enc_l, src_mask_l = _beam_setup(params, src, beam_size,
                                           num_heads, moe_top_k)
    # invariant across steps AND identical across a row's lanes: closed
    # over, not carried in the scan state (gather_state would re-copy it
    # per emitted token)
    cross = cross_kv(params, enc_l)

    def step_fn(state, prev_ids):
        cache, step = state
        logits, cache = decode_step_cached(
            params, src_mask_l, prev_ids, step[0], cache, cross, num_heads)
        return jax.nn.log_softmax(logits, axis=-1), (cache, step + 1)

    init_state = (init_decode_cache(params, enc_l, max_len),
                  jnp.zeros((bk,), jnp.int32))
    return beam_ops.beam_search(step_fn, init_state, b, beam_size, max_len,
                                bos_id, eos_id, length_penalty=length_penalty)


def generate(params, src: SequenceBatch, beam_size=4, max_len=64, bos_id=0,
             eos_id=1, num_heads=8, length_penalty=0.6, moe_top_k=2):
    """Beam decode, full-recompute step (the numerics oracle for
    generate_cached; prefer generate_cached for serving throughput)."""
    b, bk, enc_l, src_mask_l = _beam_setup(params, src, beam_size,
                                           num_heads, moe_top_k)

    def step_fn(state, prev_ids):
        toks, step = state           # toks: [BK, max_len]; step: [BK] (equal)
        t = step[0]
        toks = jax.vmap(lambda row, v: row.at[t].set(v))(toks, prev_ids)
        trg = SequenceBatch(toks, step + 1)
        logits = decode(params, enc_l, src_mask_l, trg, num_heads)
        last = jnp.take_along_axis(
            logits, jnp.broadcast_to(t.reshape(1, 1, 1),
                                     (bk, 1, logits.shape[-1])), axis=1)[:, 0]
        return jax.nn.log_softmax(last, axis=-1), (toks, step + 1)

    init_state = (jnp.full((bk, max_len), eos_id, jnp.int32),
                  jnp.zeros((bk,), jnp.int32))
    return beam_ops.beam_search(step_fn, init_state, b, beam_size, max_len,
                                bos_id, eos_id, length_penalty=length_penalty)


# ------------------------------------------------------ decoder-only LM

def _rope_flat(x_btd, positions, head_dim):
    """Apply rope to a flat [B, T, H*head_dim] projection: split heads,
    rotate, re-flatten — cached K is stored ROTATED (the standard
    KV-cache convention; old keys never need re-rotation).  Head count
    comes from the width, so grouped-KV projections rotate correctly."""
    b, t, d = x_btd.shape
    h = d // head_dim
    xh = x_btd.reshape(b, t, h, head_dim).transpose(0, 2, 1, 3)
    xh = attn_ops.rope(xh, positions)
    return xh.transpose(0, 2, 1, 3).reshape(b, t, d)


def _kv_writes(c, k_new, v_new):
    """The ONE quantize-on-write decision every cached-attn variant
    shares: an int8 cache (``"ks" in c`` — quant/kv sidecars) quantizes
    the new K/V per (position, head) and returns the int8 values plus
    their scales; a float cache passes through (scales None).  K and V
    each use their OWN sidecar's head count, matching
    ``_kv_layer_buffers``' per-projection sizing."""
    if "ks" in c:
        k_set, sk = kvq.quantize_heads(k_new, c["ks"].shape[-1])
        v_set, sv = kvq.quantize_heads(v_new, c["vs"].shape[-1])
        return k_set, v_set, sk, sv
    return k_new, v_new, None, None


def _kv_view(k, ks):
    """The matching read: dequantize an int8 buffer by its sidecar
    (``ks`` is None on the float path — identity).  Every position's
    K/V — including the step's own write — goes through the same
    quantize->dequantize round trip, so prefill/step composition and
    replay stay exact under quantization."""
    return kvq.dequantize_heads(k, ks) if ks is not None else k


def _kv_commit(c, upd, k_set, v_set, sk, sv):
    """Apply the K/V (+ sidecar) cache writes through the variant's
    ``upd(buffer, value)`` indexer — the ONE cache-update assembly all
    cached-attn variants and the prefill share.  Returns ``(nc, ks,
    vs)`` with ks/vs None on the float path (``_kv_writes``'s twin)."""
    nc = {"k": upd(c["k"], k_set), "v": upd(c["v"], v_set)}
    if sk is None:
        return nc, None, None
    ks, vs = upd(c["ks"], sk), upd(c["vs"], sv)
    nc.update(ks=ks, vs=vs)
    return nc, ks, vs


def _cached_self_attn(blk, x, c, t, pos_mask, num_heads, rope_pos=None):
    """Shared incremental self-attention block: write this position's K/V
    into the cache, attend over positions <= t, residual-add — ONE
    definition for decode_step_cached and lm_decode_step so the two
    cached steps cannot drift.  An int8 cache quantizes the write and
    attends over the dequantized view (``_kv_writes``/``_kv_view``)."""
    h = _ln(blk["ln1"], x)
    k_new = linear.matmul(h, blk["attn"]["wk"])
    q = linear.matmul(h, blk["attn"]["wq"])
    if rope_pos is not None:
        dh = q.shape[-1] // num_heads
        k_new = _rope_flat(k_new, rope_pos, dh)
        q = _rope_flat(q, rope_pos, dh)
    v_new = linear.matmul(h, blk["attn"]["wv"])
    k_set, v_set, sk, sv = _kv_writes(c, k_new, v_new)
    upd = lambda buf, val: jax.lax.dynamic_update_slice_in_dim(
        buf, val, t, axis=1)
    nc, ks, vs = _kv_commit(c, upd, k_set, v_set, sk, sv)
    att = _attend(q, _kv_view(nc["k"], ks), _kv_view(nc["v"], vs),
                  num_heads, pos_mask)
    return x + linear.matmul(att, blk["attn"]["wo"]), nc


def lm_prefill(params, prompt, max_len, num_heads=8, moe_top_k=2,
               pos_type="learned", kv_dtype=None):
    """Batched causal prefill: run the trunk over the WHOLE prompt in one
    pass (the MXU-friendly leg), writing every position's K/V into fresh
    decode caches.  Returns (per-position hidden states [B, Tp, D],
    cache) — the state lm_decode_step continues from; the caller
    gathers the position(s) it needs BEFORE the d_model x vocab
    projection (projecting every prompt position would multiply the
    most expensive matmul by Tp).  Equivalent to Tp sequential
    lm_decode_step calls (the generation oracle test covers the
    composition), ~Tp x fewer serial steps.  With ragged prompts
    causality keeps padding positions out of real ones.

    kv_dtype="int8" (quant/kv.py) quantizes each position's K/V on the
    way into the cache AND attends over the quantize->dequantize round
    trip — exactly what sequential quantized decode steps compute, so
    the prefill/step composition stays exact under quantization (slot
    recovery, CoW re-seating and continuation replay depend on it)."""
    b, tp = prompt.shape
    cache = init_lm_cache(params, b, max_len, kv_dtype=kv_dtype,
                          num_heads=num_heads)
    params = _maybe_dequant(params)
    if (pos_type == "learned") != ("pos" in params):
        raise ValueError(
            f"pos_type={pos_type!r} but params were initialized "
            f"{'with' if 'pos' in params else 'without'} a learned "
            "positional table — pass the SAME pos_type used at init")
    x = emb_ops.embedding_lookup(params["src_emb"], prompt)
    x = x * math.sqrt(x.shape[-1])
    if pos_type == "learned":
        x = x + params["pos"][:tp][None]
    new_cache = []
    for blk, c in zip(params["enc"], cache):
        h = _ln(blk["ln1"], x)
        k = linear.matmul(h, blk["attn"]["wk"])
        v = linear.matmul(h, blk["attn"]["wv"])
        q = linear.matmul(h, blk["attn"]["wq"])
        d = q.shape[-1]
        dh = d // num_heads
        if pos_type == "rope":
            # cache stores ROTATED keys (old keys never re-rotate)
            k = _rope_flat(k, jnp.arange(tp), dh)
            q = _rope_flat(q, jnp.arange(tp), dh)
        hkv = k.shape[-1] // dh
        k_set, v_set, sk, sv = _kv_writes(c, k, v)
        import importlib
        # importlib: the ops.pallas package re-exports the
        # flash_attention FUNCTION, shadowing the submodule attribute
        _flash_mod = importlib.import_module(
            "paddle_tpu.ops.pallas.flash_attention")
        # int8 caches first try the quant flash kernel (the
        # pallas_prefill_quant trace-time routing): the just-quantized
        # int8 bytes + scale sidecars stream straight into the kernel,
        # widened in registers — no dequantized f32 [Tp, Dkv] buffer
        # (perf/analytic.assert_prefill_kv_quantized pins its absence).
        # The quantization math above is IDENTICAL either way, so the
        # cache stays bit-exact to sequential quantized steps on every
        # path.
        att = _flash_mod.maybe_prefill_quant(q, k_set, v_set, sk, sv,
                                             num_heads)
        if att is None:
            if sk is not None:
                # quantize-on-write + attend over the round trip:
                # position p's K/V is quantized BEFORE any later
                # position attends it, so the batched pass equals
                # sequential quantized steps
                k, v = _kv_view(k_set, sk), _kv_view(v_set, sv)
            split = lambda a, hh: a.reshape(b, tp, hh, dh).transpose(
                0, 2, 1, 3)
            # batched causal pass: the pallas_prefill flag (trace-time,
            # like pallas_decode) routes it through
            # ops/pallas/flash_attention — O(Tp) HBM, no [Tp, Tp] score
            # matrix (perf/analytic.py's prefill-flash gate pins its
            # absence).  The CPU tier-1 default stays the masked XLA
            # reference so greedy bit-identity discipline is untouched;
            # flash_attention itself falls back on shapes its blocking
            # cannot cover.
            att = attn_ops.dot_product_attention(
                split(q, num_heads),
                attn_ops.repeat_kv_heads(split(k, hkv), num_heads),
                attn_ops.repeat_kv_heads(split(v, hkv), num_heads),
                causal=True, use_flash=_flash_mod.prefill_flash_enabled())
            att = att.transpose(0, 2, 1, 3).reshape(b, tp, d)
        x = x + linear.matmul(att, blk["attn"]["wo"])
        x = x + _block_ffn(blk, _ln(blk["ln2"], x), moe_top_k)[0]
        upd = lambda buf, val: jax.lax.dynamic_update_slice_in_dim(
            buf, val, 0, axis=1)
        new_cache.append(
            _kv_commit(c, upd, k_set, v_set, sk, sv)[0])
    return x, new_cache


def lm_decode_step(params, prev_ids, t, cache, num_heads=8,
                   moe_top_k=2, pos_type="learned"):
    """One incremental position of the decoder-only trunk (the enc stack
    run causal, lm_loss's twin): prev_ids [B] at position t -> (logits
    [B, V], updated cache).  cache: per-enc-layer K/V buffers
    [B, max_len, Dkv] where Dkv is each block's KV projection width —
    d_model normally, num_kv_heads*head_dim on a GQA trunk
    (init_lm_cache sizes off the weights)."""
    params = _maybe_dequant(params)
    b = prev_ids.shape[0]
    max_len = cache[0]["k"].shape[1]
    x = emb_ops.embedding_lookup(params["src_emb"], prev_ids)[:, None]
    x = x * math.sqrt(x.shape[-1])
    if pos_type == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(params["pos"], t, 1)[None]
    rope_pos = (jnp.asarray(t)[None] if pos_type == "rope" else None)
    pos_mask = jnp.broadcast_to(jnp.arange(max_len)[None, :] <= t,
                                (b, max_len))
    new_cache = []
    for blk, c in zip(params["enc"], cache):
        x, nc = _cached_self_attn(blk, x, c, t, pos_mask, num_heads,
                                  rope_pos)
        x = x + _block_ffn(blk, _ln(blk["ln2"], x), moe_top_k)[0]
        new_cache.append(nc)
    return _lm_project(params, x)[:, 0], new_cache


def _shard_gather_att(att, shard_axis):
    """The ATTENTION-OUTPUT seam of the sharded decode step: inside the
    serving shard_map each chip's ``att`` is the contiguous head stripe
    its local wq/wk/wv columns produced — numerically identical to the
    same columns of the replicated computation (head h attends only to
    its own KV stripe; a column slice of a matmul reorders nothing).
    The tiled all-gather concatenates the stripes in device order =
    head order, so the replicated wo contraction that follows runs on a
    bit-identical [.., d] input.  No-op when unsharded."""
    if shard_axis is None:
        return att
    return jax.lax.all_gather(att, shard_axis, axis=-1, tiled=True)


# ------------------------------------------------ chunked decode steps
#
# The serving step (serving/decode_engine.py; docs/serving.md "Chunked
# prefill"): ONE jitted step advances a MIX of decode rows (1 token) and
# prompt-ingesting rows (up to K tokens — Sarathi-style chunked prefill
# on the Orca-style slot scheduler).  Row r feeds tokens[r, :lengths[r]] at positions
# positions[r] .. positions[r]+lengths[r]-1; lane i attends causally
# within the chunk AND over the row's live prefix (cols <= its own
# position), and the returned logits are each row's LAST fed lane —
# exactly what lm_prefill + lm_decode_step compose to, so greedy
# streams stay bit-identical to lm_generate.  lengths is DATA: the
# per-step chunk budget never retraces.


def _chunk_lanes(positions, lengths, kk):
    """(clamped lane indices [S, K], per-lane query positions [S, K]).
    Lanes past a row's ``lengths`` clamp to its LAST active lane: they
    re-compute (and re-write) the last real token's K/V — identical
    values at an identical target, so the duplicate scatter is
    deterministic and no garbage ever lands in the cache."""
    lane = jnp.arange(kk)[None, :]
    li = jnp.minimum(lane, lengths[:, None] - 1)
    return li, positions[:, None] + li


def _cached_self_attn_chunk(blk, x, c, li, qpos, pos_mask, num_heads,
                            rope_pos=None, shard_axis=None):
    """``_cached_self_attn`` with PER-ROW, PER-LANE positions over a slot
    slab: row r scatter-writes lane i's K/V at its own ``qpos[r, i]``
    and lane i attends under its own mask row (cols <= qpos[r, i] —
    causal within the chunk, clamped at the live prefix).  Writes happen
    BEFORE the attention, so within-chunk causality falls out of the
    ordinary masked cache read.  Lane numerics are position-local
    (batched matmuls over the flattened [S*K] leading axis), so each
    lane computes exactly ``_cached_self_attn``'s result at
    t=qpos[r, i], whatever the other slots and lanes are doing.

    shard_axis: set inside the serving shard_map — blk's wq/wk/wv are
    local head stripes, c local KV stripes, num_heads the LOCAL count;
    everything below computes the stripe exactly as the single chip
    computes those heads, and ``_shard_gather_att`` reassembles before
    the replicated wo."""
    s, kk, _d = x.shape
    h = _ln(blk["ln1"], x)
    k_new = linear.matmul(h, blk["attn"]["wk"])
    q = linear.matmul(h, blk["attn"]["wq"])
    if rope_pos is not None:
        dh = q.shape[-1] // num_heads
        k_new = _rope_flat(k_new, rope_pos, dh)
        q = _rope_flat(q, rope_pos, dh)
    v_new = linear.matmul(h, blk["attn"]["wv"])
    # clamped-lane selection: inactive lanes take the last active lane's
    # values, so their (duplicate-target) writes are bit-identical
    k_sel = jnp.take_along_axis(k_new, li[:, :, None], axis=1)
    v_sel = jnp.take_along_axis(v_new, li[:, :, None], axis=1)
    rows = jnp.arange(s)[:, None]
    # quantize-on-write (int8 cache): duplicate clamped lanes quantize
    # identical values to identical targets, so the scatter stays
    # deterministic; scales None on the f32 path
    k_set, v_set, sk, sv = _kv_writes(c, k_sel, v_sel)
    upd = lambda buf, val: buf.at[rows, qpos].set(val)
    nc, ks, vs = _kv_commit(c, upd, k_set, v_set, sk, sv)
    k, v = nc["k"], nc["v"]
    # fused Pallas kernel (ops/pallas/decode_attention.py): each row's
    # stripe streams HBM->VMEM once and every lane consumes it in VMEM —
    # no [S, K, T] score matrix, grouped KV expanded in registers (int8:
    # scale sidecars dequantized there too).  None -> the reference XLA
    # path (the CPU tier-1 default; the pallas_decode flag gates — see
    # maybe_slab_chunk), which widens the stripe via _kv_view — same
    # math as the kernel's register dequant.
    from paddle_tpu.ops.pallas import decode_attention as _decode_kernels
    att = _decode_kernels.maybe_slab_chunk(q, k, v, qpos, num_heads,
                                           kscale=ks, vscale=vs)
    if att is None:
        att = _attend(q, _kv_view(k, ks), _kv_view(v, vs), num_heads,
                      pos_mask)
    att = _shard_gather_att(att, shard_axis)
    return x + linear.matmul(att, blk["attn"]["wo"]), nc


def lm_decode_chunk_slots(params, tokens, positions, lengths, cache,
                          num_heads=8, moe_top_k=2, pos_type="learned",
                          all_lanes=False, shard_axis=None):
    """The slot-slab serving step: every row of the slab advances
    ``lengths[r]`` (1..K) positions in ONE step, each row at its OWN
    position — the continuous-batching twin of ``lm_decode_step`` (which
    advances the whole batch by one token at one shared t).

    tokens [S, K] int32 (row r's lanes < lengths[r] are fed; the rest
    are ignored — callers pad with anything in-vocab), positions [S]
    (lane 0's position), lengths [S] in [1, K]; cache: per-enc-layer K/V
    [S, max_len, Dkv] (``init_lm_cache``) -> (logits [S, V] at each
    row's LAST fed lane, new cache).  Lane i of row r computes exactly
    ``lm_decode_step``'s result at t=positions[r]+i: the position row is
    gathered instead of sliced, the K/V write is a per-row scatter, and
    the attention mask is per-lane ``<= qpos`` — same values, same
    masked-softmax width (masked logits sit at -1e30, whose exp is
    exactly 0.0, so cache width beyond a lane's position never perturbs
    its numerics).  A row with lengths[r]=1 is one plain decode step; a
    row chunking through its prompt computes exactly what sequential
    steps would — tokens and lengths are DATA, so mixing decode and
    prefill rows never retraces.  tests/test_decode_engine.py pins the
    per-request bit-identity against ``lm_generate``.

    all_lanes=True (a TRACE-TIME constant, like num_heads) projects
    EVERY lane instead of only the last fed one -> logits [S, K, V]:
    the speculative-decoding verify surface (serving/speculative.py) —
    lane i's logits are the target's next-token distribution after the
    prefix through lane i, so host-side acceptance can take the longest
    matched greedy prefix from ONE step.

    shard_axis (trace-time): the tensor-parallel serving path
    (docs/serving.md "Sharded decode") — inside the engine's shard_map
    params/cache are local head/vocab stripes and num_heads the LOCAL
    count (src_emb shards its VOCAB axis, so the embedded x keeps the
    full width d and the sqrt(d) scale is untouched); the two all-gather
    seams (attention output, logits) plus the embedding psum reassemble
    bit-identically to the single chip.  The draft trunk's rollout runs
    through here inside its own shard_map."""
    params = _maybe_dequant(params)
    s, kk = tokens.shape
    max_len = cache[0]["k"].shape[1]
    li, qpos = _chunk_lanes(positions, lengths, kk)
    x = _lm_embed(params, tokens, shard_axis)
    x = x * math.sqrt(x.shape[-1])
    if pos_type == "learned":
        x = x + params["pos"][qpos]
    rope_pos = qpos if pos_type == "rope" else None
    pos_mask = jnp.arange(max_len)[None, None, :] <= qpos[:, :, None]
    new_cache = []
    for blk, c in zip(params["enc"], cache):
        x, nc = _cached_self_attn_chunk(blk, x, c, li, qpos, pos_mask,
                                        num_heads, rope_pos, shard_axis)
        x = x + _block_ffn(blk, _ln(blk["ln2"], x), moe_top_k)[0]
        new_cache.append(nc)
    if all_lanes:
        return _lm_project(params, x, shard_axis), new_cache
    h_last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
    return _lm_project(params, h_last, shard_axis)[:, 0], new_cache


def _cached_self_attn_chunk_paged(blk, x, c, li, qpos, tables, pos_mask,
                                  num_heads, rope_pos=None,
                                  shard_axis=None):
    """``_cached_self_attn_chunk`` over a PAGED KV pool: the cache is a
    shared pool of fixed-size blocks ``[num_blocks, block_size, Dkv]``
    and each row's K/V live wherever its block table says (``tables``
    [S, blocks_per_row] int32 of physical block ids).  Lane i of row r
    scatter-writes into ``pool[tables[r, qpos // bs], qpos % bs]`` (host
    scheduling guarantees writer exclusivity for the WHOLE span before
    the step: a block being written has pool refcount 1 — the
    copy-on-write fork in serving/kv_pool.py; free rows all target the
    reserved scratch block 0, whose contents are never attended) and
    attends over its own chain.  The chain's values at positions <=
    qpos[r, i] are exactly what the slab holds at those logical
    positions, and masked positions contribute exp(-1e30) = 0.0, so the
    lane's numerics are bit-identical to ``_cached_self_attn_chunk`` —
    shared physical blocks and all."""
    s = qpos.shape[0]
    block_size = c["k"].shape[1]
    h = _ln(blk["ln1"], x)
    k_new = linear.matmul(h, blk["attn"]["wk"])
    q = linear.matmul(h, blk["attn"]["wq"])
    if rope_pos is not None:
        dh = q.shape[-1] // num_heads
        k_new = _rope_flat(k_new, rope_pos, dh)
        q = _rope_flat(q, rope_pos, dh)
    v_new = linear.matmul(h, blk["attn"]["wv"])
    k_sel = jnp.take_along_axis(k_new, li[:, :, None], axis=1)
    v_sel = jnp.take_along_axis(v_new, li[:, :, None], axis=1)
    rows = jnp.arange(s)[:, None]
    bids = tables[rows, qpos // block_size]
    offs = qpos % block_size
    k_set, v_set, sk, sv = _kv_writes(c, k_sel, v_sel)
    upd = lambda buf, val: buf.at[bids, offs].set(val)
    nc, ks, vs = _kv_commit(c, upd, k_set, v_set, sk, sv)
    k, v = nc["k"], nc["v"]
    # fused Pallas paged kernel (ops/pallas/decode_attention.py): the
    # block table rides as scalar-prefetch data and the kernel walks
    # each row's chain in place — no [S, T, Dkv] gathered copy, no
    # score matrix (perf/analytic.py's fusion-proof gate pins the
    # gather's absence; int8 sidecar blocks ride the same walk).
    # None -> the reference chain-gather path.
    from paddle_tpu.ops.pallas import decode_attention as _decode_kernels
    att = _decode_kernels.maybe_paged_chunk(q, k, v, qpos, tables,
                                            num_heads, kscale=ks,
                                            vscale=vs)
    if att is None:
        # chain gather: [S, blocks_per_row, bs, Dkv] -> [S, T, Dkv]
        # where T = blocks_per_row * bs covers every position a row can
        # hold (int8: the gathered chain widens via its gathered scales)
        k_rows = _kv_view(k[tables],
                          None if ks is None else ks[tables]) \
            .reshape(s, -1, k.shape[-1])
        v_rows = _kv_view(v[tables],
                          None if vs is None else vs[tables]) \
            .reshape(s, -1, v.shape[-1])
        att = _attend(q, k_rows, v_rows, num_heads, pos_mask)
    att = _shard_gather_att(att, shard_axis)
    return x + linear.matmul(att, blk["attn"]["wo"]), nc


def lm_decode_chunk_paged(params, tokens, positions, lengths, cache,
                          tables, num_heads=8, moe_top_k=2,
                          pos_type="learned", all_lanes=False,
                          shard_axis=None):
    """The paged twin of ``lm_decode_chunk_slots``: same lane semantics
    over K/V block pools ``[num_blocks, block_size, Dkv]``
    (``init_lm_cache_paged``) addressed through ``tables`` [S,
    blocks_per_row] int32 physical block ids (block 0 = the reserved
    scratch block free rows point at).  The block table is DATA, not
    shape: admission, eviction and copy-on-write forks churn ``tables``
    between steps without ever retracing (tests/test_kv_pool.py pins 1
    warm-up trace, 0 after).  ``all_lanes`` is the same trace-time
    verify switch; ``shard_axis`` the same tensor-parallel switch — each
    chip walks the SAME replicated block tables over its local Hkv/n
    stripe of every pool block."""
    params = _maybe_dequant(params)
    s, kk = tokens.shape
    block_size = cache[0]["k"].shape[1]
    t_span = tables.shape[1] * block_size
    li, qpos = _chunk_lanes(positions, lengths, kk)
    x = _lm_embed(params, tokens, shard_axis)
    x = x * math.sqrt(x.shape[-1])
    if pos_type == "learned":
        x = x + params["pos"][qpos]
    rope_pos = qpos if pos_type == "rope" else None
    pos_mask = jnp.arange(t_span)[None, None, :] <= qpos[:, :, None]
    new_cache = []
    for blk, c in zip(params["enc"], cache):
        x, nc = _cached_self_attn_chunk_paged(blk, x, c, li, qpos,
                                              tables, pos_mask,
                                              num_heads, rope_pos,
                                              shard_axis)
        x = x + _block_ffn(blk, _ln(blk["ln2"], x), moe_top_k)[0]
        new_cache.append(nc)
    if all_lanes:
        return _lm_project(params, x, shard_axis), new_cache
    h_last = jnp.take_along_axis(x, (lengths - 1)[:, None, None], axis=1)
    return _lm_project(params, h_last, shard_axis)[:, 0], new_cache


def _kv_layer_buffers(params, lead_shape, kv_dtype, num_heads):
    """One layer list of K/V buffers shaped ``lead_shape + (Dkv,)`` —
    the shared core of ``init_lm_cache``/``init_lm_cache_paged``.
    ``kv_dtype="int8"`` adds the per-(position, head) f32 scale
    sidecars ``{"ks", "vs"}`` of ``lead_shape + (Hkv,)`` (quant/kv.py);
    None/"float32" keeps the float layout byte-identical to before.
    The sidecar width derives from ``num_heads``, so int8 REQUIRES the
    trunk's real head count — a defaulted/wrong one would silently
    quantize at the wrong granularity."""
    if kv_dtype not in (None, "float32", "int8"):
        raise ValueError(f"kv_dtype={kv_dtype!r} (supported: "
                         "'float32', 'int8')")
    emb = params["src_emb"]
    dt = jnp.float32 if _w_quantized(emb) else emb.dtype
    d = _w_shape(emb)[1]
    if kv_dtype == "int8":
        if num_heads is None:
            raise ValueError(
                "kv_dtype='int8' needs the trunk's num_heads: the "
                "per-(position, head) scale sidecar is sized Hkv = "
                "Dkv / (d_model / num_heads)")
        if d % num_heads:
            raise ValueError(f"num_heads={num_heads} does not divide "
                             f"d_model={d}")
    layers = []
    for blk in params["enc"]:
        dkv = _w_shape(blk["attn"]["wk"])[1]
        dkv_v = _w_shape(blk["attn"]["wv"])[1]
        c = {"k": jnp.zeros(lead_shape + (dkv,),
                            jnp.int8 if kv_dtype == "int8" else dt),
             "v": jnp.zeros(lead_shape + (dkv_v,),
                            jnp.int8 if kv_dtype == "int8" else dt)}
        if kv_dtype == "int8":
            dh = d // num_heads
            if dkv % dh or dkv_v % dh:
                raise ValueError(
                    f"head_dim {dh} (d_model {d} / num_heads "
                    f"{num_heads}) does not divide Dkv {dkv}/{dkv_v}")
            c["ks"] = jnp.zeros(lead_shape + (dkv // dh,), jnp.float32)
            c["vs"] = jnp.zeros(lead_shape + (dkv_v // dh,), jnp.float32)
        layers.append(c)
    return layers


def init_lm_cache_paged(params, num_blocks, block_size, max_len=None,
                        kv_dtype=None, num_heads=None):
    """K/V block pools for ``lm_decode_chunk_paged``: per enc layer
    ``{"k","v"}`` of ``[num_blocks, block_size, Dkv]`` — the paged twin
    of ``init_lm_cache`` (same per-block KV width inference, so GQA
    trunks get proportionally smaller blocks).  Block 0 is reserved as
    the scratch block free rows read/write; the allocator
    (serving/kv_pool.py BlockPool) hands out ids 1..num_blocks-1.
    ``max_len``: the logical per-row span, validated against the learned
    positional table exactly like ``init_lm_cache`` (a rope trunk has no
    cap).  ``kv_dtype="int8"``: int8 pools + per-(position, head) scale
    sidecar pools ``[num_blocks, block_size, Hkv]`` — ~4x smaller
    blocks, so a fixed byte budget holds ~2x the block count
    (serving/kv_pool.slab_equivalent_blocks)."""
    if num_blocks < 2 or block_size < 1:
        raise ValueError(
            f"paged cache needs num_blocks >= 2 (one is the reserved "
            f"scratch block) and block_size >= 1; got {num_blocks}, "
            f"{block_size}")
    if max_len is not None and "pos" in params \
            and max_len > _w_shape(params["pos"])[0]:
        raise ValueError(
            f"lm decode max_len {max_len} exceeds the positional table "
            f"({_w_shape(params['pos'])[0]}); re-init with a larger max_len "
            "or use pos_type='rope'")
    return _kv_layer_buffers(params, (num_blocks, block_size), kv_dtype,
                             num_heads)


def init_lm_cache(params, batch, max_len, kv_dtype=None,
                  num_heads=None):
    """K/V buffers for lm_decode_step (mirrors init_decode_cache, but for
    the enc stack the LM trunk runs).  ``kv_dtype="int8"``: int8 slab +
    per-(position, head) f32 scale sidecars (quant/kv.py)."""
    if "pos" in params and max_len > _w_shape(params["pos"])[0]:
        # learned table caps the length; a rope trunk has no cap
        raise ValueError(
            f"lm decode max_len {max_len} exceeds the positional table "
            f"({_w_shape(params['pos'])[0]}); re-init with a larger max_len "
            "or use pos_type='rope'")
    # per-block KV width from the projection itself: grouped-KV trunks
    # (init num_kv_heads=) get the proportionally smaller cache — the
    # point of GQA at serving time
    return _kv_layer_buffers(params, (batch, max_len), kv_dtype,
                             num_heads)


def lm_generate(params, prompt, max_len, num_heads=8, temperature=0.0,
                top_k=0, rng=None, eos_id=None, prompt_lengths=None,
                moe_top_k=2, pos_type="learned", kv_dtype=None):
    """Autoregressive sampling from the decoder-only LM (KV-cached, one
    jittable lax.scan): prompt [B, Tp] int ids -> ids [B, max_len]
    beginning with each row's prompt.  prompt_lengths [B] supports
    RAGGED prompts in one batch (rows padded to Tp; row i's generation
    starts at its own length — pad value never matters because causal
    attention keeps padding positions out of real ones and the scan
    rewrites each position's K/V as it passes).

    temperature=0 is greedy (deterministic argmax — the rollout the
    oracle test replays with full-sequence lm_logits); otherwise
    categorical over logits/temperature, optionally truncated to the
    top_k highest-probability tokens.  eos_id: rows that emit it keep
    emitting it (done-row pinning, matching beam-search semantics).

    The prompt is consumed by ONE batched causal pass (lm_prefill — the
    MXU-friendly leg that fills the KV cache for all Tp positions at
    once); the per-token scan starts at the SHORTEST row's length and
    re-feeds longer rows' remaining prompt tokens (their K/V rewrites
    are identical — projections are position-local).

    kv_dtype="int8": the scan runs on the quantized KV cache
    (quant/kv.py) — the single-batch oracle for the quantized serving
    engines, exactly as the f32 path is for theirs."""
    params = _maybe_dequant(params)
    prompt = jnp.asarray(prompt, jnp.int32)
    b, tp = prompt.shape
    if not (0 < tp <= max_len):
        raise ValueError(f"prompt length {tp} must be in [1, {max_len}]")
    if temperature and rng is None:
        raise ValueError("temperature > 0 sampling needs rng=jax.random."
                         "PRNGKey(...)")
    vocab = params["src_emb"].shape[0]
    if top_k and not (0 < top_k <= vocab):
        # the negative gather index would silently clamp inside jit and
        # disable truncation entirely
        raise ValueError(f"top_k={top_k} must be in [1, vocab={vocab}]")
    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if prompt_lengths is None:
        lengths = jnp.full((b,), tp, jnp.int32)
        t_start = tp
    else:
        lengths = jnp.asarray(prompt_lengths, jnp.int32)
        # static scan start: the shortest row's length when concrete
        # (the usual outside-jit call); under a trace fall back to
        # re-feeding from position 1 (still one prefill for the bulk).
        # Two traced shapes exist: an ARGUMENT is a Tracer (int() would
        # raise TracerIntegerConversionError), a closed-over constant
        # stages its ops (ConcretizationTypeError) — handle both.
        if isinstance(lengths, jax.core.Tracer):
            t_start = 1
        else:
            try:
                t_start = int(jnp.min(lengths))
            except jax.errors.ConcretizationTypeError:
                t_start = 1
            else:
                if t_start < 1 or int(jnp.max(lengths)) > tp:
                    raise ValueError(
                        f"prompt_lengths must be in [1, {tp}] (got "
                        f"[{t_start}, {int(jnp.max(lengths))}])")

    def sample(logits, key):
        if not temperature:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        logits = logits / temperature
        if top_k:
            from paddle_tpu.ops.sampling import top_k as topk_op
            kvals, _ = topk_op(logits, top_k)       # lax.top_k, no sort
            logits = jnp.where(logits < kvals[:, -1:], -jnp.inf, logits)
        return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)

    hidden, cache = lm_prefill(params, prompt, max_len, num_heads,
                               moe_top_k, pos_type, kv_dtype=kv_dtype)
    # each row's first generated token comes from ITS last real
    # position — gather the hidden state first, project ONE position
    # (the d_model x vocab matmul is the expensive part)
    h_last = jnp.take_along_axis(
        hidden, (lengths - 1)[:, None, None], axis=1)
    logits0 = _lm_project(params, h_last)[:, 0]
    rng, sub = jax.random.split(rng)
    first = sample(logits0, sub)
    ids0 = jnp.zeros((b, max_len), jnp.int32)
    ids0 = jax.lax.dynamic_update_slice(ids0, prompt, (0, 0))
    # seed each row's first generated slot; a row whose prompt already
    # fills max_len keeps its prompt value (clamped position, old value)
    seed_pos = jnp.minimum(lengths, max_len - 1)
    keep = jnp.take_along_axis(ids0, seed_pos[:, None], axis=1)[:, 0]
    ids0 = ids0.at[jnp.arange(b), seed_pos].set(
        jnp.where(lengths < max_len, first, keep))

    def step(carry, t):
        # token at t is generated for rows with lengths <= t, still
        # prompt for longer rows (re-fed; identical K/V rewrite)
        ids, cache, key, done = carry
        tok = jnp.take_along_axis(ids, t[None, None], axis=1)[:, 0]
        logits, cache = lm_decode_step(params, tok, t, cache,
                                       num_heads, moe_top_k, pos_type)
        key, sub = jax.random.split(key)
        nxt = sample(logits, sub)
        if eos_id is not None:
            # only GENERATED eos pins a row: a bos==eos vocab or an
            # eos-valued separator inside the prompt must not suppress
            # the whole continuation
            done = done | ((tok == eos_id) & (t >= lengths))
            nxt = jnp.where(done, eos_id, nxt)
        # rows whose prompt extends past t keep their given token; the
        # slot at a row's own `lengths` was seeded from prefill logits
        cur = jnp.take_along_axis(ids, (t + 1)[None, None], axis=1)[:, 0]
        nxt = jnp.where((t + 1) <= lengths, cur, nxt)
        ids = jax.vmap(lambda row, v: row.at[t + 1].set(v))(ids, nxt)
        return (ids, cache, key, done), None

    init = (ids0, cache, rng, jnp.zeros((b,), bool))
    (ids, _, _, _), _ = jax.lax.scan(step, init,
                                     jnp.arange(t_start, max_len - 1))
    return ids

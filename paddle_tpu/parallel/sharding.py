"""Parameter/activation sharding rules.

This is the TPU-native replacement for the reference's entire distributed
parameter plane: ParameterServer2 block sharding (pserver/ParameterServer2.h:
115-120 blockOffsetMap_), ParameterClient2 block routing (block i -> server
i mod N), and MultiGradientMachine's replicate-params/ring-reduce-grads
(MultiGradientMachine.h:57-74).  Here the rules are declarative PartitionSpecs
handed to jit; XLA inserts the psum/all-gather/reduce-scatter collectives
that the reference hand-built with sockets and threads.

Default policy (overridable per-param by regex rules):
  - embeddings [vocab, dim]       -> shard vocab over 'model' (the reference's
                                     sparse pserver ports / SparseRowMatrix)
  - large fc kernels [in, out]    -> shard out over 'model' (megatron column)
    paired projections back       -> shard in  over 'model' (megatron row)
  - everything else               -> replicated (psum'd grads = the pserver
                                     dense path)
Optimizer state inherits its parameter's spec via the same path matching.
"""

import re
from typing import Optional

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from paddle_tpu.parallel.mesh import AXIS_DATA, AXIS_MODEL

shard_map = jax.shard_map


def _path_str(path):
    parts = []
    for entry in path:
        if hasattr(entry, "key"):
            parts.append(str(entry.key))
        elif hasattr(entry, "idx"):
            parts.append(str(entry.idx))
        else:
            parts.append(str(entry))
    return "/".join(parts)


class ShardingRules:
    """Ordered (regex -> PartitionSpec) rules matched against the pytree path
    'layer_name/param_name'."""

    def __init__(self, rules=None, default=P()):
        self.rules = [(re.compile(pat), spec) for pat, spec in (rules or [])]
        self.default = default

    def spec_for(self, path: str) -> P:
        for pat, spec in self.rules:
            if pat.search(path):
                return spec
        return self.default


def megatron_rules(extra=()):
    """Column-parallel in-projections, row-parallel out-projections, sharded
    embeddings (tensor parallelism over the 'model' axis)."""
    rules = list(extra) + [
        (r"emb|embedding|table", P(AXIS_MODEL, None)),
        # attention: q/k/v in-projections column-parallel (head sharding),
        # out-projection row-parallel — megatron's attention split
        (r"(^|/)(w[qkv]|wqkv)$", P(None, AXIS_MODEL)),
        (r"(^|/)wo$", P(AXIS_MODEL, None)),
        (r"(w_out|proj_out|o_proj|fc2|down)(/|$)", P(AXIS_MODEL, None)),
        (r"(^|/)(w|w\d+|kernel)$", P(None, AXIS_MODEL)),
    ]
    return ShardingRules(rules)


def valid_spec(spec: P, shape, mesh: Mesh, path: str = None) -> P:
    """Drop axis assignments that don't evenly divide the dim (that dim
    falls back to replication) — keeps tiny/odd params replicated instead of
    erroring, like the reference's block-size threshold in
    ParameterClient2::calcParameterBlockSize.

    Every fallback on a non-trivial dim is logged: a fat embedding silently
    replicated onto every chip is exactly the OOM you want a warning for."""
    from paddle_tpu.utils.logging import logger
    ndim = len(shape)
    entries = list(tuple(spec)) + [None] * (ndim - len(tuple(spec)))
    out = []
    for i, axis in enumerate(entries[:ndim]):
        if axis is None:
            out.append(None)
            continue
        axes = axis if isinstance(axis, tuple) else (axis,)
        size = int(np.prod([mesh.shape[a] for a in axes]))
        ok = shape[i] % size == 0 and shape[i] >= size
        if not ok and int(np.prod(shape)) >= 65536:
            logger.warning(
                "sharding: %sdim %d of shape %s not divisible by %s=%d -> "
                "REPLICATED (%.1f MB per device)",
                f"{path}: " if path else "", i, tuple(shape), axes, size,
                np.prod(shape) * 4 / 2 ** 20)
        out.append(axis if ok else None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def param_shardings(params, mesh: Mesh, rules: Optional[ShardingRules] = None):
    """NamedSharding pytree for jit in_shardings/out_shardings/device_put."""
    rules = rules or ShardingRules()
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: NamedSharding(
            mesh, valid_spec(rules.spec_for(_path_str(path)),
                             np.shape(leaf), mesh, path=_path_str(path))),
        params)


def shard_params(params, mesh: Mesh, rules: Optional[ShardingRules] = None):
    """Place a params pytree onto the mesh (the pserver 'scatter parameters
    to shards' moment, minus the sockets)."""
    shardings = param_shardings(params, mesh, rules)
    return jax.tree_util.tree_map(jax.device_put, params, shardings)


def batch_shardings(feed, mesh: Mesh):
    """Shard every array's leading (batch) dim over 'data'; scalars
    replicated.  SequenceBatch lengths shard over 'data' too.  Leaves may
    be jax.ShapeDtypeStructs (the SGD.precompile AOT path lowers against
    abstract feeds)."""
    def spec_for_leaf(x):
        shape = getattr(x, "shape", None)
        nd = len(shape) if shape is not None else np.ndim(x)
        if nd == 0:
            return NamedSharding(mesh, P())
        return NamedSharding(mesh, P(*([AXIS_DATA] + [None] * (nd - 1))))
    return jax.tree_util.tree_map(spec_for_leaf, feed)


def replicated_shardings(tree, mesh: Mesh):
    return jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P()), tree)


# ------------------------------------------------- sharded serving decode
#
# The serving-side tensor-parallel policy (docs/serving.md "Sharded
# decode").  Unlike the training rules above, serving carries a HARD
# bit-identity guarantee against the single-chip twin, which rules out
# megatron row-parallel entirely: a psum of partial contractions reorders
# a float sum and therefore changes bits.  Only tensors whose sharded
# compute is a pure COLUMN SLICE of the replicated compute are split —
# the per-column numerics are untouched and a tiled all-gather
# reassembles the columns in device order, i.e. the original order:
#
#   - wq/wk/wv shard their out-feature (head) axis: each chip computes a
#     contiguous stripe of heads exactly as the single chip would.
#   - the KV cache (slab rows or pool blocks, float or int8 + scale
#     sidecars) shards its trailing head axis the same way — each chip
#     holds its Hkv/n stripe of EVERY row/block, so block tables,
#     allocator, prefix index and CoW stay replicated host data.
#   - src_emb shards its vocab axis: the input lookup is a local gather
#     whose misses are exact zeros (psum-of-zeros seam), and the tied
#     logits projection is a local vocab stripe re-gathered tiled.
#   - EVERYTHING else (wo, the FFN, biases, LNs, pos) is replicated —
#     their contractions run whole on every chip, bit-identically.
#
# The two all-gather seams (attention output, logits) plus the embedding
# psum are the ONLY collectives in the step.

_RX_EMB_SCALE = re.compile(r"(^|/)src_emb/(s|__scale__)$")
_RX_EMB = re.compile(r"(^|/)src_emb(/(q|__int8__))?$")
_RX_QKV = re.compile(r"/attn/w[qkv](/(q|s|__int8__|__scale__))?$")


def lm_decode_param_specs(params, axis=AXIS_MODEL):
    """PartitionSpec pytree for the decoder-only LM trunk under the
    bit-exact serving policy above.  Quantized ``{"q","s"}`` leaves
    shard together: a per-out-channel scale ``[1, dout]`` rides its out
    axis with the int8 payload; src_emb's scale is per-COLUMN ``[1, d]``
    (the vocab axis is the one reduced over) and stays replicated."""
    def spec(path, leaf):
        p = _path_str(path)
        if _RX_EMB_SCALE.search(p):
            return P()
        if _RX_EMB.search(p):
            return P(axis, None)
        if _RX_QKV.search(p):
            return P(None, axis)
        return P()
    return jax.tree_util.tree_map_with_path(spec, params)


def lm_cache_specs(cache, axis=AXIS_MODEL):
    """Trailing-axis (head-stripe) specs for a slab or paged KV cache
    tree: every buffer — K/V and the int8 scale sidecars — is
    ``[lead..., Hkv*dh or Hkv]``, so each chip holds its ``Hkv/n``
    stripe of every slot row / pool block."""
    return jax.tree_util.tree_map(
        lambda l: P(*([None] * (np.ndim(l) - 1) + [axis])), cache)


def new_lm_cache(build, mesh, axis=AXIS_MODEL):
    """A fresh KV cache from ``build()`` (a zero-argument constructor such
    as ``lambda: init_lm_cache_paged(...)``), every buffer born as its
    per-chip head stripes: the constructor runs jitted with the
    ``lm_cache_specs`` shardings as its out_shardings, so each chip only
    ever allocates its own share.  Built whole and spread afterwards, a
    pool sized to the per-chip budget of an n-chip mesh would first have
    to fit — n times over — on the one chip it was created on."""
    shardings = jax.tree_util.tree_map(
        lambda spec: NamedSharding(mesh, spec),
        lm_cache_specs(jax.eval_shape(build), axis))
    return jax.jit(build, out_shardings=shardings)()


def lm_shard_problems(params, num_heads, shards):
    """Why this LM trunk CANNOT split ``shards`` ways under the
    bit-exact policy (empty list = it can): every sharded axis must
    divide evenly — query heads (wq stripes), KV heads (a contiguous
    ``Hkv/n`` stripe only lines up with its query stripe's GQA groups
    when ``n | Hkv``) and vocab (embedding stripes)."""
    shards = int(shards)
    if shards <= 1:
        return []
    from paddle_tpu.quant.weights import weight_shape
    probs = []
    vocab = int(weight_shape(params["src_emb"])[0])
    if num_heads % shards:
        probs.append(f"num_heads={num_heads} not divisible by "
                     f"shards={shards}")
    if vocab % shards:
        probs.append(f"vocab={vocab} not divisible by shards={shards}")
    enc = params.get("enc") or []
    if enc and num_heads and num_heads % shards == 0:
        d_q = int(weight_shape(enc[0]["attn"]["wq"])[1])
        dkv = int(weight_shape(enc[0]["attn"]["wk"])[1])
        dh = d_q // num_heads
        hkv = dkv // dh if dh and dkv % dh == 0 else 0
        if not hkv or hkv % shards:
            probs.append(f"kv heads={hkv or f'?(dkv={dkv})'} not "
                         f"divisible by shards={shards}")
    return probs


def decode_mesh(shards, devices=None):
    """A 1-axis ``('model',)`` mesh over the first ``shards`` local
    devices — the serving mesh (no data axis: continuous batching IS
    the batch plane, and its slots axis must stay whole for the
    per-row scatter writes)."""
    devices = list(jax.devices() if devices is None else devices)
    shards = int(shards)
    if shards < 1 or shards > len(devices):
        raise ValueError(
            f"decode_mesh: shards={shards} outside [1, "
            f"{len(devices)} visible devices]")
    return Mesh(np.asarray(devices[:shards]), (AXIS_MODEL,))


def globalize_pytree(tree, shardings, gather=None):
    """Host pytree -> global jax.Arrays on a process-spanning mesh.
    Every process holds the same host value (SPMD discipline:
    deterministic init / identical batch streams); each device takes its
    addressable shard via the callback.  The single implementation behind
    both the trainer's synchronous path (SGD._globalize) and the prefetch
    producer thread (data.prefetch.device_placer) — the multi-process
    assembly is subtle enough that two copies would drift.

    gather: optional fn pulling an already-global (non-fully-addressable)
    jax.Array back to a host value first; leaves are assumed host-side
    when omitted."""
    def conv(x, sh):
        if gather is not None and isinstance(x, jax.Array) \
                and not x.is_fully_addressable:
            x = gather(x)
        a = np.asarray(x)
        return jax.make_array_from_callback(a.shape, sh, lambda idx: a[idx])
    return jax.tree_util.tree_map(conv, tree, shardings)

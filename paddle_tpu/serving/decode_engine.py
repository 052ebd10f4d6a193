"""Continuous-batching generation: slot-based KV-cache decode engine.

``models/transformer.lm_generate`` decodes one fixed prompt batch end to
end: a single long request holds the whole batch hostage, finished rows
keep burning decode steps until the slowest row is done, and new
requests wait for the entire batch to drain.  This module is the serving
answer (Orca-style iteration-level scheduling, Sarathi-style chunked
prefill, over a vLLM-style cache):

* ``DecodeEngine`` — ``num_slots`` slots, each holding one request at
  its own depth, and ONE jitted step (``lm_decode_chunk_slots`` /
  ``lm_decode_chunk_paged``, or a model's own ``decode_chunk``) that
  advances every slot by up to ``prefill_chunk`` = K token lanes: a
  decoding slot feeds 1 lane, a slot still ingesting its prompt feeds
  up to K prompt tokens (re-derived emissions swallowed until the last
  chunk, whose output is the first real token).  Tokens, positions AND
  per-slot lane counts are data, so the chunk budget tunes without
  retracing; there is no separate prefill program, no admission write
  and no prompt cap below ``max_len`` — one executable is the whole
  serving hot path (a model's step packs the lanes its rows feed and is
  the same program compiled at up to three widths, each at warm-up:
  docs/serving.md "The packed lanes").  Admission and eviction happen BETWEEN steps,
  entirely on the host, so scheduling never touches compiled code and
  the step traces exactly once at warm-up and never again
  (``expect_traces`` discipline, shared with ``InferenceEngine.warmup``
  and ``SGD.precompile``).  Every greedy stream is bit-identical to
  running the request alone through ``lm_generate`` (the parity tests
  pin this token for token).

* Two cache layouts.  ``kv_layout="slab"``: a fixed-shape KV-cache SLAB
  ``[num_slots, max_len, Dkv]`` per layer (``init_lm_cache``); a freed
  slot's row is simply overwritten by its next occupant.
  ``kv_layout="paged"`` (docs/serving.md §5): per layer a block POOL
  ``[num_blocks, block_size, Dkv]`` plus per-slot block tables, managed
  by the host-side allocator in ``serving/kv_pool.py`` (free list,
  per-block refcounts, copy-on-write forks, prefix index).  Memory is
  committed per BLOCK as a stream actually grows instead of ``max_len``
  up front, so mixed-length traffic packs by actual length, and requests
  sharing a prompt prefix map their leading blocks to the SAME physical
  blocks (admission takes references instead of re-ingesting — the
  vLLM/PagedAttention memory tier).  The block table is data, not
  shape, so admission/eviction/fork churn never retraces, and greedy
  streams stay bit-identical to the slab and to ``lm_generate``
  (tests/test_kv_pool.py).

* ``GenerationBatcher`` — the request front: bounded queue, per-request
  deadlines (``DeadlineExceededError`` while queued), admission control
  (``InvalidRequestError`` before the queue, ``OverloadedError`` on a
  full queue), streaming ``on_token`` callbacks, graceful drain, and
  batch-failure isolation (a step failure fails only the requests that
  were in flight; the engine resets and keeps serving).

Greedy decode only (temperature-0 argmax inside the jitted step): the
deterministic serving mode whose numerics the oracle tests can pin.
Sampling stays on ``lm_generate``.
"""

import collections
import queue
import threading
import time
from concurrent.futures import Future, InvalidStateError

import numpy as np
import jax
import jax.numpy as jnp

from paddle_tpu.obs import trace as obstrace
from paddle_tpu.resilience import faults
from paddle_tpu.resilience.supervisor import (BreakerOpenError,
                                              WatchdogTimeout)
from paddle_tpu.serving.batcher import (BatchExecutionError,
                                        DeadlineExceededError,
                                        OverloadedError, ShutdownError)
from paddle_tpu.serving.engine import InvalidRequestError
from paddle_tpu.quant.kv import KV_DTYPES
from paddle_tpu.quant.weights import weight_shape as _w_shape
from paddle_tpu.serving.kv_pool import (BLOCK_LEAF, SLOT_LEAF, HostTier,
                                        InsufficientBlocksError,
                                        PagedKVState,
                                        RestorePendingError,
                                        WireFormatError, leaf_bytes,
                                        map_block_leaves,
                                        peek_chain_header,
                                        restore_chain, serialize_chain,
                                        slab_equivalent_blocks)
from paddle_tpu.serving.metrics import ServingMetrics
from paddle_tpu.testing.trace import expect_traces
from paddle_tpu.utils.error import ConfigError
from paddle_tpu.utils.logging import logger

# lane 0 of a row whose next token is the pick of the step before, which
# has not left the device: ids are validated non-negative, so a negative
# one can carry this meaning through the host arrays the step takes anyway
PICK_IN_FLIGHT = -1


def _lane0_from_device(tokens, prev):
    """The step's token lanes with ``PICK_IN_FLIGHT`` in lane 0 replaced
    by that row of ``prev``, the step before's picks."""
    lane0 = tokens[:, 0]
    return tokens.at[:, 0].set(jnp.where(lane0 < 0, prev, lane0))


class _StepHandle:
    """One dispatched device step until its tokens are read: the device
    outputs, the host arrays it was fed, and when it was handed over."""

    __slots__ = ("nxt", "prev", "tokens", "pos", "lens", "aux",
                 "spec_armed", "n_active", "epoch", "t0", "step", "done")

    def __init__(self, **kw):
        self.done = False
        for k, v in kw.items():
            setattr(self, k, v)


class DecodeEngine:
    """Slot-based continuous-batching decoder over a decoder-only LM trunk
    (``models/transformer`` params with ``dec_layers=0``).

    params: the trunk pytree; num_slots: concurrent requests the engine
    holds; max_len: positions a slot can hold — every request must
    satisfy ``len(prompt) + max_tokens <= max_len``; eos_id: default
    stop token (None = run to max_tokens; per-request override at
    submit).

    prefill_chunk: K >= 1, the token lanes of the one step — prompts
    ingest through it as up-to-K-token chunks (``[S, K]`` token lanes;
    docs/serving.md "Chunked prefill"), a decoding slot feeds one.
    prefill_chunk_budget: max teacher-forced lanes one step may feed
    across all slots (0 = unbounded) — pure data, bounds per-step
    prefill work and hence TPOT jitter.  With ``model=`` it also bounds
    the widths the step is compiled at: no step feeds more than each
    row's own lane and the budget's.
    report_logits (``model=`` only): every step also leaves its logits
    ``[num_slots, vocab]`` on the device, beside what the model reports
    (``step_aux``, ``recorded_steps()``): the compiled step that serves is
    then the one a check or a log-probability surface reads.

    kv_layout: ``"slab"`` (default — one ``[num_slots, max_len, Dkv]``
    row per slot) or ``"paged"`` (a shared ``[kv_num_blocks,
    kv_block_size, Dkv]`` block pool + per-slot block tables,
    serving/kv_pool.py; docs/serving.md §5).  Paged-only knobs:
    kv_block_size (positions per block); kv_num_blocks (pool size
    including the reserved scratch block 0; 0 = auto-size to the slab
    equivalent ``num_slots * ceil(max_len / block_size) + 1`` — same KV
    bytes, strictly more packable); prefix_cache (share resident prompt-
    prefix blocks across requests, copy-on-write on divergence).

    kv_dtype: ``"float32"`` (default) or ``"int8"`` — quantized serving
    (quant/kv.py; docs/serving.md "Quantized serving"): the cache
    stores int8 K/V + per-(position, head) f32 scale sidecars, every
    scatter-write quantizes on the way in, the fused kernels widen in
    registers, and the paged auto-sizing DOUBLES ``kv_num_blocks`` at
    the same byte budget.  Composable with quantized weights
    (quant/weights.quantize_lm — just pass the quantized params tree).

    Slot lifecycle (docs/serving.md §4): FREE -> SEATED (context fed K
    lanes a step, emissions swallowed) -> ACTIVE -> one emitted token
    per ``step()`` -> EVICTED (eos | length | error | shutdown |
    pool_exhausted) -> FREE.  All bookkeeping is host-side numpy; the
    device only ever sees the fixed-shape step (and, paged, the
    fixed-shape block fork and restore write).
    """

    def __init__(self, params, *, num_heads=8, num_slots=8, max_len=256,
                 eos_id=None, moe_top_k=2, pos_type="learned",
                 metrics=None, name="lm", warm=True,
                 kv_layout="slab", kv_block_size=16, kv_num_blocks=0,
                 prefix_cache=True, prefill_chunk=8,
                 prefill_chunk_budget=0, kv_dtype="float32",
                 speculate_k=0, draft=None, mesh=None, kv_host_bytes=0,
                 model=None, report_logits=False):
        from paddle_tpu.models import transformer
        self._transformer = transformer
        # model=None: the transformer trunk (every default below).  A
        # model object (models/hybrid_lm.Served) brings its own chunk step
        # and cache, whose leaves it declares block- or slot-addressed
        # (kv_pool.BLOCK_LEAF / SLOT_LEAF; docs/serving.md "Models that
        # hold state")
        self._model = model
        self._leaf_kinds = None
        if model is not None:
            self._check_model_config(
                kv_layout=kv_layout, prefix_cache=prefix_cache,
                speculate_k=speculate_k, kv_host_bytes=kv_host_bytes,
                mesh=mesh, kv_dtype=kv_dtype)
            self._leaf_kinds = model.cache_kinds()
        elif report_logits:
            raise ConfigError(
                "report_logits serves with model= only: the trunk's step "
                "takes its argmax inside the sharded body")
        self.report_logits = bool(report_logits)
        # does a slot own state that seating must reset (a SLOT_LEAF)
        self._slot_state = SLOT_LEAF in jax.tree_util.tree_leaves(
            self._leaf_kinds)
        if params.get("dec"):
            raise ConfigError(
                "DecodeEngine serves the decoder-only LM trunk "
                "(init dec_layers=0); this params tree has a seq2seq "
                "decoder stack — use generate_cached for that")
        self.params = params
        self.num_heads = int(num_heads)
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.eos_id = eos_id
        self.moe_top_k = moe_top_k
        self.pos_type = pos_type
        self.name = name
        self._metrics = metrics or ServingMetrics()
        # chunked prefill (docs/serving.md "Chunked prefill"): prompt
        # ingestion rides the ONE jitted step — each step advances a mix
        # of decode rows (1 token) and admitting rows (up to K prompt
        # tokens, logits discarded until the last chunk).
        # prefill_chunk_budget: max teacher-forced lanes per step across
        # all slots (0 = unbounded) — data, not shape, so tuning it
        # never retraces.
        self.prefill_chunk = int(prefill_chunk or 0)
        self.prefill_chunk_budget = int(prefill_chunk_budget or 0)
        if not 1 <= self.prefill_chunk <= self.max_len:
            raise ConfigError(
                f"prefill_chunk={prefill_chunk} must be in "
                f"[1, max_len={self.max_len}] (0 used to select the "
                "bucketed prefill ladder, which is gone: prompts ingest "
                "through the one chunk step)")
        # speculative decoding (serving/speculative.py; docs/serving.md
        # "Speculative decoding"): a draft trunk proposes up to
        # speculate_k tokens per slot, the ONE chunked step scores them
        # all as verify lanes, and host-side acceptance commits the
        # longest greedily-matched prefix via advance(consumed=).  The
        # draft only ever changes SPEED — acceptance keeps exactly what
        # the target would have emitted, so streams stay bit-identical
        # to lm_generate.  k/acceptance/per-slot draft state are DATA:
        # churn never retraces.
        self.speculate_k = int(speculate_k or 0)
        if self.speculate_k < 0 or self.speculate_k >= self.max_len:
            raise ConfigError(
                f"speculate_k={speculate_k} must be in "
                f"[0, max_len={self.max_len})")
        if draft is not None and not self.speculate_k:
            raise ConfigError("a draft trunk without speculate_k > 0 "
                              "would never run")
        if self.speculate_k and draft is None:
            raise ConfigError(
                "speculate_k > 0 needs a draft (a DraftTrunk, or a "
                "params tree to build one from — serving/speculative."
                "make_draft derives one from the target's)")
        # token-lane width: the chunk step's K dimension must hold the
        # larger of a prefill chunk and a full verify span (the
        # committed token + speculate_k draft lanes)
        self._kk = max(self.prefill_chunk, self.speculate_k + 1)
        if self.num_slots < 1:
            raise ConfigError("num_slots must be >= 1")
        if kv_layout not in ("slab", "paged"):
            raise ConfigError(f"kv_layout={kv_layout!r} (supported: "
                              "'slab', 'paged')")
        if int(kv_host_bytes) < 0:
            raise ConfigError(
                f"kv_host_bytes={kv_host_bytes} must be >= 0")
        if int(kv_host_bytes) and kv_layout != "paged":
            raise ConfigError(
                "kv_host_bytes needs kv_layout='paged': the host tier "
                "spills evicted prefix-chain blocks")
        if kv_dtype not in KV_DTYPES:
            raise ConfigError(f"kv_dtype={kv_dtype!r} (supported: "
                              f"{KV_DTYPES})")
        self.kv_layout = kv_layout
        # int8 KV (quant/kv.py; docs/serving.md "Quantized serving"):
        # the cache stores int8 K/V + per-(position, head) f32 scale
        # sidecars, the step quantizes scatter-writes on the way in, and
        # the fused kernels widen in registers.  The pytree structure is
        # what threads it — no step-signature change, so the 1-trace/
        # 0-retrace discipline is untouched.
        self.kv_dtype = kv_dtype
        # tensor-parallel sharded decode (docs/serving.md "Sharded
        # decode"): mesh=... runs the ONE step under
        # parallel.sharding.shard_map with head-sharded attention, a
        # head-sharded KV pool (each chip holds its Hkv/n stripe of
        # every slot row / pool block — tables/allocator/prefix-index/
        # CoW stay replicated host data) and vocab-sharded tied
        # embeddings.  Only column-slice-exact tensors shard, so greedy
        # streams are BIT-IDENTICAL to the single-chip twin; wo and the
        # FFN replicate (a row-parallel psum would reorder float sums).
        self.mesh = mesh
        self.mesh_shards = 1
        self._shard_axis = None
        if mesh is not None:
            from paddle_tpu.parallel import sharding as _psh
            from paddle_tpu.parallel.mesh import AXIS_MODEL
            from jax.sharding import NamedSharding
            if AXIS_MODEL not in dict(mesh.shape):
                raise ConfigError(
                    "DecodeEngine(mesh=...) needs a mesh with a "
                    f"'{AXIS_MODEL}' axis "
                    "(parallel.sharding.decode_mesh builds one)")
            probs = _psh.lm_shard_problems(params, self.num_heads,
                                           int(mesh.shape[AXIS_MODEL]))
            if probs:
                raise ConfigError(
                    f"cannot shard this trunk over the mesh: "
                    + "; ".join(probs))
            self._psh = _psh
            self._shard_axis = AXIS_MODEL
            self.mesh_shards = int(mesh.shape[AXIS_MODEL])
            # place the params ONCE: wq/wk/wv + src_emb (and their int8
            # payload/scale leaves) as stripes, everything else
            # replicated — step and reset both reuse this placement
            pspecs = _psh.lm_decode_param_specs(params, AXIS_MODEL)
            params = jax.tree_util.tree_map(
                lambda l, s: jax.device_put(l, NamedSharding(mesh, s)),
                params, pspecs)
            self.params = params
        self._paged = None
        self._host_tier = None
        self._pending_restores = {}
        if kv_layout == "paged":
            self.block_size = int(kv_block_size)
            if self.block_size < 1:
                raise ConfigError("kv_block_size must be >= 1")
            # kv_num_blocks=0 auto-sizes to the SLAB-EQUIVALENT byte
            # budget — int8 blocks are small enough that the same budget
            # holds 2x the count, and a mesh multiplies by n: each chip
            # stores only its Hkv/n stripe of a block, so the PER-CHIP
            # budget holds n× the blocks (slab_equivalent_blocks)
            num_blocks = (int(kv_num_blocks) if kv_num_blocks
                          else slab_equivalent_blocks(
                              self.num_slots, self.max_len,
                              self.block_size, kv_dtype,
                              mesh_shards=self.mesh_shards))
            # hierarchical KV (docs/serving.md "Hierarchical KV"):
            # kv_host_bytes > 0 attaches an LRU host-RAM spill tier —
            # prefix chains evicted under pool pressure serialize to
            # host blobs instead of being destroyed, and the next hit
            # restores them over the host link when the analytic model
            # says that beats recomputing (perf/analytic.py)
            if int(kv_host_bytes):
                if not prefix_cache:
                    raise ConfigError(
                        "kv_host_bytes needs the prefix cache: the host "
                        "tier spills/restores prefix-index chains")
                if mesh is not None:
                    raise ConfigError(
                        "kv_host_bytes is single-chip for now: a sharded "
                        "pool's blocks are head stripes, and the "
                        "cross-replica payload transport is ROADMAP "
                        "item 2(b)")
                self._host_tier = HostTier(cap_bytes=int(kv_host_bytes))
            # host allocator + prefix index + per-slot block tables
            self._paged = PagedKVState(
                self.num_slots, num_blocks, self.block_size, self.max_len,
                prefix_cache=prefix_cache,
                on_evict=self._spill_chain if self._host_tier is not None
                else None)
            # per-layer [num_blocks, block_size, Dkv] pools (block 0 is
            # the scratch block free slot rows point at)
            self._cache = self._new_cache(self._build_paged_cache)
            # host-tier restore bookkeeping (``_pending_restores``: one
            # in-flight marker per prefix key -> (epoch at submit,
            # t_submit) — poll_restores drops a job whose epoch went
            # stale, its claim having died with the old paged state).
            # The trunk signature fences blob relocation to identical
            # trunks; the param count/bytes feed the restore-vs-
            # recompute model.
            enc = params.get("enc") or []
            if model is None:
                d = int(_w_shape(params["src_emb"])[1])
                dkv = int(_w_shape(enc[0]["attn"]["wk"])[1]) if enc else 0
                self._kv_dims = (len(enc), dkv)
                self._trunk_sig = (f"L{len(enc)}.d{d}.dkv{dkv}"
                                   f".h{self.num_heads}.{kv_dtype}"
                                   f".b{self.block_size}")
            leaves = jax.tree_util.tree_leaves(params)
            self._param_count = sum(int(l.size) for l in leaves)
            self._param_bytes = sum(
                int(l.size) * np.dtype(l.dtype).itemsize for l in leaves)
            # the staging job (transfer thread) rebuilds per-block chunk
            # pytrees matching the cache structure WITHOUT touching the
            # live (donated) cache: structure and leaf names are frozen
            # here, once — they are reset-stable (same init fn)
            flat = jax.tree_util.tree_flatten_with_path(self._cache)
            self._cache_leaf_names = [jax.tree_util.keystr(p)
                                      for p, _l in flat[0]]
            self._cache_treedef = flat[1]
        else:
            # init_lm_cache validates max_len against the positional table
            self._cache = self._new_cache(
                lambda: transformer.init_lm_cache(
                    params, self.num_slots, self.max_len,
                    kv_dtype=kv_dtype, num_heads=self.num_heads))
        # prefill-compute ledger: context positions seated for ingestion
        # through the step (the paged prefix cache's whole point is to
        # NOT grow this: a resident prefix seats by reference)
        self.prefill_positions_total = 0
        # host-side slot state: the K token lanes fed at the NEXT step,
        # how many of them are live (_len — the per-slot variable
        # advance) and the position lane 0 sits at; free slots idle at
        # (0, 0, 1 lane) — their compute is discarded and their cache
        # row is overwritten by the next occupant
        self._tokens = np.zeros((self.num_slots, self._kk), np.int32)
        self._len = np.ones((self.num_slots,), np.int32)
        # draft-side host bookkeeping (speculative mode).  Invariant per
        # active slot: _d_pos + len(_d_feed) == _pos + 1 — every
        # committed token (and nothing else) either sits in the draft
        # cache or waits in the feed.  Rollout writes past the committed
        # stream are NEVER counted: they are re-fed on commit, and the
        # chunk step writes lanes BEFORE attending, so stale draft K/V
        # is overwritten before anything reads it.
        self._draft = None
        if self.speculate_k:
            from paddle_tpu.serving.speculative import DraftTrunk
            if not isinstance(draft, DraftTrunk):
                draft = DraftTrunk(
                    draft, k=self.speculate_k, num_slots=self.num_slots,
                    max_len=self.max_len,
                    chunk=max(self.speculate_k + 2, self.prefill_chunk),
                    num_heads=self.num_heads, moe_top_k=self.moe_top_k,
                    pos_type=self.pos_type, name=f"{self.name}.draft",
                    warm=False, mesh=self.mesh)
            elif draft.mesh_shards != self.mesh_shards:
                raise ConfigError(
                    f"draft trunk spans {draft.mesh_shards} mesh "
                    f"shard(s) but the engine spans {self.mesh_shards}: "
                    "build the DraftTrunk with the engine's mesh (or "
                    "pass the raw draft params and let the engine "
                    "build it)")
            elif (draft.k != self.speculate_k
                  or draft.num_slots != self.num_slots
                  or draft.max_len < self.max_len):
                raise ConfigError(
                    f"draft trunk (k={draft.k}, slots={draft.num_slots}, "
                    f"max_len={draft.max_len}) does not match the engine "
                    f"(k={self.speculate_k}, slots={self.num_slots}, "
                    f"max_len={self.max_len})")
            self._draft = draft
            self._d_feed = [[] for _ in range(self.num_slots)]
            self._d_pos = np.zeros((self.num_slots,), np.int32)
            self._d_last = np.zeros((self.num_slots,), np.int32)
            self._spec_armed = {}      # slot -> k_eff armed for the next step
            self._spec_result = {}     # slot -> accepted emission run
        self._pos = np.zeros((self.num_slots,), np.int32)
        self._free = list(range(self.num_slots))[::-1]   # pop() -> slot 0 first
        # epoch guard: reset() bumps it, step() refuses to commit across
        # a bump — a watchdog-abandoned step finishing LATE (its thread
        # cannot be killed) can never write its cache into a rebuilt
        # slab.  The lock makes {epoch check + cache commit} atomic
        # against {epoch bump + slab rebuild}: without it a stale step
        # could pass the check and then overwrite the fresh slab.
        self._epoch = 0
        self._epoch_lock = threading.Lock()
        self._step_traces = {}     # lanes a compiled step computes -> traces
        # resolved at warm-up (the step's trace time): did the compiled
        # step take the fused Pallas decode-attention path, and if not,
        # the guard's reason (ops/pallas/decode_attention.decline_reason)
        self.decode_kernels = False
        self.decode_decline_reason = None
        # K/V positions a step of the fused kernel covers
        # (decode_attention.tile_positions); None on the reference path
        self.decode_tile = None
        # the same for a model's recurrent kernel (ops/pallas/kda.py):
        # False and None where there is no model or it has no such layer
        self.kda_kernels = False
        self.kda_decline_reason = None
        # and for its latent-attention kernel (ops/pallas/mla.py)
        self.mla_kernels = False
        self.mla_decline_reason = None
        # its selective-scan kernel (ops/pallas/mamba.py)
        self.mamba_kernels = False
        self.mamba_decline_reason = None
        # and the paged decode-attention kernel under its softmax
        # attention layers (the trunk's own is ``decode_kernels``)
        self.attn_kernels = False
        self.attn_decline_reason = None
        # and its windowed form under the window layers, over their rings
        self.window_kernels = False
        self.window_decline_reason = None
        # and the indexer, selection and attention kernels of its sparse
        # layers (ops/pallas/dsa.py)
        self.sparse_kernels = False
        self.sparse_decline_reason = None
        # positions a window layer attends (0: the model has none)
        self._window = getattr(model, "window", 0)
        self._window_attended = 0
        # positions a sparse layer's lane keeps (0: the model has none),
        # and those the prepared step's lanes select, all sparse layers
        self._sparse = getattr(model, "sparse_topk", 0)
        self._selected = 0
        # what a model's last step reported of itself (hybrid_lm: the
        # chosen experts), left on the device; None for the trunk
        self.step_aux = None
        self._step_log = None      # a list while record_steps() is on
        self._step_keep = None
        # the picks of the step dispatched last, on the device: the next
        # step's argument (zeros before the first step and after a reset),
        # born and replaced together with the cache it belongs to
        self._prev_pick = self._new_pick()
        self._last_step = None     # that step's handle
        self._t_ready = 0.0        # when a step's tokens were last read
        self._attended = 0         # positions the prepared step attends

        # all_lanes is a TRACE-TIME constant: a speculating engine's
        # step returns EVERY lane's argmax [S, K] (the verify surface —
        # host acceptance needs the target's pick after each draft
        # lane); a plain chunked engine keeps the last-lane [S] output
        spec = bool(self.speculate_k)
        # inside the sharded step's shard_map the model sees LOCAL head
        # stripes; the single-chip path sees the full count.  Both are
        # trace-time constants.
        axis = self._shard_axis
        heads = (self.num_heads // self.mesh_shards if axis is not None
                 else self.num_heads)

        def _traced(width):     # runs only under tracing
            self._step_traces[width] = self._step_traces.get(width, 0) + 1

        if model is not None:
            def _step_fn(p, cache, prev, tokens, pos, lens, tables, src,
                         back):
                _traced(src.size)
                logits, cache, aux = model.decode_chunk(
                    p, _lane0_from_device(tokens, prev), pos, lens, cache,
                    tables, src, back)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                if self.report_logits:
                    aux = (aux, logits)
                return (nxt, aux), cache
        elif self.kv_layout == "paged":
            def _model(p, cache, tokens, pos, lens, tables):
                logits, cache = transformer.lm_decode_chunk_paged(
                    p, tokens, pos, lens, cache, tables, heads,
                    self.moe_top_k, self.pos_type, all_lanes=spec,
                    shard_axis=axis)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache
            body = self._shard_body(_model, n_data=4)

            def _step_fn(p, cache, prev, tokens, pos, lens, tables):
                _traced(tokens.size)
                return body(p, cache, _lane0_from_device(tokens, prev),
                            pos, lens, tables)
        else:
            def _model(p, cache, tokens, pos, lens):
                logits, cache = transformer.lm_decode_chunk_slots(
                    p, tokens, pos, lens, cache, heads,
                    self.moe_top_k, self.pos_type, all_lanes=spec,
                    shard_axis=axis)
                return jnp.argmax(logits, axis=-1).astype(jnp.int32), cache
            body = self._shard_body(_model, n_data=3)

            def _step_fn(p, cache, prev, tokens, pos, lens):
                _traced(tokens.size)
                return body(p, cache, _lane0_from_device(tokens, prev),
                            pos, lens)
        # donate the cache: the step rewrites a few positions per row, the
        # rest is carried through — without donation every step would copy
        # the whole slab/pool.  A model's step is compiled once for each of
        # its widths (the shape of ``src``), each with its cache in place
        self._jit_step = jax.jit(_step_fn, donate_argnums=(1,))
        # the lanes a step may compute, narrowest first: the trunk has one
        # shape; a model packs the lanes its rows feed and names the
        # widths it is worth compiling (hybrid_lm.step_widths)
        self.step_widths = (self.num_slots * self._kk,) if model is None \
            else tuple(model.step_widths(self.num_slots, self._kk))
        if model is not None and self.prefill_chunk_budget:
            # a budgeted step feeds each row's own lane and the budget's
            # at most: a wider program would be compiled and never run
            most = self.num_slots + self.prefill_chunk_budget
            self.step_widths = tuple(
                w for i, w in enumerate(self.step_widths)
                if i == 0 or self.step_widths[i - 1] < most)

        # block writes and copies touch the block-addressed leaves alone
        # (every leaf of the transformer trunk's cache)
        kinds = self._leaf_kinds

        def _write_fn(cache, chunk, bid):
            self._write_traces[0] += 1
            return map_block_leaves(
                lambda c, ch: c.at[bid].set(ch.astype(c.dtype)),
                cache, kinds, chunk)

        def _copy_fn(cache, src, dst):
            self._copy_traces[0] += 1
            return map_block_leaves(
                lambda c: c.at[dst].set(c[src]), cache, kinds)

        # paged device ops: ONE fixed [block_size, Dkv] write shape (a
        # host-tier restore landing a block) and the copy-on-write
        # block fork
        self._write_traces = [0]
        self._copy_traces = [0]
        self._jit_write = jax.jit(_write_fn, donate_argnums=(0,))
        self._jit_copy = jax.jit(_copy_fn, donate_argnums=(0,))
        self._warm = False
        if warm:
            self.warmup()

    # ------------------------------------------------ models with state

    @staticmethod
    def _check_model_config(*, kv_layout, prefix_cache, speculate_k,
                            kv_host_bytes, mesh, kv_dtype):
        """What ``model=`` cannot be combined with yet.  A slot of such a
        model owns state that no block table addresses; each feature below
        needs a way to SNAPSHOT that state at a position (ROADMAP D6), and
        until one exists the engine refuses rather than serve tokens
        computed from the wrong state."""
        lacks = "needs a state snapshot kind the cache does not have yet"
        if kv_layout != "paged":
            raise ConfigError(
                "model= serves through the paged layout only: the slab "
                "layout keeps one row of positions a slot and has no place "
                "for state that positions do not address")
        if prefix_cache:
            raise ConfigError(
                "prefix_cache with model=: a shared prefix's blocks hold "
                "its latents but not the recurrent state at its end; "
                f"sharing {lacks} (pass prefix_cache=False)")
        if speculate_k:
            raise ConfigError(
                "speculate_k with model=: rejected draft lanes would have "
                f"advanced the recurrent state; rollback {lacks}")
        if kv_host_bytes:
            raise ConfigError(
                "kv_host_bytes with model=: a spilled chain would come "
                f"back without its recurrent state; spill {lacks}")
        if mesh is not None:
            raise ConfigError(
                "mesh with model=: the sharding rules cover the "
                "transformer trunk's K/V stripes only; slot-addressed "
                "state has no placement rule yet")
        if kv_dtype != "float32":
            raise ConfigError(
                "kv_dtype with model=: the model chooses its pool's dtype "
                "(hybrid_lm.Served(latent_dtype=...))")

    def record_steps(self, on=True, keep=None):
        """While on, ``step()`` keeps what each step fed and what the model
        reported of it (``recorded_steps()``): the host arrays it
        snapshotted anyway and the unread device report, or what ``keep(
        tokens, positions, lengths, report)`` makes of them when the step
        is read (a check that wants a few rows of a large report takes them
        there, and the rest is freed with the step).  For a check that
        must know what the SERVER's step chose (the benchmark hands the
        routed experts of streamed tokens to its reference); off by
        default, and nothing is kept then."""
        self._step_keep = keep
        self._step_log = [] if on else None

    def recorded_steps(self):
        """[(tokens [S, K], positions [S], lengths [S], report)] of the
        steps since ``record_steps()``, oldest first; with
        ``report_logits`` the report is (the model's, logits [S, V]), on
        the device (or what ``keep`` made of it)."""
        return list(self._step_log or ())

    def slot_state(self, slot):
        """Host copies of what ``slot`` owns of a model's cache, as the
        last committed step left it: the cache's tree with the slot's part
        of each slot-addressed leaf, ``None`` for a block-addressed one.
        For a check BETWEEN steps (nothing in flight: a step handed over
        meanwhile donates the buffers this reads)."""
        return jax.tree_util.tree_map(
            lambda leaf, kind: np.asarray(leaf[slot])
            if kind == SLOT_LEAF else None, self._cache, self._leaf_kinds)

    def _build_paged_cache(self):
        """A fresh, zeroed paged cache: the model's own, or the
        transformer trunk's K/V pools."""
        blocks = self._paged.pool.num_blocks
        if self._model is not None:
            return self._model.init_cache(self.num_slots, blocks,
                                          self.block_size, chunk=self._kk)
        return self._transformer.init_lm_cache_paged(
            self.params, blocks, self.block_size, max_len=self.max_len,
            kv_dtype=self.kv_dtype, num_heads=self.num_heads)

    # --------------------------------------------------- sharded decode

    def _new_cache(self, build):
        """A fresh KV cache from the zero-argument constructor ``build``.
        Sharded engines get every buffer born as per-chip head stripes
        (``parallel.sharding.new_lm_cache``: each chip holds its ``Hkv/n``
        stripe of every slot row / pool block, and never more).  Used at
        construction AND by ``reset()`` — a recovery rebuild must come
        back with the same placement or the warm step would recompile."""
        if self._shard_axis is None:
            return build()
        return self._psh.new_lm_cache(build, self.mesh, self._shard_axis)

    def _new_pick(self):
        """Zeros in the shape and placement of a step's picks ``[S]``."""
        zeros = jnp.zeros((self.num_slots,), jnp.int32)
        if self._shard_axis is None:
            return zeros
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.device_put(zeros,
                              NamedSharding(self.mesh, PartitionSpec()))

    def _shard_body(self, fn, n_data):
        """Wrap a step body in ``parallel.sharding.shard_map``
        over the engine's mesh (identity when unsharded).  in_specs:
        the param-stripe tree, the cache-stripe tree, then ``n_data``
        replicated host operands (tokens/pos/lens[/tables]).  The
        replication check is disabled: the tiled all-gathers inside the
        model produce values the checker cannot prove replicated, but
        bit-identity to the twin is pinned by tests, which is the
        stronger guarantee."""
        if self._shard_axis is None:
            return fn
        from jax.sharding import PartitionSpec as _P
        pspecs = self._psh.lm_decode_param_specs(self.params,
                                                 self._shard_axis)
        cspecs = self._psh.lm_cache_specs(self._cache, self._shard_axis)
        return self._psh.shard_map(
            fn, mesh=self.mesh,
            in_specs=(pspecs, cspecs) + (_P(),) * n_data,
            out_specs=(_P(), cspecs), check_vma=False)

    # ------------------------------------------------------------ slots

    def _arm(self, slot, token, pos):
        """Point a slot at (token, position), one lane, for the next
        step."""
        self._tokens[slot, :] = 0
        self._tokens[slot, 0] = token
        self._len[slot] = 1
        self._pos[slot] = pos

    def _set_cache_gauges(self):
        """The bytes a model's two kinds of cache leaf hold, and the share
        of the first that ONE slot owns (0 for the transformer trunk, whose
        pool ``kv_blocks_*`` describe)."""
        if self._model is not None:
            slot_bytes = leaf_bytes(self._cache, self._leaf_kinds, SLOT_LEAF)
            self._metrics.set_state_cache_bytes(
                slot_bytes,
                leaf_bytes(self._cache, self._leaf_kinds, BLOCK_LEAF),
                slot_bytes // self.num_slots)

    @property
    def free_slots(self):
        return len(self._free)

    @property
    def num_active(self):
        return self.num_slots - len(self._free)

    @property
    def step_trace_count(self):
        """Traces of the decode step at the width traced most (the
        no-retrace discipline: exactly 1 after warm-up, flat across
        admission/eviction churn).  The trunk has one width; a model's
        step is compiled at each of ``step_widths``, once each at warm-up,
        and a retrace of any of them reads 2 here (``step_traces`` keeps
        them apart).  ``lower()`` is an offline tool and re-stages (+1)."""
        return max(self._step_traces.values(), default=0)

    @property
    def step_traces(self):
        """{lanes a compiled step computes: times it was traced}."""
        return dict(self._step_traces)

    @property
    def ready(self):
        """Readiness (/readyz): the step (and the paged block ops) are
        warm."""
        return self._warm

    @property
    def metrics(self):
        return self._metrics

    @metrics.setter
    def metrics(self, m):
        # the config gauges are the engine's, not the object's: a fresh
        # metrics object inherits them immediately
        self._metrics = m
        self._set_cache_gauges()
        m.set_prefill_chunk(self.prefill_chunk)
        m.set_kv_dtype(self.kv_dtype)
        m.set_speculate_k(self.speculate_k)
        m.set_mesh_shards(self.mesh_shards)

    def seat_cached(self, full, covered, chain):
        """Seat one request whose leading ``covered`` positions are
        already RESIDENT in ``chain`` (a prefix-cache hit, paged layout
        only): take shared references on the physical blocks — no
        recompute, no copy — arm the slot at ``pre = min(covered,
        len(full) - 1)`` with ``full[pre]``, and return ``(slot,
        replay_feed)`` where replay_feed is the teacher-forced remainder
        ``full[pre+1:]`` (its re-derived emissions are swallowed by the
        batcher, so the stream is bit-identical to a fresh ingestion).
        The slot's first write lands either in a fresh block (divergent
        suffix) or inside the last shared block — which ``prepare_step``
        then copy-on-write forks before the step touches it."""
        if not self._free:
            raise RuntimeError(f"{self.name}: no free decode slot")
        full = np.asarray(full, np.int32)
        pre = min(int(covered), full.size - 1)
        slot = self._free.pop()
        try:
            self._paged.seat_shared(slot, chain, pre + 1)
        except Exception:
            self._free.append(slot)
            raise
        self._arm(slot, full[pre], pre)
        # the draft cache holds NOTHING for this slot (the prefix index
        # is target-only): the covered prefix joins the feed and drains
        # through the draft's chunk ingest before speculation starts
        self._draft_seed(slot, full[:pre + 1])
        return slot, [int(t) for t in full[pre + 1:]]

    def seat_chunked(self, full):
        """Seat one request whose whole context must be ingested: arm a
        free slot at (``full[0]``, position 0) and return ``(slot,
        feed)`` where ``feed = full[1:]`` is what the batcher
        chunk-loads through the step (its re-derived emissions swallowed
        until the last token is fed — whose step output IS the first
        real emission).  No device write at admission: the slab layout
        touches no device state at all, and the paged layout seats an
        EMPTY chain that ``prepare_step`` grows block by block as the
        span advances."""
        if not self._free:
            raise RuntimeError(f"{self.name}: no free decode slot")
        full = np.asarray(full, np.int32)
        slot = self._free.pop()
        if self.kv_layout == "paged":
            try:
                self._paged.seat_fresh(slot, 0)
            except InsufficientBlocksError:
                self._free.append(slot)
                raise
        self._arm(slot, full[0], 0)
        self._draft_seed(slot, full[:1])
        if self._slot_state:
            # the step zeroes the slot's state itself, on seeing position 0
            self.metrics.observe_state_reset()
        return slot, [int(t) for t in full[1:]]

    def load_chunk(self, slot, toks):
        """Arm lanes 1..n of ``slot`` for the NEXT step: after the
        slot's current token, feed ``toks`` (the next teacher-forced
        prompt/replay tokens) in the same step.  Called by the batcher
        strictly BETWEEN steps — lane counts are data, so loading never
        retraces."""
        n = len(toks)
        if n >= self.prefill_chunk:
            raise RuntimeError(
                f"{self.name}: load_chunk({n}) needs prefill_chunk > "
                f"{n} (engine has {self.prefill_chunk})")
        self._tokens[slot, 1:1 + n] = toks
        self._len[slot] = 1 + n
        self.metrics.observe_prefill_chunk(n)

    def chunk_len(self, slot):
        """Lanes the next/current step feeds for ``slot`` (1 = plain
        decode)."""
        return int(self._len[slot])

    @property
    def speculating(self):
        """True when a draft trunk is attached (``speculate_k > 0``)."""
        return self._draft is not None

    @property
    def draft(self):
        """The attached ``DraftTrunk`` (None unless speculating)."""
        return self._draft

    def _draft_seed(self, slot, toks):
        """(Re)start a slot's draft bookkeeping: the draft cache holds
        nothing for it yet, so ``toks`` (its committed context so far)
        becomes the feed the next ``speculate`` calls drain through the
        draft's chunk ingest.  Called at every seat and at eviction —
        recovery/re-seat paths rebuild the draft cache through the same
        one mechanism."""
        if self._draft is None:
            return
        self._d_feed[slot] = [int(t) for t in toks]
        self._d_pos[slot] = 0
        self._d_last[slot] = 0
        self._spec_armed.pop(slot, None)
        self._spec_result.pop(slot, None)

    def speculate(self, budgets):
        """ONE batched draft rollout, strictly between steps: drain up
        to a chunk of every active slot's committed-token feed into the
        draft cache, then arm draft lanes for each slot in ``budgets``
        (slot -> remaining emission allowance) whose feed fully drained
        THIS call — the rollout's candidates are only fresh for those.
        Arms lanes 1..k_eff of the verify span (lane 0 stays the
        committed token) with ``k_eff = min(speculate_k, budget - 1,
        room to max_len)`` and returns {slot: k_eff}.  Everything here
        is data — feed lengths, positions, acceptance — so speculation
        churn never retraces the rollout or the step."""
        if self._draft is None:
            return {}
        chunk = self._draft.chunk
        tokens = np.zeros((self.num_slots, chunk), np.int32)
        positions = np.zeros((self.num_slots,), np.int32)
        lengths = np.ones((self.num_slots,), np.int32)
        fed = {}
        free_set = set(self._free)
        for slot in range(self.num_slots):
            if slot in free_set:
                continue
            feed = self._d_feed[slot]
            take = min(chunk, len(feed))
            if take:
                tokens[slot, :take] = feed[:take]
                positions[slot] = self._d_pos[slot]
                lengths[slot] = take
                fed[slot] = take
            else:
                # nothing pending: idempotently re-feed the last
                # ingested token (identical K/V rewrite) instead of
                # special-casing the row out of the fixed-shape call
                tokens[slot, 0] = self._d_last[slot]
                positions[slot] = max(int(self._d_pos[slot]) - 1, 0)
        drafts = self._draft.rollout(tokens, positions, lengths)
        if drafts is None:
            return {}       # reset() raced the rollout: arm nothing
        for slot, take in fed.items():
            self._d_last[slot] = self._d_feed[slot][take - 1]
            del self._d_feed[slot][:take]
            self._d_pos[slot] += take
        armed = {}
        for slot, budget in budgets.items():
            if fed.get(slot) is None or self._d_feed[slot]:
                continue    # feed not fully drained: candidates stale
            k_eff = min(self.speculate_k, int(budget) - 1,
                        self.max_len - 1 - int(self._pos[slot]))
            if k_eff < 1:
                continue
            self._tokens[slot, 1:1 + k_eff] = drafts[slot, :k_eff]
            self._tokens[slot, 1 + k_eff:] = 0
            self._len[slot] = 1 + k_eff
            self._spec_armed[slot] = k_eff
            armed[slot] = k_eff
        return armed

    def take_spec_result(self, slot):
        """Pop the last step's accepted run for ``slot``: the matched
        draft tokens followed by the target's own argmax at the first
        mismatch (so a run is never empty — every verify step nets at
        least the token a plain step would have produced).  None if the
        slot was not speculating that step."""
        if self._draft is None:
            return None
        return self._spec_result.pop(slot, None)

    def register_context(self, slot, tokens):
        """Publish a fully-ingested context's prompt prefix into the
        paged prefix index (no-op on slab / with the cache off)."""
        if self.kv_layout == "paged":
            self._paged.register_prefix(np.asarray(tokens, np.int32),
                                        slot)

    # ------------------------------------------------- hierarchical KV tier

    @property
    def host_tier(self):
        """The attached host-RAM spill tier (None unless
        ``kv_host_bytes > 0`` on a paged engine)."""
        return self._host_tier

    @property
    def restores_pending(self):
        """True while a host-tier restore is staged or waits for its
        commit (``poll_restores``)."""
        return bool(self._pending_restores)

    def _spill_chain(self, key, covered, chain):
        """``PrefixIndex`` eviction hook: gather the chain's block rows
        off the device (the contents are still owned — the hook fires
        BEFORE the references release), serialize them as a relocatable
        blob (``kv_pool.serialize_chain``), and park it in the host
        tier.  Runs on the batcher worker thread strictly between steps
        (evictions only happen inside ``_alloc``), so the committed
        cache is safe to read."""
        tier = self._host_tier
        # the index registers EVERY full-block prefix of a stream as its
        # own entry, and pool pressure evicts them shortest-first — so a
        # naive hook would serialize the same leading blocks once per
        # prefix length (O(n^2) payload, all on the claim path that is
        # waiting for these very blocks).  A spill is redundant while a
        # LONGER entry of the same stream is still resident (it spills
        # the superset payload if it ever leaves; until then the content
        # is servable from the index itself) or already parked.
        key = tuple(key)
        n = len(key)
        if any(len(k) > n and k[:n] == key
               for k in self._paged.index._entries):
            return
        if tier.covers(key):
            return
        idx = np.asarray(chain, np.int32)
        arrays = [(name, np.asarray(leaf[idx]))
                  for name, leaf in zip(
                      self._cache_leaf_names,
                      jax.tree_util.tree_leaves(self._cache))]
        blob = serialize_chain(key, covered, arrays, self._trunk_sig)
        dropped = tier.put(key, covered, blob)
        self.metrics.observe_kv_spill(len(chain))
        self.metrics.set_host_tier_bytes(tier.bytes)
        obstrace.instant("kv.spill", blocks=len(chain), bytes=len(blob),
                         covered=int(covered), lru_dropped=dropped)

    def _restore_predicted_faster(self, covered):
        """The restore-vs-recompute router (perf/analytic.py): predicted
        wall cost of streaming ``covered`` spilled positions back over
        the host link vs re-running them through chunked prefill, at the
        chip spec matching this backend.  Returns ``(verdict,
        restore_ms, recompute_ms)``."""
        from paddle_tpu.perf import analytic, roofline
        chip = roofline.for_device_kind(jax.devices()[0].device_kind)
        layers, dkv = self._kv_dims
        restore = analytic.predicted_restore_ms(
            covered, layers, dkv, self.num_heads, self.kv_dtype, chip)
        recompute = analytic.predicted_recompute_ms(
            covered, self._param_count, self._param_bytes,
            self.prefill_chunk, chip)
        return restore < recompute, restore, recompute

    def _handoff_predicted_faster(self, covered):
        """The handoff-vs-recompute router (perf/analytic.py): predicted
        wall cost of pulling ``covered`` positions' K/V from a peer
        replica over the network AND restoring them over the host link,
        vs re-running them through chunked prefill here.  Returns
        ``(verdict, handoff_ms, recompute_ms)``."""
        from paddle_tpu.perf import analytic, roofline
        chip = roofline.for_device_kind(jax.devices()[0].device_kind)
        layers, dkv = self._kv_dims
        handoff = analytic.predicted_handoff_ms(
            covered, layers, dkv, self.num_heads, self.kv_dtype, chip)
        recompute = analytic.predicted_recompute_ms(
            covered, self._param_count, self._param_bytes,
            self.prefill_chunk, chip)
        return handoff < recompute, handoff, recompute

    def export_chain(self, tokens):
        """Serialize the longest resident coverage of ``tokens`` as a
        relocatable wire-format blob for a cross-replica handoff
        (serving/transfer.py).  Worker-thread-only — the gather reads
        the committed cache exactly like ``_spill_chain`` does, so HTTP
        handlers must route through ``GenerationBatcher.export_chain``
        (which queues it to run strictly between steps).  Prefers the
        resident prefix index (read-only lookup, no references taken);
        falls back to an already-serialized host-tier blob.  Returns
        ``(key, covered, blob)`` or ``(None, 0, None)``."""
        if self.kv_layout != "paged":
            return None, 0, None
        full = np.asarray(tokens, np.int32)
        covered, chain = self._paged.lookup_prefix(full)
        if covered:
            key = tuple(int(t) for t in full[:covered])
            idx = np.asarray(chain, np.int32)
            arrays = [(name, np.asarray(leaf[idx]))
                      for name, leaf in zip(
                          self._cache_leaf_names,
                          jax.tree_util.tree_leaves(self._cache))]
            blob = serialize_chain(key, covered, arrays,
                                   self._trunk_sig)
            obstrace.instant("kv.handoff_export", blocks=len(chain),
                             bytes=len(blob), covered=int(covered))
            return key, covered, blob
        if self._host_tier is not None:
            key, covered, blob = self._host_tier.lookup(full,
                                                        self.block_size)
            if key is not None:
                obstrace.instant("kv.handoff_export", blocks=0,
                                 bytes=len(blob), covered=int(covered),
                                 from_tier=True)
                return key, covered, blob
        return None, 0, None

    def deliver_chain_blob(self, blob, max_bytes=None):
        """Cross-replica handoff delivery (any thread): validate the
        blob's envelope against THIS engine's trunk signature and park
        it in the host tier.  The next request whose context the blob
        covers seats it through the EXISTING restore pipeline
        (``_maybe_begin_restore`` claim → async stage → between-steps
        commit) — no new jitted code, no new write shape.  Returns
        ``(key, covered)``; raises ``WireFormatError`` (foreign,
        garbled, or pool-poisoning header) or ``ConfigError`` (no host
        tier attached — decode-role replicas need
        ``kv_host_bytes > 0``)."""
        if self._host_tier is None:
            raise ConfigError(
                "handoff delivery needs the host tier: run the decode "
                "replica with kv_host_bytes > 0")
        header = peek_chain_header(blob, self._trunk_sig, max_bytes)
        key = tuple(int(t) for t in header.get("tokens", ()))
        covered = int(header.get("covered", 0))
        # a header that lies about its coverage could wedge receivers in
        # eternal claim-defer (covered > pool) or seat garbage past the
        # key — reject it before it touches the tier
        if not key or covered != len(key) or covered > self.max_len:
            raise WireFormatError(
                f"handoff blob declares covered={covered} over a "
                f"{len(key)}-token key (max_len {self.max_len}); "
                "refusing to pool it")
        self._host_tier.put(key, covered, blob)
        self.metrics.set_host_tier_bytes(self._host_tier.bytes)
        return key, covered

    def _maybe_begin_restore(self, full):
        """Probe the host tier for a spilled coverage of ``full`` after
        the resident prefix index missed.  On a worthwhile hit whose
        restore the analytic model predicts to beat recompute: claim
        fresh blocks (``claim_pending``) and submit the staging job
        (deserialize + per-block ``device_put``) to the tier's transfer
        thread, then return ``RestorePendingError`` — the batcher defers
        the request exactly like a pool-dry one, and its retry after
        ``poll_restores`` commits seats an ordinary resident hit.
        Returns None to route as a plain miss (no tier, no coverage, or
        the model says recompute)."""
        tier = self._host_tier
        if tier is None:
            return None
        full = np.asarray(full, np.int32)
        key, covered, blob = tier.lookup(full, self.block_size)
        if key is None:
            return None
        if key in self._pending_restores:
            return RestorePendingError(
                f"host-tier restore of {covered} position(s) already "
                "in flight")
        faster, restore_ms, recompute_ms = \
            self._restore_predicted_faster(covered)
        obstrace.instant("kv.restore_route", covered=int(covered),
                         restore_ms=round(restore_ms, 4),
                         recompute_ms=round(recompute_ms, 4),
                         restore=faster)
        if not faster:
            return None
        try:
            self._paged.claim_pending(key, covered)
        except InsufficientBlocksError as e:
            return e        # defer without a marker: the pool must
            #                 drain before the claim can even be staged
        names = self._cache_leaf_names
        treedef = self._cache_treedef
        sig = self._trunk_sig

        def _stage(blob=blob):
            # transfer-thread body: deserialize + rebuild one chunk
            # pytree per block (the cache STRUCTURE was frozen at
            # construction — the live donated cache is never touched
            # here) and device_put each; the worker thread _jit_writes
            # them into the claimed blocks between steps
            _toks, cov, arrays = restore_chain(blob, sig)
            named = dict(arrays)
            n_blocks = int(named[names[0]].shape[0]) if names else 0
            chunks = []
            for j in range(n_blocks):
                chunk = jax.tree_util.tree_unflatten(
                    treedef, [named[n][j] for n in names])
                chunks.append(jax.device_put(chunk))
            return cov, chunks

        self._pending_restores[key] = (self._epoch, time.perf_counter())
        tier.submit(key, _stage)
        return RestorePendingError(
            f"host-tier restore of {covered} position(s) started")

    def poll_restores(self, timeout=0.0):
        """Land completed host-tier restores, strictly BETWEEN steps
        (the batcher worker calls this at the top of its loop): write
        each staged chunk into its claimed block (``_jit_write`` — the
        one compiled write shape, zero new traces), publish the chain
        into the prefix index (``commit_pending``), and drop the blob.
        Epoch-guarded: a job submitted before a ``reset()`` is dropped —
        its claim died with the replaced paged state and its blob stays
        resident for the next probe.  A failed job releases its claim
        and forgets the blob (recompute serves the prefix instead).
        Returns the number of restores committed."""
        tier = self._host_tier
        if tier is None or not self._pending_restores:
            return 0
        landed = 0
        while self._pending_restores:
            job = tier.poll(timeout=timeout if not landed else 0.0)
            if job is None:
                break
            key, result = job
            info = self._pending_restores.pop(key, None)
            if info is None:
                continue        # marker cleared by a reset
            epoch, t0 = info
            if epoch != self._epoch:
                obstrace.instant("kv.restore_stale")
                continue
            from paddle_tpu.data.prefetch import _Failure
            chain = list(self._paged._pending.get(key, ()))
            if isinstance(result, _Failure):
                self._paged.release_pending(key)
                tier.pop(key)   # a blob that failed to stage must not
                #                 retry forever
                logger.warning(
                    "%s: host-tier restore failed (prefix falls back to "
                    "recompute): %s: %s", self.name,
                    type(result.exc).__name__, result.exc)
                continue
            covered, chunks = result
            if len(chunks) != len(chain):
                self._paged.release_pending(key)
                tier.pop(key)
                logger.warning(
                    "%s: host-tier restore staged %d block(s) for a "
                    "%d-block claim; dropped", self.name, len(chunks),
                    len(chain))
                continue
            for bid, chunk in zip(chain, chunks):
                self._cache = self._jit_write(self._cache, chunk,
                                              np.int32(bid))
            self._paged.commit_pending(key, covered)
            ent = tier.pop(key)
            self.metrics.observe_kv_restore(
                len(ent[1]) if ent else 0, time.perf_counter() - t0)
            self.metrics.set_host_tier_bytes(tier.bytes)
            obstrace.instant("kv.restore_commit", blocks=len(chain),
                             covered=int(covered))
            landed += 1
        return landed

    def seat_prefilled(self, fulls):
        """THE seat-prefix helper (one definition, four callers: fresh
        admission, ``Supervisor.reprefill`` slot recovery, the batcher's
        continuation-``replay`` leg, and pool-pressure re-seating).  For
        each 1-D ``full`` context array, seat a slot that will hold K/V
        for the context with the following token armed, WITHOUT
        emitting anything on the way:

        1. paged + prefix cache: a resident chain seats by REFERENCE
           (``seat_cached`` — zero recompute for the covered positions;
           any resident coverage strictly shrinks the feed);
        2. a prefix spilled to the host tier defers behind its async
           restore when the analytic model says that beats recompute;
        3. otherwise ``seat_chunked``: the whole context is the feed.

        Either way the remainder returns as the teacher-forced
        ``replay_feed`` the batcher drains K lanes per step through the
        one step, with re-derived emissions swallowed; greedy decode
        being deterministic, the slot ends byte-for-byte at its target
        state.  Returns a list aligned with ``fulls``: ``(slot,
        replay_feed)`` per seated item, or the exception that failed it
        (``InsufficientBlocksError`` means "defer and retry", not
        "fail")."""
        results = [None] * len(fulls)
        for i, full in enumerate(fulls):
            full = np.asarray(full, np.int32)
            if self.kv_layout == "paged":
                covered, chain = self._paged.lookup_prefix(full)
                if covered:
                    try:
                        results[i] = self.seat_cached(full, covered,
                                                      chain)
                        self.prefill_positions_total += max(
                            0, int(full.size) - int(covered))
                    except Exception as e:  # noqa: BLE001 — isolate
                        results[i] = e      # to this item
                    continue
                # resident miss: consult the host tier before burning
                # chunk steps on a prefix one restore away
                pending = self._maybe_begin_restore(full)
                if pending is not None:
                    results[i] = pending
                    continue
                if not self.can_admit(full.size + 1):
                    # pool-dry fast path: defer before burning any work
                    # (growth preemption covers transient shortfalls,
                    # but a context the pool can't plausibly hold yet
                    # should wait, not thrash victims)
                    results[i] = InsufficientBlocksError(
                        f"pool cannot hold {int(full.size) + 1} "
                        "positions yet")
                    continue
            try:
                results[i] = self.seat_chunked(full)
                self.prefill_positions_total += int(full.size)
            except Exception as e:  # noqa: BLE001 — per-item isolation
                results[i] = e
        return results

    def prefix_lookup(self, prompt):
        """``(covered_positions, chain)`` of the longest cached block-
        aligned prefix of ``prompt`` — ``(0, [])`` on a miss or on the
        slab layout.  Read-only (an LRU touch); seating takes the
        references."""
        if self.kv_layout != "paged":
            return 0, []
        return self._paged.lookup_prefix(np.asarray(prompt))

    def can_admit(self, n_positions):
        """Paged admission gate: could the pool produce blocks covering
        ``n_positions`` right now (free list + evictable prefix-index
        entries)?  Always True on the slab layout (the slab reserves per
        slot up front)."""
        if self.kv_layout != "paged":
            return True
        return self._paged.can_admit(int(n_positions))

    def prepare_step(self):
        """Paged layout: make every active slot's CURRENT write position
        exclusive before the step — grow chains into fresh blocks, and
        copy-on-write fork blocks still shared with the prefix index or
        another slot (``cow_forks_total``).  Under pool exhaustion,
        preempt victim slots youngest-first (``evictions{reason=
        "pool_exhausted"}``) and return their ids — the batcher re-seats
        those requests through ``seat_prefilled`` once space frees, so
        their streams continue bit-identically.  Slab layout: no-op."""
        if self.kv_layout != "paged":
            return []
        victims = []
        free_set = set(self._free)
        bs = self.block_size
        for slot in range(self.num_slots):
            if slot in free_set or slot in victims:
                continue
            pos = int(self._pos[slot])
            # the slot writes a SPAN this step (lane 0 .. lane _len-1):
            # provision every touched block, in order, each CoW executed
            # immediately so a mid-span exhaustion can never orphan a
            # planned fork
            n = int(self._len[slot])
            for j in range(pos // bs, (pos + n - 1) // bs + 1):
                p = pos if j == pos // bs else j * bs
                while True:
                    try:
                        plan = self._paged.write_plan(slot, p)
                    except InsufficientBlocksError:
                        v = self._paged.victim(
                            exclude=set(victims) | {slot})
                        if v is None:
                            raise     # one lone request outgrew the pool
                            #           — validate_request bounds this;
                            #           backstop
                        obstrace.instant("kv.pool_exhausted_preempt",
                                         slot=v)
                        self.evict(v, "pool_exhausted")
                        victims.append(v)
                        continue
                    break
                if plan is not None and plan[0] == "cow":
                    _tag, _j, src, dst = plan
                    self._cache = self._jit_copy(self._cache,
                                                 np.int32(src),
                                                 np.int32(dst))
                    obstrace.instant("kv.cow_fork", slot=slot,
                                     src=int(src), dst=int(dst))
                    self.metrics.observe_cow_fork()
        # what the step's lanes will attend: a seated row at position p
        # feeding n lanes adds (p + 1) + ... + (p + n)
        seated = np.ones(self.num_slots, bool)
        seated[self._free] = False
        n, p = self._len[seated].astype(np.int64), self._pos[seated]
        # (kept for the step's own dispatch phase: its ``attended`` stat)
        self._attended = int((n * p + n * (n + 1) // 2).sum())
        self.metrics.observe_attended_positions(self._attended)
        if self._window:
            # and in a window layer: min(q + 1, W) for each lane at q; and
            # what the rows read, each position once a row, in either kind
            self._window_attended, read = self._model.window_counts(p, n)
            self.metrics.observe_window_positions(
                self._window_attended, read, int((p + n).sum()))
        if self._sparse:
            # scored, selected and read in all the sparse layers
            scored, self._selected, read = self._model.sparse_counts(p, n)
            self.metrics.observe_sparse_positions(
                scored, self._selected, read, int((p + n).sum()))
        return victims

    def evict(self, slot, reason):
        """Free a slot (between steps).  Slab: the cache row is left
        as-is — the next occupant overwrites each position in the step
        that first unmasks it.  Paged: the
        slot's block references release (shared blocks stay resident for
        their other sharers / the prefix index)."""
        if self.kv_layout == "paged":
            self._paged.evict(slot)
        self._arm(slot, 0, 0)
        self._draft_seed(slot, [])
        self._free.append(slot)
        self.metrics.evict_slot(reason)

    def step(self):
        """Advance EVERY slot by its armed lanes; returns the next token
        per slot ([num_slots] np.int32: the pick after each slot's last
        fed lane).  Free slots compute too — the trunk's step has ONE
        shape, all ``S x K`` lanes, and that is its cost model; a model's
        step (``model=``) packs the lanes its rows feed, a free slot's one
        armed lane among them, and runs at the narrowest of its compiled
        widths that holds them (docs/serving.md "The packed lanes") — but
        a free slot's output is garbage the caller ignores and
        its cache rows are overwritten by the next occupant.  Callers
        then bump their active slots via ``advance``.

        One synchronous step: ``dispatch_step`` followed at once by
        ``collect_step``.  The generation loop calls the two apart, and
        hands the device step n+1 before it reads step n's tokens."""
        return self.collect_step(self.dispatch_step())

    @property
    def steps_dispatched(self):
        """Ordinal of the next device step: the steps counted, and the
        one whose tokens are not read yet."""
        last = self._last_step
        return self.metrics.decode_steps_total \
            + int(last is not None and not last.done)

    def dispatch_step(self):
        """Hand the device one step over every slot's armed lanes and
        return its handle without reading anything back.  A row whose
        lane 0 holds ``PICK_IN_FLIGHT`` is fed the step before's pick,
        on the device.

        Epoch-guarded: inputs are snapshotted up front and the returned
        cache is committed only if no ``reset()`` happened meanwhile — a
        watchdog-abandoned step that gets here late consumes its own
        (already orphaned) cache buffer and discards itself, instead of
        poisoning the rebuilt slab."""
        # the two host phases of a device step (obs/trace.py phase()):
        # handing the step over, here, and waiting for its tokens, in
        # collect_step.  A supervised step runs both on the watchdog's
        # thread.
        step = self.steps_dispatched
        in_flight = step - self.metrics.decode_steps_total
        with obstrace.phase("engine.step.dispatch", step=step,
                            in_flight=in_flight) as ph:
            epoch = self._epoch
            params, cache, prev = self.params, self._cache, self._prev_pick
            # the host arrays the call takes: snapshotted, so an eviction
            # racing the step changes nothing it reads
            tokens = self._tokens.copy()
            lens = self._len.copy()
            host = [tokens, self._pos.copy(), lens]
            if self.kv_layout == "paged":
                # block tables ride as DATA (snapshotted, like
                # tokens/pos): table churn between steps never retraces
                host.append(self._paged.tables.copy())
            # the lanes the step computes, and those of them that rows
            # feed (a free slot's one armed lane among them).  The trunk
            # computes all S x K; a model packs the fed lanes and runs
            # the narrowest of its widths that holds them, the packing
            # riding as data too (hybrid_lm.Served.pack)
            width = tokens.size
            if self._model is not None:
                src, back = self._model.pack(lens, self._kk)
                host += [src, back]
                width = src.size
            live = int(lens.sum())
            # verify spans armed for THIS step (speculative mode); popped
            # with the snapshot so an eviction racing the step can never
            # resurrect a stale acceptance
            spec_armed = {}
            if self._draft is not None:
                spec_armed, self._spec_armed = self._spec_armed, {}
            # what the step carries, the load its time follows: seated
            # rows, those of them fed a prompt chunk (draft lanes are
            # speculation, as in _collect), the chunks' lanes, and the
            # positions its lanes attend (prepare_step's count, paged)
            prefill_rows = int((lens > 1).sum()) - len(spec_armed)
            # and the seated rows fed exactly one lane: those the tiled
            # attention kernel computes one lane of (a free slot takes
            # that path too, and is not a row of the step)
            seated = np.ones(self.num_slots, bool)
            seated[self._free] = False
            one_lane_rows = int((lens[seated] == 1).sum())
            ph.set(width=width, live=live, lanes=tokens.size,
                   rows=self.num_active, prefill_rows=prefill_rows,
                   prefill_lanes=live - self.num_slots
                   - sum(spec_armed.values()),
                   attended=self._attended, one_lane_rows=one_lane_rows)
            self._attended = 0
            if self._window:
                ph.set(window_attended=self._window_attended)
                self._window_attended = 0
            if self._sparse:
                ph.set(selected=self._selected)
                self._selected = 0
            self.metrics.observe_step_lanes(width, live, prefill_rows,
                                            one_lane_rows)
            # the fault point sits at the device-step boundary: a hang
            # here models a wedged device step for the watchdog to catch
            faults.hit("serving.decode_step")
            t0 = time.perf_counter()
            nxt, cache = self._jit_step(params, cache, prev, *host)
            aux = None
            if self._model is not None:
                nxt, aux = nxt
            handle = _StepHandle(
                nxt=nxt, prev=prev, tokens=tokens, pos=host[1], lens=lens,
                aux=aux, spec_armed=spec_armed, n_active=self.num_active,
                epoch=epoch, t0=t0, step=step)
            with self._epoch_lock:
                if epoch != self._epoch:
                    raise RuntimeError(
                        f"{self.name}: engine was reset mid-step; stale "
                        "step result discarded")
                self._cache = cache
                self.step_aux = aux
                if nxt.ndim == 1:
                    # (a speculating step returns every lane's pick, and
                    # its rows never wait for one: acceptance is host work)
                    self._prev_pick = nxt
                self._last_step = handle
        if in_flight:
            self.metrics.observe_step_overlapped()
        return handle

    def collect_step(self, handle, during=None):
        """Read a dispatched step's tokens ([num_slots] np.int32) and
        account for the step.  ``during``: the ordinal of the loop
        iteration that waits, where it is not the step's own (the phase
        joins the iteration's others by it)."""
        try:
            return self._collect(handle, during)
        finally:
            handle.done = True      # read and counted, or lost

    def _collect(self, handle, during):
        with obstrace.phase("engine.step.wait",
                            step=handle.step if during is None else during,
                            of_step=handle.step):
            nxt = np.asarray(handle.nxt)
        t_ready = time.perf_counter()
        if handle.epoch != self._epoch:
            raise RuntimeError(
                f"{self.name}: engine was reset mid-step; stale step "
                "result discarded")
        tokens, lens = handle.tokens, handle.lens
        log = self._step_log        # (record_steps() may end it meanwhile)
        if log is not None:
            # what the lanes were really fed: the picks a row took on the
            # device are on the host by now (steps are read in order)
            waited = tokens[:, 0] < 0
            if waited.any():
                tokens[waited, 0] = np.asarray(handle.prev)[waited]
            keep = self._step_keep
            log.append((tokens, handle.pos, lens, handle.aux if keep is None
                        else keep(tokens, handle.pos, lens, handle.aux)))
        # teacher-forced lanes this step fed beyond the per-slot token
        # (the chunked-prefill occupancy surface)
        chunk_lanes = int(lens.sum() - self.num_slots)
        kw = {}
        if self._draft is not None:
            # speculating step output is EVERY lane's argmax [S, K]:
            # row[i] is the target's greedy pick after lane i.
            # Acceptance per armed slot: lanes 1..k_eff held drafts
            # d_1..d_k; the matched prefix is the run of d_{i+1} ==
            # row[i], and row[j] at the first mismatch is the target's
            # OWN next token — the accepted run row[:j+1] is exactly
            # what sequential greedy decode would have emitted, which is
            # the whole bit-identity argument.  Non-speculating rows
            # reduce to their last fed lane, same as a plain engine.
            rows = nxt
            nxt = rows[np.arange(self.num_slots), lens - 1]
            accepted = drafted = 0
            for slot, k_eff in handle.spec_armed.items():
                row, want = rows[slot], tokens[slot, 1:1 + k_eff]
                j = 0
                while j < k_eff and int(row[j]) == int(want[j]):
                    j += 1
                self._spec_result[slot] = [int(t) for t in row[:j + 1]]
                accepted += j
                drafted += k_eff
            # draft lanes are speculation, not prompt ingestion: keep
            # them out of the prefill-occupancy surface
            chunk_lanes -= drafted
            # kwargs passed ONLY in spec mode: test spies subclassing
            # observe_decode_step with the old signature stay valid on
            # non-speculating engines
            kw = dict(accepted_tokens=accepted, drafted_tokens=drafted,
                      spec_slots=len(handle.spec_armed))
        # the interval a client sees between tokens: from this step's
        # hand-over, or from the tokens of the step before becoming ready
        # where this one was handed over ahead of that
        seconds = t_ready - max(handle.t0, self._t_ready)
        self._t_ready = t_ready
        self.metrics.observe_decode_step(handle.n_active, self.num_slots,
                                         seconds,
                                         prefill_lanes=chunk_lanes, **kw)
        if self.kv_layout == "paged":
            self.metrics.set_kv_pool(self._paged.pool.num_free,
                                     self._paged.pool.num_allocatable)
        return nxt

    def advance(self, slot, token, consumed=1):
        """Record the token fed at the next step for ``slot``, advanced
        past the ``consumed`` lanes the last step processed (1 = plain
        decode; a chunk advances by its lane count — the per-slot
        variable advance).  ``token`` is ``PICK_IN_FLIGHT`` while the
        step that picks it runs; ``advance(slot, pick, 0)`` fills it in
        once read, for a slot no later step has taken it from."""
        if self._draft is not None:
            # every committed token re-feeds the draft cache (matched
            # drafts rewrite identical K/V; a mismatch feeds the
            # corrected token over the stale rollout write) — lanes
            # 1..consumed-1 are read BEFORE lane 0 is overwritten
            self._d_feed[slot].extend(
                [int(t) for t in self._tokens[slot, 1:consumed]]
                + [int(token)])
        self._tokens[slot, 0] = token
        self._len[slot] = 1
        self._pos[slot] += consumed
        if self._draft is not None and self._paged is not None:
            # paged rollback (kv_pool.truncate): release blocks the
            # verify span provisioned past the committed stream —
            # keeping the block the next write lands in
            self._paged.truncate(slot, int(self._pos[slot]) + 1)

    def reset(self):
        """Drop all slot state and re-zero the cache slab (the batch-
        failure isolation path: a failed step must not leak a poisoned
        slab into the next batch).  The compiled step and block-op
        executables stay jit-cached — a rebuild costs zero new traces —
        and the epoch bump orphans any still-running stale step."""
        with self._epoch_lock:
            self._epoch += 1
            if self.kv_layout == "paged":
                # fresh pool + allocator + (empty) prefix index: the
                # blocks' contents are gone, so every cached chain is
                # invalid — recovery re-seats through seat_prefilled,
                # which misses and re-ingests.  REPLACE the state (a
                # watchdog-abandoned stale step may still be reading the
                # old tables array).
                old = self._paged
                self._paged = PagedKVState(
                    self.num_slots, old.pool.num_blocks, self.block_size,
                    self.max_len, prefix_cache=old.index is not None,
                    on_evict=self._spill_chain
                    if self._host_tier is not None else None)
                # in-flight restore claims died with the old state;
                # poll_restores drops their jobs at the epoch check, and
                # the blobs stay in the tier — recovery re-seats can
                # restore-hit the same spilled prefixes
                self._pending_restores.clear()
                # _new_cache: a sharded engine's rebuilt pool must come
                # back with the same mesh placement or the (still-cached)
                # compiled step would see new shardings and recompile
                self._cache = self._new_cache(self._build_paged_cache)
            else:
                self._cache = self._new_cache(
                    lambda: self._transformer.init_lm_cache(
                        self.params, self.num_slots, self.max_len,
                        kv_dtype=self.kv_dtype, num_heads=self.num_heads))
            # a step in flight is void with the cache it wrote
            self._prev_pick = self._new_pick()
            self._last_step = None
        self._tokens[:] = 0
        self._pos[:] = 0
        self._len[:] = 1
        self._free = list(range(self.num_slots))[::-1]
        if self._draft is not None:
            # BOTH caches rebuild: recovery re-seats every stream and
            # its context re-feeds the draft through _draft_seed
            self._draft.reset()
            self._d_feed = [[] for _ in range(self.num_slots)]
            self._d_pos[:] = 0
            self._d_last[:] = 0
            self._spec_armed.clear()
            self._spec_result.clear()

    # ------------------------------------------------------------ warm-up

    def warmup(self):
        """Compile + execute the step (and, paged, the block fork and
        the restore write) before traffic, asserting the trace
        discipline: the step's Python body traces exactly ONCE here for
        each width it may take (one for the trunk; ``step_widths``) and
        never again in steady state (admission/eviction are host-side, so
        churn cannot retrace by construction — the churn test pins it).
        Idempotent."""
        if self._warm:
            return
        # resolve the kernel path NOW — warm-up is the step's one trace,
        # so this is the selection the compiled step actually took
        # (ops/pallas/decode_attention.py; pallas_decode flag)
        from paddle_tpu.ops.pallas import decode_attention as _dk
        enc = self.params.get("enc") or []
        if enc:
            d = int(_w_shape(self.params["src_emb"])[1])
            dkv = int(_w_shape(enc[0]["attn"]["wk"])[1])
            paged = self.kv_layout == "paged"
            blk_len = self.block_size if paged else self.max_len
            # decline_reason() sees the PER-CHIP stripe (shards=): a
            # kernel that covers 8 KV heads may not cover the 4-head shard
            # — the resolved path below is what the compiled step actually
            # took, and a reference path always carries its sentence
            call = dict(paged=paged, chunk=self._kk,
                        quant=self.kv_dtype == "int8",
                        shards=self.mesh_shards)
            self.decode_decline_reason = _dk.decline_reason(
                self.num_heads, d, dkv, blk_len, **call)
            self.decode_kernels = self.decode_decline_reason is None
            if self.decode_kernels:
                self.decode_tile = _dk.tile_positions(
                    self.num_heads, d, dkv, blk_len,
                    nb_row=self._paged.tables.shape[1] if paged else 1,
                    **call)
            if not self.decode_kernels and _dk.decode_kernels_enabled():
                # kernels asked for, a shape guard said no: the reference
                # path is never silent — it writes the score matrix (and,
                # paged, gathers every chain) the fused kernels exist to
                # avoid
                logger.warning(
                    "decode[%s]: fused decode kernel declined -> XLA "
                    "reference path: %s", self.name,
                    self.decode_decline_reason)
        if self._model is not None:
            report = self._model.kernel_report(
                self._kk, self.block_size, self.num_slots,
                entries=self._paged.tables.shape[1])
            for key, value in report.items():
                setattr(self, key, value)
            for kernel, instead in (
                    ("kda", "XLA scan (every lane rewrites the state)"),
                    ("mla", "XLA gather and [S, K, H, T] scores"),
                    ("mamba", "XLA scan (every lane rewrites every state)"),
                    ("attn", "XLA gather and [S, K, H, T] scores"),
                    ("window", "XLA [S, K, H, ring] scores"),
                    ("sparse", "XLA top-k and [S, K, H, T] scores")):
                if report[kernel + "_decline_reason"]:
                    logger.warning(
                        "decode[%s]: %s kernel declined -> %s: %s",
                        self.name, kernel, instead,
                        report[kernel + "_decline_reason"])
            self.metrics.set_model_kernels(self.kda_kernels,
                                           self.mla_kernels,
                                           self.mamba_kernels)
            if self._window:
                self.metrics.set_window(
                    self._model.ring_bytes(self._cache), self.window_kernels)
            if self._sparse:
                self.metrics.set_sparse(self.sparse_kernels)
        self.metrics.set_prefill_chunk(self.prefill_chunk)
        self.metrics.set_kv_dtype(self.kv_dtype)
        self.metrics.set_speculate_k(self.speculate_k)
        self.metrics.set_mesh_shards(self.mesh_shards)
        self._set_cache_gauges()
        if self._draft is not None:
            # the draft rollout is its own ONE warm-up trace
            self._draft.warmup()
        if self.kv_layout == "paged":
            if self._host_tier is not None:
                # host-tier restores land through the block write;
                # warm it HERE so the first restore commits with zero
                # new compiles (ingestion itself never uses it — prompt
                # writes ride the step)
                chunk = jax.tree_util.tree_map(
                    lambda l: np.zeros(l.shape[1:], l.dtype),
                    self._cache)
                with expect_traces(lambda: self._write_traces[0], 1,
                                   f"decode[{self.name}]: "
                                   "block-write warm-up"):
                    self._cache = self._jit_write(self._cache, chunk,
                                                  np.int32(0))
            # the CoW fork is the only other device op the paged engine
            # uses; warmed (and executed) against the scratch block,
            # whose contents are never attended
            with expect_traces(lambda: self._copy_traces[0], 1,
                               f"decode[{self.name}]: block-fork "
                               "warm-up"):
                self._cache = self._jit_copy(self._cache, np.int32(0),
                                             np.int32(0))
        # every width the step may take, compiled and run once (all slots
        # free: one lane a row, which the narrowest holds)
        for width in self.step_widths:
            with expect_traces(
                    lambda: sum(self._step_traces.values()), 1,
                    f"decode[{self.name}]: {self.kv_layout} step warm-up "
                    f"at {width} lanes",
                    hint="the decode step is not shape-stable"):
                nxt, self._cache = self._jit_step(
                    self.params, self._cache, self._prev_pick,
                    *self._step_args(width))
                jax.block_until_ready(nxt)
        self._warm = True
        # the host arrays every step hands over (tokens, positions, lane
        # counts, the block tables, a model's packing; the picks of the
        # step before stay on the device): fixed by the engine's shapes
        host = self._step_args(self.step_widths[-1])
        logger.info(
            "decode[%s]: warm (%d slots, max_len %d, kv %s/%s, decode "
            "kernels %s, chunked prefill K=%d budget=%s, "
            "speculate_k=%d, mesh_shards=%d, host args a step %d of %d "
            "bytes)", self.name,
            self.num_slots, self.max_len, self.kv_layout,
            self.kv_dtype, self._kernel_path(),
            self.prefill_chunk, self.prefill_chunk_budget or "inf",
            self.speculate_k, self.mesh_shards, len(host),
            sum(a.nbytes for a in host))

    def _step_args(self, width):
        """The host arrays a step of ``width`` lanes takes, as the slots
        stand (warm-up and ``lower()``; ``dispatch_step`` snapshots its
        own)."""
        args = [self._tokens, self._pos, self._len]
        if self.kv_layout == "paged":
            args.append(self._paged.tables.copy())
        if self._model is not None:
            args += self._model.pack(self._len, self._kk, width)
        return args

    def _kernel_path(self):
        """The warm line's account of the path the compiled step took."""
        if self.decode_kernels:
            return f"fused-pallas, {self.decode_tile} positions a step"
        model = ("kda", "mla", "mamba", "attn", "window")
        fused = [k for k in model if getattr(self, k + "_kernels")]
        if fused:
            return "fused-pallas (%s)" % ", ".join(fused)
        return "xla-ref (%s)" % next(filter(None, (
            getattr(self, k + "_decline_reason")
            for k in model + ("decode",))), None)

    def lower(self, what="step"):
        """``jax.stages.Lowered`` of the decode step (the structure
        gates of perf/analytic.py read its HLO); ``what="draft"`` lowers
        the attached draft trunk's rollout instead.  Offline tool: it
        re-stages the function (one extra trace), like
        ``InferenceEngine.lower``."""
        if what == "draft":
            if self._draft is None:
                raise ConfigError(
                    f"{self.name}: no draft trunk (speculate_k=0)")
            return self._draft.lower()
        if what != "step":
            raise ConfigError(
                f"{self.name}: lower({what!r}) (takes 'step' | 'draft')")
        return self._jit_step.lower(self.params, self._cache,
                                    self._prev_pick,
                                    *self._step_args(self.step_widths[-1]))

    # ------------------------------------------------------------ validate

    def _validate_ids(self, name, ids):
        """Shared admission check: a non-empty 1-D integer id sequence
        within the vocab.  Returns the array."""
        ids = np.asarray(ids)
        if ids.ndim != 1 or ids.size < 1:
            raise InvalidRequestError(
                f"{name} must be a non-empty 1-D id sequence, got shape "
                f"{ids.shape}")
        if not np.issubdtype(ids.dtype, np.integer):
            raise InvalidRequestError(
                f"{name} must be integer token ids, got {ids.dtype}")
        vocab = (self._model.vocab_size if self._model is not None
                 else _w_shape(self.params["src_emb"])[0])
        if int(ids.min()) < 0 or int(ids.max()) >= vocab:
            raise InvalidRequestError(
                f"{name} ids must be in [0, {vocab}); got "
                f"[{int(ids.min())}, {int(ids.max())}]")
        return ids

    @staticmethod
    def _parse_max_tokens(max_tokens):
        try:
            max_tokens = int(max_tokens)
        except (TypeError, ValueError):
            raise InvalidRequestError(
                f"max_tokens must be an int, got {max_tokens!r}") from None
        if max_tokens < 1:
            raise InvalidRequestError(f"max_tokens={max_tokens} must be "
                                      ">= 1")
        return max_tokens

    def validate_request(self, prompt, max_tokens):
        """Admission-control checks, raised BEFORE the queue.  Only
        ``max_len`` caps the prompt (chunks bound per-STEP work)."""
        prompt = self._validate_ids("prompt", prompt)
        max_tokens = self._parse_max_tokens(max_tokens)
        if prompt.size + max_tokens > self.max_len:
            raise InvalidRequestError(
                f"prompt ({prompt.size}) + max_tokens ({max_tokens}) "
                f"exceeds the engine max_len ({self.max_len})")
        self._check_pool_fit(prompt.size + max_tokens)
        return prompt.astype(np.int32), max_tokens

    def _check_pool_fit(self, n_positions):
        """Paged: one request must fit the pool ALONE (the runtime
        preemption path can evict every other slot but never this one —
        docs/serving.md §5 pool sizing)."""
        if self.kv_layout != "paged":
            return
        need = self._paged.blocks_for(n_positions)
        if need > self._paged.pool.num_allocatable:
            raise InvalidRequestError(
                f"request needs {need} KV blocks of "
                f"{self.block_size} positions but the pool only holds "
                f"{self._paged.pool.num_allocatable}")

    def validate_continuation(self, prompt, replay, max_tokens):
        """Admission checks for a mid-stream CONTINUATION: ``replay``
        tokens were already delivered to the caller by a previous serving
        of this stream (a router failing over off a dead replica —
        docs/serving.md §7) and must be teacher-forced, never re-emitted.
        Seating feeds the whole combined context through the step (the
        exact ``Supervisor.reprefill`` contract), so like a fresh prompt
        only the slot length bounds it: ``len(prompt) + len(replay) +
        max_tokens <= max_len``."""
        prompt = self._validate_ids("prompt", prompt)
        replay = self._validate_ids("replay", replay)
        max_tokens = self._parse_max_tokens(max_tokens)
        if prompt.size + replay.size + max_tokens > self.max_len:
            raise InvalidRequestError(
                f"prompt ({prompt.size}) + replay ({replay.size}) + "
                f"max_tokens ({max_tokens}) exceeds the engine max_len "
                f"({self.max_len})")
        self._check_pool_fit(prompt.size + replay.size + max_tokens)
        return prompt.astype(np.int32), replay.astype(np.int32), max_tokens


class _GenRequest:
    __slots__ = ("prompt", "max_tokens", "eos_id", "future", "deadline",
                 "t_submit", "t_seat", "t_first", "on_token", "tokens",
                 "slot",
                 "abandoned", "recoveries", "replay_feed", "replay_ctx",
                 "started", "admit_covered", "prefix_counted",
                 "trace_ctx", "queue_span", "slot_span")

    def __init__(self, prompt, max_tokens, eos_id, deadline, on_token,
                 replay_ctx=None):
        # tracing (obs/trace.py): the submitting thread's context (the
        # HTTP handler's request span) is captured HERE because the
        # worker thread that seats and decodes this request has no
        # ambient context of its own.  submit() starts queue_span only
        # once the request is actually enqueued (a rejected submit must
        # not leak a forever-active span); it ends at admission pickup.
        # slot_span is the request's slot-LIFETIME span (seat ->
        # eviction, carrying TTFT/recovery/preemption events).
        self.trace_ctx = obstrace.current()
        self.queue_span = obstrace.NULL
        self.slot_span = obstrace.NULL
        self.abandoned = False
        self.recoveries = 0
        self.started = False      # future marked running (a request can
        #                           re-enter admission — pool-deferred —
        #                           but the transition fires once)
        self.replay_feed = []     # context (prompt, continuation, recovery
        #                           replay) still to teacher-force through
        #                           the step
        self.replay_ctx = replay_ctx   # continuation context: tokens a
        #                                previous serving of this stream
        #                                already delivered (never re-emitted)
        self.admit_covered = 0    # this admission pass's prefix-cache
        #                           lookup (positions covered), reused by
        #                           routing so the pass looks up once
        self.prefix_counted = False   # hit/miss observed (a pool-
        #                               deferred request re-enters
        #                               admission; the counter must see
        #                               it once)
        self.prompt = prompt
        self.max_tokens = max_tokens
        self.eos_id = eos_id
        self.future = Future()
        self.deadline = deadline          # absolute perf_counter() or None
        self.t_submit = time.perf_counter()
        self.t_seat = None                # first seated (perf_counter())
        self.t_first = None
        self.on_token = on_token
        self.tokens = []
        self.slot = None

    @property
    def context(self):
        """Every token the stream holds BEFORE its first new emission:
        the prompt plus (for a continuation) the already-delivered replay
        tokens — what slot recovery must reconstruct."""
        if self.replay_ctx is None:
            return self.prompt
        return np.concatenate([self.prompt, self.replay_ctx])

    def fail(self, exc):
        # end both trace spans (idempotent): a request failed while
        # queued or seated must not leak forever-active spans
        self.queue_span.end()
        self.slot_span.end(reason="failed",
                           error=type(exc).__name__,
                           tokens=len(self.tokens))
        try:
            self.future.set_exception(exc)
        except InvalidStateError:
            pass

    def emit(self, token, name):
        self.tokens.append(int(token))
        if self.t_first is None:
            self.t_first = time.perf_counter()
        if self.on_token is not None:
            try:
                self.on_token(int(token))
            except Exception as e:    # noqa: BLE001 — a client callback
                # must never wedge the decode loop
                logger.warning("%s: on_token callback failed: %s: %s",
                               name, type(e).__name__, e)
                self.on_token = None


class GenerationBatcher:
    """Continuous-batching front for a ``DecodeEngine`` — the generation
    twin of ``Batcher``: bounded queue, futures, deadlines, drain; plus
    streaming (per-token callbacks) and slot scheduling.

    ONE worker thread runs the loop: seat queued requests into free
    slots, load each feeding slot's next chunk, hand the device one step,
    deliver each active slot's token, evict finished slots.  Freed slots
    refill from the queue between ANY two steps; admission happens
    strictly BETWEEN dispatches, so the compiled step never sees a shape
    change.

    The loop keeps ONE step in flight (docs/serving.md "One step in
    flight"): it hands the device step n+1 before it reads step n's
    tokens, since nothing it does for n+1 needs them — the picked id
    stays on the device (``PICK_IN_FLIGHT``).  Where host work does need
    committed tokens or a quiescent cache (``_overlaps``), the iteration
    reads first and runs the two in today's order; both are the same
    code with the read earlier or later.
    """

    def __init__(self, engine, queue_size=256, default_deadline_ms=None,
                 default_max_tokens=64, name=None, supervisor=None):
        self.engine = engine
        self.metrics = engine.metrics
        # resilience.Supervisor (None = PR-5 semantics: a step failure
        # fails the in-flight batch).  With one attached: step failures
        # and watchdog trips REBUILD the cache and re-seat every
        # in-flight request (streams continue bit-identically), and the
        # circuit breaker sheds admissions after repeated failures.
        self.supervisor = supervisor
        self.default_deadline_s = (float(default_deadline_ms) / 1e3
                                   if default_deadline_ms else None)
        self.default_max_tokens = int(default_max_tokens)
        if int(queue_size) < 1:
            raise ValueError("queue_size must be >= 1")
        self._q = queue.Queue(maxsize=int(queue_size))
        # cross-replica KV exports (serving/transfer.py): HTTP handlers
        # queue (tokens, result_box, done_event) here and the worker
        # serves them strictly between steps — the gather must read the
        # committed cache, which belongs to the worker thread
        self._export_q = queue.Queue()
        self._depth_fn = self._q.qsize
        self.metrics.queue_depth_fns.append(self._depth_fn)
        self._closed = threading.Event()
        self._drain = True
        self._admit_lock = threading.Lock()
        self._by_slot = {}          # slot -> _GenRequest
        self._abandoned = set()     # futures flagged in the seating window
        #                             (before their request reached a slot)
        # paged-layout overflow lanes (both worker-thread-only):
        # _waiting: popped requests the pool cannot seat yet (retried
        # ahead of the queue); _preempted: requests whose slot was
        # evicted under pool pressure (reason="pool_exhausted") — they
        # hold delivered tokens and re-seat through seat_prefilled, so
        # their streams continue bit-identically
        self._waiting = collections.deque()
        self._preempted = []
        # the step whose tokens are not read yet (worker-thread-only):
        # (handle, or with a watchdog the tokens themselves; the step's
        # ordinal; the (request, slot) rows it emits for)
        self._flying = None
        self.name = name or f"gen_batcher[{engine.name}]"
        self._thread = threading.Thread(target=self._loop, daemon=True,
                                        name=self.name)
        self._thread.start()

    # ------------------------------------------------------------ submit

    def submit(self, prompt, max_tokens=None, eos_id=None, deadline_ms=None,
               on_token=None, replay=None):
        """Admit one generation request; returns a Future resolving to
        ``{"tokens": [ids...], "finish_reason": "eos"|"length",
        "ttft_ms": float}``.

        prompt: 1-D int token ids;
        max_tokens: emission cap (default: the batcher default), with
        ``len(prompt) + max_tokens <= engine.max_len``; eos_id: stop
        token override (None = the engine default); on_token: optional
        callable invoked per emitted token from the engine thread (the
        streaming hook — exceptions are logged, never fatal).

        replay: mid-stream CONTINUATION — tokens a previous serving of
        this stream already delivered (a router failing over off a dead
        replica, docs/serving.md §7).  Seating teacher-forces ``prompt +
        replay`` through the step with re-derived emissions swallowed
        (``Supervisor.reprefill`` semantics), so the result's ``tokens``
        are ONLY the new emissions and — greedy decode being
        deterministic — the concatenated stream is bit-identical to the
        uninterrupted one.  ``max_tokens`` counts new emissions;
        ``len(prompt) + len(replay) + max_tokens <= engine.max_len``.

        Raises synchronously: ``InvalidRequestError``,
        ``OverloadedError`` (queue full), ``ShutdownError`` (draining),
        ``BreakerOpenError`` (circuit breaker shedding; carries
        ``retry_after_s``).
        """
        # fault point FIRST: an injected submit failure provably mutated
        # nothing, so retry_transient's idempotence guarantee holds
        faults.hit("batcher.submit")
        if self._closed.is_set():
            self.metrics.reject("shutdown")
            raise ShutdownError(f"{self.name} is draining; submit rejected")
        try:
            if replay is None:
                prompt, max_tokens = self.engine.validate_request(
                    prompt, max_tokens if max_tokens is not None
                    else self.default_max_tokens)
            else:
                prompt, replay, max_tokens = \
                    self.engine.validate_continuation(
                        prompt, replay,
                        max_tokens if max_tokens is not None
                        else self.default_max_tokens)
        except InvalidRequestError:
            self.metrics.reject("invalid")
            raise
        # breaker AFTER validation: a malformed request must not burn the
        # half-open probe slot (it would never reach a step to resolve it)
        if self.supervisor is not None:
            ok, retry_after = self.supervisor.breaker.admit()
            if not ok:
                self.metrics.reject("breaker")
                self._snap_breaker()
                raise BreakerOpenError(
                    f"{self.name}: circuit breaker open (engine recently "
                    f"failed repeatedly); retry in {retry_after:.2f}s",
                    retry_after_s=retry_after)
        dl_s = (float(deadline_ms) / 1e3 if deadline_ms
                else self.default_deadline_s)
        req = _GenRequest(prompt, max_tokens,
                          self.engine.eos_id if eos_id is None else eos_id,
                          time.perf_counter() + dl_s if dl_s else None,
                          on_token, replay_ctx=replay)
        # start the queue-wait span before the enqueue (the worker may
        # pull the request the instant it lands); the rejection paths
        # below end it so a refused submit leaks nothing
        # root=False: a direct (non-HTTP) submit has no request span,
        # and infrastructure spans must not pollute slowest()
        req.queue_span = obstrace.start_span("gen.queue_wait",
                                             ctx=req.trace_ctx,
                                             root=False)
        with self._admit_lock:
            if self._closed.is_set():   # close() raced the check above
                req.queue_span.end()
                self.metrics.reject("shutdown")
                if self.supervisor is not None:     # the request never
                    self.supervisor.breaker.release_probe()   # ran: hand
                #                                     the probe slot back
                raise ShutdownError(
                    f"{self.name} is draining; submit rejected")
            try:
                self._q.put_nowait(req)
            except queue.Full:
                req.queue_span.end()
                self.metrics.reject("overload")
                if self.supervisor is not None:
                    self.supervisor.breaker.release_probe()
                raise OverloadedError(
                    f"{self.name}: queue full ({self._q.maxsize} waiting)") \
                    from None
        self.metrics.accepted()
        return req.future

    def generate(self, prompt, timeout=None, **kw):
        """submit() + block for the result (the HTTP handler's path)."""
        return self.submit(prompt, **kw).result(timeout)

    def export_chain(self, tokens, timeout=5.0):
        """Serialize the longest resident KV coverage of ``tokens`` for
        a cross-replica handoff (the ``/v1/kv/export`` route's path).
        The gather reads the committed cache — worker-thread state — so
        the request queues and the worker serves it strictly between
        steps (the loop's idle poll is 50ms, bounding the wait).
        Returns ``(key, covered, blob)``, or ``(None, 0, None)`` on no
        coverage, a closed batcher, or timeout."""
        box = [None, 0, None]
        done = threading.Event()
        self._export_q.put((tokens, box, done))
        if not done.wait(timeout):
            return None, 0, None
        return box[0], box[1], box[2]

    def _serve_exports(self):
        """Worker thread, strictly between steps: drain queued
        cross-replica export requests.  An export failure resolves THAT
        request empty (the peer falls back to recompute) and never
        touches the serving loop."""
        while True:
            try:
                tokens, box, done = self._export_q.get_nowait()
            except queue.Empty:
                return
            try:
                box[0], box[1], box[2] = self.engine.export_chain(tokens)
            except Exception as e:      # noqa: BLE001 — isolate to this
                # export; the requester serves a miss (recompute)
                logger.warning("%s: kv export failed: %s: %s",
                               self.name, type(e).__name__, e)
            done.set()

    def abandon(self, future):
        """The caller behind ``future`` is gone (e.g. the streaming HTTP
        client disconnected): stop spending decode steps on it.  A still-
        queued request is cancelled outright; a slotted one is flagged
        and the worker evicts it at the next token boundary instead of
        decoding to max_tokens.  No-op if it already finished."""
        if future.done() or future.cancel():
            return          # finished, or still queued (admission drops
            #                 cancelled work)
        for req in list(self._by_slot.values()):
            if req.future is future:
                req.abandoned = True
                return
        # running but not slotted: it is inside the seating window —
        # admission checks this set before seating it
        self._abandoned.add(future)

    # ------------------------------------------------------------ worker

    def _pull(self, block):
        if self._waiting:               # pool-deferred requests go first
            return self._waiting.popleft()
        try:
            req = self._q.get(timeout=0.05) if block else \
                self._q.get_nowait()
        except queue.Empty:
            return None
        # the queue wait ends at pickup (idempotent: a pool-deferred
        # request re-enters admission but its wait ended the first time)
        req.queue_span.end()
        return req

    def _finish(self, req, reason):
        """Resolve a request's future, evicting its slot if it holds
        one.  It may not: its last token by count left its slot free
        while the step ran, or the pool took the slot with a token in
        flight that ends the stream."""
        if req.slot is not None:
            self.engine.evict(req.slot, reason)
            del self._by_slot[req.slot]
            req.slot = None
        elif req in self._preempted:
            self._preempted.remove(req)
        self._resolve(req, reason)

    def _resolve(self, req, reason):
        """Resolve a finished request's future — the ONE place the
        response shape is built (slotted finishes and requests
        abandoned before they reached a slot all land here)."""
        self._abandoned.discard(req.future)     # a late abandon() of a
        #                                         finished future is inert
        ttft = (req.t_first - req.t_submit) if req.t_first else 0.0
        # the slot-lifetime span ends with the request, carrying the
        # eviction reason next to TTFT (NULL no-op for requests that
        # never held a slot)
        req.slot_span.end(reason=reason, tokens=len(req.tokens),
                          ttft_ms=round(ttft * 1e3, 3))
        self.metrics.observe_response(time.perf_counter() - req.t_submit)
        try:
            req.future.set_result({
                "tokens": list(req.tokens),
                "finish_reason": reason,
                "ttft_ms": round(ttft * 1e3, 3),
            })
        except InvalidStateError:
            pass

    def _flag_abandoned(self, req):
        """Fold a seating-window ``abandon()`` into the request's
        flag."""
        if req.future in self._abandoned:
            self._abandoned.discard(req.future)
            req.abandoned = True
        return req.abandoned

    def _admit_from_queue(self, block):
        """Fill free slots from the queue.  Runs strictly between steps.

        Every picked request — fresh prompt, continuation
        (``replay_ctx``), prefix-cache hit — seats through
        ``engine.seat_prefilled`` (the one seat-prefix helper, shared
        with ``Supervisor.reprefill``) and its context drains through
        the step as K-lane chunks: teacher-forced, re-derived emissions
        swallowed, first emission at the last chunk, so every stream is
        bit-identical to an uninterrupted one.  On the paged layout,
        requests the pool cannot hold yet are DEFERRED (``_waiting`` /
        ``_preempted``), never failed."""
        self._reseat_preempted()
        block = block and not self._preempted
        picked = []
        kv_budget = None
        if self.engine.kv_layout == "paged":
            kv_budget = [self.engine._paged.pool.num_free]
        stashed = []
        while self.engine.free_slots > len(picked):
            req = self._pull(block and not picked)
            if req is None:
                break
            block = False
            now = time.perf_counter()
            if req.deadline is not None and now > req.deadline:
                self.metrics.reject("deadline")
                req.fail(DeadlineExceededError(
                    f"deadline exceeded after "
                    f"{(now - req.t_submit) * 1e3:.1f}ms in queue"))
                continue
            if not req.started:
                if not req.future.set_running_or_notify_cancel():
                    continue        # client cancelled while queued
                req.started = True
            covered = 0
            if kv_budget is not None and req.replay_ctx is None:
                covered = self.engine.prefix_lookup(req.prompt)[0]
                if not covered:
                    # paged fresh miss: it will claim private blocks for
                    # its whole prompt — defer it while the pool (free
                    # blocks minus what this admission round already
                    # earmarked) cannot hold them, instead of seating it
                    # just to preempt it
                    need = self.engine._paged.blocks_for(
                        req.prompt.size + 1)
                    if need > kv_budget[0] \
                            and not self.engine.can_admit(
                                req.prompt.size + 1):
                        stashed.append(req)
                        continue
                    kv_budget[0] -= need
                if not req.prefix_counted:
                    req.prefix_counted = True
                    self.metrics.observe_prefix_cache(hit=covered > 0)
            # the lookup above is this pass's routing; seat_prefilled
            # re-looks-up at seating time (the pool may shift as items
            # seat), so that one stays the authoritative reference-taker
            req.admit_covered = covered
            picked.append(req)
        self._waiting.extend(stashed)
        self._seat_reconstructed(picked)

    def _seat_reconstructed(self, reqs):
        """Seat picked requests through ``engine.seat_prefilled``;
        pool-dry items defer to ``_waiting``."""
        live = []
        for req in reqs:
            if self._flag_abandoned(req):
                self._resolve(req, "abandoned")
            else:
                live.append(req)
        if not live:
            return
        outcomes = self.engine.seat_prefilled([r.context for r in live])
        hard = None
        for req, out in zip(live, outcomes):
            if isinstance(out, InsufficientBlocksError):
                self._waiting.append(req)     # space, not failure: retry
            elif isinstance(out, BaseException):
                hard = out
                self.metrics.observe_error(1)
                req.fail(BatchExecutionError(
                    f"seat failed: {type(out).__name__}: {out}"))
            else:
                req.slot, req.replay_feed = out
                self._by_slot[req.slot] = req
                if req.replay_ctx is not None:
                    mode = "continuation"
                elif req.admit_covered:
                    mode = "prefix_hit"
                else:
                    mode = "prefill"        # fresh admission
                if req.t_seat is None:      # (a deferred seat retries)
                    req.t_seat = time.perf_counter()
                req.slot_span = obstrace.start_span(
                    "slot", ctx=req.trace_ctx, root=False,
                    slot=int(req.slot), mode=mode,
                    teacher_forced=len(req.replay_feed))
                if req.slot_span.recording:
                    # the join key (docs/observability.md): the first
                    # device step that can carry the request
                    req.slot_span.set(
                        step=self.engine.steps_dispatched,
                        prompt_tokens=int(req.prompt.size),
                        chunk=self.engine.prefill_chunk)
        if hard is not None:
            # a seat that failed for anything but pool space left the
            # engine's slot state in doubt — fail everything in flight
            # and reset instead of stepping it
            self._fail_all_inflight(hard)

    def _reseat_preempted(self):
        """Re-seat pool-preempted requests (oldest first) from prompt +
        delivered tokens — ``seat_prefilled`` reconstructs the slot and
        the teacher-forced replay swallows every re-derived emission, so
        the client's stream continues bit-identically.  Items the pool
        still cannot hold stay preempted for the next cycle."""
        if not self._preempted or not self.engine.free_slots:
            return
        batch = self._preempted[:self.engine.free_slots]
        rest = self._preempted[len(batch):]
        self._preempted = rest
        live, fulls = [], []
        for req in batch:
            if self._flag_abandoned(req):
                self._resolve(req, "abandoned")
                continue
            live.append(req)
            fulls.append(np.concatenate(
                [req.context, np.asarray(req.tokens, np.int32)]))
        if not live:
            return
        outcomes = self.engine.seat_prefilled(fulls)
        hard = None
        for req, out in zip(live, outcomes):
            if isinstance(out, InsufficientBlocksError):
                self._preempted.append(req)
            elif isinstance(out, BaseException):
                hard = out
                self.metrics.observe_error(1)
                req.fail(BatchExecutionError(
                    f"re-seat after pool preemption failed: "
                    f"{type(out).__name__}: {out}"))
            else:
                req.slot, req.replay_feed = out
                self._by_slot[req.slot] = req
                if req.slot_span is obstrace.NULL:
                    req.slot_span = obstrace.start_span(
                        "slot", ctx=req.trace_ctx, root=False,
                        slot=int(req.slot), mode="reseat",
                        teacher_forced=len(req.replay_feed))
                else:
                    req.slot_span.event("reseated", slot=int(req.slot),
                                        teacher_forced=len(
                                            req.replay_feed))
                self.metrics.observe_slot_reprefill()
        if hard is not None:
            # same donated-cache safety as _seat_reconstructed: the
            # failed seat was a device op — never step a possibly-
            # consumed buffer
            self._fail_all_inflight(hard)

    def _load_chunks(self, step):
        """Strictly between steps: arm each feeding slot's
        next up-to-(K-1)-token chunk (prompt ingestion, continuation
        replay, recovery replay — one mechanism), bounded by the
        engine's per-step chunk budget.  Lane counts are DATA: mixing
        decode rows with chunking rows never retraces.  A slot that gets
        no lanes this step (budget spent) still advances one
        teacher-forced token through its lane 0, so feeding always makes
        progress.  Returns the lanes armed.  Each row's slot span says
        what ``step`` fed it (``prefill_chunk``: the lanes it got and the
        lanes it ``wanted``) or left it out (``prefill_stall``)."""
        kk = self.engine.prefill_chunk
        budget = self.engine.prefill_chunk_budget
        used = stalled = 0
        for slot, req in self._by_slot.items():
            if not req.replay_feed or kk < 2:
                continue
            wanted = min(kk - 1, len(req.replay_feed))
            n = min(wanted, budget - used) if budget else wanted
            if n <= 0:
                stalled += 1
                req.slot_span.event("prefill_stall", step=step)
                continue
            self.engine.load_chunk(slot, req.replay_feed[:n])
            used += n
            if req.slot_span.recording:
                req.slot_span.event("prefill_chunk", step=step, lanes=n,
                                    wanted=wanted,
                                    pos=int(self.engine._pos[slot]))
        if stalled:
            self.metrics.observe_prefill_stalled(stalled)
        return used

    def _load_spec(self):
        """Speculative mode, strictly between steps (after
        ``_load_chunks``): one batched draft rollout drains every active
        slot's committed-token feed, then draft lanes arm for the slots
        that are PURELY decoding — a slot still chunk-ingesting keeps
        its prefill lanes and joins speculation once its feed drains, so
        ingestion and speculation coexist across slots in the SAME step.
        Budgets cap each verify span at the request's remaining emission
        allowance (a run can never overshoot max_tokens)."""
        budgets = {}
        for slot, req in self._by_slot.items():
            if req.replay_feed or req.abandoned:
                continue
            budgets[slot] = req.max_tokens - len(req.tokens)
        for slot, k_eff in self.engine.speculate(budgets).items():
            span = self._by_slot[slot].slot_span
            if span.recording:
                span.event("speculate", k=int(k_eff),
                           pos=int(self.engine._pos[slot]))

    def _emit_spec_run(self, req, slot, run, of_step):
        """Deliver one verify step's accepted run (matched drafts + the
        target's own token at the first mismatch) with full per-token
        semantics: EOS inside the run finishes the stream THERE (the
        trailing accepted tokens are discarded — the engine never
        advances past what was delivered), and max_tokens can end it
        mid-run.  A surviving stream advances past the whole run in one
        ``advance(consumed=)``."""
        emitted = 0
        for tok in run:
            first_emit = req.t_first is None
            req.emit(tok, self.name)
            emitted += 1
            if first_emit:
                self._first_token(req, of_step)
            self.metrics.observe_gen_tokens(1)
            if req.eos_id is not None and tok == req.eos_id:
                req.slot_span.event("accept", accepted=len(run) - 1,
                                    emitted=emitted, finish="eos")
                self._finish(req, "eos")
                return
            if len(req.tokens) >= req.max_tokens:
                req.slot_span.event("accept", accepted=len(run) - 1,
                                    emitted=emitted, finish="length")
                self._finish(req, "length")
                return
        req.slot_span.event("accept", accepted=len(run) - 1,
                            emitted=emitted)
        self.engine.advance(slot, run[-1], len(run))

    def _first_token(self, req, of_step):
        """A request's first token was just emitted: ``of_step``, the
        device step whose read produced it, joins the slot span to that
        step's phases (docs/observability.md)."""
        req.slot_span.event("first_token", of_step=of_step)
        self.metrics.observe_ttft(req.t_first - req.t_submit)
        self.metrics.observe_prefill(req.t_first - req.t_seat)

    def _snap_breaker(self):
        """Mirror the breaker's state into the metrics gauge."""
        b = self.supervisor.breaker
        self.metrics.set_breaker_state(b.state, b.opened_total)

    def _recover_inflight(self, e):
        """The supervised step failed (error or watchdog trip): rebuild
        the cache (``reset()`` — the compiled step is jit-cached, so the
        rebuild costs ZERO new traces) and re-seat every in-flight
        request from prompt + tokens-generated-so-far, continuing each
        greedy stream bit-identically (``Supervisor.reprefill``).  A
        request whose recovery budget ran out fails with the cause;
        everything else keeps streaming."""
        sup = self.supervisor
        victims = list(self._by_slot.values()) + self._void_flying()
        self._by_slot.clear()
        logger.warning("%s: supervised step over %d request(s) failed: "
                       "%s: %s — rebuilding slab + re-prefilling",
                       self.name, len(victims), type(e).__name__, e)
        # the rebuild-and-reprefill window as one span: a recovered
        # stream's trace shows exactly how long the failure stalled it
        recover_sp = obstrace.start_span("supervisor.recover",
                                         root=False, n=len(victims),
                                         cause=type(e).__name__)
        self.engine.reset()     # bumps the epoch: a hung stale step can
        #                         never commit into the rebuilt slab
        # eviction reasons are counted per OUTCOME below: a victim that
        # re-seats counts "recovered"; one whose caller left counts
        # "abandoned"; one that cannot be recovered counts "error"
        recoverable = []
        for req in victims:
            if req.future in self._abandoned:
                self._abandoned.discard(req.future)
                req.abandoned = True
            if req.abandoned:
                self.metrics.evict_slot("abandoned")
                self._resolve(req, "abandoned")
                continue
            req.recoveries += 1
            if req.recoveries > sup.max_request_recoveries:
                self.metrics.evict_slot("error")
                self.metrics.observe_error(1)
                req.fail(BatchExecutionError(
                    f"request failed after {req.recoveries - 1} slot "
                    f"recoveries: {type(e).__name__}: {e}"))
                continue
            recoverable.append(req)
        if not recoverable:
            recover_sp.end(recovered=0)
            return
        # each result is (slot, replay_feed) or the exception for that
        # victim
        try:
            outcomes = sup.reprefill(self.engine,
                                     [(req.context, req.tokens)
                                      for req in recoverable])
        except Exception as re:    # noqa: BLE001 — an unexpected recovery
            # crash must fail the victims, never the worker thread
            outcomes = [re] * len(recoverable)
        for req, out in zip(recoverable, outcomes):
            if isinstance(out, InsufficientBlocksError):
                # space, not failure: the rebuilt pool starts with an
                # empty prefix index, so victims that shared blocks may
                # not all fit privately at once.  Park the overflow —
                # _reseat_preempted replays it bit-identically once
                # blocks free up, same as any pool-pressure preemption.
                self.metrics.evict_slot("pool_exhausted")
                self._preempted.append(req)
                continue
            if isinstance(out, BaseException):
                self.metrics.evict_slot("error")
                self.metrics.observe_error(1)
                req.fail(BatchExecutionError(
                    f"slot recovery failed: {type(out).__name__}: {out} "
                    f"(after step failure: {type(e).__name__}: {e})"))
                continue
            req.slot, req.replay_feed = out
            self._by_slot[req.slot] = req
            req.slot_span.event("recovery_reprefill",
                                slot=int(req.slot),
                                teacher_forced=len(req.replay_feed))
            self.metrics.evict_slot("recovered")
            self.metrics.observe_slot_reprefill()
        recover_sp.end(recovered=len(self._by_slot))

    def _void_flying(self):
        """Forget the step in flight, whose tokens are lost with the cache
        (the caller resets the engine, which drops the handle).  Returns
        the requests that step was the LAST of by count: they hold no slot
        any more and are victims all the same.  Every other request of
        the step is seated, or preempted and re-seats from its delivered
        tokens, which no step in flight has touched."""
        rows = self._flying[2] if self._flying is not None else ()
        self._flying = None
        return [req for req, _slot in rows
                if req.slot is None and not req.future.done()
                and req not in self._preempted]

    def _fail_all_inflight(self, e):
        """A device operation (step or slot admission) failed: fail every
        in-flight request with the cause, reset the engine (the donated
        slab may be consumed), and let the loop keep serving."""
        victims = list(self._by_slot.values()) + self._void_flying()
        logger.warning("%s: device op over %d request(s) failed: %s: %s",
                       self.name, len(victims), type(e).__name__, e)
        self.metrics.observe_error(len(victims))
        for req in victims:
            req.fail(BatchExecutionError(
                f"decode batch failed: {type(e).__name__}: {e}"))
        for _ in self._by_slot:
            self.metrics.evict_slot("error")
        self._by_slot.clear()
        self.engine.reset()

    def _loop(self):
        while True:
            if self._closed.is_set() and not self._drain:
                # the worker owns slot state: fail the in-flight requests
                # here, never from close()'s thread
                self._land()
                for slot, req in list(self._by_slot.items()):
                    req.fail(ShutdownError(
                        "generation batcher closed without drain"))
                    self.engine.evict(slot, "shutdown")
                self._by_slot.clear()
                for req in self._preempted + list(self._waiting):
                    req.fail(ShutdownError(
                        "generation batcher closed without drain"))
                self._preempted, self._waiting = [], collections.deque()
                return
            if not self._by_slot:
                # no slot is active: until a request seats, the loop
                # waits for work (the blocking queue read, mostly)
                with obstrace.phase("gen.loop.nowork"):
                    self._admit(block=True)
                if not self._by_slot:
                    if self._closed.is_set() and self._q.empty() \
                            and not self._waiting and not self._preempted:
                        return
                    if self._waiting:
                        # every runnable request is deferred (restore in
                        # flight / pool dry): wait a tick on the transfer
                        # thread instead of hot-spinning the retry loop
                        self.engine.poll_restores(timeout=0.005)
                    continue
            # the phases of one iteration (obs/trace.py phase()) share
            # ``step``: the ordinal of the device step it hands over (the
            # tokens it reads may be those of the step before: ``of_step``)
            step = self.engine.steps_dispatched
            with obstrace.phase("gen.loop.iter", step=step,
                                active=len(self._by_slot)):
                self._iterate(step)
                if not self._by_slot:
                    # nothing left to hand over: the loop is about to wait
                    # for work, and the step in flight holds the last
                    # tokens of the requests that just left
                    self._land(step)

    def _admit(self, block):
        """What lands strictly between steps, in order."""
        # host-tier restores land HERE: the staged chunks write into
        # their claimed blocks and the chain publishes into the prefix
        # index, so a deferred request's next retry seats as an ordinary
        # resident hit
        self.engine.poll_restores()
        # cross-replica exports land here too: same between-steps seam,
        # same committed-cache safety as the restore commits
        self._serve_exports()
        self._admit_from_queue(block=block)

    def _step_failed(self, e):
        """A device operation of this iteration raised: isolate it to
        the requests in flight — those of the step being handed over and
        those of the step not read yet alike, both void; the loop keeps
        serving."""
        sup = self.supervisor
        if sup is None:
            self._fail_all_inflight(e)
            return
        opened = sup.breaker.record_failure()
        self._snap_breaker()
        if opened:
            logger.warning(
                "%s: circuit breaker OPEN after %d consecutive "
                "step failures; shedding new admissions for "
                "%.1fs", self.name, sup.breaker.threshold,
                sup.breaker.cooldown_s)
        self._recover_inflight(e)

    def _overlaps(self):
        """Whether this iteration may hand the device its step before the
        last one's tokens are read.  Not where host work needs committed
        tokens or a quiescent cache: a draft trunk (acceptance is host
        work on the step's output), a step deadline (the watchdog times
        one whole step), a preempted request waiting to re-seat from its
        delivered tokens, a host-tier restore to commit or an export to
        serve from the cache."""
        sup = self.supervisor
        return not (self.engine.speculating
                    or (sup is not None and sup.step_deadline_s is not None)
                    or self._preempted
                    or self.engine.restores_pending
                    or not self._export_q.empty())

    def _iterate(self, step):
        """One iteration with a slot active: admit, prepare, hand the
        step over, read the tokens of the step before (or, with nothing
        in flight, this one's) and emit them — each a phase on the
        profiler's clock and in the tracer's phase ring, so a device gap
        names what the host did in it."""
        overlap = self._overlaps()
        if not overlap and not self._land(step):
            return
        with obstrace.phase("gen.loop.admit", step=step) as ph:
            seated = len(self._by_slot)
            self._admit(block=False)
            ph.set(admitted=max(0, len(self._by_slot) - seated))
        if not self._by_slot:
            return                  # a failed admission cleared the slots
        with obstrace.phase("gen.loop.prepare", step=step) as ph:
            lanes = self._load_chunks(step)
            if self.engine.speculating:
                self._load_spec()
            try:
                # paged layout: provision every active slot's write block
                # (chain growth + copy-on-write forks) strictly BETWEEN
                # dispatches; pool exhaustion preempts the youngest slots
                # — their requests re-seat via _reseat_preempted and their
                # streams continue bit-identically
                victims = self.engine.prepare_step()
                for slot in victims:
                    req = self._by_slot.pop(slot)
                    req.slot = None
                    req.slot_span.event("preempted",
                                        reason="pool_exhausted")
                    self._preempted.append(req)
            except Exception as e:    # noqa: BLE001 — see _step_failed
                self._step_failed(e)
                return
            ph.set(chunk_lanes=lanes, preempted=len(victims))
        if not self._by_slot:
            return                  # everything was preempted
        sup = self.supervisor
        try:
            if sup is not None and sup.step_deadline_s is not None:
                try:
                    out = sup.run_step(self.engine)     # the tokens
                except WatchdogTimeout:
                    self.metrics.observe_watchdog_trip()
                    raise
            else:
                out = self.engine.dispatch_step()       # a handle
        except Exception as e:    # noqa: BLE001 — see _step_failed
            self._step_failed(e)
            return
        if not self._land(step):
            return
        self._flying = (out, step, self._advance_rows())
        if not overlap:
            self._land(step)

    def _land(self, during=None):
        """Read the tokens of the step in flight, if there is one, and
        emit them, inside the iteration ``during``.  False where the read
        failed: recovery has run, and the iteration is over."""
        if self._flying is None:
            return True
        out, of_step, rows = self._flying
        try:
            nxt = out if isinstance(out, np.ndarray) \
                else self.engine.collect_step(out, during)
        except Exception as e:    # noqa: BLE001 — see _step_failed
            self._step_failed(e)
            return False
        self._flying = None
        if self.supervisor is not None:
            self.supervisor.breaker.record_success()
            self._snap_breaker()
        with obstrace.phase("gen.loop.emit",
                            step=of_step if during is None else during,
                            of_step=of_step) as ph:
            emitted = self.metrics.gen_tokens_total
            finished = self.metrics.responses_total
            self._emit(rows, nxt, of_step)
            ph.set(emitted=self.metrics.gen_tokens_total - emitted,
                   finished=self.metrics.responses_total - finished)
        return True

    def _advance_rows(self):
        """The half of delivering a step that needs no token, done while
        the step runs: every seated row moves past the lanes it was fed.
        A row still ingesting takes its next token from the recorded
        stream, as ever.  A row that emits is returned as ``(request,
        slot)`` for ``_emit``, and unless acceptance has yet to say how
        far it got (a draft trunk) moves on now: to lane 0 =
        ``PICK_IN_FLIGHT``, the pick the device already holds, or — its
        token being its last by ``max_tokens``, which is known before the
        read — out of its slot, which the next admission may fill."""
        engine = self.engine
        rows = []
        for slot, req in list(self._by_slot.items()):
            if self._flag_abandoned(req):
                self._finish(req, "abandoned")
                continue
            # lanes the step processes for the slot (1 = plain decode;
            # >1 = a prefill/replay chunk)
            consumed = engine.chunk_len(slot)
            if req.replay_feed:
                if len(req.replay_feed) >= consumed:
                    # teacher-forced feeding continues: this step's
                    # emission re-derives an already-known token —
                    # swallow it and feed the recorded stream, until
                    # the slot reaches the end of its context
                    engine.advance(slot, req.replay_feed[consumed - 1],
                                   consumed)
                    del req.replay_feed[:consumed]
                    continue
                # the feed drains EXACTLY at this step's last lane: its
                # emission is the first real one
                del req.replay_feed[:]
            rows.append((req, slot))
            if req.t_first is None and req.replay_ctx is None:
                # the step that fed the prompt's last chunk is on its way,
                # and whoever reads its K/V runs later on the device:
                # publish the prompt to the paged prefix index (no-op on
                # slab) before the slot writes on, or goes
                engine.register_context(slot, req.prompt)
            if engine.speculating:
                continue
            if len(req.tokens) + 1 < req.max_tokens:
                engine.advance(slot, PICK_IN_FLIGHT, consumed)
            else:
                engine.evict(slot, "length")
                del self._by_slot[slot]
                req.slot = None
        return rows

    def _emit(self, rows, nxt, of_step):
        """Deliver the tokens ``nxt`` of device step ``of_step`` to the
        rows that emit, and finish the streams they end."""
        engine = self.engine
        for req, slot in rows:
            if self._flag_abandoned(req):
                self._finish(req, "abandoned")
                continue
            if engine.speculating:
                run = engine.take_spec_result(slot)
                if run is not None:
                    # a verify step: the whole accepted run emits in
                    # one go (and does its own advance/finish)
                    self._emit_spec_run(req, slot, run, of_step)
                    continue
            tok = int(nxt[slot])
            first_emit = req.t_first is None
            req.emit(tok, self.name)
            if first_emit:
                self._first_token(req, of_step)
            self.metrics.observe_gen_tokens(1)
            if req.eos_id is not None and tok == req.eos_id:
                # not known before the read: with a step in flight the
                # row ran one surplus lane in it, in a block of its own,
                # whose pick no one reads (_advance_rows skips the slot)
                self._finish(req, "eos")
            elif len(req.tokens) >= req.max_tokens:
                self._finish(req, "length")
            elif engine.speculating:
                engine.advance(slot, tok, engine.chunk_len(slot))
            elif req.slot == slot:
                engine.advance(slot, tok, 0)

    # ------------------------------------------------------------ shutdown

    def close(self, drain=True, timeout=60.0):
        """Stop admissions, then either finish every queued AND in-flight
        generation (drain=True) or fail them (drain=False).  Idempotent."""
        with self._admit_lock:
            self._drain = drain
            self._closed.set()
        try:
            self.metrics.queue_depth_fns.remove(self._depth_fn)
        except ValueError:
            pass                    # already removed (idempotent close)
        self._thread.join(timeout)
        if self._thread.is_alive():
            # a wedged step: slot state belongs to the (still running)
            # worker — touching _by_slot or the engine from here would
            # race it; callers' own result() timeouts bound their wait
            logger.warning("%s: worker did not drain within %.0fs; "
                           "leaving in-flight slots to it", self.name,
                           timeout)
        # empty anything still queued (a submit that raced the close, or
        # drain=False leftovers) — the queue is thread-safe either way
        while True:
            try:
                req = self._q.get_nowait()
            except queue.Empty:
                break
            self.metrics.reject("shutdown")
            req.fail(ShutdownError("generation batcher closed"))

    @property
    def closed(self):
        return self._closed.is_set()

    @property
    def ready(self):
        """Readiness (/readyz): accepting work, the engine is warm, and
        the circuit breaker is not OPEN.  Half-open counts ready: the
        balancer must route again or the probe that would reclose the
        breaker could never arrive (non-probe admits shed with
        Retry-After, which is the breaker doing its job)."""
        if self._closed.is_set() or not self.engine.ready:
            return False
        return self.supervisor is None \
            or self.supervisor.breaker.state != "open"

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

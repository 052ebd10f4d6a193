"""Speculative decoding: the draft trunk and its jitted k-token rollout.

Leviathan-style greedy draft/verify on the slot engine
(docs/serving.md "Speculative decoding").  A small trunk — fewer layers
(and optionally fewer heads / int8 weights) than the target, sharing the
target's embedding and vocab — runs its OWN k-token autoregressive
rollout per slot against a private slab KV cache, and the TARGET's one
chunked step (``lm_decode_chunk_slots``/``_paged`` with
``all_lanes=True``) then scores every drafted lane at once.  The draft
only ever changes SPEED: acceptance keeps exactly the longest prefix the
target itself would have emitted greedily, so streams stay token-
identical to ``lm_generate`` no matter how good or bad the draft is.

Trace discipline matches the target engine: ONE jitted rollout function
(chunk-ingest the committed tokens, then k-1 static-unrolled one-lane
steps), warmed exactly once; k is a constructor constant and
per-slot feed lengths/positions are data, so acceptance churn never
retraces.  The draft cache is epoch-guarded like the target's
(``reset()`` bumps the epoch; an in-flight rollout's cache commit is
dropped if it lost the race) — PR 6 supervisor recovery resets BOTH
caches and the re-seat replay rebuilds them through the same feed path.

Bookkeeping contract with ``DecodeEngine`` (the ``_d_feed``/``_d_pos``
invariant): rollout K/V writes past the committed stream are NEVER
counted as ingested.  The engine re-feeds every committed token through
``rollout`` (matched drafts re-feed identical values; mismatches feed
the corrected token), and because the chunk step writes all lanes
BEFORE attending, stale rollout writes at those positions are
overwritten before anything reads them — the slab needs no rollback at
all.
"""

import threading

import jax
import jax.numpy as jnp
import numpy as np

from paddle_tpu.models import transformer
from paddle_tpu.testing.trace import expect_traces
from paddle_tpu.utils.error import ConfigError


def make_draft(params, layers=2, quantize=False):
    """Derive a draft parameter tree from the target's: same embedding /
    positional table / final LN / vocab (ARRAYS SHARED, not copied — the
    draft adds only ``layers`` blocks of weight bytes), trunk truncated
    to the first ``layers`` enc blocks.  The shallow trunk stays a
    well-formed LM the transformer entry points accept unchanged; with
    ``quantize=True`` the blocks are int8-quantized via PR 14's
    ``quant/weights.py`` (the shared embedding is quantized too — the
    target holds its own float copy, so this only narrows the draft's
    weight stream)."""
    n = len(params["enc"])
    if not 1 <= layers <= n:
        raise ConfigError(
            f"draft layers must be in [1, {n}] (the target's enc depth), "
            f"got {layers}")
    draft = dict(params)
    draft["enc"] = list(params["enc"][:layers])
    if quantize:
        from paddle_tpu.quant import weights as qw
        draft = qw.quantize_lm(draft)
    return draft


class DraftTrunk:
    """The draft model half of speculative decoding: slab KV cache with
    the target engine's slot indexing, one jitted rollout producing k
    greedy draft tokens per slot per call.

    ``rollout(tokens, positions, lengths)``: chunk-ingest each row's
    ``lengths[r]`` committed tokens starting at ``positions[r]`` (lanes
    past the length are ignored), then unroll ``k - 1`` single-position
    steps feeding the draft's own argmax back in.  Returns drafts
    [num_slots, k] (row r's candidates for stream positions
    ``positions[r] + lengths[r] ..``) — or None if ``reset()`` won the
    epoch race mid-call (the caller arms nothing and retries next step).
    """

    def __init__(self, params, *, k, num_slots, max_len, chunk,
                 num_heads=8, moe_top_k=2, pos_type="learned",
                 name="draft", warm=False, mesh=None):
        if k < 1:
            raise ConfigError(f"speculate_k must be >= 1, got {k}")
        if chunk < 1:
            raise ConfigError(f"draft chunk must be >= 1, got {chunk}")
        # tensor-parallel rollout (docs/serving.md "Sharded decode"): the
        # draft shards EXACTLY like its target — same head/vocab stripe
        # policy, its own private shard_map — so a sharded engine's
        # speculation path never leaves the mesh.  The draft shares the
        # target's head count and vocab, so the engine's divisibility
        # validation covers it; standalone construction re-checks.
        self.mesh = mesh
        self.mesh_shards = 1
        self._shard_axis = None
        if mesh is not None:
            from paddle_tpu.parallel import sharding as _psh
            from paddle_tpu.parallel.mesh import AXIS_MODEL
            from jax.sharding import NamedSharding
            self._psh = _psh
            self._shard_axis = AXIS_MODEL
            self.mesh_shards = int(mesh.shape[AXIS_MODEL])
            probs = _psh.lm_shard_problems(params, num_heads,
                                           self.mesh_shards)
            if probs:
                raise ConfigError(
                    f"{name}: cannot shard the draft trunk "
                    f"{self.mesh_shards} ways: " + "; ".join(probs))
            pspecs = _psh.lm_decode_param_specs(params, AXIS_MODEL)
            params = jax.tree_util.tree_map(
                lambda l, s: jax.device_put(l, NamedSharding(mesh, s)),
                params, pspecs)
        self.params = params
        self.k = int(k)
        self.num_slots = int(num_slots)
        self.max_len = int(max_len)
        self.chunk = int(chunk)
        self.num_heads = num_heads
        self.moe_top_k = moe_top_k
        self.pos_type = pos_type
        self.name = name
        self._trace = [0]
        self._warm = False
        self._epoch = 0
        self._epoch_lock = threading.Lock()
        self._cache = self._new_cache()

        axis = self._shard_axis
        heads = (self.num_heads // self.mesh_shards if axis is not None
                 else self.num_heads)

        def _model(p, cache, tokens, positions, lengths):
            logits, cache = transformer.lm_decode_chunk_slots(
                p, tokens, positions, lengths, cache, heads,
                self.moe_top_k, self.pos_type, shard_axis=axis)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            drafts = [nxt]
            # rollout writes land past the committed stream; the clamp
            # keeps the scatter in-bounds for rows parked at the cache
            # edge (their junk write is re-fed before anything attends)
            base = positions + lengths
            one = jnp.ones_like(lengths)
            for i in range(self.k - 1):
                qp = jnp.minimum(base + i, self.max_len - 1)
                logits, cache = transformer.lm_decode_chunk_slots(
                    p, nxt[:, None], qp, one, cache, heads,
                    self.moe_top_k, self.pos_type, shard_axis=axis)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                drafts.append(nxt)
            return jnp.stack(drafts, axis=1), cache

        if axis is not None:
            # ONE shard_map around the whole rollout: the k-1 unrolled
            # steps stay inside, so the only collectives are the model's
            # own seams — no per-step re-entry
            from jax.sharding import PartitionSpec as _P
            pspecs = self._psh.lm_decode_param_specs(self.params, axis)
            cspecs = self._psh.lm_cache_specs(self._cache, axis)
            body = self._psh.shard_map(
                _model, mesh=mesh,
                in_specs=(pspecs, cspecs, _P(), _P(), _P()),
                out_specs=(_P(), cspecs), check_vma=False)
        else:
            body = _model

        def _draft_fn(p, cache, tokens, positions, lengths):
            self._trace[0] += 1
            return body(p, cache, tokens, positions, lengths)

        self._jit = jax.jit(_draft_fn, donate_argnums=(1,))
        if warm:
            self.warmup()

    def _new_cache(self):
        """A fresh draft slab; on a mesh, born as per-chip head stripes
        like the target's (``parallel.sharding.new_lm_cache``)."""
        def build():
            return transformer.init_lm_cache(self.params, self.num_slots,
                                             self.max_len)
        if self._shard_axis is None:
            return build()
        return self._psh.new_lm_cache(build, self.mesh, self._shard_axis)

    @property
    def trace_count(self):
        return self._trace[0]

    def _dummy_feed(self):
        tokens = np.zeros((self.num_slots, self.chunk), np.int32)
        positions = np.zeros((self.num_slots,), np.int32)
        lengths = np.ones((self.num_slots,), np.int32)
        return tokens, positions, lengths

    def rollout(self, tokens, positions, lengths):
        with self._epoch_lock:
            epoch = self._epoch
        drafts, cache = self._jit(self.params, self._cache,
                                  jnp.asarray(tokens, jnp.int32),
                                  jnp.asarray(positions, jnp.int32),
                                  jnp.asarray(lengths, jnp.int32))
        with self._epoch_lock:
            if epoch != self._epoch:
                return None          # reset() raced us; drop the commit
            self._cache = cache
        return np.asarray(drafts)

    def reset(self):
        """Invalidate the draft cache (supervisor recovery / engine
        reset): epoch bump drops any in-flight rollout's commit, fresh
        slab rebuilt from the params.  Host-side feed bookkeeping lives
        in the engine and is re-seeded by the re-seat paths."""
        with self._epoch_lock:
            self._epoch += 1
            self._cache = self._new_cache()

    def warmup(self):
        """Trace the rollout exactly once at the live shapes.
        Idempotent, like the engine's."""
        if self._warm:
            return
        self._warm = True
        tokens, positions, lengths = self._dummy_feed()
        with expect_traces(lambda: self._trace[0], 1,
                           f"{self.name} rollout warmup",
                           hint="draft rollout shapes must be fixed at "
                                "construction (k/chunk/num_slots)"):
            out = self.rollout(tokens, positions, lengths)
        assert out is not None and out.shape == (self.num_slots, self.k)
        self.reset()

    def lower(self):
        """Lowered (unspecialized-to-device-data) rollout, for
        compiled-HLO inspection."""
        tokens, positions, lengths = self._dummy_feed()
        return self._jit.lower(self.params, self._cache,
                               jnp.asarray(tokens), jnp.asarray(positions),
                               jnp.asarray(lengths))

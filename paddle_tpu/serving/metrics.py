"""Live serving metrics: latency percentiles, queue depth, batch occupancy.

The reference's serving story had no observability beyond host logs; a
dynamic batcher is unoperable without numbers — whether batching is
actually happening (occupancy), how much compute padding burns (waste
ratio), and where the tail latency sits.  One ``ServingMetrics`` instance
is shared by the engine, the batcher, and the HTTP front-end, built on
``utils/stats.py`` (the ``Histogram`` percentile machinery, ``keep="last"``
so a long-running server reports RECENT latency, and a ``global_stats``
timer for the per-batch engine time so ``print_all_stats()`` shows serving
next to training).

``render_prometheus()`` is the text format served at ``/metrics``;
``snapshot()`` is the same data as a dict (the benchmark's drivers and
the smoke JSON consume it).
"""

import threading

from paddle_tpu.utils.stats import Histogram

# submit() rejection reasons — keys are part of the /metrics surface.
# breaker = the circuit breaker is open (resilience/supervisor.py): the
# engine recently failed M consecutive steps, shed fast with 503.
REJECT_REASONS = ("overload", "deadline", "invalid", "shutdown", "breaker")

# decode-slot eviction reasons (generation serving, decode_engine.py):
# eos = the model emitted the stop token, length = per-request max_tokens
# reached, error = the slot's request failed with its batch, shutdown =
# drain(False) failed it, abandoned = the caller disconnected mid-stream,
# recovered = the slot was torn down by a step failure and re-prefilled
# onto the rebuilt slab (resilience/supervisor.py), pool_exhausted = the
# paged KV block pool ran dry and the slot was preempted (its request
# re-seats and continues bit-identically; serving/kv_pool.py).  Keys are
# part of the /metrics surface.
EVICT_REASONS = ("eos", "length", "error", "shutdown", "abandoned",
                 "recovered", "pool_exhausted")

# cross-replica KV handoff outcomes (serving/transfer.py): sent = this
# replica exported a chain blob to a peer, received = a peer's blob was
# fetched + delivered into the host tier, fallback = the handoff was
# skipped or failed and the stream recomputed its context instead
# (bit-identical either way).  Keys are part of the /metrics surface.
HANDOFF_OUTCOMES = ("sent", "received", "fallback")

# circuit-breaker state gauge encoding (breaker_state metric)
BREAKER_STATES = {"closed": 0, "half_open": 1, "open": 2}

_QUANTILES = (50, 95, 99)


class ServingMetrics:
    """Thread-safe counters + latency/batch histograms for one engine.

    clock: injectable zero-arg monotonic clock threaded into every
    recent-window histogram (default: ``time.monotonic`` — zero behavior
    change), so the autoscaler's windowed SLO reads
    (``ttft.percentiles(window_s=...)``) and the tests that drive them
    run on a simulated clock instead of wall-clock sleeps."""

    def __init__(self, name="paddle_tpu_serving", max_samples=100000,
                 clock=None):
        import time as _time
        self.name = name
        self.clock = clock or _time.monotonic
        self._lock = threading.Lock()
        self.requests_total = 0          # accepted into the queue
        self.responses_total = 0         # futures resolved with a result
        self.errors_total = 0            # futures failed by a batch error
        self.rejected = {r: 0 for r in REJECT_REASONS}
        self.batches_total = 0
        self.batch_rows_total = 0        # real rows executed
        self.batch_slots_total = 0       # padded bucket slots executed
        # request wall latency submit -> future resolved (seconds)
        self.latency = Histogram(f"{name}_latency", max_samples=max_samples,
                                 keep="last", clock=self.clock)
        # engine batch execution time (seconds)
        self.batch_time = Histogram(f"{name}_batch_time",
                                    max_samples=max_samples, keep="last",
                                    clock=self.clock)
        # ---- generation serving (decode_engine.py) ----
        # time-to-first-token: submit -> the request's first token exists
        # (prefill done); the latency a chat user feels before anything
        # streams
        self.ttft = Histogram(f"{name}_ttft", max_samples=max_samples,
                              keep="last", clock=self.clock)
        # time-per-output-token: the interval between consecutive steps'
        # tokens becoming ready (one step's wall time, hand-over to read,
        # where the loop keeps none in flight) — every active request
        # emits exactly one token per step, so this IS the per-token
        # latency of the stream
        self.tpot = Histogram(f"{name}_tpot", max_samples=max_samples,
                              keep="last", clock=self.clock)
        # seat -> first token: the part of ttft the engine's steps take,
        # one step a chunk of prompt, after the queue and before the front
        self.prefill = Histogram(f"{name}_prefill", max_samples=max_samples,
                                 keep="last", clock=self.clock)
        self.gen_tokens_total = 0        # useful (delivered) tokens
        self.decode_steps_total = 0
        # steps that carried a prompt chunk for at least one row (counted
        # at the hand-over): the steps whose time is the tail of tpot
        self.decode_steps_with_prefill_total = 0
        # row-steps in which a row with prompt left to feed got NO chunk
        # lanes (prefill_chunk_budget spent: one token through lane 0)
        self.prefill_stalled_row_steps_total = 0
        # steps handed to the device before the tokens of the step
        # before were read (the loop's one step in flight)
        self.decode_steps_overlapped_total = 0
        self.active_slot_steps_total = 0  # sum of active slots over steps
        self.slot_count = 0              # gauge, set by the decode engine
        # ---- unified chunked prefill (decode_engine.py prefill_chunk):
        # prompt ingestion folded into the decode step as K-lane chunks
        self.prefill_chunks_total = 0    # chunks loaded into steps
        self.prefill_chunk_lanes_total = 0  # teacher-forced lanes loaded
        self.prefill_lane_steps_total = 0   # sum of per-step chunk lanes
        # cached positions the seated rows' lanes attended, their own
        # included (paged steps, counted at prepare_step): the work of an
        # attention kernel, whatever it moves
        self.attended_positions_total = 0
        # the same in ONE window layer of a model that has them: a lane at
        # position q attends min(q + 1, window) (0 for every other model);
        # and what such a model's rows READ, each position once a row
        # whatever its lanes: in a window layer, and in a full one (or, in
        # a model with sparse layers, what the indexer reads in one)
        self.window_attended_positions_total = 0
        self.window_read_positions_total = 0
        self.read_positions_total = 0
        # a model with sparse layers, over all of them: positions its
        # indexer scored (q + 1 a lane at q), positions its lanes selected
        # (min(q + 1, topk)), and positions its rows read, the union of a
        # row's lanes' selections once a row (its bound where a row feeds
        # more than one lane; serving/decode_engine prepare_step)
        self.sparse_scored_positions_total = 0
        self.sparse_selected_positions_total = 0
        self.sparse_read_positions_total = 0
        # lanes the steps computed (the trunk: S x K; a model: the packed
        # width the step ran at) and lanes rows fed into them, a free
        # slot's one armed lane included; counted at the hand-over
        self.step_lanes_computed_total = 0
        self.step_lanes_live_total = 0
        # row-steps of seated rows fed exactly one lane (decoding rows, a
        # stalled row's one token): those the tiled attention kernel
        # computes one lane of; counted at the hand-over
        self.one_lane_row_steps_total = 0
        # facts, set at warm-up: did a model's step (DecodeEngine(model=))
        # take its recurrent (kda_chunk), latent (mla_chunk) and
        # selective-scan (mamba_chunk) kernels
        self.kda_kernels = 0
        self.mla_kernels = 0
        self.mamba_kernels = 0
        # and its window layers' kernel over their per-slot rings, whose
        # bytes are a gauge of their own (they are among the slot-addressed
        # leaves too)
        self.window_kernels = 0
        self.window_ring_bytes = 0
        # and its sparse layers' indexer, selection and attention kernels
        self.sparse_kernels = 0
        self.prefill_chunk_size = 0      # gauge: engine K (0 = ladder)
        self.evictions = {r: 0 for r in EVICT_REASONS}
        # ---- speculative decoding (serving/speculative.py): draft
        # lanes scored by verify steps and how many the target accepted
        self.speculate_k = 0             # gauge: draft lanes per slot (0=off)
        # ---- tensor-parallel sharded decode (DecodeEngine(mesh=...)):
        # how many chips the ONE jitted step spans (1 = single-chip)
        self.mesh_shards = 1             # gauge: model-axis mesh size
        self.drafted_tokens_total = 0    # draft lanes scored
        self.accepted_tokens_total = 0   # draft lanes accepted (matched)
        self.spec_steps_total = 0        # steps that verified >= 1 span
        self.spec_slot_steps_total = 0   # sum of speculating slots over steps
        # ---- paged KV cache (decode_engine.py kv_layout="paged" over
        # serving/kv_pool.py): block-pool gauges + prefix-sharing and
        # copy-on-write counters
        self.kv_blocks_total = 0         # gauge: allocatable pool blocks
        self.kv_blocks_free = 0          # gauge: free-list depth
        self.kv_dtype = "float32"        # gauge: cache storage dtype
        #                                  ("int8" = quantized serving)
        self.prefix_cache_hits = 0       # fresh admissions seated from
        #                                  resident prefix blocks
        self.prefix_cache_misses = 0     # fresh admissions that prefilled
        self.cow_forks = 0               # copy-on-write block forks
        # ---- models that hold state (DecodeEngine(model=...)): what the
        # two kinds of cache leaf hold, and how often a slot started over
        self.recurrent_state_bytes = 0   # gauge: slot-addressed leaves
        self.slot_state_bytes = 0        # gauge: those one slot owns
        self.latent_pool_bytes = 0       # gauge: the model's block pools
        self.state_resets_total = 0      # slots seated at position 0
        # ---- hierarchical KV host tier (decode_engine.py kv_host_bytes
        # over serving/kv_pool.HostTier): evicted prefix chains spill to
        # host RAM and restore over the host link instead of recomputing
        self.kv_spill_blocks_total = 0   # blocks serialized to the tier
        self.kv_restore_hits_total = 0   # spilled chains restored + seated
        self.kv_restore_bytes_total = 0  # payload bytes restored H2D
        self.host_tier_bytes = 0         # gauge: resident spilled bytes
        # submit -> commit wall time of one async restore (seconds)
        self.kv_restore = Histogram(f"{name}_kv_restore",
                                    max_samples=max_samples,
                                    keep="last", clock=self.clock)
        # ---- disaggregated serving (serving/transfer.py): KV chains
        # crossing replicas as wire-format blobs at stream handoff
        self.serving_role = "mixed"      # gauge: this replica's fleet role
        self.kv_handoffs = {o: 0 for o in HANDOFF_OUTCOMES}
        self.kv_handoff_bytes_total = 0  # blob bytes sent + received
        # decide -> deliver wall time of one receive-side handoff (s)
        self.kv_handoff = Histogram(f"{name}_kv_handoff",
                                    max_samples=max_samples,
                                    keep="last", clock=self.clock)
        # v2 Inference per-row-signature engine cache (satellite): LRU
        # evictions of whole compiled engines under ragged feed signatures
        self.engine_cache_evictions = 0
        # ---- resilience (resilience/): recovery events all flow here
        self.retries_total = 0           # transient submit retries taken
        self.watchdog_trips_total = 0    # step deadline misses
        self.slot_reprefills_total = 0   # slots rebuilt by re-prefill
        self.breaker_open_total = 0      # times the breaker tripped open
        self.breaker_state = 0           # gauge: 0 closed/1 half-open/2 open
        # wired by batchers: each contributes a zero-arg callable -> its
        # current queue depth; queue_depth() sums them (a combined
        # inference+generation server shares ONE metrics object, and one
        # plane's backlog must never mask another's)
        self.queue_depth_fns = []

    # ------------------------------------------------------------ record

    def accepted(self):
        with self._lock:
            self.requests_total += 1

    def reject(self, reason):
        with self._lock:
            self.rejected[reason] = self.rejected.get(reason, 0) + 1

    def observe_batch(self, n_real, bucket, seconds):
        with self._lock:
            self.batches_total += 1
            self.batch_rows_total += int(n_real)
            self.batch_slots_total += int(bucket)
        self.batch_time.add(seconds)

    def observe_response(self, latency_s):
        with self._lock:
            self.responses_total += 1
        self.latency.add(latency_s)

    def observe_error(self, n=1):
        with self._lock:
            self.errors_total += int(n)

    def observe_ttft(self, seconds):
        self.ttft.add(seconds)

    def observe_decode_step(self, n_active, n_slots, seconds,
                            prefill_lanes=0, accepted_tokens=0,
                            drafted_tokens=0, spec_slots=0):
        """One slab decode step: n_active of n_slots held live requests;
        prefill_lanes = teacher-forced chunk lanes the step fed beyond
        each slot's own token (0 outside chunked-prefill mode).
        Speculative mode adds drafted_tokens (draft lanes the step
        scored), accepted_tokens (lanes the target matched) and
        spec_slots (slots that speculated) — the engine passes these
        kwargs ONLY when a draft trunk is attached, so subclasses with
        the pre-speculation signature keep working unchanged."""
        with self._lock:
            self.decode_steps_total += 1
            self.active_slot_steps_total += int(n_active)
            self.slot_count = int(n_slots)
            self.prefill_lane_steps_total += int(prefill_lanes)
            self.drafted_tokens_total += int(drafted_tokens)
            self.accepted_tokens_total += int(accepted_tokens)
            if spec_slots:
                self.spec_steps_total += 1
                self.spec_slot_steps_total += int(spec_slots)
        self.tpot.add(seconds)

    def observe_step_overlapped(self):
        """One decode step dispatched with the step before still in
        flight."""
        with self._lock:
            self.decode_steps_overlapped_total += 1

    def observe_prefill_chunk(self, lanes):
        """One prefill chunk loaded into the next step (``lanes``
        teacher-forced lanes beyond the slot's armed token)."""
        with self._lock:
            self.prefill_chunks_total += 1
            self.prefill_chunk_lanes_total += int(lanes)

    def observe_attended_positions(self, n):
        """Positions the lanes of the step being prepared attend."""
        with self._lock:
            self.attended_positions_total += int(n)

    def observe_window_positions(self, attended, window_read, read):
        """Positions the lanes of the step being prepared attend in one
        window layer, and the positions its rows read, once a row, in a
        window layer and in a full one."""
        with self._lock:
            self.window_attended_positions_total += int(attended)
            self.window_read_positions_total += int(window_read)
            self.read_positions_total += int(read)

    def observe_sparse_positions(self, scored, selected, read, held):
        """What the lanes of the step being prepared score, select and
        read in the sparse layers, all of them, and the positions its rows
        hold (``read_positions_total``: the indexer reads each once a row
        and layer)."""
        with self._lock:
            self.sparse_scored_positions_total += int(scored)
            self.sparse_selected_positions_total += int(selected)
            self.sparse_read_positions_total += int(read)
            self.read_positions_total += int(held)

    def observe_step_lanes(self, computed, live, prefill_rows=0,
                           one_lane_rows=0):
        """The width of the step being handed over, the lanes fed, the
        rows among them that are fed a prompt chunk and the seated rows
        fed exactly one lane."""
        with self._lock:
            self.step_lanes_computed_total += int(computed)
            self.step_lanes_live_total += int(live)
            self.decode_steps_with_prefill_total += int(prefill_rows > 0)
            self.one_lane_row_steps_total += int(one_lane_rows)

    def observe_prefill_stalled(self, rows):
        """Rows of the step being prepared that still have prompt to
        feed and got no chunk lanes."""
        with self._lock:
            self.prefill_stalled_row_steps_total += int(rows)

    def observe_prefill(self, seconds):
        self.prefill.add(seconds)

    def set_model_kernels(self, kda, mla, mamba):
        """Facts: the paths a model's compiled step took."""
        with self._lock:
            self.kda_kernels, self.mla_kernels = int(kda), int(mla)
            self.mamba_kernels = int(mamba)

    def set_window(self, ring_bytes, kernels):
        """Facts of a model with window layers: the bytes of their rings
        and whether its step took the window kernel."""
        with self._lock:
            self.window_ring_bytes = int(ring_bytes)
            self.window_kernels = int(kernels)

    def set_sparse(self, kernels):
        """Fact of a model with sparse layers: whether its step took the
        indexer, selection and sparse attention kernels."""
        with self._lock:
            self.sparse_kernels = int(kernels)

    def set_prefill_chunk(self, k):
        """Gauge: the engine's chunk size K (0 = legacy ladder mode)."""
        with self._lock:
            self.prefill_chunk_size = int(k)

    def set_speculate_k(self, k):
        """Gauge: the engine's draft lanes per slot (0 = speculation
        off).  Config, like the chunk gauge: the engine's metrics-swap
        setter re-applies it so a fresh object inherits it."""
        with self._lock:
            self.speculate_k = int(k)

    def set_mesh_shards(self, n):
        """Gauge: model-axis mesh size the decode step is sharded over
        (1 = single-chip).  Config, like the chunk/speculate gauges."""
        with self._lock:
            self.mesh_shards = max(1, int(n))

    def observe_gen_tokens(self, n=1):
        with self._lock:
            self.gen_tokens_total += int(n)

    def evict_slot(self, reason):
        with self._lock:
            self.evictions[reason] = self.evictions.get(reason, 0) + 1

    def evict_engine_cache(self):
        with self._lock:
            self.engine_cache_evictions += 1

    # ---- paged KV cache (decode_engine.py / serving/kv_pool.py) ----

    def observe_prefix_cache(self, hit):
        """One fresh admission's prefix-cache outcome: seated from
        resident blocks (hit) or prefilled (miss)."""
        with self._lock:
            if hit:
                self.prefix_cache_hits += 1
            else:
                self.prefix_cache_misses += 1

    def observe_cow_fork(self, n=1):
        with self._lock:
            self.cow_forks += int(n)

    def set_state_cache_bytes(self, slot_bytes, block_bytes, per_slot):
        """Gauges: bytes of a served model's slot-addressed state, of its
        block-addressed pools, and of the state ONE slot owns over all
        layers (all 0 for the transformer trunk)."""
        with self._lock:
            self.recurrent_state_bytes = int(slot_bytes)
            self.latent_pool_bytes = int(block_bytes)
            self.slot_state_bytes = int(per_slot)

    def observe_state_reset(self, n=1):
        """A slot of a state-holding model was seated at position 0: the
        next step zeroes its state."""
        with self._lock:
            self.state_resets_total += int(n)

    def set_kv_pool(self, free, total):
        """Snapshot the block pool's free/allocatable gauges."""
        with self._lock:
            self.kv_blocks_free = int(free)
            self.kv_blocks_total = int(total)

    def set_kv_dtype(self, kv_dtype):
        """Gauge: the engine's KV-cache storage dtype (quantized
        serving: "int8" -> ``kv_cache_int8 1`` on /metrics)."""
        with self._lock:
            self.kv_dtype = str(kv_dtype)

    def observe_kv_spill(self, blocks):
        """One prefix chain spilled to the host tier at eviction."""
        with self._lock:
            self.kv_spill_blocks_total += int(blocks)

    def observe_kv_restore(self, nbytes, seconds):
        """One spilled chain restored and committed back into the pool
        (``seconds`` = submit -> commit wall time of the async job)."""
        with self._lock:
            self.kv_restore_hits_total += 1
            self.kv_restore_bytes_total += int(nbytes)
        self.kv_restore.add(seconds)

    def set_host_tier_bytes(self, nbytes):
        """Gauge: serialized payload bytes resident in the host tier."""
        with self._lock:
            self.host_tier_bytes = int(nbytes)

    def set_serving_role(self, role):
        """Gauge: this replica's fleet role ("prefill" | "decode" |
        "mixed") — the router reads it off /metrics to build pools."""
        with self._lock:
            self.serving_role = str(role)

    def observe_kv_handoff(self, outcome, nbytes=0, seconds=None):
        """One cross-replica KV handoff event (serving/transfer.py):
        ``outcome`` in ``HANDOFF_OUTCOMES``; ``nbytes`` the blob bytes
        crossing the socket; ``seconds`` the receive side's
        decide-to-deliver wall time."""
        with self._lock:
            self.kv_handoffs[outcome] += 1
            self.kv_handoff_bytes_total += int(nbytes)
        if seconds is not None:
            self.kv_handoff.add(seconds)

    # ---- resilience events (resilience/supervisor.py callers) ----

    def observe_retry(self, n=1):
        with self._lock:
            self.retries_total += int(n)

    def observe_watchdog_trip(self):
        with self._lock:
            self.watchdog_trips_total += 1

    def observe_slot_reprefill(self, n=1):
        with self._lock:
            self.slot_reprefills_total += int(n)

    def set_breaker_state(self, state, opened_total=None):
        """Snapshot the breaker's state ('closed'/'half_open'/'open')
        and cumulative open count into the gauge/counter pair."""
        with self._lock:
            self.breaker_state = BREAKER_STATES.get(state, 0)
            if opened_total is not None:
                self.breaker_open_total = int(opened_total)

    # ------------------------------------------------------------ derive

    @property
    def mean_occupancy(self):
        """Real rows per executed batch (> 1.0 iff batching happened)."""
        with self._lock:
            return (self.batch_rows_total / self.batches_total
                    if self.batches_total else 0.0)

    @property
    def padding_waste(self):
        """Fraction of executed bucket slots that held padding."""
        with self._lock:
            return (1.0 - self.batch_rows_total / self.batch_slots_total
                    if self.batch_slots_total else 0.0)

    @property
    def mean_slot_occupancy(self):
        """Active slots per decode step (generation serving); the fraction
        of the slab doing useful work is this over ``slot_count``."""
        with self._lock:
            return (self.active_slot_steps_total / self.decode_steps_total
                    if self.decode_steps_total else 0.0)

    @property
    def mean_prefill_chunk_occupancy(self):
        """Fraction of the per-step chunk-lane capacity
        (``slots * (K - 1)`` teacher-forced lanes) actually fed, over
        the steps executed — how much of each unified step is prompt
        ingestion vs decode.  0.0 outside chunked mode."""
        with self._lock:
            cap = (self.decode_steps_total * self.slot_count
                   * max(0, self.prefill_chunk_size - 1))
            return (self.prefill_lane_steps_total / cap) if cap else 0.0

    @property
    def spec_acceptance_rate(self):
        """Fraction of drafted lanes the target accepted (speculative
        decoding quality; 0.0 with no drafts scored)."""
        with self._lock:
            return (self.accepted_tokens_total / self.drafted_tokens_total
                    if self.drafted_tokens_total else 0.0)

    @property
    def spec_tokens_per_step(self):
        """Mean emitted tokens per speculating slot-step (each verify
        span emits its accepted run + the target's own token, so this is
        >= 1.0 whenever speculation ran; the headline effective-tokens-
        per-target-step number).  0.0 with no speculation."""
        with self._lock:
            return ((self.accepted_tokens_total + self.spec_slot_steps_total)
                    / self.spec_slot_steps_total
                    if self.spec_slot_steps_total else 0.0)

    def tpot_jitter(self):
        """Recent-window TPOT p99/p50 ratio — the jitter a long-prompt
        admission injects into in-flight streams' token cadence (1.0 =
        perfectly steady; the chunked-prefill acceptance metric).  0.0
        with no samples."""
        pct = self.tpot.percentiles((50, 99))
        p50, p99 = pct.get(50, 0.0), pct.get(99, 0.0)
        return (p99 / p50) if p50 > 0 else 0.0

    def queue_depth(self):
        total = 0
        for fn in list(self.queue_depth_fns):
            try:
                total += int(fn())
            except Exception:   # noqa: BLE001 — a dying queue must not
                pass            # kill /metrics
        return total

    def snapshot(self):
        """All metrics as one dict (benchmark / smoke JSON surface)."""
        lat = self.latency.percentiles(_QUANTILES)
        bt = self.batch_time.percentiles(_QUANTILES)
        ttft = self.ttft.percentiles(_QUANTILES)
        prefill = self.prefill.percentiles(_QUANTILES)
        tpot = self.tpot.percentiles(_QUANTILES)
        with self._lock:
            out = {
                "requests_total": self.requests_total,
                "responses_total": self.responses_total,
                "errors_total": self.errors_total,
                "rejected": dict(self.rejected),
                "batches_total": self.batches_total,
                "batch_rows_total": self.batch_rows_total,
                "batch_slots_total": self.batch_slots_total,
                "gen_tokens_total": self.gen_tokens_total,
                "decode_steps_total": self.decode_steps_total,
                "decode_steps_overlapped_total":
                    self.decode_steps_overlapped_total,
                "decode_steps_with_prefill_total":
                    self.decode_steps_with_prefill_total,
                "prefill_stalled_row_steps_total":
                    self.prefill_stalled_row_steps_total,
                "slot_count": self.slot_count,
                "prefill_chunks_total": self.prefill_chunks_total,
                "prefill_chunk_lanes_total":
                    self.prefill_chunk_lanes_total,
                "attended_positions_total": self.attended_positions_total,
                "window_attended_positions_total":
                    self.window_attended_positions_total,
                "window_read_positions_total":
                    self.window_read_positions_total,
                "read_positions_total": self.read_positions_total,
                "sparse_scored_positions_total":
                    self.sparse_scored_positions_total,
                "sparse_selected_positions_total":
                    self.sparse_selected_positions_total,
                "sparse_read_positions_total":
                    self.sparse_read_positions_total,
                "step_lanes_computed_total": self.step_lanes_computed_total,
                "step_lanes_live_total": self.step_lanes_live_total,
                "one_lane_row_steps_total": self.one_lane_row_steps_total,
                "kda_kernels": self.kda_kernels,
                "mla_kernels": self.mla_kernels,
                "mamba_kernels": self.mamba_kernels,
                "window_kernels": self.window_kernels,
                "window_ring_bytes": self.window_ring_bytes,
                "sparse_kernels": self.sparse_kernels,
                "prefill_chunk_size": self.prefill_chunk_size,
                "speculate_k": self.speculate_k,
                "mesh_shards": self.mesh_shards,
                "drafted_tokens_total": self.drafted_tokens_total,
                "accepted_tokens_total": self.accepted_tokens_total,
                "spec_steps_total": self.spec_steps_total,
                "spec_slot_steps_total": self.spec_slot_steps_total,
                "evictions": dict(self.evictions),
                "kv_blocks_total": self.kv_blocks_total,
                "kv_blocks_free": self.kv_blocks_free,
                "kv_dtype": self.kv_dtype,
                "kv_blocks_used": self.kv_blocks_total
                - self.kv_blocks_free,
                "kv_block_utilization": round(
                    (self.kv_blocks_total - self.kv_blocks_free)
                    / self.kv_blocks_total, 3) if self.kv_blocks_total
                else 0.0,
                "prefix_cache_hits_total": self.prefix_cache_hits,
                "prefix_cache_misses_total": self.prefix_cache_misses,
                "cow_forks_total": self.cow_forks,
                "recurrent_state_bytes": self.recurrent_state_bytes,
                "latent_pool_bytes": self.latent_pool_bytes,
                "slot_state_bytes": self.slot_state_bytes,
                "state_resets_total": self.state_resets_total,
                "kv_spill_blocks_total": self.kv_spill_blocks_total,
                "kv_restore_hits_total": self.kv_restore_hits_total,
                "kv_restore_bytes_total": self.kv_restore_bytes_total,
                "host_tier_bytes": self.host_tier_bytes,
                "serving_role": self.serving_role,
                "kv_handoffs_total": dict(self.kv_handoffs),
                "kv_handoff_bytes_total": self.kv_handoff_bytes_total,
                "engine_cache_evictions": self.engine_cache_evictions,
                "retries_total": self.retries_total,
                "watchdog_trips_total": self.watchdog_trips_total,
                "slot_reprefills_total": self.slot_reprefills_total,
                "breaker_open_total": self.breaker_open_total,
                "breaker_state": self.breaker_state,
            }
        from paddle_tpu.resilience import faults
        out["faults_fired"] = faults.fired_counts()
        out["queue_depth"] = self.queue_depth()
        out["mean_occupancy"] = round(self.mean_occupancy, 3)
        out["padding_waste"] = round(self.padding_waste, 3)
        out["mean_slot_occupancy"] = round(self.mean_slot_occupancy, 3)
        out["mean_prefill_chunk_occupancy"] = round(
            self.mean_prefill_chunk_occupancy, 4)
        out["tpot_jitter_p99_p50"] = round(self.tpot_jitter(), 3)
        out["spec_acceptance_rate"] = round(self.spec_acceptance_rate, 4)
        out["spec_tokens_per_step"] = round(self.spec_tokens_per_step, 4)
        out["latency_ms"] = {f"p{q}": round(v * 1e3, 3)
                             for q, v in lat.items()}
        out["batch_time_ms"] = {f"p{q}": round(v * 1e3, 3)
                                for q, v in bt.items()}
        out["ttft_ms"] = {f"p{q}": round(v * 1e3, 3)
                          for q, v in ttft.items()}
        out["prefill_ms"] = {f"p{q}": round(v * 1e3, 3)
                             for q, v in prefill.items()}
        out["tpot_ms"] = {f"p{q}": round(v * 1e3, 3)
                          for q, v in tpot.items()}
        out["kv_restore_ms"] = {
            f"p{q}": round(v * 1e3, 3)
            for q, v in self.kv_restore.percentiles(_QUANTILES).items()}
        out["kv_handoff_ms"] = {
            f"p{q}": round(v * 1e3, 3)
            for q, v in self.kv_handoff.percentiles(_QUANTILES).items()}
        return out

    # ------------------------------------------------------------ render

    def render_prometheus(self):
        """Prometheus text exposition for the /metrics endpoint."""
        n = self.name
        lat = self.latency.percentiles(_QUANTILES)
        bt = self.batch_time.percentiles(_QUANTILES)
        lines = []

        def emit(metric, value, help_, mtype="gauge", labels=""):
            lines.append(f"# HELP {n}_{metric} {help_}")
            lines.append(f"# TYPE {n}_{metric} {mtype}")
            lines.append(f"{n}_{metric}{labels} {value}")

        with self._lock:
            counters = [
                ("requests_total", self.requests_total,
                 "requests accepted into the batching queue"),
                ("responses_total", self.responses_total,
                 "requests answered with a result"),
                ("errors_total", self.errors_total,
                 "requests failed by a batch execution error"),
                ("batches_total", self.batches_total,
                 "engine batches executed"),
                ("batch_rows_total", self.batch_rows_total,
                 "real request rows executed"),
                ("batch_slots_total", self.batch_slots_total,
                 "bucket slots executed (rows + padding)"),
            ]
            rejected = dict(self.rejected)
        for metric, value, help_ in counters:
            emit(metric, value, help_, mtype="counter")
        lines.append(f"# HELP {n}_rejected_total requests rejected before "
                     "batching, by reason")
        lines.append(f"# TYPE {n}_rejected_total counter")
        for reason in sorted(rejected):
            lines.append(
                f'{n}_rejected_total{{reason="{reason}"}} {rejected[reason]}')
        emit("queue_depth", self.queue_depth(), "requests waiting in queue")
        emit("batch_occupancy_mean", f"{self.mean_occupancy:.6f}",
             "mean real rows per executed batch")
        emit("padding_waste_ratio", f"{self.padding_waste:.6f}",
             "fraction of executed slots that held padding")
        lines.append(f"# HELP {n}_latency_seconds request wall latency "
                     "(submit to response), recent-window quantiles")
        lines.append(f"# TYPE {n}_latency_seconds summary")
        for q, v in lat.items():
            lines.append(
                f'{n}_latency_seconds{{quantile="0.{q}"}} {v:.6f}')
        lines.append(f"{n}_latency_seconds_count {self.latency.count}")
        lines.append(f"# HELP {n}_batch_time_seconds engine batch execution "
                     "time, recent-window quantiles")
        lines.append(f"# TYPE {n}_batch_time_seconds summary")
        for q, v in bt.items():
            lines.append(
                f'{n}_batch_time_seconds{{quantile="0.{q}"}} {v:.6f}')
        lines.append(f"{n}_batch_time_seconds_count {self.batch_time.count}")

        # ---- generation serving (decode_engine.py) ----
        ttft = self.ttft.percentiles(_QUANTILES)
        prefill = self.prefill.percentiles(_QUANTILES)
        tpot = self.tpot.percentiles(_QUANTILES)
        with self._lock:
            gen_counters = [
                ("gen_tokens_total", self.gen_tokens_total,
                 "generated tokens delivered to requests"),
                ("decode_steps_total", self.decode_steps_total,
                 "continuous-batching slab decode steps executed"),
                ("decode_steps_overlapped_total",
                 self.decode_steps_overlapped_total,
                 "decode steps handed to the device before the previous "
                 "step's tokens were read"),
                ("decode_steps_with_prefill_total",
                 self.decode_steps_with_prefill_total,
                 "decode steps that carried a prompt chunk for at least "
                 "one row"),
                ("prefill_stalled_row_steps_total",
                 self.prefill_stalled_row_steps_total,
                 "row-steps in which a row with prompt left to feed got "
                 "no chunk lanes (prefill_chunk_budget spent)"),
                ("engine_cache_evictions_total",
                 self.engine_cache_evictions,
                 "compiled engines evicted from the per-row-signature "
                 "LRU cache"),
                ("prefix_cache_hits_total", self.prefix_cache_hits,
                 "fresh admissions seated from resident prefix blocks "
                 "(paged KV cache)"),
                ("prefix_cache_misses_total", self.prefix_cache_misses,
                 "fresh admissions that re-prefilled (paged KV cache)"),
                ("cow_forks_total", self.cow_forks,
                 "copy-on-write KV block forks (paged KV cache)"),
                ("state_resets_total", self.state_resets_total,
                 "slots of a state-holding model seated at position 0 "
                 "(the step zeroes their recurrent state)"),
                ("kv_spill_blocks_total", self.kv_spill_blocks_total,
                 "KV blocks serialized to the host tier at prefix "
                 "eviction (hierarchical KV)"),
                ("kv_restore_hits_total", self.kv_restore_hits_total,
                 "spilled prefix chains restored from the host tier "
                 "and reseated (hierarchical KV)"),
                ("kv_restore_bytes_total", self.kv_restore_bytes_total,
                 "serialized payload bytes restored host-to-device "
                 "(hierarchical KV)"),
                ("prefill_chunks_total", self.prefill_chunks_total,
                 "prompt-ingestion chunks fed through the unified "
                 "decode step (chunked prefill)"),
                ("prefill_chunk_lanes_total",
                 self.prefill_chunk_lanes_total,
                 "teacher-forced chunk lanes fed through the unified "
                 "decode step (chunked prefill)"),
                ("attended_positions_total", self.attended_positions_total,
                 "cached positions the seated rows' lanes attended, "
                 "their own included (paged steps)"),
                ("window_attended_positions_total",
                 self.window_attended_positions_total,
                 "positions the seated rows' lanes attended in one window "
                 "layer (min(position + 1, window) a lane)"),
                ("window_read_positions_total",
                 self.window_read_positions_total,
                 "positions the seated rows read in one window layer, each "
                 "once a row (models with window layers)"),
                ("read_positions_total", self.read_positions_total,
                 "positions the seated rows read in one full attention "
                 "layer, each once a row (models with window or sparse "
                 "layers)"),
                ("sparse_scored_positions_total",
                 self.sparse_scored_positions_total,
                 "positions a sparse model's indexer scored, over its "
                 "lanes and sparse layers (position + 1 a lane)"),
                ("sparse_selected_positions_total",
                 self.sparse_selected_positions_total,
                 "positions a sparse model's lanes attended, over its "
                 "sparse layers (min(position + 1, topk) a lane)"),
                ("sparse_read_positions_total",
                 self.sparse_read_positions_total,
                 "positions a sparse model's rows selected, the union of "
                 "a row's lanes once a row and sparse layer (its bound "
                 "for a row fed more than one lane)"),
                ("step_lanes_computed_total",
                 self.step_lanes_computed_total,
                 "lanes the decode steps computed (a model's steps: the "
                 "packed width each ran at)"),
                ("step_lanes_live_total", self.step_lanes_live_total,
                 "lanes rows fed into the decode steps, a free slot's "
                 "one included"),
                ("one_lane_row_steps_total", self.one_lane_row_steps_total,
                 "seated rows fed exactly one lane, summed over steps: the "
                 "rows the tiled attention kernel computes one lane of"),
                ("drafted_tokens_total", self.drafted_tokens_total,
                 "draft lanes scored by verify steps (speculative "
                 "decoding)"),
                ("accepted_tokens_total", self.accepted_tokens_total,
                 "draft lanes the target accepted (speculative "
                 "decoding)"),
                ("spec_steps_total", self.spec_steps_total,
                 "decode steps that verified at least one draft span"),
                ("spec_slot_steps_total", self.spec_slot_steps_total,
                 "per-slot verify spans scored (speculating slots "
                 "summed over steps)"),
            ]
            gen_counters.append(
                ("kv_handoff_bytes_total", self.kv_handoff_bytes_total,
                 "KV blob bytes crossing the cross-replica handoff "
                 "socket, sent + received (disaggregated serving)"))
            evictions = dict(self.evictions)
            handoffs = dict(self.kv_handoffs)
            role = self.serving_role
            slot_count = self.slot_count
            kv_total = self.kv_blocks_total
            kv_free = self.kv_blocks_free
            host_bytes = self.host_tier_bytes
            kv_int8 = self.kv_dtype == "int8"
            chunk_size = self.prefill_chunk_size
            spec_k = self.speculate_k
            mesh_shards = self.mesh_shards
            state_bytes = self.recurrent_state_bytes
            latent_bytes = self.latent_pool_bytes
            slot_state_bytes = self.slot_state_bytes
            kda_kernels, mla_kernels = self.kda_kernels, self.mla_kernels
            mamba_kernels = self.mamba_kernels
            window_kernels = self.window_kernels
            ring_bytes = self.window_ring_bytes
            sparse_kernels = self.sparse_kernels
        for metric, value, help_ in gen_counters:
            emit(metric, value, help_, mtype="counter")
        emit("prefill_chunk_size", chunk_size,
             "chunked-prefill lanes per step (K; 0 = legacy ladder)")
        emit("prefill_chunk_occupancy_mean",
             f"{self.mean_prefill_chunk_occupancy:.6f}",
             "fraction of per-step chunk-lane capacity fed")
        emit("speculate_k", spec_k,
             "draft lanes per slot per verify step (0 = speculation off)")
        emit("mesh_shards", mesh_shards,
             "model-axis mesh size the decode step spans (1 = "
             "single-chip)")
        emit("spec_acceptance_rate", f"{self.spec_acceptance_rate:.6f}",
             "fraction of drafted lanes the target accepted")
        emit("spec_tokens_per_step", f"{self.spec_tokens_per_step:.6f}",
             "mean emitted tokens per speculating slot-step (>= 1 when "
             "speculation runs)")
        emit("tpot_jitter_p99_p50", f"{self.tpot_jitter():.6f}",
             "recent-window TPOT p99/p50 ratio (token-cadence jitter)")
        emit("kv_blocks_total", kv_total,
             "allocatable KV blocks in the paged pool (0 = slab layout)")
        emit("kv_blocks_free", kv_free, "free KV blocks in the paged pool")
        emit("kv_blocks_used", kv_total - kv_free,
             "KV blocks held by slot chains / the prefix index")
        emit("kv_block_utilization",
             f"{((kv_total - kv_free) / kv_total if kv_total else 0.0):.6f}",
             "fraction of the paged KV pool in use")
        emit("recurrent_state_bytes", state_bytes,
             "bytes of slot-addressed recurrent state a served model "
             "holds (0 = the transformer trunk)")
        emit("latent_pool_bytes", latent_bytes,
             "bytes of a served model's block-addressed pools (latent "
             "attention; 0 = the transformer trunk)")
        emit("slot_state_bytes", slot_state_bytes,
             "bytes of recurrent state ONE slot of a served model owns, all "
             "layers (0 = the transformer trunk)")
        emit("kda_kernels", kda_kernels,
             "1 when a served model's step took the kda_chunk kernel")
        emit("mla_kernels", mla_kernels,
             "1 when a served model's step took the mla_chunk kernel")
        emit("mamba_kernels", mamba_kernels,
             "1 when a served model's step took the mamba_chunk kernel")
        emit("window_kernels", window_kernels,
             "1 when a served model's step took the window kernel over its "
             "window layers' rings")
        emit("window_ring_bytes", ring_bytes,
             "bytes of a served model's window-layer rings, window + chunk "
             "positions a slot (0 = no window layer)")
        emit("sparse_kernels", sparse_kernels,
             "1 when a served model's step took the indexer, selection and "
             "sparse attention kernels over its sparse layers")
        emit("kv_cache_int8", int(kv_int8),
             "1 when the KV cache stores int8 + per-head scale sidecars "
             "(quantized serving; docs/serving.md)")
        emit("host_tier_bytes", host_bytes,
             "serialized KV payload bytes resident in the host spill "
             "tier (hierarchical KV; 0 = tier off)")
        kvr = self.kv_restore.percentiles(_QUANTILES)
        lines.append(f"# HELP {n}_kv_restore_seconds host-tier restore "
                     "submit-to-commit wall time, recent-window quantiles")
        lines.append(f"# TYPE {n}_kv_restore_seconds summary")
        for q, v in kvr.items():
            lines.append(
                f'{n}_kv_restore_seconds{{quantile="0.{q}"}} {v:.6f}')
        lines.append(f"{n}_kv_restore_seconds_count "
                     f"{self.kv_restore.count}")
        emit("serving_role", 1,
             "this replica's disaggregated-fleet role (the router "
             "builds its prefill/decode pools from this)",
             labels=f'{{role="{role}"}}')
        lines.append(f"# HELP {n}_kv_handoffs_total cross-replica KV "
                     "handoffs, by outcome (disaggregated serving)")
        lines.append(f"# TYPE {n}_kv_handoffs_total counter")
        for outcome in sorted(handoffs):
            lines.append(f'{n}_kv_handoffs_total{{outcome="{outcome}"}} '
                         f"{handoffs[outcome]}")
        kvh = self.kv_handoff.percentiles(_QUANTILES)
        lines.append(f"# HELP {n}_kv_handoff_seconds receive-side "
                     "handoff decide-to-deliver wall time, "
                     "recent-window quantiles")
        lines.append(f"# TYPE {n}_kv_handoff_seconds summary")
        for q, v in kvh.items():
            lines.append(
                f'{n}_kv_handoff_seconds{{quantile="0.{q}"}} {v:.6f}')
        lines.append(f"{n}_kv_handoff_seconds_count "
                     f"{self.kv_handoff.count}")
        lines.append(f"# HELP {n}_slot_evictions_total decode slots "
                     "evicted, by reason")
        lines.append(f"# TYPE {n}_slot_evictions_total counter")
        for reason in sorted(evictions):
            lines.append(f'{n}_slot_evictions_total{{reason="{reason}"}} '
                         f"{evictions[reason]}")
        emit("slot_count", slot_count, "decode slots in the slab")
        emit("slot_occupancy_mean", f"{self.mean_slot_occupancy:.6f}",
             "mean active slots per decode step")
        lines.append(f"# HELP {n}_ttft_seconds time to first token "
                     "(submit to first token), recent-window quantiles")
        lines.append(f"# TYPE {n}_ttft_seconds summary")
        for q, v in ttft.items():
            lines.append(f'{n}_ttft_seconds{{quantile="0.{q}"}} {v:.6f}')
        lines.append(f"{n}_ttft_seconds_count {self.ttft.count}")
        lines.append(f"# HELP {n}_prefill_seconds seat to first token "
                     "(the steps that feed the prompt), recent-window "
                     "quantiles")
        lines.append(f"# TYPE {n}_prefill_seconds summary")
        for q, v in prefill.items():
            lines.append(f'{n}_prefill_seconds{{quantile="0.{q}"}} {v:.6f}')
        lines.append(f"{n}_prefill_seconds_count {self.prefill.count}")
        lines.append(f"# HELP {n}_tpot_seconds per-output-token latency "
                     "(one slab decode step), recent-window quantiles")
        lines.append(f"# TYPE {n}_tpot_seconds summary")
        for q, v in tpot.items():
            lines.append(f'{n}_tpot_seconds{{quantile="0.{q}"}} {v:.6f}')
        lines.append(f"{n}_tpot_seconds_count {self.tpot.count}")

        # ---- resilience (resilience/: faults, watchdog, breaker) ----
        from paddle_tpu.resilience import faults
        with self._lock:
            res_counters = [
                ("retries_total", self.retries_total,
                 "transient submit failures absorbed by bounded retry"),
                ("watchdog_trips_total", self.watchdog_trips_total,
                 "decode steps abandoned past the watchdog deadline"),
                ("slot_reprefills_total", self.slot_reprefills_total,
                 "decode slots recovered by re-prefill after a rebuild"),
                ("breaker_open_total", self.breaker_open_total,
                 "times the circuit breaker tripped open"),
            ]
            breaker_state = self.breaker_state
        for metric, value, help_ in res_counters:
            emit(metric, value, help_, mtype="counter")
        emit("breaker_state", breaker_state,
             "circuit breaker state (0 closed, 1 half-open, 2 open)")
        fired = faults.fired_counts()
        lines.append(f"# HELP {n}_fault_injections_total injected faults "
                     "fired, by point (resilience/faults.py)")
        lines.append(f"# TYPE {n}_fault_injections_total counter")
        for point in sorted(fired):
            lines.append(f'{n}_fault_injections_total{{point="{point}"}} '
                         f"{fired[point]}")
        return "\n".join(lines) + "\n"

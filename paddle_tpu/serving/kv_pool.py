"""Paged KV cache: block-pool allocator + copy-on-write prefix sharing.

The slab layout (``DecodeEngine(kv_layout="slab")``) reserves
``max_len`` KV positions per slot no matter how long the request
actually runs — the reservation waste PagedAttention (vLLM, SOSP'23)
eliminates — and at fleet scale most traffic shares a handful of
system-prompt prefixes the slab recomputes and stores once PER SLOT.
This module is the host half of the paged answer (docs/serving.md §5):

* ``BlockPool`` — a fixed pool of ``num_blocks`` KV blocks of
  ``block_size`` positions each (the device arrays live in the engine:
  per-layer ``[num_blocks, block_size, Dkv]``,
  ``transformer.init_lm_cache_paged``).  Free-list allocation with
  per-block REFCOUNTS: a physical block referenced by several slot
  chains (and/or the prefix index) stays resident until the last
  reference releases it.  Block 0 is reserved as the scratch block free
  slot rows point at; allocatable ids are ``1..num_blocks-1``.

* ``PrefixIndex`` — maps block-aligned prompt prefixes (token tuples of
  length ``k * block_size``) to the already-resident block chains that
  hold their K/V.  A new request whose prompt starts with a cached
  prefix admits by TAKING REFERENCES to those physical blocks instead
  of re-prefilling them: duplicate KV bytes and duplicate prefill
  compute both disappear.  LRU: under pool pressure the allocator
  evicts the stalest entries (their blocks free once no slot shares
  them).

* ``PagedKVState`` — per-engine bookkeeping tying the two together:
  the per-slot block tables (``[num_slots, blocks_per_row]`` int32 fed
  to the jitted step as DATA — churn never retraces), per-slot chain
  ledgers, and the write-exclusivity rule that yields COPY-ON-WRITE: a
  slot about to write into a block whose refcount exceeds 1 first forks
  it (the engine device-copies the block, the table entry swaps to the
  private copy) so shared prefix blocks are physically immutable while
  referenced.

Everything here is host-side numpy/bookkeeping between steps; the one
jitted step (``transformer.lm_decode_chunk_paged``) only ever sees
fixed-shape pools and tables.  ``check()`` verifies the refcount ledger
(no leak, no double-free) — the chaos tests run it after every fault
matrix pass.

The HIERARCHICAL tier (docs/serving.md §5 "Hierarchical KV") extends
the story below HBM: ``HostTier`` is an LRU byte-capped host-RAM store
of SPILLED prefix chains — when ``PrefixIndex.evict_lru`` drops an
entry under pool pressure, an ``on_evict`` hook (the engine's) gathers
the chain's device blocks and ``serialize_chain``s them into the tier,
keyed by the SAME block-aligned prefix key; a later prompt that would
have recomputed that prefix instead restores it asynchronously (the
tier's ``TransferWorker`` thread deserializes + stages device chunks
while decode steps keep running) into freshly claimed blocks and seats
by reference like any resident hit.  ``serialize_chain``/
``restore_chain`` are the relocatable wire format (version byte +
trunk signature) the ROADMAP item 2(b) cross-replica handoff reuses.
"""

import collections
import json
import threading

import numpy as np

from paddle_tpu.obs import trace as obstrace
from paddle_tpu.utils.error import ConfigError
from paddle_tpu.utils.logging import logger

SCRATCH_BLOCK = 0

# serialize_chain wire-format version: byte 0 of every blob.  Bump on
# any layout change — restore_chain rejects other versions, so a
# cross-replica peer (item 2(b)) can never mis-parse a newer blob.
WIRE_VERSION = 1

# Default decoded-blob ceiling at the NETWORK boundary (serving/
# transfer.py) — a garbled or malicious peer's length prefix / shape
# manifest must never drive an allocation.  The host tier itself is
# byte-capped separately; this bounds a SINGLE blob.
MAX_CHAIN_BLOB_BYTES = 1 << 30


# The two kinds of leaf a cache pytree may hold (the first step of ROADMAP
# D6; docs/serving.md "Models that hold state").  A model served through
# ``DecodeEngine(model=...)`` declares one of them for every leaf, as a
# pytree of these strings shaped like its cache.
BLOCK_LEAF = "block"    # [num_blocks, block_size, ...]: a position lives
#                         where the slot's block table says (K/V, latents)
SLOT_LEAF = "slot"      # [num_slots, ...]: owned whole by the slot, not
#                         addressed by position (recurrent state)


def map_block_leaves(fn, cache, kinds, *rest):
    """``tree_map(fn, cache, *rest)`` over the block-addressed leaves
    alone; slot-addressed leaves come back as they are (block writes and
    copies have nothing to say about them).  ``kinds=None``: every leaf is
    block-addressed (the transformer trunk's K/V pools).  ``rest`` trees
    are shaped like ``cache``; their slot-addressed entries are ignored."""
    import jax
    if kinds is None:
        return jax.tree_util.tree_map(fn, cache, *rest)
    return jax.tree_util.tree_map(
        lambda kind, leaf, *r: fn(leaf, *r) if kind == BLOCK_LEAF else leaf,
        kinds, cache, *rest)


def leaf_bytes(cache, kinds, kind):
    """Bytes of the cache leaves that ``kinds`` declares of ``kind``."""
    import jax
    return sum(int(l.size) * l.dtype.itemsize
               for l, k in zip(jax.tree_util.tree_leaves(cache),
                               jax.tree_util.tree_leaves(kinds))
               if k == kind)


class WireFormatError(ValueError):
    """A chain blob violates the ``serialize_chain`` wire format
    (truncated, oversized, inconsistent manifest, foreign trunk).
    Subclasses ``ValueError`` so every existing rejection path — and
    test — keeps working; the network receiver catches THIS to count a
    rejected peer blob without masking programming errors."""


class WireVersionError(WireFormatError):
    """The blob's version byte (or header version field) is not the
    ``WIRE_VERSION`` this build speaks — an EXPLICIT mismatch, never a
    silent misparse: a newer peer's layout change lands here instead of
    inside the manifest parser."""


def slab_equivalent_blocks(num_slots, max_len, block_size,
                           kv_dtype="float32", mesh_shards=1):
    """Auto pool size (``DecodeEngine(kv_num_blocks=0)``) at the SLAB-
    EQUIVALENT **per-chip** byte budget: an f32 pool gets exactly the
    slab's ``num_slots * ceil(max_len / block_size)`` blocks (same KV
    bytes, strictly more packable).  ``kv_dtype="int8"`` DOUBLES the
    block count inside that same budget: an int8 block plus its f32
    per-(position, head) scale sidecar costs ``(1/4 + 1/head_dim)`` of
    the f32 block's bytes (quant/kv.kv_bytes_per_position), i.e. at
    most half for head_dim >= 4 — so twice the blocks still fit, with
    headroom that grows with head_dim.  ``mesh_shards=n`` (the sharded
    decode mesh, docs/serving.md "Sharded decode") MULTIPLIES by n: a
    chip holds only its ``Hkv/n`` head stripe of each block, so the
    single-chip per-chip budget holds n× the block count — the capacity
    win tensor-parallel serving exists for.  +1 everywhere for the
    reserved scratch block 0."""
    per_row = -(-int(max_len) // int(block_size))
    blocks = int(num_slots) * per_row
    if kv_dtype == "int8":
        blocks *= 2
    blocks *= max(1, int(mesh_shards))
    return blocks + 1


class InsufficientBlocksError(RuntimeError):
    """The pool cannot supply the requested blocks even after evicting
    every prefix-index entry.  Admission defers the request (it is NOT a
    client error); mid-decode the engine preempts a victim slot instead
    (``evictions{reason="pool_exhausted"}``)."""


class RestorePendingError(InsufficientBlocksError):
    """A host-tier restore covering this request's prefix is in flight:
    blocks are claimed and the payload is crossing the link, so seating
    now would recompute K/V the transfer is about to deliver.  Subclasses
    ``InsufficientBlocksError`` on purpose — every defer-and-retry seam
    (``_waiting`` / ``_preempted``) already treats that as "space, not
    failure", and the retry after the restore commits seats as an
    ordinary resident prefix hit."""


def serialize_chain(tokens, covered, arrays, trunk_sig):
    """Pack one prefix chain's K/V payload into a RELOCATABLE blob: the
    block-aligned prefix key (``tokens``), the positions it covers, and
    each cache leaf's gathered block rows (int8 payload + f32 scale
    sidecars on a quantized engine — spilled bytes stay ~halved) as raw
    bytes behind a JSON manifest.  Nothing in the blob references block
    IDS — restore lands the payload in whatever blocks the destination
    pool hands out, which is exactly what lets the same format cross
    replicas (ROADMAP item 2(b)).

    Layout: 1 version byte, 8-byte little-endian header length, the
    JSON header ``{version, trunk_sig, tokens, covered, arrays:
    [{name, dtype, shape}...]}``, then each array's contiguous bytes in
    manifest order.  ``trunk_sig`` fingerprints the producing engine's
    trunk (dims + layers + kv dtype + block size); ``restore_chain``
    rejects a mismatch — K/V bytes are only relocatable between
    identical trunks."""
    arrays = list(arrays)
    header = {
        "version": WIRE_VERSION,
        "trunk_sig": str(trunk_sig),
        "tokens": [int(t) for t in tokens],
        "covered": int(covered),
        "arrays": [{"name": str(n), "dtype": str(a.dtype),
                    "shape": [int(s) for s in a.shape]}
                   for n, a in arrays],
    }
    hdr = json.dumps(header, sort_keys=True).encode("utf-8")
    parts = [bytes([WIRE_VERSION]), len(hdr).to_bytes(8, "little"), hdr]
    for _n, a in arrays:
        parts.append(np.ascontiguousarray(a).tobytes())
    return b"".join(parts)


def peek_chain_header(blob, trunk_sig=None, max_bytes=None):
    """Parse and validate ONLY the blob's envelope — version byte,
    header length, JSON header, optional trunk-signature and size
    bound — without touching (or allocating for) the array payload.
    The network receiver (serving/transfer.py) calls this on received
    bytes BEFORE anything is staged, so a garbled peer is rejected at
    the manifest, never mid-``frombuffer``.  Returns the header dict.

    Raises ``WireVersionError`` on a version mismatch and
    ``WireFormatError`` on everything else (both ``ValueError``)."""
    if max_bytes is not None and len(blob) > int(max_bytes):
        raise WireFormatError(
            f"chain blob of {len(blob)} byte(s) exceeds the "
            f"{int(max_bytes)}-byte receive bound")
    if len(blob) < 9:
        raise WireFormatError(
            f"chain blob truncated: {len(blob)} byte(s)")
    if blob[0] != WIRE_VERSION:
        raise WireVersionError(f"chain blob version {blob[0]} != "
                               f"{WIRE_VERSION} (wire format mismatch)")
    hlen = int.from_bytes(blob[1:9], "little")
    if 9 + hlen > len(blob):
        raise WireFormatError("chain blob header overruns the payload")
    try:
        header = json.loads(blob[9:9 + hlen].decode("utf-8"))
    except (UnicodeDecodeError, json.JSONDecodeError) as e:
        raise WireFormatError(f"chain blob header is not valid JSON: "
                              f"{e}") from None
    if not isinstance(header, dict):
        raise WireFormatError("chain blob header is not a JSON object")
    if header.get("version") != WIRE_VERSION:
        raise WireVersionError(
            f"chain header version {header.get('version')} "
            f"!= {WIRE_VERSION}")
    if trunk_sig is not None and header.get("trunk_sig") != str(trunk_sig):
        raise WireFormatError(
            f"chain trunk signature {header.get('trunk_sig')!r} does not "
            f"match this engine's {str(trunk_sig)!r}: K/V bytes are only "
            "relocatable between identical trunks")
    return header


def restore_chain(blob, trunk_sig, max_bytes=None):
    """Inverse of ``serialize_chain``: returns ``(tokens_tuple,
    covered, [(name, ndarray), ...])``.  Raises ``WireVersionError`` on
    a version mismatch and ``WireFormatError`` (both ``ValueError``) on
    a trunk-signature mismatch or a truncated / oversized payload — a
    corrupt or foreign blob must never seat.  ``max_bytes`` bounds the
    whole blob BEFORE any manifest-driven decoding (the network-boundary
    defense; None = trusted local blob)."""
    header = peek_chain_header(blob, trunk_sig, max_bytes)
    hlen = int.from_bytes(blob[1:9], "little")
    off = 9 + hlen
    arrays = []
    for spec in header["arrays"]:
        try:
            dt = np.dtype(spec["dtype"])
            shape = tuple(int(s) for s in spec["shape"])
        except (TypeError, ValueError, KeyError) as e:
            raise WireFormatError(
                f"chain blob manifest is malformed: {e}") from None
        if any(s < 0 for s in shape):
            raise WireFormatError(
                f"chain blob array {spec.get('name')!r} declares a "
                "negative dimension")
        count = int(np.prod(shape, dtype=np.int64))
        nbytes = dt.itemsize * count
        if off + nbytes > len(blob):
            raise WireFormatError(f"chain blob truncated inside array "
                                  f"{spec['name']!r}")
        arrays.append((spec["name"],
                       np.frombuffer(blob, dt, count=count,
                                     offset=off).reshape(shape)))
        off += nbytes
    if off != len(blob):
        raise WireFormatError(f"chain blob holds {len(blob) - off} "
                              "trailing byte(s) past the manifest")
    return tuple(header["tokens"]), int(header["covered"]), arrays


class HostTier:
    """LRU host-RAM store of spilled prefix-chain blobs, byte-capped.

    The device-side ``PrefixIndex`` holds CHAINS (pool references); this
    tier holds their serialized PAYLOADS after eviction, keyed by the
    same block-aligned prefix keys, so the reusable-prefix working set
    is bounded by ``cap_bytes`` of host RAM instead of HBM.  LRU within
    the cap: ``put`` evicts the stalest blobs until the new one fits
    (spill-of-spill simply falls off the end — those prefixes recompute,
    exactly as they would with no tier).

    The tier also owns the bounded background transfer thread
    (``data/prefetch.TransferWorker``) restores run on: the engine
    submits a staging job (deserialize + per-block ``device_put``) and
    polls completions strictly BETWEEN decode steps, so the transfer
    overlaps compute and the donated cache is only ever written by the
    worker-thread seam.  All map state is lock-guarded — spills/probes
    happen on the batcher worker thread while ``/metrics`` reads the
    byte gauge from HTTP threads.
    """

    def __init__(self, cap_bytes=0, worker_depth=8):
        if int(cap_bytes) < 0:
            raise ConfigError(f"HostTier cap_bytes must be >= 0, got "
                              f"{cap_bytes}")
        self.cap_bytes = int(cap_bytes)
        self._lock = threading.Lock()
        self._entries = collections.OrderedDict()  # key -> (covered, blob)
        self._bytes = 0
        self._worker_depth = int(worker_depth)
        self._worker = None         # lazy: tests exercise put/lookup
        #                             without ever paying for a thread

    def __len__(self):
        with self._lock:
            return len(self._entries)

    @property
    def bytes(self):
        """Current resident payload bytes (the host_tier_bytes gauge)."""
        with self._lock:
            return self._bytes

    # ------------------------------------------------------------ store

    def put(self, key, covered, blob):
        """Insert (or refresh) one spilled chain; evicts LRU entries
        until the tier fits ``cap_bytes`` again.  Returns the number of
        entries evicted to make room.  Strict-prefix entries of ``key``
        are dropped — the new blob's payload supersets theirs, and
        ``lookup`` probes longest-first anyway."""
        key = tuple(int(t) for t in key)
        dropped = 0
        with self._lock:
            old = self._entries.pop(key, None)
            if old is not None:
                self._bytes -= len(old[1])
            for k in [k for k in self._entries
                      if len(k) < len(key) and key[:len(k)] == k]:
                _cov, shadowed = self._entries.pop(k)
                self._bytes -= len(shadowed)
            self._entries[key] = (int(covered), blob)
            self._bytes += len(blob)
            while self.cap_bytes and self._bytes > self.cap_bytes \
                    and self._entries:
                _k, (_cov, dropped_blob) = self._entries.popitem(
                    last=False)
                self._bytes -= len(dropped_blob)
                dropped += 1
        return dropped

    def pop(self, key):
        """Remove and return ``(covered, blob)`` for ``key``, or None."""
        with self._lock:
            ent = self._entries.pop(tuple(int(t) for t in key), None)
            if ent is not None:
                self._bytes -= len(ent[1])
            return ent

    def covers(self, key):
        """True if some stored entry's key EXTENDS ``key`` (equal or
        longer, same leading tokens) — its payload supersets what a
        spill of ``key`` would store, so that spill is redundant."""
        key = tuple(int(t) for t in key)
        n = len(key)
        with self._lock:
            return any(len(k) >= n and k[:n] == key
                       for k in self._entries)

    def lookup(self, tokens, block_size):
        """Longest spilled coverage of ``tokens`` — the host-tier twin
        of ``PrefixIndex.lookup``: the exact probe first, then
        block-aligned prefixes descending.  Returns ``(key, covered,
        blob)`` or ``(None, 0, None)``.  The hit is an LRU touch; the
        entry stays resident until the restore COMMITS (an in-flight
        job going stale across a reset must not lose the payload)."""
        bs = int(block_size)
        toks = tuple(int(t) for t in tokens)
        with self._lock:
            ent = self._entries.get(toks)
            if ent is not None:
                self._entries.move_to_end(toks)
                return toks, ent[0], ent[1]
            for m in range(len(toks) // bs, 0, -1):
                ent = self._entries.get(toks[:m * bs])
                if ent is not None:
                    self._entries.move_to_end(toks[:m * bs])
                    return toks[:m * bs], ent[0], ent[1]
        return None, 0, None

    def clear(self):
        with self._lock:
            self._entries.clear()
            self._bytes = 0

    # ------------------------------------------------------ transfer thread

    def submit(self, tag, fn):
        """Run ``fn`` on the tier's background transfer thread; the
        result arrives via ``poll()`` as ``(tag, result)``."""
        if self._worker is None:
            from paddle_tpu.data.prefetch import TransferWorker
            self._worker = TransferWorker(name="paddle-tpu-kv-restore",
                                          depth=self._worker_depth)
        self._worker.submit(tag, fn)

    def poll(self, timeout=0.0):
        """Next completed transfer job, or None.  The result may be a
        ``prefetch._Failure`` — the engine decides per-job fate (a
        failed restore falls back to recompute, never kills serving)."""
        if self._worker is None:
            return None
        return self._worker.poll(timeout=timeout)

    def close(self):
        if self._worker is not None:
            self._worker.close()
            self._worker = None


class BlockPool:
    """Free-list + refcount allocator over ``num_blocks`` KV blocks.

    ``alloc()`` hands out a block at refcount 1; ``share()`` adds a
    reference (a second slot chain or a prefix-index entry);
    ``release()`` drops one and returns the block to the free list at
    zero.  All host-side integers — the device arrays are the engine's.
    """

    def __init__(self, num_blocks, block_size):
        if num_blocks < 2:
            raise ConfigError("BlockPool needs num_blocks >= 2 (block 0 "
                              "is the reserved scratch block)")
        if block_size < 1:
            raise ConfigError("BlockPool needs block_size >= 1")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # pop() -> block 1 first; scratch block 0 is never allocatable
        self._free = list(range(self.num_blocks - 1, 0, -1))
        self._ref = np.zeros((self.num_blocks,), np.int64)

    @property
    def num_allocatable(self):
        return self.num_blocks - 1

    @property
    def num_free(self):
        return len(self._free)

    @property
    def num_used(self):
        return self.num_allocatable - len(self._free)

    def refcount(self, bid):
        return int(self._ref[bid])

    def alloc(self):
        """One free block at refcount 1, or None when the pool is dry
        (callers then evict prefix-index entries / preempt a slot)."""
        if not self._free:
            return None
        bid = self._free.pop()
        self._ref[bid] = 1
        return bid

    def share(self, bid):
        if self._ref[bid] < 1:
            raise RuntimeError(f"BlockPool.share of unowned block {bid}")
        self._ref[bid] += 1
        return bid

    def release(self, bid):
        if self._ref[bid] < 1:
            raise RuntimeError(f"BlockPool.release of free block {bid} "
                               "(double free)")
        self._ref[bid] -= 1
        if self._ref[bid] == 0:
            self._free.append(bid)

    def check(self):
        """Internal-consistency invariants: the free list and the
        refcounts partition the allocatable ids exactly.  Raises on any
        violation (leak or double-free would break one)."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise AssertionError("free list holds duplicates: "
                                 f"{sorted(self._free)}")
        if SCRATCH_BLOCK in free or self._ref[SCRATCH_BLOCK] != 0:
            raise AssertionError("scratch block 0 entered the allocator")
        held = {int(b) for b in np.nonzero(self._ref)[0]}
        if free & held:
            raise AssertionError(f"blocks both free and referenced: "
                                 f"{sorted(free & held)}")
        if len(free) + len(held) != self.num_allocatable:
            raise AssertionError(
                f"leaked blocks: {self.num_allocatable} allocatable != "
                f"{len(free)} free + {len(held)} held")


class PrefixIndex:
    """Prompt prefix -> resident block chain, LRU.

    Two key kinds share one map: every BLOCK-ALIGNED prefix of a
    registered prompt (token tuples of length ``k * block_size`` —
    reusable by any prompt sharing those leading blocks), plus the EXACT
    full prompt when its tail block is partial (reusable by EXACT
    duplicates only — ``lookup`` probes the exact key and block-aligned
    prefixes, so a LONGER probe sharing this prompt matches just the
    aligned portion — the seat then lands INSIDE the shared tail block
    and the first write copy-on-write forks it).  An entry holds ONE
    pool reference per block, so the
    chain outlives the slot that prefilled it.  ``lookup`` returns the
    LONGEST registered coverage of the probe (and refreshes its LRU
    position); ``evict_lru`` releases the stalest entry's references —
    the blocks actually free only once no slot chain shares them.
    """

    def __init__(self, pool, on_evict=None):
        self._pool = pool
        # spill hook (the engine's D2H gather + HostTier.put): called
        # with (key, covered, [bids]) BEFORE the references release —
        # the block contents must be read while still owned
        self._on_evict = on_evict
        self._entries = collections.OrderedDict()  # key -> (covered, [bids])

    def __len__(self):
        return len(self._entries)

    @property
    def block_refs(self):
        """Total (entry, block) references the index holds — the ledger
        term ``PagedKVState.check`` audits."""
        return sum(len(c) for _cov, c in self._entries.values())

    def _add(self, key, covered, blocks):
        if key in self._entries:
            # existing entries win — their blocks hold identical K/V by
            # determinism, and keeping them preserves their sharers
            self._entries.move_to_end(key)
            return
        self._entries[key] = (covered, [self._pool.share(b)
                                        for b in blocks])

    def register(self, tokens, chain):
        """Publish ``tokens`` (the real prefix of a just-admitted
        prompt, whose K/V ``chain`` holds): every block-aligned prefix,
        plus the exact full key when the tail block is partial."""
        bs = self._pool.block_size
        toks = tuple(int(t) for t in tokens)
        for m in range(1, len(toks) // bs + 1):
            self._add(toks[:m * bs], m * bs, chain[:m])
        if len(toks) % bs:
            self._add(toks, len(toks),
                      chain[:-(-len(toks) // bs)])

    def lookup(self, tokens):
        """Longest registered coverage of ``tokens``: the exact probe
        first (duplicate prompt — covers its partial tail too), then
        block-aligned prefixes descending.  Returns
        ``(covered_positions, [bids])`` or ``(0, [])``.  The hit is an
        LRU touch; references are NOT taken here — seating does."""
        bs = self._pool.block_size
        toks = tuple(int(t) for t in tokens)
        ent = self._entries.get(toks)
        if ent is not None:
            self._entries.move_to_end(toks)
            return ent[0], list(ent[1])
        for m in range(len(toks) // bs, 0, -1):
            ent = self._entries.get(toks[:m * bs])
            if ent is not None:
                self._entries.move_to_end(toks[:m * bs])
                return ent[0], list(ent[1])
        return 0, []

    def evict_lru(self):
        """Release the stalest entry's block references; True if one was
        evicted.  With a spill hook installed, the entry's key/coverage/
        chain are handed to it FIRST (the hook gathers the device bytes
        into the host tier) — a hook failure only loses the spill, never
        the eviction, so pool pressure always makes progress."""
        if not self._entries:
            return False
        key, (cov, chain) = self._entries.popitem(last=False)
        if self._on_evict is not None:
            try:
                self._on_evict(key, cov, list(chain))
            except Exception as e:  # noqa: BLE001 — a spill failure
                # must never wedge the allocator under pressure
                logger.warning("prefix spill of %d block(s) failed: "
                               "%s: %s", len(chain), type(e).__name__, e)
        for bid in chain:
            self._pool.release(bid)
        return True

    def clear(self):
        while self.evict_lru():
            pass


class PagedKVState:
    """Host bookkeeping for one paged ``DecodeEngine``: pool + prefix
    index + per-slot block tables/chains + the write-exclusivity plan.

    The engine owns every device operation (the jitted step, block
    write, block copy); this object only decides WHICH blocks — methods
    that need a device copy return the plan and the engine executes it.
    """

    def __init__(self, num_slots, num_blocks, block_size, max_len,
                 prefix_cache=True, on_evict=None):
        self.pool = BlockPool(num_blocks, block_size)
        self.index = PrefixIndex(self.pool, on_evict=on_evict) \
            if prefix_cache else None
        self.block_size = self.pool.block_size
        self.blocks_per_row = -(-int(max_len) // self.block_size)
        self.tables = np.zeros((int(num_slots), self.blocks_per_row),
                               np.int32)
        self._chains = [[] for _ in range(int(num_slots))]
        # host-tier restores in flight: prefix key -> [bids] claimed
        # ahead of the async transfer (refs held here so the pool can
        # never hand them out twice; committed into the index — or
        # released — when the restore lands or dies)
        self._pending = {}
        # admission order, for pool-pressure victim choice (youngest
        # first: cheapest replay, most blocks still ahead of it)
        self._seat_seq = np.zeros((int(num_slots),), np.int64)
        self._seq = 0

    # ------------------------------------------------------------ sizing

    def blocks_for(self, n_positions):
        return -(-int(n_positions) // self.block_size)

    def can_admit(self, n_positions):
        """Could ``blocks_for(n_positions)`` blocks be produced right
        now (free list + whatever evicting the whole prefix index would
        release)?  Conservative: index blocks shared by live slots are
        counted as unevictable."""
        need = self.blocks_for(n_positions)
        free = self.pool.num_free
        if free >= need:
            return True
        if self.index is None:
            return False
        live = {b for c in self._chains for b in c}
        evictable = {b for _cov, chain in self.index._entries.values()
                     for b in chain
                     if b not in live and self.pool.refcount(b) >= 1}
        return free + len(evictable) >= need

    def _alloc(self):
        """One block, evicting LRU prefix entries under pressure;
        None when truly dry (the caller preempts a slot)."""
        bid = self.pool.alloc()
        while bid is None and self.index is not None \
                and self.index.evict_lru():
            bid = self.pool.alloc()
        return bid

    # ------------------------------------------------------------ seating

    def seat_fresh(self, slot, n_positions):
        """Claim private blocks covering ``[0, n_positions)`` for a
        fresh admission (0: an empty chain ``write_plan`` grows as the
        span advances); returns the chain.  All-or-nothing: on exhaustion
        nothing is claimed and ``InsufficientBlocksError`` raises (the
        batcher defers the request)."""
        need = self.blocks_for(n_positions)
        chain = []
        for _ in range(need):
            bid = self._alloc()
            if bid is None:
                for b in chain:
                    self.pool.release(b)
                raise InsufficientBlocksError(
                    f"pool dry: {need} block(s) wanted, "
                    f"{self.pool.num_free} free")
            chain.append(bid)
        self._install(slot, chain)
        obstrace.instant("kv.seat", slot=slot, blocks=len(chain),
                         free=self.pool.num_free)
        return chain

    def seat_shared(self, slot, chain, n_positions):
        """Seat a prefix-cache hit: take shared references on
        ``chain[:blocks_for(n_positions)]`` — no prefill, no copy; the
        first divergent write triggers the copy-on-write fork in
        ``write_plan``."""
        take = [self.pool.share(b)
                for b in chain[:self.blocks_for(n_positions)]]
        self._install(slot, take)
        obstrace.instant("kv.seat_shared", slot=slot, blocks=len(take),
                         free=self.pool.num_free)
        return take

    def _install(self, slot, chain):
        if self._chains[slot]:
            raise RuntimeError(f"slot {slot} already holds a chain")
        self._chains[slot] = chain
        self.tables[slot, :len(chain)] = chain
        self._seq += 1
        self._seat_seq[slot] = self._seq

    def register_prefix(self, tokens, slot):
        """Publish the seated slot's full-block prompt prefixes into the
        index (no-op with the prefix cache off)."""
        if self.index is not None:
            self.index.register(tokens, self._chains[slot])

    def lookup_prefix(self, tokens):
        if self.index is None:
            return 0, []
        return self.index.lookup(tokens)

    # ------------------------------------------------------ host-tier restore

    def claim_pending(self, key, n_positions):
        """Claim ``blocks_for(n_positions)`` fresh blocks for an async
        host-tier restore of prefix ``key`` — held in the pending ledger
        (refcount 1, outside every slot chain) until the transfer lands.
        All-or-nothing like ``seat_fresh``; raises
        ``InsufficientBlocksError`` leaving nothing claimed."""
        key = tuple(int(t) for t in key)
        if key in self._pending:
            raise RuntimeError(f"restore of {len(key)}-token prefix "
                               "already in flight")
        need = self.blocks_for(n_positions)
        chain = []
        for _ in range(need):
            bid = self._alloc()
            if bid is None:
                for b in chain:
                    self.pool.release(b)
                raise InsufficientBlocksError(
                    f"pool dry claiming {need} block(s) for a host-tier "
                    f"restore ({self.pool.num_free} free)")
            chain.append(bid)
        self._pending[key] = chain
        obstrace.instant("kv.restore_claim", blocks=len(chain),
                         free=self.pool.num_free)
        return list(chain)

    def release_pending(self, key):
        """Drop a claim whose restore died (job failure or a stale
        epoch that was caught before the state was replaced)."""
        chain = self._pending.pop(tuple(int(t) for t in key), None)
        if chain:
            for bid in chain:
                self.pool.release(bid)

    def commit_pending(self, key, covered):
        """The restore landed (the engine wrote every staged chunk into
        the claimed blocks): publish the chain into the prefix index —
        the entry takes its own references, exactly like a chain a slot
        registered — and drop the pending claim.  If the key was
        recomputed into the index while the transfer flew, the existing
        entry wins (identical K/V by determinism) and the restored
        blocks simply free."""
        key = tuple(int(t) for t in key)
        chain = self._pending.pop(key)
        if self.index is not None:
            self.index._add(key, int(covered), chain)
        for bid in chain:
            self.pool.release(bid)

    # ------------------------------------------------------------ stepping

    def write_plan(self, slot, position):
        """Make ``position`` writable for ``slot`` before the next step.
        Returns None (already exclusive), ``("alloc", j, bid)`` (chain
        grew into a fresh block), or ``("cow", j, src, dst)`` — the
        engine must device-copy block ``src`` into ``dst`` (the
        copy-on-write fork; ``src`` stays resident for its other
        sharers).  Raises ``InsufficientBlocksError`` when the pool is
        dry — the engine preempts a victim slot and retries."""
        j = position // self.block_size
        chain = self._chains[slot]
        if j > len(chain):
            raise RuntimeError(
                f"slot {slot} chain has {len(chain)} block(s) but writes "
                f"block {j}: positions were skipped")
        if j == len(chain):
            bid = self._alloc()
            if bid is None:
                raise InsufficientBlocksError(
                    f"pool dry growing slot {slot} to block {j}")
            chain.append(bid)
            self.tables[slot, j] = bid
            return ("alloc", j, bid)
        src = chain[j]
        if self.pool.refcount(src) == 1:
            return None
        dst = self._alloc()
        if self.pool.refcount(src) == 1:
            # _alloc's LRU evictions dropped the last OTHER reference
            # (the sharer was the index): the block is exclusive after
            # all — no fork, and a request sized to fit the pool alone
            # never dies here
            if dst is not None:
                self.pool.release(dst)
            return None
        if dst is None:
            raise InsufficientBlocksError(
                f"pool dry forking shared block {src} for slot {slot}")
        self.pool.release(src)      # our reference moves to the fork
        chain[j] = dst
        self.tables[slot, j] = dst
        return ("cow", j, src, dst)

    def truncate(self, slot, n_positions):
        """Roll back ``slot``'s chain to the blocks covering
        ``[0, n_positions)`` — the speculative-decoding rejection path
        (docs/serving.md "Speculative decoding"): ``prepare_step``
        provisioned blocks for the whole drafted span before the verify
        step, but acceptance committed fewer positions, so the tail
        blocks past the committed span release back to the pool.  Their
        contents need no scrubbing: the attention mask stops at each
        lane's own position, and a later write into those positions
        re-provisions a block and overwrites it in the same step that
        first unmasks it.  A shared tail block (possible when a prefix
        seat over-covered) only drops this slot's reference.  Returns
        the number of blocks released."""
        keep = self.blocks_for(n_positions)
        chain = self._chains[slot]
        dropped = 0
        while len(chain) > keep:
            bid = chain.pop()
            self.tables[slot, len(chain)] = SCRATCH_BLOCK
            self.pool.release(bid)
            dropped += 1
        if dropped:
            obstrace.instant("kv.truncate", slot=slot, blocks=dropped,
                             free=self.pool.num_free)
        return dropped

    def victim(self, exclude):
        """Youngest active slot outside ``exclude`` (pool-pressure
        preemption order), or None."""
        best, best_seq = None, -1
        for s, chain in enumerate(self._chains):
            if chain and s not in exclude \
                    and self._seat_seq[s] > best_seq:
                best, best_seq = s, self._seat_seq[s]
        return best

    # ------------------------------------------------------------ teardown

    def evict(self, slot):
        """Release the slot's chain (shared blocks stay resident for
        their other sharers / the index) and zero its table row."""
        for bid in self._chains[slot]:
            self.pool.release(bid)
        self._chains[slot] = []
        self.tables[slot, :] = SCRATCH_BLOCK

    def check(self):
        """Full ledger audit: every block's refcount equals the number
        of slot-chain plus index references to it (no leak, no double
        count), and the pool's own free/held partition holds."""
        self.pool.check()
        expect = collections.Counter()
        for chain in self._chains:
            expect.update(chain)
        for chain in self._pending.values():
            expect.update(chain)
        if self.index is not None:
            for _cov, chain in self.index._entries.values():
                expect.update(chain)
        for bid in range(1, self.pool.num_blocks):
            if self.pool.refcount(bid) != expect.get(bid, 0):
                raise AssertionError(
                    f"block {bid}: refcount {self.pool.refcount(bid)} != "
                    f"{expect.get(bid, 0)} ledger references")

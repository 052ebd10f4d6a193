"""Hierarchical KV cache (serving/kv_pool.py HostTier + DecodeEngine
kv_host_bytes; docs/serving.md "Hierarchical KV").

When the paged pool evicts a prefix chain under pressure, the chain's
payload spills to a byte-capped LRU host-RAM tier as a RELOCATABLE blob
(``serialize_chain`` — the ROADMAP item 2(b) wire format); the next
prompt covered by that prefix restores it asynchronously (claim fresh
blocks -> transfer-thread staging -> between-steps commit) and seats by
reference exactly like a resident hit.  The correctness bar is the
paged layout's own: greedy streams BIT-IDENTICAL to the tier-less
twin's cold recompute, ZERO prefill chunk lanes for a fully covered
return visit, ONE warm-up trace and zero retraces through the whole
spill/restore churn, and a balanced refcount ledger (including the
pending-restore claims) after every scenario.  A PR-6 ``reset()``
racing an in-flight restore must drop the stale landing (epoch guard)
while the blob survives for the next probe.
"""

import threading
import time

import numpy as np
import pytest
import jax

from paddle_tpu.models import transformer
from paddle_tpu.resilience import Supervisor, faults
from paddle_tpu.serving import GenerationBatcher, ServingMetrics
from paddle_tpu.serving.decode_engine import DecodeEngine
from paddle_tpu.serving.kv_pool import (HostTier, RestorePendingError,
                                        WIRE_VERSION, restore_chain,
                                        serialize_chain)
from paddle_tpu.testing import assert_no_retrace
from paddle_tpu.utils.error import ConfigError

VOCAB, D_MODEL, LAYERS, HEADS = 64, 32, 2, 2
MAX_LEN, SLOTS, BS, CHUNK = 48, 4, 8, 8
# two slots' worth of blocks + scratch: churn traffic evicts the shared
# chain deterministically
POOL_BLOCKS = 2 * (MAX_LEN // BS) + 1
SIG = f"L{LAYERS}.d{D_MODEL}.dkv{D_MODEL // HEADS}.h{HEADS}.float32.b{BS}"


@pytest.fixture(autouse=True)
def _no_leaked_fault_plan():
    yield
    faults.clear()


@pytest.fixture(scope="module")
def params():
    return transformer.init(jax.random.PRNGKey(0), src_vocab=VOCAB,
                            trg_vocab=1, d_model=D_MODEL, num_heads=HEADS,
                            dff=64, enc_layers=LAYERS, dec_layers=0,
                            max_len=MAX_LEN)


@pytest.fixture(scope="module")
def spill_eng(params):
    """Tiny-pool chunked paged engine with the host tier attached."""
    return DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                        max_len=MAX_LEN, name="spill_lm", kv_layout="paged",
                        kv_block_size=BS, kv_num_blocks=POOL_BLOCKS,
                        prefill_chunk=CHUNK, kv_host_bytes=64 << 20)


@pytest.fixture(scope="module")
def twin_eng(params):
    """The cold-recompute twin: same trunk, same tiny pool, no tier."""
    return DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                        max_len=MAX_LEN, name="spill_twin", kv_layout="paged",
                        kv_block_size=BS, kv_num_blocks=POOL_BLOCKS,
                        prefill_chunk=CHUNK)


def _fresh(eng):
    """Reset one of the module engines to a clean scenario baseline."""
    eng.reset()
    if eng.host_tier is not None:
        eng.host_tier.clear()
    eng.metrics = ServingMetrics()
    return eng


def _prompt(rng, n):
    return rng.randint(1, VOCAB, n).astype(np.int32)


def _churn_out(eng, bat, rng, shared, rounds=4):
    """Admit fresh traffic until the shared chain is no longer resident
    (evicted => spilled on a tier engine)."""
    for _ in range(rounds):
        bat.submit(_prompt(rng, 28), max_tokens=4).result(60)
    assert eng._paged.lookup_prefix(shared)[0] == 0, \
        "churn failed to evict the shared chain"


def _arrays(rng, blocks=3):
    return [("k0", rng.standard_normal((blocks, BS, 16))
             .astype(np.float32)),
            ("v0", rng.standard_normal((blocks, BS, 16))
             .astype(np.float32)),
            ("scale", rng.standard_normal((blocks, BS, HEADS))
             .astype(np.float32))]


# ------------------------------------------------------- wire format


def test_wire_format_round_trip_property():
    """serialize -> restore is the identity on (tokens, covered,
    arrays) across random shapes/dtypes — the relocatable-blob property
    the cross-replica handoff (ROADMAP item 2(b)) relies on."""
    rng = np.random.default_rng(0)
    for trial in range(8):
        n_blocks = int(rng.integers(1, 5))
        tokens = [int(t) for t in rng.integers(1, VOCAB, n_blocks * BS)]
        arrays = [(f"leaf{i}",
                   (rng.standard_normal(
                       (n_blocks, BS, int(rng.integers(1, 9))))
                    * 8).astype(dt))
                  for i, dt in enumerate(
                      [np.float32, np.int8, np.float32][:int(
                          rng.integers(1, 4))])]
        blob = serialize_chain(tokens, n_blocks * BS, arrays, SIG)
        assert blob[0] == WIRE_VERSION
        toks, covered, out = restore_chain(blob, SIG)
        assert toks == tuple(tokens) and covered == n_blocks * BS
        assert [n for n, _ in out] == [n for n, _ in arrays], trial
        for (_, a), (_, b) in zip(arrays, out):
            assert a.dtype == b.dtype and a.shape == b.shape
            np.testing.assert_array_equal(a, b)


def test_wire_format_rejects_foreign_and_corrupt_blobs():
    rng = np.random.default_rng(1)
    tokens = [int(t) for t in rng.integers(1, VOCAB, BS)]
    blob = serialize_chain(tokens, BS, _arrays(rng, 1), SIG)
    # trunk-signature mismatch: K/V bytes only relocate between twins
    with pytest.raises(ValueError, match="trunk signature"):
        restore_chain(blob, SIG.replace(f"L{LAYERS}", f"L{LAYERS + 1}"))
    # version-byte mismatch
    with pytest.raises(ValueError, match="version"):
        restore_chain(bytes([WIRE_VERSION + 1]) + blob[1:], SIG)
    # truncation (inside the payload) and trailing garbage
    with pytest.raises(ValueError, match="truncated"):
        restore_chain(blob[:-3], SIG)
    with pytest.raises(ValueError, match="trailing"):
        restore_chain(blob + b"xx", SIG)
    with pytest.raises(ValueError, match="truncated"):
        restore_chain(b"\x01\x00", SIG)


# --------------------------------------------------------- host tier


def test_host_tier_lru_cap_lookup_and_covers():
    rng = np.random.default_rng(2)
    blob = serialize_chain([1] * BS, BS, _arrays(rng, 1), SIG)
    tier = HostTier(cap_bytes=int(len(blob) * 3.5))
    t1, t2 = tuple(range(1, BS + 1)), tuple(range(101, 101 + BS))
    assert tier.put(t1, BS, blob) == 0
    assert tier.put(t2, BS, blob) == 0
    assert len(tier) == 2 and tier.bytes == 2 * len(blob)
    # block-aligned descending lookup: a longer query finds the prefix
    key, covered, got = tier.lookup(list(t1) + [7, 8, 9], BS)
    assert key == t1 and covered == BS and got == blob
    assert tier.lookup([9] * BS, BS) == (None, 0, None)
    # covers(): equal-or-longer stored key supersets the probe
    long_key = t1 + tuple(range(51, 51 + BS))
    tier.put(long_key, 2 * BS, blob + blob[9:])
    assert tier.covers(t1) and tier.covers(long_key)
    assert not tier.covers(t2 + (1,))
    # the strict-prefix entry was dropped as superseded by long_key
    assert tier.lookup(list(t1), BS) == (None, 0, None)
    # LRU byte cap: t2 (stalest) falls off when the next put overflows
    dropped = tier.put(tuple(range(201, 201 + BS)), BS, blob)
    assert dropped >= 1 and tier.bytes <= tier.cap_bytes
    assert tier.lookup(list(t2), BS) == (None, 0, None)
    assert tier.pop(long_key) is not None
    tier.clear()
    assert len(tier) == 0 and tier.bytes == 0


def test_engine_config_validation(params):
    for kw, match in (
            (dict(kv_layout="slab", kv_host_bytes=1), "paged"),
            (dict(kv_layout="paged", prefix_cache=False,
                  kv_host_bytes=1), "prefix"),
            (dict(kv_layout="paged", kv_host_bytes=-1), ">= 0"),
    ):
        with pytest.raises(ConfigError, match=match):
            DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                         max_len=MAX_LEN, name="bad_spill", kv_block_size=BS,
                         prefill_chunk=CHUNK, warm=False, **kw)


def test_restore_vs_recompute_routing_directions(params):
    """The analytic router (perf/analytic.predicted_restore_ms vs
    predicted_recompute_ms, consulted at seat time) must favor RESTORE
    for a multi-block prefix and RECOMPUTE for a sub-chunk one."""
    eng = DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                       max_len=MAX_LEN, name="route_lm", kv_layout="paged",
                       kv_block_size=BS, prefill_chunk=CHUNK,
                       kv_host_bytes=1 << 20, warm=False)
    long_v, long_r, long_c = eng._restore_predicted_faster(4 * BS)
    short_v, short_r, short_c = eng._restore_predicted_faster(CHUNK // 2)
    assert long_v and long_r < long_c, (long_r, long_c)
    assert not short_v and short_c < short_r, (short_r, short_c)


# ------------------------------------------------- spill -> restore


def _audit(eng):
    eng._paged.check()
    assert eng.free_slots == eng.num_slots
    assert not eng._paged._pending, "leaked pending restore claims"


def test_spill_restore_bit_identical_zero_lanes_one_trace(
        params, spill_eng, twin_eng):
    """The tentpole scenario end-to-end: a block-aligned shared prefix
    is registered, churn evicts (and spills) it, and its return visit
    restore-hits — seating by reference with ZERO prefill chunk lanes,
    the stream bit-identical both to its own first serving and to the
    tier-less twin's cold recompute, with no trace past warm-up and a
    balanced ledger."""
    eng, twin = _fresh(spill_eng), _fresh(twin_eng)
    rng = np.random.RandomState(3)
    shared = _prompt(rng, 4 * BS)
    with assert_no_retrace(
            lambda: eng.step_trace_count + eng._write_traces[0]
            + eng._copy_traces[0], "spill/restore churn"):
        bat = GenerationBatcher(eng)
        r1 = bat.submit(shared, max_tokens=6).result(60)
        _churn_out(eng, bat, rng, shared)
        snap = eng.metrics.snapshot()
        assert snap["kv_spill_blocks_total"] > 0, "eviction never spilled"
        assert eng.host_tier.covers(tuple(int(t) for t in shared))
        lanes0 = snap["prefill_chunk_lanes_total"]
        r2 = bat.submit(shared, max_tokens=6).result(60)
        bat.close()
    snap = eng.metrics.snapshot()
    assert snap["kv_restore_hits_total"] == 1, snap
    assert snap["kv_restore_bytes_total"] > 0
    assert snap["kv_restore_ms"]["p50"] > 0
    assert snap["host_tier_bytes"] == eng.host_tier.bytes
    # the covered return visit consumed NO chunk lanes: the restored
    # chain seated by reference, not through prefill
    assert snap["prefill_chunk_lanes_total"] == lanes0, snap
    tbat = GenerationBatcher(twin)
    t1 = tbat.submit(shared, max_tokens=6).result(60)
    tbat.close()
    assert r2["tokens"] == r1["tokens"] == t1["tokens"]
    assert eng.step_trace_count == 1
    _audit(eng)


def test_reset_races_inflight_restore_epoch_guard(params, spill_eng):
    """PR-6 supervisor recovery racing an in-flight restore: the reset
    bumps the epoch and replaces the paged state, so the staged landing
    must be DROPPED (never seated into the fresh pool) — while the blob
    stays resident in the tier, and the next visit restore-hits and
    streams bit-identically."""
    eng = _fresh(spill_eng)
    rng = np.random.RandomState(4)
    shared = _prompt(rng, 4 * BS)
    bat = GenerationBatcher(eng)
    r1 = bat.submit(shared, max_tokens=6).result(60)
    _churn_out(eng, bat, rng, shared)
    bat.close()
    # begin a restore by hand (no batcher: the worker thread must not
    # race the claim), then reset while the transfer is in flight
    pending = eng._maybe_begin_restore(shared)
    assert isinstance(pending, RestorePendingError)
    assert eng._paged._pending, "restore claimed no blocks"
    eng.reset()
    assert not eng._pending_restores   # reset cleared the marker
    assert not eng._paged._pending     # claim died with the old state
    # give the worker time to stage the orphaned job; its completion
    # must land NOTHING in the fresh pool (no marker -> early-out)
    time.sleep(0.3)
    assert eng.poll_restores(timeout=0.05) == 0
    assert len(eng._paged.index) == 0
    assert eng.metrics.snapshot()["kv_restore_hits_total"] == 0
    eng._paged.check()
    # the blob survived the reset: the next visit restores (the stale
    # completion drains benignly — identical payload, same key) and
    # the stream still matches the pre-reset serving
    bat = GenerationBatcher(eng)
    r2 = bat.submit(shared, max_tokens=6).result(60)
    bat.close()
    assert r2["tokens"] == r1["tokens"]
    assert eng.metrics.snapshot()["kv_restore_hits_total"] == 1
    _audit(eng)


# ------------------------------------------------------- slow lane


@pytest.mark.slow
def test_cow_fork_on_restored_chain_bit_identical(params, spill_eng,
                                                  twin_eng):
    """A restored chain is a first-class prefix-cache entry: an exact
    duplicate (CoW fork in the shared tail) and a divergent follower
    both seat on it by reference, every stream bit-identical to the
    tier-less twin."""
    eng, twin = _fresh(spill_eng), _fresh(twin_eng)
    rng = np.random.RandomState(5)
    shared = _prompt(rng, 4 * BS)
    q = _prompt(rng, 4)
    cases = [(shared, 6), (shared, 6),
             (np.concatenate([shared, q]), 6)]
    bat = GenerationBatcher(eng)
    bat.submit(shared, max_tokens=6).result(60)      # register
    _churn_out(eng, bat, rng, shared)
    outs = [bat.submit(p, max_tokens=n).result(60)["tokens"]
            for p, n in cases]
    bat.close()
    snap = eng.metrics.snapshot()
    assert snap["kv_restore_hits_total"] >= 1, snap
    assert snap["cow_forks_total"] >= 1, snap
    tbat = GenerationBatcher(twin)
    ref = [tbat.submit(p, max_tokens=n).result(60)["tokens"]
           for p, n in cases]
    tbat.close()
    assert outs == ref
    _audit(eng)


@pytest.mark.slow
@pytest.mark.parametrize("seed", [11, 12, 13])
def test_spill_storm_staggered_admissions_bit_identical(params, seed):
    """Pool-exhaustion spill storm: staggered concurrent clients with a
    recurring shared prefix over a pool too small to hold everyone —
    evictions spill, returns restore, preemptions ride the existing
    defer seams — and EVERY stream must match the tier-less twin token
    for token with a balanced ledger at the end."""
    def build(name, host_bytes):
        return DecodeEngine(
            transformer.init(jax.random.PRNGKey(0), src_vocab=VOCAB,
                             trg_vocab=1, d_model=D_MODEL,
                             num_heads=HEADS, dff=64, enc_layers=LAYERS,
                             dec_layers=0, max_len=MAX_LEN),
            num_heads=HEADS, num_slots=SLOTS, max_len=MAX_LEN, name=name, kv_layout="paged",
            kv_block_size=BS, kv_num_blocks=POOL_BLOCKS,
            prefill_chunk=CHUNK, kv_host_bytes=host_bytes)

    eng, twin = build(f"storm_{seed}", 64 << 20), build(
        f"storm_twin_{seed}", 0)
    rng = np.random.RandomState(seed)
    shared = _prompt(rng, 4 * BS)
    cases = []
    for i in range(14):
        if i % 3 == 0:
            cases.append((shared, 5))
        else:
            cases.append((_prompt(rng, int(rng.randint(20, 33))),
                          4 + i % 4))

    def drive(engine):
        bat = GenerationBatcher(engine, queue_size=256)
        results = [None] * len(cases)
        excs = []

        def client(i):
            try:
                time.sleep(0.004 * i)
                results[i] = bat.submit(
                    cases[i][0], max_tokens=cases[i][1]).result(120)
            except Exception as e:      # noqa: BLE001
                excs.append((i, e))

        ts = [threading.Thread(target=client, args=(i,))
              for i in range(len(cases))]
        for t in ts:
            t.start()
        for t in ts:
            t.join(180)
            assert not t.is_alive(), "client wedged: DEADLOCK"
        bat.close()
        assert not excs, excs
        return [r["tokens"] for r in results]

    got, ref = drive(eng), drive(twin)
    assert got == ref
    assert eng.metrics.snapshot()["kv_spill_blocks_total"] > 0
    assert eng.step_trace_count == 1
    _audit(eng)


@pytest.mark.slow
def test_supervisor_chaos_with_tier_bit_identical(params, spill_eng,
                                                  twin_eng):
    """The PR-6 fault matrix on a tier engine: an injected decode-step
    fault mid-storm rebuilds the pool; the tier (and any spilled
    payloads) survives the reset, recovery re-seats every stream, and
    all outputs still match the twin."""
    eng, twin = _fresh(spill_eng), _fresh(twin_eng)
    rng = np.random.RandomState(9)
    shared = _prompt(rng, 4 * BS)
    cases = [(shared, 6)] + [(_prompt(rng, 28), 5) for _ in range(4)] \
        + [(shared, 6)]
    faults.install_spec("serving.decode_step:at=7")
    sup = Supervisor(breaker_threshold=10)
    bat = GenerationBatcher(eng, supervisor=sup)
    outs = [bat.submit(p, max_tokens=n).result(120)["tokens"]
            for p, n in cases]
    bat.close()
    assert faults.fired_counts() == {"serving.decode_step": 1}
    faults.clear()
    tbat = GenerationBatcher(twin)
    ref = [tbat.submit(p, max_tokens=n).result(120)["tokens"]
           for p, n in cases]
    tbat.close()
    assert outs == ref
    assert eng.metrics.snapshot()["evictions"]["recovered"] >= 1
    _audit(eng)

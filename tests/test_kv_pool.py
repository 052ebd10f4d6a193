"""Paged KV cache (serving/kv_pool.py + DecodeEngine kv_layout="paged").

The correctness bar is the slab's own: every greedy stream served
through the paged layout — chunked ingestion, prefix-cache seating,
copy-on-write forks, pool-pressure preemption and re-seat, supervisor
recovery, continuation replay — must be BIT-IDENTICAL to the
single-request oracle (``models/transformer.lm_generate``) and hence to
the slab layout.  Trace discipline: ONE warm-up trace for the paged
step (plus one block-fork executable), ZERO traces across any
block-table churn — the table is data, not shape.

The allocator's refcount ledger (``PagedKVState.check``: every block's
refcount equals its slot-chain + prefix-index references; the free list
and refcounts partition the pool exactly) is audited after every
scenario here, including a chaos run through the PR-6 fault points —
no leak, no double-free.
"""

import threading
import time

import numpy as np
import pytest
import jax

from paddle_tpu.models import transformer
from paddle_tpu.resilience import Supervisor, faults
from paddle_tpu.serving import (GenerationBatcher, InvalidRequestError,
                                ServingMetrics)
from paddle_tpu.serving.decode_engine import DecodeEngine
from paddle_tpu.serving.kv_pool import (SCRATCH_BLOCK, BlockPool,
                                        InsufficientBlocksError,
                                        PagedKVState, PrefixIndex)
from paddle_tpu.testing import assert_no_retrace
from paddle_tpu.utils.error import ConfigError

VOCAB, D_MODEL, LAYERS, HEADS = 64, 32, 2, 2
MAX_LEN, SLOTS, PROMPT_TOP, BS = 48, 4, 16, 8


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
def engine(params):
    """Auto-sized pool (the slab-equivalent byte budget), prefix cache
    on — the default paged configuration."""
    return DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                        max_len=MAX_LEN, name="paged_lm", kv_layout="paged",
                        kv_block_size=BS)


def _prompt(rng, n=None):
    return rng.randint(1, VOCAB, n or rng.randint(3, PROMPT_TOP + 1)
                       ).astype(np.int32)


def _oracle(params, prompt, n_tokens, eos_id=None):
    """Single-request greedy lm_generate at the engine's cache width
    (the prompt padded to a multiple of PROMPT_TOP: a shape or two to
    compile, and lm_generate ignores the pad)."""
    width = -(-prompt.size // PROMPT_TOP) * PROMPT_TOP
    padded = np.zeros((1, width), np.int32)
    padded[0, :prompt.size] = prompt
    ids = np.asarray(transformer.lm_generate(
        params, padded, max_len=MAX_LEN, num_heads=HEADS,
        eos_id=eos_id, prompt_lengths=np.asarray([prompt.size])))
    return ids[0, prompt.size:prompt.size + n_tokens].tolist()


def _drive(bat, cases, stagger_s=0.004):
    """Concurrent client threads; returns results (None on failure) and
    per-request exceptions."""
    results, excs = [None] * len(cases), [None] * len(cases)

    def client(i):
        prompt, n = cases[i]
        try:
            time.sleep(stagger_s * i)
            results[i] = bat.submit(prompt, max_tokens=n).result(120)
        except Exception as e:      # noqa: BLE001
            excs[i] = e

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(cases))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(180)
        assert not t.is_alive(), "client thread wedged: DEADLOCK"
    return results, excs


def _audit(engine):
    """The no-leak/no-double-free ledger invariant, plus: every slot is
    free again, so only prefix-index references may keep blocks held."""
    engine._paged.check()
    assert engine.free_slots == engine.num_slots
    held = engine._paged.pool.num_used
    idx = engine._paged.index
    assert held == (len({b for _c, ch in idx._entries.values()
                         for b in ch}) if idx is not None else 0)


# ------------------------------------------------------- allocator units


def test_block_pool_alloc_share_release_and_errors():
    pool = BlockPool(num_blocks=5, block_size=4)
    assert pool.num_allocatable == 4 and pool.num_free == 4
    a, b = pool.alloc(), pool.alloc()
    assert {a, b}.isdisjoint({SCRATCH_BLOCK})
    assert pool.refcount(a) == 1
    pool.share(a)
    assert pool.refcount(a) == 2
    pool.release(a)
    pool.release(a)                     # refcount 0 -> back on free list
    assert pool.num_free == 3
    with pytest.raises(RuntimeError, match="double free"):
        pool.release(a)
    with pytest.raises(RuntimeError, match="unowned"):
        pool.share(a)
    c, d = pool.alloc(), pool.alloc()
    assert pool.alloc() is not None     # 4th allocatable
    assert pool.alloc() is None         # dry, not an exception
    pool.check()
    pool.release(b), pool.release(c), pool.release(d)
    # a manufactured leak trips check()
    pool._ref[2] += 1
    with pytest.raises(AssertionError):
        pool.check()
    with pytest.raises(ConfigError):
        BlockPool(num_blocks=1, block_size=4)
    with pytest.raises(ConfigError):
        BlockPool(num_blocks=4, block_size=0)


def test_prefix_index_longest_match_and_lru():
    pool = BlockPool(num_blocks=12, block_size=4)
    chain = [pool.alloc() for _ in range(3)]
    idx = PrefixIndex(pool)
    toks = list(range(1, 11))               # 10 tokens = 2.5 blocks
    idx.register(toks, chain)
    # entries: [0:4], [0:8] aligned + the exact 10-token partial tail
    assert len(idx) == 3
    assert idx.lookup(toks) == (10, chain)              # exact, tail too
    cov, got = idx.lookup(toks[:8] + [99, 98, 97])      # divergent tail
    assert cov == 8 and got == chain[:2]
    cov, got = idx.lookup(toks[:4] + [99] * 6)
    assert cov == 4 and got == chain[:1]
    assert idx.lookup([99, 98]) == (0, [])
    # one pool reference per (entry, block): 1 + 2 + 3
    assert idx.block_refs == 6
    assert pool.refcount(chain[0]) == 4     # owner + three entries
    # LRU: evicting all entries releases exactly the index references
    idx.clear()
    assert len(idx) == 0 and idx.block_refs == 0
    for b in chain:
        assert pool.refcount(b) == 1
        pool.release(b)
    assert pool.num_free == pool.num_allocatable
    pool.check()


def test_paged_state_seating_cow_victim_and_atomic_exhaustion():
    st = PagedKVState(num_slots=2, num_blocks=6, block_size=4, max_len=16)
    chain = st.seat_fresh(0, 6)             # 2 blocks
    st.register_prefix(list(range(1, 7)), 0)
    # a sharer seats on the registered chain: refcounts go shared
    st.seat_shared(1, chain, 6)
    assert st.pool.refcount(chain[0]) > 1
    # slot 1's next write into the shared tail block must CoW-fork it
    plan = st.write_plan(1, 5)
    assert plan[0] == "cow" and plan[2] == chain[1]
    assert st.tables[1, 1] == plan[3] != chain[1]
    # growth past the chain allocates ("alloc"), then the pool runs dry
    # mid-claim: seat_fresh is all-or-nothing and the ledger stays clean
    assert st.write_plan(1, 8)[0] == "alloc"
    with pytest.raises(InsufficientBlocksError):
        st.seat_fresh(None, 99)             # would need 25 blocks
    st.check()
    # victim order: youngest (most recently seated) goes first
    assert st.victim(exclude=set()) == 1
    assert st.victim(exclude={1}) == 0
    st.evict(1)
    st.evict(0)
    st.check()
    assert (st.tables == SCRATCH_BLOCK).all()


# ------------------------------------------------------------- parity


def test_paged_staggered_admissions_bit_identical_to_lm_generate(
        params, engine):
    """The acceptance drive on the paged layout: more requests than
    slots, mixed prompt lengths and max_tokens, staggered so admissions
    and evictions churn the block tables mid-decode — every stream must
    equal the single-request oracle exactly, and the refcount ledger
    must balance afterwards."""
    engine.metrics = ServingMetrics()
    bat = GenerationBatcher(engine, default_max_tokens=8)
    rng = np.random.RandomState(1)
    cases = [(_prompt(rng), int(rng.randint(2, 13))) for _ in range(12)]
    results, excs = _drive(bat, cases)
    bat.close()
    assert all(e is None for e in excs), excs
    for (prompt, n), res in zip(cases, results):
        assert res["finish_reason"] == "length"
        assert res["tokens"] == _oracle(params, prompt, n), \
            f"prompt len {prompt.size}, n {n}"
    snap = engine.metrics.snapshot()
    assert snap["evictions"]["length"] == 12
    assert snap["kv_blocks_total"] == engine._paged.pool.num_allocatable
    _audit(engine)


def test_prefix_cache_hit_and_cow_fork_bit_identical(params, engine):
    """Prefix sharing end to end: a leader registers a 1.5-block system
    prompt; an EXACT duplicate then seats inside the shared tail block
    (copy-on-write fork on its first write) and a divergent prompt
    seats on the shared aligned block — both by reference, only the
    uncovered suffix ingested, all three streams bit-identical to the
    oracle."""
    engine.metrics = ServingMetrics()
    rng = np.random.RandomState(2)
    sys_prompt = _prompt(rng, BS + BS // 2)
    divergent = np.concatenate([sys_prompt[:BS], _prompt(rng, 4)])
    bat = GenerationBatcher(engine)
    pre0 = engine.prefill_positions_total
    lead = bat.submit(sys_prompt, max_tokens=6).result(60)
    prefilled_lead = engine.prefill_positions_total - pre0
    dup = bat.submit(sys_prompt, max_tokens=6).result(60)
    div = bat.submit(divergent, max_tokens=6).result(60)
    bat.close()
    assert lead["tokens"] == dup["tokens"] \
        == _oracle(params, sys_prompt, 6)
    assert div["tokens"] == _oracle(params, divergent, 6)
    snap = engine.metrics.snapshot()
    assert snap["prefix_cache_hits_total"] == 2
    assert snap["cow_forks_total"] >= 1
    # the hits ingested their uncovered suffix alone: nothing for the
    # duplicate, the 4 divergent tokens for the other
    assert prefilled_lead == sys_prompt.size
    assert engine.prefill_positions_total - pre0 \
        == prefilled_lead + divergent.size - BS
    _audit(engine)


def test_paged_equals_slab_layout_token_for_token(params, engine):
    """The two memory layouts are one compiled trunk: the same prompts
    through a slab engine produce byte-identical streams."""
    slab = DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                        max_len=MAX_LEN, name="slab_twin")
    rng = np.random.RandomState(3)
    cases = [(_prompt(rng), 7) for _ in range(6)]
    engine.metrics = ServingMetrics()
    for eng in (engine, slab):
        bat = GenerationBatcher(eng)
        outs = [bat.submit(p, max_tokens=n).result(60)["tokens"]
                for p, n in cases]
        bat.close()
        if eng is engine:
            paged_outs = outs
    assert paged_outs == outs
    _audit(engine)


def test_prefix_cache_off_still_bit_identical(params):
    """kv_layout="paged" with prefix_cache=False: pure block packing,
    no sharing — parity and the ledger still hold, and duplicates
    re-ingest (zero hits by construction)."""
    eng = DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                       max_len=MAX_LEN,
                       name="paged_nocache", kv_layout="paged",
                       kv_block_size=BS, prefix_cache=False)
    eng.metrics = ServingMetrics()
    rng = np.random.RandomState(4)
    p = _prompt(rng, 10)
    bat = GenerationBatcher(eng)
    a = bat.submit(p, max_tokens=5).result(60)
    b = bat.submit(p, max_tokens=5).result(60)
    bat.close()
    assert a["tokens"] == b["tokens"] == _oracle(params, p, 5)
    snap = eng.metrics.snapshot()
    assert snap["prefix_cache_hits_total"] == 0
    assert eng._paged.pool.num_used == 0
    eng._paged.check()


# ------------------------------------------------------- pool pressure


@pytest.mark.slow
def test_pool_pressure_preemption_recovers_bit_identical(params):
    """A pool deliberately too small for the offered load: admissions
    defer and mid-decode growth preempts victim slots (evictions
    reason="pool_exhausted"); preempted requests re-seat through the
    shared seat-prefix helper and every stream still completes
    bit-identical to the oracle — space pressure is never a failure.

    The pressure schedule is DETERMINISTIC: every request is submitted
    from this thread in one tight loop (submit() is non-blocking), so
    the full backlog is queued orders of magnitude faster than one
    decode step and the admission gate sees the same queue on every
    host.  The old staggered-client-thread drive let a slow 1-core box
    serialize the clients — requests finished before pressure ever
    built, and the preemption asserts below flaked."""
    eng = DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                       max_len=MAX_LEN, name="paged_tight", kv_layout="paged",
                       kv_block_size=BS, kv_num_blocks=10)
    eng.metrics = ServingMetrics()
    bat = GenerationBatcher(eng, default_max_tokens=8)
    rng = np.random.RandomState(5)
    # each request spans 16-token prompt + 16 tokens = 4 blocks; the
    # admission gate books 3 (prompt + first emission), so 3 of the 9
    # allocatable-block budget's requests seat concurrently and their
    # growth to 12 wanted blocks guarantees mid-decode preemption —
    # regardless of how fast the worker runs relative to this thread
    cases = [(_prompt(rng, PROMPT_TOP), 16) for _ in range(6)]
    futs = [bat.submit(p, max_tokens=n) for p, n in cases]
    results = [f.result(300) for f in futs]
    bat.close()
    for (prompt, n), res in zip(cases, results):
        assert res["tokens"] == _oracle(params, prompt, n)
    snap = eng.metrics.snapshot()
    assert snap["evictions"]["pool_exhausted"] >= 1, snap
    assert snap["slot_reprefills_total"] >= 1, snap
    eng._paged.check()
    assert eng.free_slots == SLOTS


def test_request_that_cannot_fit_pool_rejected_up_front(params):
    """One request larger than the whole pool is a client error at
    submit (the preemption path could never make room), while the same
    request fits the auto-sized pool."""
    eng = DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                       max_len=MAX_LEN, name="paged_small", kv_layout="paged",
                       kv_block_size=BS, kv_num_blocks=3)
    bat = GenerationBatcher(eng)
    with pytest.raises(InvalidRequestError, match="KV blocks"):
        bat.submit(np.arange(1, 13, dtype=np.int32), max_tokens=8)
    bat.close()


# ------------------------------------------------------- trace counts


def test_one_warmup_trace_zero_retraces_under_block_churn(params):
    """Warm-up traces the paged step exactly once (plus ONE block-fork
    executable; prompt writes ride the step, and the block write only
    exists for host-tier restores); then a churn run covering admission,
    prefix-cache seating, CoW forks, pool-pressure preemption and
    re-seat retraces NOTHING: the block table is data, not shape."""
    eng = DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                       max_len=MAX_LEN, name="paged_trace",
                       kv_layout="paged",
                       kv_block_size=BS, kv_num_blocks=12)
    assert eng.step_trace_count == 1
    assert (eng._write_traces[0], eng._copy_traces[0]) == (0, 1)
    rng = np.random.RandomState(6)
    shared = _prompt(rng, BS + 2)
    with assert_no_retrace(lambda: eng.step_trace_count
                           + eng._write_traces[0] + eng._copy_traces[0],
                           "paged block churn (admit/CoW/preempt)"):
        bat = GenerationBatcher(eng, default_max_tokens=10)
        # the leader alone first (a chain is published at its prompt's
        # first token), then its duplicate — prefix hit + CoW fork —
        # among the churners
        cases = [(shared, 10)]
        cases += [(_prompt(rng, PROMPT_TOP), 12) for _ in range(4)]
        results, excs = _drive(bat, [(shared, 10)])
        more, more_excs = _drive(bat, cases)
        results, excs = results + more, excs + more_excs
        bat.close()
    assert all(e is None for e in excs), excs
    snap = eng.metrics.snapshot()
    assert snap["cow_forks_total"] >= 1         # the churn really forked
    eng._paged.check()


# ------------------------------------------------- recovery + replay


def test_supervisor_recovery_on_paged_engine_bit_identical(params, engine):
    """PR-6 chaos on the paged layout: an injected decode-step fault
    rebuilds the pool (fresh allocator, empty prefix index) and the
    supervisor re-seats every in-flight stream through the shared
    seat-prefix helper — all streams bit-identical, zero extra traces,
    and the refcount ledger balances after the storm."""
    engine.metrics = ServingMetrics()
    rng = np.random.RandomState(7)
    cases = [(_prompt(rng), 4 + (i % 5)) for i in range(8)]
    ref = [_oracle(params, p, n) for p, n in cases]
    sup = Supervisor(breaker_threshold=10)
    bat = GenerationBatcher(engine, supervisor=sup)
    faults.install_spec("serving.decode_step:at=6")
    with assert_no_retrace(lambda: engine.step_trace_count,
                           "paged chaos recovery"):
        results, excs = _drive(bat, cases)
        bat.close()
    assert faults.fired_counts() == {"serving.decode_step": 1}
    faults.clear()
    assert all(e is None for e in excs), excs
    assert [r["tokens"] for r in results] == ref
    snap = engine.metrics.snapshot()
    assert snap["evictions"]["recovered"] >= 1
    assert snap["slot_reprefills_total"] >= 1
    _audit(engine)


def test_continuation_replay_on_paged_engine_bit_identical(params, engine):
    """The PR-7 cross-replica continuation (`submit(replay=)`) on the
    paged layout: a stream interrupted after k delivered tokens finishes
    through a paged engine emitting ONLY the remaining tokens, and the
    concatenation equals the uninterrupted oracle — including when the
    replay context takes several chunks to ingest."""
    engine.metrics = ServingMetrics()
    rng = np.random.RandomState(8)
    bat = GenerationBatcher(engine)
    for plen, n, k in ((6, 10, 3), (PROMPT_TOP, 12, 7),
                       (PROMPT_TOP, 24, 14)):   # 30 tokens: 4 chunks
        prompt = _prompt(rng, plen)
        full = _oracle(params, prompt, n)
        res = bat.submit(prompt, replay=np.asarray(full[:k], np.int32),
                         max_tokens=n - k).result(60)
        assert res["tokens"] == full[k:], (plen, n, k)
    bat.close()
    _audit(engine)


# ------------------------------------------------------- construction


def test_paged_config_validation_and_auto_sizing(params):
    blocks_per_row = -(-MAX_LEN // BS)
    eng = DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                       max_len=MAX_LEN, name="paged_auto", kv_layout="paged",
                       kv_block_size=BS, kv_num_blocks=0, warm=False)
    # auto-size = the slab-equivalent KV bytes + the scratch block
    assert eng._paged.pool.num_blocks == SLOTS * blocks_per_row + 1
    assert eng._cache[0]["k"].shape == \
        (SLOTS * blocks_per_row + 1, BS,
         params["enc"][0]["attn"]["wk"].shape[1])
    with pytest.raises(ConfigError):
        DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                     max_len=MAX_LEN, kv_layout="bogus", warm=False)
    with pytest.raises(ConfigError):
        DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                     max_len=MAX_LEN, kv_layout="paged",
                     kv_block_size=0, warm=False)

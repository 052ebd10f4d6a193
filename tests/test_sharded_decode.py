"""Tensor-parallel sharded decode (DecodeEngine mesh=decode_mesh(n)).

The ONE unified chunked step runs under parallel.sharding.shard_map
over a 1-axis "model" mesh: head-sharded attention + KV pool, vocab-
sharded tied embeddings, everything else replicated — only column-
slice-exact tensors shard, so the greedy streams are BIT-IDENTICAL to
the single-chip twin (the lm_generate oracle) on both KV layouts, with
speculation composed.  Trace discipline is unchanged by the mesh: one
warm-up trace for the engine step, one for the draft rollout, zero
retraces across admission / acceptance churn (placement is data for
the tracer, not shape).

tests/conftest.py forces 8 virtual host devices, so a real >= 2-chip
mesh backs every run here — in-process, no subprocess re-exec.

Fast lane: ONE module-shared warm sharded engine (paged + speculating,
the deepest composition) plus the config seams and pool-sizing math.
Layout x k grids, int8 composition, chaos recovery, continuation
replay, and the 4-way mesh ride the slow lane (the tier-1 wrapper is
saturated on this host).
"""

import threading
import time

import numpy as np
import pytest
import jax

from paddle_tpu.models import transformer
from paddle_tpu.parallel.sharding import decode_mesh
from paddle_tpu.resilience import Supervisor, faults
from paddle_tpu.serving import GenerationBatcher, ServingMetrics
from paddle_tpu.serving.decode_engine import DecodeEngine
from paddle_tpu.serving.kv_pool import slab_equivalent_blocks
from paddle_tpu.serving.speculative import DraftTrunk, make_draft
from paddle_tpu.testing import forbid_retrace
from paddle_tpu.utils.error import ConfigError

VOCAB, D_MODEL, LAYERS, HEADS = 64, 32, 2, 2
MAX_LEN, SLOTS, BS, SHARDS, SPEC_K = 48, 4, 8, 2, 3


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


def _engine(params, shards=SHARDS, **kw):
    kw.setdefault("prefill_chunk", 4)
    if shards:
        kw.setdefault("mesh", decode_mesh(shards))
    return DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                        max_len=MAX_LEN, **kw)


@pytest.fixture(scope="module")
def sharded_engine(params):
    # ONE warm sharded engine shared across the fast lane — warm-up is
    # the expensive part, and sharing pins the trace counters across
    # every drive below (they must END at 1/1, not per-test 1/1).
    # Paged + speculating: the deepest composition (head-sharded pool
    # blocks, chain rollback, sharded draft rollout); the slow-lane
    # grid sweeps slab and the non-speculating corner.
    return _engine(params, name="sharded_shared", kv_layout="paged",
                   kv_block_size=BS, speculate_k=SPEC_K,
                   draft=make_draft(params, layers=1))


def _prompt(rng, n=None):
    return rng.randint(1, VOCAB, n or rng.randint(1, 30)).astype(np.int32)


def _oracle(params, prompt, n_tokens):
    """The single-chip twin: plain replicated greedy decode."""
    ids = np.asarray(transformer.lm_generate(
        params, prompt[None], max_len=MAX_LEN, num_heads=HEADS,
        prompt_lengths=np.asarray([prompt.size])))
    return ids[0, prompt.size:prompt.size + n_tokens].tolist()


def _drive(bat, cases, stagger_s=0.002):
    """Concurrent client threads (admissions land mid-step)."""
    results, excs = [None] * len(cases), [None] * len(cases)

    def client(i):
        prompt, n = cases[i]
        try:
            time.sleep(stagger_s * i)
            results[i] = bat.submit(prompt, max_tokens=n).result(180)
        except Exception as e:      # noqa: BLE001
            excs[i] = e

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(cases))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(240)
        assert not t.is_alive(), "client thread wedged: DEADLOCK"
    assert all(e is None for e in excs), excs
    return results


# ------------------------------------------------- bit-identity core


@pytest.mark.slow
def test_sharded_streams_bit_identical_paged(params, sharded_engine):
    """Staggered concurrent streams off the 2-way sharded speculating
    paged engine reproduce the single-chip oracle token for token —
    every collective is a concatenation or an add-zero psum, so the
    mesh changes placement, never a bit — with the mesh gauge live on
    /metrics and the block ledger balanced across the head stripes."""
    eng = sharded_engine
    eng.metrics = ServingMetrics()
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(0)
    cases = [(_prompt(rng), 4 + (i % 7)) for i in range(6)]
    with forbid_retrace(eng, eng.draft, what="sharded paged serving"):
        results = _drive(bat, cases)
    bat.close()
    assert [r["tokens"] for r in results] == \
        [_oracle(params, p, n) for p, n in cases]
    snap = eng.metrics.snapshot()
    assert snap["mesh_shards"] == SHARDS, snap
    assert snap["drafted_tokens_total"] > 0, snap
    assert f"{eng.metrics.name}_mesh_shards {SHARDS}" \
        in eng.metrics.render_prometheus()
    eng._paged.check()


def test_sharded_slab_bit_identical(params):
    """The slab layout shards the same way (each chip's rows carry its
    Dkv stripe): streams oracle-identical at 1 warm-up trace."""
    eng = _engine(params, name="sharded_slab", kv_layout="slab")
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(1)
    cases = [(_prompt(rng), 4 + (i % 5)) for i in range(4)]
    with forbid_retrace(eng, what="sharded slab serving"):
        results = _drive(bat, cases)
    bat.close()
    assert [r["tokens"] for r in results] == \
        [_oracle(params, p, n) for p, n in cases]
    assert eng.step_trace_count == 1
    assert eng.metrics.snapshot()["mesh_shards"] == SHARDS
    # the unsharded twin reports the degenerate gauge
    assert _engine(params, shards=0, name="twin_gauge") \
        .metrics.snapshot()["mesh_shards"] == 1


# --------------------------------------------- capacity + trace + config


def test_sharded_pool_capacity_multiplies(params, sharded_engine):
    """A chip holds only its Hkv/n stripe of each block, so the slab-
    equivalent PER-CHIP byte budget holds n× the blocks — the capacity
    win tensor parallelism exists for — and int8 composes on top."""
    base = slab_equivalent_blocks(SLOTS, MAX_LEN, BS)
    both = slab_equivalent_blocks(SLOTS, MAX_LEN, BS, kv_dtype="int8",
                                  mesh_shards=SHARDS)
    assert base == SLOTS * (MAX_LEN // BS) + 1
    assert slab_equivalent_blocks(SLOTS, MAX_LEN, BS,
                                  mesh_shards=SHARDS) == \
        SHARDS * (base - 1) + 1
    assert both == 2 * SHARDS * (base - 1) + 1
    # the shared engine's auto-sized pool really got the n× count
    assert sharded_engine._paged.pool.num_blocks == \
        slab_equivalent_blocks(SLOTS, MAX_LEN, BS, mesh_shards=SHARDS)


def test_sharded_trace_discipline(sharded_engine):
    """After every fast-lane drive above: the sharded engine step
    traced ONCE and the sharded draft rollout traced ONCE — the mesh
    never bought a second trace."""
    assert sharded_engine.step_trace_count == 1
    assert sharded_engine.draft.trace_count == 1


def test_sharded_step_holds_exactly_its_three_seams(params):
    """The compiled sharded step carries exactly ``layers + 1``
    all-gathers (one per layer's attention output, one for the logits)
    and the embedding psum — nothing sums float partial products — while
    the single-chip twin carries no collective at all."""
    import re

    def collectives(eng):
        ops = re.findall(r"= \S+ ([a-z][a-z0-9\-]*)\(",
                         eng.lower().compile().as_text())
        return (sum(o == "all-gather" for o in ops),
                sum(o in ("all-reduce", "reduce-scatter") for o in ops))

    kw = dict(kv_layout="paged", kv_block_size=BS, warm=False)
    gathers, reduces = collectives(_engine(params, name="seams", **kw))
    assert gathers == LAYERS + 1 and reduces >= 1, (gathers, reduces)
    assert collectives(_engine(params, shards=0, name="seams_twin",
                               **kw)) == (0, 0)


def test_sharded_config_validation(params):
    """The config seams fail fast at construction: a mesh without the
    'model' axis, an indivisible trunk, and a draft on a different
    mesh."""
    from jax.sharding import Mesh
    with pytest.raises(ConfigError, match="axis"):
        _engine(params, shards=0,
                mesh=Mesh(np.asarray(jax.devices()[:2]), ("data",)))
    with pytest.raises(ConfigError, match="cannot shard"):
        _engine(params, shards=3)       # 2 heads / 64 vocab don't split 3
    with pytest.raises(ConfigError, match="mesh"):
        single = DraftTrunk(make_draft(params, layers=1), k=SPEC_K,
                            num_slots=SLOTS, max_len=MAX_LEN,
                            chunk=SPEC_K + 2, num_heads=HEADS)
        _engine(params, speculate_k=SPEC_K, draft=single)


# ------------------------------------------------------- slow lane


@pytest.mark.slow
@pytest.mark.parametrize("layout", ["slab", "paged"])
@pytest.mark.parametrize("k", [0, 2])
def test_sharded_layout_k_grid_bit_identical(params, layout, k):
    """layout x speculate_k sweep on the 2-way mesh: every pairing
    reproduces the oracle under staggered concurrency, zero retraces."""
    kw = {"kv_layout": layout, "speculate_k": k}
    if layout == "paged":
        kw["kv_block_size"] = BS
    if k:
        kw["draft"] = make_draft(params, layers=1)
    eng = _engine(params, name=f"sharded_{layout}_{k}", **kw)
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(10 + k)
    cases = [(_prompt(rng), 4 + (i % 6)) for i in range(6)]
    jits = (eng, eng.draft) if k else (eng,)
    with forbid_retrace(*jits, what=f"sharded {layout} k={k}"):
        results = _drive(bat, cases)
    bat.close()
    assert [r["tokens"] for r in results] == \
        [_oracle(params, p, n) for p, n in cases]


@pytest.mark.slow
def test_sharded_int8_kv_matches_unsharded_twin(params):
    """Quant composition: an int8-KV sharded paged engine (per-chip
    stripes of the int8 blocks AND their scale sidecars) emits the
    SAME streams as its int8-KV single-chip twin — bit-identity holds
    within the quantization mode."""
    kw = dict(kv_layout="paged", kv_block_size=BS, kv_dtype="int8")
    shd = _engine(params, name="sharded_q", **kw)
    twin = _engine(params, shards=0, name="sharded_q_twin", **kw)
    rng = np.random.RandomState(20)
    cases = [(_prompt(rng), 4 + (i % 6)) for i in range(6)]
    bat = GenerationBatcher(shd)
    got = [r["tokens"] for r in _drive(bat, cases)]
    bat.close()
    bat = GenerationBatcher(twin)
    ref = [r["tokens"] for r in _drive(bat, cases)]
    bat.close()
    assert got == ref
    shd._paged.check()


@pytest.mark.slow
def test_sharded_chaos_recovery_bit_identical(params):
    """An injected decode-step fault on the sharded engine rebuilds the
    SHARDED caches (reset() re-places every stripe on the mesh) and
    re-seats every stream: all streams oracle-identical, zero extra
    traces — recovery never falls back to replicated buffers."""
    eng = _engine(params, name="sharded_chaos", kv_layout="paged",
                  kv_block_size=BS)
    rng = np.random.RandomState(30)
    cases = [(_prompt(rng), 4 + (i % 5)) for i in range(8)]
    ref = [_oracle(params, p, n) for p, n in cases]
    sup = Supervisor(breaker_threshold=10)
    bat = GenerationBatcher(eng, supervisor=sup)
    faults.install_spec("serving.decode_step:at=6")
    with forbid_retrace(eng, what="sharded chaos recovery"):
        results = _drive(bat, cases)
        bat.close()
    assert faults.fired_counts() == {"serving.decode_step": 1}
    faults.clear()
    assert [r["tokens"] for r in results] == ref
    assert eng.metrics.snapshot()["evictions"]["recovered"] >= 1
    eng._paged.check()


@pytest.mark.slow
def test_sharded_continuation_replay_bit_identical(params):
    """Continuations ride the mesh: a stream interrupted after j
    delivered tokens finishes emitting ONLY the remainder through the
    sharded step."""
    eng = _engine(params, name="sharded_cont")
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(40)
    for plen, n, j in ((5, 10, 3), (16, 12, 7)):
        prompt = _prompt(rng, plen)
        full = _oracle(params, prompt, n)
        res = bat.submit(prompt, replay=np.asarray(full[:j], np.int32),
                         max_tokens=n - j).result(60)
        assert res["tokens"] == full[j:], (plen, n, j)
    bat.close()


@pytest.mark.slow
def test_sharded_4way_mesh_bit_identical():
    """A 4-way mesh on a 4-head trunk (1 head stripe per chip, vocab
    16/chip): the policy holds at deeper splits, streams oracle-
    identical."""
    params4 = transformer.init(jax.random.PRNGKey(2), src_vocab=VOCAB,
                               trg_vocab=1, d_model=D_MODEL, num_heads=4,
                               dff=64, enc_layers=LAYERS, dec_layers=0,
                               max_len=MAX_LEN)
    eng = DecodeEngine(params4, num_heads=4, num_slots=SLOTS,
                       max_len=MAX_LEN, prefill_chunk=4,
                       mesh=decode_mesh(4), name="sharded_4way")
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(50)
    cases = [(_prompt(rng), 4 + (i % 5)) for i in range(4)]
    with forbid_retrace(eng, what="4-way sharded serving"):
        results = _drive(bat, cases)
    bat.close()
    got = [r["tokens"] for r in results]
    ref = []
    for p, n in cases:
        ids = np.asarray(transformer.lm_generate(
            params4, p[None], max_len=MAX_LEN, num_heads=4,
            prompt_lengths=np.asarray([p.size])))
        ref.append(ids[0, p.size:p.size + n].tolist())
    assert got == ref
    assert eng.metrics.snapshot()["mesh_shards"] == 4

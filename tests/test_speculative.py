"""Speculative decoding on the slot engine (DecodeEngine speculate_k > 0).

A truncated-trunk draft rolls k tokens ahead per slot; the target's ONE
chunked step scores the committed token + k draft lanes (all_lanes) and
the host accepts the longest greedy-matching prefix — so every verify
step nets >= 1 token and the emitted stream is BIT-IDENTICAL to
non-speculative greedy decode for ANY draft, on every layout.  Trace
discipline: one warm-up trace for the engine step, one for the draft
rollout, zero retraces across acceptance churn (k_eff, feeds, and
budgets are data, not shape).

Fast lane: the degenerate/boundary/adversarial facts at tiny shapes.
Heavy k x layout x quant grids, chaos recovery, and continuation replay
ride the slow lane (the tier-1 wrapper is saturated on this host).
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
from paddle_tpu.serving.speculative import DraftTrunk, make_draft
from paddle_tpu.testing import forbid_retrace
from paddle_tpu.utils.error import ConfigError

VOCAB, D_MODEL, LAYERS, HEADS = 64, 32, 2, 2
MAX_LEN, SLOTS, BS, SPEC_K = 48, 4, 8, 3


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
def adversarial_params():
    # independently initialized: near-zero agreement with `params`'
    # greedy argmaxes, the draft-quality worst case
    return transformer.init(jax.random.PRNGKey(7), src_vocab=VOCAB,
                            trg_vocab=1, d_model=D_MODEL, num_heads=HEADS,
                            dff=64, enc_layers=LAYERS, dec_layers=0,
                            max_len=MAX_LEN)


def _engine(params, **kw):
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("speculate_k", SPEC_K)
    if kw["speculate_k"] and "draft" not in kw:
        kw["draft"] = make_draft(params, layers=1)
    return DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                        max_len=MAX_LEN, **kw)


@pytest.fixture(scope="module")
def spec_engine(params):
    # ONE paged spec engine shared across the fast lane — warm-up is the
    # expensive part, and sharing also pins the trace counters across
    # every drive below (they must END at 1/1, not per-test 1/1).  Paged
    # because that's the layout with real rollback code (chain
    # truncation); the adversarial engine below covers slab, and the
    # slow-lane grid sweeps both layouts at every k.
    return _engine(params, name="spec_shared", kv_layout="paged",
                   kv_block_size=BS)


def _prompt(rng, n=None):
    return rng.randint(1, VOCAB, n or rng.randint(1, 30)).astype(np.int32)


def _oracle(params, prompt, n_tokens, eos_id=None):
    ids = np.asarray(transformer.lm_generate(
        params, prompt[None], max_len=MAX_LEN, num_heads=HEADS,
        eos_id=eos_id, prompt_lengths=np.asarray([prompt.size])))
    return ids[0, prompt.size:prompt.size + n_tokens].tolist()


def _drive(bat, cases, stagger_s=0.002):
    """Concurrent client threads (admissions land mid-verify)."""
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


def test_spec_streams_bit_identical_paged(params, spec_engine):
    """Staggered concurrent streams off the speculating paged engine
    reproduce the single-request oracle token for token — draft-lane
    K/V past the accepted prefix rolls back by chain truncation
    (PagedKVState.truncate), ledger balanced — with real acceptance
    evidence (lanes drafted AND accepted) and >= 1 token per verify
    step."""
    eng = spec_engine
    eng.metrics = ServingMetrics()
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(0)
    cases = [(_prompt(rng), 4 + (i % 7)) for i in range(6)]
    with forbid_retrace(eng, eng.draft, what="paged spec serving"):
        results = _drive(bat, cases)
    bat.close()
    assert [r["tokens"] for r in results] == \
        [_oracle(params, p, n) for p, n in cases]
    snap = eng.metrics.snapshot()
    assert snap["drafted_tokens_total"] > 0, snap
    assert snap["accepted_tokens_total"] > 0, snap
    assert snap["spec_tokens_per_step"] >= 1.0, snap
    assert snap["speculate_k"] == SPEC_K, snap
    eng._paged.check()


@pytest.mark.slow
def test_adversarial_draft_bit_identical_nets_one(params,
                                                  adversarial_params):
    """A draft that (almost) never agrees with the target costs
    throughput, never correctness: streams stay oracle-identical and
    every verify step still nets >= 1 token."""
    eng = _engine(params, name="spec_adv",
                  draft=make_draft(adversarial_params, layers=1))
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(2)
    cases = [(_prompt(rng), 5 + (i % 5)) for i in range(5)]
    results = _drive(bat, cases)
    bat.close()
    assert [r["tokens"] for r in results] == \
        [_oracle(params, p, n) for p, n in cases]
    snap = eng.metrics.snapshot()
    assert snap["drafted_tokens_total"] > 0, snap
    assert snap["spec_tokens_per_step"] >= 1.0, snap
    assert snap["spec_acceptance_rate"] < 0.5, snap


# ------------------------------------------------- boundary behavior


@pytest.mark.slow
def test_k1_degenerate_matches_nonspec(params):
    """speculate_k=1 is the smallest speculating engine: one draft lane
    per verify span, streams byte-for-byte the oracle's, tokens per
    step within [1, 2].  Slow lane: the k x layout grid already drives
    k=1 on both layouts; this adds only the tokens-per-step bound."""
    eng = _engine(params, name="spec_k1", speculate_k=1)
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(3)
    cases = [(_prompt(rng), 4 + (i % 5)) for i in range(4)]
    results = _drive(bat, cases)
    bat.close()
    assert [r["tokens"] for r in results] == \
        [_oracle(params, p, n) for p, n in cases]
    snap = eng.metrics.snapshot()
    assert snap["drafted_tokens_total"] > 0, snap
    assert 1.0 <= snap["spec_tokens_per_step"] <= 2.0, snap


def test_eos_inside_accepted_run(params, spec_engine):
    """EOS landing INSIDE an accepted draft run must clip the emission
    exactly where non-speculative decode would stop — accepted lanes
    past the EOS are discarded, finish_reason is eos."""
    bat = GenerationBatcher(spec_engine)
    rng = np.random.RandomState(4)
    for _ in range(5):              # the 6th seeded prompt's stream
        _prompt(rng, 9)             # first emits its EOS id at index 2
    prompt = _prompt(rng, 9)
    full = _oracle(params, prompt, 12)
    eos = full[2]
    assert eos not in full[:2], full    # seeded: EOS lands MID-run
    res = bat.submit(prompt, max_tokens=12, eos_id=eos).result(60)
    assert res["finish_reason"] == "eos", res
    assert res["tokens"] == full[:3], (res["tokens"], full)
    # immediate first-token EOS: the degenerate clip
    res = bat.submit(prompt, max_tokens=12, eos_id=full[0]).result(60)
    assert res["finish_reason"] == "eos" and res["tokens"] == [full[0]]
    bat.close()


def test_max_tokens_boundary_mid_run(params, spec_engine):
    """max_tokens landing inside an accepted run truncates the emission
    at the budget, exactly like the non-speculating engine."""
    bat = GenerationBatcher(spec_engine)
    rng = np.random.RandomState(5)
    prompt = _prompt(rng, 7)
    full = _oracle(params, prompt, SPEC_K + 2)
    for n in (1, 2, SPEC_K + 2):
        res = bat.submit(prompt, max_tokens=n).result(60)
        assert res["finish_reason"] == "length", (n, res)
        assert res["tokens"] == full[:n], (n, res["tokens"], full[:n])
    bat.close()


# ------------------------------------------- metrics + trace + config


def test_metrics_swap_reapplies_speculate_k(params, spec_engine):
    """A per-drive metrics reset: a swapped-in ServingMetrics
    inherits the speculate_k gauge immediately (config, like the chunk
    gauge) and the spec counters grow on the NEW object only."""
    eng = spec_engine
    old = eng.metrics
    eng.metrics = fresh = ServingMetrics()
    assert fresh.snapshot()["speculate_k"] == SPEC_K
    before_old = old.snapshot()["drafted_tokens_total"]
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(6)
    res = bat.submit(_prompt(rng, 5), max_tokens=6).result(60)
    bat.close()
    assert res["tokens"] == _oracle(params, _prompt(
        np.random.RandomState(6), 5), 6)
    snap = fresh.snapshot()
    assert snap["drafted_tokens_total"] > 0, snap
    assert snap["spec_steps_total"] > 0, snap
    assert old.snapshot()["drafted_tokens_total"] == before_old
    # prometheus surface: acceptance evidence renders off the new object
    text = fresh.render_prometheus()
    assert f"{fresh.name}_speculate_k {SPEC_K}" in text
    assert "_spec_acceptance_rate " in text


def test_spec_trace_discipline(spec_engine):
    """After every fast-lane drive above: the engine step traced ONCE
    (warm-up) and the draft rollout traced ONCE — acceptance churn,
    EOS clips, and budget truncation never retraced either."""
    assert spec_engine.step_trace_count == 1
    assert spec_engine.draft.trace_count == 1


def test_spec_config_validation(params):
    """The config seams: a draft without speculate_k and a mismatched
    DraftTrunk both fail fast at construction."""
    with pytest.raises(ConfigError, match="draft"):
        _engine(params, speculate_k=0, draft=make_draft(params, layers=1))
    with pytest.raises(ConfigError, match="does not match"):
        mismatched = DraftTrunk(make_draft(params, layers=1),
                                k=SPEC_K + 1, num_slots=SLOTS,
                                max_len=MAX_LEN, chunk=SPEC_K + 3,
                                num_heads=HEADS)
        _engine(params, draft=mismatched)
    with pytest.raises(ConfigError, match="layers"):
        make_draft(params, layers=LAYERS + 1)


# ------------------------------------------------------- slow lane


@pytest.mark.slow
@pytest.mark.parametrize("layout", ["slab", "paged"])
@pytest.mark.parametrize("k", [1, 2, 4])
def test_spec_k_layout_grid_bit_identical(params, layout, k):
    """k x layout sweep: every (k, layout) pairing reproduces the
    oracle under staggered concurrency."""
    kw = {"kv_layout": layout}
    if layout == "paged":
        kw["kv_block_size"] = BS
    eng = _engine(params, name=f"spec_{layout}_{k}", speculate_k=k, **kw)
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(10 + k)
    cases = [(_prompt(rng), 4 + (i % 6)) for i in range(6)]
    with forbid_retrace(eng, eng.draft, what=f"{layout} spec k={k}"):
        results = _drive(bat, cases)
    bat.close()
    assert [r["tokens"] for r in results] == \
        [_oracle(params, p, n) for p, n in cases]


@pytest.mark.slow
def test_spec_int8_kv_quant_draft_matches_nonspec_twin(params):
    """Quant composition: an int8-KV paged spec engine with an int8
    draft emits the SAME streams as its non-speculating int8-KV twin —
    bit-identity holds within the quantization mode."""
    kw = dict(kv_layout="paged", kv_block_size=BS, kv_dtype="int8")
    spec = _engine(params, name="spec_q",
                   draft=make_draft(params, layers=1, quantize=True),
                   **kw)
    twin = _engine(params, name="spec_q_twin", speculate_k=0, **kw)
    rng = np.random.RandomState(20)
    cases = [(_prompt(rng), 4 + (i % 6)) for i in range(6)]
    bat = GenerationBatcher(spec)
    got = [r["tokens"] for r in _drive(bat, cases)]
    bat.close()
    bat = GenerationBatcher(twin)
    ref = [r["tokens"] for r in _drive(bat, cases)]
    bat.close()
    assert got == ref
    assert spec.metrics.snapshot()["drafted_tokens_total"] > 0
    spec._paged.check()


@pytest.mark.slow
def test_spec_supervisor_recovery_bit_identical(params):
    """PR-6 chaos on the speculating engine: an injected decode-step
    fault rebuilds BOTH caches (target + draft) and re-seats every
    stream; contexts re-feed the draft through _draft_seed — all
    streams oracle-identical, zero extra traces."""
    eng = _engine(params, name="spec_chaos", kv_layout="paged",
                  kv_block_size=BS)
    rng = np.random.RandomState(30)
    cases = [(_prompt(rng), 4 + (i % 5)) for i in range(8)]
    ref = [_oracle(params, p, n) for p, n in cases]
    sup = Supervisor(breaker_threshold=10)
    bat = GenerationBatcher(eng, supervisor=sup)
    faults.install_spec("serving.decode_step:at=6")
    with forbid_retrace(eng, eng.draft, what="spec chaos recovery"):
        results = _drive(bat, cases)
        bat.close()
    assert faults.fired_counts() == {"serving.decode_step": 1}
    faults.clear()
    assert [r["tokens"] for r in results] == ref
    snap = eng.metrics.snapshot()
    assert snap["evictions"]["recovered"] >= 1
    eng._paged.check()


@pytest.mark.slow
def test_spec_continuation_replay_bit_identical(params):
    """PR-7 continuations ride speculation: a stream interrupted after
    j delivered tokens finishes emitting ONLY the remainder, and the
    replayed context re-feeds the draft like any committed prefix."""
    eng = _engine(params, name="spec_cont")
    bat = GenerationBatcher(eng)
    rng = np.random.RandomState(40)
    for plen, n, j in ((5, 10, 3), (16, 12, 7)):
        prompt = _prompt(rng, plen)
        full = _oracle(params, prompt, n)
        res = bat.submit(prompt, replay=np.asarray(full[:j], np.int32),
                         max_tokens=n - j).result(60)
        assert res["tokens"] == full[j:], (plen, n, j)
    bat.close()

"""Fused Pallas decode-attention kernels (ops/pallas/decode_attention.py).

Interpret-mode (CPU) coverage of the two serving decode kernels — the
slab stripe kernel and the block-table-walking paged kernel — against
the reference XLA paths in ``models/transformer``:

* kernel numerics vs ``_attend`` / the chain-gather path (allclose,
  incl. grouped-KV head layouts), as one-lane calls (K = 1: the paged
  kernel in its block-a-grid-step form AND its tiled form) and at the
  serving step's K lanes;
* masked-width semantics at block boundaries (a position on the last
  slot of a block must not read the next block);
* the reserved scratch block 0 is NEVER attended by an active row
  (poisoned with NaN, outputs unchanged);
* engine-level greedy streams token-identical to ``lm_generate`` with
  the kernels compiled into the step — across staggered admissions,
  prefix-cache hits, CoW forks, and PR-6 supervisor recovery — at
  exactly 1 warm-up trace and 0 retraces under churn;
* the fusion-proof analytic gate (perf/analytic.assert_decode_fused)
  passes on the fused step's HLO and FAILS on the reference step's.

The kernels are forced via ``decode_attention.forced_mode("always")``
(interpret mode off-TPU); the default CPU path stays the reference XLA
implementation, so every other test file keeps pinning bit-identity
against it.  The chaos-recovery, rope-trunk, and fusion-gate cases ride
the slow lane (each builds/lowers an extra engine or step); the kernel
numerics and both engine bit-identity drives stay in the fast lane.
"""

import threading
import time

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.models import transformer
from paddle_tpu.ops.pallas import decode_attention as dk
from paddle_tpu.perf import analytic as perf_analytic
from paddle_tpu.resilience import Supervisor, faults
from paddle_tpu.serving import GenerationBatcher, ServingMetrics
from paddle_tpu.serving.decode_engine import DecodeEngine
from paddle_tpu.testing import assert_no_retrace

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
def slab_engine(params):
    """Slab engine whose step COMPILED the fused kernel in (the mode is
    read at trace time = warm-up; later drives run the baked step)."""
    with dk.forced_mode("always"):
        eng = DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                           max_len=MAX_LEN, name="kern_slab")
    assert eng.decode_kernels
    return eng


@pytest.fixture(scope="module")
def paged_engine(params):
    with dk.forced_mode("always"):
        eng = DecodeEngine(params, num_heads=HEADS, num_slots=SLOTS,
                           max_len=MAX_LEN,
                           name="kern_paged", kv_layout="paged",
                           kv_block_size=BS)
    assert eng.decode_kernels
    return eng


def _prompt(rng, n=None):
    return rng.randint(1, VOCAB, n or rng.randint(3, PROMPT_TOP + 1)
                       ).astype(np.int32)


def _oracle(params, prompt, n_tokens):
    """Single-request greedy lm_generate — runs the REFERENCE XLA path
    (kernels are off outside forced_mode on CPU), so engine-vs-oracle
    equality crosses the kernel/reference boundary.  (The prompt is
    padded to a multiple of PROMPT_TOP: a shape or two to compile, and
    lm_generate ignores the pad.)"""
    width = -(-prompt.size // PROMPT_TOP) * PROMPT_TOP
    padded = np.zeros((1, width), np.int32)
    padded[0, :prompt.size] = prompt
    ids = np.asarray(transformer.lm_generate(
        params, padded, max_len=MAX_LEN, num_heads=HEADS,
        prompt_lengths=np.asarray([prompt.size])))
    return ids[0, prompt.size:prompt.size + n_tokens].tolist()


def _drive(bat, cases, stagger_s=0.004):
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


# --------------------------------------------------------- kernel numerics


def _ref_slab(q, k, v, positions, num_heads):
    t = k.shape[1]
    pm = jnp.arange(t)[None, :] <= jnp.asarray(positions)[:, None]
    return np.asarray(transformer._attend(
        jnp.asarray(q)[:, None], jnp.asarray(k), jnp.asarray(v),
        num_heads, jnp.broadcast_to(pm, (q.shape[0], t))))[:, 0]


def _slab_lane(q, k, v, pos, h):
    """A one-lane call of the slab kernel: q [S, D], pos [S] -> [S, D]."""
    with dk.forced_mode("always"):
        out = dk.maybe_slab_chunk(jnp.asarray(q)[:, None], jnp.asarray(k),
                                  jnp.asarray(v), jnp.asarray(pos)[:, None],
                                  h)
    assert out is not None
    return np.asarray(out)[:, 0]


@pytest.fixture(params=["block_a_step", "tiled"])
def paged_lane(request, monkeypatch):
    """A one-lane call of the paged kernel, q [S, D], pos [S] -> [S, D],
    in both of its forms: G = 1 (a table entry a grid step) and the tile
    ``paged_chunk_tile`` picks for a single lane (here the whole table)."""
    tiled = request.param == "tiled"
    if not tiled:
        monkeypatch.setattr(dk, "paged_chunk_tile", lambda *a, **k: 1)
    calls = []
    real = dk._paged_chunk_tiled
    monkeypatch.setattr(dk, "_paged_chunk_tiled",
                        lambda *a, **k: calls.append(k["g"]) or real(*a, **k))

    def call(q, kp, vp, pos, tables, h):
        del calls[:]
        with dk.forced_mode("always"):
            out = dk.maybe_paged_chunk(
                jnp.asarray(q)[:, None], jnp.asarray(kp), jnp.asarray(vp),
                jnp.asarray(pos)[:, None], jnp.asarray(tables), h)
        assert out is not None
        assert bool(calls) == tiled and all(g > 1 for g in calls), calls
        return np.asarray(out)[:, 0]
    return call


@pytest.mark.parametrize("h,hkv,dh,t", [(2, 2, 16, 24), (4, 2, 8, 48),
                                        (4, 1, 32, 16), (2, 2, 64, 130)])
def test_slab_kernel_matches_attend(h, hkv, dh, t):
    rng = np.random.RandomState(h * 100 + t)
    s, d, dkv = 5, h * dh, hkv * dh
    q = rng.randn(s, d).astype(np.float32)
    k = rng.randn(s, t, dkv).astype(np.float32)
    v = rng.randn(s, t, dkv).astype(np.float32)
    pos = rng.randint(0, t, s).astype(np.int32)
    np.testing.assert_allclose(_slab_lane(q, k, v, pos, h),
                               _ref_slab(q, k, v, pos, h),
                               rtol=1e-5, atol=1e-5)


def _paged_setup(rng, s, nb, bs, nb_row, dkv, d):
    """Random pool + per-row private chains (block 0 stays scratch) —
    the shared builder from testing/kernel_smoke."""
    from paddle_tpu.testing.kernel_smoke import build_private_tables
    t = nb_row * bs
    q = rng.randn(s, d).astype(np.float32)
    kp = rng.randn(nb, bs, dkv).astype(np.float32)
    vp = rng.randn(nb, bs, dkv).astype(np.float32)
    pos = rng.randint(0, t, s).astype(np.int32)
    tables = build_private_tables(pos, nb_row, bs, nb)
    return q, kp, vp, pos, tables, t


def _ref_paged(q, kp, vp, pos, tables, num_heads):
    s = q.shape[0]
    dkv = kp.shape[-1]
    t = tables.shape[1] * kp.shape[1]
    k_rows = kp[tables].reshape(s, -1, dkv)
    v_rows = vp[tables].reshape(s, -1, dkv)
    pm = np.arange(t)[None, :] <= pos[:, None]
    return np.asarray(transformer._attend(
        jnp.asarray(q)[:, None], jnp.asarray(k_rows),
        jnp.asarray(v_rows), num_heads, jnp.asarray(pm)))[:, 0]


@pytest.mark.parametrize("h,hkv,dh,bs", [(2, 2, 16, 8), (4, 2, 8, 4)])
def test_paged_kernel_matches_chain_gather(paged_lane, h, hkv, dh, bs):
    rng = np.random.RandomState(h * 10 + bs)
    s, nb_row = 4, 3
    d, dkv = h * dh, hkv * dh
    q, kp, vp, pos, tables, _t = _paged_setup(rng, s, 13, bs, nb_row,
                                              dkv, d)
    np.testing.assert_allclose(paged_lane(q, kp, vp, pos, tables, h),
                               _ref_paged(q, kp, vp, pos, tables, h),
                               rtol=1e-5, atol=1e-5)


def test_block_boundary_positions(paged_lane):
    """Masked-width semantics at the block seams: a row whose position
    sits on the LAST slot of a block (p % bs == bs-1) must attend that
    whole block and nothing of the next; the first slot of a block
    (p % bs == 0) must attend exactly one position of it."""
    rng = np.random.RandomState(3)
    h, dh, bs, nb_row = 2, 16, 8, 3
    d = dkv = h * dh
    s = 4
    q, kp, vp, _pos, _tables, t = _paged_setup(rng, s, 13, bs, nb_row,
                                               dkv, d)
    from paddle_tpu.testing.kernel_smoke import build_private_tables
    pos = np.asarray([bs - 1, bs, 2 * bs - 1, 0], np.int32)
    tables = build_private_tables(pos, nb_row, bs, 13)
    np.testing.assert_allclose(paged_lane(q, kp, vp, pos, tables, h),
                               _ref_paged(q, kp, vp, pos, tables, h),
                               rtol=1e-5, atol=1e-5)
    # slab twin at the same boundary positions
    ks = rng.randn(s, t, dkv).astype(np.float32)
    vs = rng.randn(s, t, dkv).astype(np.float32)
    np.testing.assert_allclose(_slab_lane(q, ks, vs, pos, h),
                               _ref_slab(q, ks, vs, pos, h),
                               rtol=1e-5, atol=1e-5)


def test_scratch_block_rows_never_attended(paged_lane):
    """Poison the reserved scratch block 0 with NaN: every ACTIVE row's
    output must be bit-identical to the clean-pool kernel run — the
    clamped table walk never even addresses block 0 for a row that owns
    its chain."""
    rng = np.random.RandomState(4)
    h, dh, bs, nb_row = 2, 16, 8, 3
    d = dkv = h * dh
    q, kp, vp, pos, tables, _t = _paged_setup(rng, 6, 19, bs, nb_row,
                                              dkv, d)
    clean = paged_lane(q, kp, vp, pos, tables, h)
    kp2, vp2 = kp.copy(), vp.copy()
    kp2[0] = np.nan
    vp2[0] = np.nan
    poisoned = paged_lane(q, kp2, vp2, pos, tables, h)
    np.testing.assert_array_equal(poisoned, clean)
    assert np.all(np.isfinite(poisoned))


# ------------------------------------------------ the paged chunk tile

KK = 8          # lanes a row, the serving default's prefill chunk


def _chunk_setup(rng, last, lens, h, hkv, dh, bs, nb_row):
    """q, pool, per-lane positions and private chains for rows whose
    furthest lane sits at ``last`` with ``lens`` live lanes (dead lanes
    repeat the last live one, the engine's ``_chunk_lanes``)."""
    from paddle_tpu.testing.kernel_smoke import (_chunk_lanes_ref,
                                                 build_private_tables)
    last, lens = np.asarray(last, np.int32), np.asarray(lens, np.int32)
    s, nb = last.size, last.size * nb_row + 1
    qpos = _chunk_lanes_ref(last - lens + 1, lens, KK)
    q = rng.randn(s, KK, h * dh).astype(np.float32)
    kp = rng.randn(nb, bs, hkv * dh).astype(np.float32)
    vp = rng.randn(nb, bs, hkv * dh).astype(np.float32)
    tables = build_private_tables(last, nb_row, bs, nb)
    live = np.arange(KK)[None, :] < lens[:, None]
    return q, kp, vp, qpos, tables, live


def _ref_paged_chunk(q, kp, vp, qpos, tables, num_heads):
    s, dkv = q.shape[0], kp.shape[-1]
    t = tables.shape[1] * kp.shape[1]
    pm = np.arange(t)[None, None, :] <= qpos[:, :, None]
    return np.asarray(transformer._attend(
        jnp.asarray(q), jnp.asarray(kp[tables].reshape(s, -1, dkv)),
        jnp.asarray(vp[tables].reshape(s, -1, dkv)), num_heads,
        jnp.asarray(pm)))


def _paged_chunk(q, kp, vp, qpos, tables, h):
    with dk.forced_mode("always"):
        out = dk.maybe_paged_chunk(*map(jnp.asarray, (q, kp, vp, qpos,
                                                      tables)), h)
    assert out is not None
    return np.asarray(out)


# (heads, kv heads, head dim): OPT's layout (two heads of 64 a panel),
# grouped K/V in one panel, a head that fills the lane row by itself
LAYOUTS = {"mha_dh64": (4, 4, 64), "gqa_dh16": (4, 2, 16),
           "mqa_dh128": (2, 1, 128)}
BS_T, NB_ROW_T = 16, 20     # a table of 320 positions: two tiles and a half


@pytest.mark.parametrize("lanes", ["decode", "prefill", "mixed"])
@pytest.mark.parametrize("where", ["first_position", "tile_last",
                                   "next_tile_first", "table_last",
                                   "under_one_tile"])
def test_paged_chunk_tile_matches_chain_gather(where, lanes):
    """The tiled kernel (G table entries a step of its loop) against
    ``_attend`` over the chain gather, for a row that ends on each seam
    of the tiling — beside rows elsewhere, decode rows (one live lane)
    and prefilling rows (every lane) mixed; G does not divide the table."""
    h, hkv, dh = LAYOUTS["mha_dh64"]
    g = dk.paged_chunk_tile(h, h * dh, hkv * dh, BS_T, NB_ROW_T, KK)
    tile = g * BS_T
    assert g > 1 and NB_ROW_T % g, g
    rng = np.random.RandomState(sum(map(ord, where + lanes)))
    last = [{"first_position": 0, "tile_last": tile - 1,
             "next_tile_first": tile, "table_last": NB_ROW_T * BS_T - 1,
             "under_one_tile": 2 * BS_T + 3}[where],
            int(rng.randint(KK, tile)), int(rng.randint(tile, 2 * tile)),
            int(rng.randint(2 * tile, NB_ROW_T * BS_T))]
    lens = {"decode": [1] * 4, "prefill": [KK] * 4,
            "mixed": [1, KK, 3, 1]}[lanes]
    lens = [min(n, p + 1) for n, p in zip(lens, last)]
    q, kp, vp, qpos, tables, live = _chunk_setup(rng, last, lens, h, hkv,
                                                 dh, BS_T, NB_ROW_T)
    got = _paged_chunk(q, kp, vp, qpos, tables, h)
    want = _ref_paged_chunk(q, kp, vp, qpos, tables, h)
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("nb_row", [16, 20, 3])
def test_paged_chunk_tile_layouts_and_table_lengths(layout, nb_row):
    """Every panel layout, over a table that G divides, one that it does
    not, and one shorter than a lane row of positions (G = the table)."""
    h, hkv, dh = LAYOUTS[layout]
    g = dk.paged_chunk_tile(h, h * dh, hkv * dh, BS_T, nb_row, KK)
    assert g == min(nb_row, 128 // BS_T)
    rng = np.random.RandomState(nb_row + len(layout))
    t = nb_row * BS_T
    last = [t - 1, int(rng.randint(0, t)), 0, int(rng.randint(KK, t))]
    q, kp, vp, qpos, tables, live = _chunk_setup(
        rng, last, [KK, 1, 1, 5], h, hkv, dh, BS_T, nb_row)
    got = _paged_chunk(q, kp, vp, qpos, tables, h)
    want = _ref_paged_chunk(q, kp, vp, qpos, tables, h)
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("lanes", ["decode", "prefill"])
def test_paged_chunk_tile_never_reads_a_block_it_does_not_own(lanes):
    """NaN in every block no row owns — scratch block 0, which the dead
    entries of each table name, and the pool's spare blocks — and in the
    owned last block past each row's position nothing but finite stale
    data: the output is bit-identical to the clean pool's and finite.
    Dead entries INSIDE a live tile are neither copied nor addressed."""
    h, hkv, dh = LAYOUTS["mha_dh64"]
    rng = np.random.RandomState(len(lanes))
    last = [0, 2 * BS_T + 3, 128, NB_ROW_T * BS_T - BS_T - 1]
    lens = [1] * 4 if lanes == "decode" else [1, KK, KK, KK]
    q, kp, vp, qpos, tables, live = _chunk_setup(rng, last, lens, h, hkv,
                                                 dh, BS_T, NB_ROW_T)
    clean = _paged_chunk(q, kp, vp, qpos, tables, h)
    owned = np.unique(tables[tables > 0])
    unowned = np.setdiff1d(np.arange(kp.shape[0]), owned)
    assert 0 in unowned and unowned.size > NB_ROW_T
    kp, vp = kp.copy(), vp.copy()
    kp[unowned] = np.nan
    vp[unowned] = np.nan
    poisoned = _paged_chunk(q, kp, vp, qpos, tables, h)
    np.testing.assert_array_equal(poisoned[live], clean[live])
    assert np.all(np.isfinite(poisoned))


def test_paged_chunk_tile_rule(monkeypatch):
    """G follows from what the call sees (block size, Dkv, table length,
    the VMEM budget, the block_k cap), and falls to 1 — the block-a-step
    kernel — where no larger tile runs."""
    tile = dk.paged_chunk_tile
    # interpret mode (this backend): a lane row of positions, cut to the table
    assert tile(32, 2048, 2048, 16, 128, 8) == 8
    assert tile(32, 2048, 2048, 16, 5, 8) == 5
    assert tile(32, 2048, 2048, 32, 64, 8) == 4
    assert tile(32, 2048, 2048, 128, 16, 8) == 1     # a block is a tile
    assert tile(32, 2048, 2048, 16, 128, 8, quant=True) == 1
    monkeypatch.setattr(dk, "_interpret", lambda i: False)
    # compiled: OPT-1.3B's call, 128 positions = 4 MiB of the budget
    assert tile(32, 2048, 2048, 16, 128, 8) == 8
    assert 128 * dk._vmem_bytes_per_position(2048, False) == 4 << 20
    # the VMEM budget cuts a wide Dkv's tile below the lane row
    wide = dk.vmem_budget_bytes() // dk._vmem_bytes_per_position(
        16384, False)
    assert 16 < wide < 128
    assert tile(128, 16384, 16384, 16, 128, 8) == wide // 16
    # panel rows that are not whole sublanes (2 heads x 1 x 3 lanes), and
    # panels that are not whole lane rows: G = 1
    assert tile(32, 2048, 2048, 16, 128, 3) == 1
    assert tile(3, 96, 96, 16, 128, 8) == 8      # one panel holds all 3
    assert tile(8, 384, 384, 16, 128, 8) == 1    # 2 heads of 48: 96 lanes
    # the flag's cap on positions a step still binds
    monkeypatch.setattr(dk, "_block_k_cap", lambda: 64)
    assert tile(32, 2048, 2048, 16, 128, 8) == 4


# the cells' panels (kernel_smoke.ONE_LANE_PANELS) cut to interpret-mode
# sizes, every panel layout kept: chat's block-diagonal two heads of 64,
# group 6 and group 9 (windowed, over rings) on 128-wide heads, group 20 on
# ONE K/V head.  (query heads, K/V heads, head dim, lanes, block, dtype,
# window)
TINY_PANELS = {
    "opt1.3b_chat": (4, 4, 64, KK, BS_T, jnp.float32, None),
    "laguna_full": (12, 2, 128, KK, BS_T, jnp.bfloat16, None),
    "laguna_window": (18, 2, 128, KK, 8, jnp.bfloat16, 16),
    "jamba_attn": (20, 1, 128, KK, BS_T, jnp.bfloat16, None),
}


@pytest.mark.parametrize("panel", sorted(TINY_PANELS))
def test_one_lane_path_is_the_whole_panel_bit_for_bit(panel):
    """A row that feeds one lane takes the tiled kernel's one-lane path
    (lane 0's rows of each panel); the same row fed a second lane takes the
    whole panel.  Lane 0 is the same bits either way, at the first
    position, on each side of a tile seam and at the table's end, and the
    lanes no token fed are exact zeros."""
    from paddle_tpu.testing.kernel_smoke import one_lane_vs_panel
    spec = TINY_PANELS[panel]
    entries = 16 if spec[-1] else NB_ROW_T
    span = entries * spec[4]
    tile = 128
    positions = [p for p in (0, 5, tile - 2, tile - 1, tile, span - 2)
                 if p + 1 < span]
    one, two = one_lane_vs_panel(spec, positions, entries,
                                 seed=len(panel))
    np.testing.assert_array_equal(one[:, 0], two[:, 0])
    assert np.isfinite(one[:, 0].astype(np.float32)).all()
    assert not one[:, 1:].astype(np.float32).any()
    # the second lane is a lane of its own: the panel computed it
    assert two[:, 1].astype(np.float32).any()


@pytest.mark.parametrize("panel", sorted(TINY_PANELS))
def test_one_lane_rows_beside_every_other_kind_of_row(panel):
    """One step of every kind of row: rows decoding (one lane, the
    one-lane path) deep in the table and at its start, a row prefilling
    every lane, the partly live last chunk of a prompt, and a free slot
    (its one armed lane at position 0 over the scratch block, or its own
    ring).  Every live lane matches the XLA reference; the lanes no token
    fed in a one-lane row are 0.0."""
    from paddle_tpu.models import hybrid_lm
    h, hkv, dh, kk, bs, dtype, window = TINY_PANELS[panel]
    entries = 16 if window else NB_ROW_T
    span = entries * bs
    last = np.asarray([span - 3, 9, 200 % span, span // 2 + 5, 0])
    lens = np.asarray([1, 1, kk, 3, 1])
    free = 4
    rng = np.random.RandomState(len(panel) + 7)
    s = last.size
    qpos = _chunk_lanes_ref_np(last - lens + 1, lens, kk)
    q = jnp.asarray(rng.randn(s, kk, h * dh) * 0.5, dtype)
    live = np.arange(kk)[None, :] < lens[:, None]
    f32 = lambda x: np.asarray(jnp.asarray(x, jnp.float32))
    with dk.forced_mode("always"):
        if window is None:
            nb = s * entries + 1
            kp, vp = (jnp.asarray(rng.randn(nb, bs, hkv * dh) * 0.5, dtype)
                      for _ in range(2))
            from paddle_tpu.testing.kernel_smoke import build_private_tables
            tables = build_private_tables(last, entries, bs, nb)
            tables[free] = 0            # a free slot's table names block 0
            got = dk.maybe_paged_chunk(q, kp, vp, jnp.asarray(qpos),
                                       jnp.asarray(tables), h)
            want = _ref_paged_chunk(f32(q), f32(kp), f32(vp), qpos, tables, h)
        else:
            ring = -(-(window + kk - 1) // bs) * bs
            kr, vr = (jnp.asarray(rng.randn(s, ring, hkv * dh) * 0.5, dtype)
                      for _ in range(2))
            got = dk.maybe_window_chunk(q, kr, vr, jnp.asarray(qpos), h,
                                        window, block=bs, entries=entries)
            want = hybrid_lm._ring_attention(
                *map(jnp.asarray, (f32(q), f32(kr), f32(vr))),
                jnp.asarray(qpos), hkv, dh, window).reshape(s, kk, h * dh)
    assert got is not None
    got = f32(got)
    np.testing.assert_allclose(got[live], np.asarray(want)[live],
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=2e-2 if dtype == jnp.bfloat16 else 1e-5)
    assert not got[lens == 1][:, 1:].any()
    assert np.isfinite(got).all()


def _chunk_lanes_ref_np(positions, lengths, kk):
    from paddle_tpu.testing.kernel_smoke import _chunk_lanes_ref
    return _chunk_lanes_ref(np.asarray(positions), np.asarray(lengths), kk)


def test_tile_positions_names_each_kernels_step(slab_engine, paged_engine):
    """What ``DecodeEngine.warmup`` logs beside the resolved path: the
    K/V positions one step of the serving kernel covers — the slab
    kernel's k-tile, G pool blocks for the paged one (a one-lane call
    included) — on the per-chip stripe, like ``decline_reason``."""
    assert dk.tile_positions(2, 32, 32, 48) == 48
    assert dk.tile_positions(2, 32, 32, 1024) == 512     # the flag's cap
    # a table of 6 blocks of 8 is under a lane row: one tile holds it
    assert dk.tile_positions(2, 32, 32, 8, nb_row=6, paged=True) == 48
    assert dk.tile_positions(32, 2048, 2048, 16, nb_row=128, paged=True,
                             chunk=8) == 128
    assert dk.tile_positions(32, 2048, 2048, 16, nb_row=128, paged=True,
                             chunk=8, shards=2) == 128
    assert dk.tile_positions(32, 2048, 2048, 16, nb_row=128, paged=True,
                             chunk=8, quant=True) == 16
    assert slab_engine.decode_tile == MAX_LEN
    assert paged_engine.decode_tile == MAX_LEN
    assert paged_engine._kernel_path() == \
        f"fused-pallas, {MAX_LEN} positions a step"


@pytest.mark.parametrize("g", [1, 8])
def test_covers_agrees_with_the_paged_chunk_call(monkeypatch, g):
    """``decline_reason`` / ``covers`` stay THE predicate whichever tile
    serves: what they cover the call runs (tiled or block-a-step), what
    they decline ``maybe_paged_chunk`` hands back as None."""
    if g == 1:
        monkeypatch.setattr(dk, "paged_chunk_tile", lambda *a, **k: 1)
    calls = []
    real = dk._paged_chunk_tiled
    monkeypatch.setattr(dk, "_paged_chunk_tiled",
                        lambda *a, **k: calls.append(k["g"]) or real(*a, **k))
    h, hkv, dh = LAYOUTS["gqa_dh16"]
    rng = np.random.RandomState(g)
    q, kp, vp, qpos, tables, live = _chunk_setup(
        rng, [5, 200, 319], [1, KK, 4], h, hkv, dh, BS_T, NB_ROW_T)
    with dk.forced_mode("always"):
        assert dk.covers(h, h * dh, hkv * dh, BS_T, paged=True, chunk=KK)
    got = _paged_chunk(q, kp, vp, qpos, tables, h)
    assert calls == ([] if g == 1 else [8])
    want = _ref_paged_chunk(q, kp, vp, qpos, tables, h)
    np.testing.assert_allclose(got[live], want[live], rtol=1e-5, atol=1e-5)
    with dk.forced_mode("always"):
        # a block of 136 positions: declined before any tile is chosen
        assert not dk.covers(h, h * dh, hkv * dh, 136, paged=True, chunk=KK)
        big = jnp.zeros((4, 136, hkv * dh), jnp.float32)
        assert dk.maybe_paged_chunk(
            jnp.asarray(q), big, big, jnp.asarray(qpos),
            jnp.zeros((3, 2), jnp.int32), h) is None


def test_dispatch_gating():
    """auto on CPU -> reference path (None); off -> None even when
    forced upstream; always -> kernel output; bad mode -> error."""
    rng = np.random.RandomState(5)
    q = jnp.asarray(rng.randn(2, 1, 32), jnp.float32)      # one lane
    k = jnp.asarray(rng.randn(2, 16, 32), jnp.float32)
    v = jnp.asarray(rng.randn(2, 16, 32), jnp.float32)
    pos = jnp.asarray([[3], [7]], jnp.int32)
    with dk.forced_mode("auto"):
        assert dk.maybe_slab_chunk(q, k, v, pos, 2) is None  # CPU backend
    with dk.forced_mode("off"):
        assert dk.maybe_slab_chunk(q, k, v, pos, 2) is None
    with dk.forced_mode("always"):
        assert dk.maybe_slab_chunk(q, k, v, pos, 2) is not None
    with dk.forced_mode("bogus"), pytest.raises(ValueError,
                                                match="pallas_decode"):
        dk.decode_kernels_enabled()
    # the FLAGS path (MODE=None reads utils.flags.FLAGS.pallas_decode)
    from paddle_tpu.utils.flags import FLAGS
    old = FLAGS.pallas_decode
    try:
        FLAGS.pallas_decode = "always"
        assert dk.decode_kernels_enabled()
        FLAGS.pallas_decode = "off"
        assert not dk.decode_kernels_enabled()
    finally:
        FLAGS.pallas_decode = old


def test_untileable_shapes_fall_back_not_crash():
    """Shapes the lane-replicated stat layout cannot express must
    DECLINE (None -> reference path), never fail mid-trace: a paged
    block_size of 136 (> LANES, not a LANES multiple — `_lanes` can
    neither slice nor tile it) and an interpret-mode head dim of 136
    both go through `covers` -> False."""
    rng = np.random.RandomState(6)
    with dk.forced_mode("always"):
        assert not dk.covers(2, 32, 32, 136, paged=True)
        q = jnp.asarray(rng.randn(2, 1, 32), jnp.float32)  # one lane
        kp = jnp.asarray(rng.randn(5, 136, 32), jnp.float32)
        tbl = jnp.zeros((2, 2), jnp.int32)
        pos = jnp.asarray([[3], [7]], jnp.int32)
        assert dk.maybe_paged_chunk(q, kp, kp, pos, tbl, 2) is None
        # dh = 136: _lanes on the [H, dh] accumulator can't tile either
        assert not dk.covers(2, 272, 272, 16, paged=True)
        q2 = jnp.asarray(rng.randn(2, 1, 272), jnp.float32)
        k2 = jnp.asarray(rng.randn(2, 16, 272), jnp.float32)
        assert dk.maybe_slab_chunk(q2, k2, k2, pos, 2) is None


def test_one_lane_call_takes_the_kernel_or_says_why(monkeypatch):
    """A one-lane call (``chunk=1``: the draft's rollout steps, an engine
    at ``prefill_chunk=1``) is a legal shape of both kernels.  Compiled,
    the [K*H, .] blocks want whole sublanes, so it takes the kernel where
    the heads are a multiple of 8 and carries its sentence where not;
    interpreted, any head count runs."""
    with dk.forced_mode("always"):
        for paged, blk in ((False, 256), (True, 16)):
            assert dk.decline_reason(2, 32, 32, blk, paged=paged,
                                     chunk=1) is None
        monkeypatch.setattr(dk, "_interpret", lambda i: False)
        for paged, blk in ((False, 256), (True, 16)):
            assert dk.decline_reason(16, 2048, 2048, blk, paged=paged,
                                     chunk=1) is None
            why = dk.decline_reason(4, 512, 512, blk, paged=paged, chunk=1)
            assert why == ("chunk 1 x heads 4 is not a multiple of 8 "
                           "sublanes")
        # compiled, one lane of two heads a panel is 2 rows, not a whole
        # sublane tile: the paged call runs block-a-grid-step (G = 1);
        # a grouped layout whose panel holds 8 rows tiles
        assert dk.tile_positions(16, 1024, 1024, 16, nb_row=128,
                                 paged=True, chunk=1) == 16
        assert dk.tile_positions(16, 2048, 256, 16, nb_row=128,
                                 paged=True, chunk=1) == 128


def test_covers_judges_the_per_chip_stripe():
    """Tensor-parallel coverage (docs/serving.md "Sharded decode") is
    judged on the PER-CHIP widths — num_heads/n query heads over a
    d/n-wide q and dkv/n-wide K/V stripe — never the full trunk's:
    inside the engine's shard_map the maybe_* dispatch sees the local
    arrays, so warmup's resolved-path prediction (covers(shards=n))
    must localize the same way or the logged path lies."""
    with dk.forced_mode("always"):
        # full trunk covered; the 2-way stripe still splits its heads
        # (hkv = 2 -> one KV head per chip)
        assert dk.covers(4, 128, 64, 16)
        assert dk.covers(4, 128, 64, 16, shards=2)
        # 4-way: the local stripe is one query head over a 16-wide Dkv
        # — dkv/n stops dividing dh, the grouped-head layout is gone
        assert not dk.covers(4, 128, 64, 16, shards=4)
        # uneven stripes never reach the kernels at all
        assert not dk.covers(4, 128, 64, 16, shards=8)
        assert not dk.covers(4, 128, 64, 16, shards=3)


def test_covers_compiled_stripe_loses_lane_tiling(monkeypatch):
    """Compiled-mode pin for the same localization: a Dkv that Mosaic's
    lanes tile at full width (384 = 3 * 128) stops tiling at the 2-way
    stripe (192 is neither <= 128 nor a 128-multiple), so the sharded
    engine must reject to the reference path even though the identical
    single-chip trunk compiles the fused kernel."""
    monkeypatch.setattr(dk, "_interpret", lambda i: False)
    with dk.forced_mode("always"):
        assert dk.covers(16, 384, 384, 16, paged=True)
        assert not dk.covers(16, 384, 384, 16, paged=True, shards=2)


# ------------------------------------------------------- engine parity


def test_slab_engine_greedy_bit_identical_no_retrace(params, slab_engine):
    """Staggered admissions through the KERNEL-compiled slab step: every
    greedy stream token-identical to the reference-path lm_generate
    oracle; 1 warm-up trace, 0 retraces across churn."""
    eng = slab_engine
    eng.metrics = ServingMetrics()
    assert eng.step_trace_count == 1
    rng = np.random.RandomState(11)
    cases = [(_prompt(rng), int(rng.randint(2, 13))) for _ in range(6)]
    with assert_no_retrace(lambda: eng.step_trace_count,
                           "fused slab churn"):
        bat = GenerationBatcher(eng, default_max_tokens=8)
        results, excs = _drive(bat, cases)
        bat.close()
    assert all(e is None for e in excs), excs
    for (prompt, n), res in zip(cases, results):
        assert res["tokens"] == _oracle(params, prompt, n), \
            f"prompt len {prompt.size}, n {n}"
    assert eng.free_slots == SLOTS


def test_paged_engine_greedy_bit_identical_under_churn(params,
                                                      paged_engine):
    """The paged kernel under real allocator churn: shared prompts
    (prefix hit + CoW fork), mixed lengths, slot reuse — streams
    token-identical to the oracle, zero retraces of step/write/fork."""
    eng = paged_engine
    eng.metrics = ServingMetrics()
    rng = np.random.RandomState(12)
    shared = _prompt(rng, BS + 3)
    cases = [(shared, 8), (shared, 8)]
    cases += [(_prompt(rng), int(rng.randint(2, 11))) for _ in range(5)]
    with assert_no_retrace(lambda: eng.step_trace_count
                           + eng._write_traces[0] + eng._copy_traces[0],
                           "fused paged churn (admit/CoW/evict)"):
        bat = GenerationBatcher(eng, default_max_tokens=8)
        # the leader alone first: a prompt's chain is published at its
        # first token, once its chunks are in
        lead, lead_excs = _drive(bat, cases[:1])
        results, excs = _drive(bat, cases[1:])
        results, excs = lead + results, lead_excs + excs
        bat.close()
    assert all(e is None for e in excs), excs
    for (prompt, n), res in zip(cases, results):
        assert res["tokens"] == _oracle(params, prompt, n), \
            f"prompt len {prompt.size}, n {n}"
    snap = eng.metrics.snapshot()
    assert snap["prefix_cache_hits_total"] >= 1
    assert snap["cow_forks_total"] >= 1
    eng._paged.check()


def test_chunked_engine_streams_cross_the_tile_seam():
    """The tiled kernel compiled into the chunked paged step: prompts
    that end before, on and past the first tile's last position (127),
    decode rows beside prefilling rows under slot reuse — every greedy
    stream token-identical to ``lm_generate``, one trace."""
    max_len, bs, kk = 160, 8, 4
    p = transformer.init(jax.random.PRNGKey(3), src_vocab=VOCAB,
                         trg_vocab=1, d_model=D_MODEL, num_heads=HEADS,
                         dff=64, enc_layers=LAYERS, dec_layers=0,
                         max_len=max_len)
    assert dk.paged_chunk_tile(HEADS, D_MODEL, D_MODEL, bs, max_len // bs,
                               kk) * bs == 128
    with dk.forced_mode("always"):
        eng = DecodeEngine(p, num_heads=HEADS, num_slots=3,
                           max_len=max_len, name="kern_tile",
                           kv_layout="paged", kv_block_size=bs,
                           prefill_chunk=kk)
    assert eng.decode_kernels
    assert eng._kernel_path() == "fused-pallas, 128 positions a step"
    rng = np.random.RandomState(15)
    cases = [(_prompt(rng, n), m) for n, m in
             [(120, 12), (5, 6), (127, 4), (128, 3), (30, 9), (141, 8)]]
    with assert_no_retrace(lambda: eng.step_trace_count,
                           "tiled chunk churn"):
        bat = GenerationBatcher(eng, default_max_tokens=8)
        results, excs = _drive(bat, cases)
        bat.close()
    assert all(e is None for e in excs), excs
    for (prompt, n), res in zip(cases, results):
        ids = np.asarray(transformer.lm_generate(
            p, prompt[None], max_len=max_len, num_heads=HEADS,
            prompt_lengths=np.asarray([prompt.size])))
        assert res["tokens"] == ids[0, prompt.size:prompt.size + n].tolist(), \
            f"prompt len {prompt.size}, n {n}"
    eng._paged.check()


@pytest.mark.slow
def test_supervisor_recovery_with_kernels_bit_identical(params,
                                                        paged_engine):
    """The PR-6 chaos case with the kernels compiled in: an injected
    decode-step fault rebuilds the pool and the supervisor re-seats
    every in-flight stream — all streams bit-identical to the
    reference-path oracle, ZERO extra traces (recovery re-runs the same
    compiled kernel step), exact fault counts, ledger balanced."""
    eng = paged_engine
    eng.metrics = ServingMetrics()
    rng = np.random.RandomState(13)
    cases = [(_prompt(rng), 4 + (i % 5)) for i in range(8)]
    ref = [_oracle(params, p, n) for p, n in cases]
    sup = Supervisor(breaker_threshold=10)
    bat = GenerationBatcher(eng, supervisor=sup)
    faults.install_spec("serving.decode_step:at=6")
    with assert_no_retrace(lambda: eng.step_trace_count,
                           "fused paged chaos recovery"):
        results, excs = _drive(bat, cases)
        bat.close()
    assert faults.fired_counts() == {"serving.decode_step": 1}
    faults.clear()
    assert all(e is None for e in excs), excs
    assert [r["tokens"] for r in results] == ref
    snap = eng.metrics.snapshot()
    assert snap["evictions"]["recovered"] >= 1
    assert snap["slot_reprefills_total"] >= 1
    eng._paged.check()
    assert eng.free_slots == eng.num_slots


# --------------------------------------------------- fusion-proof gate


@pytest.mark.slow
def test_fusion_proof_gate_both_directions(paged_engine):
    """perf/analytic.assert_decode_fused: clean on the fused step's
    post-optimization HLO, and the SAME detector flags the reference
    chain-gather step — the PR-3 de-fusion detector run in reverse."""
    eng = paged_engine
    t_span = eng._paged.tables.shape[1] * eng.block_size
    dkv = int(eng.params["enc"][0]["attn"]["wk"].shape[1])
    with dk.forced_mode("always"):
        fused_text = eng.lower().compile().as_text()
    perf_analytic.assert_decode_fused(fused_text, eng.num_slots, t_span,
                                      dkv)

    def staged(mode):
        # a FRESH jit wrapper per mode: the dispatch is read at trace
        # time and pjit caches the engine step's jaxpr by avals, so
        # flipping the mode around eng.lower() would silently reuse the
        # warm-up trace
        with dk.forced_mode(mode):
            def fn(p, c, tok, po, ln, tbl):
                return transformer.lm_decode_chunk_paged(p, tok, po, ln, c,
                                                         tbl, HEADS)
            return jax.jit(fn).lower(
                eng.params, eng._cache, eng._tokens, eng._pos, eng._len,
                eng._paged.tables).compile().as_text()

    ref_text = staged("off")
    hits = perf_analytic.chain_buffer_instrs(ref_text, eng.num_slots,
                                             t_span, dkv)
    assert hits, "detector missed the reference chain gather"
    with pytest.raises(AssertionError, match="full-chain"):
        perf_analytic.assert_decode_fused(ref_text, eng.num_slots,
                                          t_span, dkv)


def test_chain_buffer_detector_shapes():
    """The detector keys on leading-dim == S and exact element count, so
    the pool itself (leading dim num_blocks) and small row buffers never
    false-positive."""
    hlo = """ENTRY main {
  %p = f32[257,8,128]{2,1,0} parameter(0)
  %g = f32[4,6,8,32]{3,2,1,0} gather(f32[49,8,32]{2,1,0} %p2, s32[4,6,1]{2,1,0} %i)
  %r = f32[4,48,32]{2,1,0} reshape(f32[4,6,8,32]{3,2,1,0} %g)
  %small = f32[4,32]{1,0} add(f32[4,32]{1,0} %a, f32[4,32]{1,0} %b)
}"""
    hits = perf_analytic.chain_buffer_instrs(hlo, 4, 48, 32)
    assert len(hits) == 2           # the gather and its reshape
    assert not perf_analytic.chain_buffer_instrs(hlo, 8, 48, 32)


# ------------------------------------------------------------- rope


@pytest.mark.slow
def test_rope_trunk_slab_kernel_bit_identical():
    """Rope rotation happens BEFORE the kernel (q/k_new pre-rotated, the
    cache stores rotated keys) — the kernel path must keep the rope
    trunk's engine streams token-identical to lm_generate too."""
    rope_params = transformer.init(jax.random.PRNGKey(2), src_vocab=VOCAB,
                                   trg_vocab=1, d_model=D_MODEL,
                                   num_heads=HEADS, dff=64,
                                   enc_layers=LAYERS, dec_layers=0,
                                   max_len=MAX_LEN, pos_type="rope")
    with dk.forced_mode("always"):
        eng = DecodeEngine(rope_params, num_heads=HEADS, num_slots=2,
                           max_len=MAX_LEN, name="kern_rope",
                           pos_type="rope")
    assert eng.decode_kernels
    bat = GenerationBatcher(eng, default_max_tokens=6)
    rng = np.random.RandomState(14)
    prompt = _prompt(rng, 6)
    res = bat.submit(prompt, max_tokens=6).result(60)
    bat.close()
    padded = np.zeros((1, 8), np.int32)
    padded[0, :prompt.size] = prompt
    ids = np.asarray(transformer.lm_generate(
        rope_params, padded, max_len=MAX_LEN, num_heads=HEADS,
        prompt_lengths=np.asarray([prompt.size]), pos_type="rope"))
    assert res["tokens"] == ids[0, prompt.size:prompt.size + 6].tolist()

"""Decoder-only causal LM (transformer.lm_loss): packed rows train every
segment as if alone, and the loss composes with sequence parallelism and
the zigzag causal ring — the modern no-padding training plane the
reference's Argument.sequenceStartPositions pointed toward."""

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.core.sequence import SequenceBatch, pack_sequences
from paddle_tpu.models import transformer

V, DM, HEADS, T = 48, 16, 2, 16

needs_8 = pytest.mark.skipif(len(jax.devices()) < 8,
                             reason="needs 8 virtual devices")


def _params(max_len=T):
    return transformer.init(jax.random.PRNGKey(0), src_vocab=V, trg_vocab=1,
                            d_model=DM, dff=32, enc_layers=2, dec_layers=0,
                            max_len=max_len)


def _packed(np_rng, lens=(5, 9, 7, 3, 12, 4), t=T):
    seqs = [np_rng.randint(3, V, n) for n in lens]
    data, seg, pos = pack_sequences(seqs, max_len=t)
    b = data.shape[0]
    return (SequenceBatch(jnp.asarray(data), jnp.full((b,), t, jnp.int32)),
            jnp.asarray(seg), jnp.asarray(pos), seqs)


def test_lm_packed_matches_one_segment_per_row(np_rng):
    """Token-mean loss over PACKED rows == the same sequences laid out one
    per (padded) row: packing changes the layout, not the objective."""
    params = _params()
    tokens, seg, pos, seqs = _packed(np_rng)

    packed = transformer.lm_loss(params, tokens, HEADS, segment_ids=seg,
                                 positions=pos)

    b = len(seqs)
    data1 = np.zeros((b, T), np.int32)
    seg1 = np.zeros((b, T), np.int32)
    pos1 = np.zeros((b, T), np.int32)
    for i, s in enumerate(seqs):
        data1[i, :len(s)] = s
        seg1[i, :len(s)] = 1
        pos1[i, :len(s)] = np.arange(len(s))
    alone = transformer.lm_loss(
        params,
        SequenceBatch(jnp.asarray(data1), jnp.full((b,), T, jnp.int32)),
        HEADS, segment_ids=jnp.asarray(seg1), positions=jnp.asarray(pos1))
    np.testing.assert_allclose(float(packed), float(alone), rtol=2e-5)


def test_lm_unpacked_matches_single_segment_labels(np_rng):
    """The unpacked path (lengths mask) produces the same loss as the
    explicit one-segment-per-row packed encoding of the same batch."""
    params = _params()
    lens = np.asarray([6, 11, 16, 3])
    b = len(lens)
    data = np.zeros((b, T), np.int32)
    seg = np.zeros((b, T), np.int32)
    pos = np.zeros((b, T), np.int32)
    rng = np_rng
    for i, n in enumerate(lens):
        data[i, :n] = rng.randint(3, V, n)
        seg[i, :n] = 1
        pos[i, :n] = np.arange(n)
    sb = SequenceBatch(jnp.asarray(data), jnp.asarray(lens, jnp.int32))
    unpacked = transformer.lm_loss(params, sb, HEADS)
    packed = transformer.lm_loss(
        params,
        SequenceBatch(jnp.asarray(data), jnp.full((b,), T, jnp.int32)),
        HEADS, segment_ids=jnp.asarray(seg), positions=jnp.asarray(pos))
    np.testing.assert_allclose(float(unpacked), float(packed), rtol=2e-5)


def test_lm_loss_trains(np_rng):
    """60 SGD steps on a copy-pattern corpus halve the loss — the LM path
    is trainable end to end, grads flow through the tied embedding."""
    from paddle_tpu import optim
    params = _params()
    tokens, seg, pos, _ = _packed(np_rng, lens=(9, 9, 9, 9, 9))
    opt = optim.Adam(learning_rate=3e-3)
    state = opt.init(params)

    @jax.jit
    def step(p, s):
        l, g = jax.value_and_grad(
            lambda p: transformer.lm_loss(p, tokens, HEADS,
                                          segment_ids=seg,
                                          positions=pos))(p)
        p2, s2 = opt.update(g, s, p)
        return p2, s2, l

    first = None
    for i in range(60):
        params, state, l = step(params, state)
        first = first if first is not None else float(l)
    assert float(l) < 0.6 * first, (first, float(l))


@needs_8
@pytest.mark.parametrize("zigzag", [False, True], ids=["ring", "zigzag"])
def test_lm_packed_seq_parallel_matches_single(np_rng, zigzag):
    """Packed causal LM under a data x seq mesh (plain and zigzag ring)
    reproduces the single-device loss and grads — all three marquee
    features (packing, causal LM, sequence parallelism) in one call."""
    from paddle_tpu.parallel import MeshConfig, make_mesh
    mesh = make_mesh(MeshConfig(data=2, seq=4))
    params = _params()
    tokens, seg, pos, _ = _packed(np_rng)

    def lm(p, mesh_arg, zz):
        return transformer.lm_loss(p, tokens, HEADS, segment_ids=seg,
                                   positions=pos, mesh=mesh_arg, zigzag=zz)

    l1, g1 = jax.jit(jax.value_and_grad(
        lambda p: lm(p, None, False)))(params)
    l2, g2 = jax.jit(jax.value_and_grad(
        lambda p: lm(p, mesh, zigzag)))(params)
    np.testing.assert_allclose(float(l2), float(l1), rtol=2e-4)
    for a, b_ in zip(jax.tree_util.tree_leaves(g2),
                     jax.tree_util.tree_leaves(g1)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=5e-3, atol=1e-4)


def test_lm_zigzag_guards():
    params = _params()
    tokens = SequenceBatch(jnp.zeros((2, T), jnp.int32),
                           jnp.full((2,), T, jnp.int32))
    with pytest.raises(ValueError, match="seq > 1"):
        transformer.lm_loss(params, tokens, HEADS, zigzag=True)


def _oracle_greedy(params, prompt, max_len, heads=HEADS):
    """Full-recompute greedy rollout via lm_logits — the numerics oracle
    for the KV-cached lm_generate."""
    b, tp = prompt.shape
    ids = np.zeros((b, max_len), np.int32)
    ids[:, :tp] = prompt
    for t in range(max_len - 1):
        sb = SequenceBatch(jnp.asarray(ids), jnp.full((b,), t + 1,
                                                      jnp.int32))
        logits = transformer.lm_logits(params, sb, heads)
        nxt = np.asarray(jnp.argmax(logits[:, t], axis=-1))
        if t + 1 < tp:
            continue
        ids[:, t + 1] = nxt
    return ids


def test_lm_generate_cached_matches_full_recompute(np_rng):
    """Greedy lm_generate (KV cache, one position per step) reproduces
    the full-sequence argmax rollout exactly."""
    params = _params(max_len=12)
    prompt = np_rng.randint(3, V, (3, 4)).astype(np.int32)
    got = np.asarray(transformer.lm_generate(params, prompt, max_len=12,
                                             num_heads=HEADS))
    want = _oracle_greedy(params, prompt, 12)
    np.testing.assert_array_equal(got, want)
    # prompt preserved
    np.testing.assert_array_equal(got[:, :4], prompt)


def test_lm_generate_sampling_and_eos(np_rng):
    params = _params(max_len=16)
    prompt = np_rng.randint(3, V, (4, 2)).astype(np.int32)
    ids = np.asarray(transformer.lm_generate(
        params, prompt, max_len=16, num_heads=HEADS, temperature=0.8,
        top_k=5, rng=jax.random.PRNGKey(3)))
    assert ids.shape == (4, 16)
    assert ((ids >= 0) & (ids < V)).all()
    # same rng -> same draw; different rng -> (overwhelmingly) different
    ids2 = np.asarray(transformer.lm_generate(
        params, prompt, max_len=16, num_heads=HEADS, temperature=0.8,
        top_k=5, rng=jax.random.PRNGKey(3)))
    np.testing.assert_array_equal(ids, ids2)

    # eos pinning: once a row emits eos, it keeps emitting eos
    eos = 7
    ids3 = np.asarray(transformer.lm_generate(
        params, prompt, max_len=16, num_heads=HEADS, temperature=1.5,
        rng=jax.random.PRNGKey(5), eos_id=eos))
    for row in ids3:
        hit = np.where(row == eos)[0]
        if hit.size and hit[0] >= 2:           # ignore eos inside prompt
            assert (row[hit[0]:] == eos).all()

    # guards
    with pytest.raises(ValueError, match="needs rng"):
        transformer.lm_generate(params, prompt, max_len=16,
                                num_heads=HEADS, temperature=0.5)
    with pytest.raises(ValueError, match="prompt length"):
        transformer.lm_generate(params, np.zeros((1, 20), np.int32),
                                max_len=16, num_heads=HEADS)


def test_lm_generate_eos_in_prompt_does_not_pin(np_rng):
    """An eos-valued token INSIDE the prompt (bos==eos vocabs, separator
    tokens) must not suppress the continuation — only generated eos
    pins a row."""
    params = _params(max_len=12)
    eos = 5
    prompt = np.asarray([[eos, 10, 11, 12]], np.int32)
    ids = np.asarray(transformer.lm_generate(
        params, prompt, max_len=12, num_heads=HEADS, eos_id=eos))
    np.testing.assert_array_equal(ids[0, :4], prompt[0])
    # greedy continuation must equal the no-eos run until it first
    # GENERATES eos (if ever) — i.e. eos handling changed nothing early
    ids_free = np.asarray(transformer.lm_generate(
        params, prompt, max_len=12, num_heads=HEADS))
    gen, free = ids[0, 4:], ids_free[0, 4:]
    cut = np.where(free == eos)[0]
    upto = cut[0] + 1 if cut.size else len(free)
    np.testing.assert_array_equal(gen[:upto], free[:upto])


def test_lm_generate_ragged_prompts_match_per_row(np_rng):
    """One batch with per-row prompt lengths == each row generated alone
    with its exact prompt (greedy): the ragged path changes batching,
    not numerics."""
    params = _params(max_len=14)
    tp = 6
    lens = [2, 6, 4]
    prompt = np_rng.randint(3, V, (3, tp)).astype(np.int32)
    prompt[0, lens[0]:] = 0          # pad values must not matter
    prompt[2, lens[2]:] = V - 1
    got = np.asarray(transformer.lm_generate(
        params, prompt, max_len=14, num_heads=HEADS,
        prompt_lengths=np.asarray(lens)))
    for i, li in enumerate(lens):
        alone = np.asarray(transformer.lm_generate(
            params, prompt[i:i + 1, :li], max_len=14, num_heads=HEADS))
        np.testing.assert_array_equal(got[i], alone[0], err_msg=f"row {i}")
    # bad lengths fail fast
    with pytest.raises(ValueError, match="prompt_lengths"):
        transformer.lm_generate(params, prompt, max_len=14,
                                num_heads=HEADS,
                                prompt_lengths=np.asarray([2, 9, 4]))


@pytest.mark.slow   # multi-second end-to-end; nightly lane
def test_lm_demo_runs():
    """demo/lm end to end at smoke scale: trains, then prints greedy and
    sampled continuations (the 15th demo family stays green)."""
    import os
    import subprocess
    import sys
    demo = os.path.join(os.path.dirname(__file__), "..", "demo", "lm",
                        "train_and_sample.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, demo, "--epochs", "1"],
                       capture_output=True, text=True, env=env,
                       timeout=480)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "greedy" in r.stdout and "sampled" in r.stdout, r.stdout

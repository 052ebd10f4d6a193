"""Decoder-only language-model demo (post-reference capability:
models/transformer.lm_loss + lm_generate).

A char-level LM learns a tiny synthetic grammar (zero egress), trained
PADDING-FREE — ragged sentences first-fit-packed into full rows by the
`packed` reader decorator, attention block-diagonal per segment — then
samples continuations through the KV-cached generator.  The same loss
scales to a data x seq mesh with zigzag ring attention
(lm_loss(mesh=..., zigzag=True)); see docs/cluster_training.md.

Run:  python demo/lm/train_and_sample.py [--epochs 12]
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import numpy as np

# the grammar: subject verb object ".", tokenized per char group
WORDS = {
    "sub": ["cat", "dog", "bird"],
    "verb": ["sees", "likes"],
    "obj": ["fish", "seed", "bone"],
}
CHARS = sorted({c for ws in WORDS.values() for w in ws for c in w}
               | {" ", "."})
PAD, BOS = 0, 1
VOCAB = len(CHARS) + 2
ENC = {c: i + 2 for i, c in enumerate(CHARS)}
DEC = {i: c for c, i in ENC.items()}


def sentences(n, seed=0):
    rng = np.random.RandomState(seed)
    for _ in range(n):
        s = " ".join([rng.choice(WORDS["sub"]), rng.choice(WORDS["verb"]),
                      rng.choice(WORDS["obj"])]) + "."
        yield np.asarray([BOS] + [ENC[c] for c in s], np.int32)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=12)
    ap.add_argument("--max_len", type=int, default=24)
    ap.add_argument("--batch", type=int, default=16)
    args = ap.parse_args(argv)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.data import reader as reader_mod
    from paddle_tpu.models import transformer
    from paddle_tpu import optim

    params = transformer.init(jax.random.PRNGKey(0), src_vocab=VOCAB,
                              trg_vocab=1, d_model=48, dff=96,
                              enc_layers=2, dec_layers=0,
                              max_len=args.max_len)
    opt = optim.Adam(learning_rate=3e-3)
    state = opt.init(params)
    packed = reader_mod.batch(
        reader_mod.packed(lambda: sentences(512), args.max_len,
                          buffer_size=64), args.batch, drop_last=True)

    @jax.jit
    def step(p, s, data, seg, pos):
        toks = SequenceBatch(data, jnp.full((data.shape[0],),
                                            args.max_len, jnp.int32))
        l, g = jax.value_and_grad(lambda p: transformer.lm_loss(
            p, toks, 4, segment_ids=seg, positions=pos))(p)
        p2, s2 = opt.update(g, s, p)
        return p2, s2, l

    loss = None
    for epoch in range(args.epochs):
        for rows in packed():
            params, state, loss = step(
                params, state,
                jnp.asarray(np.stack([r[0] for r in rows])),
                jnp.asarray(np.stack([r[1] for r in rows])),
                jnp.asarray(np.stack([r[2] for r in rows])))
        print(f"epoch {epoch}: loss {float(loss):.4f}", flush=True)

    # sample continuations from subject prompts (greedy + temperature)
    for prompt_txt in ("cat ", "bird "):
        prompt = np.asarray([[BOS] + [ENC[c] for c in prompt_txt]],
                            np.int32)
        ids = np.asarray(transformer.lm_generate(
            params, prompt, max_len=args.max_len, num_heads=4))[0]
        txt = "".join(DEC.get(int(i), "") for i in ids[1:])
        print(f"greedy   {prompt_txt!r} -> {txt!r}", flush=True)
        ids = np.asarray(transformer.lm_generate(
            params, prompt, max_len=args.max_len, num_heads=4,
            temperature=0.7, top_k=8, rng=jax.random.PRNGKey(7)))[0]
        txt = "".join(DEC.get(int(i), "") for i in ids[1:])
        print(f"sampled  {prompt_txt!r} -> {txt!r}", flush=True)
    return float(loss)


if __name__ == "__main__":
    main()

"""Dual-backend differential runner (SURVEY §4 pattern 1).

The reference's strongest numeric tool runs every op on CpuMatrix and
GpuMatrix and compares results within epsilon (math/tests/
test_matrixCompare.cpp, TensorCheck.h).  The TPU-native equivalent: execute
the SAME jitted forward + gradient for every case in the registry-driven
layer sweep (tests/test_layer_grad_sweep.py CASES) on one backend per
process and dump the arrays; a comparing test diffs a CPU dump against a
TPU dump.

Run (one process per platform — the platform must be pinned before any
backend touch; the TPU dump runs on the chip, through the chip tool):

    python -m paddle_tpu.testing.tpu_diff cpu /tmp/diff_cpu.npz
    python -m paddle_tpu.testing.tpu_diff tpu /tmp/diff_tpu.npz

Determinism across platforms: param init uses jax.random (threefry —
platform-invariant), case inputs use seeded numpy, and matmul precision is
forced to HIGHEST so the MXU does full-f32 passes instead of bf16x3.
"""

import os
import sys
import zlib


def _pin_platform(platform):
    """``platform`` is a JAX platform name ("cpu" | "tpu"); a backend that
    cannot initialise raises at the first device touch."""
    import jax
    os.environ["JAX_PLATFORMS"] = platform
    jax.config.update("jax_platforms", platform)
    jax.config.update("jax_default_matmul_precision", "highest")


def run_cases(only=None, out_dir=None):
    """Build every sweep case, run forward (mode='test') + grads of the
    scalar loss wrt all float params, return {name: {label: np.ndarray}}.
    With out_dir, each case is written to <out_dir>/<case>.npz as it
    completes and already-present cases are skipped (resumable — remote TPU
    compiles make a full cold sweep take tens of minutes)."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, repo)
    from tests.test_layer_grad_sweep import CASES, B0, T0
    from paddle_tpu.layers.graph import Topology, reset_names, value_data

    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
    results = {}
    for name in sorted(CASES):
        if only and name not in only:
            continue
        if out_dir and os.path.exists(os.path.join(out_dir, name + ".npz")):
            print(f"[tpu_diff] {name}: cached", file=sys.stderr, flush=True)
            continue
        build, _ = CASES[name]
        reset_names()
        r = np.random.RandomState(zlib.crc32(name.encode()) % (2 ** 31))
        outs, feed = build(r, B0, T0)
        outs = outs if isinstance(outs, list) else [outs]
        topo = Topology(outs)
        params = topo.init(jax.random.PRNGKey(0))
        # device arrays, not numpy: a numpy feed closed over by jit breaks
        # ops that numpy-index the feed with a traced array (conv_shift)
        feed = jax.tree_util.tree_map(jnp.asarray, feed)

        def fwd(p):
            out = topo.apply(p, feed, mode="test", rng=jax.random.PRNGKey(7))
            vals = out if isinstance(out, tuple) else (out,)
            return [value_data(v) for v in vals]

        def loss(p):
            return sum(jnp.mean(d.astype(jnp.float32)) for d in fwd(p))

        def thunk(fwd=fwd, loss=loss, params=params):
            vals = jax.jit(fwd)(params)
            rec = {f"out{i}": np.asarray(v, np.float32)
                   for i, v in enumerate(vals)}
            rec.update(_grad_arrays(jax.jit(jax.grad(loss))(params)))
            return rec
        _run_case(name, thunk, out_dir, results)
    return results


def _grad_arrays(grads):
    """Float grad leaves as {gradPATH: f32 array} — the one flattening
    every runner shares."""
    import numpy as np
    import jax
    out = {}
    for path, g in jax.tree_util.tree_flatten_with_path(grads)[0]:
        if np.issubdtype(np.asarray(g).dtype, np.floating):
            out["grad" + jax.tree_util.keystr(path)] = (
                np.asarray(g, np.float32))
    return out


def _run_case(cname, thunk, out_dir, results):
    """Shared per-case scaffolding (cache skip, __error__ capture in the
    format test_tpu_differential parses, save, progress print) — one
    definition for all three runners so the dump format cannot diverge.
    Assumes the caller already checked the cache when it needed to skip
    building inputs too; a second check here is cheap and keeps direct
    callers safe."""
    import numpy as np
    if out_dir and os.path.exists(os.path.join(out_dir, cname + ".npz")):
        print(f"[tpu_diff] {cname}: cached", file=sys.stderr, flush=True)
        return
    try:
        rec = thunk()
    except Exception as e:   # record, don't abort the sweep
        rec = {"__error__": np.frombuffer(
            f"{type(e).__name__}: {e}"[:500].encode(), np.uint8)}
    results[cname] = rec
    if out_dir:
        np.savez_compressed(os.path.join(out_dir, cname + ".npz"), **rec)
    print(f"[tpu_diff] {cname}: {len(rec)} arrays", file=sys.stderr,
          flush=True)


# name -> zero-arg ctor; the supervisor derives the __optim__ resume marker
# from the LAST sorted name, so additions stay resume-safe automatically
_OPTIM_CTORS = {
    "momentum": lambda: _optim().Momentum(0.1, momentum=0.9),
    "nesterov": lambda: _optim().Momentum(0.1, momentum=0.9, nesterov=True),
    "adagrad": lambda: _optim().AdaGrad(0.1),
    "adadelta": lambda: _optim().AdaDelta(rho=0.95),
    "rmsprop": lambda: _optim().RMSProp(0.01),
    "decayed_adagrad": lambda: _optim().DecayedAdaGrad(0.1),
    "adam": lambda: _optim().Adam(0.01),
    "adamax": lambda: _optim().AdaMax(0.01),
}


def _optim():
    from paddle_tpu import optim
    return optim


def _optim_marker():
    return "optim_" + sorted(_OPTIM_CTORS)[-1]


def run_optimizer_cases(out_dir=None):
    """Differential coverage for the optimizer zoo (reference
    math/tests/test_TrainingAlgorithm.cpp compares each update kernel
    CPU-vs-GPU): run 5 chained updates of every optimizer on seeded
    params/grads and dump the resulting params + slots."""
    import numpy as np
    import jax
    import jax.numpy as jnp

    mk = _OPTIM_CTORS
    r = np.random.RandomState(11)
    params = {"w": jnp.asarray(r.randn(17, 9), jnp.float32),
              "b": jnp.asarray(r.randn(9), jnp.float32)}
    grad_seq = [jax.tree_util.tree_map(
        lambda x, i=i: jnp.asarray(
            np.random.RandomState(100 + i).randn(*x.shape), jnp.float32),
        params) for i in range(5)]

    results = {}
    for name, ctor in sorted(mk.items()):
        def thunk(ctor=ctor):
            opt = ctor()
            state = opt.init(params)

            @jax.jit
            def chain(p, s):
                for g in grad_seq:
                    p, s = opt.update(g, s, p)
                return p, s

            p, s = chain(params, state)
            rec = {}
            for k, v in jax.tree_util.tree_flatten_with_path(
                    {"p": p, "s": s})[0]:
                if np.issubdtype(np.asarray(v).dtype, np.floating):
                    rec[jax.tree_util.keystr(k)] = np.asarray(v, np.float32)
            return rec
        _run_case(f"optim_{name}", thunk, out_dir, results)
    return results


def _model_case_packed_lm():
    """Packed causal LM (transformer.lm_loss): segments + within-segment
    positions + causal attention + tied projection, fwd + grads."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.sequence import SequenceBatch, pack_sequences
    from paddle_tpu.models import transformer
    r = np.random.RandomState(3)
    seqs = [r.randint(3, 48, n) for n in (5, 9, 7, 3, 12, 4)]
    data, seg, pos = pack_sequences(seqs, max_len=16)
    b = data.shape[0]
    params = transformer.init(jax.random.PRNGKey(0), src_vocab=48,
                              trg_vocab=1, d_model=16, dff=32,
                              enc_layers=2, dec_layers=0, max_len=16)
    tokens = SequenceBatch(jnp.asarray(data),
                           jnp.full((b,), 16, jnp.int32))
    seg, pos = jnp.asarray(seg), jnp.asarray(pos)

    def loss(p):
        return transformer.lm_loss(p, tokens, 2, segment_ids=seg,
                                   positions=pos)
    return params, loss


def _model_case_chunked_segment_attn():
    """chunked_attention with segment ids (the O(T) packed-attention
    numerics core), fwd + grads wrt the inputs."""
    import numpy as np
    import jax.numpy as jnp
    from paddle_tpu.core.sequence import pack_sequences
    from paddle_tpu.ops import attention as att
    r = np.random.RandomState(5)
    seqs = [r.randint(0, 9, n) for n in (11, 7, 13, 5, 9, 18)]
    _, seg, _ = pack_sequences(seqs, max_len=32)
    b = seg.shape[0]
    x = jnp.asarray(r.randn(b, 2, 32, 8) * 0.5, jnp.float32)
    segj = jnp.asarray(seg)
    m = (segj > 0).astype(jnp.float32)

    def loss(p):
        out = att.chunked_attention(p["x"], p["x"], p["x"], causal=True,
                                    q_segment_ids=segj, q_chunk=8,
                                    k_chunk=8, key_mask=m)
        return jnp.sum((out * m[:, None, :, None]) ** 2)
    return {"x": x}, loss


def _model_case_mt_loss():
    """transformer.loss (encoder + causal decoder + cross-attention +
    label smoothing): the flagship MT train objective."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from paddle_tpu.core.sequence import SequenceBatch
    from paddle_tpu.models import transformer
    r = np.random.RandomState(7)
    params = transformer.init(jax.random.PRNGKey(1), src_vocab=48,
                              trg_vocab=48, d_model=16, dff=32,
                              enc_layers=1, dec_layers=1, max_len=12)
    mk = lambda: SequenceBatch(
        jnp.asarray(r.randint(3, 48, (3, 12)), jnp.int32),
        jnp.asarray(r.randint(6, 13, (3,)), jnp.int32))
    src, trg_in, trg_next = mk(), mk(), mk()

    def loss(p):
        return transformer.loss(p, src, trg_in, trg_next, num_heads=2)
    return params, loss


def _model_case_ring1_attention():
    """ring_attention on a 1-device mesh: compiles the shard_map +
    ppermute + online-softmax rotation on the real backend (the
    multi-chip numerics core, single-chip-verifiable half)."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from paddle_tpu.parallel.ring_attention import ring_attention
    r = np.random.RandomState(9)
    q = jnp.asarray(r.randn(2, 2, 16, 8) * 0.5, jnp.float32)
    k = jnp.asarray(r.randn(2, 2, 16, 8) * 0.5, jnp.float32)
    v = jnp.asarray(r.randn(2, 2, 16, 8) * 0.5, jnp.float32)
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("seq",))

    def loss(p):
        out = ring_attention(p["q"], p["k"], p["v"], mesh, causal=True)
        return jnp.sum(out ** 2)
    return {"q": q, "k": k, "v": v}, loss


_MODEL_CASES = {
    "packed_lm": _model_case_packed_lm,
    "chunked_segment_attn": _model_case_chunked_segment_attn,
    "mt_loss": _model_case_mt_loss,
    "ring1_attention": _model_case_ring1_attention,
}


def _model_marker():
    return "model_" + sorted(_MODEL_CASES)[-1]


def run_model_cases(out_dir=None):
    """Differential coverage for the model-level paths the layer sweep
    can't reach: packed causal LM, segment-packed chunked attention, the
    flagship MT loss, and the ring rotation (1-device)."""
    import numpy as np
    import jax

    results = {}
    for name, build in sorted(_MODEL_CASES.items()):
        def thunk(build=build):
            params, loss = build()
            val, grads = jax.jit(jax.value_and_grad(loss))(params)
            rec = {"out0": np.asarray(val, np.float32)}
            rec.update(_grad_arrays(grads))
            return rec
        _run_case(f"model_{name}", thunk, out_dir, results)
    return results


def _code_revision():
    from paddle_tpu.utils.revision import code_revision
    return code_revision()


def consolidate(out_dir, out_path):
    import numpy as np
    flat = {}
    for fn in sorted(os.listdir(out_dir)):
        if not fn.endswith(".npz"):
            continue
        case = fn[:-4]
        with np.load(os.path.join(out_dir, fn)) as z:
            for label in z.files:
                flat[f"{case}::{label}"] = z[label]
    # stamp with the revision the CACHE was produced at (REVISION is
    # written by supervise before any case runs) — not the possibly-moved
    # current HEAD
    rev_file = os.path.join(out_dir, "REVISION")
    rev = open(rev_file).read().strip() if os.path.exists(rev_file) \
        else _code_revision()
    flat["__revision__"] = np.frombuffer(rev.encode(), np.uint8).copy()
    np.savez_compressed(out_path, **flat)
    return len(flat)


def _is_error_record(path):
    import numpy as np
    try:
        with np.load(path) as z:
            return list(z.files) == ["__error__"]
    except Exception:   # unreadable/corrupt record: treat as retryable
        return True


def _case_names():
    repo = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    sys.path.insert(0, repo)
    from tests.test_layer_grad_sweep import CASES
    return sorted(CASES)


def supervise(platform, out_path, case_timeout=150.0, max_consec_fail=4):
    """One worker subprocess per case with a hard timeout — a hung compile
    can only be killed from outside the process (it blocks in C++ where no
    Python signal lands).  Consecutive-failure cap aborts the sweep when
    the backend itself is down rather than one case."""
    import shutil
    import subprocess
    import numpy as np
    out_dir = out_path + ".d"
    # the per-case resume cache is only valid for the code that wrote it:
    # a resumed dump mixing revisions would make the cross-platform compare
    # diff two different programs
    rev = _code_revision()
    rev_file = os.path.join(out_dir, "REVISION")
    keep_stamp = None
    if os.path.isdir(out_dir):
        old = open(rev_file).read().strip() \
            if os.path.exists(rev_file) else None
        if old is None:
            # pre-stamping cache: adopt it rather than destroy tens of
            # minutes of TPU compiles (its provenance is the operator's
            # responsibility; from now on changes invalidate it properly)
            print("[tpu_diff] adopting unstamped case cache as current "
                  "revision", file=sys.stderr, flush=True)
        elif rev == "unknown":
            # can't VERIFY the cache ('unknown' means git is unavailable,
            # not a different revision) — keep it and its concrete stamp
            print("[tpu_diff] code revision unverifiable (no git); "
                  "keeping existing case cache", file=sys.stderr,
                  flush=True)
            keep_stamp = old
        elif old != rev:
            print(f"[tpu_diff] clearing stale case cache ({old} != "
                  f"{rev})", file=sys.stderr, flush=True)
            shutil.rmtree(out_dir)
    os.makedirs(out_dir, exist_ok=True)
    with open(rev_file, "w") as f:
        f.write((keep_stamp or rev) + "\n")
    retry_errors = os.environ.get("TPU_DIFF_RETRY_ERRORS", "0") == "1"
    consec = 0
    names = _case_names() + ["__optim__", "__models__"]
    group_markers = {"__optim__": _optim_marker,
                     "__models__": _model_marker}
    group_subcases = {
        "__optim__": lambda: [f"optim_{n}" for n in _OPTIM_CTORS],
        "__models__": lambda: [f"model_{n}" for n in _MODEL_CASES]}
    for name in names:
        # marker must be the LAST file the worker writes (sorted order), or
        # a mid-sweep kill would make resume skip the remainder
        marker = os.path.join(
            out_dir,
            (group_markers[name]() if name in group_markers else name)
            + ".npz")
        deleted_stale = False
        if retry_errors:
            # drop error-only records so the worker recomputes them; for
            # a group that means ANY sub-case record, not just the marker
            # (the worker skips per-sub-case caches)
            stale = ([os.path.join(out_dir, f"{c}.npz")
                      for c in group_subcases[name]()]
                     if name in group_subcases else [marker])
            for p in stale:
                if os.path.exists(p) and _is_error_record(p):
                    os.unlink(p)
                    deleted_stale = True
        # a healthy marker must not suppress the rerun that recomputes a
        # just-deleted stale record
        if os.path.exists(marker) and not deleted_stale:
            continue
        cmd = [sys.executable, "-m", "paddle_tpu.testing.tpu_diff",
               platform, out_path, name, "--worker"]
        try:
            subprocess.run(cmd, timeout=case_timeout, check=True,
                           stdout=subprocess.DEVNULL,
                           stderr=subprocess.DEVNULL)
            consec = 0
        except subprocess.TimeoutExpired:
            # record the timeout so the comparing test FAILS on it instead
            # of silently skipping (the test enumerates cases from the CPU
            # dump, so a missing record means the case never gets compared
            # at all); TPU_DIFF_RETRY_ERRORS=1 deletes these on the next
            # run.  Group cases get a record per MISSING sub-case —
            # completed sub-cases keep their caches (healthy results AND
            # genuine __error__ records the worker wrote before wedging:
            # a real error message beats a generic timeout), so a retried
            # group resumes from where the kill landed.
            timeout_rec = np.frombuffer(
                f"TimeoutExpired: worker exceeded {case_timeout}s "
                f"(wedged backend?)".encode(), np.uint8)
            missing = ([os.path.join(out_dir, c + ".npz")
                        for c in group_subcases[name]()]
                       if name in group_subcases else [marker])
            for p in missing:
                if not os.path.exists(p):
                    np.savez_compressed(p, __error__=timeout_rec)
            consec += 1
            print(f"[tpu_diff] {name}: TIMEOUT ({case_timeout}s)",
                  file=sys.stderr, flush=True)
        except subprocess.CalledProcessError as e:
            consec += 1
            print(f"[tpu_diff] {name}: worker rc={e.returncode}",
                  file=sys.stderr, flush=True)
        else:
            print(f"[tpu_diff] {name}: done", file=sys.stderr, flush=True)
        if consec >= max_consec_fail:
            print(f"[tpu_diff] aborting: {consec} consecutive failures "
                  "(backend down?)", file=sys.stderr, flush=True)
            return False
    n = consolidate(out_dir, out_path)
    print(f"[tpu_diff] wrote {n} arrays to {out_path}", file=sys.stderr)
    return True


def main():
    platform, out_path = sys.argv[1], sys.argv[2]
    rest = [a for a in sys.argv[3:] if a != "--worker"]
    worker = "--worker" in sys.argv
    only = set(rest[0].split(",")) if rest else None

    if not worker:
        ok = supervise(platform, out_path,
                       case_timeout=float(
                           os.environ.get("TPU_DIFF_CASE_TIMEOUT", "150")))
        sys.exit(0 if ok else 3)

    _pin_platform(platform)
    out_dir = out_path + ".d"
    if only == {"__optim__"}:
        run_optimizer_cases(out_dir=out_dir)
    elif only == {"__models__"}:
        run_model_cases(out_dir=out_dir)
    else:
        run_cases(only, out_dir=out_dir)


if __name__ == "__main__":
    main()

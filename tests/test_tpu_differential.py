"""CPU-vs-TPU differential comparison (SURVEY §4 pattern 1; reference
math/tests/test_matrixCompare.cpp runs every op on CpuMatrix+GpuMatrix and
compares within epsilon).

Two-process protocol (the suite pins jax to the virtual CPU mesh, and a
platform cannot be re-pinned after backend init):

    python -m paddle_tpu.testing.tpu_diff cpu     /tmp/diff_cpu.npz
    python -m paddle_tpu.testing.tpu_diff tpu     /tmp/diff_tpu.npz  # on the chip
    PADDLE_TPU_DIFF="/tmp/diff_cpu.npz:/tmp/diff_tpu.npz" pytest \
        tests/test_tpu_differential.py

Skipped unless PADDLE_TPU_DIFF points at the two dumps — the dumps need a
real chip, which CI boxes don't have.
"""

import functools
import os

import numpy as np
import pytest

_SPEC = os.environ.get("PADDLE_TPU_DIFF", "")
_PATHS = _SPEC.split(":")
_READY = len(_PATHS) == 2 and all(os.path.exists(p) for p in _PATHS)


@functools.lru_cache(maxsize=1)
def _load():
    cpu_path, tpu_path = _PATHS
    return np.load(cpu_path), np.load(tpu_path)


pytestmark = pytest.mark.skipif(
    not _READY,
    reason="PADDLE_TPU_DIFF=cpu.npz:tpu.npz not set (needs a TPU dump)")


def _cases():
    if not _READY:
        return []
    cpu, _ = _load()
    return sorted({k.split("::")[0] for k in cpu.files
                   if not k.startswith("__")})


def test_same_code_revision():
    """Both dumps must come from the same code state — a resumed cache
    from an older revision would diff two different programs."""
    cpu, tpu = _load()
    revs = []
    for z in (cpu, tpu):
        revs.append(bytes(z["__revision__"]).decode()
                    if "__revision__" in z.files else "<unstamped>")
    for r in revs:
        assert r not in ("unknown", "<unstamped>"), (
            f"dump revision unverifiable ({revs}) — regenerate with git "
            "available so provenance can be checked")
    assert revs[0] == revs[1], (
        f"dump revision mismatch: cpu={revs[0]} tpu={revs[1]} — "
        "regenerate both dumps at the current revision")


@pytest.mark.parametrize("case", _cases())
def test_case_matches(case):
    cpu, tpu = _load()
    cpu_keys = {k for k in cpu.files if k.startswith(case + "::")}
    tpu_keys = {k for k in tpu.files if k.startswith(case + "::")}
    assert cpu_keys == tpu_keys, (cpu_keys ^ tpu_keys)
    for k in sorted(cpu_keys):
        if k.endswith("__error__"):
            msg_c = bytes(cpu[k]).decode()
            msg_t = bytes(tpu[k]).decode()
            # a timeout means the case was never numerically compared —
            # that must FAIL, not hide behind the same-error exemption
            assert not msg_c.startswith("TimeoutExpired"), (k, msg_c)
            assert not msg_t.startswith("TimeoutExpired"), (k, msg_t)
            # an identical in-case failure on both platforms is a sweep
            # harness limitation, not a numerics divergence — surface it
            print(f"{k}: {msg_c[:120]}")
            assert msg_c[:80] == msg_t[:80]
            continue
        a, b = cpu[k], tpu[k]
        assert a.shape == b.shape, k
        scale = max(np.abs(a).max(), 1.0)
        # HIGHEST matmul precision on the MXU: f32-comparable; transcendental
        # op tables differ slightly between backends
        np.testing.assert_allclose(
            b, a, rtol=5e-3, atol=5e-4 * scale,
            err_msg=f"{k}: CPU and TPU disagree")

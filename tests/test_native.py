"""Native data-path runtime tests (C++ dataio: packing, record IO, prefetch
pool).  Pure host-side — no JAX needed.

The .so binaries are NOT committed (gitignored); `native.build.ensure`
rebuilds them on demand the first time the module is touched, which the
cold-build test below proves from a binary-less state."""

import os
import shutil
import struct
import subprocess
import sys
import tempfile

import numpy as np
import pytest

from paddle_tpu import native


_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_DIR = os.path.join(_ROOT, "paddle_tpu", "native")

# applied per-test (NOT module-wide): the two gate tests below must run
# even where the lib can't build — a host without g++ is exactly where a
# committed stale .so would otherwise slip through
needs_lib = pytest.mark.skipif(
    not native.is_available(),
    reason="native lib not built (python -m paddle_tpu.native.build)")


def test_no_binaries_committed():
    """The shared libraries are build artifacts: gitignored, rebuilt on
    demand — a committed .so would go stale against its source silently."""
    r = subprocess.run(["git", "ls-files", "--", "*.so"], cwd=_ROOT,
                       capture_output=True, text=True)
    if r.returncode != 0:
        pytest.skip("not a git checkout")
    assert r.stdout.strip() == "", (
        f"committed binaries found: {r.stdout} — git rm them; "
        "native/build.py builds on demand")


def test_analysis_baseline_committed_and_parseable():
    """The static-analyzer allow-list rides the same git gate: the
    committed baseline must exist IN git (not just on disk — an
    untracked baseline silently vanishes for the next clone, turning
    every documented exception into a red gate) and must parse under
    the strict loader (every entry keyed + justified)."""
    rel = os.path.join("paddle_tpu", "analysis", "baseline.json")
    r = subprocess.run(["git", "ls-files", "--", rel], cwd=_ROOT,
                       capture_output=True, text=True)
    if r.returncode != 0:
        pytest.skip("not a git checkout")
    assert r.stdout.strip() == rel, (
        f"{rel} is not committed — the analyzer gate needs its "
        "allow-list in git")
    from paddle_tpu.analysis import baseline
    entries = baseline.load(os.path.join(_ROOT, rel))
    for key, reason in entries.items():
        assert reason.strip(), f"baseline entry {key} has no reason"


@pytest.mark.slow   # full g++ rebuild in a subprocess; nightly lane
def test_cold_build_from_binaryless_checkout(tmp_path):
    """A clean checkout has no .so: the first native touch must build it
    (build.ensure).  Proven cold — the binary is moved aside and a fresh
    interpreter has to rebuild it before packing works.  (The fast lane
    still exercises the on-demand build implicitly: importing
    paddle_tpu.native on a fresh checkout runs build.ensure.)"""
    if shutil.which("g++") is None:
        pytest.skip("no g++ toolchain")
    so = os.path.join(_NATIVE_DIR, "libpaddle_tpu_dataio.so")
    backup = None
    if os.path.exists(so):
        backup = str(tmp_path / "dataio.so.bak")
        shutil.move(so, backup)
    code = ("import numpy as np\n"
            "from paddle_tpu import native\n"
            "assert native.is_available()\n"
            "out, lens = native.pack_i32([np.arange(3, dtype=np.int32)])\n"
            "assert out.shape == (1, 3) and lens[0] == 3\n"
            "print('COLD_BUILD_OK')\n")
    try:
        r = subprocess.run([sys.executable, "-c", code], cwd=_ROOT,
                           capture_output=True, text=True, timeout=300)
        assert "COLD_BUILD_OK" in r.stdout, r.stdout + r.stderr
        assert os.path.exists(so), "ensure() did not rebuild the .so"
    finally:
        if backup and not os.path.exists(so):
            shutil.move(backup, so)


@needs_lib
def test_pack_i32_matches_numpy(np_rng):
    seqs = [np_rng.randint(0, 100, (l,)).astype(np.int32) for l in (4, 1, 7)]
    out, lens = native.pack_i32(seqs, pad=-7)
    assert out.shape == (3, 7)
    for i, s in enumerate(seqs):
        np.testing.assert_array_equal(out[i, :len(s)], s)
        assert np.all(out[i, len(s):] == -7)
    np.testing.assert_array_equal(lens, [4, 1, 7])


@needs_lib
def test_pack_i32_truncates():
    out, lens = native.pack_i32([np.arange(10, dtype=np.int32)], max_len=4)
    np.testing.assert_array_equal(out[0], [0, 1, 2, 3])
    assert lens[0] == 4


@needs_lib
def test_pack_f32(np_rng):
    seqs = [np_rng.randn(l, 3).astype(np.float32) for l in (2, 5)]
    out, lens = native.pack_f32(seqs)
    assert out.shape == (2, 5, 3)
    np.testing.assert_allclose(out[0, :2], seqs[0])
    assert np.all(out[0, 2:] == 0)


@needs_lib
def test_densify_sparse():
    d = native.densify_sparse([0, 0, 2], [1, 3, 0], None, 3, 4)
    assert d[0, 1] == 1.0 and d[0, 3] == 1.0 and d[2, 0] == 1.0
    assert d.sum() == 3.0
    with pytest.raises(RuntimeError):
        native.densify_sparse([5], [0], None, 3, 4)  # row out of range


@needs_lib
@pytest.mark.parametrize("call", [
    lambda: native.pack_i32([np.arange(5, dtype=np.int32)] * 64),
    lambda: native.pack_f32([np.ones((3, 2), np.float32)] * 64),
    lambda: native.densify_sparse([0, 1], [1, 0], [0.5, 2.0], 2, 2),
], ids=["pack_i32", "pack_f32", "densify_sparse"])
def test_foreign_calls_leave_nothing_to_the_collector(call):
    """A pointer made by ``ndarray.ctypes.data_as`` refers to itself
    (ctypes.cast, bugs.python.org/issue12836): one per row was the training
    loop's cyclic garbage, and its full collections stalled the step."""
    import gc
    call()
    gc.collect()
    gc.disable()
    try:
        for _ in range(3):
            call()
        assert gc.collect() == 0
    finally:
        gc.enable()


@needs_lib
def test_record_roundtrip():
    p = os.path.join(tempfile.mkdtemp(), "x.ptrc")
    payloads = [struct.pack("<3i", i, i * 2, i * 3) for i in range(20)]
    with native.RecordWriter(p) as w:
        for pl in payloads:
            w.put(pl)
    with native.RecordReader(p) as r:
        got = list(r)
    assert got == payloads


@needs_lib
def test_record_reader_rejects_garbage():
    p = os.path.join(tempfile.mkdtemp(), "bad.ptrc")
    with open(p, "wb") as f:
        f.write(b"NOTAMAGIC")
    with pytest.raises(IOError):
        native.RecordReader(p)


@needs_lib
def test_prefetch_queue_streams_all():
    d = tempfile.mkdtemp()
    paths = []
    for fi in range(3):
        p = os.path.join(d, f"f{fi}.ptrc")
        with native.RecordWriter(p) as w:
            for i in range(10):
                w.put(bytes([fi, i]))
        paths.append(p)
    q = native.PrefetchQueue(4)
    for p in paths:
        q.add_file(p)
    got = []
    while True:
        item = q.pop(500)
        if item is None:
            break
        got.append(item)
    q.close()
    assert len(got) == 30
    assert sorted(got) == sorted(bytes([fi, i])
                                 for fi in range(3) for i in range(10))


@needs_lib
def test_prefetch_queue_timeout_empty():
    q = native.PrefetchQueue(4)
    assert q.pop(50) is None
    q.close()

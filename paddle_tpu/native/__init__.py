"""ctypes bindings for the native data-path runtime (src/dataio.cpp).

Build: python -m paddle_tpu.native.build   (g++ -O3 -shared; no deps).
Falls back gracefully — is_available() gates the fast paths; the pure-Python
feeder keeps working without the .so.
"""

import ctypes
import os

import numpy as np

_DIR = os.path.dirname(os.path.abspath(__file__))
_SO = os.path.join(_DIR, "libpaddle_tpu_dataio.so")
_lib = None


def _load():
    global _lib
    if _lib is not None:
        return _lib
    # binaries are not committed; build on first use and rebuild when the
    # source is newer than the binary (best-effort — ensure() no-ops fast
    # when the .so is current)
    from paddle_tpu.native import build as _build
    _build.ensure("dataio")
    if not os.path.exists(_SO):
        return None
    lib = ctypes.CDLL(_SO)
    lib.pt_pack_i32.restype = ctypes.c_int
    lib.pt_pack_f32.restype = ctypes.c_int
    lib.pt_densify_sparse.restype = ctypes.c_int
    lib.pt_writer_open.restype = ctypes.c_void_p
    lib.pt_writer_open.argtypes = [ctypes.c_char_p]
    lib.pt_writer_put.restype = ctypes.c_int
    lib.pt_writer_put.argtypes = [ctypes.c_void_p,
                                  ctypes.POINTER(ctypes.c_uint8),
                                  ctypes.c_uint32]
    lib.pt_writer_close.restype = ctypes.c_int
    lib.pt_writer_close.argtypes = [ctypes.c_void_p]
    lib.pt_reader_open.restype = ctypes.c_void_p
    lib.pt_reader_open.argtypes = [ctypes.c_char_p]
    lib.pt_reader_next.restype = ctypes.c_int64
    lib.pt_reader_next.argtypes = [ctypes.c_void_p,
                                   ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8))]
    lib.pt_reader_close.restype = ctypes.c_int
    lib.pt_reader_close.argtypes = [ctypes.c_void_p]
    lib.pt_queue_create.restype = ctypes.c_void_p
    lib.pt_queue_create.argtypes = [ctypes.c_int32]
    lib.pt_queue_add_file.restype = ctypes.c_int
    lib.pt_queue_add_file.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
    lib.pt_queue_pop.restype = ctypes.c_int64
    lib.pt_queue_pop.argtypes = [ctypes.c_void_p,
                                 ctypes.POINTER(ctypes.POINTER(ctypes.c_uint8)),
                                 ctypes.c_int32]
    lib.pt_queue_destroy.restype = ctypes.c_int
    lib.pt_queue_destroy.argtypes = [ctypes.c_void_p]
    _lib = lib
    return lib


def is_available():
    return _load() is not None


def feeder_status():
    """Which sequence packer the DataFeeder runs, and why:
    ``{"path": "native" | "python", "why": ...}``."""
    if is_available():
        return {"path": "native",
                "why": f"{os.path.basename(_SO)} built from src/dataio.cpp "
                       "(paddle_tpu/native/build.ensure)"}
    from paddle_tpu.native import build as _build
    return {"path": "python",
            "why": _build.why_unavailable("dataio") or f"{_SO} is missing"}


def _ptr(a):
    """Address of a contiguous array for a foreign call.  Not
    ``a.ctypes.data_as(...)``: that goes through ``ctypes.cast``, which
    leaves a pointer that refers to itself (bugs.python.org/issue12836) —
    one piece of cyclic garbage a row, which only the collector frees and
    whose full passes stall a training loop.  The caller keeps ``a`` alive
    across the call."""
    return ctypes.c_void_p(a.ctypes.data)


def _row_ptrs(arrs):
    """The rows' addresses as an array of pointers (a numpy one: a ctypes
    array type is itself cyclic garbage once its last instance dies)."""
    return np.array([a.ctypes.data for a in arrs], np.uintp)


def pack_i32(seqs, max_len=None, pad=0):
    """seqs: list of 1-D int32 arrays -> (out [B, T] int32, lengths [B])."""
    lib = _load()
    b = len(seqs)
    arrs = [np.ascontiguousarray(s, dtype=np.int32) for s in seqs]
    lens = np.asarray([len(a) for a in arrs], np.int32)
    t = int(max_len or (lens.max() if b else 1))
    out = np.empty((b, t), np.int32)
    out_lens = np.empty((b,), np.int32)
    rows = _row_ptrs(arrs)
    rc = lib.pt_pack_i32(_ptr(rows), _ptr(lens), b, t, pad,
                         _ptr(out), _ptr(out_lens))
    if rc != 0:
        raise RuntimeError(f"pt_pack_i32 failed rc={rc}")
    return out, out_lens


def pack_f32(seqs, max_len=None):
    """seqs: list of [len, dim] float32 arrays -> ([B, T, D], lengths)."""
    lib = _load()
    b = len(seqs)
    arrs = [np.ascontiguousarray(s, dtype=np.float32) for s in seqs]
    dim = arrs[0].shape[1]
    lens = np.asarray([a.shape[0] for a in arrs], np.int32)
    t = int(max_len or (lens.max() if b else 1))
    out = np.empty((b, t, dim), np.float32)
    out_lens = np.empty((b,), np.int32)
    rows = _row_ptrs(arrs)
    rc = lib.pt_pack_f32(_ptr(rows), _ptr(lens), b, t, dim,
                         _ptr(out), _ptr(out_lens))
    if rc != 0:
        raise RuntimeError(f"pt_pack_f32 failed rc={rc}")
    return out, out_lens


def densify_sparse(rows, cols, vals, b, dim):
    lib = _load()
    rows = np.ascontiguousarray(rows, np.int32)
    cols = np.ascontiguousarray(cols, np.int32)
    out = np.empty((b, dim), np.float32)
    vp = None
    if vals is not None:
        vals = np.ascontiguousarray(vals, np.float32)
        vp = _ptr(vals)
    rc = lib.pt_densify_sparse(_ptr(rows), _ptr(cols), vp, len(rows), b, dim,
                               _ptr(out))
    if rc != 0:
        raise RuntimeError(f"pt_densify_sparse failed rc={rc}")
    return out


class RecordWriter:
    """PTRC record-file writer (the ProtoDataProvider binary-format role)."""

    def __init__(self, path):
        lib = _load()
        self._lib = lib
        self._h = lib.pt_writer_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path}")

    def put(self, payload: bytes):
        buf = (ctypes.c_uint8 * len(payload)).from_buffer_copy(payload)
        rc = self._lib.pt_writer_put(self._h, buf, len(payload))
        if rc != 0:
            raise IOError(f"write failed rc={rc}")

    def close(self):
        if self._h:
            self._lib.pt_writer_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


class RecordReader:
    def __init__(self, path):
        lib = _load()
        self._lib = lib
        self._h = lib.pt_reader_open(path.encode())
        if not self._h:
            raise IOError(f"cannot open {path}")

    def __iter__(self):
        ptr = ctypes.POINTER(ctypes.c_uint8)()
        while True:
            n = self._lib.pt_reader_next(self._h, ctypes.byref(ptr))
            if n < 0:
                if n == -2:
                    raise IOError("corrupt record file")
                break
            yield ctypes.string_at(ptr, n)

    def close(self):
        if self._h:
            self._lib.pt_reader_close(self._h)
            self._h = None

    def __enter__(self):
        return self

    def __exit__(self, *a):
        self.close()


class PrefetchQueue:
    """Native worker threads stream record files into a bounded queue
    (the DoubleBuffer async-load role)."""

    def __init__(self, capacity=64):
        self._lib = _load()
        self._h = self._lib.pt_queue_create(capacity)

    def add_file(self, path):
        rc = self._lib.pt_queue_add_file(self._h, path.encode())
        if rc != 0:
            raise IOError(f"add_file failed rc={rc}")

    def pop(self, timeout_ms=1000):
        ptr = ctypes.POINTER(ctypes.c_uint8)()
        n = self._lib.pt_queue_pop(self._h, ctypes.byref(ptr), timeout_ms)
        if n < 0:
            return None
        return ctypes.string_at(ptr, n)

    def close(self):
        if self._h:
            self._lib.pt_queue_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

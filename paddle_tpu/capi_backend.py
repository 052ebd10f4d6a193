"""Python side of the C inference API (reference paddle/capi/).

The reference's C API wraps GradientMachine for embedding into C/C++ apps
(capi/gradient_machine.h:36-59); its trainer embeds Python for config
parsing (utils/PythonUtil.cpp).  The TPU-native C API mirrors both ideas:
libpaddle_tpu_capi.so (native/src/capi.cpp) embeds CPython and calls into
this module, which builds the topology from a Python config file and runs
jitted inference on the default JAX device.

The config file is executed and must expose the output layer(s) as a
module-level `predict` LayerOutput (or set `__outputs__` = [layers]).  The
parameter file is a merged model (trainer.checkpoint.merge_model).
"""

import os
import threading
import traceback

import numpy as np


_machines = {}
_next_id = [1]
_id_lock = threading.Lock()   # handle allocation under concurrent C threads


def _alloc_id():
    with _id_lock:
        nid = _next_id[0]
        _next_id[0] += 1
        return nid


# per-thread error slot: concurrent C threads (pt_capi_clone pattern) must
# each read their OWN failure, not the last one process-wide
_tls = threading.local()


def last_error():
    return getattr(_tls, "err", "")


def _store_error(e):
    _tls.err = "".join(
        traceback.format_exception(type(e), e, e.__traceback__))
    return -1


def create(config_path, params_path):
    """Build an inference machine; returns handle id (>0) or -1."""
    try:
        import jax.numpy as jnp
        from paddle_tpu.layers.graph import LayerOutput
        from paddle_tpu.trainer.checkpoint import load_merged
        from paddle_tpu.trainer.trainer import Inferencer

        ns = {"__name__": "__paddle_tpu_config__"}
        with open(config_path) as f:
            exec(compile(f.read(), config_path, "exec"), ns)
        outs = ns.get("__outputs__")
        if outs is None:
            outs = ns.get("predict")
        if outs is None:
            outs = [v for v in ns.values() if isinstance(v, LayerOutput)][-1:]
        if not outs:
            raise ValueError(
                f"{config_path} defines no output layer (set `predict = "
                "<LayerOutput>` or `__outputs__ = [...]`)")
        params, model_state, _meta = load_merged(params_path)
        inf = Inferencer(outs, params, model_state)
        mid = _alloc_id()
        _machines[mid] = {"inf": inf, "feed": {}, "outs": None}
        return mid
    except Exception as e:  # noqa: BLE001 - crosses the C ABI
        return _store_error(e)


def create_exported(path):
    """Build an inference machine from a serialized StableHLO artifact
    (export.export_inference); the C service needs neither the config file
    nor the merged params — the artifact is self-contained.  Returns
    handle id (>0) or -1."""
    try:
        from paddle_tpu.export import load_inference
        run_fn = load_inference(path)
        mid = _alloc_id()
        _machines[mid] = {"call": run_fn, "feed": {}, "outs": None}
        return mid
    except Exception as e:  # noqa: BLE001 - crosses the C ABI
        return _store_error(e)


def set_input_dense(mid, name, arr):
    try:
        _machines[mid]["feed"][name] = np.asarray(arr, np.float32)
        return 0
    except Exception as e:
        return _store_error(e)


def set_input_sparse_binary(mid, name, dim, col_ids, row_offsets):
    """Sparse-binary input in CSR form (reference capi/matrix.h
    paddle_matrix_create_sparse + paddle_matrix_sparse_copy_from:
    row_offsets has rows+1 entries; col_ids[row_offsets[i]:row_offsets[i+1]]
    are the set columns of row i).  Densified to float32 [rows, dim] — the
    MXU path takes dense rows, same as data/feeder.py's sparse_binary
    handling."""
    try:
        col_ids = np.asarray(col_ids, np.int64)
        row_offsets = np.asarray(row_offsets, np.int64)
        rows = len(row_offsets) - 1
        if (rows < 0 or row_offsets[0] != 0
                or row_offsets[-1] != len(col_ids)
                or (rows > 0 and np.any(np.diff(row_offsets) < 0))):
            raise ValueError(
                f"bad CSR: offsets {row_offsets.tolist()} for "
                f"{len(col_ids)} col ids (must start at 0, end at n_cols, "
                "and be non-decreasing)")
        out = np.zeros((rows, dim), np.float32)
        for i in range(rows):
            cols = col_ids[row_offsets[i]:row_offsets[i + 1]]
            if len(cols) and (cols.min() < 0 or cols.max() >= dim):
                raise ValueError(f"col id out of range [0, {dim}) in row {i}")
            out[i, cols] = 1.0
        _machines[mid]["feed"][name] = out
        return 0
    except Exception as e:
        return _store_error(e)


def clone_shared(mid):
    """New handle sharing the loaded machine's parameters (reference
    capi/gradient_machine.h paddle_gradient_machine_create_shared_param:
    per-thread machines over one parameter set).  The Inferencer — params
    and jitted fn — is shared; only the feed/output slots are per-handle,
    so concurrent threads don't race on inputs."""
    try:
        m = _machines[mid]
        engine = {k: m[k] for k in ("inf", "call") if k in m}
        nid = _alloc_id()
        _machines[nid] = dict(engine, feed={}, outs=None)
        return nid
    except Exception as e:
        return _store_error(e)


def set_input_ids(mid, name, ids, lengths=None):
    try:
        ids = np.asarray(ids, np.int32)
        if lengths is not None:
            from paddle_tpu.core.sequence import SequenceBatch
            import jax.numpy as jnp
            _machines[mid]["feed"][name] = SequenceBatch(
                data=jnp.asarray(ids), lengths=jnp.asarray(
                    np.asarray(lengths, np.int32)))
        else:
            _machines[mid]["feed"][name] = ids
        return 0
    except Exception as e:
        return _store_error(e)


def run(mid):
    """Run forward; returns number of outputs or -1."""
    try:
        m = _machines[mid]
        if "call" in m:   # StableHLO-exported machine (create_exported)
            out = m["call"](dict(m["feed"]))
        else:
            out = m["inf"].infer(dict(m["feed"]))
        outs = out if isinstance(out, tuple) else (out,)
        arrs = []
        for o in outs:
            data = o.data if hasattr(o, "data") else o
            arrs.append(np.asarray(data, np.float32))
        m["outs"] = arrs
        return len(arrs)
    except Exception as e:
        return _store_error(e)


def output_shape(mid, idx):
    """[rows, cols] with trailing dims flattened; 0-d outputs are [1, 1]."""
    try:
        a = _machines[mid]["outs"][idx]
        if a.ndim == 0:
            return [1, 1]
        return [int(a.shape[0]), int(np.prod(a.shape[1:], dtype=np.int64))]
    except Exception as e:
        _store_error(e)
        return [-1, -1]


def get_output(mid, idx):
    """Returns the output as flat float32 bytes."""
    try:
        a = _machines[mid]["outs"][idx]
        return np.ascontiguousarray(a, np.float32).tobytes()
    except Exception as e:
        _store_error(e)
        return b""


def destroy(mid):
    _machines.pop(mid, None)
    return 0

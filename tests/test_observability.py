"""Observability floor (VERDICT r1 item 8): jax.profiler wiring, debug_nans
flag, valid_spec replication warnings, per-pass step-time percentiles.

Reference: utils/Stat.h:70-241 (REGISTER_TIMER/globalStat dumps),
utils/BarrierStat.h:196 (worker-skew profiling), TrainerMain.cpp:49
(feenableexcept: NaN -> crash)."""

import logging
import os

import numpy as np
import pytest
import jax
import jax.numpy as jnp


@pytest.mark.slow   # multi-second end-to-end; nightly lane
def test_profiler_trace_writes_files(tmp_path):
    from paddle_tpu.utils import profiler
    d = str(tmp_path / "xprof")
    with profiler.trace(d):
        with profiler.annotate("matmul_region"):
            x = jnp.ones((64, 64))
            (x @ x).block_until_ready()
    assert not profiler.is_tracing()
    found = [os.path.join(r, f) for r, _, fs in os.walk(d) for f in fs]
    assert found, "no trace files written"


@pytest.mark.slow
def test_profiler_start_idempotent(tmp_path):
    from paddle_tpu.utils import profiler
    d = str(tmp_path / "xprof2")
    profiler.start(d)
    profiler.start(d)   # warns, doesn't raise
    profiler.stop()
    profiler.stop()     # no-op


def test_flags_apply_debug_nans():
    from paddle_tpu.utils.flags import Flags
    f = Flags(debug_nans=True, dtype="float32", compute_dtype="auto")
    try:
        f.apply()
        with pytest.raises((FloatingPointError, Exception)) as ei:
            jax.jit(lambda x: jnp.log(x))(jnp.zeros(())).block_until_ready()
            # log(0) = -inf is fine; 0/0 produces the NaN
            jax.jit(lambda x: x / x)(jnp.zeros(())).block_until_ready()
        assert "nan" in str(ei.value).lower()
    finally:
        jax.config.update("jax_debug_nans", False)


def test_flags_surface_covers_reference_names():
    """Every reference gflag name resolves: either a field, a renamed field,
    or an entry in the SUBSUMED lookup table."""
    from paddle_tpu.utils import flags as F
    import dataclasses
    fields = {f.name for f in dataclasses.fields(F.Flags)}
    renames = {"trainer_id": "process_id",
               "num_gradient_servers": "num_processes",
               "trainer_count": "data_parallel"}
    reference_flags = [
        "use_gpu", "trainer_count", "port", "ports_num", "nics", "rdma_tcp",
        "trainer_id", "num_gradient_servers", "comment", "log_period",
        "checkgrad_eps", "beam_size", "predict_file", "init_model_path",
        "job", "config", "config_args", "save_dir", "saving_period",
        "saving_period_by_batches", "num_passes", "start_pass", "test_pass",
        "test_period", "average_test_period", "save_only_one", "seed",
        "load_missing_parameter_strategy", "show_parameter_stats_period",
        "show_layer_stat", "prev_batch_state", "with_cost", "dot_period",
        "predict_output_dir", "parallel_nn", "start_pserver", "local",
        "distribute_test", "test_wait", "enable_parallel_vector",
        "loadsave_parameters_in_pserver", "log_period_server",
        "ports_num_for_sparse", "test_all_data_in_one_period",
    ]
    missing = []
    for name in reference_flags:
        if name in fields or renames.get(name) in fields:
            continue
        if any(name in k for k in F.SUBSUMED):
            continue
        missing.append(name)
    assert not missing, f"unaccounted reference flags: {missing}"


@pytest.fixture
def propagating_logger():
    """paddle_tpu's logger sets propagate=False (own stderr handler);
    caplog needs propagation to see records."""
    from paddle_tpu.utils.logging import logger as plogger
    plogger.propagate = True
    yield
    plogger.propagate = False


def test_valid_spec_warns_on_big_replication_fallback(caplog,
                                                      propagating_logger):
    from paddle_tpu.parallel import MeshConfig, make_mesh, valid_spec
    from jax.sharding import PartitionSpec as P
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_mesh(MeshConfig(data=4, model=2))
    with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
        # big embedding with an odd vocab: fallback must warn
        spec = valid_spec(P("model", None), (100001, 512), mesh,
                          path="emb/w")
        assert spec == P()
        assert any("REPLICATED" in r.message for r in caplog.records)
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger="paddle_tpu"):
        # tiny param: silent fallback (no warning spam)
        spec = valid_spec(P("model"), (7,), mesh)
        assert spec == P()
        assert not caplog.records


def test_pass_end_step_histogram(caplog, propagating_logger):
    """trainer.train logs p50/p90/p99 step times at each pass end and
    resets the histogram."""
    import paddle_tpu.layers as L
    from paddle_tpu import optim
    from paddle_tpu.layers.graph import reset_names
    from paddle_tpu.trainer import SGD
    from paddle_tpu.utils.stats import step_histogram

    reset_names()
    x = L.data_layer("x", size=4)
    lab = L.data_layer("lab", size=1)
    cost = L.classification_cost(
        input=L.fc_layer(x, size=2, act="softmax"), label=lab)
    r = np.random.RandomState(0)
    batches = [{"x": r.randn(4, 4).astype(np.float32),
                "lab": r.randint(0, 2, (4, 1)).astype(np.int32)}
               for _ in range(3)]
    tr = SGD(cost=cost, update_equation=optim.Momentum(learning_rate=0.1),
             seed=0)
    with caplog.at_level(logging.INFO, logger="paddle_tpu"):
        tr.train(lambda: iter(batches), num_passes=1, log_period=0)
    assert any("p99" in rec.message or "p99" in rec.getMessage()
               for rec in caplog.records)
    assert not step_histogram.samples  # reset after the pass

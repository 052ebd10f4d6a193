"""CLI driver: the `paddle train|test|merge_model|version` surface
(reference trainer/TrainerMain.cpp:32-65 + scripts/submit_local.sh.in).

Usage:
  python -m paddle_tpu train --config my_config.py [--num_passes N]
       [--save_dir DIR] [--start_pass K] [--data_parallel N --model_parallel M]
  python -m paddle_tpu test  --config my_config.py --model_dir DIR
  python -m paddle_tpu merge_model --model_dir DIR --out model.npz
  python -m paddle_tpu version

The config file is a Python script defining `get_config()` returning a dict:
  {"cost": LayerOutput, "optimizer": optim.Optimizer,
   "train_reader": reader, "test_reader": reader (optional),
   "feeding": {name: InputType}, "batch_size": int (reader already batched)}
(reference --config=trainer_config.conf scripts, with config_args available
as CONFIG_ARGS in the script's namespace).
"""

import argparse
import os
import runpy
import sys


def _load_config(path, config_args):
    """Native configs define get_config(); reference-style v1 configs
    (`from paddle.trainer_config_helpers import *` + settings/outputs) run
    through the config compiler (paddle_tpu.compat) unchanged."""
    src = open(path).read()
    if "def get_config" in src:
        # fresh layer-name registry per invocation: a second cli.main()
        # in the same process (train then test) must mint the SAME layer
        # names, or loaded params won't match the rebuilt graph (the
        # compat path already resets inside parse_config)
        from paddle_tpu.layers.graph import reset_names
        reset_names()
        ns = runpy.run_path(path, init_globals={"CONFIG_ARGS": config_args})
        if "get_config" in ns:
            return ns["get_config"]()
    from paddle_tpu.compat import parse_config, config_to_runtime
    return config_to_runtime(parse_config(path, config_args))


def _resolve_feeder(feeding, seq_buckets=None, pad_batch=None):
    """feeding may be a DataFeeder, an input-types dict, or None.

    seq_buckets: allowed padded sequence lengths (XLA compiles one program
    per bucket instead of one per distinct batch shape — essential for
    variable-length data on TPU); pad_batch: fixed batch size."""
    from paddle_tpu.data.feeder import DataFeeder
    if isinstance(feeding, DataFeeder):
        return feeding
    if not feeding:
        return None
    return DataFeeder(feeding, bucket_bounds=seq_buckets,
                      pad_batch_to=pad_batch)


def _seq_buckets_arg(value):
    """argparse type for --seq_buckets: sorted positive ints."""
    try:
        bounds = sorted(int(b) for b in value.split(",") if b.strip())
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--seq_buckets wants comma-separated ints, got {value!r}")
    if not bounds or any(b < 1 for b in bounds):
        raise argparse.ArgumentTypeError(
            f"--seq_buckets wants positive lengths, got {value!r}")
    return bounds


def _feeder_from_args(args, cfg, allow_pad=True):
    """The job's DataFeeder honoring --seq_buckets/--pad_batch (jobs whose
    parsers don't register the flags fall back to plain resolution).

    allow_pad=False for the test job: batch padding duplicates the last
    sample, which would bias an unmasked test metric."""
    from paddle_tpu.data.feeder import DataFeeder
    from paddle_tpu.utils.logging import logger
    buckets = getattr(args, "seq_buckets", None)
    want_pad = getattr(args, "pad_batch", False) and allow_pad
    if isinstance(cfg.get("feeding"), DataFeeder):
        if buckets or want_pad:
            logger.warning(
                "--seq_buckets/--pad_batch ignored: the config supplies a "
                "ready-made DataFeeder; set bucket_bounds/pad_batch_to on "
                "it instead")
        return cfg["feeding"]
    pad = None
    if want_pad:
        pad = cfg.get("batch_size")
        if not pad:
            logger.warning(
                "--pad_batch ignored: the config declares no batch_size")
    if getattr(args, "pad_batch", False) and not allow_pad:
        logger.info("--pad_batch not applied to the test job (padding "
                    "duplicates samples, biasing the metric)")
    return _resolve_feeder(cfg.get("feeding"), seq_buckets=buckets,
                           pad_batch=pad)


def _resolve_prefetch(args):
    """--prefetch, defaulting to the FLAGS pair the reference shipped:
    async_load_data (DoubleBuffer on/off) × prefetch_depth."""
    p = getattr(args, "prefetch", None)
    if p is not None:
        return p
    from paddle_tpu.utils.flags import FLAGS
    return FLAGS.prefetch_depth if FLAGS.async_load_data else 0


def _parse_config_args(s):
    out = {}
    if s:
        for kv in s.split(","):
            k, _, v = kv.partition("=")
            out[k.strip()] = v.strip()
    return out


def main(argv=None):
    parser = argparse.ArgumentParser(prog="paddle_tpu")
    sub = parser.add_subparsers(dest="job", required=True)

    def add_common(p):
        p.add_argument("--config", required=True)
        p.add_argument("--config_args", default="",
                       help="k=v,k=v passed to the config script")
        p.add_argument("--data_parallel", type=int, default=0)
        p.add_argument("--model_parallel", type=int, default=1)
        p.add_argument("--seq_parallel", type=int, default=1)
        p.add_argument("--profile_dir", default=None,
                       help="capture an xprof device trace of the run")
        p.add_argument("--debug_nans", action="store_true",
                       help="fail fast on the op producing a NaN "
                            "(reference feenableexcept)")
        p.add_argument("--comment", default="",
                       help="freeform run annotation, logged once")
        p.add_argument("--seq_buckets", default=None,
                       type=_seq_buckets_arg,
                       help="comma-separated allowed padded sequence "
                            "lengths, e.g. 32,64,128: bounds XLA "
                            "recompilation to one program per bucket "
                            "(recommended for variable-length data on "
                            "TPU).  Sequences longer than the largest "
                            "bucket are truncated to it (warned)")
        p.add_argument("--pad_batch", action="store_true",
                       help="pad the final short batch to the full batch "
                            "size (one more shape avoided)")
        p.add_argument("--dtype", default="auto",
                       choices=["auto", "float32", "bfloat16"],
                       help="compute dtype for forward+backward; master "
                            "params and the optimizer stay float32.  "
                            "auto = platform policy (bf16 matmul inputs "
                            "on TPU, f32 elsewhere); float32 FORCES full "
                            "f32 even on TPU (numerics debugging); "
                            "bfloat16 forces bf16 everywhere and also "
                            "casts params+feeds at the step boundary "
                            "(half-width HBM reads; no loss scaling "
                            "needed)")

    t = sub.add_parser("train")
    add_common(t)
    t.add_argument("--num_passes", type=int, default=1)
    t.add_argument("--prefetch", type=int, default=None,
                   help="overlapped input pipeline: convert + H2D-transfer "
                        "N batches ahead on a background thread so the "
                        "step never waits on input (0 = off; costs ~N+1 "
                        "batches of extra HBM).  Default comes from FLAGS: "
                        "prefetch_depth when async_load_data (the "
                        "reference DoubleBuffer default), else 0")
    t.add_argument("--grad_accum_steps", type=int, default=1,
                   help="sum grads over N micro-batches, apply their mean "
                        "every Nth step (large effective batch in fixed "
                        "HBM)")
    t.add_argument("--quant-train", dest="quant_train",
                   action="store_true",
                   help="int8 weight-streaming training: the jitted step "
                        "reads per-out-channel int8 weights + f32 scale "
                        "sidecars at the matmul boundary, f32 masters "
                        "update optimizer-side and requantize each step; "
                        "checkpoints carry both trees (quant_train flag)")
    t.add_argument("--save_dir", default=None)
    t.add_argument("--saving_period", type=int, default=1)
    t.add_argument("--save_only_one", action="store_true")
    t.add_argument("--start_pass", type=int, default=0)
    t.add_argument("--log_period", type=int, default=100)
    t.add_argument("--test_period", type=int, default=0)
    t.add_argument("--show_parameter_stats_period", type=int, default=0)
    t.add_argument("--init_model_path", default=None,
                   help="warm-start parameters from this checkpoint dir")
    t.add_argument("--load_missing_parameter_strategy", default="fail",
                   choices=["fail", "rand", "zero"])
    t.add_argument("--show_layer_stat", action="store_true",
                   help="log per-layer output stats on the first batch of "
                        "each pass")

    te = sub.add_parser("test")
    add_common(te)
    te.add_argument("--model_dir", required=True)
    te.add_argument("--test_pass", type=int, default=None)

    tm = sub.add_parser("time",
                        help="time the train step (reference --job=time, "
                             "TrainerBenchmark.cpp): warm up, then report "
                             "ms/batch percentiles over --num_batches")
    add_common(tm)
    tm.add_argument("--num_batches", type=int, default=20)
    tm.add_argument("--warmup", type=int, default=2)

    cg = sub.add_parser("checkgrad",
                        help="finite-difference gradient check "
                             "(reference --job=checkgrad; single-device, "
                             "parallel flags are ignored)")
    cg.add_argument("--config", required=True)
    cg.add_argument("--config_args", default="")
    cg.add_argument("--eps", type=float, default=1e-3)

    m = sub.add_parser("merge_model")
    m.add_argument("--model_dir", required=True)
    m.add_argument("--out", required=True)
    m.add_argument("--pass_id", type=int, default=None)

    sub.add_parser("version")

    args = parser.parse_args(argv)

    if args.job == "version":
        from paddle_tpu.version import __version__
        import jax
        print(f"paddle_tpu {__version__} (jax {jax.__version__})",
              flush=True)
        print(f"devices: {jax.devices()}")
        return 0

    if args.job == "merge_model":
        from paddle_tpu.trainer.checkpoint import merge_model
        out = merge_model(args.model_dir, args.out, args.pass_id)
        print("wrote", out)
        return 0

    if getattr(args, "debug_nans", False):
        import jax
        jax.config.update("jax_debug_nans", True)
    from paddle_tpu.utils.flags import set_compilation_cache_dir
    set_compilation_cache_dir()
    if getattr(args, "comment", ""):
        from paddle_tpu.utils.logging import logger
        logger.info("comment: %s", args.comment)

    # launched by scripts/launch_cluster (PADDLE_TPU_* rendezvous) or on a
    # Cloud-TPU pod (platform fan-out; jax autodetects the coordinator):
    # connect the multi-controller runtime BEFORE first device use — here,
    # ahead of the config exec — or every rank would silently train an
    # independent full copy.  Deliberately AFTER the version/merge_model
    # early returns, which must never block in a rendezvous.
    # pod detection must require MULTIPLE workers: single-host TPU images
    # set TPU_WORKER_HOSTNAMES=localhost, and a 1-host rendezvous would
    # add latency for nothing
    _hostnames = os.environ.get("TPU_WORKER_HOSTNAMES", "")
    _multihost_pod = ("," in _hostnames
                      or "MEGASCALE_COORDINATOR_ADDRESS" in os.environ)
    if os.environ.get("PADDLE_TPU_COORDINATOR") or _multihost_pod:
        from paddle_tpu.parallel import distributed as dist
        dist.init_distributed()

    cfg = _load_config(args.config, _parse_config_args(args.config_args))

    if args.job == "checkgrad":
        from paddle_tpu.layers.graph import Topology
        from paddle_tpu.testing import check_topology_grads
        feeder = _feeder_from_args(args, cfg)
        batch = next(iter(cfg["train_reader"]()))
        feed = feeder(batch) if feeder else batch
        costs = cfg["cost"]
        topo = Topology(costs if isinstance(costs, (list, tuple))
                        else [costs])
        results = check_topology_grads(topo, feed, eps=args.eps,
                                       raise_on_fail=False)
        bad = False
        for path, err, ok in results:
            print(f"  {path}: max rel err {err:.3g}"
                  + ("" if ok else "  MISMATCH"))
            bad = bad or not ok
        print("checkgrad FAILED" if bad else "checkgrad PASSED")
        return 1 if bad else 0

    from paddle_tpu.trainer import SGD
    mesh = None
    if args.model_parallel > 1 or args.seq_parallel > 1 or args.data_parallel > 1:
        from paddle_tpu.parallel import MeshConfig, make_mesh, megatron_rules
        mesh = make_mesh(MeshConfig(data=args.data_parallel,
                                    model=args.model_parallel,
                                    seq=args.seq_parallel))
    else:
        import jax as _jax
        if _jax.process_count() > 1:
            # multi-process launch with no explicit parallel flags: the
            # only sane default is data-parallel over every device in the
            # job (a per-rank local mesh would train N independent copies)
            from paddle_tpu.parallel import MeshConfig, make_mesh
            mesh = make_mesh(MeshConfig(data=_jax.device_count()))
            logger_note = (f"multi-process job: defaulting to "
                           f"data_parallel={_jax.device_count()}")
            from paddle_tpu.utils.logging import logger
            logger.info(logger_note)
    optimizer = cfg.get("optimizer")
    if optimizer is None:
        # same default as the v1 settings() compat path (compat/v1.py:
        # MomentumOptimizer(momentum=0) at learning_rate=1e-3) so the two
        # config styles train identically when no optimizer is named
        from paddle_tpu import optim
        optimizer = optim.Momentum(learning_rate=1e-3, momentum=0.0)
    import jax.numpy as jnp
    if args.dtype != "auto":
        # op-level policy: explicit float32 must ALSO be asserted (the
        # auto policy would keep feeding the MXU bf16 inputs on TPU);
        # bfloat16 additionally casts params + feeds at the step boundary
        # via SGD(compute_dtype=...) so HBM reads are half-width
        from paddle_tpu.core import dtypes as _dtypes
        _dtypes.set_policy(compute_dtype=args.dtype)
    from paddle_tpu.utils.flags import FLAGS
    quant_train = bool(getattr(args, "quant_train", False)
                       or getattr(FLAGS, "quant_train", False))
    if quant_train:
        FLAGS.quant_train = True
    trainer = SGD(cost=cfg["cost"], update_equation=optimizer,
                  mesh=mesh,
                  sharding_rules=cfg.get("sharding_rules"),
                  evaluators=cfg.get("evaluators"),
                  compute_dtype=(jnp.bfloat16
                                 if args.dtype == "bfloat16" else None),
                  grad_accum_steps=getattr(args, "grad_accum_steps", 1),
                  quant_weights=quant_train)

    if args.job == "train":
        save_dir = args.save_dir or cfg.get("save_dir")
        if args.init_model_path:
            trainer.load_parameters(
                args.init_model_path,
                missing_strategy=args.load_missing_parameter_strategy)
        if args.start_pass:
            if not save_dir:
                raise SystemExit("--start_pass needs --save_dir (or a "
                                 "save_dir in the config)")
            trainer.load(save_dir, args.start_pass - 1)
        ev_handler = None
        if args.show_layer_stat:
            from paddle_tpu.trainer import events as _ev
            feeder = _feeder_from_args(args, cfg)

            def ev_handler(ev, _tr=trainer, _cfg=cfg, _feeder=feeder):
                if isinstance(ev, _ev.BeginPass):
                    batch = next(iter(_cfg["train_reader"]()), None)
                    if batch is None:   # empty (or one-shot, drained) reader
                        return
                    _tr.log_layer_stats(_feeder(batch) if _feeder else batch)
        if args.profile_dir:
            from paddle_tpu.utils import profiler
            profiler.start(args.profile_dir)
        try:
            trainer.train(cfg["train_reader"],
                          num_passes=args.num_passes,
                          event_handler=ev_handler,
                          feeding=_feeder_from_args(args, cfg),
                          save_dir=save_dir,
                          saving_period=args.saving_period,
                          save_only_one=args.save_only_one,
                          test_reader=cfg.get("test_reader"),
                          test_period=args.test_period,
                          log_period=args.log_period,
                          show_parameter_stats_period=
                          args.show_parameter_stats_period,
                          prefetch=_resolve_prefetch(args))
        finally:
            # flush the trace even on a mid-pass failure — crashed runs are
            # the ones you most want a profile of
            if args.profile_dir:
                from paddle_tpu.utils import profiler
                profiler.stop()
        return 0

    if args.job == "test":
        trainer.load(args.model_dir, args.test_pass)
        cost = trainer.test(cfg.get("test_reader") or cfg["train_reader"],
                            feeding=_feeder_from_args(args, cfg,
                                                      allow_pad=False))
        print(f"test cost: {cost:.5f}")
        return 0

    if args.job == "time":
        import time as _time
        feeder = _feeder_from_args(args, cfg)
        reader = cfg["train_reader"]
        batches = []
        for b in reader():
            batches.append(b)
            if len(batches) >= args.num_batches + args.warmup:
                break
        if len(batches) <= args.warmup:
            print(f"time: need more than --warmup={args.warmup} batches, "
                  f"reader yielded {len(batches)}", file=sys.stderr)
            return 2
        import jax as _jax
        durs = []
        for i, b in enumerate(batches):
            t0 = _time.perf_counter()
            cost = trainer.train_one_batch(b, feeder=feeder)
            _jax.block_until_ready(cost)    # real step time, not dispatch
            if i >= args.warmup:
                durs.append((_time.perf_counter() - t0) * 1e3)
        durs.sort()
        n = len(durs)
        if n < 100:
            # with few samples a "p99" is just the max — don't overstate
            # fidelity with percentile labels
            print(f"time: {n} batches  min={durs[0]:.2f}ms  "
                  f"mean={sum(durs) / n:.2f}ms  max={durs[-1]:.2f}ms")
        else:
            import numpy as _np
            # same estimator as utils.stats.Histogram so the trainer's
            # pass-end log and this job agree on what "p99" means
            p50, p90, p99 = _np.percentile(durs, [50, 90, 99])
            print(f"time: {n} batches  p50={p50:.2f}ms  "
                  f"p90={p90:.2f}ms  p99={p99:.2f}ms  "
                  f"mean={sum(durs) / n:.2f}ms")
        return 0



if __name__ == "__main__":
    sys.exit(main())

"""Multi-process distributed bring-up worker (test fixture).

Run as `python -m paddle_tpu.testing.dist_worker OUT_DIR [options]` under
the PADDLE_TPU_* rendezvous env vars (parallel/distributed.py:12-18).
Each process connects through jax.distributed.initialize, builds a global
mesh over every process's devices, and trains a tiny model.  Every
process materializes the full (deterministically seeded) host batch and
jax.make_array_from_callback hands each device its addressable shard —
mesh-shape-agnostic, which the 2x2 data,model mode needs; the stricter
process-local-ingestion path (jax.make_array_from_process_local_data,
where a process never holds peers' data) is covered by
tests/test_parallel_matrix.py.  The final loss
and a parameter checksum are written to OUT_DIR/rank{i}.json so tests can
assert multi-process == single-process numerics (the reference proved its
distributed plane the same way: test_CompareSparse.cpp:66-87 trains
against in-process pservers and compares with local training).

Modes:
  --mesh data        1-axis data-parallel mesh over all devices (default)
  --mesh data,model  2x2 mesh: data axis AND model (tensor) axis both >1
                     with parameters sharded over `model` — the reference
                     distributed plane had the same two splits
                     (num_gradient_servers x parallel_nn model split)
  --mesh stage       GPipe pipeline across processes: each rank's device
                     owns one stage, the stage-to-stage ppermute rides
                     the inter-process transport
Failure/restart drill (the reference's fault story was pserver
checkpointing; here it's coordinator checkpoints + whole-job relaunch):
  --ckpt-dir D       rank 0 checkpoints params at step --ckpt-step;
                     on startup, if D holds a checkpoint, RESUME from it
  --crash-rank R --crash-step S   rank R calls os._exit(3) before
                     running step S (simulates a dying host mid-pass)
"""

import argparse
import json
import os
import sys


def _global_array(sharding, host_value):
    """Build a process-spanning global array from an identical-per-process
    host value: each device picks its addressable shard via the callback
    (mesh-shape-agnostic — works for data, tensor, and stage shardings)."""
    import jax
    return jax.make_array_from_callback(
        host_value.shape, sharding, lambda idx: host_value[idx])


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("out_dir")
    ap.add_argument("--mesh", default="data",
                    choices=["data", "data,model", "stage"])
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-step", type=int, default=10)
    ap.add_argument("--crash-rank", type=int, default=None)
    ap.add_argument("--crash-step", type=int, default=None)
    ap.add_argument("--trainer-sparse", action="store_true",
                    help="train the sparse-embedding model through the "
                         "REAL layers+SGD trainer API on the global mesh "
                         "(reference test_CompareSparse: multi-trainer "
                         "sparse vs local numerics)")
    args = ap.parse_args(argv)
    os.makedirs(args.out_dir, exist_ok=True)

    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    from paddle_tpu.parallel import distributed as dist
    dist.init_distributed()

    import numpy as np
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    nproc = jax.process_count()
    rank = jax.process_index()
    assert nproc == int(os.environ["PADDLE_TPU_NUM_PROCESSES"])

    if args.trainer_sparse:
        return _trainer_sparse(args, nproc, rank)

    devices = np.asarray(jax.devices())
    if args.mesh == "stage":
        return _pipeline_stage(args, nproc, rank, devices)
    if args.mesh == "data,model":
        assert devices.size % 2 == 0, \
            "data,model mesh needs an even device count"
        mesh = Mesh(devices.reshape(devices.size // 2, 2),
                    ("data", "model"))
        # tensor-parallel parameter layout: hidden dim split over `model`
        pspec = {"w1": P(None, "model"), "b1": P("model"),
                 "w2": P("model", None)}
    else:
        mesh = Mesh(devices, ("data",))
        pspec = {"w1": P(), "b1": P(), "w2": P()}
    param_sh = {k: NamedSharding(mesh, s) for k, s in pspec.items()}
    batch_sh = NamedSharding(mesh, P("data"))

    # identical init on every process (SPMD: same program, same params)
    rng = np.random.RandomState(0)
    init = {
        "w1": jnp.asarray(rng.randn(8, 16) * 0.5, jnp.float32),
        "b1": jnp.zeros((16,), jnp.float32),
        "w2": jnp.asarray(rng.randn(16, 1) * 0.5, jnp.float32),
    }

    B, STEPS = 32, args.steps
    xs = rng.randn(STEPS, B, 8).astype(np.float32)
    ys = (xs[..., :3].sum(-1, keepdims=True) > 0).astype(np.float32)

    start_step = 0
    if args.ckpt_dir and os.path.isdir(args.ckpt_dir) \
            and any(n.startswith("pass-")
                    for n in os.listdir(args.ckpt_dir)):
        from paddle_tpu.trainer.checkpoint import load_checkpoint
        params_host, _opt, _ms, meta = load_checkpoint(args.ckpt_dir)
        init = {k: jnp.asarray(v) for k, v in params_host.items()}
        start_step = int(meta["step"])
        print(f"[dist_worker] rank {rank} resuming from step {start_step}",
              flush=True)

    # every process holds the full host value (deterministic seed /
    # checkpoint); _global_array shards it per device
    global_array = _global_array

    params = {k: global_array(param_sh[k], np.asarray(v))
              for k, v in init.items()}

    def loss_fn(p, x, y):
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        pred = jax.nn.sigmoid(h @ p["w2"])
        return jnp.mean((pred - y) ** 2)

    @jax.jit
    def step(p, x, y):
        loss, g = jax.value_and_grad(loss_fn)(p, x, y)
        p = jax.tree_util.tree_map(lambda w, gw: w - 0.5 * gw, p, g)
        return p, loss

    loss = first_loss = None
    for t in range(start_step, STEPS):
        if args.crash_rank == rank and args.crash_step == t:
            print(f"[dist_worker] rank {rank} CRASHING at step {t}",
                  flush=True)
            os._exit(3)
        x = global_array(batch_sh, xs[t])
        y = global_array(batch_sh, ys[t])
        params, loss = step(params, x, y)
        if first_loss is None:
            first_loss = float(loss)
        if args.ckpt_dir and t + 1 == args.ckpt_step:
            # replicate, then fetch: model-sharded params are not
            # rank-0-addressable, so rejit to P() makes every process hold
            # the full value; only rank 0 writes
            repl = NamedSharding(mesh, P())
            gather = jax.jit(lambda a: a, out_shardings=repl)
            host = {k: np.asarray(jax.device_get(gather(v)))
                    for k, v in params.items()}
            if rank == 0:
                from paddle_tpu.trainer.checkpoint import save_checkpoint
                save_checkpoint(args.ckpt_dir, 0, host,
                                extra={"step": t + 1})
            # nobody crosses the checkpoint boundary until it's on disk —
            # a crash after this barrier can always resume from it
            dist.barrier(f"ckpt{t}")

    dist.barrier("final")
    checksum = float(sum(jnp.sum(jnp.abs(v)) for v in
                         jax.tree_util.tree_leaves(params)))
    out = {"rank": rank, "nproc": nproc, "loss": float(loss),
           "first_loss": first_loss, "checksum": checksum,
           "global_devices": jax.device_count(),
           "mesh": args.mesh, "start_step": start_step,
           "coordinator": dist.is_coordinator()}
    with open(os.path.join(args.out_dir, f"rank{rank}.json"), "w") as f:
        json.dump(out, f)
    print(f"[dist_worker] rank {rank}/{nproc} loss={out['loss']:.6f} "
          f"checksum={checksum:.6f}", flush=True)


def _pipeline_stage(args, nproc, rank, devices):
    """Pipeline parallelism ACROSS PROCESSES: each rank's device owns one
    GPipe stage; the stage-to-stage ppermute rides the inter-process
    transport.  The test compares against an in-process sequential run of
    the same blocks (the reference's config-pair equivalence discipline)."""
    import json as _json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.parallel import distributed as dist
    from paddle_tpu.parallel.pipeline import (gpipe, microbatch,
                                              unmicrobatch)

    mesh = Mesh(devices, ("stage",))
    s = devices.size
    rng = np.random.RandomState(0)
    host_stacked = {
        "w": np.stack([rng.randn(8, 8).astype(np.float32) * 0.4
                       for _ in range(s)]),
        "b": np.zeros((s, 8), np.float32)}
    B, STEPS = 16, args.steps
    xs = rng.randn(STEPS, B, 8).astype(np.float32)
    ys = np.tanh(rng.randn(STEPS, B, 8)).astype(np.float32)

    ga = _global_array
    psh = {k: NamedSharding(mesh, P("stage")) for k in host_stacked}
    repl = NamedSharding(mesh, P())
    params = {k: ga(psh[k], v) for k, v in host_stacked.items()}

    def stage_fn(p, h):
        return jnp.tanh(h @ p["w"] + p["b"])

    @jax.jit
    def step(sp, x, y):
        def loss_fn(sp):
            out = unmicrobatch(gpipe(stage_fn, sp, microbatch(x, 4),
                                     mesh=mesh))
            return jnp.mean((out - y) ** 2)
        loss, g = jax.value_and_grad(loss_fn)(sp)
        return jax.tree_util.tree_map(
            lambda w, gw: w - 0.3 * gw, sp, g), loss

    loss = first_loss = None
    for t in range(STEPS):
        params, loss = step(params, ga(repl, xs[t]), ga(repl, ys[t]))
        if first_loss is None:
            first_loss = float(loss)

    dist.barrier("final")
    checksum = float(sum(jnp.sum(jnp.abs(v)) for v in
                         jax.tree_util.tree_leaves(params)))
    out = {"rank": rank, "nproc": nproc, "loss": float(loss),
           "first_loss": first_loss, "checksum": checksum,
           "global_devices": jax.device_count(), "mesh": args.mesh,
           "start_step": 0, "coordinator": dist.is_coordinator()}
    with open(os.path.join(args.out_dir, f"rank{rank}.json"), "w") as f:
        _json.dump(out, f)
    print(f"[dist_worker] rank {rank}/{nproc} pipeline loss="
          f"{out['loss']:.6f} checksum={checksum:.6f}", flush=True)


def _trainer_sparse(args, nproc, rank):
    """The user-facing path at multi-process scale: layers DSL model with a
    sparse_update embedding trained through trainer.SGD(mesh=global mesh).
    Deterministic batches (same stream every process — SPMD); final cost +
    parameter checksums land in rank{i}.json for the numerics compare."""
    import json as _json
    import numpy as np
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh

    import paddle_tpu.layers as L
    from paddle_tpu import optim
    from paddle_tpu.core.sequence import pad_sequences
    from paddle_tpu.layers.graph import reset_names
    from paddle_tpu.parallel import distributed as dist
    from paddle_tpu.trainer.trainer import SGD
    from paddle_tpu.trainer import events

    vocab, emb_dim, b, t = 64, 8, 8, 5
    reset_names()
    w = L.data_layer("w", size=vocab, is_seq=True)
    emb = L.embedding_layer(w, size=emb_dim, sparse_update=True,
                            param_attr={"initial_std": 0.1, "name": "emb"})
    pooled = L.pooling_layer(emb, pooling_type="sum")
    out = L.fc_layer(pooled, size=2, act="softmax",
                     param_attr={"initial_std": 0.1, "name": "fc"})
    lab = L.data_layer("lab", size=1)
    cost = L.classification_cost(input=out, label=lab)

    rng = np.random.RandomState(5)
    batches = []
    for _ in range(12):
        seqs = [rng.randint(0, vocab, (rng.randint(2, t + 1),))
                for _ in range(b)]
        # learnable labels ("does any low token appear") so the test can
        # assert progress, not just numerics agreement
        labs = np.asarray([[int((s < vocab // 4).any())] for s in seqs],
                          np.int32)
        batches.append({"w": pad_sequences(seqs, max_len=t), "lab": labs})

    mesh = Mesh(np.asarray(jax.devices()), ("data",))
    tr = SGD(cost=cost, update_equation=optim.Momentum(learning_rate=0.1,
                                                       momentum=0.0),
             mesh=mesh, seed=3, donate=False)
    costs = []
    # the cross-rank straggler report fires once per PASS END (over all
    # 12 batches' step times); exported below for the test to assert on
    tr.train(lambda: iter(batches), num_passes=2, log_period=6,
             event_handler=lambda e: costs.append(float(e.cost))
             if isinstance(e, events.EndIteration) else None)

    dist.barrier("final")
    repl = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())

    def subtree_checksum(key):
        leaves = jax.tree_util.tree_leaves(tr.parameters[key])
        total = 0.0
        for v in leaves:
            g = jax.device_get(jax.jit(lambda a: a, out_shardings=repl)(v))
            total += float(np.abs(g).sum())
        return total

    out_rec = {"rank": rank, "nproc": nproc,
               "loss": costs[-1], "first_loss": costs[0],
               "emb_checksum": subtree_checksum("emb"),
               "fc_checksum": subtree_checksum("fc"),
               "global_devices": jax.device_count(),
               "skew_report": tr.last_skew_report,
               "mode": "trainer-sparse"}
    with open(os.path.join(args.out_dir, f"rank{rank}.json"), "w") as f:
        _json.dump(out_rec, f)
    print(f"[dist_worker] trainer-sparse rank {rank}/{nproc} "
          f"loss={costs[-1]:.6f}", flush=True)


if __name__ == "__main__":
    main()

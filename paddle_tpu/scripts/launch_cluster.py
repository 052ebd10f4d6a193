"""Multi-host cluster launcher.

Reference: paddle/scripts/cluster_train/paddle.py:101-176 — a fabric/SSH
launcher that started `paddle pserver` on every node then `paddle train
--trainer_id=i --pservers=...`.  The TPU-native launcher has no pserver
role: it starts the SAME training command on every host with the
PADDLE_TPU_* rendezvous env vars (parallel.distributed contract); host 0
is the coordinator.  On Cloud-TPU-style deployments where each host
already knows the pod topology, prefer the platform's own fan-out
(gcloud ... --worker=all / GKE JobSet) and skip this launcher entirely —
jax.distributed autodetects there.

Usage:
  python -m paddle_tpu.scripts.launch_cluster \
      --hosts host1,host2,host3,host4 --port 8476 \
      -- python -m paddle_tpu.trainer.cli train --config conf.py ...

Requires passwordless ssh to each host and the repo available at the same
path everywhere (reference conf.py HOSTS assumption).

`--local N` fans out N ranks as plain subprocesses on THIS machine instead
of ssh — the CPU test mode (what the multi-process distributed test
drives, under JAX_PLATFORMS=cpu).  Every rank inherits the same
environment and so sees every chip: on a TPU host only one of them could
take the chips and the rest would fail or hang.  One process drives all
the chips of a host; use `--hosts` with one rank per host there.
"""

import argparse
import os
import shlex
import signal
import subprocess
import sys


def rendezvous_env(coordinator_host, port, world_size, rank):
    return {
        "PADDLE_TPU_COORDINATOR": f"{coordinator_host}:{port}",
        "PADDLE_TPU_NUM_PROCESSES": str(world_size),
        "PADDLE_TPU_PROCESS_ID": str(rank),
    }


def build_ssh_cmd(host, rank, args, command):
    env = rendezvous_env(args.hosts[0], args.port, len(args.hosts), rank)
    env_str = " ".join(f"{k}={shlex.quote(v)}" for k, v in env.items())
    remote = f"cd {shlex.quote(args.workdir)} && {env_str} {command}"
    # -tt: force a remote tty so killing the LOCAL ssh client (fail-fast,
    # ^C) delivers SIGHUP to the remote rank — without it the remote
    # python would survive the teardown blocked in a collective, holding
    # the coordinator port (the reference launcher killed jobs over ssh
    # for the same reason, paddle.py:52-60)
    return ["ssh", "-tt", "-o", "BatchMode=yes", host, remote]


def wait_fail_fast(procs, poll_s=0.2):
    """Wait for every rank; if one dies nonzero, SIGTERM the rest and
    return its rc.  Without this, a crashed rank leaves the others blocked
    forever inside a collective (jax.distributed has no dead-peer timeout
    at this layer) and the launcher would never return — the reference
    launcher killed the whole job on any node failure too
    (scripts/cluster_train/paddle.py:52-60)."""
    import time
    while True:
        rcs = [p.poll() for p in procs]
        bad = [rc for rc in rcs if rc not in (None, 0)]
        if bad:
            for p in procs:
                if p.poll() is None:
                    p.send_signal(signal.SIGTERM)
            deadline = time.time() + 10
            for p in procs:
                try:
                    p.wait(timeout=max(0.1, deadline - time.time()))
                except subprocess.TimeoutExpired:
                    p.kill()
                    p.wait()
            return bad[0]
        if all(rc == 0 for rc in rcs):
            return 0
        time.sleep(poll_s)


def main(argv=None):
    parser = argparse.ArgumentParser(
        prog="paddle_tpu.launch_cluster",
        usage="%(prog)s --hosts h1,h2 [--port P] [--workdir D] -- command…")
    parser.add_argument("--hosts",
                        help="comma-separated host list; first = coordinator")
    parser.add_argument("--local", type=int, metavar="N",
                        help="run N ranks as local subprocesses (no ssh) — "
                             "the CPU test mode: every rank sees every "
                             "chip, so not for a TPU host")
    parser.add_argument("--port", type=int, default=8476)
    parser.add_argument("--workdir", default=os.getcwd())
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="training command to run on every host")
    args = parser.parse_args(argv)
    if (args.hosts is None) == (args.local is None):
        parser.error("exactly one of --hosts / --local N is required")
    if args.local is not None and args.local < 1:
        parser.error(f"--local needs a positive rank count, got {args.local}")
    cmd_parts = list(args.command)
    if cmd_parts and cmd_parts[0] == "--":
        cmd_parts = cmd_parts[1:]
    command = " ".join(shlex.quote(c) for c in cmd_parts)
    if not command:
        parser.error("missing training command after --")

    procs = []

    def _terminate(signum, frame):
        # SIGTERM must reap the ranks like ^C does, or a killed launcher
        # orphans every worker (they re-parent and hold the coordinator port)
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            p.wait()
        sys.exit(128 + signum)

    prev_sigterm = signal.signal(signal.SIGTERM, _terminate)
    try:
        if args.local:
            for rank in range(args.local):
                env = dict(os.environ)
                env.update(rendezvous_env("127.0.0.1", args.port,
                                          args.local, rank))
                print(f"[launch] local rank {rank}: {command}", flush=True)
                procs.append(subprocess.Popen(
                    cmd_parts, env=env, cwd=args.workdir))
        else:
            args.hosts = [h.strip() for h in args.hosts.split(",")
                          if h.strip()]
            for rank, host in enumerate(args.hosts):
                cmd = build_ssh_cmd(host, rank, args, command)
                print(f"[launch] rank {rank} @ {host}: {command}",
                      flush=True)
                procs.append(subprocess.Popen(cmd))
        return wait_fail_fast(procs)
    except KeyboardInterrupt:
        # reference launcher killed jobs over SSH (paddle.py:52-60)
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for p in procs:
            p.wait()
        return 130
    finally:
        # don't leak the handler into an embedding process (tests import
        # main() in-process)
        signal.signal(signal.SIGTERM, prev_sigterm)


if __name__ == "__main__":
    sys.exit(main())

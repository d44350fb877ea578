"""Distributed launcher CLI (reference: python/paddle/distributed/launch/
main.py:23 — Context -> collective controller spawning N local procs with
PADDLE_TRAINER_* env; Master KV rendezvous; watcher; elastic relaunch).

TPU-native: one *process per host* (single-controller SPMD drives all local
chips), so `--nproc_per_node` defaults to 1 and exists for CPU-mesh
simulation/testing. Rendezvous is the JAX coordination service — the
launcher only distributes the env contract (PADDLE_MASTER /
PADDLE_TRAINER_ID / PADDLE_TRAINERS_NUM) that
`paddle_tpu.distributed.init_parallel_env` feeds to
`jax.distributed.initialize`. `--max_restarts` gives launch-level fault
recovery (the reference's elastic relaunch loop, minus etcd).

Every relaunch (worker restart or elastic re-form) exports
`PADDLE_RESTART_GEN` with the bumped generation; `Model.fit` reads it
(ISSUE 15) so a restarted worker with `snapshot_dir=` armed resumes
from its snapshot cursor automatically — the relaunch path passes
`resume=` through without the training script changing.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time


def _parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="launch a distributed training job",
    )
    p.add_argument("--master", default=None,
                   help="coordinator addr host:port (default: this host)")
    p.add_argument("--nnodes", type=int, default=1, help="number of nodes")
    p.add_argument("--rank", type=int, default=0, help="this node's rank")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="processes per node (1 for TPU SPMD; >1 for CPU-mesh simulation)")
    p.add_argument("--log_dir", default=None, help="per-rank log directory")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="relaunch failed workers up to N times")
    p.add_argument("--devices", default=None,
                   help="accepted for reference-CLI compatibility; has NO "
                        "effect on a TPU host, where one process drives every "
                        "local chip and the runtime reads no device mask from "
                        "the launcher")
    p.add_argument("--elastic_level", type=int, default=0,
                   help="0: off; >=1: run the Master KV rendezvous + elastic "
                        "manager; worker relaunch is driven by its decisions")
    p.add_argument("--job_id", default="default", help="elastic job id")
    p.add_argument("training_script", help="script to run")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p.parse_args(argv)


def _spawn(args, local_rank: int, generation: int = 0):
    world = args.nnodes * args.nproc_per_node
    rank = args.rank * args.nproc_per_node + local_rank
    env = dict(os.environ)
    master = args.master or "127.0.0.1:49178"
    env.update(
        PADDLE_MASTER=master,
        MASTER_ADDR=master.rsplit(":", 1)[0],
        MASTER_PORT=master.rsplit(":", 1)[1] if ":" in master else "49178",
        PADDLE_TRAINER_ID=str(rank),
        RANK=str(rank),
        PADDLE_TRAINERS_NUM=str(world),
        WORLD_SIZE=str(world),
        PADDLE_LOCAL_RANK=str(local_rank),
        PADDLE_NNODES=str(args.nnodes),
        PADDLE_NODE_RANK=str(args.rank),
        PADDLE_RESTART_GEN=str(generation),
        PADDLE_JOB_ID=str(getattr(args, "job_id", "default")),
    )
    cmd = [sys.executable, args.training_script] + list(args.training_script_args)
    stdout = stderr = None
    if args.log_dir:
        os.makedirs(args.log_dir, exist_ok=True)
        logf = open(os.path.join(args.log_dir, f"worker.{rank}.log"), "ab")
        stdout = stderr = logf
    return subprocess.Popen(cmd, env=env, stdout=stdout, stderr=stderr)


def launch(argv=None) -> int:
    args = _parse_args(argv)
    restarts = {i: 0 for i in range(args.nproc_per_node)}

    # elastic mode: the node launcher joins the Master KV service (rank 0
    # hosts the store one port above the trainer master port) and runs an
    # ElasticManager whose HOLD/RESTART/EXIT decisions drive this loop —
    # the reference's manager→launcher wiring (elastic/manager.py:125)
    master = None
    elastic = None
    generation = 0
    if args.elastic_level > 0:
        from ..fleet.elastic import ElasticManager, ElasticStatus
        from .master import Master

        ep = args.master or "127.0.0.1:49178"
        host, _, port = ep.rpartition(":")
        store_ep = f"{host or '127.0.0.1'}:{int(port) + 1}"
        master = Master(store_ep, args.rank, args.nnodes, job_id=args.job_id)
        master.register(ep, args.nproc_per_node)
        master.sync_peers(timeout=60.0)
        generation = master.generation()
        elastic = ElasticManager(rank=args.rank, world_size=args.nnodes,
                                 store=master.store, job_id=args.job_id)
        elastic.start()

    procs = {i: _spawn(args, i, generation) for i in range(args.nproc_per_node)}

    def _terminate_all():
        for p in procs.values():
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 10
        for p in procs.values():
            try:
                p.wait(max(deadline - time.time(), 0.1))
            except subprocess.TimeoutExpired:
                p.kill()

    def _restart_worker(i, code):
        nonlocal generation
        restarts[i] += 1
        if master is not None:
            generation = master.bump_generation()
        print(f"[launch] worker {i} exited {code}; RESTART "
              f"{restarts[i]}/{args.max_restarts} (gen {generation})",
              file=sys.stderr)
        procs[i] = _spawn(args, i, generation)

    try:
        while True:
            alive = False
            for i, p in list(procs.items()):
                code = p.poll()
                if code is None:
                    alive = True
                elif code != 0:
                    if restarts[i] < args.max_restarts:
                        _restart_worker(i, code)
                        alive = True
                    else:
                        print(f"[launch] worker {i} failed with code {code}; "
                              "terminating job", file=sys.stderr)
                        if elastic is not None:
                            elastic.exit(completed=False)
                        _terminate_all()
                        return code
            # elastic membership scan: a peer NODE going stale is a RESTART
            # decision — re-form the job at a new generation so workers
            # re-rendezvous and resume from the dist checkpoint
            if elastic is not None and alive:
                status = elastic.watch()
                if status == ElasticStatus.RESTART:
                    cur = master.generation()
                    if cur == generation:
                        generation = master.bump_generation()
                    else:
                        generation = cur
                    print(f"[launch] elastic RESTART -> generation "
                          f"{generation}", file=sys.stderr)
                    _terminate_all()
                    procs.update({i: _spawn(args, i, generation)
                                  for i in range(args.nproc_per_node)})
                elif status == ElasticStatus.COMPLETED:
                    pass  # workers will exit 0 on their own
            if not alive:
                if elastic is not None:
                    elastic.exit(completed=True)
                return 0
            time.sleep(0.2)
    except KeyboardInterrupt:
        if elastic is not None:
            elastic.exit(completed=False)
        _terminate_all()
        return 130


if __name__ == "__main__":
    sys.exit(launch())

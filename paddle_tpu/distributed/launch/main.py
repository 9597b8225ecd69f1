"""Collective launch controller.

ref: launch/main.py:23 + launch/controllers/collective.py — spawn one
worker process per device/replica with the rank env the framework reads
(PADDLE_TRAINER_ID, PADDLE_TRAINERS_NUM, PADDLE_MASTER), aggregate logs
under --log_dir, propagate the first failure, and (elastic mode) restart
workers that exit with the restart code.

TPU note: on a TPU pod each *host* is one worker (jax distributed
single-process-per-host), so --nproc_per_node defaults to 1; the CPU-mesh
test path uses --devices to emulate N single-chip workers. With
--nproc_per_node > 1 on a TPU host, worker i is pinned to chip i (a chip
belongs to one process at a time).

Pod bootstrap (the production multi-controller regime): every launched
worker that calls ``paddle_tpu.distributed.init_parallel_env()`` brings
up the global JAX runtime via ``jax.distributed.initialize`` using the
injected env (coordinator = PADDLE_MASTER, process_id =
PADDLE_TRAINER_ID, num_processes = PADDLE_TRAINERS_NUM) BEFORE first
backend use. After that, ``jax.devices()`` spans all hosts' chips and
every collective — eager ones through the compiled one-collective
programs in ``distributed.collective``, and all collectives inside
jitted train steps — rides ICI/DCN. On the CPU backend the same path
uses gloo cross-process collectives (set automatically); this is what
tests/test_multicontroller.py exercises with real processes.
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time
from typing import List, Optional

from ...core.device import env_wants_cpu, one_chip_env
from ..elastic import ELASTIC_EXIT_CODE, ELASTIC_RESTART_CODE  # noqa: F401
# (single source of truth for the 101/102 restart protocol —
# ref: fleet/elastic/manager.py:33-34)


def _parse(argv):
    p = argparse.ArgumentParser(
        prog="python -m paddle_tpu.distributed.launch",
        description="launch distributed training "
                    "(ref: paddle.distributed.launch)")
    p.add_argument("--nnodes", type=int, default=1)
    p.add_argument("--node_rank", type=int, default=0)
    p.add_argument("--nproc_per_node", type=int, default=1)
    p.add_argument("--devices", type=str, default=None,
                   help="comma list; len(devices) overrides nproc_per_node")
    p.add_argument("--master", type=str, default="127.0.0.1:29500",
                   help="host:port of the rank-0 TCPStore")
    p.add_argument("--log_dir", type=str, default="log")
    p.add_argument("--elastic_retries", type=int, default=0,
                   help="restarts allowed on exit code 101")
    p.add_argument("--elastic", action="store_true",
                   help="store-backed node membership: TTL heartbeats to "
                        "the master, rank rewrite + worker restart on "
                        "node join/leave (ref: fleet/elastic/manager.py)")
    p.add_argument("--elastic_ttl", type=float, default=6.0)
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if ":" not in args.master:
        p.error(f"--master must be host:port, got {args.master!r}")
    return args


def _worker_env(args, local_rank: int, nproc: int) -> dict:
    env = dict(os.environ)
    rank = args.node_rank * nproc + local_rank
    world = args.nnodes * nproc
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_MASTER": args.master,
        "MASTER_ADDR": args.master.split(":")[0],
        "MASTER_PORT": args.master.split(":")[1],
        # jax multi-host bootstrap mirrors the same coordinates
        "JAX_COORDINATOR_ADDRESS": args.master,
        "JAX_NUM_PROCESSES": str(world),
        "JAX_PROCESS_ID": str(rank),
    })
    if args.devices:
        devs = args.devices.split(",")
        env["PADDLE_VISIBLE_DEVICES"] = devs[local_rank % len(devs)]
    if nproc > 1 and not env_wants_cpu(env):
        # several workers on one TPU host: a chip belongs to one
        # process, so worker i gets chip i and nothing else
        env.update(one_chip_env(local_rank))
    return env


def launch(argv: Optional[List[str]] = None) -> int:
    args = _parse(argv if argv is not None else sys.argv[1:])
    nproc = (len(args.devices.split(","))
             if args.devices else args.nproc_per_node)
    os.makedirs(args.log_dir, exist_ok=True)

    retries = {i: args.elastic_retries for i in range(nproc)}
    procs: List[Optional[subprocess.Popen]] = [None] * nproc
    logs: dict = {}  # worker index -> open log handle (reused on respawn)
    # elastic membership state: (world_nodes, my_node_index) — rewrites the
    # rank env on change (ref: fleet/elastic/manager.py rank rewrite)
    membership = {"nodes": args.nnodes, "index": args.node_rank,
                  "restart": False, "exit": False}

    def spawn(i):
        if i in logs:
            logs[i].close()
        log = open(os.path.join(args.log_dir, f"workerlog.{i}"), "ab")
        logs[i] = log
        env = _worker_env(args, i, nproc)
        if args.elastic:
            world = membership["nodes"] * nproc
            rank = membership["index"] * nproc + i
            env.update({
                "PADDLE_TRAINER_ID": str(rank),
                "PADDLE_TRAINERS_NUM": str(world),
                "JAX_NUM_PROCESSES": str(world),
                "JAX_PROCESS_ID": str(rank),
            })
        procs[i] = subprocess.Popen(
            [sys.executable, args.training_script,
             *args.training_script_args],
            env=env, stdout=log, stderr=log)

    manager = None
    if args.elastic:
        from ..elastic import ElasticManager
        from ..store import TCPStore
        host, port = args.master.rsplit(":", 1)
        store = TCPStore(host, int(port) + 2,
                         is_master=args.node_rank == 0,
                         world_size=args.nnodes, timeout=60.0)

        def on_change(alive, my_index):
            if my_index < 0:
                membership["exit"] = True
            else:
                membership["nodes"] = len(alive)
                membership["index"] = my_index
                membership["restart"] = True
            sys.stderr.write(
                f"[elastic] membership now {alive}, my_index={my_index}; "
                f"{'exiting' if my_index < 0 else 'restarting workers'}\n")

        manager = ElasticManager(
            store, str(args.node_rank), ttl=args.elastic_ttl,
            on_membership_change=on_change).start()

    for i in range(nproc):
        spawn(i)

    def _kill_workers():
        for i, p in enumerate(procs):
            if p is not None and p.poll() is None:
                p.send_signal(signal.SIGTERM)
        deadline = time.time() + 10
        for p in procs:
            if p is not None:
                while p.poll() is None and time.time() < deadline:
                    time.sleep(0.1)
                if p.poll() is None:
                    p.kill()

    exit_code = 0
    try:
        while any(p is not None for p in procs):
            time.sleep(0.2)
            if membership["exit"]:
                raise RuntimeError(
                    "elastic: this node left the alive set (heartbeat "
                    "lost); stopping workers")
            if membership["restart"]:
                membership["restart"] = False
                _kill_workers()
                for i in range(nproc):
                    spawn(i)  # rewritten rank env (elastic scale event)
                continue
            for i, p in enumerate(procs):
                if p is None:
                    continue
                rc = p.poll()
                if rc is None:
                    continue
                if rc == 0:
                    procs[i] = None
                elif rc == ELASTIC_RESTART_CODE and retries[i] > 0:
                    retries[i] -= 1
                    spawn(i)  # elastic restart (ref: manager.py protocol)
                else:
                    exit_code = rc
                    raise RuntimeError(
                        f"worker {i} failed with exit code {rc} "
                        f"(log: {args.log_dir}/workerlog.{i})")
    except RuntimeError as e:
        sys.stderr.write(str(e) + "\n")
        _kill_workers()
        exit_code = exit_code or 1
    finally:
        if manager is not None:
            manager.stop()
        for log in logs.values():
            log.close()
    return exit_code


def main():
    sys.exit(launch())

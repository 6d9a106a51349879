"""Process launcher: `python -m paddle_tpu.distributed.launch train.py`.

Analog of reference python/paddle/distributed/launch.py + utils.py
(get_cluster :297, start_local_trainers :424 setting the PADDLE_* env
contract and watching children). On TPU, one process per HOST (not per
chip): jax's single-controller runtime drives all local chips through the
mesh, so single-host launches collapse to exec'ing the script with rank 0
env. A chip belongs to one process at a time and nothing here hands a
rank its own chip: `nproc_per_node > 1` is for CPU tests and for
describing multi-host jobs, never for splitting one host's chips — on a
four-chip host every child would claim every chip.
"""
from __future__ import annotations

import os
import subprocess
import sys
import time

from .elastic import Supervisor, _reap

__all__ = ["launch", "launch_elastic", "launch_ps", "main"]


def _build_env(rank, nranks, endpoints):
    env = dict(os.environ)
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(nranks),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
        "PADDLE_RANK_IN_NODE": str(rank),
    })
    return env


def launch(script, script_args=(), nproc_per_node=1, host="127.0.0.1",
           start_port=6170, elastic_retries=0):
    """Start one process per rank and watch them (reference
    utils.py:424 start_local_trainers + watch loop). With
    elastic_retries > 0, a failed job RESTARTS as a whole up to that many
    times — trainers resume from auto-checkpoint (incubate/checkpoint.py),
    the reference's elastic knob made concrete (its snapshot stubs it,
    distributed_strategy.py:1160; collective jobs can't hot-swap a rank
    mid-step, so whole-job restart from the latest step is the recovery
    unit)."""
    endpoints = [f"{host}:{start_port + i}" for i in range(nproc_per_node)]

    def start_all():
        return [subprocess.Popen([sys.executable, script, *script_args],
                                 env=_build_env(rank, nproc_per_node,
                                                endpoints))
                for rank in range(nproc_per_node)]

    attempt = 0
    while True:
        procs = start_all()
        failed_ret = None
        try:
            while procs:
                for p in list(procs):
                    ret = p.poll()
                    if ret is None:
                        continue
                    procs.remove(p)
                    if ret != 0:
                        # teardown must not hang on (or leak) a wedged
                        # sibling: TERM, bounded wait, escalate to KILL
                        _reap(procs)
                        procs.clear()
                        failed_ret = ret
                        # the snapshot is stale now — every sibling was
                        # just reaped; iterating on would re-remove them
                        break
                time.sleep(0.5)
        except KeyboardInterrupt:
            _reap(procs)
            raise
        if failed_ret is None:
            return 0
        attempt += 1
        if attempt > elastic_retries:
            raise SystemExit(failed_ret)
        print(f"[paddle_tpu.launch] job failed (rc={failed_ret}); elastic "
              f"restart {attempt}/{elastic_retries}", flush=True)


def launch_elastic(script, script_args=(), nproc_per_node=1,
                   host="127.0.0.1", start_port=6170, heartbeat_dir=None,
                   max_restarts=None, stall_timeout_s=None,
                   heartbeat_timeout_s=None, backoff_s=None):
    """Detection-driven elastic launch (`--elastic`): instead of the
    blind whole-job restart of `launch(elastic_retries=...)`, a
    `Supervisor` (distributed/elastic.py) watches each trainer's exit
    status AND its heartbeat file, and kills+restarts INDIVIDUAL
    trainers on death, heartbeat silence, or stalled step progress —
    with linear backoff and a PADDLE_ELASTIC_MAX_RESTARTS budget per
    rank. Trainers see the heartbeat directory as
    $PADDLE_ELASTIC_HEARTBEAT_DIR and should run a
    `Heartbeat(dir, step_fn=...)` + auto-checkpoint; restart recovery is
    exact via the verified checkpoint tier."""
    endpoints = [f"{host}:{start_port + i}" for i in range(nproc_per_node)]
    heartbeat_dir = heartbeat_dir or os.environ.get(
        "PADDLE_ELASTIC_HEARTBEAT_DIR")

    def start_rank(rank):
        env = _build_env(rank, nproc_per_node, endpoints)
        if heartbeat_dir:
            env["PADDLE_ELASTIC_HEARTBEAT_DIR"] = heartbeat_dir
        return subprocess.Popen([sys.executable, script, *script_args],
                                env=env)

    return Supervisor(start_rank, nranks=nproc_per_node,
                      heartbeat_dir=heartbeat_dir,
                      max_restarts=max_restarts,
                      stall_timeout_s=stall_timeout_s,
                      heartbeat_timeout_s=heartbeat_timeout_s,
                      backoff_s=backoff_s).run()


def launch_ps(script, script_args=(), server_num=1, worker_num=2,
              host="127.0.0.1", start_port=6270, elastic_retries=0):
    """PS-mode launcher (reference fleet launch --server_num/--worker_num,
    python/paddle/distributed/fleet/launch.py): starts server processes
    (TRAINING_ROLE=PSERVER) and worker processes (TRAINING_ROLE=TRAINER)
    with the PADDLE_PSERVERS_IP_PORT_LIST contract. The job succeeds when
    every WORKER exits 0 (servers are then terminated); a worker failure
    kills the job and, with elastic_retries > 0, restarts servers AND
    workers — scripts recover table state via PSClient.load_snapshot
    (large_scale_kv checkpointing analog)."""
    eps = [f"{host}:{start_port + i}" for i in range(server_num)]

    def start_all(attempt):
        base = dict(os.environ)
        base["PADDLE_PSERVERS_IP_PORT_LIST"] = ",".join(eps)
        base["PADDLE_TRAINERS_NUM"] = str(worker_num)
        base["PADDLE_LAUNCH_ATTEMPT"] = str(attempt)
        servers = []
        for i in range(server_num):
            env = dict(base)
            env.update({"TRAINING_ROLE": "PSERVER",
                        "PADDLE_PSERVER_ID": str(i),
                        "PADDLE_PORT": eps[i].rsplit(":", 1)[1]})
            servers.append(subprocess.Popen(
                [sys.executable, script, *script_args], env=env))
        workers = []
        for i in range(worker_num):
            env = dict(base)
            env.update({"TRAINING_ROLE": "TRAINER",
                        "PADDLE_TRAINER_ID": str(i)})
            workers.append(subprocess.Popen(
                [sys.executable, script, *script_args], env=env))
        return servers, workers

    attempt = 0
    while True:
        servers, workers = start_all(attempt)
        failed_ret = None
        live = list(workers)
        try:
            while live and failed_ret is None:
                for p in list(live):
                    ret = p.poll()
                    if ret is None:
                        continue
                    live.remove(p)
                    if ret != 0:
                        failed_ret = ret
                for s in servers:          # a dead server fails the job
                    ret = s.poll()
                    if ret is not None and ret != 0 and failed_ret is None:
                        failed_ret = ret
                time.sleep(0.3)
        finally:
            # bounded reap with KILL escalation for EVERY child: a hung
            # server must neither raise TimeoutExpired through this
            # teardown nor leak the rest of the fleet
            _reap(live + servers, grace_s=30.0)
        if failed_ret is None:
            return 0
        attempt += 1
        if attempt > elastic_retries:
            raise SystemExit(failed_ret)
        print(f"[paddle_tpu.launch] ps job failed (rc={failed_ret}); "
              f"elastic restart {attempt}/{elastic_retries}", flush=True)


def main():
    import argparse
    ap = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    ap.add_argument("--nproc_per_node", type=int, default=1)
    ap.add_argument("--started_port", type=int, default=6170)
    ap.add_argument("--elastic_retries", type=int, default=0)
    ap.add_argument("--elastic", action="store_true",
                    help="supervisor-driven per-trainer restart "
                         "(heartbeat/stall detection, "
                         "PADDLE_ELASTIC_* knobs) instead of the "
                         "whole-job elastic_retries loop")
    ap.add_argument("--heartbeat_dir", default=None,
                    help="heartbeat directory for --elastic "
                         "(default $PADDLE_ELASTIC_HEARTBEAT_DIR)")
    ap.add_argument("--server_num", type=int, default=0)
    ap.add_argument("--worker_num", type=int, default=0)
    ap.add_argument("script")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if args.server_num or args.worker_num:
        return launch_ps(args.script, args.script_args,
                         server_num=max(args.server_num, 1),
                         worker_num=max(args.worker_num, 1),
                         start_port=args.started_port,
                         elastic_retries=args.elastic_retries)
    if args.elastic:
        return launch_elastic(args.script, args.script_args,
                              args.nproc_per_node,
                              start_port=args.started_port,
                              heartbeat_dir=args.heartbeat_dir)
    return launch(args.script, args.script_args, args.nproc_per_node,
                  start_port=args.started_port,
                  elastic_retries=args.elastic_retries)


if __name__ == "__main__":
    sys.exit(main())

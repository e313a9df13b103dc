"""Distributed launch CLI.

Analog of `python/paddle/distributed/launch/main.py:23` + the collective
controller (`launch/controllers/collective.py:22`, elastic variant `:262`)
and watcher (`launch/controllers/watcher.py`) — SURVEY.md §3.4 step 1-2 and
§5.3 failure detection.

Spawns one worker process per node (TPU: all local chips belong to one
process — unlike the reference's process-per-GPU), wires the env contract
(PADDLE_TRAINER_ID, PADDLE_TRAINERS_NUM, PADDLE_MASTER,
PADDLE_TRAINER_ENDPOINTS, PADDLE_CURRENT_ENDPOINT), watches children, tears
the job down on failure, and (elastic mode) relaunches up to
--max_restart times. Workers rendezvous through the JAX coordination
service (`init_parallel_env` reads the same env).
"""
from __future__ import annotations

import argparse
import glob
import os
import signal
import subprocess
import sys
import time

__all__ = ["main", "launch"]


def build_parser():
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="launch a distributed training job")
    p.add_argument("--master", default=None,
                   help="coordinator endpoint ip:port")
    p.add_argument("--nnodes", default="1",
                   help="node count or min:max range (elastic)")
    p.add_argument("--nproc_per_node", type=int, default=1,
                   help="worker processes per node (TPU: usually 1 per host)")
    p.add_argument("--rank", type=int, default=-1, help="node rank")
    p.add_argument("--run_mode", default="collective",
                   choices=["collective", "ps"])
    p.add_argument("--job_id", default="default")
    p.add_argument("--devices", "--gpus", "--xpus", default=None,
                   help="accepted for the reference CLI's sake and ignored: "
                        "one process drives every local chip")
    p.add_argument("--log_dir", default="log")
    p.add_argument("--log_level", default="INFO")
    p.add_argument("--max_restart", type=int, default=3,
                   help="elastic: relaunch budget after worker failure")
    p.add_argument("--elastic_level", type=int, default=-1,
                   help=">0 (or nnodes=min:max) enables membership-based "
                        "elastic scale up/down")
    p.add_argument("--elastic_store", default=None,
                   help="membership store path (default <log_dir>/elastic."
                        "json); external pods registered here join the job "
                        "at the next restart (single-launcher build: all "
                        "pods run as this launcher's local processes)")
    p.add_argument("--elastic_timeout", type=float, default=15.0,
                   help="seconds to wait for membership >= min after a "
                        "failure before giving up")
    p.add_argument("--host", default="127.0.0.1")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    return p


def _worker_env(args, local_rank: int, world_size: int, base_port: int):
    env = dict(os.environ)
    rank = max(args.rank, 0) * args.nproc_per_node + local_rank
    endpoints = ",".join(f"{args.host}:{base_port + i}"
                         for i in range(world_size))
    env.update({
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world_size),
        "PADDLE_GLOBAL_SIZE": str(world_size),
        "PADDLE_LOCAL_RANK": str(local_rank),
        "PADDLE_TRAINER_ENDPOINTS": endpoints,
        "PADDLE_CURRENT_ENDPOINT": f"{args.host}:{base_port + rank}",
        "PADDLE_MASTER": args.master or f"{args.host}:{base_port - 1}",
    })
    return env


def _on_tpu_host() -> bool:
    """Whether this host has TPU chips, told from the device files libtpu
    opens (`/dev/accel*`, or `/dev/vfio/<n>` on the VFIO hosts) — without
    JAX: a launcher that initialised the backend would itself hold every
    chip its workers need."""
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def _spawn(args, world_size, base_port):
    if args.nproc_per_node > 1 and _on_tpu_host() \
            and os.environ.get("JAX_PLATFORMS", "").lower() != "cpu":
        # every worker gets the same environment, so each would claim
        # every chip: the second one fails or hangs inside libtpu
        raise SystemExit(
            f"[launch] --nproc_per_node={args.nproc_per_node} on a TPU "
            "host: a chip belongs to one process, and one process drives "
            "all local chips — launch one worker per host (or set "
            "JAX_PLATFORMS=cpu for a CPU job)")
    procs = []
    os.makedirs(args.log_dir, exist_ok=True)
    for local_rank in range(args.nproc_per_node):
        env = _worker_env(args, local_rank, world_size, base_port)
        log_path = os.path.join(args.log_dir,
                                f"workerlog.{env['PADDLE_TRAINER_ID']}")
        log_f = open(log_path, "a")  # append: elastic restarts must not
        # erase the previous round's history
        cmd = [sys.executable, "-u", args.training_script] + \
            args.training_script_args
        procs.append((subprocess.Popen(cmd, env=env, stdout=log_f,
                                       stderr=subprocess.STDOUT), log_f))
    return procs


def _watch(procs, on_tick=None) -> tuple:
    """Block until all exit or one fails; on failure kill the rest
    (reference watcher + LauncherInterface._terminate_procs). Returns
    (exit_code, failed_local_ranks). `on_tick` runs each poll cycle
    (elastic heartbeats)."""
    def _kill_all():
        for other, _ in procs:
            if other.poll() is None:
                other.send_signal(signal.SIGTERM)
        time.sleep(2)
        for other, _ in procs:
            if other.poll() is None:
                other.kill()

    while True:
        failed = [i for i, (proc, _) in enumerate(procs)
                  if proc.poll() not in (None, 0)]
        if failed:
            code = procs[failed[0]][0].poll()
            _kill_all()
            return code, failed
        if not any(proc.poll() is None for proc, _ in procs):
            return 0, []
        if on_tick is not None and on_tick():
            # membership changed (scale-out joiner): graceful restart
            _kill_all()
            return "rescale", []
        time.sleep(0.5)


def launch(argv=None) -> int:
    args = build_parser().parse_args(argv)
    parts = str(args.nnodes).split(":")
    min_n, max_n = int(parts[0]), int(parts[-1])
    base_port = 36000 + (hash(args.job_id) % 1000)
    elastic = max_n > min_n or args.elastic_level > 0
    if elastic:
        return _launch_elastic(args, min_n, max_n, base_port)
    world_size = min_n * args.nproc_per_node
    restarts = 0
    while True:
        procs = _spawn(args, world_size, base_port)
        code, _failed = _watch(procs)
        for _, f in procs:
            f.close()
        if code == 0:
            return 0
        restarts += 1
        if restarts > args.max_restart:
            print(f"[launch] workers failed (exit {code}); restart budget "
                  f"exhausted after {restarts - 1} retries", file=sys.stderr)
            return code
        print(f"[launch] worker failed (exit {code}); relaunch "
              f"{restarts}/{args.max_restart}", file=sys.stderr)


def _launch_elastic(args, min_n, max_n, base_port) -> int:
    """Membership-based elastic controller (reference
    `fleet/elastic/manager.py:125,410,457`): every worker slot is a pod in
    the MembershipStore; a dead pod is deregistered, the world shrinks to
    the surviving members (>= min), and externally registered pods scale it
    back up on the next restart — ranks regenerated each round. Workers see
    the new world via the standard env contract and resume from their last
    checkpoint (reshard-on-load).

    Single-launcher build: every pod in the store runs as a LOCAL process
    of this launcher (joiners are adopted on restart), so this launcher
    owns — and heartbeats — every pod it spawned. Multi-launcher
    coordination over a shared store is the designed extension point, not
    implemented here."""
    from ..elastic import ElasticManager, MembershipStore

    # single-host model: each worker process is a pod; nnodes=min:max bounds
    # the worker count and nproc_per_node is the initial pod count. A
    # multi-host job runs one launcher per node sharing --elastic_store.
    min_w, max_w = min_n, max_n
    init_w = max(min_w, min(args.nproc_per_node, max_w))
    store_path = args.elastic_store or os.path.join(args.log_dir,
                                                    "elastic.json")
    os.makedirs(args.log_dir, exist_ok=True)
    store = MembershipStore(store_path, ttl=max(args.elastic_timeout, 10.0))
    mgr = ElasticManager(store, min_w, max_w, stabilize_s=0.3)
    # zero-padded ids: pod order (lexicographic) == numeric slot order
    for i in range(init_w):  # seed membership with this launcher's slots
        mgr.register(f"{args.host}:slot{i:04d}",
                     f"{args.host}:{base_port + i}")

    restarts = 0
    while True:
        pods = mgr.wait_for_world(deadline_s=args.elastic_timeout)
        if pods is None:
            print(f"[launch][elastic] membership below min ({min_w}) for "
                  f"{args.elastic_timeout}s; giving up", file=sys.stderr)
            return 1
        world_size = len(pods)
        print(f"[launch][elastic] starting round with world_size="
              f"{world_size} pods={pods}", file=sys.stderr, flush=True)
        args.nproc_per_node = world_size  # all pods local in this model
        procs = _spawn(args, world_size, base_port)

        def tick(pods=pods):
            # one locked store write renews every local pod's lease
            mgr.heartbeat_many(pods)
            changed, now = mgr.scale_changed(pods)
            # scale OUT mid-round (a joiner registered): restart to adopt
            # it; scale-in is driven by process death, not membership
            return changed and len(now) > len(pods) and \
                len(now) >= min_w

        code, failed = _watch(procs, on_tick=tick)
        for _, f in procs:
            f.close()
        if code == 0:
            return 0
        if code == "rescale":
            print("[launch][elastic] membership grew; restarting with the "
                  "larger world", file=sys.stderr, flush=True)
            continue  # voluntary: not counted against the restart budget
        dead = [pods[idx] for idx in failed if idx < len(pods)]
        for pid in dead:  # fault detection -> membership update
            print(f"[launch][elastic] pod {pid} died (exit {code}); "
                  "deregistering", file=sys.stderr, flush=True)
            mgr.report_dead(pid)
        restarts += 1
        if restarts > args.max_restart:
            print(f"[launch][elastic] restart budget exhausted after "
                  f"{restarts - 1} retries", file=sys.stderr)
            return code
        if len(mgr.ranks()) < min_w:
            # below min with budget left: this launcher owns the dead local
            # slots, so re-register them — a fault-tolerance restart at the
            # same scale instead of aborting (elastic must not be LESS
            # fault-tolerant than the plain relaunch path)
            for pid in dead:
                print(f"[launch][elastic] re-registering local slot {pid} "
                      "to stay above min", file=sys.stderr, flush=True)
                mgr.register(pid)
        print(f"[launch][elastic] relaunch {restarts}/{args.max_restart} "
              f"with regenerated ranks", file=sys.stderr, flush=True)


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()

"""``python -m paddle_tpu.distributed.launch`` (``python/paddle/
distributed/launch/`` parity).

The reference spawns one process per GPU with PADDLE_TRAINER_* env and an
HTTP/etcd master. Single-controller jax on TPU wants ONE process per host
seeing all local chips (a chip belongs to one process: a second one that
reaches for it fails or hangs), so the default is nprocs=1 with the env
set for rank bookkeeping. ``--nproc_per_node`` > 1 spawns the reference's
multi-process layout for CPU emulation/tests only (each proc gets the
same CPU device view; collectives run through the TCPStore) and is
refused where the children would share an accelerator. The launcher
itself never touches JAX — it must not hold the chip its child needs.
"""
from __future__ import annotations

import glob
import os
import subprocess
import sys


def children_share_chip(nprocs: int, environ=None) -> bool:
    """Would ``nprocs`` children started from this process contend for
    one accelerator? Answered WITHOUT touching JAX (asking JAX would
    take the chip): children held to the CPU by ``JAX_PLATFORMS`` never
    do; otherwise they do whenever the host has TPU device nodes, since
    every child sees the same chips."""
    if nprocs <= 1:
        return False
    environ = os.environ if environ is None else environ
    platforms = environ.get("JAX_PLATFORMS", "")
    if platforms:
        return platforms.split(",")[0].strip().lower() != "cpu"
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def parse_args(argv):
    import argparse
    p = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    p.add_argument("--devices", "--gpus", "--xpus", default=None,
                   dest="devices")
    p.add_argument("--nnodes", default="1",
                   help="node count, or 'min:max' for elastic range")
    p.add_argument("--ips", default=None,
                   help="comma-separated host list for multi-node; "
                        "this node's position = --rank (or inferred "
                        "from the local hostname/IP)")
    p.add_argument("--nproc_per_node", type=int, default=None)
    p.add_argument("--master", default=None)
    p.add_argument("--rank", type=int, default=-1,
                   help="node rank among --ips (-1: infer)")
    p.add_argument("--log_dir", default="log")
    p.add_argument("--job_id", default="default")
    p.add_argument("--max_restarts", type=int, default=0,
                   help="elastic: relaunch the pod this many times "
                        "after a worker failure (checkpoint-resume is "
                        "the training script's job)")
    p.add_argument("--elastic_level", type=int, default=0,
                   help=">0 enables heartbeat hang-detection: workers "
                        "register with the controller's TCPStore and a "
                        "rank whose heartbeat stops (hung, not just "
                        "exited) triggers pod restart")
    p.add_argument("--elastic_timeout", type=float, default=30.0,
                   help="seconds without a heartbeat before a rank is "
                        "declared dead (with --elastic_level > 0)")
    p.add_argument("training_script")
    p.add_argument("training_script_args", nargs="...")
    return p.parse_args(argv)


def _node_layout(args, nprocs):
    """(hosts, node_rank, master): the multi-node topology. Single-node
    default is localhost; with --ips the reference semantics apply —
    node 0's address hosts the master, global trainer ids are
    node_rank*nprocs + local_rank."""
    import socket
    if not args.ips:
        return ["127.0.0.1"], 0, args.master or "127.0.0.1:6170"
    hosts = [h.strip() for h in args.ips.split(",") if h.strip()]
    node_rank = args.rank
    if node_rank < 0:
        me = {socket.gethostname(), "127.0.0.1", "localhost"}
        try:
            me.add(socket.gethostbyname(socket.gethostname()))
        except OSError:
            pass
        matches = [i for i, h in enumerate(hosts) if h in me]
        if len(matches) != 1:
            raise SystemExit(
                f"launch: cannot infer this node's rank among "
                f"--ips {hosts}; pass --rank explicitly")
        node_rank = matches[0]
    master = args.master or f"{hosts[0]}:6170"
    return hosts, node_rank, master


def _spawn_pod(args, nprocs, attempt, elastic_port=None):
    """Start one process per LOCAL rank; returns [(Popen, log_file)].
    Multi-node: global ids/endpoints span every host in --ips."""
    hosts, node_rank, master = _node_layout(args, nprocs)
    endpoints = ",".join(f"{h}:{6170 + i}" for h in hosts
                         for i in range(nprocs))
    world = len(hosts) * nprocs
    procs = []
    for rank in range(nprocs):
        global_rank = node_rank * nprocs + rank
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(global_rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_TRAINER_ENDPOINTS": endpoints,
            "PADDLE_CURRENT_ENDPOINT":
                f"{hosts[node_rank]}:{6170 + rank}",
            "PADDLE_MASTER": master,
            "PADDLE_NODE_RANK": str(node_rank),
            "PADDLE_LOCAL_SIZE": str(nprocs),
            "PADDLE_RESTART_ATTEMPT": str(attempt),
            "PADDLE_LOG_DIR": args.log_dir,
            "FLAGS_selected_gpus": str(rank),
        })
        if elastic_port is not None:
            env.update({
                "PADDLE_ELASTIC_ENABLE": "1",
                "PADDLE_ELASTIC_PORT": str(elastic_port),
                "PADDLE_ELASTIC_EXTERNAL": "1",  # controller owns store
            })
        suffix = f".{attempt}" if attempt else ""
        log = open(os.path.join(args.log_dir,
                                f"workerlog.{rank}{suffix}"), "w")
        cmd = [sys.executable, args.training_script] + \
            list(args.training_script_args)
        procs.append((subprocess.Popen(
            cmd, env=env,
            stdout=log if rank != 0 else None,
            stderr=subprocess.STDOUT if rank != 0 else None), log))
    return procs


def _watch_pod(procs, poll_s=0.2, watcher=None, register_deadline=120.0):
    """Reference controller watch loop: poll children; on the FIRST
    non-zero exit kill the whole pod (a half-dead mesh cannot make
    progress) and report failure. With an ElasticManager ``watcher``,
    a hung rank also fails the pod — whether it hung after starting
    (beat went stale) or during startup (never registered within
    ``register_deadline`` seconds). Returns 0 when all exit clean."""
    import time
    live = list(procs)
    failed = 0
    t0 = time.monotonic()
    while live and not failed:
        time.sleep(poll_s)
        for p, _log in list(live):
            rc = p.poll()
            if rc is None:
                continue
            live.remove((p, _log))
            if rc != 0:
                failed = rc
                break
        if not failed and watcher is not None and live:
            polled = watcher.poll()  # ONE store sweep per tick
            if polled["dead"]:
                print("[launch] heartbeat lost for ranks "
                      f"{polled['dead']}; failing the pod",
                      file=sys.stderr)
                failed = 1
            elif polled["pending"] and \
                    time.monotonic() - t0 > register_deadline:
                print("[launch] ranks never registered within "
                      f"{register_deadline}s: {polled['pending']}; "
                      "failing the pod", file=sys.stderr)
                failed = 1
    if failed:
        for p, _log in live:
            try:
                p.terminate()
            except OSError:
                pass
        deadline = time.monotonic() + 10
        for p, _log in live:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                p.kill()
    for _p, log in procs:
        log.close()
    return failed


def launch(argv=None):
    args = parse_args(argv if argv is not None else sys.argv[1:])
    nprocs = args.nproc_per_node or 1
    if children_share_chip(nprocs):
        raise SystemExit(
            f"launch: --nproc_per_node {nprocs} would start {nprocs} "
            "processes that all open this host's accelerator, and a "
            "chip belongs to one process. Run one process per host (it "
            "drives every local chip through the mesh), or set "
            "JAX_PLATFORMS=cpu for the multi-process CPU emulation.")
    os.makedirs(args.log_dir, exist_ok=True)
    watcher = None
    elastic_port = None
    if args.elastic_level:
        if args.ips:
            # per-node watchers would poll GLOBAL ranks that register
            # on other nodes and kill healthy jobs; multi-node hang
            # detection needs the (future) cross-node master —
            # exit-code watching and --max_restarts still apply
            print("[launch] --elastic_level heartbeat watch is "
                  "single-node only; multi-node runs keep exit-code "
                  "watching", file=sys.stderr)
        else:
            from ..fleet.elastic import ElasticManager
            # controller hosts the liveness store; workers only connect
            watcher = ElasticManager(port=0, world_size=nprocs,
                                     is_master=True,
                                     timeout=args.elastic_timeout)
            elastic_port = watcher.port
    attempt = 0
    while True:
        procs = _spawn_pod(args, nprocs, attempt,
                           elastic_port=elastic_port)
        code = _watch_pod(procs, watcher=watcher,
                          register_deadline=max(
                              60.0, 10 * args.elastic_timeout))
        if code == 0:
            return
        if attempt >= args.max_restarts:
            raise SystemExit(code)
        attempt += 1
        if watcher is not None:
            watcher.reset()  # stale beats must not mask the next pod
        print(f"[launch] pod failed (rc={code}); elastic restart "
              f"{attempt}/{args.max_restarts}", file=sys.stderr)


def main():
    launch()

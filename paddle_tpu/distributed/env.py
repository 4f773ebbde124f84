"""Distributed environment: the Mesh is the ProcessGroup.

Reference parity: ``ProcessGroupNCCL`` + ``TCPStore`` bootstrap
(``paddle/fluid/distributed/collective/``, ``paddle/fluid/distributed/
store/tcp_store.cc``). TPU-first: ``jax.distributed.initialize`` is the
rendezvous, ``jax.sharding.Mesh`` axes are the process groups, collectives
are XLA ops over ICI/DCN (SURVEY.md §5.8 mapping).

Single-controller jax means "rank" here is the process index
(``jax.process_index``), and intra-process device parallelism is expressed
with shardings rather than ranks.
"""
from __future__ import annotations

import os
from typing import Dict, List, Optional

import numpy as np

import jax


class ParallelEnv:
    """``paddle.distributed.ParallelEnv`` parity."""

    def __init__(self):
        self._init_from_env()

    def _init_from_env(self):
        # a launcher's environment answers without touching JAX: the
        # process index / count queries initialise the backend, which
        # must not happen before ``jax.distributed.initialize``
        rank = os.environ.get("PADDLE_TRAINER_ID")
        self.rank = int(rank) if rank is not None \
            else jax.process_index()
        eps = os.environ.get("PADDLE_TRAINER_ENDPOINTS", "")
        world = os.environ.get("PADDLE_TRAINERS_NUM")
        if world is not None:
            self.world_size = int(world)
        else:
            self.world_size = len(eps.split(",")) if eps \
                else jax.process_count()
        self.device_id = int(os.environ.get("FLAGS_selected_gpus",
                                            "0").split(",")[0])
        self.current_endpoint = os.environ.get("PADDLE_CURRENT_ENDPOINT",
                                               "127.0.0.1:6170")
        self.trainer_endpoints = eps.split(",") if eps else [
            self.current_endpoint]

    @property
    def nranks(self):
        return self.world_size

    @property
    def local_rank(self):
        return self.rank

    @property
    def dev_id(self):
        return self.device_id


_parallel_env: Optional[ParallelEnv] = None
_initialized = False
_global_mesh: Optional[jax.sharding.Mesh] = None


def _env() -> ParallelEnv:
    global _parallel_env
    if _parallel_env is None:
        _parallel_env = ParallelEnv()
    return _parallel_env


def init_parallel_env(strategy=None):
    """``paddle.distributed.init_parallel_env`` — multi-host rendezvous via
    the jax coordination service when endpoints are configured."""
    global _initialized
    if _initialized:
        return _env()
    env = _env()
    coord = os.environ.get("PADDLE_MASTER") or \
        os.environ.get("MASTER_ADDR")
    # One process per host drives all its chips, so the rendezvous is
    # for jobs that run ONE process on each of several hosts. A
    # launcher that starts several processes on this host
    # (PADDLE_LOCAL_SIZE > 1) is the CPU emulation layout: its ranks
    # share one device view and talk through the TCPStore collectives,
    # never through the coordination service.
    local_size = int(os.environ.get("PADDLE_LOCAL_SIZE", "1"))
    if coord and env.world_size > 1 and local_size == 1 \
            and not jax.distributed.is_initialized():
        port = os.environ.get("MASTER_PORT", "8476")
        jax.distributed.initialize(
            coordinator_address=f"{coord}:{port}"
            if ":" not in coord else coord,
            num_processes=env.world_size, process_id=env.rank)
    log_dir = os.environ.get("PADDLE_LOG_DIR")
    if log_dir:
        from ..framework.log import init_per_rank_logging
        init_per_rank_logging(log_dir, rank=env.rank)
    from ..framework.log import vlog
    vlog(1, "init_parallel_env: rank %d / world %d", env.rank,
         env.world_size)
    if os.environ.get("PADDLE_ELASTIC_ENABLE") == "1" \
            and env.world_size > 1:
        try:
            _start_elastic_heartbeat(env, coord)
        except Exception as exc:
            import warnings
            warnings.warn(
                f"elastic heartbeat disabled: could not reach the "
                f"liveness store ({exc!r}); training continues without "
                "hang detection")
    _initialized = True
    return env


def _start_elastic_heartbeat(env, coord):
    """Opt-in (PADDLE_ELASTIC_ENABLE=1): register this rank with the
    native-TCPStore ElasticManager and beat in a daemon thread so the
    launch controller's watch loop sees liveness (SURVEY §5.3)."""
    import threading
    import time
    from .fleet.elastic import ElasticManager
    host = (coord or "127.0.0.1").split(":")[0]
    port = int(os.environ.get("PADDLE_ELASTIC_PORT", "6179"))
    interval = float(os.environ.get("PADDLE_ELASTIC_BEAT_S", "5"))
    # PADDLE_ELASTIC_EXTERNAL=1: the launch controller hosts the store
    # (it outlives pod restarts); otherwise rank 0 hosts it in-process
    external = os.environ.get("PADDLE_ELASTIC_EXTERNAL") == "1"
    mgr = ElasticManager(host=host, port=port, rank=env.rank,
                         world_size=env.world_size,
                         is_master=(not external) and env.rank == 0,
                         timeout=3 * interval)
    mgr.register()

    def beat():
        while not getattr(mgr, "_stop_beat", False):
            time.sleep(interval)
            try:
                mgr.heartbeat()
            except Exception:
                return  # store gone: job is tearing down

    t = threading.Thread(target=beat, daemon=True,
                         name="paddle-elastic-heartbeat")
    t.start()

    def _stop_at_exit():
        # a daemon thread killed mid-ctypes-RPC at interpreter shutdown
        # segfaults — stop it, join, then shut the socket down (close
        # unblocks any straggling RPC safely: tcp_store.cc close locks
        # the request mutex and only invalidates the fd)
        mgr._stop_beat = True
        t.join(timeout=interval + 1.0)
        try:
            mgr.deregister()  # clean exit != death: no spurious restart
            mgr.close()
        except Exception:
            pass

    import atexit
    atexit.register(_stop_at_exit)
    env.elastic_manager = mgr


def is_initialized() -> bool:
    return _initialized


def get_rank(group=None) -> int:
    if group is not None and hasattr(group, "rank"):
        return group.rank
    return _env().rank


def get_world_size(group=None) -> int:
    if group is not None and hasattr(group, "nranks"):
        return group.nranks
    return _env().world_size


def device_mesh(shape: Dict[str, int] = None) -> jax.sharding.Mesh:
    """The global device mesh. Default: all local devices on one 'dp' axis;
    fleet topology reshapes it into (pp, dp, sharding, sep, mp) axes."""
    global _global_mesh
    if shape is None:
        if _global_mesh is None:
            devs = np.array(jax.devices())
            _global_mesh = jax.sharding.Mesh(devs, ("dp",))
        return _global_mesh
    names = tuple(shape.keys())
    sizes = tuple(shape.values())
    devs = np.array(jax.devices())
    total = int(np.prod(sizes))
    if total > devs.size:
        raise ValueError(
            f"mesh {dict(shape)} needs {total} devices, "
            f"have {devs.size}")
    mesh = jax.sharding.Mesh(devs[:total].reshape(sizes), names)
    _global_mesh = mesh
    return mesh


def set_mesh(mesh: jax.sharding.Mesh):
    global _global_mesh
    _global_mesh = mesh


def get_mesh() -> Optional[jax.sharding.Mesh]:
    return _global_mesh

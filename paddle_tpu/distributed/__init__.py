"""``paddle.distributed`` namespace (L4 in SURVEY.md §1).

Mesh-based: process groups are mesh axes, collectives are XLA ops, the
launcher shims onto single-controller jax or multi-process emulation.
"""
from .env import (ParallelEnv, get_rank, get_world_size, init_parallel_env,
                  is_initialized, device_mesh, get_mesh, set_mesh)
from .collective import (Group, P2POp, ReduceOp, all_gather,
                         all_gather_object, all_reduce, alltoall,
                         alltoall_single, barrier, batch_isend_irecv,
                         broadcast, broadcast_object_list, get_group,
                         isend, irecv, new_group, recv, reduce, reduce_scatter,
                         scatter, send, wait, _all_reduce_eager_mean)
from . import collective_ops
from .collective_ops import *  # noqa: F401,F403
from . import fleet
from . import auto_parallel
from . import checkpoint
from . import rpc
from . import ps
from . import sharding as sharding_mod
from .auto_parallel import (DistAttr, Partial, Placement, ProcessMesh,
                            Replicate, Shard, Strategy, dtensor_from_fn,
                            reshard, shard_layer, shard_optimizer,
                            shard_tensor, to_static, unshard_dtensor)
from .checkpoint import load_state_dict, save_state_dict
from .moe import MoELayer


def __getattr__(name):
    # TCPStore is native (ctypes over native/tcp_store.cc); import lazily
    # so `import paddle_tpu` works before the lib is first built.
    if name == "TCPStore":
        from ..native import TCPStore
        return TCPStore
    raise AttributeError(name)
from .pipeline import pipeline_apply, stack_stage_params
from .recompute import recompute, recompute_sequential
from .ring_attention import RingFlashAttention, ring_flash_attention
from .sep_parallel import (ReshardLayer, sep_attention,
                           ulysses_attention)
from .shard_utils import constraint as shard_op_constraint
from .sharding import group_sharded_parallel, save_group_sharded_model

# paddle.distributed.sharding submodule path parity
import sys as _sys
_sys.modules[__name__ + ".sharding"] = sharding_mod
sharding = sharding_mod


def spawn(func, args=(), nprocs=-1, join=True, daemon=False, **options):
    """``paddle.distributed.spawn`` — multiprocess launch for CPU
    emulation (each proc sees the same CPU device view). Refused where
    the children would share an accelerator: a chip belongs to one
    process, and one process drives every local chip through the
    mesh."""
    import multiprocessing as mp
    import os
    import jax
    from jax._src import xla_bridge
    from .launch import children_share_chip
    if nprocs == -1:
        nprocs = 1
    parent_holds_chip = xla_bridge.backends_are_initialized() \
        and jax.default_backend() != "cpu"
    if parent_holds_chip or children_share_chip(nprocs):
        raise RuntimeError(
            f"distributed.spawn(nprocs={nprocs}): the children would "
            "open an accelerator that "
            + ("this process already holds" if parent_holds_chip
               else "they all share")
            + ", and a chip belongs to one process; set "
            "JAX_PLATFORMS=cpu for multi-process CPU emulation")
    procs = []
    for rank in range(nprocs):
        env = dict(os.environ)
        env["PADDLE_TRAINER_ID"] = str(rank)
        env["PADDLE_TRAINERS_NUM"] = str(nprocs)

        def target(r=rank, e=env):
            os.environ.update(e)
            func(*args)

        p = mp.Process(target=target, daemon=daemon)
        p.start()
        procs.append(p)
    if join:
        for p in procs:
            p.join()
        for p in procs:
            if p.exitcode != 0:
                raise RuntimeError(
                    f"spawned process exited with {p.exitcode}")
    return procs


def get_backend():
    return "xla"

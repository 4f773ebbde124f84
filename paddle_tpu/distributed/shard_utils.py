"""GSPMD sharding helpers shared by TP/SP/sharding/auto-parallel layers.

Design (SURVEY.md §7.2): parallel layers are *facades that set
PartitionSpecs*. Parameters carry ``dist_spec``; activations get
``with_sharding_constraint`` hints; XLA/GSPMD inserts the collectives the
reference implements by hand in ``ProcessGroupNCCL``.
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..framework.core import Tensor, apply_jax, as_jax, _wrap_out
from . import env as _env

__all__ = ["P", "mesh_axis_size", "annotate_param", "constraint",
           "place_param", "batch_shard", "current_mesh", "manual_region",
           "in_manual_region"]


def current_mesh() -> Optional[Mesh]:
    return _env.get_mesh()


# Inside a shard_map body the mesh axes are Manual — GSPMD constraint /
# reshard ops emitted there (by TP layers etc.) are invalid. The pipeline
# engine traces its stage functions under this flag so the sharding
# facades become identities; the shard_map in/out specs already define
# the data placement.
import contextlib as _contextlib
import threading as _threading

_manual_tls = _threading.local()


@_contextlib.contextmanager
def manual_region():
    prev = getattr(_manual_tls, "on", False)
    _manual_tls.on = True
    try:
        yield
    finally:
        _manual_tls.on = prev


def in_manual_region() -> bool:
    return getattr(_manual_tls, "on", False)


def mesh_axis_size(axis) -> int:
    mesh = current_mesh()
    if mesh is None:
        return 1
    if isinstance(axis, (tuple, list)):
        n = 1
        for a in axis:
            n *= mesh.shape.get(a, 1)
        return n
    return mesh.shape.get(axis, 1)


def annotate_param(param: Tensor, spec: Sequence):
    """Attach a PartitionSpec to a parameter and (eagerly) place it."""
    param.dist_spec = P(*spec)
    place_param(param)
    return param


def place_param(param: Tensor):
    mesh = current_mesh()
    spec = getattr(param, "dist_spec", None)
    if mesh is None or spec is None:
        return param
    # only shard axes that exist with size>1; GSPMD treats missing as
    # replicated
    try:
        param._data = jax.device_put(param._data,
                                     NamedSharding(mesh, spec))
    except Exception:
        pass  # mesh smaller than spec (e.g. degree 1) -> replicated
    return param


def _is_tracer(x):
    return isinstance(x, jax.core.Tracer)


def constraint(x, *spec):
    """with_sharding_constraint as a differentiable identity op."""
    mesh = current_mesh()
    if mesh is None or in_manual_region():
        return x if isinstance(x, Tensor) else _wrap_out(as_jax(x))
    sharding = NamedSharding(mesh, P(*spec))

    def f(a):
        try:
            return jax.lax.with_sharding_constraint(a, sharding)
        except Exception:
            return a
    return apply_jax("sharding_constraint", f, x)


def batch_shard(x, axes=("dp", "sharding", "ep")):
    """Shard the leading (batch) dim over the data-parallel axes (the
    expert axis carries tokens too: EP shards the batch like DP and
    exchanges (token, slot) pairs by all-to-all inside the MoE
    dispatch)."""
    mesh = current_mesh()
    if mesh is None or in_manual_region():
        return x
    live = tuple(a for a in axes if mesh.shape.get(a, 1) > 1)
    if not live:
        return x
    arr = as_jax(x)
    spec = P(live) if len(live) > 1 else P(live[0])
    full = P(*([spec[0]] + [None] * (arr.ndim - 1)))
    if _is_tracer(arr):
        out = jax.lax.with_sharding_constraint(
            arr, NamedSharding(mesh, full))
    else:
        out = jax.device_put(arr, NamedSharding(mesh, full))
    if isinstance(x, Tensor):
        x._data = out
        return x
    return _wrap_out(out)

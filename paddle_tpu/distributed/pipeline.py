"""Pipeline parallelism on TPU: GPipe/1F1B as shard_map + collective_permute.

Reference parity: ``python/paddle/distributed/fleet/meta_parallel/
pipeline_parallel.py`` (PipelineParallel.train_batch, FThenB/1F1B
schedules) + ``pp_utils/p2p_communication.py`` (batched NCCL send/recv).

TPU-first design (SURVEY.md §5.8, §7.4): there is no NCCL p2p — stage
activations ride ``jax.lax.ppermute`` over the ``pp`` mesh axis inside a
``shard_map``; the fill-drain schedule is a ``lax.scan`` over ticks, so
XLA sees one static program and overlaps the permute with stage compute.
All stages execute the same homogeneous stage function with their own
weight shard (stacked params, leading dim sharded over ``pp``), which is
how GSPMD-style pipelining wants it. Backward is just ``jax.grad``
through the scan — ppermute transposes to the reverse permute, giving the
backward pipeline for free (no hand-written 1F1B bookkeeping).
"""
from __future__ import annotations

from typing import Any, Callable, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..framework.core import Tensor, apply_jax, as_jax, _wrap_out
from . import env as _env

__all__ = ["pipeline_apply", "stack_stage_params", "PipelineStageFn"]

PipelineStageFn = Callable[[Any, jnp.ndarray], jnp.ndarray]


def stack_stage_params(per_stage_params: List[Any]):
    """[stage0_tree, stage1_tree, ...] → one tree with leading pp dim."""
    return jax.tree_util.tree_map(
        lambda *xs: jnp.stack(xs, axis=0), *per_stage_params)


def _live_batch_axes(mesh, axis, batch_axes, mb_dim):
    """Mesh axes that may shard the per-microbatch batch dim: keep an
    axis only while the *product* of kept axes still divides it."""
    live = []
    prod = 1
    for a in (batch_axes or ()):
        sz = mesh.shape.get(a, 1)
        if a != axis and sz > 1 and mb_dim % (prod * sz) == 0:
            live.append(a)
            prod *= sz
    live = tuple(live)
    return live if len(live) > 1 else (live[0] if live else None)


def pipeline_apply(stage_fn: PipelineStageFn, stacked_params,
                   microbatches, mesh: Mesh = None, axis: str = "pp",
                   extra_inputs=None, batch_axes=("dp", "sharding"),
                   first_fn=None, first_params=None,
                   last_fn=None, last_params=None, last_feeds=None,
                   remat=False):
    """Run the pipelined forward.

    stage_fn(params_local, x, *extra) -> y  — one stage's compute; must
        be shape-preserving on x (homogeneous stages).
    stacked_params: pytree, leaves [pp, ...] (will be sharded over axis).
    microbatches: [n_micro, mb, ...] array; fed to stage 0 in order.
    batch_axes: mesh axes (those present with size>1) that shard the
        per-microbatch batch dim (dim 1) inside the pipe — data parallel
        composes with pp without leaving the shard_map.

    Heterogeneous first/last stages (the reference's first/last-stage
    special-casing in ``pipeline_parallel.py``):

    first_fn(first_params, feed_mb, *extra) -> h  — runs ONLY on stage 0,
        per tick, converting the raw feed microbatch (e.g. token ids)
        into the ring's boundary activation. Its work overlaps the
        pipeline instead of running replicated up front.
    last_fn(last_params, y, last_feed_mb, *extra) -> out  — runs ONLY on
        the last stage (head / loss prep). ``last_feeds`` is an optional
        [n_micro, ...] per-micro side input (e.g. labels).
    remat=True checkpoints stage_fn so the backward recomputes stage
        interiors — per-device live activations are the per-tick BOUNDARY
        tensors only (the GPipe+remat memory regime; see
        ``pipeline_1f1b`` for the O(pp) schedule).

    Returns [n_micro, ...] outputs (valid on every device — the last
    stage's results are broadcast over the pp axis).
    """
    mesh = mesh or _env.get_mesh()
    pp = mesh.shape[axis]
    n_micro = microbatches.shape[0]
    n_ticks = n_micro + pp - 1
    extra = extra_inputs if extra_inputs is not None else ()
    if remat:
        stage_fn = jax.checkpoint(stage_fn)

    in_spec_params = jax.tree_util.tree_map(
        lambda _: P(axis), stacked_params)
    batch_spec = _live_batch_axes(mesh, axis, batch_axes,
                                  microbatches.shape[1])
    mb_spec = P(None, batch_spec, *([None] * (microbatches.ndim - 2)))
    _axes = (batch_spec,) if isinstance(batch_spec, str) \
        else (batch_spec or ())
    _prod = int(np.prod([mesh.shape[a] for a in _axes])) if _axes else 1
    local_mb = microbatches.shape[1] // _prod

    # boundary activation spec (ring dtype/shape) — PER-DEVICE view:
    # the batch dim inside shard_map is the local shard
    local_feed = jax.ShapeDtypeStruct(
        (local_mb,) + microbatches.shape[2:], microbatches.dtype)
    if first_fn is not None:
        h_struct = jax.eval_shape(
            lambda p, x, *e: first_fn(p, x, *e),
            first_params, local_feed, *extra)
    else:
        h_struct = local_feed
    if last_fn is not None:
        lf_struct = None if last_feeds is None else jax.ShapeDtypeStruct(
            last_feeds.shape[1:], last_feeds.dtype)
        out_struct = jax.eval_shape(
            lambda p, y, lf, *e: last_fn(p, y, lf, *e),
            last_params, h_struct, lf_struct, *extra)
    else:
        out_struct = h_struct
    out_spec = P(None) if out_struct.ndim == 0 else P(
        None, batch_spec if out_struct.shape[0] == local_mb else None,
        *([None] * (out_struct.ndim - 1)))

    rep = lambda tree: jax.tree_util.tree_map(
        lambda x: P(*([None] * jnp.ndim(x))), tree)

    def per_device(params_block, mbs, fparams, lparams, lfeeds,
                   *extra_args):
        # params_block leaves: [1, ...] (this stage's slice)
        params_local = jax.tree_util.tree_map(
            lambda x: x[0], params_block)
        stage_idx = jax.lax.axis_index(axis)
        perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]

        y0 = jnp.zeros(h_struct.shape, h_struct.dtype)

        def tick(carry, t):
            recv = carry
            feed = jnp.where(t < n_micro, t, 0)
            if first_fn is not None:
                x_first = jax.lax.cond(
                    stage_idx == 0,
                    lambda: first_fn(fparams, mbs[feed], *extra_args),
                    lambda: jnp.zeros(h_struct.shape, h_struct.dtype))
                x_in = jnp.where(stage_idx == 0, x_first, recv)
            else:
                x_in = jnp.where(stage_idx == 0, mbs[feed], recv)
            y = stage_fn(params_local, x_in, *extra_args)
            send = jax.lax.ppermute(y, axis, perm_fwd)
            if last_fn is not None:
                oidx = jnp.clip(t - (pp - 1), 0, n_micro - 1)
                lf = None if lfeeds is None else lfeeds[oidx]
                out = jax.lax.cond(
                    stage_idx == pp - 1,
                    lambda: last_fn(lparams, y, lf, *extra_args),
                    lambda: jnp.zeros(out_struct.shape, out_struct.dtype))
            else:
                # output from the last stage this tick
                out = jnp.where(stage_idx == pp - 1, y,
                                jnp.zeros_like(y))
            return send, out

        _, outs = jax.lax.scan(tick, y0, jnp.arange(n_ticks))
        # outs: [n_ticks, ...]; last stage's valid range is
        # ticks [pp-1, pp-1+n_micro). psum over pp broadcasts them
        # (all other stages contributed zeros).
        valid = jax.lax.dynamic_slice_in_dim(outs, pp - 1, n_micro, axis=0)
        return jax.lax.psum(valid, axis)

    # per-micro labels must follow the same batch sharding as the
    # microbatches, or dp shards would pair local activations with the
    # GLOBAL label slice
    lf_spec = None if last_feeds is None else P(
        None, batch_spec if last_feeds.shape[1] == microbatches.shape[1]
        else None, *([None] * (last_feeds.ndim - 2)))

    from .shard_utils import manual_region
    mapped = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(in_spec_params, mb_spec, rep(first_params),
                  rep(last_params), lf_spec,
                  *[P(*([None] * jnp.ndim(e))) for e in extra]),
        out_specs=out_spec, check_vma=False)
    with manual_region():
        return mapped(stacked_params, microbatches, first_params,
                      last_params, last_feeds, *extra)

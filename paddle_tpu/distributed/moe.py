"""Mixture-of-Experts with expert parallelism
(``python/paddle/incubate/distributed/models/moe/moe_layer.py`` +
``gate/*.py`` parity).

TPU-first (SURVEY.md §7.4). Three dispatch formulations share one
sort-based router (``_sort_pairs``: a stable argsort groups (token,
slot) pairs expert-major; the inverse permutation is one int32
scatter):

- ``moe_dispatch_combine`` — GShard static-capacity dispatch against a
  ``[e, c, d]`` padded buffer; works with ARBITRARY per-expert layers
  (``expert_fn``) and under any GSPMD sharding. The all-to-all the
  reference codes against ProcessGroup appears as GSPMD collectives
  when the expert dim is mesh-sharded.
- ``moe_dispatch_combine_grouped`` — capacity SEMANTICS on the
  grouped-matmul engine for stacked SwiGLU experts: dropped pairs are
  zero-gated instead of excluded, so compute is the dropless total
  (s*k rows) with no capacity padding.
- ``moe_dispatch_combine_dropless`` — capacity-free routing as two
  grouped matmuls (megablox Pallas kernel on TPU, lax.ragged_dot
  elsewhere). Under an expert-sharded mesh the whole pipeline runs
  INSIDE ``shard_map`` (``_dropless_ep``): explicit all-to-alls place
  pairs on the shard owning their expert, the grouped kernels run on
  static per-shard shapes, and a hand-written custom VJP replays the
  same structure backward with separately tuned tilings.

``MOE_STATS`` records (at trace time) which path/kernel a compilation
took; static shapes throughout, as jit requires.
"""
from __future__ import annotations

import contextlib
import math
import os
import threading
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import (Tensor, apply_jax, as_jax, component,
                              _wrap_out)
from ..nn import functional as F
from ..nn.layer.layers import Layer
from .shard_utils import annotate_param, constraint, mesh_axis_size

__all__ = ["MoELayer", "NaiveGate", "GShardGate", "SwitchGate",
           "moe_dispatch_combine", "moe_dispatch_combine_dropless",
           "moe_dispatch_combine_grouped", "moe_stats",
           "reset_moe_stats", "moe_fused_enabled", "serving_stats_tap",
           "serving_rows_mask", "serving_share_counts",
           "group_limited_gate", "moe_share_dispatch_combine",
           "ClipGradForMOEByGlobalNorm"]


from ..nn.clip import ClipGradByGlobalNorm as _ClipGradByGlobalNorm


class ClipGradForMOEByGlobalNorm(_ClipGradByGlobalNorm):
    """MoE-aware global-norm clip (reference:
    ``incubate/distributed/models/moe/grad_clip.py``). The reference
    splits (param, grad) pairs into expert / non-expert sets and
    all-reduces the expert-set norm over the moe group, because with EP
    each rank holds only its local experts; expert_sq + normal_sq is
    then the true global norm. TPU-first: expert params are stacked
    GSPMD arrays that are *logically global*, so the plain global norm
    over all grads is already the same quantity — this subclass exists
    so reference scripts construct the same class name, and keeps the
    constructor surface (predicate/group args are metadata here)."""

    def __init__(self, clip_norm, is_expert_param_func=None,
                 moe_group=None, group_name="default_moe_group"):
        super().__init__(clip_norm, group_name=group_name)
        self.is_expert_param_func = is_expert_param_func
        self.moe_group = moe_group


class BaseGate(Layer):
    def __init__(self, d_model, num_expert):
        super().__init__()
        self.d_model = d_model
        self.num_expert = num_expert
        self.loss = None


class NaiveGate(BaseGate):
    def __init__(self, d_model, num_expert, world_size=1, topk=2):
        super().__init__(d_model, num_expert)
        from ..nn.layer.common import Linear
        self.gate = Linear(d_model, num_expert)
        self.top_k = topk

    def forward(self, x):
        return self.gate(x)


class GShardGate(NaiveGate):
    """GShard top-2 gate (``gate/gshard_gate.py`` parity): the 2nd-choice
    expert receives the token only with probability ``min(1, 2*g2)``
    (GShard's random routing), sampled per token during training."""

    def __init__(self, d_model, num_expert, world_size=1, topk=2,
                 capacity=(1.2, 2.4), group=None, gate_bias=True):
        super().__init__(d_model, num_expert, world_size, topk)
        self.capacity_factor = capacity[0]
        self.second_expert_policy = "random"


class SwitchGate(NaiveGate):
    """Switch top-1 gate (``gate/switch_gate.py`` parity): multiplicative
    jitter noise ``U(1-eps, 1+eps)`` on the router input during
    training; capacity-drop statistics surface via ``drop_rate``."""

    def __init__(self, d_model, num_expert, world_size=1, topk=1,
                 switch_eps=0.1, capacity=(1.2, 2.4), group=None):
        super().__init__(d_model, num_expert, world_size, topk=1)
        self.capacity_factor = capacity[0]
        self.switch_eps = float(switch_eps)

    def forward(self, x):
        if self.training and self.switch_eps > 0:
            from ..framework import random as _random
            key = _random.next_key()
            eps = self.switch_eps

            def jitter(a):
                noise = jax.random.uniform(
                    key, a.shape, jnp.float32, 1.0 - eps, 1.0 + eps)
                return a * noise.astype(a.dtype)
            x = apply_jax("switch_jitter", jitter, x)
        return self.gate(x)


# ---------------------------------------------------------------------------
# Gather-only dispatch plumbing.
#
# The (token, slot) -> (expert, capacity-slot) mapping is a partial
# permutation whose inverse we hold explicitly (one tiny int32 scatter
# builds it), so BOTH autodiff directions of pack/combine can be row
# gathers. XLA cannot know a scatter's indices are unique, so its
# scatter-add lowering serializes on TPU; these custom VJPs replace every
# float scatter in the MoE fwd+bwd with a gather (measured 10.5 -> 7.9
# ms/block fwd+bwd at the bench shapes [s=8192, d=1024, e=32, k=4]).
# ---------------------------------------------------------------------------

import functools as _functools


# Trace-time path-selection statistics. Incremented while the dispatch
# functions TRACE (not per executed step), so a test — or an operator
# reading bench output — can prove WHICH kernel a given mesh/shape
# combination compiled: the megablox grouped Pallas kernel, the
# lax.ragged_dot grouped fallback, or the dense capacity-padded einsum
# path, and whether the EP shard_map fast path was entered.
#
# Since the telemetry PR these are SERVED BY the framework-wide metrics
# registry (``paddle_tpu.monitor``): ``MOE_STATS`` is a thin mapping
# alias over a ``moe_path_calls{path=...}`` gauge plus a
# ``moe_grouped_mm_kernel`` info metric, so the counters land in the
# JSONL export/atexit table alongside every other metric while the
# historical dict-style API (``MOE_STATS[k] += 1``, ``moe_stats()``,
# ``reset_moe_stats()``) keeps working unchanged.
from .. import monitor as _monitor

_moe_path_calls = _monitor.gauge(
    "moe_path_calls",
    "MoE dispatch path selections recorded at trace time",
    labels=("path",))
_moe_kernel_info = _monitor.info(
    "moe_grouped_mm_kernel",
    "last grouped-matmul kernel a compilation selected")

from collections.abc import MutableMapping as _MutableMapping


class _MoeStats(_MutableMapping):
    """Dict-shaped view over the registry-backed MoE path counters."""

    _COUNTER_KEYS = ("grouped_mm_calls", "ep_shard_map_calls",
                     "padded_einsum_calls")
    _KEYS = ("grouped_mm_calls", "grouped_mm_kernel",
             "ep_shard_map_calls", "padded_einsum_calls")

    def __getitem__(self, k):
        if k == "grouped_mm_kernel":
            return _moe_kernel_info.get()
        if k in self._COUNTER_KEYS:
            return int(_moe_path_calls.labels(path=k).value())
        raise KeyError(k)

    def __setitem__(self, k, v):
        if k == "grouped_mm_kernel":
            _moe_kernel_info.set(v)
        elif k in self._COUNTER_KEYS:
            _moe_path_calls.labels(path=k).set(int(v))
        else:
            raise KeyError(k)

    def __delitem__(self, k):
        raise TypeError("MOE_STATS keys are fixed")

    def __iter__(self):
        return iter(self._KEYS)

    def __len__(self):
        return len(self._KEYS)

    def __repr__(self):
        return repr(dict(self))


MOE_STATS = _MoeStats()


def reset_moe_stats():
    MOE_STATS.update(grouped_mm_calls=0, grouped_mm_kernel=None,
                     ep_shard_map_calls=0, padded_einsum_calls=0)


def moe_stats():
    return dict(MOE_STATS)


def _sort_pairs(flat_e, e, valid=None):
    """Sort-based token→expert grouping (replaces the r5 chunked-cumsum
    position scan, which profiling showed dominating dispatch at bench
    shapes). A single stable argsort of the pair→expert keys groups the
    (token, slot) pairs expert-major while preserving arrival order —
    so capacity semantics (earlier tokens win slots) are unchanged —
    and its inverse permutation comes from one int32 scatter.

    Returns ``(order, rank, counts)``: ``order[r]`` = pair index at
    sorted position r, ``rank`` = inverse permutation, ``counts[j]`` =
    pairs routed to expert j. Pairs with ``valid=False`` get sentinel
    key ``e`` so they sort last and are excluded from ``counts``."""
    n = flat_e.shape[0]
    key = flat_e if valid is None else jnp.where(valid, flat_e, e)
    order = jnp.argsort(key).astype(jnp.int32)
    rank = jnp.zeros(n, jnp.int32).at[order].set(
        jnp.arange(n, dtype=jnp.int32))
    counts = jnp.zeros(e, jnp.int32).at[key].add(1, mode="drop")
    return order, rank, counts


@_functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def _moe_pack(x, src_row, filled, dest, top_k):
    """expert_in[e, c] = x[src_row[e, c]] * filled[e, c].

    src_row: [e, c] token id feeding each expert slot (any value where
    unfilled); filled: [e, c] bool; dest: [s, k] int32 flat index of each
    (token, slot) in the padded [e * (c+1)] layout (sentinel column c for
    dropped slots) — used only by the backward gather.
    """
    ei = jnp.take(x, src_row, axis=0)
    return ei * filled[..., None].astype(x.dtype)


def _moe_pack_fwd(x, src_row, filled, dest, top_k):
    out = _moe_pack(x, src_row, filled, dest, top_k)
    return out, (out.shape[:2], dest)


def _moe_pack_bwd(top_k, res, g):
    (e, c), dest = res
    # dx[s] = sum_k g[dest(s, k)]; pad a zero sentinel column per expert
    # so dropped slots read zeros instead of needing a mask
    gf = jnp.pad(g, ((0, 0), (0, 1), (0, 0))).reshape(e * (c + 1), -1)
    rows = jnp.take(gf, dest.reshape(-1), axis=0)
    dx = rows.reshape(-1, top_k, gf.shape[-1]).sum(axis=1)
    return (dx.astype(g.dtype), None, None, None)


_moe_pack.defvjp(_moe_pack_fwd, _moe_pack_bwd)


@jax.custom_vjp
def _moe_combine(expert_out, gates, dest, src_row, filled, gates_ec):
    """y[s] = sum_k gates[s, k] * expert_out[dest(s, k)].

    gates_ec: [e, c] the gate weight of the (token, slot) feeding each
    expert slot (zero where unfilled) — the backward gather's coefficient.
    """
    e, c, d = expert_out.shape
    eof = jnp.pad(expert_out, ((0, 0), (0, 1), (0, 0))) \
        .reshape(e * (c + 1), d)
    k = dest.shape[1]
    picked = jnp.take(eof, dest.reshape(-1), axis=0).reshape(-1, k, d)
    return jnp.einsum("sk,skd->sd", gates.astype(expert_out.dtype),
                      picked)


def _moe_combine_fwd(expert_out, gates, dest, src_row, filled, gates_ec):
    y = _moe_combine(expert_out, gates, dest, src_row, filled, gates_ec)
    return y, (expert_out, gates, dest, src_row, filled, gates_ec)


def _moe_combine_bwd(res, dy):
    expert_out, gates, dest, src_row, filled, gates_ec = res
    e, c, d = expert_out.shape
    k = dest.shape[1]
    # d_expert_out[e, c] = dy[src_row] * gate-of-that-slot  (gather)
    deo = jnp.take(dy, src_row, axis=0)
    coef = (gates_ec * filled.astype(gates_ec.dtype))
    deo = deo * coef[..., None].astype(dy.dtype)
    # d_gates[s, k] = <dy[s], expert_out[dest(s, k)]>
    eof = jnp.pad(expert_out, ((0, 0), (0, 1), (0, 0))) \
        .reshape(e * (c + 1), d)
    picked = jnp.take(eof, dest.reshape(-1), axis=0).reshape(-1, k, d)
    dgates = jnp.einsum("sd,skd->sk", dy.astype(jnp.float32),
                        picked.astype(jnp.float32))
    return (deo.astype(expert_out.dtype), dgates.astype(gates.dtype),
            None, None, None, None)


_moe_combine.defvjp(_moe_combine_fwd, _moe_combine_bwd)


@jax.custom_vjp
def _perm_rows(x, idx, inv_idx):
    """y[i] = x[idx[i]] where idx is a permutation with inverse inv_idx
    (backward is the inverse gather, never a scatter)."""
    return jnp.take(x, idx, axis=0)


def _perm_rows_fwd(x, idx, inv_idx):
    return jnp.take(x, idx, axis=0), (idx, inv_idx)


def _perm_rows_bwd(res, g):
    idx, inv_idx = res
    return (jnp.take(g, inv_idx, axis=0), None, None)


_perm_rows.defvjp(_perm_rows_fwd, _perm_rows_bwd)


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _expand_sort(x, src_tok, rank, top_k):
    """xs[r] = x[src_tok[r]]: expand each token to its top_k slots in
    expert-sorted order. rank: [s * k] position of (token, slot) in the
    sorted order (token-major) — the inverse mapping for the backward
    gather: dx[s] = sum_k g[rank[s * k + k]]."""
    return jnp.take(x, src_tok, axis=0)


def _expand_sort_fwd(x, src_tok, rank, top_k):
    return jnp.take(x, src_tok, axis=0), (rank,)


def _expand_sort_bwd(top_k, res, g):
    (rank,) = res
    rows = jnp.take(g, rank, axis=0)               # token-major [s*k, d]
    dx = rows.reshape(-1, top_k, g.shape[-1]).sum(axis=1)
    return (dx.astype(g.dtype), None, None)


_expand_sort.defvjp(_expand_sort_fwd, _expand_sort_bwd)


def moe_dispatch_combine(x, gate_logits, num_expert, top_k=2,
                         capacity_factor=1.25, expert_fn=None,
                         expert_axis=None, normalize_gates=True,
                         second_expert_policy="all", rng_key=None,
                         return_stats=False):
    """Pure-array GShard dispatch → expert_fn → combine.

    x: [tokens, d]; gate_logits: [tokens, e]. expert_fn(inputs[e, c, d])
    -> [e, c, d]. Returns (y [tokens, d], aux_loss scalar), plus a stats
    dict (capacity ``drop_rate``) when ``return_stats``.
    ``normalize_gates=False`` combines with the raw softmax probs of the
    selected experts (Qwen2-MoE/DeepSeek ``norm_topk_prob=False``).
    ``second_expert_policy="random"`` + ``rng_key`` enables GShard's
    random routing: slot j>=1 dispatches with probability
    ``min(1, k * g_j)``.

    Pack and combine are gather-only in both autodiff directions (see
    the custom-VJP helpers above); the single scatter left is the int32
    slot-occupancy map, which is negligible next to the float traffic.
    """
    s, d = x.shape
    e = num_expert
    c = max(int(math.ceil(capacity_factor * s * top_k / e)), 1)

    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    # top-k selection
    topk_prob, topk_idx = jax.lax.top_k(probs, top_k)  # [s, k]

    sel = None
    if second_expert_policy == "random" and rng_key is not None \
            and top_k >= 2:
        u = jax.random.uniform(rng_key, (s, top_k))
        sel = u < jnp.minimum(top_k * topk_prob, 1.0)
        sel = sel.at[:, 0].set(True)  # 1st choice always dispatches

    # position of each (token, k) within its expert's queue via the
    # sort-based grouping (random-skipped slots don't consume capacity)
    flat_e_all = topk_idx.reshape(-1).astype(jnp.int32)
    _order, rank, counts = _sort_pairs(
        flat_e_all, e, valid=None if sel is None else sel.reshape(-1))
    starts = jnp.cumsum(counts) - counts
    pos = (rank - starts[flat_e_all]).reshape(s, top_k)
    slot_used = jnp.ones((s, top_k), bool) if sel is None else sel
    keep = (pos < c) & slot_used

    # load-balancing aux loss (GShard eq.: e * sum(me * ce))
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(topk_idx[:, 0], e,
                                 dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)
    MOE_STATS["padded_einsum_calls"] += 1

    # random-skipped slots are zeroed BEFORE normalization (GShard/
    # fairseq top2gating order): a token whose 2nd expert was skipped
    # combines with weight ~1.0, not g1/(g1+g2)
    eff_prob = topk_prob if sel is None \
        else topk_prob * sel.astype(topk_prob.dtype)
    if normalize_gates:
        gates = eff_prob / jnp.maximum(
            jnp.sum(eff_prob, axis=-1, keepdims=True), 1e-9)
    else:
        gates = eff_prob
    gates = jnp.where(keep, gates, 0.0).astype(x.dtype)

    # slot-occupancy map: one int32 scatter builds the inverse of the
    # (token, slot) -> (expert, pos) mapping; dropped slots land in a
    # per-expert sentinel column that pack/combine read as zeros
    flat_e = topk_idx.reshape(-1)                       # [s*k]
    flat_p = jnp.where(keep, pos, c).reshape(-1)        # [s*k]
    dest = (flat_e * (c + 1) + flat_p).astype(jnp.int32)
    inv = jnp.zeros(e * (c + 1), jnp.int32)
    inv = inv.at[dest].set(jnp.arange(s * top_k, dtype=jnp.int32) + 1)
    inv = inv.reshape(e, c + 1)[:, :c]                  # [e, c]
    src_slot = jnp.maximum(inv - 1, 0)
    src_row = src_slot // top_k                         # token per slot
    filled = inv > 0
    gates_ec = jnp.take(gates.reshape(-1), src_slot.reshape(-1)) \
        .reshape(e, c)
    dest = dest.reshape(s, top_k)

    from ..profiler import RecordEvent
    with RecordEvent("moe:dispatch"):
        expert_in = _moe_pack(x, src_row, filled, dest, top_k)
        if expert_axis is not None:
            expert_in = _ep_constraint(expert_in, expert_axis)
    with RecordEvent("moe:expert_mm"):
        expert_out = expert_fn(expert_in)      # [e, c, d_out]
        if expert_axis is not None:
            expert_out = _ep_constraint(expert_out, expert_axis)
    with RecordEvent("moe:combine"):
        y = _moe_combine(expert_out, gates, dest, src_row, filled,
                         gates_ec)
    if return_stats:
        # fraction of requested (token, slot) dispatches that were
        # dropped — capacity overflow plus random-routing skips
        stats = {"drop_rate": 1.0 - jnp.sum(keep.astype(jnp.float32))
                 / float(s * top_k)}
        return y, aux, stats
    return y, aux


# megablox grouped-matmul tilings tuned on the bench shapes (v5e: the
# (m, k, n) tile must keep the last two block dims 8/128-aligned).
# Backward kernels (transposed gmm + tgmm) prefer the smaller k tile:
# tgmm at [32768, 1024->1408] measured 3.30 ms with (512,1024,512) vs
# 2.32 with (512,512,512)
_GMM_TILING = (512, 1024, 512)
_GMM_TILING_BWD = (512, 512, 512)
# row tile of a chip's expert-parallel share (megablox wants the pair
# buffer a whole number of row tiles)
_SHARE_TM = 128


@_functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm32(lhs, rhs, group_sizes, tiling):
    """megablox gmm with every Pallas trace under ``kernel_scope``.

    The stock ``megablox.ops.gmm`` custom VJP traces its backward
    kernels when jax.grad runs — outside any caller context manager —
    and under jax_enable_x64 (the framework default) a weak-f64 constant
    makes Mosaic's convert lowering recurse forever. This wrapper owns
    the VJP so fwd AND bwd kernels trace in 32-bit mode.
    """
    import importlib
    # the megablox package re-exports a FUNCTION named gmm that shadows
    # the module of the same name; importlib reaches the module
    _mb = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    from ..ops.pallas.flash_attention_kernel import kernel_scope
    with kernel_scope("megablox_gmm"):
        return _mb.gmm(lhs, rhs, group_sizes,
                       preferred_element_type=lhs.dtype, tiling=tiling)


def _gmm32_fwd(lhs, rhs, group_sizes, tiling):
    return _gmm32(lhs, rhs, group_sizes, tiling), (lhs, rhs, group_sizes)


def _mb_bwd_dlhs(g, rhs, group_sizes):
    """Raw megablox d(lhs): transpose-rhs gmm under the bwd tiling."""
    import importlib
    _mb = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    from ..ops.pallas.flash_attention_kernel import kernel_scope
    with kernel_scope("megablox_gmm"):
        return _mb.gmm(g, rhs, group_sizes,
                       preferred_element_type=g.dtype,
                       tiling=_GMM_TILING_BWD, transpose_rhs=True)


def _mb_bwd_drhs(lhs, g, group_sizes, num_groups):
    """Raw megablox d(rhs): tgmm under the bwd tiling."""
    import importlib
    _mb = importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")
    from ..ops.pallas.flash_attention_kernel import kernel_scope
    with kernel_scope("megablox_tgmm"):
        return _mb.tgmm(lhs.swapaxes(0, 1), g, group_sizes,
                        preferred_element_type=g.dtype,
                        tiling=_GMM_TILING_BWD,
                        num_actual_groups=num_groups)


def _gmm32_bwd(tiling, res, g):
    # no fallback here by design: a shape that traced the forward
    # kernel traces the backward (same block alignment, dims swapped)
    lhs, rhs, gs = res
    dlhs = _mb_bwd_dlhs(g, rhs, gs)
    drhs = _mb_bwd_drhs(lhs, g, gs, rhs.shape[0])
    return dlhs.astype(lhs.dtype), drhs.astype(rhs.dtype), None


_gmm32.defvjp(_gmm32_fwd, _gmm32_bwd)


def _use_megablox(n_rows, d_in, d_out):
    """The Pallas grouped-matmul kernel beats lax.ragged_dot on real TPU
    at MXU-scale shapes (measured 2.25 -> 1.70 ms at [32768, 1024, 1408])
    but needs a tpu backend and 8-aligned dims (its TILE dims carry the
    (8, 128) rule; array dims only need sublane alignment — d=704 works
    under the fixed (512, 1024, 512) tiling). Since r6 this predicate
    also gates the PER-SHARD shapes inside the EP shard_map fast path —
    per-shard buffer shapes are static there, so the kernel is legal
    under expert sharding. ``n_rows`` is the STATIC row count of the
    buffer, not the rows that are live: a chip's expert-parallel share
    (``moe_share_dispatch_combine``) passes its whole ``rows x k`` pair
    buffer (4,352 at a 544-row serving tick, of which a sixteenth is
    live), so a full-size tick takes the kernel and a small engine
    ragged_dot; which one a serving tick traced is in ``ServingEngine.
    stats()["moe_grouped_mm_kernel"]`` (``"megablox"``: a device
    trace and the kernel census name those kernels ``gmm``, the library
    function's own name inside ``kernel_scope("megablox_gmm")``). CPU
    test meshes and tiny shapes take the
    ragged_dot path; a shape this gate admits that the kernel then
    refuses to trace or compile is an error, not a fallback."""
    return (jax.default_backend() == "tpu"
            and n_rows >= 1024 and d_in % 8 == 0 and d_out % 8 == 0)


def _grouped_mm(lhs, rhs, group_sizes, tiling=None,
                allow_pallas=True):
    """Single entry point for the grouped expert matmul: the megablox
    Pallas kernel on real TPU at MXU-scale aligned shapes (fwd AND bwd
    run grouped kernels via the ``_gmm32`` custom VJP, with the
    separately tuned backward tiling), ``jax.lax.ragged_dot`` elsewhere.
    Increments ``MOE_STATS`` at trace time so tests can assert which
    kernel a given mesh/shape combination actually compiled.

    ``allow_pallas=False`` forces ragged_dot: the Pallas kernel is only
    legal on REPLICATED/manual (shard_map) operands — under GSPMD
    sharding an opaque pallas_call can't be partitioned, so the sharded
    non-shard_map fallback path must keep the r5 ragged_dot gate."""
    MOE_STATS["grouped_mm_calls"] += 1
    if allow_pallas and _use_megablox(lhs.shape[0], lhs.shape[1],
                                      rhs.shape[-1]):
        MOE_STATS["grouped_mm_kernel"] = "megablox"
        return _gmm32(lhs, rhs, group_sizes, tiling or _GMM_TILING)
    MOE_STATS["grouped_mm_kernel"] = "ragged_dot"
    return jax.lax.ragged_dot(lhs, rhs, group_sizes)


def _grouped_mm_dlhs(g, rhs, group_sizes):
    """d(lhs) of the grouped matmul for the hand-written EP backward:
    transpose-rhs grouped matmul with the backward tiling."""
    MOE_STATS["grouped_mm_calls"] += 1
    if _use_megablox(g.shape[0], g.shape[1], rhs.shape[1]):
        MOE_STATS["grouped_mm_kernel"] = "megablox"
        return _mb_bwd_dlhs(g, rhs, group_sizes)
    MOE_STATS["grouped_mm_kernel"] = "ragged_dot"
    return jax.lax.ragged_dot(g, rhs.swapaxes(1, 2), group_sizes)


def _grouped_mm_drhs(lhs, g, group_sizes, num_groups):
    """d(rhs) of the grouped matmul for the hand-written EP backward:
    megablox tgmm with the backward tiling on TPU, the linear transpose
    of ragged_dot elsewhere."""
    MOE_STATS["grouped_mm_calls"] += 1
    if _use_megablox(lhs.shape[0], lhs.shape[1], g.shape[-1]):
        MOE_STATS["grouped_mm_kernel"] = "megablox"
        return _mb_bwd_drhs(lhs, g, group_sizes, num_groups)
    MOE_STATS["grouped_mm_kernel"] = "ragged_dot"
    shape = jax.ShapeDtypeStruct(
        (num_groups, lhs.shape[1], g.shape[-1]), g.dtype)
    transposed = jax.linear_transpose(
        lambda r: jax.lax.ragged_dot(lhs, r, group_sizes), shape)
    return transposed(g)[0]


def _expert_swiglu_grouped(xs, gate_up, down, group_sizes, dtype,
                           allow_pallas=True, tiling=None):
    """Expert SwiGLU MLP over expert-sorted rows as TWO grouped
    matmuls (``[n, d] x [e, d, 2f] -> [n, 2f]``, swiglu,
    ``[n, f] x [e, f, d] -> [n, d]``)."""
    gu = _grouped_mm(xs, gate_up.astype(dtype), group_sizes,
                     tiling=tiling, allow_pallas=allow_pallas)
    g, u = jnp.split(gu, 2, axis=-1)
    h = jax.nn.silu(g.astype(jnp.float32)).astype(dtype) * u
    return _grouped_mm(h, down.astype(dtype), group_sizes,
                       tiling=tiling, allow_pallas=allow_pallas)


# ---------------------------------------------------------------------------
# Fused-dispatch grouped MoE (ops/pallas/moe_gmm.py): the sort/dispatch
# permutation folds into the grouped matmuls themselves — gather-on-read
# lhs (the sorted packed buffer never reaches HBM), swiglu in the first
# matmul's epilogue (the [m, 2f] projection never reaches HBM), and the
# combine's unsort as the second matmul's scatter store. The custom VJP
# below replays the same gather/scatter structure backward.
# ---------------------------------------------------------------------------


def moe_fused_enabled() -> bool:
    """Kill switch: ``PADDLE_TPU_MOE_FUSED_GMM=0`` restores the
    sort→pack→gmm path everywhere, bit-for-bit (the fused kernels are
    never traced)."""
    return os.environ.get("PADDLE_TPU_MOE_FUSED_GMM", "1") != "0"


def _use_fused_gmm(n_rows, d_model, d_ffn, fused=None):
    """Eligibility of the fused-dispatch kernels for this shape.
    Returns ``False`` (sorted path) or ``"interpret"`` (Pallas
    interpreter — CPU tests set ``PADDLE_TPU_MOE_FUSED_GMM=interpret``
    to exercise the fused graph end-to-end). ``fused``: the
    per-call/config override (``None`` = env default).

    NEVER the compiled kernels: Mosaic (jax 0.9.0 / libtpu 0.0.34, one
    v5e, ``chip_smoke.py`` PR 21) refuses both the gather-on-read and
    the scatter-on-write — their per-row async copies slice ONE row
    out of a tiled array ("Slice shape along dimension 0 must be
    aligned to tiling (8), but is 1", for the HBM activations and the
    VMEM store tile alike). On a TPU backend every shape therefore
    takes the sorted megablox path; what a row gather Mosaic accepts
    looks like is ROADMAP S1."""
    env = os.environ.get("PADDLE_TPU_MOE_FUSED_GMM", "1")
    if env != "interpret" or fused is False:
        return False
    aligned = (d_model % 128 == 0 and d_ffn % 128 == 0
               and n_rows % 128 == 0)
    return "interpret" if aligned else False


@_functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _fused_moe_core(top_k, interpret, x, gate_up, down, gates, order,
                    src_rows, gs):
    """Fused dispatch→experts→combine over the sorted row partition:
    ``y[s] = sum_k gates[s, k] * (swiglu expert of x[s])`` with the
    sort (``src_rows = order // top_k``) fused into the first matmul's
    load and the unsort (``order``) into the second's store. ``gs``
    must sum to ``s * top_k`` (tail-padded last group, exactly as the
    sorted path)."""
    from ..ops.pallas.moe_gmm import gather_gmm_swiglu, scatter_gmm
    h = gather_gmm_swiglu(x, src_rows, gate_up.astype(x.dtype), gs,
                          interpret=interpret)
    ys_tok = scatter_gmm(h, down.astype(x.dtype), gs, order,
                         interpret=interpret)
    s = x.shape[0]
    picked = ys_tok.reshape(s, top_k, -1)
    return jnp.einsum("sk,skd->sd", gates.astype(x.dtype), picked)


def _fused_moe_fwd(top_k, interpret, x, gate_up, down, gates, order,
                   src_rows, gs):
    from ..ops.pallas.moe_gmm import gather_gmm_swiglu, scatter_gmm
    h = gather_gmm_swiglu(x, src_rows, gate_up.astype(x.dtype), gs,
                          interpret=interpret)
    ys_tok = scatter_gmm(h, down.astype(x.dtype), gs, order,
                         interpret=interpret)
    s = x.shape[0]
    picked = ys_tok.reshape(s, top_k, -1)
    y = jnp.einsum("sk,skd->sd", gates.astype(x.dtype), picked)
    return y, (x, gate_up, down, gates, order, src_rows, gs, h,
               ys_tok)


def _fused_moe_bwd(top_k, interpret, res, dy):
    """Backward replays the SAME fused structure: the token-major
    cotangent is gathered into sorted order by the first backward
    matmul's load (``order`` drives it exactly like ``src_rows`` drove
    the forward), and d(x) leaves the last backward matmul through the
    scatter epilogue. The gate/up projection — never materialized
    forward — is recomputed here with one extra gather-gmm (recompute
    beats carrying an ``[m, 2f]`` residual through the step, same
    trade as remat); weight grads run the tuned tgmm path on
    materialized sorted operands (backward-only traffic)."""
    from ..ops.pallas.moe_gmm import gather_gmm, scatter_gmm
    x, gate_up, down, gates, order, src_rows, gs, h, ys_tok = res
    s, d = x.shape
    e = gate_up.shape[0]
    picked = ys_tok.reshape(s, top_k, -1)
    dgates = jnp.einsum("sd,skd->sk", dy.astype(jnp.float32),
                        picked.astype(jnp.float32))
    dpair_tok = (gates.astype(dy.dtype)[..., None] * dy[:, None, :]) \
        .reshape(s * top_k, d)
    dh = gather_gmm(dpair_tok, order, down.astype(dy.dtype), gs,
                    transpose_rhs=True, interpret=interpret)
    dpair_sorted = jnp.take(dpair_tok, order, axis=0)
    ddown = _grouped_mm_drhs(h, dpair_sorted, gs, e)
    gu = gather_gmm(x, src_rows, gate_up.astype(x.dtype), gs,
                    interpret=interpret)
    g_a, u_a = jnp.split(gu, 2, axis=-1)
    g32 = g_a.astype(jnp.float32)
    sig = jax.nn.sigmoid(g32)
    dh32 = dh.astype(jnp.float32)
    dg = dh32 * u_a.astype(jnp.float32) * sig * (1 + g32 * (1 - sig))
    du = dh32 * (g32 * sig)
    dgu = jnp.concatenate([dg, du], axis=-1).astype(x.dtype)
    xs = jnp.take(x, src_rows, axis=0)
    dguw = _grouped_mm_drhs(xs, dgu, gs, e)
    dx_tok = scatter_gmm(dgu, gate_up.astype(x.dtype), gs, order,
                         transpose_rhs=True, interpret=interpret)
    dx = dx_tok.reshape(s, top_k, d).sum(axis=1)
    return (dx.astype(x.dtype), dguw.astype(gate_up.dtype),
            ddown.astype(down.dtype), dgates.astype(gates.dtype),
            None, None, None)


_fused_moe_core.defvjp(_fused_moe_fwd, _fused_moe_bwd)


# -- serving-time routing telemetry tap -------------------------------------
# The serving engine arms a per-thread sink while TRACING its
# executables; an armed dispatch adds one tiny jax.debug.callback
# (per-expert load fractions + routing entropy) that fires on every
# EXECUTION of the compiled step — decode-time router telemetry with no
# change to the model-step calling convention.
_SERVING_TAP = threading.local()


@contextlib.contextmanager
def serving_stats_tap(sink):
    """Arm ``sink(load [e] np.ndarray, entropy float)`` for every MoE
    dispatch traced on this thread inside the context."""
    prev = getattr(_SERVING_TAP, "sink", None)
    _SERVING_TAP.sink = sink
    try:
        yield
    finally:
        _SERVING_TAP.sink = prev


@contextlib.contextmanager
def serving_rows_mask(mask):
    """Arm a per-ROW validity mask (``[s]`` bool, traced) for MoE
    dispatches traced inside the context. Serving executables run
    fixed-shape row buffers whose PAD rows still route through the
    dispatch — without the mask their (identical, meaningless) expert
    picks would dominate the routing telemetry of a lightly loaded
    tick, reading as hot-expert skew that isn't there. The engine's
    ``_compile_*`` wrappers arm the step's live-row mask around the
    model trace; the tap then counts only real rows."""
    prev = getattr(_SERVING_TAP, "rows_mask", None)
    _SERVING_TAP.rows_mask = mask
    try:
        yield
    finally:
        _SERVING_TAP.rows_mask = prev


@contextlib.contextmanager
def serving_share_counts(sink):
    """Arm ``sink`` (a list) for every expert-parallel share
    (``moe_share_dispatch_combine``) traced on this thread inside the
    context: each appends one traced int32 ``[count + 1]`` — the live
    pairs that fell on each expert held here, then the live rows it
    routed. The serving engine stacks them into an output of its tick
    executable, so the counts of a tick reach ``stats()`` and the
    ``tick`` span with the tick's own fetch and no callback."""
    prev = getattr(_SERVING_TAP, "share_counts", None)
    _SERVING_TAP.share_counts = sink
    try:
        yield
    finally:
        _SERVING_TAP.share_counts = prev


def _tap_routing(flat_e, e, top_k, counts):
    """If a serving sink is armed (trace time), emit this dispatch's
    per-expert load fractions and routing entropy (nats) at run time —
    over LIVE rows only when a row mask is armed (pad rows of the
    fixed-shape serving buffers are excluded; see
    ``serving_rows_mask``)."""
    sink = getattr(_SERVING_TAP, "sink", None)
    if sink is None:
        return
    mask = getattr(_SERVING_TAP, "rows_mask", None)
    if mask is not None \
            and mask.shape[0] * top_k == flat_e.shape[0]:
        valid = jnp.repeat(mask.astype(jnp.int32), top_k)
        counts = jnp.zeros(e, jnp.int32).at[flat_e].add(valid,
                                                        mode="drop")
    total = jnp.maximum(jnp.sum(counts), 1).astype(jnp.float32)
    load = counts.astype(jnp.float32) / total
    ent = -jnp.sum(jnp.where(load > 0,
                             load * jnp.log(jnp.maximum(load, 1e-12)),
                             0.0))
    jax.debug.callback(sink, load, ent)


def group_limited_gate(logits, bias, *, n_group, topk_group, top_k,
                       norm_topk_prob=True, routed_scaling_factor=1.0,
                       eps=1e-20):
    """The sigmoid / bias / group-limited router of the DeepSeek-V3
    lineage (``noaux_tc``), in float32 as published. ``logits``
    ``[s, e]`` are the gate's outputs; the scores are their sigmoid;
    the CHOICE is made on ``scores + bias``
    (``e_score_correction_bias``): the ``e`` experts lie in ``n_group``
    consecutive groups, a group's score is the sum of its two largest
    choice scores, the best ``topk_group`` groups stay (the others'
    choice scores become 0) and the ``top_k`` largest choice scores
    inside them are the experts. The WEIGHTS are the chosen experts'
    scores without the bias, divided by their sum plus ``eps``
    (``norm_topk_prob``; the DeepSeek-V3 lineage publishes 1e-20, LFM2
    1e-6), times ``routed_scaling_factor``. With ``n_group =
    topk_group = 1`` this is the plain sigmoid top-k router with a
    choice bias. Ties go to the lower index.
    Returns ``(topk_idx [s, k] int32, topk_weight [s, k] f32)``."""
    with component("moe.gate"):
        scores = jax.nn.sigmoid(logits.astype(jnp.float32))
        choice = scores + bias.astype(jnp.float32)
        s, e = scores.shape
        per = e // n_group
        group_score = jnp.sum(
            jax.lax.top_k(choice.reshape(s, n_group, per), 2)[0], axis=-1)
        _, gidx = jax.lax.top_k(group_score, topk_group)
        kept = jnp.zeros((s, n_group), bool).at[
            jnp.arange(s)[:, None], gidx].set(True)
        choice = jnp.where(jnp.repeat(kept, per, axis=1), choice, 0.0)
        _, idx = jax.lax.top_k(choice, top_k)
        w = jnp.take_along_axis(scores, idx, axis=1)
        if norm_topk_prob:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
        return idx.astype(jnp.int32), w * jnp.float32(routed_scaling_factor)


def moe_share_dispatch_combine(x, topk_idx, topk_weight, gate_up, down,
                               *, first, num_expert):
    """One chip's share of an expert-parallel layer, with no exchange:
    the layer was routed over all ``num_expert`` experts
    (``topk_idx`` / ``topk_weight`` ``[s, k]``, e.g. from
    ``group_limited_gate``) and holds experts ``first .. first +
    count`` (``gate_up`` ``[count, d, 2f]``, ``down`` ``[count, f,
    d]``). The (row, expert) pairs that fell on an expert held here
    are sorted expert-major into a static buffer of ``s * k`` pairs
    and run through the two grouped matmuls over the ``count`` groups;
    the buffer's tail — pairs of absent experts, and under
    ``serving_rows_mask`` the pad rows of a serving tick — belongs to
    no group, so it is not computed, and it is gated to zero. What the
    absent experts would have added is left out: ``y [s, d]`` is this
    chip's part of the layer's routed output, and the parts of all
    ranks add up to the whole (``tests/test_deepseek_v3.py``). Armed
    by ``serving_share_counts`` it also reports its live pairs an
    expert and its live rows."""
    s, d = x.shape
    k = topk_idx.shape[1]
    count = gate_up.shape[0]
    mask = getattr(_SERVING_TAP, "rows_mask", None)
    # the grouped kernel walks whole row tiles: the pair buffer is
    # s * k rounded up to one (the extra pairs are absent ones)
    m = -(-s * k // _SHARE_TM) * _SHARE_TM
    pad = m - s * k
    with component("moe.gate"):
        local = topk_idx.astype(jnp.int32) - jnp.int32(first)
        held = (local >= 0) & (local < count)
        live = mask if mask is not None and mask.shape[0] == s \
            else jnp.ones((s,), bool)
        held = held & live[:, None]
        flat_e = jnp.pad(local.reshape(-1), (0, pad))
        flat_ok = jnp.pad(held.reshape(-1), (0, pad))
        order, rank, counts = _sort_pairs(flat_e, count, valid=flat_ok)
    sink = getattr(_SERVING_TAP, "share_counts", None)
    if sink is not None:
        with component("tick.io"):
            sink.append(jnp.concatenate(
                [counts, jnp.sum(live, dtype=jnp.int32)[None]]))
    from ..ops.pallas.paged_attention import serving_tp_active
    from ..profiler import RecordEvent
    with RecordEvent("moe:dispatch"), component("moe.dispatch"):
        xs = x[jnp.minimum(order // k, s - 1)]              # [m, d]
    # a group here is an expert's few rows of one tick (rows * k /
    # num_expert on average): row tiles of 128 keep its matmuls under
    # the time its weights take to arrive
    tm = 512 if s * k // num_expert >= 512 else _SHARE_TM
    with RecordEvent("moe:expert_mm"), component("moe.experts"):
        ys = _expert_swiglu_grouped(
            xs, gate_up, down, counts, x.dtype,
            allow_pallas=not serving_tp_active(),
            tiling=(tm, 1024, 1024))
    with RecordEvent("moe:combine"), component("moe.combine"):
        picked = ys[rank[:s * k]].reshape(s, k, d)
        # rows of the buffer's tail were never computed: select, do not
        # multiply by a zero weight
        y = jnp.sum(jnp.where(
            held[..., None],
            picked.astype(jnp.float32) * topk_weight[..., None], 0.0),
            axis=1)
        return y.astype(x.dtype)


def moe_dispatch_combine_dropless(x, gate_logits, num_expert, top_k,
                                  gate_up, down, normalize_gates=True,
                                  expert_axis=None, return_stats=False,
                                  ep_buffer_factor=2.0, fused=None):
    """DROPLESS dispatch → SwiGLU experts → combine (reference:
    capacity-free routing the fused-MoE kernels in
    ``phi/kernels/fusion/`` approximate; design follows the MegaBlocks
    grouped-matmul formulation).

    No capacity factor and no dropped tokens: (token, slot) pairs are
    grouped by expert with ONE stable argsort (``_sort_pairs``) and the
    expert MLP runs as TWO grouped matmuls — the megablox Pallas kernel
    on real TPU (tiles each ragged expert segment onto the MXU),
    ``jax.lax.ragged_dot`` elsewhere. Sort and unsort are gathers in
    both autodiff directions (``_expand_sort`` / ``_perm_rows`` custom
    VJPs). Under an expert-sharded mesh the whole pipeline moves INSIDE
    ``shard_map`` (``_dropless_ep``): explicit all-to-alls place each
    pair on the shard owning its expert, the grouped kernels run on
    static per-shard shapes, and a hand-written custom VJP replays the
    same structure backward with the separately tuned backward tilings.
    ``ep_buffer_factor`` bounds the per-(src, dst) exchange slots;
    >= the EP degree is exactly dropless (overflow is reported in the
    ``drop_rate`` stat).

    x: [s, d]; gate_logits: [s, e]; gate_up: [e, d, 2f]; down: [e, f, d].
    Returns (y [s, d], aux) (+ stats dict with drop_rate).
    """
    return _grouped_dispatch(
        x, gate_logits, num_expert, top_k, gate_up, down,
        capacity_factor=None, normalize_gates=normalize_gates,
        expert_axis=expert_axis, ep_buffer_factor=ep_buffer_factor,
        return_stats=return_stats, fused=fused)


def moe_dispatch_combine_grouped(x, gate_logits, num_expert, top_k,
                                 gate_up, down, capacity_factor=1.25,
                                 normalize_gates=True,
                                 second_expert_policy="all",
                                 rng_key=None, expert_axis=None,
                                 return_stats=False, fused=None):
    """GShard CAPACITY semantics on the grouped-matmul engine: same
    routing, same capacity rule (earlier tokens win their expert's
    slots), same gate zeroing for dropped pairs as the padded
    ``moe_dispatch_combine`` — but the expert MLP runs as two grouped
    matmuls over expert-sorted rows instead of the ``[e, c, d]``
    capacity-padded batched einsum. Dropped pairs are zero-gated at
    combine rather than excluded from the matmul, so the compute is
    exactly the dropless total (s*k rows) and the ~(cf-1) capacity
    padding waste is gone.

    Under an expert-sharded mesh this falls back to the padded GSPMD
    formulation (the capacity rule needs global arrival positions; the
    shard_map fast path is dropless-only)."""
    sharded = expert_axis is not None and mesh_axis_size(expert_axis) > 1
    if sharded:
        def efn(expert_in):
            gu = jnp.einsum("ecd,edm->ecm", expert_in,
                            gate_up.astype(expert_in.dtype))
            g, u = jnp.split(gu, 2, axis=-1)
            h = jax.nn.silu(g.astype(jnp.float32)) \
                .astype(expert_in.dtype) * u
            return jnp.einsum("ecm,emd->ecd", h,
                              down.astype(expert_in.dtype))
        return moe_dispatch_combine(
            x, gate_logits, num_expert, top_k=top_k,
            capacity_factor=capacity_factor, expert_fn=efn,
            expert_axis=expert_axis, normalize_gates=normalize_gates,
            second_expert_policy=second_expert_policy, rng_key=rng_key,
            return_stats=return_stats)
    return _grouped_dispatch(
        x, gate_logits, num_expert, top_k, gate_up, down,
        capacity_factor=capacity_factor, normalize_gates=normalize_gates,
        second_expert_policy=second_expert_policy, rng_key=rng_key,
        expert_axis=expert_axis, return_stats=return_stats, fused=fused)


def _grouped_dispatch(x, gate_logits, num_expert, top_k, gate_up, down,
                      *, capacity_factor, normalize_gates=True,
                      second_expert_policy="all", rng_key=None,
                      expert_axis=None, ep_buffer_factor=2.0,
                      return_stats=False, fused=None):
    """Shared engine behind the dropless and capacity-grouped paths:
    route → sort-group → grouped expert matmuls → combine, with the EP
    shard_map fast path when the expert axis is mesh-sharded."""
    s, d = x.shape
    e = num_expert
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    topk_prob, topk_idx = jax.lax.top_k(probs, top_k)       # [s, k]
    topk_idx = topk_idx.astype(jnp.int32)

    sel = None
    if second_expert_policy == "random" and rng_key is not None \
            and top_k >= 2:
        u = jax.random.uniform(rng_key, (s, top_k))
        sel = u < jnp.minimum(top_k * topk_prob, 1.0)
        sel = sel.at[:, 0].set(True)  # 1st choice always dispatches

    flat_e = topk_idx.reshape(-1)                           # [s*k]
    valid = None if sel is None else sel.reshape(-1)
    order, rank, counts = _sort_pairs(flat_e, e, valid=valid)

    if capacity_factor is None:
        keep = sel                                          # dropless
    else:
        c = max(int(math.ceil(capacity_factor * s * top_k / e)), 1)
        starts = jnp.cumsum(counts) - counts
        pos = (rank - starts[flat_e]).reshape(s, top_k)
        slot_used = jnp.ones((s, top_k), bool) if sel is None else sel
        keep = (pos < c) & slot_used

    # random-skipped slots are zeroed BEFORE normalization (GShard/
    # fairseq top2gating order), capacity-dropped slots after
    eff_prob = topk_prob if sel is None \
        else topk_prob * sel.astype(topk_prob.dtype)
    if normalize_gates:
        gates = eff_prob / jnp.maximum(
            jnp.sum(eff_prob, axis=-1, keepdims=True), 1e-9)
    else:
        gates = eff_prob
    if keep is not None:
        gates = jnp.where(keep, gates, 0.0)
    gates = gates.astype(x.dtype)

    ep = mesh_axis_size(expert_axis) if expert_axis is not None else 1
    ep_drop = None
    _tap_routing(flat_e, e, top_k, counts)
    from ..profiler import RecordEvent
    if ep > 1 and capacity_factor is None and e % ep == 0 \
            and s % ep == 0 and _env_mesh() is not None:
        with RecordEvent("moe:ep_dispatch_combine"):
            y, ep_drop = _dropless_ep(x, gates, topk_idx, gate_up,
                                      down, expert_axis, ep,
                                      ep_buffer_factor)
    else:
        if ep > 1:
            gate_up = _ep_constraint(gate_up, expert_axis)
            down = _ep_constraint(down, expert_axis)
        gs = counts.at[e - 1].add(
            jnp.int32(s * top_k) - jnp.sum(counts, dtype=jnp.int32))
        d_ffn = down.shape[1]
        # inside a TP engine's trace GSPMD owns the partitioning (the
        # expert weights arrive mp-sharded): opaque Pallas kernels —
        # fused AND megablox — must stay off, exactly like the r5
        # sharded-fallback ragged_dot gate
        from ..ops.pallas.paged_attention import serving_tp_active
        gspmd_tp = serving_tp_active()
        fmode = _use_fused_gmm(s * top_k, d, d_ffn, fused=fused) \
            if ep <= 1 and not gspmd_tp else False
        if fmode:
            # fused-dispatch path: the sort is the first matmul's
            # gather-on-read load, swiglu its epilogue, the unsort the
            # second matmul's scatter store — the packed [s*k, d]
            # buffer and the [s*k, 2f] projection never reach HBM.
            # Same routing, same gs tail-pad, so capacity zero-gating
            # and random-skip absorption behave exactly as the sorted
            # path they replace.
            MOE_STATS["grouped_mm_calls"] += 2
            MOE_STATS["grouped_mm_kernel"] = "fused_gmm"
            with RecordEvent("moe:fused_dispatch_combine"):
                y = _fused_moe_core(
                    top_k, fmode == "interpret", x, gate_up, down,
                    gates, order, (order // top_k).astype(jnp.int32),
                    gs)
        else:
            # local sorted grouped-matmul path: all s*k pairs flow
            # through the grouped matmuls (capacity-dropped pairs are
            # zero-gated at combine — same total rows as dropless, no
            # capacity padding); pairs skipped by random routing sort
            # into the tail and are absorbed into the last group. When
            # the expert axis IS sharded but the shard_map fast path
            # was ineligible (non-divisible e/s), GSPMD owns the
            # partitioning — the opaque Pallas kernel can't be
            # partitioned, so force the ragged_dot lowering (the r5
            # gate, kept exactly where it is still required).
            with RecordEvent("moe:dispatch"):
                xs = _expand_sort(x, order // top_k, rank,
                                  top_k)                   # [s*k, d]
            with RecordEvent("moe:expert_mm"):
                ys = _expert_swiglu_grouped(
                    xs, gate_up, down, gs, x.dtype,
                    allow_pallas=(ep <= 1 and not gspmd_tp))
            with RecordEvent("moe:combine"):
                picked = _perm_rows(ys, rank, order) \
                    .reshape(s, top_k, -1)
                y = jnp.einsum("sk,skd->sd", gates, picked)

    # GShard load-balance aux (top-1 occupancy), as the padded path
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jax.nn.one_hot(topk_idx[:, 0], e,
                                 dtype=jnp.float32), axis=0)
    aux = e * jnp.sum(me * ce)
    if return_stats:
        if ep_drop is not None:
            drop = ep_drop          # EP exchange-buffer overflow
        elif keep is None:
            drop = jnp.float32(0.0)
        else:
            drop = 1.0 - jnp.sum(keep.astype(jnp.float32)) \
                / float(s * top_k)
        return y, aux, {"drop_rate": drop}
    return y, aux


def _env_mesh():
    from . import env as _env
    return _env.get_mesh()


def _dropless_ep(x, gates, topk_idx, gate_up, down, axis, ep,
                 buffer_factor):
    """EP-sharded dropless fast path: grouped matmuls INSIDE shard_map.

    The r5 sharded path handed the whole dispatch to GSPMD with
    sharding constraints and fell back to ``lax.ragged_dot`` — the
    megablox kernel was gated off exactly where multi-chip training
    runs. Inside ``shard_map`` the per-shard buffer shapes are STATIC,
    so the Pallas grouped kernel is legal under expert sharding, and
    the collective placement is explicit instead of inferred.

    Per shard (s_l = s/ep local tokens, e_l = e/ep local experts,
    experts laid out shard-major so destination-shard regions are
    contiguous in expert-sorted order):

      1. stable-sort local (token, slot) pairs by destination expert;
      2. gather rows into a ``[ep, cap_pair, d]`` send buffer, exchange
         per-expert counts, then ONE ``lax.all_to_all`` places every
         pair on the shard owning its expert;
      3. derive per-row local expert ids from the exchanged counts,
         re-sort received rows expert-major, run the TWO grouped
         matmuls (megablox on TPU), unsort;
      4. the reverse ``all_to_all`` returns expert outputs to their
         source shard, which combines them with the gate weights.

    The whole pipeline is one custom_vjp: the backward replays the same
    all-to-all structure on cotangents and runs the grouped matmuls
    with the separately tuned backward tilings (transpose-rhs gmm +
    tgmm) instead of letting autodiff transpose the dispatch gathers
    into serialized scatters.

    ``cap_pair`` bounds each (src, dst) exchange slot at
    ``buffer_factor * s_l * k / ep`` rows (rounded up to the sublane
    multiple); pairs beyond it are dropped and reported via the
    returned drop fraction. ``buffer_factor >= ep`` is exactly
    dropless (the per-slot worst case is all local pairs to one
    shard)."""
    mesh = _env_mesh()
    s, d = x.shape
    k = topk_idx.shape[1]
    e = gate_up.shape[0]
    e_l = e // ep
    s_l = s // ep
    n_l = s_l * k
    cap_pair = int(math.ceil(float(buffer_factor) * n_l / ep))
    cap_pair = min(max(cap_pair, 1), n_l)
    cap_pair = -(-cap_pair // 8) * 8          # sublane-align the slots
    n_r = ep * cap_pair
    MOE_STATS["ep_shard_map_calls"] += 1

    def _fwd(x_l, gates_l, idx_l, gu_w, dn_w):
        flat_e = idx_l.reshape(-1)                        # [n_l] global
        order, rank, counts = _sort_pairs(flat_e, e)
        cnt_de = counts.reshape(ep, e_l)                  # [dest, le]
        shard_cnt = cnt_de.sum(axis=1)                    # [ep]
        shard_start = jnp.cumsum(shard_cnt) - shard_cnt
        # per-(dest, expert) counts that fit the slot (tail clipped)
        exp_off = jnp.cumsum(cnt_de, axis=1) - cnt_de
        cnt_send = jnp.clip(jnp.minimum(cnt_de, cap_pair - exp_off),
                            0, None).astype(jnp.int32)
        # gather pairs into send slots (dest-major sorted order)
        pslot = shard_start[:, None] + jnp.arange(cap_pair)[None, :]
        sent = jnp.arange(cap_pair)[None, :] < jnp.minimum(
            shard_cnt, cap_pair)[:, None]                 # [ep, cap]
        send_pair = jnp.take(order, jnp.clip(pslot, 0, n_l - 1))
        send = jnp.take(x_l, (send_pair // k).reshape(-1), axis=0) \
            .reshape(ep, cap_pair, d)
        cnt_recv = jax.lax.all_to_all(cnt_send, axis, 0, 0)
        recv = jax.lax.all_to_all(send, axis, 0, 0)       # [src, cap, d]
        # local expert id of each received row from the counts matrix;
        # rows past a slot's total get sentinel e_l and sort last
        bounds = jnp.cumsum(cnt_recv, axis=1)             # [src, e_l]
        j = jnp.arange(cap_pair)
        eid = (j[None, :, None] >= bounds[:, None, :]).sum(-1) \
            .astype(jnp.int32)
        order2, rank2, _ = _sort_pairs(eid.reshape(-1), e_l)
        xs = jnp.take(recv.reshape(n_r, d), order2, axis=0)
        gs = cnt_recv.sum(axis=0).astype(jnp.int32)
        gs = gs.at[e_l - 1].add(
            jnp.int32(n_r) - jnp.sum(gs, dtype=jnp.int32))    # pads
        gu = _grouped_mm(xs, gu_w.astype(xs.dtype), gs)
        g_a, u_a = jnp.split(gu, 2, axis=-1)
        h = jax.nn.silu(g_a.astype(jnp.float32)).astype(xs.dtype) * u_a
        ys = _grouped_mm(h, dn_w.astype(xs.dtype), gs)
        back = jnp.take(ys, rank2, axis=0).reshape(ep, cap_pair, d)
        outs = jax.lax.all_to_all(back, axis, 0, 0)       # [dest, cap, d]
        dest = flat_e // e_l
        off = rank - shard_start[dest]
        kept = off < cap_pair
        slot = dest * cap_pair + jnp.minimum(off, cap_pair - 1)
        per_pair = jnp.take(outs.reshape(n_r, d), slot, axis=0)
        per_pair = jnp.where(kept[:, None], per_pair,
                             jnp.zeros((), x_l.dtype))
        picked = per_pair.reshape(s_l, k, d)
        y = jnp.einsum("sk,skd->sd", gates_l.astype(x_l.dtype), picked)
        drop = jax.lax.psum(jnp.sum((~kept).astype(jnp.float32)),
                            axis) / float(s * k)
        res = (xs, gu, picked, gates_l, send_pair, sent, order2,
               rank2, kept, slot, gs, gu_w, dn_w)
        return (y, drop), res

    @jax.custom_vjp
    def core(x_l, gates_l, idx_l, gu_w, dn_w):
        out, _ = _fwd(x_l, gates_l, idx_l, gu_w, dn_w)
        return out

    def core_fwd(x_l, gates_l, idx_l, gu_w, dn_w):
        return _fwd(x_l, gates_l, idx_l, gu_w, dn_w)

    def core_bwd(res, ct):
        dy, _ddrop = ct
        (xs, gu, picked, gates_l, send_pair, sent, order2, rank2,
         kept, slot, gs, gu_w, dn_w) = res
        dy32 = dy.astype(jnp.float32)
        dgates = jnp.einsum("sd,skd->sk", dy32,
                            picked.astype(jnp.float32))
        # per-pair output cotangent routed through the SAME slots
        dpair = (gates_l.astype(jnp.float32)[..., None]
                 * dy32[:, None, :]).reshape(n_l, d).astype(dy.dtype)
        dsend = jnp.take(dpair, send_pair.reshape(-1), axis=0) \
            .reshape(ep, cap_pair, d)
        dsend = jnp.where(sent[..., None], dsend,
                          jnp.zeros((), dsend.dtype))
        dback = jax.lax.all_to_all(dsend, axis, 0, 0)
        dys = jnp.take(dback.reshape(n_r, d), order2, axis=0)
        g_a, u_a = jnp.split(gu, 2, axis=-1)
        g32 = g_a.astype(jnp.float32)
        sg = jax.nn.silu(g32)
        h = (sg * u_a.astype(jnp.float32)).astype(xs.dtype)
        ddn = _grouped_mm_drhs(h, dys, gs, e_l)
        dh = _grouped_mm_dlhs(dys, dn_w.astype(dys.dtype), gs) \
            .astype(jnp.float32)
        sig = jax.nn.sigmoid(g32)
        dg = dh * u_a.astype(jnp.float32) * sig * (1 + g32 * (1 - sig))
        du = dh * sg
        dgu = jnp.concatenate([dg, du], axis=-1).astype(xs.dtype)
        dguw = _grouped_mm_drhs(xs, dgu, gs, e_l)
        dxs = _grouped_mm_dlhs(dgu, gu_w.astype(dgu.dtype), gs)
        drecv = jnp.take(dxs, rank2, axis=0).reshape(ep, cap_pair, d)
        dsent = jax.lax.all_to_all(drecv, axis, 0, 0)
        dpx = jnp.take(dsent.reshape(n_r, d), slot, axis=0)
        dpx = jnp.where(kept[:, None], dpx, jnp.zeros((), dpx.dtype))
        dx = dpx.reshape(s_l, k, d).sum(axis=1)
        return (dx.astype(xs.dtype), dgates.astype(gates_l.dtype),
                None, dguw.astype(gu_w.dtype), ddn.astype(dn_w.dtype))

    core.defvjp(core_fwd, core_bwd)

    from jax.sharding import PartitionSpec as P
    f = jax.shard_map(
        core, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis, None),
                  P(axis, None, None), P(axis, None, None)),
        out_specs=(P(axis, None), P()), check_vma=False)
    return f(x, gates, topk_idx, gate_up, down)


def _ep_constraint(arr, axis):
    from . import env as _env
    from jax.sharding import NamedSharding, PartitionSpec as P
    mesh = _env.get_mesh()
    if mesh is None or mesh.shape.get(axis, 1) <= 1:
        return arr
    spec = P(*([axis] + [None] * (arr.ndim - 1)))
    try:
        return jax.lax.with_sharding_constraint(
            arr, NamedSharding(mesh, spec))
    except Exception as exc:
        import warnings
        warnings.warn(
            f"moe: expert-parallel sharding constraint on axis {axis!r} "
            f"failed ({exc!r}); expert compute stays replicated")
        return arr


class MoELayer(Layer):
    """``MoELayer`` parity. experts: list of Layers (one per local
    expert) with identical structure; their params are stacked into
    [e, ...] arrays sharded over ``moe_axis``."""

    def __init__(self, d_model, experts: List[Layer] = None, gate=None,
                 moe_group=None, mp_group=None, recompute_interval=0,
                 top_k=2, capacity_factor=None, moe_axis="dp", **kwargs):
        super().__init__()
        self.d_model = d_model
        from ..nn.layer.container import LayerList
        self.experts = LayerList(experts or [])
        self.num_expert = len(self.experts)
        if gate is None or isinstance(gate, dict):
            cfg = gate or {}
            gtype = cfg.get("type", "gshard")
            topk = cfg.get("top_k", top_k)
            cls = {"naive": NaiveGate, "gshard": GShardGate,
                   "switch": SwitchGate}[gtype]
            gate = cls(d_model, self.num_expert, topk=topk)
        self.gate = gate
        self.top_k = getattr(gate, "top_k", top_k)
        # explicit layer arg wins; else the gate's capacity; else 1.25
        if capacity_factor is not None:
            self.capacity_factor = capacity_factor
        else:
            gate_cap = getattr(gate, "capacity_factor", None)
            self.capacity_factor = 1.25 if gate_cap is None else gate_cap
        self.moe_axis = moe_axis
        # stacked expert params: [e, ...] (template = expert 0)
        self._template = self.experts[0] if self.num_expert else None
        # mark for MoE-aware grad clip (ClipGradForMOEByGlobalNorm)
        for exp in self.experts:
            for p in exp.parameters():
                p.is_expert_param = True
        self.drop_rate = None

    def _flat_params(self):
        """All expert params expert-major, as the live Tensor objects (so
        the tape records grads against each expert's own parameters)."""
        items = [list(exp.named_parameters()) for exp in self.experts]
        n_per = len(items[0])
        flat = [p for exp_items in items for _, p in exp_items]
        return n_per, flat

    def forward(self, x):
        orig_shape = x.shape
        d = orig_shape[-1]
        from ..ops.manipulation import reshape
        x2 = reshape(x, [-1, d])
        logits = self.gate(x2)
        n_per, flat_params = self._flat_params()
        e = self.num_expert
        template = self._template
        param_objs = [p for _, p in template.named_parameters()]

        second_policy = getattr(self.gate, "second_expert_policy", "all")
        rng_key = None
        if second_policy == "random" and self.training:
            from ..framework import random as _random
            rng_key = _random.next_key()

        def f(x_arr, logit_arr, *flat):
            # restack [e, ...] per param position from the flat operands
            stk = [jnp.stack([flat[i * n_per + j] for i in range(e)],
                             axis=0) for j in range(n_per)]

            def efn(expert_in):
                def one(args):
                    params_i, xi = args
                    saved = [p._data for p in param_objs]
                    try:
                        for p, arr in zip(param_objs, params_i):
                            p._data = arr
                        from ..framework.core import no_grad, \
                            functional_mode
                        with functional_mode(), no_grad():
                            out = template(Tensor(xi))
                        return as_jax(out)
                    finally:
                        for p, arr in zip(param_objs, saved):
                            p._data = arr
                return jax.lax.map(one, (tuple(stk), expert_in))
            y, aux, stats = moe_dispatch_combine(
                x_arr, logit_arr, self.num_expert, self.top_k,
                self.capacity_factor, efn, self.moe_axis,
                second_expert_policy=second_policy, rng_key=rng_key,
                return_stats=True)
            return y, aux, stats["drop_rate"]

        y, aux, drop = apply_jax("moe", f, x2, logits, *flat_params,
                                 n_outputs=3)
        self.gate.loss = aux
        self._aux_loss = aux
        self.drop_rate = drop
        return reshape(y, list(orig_shape))

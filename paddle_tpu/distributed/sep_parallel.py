"""Ulysses-style segment parallelism over the ``sep`` mesh axis
(reference: the ``sep`` degree in
``python/paddle/distributed/fleet/base/topology.py`` plus PaddleNLP
``paddlenlp/transformers/segment_parallel_utils.py`` — SURVEY.md §5.7
mechanism 2; DeepSpeed-Ulysses is the originating design).

Mechanics, TPU-first: activations arrive sequence-sharded
``[B, L/sp, H, D]``. Inside a shard_map over the ``sep`` axis an
``all_to_all`` swaps the shard dimension — each device trades its
sequence slice of every head for the full sequence of ``H/sp`` heads —
attention runs un-sharded per head subset (so any kernel works,
including the Pallas flash kernel), and a second ``all_to_all``
restores sequence sharding. Total comm is 2 all-to-alls of the qkv/out
activations riding ICI, vs. the ring's ``sp`` ppermute hops of KV —
Ulysses wins when heads are plentiful and KV is large (GQA favors the
ring; dense MHA favors Ulysses), which is why the mechanism is a
config knob rather than hard-wired.

Distinct from ``ring_attention.py`` (context parallel): the config key
``hybrid_configs["sep_mechanism"]`` selects which mechanism consumes
the ``sep`` axis ("ulysses", the reference's sep semantics, is the
default; "ring" keeps the CP behavior).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..framework.core import Tensor, apply_jax, as_jax
from . import env as _env

__all__ = ["ulysses_attention", "sep_attention", "get_sep_mechanism",
           "ReshardLayer"]


def get_sep_mechanism() -> str:
    """Mechanism consuming the sep axis: "ulysses" (default) or "ring"."""
    try:
        from .fleet import _strategy
        if _strategy is not None:
            return _strategy.hybrid_configs.get("sep_mechanism", "ulysses")
    except Exception:
        pass
    return "ulysses"


def _full_seq_attention(q, k, v, causal, scale):
    """Attention on unsharded [B, L, H', D] blocks (head subset)."""
    from ..ops.pallas.flash_attention import flash_attention_core
    return flash_attention_core(q, k, v, is_causal=causal, scale=scale)


def ulysses_attention(q, k, v, mesh: Mesh = None, axis: str = "sep",
                      causal: bool = False, scale=None):
    """q/k/v: [B, L, H, D] with L globally sharded over ``axis`` and the
    same head count H (GQA callers repeat KV heads first). Requires
    H % sep_degree == 0. Returns [B, L, H, D], seq-sharded like q."""
    mesh = mesh or _env.get_mesh()
    q_arr, k_arr, v_arr = as_jax(q), as_jax(k), as_jax(v)
    if scale is None:
        scale = 1.0 / np.sqrt(q_arr.shape[-1])
    scale = float(scale)
    from .shard_utils import in_manual_region
    sp = mesh.shape[axis] if mesh is not None else 1
    if mesh is None or sp <= 1 or in_manual_region():
        # in_manual_region: already inside a shard_map (e.g. a pipeline
        # stage) — a nested shard_map over the same mesh is invalid, and
        # the data there is not seq-sharded, so plain attention is right
        out = jax.nn.dot_product_attention(q_arr, k_arr, v_arr,
                                           is_causal=causal, scale=scale)
        return Tensor(out) if isinstance(q, Tensor) else out

    n_heads = q_arr.shape[2]
    if n_heads % sp != 0:
        from ..framework.errors import InvalidArgumentError
        raise InvalidArgumentError(
            f"ulysses_attention: num_heads={n_heads} not divisible by "
            f"sep degree {sp}",
            hint="use sep_mechanism='ring' for this shape")

    def per_device(ql, kl, vl):
        # [B, L/sp, H, D] -> all_to_all -> [B, L, H/sp, D]
        def s2h(x):
            return jax.lax.all_to_all(x, axis, split_axis=2,
                                      concat_axis=1, tiled=True)

        def h2s(x):
            return jax.lax.all_to_all(x, axis, split_axis=1,
                                      concat_axis=2, tiled=True)

        qh, kh, vh = s2h(ql), s2h(kl), s2h(vl)
        out = _full_seq_attention(qh, kh, vh, causal, scale)
        return h2s(out)

    spec = P(None, axis, None, None)
    mapped = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)

    if isinstance(q, Tensor):
        return apply_jax("ulysses_attention", mapped, q, k, v)
    return mapped(q_arr, k_arr, v_arr)


_indivisible_warned = False


def sep_attention(q, k, v, causal: bool = True, scale=None):
    """Dispatch attention over the sep axis per the configured mechanism
    (the single entry point model code uses). Falls back to the ring
    when Ulysses can't split the heads evenly."""
    mechanism = get_sep_mechanism()
    if mechanism != "ring":
        mesh = _env.get_mesh()
        sp = mesh.shape.get("sep", 1) if mesh is not None else 1
        if sp > 1 and as_jax(q).shape[2] % sp != 0:
            global _indivisible_warned
            if not _indivisible_warned:
                _indivisible_warned = True
                import warnings
                warnings.warn(
                    "sep_attention: num_heads %d not divisible by sep "
                    "degree %d; falling back to the ring mechanism"
                    % (as_jax(q).shape[2], sp))
            mechanism = "ring"
    if mechanism == "ring":
        from .ring_attention import ring_flash_attention
        return ring_flash_attention(q, k, v, causal=causal, scale=scale)
    return ulysses_attention(q, k, v, causal=causal, scale=scale)


class ReshardLayer:
    """PaddleNLP ``segment_parallel_utils.ReshardLayer`` parity: reshard
    [b, s/sep, h, d] <-> [b, s, h/sep, d] via all_to_all on the sep
    axis (as a standalone op, outside attention)."""

    @staticmethod
    def apply(x, split_axis: int = 2, concat_axis: int = 1,
              axis: str = "sep"):
        mesh = _env.get_mesh()
        sp = mesh.shape[axis] if mesh is not None else 1
        if mesh is None or sp <= 1:
            return x

        def per_device(xl):
            return jax.lax.all_to_all(xl, axis, split_axis=split_axis,
                                      concat_axis=concat_axis, tiled=True)

        ndim = as_jax(x).ndim
        in_spec = [None] * ndim
        in_spec[concat_axis] = axis
        out_spec = [None] * ndim
        out_spec[split_axis] = axis
        mapped = jax.shard_map(
            per_device, mesh=mesh,
            in_specs=(P(*in_spec),),
            out_specs=P(*out_spec), check_vma=False)
        if isinstance(x, Tensor):
            return apply_jax("sep_reshard", mapped, x)
        return mapped(as_jax(x))

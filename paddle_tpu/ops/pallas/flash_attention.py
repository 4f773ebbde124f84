"""Flash attention for TPU.

Reference parity: ``paddle/phi/kernels/gpu/flash_attn_kernel.cu`` wrapping
the bundled FlashAttention-2 (``third_party/flashattn``). TPU-first design:
a Pallas kernel (splash-attention pattern — blocked online softmax in VMEM)
when running on real TPU, with an XLA fallback that jax fuses well on all
backends. Layout is Paddle's flash-attn convention [B, L, H, D].
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from ...framework.core import Tensor, apply_jax, as_jax
from .flash_attention_kernel import pallas_flash_attention


def _xla_attention(q, k, v, bias, is_causal, scale):
    """Fallback path: jax.nn.dot_product_attention (XLA fuses the softmax
    chain; fine for short sequences / biased attention). Grouped kv
    heads pass straight through — jax handles GQA natively when kv heads
    divide query heads, with no materialized repeat."""
    if bias is not None and bias.ndim == 4 \
            and bias.shape[1] not in (1, q.shape[2]):
        # bias per kv-head group (FlashMask dense lowering): expand to
        # the query head count, which dot_product_attention requires
        bias = jnp.repeat(bias, q.shape[2] // bias.shape[1], axis=1)
    return jax.nn.dot_product_attention(
        q, k, v, bias=bias, is_causal=is_causal, scale=scale)


def _pallas_available():
    """The flash kernels take a TPU backend; a backend query that
    raises propagates (no silent XLA run on a machine whose
    accelerator failed to come up)."""
    return jax.default_backend() == "tpu"


def _kernel_eligible(q, k, bias):
    # q and kv seq divisible into >=128 lanes, head_dim tile-friendly,
    # no dense bias (FlashMask lowers its compact form separately);
    # grouped kv heads are handled natively by the kernel
    return (bias is None and q.shape[1] % 128 == 0 and q.shape[1] >= 256
            and k.shape[1] % 128 == 0
            and q.shape[-1] in (64, 128, 256)
            and q.shape[2] % k.shape[2] == 0)


_fallback_logged = False


# the fleet mesh axes that split the batch / the heads of an activation
# (``shard_utils.batch_shard``, the Column/RowParallel head split)
_BATCH_AXES = ("dp", "sharding", "ep")
_HEAD_AXIS = "mp"


def _gspmd_shard_spec(q, k):
    """How the kernel must run when the ambient fleet mesh makes this a
    GSPMD-partitioned program: Mosaic kernels cannot be partitioned
    automatically ("Please wrap the call in a shard_map" — the first
    hybrid train step on four chips, PR 21), so the call is mapped over
    the mesh by hand, batch over the data axes and heads over ``mp``.

    Returns ``None`` when there is nothing to partition (no mesh, one
    device, or already inside a shard_map body, where axes are manual
    and shapes local), ``False`` when the shape does not divide over the
    mesh (XLA attention, which GSPMD does partition), else ``(mesh,
    PartitionSpec)`` for q/k/v/out."""
    from jax.sharding import PartitionSpec as P
    from ...distributed.shard_utils import current_mesh
    mesh = current_mesh()
    if mesh is None or mesh.size == 1 \
            or jax.sharding.get_abstract_mesh().manual_axes:
        return None
    batch = tuple(a for a in _BATCH_AXES if mesh.shape.get(a, 1) > 1)
    n_batch = int(np.prod([mesh.shape[a] for a in batch])) if batch else 1
    mp = mesh.shape.get(_HEAD_AXIS, 1)
    if q.shape[0] % n_batch or q.shape[2] % mp or k.shape[2] % mp:
        return False
    return mesh, P(batch or None, None, _HEAD_AXIS if mp > 1 else None,
                   None)


def flash_attention_core(q, k, v, bias=None, is_causal=False, scale=None):
    """Pure-array flash attention; q/k/v: [B, L, H, D]. K/V may carry
    fewer (grouped) heads — the Pallas kernel consumes them natively and
    the XLA fallback repeats them internally."""
    if scale is None:
        scale = 1.0 / np.sqrt(q.shape[-1])
    if _pallas_available():
        part = _gspmd_shard_spec(q, k) \
            if _kernel_eligible(q, k, bias) else False
        if part is None:
            return pallas_flash_attention(q, k, v, causal=is_causal,
                                          sm_scale=scale)
        if part:
            mesh, spec = part
            return jax.shard_map(
                lambda q_, k_, v_: pallas_flash_attention(
                    q_, k_, v_, causal=is_causal, sm_scale=scale),
                mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                check_vma=False)(q, k, v)
        global _fallback_logged
        if not _fallback_logged:
            _fallback_logged = True
            import warnings
            warnings.warn(
                "flash_attention: shape %s / bias=%s not eligible for the "
                "Pallas kernel (or not divisible over the mesh); using "
                "the XLA fallback (logged once)"
                % (tuple(q.shape), bias is not None))
    return _xla_attention(q, k, v, bias, is_causal, scale)


def mask_to_bias(mask, dtype):
    """Bool mask (True = keep) -> additive bias; float masks pass
    through. Single home for the convention — every attention entry
    point shares it."""
    if mask is None:
        return None
    m = as_jax(mask)
    if jnp.issubdtype(m.dtype, jnp.bool_):
        return jnp.where(m, 0.0, -1e9).astype(dtype)
    return m


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, name=None):
    bias = mask_to_bias(attn_mask, as_jax(query).dtype)

    def f(q, k, v):
        out = flash_attention_core(q, k, v, bias=bias, is_causal=is_causal)
        return out

    out = apply_jax("flash_attention", f, query, key, value)
    if dropout_p > 0.0 and training:
        from ...nn.functional.common import dropout
        out = dropout(out, dropout_p, training=True)
    return out


def flash_attention(query, key, value, dropout=0.0, causal=False,
                    return_softmax=False, fixed_seed_offset=None,
                    rng_name="", training=True, name=None):
    """``paddle.nn.functional.flash_attention.flash_attention`` parity."""
    out = scaled_dot_product_attention(query, key, value, None, dropout,
                                       causal, training)
    if return_softmax:
        return out, None
    return out, None


def _flashmask_kernel_eligible(q, idx):
    """Compact-form kernel: TPU, lane-aligned seq, supported head dim,
    bounds in {1, 2}, and mask heads dividing query heads."""
    return (_pallas_available()
            and q.shape[1] % 128 == 0 and q.shape[1] >= 256
            and q.shape[-1] in (64, 128, 256)
            and idx.shape[-1] in (1, 2)
            and q.shape[2] % idx.shape[1] == 0)


def flashmask_dense_bias(idx, seq_len, causal, dtype):
    """The O(L²) additive bias ``[B, H_m, L, L]`` the compact bounds
    ``idx [B, H_m, L, 1|2]`` stand for — the dense lowering the
    compact-form kernel exists to avoid, and its reference."""
    rows = jnp.arange(seq_len)[:, None]  # query index
    cols = jnp.arange(seq_len)[None, :]  # key index
    start = idx[..., 0]  # [B, Hm, L]: mask rows >= start per key column
    masked = rows[None, None] >= start[:, :, None, :]
    if idx.shape[-1] == 2:
        masked = masked & (rows[None, None] < idx[..., 1][:, :, None, :])
    if causal:
        masked = masked | (cols[None, None] > rows[None, None])
    return jnp.where(masked, -1e9, 0.0).astype(dtype)


def flashmask_attention(query, key, value, startend_row_indices=None,
                        dropout=0.0, causal=False, name=None):
    """FlashMask sparse-mask attention parity
    (``paddle.nn.functional.flashmask_attention``): the mask arrives as
    O(L) per-column row bounds. On TPU the Pallas compact-form kernel
    (``flashmask_kernel.py``) consumes the bounds directly — no O(L²)
    bias is ever materialized, and fully-masked blocks are skipped —
    which is the long-context memory/flop profile FlashMask exists for.
    Off-TPU (or for unsupported shapes) the bounds lower to a dense
    bias."""
    if startend_row_indices is None:
        return scaled_dot_product_attention(query, key, value, None,
                                            dropout, causal, True)
    q = as_jax(query)
    idx = as_jax(startend_row_indices)  # [B, H_k, L, bounds]
    if _flashmask_kernel_eligible(q, idx):
        from .flashmask_kernel import pallas_flashmask_attention

        def fk(q_a, k_a, v_a, idx_a):
            return pallas_flashmask_attention(q_a, k_a, v_a, idx_a,
                                              causal=causal)
        out = apply_jax("flashmask_attention", fk, query, key, value,
                        Tensor(idx))
        if dropout > 0.0:
            from ...nn.functional.common import dropout as _dropout
            out = _dropout(out, dropout, training=True)
        return out
    bias = flashmask_dense_bias(idx, q.shape[1], causal, q.dtype)
    # bias is [B, Hk, Lq, Lk]; broadcast over query heads
    mask_t = Tensor(bias)
    return scaled_dot_product_attention(query, key, value, mask_t, dropout,
                                        False, True)

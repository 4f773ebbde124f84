"""Ring (context-parallel) attention
(PaddleNLP ``paddlenlp/transformers/ring_flash_attention.py`` parity —
the reference lives out-of-tree; SURVEY.md §5.7 mechanism 3).

TPU-first: sequence is sharded over the ``sep`` mesh axis; KV blocks ride
a ``ppermute`` ring inside shard_map while each step folds a partial
attention into online-softmax accumulators (m, l, o), kept in fp32 until
the final normalization. Backward is ``jax.grad`` through the scan
(ppermute transposes to the reverse ring).

Causal efficiency:
- future KV blocks are skipped with ``lax.cond`` (no FLOPs — not
  computed-then-masked);
- ``balance=True`` (default for causal) uses the ZIGZAG layout: the
  global sequence is split into 2*sp chunks and device d holds chunks
  (d, 2sp-1-d), so every device does the same amount of causal work
  instead of device 0 idling while device sp-1 computes sp blocks. The
  contiguous->zigzag resharding is two ppermutes on entry and exit —
  callers keep the ordinary contiguous seq sharding.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from ..framework.core import Tensor, apply_jax, as_jax
from . import env as _env

__all__ = ["ring_flash_attention", "RingFlashAttention"]


def _block_attn_f32(q, k, v, scale, mask=None):
    """One partial attention in fp32: returns (o_partial, m, l).
    q: [B, Lq, H, D]; k/v: [B, Lk, H, D]."""
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    s = jnp.einsum("blhd,bmhd->bhlm", qf, kf) * scale
    if mask is not None:
        s = jnp.where(mask, s, jnp.float32(-1e30))
    m = jnp.max(s, axis=-1)                       # [B, H, Lq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)                       # [B, H, Lq]
    o = jnp.einsum("bhlm,bmhd->blhd", p, v.astype(jnp.float32))
    return o, m, l


def _merge(o_acc, m_acc, l_acc, o_p, m_p, l_p):
    """Fold a partial (o_p, m_p, l_p) into fp32 online-softmax state."""
    m_new = jnp.maximum(m_acc, m_p)
    alpha = jnp.exp(m_acc - m_new)
    beta = jnp.exp(m_p - m_new)
    l_new = l_acc * alpha + l_p * beta
    o_new = (o_acc * alpha.transpose(0, 2, 1)[..., None]
             + o_p * beta.transpose(0, 2, 1)[..., None])
    return o_new, m_new, l_new


NEG_INF = np.float32(-1e30)  # finite: exp(m_p - m_acc) of two empty
# online-softmax states must be exp(0)=1, not exp(-inf + inf)=NaN


def _zeros_state(B, L, H, D):
    return (jnp.zeros((B, L, H, D), jnp.float32),
            jnp.full((B, H, L), NEG_INF, jnp.float32),
            jnp.zeros((B, H, L), jnp.float32))


def _finalize(o, m, l, dtype):
    out = o / jnp.maximum(l, 1e-20).transpose(0, 2, 1)[..., None]
    return out.astype(dtype)


def ring_flash_attention(q, k, v, mesh: Mesh = None, axis: str = "sep",
                         causal: bool = False, scale=None,
                         balance: bool = True):
    """q/k/v: [B, L, H, D] with L globally sharded over ``axis``.
    Returns [B, L, H, D] with the same sharding."""
    mesh = mesh or _env.get_mesh()
    q_arr, k_arr, v_arr = as_jax(q), as_jax(k), as_jax(v)
    if scale is None:
        scale = 1.0 / np.sqrt(q_arr.shape[-1])
    scale = float(scale)  # keep weak-typed under x64
    from .shard_utils import in_manual_region
    sp = mesh.shape[axis] if mesh is not None else 1
    if mesh is None or sp <= 1 or in_manual_region():
        out = jax.nn.dot_product_attention(q_arr, k_arr, v_arr,
                                           is_causal=causal, scale=scale)
        return Tensor(out) if isinstance(q, Tensor) else out

    if causal and balance and q_arr.shape[1] % (2 * sp) == 0:
        per_device = functools.partial(_ring_zigzag, axis=axis, sp=sp,
                                       scale=scale)
    else:
        per_device = functools.partial(_ring_contiguous, axis=axis,
                                       sp=sp, scale=scale, causal=causal)

    spec = P(None, axis, None, None)
    mapped = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(spec, spec, spec),
        out_specs=spec, check_vma=False)

    if isinstance(q, Tensor):
        return apply_jax("ring_flash_attention", mapped, q, k, v)
    return mapped(q_arr, k_arr, v_arr)


def _ring_contiguous(ql, kl, vl, *, axis, sp, scale, causal):
    """Plain ring over the contiguous seq layout. Future blocks are
    skipped with lax.cond (zero FLOPs), the diagonal applies the
    triangular mask; non-causal computes every block."""
    my = jax.lax.axis_index(axis)
    B, L, H, D = ql.shape
    perm = [(i, (i + 1) % sp) for i in range(sp)]
    rows = jnp.arange(L)[:, None]
    cols = jnp.arange(L)[None, :]
    tri = (rows >= cols)[None, None]

    def step(carry, t):
        kt, vt, o_acc, m_acc, l_acc = carry
        src = (my - t) % sp  # which global block this kv is

        def diag(_):
            return _block_attn_f32(ql, kt, vt, scale, tri)

        def full(_):
            return _block_attn_f32(ql, kt, vt, scale, None)

        def skip(_):
            return (jnp.zeros((B, L, H, D), jnp.float32),
                    jnp.full((B, H, L), NEG_INF, jnp.float32),
                    jnp.zeros((B, H, L), jnp.float32))

        if causal:
            # 0: past (full), 1: diagonal, 2: future (skip)
            sel = jnp.int32(0) + (src == my) + 2 * (src > my)
            o_p, m_p, l_p = jax.lax.switch(sel, [full, diag, skip], None)
        else:
            o_p, m_p, l_p = full(None)
        o_new, m_new, l_new = _merge(o_acc, m_acc, l_acc, o_p, m_p, l_p)
        kn = jax.lax.ppermute(kt, axis, perm)
        vn = jax.lax.ppermute(vt, axis, perm)
        return (kn, vn, o_new, m_new, l_new), None

    o0, m0, l0 = _zeros_state(B, L, H, D)
    (_, _, o, m, l), _ = jax.lax.scan(
        step, (kl, vl, o0, m0, l0), jnp.arange(sp))
    return _finalize(o, m, l, ql.dtype)


def _zigzag_perms(sp):
    """ppermute tables: contiguous half h of device d is global chunk
    c=2d+h; zigzag owner of chunk c is c if c<sp else 2sp-1-c."""
    fwd0, fwd1 = [], []
    for d in range(sp):
        for h, table in ((0, fwd0), (1, fwd1)):
            c = 2 * d + h
            t = c if c < sp else 2 * sp - 1 - c
            table.append((d, t))
    # inverse: zigzag device d holds chunks (d, 2sp-1-d); owner of
    # chunk c in contiguous layout is c//2, half c%2
    inv0 = [(t, d) for d, t in fwd0]
    inv1 = [(t, d) for d, t in fwd1]
    return fwd0, fwd1, inv0, inv1


def _ring_zigzag(ql, kl, vl, *, axis, sp, scale):
    """Causal ring on the zigzag layout: device d computes against KV
    chunk pairs from each source with per-chunk full/diag/skip selection
    — every device does equal work. Entry/exit reshards contiguous <->
    zigzag with two ppermutes each way."""
    my = jax.lax.axis_index(axis)
    B, L, H, D = ql.shape
    Lh = L // 2
    fwd0, fwd1, inv0, inv1 = _zigzag_perms(sp)

    def to_zigzag(x):
        lo, hi = x[:, :Lh], x[:, Lh:]
        a = jax.lax.ppermute(lo, axis, fwd0)   # -> chunk (my) owner
        b = jax.lax.ppermute(hi, axis, fwd1)   # -> chunk (2sp-1-my)
        return a, b

    def from_zigzag(a, b):
        lo = jax.lax.ppermute(a, axis, inv0)
        hi = jax.lax.ppermute(b, axis, inv1)
        return jnp.concatenate([lo, hi], axis=1)

    qa, qb = to_zigzag(ql)     # my global chunks: a=my, b=2sp-1-my
    ka, kb = to_zigzag(kl)
    va, vb = to_zigzag(vl)

    rows = jnp.arange(Lh)[:, None]
    cols = jnp.arange(Lh)[None, :]
    tri = (rows >= cols)[None, None]

    def pair(qc, q_chunk, kt, vt, k_chunk):
        """Attend one q chunk against one kv chunk by causal relation
        (global chunk ids are traced scalars)."""

        def full(_):
            return _block_attn_f32(qc, kt, vt, scale, None)

        def diag(_):
            return _block_attn_f32(qc, kt, vt, scale, tri)

        def skip(_):
            return (jnp.zeros((B, Lh, H, D), jnp.float32),
                    jnp.full((B, H, Lh), NEG_INF, jnp.float32),
                    jnp.zeros((B, H, Lh), jnp.float32))

        sel = jnp.int32(0) + (k_chunk == q_chunk) + \
            2 * (k_chunk > q_chunk)
        return jax.lax.switch(sel, [full, diag, skip], None)

    # device d owns chunks {d, 2sp-1-d}; fwd0 carries EVEN global
    # chunks and fwd1 ODD ones, and d / 2sp-1-d have opposite parity —
    # so which of the pair landed in slot a/b depends on d's parity
    def owned_chunks(d):
        even = jnp.where(d % 2 == 0, d, 2 * sp - 1 - d)
        odd = jnp.where(d % 2 == 1, d, 2 * sp - 1 - d)
        return even, odd

    chunk_a, chunk_b = owned_chunks(my)

    def step(carry, t):
        (kta, vta, ktb, vtb, oa, ma, la, ob, mb, lb) = carry
        src = (my - t) % sp
        src_a, src_b = owned_chunks(src)  # kv chunk ids on the ring
        for (kt, vt, kc) in ((kta, vta, src_a), (ktb, vtb, src_b)):
            o_p, m_p, l_p = pair(qa, chunk_a, kt, vt, kc)
            oa, ma, la = _merge(oa, ma, la, o_p, m_p, l_p)
            o_p, m_p, l_p = pair(qb, chunk_b, kt, vt, kc)
            ob, mb, lb = _merge(ob, mb, lb, o_p, m_p, l_p)
        perm = [(i, (i + 1) % sp) for i in range(sp)]
        kta = jax.lax.ppermute(kta, axis, perm)
        vta = jax.lax.ppermute(vta, axis, perm)
        ktb = jax.lax.ppermute(ktb, axis, perm)
        vtb = jax.lax.ppermute(vtb, axis, perm)
        return (kta, vta, ktb, vtb, oa, ma, la, ob, mb, lb), None

    oa0, ma0, la0 = _zeros_state(B, Lh, H, D)
    ob0, mb0, lb0 = _zeros_state(B, Lh, H, D)
    carry = (ka, va, kb, vb, oa0, ma0, la0, ob0, mb0, lb0)
    (_, _, _, _, oa, ma, la, ob, mb, lb), _ = jax.lax.scan(
        step, carry, jnp.arange(sp))
    out_a = _finalize(oa, ma, la, ql.dtype)
    out_b = _finalize(ob, mb, lb, ql.dtype)
    return from_zigzag(out_a, out_b)


class RingFlashAttention:
    """Class facade matching PaddleNLP's RingFlashAttention.apply."""

    @staticmethod
    def apply(q, k, v, group=None, causal=False, **kw):
        axis = getattr(group, "axis_name", None) or "sep"
        return ring_flash_attention(q, k, v, axis=axis, causal=causal)

"""Sliding-window attention over the serving tick's packed rows, its
keys and values a bounded RING a seat kept as slot state
(``ops/paged_cache.SlotState``): the window layers of a decoder whose
attention sees the last ``W`` positions only (the ``swa`` layers of
``models/phi4flash.py``).

The ring of a seat holds the K (or V) rows of the last ``W`` positions
BEFORE this tick, position ``p`` at index ``p mod W``; the table is
``[seats + 1, W, lanes]`` whatever the context, so a window layer's
memory a seat stays ``W`` positions. A row at position ``p`` attends the
keys at positions ``(p - W, p]``: those of the ring (the seat's base
``b`` = positions before the tick: index ``j`` holds ``b - (b mod W) +
j``, less ``W`` where ``j >= b mod W``; a negative one was never
written) and this tick's own rows of its slot, causal. Afterwards the
slot's last ``W`` rows of the tick are written into the ring
(:func:`ring_write`): attention reads the ring as the tick found it.
A seat whose first row is position 0 reads nothing of the ring (every
position it names is negative), so a new request never sees its seat's
last occupant.

Queries come PAIRED (``paged_attention._pair_queries``'s head-64 layout
over pairs of kv heads): ``q [R, H, 128]`` where head ``i`` reads kv
pair ``i // (H / pairs)``'s 128 lanes, the other half of its lanes
zero, so each query's scores are its own kv head's; the output keeps
all 128 lanes, the pair's two value heads side by side. That is the
differential attention's layout (``models/phi4flash.py``).

Two shapes of work a tick, both under the scope ``window_attention``
(one metric reads both), each with the XLA mirror below as the CPU
path:

- the one-row seats: a grid step is one seat, its ring's K and V
  (``W`` x all lanes) one block each, every pair a static loop;
- THE one wide slot (a prefill chunk): a grid step is one kv pair, the
  chunk's rows against the slot's ring and the chunk's own rows.

Dispatch: the kernels on a TPU (or under
``PADDLE_TPU_PAGED_KERNEL=interpret``) where the head is 128 lanes and
``W`` whole sublane tiles, the mirror elsewhere; an ineligible shape on a
TPU is counted as a kernel fallback.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_rule import _chunk_layout, _seats
from .flash_attention_kernel import kernel_scope
from .paged_attention import (NEG_INF, _force_kernel_routing, _interpret,
                              _row_pad, count_fallback)
from ...framework.core import component

__all__ = ["window_attention", "ring_write", "ring_positions",
           "pallas_window_decode", "pallas_window_chunk"]

_F32 = jnp.float32
_VMEM_LIMIT = 64 * 1024 * 1024


def ring_positions(base, window):
    """``[..., W]`` int32: the position each ring index holds for seats
    that have seen ``base [...]`` positions (negative: never written)."""
    base = base.astype(jnp.int32)
    rem = base % window
    j = jnp.arange(window, dtype=jnp.int32)
    return (base - rem)[..., None] + j - jnp.where(j >= rem[..., None],
                                                   window, 0)


def ring_write(ring, rows, base, ragged_meta, window):
    """``ring [S + 1, W, lanes]`` with each slot's last ``W`` rows of
    this tick (``rows [R, lanes]``) written at their positions mod
    ``W``; rows no slot owns (and a slot's earlier rows) are routed to
    the null seat, which nothing reads."""
    r = rows.shape[0]
    ql, _rs, sl, _fresh, off, live = _seats(ragged_meta, r)
    n = ql.shape[0]
    keep = live & (off >= ql[sl] - window)
    pos = base.astype(jnp.int32)[sl] + off
    with component("cache"):
        return ring.at[jnp.where(keep, sl, n),
                       jnp.where(keep, pos % window, 0)].set(
                           rows.astype(ring.dtype))


# ---------------------------------------------------------------------------
# XLA mirror (the CPU path)
# ---------------------------------------------------------------------------

def _xla_window(q, k_rows, v_rows, ring_k, ring_v, base, ragged_meta,
                window, scale):
    r, h, d = q.shape
    pairs = ring_k.shape[-1] // d
    ql, _rs, sl, _fresh, off, live = _seats(ragged_meta, r)
    n = ql.shape[0]
    seat = jnp.where(live, sl, n)
    b = jnp.append(base.astype(jnp.int32), 0)[seat]
    pos = b + off
    ring_p = ring_positions(b, window)                       # [R, W]
    keep_r = (ring_p >= 0) & (ring_p > pos[:, None] - window)
    keep_t = (live[None, :] & (sl[None, :] == sl[:, None])
              & (off[None, :] <= off[:, None])
              & (off[None, :] > off[:, None] - window))      # [R, R]
    qq = q.reshape(r, pairs, h // pairs, d)
    kr = ring_k[seat].reshape(r, window, pairs, d)
    vr = ring_v[seat].reshape(r, window, pairs, d)
    kt = k_rows.reshape(r, pairs, d)
    vt = v_rows.reshape(r, pairs, d)
    s1 = jnp.einsum("rpgd,rwpd->rpgw", qq, kr,
                    preferred_element_type=_F32) * scale
    s2 = jnp.einsum("rpgd,tpd->rpgt", qq, kt,
                    preferred_element_type=_F32) * scale
    s = jnp.concatenate(
        [jnp.where(keep_r[:, None, None], s1, NEG_INF),
         jnp.where(keep_t[:, None, None], s2, NEG_INF)], axis=-1)
    p = jax.nn.softmax(s, axis=-1)
    o = (jnp.einsum("rpgw,rwpd->rpgd", p[..., :window], vr.astype(_F32))
         + jnp.einsum("rpgt,tpd->rpgd", p[..., window:], vt.astype(_F32)))
    return jnp.where(live[:, None, None], o.reshape(r, h, d), 0.0)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _decode_kernel(seat_ref, base_ref, rem_ref, nlive_ref, q_ref, k_ref,
                   v_ref, rk_ref, rv_ref, o_ref, *, window, pairs, scale):
    del seat_ref
    i = pl.program_id(0)

    @pl.when(i < nlive_ref[0])
    def _attend():
        b, rem = base_ref[i], rem_ref[i]
        j = jax.lax.broadcasted_iota(jnp.int32, (1, window), 1)
        held = b - rem + j - jnp.where(j >= rem, window, 0)
        # the row is position b: keys (b - W, b]
        keep = (held >= 0) & (held > b - window)
        d = q_ref.shape[-1]
        for p in range(pairs):
            lanes = pl.ds(p * d, d)
            q = q_ref[0, p]                               # [rp, d]
            sc = jax.lax.dot_general(
                q, rk_ref[0, :, lanes], (((1,), (1,)), ((), ())),
                preferred_element_type=_F32) * scale      # [rp, W]
            sc = jnp.where(keep, sc, NEG_INF)
            own = jnp.sum(q.astype(_F32) * k_ref[0, :, lanes].astype(_F32),
                          axis=1, keepdims=True) * scale  # [rp, 1]
            m = jnp.maximum(jnp.max(sc, axis=1, keepdims=True), own)
            e = jnp.exp(sc - m)
            e_own = jnp.exp(own - m)
            total = jnp.sum(e, axis=1, keepdims=True) + e_own
            out = jax.lax.dot_general(
                e.astype(rv_ref.dtype), rv_ref[0, :, lanes],
                (((1,), (0,)), ((), ())), preferred_element_type=_F32)
            o_ref[0, p] = (out + e_own * v_ref[0, :, lanes].astype(_F32)) \
                / total


def pallas_window_decode(q, k_rows, v_rows, ring_k, ring_v, base,
                         ragged_meta, window, scale, interpret=None):
    """The one-row seats' rows: ``q [R, H, 128]`` paired, ``k_rows,
    v_rows [R, lanes]`` this tick's, ``ring_k, ring_v [S + 1, W,
    lanes]``. Returns ``[R, H, 128]`` float32, zeros on every row that
    is not a one-row seat's."""
    r, h, d = q.shape
    lanes = ring_k.shape[-1]
    pairs = lanes // d
    rep = h // pairs
    ql, rs, sl, _fresh, _off, live = _seats(ragged_meta, r)
    n = ql.shape[0]
    one = ql == 1
    order = jnp.argsort(~one, stable=True).astype(jnp.int32)
    n_live = jnp.sum(one).astype(jnp.int32)
    step = jnp.arange(n, dtype=jnp.int32)
    on = step < n_live
    seat = jnp.where(on, order, n)
    row = jnp.where(on, jnp.minimum(rs[order], r - 1), 0)
    b = jnp.where(on, base.astype(jnp.int32)[order], 0)
    # a pair's query heads padded to a whole sublane tile of q's dtype
    rp = _row_pad(rep, q.dtype)
    with component("mixer.glue"):
        qs = jnp.pad(q[row].reshape(n, pairs, rep, d),
                     ((0, 0), (0, 0), (0, rp - rep), (0, 0)))

    def by_step(i, *_):
        return (i, 0, 0)

    def by_seat(i, seat_r, *_):
        return (seat_r[i], 0, 0)

    ring = pl.BlockSpec((1, window, lanes), by_seat)
    own = pl.BlockSpec((1, 1, lanes), by_step)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n,),
        in_specs=[pl.BlockSpec((1, pairs, rp, d),
                               lambda i, *_: (i, 0, 0, 0)),
                  own, own, ring, ring],
        out_specs=pl.BlockSpec((1, pairs, rp, d),
                               lambda i, *_: (i, 0, 0, 0)),
    )
    call = pl.pallas_call(
        functools.partial(_decode_kernel, window=window, pairs=pairs,
                          scale=np.float32(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n, pairs, rp, d), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret() if interpret is None else interpret,
    )
    with kernel_scope("window_attention"):
        o = call(seat, b, b % window, n_live[None], qs,
                 k_rows[row][:, None], v_rows[row][:, None], ring_k, ring_v)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(step)
    o = o[:, :, :rep].reshape(n, h, d)
    return jnp.where((one[sl] & live)[:, None, None], o[rank[sl]], 0.0)


def _chunk_kernel(seat_ref, base_ref, rem_ref, nw_ref, q_ref, k_ref, v_ref,
                  rk_ref, rv_ref, o_ref, *, window, shift, scale):
    del seat_ref

    @pl.when(nw_ref[0] > 0)
    def _attend():
        b, rem = base_ref[0], rem_ref[0]
        q = q_ref[0]                                  # [w * rep, d]
        rows, w = q.shape[0], k_ref.shape[0]
        t = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0) >> shift
        j = jax.lax.broadcasted_iota(jnp.int32, (1, window), 1)
        held = b - rem + j - jnp.where(j >= rem, window, 0)
        keep_r = (held >= 0) & (held > b + t - window)
        tj = jax.lax.broadcasted_iota(jnp.int32, (1, w), 1)
        keep_t = (tj <= t) & (tj > t - window)
        nt = (((1,), (1,)), ((), ()))
        s1 = jnp.where(keep_r, jax.lax.dot_general(
            q, rk_ref[0], nt, preferred_element_type=_F32) * scale, NEG_INF)
        s2 = jnp.where(keep_t, jax.lax.dot_general(
            q, k_ref[...], nt, preferred_element_type=_F32) * scale, NEG_INF)
        m = jnp.maximum(jnp.max(s1, axis=1, keepdims=True),
                        jnp.max(s2, axis=1, keepdims=True))
        e1 = jnp.exp(s1 - m)
        e2 = jnp.exp(s2 - m)
        total = jnp.sum(e1, axis=1, keepdims=True) \
            + jnp.sum(e2, axis=1, keepdims=True)
        nn = (((1,), (0,)), ((), ()))
        out = jax.lax.dot_general(e1.astype(rv_ref.dtype), rv_ref[0], nn,
                                  preferred_element_type=_F32) \
            + jax.lax.dot_general(e2.astype(v_ref.dtype), v_ref[...], nn,
                                  preferred_element_type=_F32)
        o_ref[0] = out / total


def pallas_window_chunk(q, k_rows, v_rows, ring_k, ring_v, base,
                        ragged_meta, window, scale, interpret=None):
    """The one wide slot's rows; shapes as :func:`pallas_window_decode`.
    Returns ``[R, H, 128]`` float32, zeros on every other slot's rows."""
    r, h, d = q.shape
    lanes = ring_k.shape[-1]
    pairs = lanes // d
    rep = h // pairs
    n = ring_k.shape[0] - 1
    w = -(-int(ragged_meta[5].shape[0]) // 16) * 16
    wide, n_wide, _fresh, idx, valid, mine, off = _chunk_layout(
        ragged_meta, r, w)
    seat = jnp.where(n_wide > 0, wide, n)
    b = jnp.where(n_wide > 0, base.astype(jnp.int32)[wide], 0)
    with component("mixer.glue"):
        qs = jnp.swapaxes(q[idx].reshape(w, pairs, rep, d), 0, 1) \
            .reshape(pairs, w * rep, d)
        kc = jnp.where(valid[:, None], k_rows[idx], 0)
        vc = jnp.where(valid[:, None], v_rows[idx], 0)

    by_pair = pl.BlockSpec((w, d), lambda p, *_: (0, p))
    ring = pl.BlockSpec((1, window, d),
                        lambda p, seat_r, *_: (seat_r[0], 0, p))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(pairs,),
        in_specs=[pl.BlockSpec((1, w * rep, d), lambda p, *_: (p, 0, 0)),
                  by_pair, by_pair, ring, ring],
        out_specs=pl.BlockSpec((1, w * rep, d), lambda p, *_: (p, 0, 0)),
    )
    call = pl.pallas_call(
        functools.partial(_chunk_kernel, window=window,
                          shift=rep.bit_length() - 1,
                          scale=np.float32(scale)),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((pairs, w * rep, d), _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret() if interpret is None else interpret,
    )
    with kernel_scope("window_attention"):
        o = call(seat[None], b[None], (b % window)[None],
                 n_wide.astype(jnp.int32)[None], qs, kc, vc, ring_k, ring_v)
    o = jnp.swapaxes(o.reshape(pairs, w, rep, d), 0, 1).reshape(w, h, d)
    return jnp.where(mine[:, None, None], o[jnp.clip(off, 0, w - 1)], 0.0)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def _use_kernel(q, ring_k) -> bool:
    on_tpu = jax.default_backend() == "tpu"
    h, d = q.shape[1:]
    pairs = ring_k.shape[-1] // d
    rep = h // pairs if pairs else 0
    sublanes = 32 // jnp.dtype(ring_k.dtype).itemsize
    ok = (d == 128 and ring_k.shape[-1] % d == 0 and h % pairs == 0
          and 0 < rep and rep & (rep - 1) == 0
          and ring_k.shape[1] % sublanes == 0)
    if (on_tpu or _force_kernel_routing()) and ok:
        return True
    if on_tpu:
        count_fallback("window_attention")
    return False


def window_attention(q, k_rows, v_rows, ring_k, ring_v, base, ragged_meta,
                     window, scale):
    """Attention of the packed rows over their seats' rings and their
    slots' rows of this tick (module docstring). ``q [R, H, 128]``
    paired, ``k_rows, v_rows [R, lanes]``, ``ring_k, ring_v [S + 1, W,
    lanes]`` as the tick found them, ``base [S]`` the positions each
    slot had before the tick. Returns ``[R, H, 128]`` float32; rows no
    slot owns read zeros. The rings are written after, by
    :func:`ring_write`."""
    if _use_kernel(q, ring_k):
        return (pallas_window_chunk(q, k_rows, v_rows, ring_k, ring_v,
                                    base, ragged_meta, window, scale)
                + pallas_window_decode(q, k_rows, v_rows, ring_k, ring_v,
                                       base, ragged_meta, window, scale))
    return _xla_window(q, k_rows, v_rows, ring_k, ring_v, base,
                       ragged_meta, window, scale)

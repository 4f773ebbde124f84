"""A causal depthwise short convolution over the ragged tick's packed
rows, its last ``L - 1`` inputs a SLOT kept as slot state
(``ops/paged_cache.SlotState``): the one tap reader of every mixer that
has such a filter (``models/lfm2_moe.Lfm2ShortConv`` at 3 taps,
``models/solar_open2.KimiDeltaAttention`` at 4 over ``q | k | v``).
"""
from __future__ import annotations

import jax.numpy as jnp

__all__ = ["causal_taps", "ragged_causal_taps"]


def causal_taps(w, rows):
    """``sum_j w[:, j] * rows[j]`` in float32: ``rows`` are the ``L``
    shifted copies of the filter's input (oldest first), each ``[...,
    channels]``; ``w`` is ``[channels, L]``."""
    w32 = w.astype(jnp.float32)
    acc = rows[0].astype(jnp.float32) * w32[:, 0]
    for j in range(1, len(rows)):
        acc = acc + rows[j].astype(jnp.float32) * w32[:, j]
    return acc


def ragged_causal_taps(g, state, w, ragged_meta):
    """The filter over the tick's packed rows ``g [R, channels]``;
    ``state [num_slots + 1, L - 1, channels]`` holds the last ``L - 1``
    rows of ``g`` that each slot has seen, ``w`` is ``[channels, L]``
    (tap ``j`` multiplies ``g[t - (L - 1) + j]``).

    Row ``r`` of slot ``s`` at offset ``o = r - row_starts[s]``: tap
    ``j`` reads ``g[r - (L - 1) + j]`` where the tick carries it (``o
    >= L - 1 - j``), else ``state[s, o + j]``. A slot whose first row
    is at position 0 reads zeros: a NEW request's seat never sees its
    last occupant's state, and needs no reset executable. Afterwards
    ``state[s]`` is the last ``L - 1`` of the slot's old state followed
    by its rows of this tick. A row no slot owns — past the packed
    total, or retired inside the executable by the ``done`` mask, whose
    ``q_lens`` is 0 — reads the null seat (the table's last row, never
    written) and writes nothing. Returns ``(the taps' sum in float32
    [R, channels], the new state)``."""
    ql, rs, sl, pos = ragged_meta[:4]
    keep = w.shape[1] - 1
    r = g.shape[0]
    n_slots = ql.shape[0]
    ql = ql.astype(jnp.int32)
    rs = rs.astype(jnp.int32)
    row = jnp.arange(r, dtype=jnp.int32)
    off = row - rs[sl]
    live = (off >= 0) & (off < ql[sl])
    seat = jnp.where(live, sl.astype(jnp.int32), n_slots)
    # a slot's state as its rows see it: zeros where the slot's first
    # row is position 0
    first = pos.astype(jnp.int32)[jnp.minimum(rs, r - 1)]
    fresh = (ql > 0) & (first == 0)
    old = jnp.where(fresh[:, None, None], 0, state[:n_slots])
    old = jnp.concatenate([old, state[n_slots:]])
    rows = []
    for j in range(keep):
        back = keep - j
        prev = jnp.pad(g, ((back, 0), (0, 0)))[:r]
        kept = old[seat, jnp.clip(off + j, 0, keep - 1)]
        rows.append(jnp.where((off >= back)[:, None], prev, kept))
    rows.append(g)
    conv = causal_taps(w, rows)
    # the slot's last L - 1 entries of (old state ++ rows)
    n = ql[:, None] + jnp.arange(keep, dtype=jnp.int32)[None]
    from_g = g[jnp.clip(rs[:, None] + n - keep, 0, r - 1)]
    from_old = jnp.take_along_axis(
        old[:n_slots], jnp.clip(n, 0, keep - 1)[..., None], axis=1)
    new = jnp.where((n >= keep)[..., None], from_g, from_old)
    new = jnp.where((ql > 0)[:, None, None], new, state[:n_slots])
    return conv, state.at[:n_slots].set(new.astype(state.dtype))

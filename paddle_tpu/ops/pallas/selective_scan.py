"""Mamba's selective scan (arXiv:2312.00752, Mamba-1) over the serving
tick's packed rows, its state a channel kept as slot state.

Per channel ``e`` of the ``E`` inner channels, with ``N`` state entries,
``A [N, E] = -exp(A_log)^T`` (negative), ``dt [E]`` (the softplus'd step,
positive), ``x [E]`` the convolved input and ``B, C [N]`` shared by the
channels of a row::

    s_t = exp(dt_t A) * s_{t-1} + B_t (dt_t x_t)      s [N, E], float32
    y_t = sum_n C_t[n] s_t[n]

``D x`` and the ``silu(z)`` gate are the caller's (XLA). The state
table is ``[seats + 1, N, E]`` float32: the channels along the lanes,
the ``N`` entries along the sublanes, so a row's ``B`` and ``C`` enter
as columns (their first lane of a ``[N, 128]`` block, broadcast along
the lanes) and ``y`` is a sum over sublanes.

Two shapes of work a tick, each its own ``pallas_call`` under its own
scope and each with an XLA mirror that is the CPU path (the split of
``ops/pallas/delta_rule.py``):

- **``ssm_recurrent``**: every seat that owns exactly ONE row this tick.
  A grid step is one seat: its ``[N, E]`` state is read, advanced one
  row and written IN PLACE (the table aliased input to output). Live
  seats first; the steps left over name the null seat (the table's
  last row), copied through once.
- **``ssm_chunk``**: THE one seat that owns more than one row (a prefill
  chunk; at most one a tick, ``paged_attention._xla_ragged_lanes``'s
  op contract). Sequential in VMEM: a grid step holds one tile of the
  channels and walks the chunk's rows eight at a time, the state in
  registers, so the state leaves HBM once and returns once a chunk
  whatever its length. (The recurrence is diagonal, so a tile of
  channels needs nothing of another.)

A seat whose first row is position 0 starts from zeros; rows no slot
owns touch the null seat only. Dispatch: the kernels on a TPU (or
under ``PADDLE_TPU_PAGED_KERNEL=interpret``) where ``E`` is whole lane
tiles and ``N`` whole sublane tiles, the mirrors elsewhere; an
ineligible shape on a TPU is counted as a kernel fallback.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .delta_rule import _chunk_layout, _seats
from .flash_attention_kernel import kernel_scope
from .paged_attention import (_force_kernel_routing, _interpret,
                              count_fallback)

__all__ = ["ssm_step", "ssm_recurrent", "ssm_chunk",
           "pallas_ssm_recurrent", "pallas_ssm_chunk", "ROWS"]

ROWS = 8        # rows of the chunk's walk a store (one sublane tile)
_F32 = jnp.float32
_TILES = (1024, 512, 256, 128)      # lanes of the chunk's channel tile


def _advance(s, a, dt, x, b, c):
    """One row: ``(s', y)``; ``s [..., N, E]``, ``dt, x [..., E]``,
    ``b, c [..., N]``."""
    s = jnp.exp(dt[..., None, :] * a) * s \
        + b[..., :, None] * (dt * x)[..., None, :]
    return s, jnp.sum(s * c[..., :, None], axis=-2)


# ---------------------------------------------------------------------------
# XLA mirrors (the CPU path)
# ---------------------------------------------------------------------------

def _xla_recurrent(dt, x, b, c, a, state, ragged_meta):
    r = dt.shape[0]
    ql, rs, sl, fresh, _off, live = _seats(ragged_meta, r)
    n = ql.shape[0]
    one = ql == 1
    rows = jnp.minimum(rs, r - 1)
    s = jnp.where(fresh[:, None, None], 0.0, state[:n])
    s, y = _advance(s, a, *(v[rows].astype(_F32) for v in (dt, x, b, c)))
    new = jnp.where(one[:, None, None], s, state[:n])
    out = jnp.where((one[sl] & live)[:, None], y[sl], 0.0)
    return out, state.at[:n].set(new)


def _slab(v, idx, valid):
    """The wide slot's rows of ``v [R, ...]``, zeros past its last: a
    zero step leaves the state as it is."""
    return jnp.where(valid.reshape((-1,) + (1,) * (v.ndim - 1)),
                     v[idx].astype(_F32), 0.0)


def _pad_width(ragged_meta):
    return -(-int(ragged_meta[5].shape[0]) // ROWS) * ROWS


def _xla_chunk(dt, x, b, c, a, state, ragged_meta):
    r = dt.shape[0]
    w = _pad_width(ragged_meta)
    wide, n_wide, fresh, idx, valid, mine, off = _chunk_layout(
        ragged_meta, r, w)
    s0 = state[wide]

    def step(s, row):
        return _advance(s, a, *row)

    s, ys = jax.lax.scan(step, jnp.where(fresh, 0.0, s0), tuple(
        _slab(v, idx, valid) for v in (dt, x, b, c)))
    state = state.at[wide].set(jnp.where(n_wide > 0, s, s0))
    return jnp.where(mine[:, None], ys[jnp.clip(off, 0, w - 1)], 0.0), state


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _columns(v, r):
    """``v [R, N]`` as ``[R, N, 128]`` float32, each entry on every lane
    of its sublane: a kernel takes lane 0 as the column it broadcasts."""
    return jnp.broadcast_to(v.astype(_F32)[..., None], (r, v.shape[1], 128))


def _recurrent_kernel(seat_ref, row_ref, fresh_ref, nlive_ref, dt_ref,
                      x_ref, b_ref, c_ref, a_ref, s_ref, so_ref, y_ref):
    del seat_ref, row_ref
    i = pl.program_id(0)
    n = nlive_ref[0]

    @pl.when(i < n)
    def _advance_seat():
        dt = dt_ref[0]                          # [1, E]
        s = jnp.where(fresh_ref[i] == 1, 0.0, s_ref[0])
        s = jnp.exp(a_ref[...] * dt) * s + b_ref[0][:, :1] * (dt * x_ref[0])
        so_ref[0] = s
        y_ref[0] = jnp.sum(s * c_ref[0][:, :1], axis=0, keepdims=True)

    # the steps past the live seats all name the null seat: its block
    # stays in VMEM across them, so one copy serves them all
    @pl.when(i == n)
    def _pass():
        so_ref[...] = s_ref[...]


def pallas_ssm_recurrent(dt, x, b, c, a, state, ragged_meta,
                         interpret=None):
    """``ssm_recurrent`` as a Mosaic kernel: ``dt, x [R, E]``, ``b, c
    [R, N]``, ``a [N, E]``, ``state [S + 1, N, E]`` float32 (donate it:
    written in place). Returns ``(y [R, E] — zeros on rows that are not
    a one-row seat's — , state)``."""
    r, e = dt.shape
    nst = state.shape[1]
    ql, rs, sl, fresh, _off, live = _seats(ragged_meta, r)
    n = ql.shape[0]
    one = ql == 1
    order = jnp.argsort(~one, stable=True).astype(jnp.int32)
    n_live = jnp.sum(one).astype(jnp.int32)
    step = jnp.arange(n, dtype=jnp.int32)
    on = step < n_live
    seat = jnp.where(on, order, n)
    row = jnp.where(on, jnp.minimum(rs[order], r - 1), 0)
    fresh_s = (on & fresh[order]).astype(jnp.int32)

    def by_row(i, seat_r, row_r, *_):
        return (row_r[i], 0, 0)

    def by_seat(i, seat_r, *_):
        return (seat_r[i], 0, 0)

    lane_row = pl.BlockSpec((1, 1, e), by_row)
    col = pl.BlockSpec((1, nst, 128), by_row)
    table = pl.BlockSpec((1, nst, e), by_seat)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(n,),
        in_specs=[lane_row, lane_row, col, col,
                  pl.BlockSpec((nst, e), lambda i, *_: (0, 0)), table],
        out_specs=[table, pl.BlockSpec((1, 1, e), lambda i, *_: (i, 0, 0))],
    )
    call = pl.pallas_call(
        _recurrent_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((n, 1, e), _F32)],
        # operands count the scalar prefetch: the table is the tenth
        input_output_aliases={9: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret() if interpret is None else interpret,
    )
    with kernel_scope("ssm_recurrent"):
        state, y = call(seat, row, fresh_s, n_live[None],
                        dt.astype(_F32)[:, None], x.astype(_F32)[:, None],
                        _columns(b, r), _columns(c, r), a.astype(_F32),
                        state)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(step)
    return jnp.where((one[sl] & live)[:, None], y[rank[sl], 0], 0.0), state


def _chunk_kernel(seat_ref, fresh_ref, nblk_ref, dt_ref, x_ref, b_ref,
                  c_ref, a_ref, s_ref, so_ref, y_ref):
    del seat_ref
    a = a_ref[...]                              # [N, tile]
    sub = jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)

    def block(k, s):
        r0 = pl.multiple_of(k * ROWS, ROWS)
        dt8 = dt_ref[pl.ds(r0, ROWS), :]
        x8 = x_ref[pl.ds(r0, ROWS), :]
        y8 = jnp.zeros(dt8.shape, _F32)
        for i in range(ROWS):
            dt = dt8[i:i + 1]
            s = jnp.exp(a * dt) * s + b_ref[r0 + i][:, :1] * (dt * x8[i:i + 1])
            y8 = jnp.where(sub == i, jnp.sum(s * c_ref[r0 + i][:, :1], axis=0,
                                             keepdims=True), y8)
        y_ref[pl.ds(r0, ROWS), :] = y8
        return s

    s0 = jnp.where(fresh_ref[0] == 1, 0.0, s_ref[0])
    so_ref[0] = jax.lax.fori_loop(0, nblk_ref[0], block, s0)


def _tile(e) -> int:
    return next((t for t in _TILES if e % t == 0), 0)


def pallas_ssm_chunk(dt, x, b, c, a, state, ragged_meta, interpret=None):
    """``ssm_chunk`` as a Mosaic kernel over the one wide slot's rows;
    shapes as :func:`pallas_ssm_recurrent`. Returns ``(y [R, E] — zeros
    on every other slot's rows — , state)``."""
    r, e = dt.shape
    nst = state.shape[1]
    n = state.shape[0] - 1
    w = _pad_width(ragged_meta)
    te = _tile(e)
    wide, n_wide, fresh, idx, valid, mine, off = _chunk_layout(
        ragged_meta, r, w)
    seat = jnp.where(n_wide > 0, wide, n)
    n_blk = (n_wide + ROWS - 1) // ROWS

    def lanes(j, *_):
        return (0, j)

    cols = pl.BlockSpec((w, nst, 128), lambda j, *_: (0, 0, 0))
    table = pl.BlockSpec((1, nst, te), lambda j, seat_r, *_: (seat_r[0], 0, j))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(e // te,),
        in_specs=[pl.BlockSpec((w, te), lanes)] * 2
        + [cols, cols, pl.BlockSpec((nst, te), lanes), table],
        out_specs=[table, pl.BlockSpec((w, te), lanes)],
    )
    call = pl.pallas_call(
        _chunk_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((w, e), _F32)],
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret() if interpret is None else interpret,
    )
    with kernel_scope("ssm_chunk"):
        state, y = call(seat[None], (fresh & (n_wide > 0)).astype(
            jnp.int32)[None], n_blk.astype(jnp.int32)[None],
            _slab(dt, idx, valid), _slab(x, idx, valid),
            _columns(_slab(b, idx, valid), w),
            _columns(_slab(c, idx, valid), w), a.astype(_F32), state)
    return jnp.where(mine[:, None], y[jnp.clip(off, 0, w - 1)], 0.0), state


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def _use_kernel(kind, state) -> bool:
    on_tpu = jax.default_backend() == "tpu"
    ok = (_tile(state.shape[2]) > 0 and state.shape[1] % 8 == 0
          and state.dtype == jnp.float32)
    if (on_tpu or _force_kernel_routing()) and ok:
        return True
    if on_tpu:
        count_fallback(kind)
    return False


def ssm_recurrent(dt, x, b, c, a, state, ragged_meta):
    """Advance every one-row seat's state by its row (module
    docstring): ``(y [R, E] float32, state)``."""
    if _use_kernel("ssm_recurrent", state):
        return pallas_ssm_recurrent(dt, x, b, c, a, state, ragged_meta)
    return _xla_recurrent(dt, x, b, c, a, state, ragged_meta)


def ssm_chunk(dt, x, b, c, a, state, ragged_meta):
    """Advance the one wide slot's state by its rows in sequence (module
    docstring): ``(y [R, E] float32, state)``."""
    if _use_kernel("ssm_chunk", state):
        return pallas_ssm_chunk(dt, x, b, c, a, state, ragged_meta)
    return _xla_chunk(dt, x, b, c, a, state, ragged_meta)


def ssm_step(dt, x, b, c, a, state, ragged_meta):
    """One tick of the scan over the packed rows: the wide slot's chunk,
    then the one-row seats. ``dt, x [R, E]`` (the step after softplus,
    the convolved input), ``b, c [R, N]``, ``a [N, E]`` (``-exp(A_log)``
    transposed), ``state [S + 1, N, E]`` float32. Returns ``(y [R, E]
    float32, state)``; rows no slot owns read zeros."""
    y_c, state = ssm_chunk(dt, x, b, c, a, state, ragged_meta)
    y_r, state = ssm_recurrent(dt, x, b, c, a, state, ragged_meta)
    return y_c + y_r, state

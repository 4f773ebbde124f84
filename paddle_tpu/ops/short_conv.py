"""A causal depthwise short convolution over the ragged tick's packed
rows, its last ``L - 1`` inputs a SLOT kept as slot state
(``ops/paged_cache.SlotState``): the one tap reader of every mixer that
has such a filter (``models/lfm2_moe.Lfm2ShortConv`` at 3 taps,
``models/solar_open2.KimiDeltaAttention`` at 4 over ``q | k | v``).

``ragged_causal_taps`` is ONE pass over its operands: a Mosaic kernel
(scope ``short_conv_taps``) tiled over the CHANNELS — the filter is
depthwise, so channel tiles share nothing. A grid step holds the packed
rows' tile, every seat's taps of that tile and the filter's, and yields
the taps' sum in float32 and the seats' new taps, written IN PLACE (the
table aliased input to output, as ``kda_recurrent`` writes its own): a
call reads the rows and the table once and writes the sum and the table
once. What the tick's layout decides — which seat a row reads, which
stored tap, which rows become a seat's new taps — is a handful of int32
per row and per seat, computed once in XLA from what
``delta_rule._seats`` reads off the layout, and inside the kernel
becomes 0/1 selection matrices (``iota`` compares):

- rows of a slot are contiguous, so tap ``j`` of row ``r`` is ``g[r -
  back]``, a sublane rotation of the tile, wherever the tick carries it;
- the rows that still read the table (every decode row, a chunk's first
  ``L - 1``) take their seat's stored taps as ``selection [R, seats] x
  table tile`` on the MXU, and the seats' new taps leave the rows the
  same way (``[seats, R] x rows``): a bfloat16 value times an exact one,
  accumulated in float32 with every other term an exact zero, is copied
  exactly, where a gather by row would move one sublane a step. A fresh
  seat is a row of zeros in the matrix. (The product reads ``-0.0`` back
  as ``0.0``, and a NaN or an infinity in one seat's taps would reach
  every row's: the table is finite, the engine builds it from zeros.)

The sum itself is ``causal_taps``'s: float32, oldest tap first, so the
kernel's is the mirror's bit for bit ON THE SAME OPERANDS. The kernel
is handed ``g`` as the bfloat16 array the model names, the values the
table keeps. The mirror inside a larger program may be handed more:
where ``g`` is an elementwise product (LFM2's ``b * z``) XLA fuses it
into the taps' sum and, allowed excess precision, leaves the product
unrounded in float32 for the taps the tick carries — while the table
stores it rounded — so a token's sum depended on whether its
predecessors came in the same tick or from the table (``PERF.md`` 6,
PR 33). Through the kernel it does not. The table stays ``[seats, L - 1,
channels]``; XLA keeps so small a middle dimension outermost in memory,
which is the ``[L - 1, seats, channels]`` view the kernel is handed (a
transpose that moves nothing).

Dispatch follows ``delta_rule._use_kernel``: the kernel on a TPU (or
under ``PADDLE_TPU_PAGED_KERNEL=interpret``) where it is eligible —
channels a multiple of 128 lanes, rows and table in bfloat16 (a float32
value would not cross the MXU in one exact pass) — and
``_xla_ragged_taps``, the plain ``jax.numpy`` form that is the XLA
mirror and the CPU path, elsewhere; an ineligible call on a TPU is
counted as a kernel fallback like the attention kernels'.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .pallas.delta_rule import _seats
from ..framework.core import component
from .pallas.flash_attention_kernel import kernel_scope
from .pallas.paged_attention import (_force_kernel_routing, _interpret,
                                     count_fallback)

__all__ = ["causal_taps", "ragged_causal_taps", "pallas_ragged_taps"]

_F32 = jnp.float32
_TILES = (1024, 512, 256, 128)      # lanes of a channel tile, widest first
# at 352 rows x 1,024 lanes the float32 sum and its operands (the rows,
# their rotations, each seat row's gathered taps) are ~1.4 MB apiece
_VMEM_LIMIT = 64 * 1024 * 1024


def causal_taps(w, rows):
    """``sum_j w[:, j] * rows[j]`` in float32: ``rows`` are the ``L``
    shifted copies of the filter's input (oldest first), each ``[...,
    channels]``; ``w`` is ``[channels, L]``."""
    w32 = w.astype(jnp.float32)
    acc = rows[0].astype(jnp.float32) * w32[:, 0]
    for j in range(1, len(rows)):
        acc = acc + rows[j].astype(jnp.float32) * w32[:, j]
    return acc


# ---------------------------------------------------------------------------
# XLA mirror (the CPU path)
# ---------------------------------------------------------------------------

def _xla_ragged_taps(g, state, w, ragged_meta):
    """``ragged_causal_taps`` in plain ``jax.numpy``: every tap's
    shifted, gathered and selected copy of the rows as its own array."""
    keep = w.shape[1] - 1
    r = g.shape[0]
    ql, rs, sl, fresh, off, live = _seats(ragged_meta, r)
    n_slots = ql.shape[0]
    seat = jnp.where(live, sl, n_slots)
    # a slot's state as its rows see it: zeros where the slot's first
    # row is position 0
    with component("cache"):
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
    with component("cache"):
        new = jnp.where((n >= keep)[..., None], from_g, from_old)
        new = jnp.where((ql > 0)[:, None, None], new, state[:n_slots])
        return conv, state.at[:n_slots].set(new.astype(state.dtype))


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _chosen(code, options, other):
    """``options[code]`` a row (``code [rows, 1]`` int32), ``other``
    where the code names none of them."""
    for i, option in enumerate(options):
        other = jnp.where(code == i, option, other)
    return other


def _taps_kernel(row_ref, seat_ref, w_ref, g_ref, s_ref, so_ref, conv_ref,
                 *, keep):
    r, seats = g_ref.shape[0], s_ref.shape[1]
    g = g_ref[...]
    g32 = g.astype(_F32)
    old = [s_ref[i] for i in range(keep)]
    # each row's seat's stored taps: 0 / 1 times the table's tile
    reads = (jax.lax.broadcasted_iota(jnp.int32, (r, seats), 1)
             == row_ref[0]).astype(g.dtype)
    mine = [jnp.dot(reads, o, preferred_element_type=_F32) for o in old]
    acc = None
    for j in range(keep):
        pick = row_ref[1 + j]
        tap = jnp.where(pick < 0, pltpu.roll(g32, keep - j, 0),
                        _chosen(pick, mine[:-1], mine[-1]))
        tap = tap * w_ref[j:j + 1, :]
        acc = tap if acc is None else acc + tap
    conv_ref[...] = acc + g32 * w_ref[keep:keep + 1, :]
    # each seat's new taps: a row of the tick, a stored tap, or zeros;
    # the rows leave in ONE product, the seats' taps stacked (each
    # weight tile of the rows then serves all L - 1 of them)
    pad = seat_ref.shape[1] // keep
    moved = jnp.dot(
        (jax.lax.broadcasted_iota(jnp.int32, (keep * pad, r), 1)
         == seat_ref[0]).astype(g.dtype), g, preferred_element_type=_F32)
    old32 = [o.astype(_F32) for o in old]
    for i in range(keep):
        lo = i * pad
        new = _chosen(seat_ref[1, lo:lo + seats], old32,
                      moved[lo:lo + seats])
        so_ref[i] = new.astype(so_ref.dtype)


def _tile(channels) -> int:
    """Lanes of the kernel's channel tile, 0 where no tile divides."""
    return next((t for t in _TILES if channels % t == 0), 0)


def _layout(ragged_meta, rows, keep):
    """The tick's layout as the kernel reads it. Per packed row
    ``[1 + keep, R, 1]``: the seat whose stored taps it reads (-1: a
    fresh seat's zeros) and, a tap, which of them (-1: the tick carries
    the row ``back`` before it). Per seat and new tap ``[2, keep x
    seats', 1]``, tap-major, each tap's seats padded to whole sublanes:
    the packed row it is (-1: none) and the stored tap it is (-1: the
    row, or a fresh seat's zeros where there is none)."""
    ql, rs, sl, fresh, off, live = _seats(ragged_meta, rows)
    n_slots = ql.shape[0]
    # (the null seat, one past the slots: never fresh, no rows)
    ql, rs, fresh = (jnp.append(x, x.dtype.type(0)) for x in (ql, rs, fresh))
    tap = jnp.arange(keep, dtype=jnp.int32)[:, None]
    seat = jnp.where(live, sl, n_slots)
    seat = jnp.where(fresh[seat], -1, seat)
    pick = jnp.where(off[None] >= keep - tap, -1,
                     jnp.clip(off[None] + tap, 0, keep - 1))
    # new tap i of a slot is entry q_lens + i of (its old taps ++ its
    # rows of this tick); a slot with no rows keeps tap i
    n = ql[None] + tap
    from_g = (ql[None] > 0) & (n >= keep)
    row = jnp.where(from_g, jnp.clip(rs[None] + n - keep, 0, rows - 1), -1)
    src = jnp.where(ql[None] > 0,
                    jnp.where(from_g | fresh[None], -1, n), tap)
    pad = ((0, 0), (0, -(n_slots + 1) % 8))
    per_seat = jnp.stack([jnp.pad(x, pad, constant_values=-1).reshape(-1)
                          for x in (row, src)])
    return (jnp.concatenate([seat[None], pick])[..., None],
            per_seat[..., None])


def pallas_ragged_taps(g, state, w, ragged_meta, interpret=None):
    """``ragged_causal_taps`` as a Mosaic kernel (module docstring): ``g
    [R, channels]``, ``state [seats, L - 1, channels]`` of ``g``'s
    dtype, bfloat16 (donate it: written in place), ``w [channels, L]``;
    channels a multiple of 128. Returns ``(the taps' sum in float32,
    state)``."""
    r, c = g.shape
    seats, keep = state.shape[:2]
    tc = _tile(c)
    per_row, per_seat = _layout(ragged_meta, r, keep)

    def whole(x):
        return pl.BlockSpec(x.shape, lambda i: (0,) * x.ndim)

    table = pl.BlockSpec((keep, seats, tc), lambda i: (0, 0, i))
    w_t = w.astype(_F32).T
    call = pl.pallas_call(
        functools.partial(_taps_kernel, keep=keep),
        grid=(c // tc,),
        in_specs=[whole(per_row), whole(per_seat),
                  pl.BlockSpec((keep + 1, tc), lambda i: (0, i)),
                  pl.BlockSpec((r, tc), lambda i: (0, i)), table],
        out_specs=[table, pl.BlockSpec((r, tc), lambda i: (0, i))],
        out_shape=[jax.ShapeDtypeStruct((keep, seats, c), state.dtype),
                   jax.ShapeDtypeStruct((r, c), _F32)],
        input_output_aliases={4: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret() if interpret is None else interpret,
    )
    with component("cache"):
        table_in = jnp.swapaxes(state, 0, 1)
    with kernel_scope("short_conv_taps"):
        new, conv = call(per_row, per_seat, w_t, g, table_in)
    with component("cache"):
        return conv, jnp.swapaxes(new, 0, 1)


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def _use_kernel(g, state) -> bool:
    on_tpu = jax.default_backend() == "tpu"
    ok = (_tile(g.shape[1]) > 0
          and g.dtype == state.dtype == jnp.bfloat16)
    if (on_tpu or _force_kernel_routing()) and ok:
        return True
    if on_tpu:
        count_fallback("short_conv_taps")
    return False


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
    if _use_kernel(g, state):
        return pallas_ragged_taps(g, state, w, ragged_meta)
    return _xla_ragged_taps(g, state, w, ragged_meta)

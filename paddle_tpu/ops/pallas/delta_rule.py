"""The gated delta rule with a decay per CHANNEL (Kimi Delta Attention,
arXiv:2510.26692; the gated delta rule of arXiv:2412.06464) over the
serving tick's packed rows, its matrix state a head kept as slot state.

Per head, with ``S [d_k, d_v]`` the state, ``alpha = exp(g)`` in (0, 1]
per key channel and ``beta`` a scalar a row::

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

i.e. decay ``S``'s rows, then ``S += beta k (v - S^T k)^T``. ``q``,
``k``, ``v``, ``g`` and ``beta`` are made outside (projections, the
short convolution, the l2 norm and the softplus are XLA's); ``S`` and
every accumulation here are float32. The state table is VALUE-MAJOR:
``state[seat, head]`` holds ``S^T [d_v, d_k]``, the key channels along
the lanes, so that ``k``, ``alpha`` and ``q`` multiply it as rows.

Two shapes of work a tick, each its own ``pallas_call`` under its own
scope and each with an XLA mirror that is the CPU path:

- **``kda_recurrent``**: every seat that owns exactly ONE row this tick
  (decode rows, one-row prefill trickles). A grid step is one seat: its
  ``[H, d_v, d_k]`` state is read, advanced one row and written IN
  PLACE (the table aliased input to output), so a tick moves the live
  seats' state once each way and nothing else of the table. The seats
  are visited live ones first; the steps left over all name the null
  seat (the table's last row), which is copied through unchanged. The
  two sums over the key channels (``S^T k``, ``S^T q``) are taken on
  the MXU against a matrix of ones (``_lane_sums``), which hands them
  back on every lane: the vector unit does no lane reduction and no
  lane broadcast, and the kernel runs at the speed of its copies.
- **``kda_chunk``**: THE one seat that owns more than one row (a
  prefill chunk: the engine schedules at most one wide slot a tick, the
  op contract ``paged_attention._xla_ragged_lanes`` states). The
  chunkwise (WY / UT) form in sub-chunks of ``SUB`` = 64 rows, the
  sub-chunks in sequence. With ``b_i`` the cumulative log-decay inside
  a sub-chunk, ``K+ = K exp(b)``, ``Q+ = Q exp(b)``::

      (I + strict_lower(diag(beta) A)) U = diag(beta) (V - K+ S_0)
      A_ij = sum_c k_i[c] k_j[c] exp(b_i[c] - b_j[c])
      O = Q+ S_0 + lower(P) U,  P_ij = sum_c q_i[c] k_j[c] exp(b_i - b_j)
      S_C = Diag(exp(b_C)) S_0 + (K exp(b_C - b))^T U

  ``exp(-b)`` alone is never formed (at the strongest published decay a
  64-row sub-chunk sums to -102 and float32 overflows at 88): every
  exponent is a difference ``b_i - b_j`` with ``j <= i``, or is taken
  against a reference row between the two (the first row of ``i``'s
  block of ``BLK`` = 16 rows), so it is never positive. Inside a block
  the pairwise terms are formed row by row, fused with the forward
  substitution that solves for ``U``.

A seat whose first row is at position 0 starts from zeros; rows no slot
owns (past the packed total, or retired by the ``done`` mask) touch the
null seat only. Dispatch (``kda_step``): the kernels on a TPU (or under
``PADDLE_TPU_PAGED_KERNEL=interpret``) where both head sizes are 128,
the mirrors elsewhere; an ineligible shape on a TPU is counted as a
kernel fallback like the attention kernels'.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention_kernel import kernel_scope
from .paged_attention import (_force_kernel_routing, _interpret,
                              count_fallback)

__all__ = ["kda_step", "kda_recurrent", "kda_chunk",
           "pallas_kda_recurrent", "pallas_kda_chunk", "SUB", "BLK"]

SUB = 64        # rows of a sub-chunk of the chunkwise form
BLK = 16        # rows of a block inside it (pairwise terms formed whole)
_HI = jax.lax.Precision.HIGHEST
_F32 = jnp.float32
# the recurrent kernel holds one seat's whole state (4 MB at 64 heads of
# 128 x 128) twice in and twice out
_VMEM_LIMIT = 48 * 1024 * 1024


def _seats(ragged_meta, rows):
    """What both shapes read off the tick's layout: per slot ``q_lens``
    and ``row_starts`` (int32), whether the slot's first row is
    position 0, and per packed row its slot, its offset in the slot and
    whether a slot owns it."""
    ql, rs, sl, pos = ragged_meta[:4]
    ql = ql.astype(jnp.int32)
    rs = rs.astype(jnp.int32)
    sl = sl.astype(jnp.int32)
    first = pos.astype(jnp.int32)[jnp.minimum(rs, rows - 1)]
    fresh = (ql > 0) & (first == 0)
    off = jnp.arange(rows, dtype=jnp.int32) - rs[sl]
    live = (off >= 0) & (off < ql[sl])
    return ql, rs, sl, fresh, off, live


def _wide(ql):
    """The one slot with more than one row: ``(slot, its rows or 0)``."""
    wide = jnp.argmax(ql).astype(jnp.int32)
    return wide, jnp.where(ql[wide] > 1, ql[wide], 0)


# ---------------------------------------------------------------------------
# XLA mirrors (the CPU path)
# ---------------------------------------------------------------------------

def _xla_recurrent(q, k, v, g, beta, state, ragged_meta):
    r = q.shape[0]
    ql, rs, sl, fresh, _off, live = _seats(ragged_meta, r)
    n = ql.shape[0]
    one = ql == 1
    rows = jnp.minimum(rs, r - 1)
    # the table is value-major: state[s, h] is S^T [d_v, d_k]
    s = jnp.where(fresh[:, None, None, None], 0.0, state[:n])
    qs, ks, vs, gs, bs = (x[rows].astype(_F32) for x in (q, k, v, g, beta))
    s = s * jnp.exp(gs)[..., None, :]
    pred = jnp.sum(s * ks[..., None, :], axis=-1)
    s = s + (bs[..., None] * (vs - pred))[..., None] * ks[..., None, :]
    o = jnp.sum(s * qs[..., None, :], axis=-1)
    new = jnp.where(one[:, None, None, None], s, state[:n])
    out = jnp.where((one[sl] & live)[:, None, None], o[sl], 0.0)
    return out, state.at[:n].set(new)


def _slab(x, idx, valid):
    """The wide slot's rows of ``x [R, H, ...]`` as ``[H, W, ...]``,
    zeros past its last row."""
    rows = jnp.where(valid.reshape((-1,) + (1,) * (x.ndim - 1)),
                     x[idx].astype(_F32), 0.0)
    return jnp.swapaxes(rows, 0, 1)


def _chunk_layout(ragged_meta, rows, w_pad):
    ql, rs, sl, fresh, off, live = _seats(ragged_meta, rows)
    wide, n_wide = _wide(ql)
    lane = jnp.arange(w_pad, dtype=jnp.int32)
    idx = jnp.clip(rs[wide] + lane, 0, rows - 1)
    mine = live & (sl == wide) & (n_wide > 0)
    return wide, n_wide, fresh[wide], idx, lane < n_wide, mine, off


def _pad_width(ragged_meta):
    return -(-int(ragged_meta[5].shape[0]) // SUB) * SUB


def _xla_chunk(q, k, v, g, beta, state, ragged_meta):
    r = q.shape[0]
    w_pad = _pad_width(ragged_meta)
    wide, n_wide, fresh, idx, valid, mine, off = _chunk_layout(
        ragged_meta, r, w_pad)
    # [H, W, D]; a row past the slot's last is the identity: no decay,
    # beta 0
    qs, ks, vs, gs = (_slab(x, idx, valid) for x in (q, k, v, g))
    bs = _slab(beta, idx, valid)                            # [H, W]
    s0 = state[wide]
    s = jnp.swapaxes(jnp.where(fresh, 0.0, s0), -1, -2)    # S [d_k, d_v]
    tri = jnp.tril(jnp.ones((SUB, SUB), bool))
    outs = []
    for c in range(w_pad // SUB):
        rows = slice(c * SUB, (c + 1) * SUB)
        qc, kc, vc, bc = qs[:, rows], ks[:, rows], vs[:, rows], bs[:, rows]
        b = jnp.cumsum(gs[:, rows], axis=1)                 # [H, C, D]
        diff = b[:, :, None, :] - b[:, None, :, :]          # [H, i, j, D]
        e = jnp.exp(jnp.where(tri[None, :, :, None], diff, -jnp.inf))
        a = jnp.sum(kc[:, :, None] * kc[:, None] * e, axis=-1)
        p = jnp.sum(qc[:, :, None] * kc[:, None] * e, axis=-1)
        low = bc[:, :, None] * jnp.where(tri & ~tri.T, a, 0.0)
        eb = jnp.exp(b)
        rhs = bc[..., None] * (vc - jnp.einsum(
            "hck,hkv->hcv", kc * eb, s, precision=_HI))
        u = jax.scipy.linalg.solve_triangular(
            low + jnp.eye(SUB, dtype=_F32), rhs, lower=True,
            unit_diagonal=True)
        outs.append(jnp.einsum("hck,hkv->hcv", qc * eb, s, precision=_HI)
                    + jnp.einsum("hij,hjv->hiv", p, u, precision=_HI))
        last = b[:, -1:, :]
        s = jnp.exp(last[:, 0])[..., None] * s + jnp.einsum(
            "hck,hcv->hkv", kc * jnp.exp(last - b), u, precision=_HI)
    o = jnp.concatenate(outs, axis=1)                       # [H, W, D]
    state = state.at[wide].set(
        jnp.where(n_wide > 0, jnp.swapaxes(s, -1, -2), s0))
    rows_o = jnp.swapaxes(o, 0, 1)[jnp.clip(off, 0, w_pad - 1)]
    return jnp.where(mine[:, None, None], rows_o, 0.0), state


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _lane_sums(x, ones):
    """``sum(x, axis=1)`` of a float32 ``[rows, 128]`` tile, REPLICATED
    over the 128 lanes, on the MXU: ``x`` split exactly into three
    bfloat16 parts, each multiplied with a matrix of ones at one pass
    and accumulated in float32 (the lane reduction and the lane
    broadcast that the vector unit would do a step a lane-tile)."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(_F32)
    mid = rest.astype(jnp.bfloat16)
    low = (rest - mid.astype(_F32)).astype(jnp.bfloat16)
    return (jnp.dot(hi, ones, preferred_element_type=_F32)
            + jnp.dot(mid, ones, preferred_element_type=_F32)
            + jnp.dot(low, ones, preferred_element_type=_F32))


def _recurrent_kernel(seat_ref, row_ref, nlive_ref, k_ref, a_ref, q_ref,
                      v_ref, b_ref, s_ref, so_ref, o_ref, *, heads):
    del seat_ref, row_ref
    i = pl.program_id(0)
    n = nlive_ref[0]
    d = s_ref.shape[-1]

    @pl.when(i < n)
    def _advance():
        ones = jnp.ones((d, d), jnp.bfloat16)
        eye = jax.lax.broadcasted_iota(jnp.int32, (d, d), 0) \
            == jax.lax.broadcasted_iota(jnp.int32, (d, d), 1)
        for h in range(heads):
            # S^T [d_v, d_k]: the key channels along the lanes, so k,
            # alpha and q multiply as rows; what is summed over them
            # comes back from the MXU on every lane
            k_row = k_ref[0, h:h + 1, :]
            st = s_ref[0, h] * a_ref[0, h:h + 1, :]
            miss = _lane_sums(jnp.where(
                eye, st * k_row - v_ref[0, h:h + 1, :], st * k_row), ones)
            st = st - (b_ref[0, h:h + 1, :] * miss) * k_row
            so_ref[0, h] = st
            out = _lane_sums(st * q_ref[0, h:h + 1, :], ones)
            o_ref[0, h:h + 1, :] = jnp.sum(jnp.where(eye, out, 0.0),
                                           axis=0, keepdims=True)

    # the steps past the live seats all name the null seat: its block
    # stays in VMEM across them, so one copy serves them all
    @pl.when(i == n)
    def _pass():
        so_ref[...] = s_ref[...]


def pallas_kda_recurrent(q, k, v, g, beta, state, ragged_meta,
                         interpret=None):
    """``kda_recurrent`` as a Mosaic kernel: ``q, k, v, g [R, H, 128]``
    float32, ``beta [R, H]``, ``state [S + 1, H, 128, 128]`` float32,
    value-major (donate it: written in place). Returns ``(o [R, H, 128] — zeros on
    rows that are not a one-row seat's — , state)``."""
    r, h, d = q.shape
    ql, rs, sl, fresh, _off, live = _seats(ragged_meta, r)
    n = ql.shape[0]
    one = ql == 1
    order = jnp.argsort(~one, stable=True).astype(jnp.int32)
    n_live = jnp.sum(one).astype(jnp.int32)
    step = jnp.arange(n, dtype=jnp.int32)
    on = step < n_live
    seat = jnp.where(on, order, n)
    row = jnp.where(on, jnp.minimum(rs[order], r - 1), 0)
    # a seat whose row is position 0 starts from zeros: its decay is 0
    # (what the last occupant left is finite)
    alpha = jnp.where((fresh[sl] & live)[:, None, None], 0.0,
                      jnp.exp(g.astype(_F32)))
    b_rows = jnp.broadcast_to(beta.astype(_F32)[..., None], (r, h, d))

    def by_row(i, seat_r, row_r, *_):
        return (row_r[i], 0, 0)

    def by_seat(i, seat_r, *_):
        return (seat_r[i], 0, 0, 0)

    flat = pl.BlockSpec((1, h, d), by_row)
    table = pl.BlockSpec((1, h, d, d), by_seat)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(n,),
        in_specs=[flat] * 5 + [table],
        out_specs=[table,
                   pl.BlockSpec((1, h, d), lambda i, *_: (i, 0, 0))],
    )
    call = pl.pallas_call(
        functools.partial(_recurrent_kernel, heads=h),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((n, h, d), _F32)],
        # operands count the scalar prefetch: the table is the ninth
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret() if interpret is None else interpret,
    )
    with kernel_scope("kda_recurrent"):
        state, o = call(seat, row, n_live[None], k.astype(_F32), alpha,
                        q.astype(_F32), v.astype(_F32), b_rows, state)
    rank = jnp.zeros((n,), jnp.int32).at[order].set(step)
    out = jnp.where((one[sl] & live)[:, None, None], o[rank[sl]], 0.0)
    return out, state


def _nt(a, b):
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=_HI, preferred_element_type=_F32)


def _chunk_kernel(seat_ref, fresh_ref, nsub_ref, q_ref, k_ref, kb_ref,
                  vb_ref, b_ref, s_ref, so_ref, o_ref, u_scr):
    del seat_ref
    so_ref[0, 0] = jnp.where(fresh_ref[0] == 1, 0.0, s_ref[0, 0])
    sub = jax.lax.broadcasted_iota(jnp.int32, (BLK, 1), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (2 * BLK, SUB), 1)
    d = s_ref.shape[-1]

    def one_sub(c, carry):
        r0 = pl.multiple_of(c * SUB, SUB)
        rows = pl.ds(r0, SUB)
        b = b_ref[0, rows, :]
        kk, kb, qq = k_ref[0, rows, :], kb_ref[0, rows, :], q_ref[0, rows, :]
        s0 = so_ref[0, 0]                       # S^T [d_v, d_k]
        e = jnp.exp(b)
        m = _nt(jnp.concatenate([kb * e, qq * e], axis=0), s0)
        rhs = vb_ref[0, rows, :] - m[:SUB]
        inter = m[SUB:]
        u_scr[...] = jnp.zeros_like(u_scr)
        for blk in range(SUB // BLK):
            lo = blk * BLK
            bb, kblk = b[lo:lo + BLK], kk[lo:lo + BLK]
            kbb, qb = kb[lo:lo + BLK], qq[lo:lo + BLK]
            rhs_b, o_b = rhs[lo:lo + BLK], inter[lo:lo + BLK]
            if blk:
                # the rows before this block, against the block's first
                # row: both exponents are <= 0
                ref = b[lo:lo + 1]
                dec = jnp.exp(bb - ref)
                g = _nt(jnp.concatenate([kbb * dec, qb * dec], axis=0),
                        kk * jnp.exp(jnp.minimum(ref - b, 0.0)))
                gu = jnp.dot(jnp.where(col < lo, g, 0.0), u_scr[...],
                             precision=_HI, preferred_element_type=_F32)
                rhs_b = rhs_b - gu[:BLK]
                o_b = o_b + gu[BLK:]
            u_b = jnp.zeros((BLK, d), _F32)
            out_b = jnp.zeros((BLK, d), _F32)
            for i in range(BLK):
                # row i against the block's rows j <= i
                t = kblk * jnp.exp(jnp.minimum(bb[i:i + 1] - bb, 0.0))
                ca = jnp.sum(t * kbb[i:i + 1], axis=1, keepdims=True)
                u_i = rhs_b[i:i + 1] - jnp.sum(
                    jnp.where(sub < i, ca, 0.0) * u_b, axis=0,
                    keepdims=True)
                u_b = jnp.where(sub == i, u_i, u_b)
                cp = jnp.sum(t * qb[i:i + 1], axis=1, keepdims=True)
                o_i = o_b[i:i + 1] + jnp.sum(
                    jnp.where(sub <= i, cp, 0.0) * u_b, axis=0,
                    keepdims=True)
                out_b = jnp.where(sub == i, o_i, out_b)
            u_scr[lo:lo + BLK, :] = u_b
            o_ref[0, pl.ds(pl.multiple_of(r0 + lo, BLK), BLK), :] = out_b
        last = b[SUB - 1:SUB]
        so_ref[0, 0] = jnp.exp(last) * s0 + jax.lax.dot_general(
            u_scr[...], kk * jnp.exp(last - b), (((0,), (0,)), ((), ())),
            precision=_HI, preferred_element_type=_F32)
        return carry

    jax.lax.fori_loop(0, nsub_ref[0], one_sub, 0)


def pallas_kda_chunk(q, k, v, g, beta, state, ragged_meta, interpret=None):
    """``kda_chunk`` as a Mosaic kernel over the one wide slot's rows;
    shapes as :func:`pallas_kda_recurrent`. Returns ``(o [R, H, 128] —
    zeros on every other slot's rows — , state)``."""
    r, h, d = q.shape
    n = state.shape[0] - 1
    w_pad = _pad_width(ragged_meta)
    wide, n_wide, fresh, idx, valid, mine, off = _chunk_layout(
        ragged_meta, r, w_pad)
    qs, ks, vs, gs = (_slab(x, idx, valid) for x in (q, k, v, g))
    bs = _slab(beta, idx, valid)[..., None]
    cum = jnp.cumsum(gs.reshape(h, w_pad // SUB, SUB, d),
                     axis=2).reshape(h, w_pad, d)
    seat = jnp.where(n_wide > 0, wide, n)
    n_sub = (n_wide + SUB - 1) // SUB

    def by_head(hh, *_):
        return (hh, 0, 0)

    def by_seat(hh, seat_r, *_):
        return (seat_r[0], hh, 0, 0)

    slab = pl.BlockSpec((1, w_pad, d), by_head)
    table = pl.BlockSpec((1, 1, d, d), by_seat)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(h,),
        in_specs=[slab] * 5 + [table],
        out_specs=[table, slab],
        scratch_shapes=[pltpu.VMEM((SUB, d), _F32)],
    )
    call = pl.pallas_call(
        _chunk_kernel,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((h, w_pad, d), _F32)],
        input_output_aliases={8: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=_interpret() if interpret is None else interpret,
    )
    with kernel_scope("kda_chunk"):
        state, o = call(seat[None], (fresh & (n_wide > 0)).astype(
            jnp.int32)[None], n_sub.astype(jnp.int32)[None],
            qs, ks, bs * ks, bs * vs, cum, state)
    rows_o = jnp.swapaxes(o, 0, 1)[jnp.clip(off, 0, w_pad - 1)]
    return jnp.where(mine[:, None, None], rows_o, 0.0), state


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def _use_kernel(kind, q, state) -> bool:
    on_tpu = jax.default_backend() == "tpu"
    ok = (q.shape[-1] == 128 and state.shape[-2:] == (128, 128)
          and state.dtype == jnp.float32)
    if (on_tpu or _force_kernel_routing()) and ok:
        return True
    if on_tpu:
        count_fallback(kind)
    return False


def kda_recurrent(q, k, v, g, beta, state, ragged_meta):
    """Advance every one-row seat's state by its row (module docstring):
    ``(o [R, H, d_v] float32, state)``."""
    if _use_kernel("kda_recurrent", q, state):
        return pallas_kda_recurrent(q, k, v, g, beta, state, ragged_meta)
    return _xla_recurrent(q, k, v, g, beta, state, ragged_meta)


def kda_chunk(q, k, v, g, beta, state, ragged_meta):
    """Advance the one wide slot's state by its rows, chunkwise (module
    docstring): ``(o [R, H, d_v] float32, state)``."""
    if _use_kernel("kda_chunk", q, state):
        return pallas_kda_chunk(q, k, v, g, beta, state, ragged_meta)
    return _xla_chunk(q, k, v, g, beta, state, ragged_meta)


def kda_step(q, k, v, g, beta, state, ragged_meta):
    """One tick of the delta rule over the packed rows: the wide slot's
    chunk, then the one-row seats. ``q [R, H, d_k]`` (l2-normed and
    scaled), ``k [R, H, d_k]`` (l2-normed), ``v [R, H, d_v]``, ``g [R,
    H, d_k]`` (the log-decay, <= 0), ``beta [R, H]``; ``state [S + 1,
    H, d_v, d_k]`` float32 (value-major). Returns ``(o [R, H, d_v] float32, state)``;
    rows no slot owns read zeros."""
    o_c, state = kda_chunk(q, k, v, g, beta, state, ragged_meta)
    o_r, state = kda_recurrent(q, k, v, g, beta, state, ragged_meta)
    return o_c + o_r, state

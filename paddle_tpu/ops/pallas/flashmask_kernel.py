"""FlashMask compact-form Pallas kernels.

Reference parity: ``paddle.nn.functional.flashmask_attention`` backed by
the FlashMask sparse-mask kernels (``paddle/phi/kernels/gpu/
flash_attn_kernel.cu`` + the bundled flashattn FlashMask extension,
SURVEY.md §5.7.4). The whole point of FlashMask is that the mask is
O(L) column bounds, never an O(L²) bias — these kernels consume the
``startend_row_indices`` compact form directly:

- Per key column ``j`` the mask is one row interval ``[start_j, end_j)``
  (plus the causal triangle when ``causal=True``). The column bounds ride
  into the kernel as two ``[B*Hm, L]`` int32 arrays blocked ``(1, bk)``.
- Block skip: a kv block whose every column masks the whole query block
  (``max(start) <= q_first and min(end) > q_last``), or that lies above
  the causal diagonal, is predicated off with ``pl.when`` — its MXU work
  never executes. On document-causal masks this recovers the
  block-sparsity FlashMask exists for.
- Fully-masked ROWS are representable here (unlike plain causal), so
  every ``exp`` carries a mask guard: a block whose entries are all
  ``-inf`` would otherwise normalize ``exp(-inf - -inf) = 1``.

Layouts and GQA head-group routing are shared with
``flash_attention_kernel`` (q: [B*H, L, D]; bounds heads ``Hm`` may be
1, Hkv, or H — any divisor of H).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention_kernel import (DEFAULT_BLOCK_K, DEFAULT_BLOCK_Q,
                                     NEG_INF, _block_sizes, _interpret,
                                     _kv_row, kernel_scope)


def _mask_block(s, start, end, qi, ki, block_q, block_k, causal):
    """Apply the column-interval (+ causal) mask to one score block."""
    rows = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    masked = jnp.logical_and(rows >= start[None, :],
                             rows < end[None, :])
    if causal:
        cols = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, block_k), 1)
        masked = jnp.logical_or(masked, cols > rows)
    return jnp.where(masked, NEG_INF, s)


def _block_live(start, end, qi, ki, block_q, block_k, causal):
    """False when the whole (q block, kv block) tile is masked."""
    q_first = qi * block_q
    q_last = q_first + block_q - 1
    # every column masks the whole q block?
    dead_fm = jnp.logical_and(jnp.max(start) <= q_first,
                              jnp.min(end) > q_last)
    live = jnp.logical_not(dead_fm)
    if causal:
        live = jnp.logical_and(live, ki * block_k <= q_last)
    return live


def _fm_fwd_kernel(q_ref, k_ref, v_ref, start_ref, end_ref, o_ref,
                   lse_ref, m_scr, l_scr, acc_scr, *, scale, causal,
                   block_q, block_k, n_kv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    start = start_ref[0, 0]
    end = end_ref[0, 0]

    @pl.when(_block_live(start, end, qi, ki, block_q, block_k, causal))
    def _compute():
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _mask_block(s, start, end, qi, ki, block_q, block_k, causal)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # guard: in an all-masked block m_cur == -inf and the bare
        # exp(s - m_cur) would be 1 for every masked entry
        p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - m_cur))
        alpha = jnp.exp(m_prev - m_cur)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v_ref.dtype), v_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[:] = alpha * acc_scr[:] + pv
        m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ki == n_kv - 1)
    def _finish():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, np.float32(1.0), l)
        o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)
        lse = m_scr[:, :1] + jnp.log(safe_l)
        lse_ref[0, 0] = jnp.where(l[:, 0] == 0.0, NEG_INF, lse[:, 0])


def _fm_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                  start_ref, end_ref, dq_ref, dq_scr, *, scale, causal,
                  block_q, block_k, n_kv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    start = start_ref[0, 0]
    end = end_ref[0, 0]

    @pl.when(_block_live(start, end, qi, ki, block_q, block_k, causal))
    def _compute():
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _mask_block(s, start, end, qi, ki, block_q, block_k, causal)
        # lse of a fully-masked row is -inf: guard like the forward
        p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - lse))
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(k_ref.dtype)
        dq_scr[:] += jax.lax.dot_general(
            ds, k_ref[0], (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == n_kv - 1)
    def _finish():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _fm_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   start_ref, end_ref, dk_ref, dv_ref, dk_scr, dv_scr,
                   *, scale, causal, block_q, block_k, n_q, n_t):
    ki = pl.program_id(1)
    ti = pl.program_id(2)
    qi = ti % n_q

    @pl.when(ti == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    start = start_ref[0, 0]
    end = end_ref[0, 0]

    @pl.when(_block_live(start, end, qi, ki, block_q, block_k, causal))
    def _compute():
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = _mask_block(s, start, end, qi, ki, block_q, block_k, causal)
        p = jnp.where(s <= NEG_INF * 0.5, 0.0, jnp.exp(s - lse))
        pb = p.astype(do_ref.dtype)
        dv_scr[:] += jax.lax.dot_general(
            pb, do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q_ref.dtype)
        dk_scr[:] += jax.lax.dot_general(
            ds, q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ti == n_t - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _fm_fwd(q, k, v, start, end, scale, causal, block_q, block_k,
            h, h_kv, h_m):
    """q: [B*H, L, D]; k/v: [B*Hkv, L, D]; start/end: [B*Hm, 1, L]."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    bq, bk = _block_sizes(lq, block_q, block_k)
    bk = _block_sizes(lk, block_q, bk)[1]
    n_q = lq // bq
    n_kv = lk // bk

    call = pl.pallas_call(
        functools.partial(_fm_fwd_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_kv=n_kv),
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda b, i, j: (_kv_row(b, h, h_kv), j, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda b, i, j: (_kv_row(b, h, h_kv), j, 0)),
            pl.BlockSpec((1, 1, bk),
                         lambda b, i, j: (_kv_row(b, h, h_m), 0, j)),
            pl.BlockSpec((1, 1, bk),
                         lambda b, i, j: (_kv_row(b, h, h_m), 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, lq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, 128), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )
    with kernel_scope("flashmask_attention_fwd"):
        o, lse = call(q, k, v, start, end)
    return o, lse


def _fm_bwd(scale, causal, block_q, block_k, h, h_kv, h_m, res, do):
    q, k, v, start, end, o, lse = res
    bh, lq, d = q.shape
    bhkv = k.shape[0]
    lk = k.shape[1]
    bq, bk = _block_sizes(lq, block_q, block_k)
    bk = _block_sizes(lk, block_q, bk)[1]
    n_q = lq // bq
    n_kv = lk // bk
    group = h // h_kv

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]

    dq_call = pl.pallas_call(
        functools.partial(_fm_dq_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_kv=n_kv),
        grid=(bh, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda b, i, j: (_kv_row(b, h, h_kv), j, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda b, i, j: (_kv_row(b, h, h_kv), j, 0)),
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, bq), lambda b, i, j: (b, 0, i)),
            pl.BlockSpec((1, 1, bk),
                         lambda b, i, j: (_kv_row(b, h, h_m), 0, j)),
            pl.BlockSpec((1, 1, bk),
                         lambda b, i, j: (_kv_row(b, h, h_m), 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )
    with kernel_scope("flashmask_attention_dq"):
        dq = dq_call(q, k, v, do, lse, delta, start, end)

    n_t = group * n_q

    def _q_row(b, t):
        return (b // h_kv) * h + (b % h_kv) * group + t // n_q

    def _m_row(b, t):
        # bounds row for the QUERY head this grid step processes (with
        # Hm > Hkv, different query heads of one kv group carry
        # different masks — the kv head alone does not determine it)
        q_head = (b % h_kv) * group + t // n_q
        m_head = q_head // (h // h_m)
        return (b // h_kv) * h_m + m_head

    dkv_call = pl.pallas_call(
        functools.partial(_fm_dkv_kernel, scale=scale, causal=causal,
                          block_q=bq, block_k=bk, n_q=n_q, n_t=n_t),
        grid=(bhkv, n_kv, n_t),
        in_specs=[
            pl.BlockSpec((1, bq, d),
                         lambda b, j, t: (_q_row(b, t), t % n_q, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bq, d),
                         lambda b, j, t: (_q_row(b, t), t % n_q, 0)),
            pl.BlockSpec((1, 1, bq),
                         lambda b, j, t: (_q_row(b, t), 0, t % n_q)),
            pl.BlockSpec((1, 1, bq),
                         lambda b, j, t: (_q_row(b, t), 0, t % n_q)),
            pl.BlockSpec((1, 1, bk), lambda b, j, t: (_m_row(b, t), 0, j)),
            pl.BlockSpec((1, 1, bk), lambda b, j, t: (_m_row(b, t), 0, j)),
        ],
        out_specs=[
            pl.BlockSpec((1, bk, d), lambda b, j, t: (b, j, 0)),
            pl.BlockSpec((1, bk, d), lambda b, j, t: (b, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bhkv, lk, d), k.dtype),
            jax.ShapeDtypeStruct((bhkv, lk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, d), jnp.float32),
            pltpu.VMEM((bk, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )
    with kernel_scope("flashmask_attention_dkv"):
        dk, dv = dkv_call(q, k, v, do, lse, delta, start, end)
    return dq, dk, dv, None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _fm_bhld(q, k, v, start, end, scale, causal, block_q, block_k,
             h, h_kv, h_m):
    o, _ = _fm_fwd(q, k, v, start, end, scale, causal, block_q,
                   block_k, h, h_kv, h_m)
    return o


def _fm_fwd_rule(q, k, v, start, end, scale, causal, block_q, block_k,
                 h, h_kv, h_m):
    o, lse = _fm_fwd(q, k, v, start, end, scale, causal, block_q,
                     block_k, h, h_kv, h_m)
    return o, (q, k, v, start, end, o, lse)


def _fm_bwd_rule(scale, causal, block_q, block_k, h, h_kv, h_m, res,
                 do):
    return _fm_bwd(scale, causal, block_q, block_k, h, h_kv, h_m, res,
                   do)


_fm_bhld.defvjp(_fm_fwd_rule, _fm_bwd_rule)


def pallas_flashmask_attention(q, k, v, startend_row_indices,
                               causal=False, sm_scale=None,
                               block_q=DEFAULT_BLOCK_Q,
                               block_k=DEFAULT_BLOCK_K):
    """FlashMask attention over [B, L, H, D] with the O(L) compact mask.

    startend_row_indices: [B, Hm, L, bounds] int32, bounds in {1, 2}:
    per key column j the masked query rows are [start_j, L) (bounds=1)
    or [start_j, end_j) (bounds=2); ``causal=True`` additionally masks
    above the diagonal. Hm must divide the query head count (1, Hkv and
    H all qualify). K/V may carry grouped (GQA) heads.
    """
    b, lq, h, d = q.shape
    lk = k.shape[1]
    h_kv = k.shape[2]
    idx = startend_row_indices
    h_m = idx.shape[1]
    if h % h_kv or h % h_m:
        raise ValueError(
            f"head counts must divide: q={h}, kv={h_kv}, mask={h_m}")
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    start = idx[..., 0].astype(jnp.int32).reshape(b * h_m, 1, lk)
    if idx.shape[-1] >= 2:
        end = idx[..., 1].astype(jnp.int32).reshape(b * h_m, 1, lk)
    else:
        end = jnp.full((b * h_m, 1, lk), lq, jnp.int32)

    def fold(x, l, heads):
        return x.transpose(0, 2, 1, 3).reshape(b * heads, l, x.shape[-1])
    o = _fm_bhld(fold(q, lq, h), fold(k, lk, h_kv), fold(v, lk, h_kv),
                 start, end, float(sm_scale), bool(causal),
                 int(block_q), int(block_k), int(h), int(h_kv),
                 int(h_m))
    return o.reshape(b, h, lq, d).transpose(0, 2, 1, 3)

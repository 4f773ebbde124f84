"""Pallas TPU flash attention: blocked online-softmax, VMEM tiling,
causal block skip, forward + backward kernels.

Reference parity: the bundled FlashAttention-2 CUDA kernels the reference
wraps (``paddle/phi/kernels/gpu/flash_attn_kernel.cu`` +
``third_party/flashattn``). TPU-first design (splash-attention pattern,
``/opt/skills/guides/pallas_guide.md``):

- Grid ``(batch*heads, q_blocks, kv_blocks)`` with the kv dimension
  innermost and sequential ("arbitrary"), accumulating the online-softmax
  state (running max ``m``, denominator ``l``, weighted values ``acc``)
  in VMEM scratch across kv steps — one HBM pass over K/V per q block.
- Matmuls hit the MXU at ``preferred_element_type=float32``; the
  probability block is cast back to the input dtype for the second MXU
  contraction (FlashAttention-2's bf16 recipe).
- Causal skip: fully-masked kv blocks are predicated off with
  ``pl.when`` so their FLOPs never execute; the diagonal block applies
  the triangular mask elementwise.
- Backward is the standard two-kernel FA-2 scheme: a dq pass gridded
  like the forward and a dk/dv pass gridded kv-major, both re-reading
  the saved row log-sum-exp instead of materializing L×L probabilities.
  ``delta = rowsum(dO * O)`` is precomputed with one XLA fusion.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# measured on v5e fwd+bwd with the GQA-native kernels: at [4, 2048,
# 16/8, 64] (1024, 1024) 4.72 ms vs (512, 1024) 5.76 / (512, 512)
# 6.32; at the 8B shape [2, 4096, 32/8, 64] (1024, 1024) also wins
# (14.3 vs 14.8). jax's stock flash kernel: 21.2 ms at the first shape
DEFAULT_BLOCK_Q = 1024
DEFAULT_BLOCK_K = 1024


@contextlib.contextmanager
def kernel_scope(name):
    """What EVERY ``pallas_call`` of this package is invoked under.

    - ``jax.named_scope(name)``: the stable name the compiled program's
      op metadata and a profiler trace carry for the kernel —
      ``monitor.kernel_census`` reads it back out of
      ``compiled.as_text()`` (XLA keeps no other kernel name).
    - 32-bit trace mode: the framework runs with jax_enable_x64 on;
      tracing a kernel (or its index maps) in that mode lets weak-f64 /
      i64 constants leak in, and Mosaic cannot legalize the resulting
      f64->f32 truncf."""
    with jax.named_scope(name), jax.enable_x64(False):
        yield


# strongly-typed f32 scalar: under jax_enable_x64 (which the framework
# turns on) a bare Python float traces as a weak f64 constant and the
# resulting f64->f32 tpu.truncf cannot be legalized by Mosaic
NEG_INF = np.float32(-1e30)


def _block_sizes(seq_len, block_q, block_k):
    bq = min(block_q, seq_len)
    bk = min(block_k, seq_len)
    while seq_len % bq:
        bq //= 2
    while seq_len % bk:
        bk //= 2
    return max(bq, 1), max(bk, 1)


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *, scale, causal, block_q, block_k,
                n_kv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal block skip: kv block strictly above the q block's last row
    # contributes nothing — predicate off all its compute
    q_last = (qi + 1) * block_q - 1
    k_first = ki * block_k
    live = jnp.logical_or(not causal, k_first <= q_last)

    @pl.when(live)
    def _compute():
        # FA-2 dtype recipe: dots take the INPUT dtype (bf16 hits the
        # MXU at full rate; an fp32 upcast before the dot runs the MXU
        # ~8x slower on v5e) and accumulate f32 via
        # preferred_element_type
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale

        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)

        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_cur)
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


def _kv_row(b, h, h_kv):
    """Grid row over [B*H] -> row in the [B*Hkv] folded K/V array (GQA:
    query head h maps to kv head h // (H / Hkv))."""
    group = h // h_kv
    return (b // h) * h_kv + (b % h) // group


def _fwd(q, k, v, scale, causal, block_q, block_k, h, h_kv):
    """q: [B*H, L, D], k/v: [B*Hkv, L, D] (GQA-native: kv heads are NOT
    pre-repeated; the BlockSpec index map routes each query head to its
    kv group, so grouped K/V are fetched once per group instead of once
    per query head) → (o [B*H, L, D], lse [B*H, L])."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    bq, bk = _block_sizes(lq, block_q, block_k)
    bk = _block_sizes(lk, block_q, bk)[1]
    n_q = lq // bq
    n_kv = lk // bk

    kernel = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, block_q=bq, block_k=bk,
        n_kv=n_kv)
    grid = (bh, n_q, n_kv)
    call = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda b, i, j: (_kv_row(b, h, h_kv), j, 0)),
            pl.BlockSpec((1, bk, d),
                         lambda b, i, j: (_kv_row(b, h, h_kv), j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
            # row stats ride as [BH, 1, L]: a (1, bq) block over
            # [BH, L] violates the (8, 128) tile rule, while the
            # (1, 1, bq) block's last two dims are (full dim, 128-mult)
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
    with kernel_scope("flash_attention_fwd"):
        o, lse = call(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_scr, *, scale, causal, block_q, block_k, n_kv):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    q_last = (qi + 1) * block_q - 1
    k_first = ki * block_k
    live = jnp.logical_or(not causal, k_first <= q_last)

    @pl.when(live)
    def _compute():
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]

        # bf16 dot inputs, f32 accumulation (see forward kernel note)
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        p = jnp.exp(s - lse)
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


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *, scale, causal,
                block_q, block_k, n_q, n_t):
    ki = pl.program_id(1)
    ti = pl.program_id(2)       # flattened (query-head-in-group, qi)
    qi = ti % n_q

    @pl.when(ti == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    q_last = (qi + 1) * block_q - 1
    k_first = ki * block_k
    live = jnp.logical_or(not causal, k_first <= q_last)

    @pl.when(live)
    def _compute():
        lse = lse_ref[0, 0][:, None]
        delta = delta_ref[0, 0][:, None]

        # bf16 dot inputs, f32 accumulation (see forward kernel note)
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(cols <= rows, s, NEG_INF)
        p = jnp.exp(s - lse)
        pb = p.astype(do_ref.dtype)
        # dv += p^T @ dO
        dv_scr[:] += jax.lax.dot_general(
            pb, do_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q_ref.dtype)
        # dk += ds^T @ q
        dk_scr[:] += jax.lax.dot_general(
            ds, q_ref[0], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ti == n_t - 1)
    def _finish():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd(scale, causal, block_q, block_k, h, h_kv, res, do):
    q, k, v, o, lse = res
    bh, lq, d = q.shape
    bhkv = k.shape[0]
    lk = k.shape[1]
    bq, bk = _block_sizes(lq, block_q, block_k)
    bk = _block_sizes(lk, block_q, bk)[1]
    n_q = lq // bq
    n_kv = lk // bk
    group = h // h_kv

    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)[:, None, :]  # [BH, 1, L] (tile rule)

    dq_call = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
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
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
    )
    with kernel_scope("flash_attention_dq"):
        dq = dq_call(q, k, v, do, lse, delta)

    # dk/dv grid rides the [B*Hkv] kv rows; the innermost dim flattens
    # (query-head-in-group, q_block) so one scratch accumulates the
    # whole group's contribution before writing dk/dv once
    n_t = group * n_q

    def _q_row(b, t):
        return (b // h_kv) * h + (b % h_kv) * group + t // n_q

    dkv_call = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
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
    with kernel_scope("flash_attention_dkv"):
        dk, dv = dkv_call(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

_FORCE_INTERPRET = False  # tests flip this to run the kernel on CPU


def _interpret() -> bool:
    """Interpreted only when a test asks or on the CPU backend; a
    backend query that raises propagates."""
    return _FORCE_INTERPRET or jax.default_backend() == "cpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash_bhld(q, k, v, scale, causal, block_q, block_k, h, h_kv):
    o, _ = _fwd(q, k, v, scale, causal, block_q, block_k, h, h_kv)
    return o


def _flash_fwd_rule(q, k, v, scale, causal, block_q, block_k, h, h_kv):
    o, lse = _fwd(q, k, v, scale, causal, block_q, block_k, h, h_kv)
    return o, (q, k, v, o, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, h, h_kv, res, do):
    return _bwd(scale, causal, block_q, block_k, h, h_kv, res, do)


_flash_bhld.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def pallas_flash_attention(q, k, v, causal=False, sm_scale=None,
                           block_q=DEFAULT_BLOCK_Q,
                           block_k=DEFAULT_BLOCK_K):
    """Flash attention over Paddle's flash-attn layout [B, L, H, D].
    GQA-native: K/V may carry fewer heads (H % H_kv == 0); each query
    head reads its kv group's blocks directly via the BlockSpec index
    map, so grouped K/V are never materialized at the query head count.
    Differentiable (custom VJP above)."""
    b, lq, h, d = q.shape
    lk = k.shape[1]
    h_kv = k.shape[2]
    if h % h_kv:
        raise ValueError(
            f"query heads ({h}) must be a multiple of kv heads ({h_kv})")
    if sm_scale is None:
        sm_scale = 1.0 / np.sqrt(d)
    # [B, L, H, D] -> [B*H, L, D]
    def fold(x, l, heads):
        return x.transpose(0, 2, 1, 3).reshape(b * heads, l, x.shape[-1])
    o = _flash_bhld(fold(q, lq, h), fold(k, lk, h_kv), fold(v, lk, h_kv),
                    float(sm_scale), bool(causal), int(block_q),
                    int(block_k), int(h), int(h_kv))
    return o.reshape(b, h, lq, d).transpose(0, 2, 1, 3)

"""Fused decode-tick kernels: norm -> projection(s) and
projection -> residual-add, for the serving engine's ONE ragged
executable.

PR 7 collapsed the engine to one executable per tick, but INSIDE that
executable each decoder layer was still a chain of separate kernels —
norm, three QKV dots, attention, O-projection, norm, three MLP dots —
every boundary a launch + an HBM round-trip of the per-layer
activation. Per MPK ("Mega-Kernelizing Tensor Programs") and "Operator
Fusion in XLA" (PAPERS.md) those boundaries dominate small-batch
decode, which is bandwidth-bound: the activations are tiny
(``R x hidden`` for the packed ragged rows) but each kernel writes
them to HBM for the next kernel to read back. Two Pallas bodies close
all four boundaries the ROADMAP names:

- **``fused_norm_matmul``** — RMSNorm (Llama/Qwen2) or LayerNorm
  (GPT) fused into the prologue of 1..3 projections sharing the same
  normalized input (q/k/v, or the MLP's gate/up). The normalized
  activation lives in VMEM scratch and never round-trips HBM; the
  grid walks the CONCATENATED column tiles of all the weights, each
  weight's BlockSpec index map clamping outside its own tile range so
  Pallas's revisit-elision skips the dead DMAs (total weight traffic
  stays one pass over each weight).
- **``fused_matmul_residual``** — a projection with an optional
  activation prologue (``swiglu`` for Llama's down-projection,
  tanh-``gelu`` for GPT's second MLP linear, none for the
  O-projection) and the residual add in the epilogue: the attention
  output (or MLP hidden) goes MXU -> residual without touching HBM in
  between. The contraction is tiled (grid ``(col_tiles, k_tiles)``,
  f32 accumulator in VMEM), so the resident buffers do not grow with
  the FFN width.

Both reuse the ragged row layout by construction — they are row-wise
over the packed ``[R, hidden]`` buffer, so decode (1 row/slot),
speculative verify (gamma+1 rows) and chunked prefill (chunk rows)
widths ride one body exactly like the ragged attention kernel.

**Fallback contract.** Off TPU (or for kernel-ineligible shapes) each
entry point runs an XLA fallback that is BITWISE the unfused module
path: the same ``F.rms_norm``/``F.layer_norm`` recipe (f32
accumulation, cast to the activation dtype BEFORE the weight
multiply), the same ``x @ w + b`` dots in the same order, the same
``residual + y`` add. ``fused_decode=True`` on a CPU engine therefore
produces bit-identical executables to ``fused_decode=False`` — the
token-exactness tests pin this — while interpret mode
(``PADDLE_TPU_FUSED_DECODE=interpret``) runs the real kernels under
the Pallas interpreter so CPU tests and the bench census exercise the
fused graph end-to-end (the ``PADDLE_TPU_MOE_FUSED_GMM=interpret``
precedent).

**Gating.** The serving engine arms a thread-local scope
(``fused_decode_scope``) around every ``_compile_*`` trace — exactly
the ``serving_tp_scope`` pattern — so ``generate()``'s paged loop,
training forwards and other engines on other threads are never
rerouted. Inside a GSPMD tensor-parallel trace the scope reports
"off": an opaque ``pallas_call`` cannot be partitioned (the same gate
that keeps megablox/moe_gmm off TP serving traces), so TP engines keep
the unfused projections and GSPMD's sharding of them. Kill switch
``PADDLE_TPU_FUSED_DECODE=0`` beats an explicit
``ServingConfig(fused_decode=True)`` and restores today's graph
bit-for-bit. Layers with non-float projection weights (weight-only
int8 from ``quantize_for_inference``) fall back per layer.
"""
from __future__ import annotations

import contextlib
import functools
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention_kernel import kernel_scope

__all__ = ["resolve_fused_mode", "fused_decode_scope",
           "fused_decode_mode", "fused_params_ok", "norm_matmul",
           "matmul_residual", "fused_norm_matmul",
           "fused_matmul_residual", "pallas_norm_matmul",
           "pallas_matmul_residual"]

_COL_TILE = 128
# contraction tiles the projection->residual kernel walks, largest
# first (Qwen2-7B: 3584 = 7 x 512 and 18944 = 37 x 512)
_K_TILES = (2048, 1024, 512, 256, 128)
# column tiles of the projection->residual kernel, widest first: each
# column tile re-applies the activation along its whole K walk, so a
# wide tile cuts that recompute and the grid's step count
_RES_COL_TILES = (512, 256, 128)
# VMEM the kernels ask Mosaic for (its default grant is 16 MB of the
# v5e core's 128 MB) and the share of it ``_eligible`` lets the buffer
# estimate reach — the rest is the compiler's own temporaries
_VMEM_LIMIT = 32 << 20
_VMEM_BUDGET = 24 << 20


def _tile_count(n: int) -> int:
    """Column-tile count for an ``n``-wide projection: ~128-wide tiles
    when they divide evenly, else the largest divisor-friendly count
    (interpret mode accepts any width; real-TPU eligibility is gated
    stricter in ``_eligible``)."""
    t = max(n // _COL_TILE, 1)
    while n % t:
        t -= 1
    return t


def _k_tile(k: int) -> int:
    """Contraction tile for a ``k``-deep projection: the largest of
    ``_K_TILES`` dividing ``k``, else ``k`` whole (interpret-mode
    shapes; ``_eligible`` requires a 128-multiple on TPU)."""
    for t in _K_TILES:
        if k % t == 0:
            return t
    return k


def _res_col_tile(n: int) -> int:
    """Column tile of the projection->residual kernel: the widest of
    ``_RES_COL_TILES`` dividing ``n``, else ``_tile_count``'s."""
    for t in _RES_COL_TILES:
        if n % t == 0:
            return t
    return n // _tile_count(n)


# ---------------------------------------------------------------------------
# mode resolution + trace-time scope
# ---------------------------------------------------------------------------

def resolve_fused_mode(cfg_flag=True):
    """Resolve the fused-decode mode ONCE at engine construction:
    ``None`` (off), ``"kernel"`` (Pallas on TPU, bitwise-unfused XLA
    fallback elsewhere) or ``"interpret"`` (Pallas under the
    interpreter on any backend — CPU tests/bench exercise the fused
    graph). Env twin ``PADDLE_TPU_FUSED_DECODE``: ``0`` is the kill
    switch and beats an explicit config True; ``interpret`` forces
    interpret mode; unset/``1`` follows the config flag."""
    env = os.environ.get("PADDLE_TPU_FUSED_DECODE", "1")
    if env == "0":
        return None
    if env == "interpret":
        return "interpret"
    return "kernel" if cfg_flag else None


_SCOPE = threading.local()      # thread-scoped like serving_tp_scope


@contextlib.contextmanager
def fused_decode_scope(mode):
    """Arm the fused decode path for the duration of one trace (the
    engine's ``_trace_ctx`` enters this around every ``_compile_*``).
    ``mode`` None is a no-op arm, so call sites stay unconditional."""
    prev = getattr(_SCOPE, "mode", None)
    _SCOPE.mode = mode
    try:
        yield
    finally:
        _SCOPE.mode = prev


def fused_decode_mode():
    """The armed mode, or None outside a scope / inside a GSPMD
    tensor-parallel trace (an opaque pallas_call cannot be partitioned
    — the moe_gmm/megablox gate, applied here)."""
    mode = getattr(_SCOPE, "mode", None)
    if mode is None:
        return None
    from .paged_attention import serving_tp_active
    if serving_tp_active():
        return None
    return mode


def fused_params_ok(*params) -> bool:
    """True when every given parameter exists and is a plain float
    tensor — weight-only-quantized layers (int8 weights) keep the
    module path, whose quantized matmul the kernels don't speak."""
    from ...framework.core import as_jax
    for p in params:
        if p is None:
            continue
        if not jnp.issubdtype(as_jax(p).dtype, jnp.floating):
            return False
    return True


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _norm_mm_kernel(*refs, eps, kind, has_beta, nw, offs, tiles,
                    has_bias):
    """Grid ``(sum(tiles),)`` over the concatenated column tiles of
    all ``nw`` weights. Step 0 computes the normalized activation into
    VMEM scratch with the unfused norm's recipe and roundings (f32
    statistics, cast to the activation dtype BEFORE the weight
    multiply, activation-dtype result); every step contracts it
    against its weight's current column tile — dots take the
    activation dtype and accumulate f32 (FA-2's recipe: an f32 upcast
    before the dot runs the MXU several times slower)."""
    i = 2 + (1 if has_beta else 0)
    x_ref, g_ref = refs[0], refs[1]
    b_ref = refs[2] if has_beta else None
    w_refs = refs[i:i + nw]
    i += nw
    bias_refs = []
    for hb in has_bias:
        bias_refs.append(refs[i] if hb else None)
        i += 1 if hb else 0
    o_refs = refs[i:i + nw]
    y_scr = refs[i + nw]
    j = pl.program_id(0)

    @pl.when(j == 0)
    def _normalize():
        xf = x_ref[...].astype(jnp.float32)
        if kind == "rms":
            var = jnp.mean(xf * xf, axis=-1, keepdims=True)
            y = xf * jax.lax.rsqrt(var + eps)
        else:
            m = jnp.mean(xf, axis=-1, keepdims=True)
            var = jnp.mean((xf - m) * (xf - m), axis=-1, keepdims=True)
            y = (xf - m) * jax.lax.rsqrt(var + eps)
        # f32 products of activation-dtype operands are exact, so one
        # rounding here is the unfused ``y.astype(dt) * gamma``
        y = y.astype(y_scr.dtype).astype(jnp.float32) \
            * g_ref[...].astype(jnp.float32)
        if has_beta:
            y = y.astype(y_scr.dtype).astype(jnp.float32) \
                + b_ref[...].astype(jnp.float32)
        y_scr[...] = y.astype(y_scr.dtype)

    for idx in range(nw):
        @pl.when((j >= offs[idx]) & (j < offs[idx] + tiles[idx]))
        def _project(idx=idx):
            acc = jax.lax.dot_general(
                y_scr[...], w_refs[idx][...].astype(y_scr.dtype),
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if bias_refs[idx] is not None:
                acc = acc + bias_refs[idx][...].astype(jnp.float32)
            o_refs[idx][...] = acc.astype(o_refs[idx].dtype)


def _mm_res_kernel(*refs, act, has_bias, n_in, n_k):
    """Grid ``(col_tiles, k_tiles)``, contraction innermost. Every
    step applies the (elementwise, so K-tileable) activation to its
    input tile and accumulates the product with the weight tile in f32
    scratch; the last K step adds bias + residual tile and stores —
    the projection input and its residual sum never round-trip HBM,
    and no buffer grows with the contraction depth (Qwen2-7B's
    18944-deep down-projection fits the same VMEM as a 512-deep one).
    Dots take the activation dtype, accumulating f32."""
    x_refs = refs[:n_in]
    i = n_in
    w_ref = refs[i]
    i += 1
    b_ref = refs[i] if has_bias else None
    i += 1 if has_bias else 0
    res_ref = refs[i]
    o_ref = refs[i + 1]
    acc_scr = refs[i + 2]
    kk = pl.program_id(1)

    @pl.when(kk == 0)
    def _zero():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    dt = x_refs[0].dtype
    if act == "swiglu":
        # the module's order: silu in the activation dtype, then * up
        a = (jax.nn.silu(x_refs[0][...].astype(jnp.float32)).astype(dt)
             .astype(jnp.float32)
             * x_refs[1][...].astype(jnp.float32)).astype(dt)
    elif act == "gelu_tanh":
        a = jax.nn.gelu(x_refs[0][...].astype(jnp.float32),
                        approximate=True).astype(dt)
    else:
        a = x_refs[0][...]
    acc_scr[...] += jax.lax.dot_general(
        a, w_ref[...].astype(dt), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _store():
        acc = acc_scr[...]
        if b_ref is not None:
            acc = acc + b_ref[...].astype(jnp.float32)
        acc = acc + res_ref[...].astype(jnp.float32)
        o_ref[...] = acc.astype(o_ref.dtype)


def _row_vec(v):
    """``[n]`` parameter as a ``[1, n]`` operand: a rank-1 bf16 block
    must be a 256-multiple for Mosaic, a ``(1, 128k)`` block of a
    rank-2 array need not."""
    return v.reshape(1, -1)


def pallas_norm_matmul(x2, gamma, beta, ws, bs, *, eps, kind,
                       interpret=None):
    """x2: ``[R, d]`` packed rows; gamma/beta: ``[d]`` norm params
    (beta None for RMSNorm); ws: 1..3 weights ``[d, n_i]``; bs:
    matching biases ``[n_i]`` or None. Returns a tuple of
    ``[R, n_i]`` outputs. ``kind``: ``"rms" | "ln"``."""
    r, d = x2.shape
    nw = len(ws)
    widths = [w.shape[-1] for w in ws]
    tiles = [_tile_count(n) for n in widths]
    tcs = [n // t for n, t in zip(widths, tiles)]
    offs = [int(o) for o in np.cumsum([0] + tiles[:-1])]
    has_bias = [b is not None for b in bs]
    kernel = functools.partial(
        _norm_mm_kernel, eps=np.float32(eps), kind=kind,
        has_beta=beta is not None, nw=nw, offs=offs, tiles=tiles,
        has_bias=has_bias)

    def _w_map(off, t):
        # clamped outside the weight's own tile range: the block index
        # stops changing, so Pallas skips the dead DMAs
        return lambda j: (0, jnp.clip(j - off, 0, t - 1))

    whole = lambda j: (0, 0)
    in_specs = [pl.BlockSpec((r, d), whole),
                pl.BlockSpec((1, d), whole)]
    args = [x2, _row_vec(gamma)]
    if beta is not None:
        in_specs.append(pl.BlockSpec((1, d), whole))
        args.append(_row_vec(beta))
    for tc, off, t in zip(tcs, offs, tiles):
        in_specs.append(pl.BlockSpec((d, tc), _w_map(off, t)))
    args += list(ws)
    for b, tc, off, t in zip(bs, tcs, offs, tiles):
        if b is not None:
            in_specs.append(pl.BlockSpec((1, tc), _w_map(off, t)))
            args.append(_row_vec(b))
    out_specs = [pl.BlockSpec((r, tc), _w_map(off, t))
                 for tc, off, t in zip(tcs, offs, tiles)]
    out_shape = [jax.ShapeDtypeStruct((r, n), x2.dtype)
                 for n in widths]
    call = pl.pallas_call(
        kernel,
        grid=(int(sum(tiles)),),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((r, d), x2.dtype)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_flag(interpret),
    )
    with kernel_scope("fused_norm_matmul"):
        return tuple(call(*args))


def pallas_matmul_residual(xs, w, b, residual, *, act=None,
                           interpret=None):
    """xs: 1 (or 2, for swiglu) inputs ``[R, K]``; w: ``[K, n]``;
    b: ``[n]`` or None; residual: ``[R, n]``. Returns
    ``residual + act(xs) @ w (+ b)`` as ``[R, n]``."""
    r, kdim = xs[0].shape
    n = w.shape[-1]
    tc = _res_col_tile(n)
    tk = _k_tile(kdim)
    n_k = kdim // tk
    kernel = functools.partial(
        _mm_res_kernel, act=act, has_bias=b is not None,
        n_in=len(xs), n_k=n_k)
    col = lambda j, kk: (0, j)
    in_specs = [pl.BlockSpec((r, tk), lambda j, kk: (0, kk))
                for _ in xs]
    in_specs.append(pl.BlockSpec((tk, tc), lambda j, kk: (kk, j)))
    args = list(xs) + [w]
    if b is not None:
        in_specs.append(pl.BlockSpec((1, tc), col))
        args.append(_row_vec(b))
    in_specs.append(pl.BlockSpec((r, tc), col))
    args.append(residual)
    call = pl.pallas_call(
        kernel,
        grid=(n // tc, n_k),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((r, tc), col),
        out_shape=jax.ShapeDtypeStruct((r, n), residual.dtype),
        scratch_shapes=[pltpu.VMEM((r, tc), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret_flag(interpret),
    )
    with kernel_scope("fused_matmul_residual"):
        return call(*args)


def _interpret_flag(interpret):
    """An explicit request wins; otherwise interpreted only on the CPU
    backend (a backend query that raises propagates)."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# XLA fallbacks — bitwise the unfused module path
# ---------------------------------------------------------------------------

def _xla_norm_matmul(x, gamma, beta, ws, bs, *, eps, kind):
    """Bitwise the unfused path: exactly ``F.rms_norm``/
    ``F.layer_norm``'s recipe (f32 accumulation, cast to the
    activation dtype BEFORE the weight multiply) followed by each
    projection's ``x @ w (+ b)`` — same ops, same order, so a CPU
    engine with fusion ON compiles bit-identical executables to one
    with fusion OFF."""
    xf = x.astype(jnp.float32)
    if kind == "rms":
        var = jnp.mean(xf * xf, axis=-1, keepdims=True)
        y = (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    else:
        m = jnp.mean(xf, axis=-1, keepdims=True)
        v = jnp.var(xf, axis=-1, keepdims=True)
        y = ((xf - m) * jax.lax.rsqrt(v + eps)).astype(x.dtype)
    y = y * gamma
    if beta is not None:
        y = y + beta
    outs = []
    for w, b in zip(ws, bs):
        o = y @ w + b if b is not None else y @ w
        outs.append(o)
    return tuple(outs)


def _xla_matmul_residual(xs, w, b, residual, *, act=None):
    """Bitwise the unfused path: the module's activation (``swiglu`` =
    ``silu(g) * u``, tanh-``gelu``), the projection dot, bias, then
    ``residual + y`` in the decoder layer's order."""
    if act == "swiglu":
        xin = jax.nn.silu(xs[0]) * xs[1]
    elif act == "gelu_tanh":
        xin = jax.nn.gelu(xs[0], approximate=True)
    else:
        xin = xs[0]
    y = xin @ w + b if b is not None else xin @ w
    return residual + y


# ---------------------------------------------------------------------------
# dispatchers
# ---------------------------------------------------------------------------

def _warn_fallback(kind, shape):
    """A TPU trace that asked for the fused kernel but fell back lost
    a fusion boundary — counted on the shared serving_kernel_fallback
    telemetry (``stats()['kernel_fallbacks']`` folds these in), warned
    once per entry point."""
    from .paged_attention import count_fallback
    if count_fallback(kind):
        import warnings
        warnings.warn(
            "%s: shape %s not kernel-eligible (dims must be "
            "%d-multiples, rows an 8-multiple, buffers within %d MB "
            "of VMEM); using the XLA fallback"
            % (kind, tuple(shape), _COL_TILE, _VMEM_BUDGET >> 20))


def _norm_mm_vmem(rows, d, widths, isz):
    """Resident bytes of ``pallas_norm_matmul``: the double-buffered
    whole-row input block, the normalized scratch, step 0's f32
    temporaries, and per weight a double-buffered column tile plus its
    output tile."""
    tc = max(n // _tile_count(n) for n in widths)
    return (3 * rows * d * isz + 3 * rows * d * 4
            + len(widths) * 2 * (d * tc + rows * tc) * isz)


def _mm_res_vmem(rows, kdim, n, n_in, isz):
    """Resident bytes of ``pallas_matmul_residual``: per input a
    double-buffered K tile, the activation's f32 temporaries, the
    double-buffered weight tile, residual + output tiles and the f32
    accumulator. Independent of the contraction depth beyond ``tk``."""
    tk, tc = _k_tile(kdim), _res_col_tile(n)
    return (2 * n_in * rows * tk * isz + 3 * rows * tk * 4
            + 2 * tk * tc * isz + 4 * rows * tc * isz + rows * tc * 4)


def _eligible(dims, rows, strict, vmem_bytes):
    """Kernel eligibility. ``strict`` (the real-TPU path): every dim a
    128-multiple and the packed row count an 8-sublane multiple — the
    shapes cross-lowered in ``tests/test_tpu_lowering.py`` and compiled
    by ``chip_smoke.py`` — AND the resident buffers within the VMEM
    budget; interpret mode accepts any shape the tiling divides."""
    if rows > 4096 or rows < 1:
        return False
    if strict:
        return all(n % _COL_TILE == 0 for n in dims) \
            and rows % 8 == 0 and vmem_bytes <= _VMEM_BUDGET
    return True


def _route(kind, shape, dims, rows, vmem_bytes):
    """``(use_kernel, interpret)`` for one fused entry point under the
    armed mode: ``interpret`` runs the kernel under the interpreter on
    any backend; ``kernel`` takes it on a TPU backend for eligible
    shapes and counts the refusal otherwise (off TPU the mode means
    the bitwise-unfused XLA path)."""
    mode = fused_decode_mode()
    if mode == "interpret":
        return _eligible(dims, rows, False, vmem_bytes), True
    if mode == "kernel" and jax.default_backend() == "tpu":
        if _eligible(dims, rows, True, vmem_bytes):
            return True, None
        _warn_fallback(kind, shape)
    return False, None


def fused_norm_matmul(x, gamma, beta, ws, bs, *, eps, kind):
    """Array-level dispatcher: route the fused norm->projection(s) to
    the Pallas kernel (TPU, or interpret mode) or the bitwise-unfused
    XLA fallback. ``x`` keeps its ``[..., d]`` leading shape — the
    fallback runs on it UNRESHAPED so its ops are exactly the module
    path's; only the kernel flattens to packed rows."""
    d = x.shape[-1]
    widths = [w.shape[-1] for w in ws]
    rows = int(np.prod(x.shape[:-1]))
    use_kernel, interp = _route(
        "fused_norm_matmul", x.shape, [d] + widths, rows,
        _norm_mm_vmem(rows, d, widths, x.dtype.itemsize))
    if not use_kernel:
        return _xla_norm_matmul(x, gamma, beta, ws, bs, eps=eps,
                                kind=kind)
    outs = pallas_norm_matmul(
        x.reshape(rows, d), gamma, beta, list(ws), list(bs), eps=eps,
        kind=kind, interpret=interp)
    return tuple(o.reshape(x.shape[:-1] + (o.shape[-1],))
                 for o in outs)


def fused_matmul_residual(xs, w, b, residual, *, act=None):
    """Array-level dispatcher for the projection->residual epilogue
    (optionally swiglu/gelu prologue); same routing contract as
    ``fused_norm_matmul``."""
    kdim = xs[0].shape[-1]
    n = w.shape[-1]
    rows = int(np.prod(xs[0].shape[:-1]))
    use_kernel, interp = _route(
        "fused_matmul_residual", xs[0].shape, [kdim, n], rows,
        _mm_res_vmem(rows, kdim, n, len(xs), xs[0].dtype.itemsize))
    if not use_kernel:
        return _xla_matmul_residual(xs, w, b, residual, act=act)
    out = pallas_matmul_residual(
        [x.reshape(rows, kdim) for x in xs], w, b,
        residual.reshape(rows, n), act=act, interpret=interp)
    return out.reshape(residual.shape)


# ---------------------------------------------------------------------------
# Tensor-level entry points (what the decoder layers call)
# ---------------------------------------------------------------------------

def norm_matmul(x, gamma, beta, ws, bs, *, eps, kind):
    """Tensor-level fused norm -> 1..3 projections. ``ws`` is the list
    of projection weights sharing the normalized input; ``bs`` their
    biases (None entries allowed). Returns a tuple of Tensors."""
    from ...framework.core import apply_jax
    nw = len(ws)
    has_beta = beta is not None
    has_bias = [b is not None for b in bs]

    def f(x_a, g_a, *rest):
        i = 0
        beta_a = rest[i] if has_beta else None
        i += 1 if has_beta else 0
        w_as = rest[i:i + nw]
        i += nw
        b_as = []
        for hb in has_bias:
            b_as.append(rest[i] if hb else None)
            i += 1 if hb else 0
        return fused_norm_matmul(x_a, g_a, beta_a, list(w_as), b_as,
                                 eps=eps, kind=kind)

    args = [x, gamma] + ([beta] if has_beta else []) + list(ws) \
        + [b for b in bs if b is not None]
    out = apply_jax("fused_norm_matmul", f, *args, n_outputs=nw)
    return out if isinstance(out, tuple) else (out,)


def matmul_residual(xs, w, b, residual, *, act=None):
    """Tensor-level fused (activation ->) projection -> residual-add:
    ``residual + act(xs) @ w (+ b)``."""
    from ...framework.core import apply_jax
    n_in = len(xs)
    has_bias = b is not None

    def f(*arrs):
        x_as = arrs[:n_in]
        w_a = arrs[n_in]
        b_a = arrs[n_in + 1] if has_bias else None
        res_a = arrs[-1]
        return fused_matmul_residual(list(x_as), w_a, b_a, res_a,
                                     act=act)

    args = list(xs) + [w] + ([b] if has_bias else []) + [residual]
    return apply_jax("fused_matmul_residual", f, *args)

"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880, over
Hyper-Connections, arXiv:2409.19606): the residual stream of a row is
``n`` streams ``x [n, C]``, and every sublayer connection mixes them.

With ``x~ = vec(x) / rms(vec(x))`` (float32, no gain) and the
connection's ``fn [2n + n^2, nC]`` (``Phi^T``: the rows of ``pre``,
``post``, ``res`` in that order), ``base [2n + n^2]`` and ``scale [3]``::

    H_pre  = sigmoid(scale[0] (x~ fn_pre^T)  + base_pre)           [n]
    H_post = 2 sigmoid(scale[1] (x~ fn_post^T) + base_post)        [n]
    H_res  = Sinkhorn(exp(clip(scale[2] (x~ fn_res^T) + base_res)))  [n, n]
    u      = sum_i H_pre[i] x[i]                 the sublayer's input
    x'[i]  = sum_j H_res[i, j] x[j] + H_post[i] F(RMSNorm(u))

Sinkhorn: ``iters`` alternating normalisations, rows then columns, each
``m / (sum + eps)``. Everything of the coefficients is float32: the
product ``x fn^T`` multiplies bf16 by bf16 exactly and accumulates in
float32, then takes the ``1 / rms`` as a factor a row.

ONE call, ``hc_mix``, does a whole connection BOUNDARY: it applies the
coefficients of the connection that just ran (``coef``: ``H_post`` and
``H_res`` a row, float32) to the streams and that sublayer's output,
writes the new streams, and computes from them the NEXT connection's
coefficients and its input ``u``. The tick makes ``2 L + 1`` calls for
``L`` layers: the first (``first``) copies the embedding into every
stream, the last (``last``) sums the streams for the final norm and
computes no coefficients.

A Mosaic kernel (scope ``hc_mix``) over tiles of ``TILE`` rows — every
byte of the connection passes through it once: streams and sublayer
output in, streams and ``u`` out — and its ``jax.numpy`` mirror
(``_xla_hc_mix``), the CPU path and the kernel's reference. Inside the
kernel a row's coefficients lie along the LANES (``fn x^T`` on the
MXU gives them so), so the 20 serial Sinkhorn steps are elementwise
work on whole lane tiles; one transpose hands them back a row a
sublane to scale the streams.
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

__all__ = ["hc_mix", "pallas_hc_mix", "sinkhorn", "coefficients",
           "n_coef", "TILE"]

TILE = 128      # rows a grid step
_F32 = jnp.float32
_HI = jax.lax.Precision.HIGHEST
# a tile's streams in and out, double-buffered: 4 x 128 x 14,336 x 2 B
_VMEM_LIMIT = 48 * 1024 * 1024


def n_coef(n):
    """Coefficients a connection computes a row: ``pre``, ``post``,
    ``res``."""
    return 2 * n + n * n


def sinkhorn(m, iters, eps):
    """``m [..., n, n]`` positive -> doubly stochastic: ``iters`` times
    rows, then columns, each divided by its sum plus ``eps``."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)
    return m


def coefficients(xs, fn, base, scale, *, n, iters, eps, clamp, norm_eps):
    """``(pre [R, n], post [R, n], res [R, n, n])`` float32 from the
    streams ``xs [R, nC]`` (the module docstring)."""
    x32 = xs.astype(_F32)
    inv = jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True)
                        + norm_eps)
    raw = jnp.dot(xs, fn.T, preferred_element_type=_F32,
                  precision=_HI) * inv
    b, s = base.astype(_F32), scale.astype(_F32)
    pre = jax.nn.sigmoid(s[0] * raw[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(s[1] * raw[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(jnp.clip(s[2] * raw[:, 2 * n:] + b[2 * n:], *clamp))
    return pre, post, sinkhorn(res.reshape(-1, n, n), iters, eps)


def _xla_hc_mix(x, y, coef, fn, base, scale, *, n, first, last, iters,
                eps, clamp, norm_eps):
    r, c = y.shape
    if first:
        new = jnp.broadcast_to(y[:, None, :], (r, n, c))
    else:
        post, res = coef[:, :n], coef[:, n:].reshape(r, n, n)
        new = jnp.einsum("rij,rjc->ric", res,
                         x.reshape(r, n, c).astype(_F32), precision=_HI) \
            + post[..., None] * y.astype(_F32)[:, None]
        if last:
            return jnp.sum(new, axis=1).astype(y.dtype)
        new = new.astype(x.dtype)
    xs = new.reshape(r, n * c)
    pre, post, res = coefficients(xs, fn, base, scale, n=n, iters=iters,
                                  eps=eps, clamp=clamp, norm_eps=norm_eps)
    u = jnp.einsum("ri,ric->rc", pre, new.astype(_F32), precision=_HI)
    return xs, u.astype(y.dtype), jnp.concatenate(
        [post, res.reshape(r, n * n)], axis=-1)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _lane_chunk(c):
    return next(w for w in (512, 256, 128, c) if c % w == 0)


def _hc_kernel(*refs, n, c, first, last, iters, eps, clamp, norm_eps):
    refs = list(refs)
    x_ref = None if first else refs.pop(0)
    y_ref = refs.pop(0)
    coef_ref = None if first else refs.pop(0)
    if not last:
        fn_ref, base_ref, scale_ref = refs[:3]
        refs = refs[3:]
    tr = y_ref.shape[0]
    ck = _lane_chunk(c)
    chunks = [slice(k * ck, (k + 1) * ck) for k in range(c // ck)]

    def lanes(i, chunk):            # stream i's part of a lane chunk
        return slice(i * c + chunk.start, i * c + chunk.stop)

    if not first:
        cf = coef_ref[...]
        post = [cf[:, i:i + 1] for i in range(n)]
        res = [[cf[:, n + n * i + j:n + n * i + j + 1] for j in range(n)]
               for i in range(n)]

    def mixed(i, y32, xs):
        acc = post[i] * y32
        for j in range(n):
            acc = acc + res[i][j] * xs[j]
        return acc

    if last:
        (m_ref,) = refs
        for ch in chunks:
            y32 = y_ref[:, ch].astype(_F32)
            xs = [x_ref[:, lanes(j, ch)].astype(_F32) for j in range(n)]
            acc = mixed(0, y32, xs)
            for i in range(1, n):
                acc = acc + mixed(i, y32, xs)
            m_ref[:, ch] = acc.astype(m_ref.dtype)
        return

    xo_ref, u_ref, co_ref = refs
    k = n_coef(n)
    sq = jnp.zeros((tr, 1), _F32)
    raw = jnp.zeros((k, tr), _F32)
    for ch in chunks:
        y = y_ref[:, ch]
        if not first:
            y32 = y.astype(_F32)
            xs = [x_ref[:, lanes(j, ch)].astype(_F32) for j in range(n)]
        for i in range(n):
            xi = y if first else mixed(i, y32, xs).astype(xo_ref.dtype)
            xo_ref[:, lanes(i, ch)] = xi
            x32 = xi.astype(_F32)
            sq = sq + jnp.sum(x32 * x32, axis=1, keepdims=True)
            # fn x^T: the coefficients along the lanes, a row a lane
            raw = raw + jax.lax.dot_general(
                fn_ref[:, lanes(i, ch)], xi, (((1,), (1,)), ((), ())),
                preferred_element_type=_F32,
                precision=_HI if xi.dtype == _F32 else None)
    inv = jax.lax.rsqrt(sq * (1.0 / (n * c)) + norm_eps)
    inv = jnp.transpose(jnp.broadcast_to(inv, (tr, 128)))[0:1, :]

    def row(q, which):
        return scale_ref[which] * (raw[q:q + 1, :] * inv) + base_ref[q]

    pre = [jax.nn.sigmoid(row(i, 0)) for i in range(n)]
    hpost = [2.0 * jax.nn.sigmoid(row(n + i, 1)) for i in range(n)]
    m = tuple(jnp.exp(jnp.clip(row(2 * n + q, 2), *clamp))
              for q in range(n * n))

    def step(_, m):
        m = list(m)
        for i in range(n):
            d = m[n * i]
            for j in range(1, n):
                d = d + m[n * i + j]
            d = d + eps
            for j in range(n):
                m[n * i + j] = m[n * i + j] / d
        for j in range(n):
            d = m[j]
            for i in range(1, n):
                d = d + m[n * i + j]
            d = d + eps
            for i in range(n):
                m[n * i + j] = m[n * i + j] / d
        return tuple(m)

    m = jax.lax.fori_loop(0, iters, step, m)
    lane_major = jnp.concatenate(
        pre + hpost + list(m) + [jnp.zeros((128 - k, tr), _F32)], axis=0)
    per_row = jnp.transpose(lane_major)             # [tr, 128]
    co_ref[...] = per_row[:, n:k]
    for ch in chunks:
        acc = per_row[:, 0:1] * xo_ref[:, lanes(0, ch)].astype(_F32)
        for i in range(1, n):
            acc = acc + per_row[:, i:i + 1] \
                * xo_ref[:, lanes(i, ch)].astype(_F32)
        u_ref[:, ch] = acc.astype(u_ref.dtype)


def pallas_hc_mix(x, y, coef, fn, base, scale, *, n, first, last, iters,
                  eps, clamp, norm_eps, interpret=None):
    """``hc_mix`` as a Mosaic kernel over row tiles of ``TILE`` (a last
    tile may be partial: every step of it is a row's own). Operands and
    results as ``hc_mix``'s; the streams are written over ``x`` in
    place."""
    r, c = y.shape
    tr = TILE if r >= TILE else r
    k = n_coef(n)

    def rows(width):
        return pl.BlockSpec((tr, width), lambda i: (i, 0))

    in_specs, args = [], []
    if not first:
        in_specs.append(rows(n * c))
        args.append(x)
    in_specs.append(rows(c))
    args.append(y)
    if not first:
        in_specs.append(rows(k - n))
        args.append(coef)
    if last:
        out_shape = [jax.ShapeDtypeStruct((r, c), y.dtype)]
        out_specs = [rows(c)]
    else:
        smem = pl.BlockSpec(memory_space=pltpu.SMEM)
        in_specs += [pl.BlockSpec((k, n * c), lambda i: (0, 0)), smem, smem]
        args += [fn, base.astype(_F32), scale.astype(_F32)]
        out_shape = [jax.ShapeDtypeStruct((r, n * c), y.dtype),
                     jax.ShapeDtypeStruct((r, c), y.dtype),
                     jax.ShapeDtypeStruct((r, k - n), _F32)]
        out_specs = [rows(n * c), rows(c), rows(k - n)]
    call = pl.pallas_call(
        functools.partial(_hc_kernel, n=n, c=c, first=first, last=last,
                          iters=iters, eps=eps, clamp=tuple(clamp),
                          norm_eps=norm_eps),
        grid=(pl.cdiv(r, tr),),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        input_output_aliases={} if first or last else {0: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT),
        interpret=_interpret() if interpret is None else interpret,
    )
    with kernel_scope("hc_mix"):
        out = call(*args)
    return out[0] if last else tuple(out)


def _use_kernel(y) -> bool:
    on_tpu = jax.default_backend() == "tpu"
    ok = y.shape[-1] % 128 == 0 and y.dtype == jnp.bfloat16
    if (on_tpu or _force_kernel_routing()) and ok:
        return True
    if on_tpu:
        count_fallback("hc_mix")
    return False


def hc_mix(x, y, coef, fn, base, scale, *, n, first=False, last=False,
           iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6):
    """One connection boundary over ``R`` rows (the module docstring).

    ``x [R, nC]``: the streams (None where ``first``); ``y [R, C]``: the
    sublayer's output (the embedding where ``first``); ``coef [R, n +
    n^2]`` float32: the connection's ``H_post``, then ``H_res`` row-major
    (None where ``first``); ``fn [2n + n^2, nC]``, ``base [2n + n^2]``,
    ``scale [3]``: the NEXT connection's (None where ``last``). Returns
    the merged stream ``[R, C]`` where ``last``, else ``(x' [R, nC], u
    [R, C], coef' [R, n + n^2])``."""
    kw = dict(n=n, first=first, last=last, iters=iters, eps=eps,
              clamp=tuple(clamp), norm_eps=norm_eps)
    if _use_kernel(y):
        return pallas_hc_mix(x, y, coef, fn, base, scale, **kw)
    return _xla_hc_mix(x, y, coef, fn, base, scale, **kw)

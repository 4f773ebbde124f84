"""Phi-4-mini-flash-reasoning (``model_type: phi4flash``): the SambaY
decoder-hybrid-decoder (arXiv:2507.06607; Samba, arXiv:2406.07522; YOCO,
arXiv:2405.05254). With ``N`` layers and ``i`` a layer's index
(``Phi4FlashConfig.layer_kind``):

- the SELF-DECODER, layers ``0 .. N/2 - 1``: a ``mamba`` layer where ``i
  % mb_per_layer == 0``, else ``swa``;
- layer ``N/2``: ``mamba`` whose gated inner output ``m = y * silu(z)``
  is kept as the MEMORY;
- layer ``N/2 + 1``: ``full``, whose K/V is the model's ONLY full-length
  cache (the flat paged pool);
- the CROSS-DECODER, layers ``N/2 + 2 ..``: ``gmu`` at even ``i``, else
  ``cross``.

Mixers, with ``C`` hidden, ``E = expand C`` inner channels, ``S``
state entries:

- **``mamba``** (Mamba-1, arXiv:2312.00752): ``[x, z] = u W_in``; ``x =
  silu(conv4(x) + b_conv)`` (depthwise, causal; tap ``j`` multiplies the
  input ``3 - j`` rows back); ``[d, B, C] = x W_x``; ``dt = softplus(d
  W_dt + b_dt)``; the scan ``s_t = exp(dt_t A) s_{t-1} + B_t (dt_t
  x_t)``, ``y_t = C_t s_t + D x_t`` (``A = -exp(A_log)``, float32:
  ``ops/pallas/selective_scan.py``); ``out = (y * silu(z)) W_out``. Its
  memory a sequence is slot state: the conv's last 3 inputs ``[seats +
  1, 3, E]`` and the state ``[seats + 1, S, E]`` float32.
- **differential attention** (DIFF Transformer, arXiv:2410.05258) in
  ``swa``, ``full`` and ``cross``: ``H`` query heads and ``H_kv`` kv
  heads of ``D``; differential head ``j`` takes ``q1 = q_{2j}``, ``q2 =
  q_{2j+1}``, kv pair ``p = j // (H / H_kv)`` takes ``k1 = k_{2p}``,
  ``k2 = k_{2p+1}``, ``V = [v_{2p} | v_{2p+1}]``; ``o_j = softmax(q1
  k1^T / sqrt(D)) V - lambda softmax(q2 k2^T / sqrt(D)) V``, then
  ``(1 - lambda_init) rms(o_j, subln)`` over its ``2D`` lanes; ``lambda
  = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init = 0.8
  - 0.6 exp(-0.3 i)``. No positional encoding. ``swa`` masks keys at or
  before ``p - sliding_window`` and keeps them in a RING a seat
  (``ops/pallas/window_attention.py``: ``W`` positions whatever the
  context); ``full`` writes the pool; ``cross`` has q and o
  projections only and reads ``full``'s pool, writing nothing.
- **``gmu``** (gated memory unit): ``out = (m * silu(u W_1)) W_2``, ``m``
  the memory at the same position.

Every layer is ``h += mixer(ln(h, input_layernorm))``, ``h += ffn(ln(h,
post_attention_layernorm))`` with LayerNorms that carry a bias and the
FFN ``fc2(up * silu(gate))``, ``[gate | up] = fc1(x)``; after the last,
``ln(h, final_layernorm)`` and the head tied to the embedding.

**Serving** (``ServingEngine``'s ragged tick): layers ``0 .. N/2 + 1``
run over every packed row. Layers past ``N/2 + 1`` change nothing any
later row reads (their only cache is the ``full`` layer's, already
written), so they run on the rows the tick SAMPLES alone: the engine's
``logit_rows``, one a slot, gathered from ``h`` and ``m`` BEFORE them (a
prefill chunk's other rows go through ``N/2 + 2`` layers only: YOCO's
prefill skip). Those rows attend as one row a slot at the position
after the slot's rows of this tick.

State-dict names: ``model.embed_tokens.weight``; a layer's
``input_layernorm`` / ``post_attention_layernorm`` ``.weight`` /
``.bias``, ``mlp.fc1.weight [C, 2F]``, ``mlp.fc2.weight [F, C]``;
``mamba``: ``mixer.in_proj.weight [C, 2E]``, ``mixer.conv1d.weight [E,
4]``, ``mixer.conv1d.bias``, ``mixer.x_proj.weight [E, R + 2S]``,
``mixer.dt_proj.weight [R, E]``, ``mixer.dt_proj.bias``, ``mixer.A_log
[E, S]``, ``mixer.D``, ``mixer.out_proj.weight``; ``swa`` / ``full``:
``attn.Wqkv.weight [C, (H + 2 H_kv) D]``; ``cross``: ``attn.Wq.weight``;
the three: ``attn.out_proj.weight``, ``attn.lambda_{q1,k1,q2,k2} [D]``,
``attn.subln.weight [2D]``; ``gmu``: ``mixer.in_proj.weight [C, E]``,
``mixer.out_proj.weight [E, C]``; ``model.final_layernorm.weight`` /
``.bias``. Every leaf is created in ``config.dtype``. ``generate()``'s
dense cache, tensor-parallel serving, a quantized pool and speculation
over the ring and scan state are not built.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import apply_jax, as_jax, component
from ..generation import GenerationMixin
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..ops.pallas.selective_scan import _advance as scan_row, ssm_step
from ..ops.pallas.window_attention import ring_write, window_attention
from ..ops.short_conv import causal_taps, ragged_causal_taps
from .deepseek_v3 import _Leaf, _linear, _norm, _param
from .llama import LlamaPretrainingCriterion

__all__ = ["Phi4FlashConfig", "Phi4FlashModel", "Phi4FlashForCausalLM"]

_F32 = jnp.float32


@dataclass
class Phi4FlashConfig:
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    tie_word_embeddings: bool = True
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None     # None = ceil(hidden / 16)
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    dtype: str = "float32"

    def __post_init__(self):
        n = self.num_hidden_layers
        if n % 4 or self.mb_per_layer != 2:
            raise NotImplementedError(
                "the layer layout is built for a depth divisible by 4 "
                "and mb_per_layer 2 (the published configuration)")
        if not self.tie_word_embeddings:
            raise NotImplementedError("an untied head is not published")
        h, hkv = self.num_attention_heads, self.num_key_value_heads
        if h != 2 * hkv or hkv % 2:
            raise NotImplementedError(
                "differential attention is built for the published "
                "layout: two query heads a kv head, kv heads in pairs "
                "(a pair serves two differential heads)")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return int(self.mamba_dt_rank or math.ceil(self.hidden_size / 16))

    @property
    def memory_layer(self) -> int:
        return self.num_hidden_layers // 2

    @property
    def full_layer(self) -> int:
        return self.num_hidden_layers // 2 + 1

    def layer_kind(self, i: int) -> str:
        if i < self.memory_layer:
            return "mamba" if i % self.mb_per_layer == 0 else "swa"
        if i == self.memory_layer:
            return "mamba"
        if i == self.full_layer:
            return "full"
        return "gmu" if i % 2 == 0 else "cross"

    def lambda_init(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * i)


# -- the pieces, on arrays ----------------------------------------------------

def layer_norm(x, w, b, eps):
    x32 = x.astype(_F32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + np.float32(eps))
    return (y * w.astype(_F32) + b.astype(_F32)).astype(x.dtype)


def _silu32(x):
    return jax.nn.silu(x.astype(_F32))


def diff_lambda(lq1, lk1, lq2, lk2, init):
    dot = lambda a, b: jnp.sum(a.astype(_F32) * b.astype(_F32))   # noqa: E731
    return jnp.exp(dot(lq1, lk1)) - jnp.exp(dot(lq2, lk2)) + np.float32(init)


def diff_pairs(q):
    """``q [R, H, D]`` as ``[R, H, 2D]`` over kv PAIRS: slot ``4p + 2a +
    b`` (``a`` the half of pair ``p``, ``b`` which of the pair's two
    differential heads) holds ``q_{4p + 2b + a}`` in the half ``a`` of
    its lanes, zeros in the other. The ragged and window kernels' rule
    (query slot ``i`` reads pair ``i // 4``) then gives ``q1`` of head
    ``2p + b`` kv head ``2p`` and ``q2`` kv head ``2p + 1``, and keep
    all ``2D`` output lanes: the pair's ``V``."""
    r, h, d = q.shape
    x = jnp.swapaxes(q.reshape(r, h // 4, 2, 2, d), 2, 3)      # [R,P,a,b,D]
    zero = jnp.zeros_like(x[:, :, 0])
    return jnp.stack([jnp.concatenate([x[:, :, 0], zero], -1),
                      jnp.concatenate([zero, x[:, :, 1]], -1)],
                     axis=2).reshape(r, h, 2 * d)


def diff_combine(o, lam, lam_init, subln, eps):
    """A paired attention output ``o [R, H, 2D]`` (:func:`diff_pairs`'
    slots) as the differential heads' ``[R, H/2 * 2D]`` float32: ``o1 -
    lambda o2``, rms-normed over each head's ``2D`` lanes with
    ``subln``, times ``1 - lambda_init``."""
    r, h, d2 = o.shape
    o = o.astype(_F32).reshape(r, h // 4, 2, 2, d2)
    o = (o[:, :, 0] - lam * o[:, :, 1]).reshape(r, h // 2, d2)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                          + np.float32(eps))
    return (o * subln.astype(_F32) * np.float32(1.0 - lam_init)).reshape(
        r, -1)


def diff_attention_dense(q, k, v, scale, window=None):
    """Whole-sequence differential attention's two softmaxes over ``q
    [B, T, H, D]``, ``k, v [B, T, H_kv, D]`` (causal; ``window`` keys a
    query): ``(a1 V, a2 V)``, each ``[B, T, H/2, 2D]`` float32."""
    b, t, h, d = q.shape
    g = (h // 2) // (k.shape[2] // 2)
    q1, q2 = q[:, :, 0::2], q[:, :, 1::2]
    k1 = jnp.repeat(k[:, :, 0::2], g, axis=2)
    k2 = jnp.repeat(k[:, :, 1::2], g, axis=2)
    vv = jnp.repeat(jnp.concatenate([v[:, :, 0::2], v[:, :, 1::2]], -1),
                    g, axis=2)
    i = jnp.arange(t)
    keep = i[None, :] <= i[:, None]
    if window is not None:
        keep = keep & (i[None, :] > i[:, None] - window)

    def attend(qq, kk):
        s = jnp.einsum("bqjd,bkjd->bjqk", qq, kk,
                       preferred_element_type=_F32) * np.float32(scale)
        p = jax.nn.softmax(jnp.where(keep, s, -1e30), axis=-1)
        return jnp.einsum("bjqk,bkjd->bqjd", p, vv.astype(_F32))

    return attend(q1, k1), attend(q2, k2)


def _pair_view(pool):
    """A paged pool as kv PAIRS of ``2D`` lanes, what :func:`diff_pairs`'
    queries read: a flat pool ``[NB, BS, H_kv D]`` is that already (its
    128-lane tiles), a 4-D ``[NB, BS, H_kv, D]`` one is reshaped."""
    if pool.ndim == 3:
        return pool
    nb, bs, hkv, d = pool.shape
    return pool.reshape(nb, bs, hkv // 2, 2 * d)


def cross_layout(ragged_meta, cache_lens, logit_rows):
    """The cross-decoder's rows as a tick of their own: one row a slot
    (``logit_rows`` order: slot ``s`` is row ``s``) at the position after
    the slot's rows of this tick. ``(meta, context lens)``."""
    ql, _rs, _sl, _pos, nwin, _win = ragged_meta
    n = logit_rows.shape[0]
    ql = ql.astype(jnp.int32)
    one = (ql > 0).astype(jnp.int32)
    slots = jnp.arange(n, dtype=jnp.int32)
    lens = cache_lens.astype(jnp.int32) + ql
    meta = (one, slots, slots, lens - 1, nwin, jnp.arange(1, dtype=jnp.int32))
    return meta, lens


# -- mixers -------------------------------------------------------------------

class Phi4FlashMamba(Layer):
    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        c = self.config = config
        e, n, r = c.inner, c.mamba_d_state, c.dt_rank
        self.in_proj = _linear(c, c.hidden_size, 2 * e)
        self.conv1d = _Leaf(c, (e, c.mamba_d_conv))
        self.conv1d.bias = _param(c, (e,))
        self.x_proj = _linear(c, e, r + 2 * n)
        self.dt_proj = _linear(c, r, e)
        self.dt_proj.bias = _param(c, (e,))
        self.A_log = _param(c, (e, n))
        self.D = _param(c, (e,), ones=True)
        self.out_proj = _linear(c, e, c.hidden_size)

    def _weights(self):
        return (self.in_proj.weight, self.conv1d.weight, self.conv1d.bias,
                self.x_proj.weight, self.dt_proj.weight, self.dt_proj.bias,
                self.A_log, self.D, self.out_proj.weight)

    def _in(self, u, w_in):
        with component("mixer.in"):
            xz = u @ w_in
        return jnp.split(xz, 2, axis=-1)

    def _operands(self, conv, b_conv, w_x, w_dt, b_dt, a_log):
        """From the conv's float32 sum: ``(x, dt, B, C, A)`` float32."""
        c = self.config
        n, r = c.mamba_d_state, c.dt_rank
        with component("mixer.glue"):
            x = _silu32(conv + b_conv.astype(_F32))
        with component("mixer.in"):
            dbc = x.astype(w_x.dtype) @ w_x
        with component("mixer.glue"):
            d, bb, cc = jnp.split(dbc.astype(_F32), [r, r + n], axis=-1)
        with component("mixer.in"):
            dt = d.astype(w_dt.dtype) @ w_dt
        with component("mixer.glue"):
            dt = jax.nn.softplus(dt.astype(_F32) + b_dt.astype(_F32))
            a = -jnp.exp(a_log.astype(_F32)).T
        return x, dt, bb, cc, a

    def _out(self, y, x, z, dvec, w_out):
        """``(out, m)``: ``m = (y + D x) * silu(z)`` in the model's
        dtype, ``out = m W_out``."""
        with component("mixer.glue"):
            m = ((y + dvec.astype(_F32) * x) * _silu32(z)).astype(z.dtype)
        with component("mixer.out"):
            return m @ w_out, m

    def forward(self, u):
        """No cache: ``u [B, T, C]``, the scan a ``lax.scan`` over the
        tokens. Returns ``(out, m)``."""
        taps = self.config.mamba_d_conv

        def f(u_a, w_in, w_conv, b_conv, w_x, w_dt, b_dt, a_log, dvec,
              w_out):
            t = u_a.shape[1]
            x_in, z = self._in(u_a, w_in)
            pre = jnp.pad(x_in, ((0, 0), (taps - 1, 0), (0, 0)))
            conv = causal_taps(w_conv, [pre[:, j:j + t] for j in range(taps)])
            x, dt, bb, cc, a = self._operands(conv, b_conv, w_x, w_dt,
                                              b_dt, a_log)

            s0 = jnp.zeros((u_a.shape[0],) + a.shape, _F32)
            _, y = jax.lax.scan(
                lambda s, row: scan_row(s, a, *row), s0,
                tuple(jnp.swapaxes(v, 0, 1) for v in (dt, x, bb, cc)))
            return self._out(jnp.swapaxes(y, 0, 1), x, z, dvec, w_out)

        return apply_jax("mamba", f, u, *self._weights(), n_outputs=2)

    def forward_paged(self, u, cache, ragged_meta):
        """Over the ragged tick's packed rows ``u [1, R, C]``; ``cache``
        is ``(SlotState taps, SlotState scan state)``. Returns ``(out,
        m, cache)``."""
        from ..ops.paged_cache import SlotState

        def f(u_a, w_in, w_conv, b_conv, w_x, w_dt, b_dt, a_log, dvec,
              w_out, taps_state, scan_state, *meta):
            x_in, z = self._in(u_a[0], w_in)
            with component("mixer.glue"):
                conv, taps_new = ragged_causal_taps(x_in, taps_state.data,
                                                    w_conv, meta)
            x, dt, bb, cc, a = self._operands(conv, b_conv, w_x, w_dt,
                                              b_dt, a_log)
            with component("mixer.glue"):
                y, scan_new = ssm_step(dt, x, bb, cc, a, scan_state.data,
                                       meta)
            out, m = self._out(y, x, z, dvec, w_out)
            return out[None], m[None], taps_new, scan_new

        out, m, taps_new, scan_new = apply_jax(
            "mamba_paged", f, u, *self._weights(), cache[0], cache[1],
            *ragged_meta, n_outputs=4)
        return out, m, (SlotState(as_jax(taps_new)),
                        SlotState(as_jax(scan_new)))


class Phi4FlashDiffAttention(Layer):
    """Differential attention of the ``swa``, ``full`` and ``cross``
    layers (module docstring)."""

    def __init__(self, config: Phi4FlashConfig, layer_idx: int):
        super().__init__()
        c = self.config = config
        self.kind = c.layer_kind(layer_idx)
        self.lam_init = c.lambda_init(layer_idx)
        h, hkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        if self.kind == "cross":
            self.Wq = _linear(c, c.hidden_size, h * d)
        else:
            self.Wqkv = _linear(c, c.hidden_size, (h + 2 * hkv) * d)
        self.out_proj = _linear(c, h * d, c.hidden_size)
        self.lambda_q1 = _param(c, (d,))
        self.lambda_k1 = _param(c, (d,))
        self.lambda_q2 = _param(c, (d,))
        self.lambda_k2 = _param(c, (d,))
        self.subln = _norm(c, 2 * d)

    def _weights(self):
        w = self.Wq if self.kind == "cross" else self.Wqkv
        return (w.weight, self.out_proj.weight, self.lambda_q1,
                self.lambda_k1, self.lambda_q2, self.lambda_k2,
                self.subln.weight)

    def _project(self, u, w):
        """``(q, k, v)`` heads of rows ``u [..., C]`` (``k, v`` None in a
        ``cross`` layer)."""
        c = self.config
        h, hkv, d = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        lead = u.shape[:-1]
        with component("mixer.in"):
            qkv = u @ w
        if self.kind == "cross":
            return qkv.reshape(lead + (h, d)), None, None
        q, k, v = jnp.split(qkv, [h * d, (h + hkv) * d], axis=-1)
        return (q.reshape(lead + (h, d)), k.reshape(lead + (hkv, d)),
                v.reshape(lead + (hkv, d)))

    def _output(self, o_pairs, w_out, lq1, lk1, lq2, lk2, subln, dtype):
        with component("mixer.glue"):
            lam = diff_lambda(lq1, lk1, lq2, lk2, self.lam_init)
            o = diff_combine(o_pairs, lam, self.lam_init, subln,
                             self.config.layer_norm_eps).astype(dtype)
        with component("mixer.out"):
            return o @ w_out

    def forward(self, u, kv=None):
        """No cache: ``u [B, T, C]``; a ``cross`` layer reads ``kv = (k,
        v)``, the ``full`` layer's. Returns ``(out, (k, v))``."""
        c = self.config
        window = c.sliding_window if self.kind == "swa" else None

        def f(u_a, w, w_out, lq1, lk1, lq2, lk2, subln, *kv_a):
            q, k, v = self._project(u_a, w)
            if self.kind == "cross":
                k, v = kv_a
            a1, a2 = diff_attention_dense(q, k, v, c.head_dim ** -0.5,
                                          window)
            b, t = u_a.shape[:2]
            h = c.num_attention_heads
            # (a, j = 2p + b) -> diff_pairs' slot order (p, a, b)
            pairs = jnp.stack([a1, a2], axis=2).reshape(
                b * t, 2, h // 4, 2, 2 * c.head_dim)
            pairs = jnp.swapaxes(pairs, 1, 2).reshape(b * t, h, -1)
            out = self._output(pairs, w_out, lq1, lk1, lq2, lk2, subln,
                               u_a.dtype)
            return out.reshape(b, t, -1), k, v

        extra = () if kv is None else kv
        out, k, v = apply_jax("diff_attention", f, u, *self._weights(),
                              *extra, n_outputs=3)
        return out, (k, v)

    def forward_paged(self, u, cache, block_tables, cache_lens,
                      ragged_meta):
        """Over the packed rows ``u [1, R, C]``. ``swa``: ``cache`` is
        the ring ``(SlotState k, SlotState v)``; ``full``: the flat
        pool ``(k, v)``, written; ``cross``: that pool, read only,
        ``ragged_meta`` the one-row-a-slot layout of
        :func:`cross_layout`. Returns ``(out, cache)``."""
        from ..ops.paged_cache import SlotState, write_rows
        from ..ops.pallas.paged_attention import ragged_paged_attention
        c = self.config
        scale = c.head_dim ** -0.5
        window = c.sliding_window

        def f(u_a, w, w_out, lq1, lk1, lq2, lk2, subln, kc, vc, tables,
              lens, *meta):
            q, k, v = self._project(u_a[0], w)
            r = q.shape[0]
            with component("mixer.glue"):
                qp = diff_pairs(q)
                if self.kind == "swa":
                    k, v = k.reshape(r, -1), v.reshape(r, -1)
                    o = window_attention(qp, k, v, kc.data, vc.data, lens,
                                         meta, window, scale)
                    kc = ring_write(kc.data, k, lens, meta, window)
                    vc = ring_write(vc.data, v, lens, meta, window)
                else:
                    if self.kind == "full":
                        if kc.ndim == 3:        # a flat pool's rows
                            k, v = k.reshape(r, -1), v.reshape(r, -1)
                        kc, vc = write_rows(kc, vc, tables, meta[2],
                                            meta[3], k, v)
                        # the pool's lengths count the row itself
                        lens = lens + 1
                    # ``cross``: the pool as ``full`` left it, read only
                    o = ragged_paged_attention(
                        qp, _pair_view(kc), _pair_view(vc), tables, lens,
                        *meta[:3], *meta[4:], sm_scale=scale)
            out = self._output(o, w_out, lq1, lk1, lq2, lk2, subln,
                               u_a.dtype)
            return out[None], kc, vc

        out, kc, vc = apply_jax(
            "diff_attention_paged", f, u, *self._weights(), cache[0],
            cache[1], block_tables, cache_lens, *ragged_meta, n_outputs=3)
        if self.kind == "swa":
            return out, (SlotState(as_jax(kc)), SlotState(as_jax(vc)))
        return out, (kc, vc)


class Phi4FlashGMU(Layer):
    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.in_proj = _linear(config, config.hidden_size, config.inner)
        self.out_proj = _linear(config, config.inner, config.hidden_size)

    def forward(self, u, m):
        def f(u_a, m_a, w_1, w_2):
            with component("mixer.in"):
                g = u_a @ w_1
            with component("mixer.glue"):
                g = (m_a.astype(_F32) * _silu32(g)).astype(u_a.dtype)
            with component("mixer.out"):
                return g @ w_2

        return apply_jax("gmu", f, u, m, self.in_proj.weight,
                         self.out_proj.weight)


class Phi4FlashMLP(Layer):
    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.fc1 = _linear(config, config.hidden_size,
                           2 * config.intermediate_size)
        self.fc2 = _linear(config, config.intermediate_size,
                           config.hidden_size)

    def forward(self, x):
        def f(x_a, w1, w2):
            with component("ffn"):
                gate, up = jnp.split(x_a @ w1, 2, axis=-1)
                y = (up.astype(_F32) * _silu32(gate)).astype(x_a.dtype)
                return y @ w2

        return apply_jax("phi4flash_mlp", f, x, self.fc1.weight,
                         self.fc2.weight)


class _LayerNorm(Layer):
    def __init__(self, config, width):
        super().__init__()
        self.weight = _param(config, (width,), ones=True)
        self.bias = _param(config, (width,))
        self._eps = config.layer_norm_eps

    def forward(self, x):
        with component("norm"):
            return apply_jax("layer_norm", lambda a, w, b: layer_norm(
                a, w, b, self._eps), x, self.weight, self.bias)


# -- the decoder --------------------------------------------------------------

class Phi4FlashDecoderLayer(Layer):
    def __init__(self, config: Phi4FlashConfig, layer_idx: int):
        super().__init__()
        self.kind = config.layer_kind(layer_idx)
        self.is_memory = layer_idx == config.memory_layer
        if self.kind == "mamba":
            self.mixer = Phi4FlashMamba(config)
        elif self.kind == "gmu":
            self.mixer = Phi4FlashGMU(config)
        else:
            self.attn = Phi4FlashDiffAttention(config, layer_idx)
        self.mlp = Phi4FlashMLP(config)
        self.input_layernorm = _LayerNorm(config, config.hidden_size)
        self.post_attention_layernorm = _LayerNorm(config,
                                                   config.hidden_size)

    def _residual(self, h, a):
        with component("norm"):
            h = h + a
        a = self.mlp(self.post_attention_layernorm(h))
        with component("norm"):
            return h + a

    def forward(self, h, m=None, kv=None):
        """No cache: ``(h, m, kv)`` after the layer (``m`` the memory,
        ``kv`` the ``full`` layer's keys and values, as they pass)."""
        a = self.input_layernorm(h)
        if self.kind == "mamba":
            a, mem = self.mixer(a)
            m = mem if self.is_memory else m
        elif self.kind == "gmu":
            a = self.mixer(a, m)
        else:
            a, kv_new = self.attn(a, kv if self.kind == "cross" else None)
            kv = kv_new if self.kind == "full" else kv
        return self._residual(h, a), m, kv

    def forward_paged(self, h, cache, m, block_tables, cache_lens,
                      ragged_meta):
        """The paged step: ``(h, m, cache)``."""
        a = self.input_layernorm(h)
        if self.kind == "mamba":
            a, mem, cache = self.mixer.forward_paged(a, cache, ragged_meta)
            m = mem if self.is_memory else m
        elif self.kind == "gmu":
            a = self.mixer(a, m)
        else:
            a, cache = self.attn.forward_paged(a, cache, block_tables,
                                               cache_lens, ragged_meta)
        return self._residual(h, a), m, cache


class Phi4FlashModel(Layer):
    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = _linear(config, config.vocab_size,
                                    config.hidden_size)
        self.layers = LayerList(
            [Phi4FlashDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.final_layernorm = _LayerNorm(config, config.hidden_size)

    def _embed(self, input_ids):
        with component("embed"):
            return apply_jax("embedding", lambda w, i: jnp.take(
                w, i.astype(jnp.int32), axis=0), self.embed_tokens.weight,
                input_ids)

    def forward(self, input_ids, caches=None, block_tables=None,
                cache_lens=None, ragged_meta=None, logit_rows=None,
                cross_rows=None):
        """No cache: ``h`` after the final norm, every row through every
        layer. Paged: ``(h at logit_rows, caches)``: layers ``0 .. N/2 +
        1`` over the packed rows, the rest over ``logit_rows``
        (``cross_rows`` replaces them for a test: every packed row)."""
        c = self.config
        h = self._embed(input_ids)
        if caches is None:
            m = kv = None
            for layer in self.layers:
                h, m, kv = layer(h, m, kv)
            return self.final_layernorm(h)
        m = None
        new_caches = []
        for i in range(c.full_layer + 1):
            with component(f"L{i}.{c.layer_kind(i)}"):
                h, m, cache = self.layers[i].forward_paged(
                    h, caches[i], m, block_tables, cache_lens, ragged_meta)
            new_caches.append(cache)
        if cross_rows is None:
            def layout_of(*a):
                meta, lens = cross_layout(a[:6], a[6], a[7])
                return (*meta, lens)

            with component("tick.io"):
                *meta, lens = apply_jax("cross_layout", layout_of,
                                        *ragged_meta, cache_lens,
                                        logit_rows, n_outputs=7)
            rows = logit_rows
        else:
            # every packed row, each seeing what the tick's layout gives
            # it (the pool's lengths count the row itself)
            meta, rows = ragged_meta, cross_rows
            lens = apply_jax("cross_lens", lambda a: a + 1, cache_lens)
        with component("head"):
            take = lambda a, i: jnp.take(a, i, axis=1)     # noqa: E731
            h = apply_jax("cross_rows", take, h, rows)
            m = apply_jax("cross_rows", take, m, rows)
        kv = new_caches[c.full_layer]
        for i in range(c.full_layer + 1, c.num_hidden_layers):
            with component(f"L{i}.{c.layer_kind(i)}"):
                h, m, _ = self.layers[i].forward_paged(
                    h, kv, m, block_tables, lens, tuple(meta))
        return self.final_layernorm(h), new_caches


class Phi4FlashForCausalLM(Layer, GenerationMixin):
    # the mamba and window layers' cache entries are slot state, so
    # ``init_paged_caches`` wants the engine's ``num_slots``
    paged_slot_state = True
    # what the engine's ``tick`` span calls the seats advanced one row
    # and the rows through the chunked form (docs/OPS.md "Tick phases")
    paged_scan_state = "ssm"

    def __init__(self, config: Phi4FlashConfig):
        super().__init__()
        self.config = config
        self.model = Phi4FlashModel(config)
        self.criterion = LlamaPretrainingCriterion()

    def init_caches(self, batch_size: int, max_length: int):
        raise NotImplementedError(
            "Phi4FlashForCausalLM keeps no dense cache: generate() over "
            "the scan and ring state is not built; serve it through "
            "ServingEngine (init_paged_caches)")

    def tick_counts(self, q_lens, base):
        """What the engine's ``tick`` span adds for this model (numpy on
        the slots' ``q_lens`` and base lengths, docs/OPS.md "Tick
        phases"), each summed over the layers that do the work:

        - ``window_positions``: the keys the live rows attend in the
          ``swa`` layers (a row at position ``p``: ``min(p + 1, W)``);
        - ``window_reads``: the K/V positions those layers must read,
          once a slot (its ring's ``min(base, W)`` and its rows);
        - ``shared_kv_positions``: the keys attended over the ``full``
          layer's cache — every row in that layer, one row a slot in
          each ``cross`` layer;
        - ``shared_kv_reads``: that cache's positions read, once a slot
          a layer;
        - ``cross_rows``: the rows through the cross-decoder."""
        c = self.config
        q = np.asarray(q_lens, np.int64)
        base = np.asarray(base, np.int64)
        live = q > 0
        kinds = [c.layer_kind(i) for i in range(c.num_hidden_layers)]
        swa, cross = kinds.count("swa"), kinds.count("cross")
        w = c.sliding_window
        ends = base + q

        def clipped(n):         # sum of min(k, W) over k = 1 .. n
            m = np.minimum(n, w)
            return m * (m + 1) // 2 + (n - m) * w

        return {
            "window_positions": swa * int(
                (clipped(ends) - clipped(base)).sum()),
            "window_reads": swa * int(
                (np.minimum(base, w) + q)[live].sum()),
            "shared_kv_positions": int(
                (ends * (ends + 1) // 2 - base * (base + 1) // 2).sum()
                + cross * ends[live].sum()),
            "shared_kv_reads": (1 + cross) * int(ends[live].sum()),
            "cross_rows": int(live.sum())}

    def init_paged_caches(self, num_blocks: int, block_size: int,
                          sharding=None, kv_cache_dtype=None,
                          num_slots=None):
        """For layers ``0 .. N/2 + 1``, zeroed: a ``mamba`` layer's
        ``(SlotState, SlotState)`` — the conv's ``[num_slots + 1, 3, E]``
        in the model's dtype and the scan's ``[num_slots + 1, S, E]``
        float32 —, a ``swa`` layer's ring ``(SlotState, SlotState)`` of
        ``[num_slots + 1, W, H_kv D]`` each, and the ``full`` layer's
        paged ``(k_pool, v_pool)`` (flat where the kv heads fill whole
        lane tiles); the last row of each table the
        null seat. The cross-decoder keeps no cache of its own."""
        if sharding is not None:
            raise NotImplementedError(
                "tensor-parallel serving of slot state is not built")
        if kv_cache_dtype is not None:
            raise NotImplementedError(
                f"a quantized pool (kv_cache_dtype={kv_cache_dtype!r}) "
                "beside slot state is not built")
        if num_slots is None:
            raise ValueError(
                "init_paged_caches: a model with slot state needs "
                "num_slots (the scan state is a row a slot)")
        from ..ops.paged_cache import (init_flat_pool, init_pool,
                                       init_slot_state)
        c = self.config
        dtype = jnp.dtype(c.dtype)
        lanes = c.num_key_value_heads * c.head_dim
        # flat where the kv heads fill whole lane tiles
        paged = init_flat_pool if lanes % 128 == 0 else init_pool
        out = []
        for i in range(c.full_layer + 1):
            kind = c.layer_kind(i)
            if kind == "mamba":
                out.append(
                    init_slot_state(num_slots, (c.mamba_d_conv - 1, c.inner),
                                    dtype)
                    + init_slot_state(num_slots, (c.mamba_d_state, c.inner),
                                      _F32))
            elif kind == "swa":
                ring = (c.sliding_window, lanes)
                out.append(init_slot_state(num_slots, ring, dtype)
                           + init_slot_state(num_slots, ring, dtype))
            else:
                out.append(paged(num_blocks, block_size,
                                 c.num_key_value_heads, c.head_dim, dtype))
        return out

    def _logits(self, h):
        with component("head"):
            return apply_jax("tied_head", lambda a, w: a @ w.T, h,
                             self.model.embed_tokens.weight)

    def forward(self, input_ids, labels=None, attention_mask=None,
                caches=None, offset=None, position_ids=None,
                block_tables=None, cache_lens=None, ragged_meta=None,
                logit_rows=None):
        if attention_mask is not None or position_ids is not None:
            raise NotImplementedError(
                "padded batches (attention_mask / position_ids)")
        if caches is not None:
            if block_tables is None or ragged_meta is None \
                    or logit_rows is None:
                raise NotImplementedError(
                    "a dense cache, the per-width paged step, or a tick "
                    "that names no sampled rows: the cross-decoder runs "
                    "on the rows the ragged tick samples")
            h, new_caches = self.model(
                input_ids, caches=caches, block_tables=block_tables,
                cache_lens=cache_lens, ragged_meta=ragged_meta,
                logit_rows=logit_rows)
            return self._logits(h), new_caches
        logits = self._logits(self.model(input_ids))
        return logits if labels is None \
            else self.criterion(logits, labels)

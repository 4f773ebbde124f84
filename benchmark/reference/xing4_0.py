"""Plain reference for the Xing4.0 decoder (``model_type: xing4_0``):
forward only.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision. The block is DeepSeek-V3's — latent attention, the sigmoid
``noaux_tc`` router, one shared expert, leading dense layers — and is
``benchmark/reference/deepseek_v3.py``'s, imported (that file lists its
own departures). What is new is the residual path: manifold-constrained
hyper-connections (mHC, DeepSeek-AI, arXiv:2512.24880, over
Hyper-Connections, arXiv:2409.19606). A row's residual is ``n =
hc_mult`` streams ``x [n, C]``; at each of a layer's two sublayer
connections (attention, then the FFN or expert block)::

    x~     = vec(x) / rms(vec(x))                        [nC]
    H_pre  = sigmoid(a_pre  x~ Phi_pre  + b_pre)         [n]
    H_post = 2 sigmoid(a_post x~ Phi_post + b_post)      [n]
    H_res  = Sinkhorn(exp(clamp(a_res x~ Phi_res + b_res, -30, 30)))  [n, n]
    u      = sum_i H_pre[i] x[i]
    y      = F(RMSNorm(u))          the layer's own norm weight
    x'[i]  = sum_j H_res[i, j] x[j] + H_post[i] y

Sinkhorn: ``hc_sinkhorn_iters`` times, every row divided by its sum plus
``hc_eps``, then every column likewise, one step after another.

No kernel, cache or batching; the coefficients, the mixing and the
whole stream are float32 (the program keeps its streams in bf16). It
imports nothing of ``paddle_tpu`` and takes its weights from the
benchmark's seeded generator through ``benchmark/models/xing4_0.py``.

Departures from the published description, each an assumption the
configuration file lists under ``assumed``:

- **the stream merge**: the embedding is copied into all ``n`` streams,
  and after the last layer the streams are SUMMED before the final norm
  (Hyper-Connections' sum; the checkpoint's own merge could not be
  checked here);
- **the gain of the rms**: ``x~`` is ``vec(x)`` over its root mean
  square with ``rms_norm_eps`` inside the root and NO learned gain;
- **the Sinkhorn's order and eps**: rows first, then columns, ``eps``
  added to each sum;
- **the leaves and their initialiser**: a connection's ``fn [2n + n^2,
  nC]`` is ``[Phi_pre | Phi_post | Phi_res]^T`` (``Phi_res``'s ``n^2``
  columns row-major, ``H_res[i, j]`` at ``i n + j``), ``base [2n + n^2]``
  the ``b`` in the same order, ``scale [3]`` the ``a_pre, a_post,
  a_res``; ``fn`` N(0, 0.02) as every matrix, ``base`` N(0, 1) and
  ``scale`` ones, so that the four streams differ and ``H_res`` is far
  from the identity (``models/xing4_0.py::leaves``);
- DeepSeek-V3's departures (``reference/deepseek_v3.py``), the
  multi-token-prediction module not run among them.

``lowp``: the control — every matmul operand, ``Phi`` among them,
rounded to float8 (e4m3), as ``deepseek_v3``'s.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from benchmark.reference import deepseek_v3 as ds

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def sinkhorn(m, iters, eps):
    """``m [T, n, n]``: one row normalisation, then one column
    normalisation, ``iters`` times in sequence."""
    for _ in range(iters):
        m = m / (jnp.sum(m, axis=2, keepdims=True) + F32(eps))
        m = m / (jnp.sum(m, axis=1, keepdims=True) + F32(eps))
    return m


def hc_coefficients(x, w, prefix, cfg, lowp):
    """``(pre [T, n], post [T, n], res [T, n, n])`` of one connection
    from the streams ``x [T, n, C]``; ``prefix`` names its leaves."""
    t, n, c = x.shape
    v = x.reshape(t, n * c)
    xn = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True)
                           + F32(cfg["rms_norm_eps"]))
    raw = ds._mm(xn, w[prefix + "fn"].astype(F32).T, lowp)    # [T, 2n+n^2]
    b, a = w[prefix + "base"].astype(F32), w[prefix + "scale"].astype(F32)
    pre = jax.nn.sigmoid(a[0] * raw[:, :n] + b[:n])
    post = 2.0 * jax.nn.sigmoid(a[1] * raw[:, n:2 * n] + b[n:2 * n])
    res = jnp.exp(jnp.clip(a[2] * raw[:, 2 * n:] + b[2 * n:],
                           F32(cfg["mhc_h_res_clamp_min"]),
                           F32(cfg["mhc_h_res_clamp_max"])))
    res = sinkhorn(res.reshape(t, n, n), cfg["hc_sinkhorn_iters"],
                   cfg["hc_eps"])
    return pre, post, res


def connection(x, w, prefix, norm, sublayer, cfg, lowp):
    """One sublayer connection over the streams ``x [T, n, C]``."""
    pre, post, res = hc_coefficients(x, w, prefix, cfg, lowp)
    u = jnp.einsum("ti,tic->tc", pre, x, precision=HI)
    y = sublayer(ds.rms_norm(u, w[norm], cfg["rms_norm_eps"]))
    return (jnp.einsum("tij,tjc->tic", res, x, precision=HI)
            + post[..., None] * y[:, None, :])


def layer_forward(h, w, cfg, dense, lowp=False):
    """One decoder layer over the streams ``h [B, T, n, C]``, a sequence
    at a time; ``w`` this layer's leaves by short name."""

    def ffn(x):
        if dense:
            return ds.swiglu(x, w["mlp.gate_proj.weight"],
                             w["mlp.up_proj.weight"],
                             w["mlp.down_proj.weight"], lowp)
        return ds.experts(x, w, cfg, lowp)

    def one(x):
        x = connection(x, w, "hc_attn.", "input_layernorm.weight",
                       lambda a: ds.attention(a, w, cfg, lowp), cfg, lowp)
        return connection(x, w, "hc_ffn.", "post_attention_layernorm.weight",
                          ffn, cfg, lowp)

    return jax.lax.map(one, h)


def embed(ids, table, cfg):
    """The embedding copied into every stream: ``[B, T, n, C]``."""
    e = ds.embed(ids, table)
    return jnp.broadcast_to(e[..., None, :],
                            e.shape[:-1] + (cfg["hc_mult"], e.shape[-1]))


def head(h, w_norm, w_head, cfg, lowp=False):
    """The streams summed, the final norm, the head."""
    return ds.head(jnp.sum(h, axis=-2), w_norm, w_head, cfg, lowp)


def forward(weights, ids, cfg, lowp=False):
    """Whole forward from a full weight dict: logits ``[B, T, V]``."""
    h = embed(ids, weights["model.embed_tokens.weight"], cfg)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        w = {k[len(pre):]: v for k, v in weights.items()
             if k.startswith(pre)}
        h = layer_forward(h, w, cfg, i < cfg["first_k_dense_replace"],
                          lowp)
    return head(h, weights["model.norm.weight"], weights["lm_head.weight"],
                cfg, lowp)

"""DeepSeek-V3 model family (``model_type: deepseek_v3`` — DeepSeek-V3 /
R1, GigaChat3): latent attention (MLA) and a sigmoid, group-limited
router over fine-grained experts with one shared expert.

What differs from the Llama body, and where it lives:

- **Latent attention** (``DeepseekV3Attention``). Queries go through a
  low-rank bottleneck (``q_a_proj`` -> RMSNorm -> ``q_b_proj``); keys
  and values are expanded from ONE compressed row a token,
  ``c_kv = RMSNorm(x W_kva[:, :rank])``, plus one rotary key ``k_pe``
  shared by every head. The cache holds ``(c_kv, k_pe)`` — ``rank +
  rope`` values a token a layer, not per-head K and V. Without a cache
  the layer expands per-head keys and values from the latent
  (``kv_b_proj``); over the paged cache it runs the ABSORBED form, the
  same numbers: ``q~_h = q_nope_h W_UK_h^T`` scores straight against the
  cached row, the softmax-weighted sum of cached rows is expanded by
  ``W_UV_h`` afterwards — one shared "KV head" of key width ``rank +
  rope`` whose value is the key's first ``rank`` lanes
  (``ops/pallas/paged_attention.ragged_latent_attention_step``; the
  pool is ``ops/paged_cache.init_latent_pool``'s one array a layer).
- **Rotary embedding**: YaRN (``yarn_inv_freq``: the linear-ramp blend
  of interpolated and extrapolated inverse frequencies over the
  correction range; ``yarn_softmax_scale``: the ``m^2`` on the softmax
  scale), applied to the rope lanes after the published
  interleaved-to-halves permutation.
- **Experts** (``DeepseekV3MoE``): ``distributed/moe.group_limited_gate``
  in float32, then ``moe_share_dispatch_combine`` — the layer routes
  over all ``n_routed_experts`` and computes the experts it HOLDS
  (``expert_first`` / ``expert_count``: one chip's share of an
  expert-parallel deployment, with no exchange; the default holds them
  all), plus the shared expert on every token. The first
  ``first_k_dense_replace`` layers are dense SwiGLU.

Multi-token prediction (``num_nextn_predict_layers``) is not built: the
published ``transformers`` implementation drops those weights at load.
Tensor-parallel serving and ``generate()``'s dense cache are not built
for the latent cache either; serving goes through ``ServingEngine``'s
ragged tick (or the per-width paged step, the same op with a uniform
row layout). Every leaf is CREATED in ``config.dtype`` (``_param``: the
initialiser draws, scales and rounds in one program), never in float32
first: at the published widths one expert layer's share is 0.88 B
parameters, and ``Layer.create_parameter``'s float32 with the stock
initialisers' eager temporaries beside the layers already built does
not fit a 16 GB chip.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import random as _random
from ..framework.core import apply_jax, component
from ..framework.dtype import to_np
from ..nn import functional as F
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer, create_parameter
from ..generation import GenerationMixin
from .llama import LlamaPretrainingCriterion, _rope_rotate

__all__ = ["DeepseekV3Config", "DeepseekV3Model", "DeepseekV3ForCausalLM",
           "yarn_inv_freq", "yarn_mscale", "yarn_softmax_scale"]


@dataclass
class DeepseekV3Config:
    vocab_size: int = 129280
    hidden_size: int = 7168
    intermediate_size: int = 18432          # dense-layer FFN width
    moe_intermediate_size: int = 2048       # expert (and shared) width
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 256             # the gate's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.5
    first_k_dense_replace: int = 3
    max_position_embeddings: int = 163840
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    # {"factor", "original_max_position_embeddings", "beta_fast",
    #  "beta_slow", "mscale", "mscale_all_dim"}; None = plain rope
    rope_scaling: Optional[dict] = field(default=None)
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    # one chip's share of an expert-parallel layer: the experts held
    # here are expert_first .. expert_first + expert_count of the
    # n_routed_experts the gate routes over (None = all of them)
    expert_first: int = 0
    expert_count: Optional[int] = None
    # the share is dropless by construction (ServingEngine's MoE gate)
    dropless: bool = True
    dtype: str = "float32"

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts if self.expert_count is None \
            else int(self.expert_count)

    @staticmethod
    def tiny(vocab=512, hidden=64, layers=3, heads=4, q_rank=32,
             kv_rank=32, nope=16, rope=8, v_dim=24, dense_ffn=96,
             moe_ffn=32, experts=16, groups=4, topk_group=2, topk=4,
             dense_layers=1, **kw):
        return DeepseekV3Config(
            vocab_size=vocab, hidden_size=hidden,
            intermediate_size=dense_ffn, moe_intermediate_size=moe_ffn,
            num_hidden_layers=layers, num_attention_heads=heads,
            q_lora_rank=q_rank, kv_lora_rank=kv_rank,
            qk_nope_head_dim=nope, qk_rope_head_dim=rope,
            v_head_dim=v_dim, n_routed_experts=experts, n_group=groups,
            topk_group=topk_group, num_experts_per_tok=topk,
            first_k_dense_replace=dense_layers,
            max_position_embeddings=8192, rope_theta=1e5,
            rope_scaling={"factor": 64, "beta_fast": 32, "beta_slow": 1,
                          "mscale": 1, "mscale_all_dim": 1,
                          "original_max_position_embeddings": 64}, **kw)


# -- YaRN ----------------------------------------------------------------------

def yarn_mscale(scale, m=1.0):
    """``0.1 m ln(scale) + 1`` past scale 1 (the YaRN attention
    temperature)."""
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_inv_freq(dim, theta, scaling):
    """Inverse frequencies of the ``dim`` rope lanes (``[dim / 2]``
    float32) and the factor cos/sin are multiplied by. With
    ``scaling``: lane pair ``i`` rotates ``theta^(-2i/dim)`` a position
    (extrapolated) or a ``factor``-th of it (interpolated); the blend is
    a linear ramp over the pairs whose period makes between
    ``beta_fast`` and ``beta_slow`` turns inside the original context
    — pairs faster than ``beta_fast`` keep their frequency, pairs
    slower than ``beta_slow`` are interpolated."""
    pos_freqs = float(theta) ** (np.arange(0, dim, 2, dtype=np.float64)
                                 / dim)
    if not scaling:
        return (1.0 / pos_freqs).astype(np.float32), 1.0
    factor = float(scaling["factor"])
    orig = float(scaling["original_max_position_embeddings"])

    def correction_dim(turns):
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(float(theta)))

    low = max(math.floor(correction_dim(scaling["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(scaling["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float64) - low)
                   / (high - low), 0.0, 1.0)
    inv = (1.0 / (factor * pos_freqs)) * ramp \
        + (1.0 / pos_freqs) * (1.0 - ramp)
    m, m_all = scaling.get("mscale"), scaling.get("mscale_all_dim")
    attn = yarn_mscale(factor, m) / yarn_mscale(factor, m_all) \
        if m and m_all else yarn_mscale(factor)
    return inv.astype(np.float32), float(attn)


def yarn_softmax_scale(config):
    """``(nope + rope)^-0.5``, times ``m^2`` with ``m = 0.1
    mscale_all_dim ln(factor) + 1`` under YaRN."""
    scale = (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5
    sc = config.rope_scaling
    if sc and sc.get("mscale_all_dim"):
        m = yarn_mscale(float(sc["factor"]), float(sc["mscale_all_dim"]))
        scale *= m * m
    return scale


def _rope_lanes(x, pos, inv_freq, attn_factor):
    """Rotate the rope lanes ``x [R, ..., dr]`` at positions ``pos
    [R]``: the published layout interleaves the two halves (lane 2i
    pairs with 2i+1), so the lanes are first permuted to halves."""
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv_freq)[None]
    cos = jnp.cos(ang) * np.float32(attn_factor)
    sin = jnp.sin(ang) * np.float32(attn_factor)
    x = jnp.concatenate([x[..., 0::2], x[..., 1::2]], axis=-1)
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (cos.shape[-1],)
    return _rope_rotate(x, cos.reshape(shape), sin.reshape(shape))


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _normal(key, shape, dtype, std):
    return (jax.random.normal(key, shape, jnp.float32)
            * np.float32(std)).astype(dtype)


def _param(config, shape, ones=False):
    """One parameter created in the model's dtype: N(0,
    ``initializer_range``) drawn, scaled and rounded in one program (no
    float32 copy ever stands in memory), or ones for a norm."""
    std = float(config.initializer_range)

    def init(shp, dt):
        if ones or not std:         # N(0, 0): no draw
            return jnp.full(shp, 1.0 if ones else 0.0, to_np(dt))
        return _normal(_random.next_key(), shp, to_np(dt), std)

    return create_parameter(list(shape), config.dtype,
                            default_initializer=init)


class _Leaf(Layer):
    """A layer of one parameter, ``weight`` (``_param``)."""

    def __init__(self, config, shape, ones=False):
        super().__init__()
        self.weight = _param(config, shape, ones)

    def forward(self, x):
        return F.linear(x, self.weight, None)


def _linear(config, n_in, n_out):
    return _Leaf(config, (n_in, n_out))


def _norm(config, width):
    return _Leaf(config, (width,), ones=True)


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(x32 * x32, axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(var + eps)).astype(x.dtype) * w


def _swiglu(x, gate, up, down):
    g = x @ gate
    return (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)
            * (x @ up)) @ down


# -- layers --------------------------------------------------------------------

class DeepseekV3Attention(Layer):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        c = self.config = config
        h = c.num_attention_heads
        self.q_head_dim = c.qk_nope_head_dim + c.qk_rope_head_dim
        self.q_a_proj = _linear(c, c.hidden_size, c.q_lora_rank)
        self.q_a_layernorm = _norm(c, c.q_lora_rank)
        self.q_b_proj = _linear(c, c.q_lora_rank, h * self.q_head_dim)
        self.kv_a_proj_with_mqa = _linear(
            c, c.hidden_size, c.kv_lora_rank + c.qk_rope_head_dim)
        self.kv_a_layernorm = _norm(c, c.kv_lora_rank)
        self.kv_b_proj = _linear(
            c, c.kv_lora_rank, h * (c.qk_nope_head_dim + c.v_head_dim))
        self.o_proj = _linear(c, h * c.v_head_dim, c.hidden_size)
        self._inv_freq, self._rope_factor = yarn_inv_freq(
            c.qk_rope_head_dim, c.rope_theta, c.rope_scaling)
        self._scale = yarn_softmax_scale(c)

    def _weights(self):
        return (self.q_a_proj.weight, self.q_a_layernorm.weight,
                self.q_b_proj.weight, self.kv_a_proj_with_mqa.weight,
                self.kv_a_layernorm.weight, self.kv_b_proj.weight,
                self.o_proj.weight)

    def _project(self, x, pos, wqa, qa_ln, wqb, wkva, kva_ln):
        """x ``[R, hidden]`` at positions ``pos [R]`` ->
        ``(q_nope [R, H, dn], q_pe [R, H, dr], c_kv [R, rank],
        k_pe [R, dr])``, rope applied."""
        c = self.config
        eps = c.rms_norm_eps
        with component("mixer.in"):
            q_a = x @ wqa
        with component("mixer.glue"):
            q_a = _rms(q_a, qa_ln, eps)
        with component("mixer.in"):
            q = (q_a @ wqb).reshape(
                x.shape[0], c.num_attention_heads, self.q_head_dim)
        with component("mixer.glue"):
            q_nope = q[..., :c.qk_nope_head_dim]
        with component("mixer.in"):
            ckv = x @ wkva
        with component("mixer.glue"):
            c_kv = _rms(ckv[:, :c.kv_lora_rank], kva_ln, eps)
            rope = (pos, self._inv_freq, self._rope_factor)
            q_pe = _rope_lanes(q[..., c.qk_nope_head_dim:], *rope)
            k_pe = _rope_lanes(ckv[:, c.kv_lora_rank:], *rope)
        return q_nope, q_pe, c_kv, k_pe

    def forward(self, x):
        """No cache: per-head keys and values expanded from the latent,
        plain causal softmax. x ``[B, T, hidden]``."""
        c = self.config
        b, t, _ = x.shape
        h, dn, dv = c.num_attention_heads, c.qk_nope_head_dim, c.v_head_dim

        def f(x_a, wqa, qa_ln, wqb, wkva, kva_ln, wkvb, wo):
            pos = jnp.tile(jnp.arange(t, dtype=jnp.int32), b)
            q_nope, q_pe, c_kv, k_pe = self._project(
                x_a.reshape(b * t, -1), pos, wqa, qa_ln, wqb, wkva,
                kva_ln)
            kv = (c_kv @ wkvb).reshape(b, t, h, dn + dv)
            q = jnp.concatenate([q_nope, q_pe], -1).reshape(b, t, h, -1)
            k = jnp.concatenate(
                [kv[..., :dn], jnp.broadcast_to(
                    k_pe.reshape(b, t, 1, -1),
                    (b, t, h, c.qk_rope_head_dim))], -1)
            s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                           preferred_element_type=jnp.float32) \
                * np.float32(self._scale)
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool))[None, None],
                          s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(x_a.dtype)
            o = jnp.einsum("bhqk,bkhd->bqhd", p, kv[..., dn:])
            return o.reshape(b, t, h * dv) @ wo

        return apply_jax("mla_attention", f, x, *self._weights())

    def forward_paged(self, x, cache, block_tables, cache_lens,
                      ragged_meta):
        """Over the latent pool, absorbed. x ``[B, L, hidden]``: the
        ragged tick's one packed row buffer (``B = 1``, rows laid out
        by ``ragged_meta``), or with ``ragged_meta=None`` the per-width
        step's ``[S, T]`` window of ``T`` rows a slot starting at
        ``cache_lens`` — the same op with a uniform row layout.
        ``cache`` is the layer's 1-tuple. Returns ``(out, cache)``."""
        from ..ops.pallas.paged_attention import \
            ragged_latent_attention_step
        c = self.config
        b, l, _ = x.shape
        r = b * l
        dn, dv, rank = c.qk_nope_head_dim, c.v_head_dim, c.kv_lora_rank
        h = c.num_attention_heads
        uniform = ragged_meta is None

        def f(x_a, wqa, qa_ln, wqb, wkva, kva_ln, wkvb, wo, pool,
              tables, lens, *meta):
            with component("mixer.glue"):
                lens = lens.astype(jnp.int32)
                if uniform:
                    ql = jnp.full((b,), l, jnp.int32)
                    rs = jnp.arange(b, dtype=jnp.int32) * l
                    sl = jnp.repeat(jnp.arange(b, dtype=jnp.int32), l)
                    pos_r = (lens[:, None] + jnp.arange(
                        l, dtype=jnp.int32)[None]).reshape(-1)
                    nwin = win = jnp.arange(l, dtype=jnp.int32)
                else:
                    ql, rs, sl, pos_r, nwin, win = meta
                pos = jnp.clip(pos_r.astype(jnp.int32), 0,
                               c.max_position_embeddings - 1)
            q_nope, q_pe, c_kv, k_pe = self._project(
                x_a.reshape(r, -1), pos, wqa, qa_ln, wqb, wkva, kva_ln)
            w3 = wkvb.reshape(rank, h, dn + dv)
            with component("mixer.in"):
                # the absorbed form: q against kv_b_proj's key half
                q_abs = jnp.einsum("rhn,chn->rhc", q_nope, w3[..., :dn])
            with component("mixer.glue"):
                lanes = pool.shape[-1]
                pad = lanes - rank - c.qk_rope_head_dim
                q_cat = jnp.concatenate(
                    [q_abs, q_pe, jnp.zeros((r, h, pad), q_abs.dtype)],
                    -1)
                c_new = jnp.concatenate(
                    [c_kv, k_pe, jnp.zeros((r, pad), c_kv.dtype)], -1)
                u, (pool2,) = ragged_latent_attention_step(
                    q_cat, c_new, (pool,), tables, lens, ql, rs, sl,
                    pos_r, nwin, win, rank, self._scale)
            with component("mixer.out"):
                # kv_b_proj's value half, then o_proj
                o = jnp.einsum("rhc,chv->rhv", u, w3[..., dn:])
                return (o.reshape(b, l, h * dv) @ wo), pool2

        meta = () if uniform else tuple(ragged_meta)
        out, pool = apply_jax(
            "mla_attention_paged", f, x, *self._weights(), cache[0],
            block_tables, cache_lens, *meta, n_outputs=2)
        return out, (pool,)


class DeepseekV3MLP(Layer):
    def __init__(self, config, width):
        super().__init__()
        hidden = config.hidden_size
        self.gate_proj = _linear(config, hidden, width)
        self.up_proj = _linear(config, hidden, width)
        self.down_proj = _linear(config, width, hidden)

    def forward(self, x):
        with component("ffn"):
            return apply_jax("swiglu_mlp", _swiglu, x,
                             self.gate_proj.weight, self.up_proj.weight,
                             self.down_proj.weight)


class DeepseekV3Gate(Layer):
    """The router's two leaves: ``weight [hidden, n_routed_experts]``
    and the choice bias ``e_score_correction_bias``."""

    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        e = config.n_routed_experts
        self.weight = _param(config, (config.hidden_size, e))
        self.e_score_correction_bias = _param(config, (e,))


class DeepseekV3Experts(Layer):
    """The routed experts held here as stacked leaves: ``gate_up_proj
    [held, hidden, 2 f]`` (gate columns, then up columns) and
    ``down_proj [held, f, hidden]``; the computation is
    ``distributed/moe.moe_share_dispatch_combine``'s."""

    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        held, h = config.experts_held, config.hidden_size
        f = config.moe_intermediate_size
        self.gate_up_proj = _param(config, (held, h, 2 * f))
        self.down_proj = _param(config, (held, f, h))


class DeepseekV3MoE(Layer):
    """Router, the routed experts held here, the shared expert."""

    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        self.gate = DeepseekV3Gate(config)
        self.experts = DeepseekV3Experts(config)
        self.shared_experts = DeepseekV3MLP(
            config, config.n_shared_experts * config.moe_intermediate_size)

    def forward(self, x):
        from ..distributed.moe import (group_limited_gate,
                                       moe_share_dispatch_combine)
        c = self.config

        def f(x_a, wg, bias, gate_up, down, sg, su, sd):
            x2 = x_a.reshape(-1, x_a.shape[-1])
            # the gate runs in float32 as published: on a TPU that
            # takes the highest matmul precision, not bf16 passes
            with component("moe.gate"):
                logits = jnp.matmul(x2.astype(jnp.float32),
                                    wg.astype(jnp.float32),
                                    precision=jax.lax.Precision.HIGHEST)
            idx, w = group_limited_gate(
                logits, bias, n_group=c.n_group,
                topk_group=c.topk_group, top_k=c.num_experts_per_tok,
                norm_topk_prob=c.norm_topk_prob,
                routed_scaling_factor=c.routed_scaling_factor)
            y = moe_share_dispatch_combine(
                x2, idx, w, gate_up, down, first=c.expert_first,
                num_expert=c.n_routed_experts)
            with component("ffn"):      # the shared expert
                return (y + _swiglu(x2, sg, su, sd)).reshape(x_a.shape)

        sh = self.shared_experts
        return apply_jax(
            "deepseek_v3_moe", f, x, self.gate.weight,
            self.gate.e_score_correction_bias,
            self.experts.gate_up_proj, self.experts.down_proj,
            sh.gate_proj.weight, sh.up_proj.weight, sh.down_proj.weight)


class DeepseekV3DecoderLayer(Layer):
    def __init__(self, config: DeepseekV3Config, layer_idx: int):
        super().__init__()
        self.self_attn = DeepseekV3Attention(config)
        self.mlp = DeepseekV3MoE(config) \
            if layer_idx >= config.first_k_dense_replace \
            else DeepseekV3MLP(config, config.intermediate_size)
        self.input_layernorm = _norm(config, config.hidden_size)
        self.post_attention_layernorm = _norm(config, config.hidden_size)
        self._eps = config.rms_norm_eps

    def forward(self, h, cache=None, block_tables=None, cache_lens=None,
                ragged_meta=None):
        with component("norm"):
            a = F.rms_norm(h, self.input_layernorm.weight, self._eps)
        if cache is None:
            a = self.self_attn(a)
        else:
            a, cache = self.self_attn.forward_paged(
                a, cache, block_tables, cache_lens, ragged_meta)
        with component("norm"):     # the residual stream, then its norm
            h = h + a
            a = F.rms_norm(h, self.post_attention_layernorm.weight,
                           self._eps)
        a = self.mlp(a)
        with component("norm"):
            h = h + a
        return h if cache is None else (h, cache)


class DeepseekV3Model(Layer):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        self.embed_tokens = _Leaf(
            config, (config.vocab_size, config.hidden_size))
        self.layers = LayerList(
            [DeepseekV3DecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = _norm(config, config.hidden_size)

    def _final_norm(self, h):
        return F.rms_norm(h, self.norm.weight, self.config.rms_norm_eps)

    def forward(self, input_ids, caches=None, block_tables=None,
                cache_lens=None, ragged_meta=None):
        with component("embed"):
            h = F.embedding(input_ids, self.embed_tokens.weight)
        if caches is None:
            for layer in self.layers:
                h = layer(h)
            return self._final_norm(h)
        new_caches = []
        for i, (layer, cache) in enumerate(zip(self.layers, caches)):
            with component(f"L{i}.mla"):
                h, cache = layer(h, cache, block_tables, cache_lens,
                                 ragged_meta)
            new_caches.append(cache)
        with component("norm"):
            return self._final_norm(h), new_caches


class DeepseekV3ForCausalLM(Layer, GenerationMixin):
    def __init__(self, config: DeepseekV3Config):
        super().__init__()
        self.config = config
        self.model = DeepseekV3Model(config)
        if not config.tie_word_embeddings:
            self.lm_head = _linear(config, config.hidden_size,
                                   config.vocab_size)
        self.criterion = LlamaPretrainingCriterion()

    def _logits(self, h):
        if self.config.tie_word_embeddings:
            from ..ops.linalg import matmul
            return matmul(h, self.model.embed_tokens.weight,
                          transpose_y=True)
        return self.lm_head(h)

    def init_caches(self, batch_size: int, max_length: int):
        raise NotImplementedError(
            "DeepseekV3ForCausalLM keeps no dense cache: generate() over "
            "a latent cache is not built; serve it through "
            "ServingEngine (init_paged_caches)")

    def init_paged_caches(self, num_blocks: int, block_size: int,
                          sharding=None, kv_cache_dtype=None):
        """Zeroed per-layer ``(latent_pool,)`` — ONE array a layer,
        ``[num_blocks, block_size, lanes(kv_lora_rank +
        qk_rope_head_dim)]`` (``ops/paged_cache.init_latent_pool``)."""
        if sharding is not None:
            raise NotImplementedError(
                "tensor-parallel serving of a latent (MLA) cache: every "
                "head reads the one latent, so the kv-head pool "
                "sharding does not apply")
        if kv_cache_dtype is not None:
            raise NotImplementedError(
                "a quantized latent pool (kv_cache_dtype="
                f"{kv_cache_dtype!r}) is not built")
        from ..ops.paged_cache import init_latent_pool
        c = self.config
        return [init_latent_pool(
            num_blocks, block_size, c.kv_lora_rank + c.qk_rope_head_dim,
            jnp.dtype(c.dtype)) for _ in range(c.num_hidden_layers)]

    def forward(self, input_ids, labels=None, attention_mask=None,
                caches=None, offset=None, position_ids=None,
                block_tables=None, cache_lens=None, ragged_meta=None):
        if attention_mask is not None or position_ids is not None:
            raise NotImplementedError(
                "padded batches (attention_mask / position_ids)")
        if caches is not None:
            if block_tables is None:
                raise NotImplementedError("a dense (unpaged) cache")
            h, new_caches = self.model(
                input_ids, caches=caches, block_tables=block_tables,
                cache_lens=cache_lens, ragged_meta=ragged_meta)
            with component("head"):
                return self._logits(h), new_caches
        logits = self._logits(self.model(input_ids))
        return logits if labels is None \
            else self.criterion(logits, labels)

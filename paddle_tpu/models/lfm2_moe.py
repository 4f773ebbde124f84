"""LFM2-MoE model family (``model_type: lfm2_moe`` — LFM2-8B-A1B,
LFM2-24B-A2B): a decoder whose layers are of TWO kinds, picked per
layer by ``config.layer_types``.

- **``conv``** (``Lfm2ShortConv``): a gated short convolution. ``B, C,
  z = split3(u W_in)``; ``g = B * z``; ``c[t] = sum_j w[:, j] * g[t -
  (L - 1) + j]`` (depthwise, causal, one ``L = conv_L_cache``-tap
  filter a channel, ``g`` before the sequence's start is 0); ``out =
  (C * c) W_out``. Its whole memory of the past is the last ``L - 1``
  rows of ``g``: a constant ``[L - 1, hidden]`` a sequence a layer,
  whatever the context length. Over the serving engine's tick that
  memory is **slot state** (``ops/paged_cache.SlotState``): one table
  ``[num_slots + 1, L - 1, hidden]`` a layer, indexed by the SLOT and
  not by a block table — see ``forward_paged``.
- **``full_attention``** (``Lfm2Attention``): grouped-query attention
  with a per-head RMSNorm on q and k (``q_layernorm`` / ``k_layernorm``,
  learned ``[head_dim]`` weights) BEFORE rotate-half RoPE; head size
  ``hidden / heads`` (64 at the published widths: over the flat pool
  the ragged kernel reads two KV heads to a 128-lane tile,
  ``ops/pallas/paged_attention``).
- feed-forward: the first ``num_dense_layers`` layers a dense SwiGLU
  (``w1`` gate, ``w3`` up, ``w2`` down); the others ``num_experts``
  experts, top ``num_experts_per_tok``, no shared expert: ``s =
  sigmoid(u W_g)`` in float32, the choice on ``s + expert_bias``, the
  weights the chosen ``s`` over ``(their sum + 1e-6)`` times
  ``routed_scaling_factor`` — ``distributed/moe.group_limited_gate``
  with one group, then ``moe_share_dispatch_combine`` with every expert
  held.

Every layer: ``x += mixer(rms(x, operator_norm))``, ``x += ffn(rms(x,
ffn_norm))``; after the last ``rms(x, embedding_norm)`` and the head
(the embedding, tied). State-dict names follow the published
checkpoint's; the experts are stacked (``feed_forward.experts.
gate_up_proj [E, hidden, 2 f]``, gate columns then up columns, and
``down_proj [E, f, hidden]``) for the grouped matmuls, and the
depthwise filter is ``conv.conv.weight [hidden, L]`` (tap ``j``
multiplies ``g[t - (L - 1) + j]``). Every leaf is created in
``config.dtype`` (``models/deepseek_v3._param``). ``generate()``'s
dense cache, tensor-parallel serving, a quantized pool and speculation
over the slot state are not built; serving goes through
``ServingEngine``'s ragged tick.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import apply_jax, as_jax, component
from ..nn import functional as F
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..generation import GenerationMixin
from ..ops.short_conv import causal_taps, ragged_causal_taps
from .deepseek_v3 import _linear, _norm, _param, _rms, _swiglu
from .llama import (LlamaPretrainingCriterion, _rope_rotate,
                    ragged_paged_attention_decode)

__all__ = ["Lfm2MoeConfig", "Lfm2MoeModel", "Lfm2MoeForCausalLM"]

# the published pattern: conv, conv, full_attention, conv repeated
_PERIOD = ("conv", "conv", "full_attention", "conv")


@dataclass
class Lfm2MoeConfig:
    vocab_size: int = 65536
    hidden_size: int = 2048
    intermediate_size: int = 11776          # dense-layer FFN width
    moe_intermediate_size: int = 1536       # expert width
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    num_experts: int = 64
    num_experts_per_tok: int = 4
    num_dense_layers: int = 2
    # one of "conv" / "full_attention" a layer; None = the published
    # period repeated over num_hidden_layers
    layer_types: Optional[Tuple[str, ...]] = None
    conv_L_cache: int = 3
    conv_bias: bool = False
    norm_eps: float = 1e-5
    norm_topk_prob: bool = True
    use_expert_bias: bool = True
    routed_scaling_factor: float = 1.0
    max_position_embeddings: int = 128000
    rope_theta: float = 1e6
    tie_word_embeddings: bool = True
    initializer_range: float = 0.02
    # every expert is held and no pair is dropped (ServingEngine's gate)
    dropless: bool = True
    dtype: str = "float32"

    def __post_init__(self):
        if self.layer_types is None:
            self.layer_types = tuple(
                _PERIOD[i % len(_PERIOD)]
                for i in range(self.num_hidden_layers))
        self.layer_types = tuple(self.layer_types)
        if len(self.layer_types) != self.num_hidden_layers:
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers, "
                f"num_hidden_layers is {self.num_hidden_layers}")
        bad = set(self.layer_types) - {"conv", "full_attention"}
        if bad:
            raise ValueError(f"layer_types: unknown kinds {sorted(bad)}")
        if self.conv_bias:
            raise NotImplementedError("conv_bias: not published, not built")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("hidden_size is not a multiple of the heads")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @staticmethod
    def tiny(vocab=512, hidden=256, heads=4, kv_heads=2, dense_ffn=96,
             moe_ffn=32, experts=8, topk=2, dense_layers=1,
             layer_types=("conv", "full_attention", "conv", "conv",
                          "conv"), **kw):
        return Lfm2MoeConfig(
            vocab_size=vocab, hidden_size=hidden,
            intermediate_size=dense_ffn, moe_intermediate_size=moe_ffn,
            num_hidden_layers=len(layer_types),
            num_attention_heads=heads, num_key_value_heads=kv_heads,
            num_experts=experts, num_experts_per_tok=topk,
            num_dense_layers=dense_layers, layer_types=layer_types,
            max_position_embeddings=8192, **kw)


def _rope(x, pos, theta):
    """Rotate-half RoPE of ``x [R, heads, D]`` at positions ``pos
    [R]``."""
    d = x.shape[-1]
    inv = np.float32(theta) ** (-np.arange(0, d, 2, dtype=np.float32) / d)
    ang = pos.astype(jnp.float32)[:, None] * jnp.asarray(inv)[None]
    return _rope_rotate(x, jnp.cos(ang)[:, None, :],
                        jnp.sin(ang)[:, None, :])


# -- layers --------------------------------------------------------------------

class _Filter(Layer):
    """The depthwise filter's one leaf, ``weight [hidden, L]``."""

    def __init__(self, config):
        super().__init__()
        self.weight = _param(config, (config.hidden_size,
                                      config.conv_L_cache))


class Lfm2ShortConv(Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        h = config.hidden_size
        self.in_proj = _linear(config, h, 3 * h)
        self.conv = _Filter(config)
        self.out_proj = _linear(config, h, h)

    def _weights(self):
        return (self.in_proj.weight, self.conv.weight,
                self.out_proj.weight)

    def forward(self, x):
        """No cache: the whole sequence, ``x [B, T, hidden]``."""
        taps = self.config.conv_L_cache

        def f(x_a, w_in, w, w_out):
            b_, c_, z = jnp.split(x_a @ w_in, 3, axis=-1)
            g = b_ * z
            t = g.shape[1]
            gp = jnp.pad(g, ((0, 0), (taps - 1, 0), (0, 0)))
            conv = causal_taps(w, [gp[:, j:j + t] for j in range(taps)])
            return (c_ * conv.astype(x_a.dtype)) @ w_out

        return apply_jax("short_conv", f, x, *self._weights())

    def forward_paged(self, x, cache, ragged_meta):
        """Over the ragged tick's packed rows ``x [1, R, hidden]``;
        ``cache`` is the layer's ``(SlotState,)``: ``state[s]`` holds
        the last ``L - 1`` rows of ``g`` that slot ``s`` has seen. The
        taps over the packed rows and the state's gather and scatter
        are ``ops/short_conv.ragged_causal_taps`` (which rows read the
        state, which zeros, which the null seat): they and the gating
        are the component ``mixer.glue`` (the table's relayouts
        ``cache``), the two projections ``mixer.in`` and
        ``mixer.out``. Returns ``(out, cache)``."""
        from ..ops.paged_cache import SlotState

        def f(x_a, w_in, w, w_out, state, ql, rs, sl, pos):
            with component("mixer.in"):
                bcz = x_a[0] @ w_in
            with component("mixer.glue"):
                b_, c_, z = jnp.split(bcz, 3, axis=-1)
                conv, new = ragged_causal_taps(
                    b_ * z, state.data, w, (ql, rs, sl, pos))
                y = c_ * conv.astype(x_a.dtype)
            with component("mixer.out"):
                return (y @ w_out)[None], new

        ql, rs, sl, pos = ragged_meta[:4]
        out, state = apply_jax(
            "short_conv_paged", f, x, *self._weights(), cache[0], ql, rs,
            sl, pos, n_outputs=2)
        return out, (SlotState(as_jax(state)),)


class Lfm2Attention(Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        c = self.config = config
        h, d = c.hidden_size, c.head_dim
        self.q_proj = _linear(c, h, c.num_attention_heads * d)
        self.k_proj = _linear(c, h, c.num_key_value_heads * d)
        self.v_proj = _linear(c, h, c.num_key_value_heads * d)
        self.out_proj = _linear(c, c.num_attention_heads * d, h)
        self.q_layernorm = _norm(c, d)
        self.k_layernorm = _norm(c, d)

    def _weights(self):
        return (self.q_proj.weight, self.k_proj.weight,
                self.v_proj.weight, self.out_proj.weight,
                self.q_layernorm.weight, self.k_layernorm.weight)

    def _project(self, x, pos, wq, wk, wv, qn, kn):
        """``x [R, hidden]`` at positions ``pos [R]`` -> ``(q [R, H,
        D], k [R, H_kv, D], v [R, H_kv, D])``, q and k normed per head
        and then rotated."""
        c = self.config
        r, d = x.shape[0], c.head_dim
        with component("mixer.in"):
            q = x @ wq
        with component("mixer.glue"):
            q = _rms(q.reshape(r, c.num_attention_heads, d), qn,
                     c.norm_eps)
        with component("mixer.in"):
            k = x @ wk
        with component("mixer.glue"):
            k = _rms(k.reshape(r, c.num_key_value_heads, d), kn,
                     c.norm_eps)
        with component("mixer.in"):
            v = (x @ wv).reshape(r, c.num_key_value_heads, d)
        with component("mixer.glue"):
            return (_rope(q, pos, c.rope_theta),
                    _rope(k, pos, c.rope_theta), v)

    def forward(self, x):
        """No cache: plain causal softmax over ``x [B, T, hidden]``."""
        c = self.config
        b, t, _ = x.shape
        h, hkv, d = c.num_attention_heads, c.num_key_value_heads, \
            c.head_dim

        def f(x_a, wq, wk, wv, wo, qn, kn):
            pos = jnp.tile(jnp.arange(t, dtype=jnp.int32), b)
            q, k, v = self._project(x_a.reshape(b * t, -1), pos, wq, wk,
                                    wv, qn, kn)
            q = q.reshape(b, t, hkv, h // hkv, d)
            k = k.reshape(b, t, hkv, d)
            v = v.reshape(b, t, hkv, d)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                           preferred_element_type=jnp.float32) \
                * np.float32(d ** -0.5)
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(x_a.dtype)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v)
            return o.reshape(b, t, h * d) @ wo

        return apply_jax("lfm2_attention", f, x, *self._weights())

    def forward_paged(self, x, cache, block_tables, cache_lens,
                      ragged_meta):
        """Over the paged ``(k_pool, v_pool)``: the ragged tick's packed
        rows ``x [1, R, hidden]`` laid out by ``ragged_meta``. Returns
        ``(out, cache)``."""
        c = self.config
        r = x.shape[1]

        def f(x_a, wq, wk, wv, wo, qn, kn, kp, vp, tables, lens, ql, rs,
              sl, pos_r, nwin, win):
            with component("mixer.glue"):
                pos = jnp.clip(pos_r.astype(jnp.int32), 0,
                               c.max_position_embeddings - 1)
            q, k, v = self._project(x_a[0], pos, wq, wk, wv, qn, kn)
            with component("mixer.glue"):
                o, kp2, vp2 = ragged_paged_attention_decode(
                    q, k, v, kp, vp, tables, lens, ql, rs, sl, pos_r,
                    nwin, win, c.head_dim)
            with component("mixer.out"):
                return (o.reshape(1, r, -1) @ wo), kp2, vp2

        out, kp, vp = apply_jax(
            "lfm2_attention_paged", f, x, *self._weights(), cache[0],
            cache[1], block_tables, cache_lens, *ragged_meta,
            n_outputs=3)
        return out, (kp, vp)


class Lfm2MLP(Layer):
    """The dense SwiGLU of the leading layers: ``w1`` gate, ``w3`` up,
    ``w2`` down."""

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        h, f = config.hidden_size, config.intermediate_size
        self.w1 = _linear(config, h, f)
        self.w3 = _linear(config, h, f)
        self.w2 = _linear(config, f, h)

    def forward(self, x):
        with component("ffn"):
            return apply_jax("swiglu_mlp", _swiglu, x, self.w1.weight,
                             self.w3.weight, self.w2.weight)


class _Router(Layer):
    """The router's one matrix, ``weight [hidden, num_experts]``."""

    def __init__(self, config):
        super().__init__()
        self.weight = _param(config, (config.hidden_size,
                                      config.num_experts))


class _Experts(Layer):
    """All experts as stacked leaves (``DeepseekV3Experts``' layout)."""

    def __init__(self, config):
        super().__init__()
        e, h = config.num_experts, config.hidden_size
        f = config.moe_intermediate_size
        self.gate_up_proj = _param(config, (e, h, 2 * f))
        self.down_proj = _param(config, (e, f, h))


class Lfm2MoeSparseBlock(Layer):
    """Router (float32), choice bias, every expert held."""

    # the published normaliser: weights / (their sum + 1e-6)
    NORM_EPS = 1e-6

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.gate = _Router(config)
        self.expert_bias = _param(config, (config.num_experts,))
        self.experts = _Experts(config)

    def forward(self, x):
        from ..distributed.moe import (group_limited_gate,
                                       moe_share_dispatch_combine)
        c = self.config

        def f(x_a, wg, bias, gate_up, down):
            x2 = x_a.reshape(-1, x_a.shape[-1])
            # float32 as the GigaChat configuration's gate: on a TPU
            # that takes the highest matmul precision, not bf16 passes
            with component("moe.gate"):
                logits = jnp.matmul(x2.astype(jnp.float32),
                                    wg.astype(jnp.float32),
                                    precision=jax.lax.Precision.HIGHEST)
                if not c.use_expert_bias:
                    bias = jnp.zeros_like(bias)
            idx, w = group_limited_gate(
                logits, bias, n_group=1, topk_group=1,
                top_k=c.num_experts_per_tok,
                norm_topk_prob=c.norm_topk_prob,
                routed_scaling_factor=c.routed_scaling_factor,
                eps=self.NORM_EPS)
            y = moe_share_dispatch_combine(
                x2, idx, w, gate_up, down, first=0,
                num_expert=c.num_experts)
            return y.reshape(x_a.shape)

        return apply_jax(
            "lfm2_moe", f, x, self.gate.weight, self.expert_bias,
            self.experts.gate_up_proj, self.experts.down_proj)


class Lfm2MoeDecoderLayer(Layer):
    def __init__(self, config: Lfm2MoeConfig, layer_idx: int):
        super().__init__()
        self.is_attention = config.layer_types[layer_idx] \
            == "full_attention"
        if self.is_attention:
            self.self_attn = Lfm2Attention(config)
        else:
            self.conv = Lfm2ShortConv(config)
        self.feed_forward = Lfm2MLP(config) \
            if layer_idx < config.num_dense_layers \
            else Lfm2MoeSparseBlock(config)
        self.operator_norm = _norm(config, config.hidden_size)
        self.ffn_norm = _norm(config, config.hidden_size)
        self._eps = config.norm_eps

    def forward(self, h, cache=None, block_tables=None, cache_lens=None,
                ragged_meta=None):
        with component("norm"):
            a = F.rms_norm(h, self.operator_norm.weight, self._eps)
        if cache is None:
            a = self.self_attn(a) if self.is_attention else self.conv(a)
        elif self.is_attention:
            a, cache = self.self_attn.forward_paged(
                a, cache, block_tables, cache_lens, ragged_meta)
        else:
            a, cache = self.conv.forward_paged(a, cache, ragged_meta)
        with component("norm"):     # the residual stream, then its norm
            h = h + a
            a = F.rms_norm(h, self.ffn_norm.weight, self._eps)
        a = self.feed_forward(a)
        with component("norm"):
            h = h + a
        return h if cache is None else (h, cache)


class Lfm2MoeModel(Layer):
    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = _linear(config, config.vocab_size,
                                    config.hidden_size)
        self.layers = LayerList(
            [Lfm2MoeDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.embedding_norm = _norm(config, config.hidden_size)

    def forward(self, input_ids, caches=None, block_tables=None,
                cache_lens=None, ragged_meta=None):
        with component("embed"):
            h = F.embedding(input_ids, self.embed_tokens.weight)
        new_caches = []
        for i, layer in enumerate(self.layers):
            if caches is None:
                h = layer(h)
                continue
            with component(f"L{i}.{self.config.layer_types[i]}"):
                h, cache = layer(h, caches[i], block_tables, cache_lens,
                                 ragged_meta)
            new_caches.append(cache)
        with component("norm"):
            h = F.rms_norm(h, self.embedding_norm.weight,
                           self.config.norm_eps)
        return h if caches is None else (h, new_caches)


class Lfm2MoeForCausalLM(Layer, GenerationMixin):
    # the conv layers' cache entries are slot state, so
    # ``init_paged_caches`` wants the engine's ``num_slots``
    paged_slot_state = True

    def __init__(self, config: Lfm2MoeConfig):
        super().__init__()
        self.config = config
        self.model = Lfm2MoeModel(config)
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
            "Lfm2MoeForCausalLM keeps no dense cache: generate() over "
            "the convolution state is not built; serve it through "
            "ServingEngine (init_paged_caches)")

    def init_paged_caches(self, num_blocks: int, block_size: int,
                          sharding=None, kv_cache_dtype=None,
                          num_slots=None):
        """Per layer, zeroed: an attention layer's paged ``(k_pool,
        v_pool)``, flat (``ops/paged_cache.init_flat_pool``: the view
        the ragged kernel reads, two 64-lane heads to a tile), a
        ``conv`` layer's
        ``(SlotState,)`` of ``[num_slots + 1, L - 1, hidden]`` — the
        last row the null seat (``ops/paged_cache.init_slot_state``)."""
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
                "num_slots (the convolution state is a row a slot)")
        from ..ops.paged_cache import init_flat_pool, init_slot_state
        c = self.config
        dtype = jnp.dtype(c.dtype)
        return [init_flat_pool(num_blocks, block_size,
                               c.num_key_value_heads, c.head_dim, dtype)
                if kind == "full_attention"
                else init_slot_state(
                    num_slots, (c.conv_L_cache - 1, c.hidden_size), dtype)
                for kind in c.layer_types]

    def forward(self, input_ids, labels=None, attention_mask=None,
                caches=None, offset=None, position_ids=None,
                block_tables=None, cache_lens=None, ragged_meta=None):
        if attention_mask is not None or position_ids is not None:
            raise NotImplementedError(
                "padded batches (attention_mask / position_ids)")
        if caches is not None:
            if block_tables is None or ragged_meta is None:
                raise NotImplementedError(
                    "a dense cache, or the per-width paged step: the "
                    "convolution state is carried by the ragged tick")
            h, new_caches = self.model(
                input_ids, caches=caches, block_tables=block_tables,
                cache_lens=cache_lens, ragged_meta=ragged_meta)
            with component("head"):
                return self._logits(h), new_caches
        logits = self._logits(self.model(input_ids))
        return logits if labels is None \
            else self.criterion(logits, labels)

"""Solar-Open2 model family (``model_type: solar_open2`` —
Solar-Open2-250B): a decoder whose mixers are of TWO kinds, picked per
layer by ``config.gqa_layers`` (published: one ``gqa`` layer, then
three ``kda`` layers, repeated), with an expert block in EVERY layer
(``first_k_dense_replace`` 0).

- **``kda``** (``KimiDeltaAttention``; Kimi Delta Attention,
  arXiv:2510.26692 — the gated delta rule with a decay per channel).
  With ``u`` the normed input, per head of ``linear_attn_config``:
  ``q = l2norm(silu(conv(u W_q))) d^-0.5``, ``k = l2norm(silu(conv(u
  W_k)))``, ``v = silu(conv(u W_v))`` (``conv`` depthwise, causal,
  ``short_conv_kernel_size`` taps a channel); the log-decay ``g =
  -exp(A_log) softplus(u W_f1 W_f2 + dt_bias)`` per channel; ``beta = 2
  sigmoid(u W_b)`` per head (``kda_allow_neg_eigval``; 1 x sigmoid
  otherwise); the state ``S_t = (I - beta k k^T) Diag(exp(g)) S_{t-1} +
  beta k v^T``, ``o = S^T q`` (``ops/pallas/delta_rule``); ``out =
  (rms_head(o) * sigmoid(u W_g1 W_g2)) W_o``. Its whole memory of the
  past is **slot state** of two kinds (``ops/paged_cache.SlotState``):
  the convolution's last ``taps - 1`` inputs ``[S + 1, taps - 1, 3 H
  d]`` in the model's dtype, and the matrix state ``[S + 1, H, d, d]``
  in float32 (``S^T`` a head: value-major).
- **``gqa``** (``SolarGatedAttention``): grouped-query softmax
  attention with NO positional encoding (``use_rope`` false), scale
  ``head_dim^-0.5``, and an elementwise sigmoid gate before ``o_proj``
  (``use_gqa_gate``; ``g_proj [hidden, heads x head_dim]``), over the
  flat paged pool.
- expert block (``models/deepseek_v3.DeepseekV3MoE`` with one group):
  ``s = sigmoid(u W_r)`` in float32, the choice the top
  ``num_experts_per_tok`` of ``s + e_score_correction_bias``, the
  weights the chosen ``s`` over their sum times
  ``routed_scaling_factor``; the routed experts held here are
  ``expert_first .. expert_first + expert_count`` of the
  ``n_routed_experts`` the gate routes over
  (``distributed/moe.moe_share_dispatch_combine``); one shared expert of
  ``n_shared_experts x moe_intermediate_size`` is added for every row.

Every layer: ``x += mixer(rms(x, input_layernorm))``, ``x += moe(rms(x,
post_attention_layernorm))``; after the last ``rms(x, norm)`` and the
untied head. State-dict names: ``model.layers.N.self_attn.{q,k,v,o,g}
_proj``; ``model.layers.N.linear_attn.{q,k,v,o}_proj``,
``{q,k,v}_conv1d.weight [H d, taps]`` (tap ``j`` multiplies the input
``taps - 1 - j`` rows back), ``f_a_proj`` / ``f_b_proj``, ``g_a_proj``
/ ``g_b_proj``, ``b_proj``, ``A_log [H]``, ``dt_bias [H d]``,
``o_norm.weight [d]``; ``mlp.gate.weight``,
``mlp.gate.e_score_correction_bias``, ``mlp.shared_experts.*``, the
experts stacked ``mlp.experts.gate_up_proj [held, hidden, 2 f]`` /
``down_proj [held, f, hidden]``. Every leaf is created in
``config.dtype``. ``generate()``'s dense cache, tensor-parallel
serving, a quantized pool and speculation over the slot state are not
built; serving goes through ``ServingEngine``'s ragged tick.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import apply_jax, as_jax, component
from ..nn import functional as F
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..generation import GenerationMixin
from ..ops.pallas.delta_rule import kda_step
from ..ops.short_conv import causal_taps, ragged_causal_taps
from .deepseek_v3 import (DeepseekV3MoE, _Leaf, _linear, _norm, _param,
                          _rms)
from .llama import LlamaPretrainingCriterion, ragged_paged_attention_decode

__all__ = ["SolarOpen2Config", "SolarOpen2Model", "SolarOpen2ForCausalLM"]

L2_EPS = 1e-6


def _linear_attn_config():
    return {"short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
            "num_kv_heads": None}


@dataclass
class SolarOpen2Config:
    vocab_size: int = 196608
    hidden_size: int = 4096
    intermediate_size: int = 10240          # read by no layer: none is dense
    moe_intermediate_size: int = 1280       # expert (and shared) width
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    # the layers whose mixer is gqa; None = every (gqa_interval + 1)-th
    gqa_layers: Optional[Tuple[int, ...]] = None
    gqa_interval: int = 3
    linear_attn_config: dict = field(default_factory=_linear_attn_config)
    # width of the low-rank decay and gate projections; None = the
    # linear-attention head size
    kda_low_rank: Optional[int] = None
    kda_use_full_proj: bool = False
    kda_allow_neg_eigval: bool = True
    use_rope: bool = False
    use_gqa_gate: bool = True
    n_routed_experts: int = 320             # the gate's width
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    first_k_dense_replace: int = 0
    max_position_embeddings: int = 1048576
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    # one chip's share of an expert-parallel layer (models/deepseek_v3)
    expert_first: int = 0
    expert_count: Optional[int] = None
    dropless: bool = True
    dtype: str = "float32"

    # the router is the one-group case of group_limited_gate
    n_group = 1
    topk_group = 1

    def __post_init__(self):
        if self.gqa_layers is None:
            self.gqa_layers = tuple(range(0, self.num_hidden_layers,
                                          self.gqa_interval + 1))
        self.gqa_layers = tuple(int(i) for i in self.gqa_layers)
        if any(not 0 <= i < self.num_hidden_layers
               for i in self.gqa_layers):
            raise ValueError(f"gqa_layers {self.gqa_layers}: not layers "
                             f"of {self.num_hidden_layers}")
        for key, why in (("use_rope", "rotary gqa layers"),
                         ("kda_use_full_proj", "full-rank decay and gate"),
                         ("first_k_dense_replace", "leading dense layers"),
                         ("tie_word_embeddings", "a tied head")):
            if getattr(self, key):
                raise NotImplementedError(f"{key}: {why} are not "
                                          "published, not built")
        la = self.linear_attn_config
        if la.get("num_kv_heads") not in (None, la["num_heads"]):
            raise NotImplementedError(
                "linear_attn_config.num_kv_heads: fewer key than query "
                "heads is not published, not built")

    @property
    def experts_held(self) -> int:
        return self.n_routed_experts if self.expert_count is None \
            else int(self.expert_count)

    @property
    def kda_heads(self) -> int:
        return int(self.linear_attn_config["num_heads"])

    @property
    def kda_head_dim(self) -> int:
        return int(self.linear_attn_config["head_dim"])

    @property
    def kda_taps(self) -> int:
        return int(self.linear_attn_config["short_conv_kernel_size"])

    @property
    def kda_rank(self) -> int:
        return int(self.kda_low_rank or self.kda_head_dim)

    def is_gqa(self, layer_idx: int) -> bool:
        return layer_idx in self.gqa_layers

    @staticmethod
    def tiny(vocab=512, hidden=64, layers=8, heads=4, kv_heads=2,
             head_dim=16, kda_heads=4, kda_head_dim=16, moe_ffn=32,
             experts=16, topk=2, **kw):
        return SolarOpen2Config(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=96,
            moe_intermediate_size=moe_ffn, num_hidden_layers=layers,
            num_attention_heads=heads, num_key_value_heads=kv_heads,
            head_dim=head_dim, n_routed_experts=experts,
            num_experts_per_tok=topk, max_position_embeddings=8192,
            linear_attn_config={"short_conv_kernel_size": 4,
                                "head_dim": kda_head_dim,
                                "num_heads": kda_heads,
                                "num_kv_heads": None}, **kw)


def _l2norm(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(
        jnp.sum(x * x, axis=-1, keepdims=True) + np.float32(L2_EPS))


# -- the two mixers ------------------------------------------------------------

class KimiDeltaAttention(Layer):
    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        c = self.config = config
        h, hd, rank = c.hidden_size, c.kda_heads * c.kda_head_dim, \
            c.kda_rank
        self.q_proj = _linear(c, h, hd)
        self.k_proj = _linear(c, h, hd)
        self.v_proj = _linear(c, h, hd)
        # the depthwise filters, ``weight [channels, taps]``
        self.q_conv1d = _Leaf(c, (hd, c.kda_taps))
        self.k_conv1d = _Leaf(c, (hd, c.kda_taps))
        self.v_conv1d = _Leaf(c, (hd, c.kda_taps))
        self.f_a_proj = _linear(c, h, rank)
        self.f_b_proj = _linear(c, rank, hd)
        self.g_a_proj = _linear(c, h, rank)
        self.g_b_proj = _linear(c, rank, hd)
        self.b_proj = _linear(c, h, c.kda_heads)
        self.A_log = _param(c, (c.kda_heads,))
        self.dt_bias = _param(c, (hd,))
        self.o_norm = _norm(c, c.kda_head_dim)
        self.o_proj = _linear(c, hd, h)

    def _weights(self):
        return (self.q_proj.weight, self.k_proj.weight, self.v_proj.weight,
                self.q_conv1d.weight, self.k_conv1d.weight,
                self.v_conv1d.weight, self.f_a_proj.weight,
                self.f_b_proj.weight, self.g_a_proj.weight,
                self.g_b_proj.weight, self.b_proj.weight, self.A_log,
                self.dt_bias, self.o_norm.weight, self.o_proj.weight)

    def _inputs(self, u, wq, wk, wv):
        """The convolution's input: ``u W_q | u W_k | u W_v``."""
        with component("mixer.in"):
            return jnp.concatenate([u @ wq, u @ wk, u @ wv], axis=-1)

    def _operands(self, u, conv, wfa, wfb, wb, a_log, dt_bias):
        """``(q, k, v, g, beta)`` of rows ``u [..., hidden]`` from the
        convolution's float32 output ``conv [..., 3 H d]``: float32,
        heads split out."""
        c = self.config
        heads, d = c.kda_heads, c.kda_head_dim
        lead = u.shape[:-1]
        with component("mixer.glue"):
            q, k, v = (x.reshape(lead + (heads, d)) for x in jnp.split(
                jax.nn.silu(conv), 3, axis=-1))
            q = _l2norm(q) * np.float32(d ** -0.5)
            k = _l2norm(k)
        with component("mixer.in"):     # the decay's low-rank pair
            dt = (u @ wfa) @ wfb
        with component("mixer.glue"):
            dt = dt.astype(jnp.float32) + dt_bias.astype(jnp.float32)
            g = -jnp.exp(a_log.astype(jnp.float32))[:, None] \
                * jax.nn.softplus(dt).reshape(lead + (heads, d))
        with component("mixer.in"):
            beta = u @ wb
        with component("mixer.glue"):
            beta = jax.nn.sigmoid(beta.astype(jnp.float32))
            if c.kda_allow_neg_eigval:
                beta = beta * np.float32(2.0)
            return q, k, v, g, beta

    def _output(self, u, o, wga, wgb, o_norm, wo):
        """``(rms_head(o) * sigmoid(u W_g1 W_g2)) W_o``."""
        with component("mixer.in"):     # the gate's low-rank pair
            gate = (u @ wga) @ wgb
        with component("mixer.glue"):
            gate = jax.nn.sigmoid(gate.astype(jnp.float32))
            o = _rms(o, o_norm.astype(jnp.float32),
                     self.config.rms_norm_eps)
            y = (o.reshape(gate.shape) * gate).astype(u.dtype)
        with component("mixer.out"):
            return y @ wo

    def forward(self, x):
        """No cache: the whole sequence ``x [B, T, hidden]``, the
        recurrence a scan over its tokens."""
        taps = self.config.kda_taps

        def f(x_a, wq, wk, wv, cq, ck, cv, wfa, wfb, wga, wgb, wb, a_log,
              dt_bias, o_norm, wo):
            t = x_a.shape[1]
            pre = jnp.pad(self._inputs(x_a, wq, wk, wv),
                          ((0, 0), (taps - 1, 0), (0, 0)))
            conv = causal_taps(jnp.concatenate([cq, ck, cv]),
                               [pre[:, j:j + t] for j in range(taps)])
            q, k, v, g, beta = self._operands(x_a, conv, wfa, wfb, wb,
                                              a_log, dt_bias)

            def step(s, row):
                q_t, k_t, v_t, g_t, b_t = row
                s = s * jnp.exp(g_t)[..., None]
                pred = jnp.sum(s * k_t[..., None], axis=-2)
                s = s + k_t[..., None] \
                    * (b_t[..., None] * (v_t - pred))[..., None, :]
                return s, jnp.sum(s * q_t[..., None], axis=-2)

            s0 = jnp.zeros(q.shape[:1] + q.shape[2:] + v.shape[-1:],
                           jnp.float32)
            _, o = jax.lax.scan(step, s0, tuple(
                jnp.swapaxes(a, 0, 1) for a in (q, k, v, g, beta)))
            return self._output(x_a, jnp.swapaxes(o, 0, 1), wga, wgb,
                                o_norm, wo)

        return apply_jax("kda", f, x, *self._weights())

    def forward_paged(self, x, cache, ragged_meta):
        """Over the ragged tick's packed rows ``x [1, R, hidden]``;
        ``cache`` is the layer's ``(SlotState, SlotState)``: the
        convolution's last ``taps - 1`` inputs a slot
        (``ops/short_conv.ragged_causal_taps``) and the matrix state a
        slot (``ops/pallas/delta_rule.kda_step``). Returns ``(out,
        cache)``."""
        from ..ops.paged_cache import SlotState

        def f(x_a, wq, wk, wv, cq, ck, cv, wfa, wfb, wga, wgb, wb, a_log,
              dt_bias, o_norm, wo, taps_state, rec_state, *meta):
            u = x_a[0]
            pre = self._inputs(u, wq, wk, wv)
            with component("mixer.glue"):
                conv, taps_new = ragged_causal_taps(
                    pre, taps_state.data, jnp.concatenate([cq, ck, cv]),
                    meta)
            q, k, v, g, beta = self._operands(u, conv, wfa, wfb, wb,
                                              a_log, dt_bias)
            with component("mixer.glue"):
                o, rec_new = kda_step(q, k, v, g, beta, rec_state.data,
                                      meta)
            return (self._output(u, o, wga, wgb, o_norm, wo)[None],
                    taps_new, rec_new)

        out, taps_new, rec_new = apply_jax(
            "kda_paged", f, x, *self._weights(), cache[0], cache[1],
            *ragged_meta, n_outputs=3)
        return out, (SlotState(as_jax(taps_new)),
                     SlotState(as_jax(rec_new)))


class SolarGatedAttention(Layer):
    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        c = self.config = config
        h, d = c.hidden_size, c.head_dim
        self.q_proj = _linear(c, h, c.num_attention_heads * d)
        self.k_proj = _linear(c, h, c.num_key_value_heads * d)
        self.v_proj = _linear(c, h, c.num_key_value_heads * d)
        self.g_proj = _linear(c, h, c.num_attention_heads * d)
        self.o_proj = _linear(c, c.num_attention_heads * d, h)

    def _weights(self):
        return (self.q_proj.weight, self.k_proj.weight, self.v_proj.weight,
                self.g_proj.weight, self.o_proj.weight)

    def _gated(self, u, o, wg, wo):
        """``(attn * sigmoid(u W_gate)) W_o``; without ``use_gqa_gate``
        plain ``attn W_o``."""
        o = o.reshape(u.shape[:-1] + (-1,))
        if self.config.use_gqa_gate:
            with component("mixer.in"):
                gate = u @ wg
            with component("mixer.glue"):
                gate = jax.nn.sigmoid(gate.astype(jnp.float32))
                o = (o.astype(jnp.float32) * gate).astype(u.dtype)
        with component("mixer.out"):
            return o @ wo

    def forward(self, x):
        """No cache: plain causal softmax over ``x [B, T, hidden]``, no
        positional encoding."""
        c = self.config
        b, t, _ = x.shape
        h, hkv, d = c.num_attention_heads, c.num_key_value_heads, \
            c.head_dim

        def f(x_a, wq, wk, wv, wg, wo):
            q = (x_a @ wq).reshape(b, t, hkv, h // hkv, d)
            k = (x_a @ wk).reshape(b, t, hkv, d)
            v = (x_a @ wv).reshape(b, t, hkv, d)
            s = jnp.einsum("bqgrd,bkgd->bgrqk", q, k,
                           preferred_element_type=jnp.float32) \
                * np.float32(d ** -0.5)
            s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -1e30)
            p = jax.nn.softmax(s, axis=-1).astype(x_a.dtype)
            o = jnp.einsum("bgrqk,bkgd->bqgrd", p, v)
            return self._gated(x_a, o, wg, wo)

        return apply_jax("solar_gated_attention", f, x, *self._weights())

    def forward_paged(self, x, cache, block_tables, cache_lens,
                      ragged_meta):
        """Over the paged ``(k_pool, v_pool)``: the ragged tick's packed
        rows ``x [1, R, hidden]``. Returns ``(out, cache)``."""
        c = self.config
        r, d = x.shape[1], c.head_dim

        def f(x_a, wq, wk, wv, wg, wo, kp, vp, tables, lens, ql, rs, sl,
              pos_r, nwin, win):
            u = x_a[0]
            with component("mixer.in"):
                q = (u @ wq).reshape(r, c.num_attention_heads, d)
                k = (u @ wk).reshape(r, c.num_key_value_heads, d)
                v = (u @ wv).reshape(r, c.num_key_value_heads, d)
            with component("mixer.glue"):
                o, kp2, vp2 = ragged_paged_attention_decode(
                    q, k, v, kp, vp, tables, lens, ql, rs, sl, pos_r,
                    nwin, win, d)
            return self._gated(u, o, wg, wo)[None], kp2, vp2

        out, kp, vp = apply_jax(
            "solar_gated_attention_paged", f, x, *self._weights(),
            cache[0], cache[1], block_tables, cache_lens, *ragged_meta,
            n_outputs=3)
        return out, (kp, vp)


# -- the decoder ---------------------------------------------------------------

class SolarOpen2DecoderLayer(Layer):
    def __init__(self, config: SolarOpen2Config, layer_idx: int):
        super().__init__()
        self.is_gqa = config.is_gqa(layer_idx)
        if self.is_gqa:
            self.self_attn = SolarGatedAttention(config)
        else:
            self.linear_attn = KimiDeltaAttention(config)
        self.mlp = DeepseekV3MoE(config)
        self.input_layernorm = _norm(config, config.hidden_size)
        self.post_attention_layernorm = _norm(config, config.hidden_size)
        self._eps = config.rms_norm_eps

    def forward(self, h, cache=None, block_tables=None, cache_lens=None,
                ragged_meta=None):
        with component("norm"):
            a = F.rms_norm(h, self.input_layernorm.weight, self._eps)
        if cache is None:
            a = self.self_attn(a) if self.is_gqa else self.linear_attn(a)
        elif self.is_gqa:
            a, cache = self.self_attn.forward_paged(
                a, cache, block_tables, cache_lens, ragged_meta)
        else:
            a, cache = self.linear_attn.forward_paged(a, cache,
                                                      ragged_meta)
        with component("norm"):     # the residual stream, then its norm
            h = h + a
            a = F.rms_norm(h, self.post_attention_layernorm.weight,
                           self._eps)
        a = self.mlp(a)
        with component("norm"):
            h = h + a
        return h if cache is None else (h, cache)


class SolarOpen2Model(Layer):
    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        self.config = config
        self.embed_tokens = _linear(config, config.vocab_size,
                                    config.hidden_size)
        self.layers = LayerList(
            [SolarOpen2DecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = _norm(config, config.hidden_size)

    def forward(self, input_ids, caches=None, block_tables=None,
                cache_lens=None, ragged_meta=None):
        with component("embed"):
            h = F.embedding(input_ids, self.embed_tokens.weight)
        new_caches = []
        for i, layer in enumerate(self.layers):
            if caches is None:
                h = layer(h)
                continue
            with component(f"L{i}.{'gqa' if layer.is_gqa else 'kda'}"):
                h, cache = layer(h, caches[i], block_tables, cache_lens,
                                 ragged_meta)
            new_caches.append(cache)
        with component("norm"):
            h = F.rms_norm(h, self.norm.weight, self.config.rms_norm_eps)
        return h if caches is None else (h, new_caches)


class SolarOpen2ForCausalLM(Layer, GenerationMixin):
    # the kda layers' cache entries are slot state, so
    # ``init_paged_caches`` wants the engine's ``num_slots``
    paged_slot_state = True
    # what the engine's ``tick`` span calls the seats advanced one row
    # and the rows through the chunked form (docs/OPS.md "Tick phases")
    paged_scan_state = "kda"

    def __init__(self, config: SolarOpen2Config):
        super().__init__()
        self.config = config
        self.model = SolarOpen2Model(config)
        self.lm_head = _linear(config, config.hidden_size,
                               config.vocab_size)
        self.criterion = LlamaPretrainingCriterion()

    def init_caches(self, batch_size: int, max_length: int):
        raise NotImplementedError(
            "SolarOpen2ForCausalLM keeps no dense cache: generate() over "
            "the recurrent state is not built; serve it through "
            "ServingEngine (init_paged_caches)")

    def init_paged_caches(self, num_blocks: int, block_size: int,
                          sharding=None, kv_cache_dtype=None,
                          num_slots=None):
        """Per layer, zeroed: a ``gqa`` layer's paged ``(k_pool,
        v_pool)`` (flat where the kv heads fill whole lane tiles,
        ``ops/paged_cache.init_flat_pool``), a ``kda`` layer's
        ``(SlotState, SlotState)``: the convolution's ``[num_slots + 1,
        taps - 1, 3 H d]`` in the model's dtype and the matrix state
        ``[num_slots + 1, H, d, d]`` in float32, the last row of each
        the null seat."""
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
                "num_slots (the recurrent state is a row a slot)")
        from ..ops.paged_cache import (init_flat_pool, init_pool,
                                       init_slot_state)
        c = self.config
        dtype = jnp.dtype(c.dtype)
        heads, d = c.kda_heads, c.kda_head_dim
        flat = (c.num_key_value_heads * c.head_dim) % 128 == 0
        paged = init_flat_pool if flat else init_pool
        return [paged(num_blocks, block_size, c.num_key_value_heads,
                      c.head_dim, dtype) if c.is_gqa(i)
                else init_slot_state(num_slots, (c.kda_taps - 1,
                                                 3 * heads * d), dtype)
                + init_slot_state(num_slots, (heads, d, d), jnp.float32)
                for i in range(c.num_hidden_layers)]

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
                    "recurrent state is carried by the ragged tick")
            h, new_caches = self.model(
                input_ids, caches=caches, block_tables=block_tables,
                cache_lens=cache_lens, ragged_meta=ragged_meta)
            with component("head"):
                return self.lm_head(h), new_caches
        logits = self.lm_head(self.model(input_ids))
        return logits if labels is None \
            else self.criterion(logits, labels)

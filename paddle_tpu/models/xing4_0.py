"""Xing4.0 (``model_type: xing4_0``, XingChen-AGI): the DeepSeek-V3 block —
latent attention over a paged latent cache, a sigmoid ``noaux_tc`` router
over fine-grained experts, one shared expert — whose residual stream is
``hc_mult`` streams joined at every sublayer by a manifold-constrained
hyper-connection (mHC, arXiv:2512.24880).

A decoder layer carries the streams ``x [rows, n * hidden]`` in the
model's dtype. Each of its two sublayers (attention, then the dense FFN
or the expert block) reads ``u = sum_i H_pre[i] x[i]`` through the
layer's own RMSNorm, and writes ``x'[i] = sum_j H_res[i, j] x[j] +
H_post[i] y`` — the coefficients a row's own, from ``x`` itself
(``ops/pallas/hyper_connection.py``: one ``hc_mix`` call a connection
boundary, the Mosaic kernel on a TPU). The embedding is copied into
every stream; after the last layer the streams are summed, then the
final norm and the head run.

Attention, experts, the dense FFN, YaRN and the leaf helpers are
``deepseek_v3``'s, imported: nothing of them is copied. Serving goes
through ``ServingEngine``'s ragged tick over the latent pool
(``init_paged_caches``), as DeepSeek-V3's does; the tick's ``hc_rows``
are ``hc_connections`` a live row. Multi-token prediction
(``num_nextn_predict_layers``) is not built, as in ``deepseek_v3``.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Optional

from ..framework.core import apply_jax, component
from ..nn import functional as F
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..ops.pallas.hyper_connection import hc_mix, n_coef
from .deepseek_v3 import (DeepseekV3Attention, DeepseekV3Config,
                          DeepseekV3ForCausalLM, DeepseekV3MLP,
                          DeepseekV3MoE, _Leaf, _norm, _param)
from .llama import LlamaPretrainingCriterion

__all__ = ["Xing40Config", "Xing40Model", "Xing40ForCausalLM"]

_YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 64, "mscale": 1,
         "mscale_all_dim": 1, "original_max_position_embeddings": 4096,
         "type": "yarn"}


@dataclass
class Xing40Config(DeepseekV3Config):
    """The published ``Xing4.0-29B-A4B`` by default."""
    vocab_size: int = 131072
    hidden_size: int = 3584
    intermediate_size: int = 9216
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 2.0
    first_k_dense_replace: int = 2
    max_position_embeddings: int = 262144
    rope_scaling: Optional[dict] = field(default_factory=lambda: dict(_YARN))
    # the hyper-connections
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0


class Xing40HyperConnection(Layer):
    """One connection's leaves: ``fn [2n + n^2, n * hidden]`` (the rows
    of ``pre``, ``post``, ``res``), ``base [2n + n^2]``, ``scale [3]``."""

    def __init__(self, config: Xing40Config):
        super().__init__()
        n, k = config.hc_mult, n_coef(config.hc_mult)
        self.fn = _param(config, (k, n * config.hidden_size))
        self.base = _param(config, (k,))
        self.scale = _param(config, (3,), ones=True)


def _connect(config, x, y, coef, hc, first=False, last=False):
    """One ``hc_mix`` boundary over Tensors: ``y [..., hidden]`` (the
    sublayer's output, or the embedding where ``first``), the streams
    ``x [rows, n * hidden]`` and ``coef`` (None where ``first``), the
    next connection ``hc`` (None where ``last``). Returns the merged
    stream shaped as ``y`` where ``last``, else ``(x', u shaped as y,
    coef')``."""
    c = config
    kw = dict(n=c.hc_mult, first=first, last=last,
              iters=c.hc_sinkhorn_iters, eps=c.hc_eps,
              clamp=(c.mhc_h_res_clamp_min, c.mhc_h_res_clamp_max),
              norm_eps=c.rms_norm_eps)

    def f(*arrays):
        arrays = list(arrays)
        xa = None if first else arrays.pop(0)
        ya = arrays.pop(0)
        ca = None if first else arrays.pop(0)
        fn, base, scale = arrays if arrays else (None, None, None)
        # on a TPU the kernel names itself (``kernel:hc_mix``): what
        # stays here is the casts of its leaves; on the CPU, all
        with component("residual.hc"):
            out = hc_mix(xa, ya.reshape(-1, ya.shape[-1]), ca, fn, base,
                         scale, **kw)
            if last:
                return out.reshape(ya.shape)
            xn, u, cf = out
            return xn, u.reshape(ya.shape), cf

    ins = ([] if first else [x]) + [y] + ([] if first else [coef])
    if not last:
        ins += [hc.fn, hc.base, hc.scale]
    return apply_jax("hyper_connection", f, *ins,
                     n_outputs=1 if last else 3)


class Xing40DecoderLayer(Layer):
    def __init__(self, config: Xing40Config, layer_idx: int):
        super().__init__()
        self.config = config
        self.self_attn = DeepseekV3Attention(config)
        self.mlp = DeepseekV3MoE(config) \
            if layer_idx >= config.first_k_dense_replace \
            else DeepseekV3MLP(config, config.intermediate_size)
        self.input_layernorm = _norm(config, config.hidden_size)
        self.post_attention_layernorm = _norm(config, config.hidden_size)
        self.hc_attn = Xing40HyperConnection(config)
        self.hc_ffn = Xing40HyperConnection(config)

    def forward(self, x, u, coef, nxt, cache=None, block_tables=None,
                cache_lens=None, ragged_meta=None):
        """``x`` / ``coef``: the streams and the attention connection's
        coefficients (None in the first layer: ``u`` is then the
        embedding); ``nxt``: the next layer's attention connection, None
        in the last. Returns ``(x, u, coef)`` for the next layer, or the
        merged stream after the last; with the layer's cache beside."""
        c = self.config
        if x is None:
            x, u, coef = _connect(c, None, u, None, self.hc_attn,
                                  first=True)
        with component("norm"):
            a = F.rms_norm(u, self.input_layernorm.weight, c.rms_norm_eps)
        if cache is None:
            y = self.self_attn(a)
        else:
            y, cache = self.self_attn.forward_paged(
                a, cache, block_tables, cache_lens, ragged_meta)
        x, u, coef = _connect(c, x, y, coef, self.hc_ffn)
        with component("norm"):
            a = F.rms_norm(u, self.post_attention_layernorm.weight,
                           c.rms_norm_eps)
        out = _connect(c, x, self.mlp(a), coef, nxt, last=nxt is None)
        return out, cache


class Xing40Model(Layer):
    def __init__(self, config: Xing40Config):
        super().__init__()
        self.config = config
        self.embed_tokens = _Leaf(
            config, (config.vocab_size, config.hidden_size))
        self.layers = LayerList(
            [Xing40DecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = _norm(config, config.hidden_size)

    def forward(self, input_ids, caches=None, block_tables=None,
                cache_lens=None, ragged_meta=None):
        with component("embed"):
            h = F.embedding(input_ids, self.embed_tokens.weight)
        state = (None, h, None)         # streams, u, coefficients
        layers = list(self.layers)
        new_caches = []
        for i, layer in enumerate(layers):
            nxt = layers[i + 1].hc_attn if i + 1 < len(layers) else None
            cache = None if caches is None else caches[i]
            with component(f"L{i}.mla"):
                state, cache = layer(*state, nxt, cache, block_tables,
                                     cache_lens, ragged_meta)
            new_caches.append(cache)
        with component("norm"):
            h = F.rms_norm(state, self.norm.weight,
                           self.config.rms_norm_eps)
        return h if caches is None else (h, new_caches)


class Xing40ForCausalLM(DeepseekV3ForCausalLM):
    """``DeepseekV3ForCausalLM``'s head, caches and entry points over
    ``Xing40Model``."""

    def __init__(self, config: Xing40Config):
        # past DeepseekV3ForCausalLM's own, which would build its model
        super(DeepseekV3ForCausalLM, self).__init__()
        self.config = config
        self.model = Xing40Model(config)
        if not config.tie_word_embeddings:
            self.lm_head = _Leaf(config, (config.hidden_size,
                                          config.vocab_size))
        self.criterion = LlamaPretrainingCriterion()

    @property
    def hc_connections(self) -> int:
        """Connections a row passes a tick: what the ``tick`` span's
        ``hc_rows`` counts a live row."""
        return 2 * self.config.num_hidden_layers

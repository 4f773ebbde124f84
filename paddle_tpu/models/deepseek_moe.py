"""DeepSeek-MoE (the first, 16B generation) model family — BASELINE
config 5 second entry: DENSE grouped-query attention (the shared
``LlamaAttention``, per-head K and V in the cache) with softmax-routed
fine-grained experts. It is NOT the ``deepseek_v2`` / ``deepseek_v3``
lineage, whatever an earlier docstring said: those have latent (MLA)
attention, a latent cache and a sigmoid group-limited router, and live
in ``models/deepseek_v3.py``.

Architecture signatures vs Qwen2-MoE: the first ``first_k_dense_replace``
layers use a dense MLP; sparse layers combine fine-grained routed experts
(softmax-then-topk scoring, optionally normalized) with
``n_shared_experts`` always-on shared experts added UNGATED to the routed
output. Expert storage/dispatch reuses the stacked-expert einsum path
(``qwen2_moe.StackedExpertsMLP`` + ``distributed/moe.py``) so expert
parallelism is a mesh-axis sharding, not hand-coded all-to-alls.
"""
from __future__ import annotations

from dataclasses import dataclass

from ..framework.core import Tensor, apply_jax, as_jax, _wrap_out
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..distributed.fleet.mp_layers import (ColumnParallelLinear,
                                           VocabParallelEmbedding)
from ..distributed.shard_utils import batch_shard
from ..generation import GenerationMixin
from .llama import (LlamaAttention, LlamaPretrainingCriterion,
                    _rope_tables)
from .qwen2_moe import StackedExpertsMLP, _DenseMLP

__all__ = ["DeepseekMoeConfig", "DeepseekMoeModel",
           "DeepseekMoeForCausalLM"]


@dataclass
class DeepseekMoeConfig:
    vocab_size: int = 102400
    hidden_size: int = 2048
    intermediate_size: int = 10944          # dense-layer MLP width
    moe_intermediate_size: int = 1408       # fine-grained expert width
    num_hidden_layers: int = 28
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    n_routed_experts: int = 64
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    first_k_dense_replace: int = 1
    moe_layer_freq: int = 1
    norm_topk_prob: bool = False
    router_aux_loss_coef: float = 0.001
    capacity_factor: float = 1.25
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    qkv_bias: bool = False                  # DeepSeek attention: no bias
    recompute: bool = False
    expert_axis: str = "dp"
    # dropless grouped-matmul routing (megablox on TPU; EP shard_map
    # fast path when expert_axis is mesh-sharded) vs GShard capacity
    dropless: bool = False
    ep_buffer_factor: float = 2.0
    # fused-dispatch grouped matmuls (ops/pallas/moe_gmm.py); False (or
    # PADDLE_TPU_MOE_FUSED_GMM=0) pins the sort->pack->gmm path
    moe_fused_gmm: bool = True
    dtype: str = "float32"

    @staticmethod
    def tiny(vocab=1024, hidden=128, layers=3, heads=4, kv_heads=4,
             moe_ffn=64, dense_ffn=192, experts=8, shared=2, topk=2):
        return DeepseekMoeConfig(
            vocab_size=vocab, hidden_size=hidden,
            intermediate_size=dense_ffn, moe_intermediate_size=moe_ffn,
            num_hidden_layers=layers, num_attention_heads=heads,
            num_key_value_heads=kv_heads, n_routed_experts=experts,
            n_shared_experts=shared, num_experts_per_tok=topk,
            max_position_embeddings=512)


class DeepseekMoeBlock(Layer):
    """Routed fine-grained experts + ungated shared experts."""

    def __init__(self, config: DeepseekMoeConfig):
        super().__init__()
        from ..nn.layer.common import Linear
        self.config = config
        self.gate = Linear(config.hidden_size, config.n_routed_experts,
                           bias_attr=False)
        self.experts = StackedExpertsMLP(
            config.n_routed_experts, config.hidden_size,
            config.moe_intermediate_size, config.expert_axis,
            config.initializer_range)
        self.shared_experts = _DenseMLP(
            config.hidden_size,
            config.n_shared_experts * config.moe_intermediate_size,
            config.initializer_range)

    def forward(self, x):
        cfg = self.config
        b, l, d = x.shape
        from ..ops.manipulation import reshape
        x2 = reshape(x, [-1, d])
        logits = self.gate(x2)

        def f(x_arr, logit_arr, gate_up, down):
            if getattr(cfg, "dropless", False):
                from ..distributed.moe import \
                    moe_dispatch_combine_dropless
                return moe_dispatch_combine_dropless(
                    x_arr, logit_arr, cfg.n_routed_experts,
                    cfg.num_experts_per_tok, gate_up, down,
                    normalize_gates=cfg.norm_topk_prob,
                    expert_axis=cfg.expert_axis,
                    ep_buffer_factor=getattr(cfg, "ep_buffer_factor",
                                             2.0),
                    fused=getattr(cfg, "moe_fused_gmm", None))
            from ..distributed.moe import moe_dispatch_combine_grouped
            return moe_dispatch_combine_grouped(
                x_arr, logit_arr, cfg.n_routed_experts,
                cfg.num_experts_per_tok, gate_up, down,
                capacity_factor=cfg.capacity_factor,
                expert_axis=cfg.expert_axis,
                normalize_gates=cfg.norm_topk_prob,
                fused=getattr(cfg, "moe_fused_gmm", None))

        y, aux = apply_jax("deepseek_moe_block", f, x2, logits,
                           self.experts.gate_up_proj,
                           self.experts.down_proj, n_outputs=2)
        from ..ops.math import add
        out = add(y, self.shared_experts(x2))
        return reshape(out, [b, l, d]), aux


class DeepseekMoeDecoderLayer(Layer):
    def __init__(self, config: DeepseekMoeConfig, layer_idx: int):
        super().__init__()
        self.self_attn = LlamaAttention(config)
        sparse = (layer_idx >= config.first_k_dense_replace and
                  layer_idx % config.moe_layer_freq == 0)
        if sparse:
            self.mlp = DeepseekMoeBlock(config)
        else:
            self.mlp = _DenseMLP(config.hidden_size,
                                 config.intermediate_size,
                                 config.initializer_range)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)

    def forward(self, hidden_states, rope_cos, rope_sin,
                attention_mask=None, kv_cache=None, offset=None,
                position_ids=None, block_tables=None, cache_lens=None,
                ragged_meta=None):
        h = self.input_layernorm(hidden_states)
        new_cache = None
        if kv_cache is not None:
            a, new_cache = self.self_attn(h, rope_cos, rope_sin,
                                          attention_mask, kv_cache,
                                          offset,
                                          position_ids=position_ids,
                                          block_tables=block_tables,
                                          cache_lens=cache_lens,
                                          ragged_meta=ragged_meta)
        else:
            a = self.self_attn(h, rope_cos, rope_sin, attention_mask)
        h = hidden_states + a
        h2 = self.post_attention_layernorm(h)
        m = self.mlp(h2)
        if isinstance(m, tuple):
            m, aux = m
        else:
            import jax.numpy as jnp
            aux = _wrap_out(jnp.zeros((), jnp.float32))
        if kv_cache is not None:
            return h + m, aux, new_cache
        return h + m, aux


class DeepseekMoeModel(Layer):
    def __init__(self, config: DeepseekMoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size)
        from ..nn.layer.container import LayerList
        self.layers = LayerList(
            [DeepseekMoeDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        head_dim = config.hidden_size // config.num_attention_heads
        cos, sin = _rope_tables(config.max_position_embeddings, head_dim,
                                config.rope_theta)
        self._rope_cos = Tensor(cos)
        self._rope_sin = Tensor(sin)

    def forward(self, input_ids, attention_mask=None, caches=None,
                offset=None, position_ids=None, block_tables=None,
                cache_lens=None, ragged_meta=None):
        input_ids = batch_shard(input_ids)
        h = self.embed_tokens(input_ids)
        if caches is not None:
            new_caches = []
            for layer, kv in zip(self.layers, caches):
                h, _aux, kv2 = layer(h, self._rope_cos, self._rope_sin,
                                     attention_mask, kv_cache=kv,
                                     offset=offset,
                                     position_ids=position_ids,
                                     block_tables=block_tables,
                                     cache_lens=cache_lens,
                                     ragged_meta=ragged_meta)
                new_caches.append(kv2)
            return self.norm(h), None, new_caches
        l = h.shape[1]
        cos = _wrap_out(as_jax(self._rope_cos)[:l])
        sin = _wrap_out(as_jax(self._rope_sin)[:l])
        from ..distributed.recompute import recompute
        from ..ops.math import add
        aux_total = None
        for layer in self.layers:
            if self.config.recompute and self.training:
                h, aux = recompute(layer, h, cos, sin, attention_mask)
            else:
                h, aux = layer(h, cos, sin, attention_mask)
            aux_total = aux if aux_total is None else add(aux_total, aux)
        return self.norm(h), aux_total


class DeepseekMoeForCausalLM(Layer, GenerationMixin):
    def __init__(self, config: DeepseekMoeConfig):
        super().__init__()
        self.config = config
        self.deepseek = DeepseekMoeModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=True)
        self.criterion = LlamaPretrainingCriterion()

    def _logits(self, h):
        if self.config.tie_word_embeddings:
            from ..ops.linalg import matmul
            return matmul(h, self.deepseek.embed_tokens.weight,
                          transpose_y=True)
        return self.lm_head(h)

    def init_caches(self, batch_size: int, max_length: int):
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        import jax.numpy as jnp
        dtype = jnp.dtype(getattr(cfg, "dtype", "float32"))
        return [
            (jnp.zeros((batch_size, max_length, cfg.num_key_value_heads,
                        head_dim), dtype),
             jnp.zeros((batch_size, max_length, cfg.num_key_value_heads,
                        head_dim), dtype))
            for _ in range(cfg.num_hidden_layers)
        ]

    def init_paged_caches(self, num_blocks: int, block_size: int,
                          sharding=None, kv_cache_dtype=None):
        """Zeroed per-layer paged (k_pool, v_pool) — the shared serving
        cache layout (see ``ops/paged_cache.py``), identical protocol
        to Llama/Qwen2-MoE. ``kv_cache_dtype="int8"``: quantized
        ``QuantKV`` pools."""
        from ..ops.paged_cache import init_pool
        import jax.numpy as jnp
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        dtype = jnp.dtype(getattr(cfg, "dtype", "float32")) \
            if kv_cache_dtype is None else kv_cache_dtype
        return [
            init_pool(num_blocks, block_size, cfg.num_key_value_heads,
                      head_dim, dtype, sharding=sharding)
            for _ in range(cfg.num_hidden_layers)
        ]

    def forward(self, input_ids, labels=None, attention_mask=None,
                caches=None, offset=None, position_ids=None,
                block_tables=None, cache_lens=None, ragged_meta=None):
        if caches is not None:
            h, _, new_caches = self.deepseek(input_ids, attention_mask,
                                             caches=caches, offset=offset,
                                             position_ids=position_ids,
                                             block_tables=block_tables,
                                             cache_lens=cache_lens,
                                             ragged_meta=ragged_meta)
            return self._logits(h), new_caches
        h, aux_total = self.deepseek(input_ids, attention_mask)
        logits = self._logits(h)
        if labels is None:
            return logits
        loss = self.criterion(logits, labels)
        if aux_total is not None and self.config.router_aux_loss_coef:
            from ..ops.math import add, scale
            loss = add(loss, scale(
                aux_total, self.config.router_aux_loss_coef))
        return loss

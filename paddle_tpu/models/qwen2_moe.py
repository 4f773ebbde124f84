"""Qwen2-MoE model family (PaddleNLP ``paddlenlp/transformers/qwen2_moe/
modeling.py`` parity) — BASELINE config 5.

TPU-first expert parallelism: each sparse block holds its experts as
STACKED arrays ``[e, d, m]`` annotated with a PartitionSpec on the expert
mesh axis. Dispatch/combine are the GShard einsums from
``distributed/moe.py``; when the expert dim is mesh-sharded, GSPMD lowers
the dispatch einsum into the all-to-all the reference implements by hand
over its expert ProcessGroup. All shapes static (capacity padding) so the
whole step stays inside one jit.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ..framework.core import Tensor, apply_jax, as_jax, component, \
    _wrap_out
from ..nn.initializer import Normal
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..distributed.fleet.mp_layers import (ColumnParallelLinear,
                                           RowParallelLinear,
                                           VocabParallelEmbedding)
from ..distributed.shard_utils import batch_shard
from ..generation import GenerationMixin
from ..incubate.nn.functional import swiglu
from .llama import (LlamaAttention, LlamaPretrainingCriterion,
                    _rope_tables, head_rows)

__all__ = ["Qwen2MoeConfig", "Qwen2MoeModel", "Qwen2MoeForCausalLM",
           "StackedExpertsMLP"]


@dataclass
class Qwen2MoeConfig:
    vocab_size: int = 151936
    hidden_size: int = 3584
    intermediate_size: int = 18944          # dense-layer MLP width
    moe_intermediate_size: int = 2560       # per-expert MLP width
    shared_expert_intermediate_size: int = 20480
    num_hidden_layers: int = 28
    num_attention_heads: int = 28
    num_key_value_heads: int = 4
    num_experts: int = 64
    num_experts_per_tok: int = 8
    decoder_sparse_step: int = 1            # every k-th layer is sparse
    norm_topk_prob: bool = False
    router_aux_loss_coef: float = 0.001
    capacity_factor: float = 1.25
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-6
    rope_theta: float = 1000000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    qkv_bias: bool = True                   # the Qwen2 signature detail
    recompute: bool = False
    expert_axis: str = "dp"                 # mesh axis experts shard over
    # dropless routing: no capacity factor, no dropped tokens — experts
    # run as grouped matmuls (megablox on TPU / lax.ragged_dot), inside
    # shard_map with explicit all-to-alls when expert_axis is sharded
    dropless: bool = False
    # EP exchange-slot bound, in multiples of the balanced per-shard
    # load (see moe._dropless_ep); >= the EP degree is exactly dropless
    ep_buffer_factor: float = 2.0
    # fused-dispatch grouped matmuls (ops/pallas/moe_gmm.py): the sort
    # gather rides the first expert matmul's load, swiglu its epilogue,
    # the combine unsort the second's scatter store. False (or
    # PADDLE_TPU_MOE_FUSED_GMM=0) pins the sort->pack->gmm path.
    moe_fused_gmm: bool = True
    dtype: str = "float32"

    @staticmethod
    def tiny(vocab=1024, hidden=128, layers=2, heads=4, kv_heads=2,
             moe_ffn=96, shared_ffn=192, experts=8, topk=2):
        return Qwen2MoeConfig(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=shared_ffn,
            moe_intermediate_size=moe_ffn,
            shared_expert_intermediate_size=shared_ffn,
            num_hidden_layers=layers, num_attention_heads=heads,
            num_key_value_heads=kv_heads, num_experts=experts,
            num_experts_per_tok=topk, max_position_embeddings=512)


class StackedExpertsMLP(Layer):
    """E SwiGLU experts held as stacked ``[e, ...]`` parameters.

    The reference keeps a python list of per-expert Linears and loops /
    all-to-alls between them; on TPU a stacked layout turns the expert
    compute into three batched einsums (one MXU call each) and makes the
    expert dim an ordinary shardable array axis.
    """

    def __init__(self, num_experts, d_model, d_ffn, expert_axis="dp",
                 initializer_range=0.02):
        super().__init__()
        init = Normal(0.0, initializer_range)
        self.num_experts = num_experts
        self.gate_up_proj = self.create_parameter(
            [num_experts, d_model, 2 * d_ffn], default_initializer=init)
        self.down_proj = self.create_parameter(
            [num_experts, d_ffn, d_model], default_initializer=init)
        from ..distributed.shard_utils import annotate_param
        annotate_param(self.gate_up_proj, (expert_axis, None, "mp"))
        annotate_param(self.down_proj, (expert_axis, "mp", None))
        # NOTE: the expert computation itself lives in
        # distributed/moe.py (_expert_swiglu_grouped and the padded
        # fallback's efn) — this Layer only owns the stacked params.


class Qwen2MoeSparseBlock(Layer):
    """Router + stacked routed experts + always-on shared expert."""

    def __init__(self, config: Qwen2MoeConfig):
        super().__init__()
        from ..nn.layer.common import Linear
        self.config = config
        self.gate = Linear(config.hidden_size, config.num_experts,
                           bias_attr=False)
        self.experts = StackedExpertsMLP(
            config.num_experts, config.hidden_size,
            config.moe_intermediate_size, config.expert_axis,
            config.initializer_range)
        self.shared_expert = _DenseMLP(
            config.hidden_size, config.shared_expert_intermediate_size,
            config.initializer_range)
        self.shared_expert_gate = Linear(config.hidden_size, 1,
                                         bias_attr=False)

    def forward(self, x):
        """Returns ``(out, aux_loss)`` — aux travels by value so it
        survives ``jax.checkpoint`` retracing (a value stored on ``self``
        inside the remat trace would leak the inner tracer)."""
        cfg = self.config
        b, l, d = x.shape
        from ..ops.manipulation import reshape
        x2 = reshape(x, [-1, d])
        logits = self.gate(x2)

        collect = getattr(self, "_collect_stats", False)

        def f(x_arr, logit_arr, gate_up, down):
            if getattr(cfg, "dropless", False):
                from ..distributed.moe import \
                    moe_dispatch_combine_dropless
                out = moe_dispatch_combine_dropless(
                    x_arr, logit_arr, cfg.num_experts,
                    cfg.num_experts_per_tok, gate_up, down,
                    normalize_gates=cfg.norm_topk_prob,
                    expert_axis=cfg.expert_axis,
                    ep_buffer_factor=getattr(cfg, "ep_buffer_factor",
                                             2.0),
                    fused=getattr(cfg, "moe_fused_gmm", None),
                    return_stats=collect)
            else:
                # capacity semantics on the grouped-matmul engine
                # (stacked SwiGLU experts; falls back to the padded
                # einsum under an expert-sharded mesh)
                from ..distributed.moe import \
                    moe_dispatch_combine_grouped
                out = moe_dispatch_combine_grouped(
                    x_arr, logit_arr, cfg.num_experts,
                    cfg.num_experts_per_tok, gate_up, down,
                    capacity_factor=cfg.capacity_factor,
                    expert_axis=cfg.expert_axis,
                    normalize_gates=cfg.norm_topk_prob,
                    fused=getattr(cfg, "moe_fused_gmm", None),
                    return_stats=collect)
            if collect:
                y, aux, stats = out
                return y, aux, stats["drop_rate"]
            return out

        if collect:
            y, aux, drop = apply_jax("qwen2_moe_block", f, x2, logits,
                                     self.experts.gate_up_proj,
                                     self.experts.down_proj, n_outputs=3)
            # eager-only diagnostic (a traced value here would be a
            # leaked tracer — use collect_drop_rates(), which runs eager)
            self.drop_rate = drop
        else:
            y, aux = apply_jax("qwen2_moe_block", f, x2, logits,
                               self.experts.gate_up_proj,
                               self.experts.down_proj, n_outputs=2)

        shared = self.shared_expert(x2)
        from ..ops.math import multiply, add
        from ..nn.functional import sigmoid
        sg = sigmoid(self.shared_expert_gate(x2))
        out = add(y, multiply(shared, sg))
        return reshape(out, [b, l, d]), aux


class _DenseMLP(Layer):
    def __init__(self, d_model, d_ffn, initializer_range=0.02):
        super().__init__()
        self.gate_proj = ColumnParallelLinear(
            d_model, d_ffn, has_bias=False, gather_output=False)
        self.up_proj = ColumnParallelLinear(
            d_model, d_ffn, has_bias=False, gather_output=False)
        self.down_proj = RowParallelLinear(
            d_ffn, d_model, has_bias=False, input_is_parallel=True)

    def forward(self, x):
        return self.down_proj(swiglu(self.gate_proj(x), self.up_proj(x)))


# Same GQA attention as Llama; config.qkv_bias=True is the only delta.
Qwen2MoeAttention = LlamaAttention


class Qwen2MoeDecoderLayer(Layer):
    def __init__(self, config: Qwen2MoeConfig, layer_idx: int):
        super().__init__()
        self.self_attn = Qwen2MoeAttention(config)
        sparse = (config.num_experts > 0 and
                  (layer_idx + 1) % config.decoder_sparse_step == 0)
        if sparse:
            self.mlp = Qwen2MoeSparseBlock(config)
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
        """Returns ``(h, aux_loss)`` uniformly (zero aux for dense
        layers) so the remat and non-remat paths carry the router loss
        identically; with ``kv_cache``, ``(h, aux_loss, new_cache)``.
        ``block_tables``/``cache_lens``/``ragged_meta`` select the
        paged / ragged mixed-batch serving attention (vanilla GQA — the
        Llama kernels run unmodified; only the MLP differs, and MoE
        dispatch is per-row, so packed serving rows route exactly like
        a dense batch)."""
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
            aux = _wrap_out(jnp.zeros((), jnp.float32))
        if kv_cache is not None:
            return h + m, aux, new_cache
        return h + m, aux


class Qwen2MoeModel(Layer):
    def __init__(self, config: Qwen2MoeConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size)
        from ..nn.layer.container import LayerList
        self.layers = LayerList(
            [Qwen2MoeDecoderLayer(config, i)
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
        """Returns ``(h, total_aux_loss)``; with ``caches``,
        ``(h, total_aux_loss, new_caches)``."""
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


class Qwen2MoeForCausalLM(Layer, GenerationMixin):
    def __init__(self, config: Qwen2MoeConfig):
        super().__init__()
        self.config = config
        self.qwen2_moe = Qwen2MoeModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=True)
        self.criterion = LlamaPretrainingCriterion()

    def _logits(self, h):
        if self.config.tie_word_embeddings:
            from ..ops.linalg import matmul
            return matmul(h, self.qwen2_moe.embed_tokens.weight,
                          transpose_y=True)
        return self.lm_head(h)

    def init_caches(self, batch_size: int, max_length: int):
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_attention_heads
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
        cache (see ``ops/paged_cache.py``); same layout/protocol as
        Llama's, so the serving engine and ``generate(
        cache_impl="paged")`` run MoE unmodified on the attention
        side. ``kv_cache_dtype="int8"``: quantized ``QuantKV``
        pools."""
        from ..ops.paged_cache import init_pool
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
                block_tables=None, cache_lens=None, ragged_meta=None,
                logit_rows=None):
        if caches is not None:
            h, _, new_caches = self.qwen2_moe(input_ids, attention_mask,
                                              caches=caches, offset=offset,
                                              position_ids=position_ids,
                                              block_tables=block_tables,
                                              cache_lens=cache_lens,
                                              ragged_meta=ragged_meta)
            with component("head"):
                return self._logits(head_rows(h, logit_rows)), new_caches
        h, aux_total = self.qwen2_moe(input_ids, attention_mask)
        logits = self._logits(h)
        if labels is None:
            return logits
        loss = self.criterion(logits, labels)
        if aux_total is not None and self.config.router_aux_loss_coef:
            from ..ops.math import add, scale
            loss = add(loss, scale(
                aux_total, self.config.router_aux_loss_coef))
        return loss

    def collect_drop_rates(self, input_ids):
        """Per-sparse-block expert-capacity drop rates for one EAGER
        forward (reference: the MoE stack's capacity-drop telemetry).
        Returns a list of floats, one per sparse block."""
        blocks = [lay.mlp for lay in self.qwen2_moe.layers
                  if isinstance(lay.mlp, Qwen2MoeSparseBlock)]
        for b in blocks:
            b._collect_stats = True
        was_training = self.training
        self.eval()
        try:
            from ..framework.core import no_grad
            with no_grad():                 # diagnostic: no tape
                self(input_ids)
        finally:
            if was_training:
                self.train()
            for b in blocks:
                b._collect_stats = False
        import numpy as np
        out = []
        for b in blocks:
            out.append(float(np.asarray(as_jax(b.drop_rate))))
            b.drop_rate = None              # release the graph/activations
        return out

"""GPT model family (PaddleNLP ``paddlenlp/transformers/gpt/modeling.py``
parity) with TP annotations."""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..framework.core import Tensor, apply_jax, as_jax, component, \
    _wrap_out
from ..nn import functional as F
from ..nn.layer.common import Dropout, Embedding
from ..nn.layer.container import LayerList
from ..nn.layer.layers import Layer
from ..nn.layer.norm import LayerNorm
from ..distributed.fleet.mp_layers import (ColumnParallelLinear,
                                           RowParallelLinear,
                                           VocabParallelEmbedding)
from ..distributed.shard_utils import batch_shard, constraint
from ..generation import GenerationMixin
from .llama import head_rows

__all__ = ["GPTConfig", "GPTModel", "GPTForCausalLM",
           "GPTPretrainingCriterion"]


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 1024
    num_hidden_layers: int = 24
    num_attention_heads: int = 16
    intermediate_size: int = 4096
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    layer_norm_epsilon: float = 1e-5
    initializer_range: float = 0.02

    @staticmethod
    def tiny(vocab=1024, hidden=128, layers=2, heads=4):
        return GPTConfig(vocab_size=vocab, hidden_size=hidden,
                         num_hidden_layers=layers, num_attention_heads=heads,
                         intermediate_size=hidden * 4,
                         max_position_embeddings=512)


class GPTAttention(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.num_heads = config.num_attention_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        self.qkv_proj = ColumnParallelLinear(
            config.hidden_size, 3 * config.hidden_size,
            gather_output=False)
        self.out_proj = RowParallelLinear(
            config.hidden_size, config.hidden_size,
            input_is_parallel=True)
        self.dropout_p = config.attention_probs_dropout_prob

    def forward(self, x, kv_cache=None, offset=None, block_tables=None,
                cache_lens=None, ragged_meta=None):
        b, l, d = x.shape
        qkv = self.qkv_proj(x)

        if kv_cache is not None and block_tables is not None:
            ctx, kv2 = self._attend_serving(qkv, kv_cache,
                                            block_tables, cache_lens,
                                            ragged_meta, b, l, d)
            ctx = constraint(ctx, None, None, "mp")
            return self.out_proj(ctx), kv2

        if kv_cache is not None:
            def attn_c(a, kc, vc, off):
                from .llama import cached_attention
                q, k, v = jnp.split(a, 3, axis=-1)
                qh = q.reshape(b, l, self.num_heads, self.head_dim)
                kh = k.reshape(b, l, self.num_heads, self.head_dim)
                vh = v.reshape(b, l, self.num_heads, self.head_dim)
                out, kc2, vc2 = cached_attention(qh, kh, vh, kc, vc,
                                                 off, self.head_dim)
                return out.reshape(b, l, d), kc2, vc2

            ctx, kc2, vc2 = apply_jax("gpt_attention_cached", attn_c,
                                      qkv, kv_cache[0], kv_cache[1],
                                      offset, n_outputs=3)
            ctx = constraint(ctx, None, None, "mp")
            return self.out_proj(ctx), (kc2, vc2)

        def attn(a):
            q, k, v = jnp.split(a, 3, axis=-1)
            qh = q.reshape(b, l, self.num_heads, self.head_dim)
            kh = k.reshape(b, l, self.num_heads, self.head_dim)
            vh = v.reshape(b, l, self.num_heads, self.head_dim)
            from ..ops.pallas.flash_attention import flash_attention_core
            out = flash_attention_core(qh, kh, vh, is_causal=True)
            return out.reshape(b, l, d)
        ctx = apply_jax("gpt_attention", attn, qkv)
        ctx = constraint(ctx, None, None, "mp")
        return self.out_proj(ctx)

    def _attend_serving(self, qkv, kv_cache, block_tables, cache_lens,
                        ragged_meta, b, l, d):
        """Paged/ragged split + write + attend WITHOUT the output
        projection — the shared core of the serving branches of
        ``forward`` and the fused decode path (which runs the output
        projection inside the fused residual-add epilogue). Returns
        ``(ctx [B, L, D], (k_pool, v_pool))``."""
        if ragged_meta is not None:
            # ragged mixed batch: [1, R] packed rows over the pool
            (q_lens, row_starts, row_slot, row_pos, narrow_iota,
             win_iota) = ragged_meta

            def attn_r(a, kp, vp, tables, lens, ql, rs, sl, pos_r,
                       nwin, win):
                from .llama import ragged_paged_attention_decode
                q, k, v = jnp.split(a, 3, axis=-1)
                r = b * l                        # packed rows (b == 1)
                qh = q.reshape(r, self.num_heads, self.head_dim)
                kh = k.reshape(r, self.num_heads, self.head_dim)
                vh = v.reshape(r, self.num_heads, self.head_dim)
                out, kp2, vp2 = ragged_paged_attention_decode(
                    qh, kh, vh, kp, vp, tables, lens, ql, rs, sl,
                    pos_r, nwin, win, self.head_dim)
                return out.reshape(b, l, d), kp2, vp2

            ctx, kp2, vp2 = apply_jax(
                "gpt_attention_ragged", attn_r, qkv, kv_cache[0],
                kv_cache[1], block_tables, cache_lens, q_lens,
                row_starts, row_slot, row_pos, narrow_iota, win_iota,
                n_outputs=3)
            return ctx, (kp2, vp2)

        # paged decode: kv_cache is the shared (k_pool, v_pool)
        def attn_p(a, kp, vp, tables, lens):
            from .llama import paged_attention_decode
            q, k, v = jnp.split(a, 3, axis=-1)
            qh = q.reshape(b, l, self.num_heads, self.head_dim)
            kh = k.reshape(b, l, self.num_heads, self.head_dim)
            vh = v.reshape(b, l, self.num_heads, self.head_dim)
            out, kp2, vp2 = paged_attention_decode(
                qh, kh, vh, kp, vp, tables, lens, self.head_dim)
            return out.reshape(b, l, d), kp2, vp2

        ctx, kp2, vp2 = apply_jax("gpt_attention_paged", attn_p,
                                  qkv, kv_cache[0], kv_cache[1],
                                  block_tables, cache_lens,
                                  n_outputs=3)
        return ctx, (kp2, vp2)


class GPTDecoderLayer(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.ln_1 = LayerNorm(config.hidden_size,
                              config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = LayerNorm(config.hidden_size,
                              config.layer_norm_epsilon)
        self.linear1 = ColumnParallelLinear(
            config.hidden_size, config.intermediate_size,
            gather_output=False)
        self.linear2 = RowParallelLinear(
            config.intermediate_size, config.hidden_size,
            input_is_parallel=True)
        self.dropout = Dropout(config.hidden_dropout_prob)

    def _fused_decode_eligible(self):
        """Fused decode-tick gate (the Llama twin, LayerNorm flavor):
        a serving trace armed the fused scope, the layer is in eval
        mode (the fused epilogues skip the — inert — eval dropout),
        and every weight is a plain float tensor."""
        from ..ops.pallas import decode_fused as _df
        if self.training or _df.fused_decode_mode() is None:
            return False
        return _df.fused_params_ok(
            self.ln_1.weight, self.ln_2.weight,
            getattr(self.attn.qkv_proj, "weight", None),
            getattr(self.attn.out_proj, "weight", None),
            getattr(self.linear1, "weight", None),
            getattr(self.linear2, "weight", None))

    def _forward_decode_fused(self, x, kv_cache, block_tables,
                              cache_lens, ragged_meta):
        """Mega-kernelized GPT decode tick (ISSUE 13): LayerNorm fused
        into the (already single) QKV projection, attention epilogue
        into the output projection + residual add, the second
        LayerNorm into the first MLP linear, and tanh-gelu into the
        second MLP linear + residual add. The XLA fallback is bitwise
        this layer's unfused eval-mode ops."""
        from ..ops.pallas import decode_fused as _df
        from ..ops import lora as _lora
        b, l, d = x.shape
        (qkv,) = _df.norm_matmul(
            x, self.ln_1.weight, self.ln_1.bias,
            [self.attn.qkv_proj.weight], [self.attn.qkv_proj.bias],
            eps=self.ln_1._epsilon, kind="ln")
        if _lora.armed(self.attn.qkv_proj):
            # multi-LoRA serving composes per MODULE (the Llama twin):
            # fused prologue kept, the armed projection adds its
            # ragged grouped-matmul delta off the recomputed norm —
            # bitwise the unfused module path's input, so fused
            # ON==OFF stays token-exact under adapters too
            qkv = _lora.apply(self.attn.qkv_proj, self.ln_1(x), qkv)
        ctx, new_cache = self.attn._attend_serving(
            qkv, kv_cache, block_tables, cache_lens, ragged_meta,
            b, l, d)
        if _lora.armed(self.attn.out_proj):
            # armed epilogue: module call + residual add (the unfused
            # ordering; eval-mode dropout is inert)
            x2 = x + self.attn.out_proj(ctx)
        else:
            x2 = _df.matmul_residual([ctx], self.attn.out_proj.weight,
                                     self.attn.out_proj.bias, x)
        (g,) = _df.norm_matmul(
            x2, self.ln_2.weight, self.ln_2.bias,
            [self.linear1.weight], [self.linear1.bias],
            eps=self.ln_2._epsilon, kind="ln")
        if _lora.armed(self.linear1):
            g = _lora.apply(self.linear1, self.ln_2(x2), g)
        if _lora.armed(self.linear2):
            out = x2 + self.linear2(F.gelu(g, approximate=True))
        else:
            out = _df.matmul_residual([g], self.linear2.weight,
                                      self.linear2.bias, x2,
                                      act="gelu_tanh")
        return out, new_cache

    def forward(self, x, kv_cache=None, offset=None, block_tables=None,
                cache_lens=None, ragged_meta=None):
        if kv_cache is not None and block_tables is not None \
                and self._fused_decode_eligible():
            return self._forward_decode_fused(x, kv_cache,
                                              block_tables, cache_lens,
                                              ragged_meta)
        new_cache = None
        if kv_cache is not None:
            a, new_cache = self.attn(self.ln_1(x), kv_cache, offset,
                                     block_tables=block_tables,
                                     cache_lens=cache_lens,
                                     ragged_meta=ragged_meta)
        else:
            a = self.attn(self.ln_1(x))
        x = x + self.dropout(a)
        h = self.linear2(F.gelu(self.linear1(self.ln_2(x)),
                                approximate=True))
        out = x + self.dropout(h)
        if kv_cache is not None:
            return out, new_cache
        return out


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.embeddings = VocabParallelEmbedding(config.vocab_size,
                                                 config.hidden_size)
        self.position_embeddings = Embedding(
            config.max_position_embeddings, config.hidden_size)
        self.dropout = Dropout(config.hidden_dropout_prob)
        self.h = LayerList([GPTDecoderLayer(config)
                            for _ in range(config.num_hidden_layers)])
        self.ln_f = LayerNorm(config.hidden_size,
                              config.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None, caches=None,
                offset=None, block_tables=None, cache_lens=None,
                ragged_meta=None):
        input_ids = batch_shard(input_ids)
        l = input_ids.shape[1]
        if position_ids is None:
            if ragged_meta is not None:
                # ragged mixed batch: each packed row carries its own
                # absolute position (pad rows clamp to the last learned
                # position — their output is discarded and their write
                # null-routed)
                from ..framework.core import _wrap_out as _w
                from ..framework.core import as_jax as _aj
                position_ids = _w(jnp.clip(
                    _aj(ragged_meta[3]).astype(jnp.int32), 0,
                    self.config.max_position_embeddings - 1)[None, :])
            elif cache_lens is not None:
                # paged decode: each slot sits at its own position
                # (window token t of a speculative verify chunk at
                # cache_lens + t)
                from ..framework.core import _wrap_out as _w
                from ..framework.core import as_jax as _aj
                position_ids = _w(
                    _aj(cache_lens).astype(jnp.int32)[:, None]
                    + jnp.arange(l, dtype=jnp.int32)[None, :])
            else:
                from ..ops.creation import arange
                position_ids = arange(l, dtype="int64")
                if offset is not None:
                    position_ids = position_ids + offset
        h = self.embeddings(input_ids) + \
            self.position_embeddings(position_ids)
        h = self.dropout(h)
        if caches is not None:
            new_caches = []
            for layer, kv in zip(self.h, caches):
                h, kv2 = layer(h, kv_cache=kv, offset=offset,
                               block_tables=block_tables,
                               cache_lens=cache_lens,
                               ragged_meta=ragged_meta)
                new_caches.append(kv2)
            return self.ln_f(h), new_caches
        for layer in self.h:
            h = layer(h)
        return self.ln_f(h)


class GPTPretrainingCriterion(Layer):
    """CE over pre-shifted labels (PaddleNLP parity: the dataset shifts;
    ``labels[t]`` targets ``logits[t]``)."""

    def forward(self, logits, labels):
        def f(lg, lb):
            lb = lb.astype(jnp.int32)
            logp = jax.nn.log_softmax(lg.astype(jnp.float32), axis=-1)
            picked = jnp.take_along_axis(logp, lb[..., None],
                                         axis=-1)[..., 0]
            return -jnp.mean(picked)
        return apply_jax("gpt_ce", f, logits, labels)


class GPTForCausalLM(Layer, GenerationMixin):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        self.criterion = GPTPretrainingCriterion()

    def init_caches(self, batch_size: int, max_length: int):
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        return [
            (jnp.zeros((batch_size, max_length, cfg.num_attention_heads,
                        head_dim), jnp.float32),
             jnp.zeros((batch_size, max_length, cfg.num_attention_heads,
                        head_dim), jnp.float32))
            for _ in range(cfg.num_hidden_layers)
        ]

    def init_paged_caches(self, num_blocks: int, block_size: int,
                          sharding=None, kv_cache_dtype=None):
        """Per-layer paged (k_pool, v_pool) for serving (MHA: kv head
        count equals the query head count). ``sharding``: the
        tensor-parallel kv_head split (``pool_sharding(mesh)``);
        ``kv_cache_dtype="int8"``: quantized ``QuantKV`` pools."""
        from ..ops.paged_cache import init_pool
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        dtype = jnp.float32 if kv_cache_dtype is None \
            else kv_cache_dtype
        return [
            init_pool(num_blocks, block_size, cfg.num_attention_heads,
                      head_dim, dtype, sharding=sharding)
            for _ in range(cfg.num_hidden_layers)
        ]

    def forward(self, input_ids, labels=None, caches=None, offset=None,
                block_tables=None, cache_lens=None, ragged_meta=None,
                logit_rows=None, return_hidden=False):
        from ..ops.linalg import matmul
        if caches is not None:
            h, new_caches = self.gpt(input_ids, caches=caches,
                                     offset=offset,
                                     block_tables=block_tables,
                                     cache_lens=cache_lens,
                                     ragged_meta=ragged_meta)
            with component("head"):
                logits = matmul(head_rows(h, logit_rows),
                                self.gpt.embeddings.weight,
                                transpose_y=True)
            if return_hidden:
                return (logits, h), new_caches
            return logits, new_caches
        h = self.gpt(input_ids)
        logits = matmul(h, self.gpt.embeddings.weight, transpose_y=True)
        if labels is not None:
            return self.criterion(logits, labels)
        return logits

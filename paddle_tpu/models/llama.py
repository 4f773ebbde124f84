"""Llama model family (PaddleNLP ``paddlenlp/transformers/llama/
modeling.py`` parity) — BASELINE config 4 flagship.

TPU-first 4D parallel layout:
  - TP: q/k/v/gate/up projections are ColumnParallel, o/down are
    RowParallel, embeddings VocabParallel — all via PartitionSpec
    annotations on the ``mp`` mesh axis (GSPMD inserts the collectives).
  - SP (Megatron): activation constraints on the seq dim when
    ``sequence_parallel=True``.
  - SEP: when the ``sep`` axis is >1, attention runs Ulysses all-to-all
    head<->seq reshuffles (``distributed/sep_parallel.py``, the default)
    or the ppermute ring (``distributed/ring_attention.py``), selected
    by ``hybrid_configs["sep_mechanism"]``.
  - DP/sharding: batch dim constraint + fsdp param specs (stage 3).
  - PP: homogeneous decoder layers — pipelined via
    ``distributed/pipeline.py`` through ``LlamaForCausalLMPipe``.
  - remat: per-decoder-layer jax.checkpoint when config.recompute.
"""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import (Tensor, apply_jax, as_jax, component,
                              _wrap_out)
from ..nn import functional as F
from ..nn.initializer import Normal
from ..nn.layer.layers import Layer
from ..nn.layer.norm import RMSNorm
from ..distributed.fleet.mp_layers import (ColumnParallelLinear,
                                           RowParallelLinear,
                                           VocabParallelEmbedding)
from ..distributed.shard_utils import batch_shard, constraint, \
    mesh_axis_size
from ..generation import GenerationMixin
from ..incubate.nn.functional import (fused_rotary_position_embedding,
                                      swiglu)

__all__ = ["LlamaConfig", "LlamaModel", "LlamaForCausalLM",
           "LlamaForCausalLMPipe", "LlamaPretrainingCriterion"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    sequence_parallel: bool = False
    recompute: bool = False
    # remat every k-th decoder layer (reference fleet
    # ``recompute_interval``): k=1 remats all layers; k=2 halves the
    # recompute FLOPs at ~2x the activation memory — the knob that keeps
    # deep stacks above 0.65 MFU
    recompute_interval: int = 1
    use_flash_attention: bool = True
    dtype: str = "float32"

    @staticmethod
    def llama3_8b():
        return LlamaConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=32, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=8192,
            rope_theta=500000.0)

    @staticmethod
    def tiny(vocab=1024, hidden=256, layers=2, heads=8, kv_heads=4,
             ffn=512):
        return LlamaConfig(
            vocab_size=vocab, hidden_size=hidden, intermediate_size=ffn,
            num_hidden_layers=layers, num_attention_heads=heads,
            num_key_value_heads=kv_heads, max_position_embeddings=2048)


def _rope_tables(seq_len, head_dim, theta):
    pos = np.arange(seq_len, dtype=np.float32)
    inv = theta ** (-np.arange(0, head_dim, 2,
                               dtype=np.float32) / head_dim)
    freqs = np.outer(pos, inv)
    return np.cos(freqs), np.sin(freqs)


def cached_attention(qh, kh, vh, kc, vc, off, head_dim,
                     extra_bias=None):
    """Shared KV-cache attention step (Llama/GPT families): write this
    chunk's heads [B, L, H', D] into the static cache at ``off``, attend
    q against the full cache under a causal-with-offset mask (plus an
    optional additive ``extra_bias`` broadcastable to [B, H, L, S] —
    e.g. a decode src_mask). Returns (out [B, L, H, D], new_k_cache,
    new_v_cache). GQA: cache holds KV heads; repeat to the query head
    count here."""
    b, l = qh.shape[0], qh.shape[1]
    h = qh.shape[2]
    hkv = kc.shape[2]
    rep = h // hkv
    d = qh.shape[3]
    off = off.astype(jnp.int32) if hasattr(off, "astype") else off
    zero = jnp.zeros((), jnp.int32)
    kc2 = jax.lax.dynamic_update_slice(
        kc, kh.astype(kc.dtype), (zero, off, zero, zero))
    vc2 = jax.lax.dynamic_update_slice(
        vc, vh.astype(vc.dtype), (zero, off, zero, zero))
    S = kc.shape[1]
    rows = off + jnp.arange(l)[:, None]
    cols = jnp.arange(S)[None, :]
    bias = jnp.where(cols <= rows, 0.0, -1e9)[None, None]  # [1,1,L,S]
    if extra_bias is not None:
        pad = S - extra_bias.shape[-1]
        if pad > 0:  # mask covers the live prefix; mask out the tail
            extra_bias = jnp.pad(extra_bias,
                                 [(0, 0)] * (extra_bias.ndim - 1)
                                 + [(0, pad)],
                                 constant_values=-1e9)
        bias = bias + extra_bias                   # [B,H,L,S]
    # GQA WITHOUT materializing the expanded cache: jnp.repeat here
    # would write+read rep x the whole KV cache per decode step (the
    # dominant HBM traffic at small batch); grouping the query heads
    # keeps the cache read once
    q5 = qh.reshape(b, l, hkv, rep, d)
    scores = jnp.einsum(
        "blgrd,bsgd->bgrls", q5, kc2.astype(qh.dtype),
        preferred_element_type=jnp.float32) / math.sqrt(head_dim)
    if bias.shape[1] == h:                # per-head bias (any batch dim)
        bias5 = bias.reshape(bias.shape[0], hkv, rep, l, S)
    else:                                 # broadcast causal mask (H=1)
        bias5 = bias[:, :, None]          # [B|1,1,1,L,S]
    scores = scores + bias5
    w = jax.nn.softmax(scores, axis=-1).astype(qh.dtype)
    out = jnp.einsum("bgrls,bsgd->blgrd", w, vc2.astype(qh.dtype))
    return out.reshape(b, l, h, d), kc2, vc2


def paged_attention_decode(qh, kh, vh, k_pool, v_pool, block_tables,
                           cache_lens, head_dim):
    """Shared paged-KV decode step (Llama/GPT families): write this
    chunk's K/V heads [S, T, H_kv, D] into the shared block pool at
    positions ``cache_lens[s] + t``, then attend q against each slot's
    length-bounded block list through the ragged paged kernel
    (``ops/pallas/paged_attention.py``; gather fallback off-TPU).
    ``T = 1`` is ``generate(cache_impl="paged")``'s decode step;
    ``T > 1`` is ``SpecGenerator``'s speculative verify window —
    causal within the window: token ``t`` sees
    ``cache_lens[s] + t + 1`` positions. The serving engine does not
    come here: its tick is ``ragged_paged_attention_decode`` below.
    Returns (out [S, T, H, D], new_k_pool, new_v_pool)."""
    from ..ops.pallas.paged_attention import paged_attention_step
    return paged_attention_step(qh, kh, vh, k_pool, v_pool,
                                block_tables, cache_lens,
                                sm_scale=1.0 / math.sqrt(head_dim))


def ragged_paged_attention_decode(qh, kh, vh, k_pool, v_pool,
                                  block_tables, cache_lens, q_lens,
                                  row_starts, row_slot, row_pos,
                                  narrow_iota, win_iota, head_dim):
    """Shared RAGGED mixed-batch step (Llama/GPT families): one packed
    row buffer ``[R, H, D]`` carries every live query row of a serving
    tick — decoding slots (1 row), speculative verify windows
    (gamma+1 rows) and prefill chunks — partitioned by per-slot
    ``q_lens``/``row_starts``; row ``r`` writes and attends at cache
    position ``row_pos[r]`` of slot ``row_slot[r]``. The per-width
    ``paged_attention_decode`` above is the uniform-width special case
    of this step; the serving engine's ONE ragged executable is its
    only caller. Tensor-parallel serving (inside a TP engine's trace:
    ``serving_tp_scope``, a mesh with a live ``mp`` axis, divisible
    head counts) routes the same body through ``shard_map``. Returns
    ``(out [R, H, D], new_k_pool, new_v_pool)``."""
    from ..ops.pallas.paged_attention import (
        ragged_attention_step, sharded_ragged_attention_step,
        tp_shard_degree)
    sm = 1.0 / math.sqrt(head_dim)
    if tp_shard_degree(qh.shape[1], kh.shape[1]) > 1:
        return sharded_ragged_attention_step(
            qh, kh, vh, k_pool, v_pool, block_tables, cache_lens,
            q_lens, row_starts, row_slot, row_pos, narrow_iota,
            win_iota, sm_scale=sm)
    return ragged_attention_step(
        qh, kh, vh, k_pool, v_pool, block_tables, cache_lens, q_lens,
        row_starts, row_slot, row_pos, narrow_iota, win_iota,
        sm_scale=sm)


def _rope_rotate(x, c, s):
    """Shared neox-halves rotation; c/s arrive pre-broadcast against
    [B, L, H, D/2]. Tables stay fp32 for precision; output is cast back
    so bf16 activations remain bf16."""
    d = x.shape[-1]
    xf = x.astype(jnp.float32)
    x1 = xf[..., : d // 2]
    x2 = xf[..., d // 2:]
    c = c.astype(jnp.float32)
    s = s.astype(jnp.float32)
    out = jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)
    return out.astype(x.dtype)


def _apply_rope(x, cos, sin):
    # x: [B, L, H, D]; cos/sin: [L, D/2] (shared positions)
    return _rope_rotate(x, cos[None, :, None, :], sin[None, :, None, :])


def _apply_rope_rows(x, cos, sin):
    """Rope with PER-ROW position tables (left-padded batches: each row
    starts counting positions at its first real token). x: [B, L, H, D];
    cos/sin: [B, L, D/2]."""
    return _rope_rotate(x, cos[:, :, None, :], sin[:, :, None, :])


class LlamaAttention(Layer):
    """GQA attention, shared by the Llama/Qwen2-MoE/DeepSeek families —
    ``config.qkv_bias`` (default False) is the only signature difference
    between them (Qwen2 adds bias to q/k/v)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.hidden_size = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = config.hidden_size // config.num_attention_heads
        init = Normal(0.0, config.initializer_range)
        qkv_bias = getattr(config, "qkv_bias", False)
        self.q_proj = ColumnParallelLinear(
            self.hidden_size, self.num_heads * self.head_dim,
            weight_attr=None, has_bias=qkv_bias, gather_output=False)
        self.k_proj = ColumnParallelLinear(
            self.hidden_size, self.num_kv_heads * self.head_dim,
            has_bias=qkv_bias, gather_output=False)
        self.v_proj = ColumnParallelLinear(
            self.hidden_size, self.num_kv_heads * self.head_dim,
            has_bias=qkv_bias, gather_output=False)
        self.o_proj = RowParallelLinear(
            self.num_heads * self.head_dim, self.hidden_size,
            has_bias=False, input_is_parallel=True)

    def forward(self, hidden_states, rope_cos, rope_sin,
                attention_mask=None, kv_cache=None, offset=None,
                position_ids=None, block_tables=None, cache_lens=None,
                ragged_meta=None):
        b, l, _ = hidden_states.shape
        with component("mixer.in"):
            q = self.q_proj(hidden_states)
            k = self.k_proj(hidden_states)
            v = self.v_proj(hidden_states)

        if kv_cache is not None and block_tables is not None \
                and ragged_meta is not None:
            # ragged mixed batch: [1, R] packed rows over the pool
            return self._forward_ragged(q, k, v, rope_cos, rope_sin,
                                        kv_cache, block_tables,
                                        cache_lens, ragged_meta, b, l)
        if kv_cache is not None and block_tables is not None:
            # paged decode: kv_cache is the shared (k_pool, v_pool)
            return self._forward_paged(q, k, v, rope_cos, rope_sin,
                                       kv_cache, block_tables,
                                       cache_lens, b, l)
        if kv_cache is not None:
            # attention_mask here is the [B, S] cache-length pad mask
            # (left-padded batches); position_ids [B, L] give each row
            # its own rope positions
            return self._forward_cached(q, k, v, rope_cos, rope_sin,
                                        kv_cache, offset, b, l,
                                        attention_mask=attention_mask,
                                        position_ids=position_ids)

        def attn(q_a, k_a, v_a, cos, sin):
            qh = q_a.reshape(b, l, self.num_heads, self.head_dim)
            kh = k_a.reshape(b, l, self.num_kv_heads, self.head_dim)
            vh = v_a.reshape(b, l, self.num_kv_heads, self.head_dim)
            qh = _apply_rope(qh, cos, sin)
            kh = _apply_rope(kh, cos, sin)
            from ..distributed.shard_utils import in_manual_region
            if mesh_axis_size("sep") > 1 and not in_manual_region():
                from ..distributed.sep_parallel import sep_attention
                rep = self.num_heads // self.num_kv_heads
                kh = jnp.repeat(kh, rep, axis=2)
                vh = jnp.repeat(vh, rep, axis=2)
                out = sep_attention(qh, kh, vh, causal=True)
            else:
                from ..ops.pallas.flash_attention import \
                    flash_attention_core
                # grouped kv heads pass through unexpanded — the Pallas
                # kernel routes each query head to its kv group via the
                # BlockSpec index map (the XLA fallback repeats inside)
                out = flash_attention_core(qh, kh, vh, is_causal=True)
            return out.reshape(b, l, self.num_heads * self.head_dim)

        ctx = apply_jax("llama_attention", attn, q, k, v, rope_cos,
                        rope_sin)
        ctx = constraint(ctx, None, None, "mp")
        return self.o_proj(ctx)

    def _forward_paged(self, q, k, v, rope_cos, rope_sin, kv_cache,
                       block_tables, cache_lens, b, l):
        """Continuous-batching decode attention over the paged block
        pool: per-slot rope positions come from ``cache_lens`` (each
        slot sits at its own sequence position; window token ``t`` of a
        speculative verify chunk at ``cache_lens + t``), the K/V write
        and the ragged attention run through
        ``paged_attention_decode``."""
        ctx, kp2, vp2 = self._attend_paged(q, k, v, rope_cos, rope_sin,
                                           kv_cache, block_tables,
                                           cache_lens, b, l)
        with component("mixer.out"):
            ctx = constraint(ctx, None, None, "mp")
            return self.o_proj(ctx), (kp2, vp2)

    def _attend_paged(self, q, k, v, rope_cos, rope_sin, kv_cache,
                      block_tables, cache_lens, b, l):
        """Rope + pool write + ragged paged attention WITHOUT the
        O-projection — the shared core of ``_forward_paged`` and the
        fused decode path (which runs the O-projection inside the
        fused residual-add epilogue). Returns ``(ctx [B, L, H*D],
        k_pool, v_pool)``."""

        def attn_p(q_a, k_a, v_a, cos_t, sin_t, kp, vp, tables, lens):
            qh = q_a.reshape(b, l, self.num_heads, self.head_dim)
            kh = k_a.reshape(b, l, self.num_kv_heads, self.head_dim)
            vh = v_a.reshape(b, l, self.num_kv_heads, self.head_dim)
            pos = lens.astype(jnp.int32)[:, None] \
                + jnp.arange(l, dtype=jnp.int32)[None, :]   # [S, L]
            cos = cos_t[pos]                             # [S, L, D/2]
            sin = sin_t[pos]
            qh = _apply_rope_rows(qh, cos, sin)
            kh = _apply_rope_rows(kh, cos, sin)
            out, kp2, vp2 = paged_attention_decode(
                qh, kh, vh, kp, vp, tables, lens, self.head_dim)
            return (out.reshape(b, l, self.num_heads * self.head_dim),
                    kp2, vp2)

        with component("mixer.glue"):
            return apply_jax(
                "llama_attention_paged", attn_p, q, k, v, rope_cos,
                rope_sin, kv_cache[0], kv_cache[1], block_tables,
                cache_lens, n_outputs=3)

    def _forward_ragged(self, q, k, v, rope_cos, rope_sin, kv_cache,
                        block_tables, cache_lens, ragged_meta, b, l):
        """Ragged mixed-batch attention: the hidden states arrive as
        ONE packed row buffer ``[1, R, hidden]`` (decode rows, verify
        windows and prefill chunks of every slot, concatenated); rope
        positions come per ROW (``row_pos`` — pad rows carry an
        overflow position whose clamped rope garbage never survives
        the null-routed write), and the write+attend runs through
        ``ragged_paged_attention_decode``."""
        ctx, kp2, vp2 = self._attend_ragged(q, k, v, rope_cos,
                                            rope_sin, kv_cache,
                                            block_tables, cache_lens,
                                            ragged_meta, b, l)
        with component("mixer.out"):
            ctx = constraint(ctx, None, None, "mp")
            return self.o_proj(ctx), (kp2, vp2)

    def _attend_ragged(self, q, k, v, rope_cos, rope_sin, kv_cache,
                       block_tables, cache_lens, ragged_meta, b, l):
        """Per-row rope + scatter + ragged attention WITHOUT the
        O-projection — the shared core of ``_forward_ragged`` and the
        fused decode path. Returns ``(ctx [B, L, H*D], k_pool,
        v_pool)``."""
        (q_lens, row_starts, row_slot, row_pos, narrow_iota,
         win_iota) = ragged_meta

        def attn_r(q_a, k_a, v_a, cos_t, sin_t, kp, vp, tables, lens,
                   ql, rs, sl, pos_r, nwin, win):
            r = b * l                       # packed rows (b == 1)
            qh = q_a.reshape(r, self.num_heads, self.head_dim)
            kh = k_a.reshape(r, self.num_kv_heads, self.head_dim)
            vh = v_a.reshape(r, self.num_kv_heads, self.head_dim)
            pos = jnp.clip(pos_r.astype(jnp.int32), 0,
                           cos_t.shape[0] - 1)            # [R]
            cos = cos_t[pos]                              # [R, D/2]
            sin = sin_t[pos]
            qh = _rope_rotate(qh, cos[:, None, :], sin[:, None, :])
            kh = _rope_rotate(kh, cos[:, None, :], sin[:, None, :])
            out, kp2, vp2 = ragged_paged_attention_decode(
                qh, kh, vh, kp, vp, tables, lens, ql, rs, sl, pos_r,
                nwin, win, self.head_dim)
            return (out.reshape(b, l, self.num_heads * self.head_dim),
                    kp2, vp2)

        with component("mixer.glue"):
            return apply_jax(
                "llama_attention_ragged", attn_r, q, k, v, rope_cos,
                rope_sin, kv_cache[0], kv_cache[1], block_tables,
                cache_lens, q_lens, row_starts, row_slot, row_pos,
                narrow_iota, win_iota, n_outputs=3)

    def _forward_cached(self, q, k, v, rope_cos, rope_sin, kv_cache,
                        offset, b, l, attention_mask=None,
                        position_ids=None):
        """Incremental-decode attention: write this chunk's K/V into the
        static-shape cache at ``offset`` and attend against the full
        cache under a causal-with-offset mask (KV-cache decode path —
        reference: PaddleNLP generation with ``cache_kvs``). rope tables
        arrive un-sliced; ``offset`` is a traced int32 scalar so one
        compiled program serves every decode step. Left-padded batches:
        ``attention_mask`` [B, S] masks pad cache slots and
        ``position_ids`` [B, L] give per-row rope positions."""
        with_rows = position_ids is not None
        with_mask = attention_mask is not None

        def attn_c(q_a, k_a, v_a, cos_t, sin_t, kc, vc, off, *rest):
            qh = q_a.reshape(b, l, self.num_heads, self.head_dim)
            kh = k_a.reshape(b, l, self.num_kv_heads, self.head_dim)
            vh = v_a.reshape(b, l, self.num_kv_heads, self.head_dim)
            off32 = off.astype(jnp.int32) if hasattr(off, "astype") \
                else off
            rest = list(rest)
            if with_rows:
                pos = rest.pop(0).astype(jnp.int32)     # [B, L]
                cos = cos_t[pos]                        # [B, L, D/2]
                sin = sin_t[pos]
                qh = _apply_rope_rows(qh, cos, sin)
                kh = _apply_rope_rows(kh, cos, sin)
            else:
                cos = jax.lax.dynamic_slice_in_dim(cos_t, off32, l, 0)
                sin = jax.lax.dynamic_slice_in_dim(sin_t, off32, l, 0)
                qh = _apply_rope(qh, cos, sin)
                kh = _apply_rope(kh, cos, sin)
            extra = None
            if with_mask:
                m = rest.pop(0)                         # [B, S]
                extra = jnp.where(m > 0, 0.0, -1e9)[:, None, None, :]
            out, kc2, vc2 = cached_attention(qh, kh, vh, kc, vc, off32,
                                             self.head_dim,
                                             extra_bias=extra)
            return (out.reshape(b, l, self.num_heads * self.head_dim),
                    kc2, vc2)

        extras = []
        if with_rows:
            extras.append(position_ids)
        if with_mask:
            extras.append(attention_mask)
        ctx, kc2, vc2 = apply_jax(
            "llama_attention_cached", attn_c, q, k, v, rope_cos, rope_sin,
            kv_cache[0], kv_cache[1], offset, *extras, n_outputs=3)
        ctx = constraint(ctx, None, None, "mp")
        return self.o_proj(ctx), (kc2, vc2)


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.gate_proj = ColumnParallelLinear(
            config.hidden_size, config.intermediate_size, has_bias=False,
            gather_output=False)
        self.up_proj = ColumnParallelLinear(
            config.hidden_size, config.intermediate_size, has_bias=False,
            gather_output=False)
        self.down_proj = RowParallelLinear(
            config.intermediate_size, config.hidden_size, has_bias=False,
            input_is_parallel=True)

    def forward(self, x):
        with component("ffn"):
            return self.down_proj(swiglu(self.gate_proj(x),
                                         self.up_proj(x)))


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = RMSNorm(config.hidden_size,
                                       config.rms_norm_eps)
        self.post_attention_layernorm = RMSNorm(config.hidden_size,
                                                config.rms_norm_eps)

    def _fused_decode_eligible(self):
        """Fused decode-tick path gate: a serving trace armed the
        fused scope (``ops/pallas/decode_fused`` — engine kill switch,
        config flag, GSPMD-TP exclusion all fold into the mode) and
        every weight the fused kernels would consume is a plain float
        tensor (weight-only int8 layers keep the module path)."""
        from ..ops.pallas import decode_fused as _df
        if _df.fused_decode_mode() is None:
            return False
        attn, mlp = self.self_attn, self.mlp
        return _df.fused_params_ok(
            self.input_layernorm.weight,
            self.post_attention_layernorm.weight,
            getattr(attn.q_proj, "weight", None),
            getattr(attn.k_proj, "weight", None),
            getattr(attn.v_proj, "weight", None),
            getattr(attn.o_proj, "weight", None),
            getattr(mlp.gate_proj, "weight", None),
            getattr(mlp.up_proj, "weight", None),
            getattr(mlp.down_proj, "weight", None))

    def _forward_decode_fused(self, hidden_states, rope_cos, rope_sin,
                              kv_cache, block_tables, cache_lens,
                              ragged_meta):
        """Mega-kernelized decode tick (ISSUE 13): the four per-layer
        fusion boundaries closed — RMSNorm fused into the QKV
        projection prologue, the attention epilogue into the
        O-projection + residual add, the post-attention RMSNorm into
        the gate/up prologue, and swiglu into the down-projection +
        residual add — via ``ops/pallas/decode_fused``. Per-layer
        activations stay in VMEM across every boundary on TPU; the
        XLA fallback is bitwise this layer's unfused ops, so CPU
        engines with fusion ON compile today's graph unchanged."""
        from ..ops.pallas import decode_fused as _df
        from ..ops import lora as _lora
        attn = self.self_attn
        b, l, _ = hidden_states.shape
        eps = self.input_layernorm._epsilon
        with component("mixer.in"):
            q, k, v = _df.norm_matmul(
                hidden_states, self.input_layernorm.weight, None,
                [attn.q_proj.weight, attn.k_proj.weight,
                 attn.v_proj.weight],
                [attn.q_proj.bias, attn.k_proj.bias, attn.v_proj.bias],
                eps=eps, kind="rms")
            if _lora.armed(attn.q_proj) or _lora.armed(attn.k_proj) \
                    or _lora.armed(attn.v_proj):
                # multi-LoRA serving composes per MODULE: the fused
                # prologue stays; the armed projections add their
                # ragged grouped-matmul delta off the recomputed norm
                # (bitwise the norm the unfused module path feeds
                # them, so fused ON==OFF stays token-exact under
                # adapters too)
                hn = self.input_layernorm(hidden_states)
                q = _lora.apply(attn.q_proj, hn, q)
                k = _lora.apply(attn.k_proj, hn, k)
                v = _lora.apply(attn.v_proj, hn, v)
        if ragged_meta is not None:
            ctx, kp2, vp2 = attn._attend_ragged(
                q, k, v, rope_cos, rope_sin, kv_cache, block_tables,
                cache_lens, ragged_meta, b, l)
        else:
            ctx, kp2, vp2 = attn._attend_paged(
                q, k, v, rope_cos, rope_sin, kv_cache, block_tables,
                cache_lens, b, l)
        with component("mixer.out"):
            if _lora.armed(attn.o_proj):
                # an armed epilogue falls back to module call +
                # residual add (the unfused ordering — module forward
                # applies the delta), keeping the prologue fusions above
                h = hidden_states + attn.o_proj(ctx)
            else:
                h = _df.matmul_residual([ctx], attn.o_proj.weight,
                                        attn.o_proj.bias, hidden_states)
        mlp = self.mlp
        with component("ffn"):
            g, u = _df.norm_matmul(
                h, self.post_attention_layernorm.weight, None,
                [mlp.gate_proj.weight, mlp.up_proj.weight], [None, None],
                eps=self.post_attention_layernorm._epsilon, kind="rms")
            if _lora.armed(mlp.gate_proj) or _lora.armed(mlp.up_proj):
                hn2 = self.post_attention_layernorm(h)
                g = _lora.apply(mlp.gate_proj, hn2, g)
                u = _lora.apply(mlp.up_proj, hn2, u)
            if _lora.armed(mlp.down_proj):
                out = h + mlp.down_proj(swiglu(g, u))
            else:
                out = _df.matmul_residual([g, u], mlp.down_proj.weight,
                                          mlp.down_proj.bias, h,
                                          act="swiglu")
        return out, (kp2, vp2)

    def forward(self, hidden_states, rope_cos, rope_sin,
                attention_mask=None, kv_cache=None, offset=None,
                position_ids=None, block_tables=None, cache_lens=None,
                ragged_meta=None):
        if kv_cache is not None and block_tables is not None \
                and self._fused_decode_eligible():
            return self._forward_decode_fused(
                hidden_states, rope_cos, rope_sin, kv_cache,
                block_tables, cache_lens, ragged_meta)
        residual = hidden_states
        with component("norm"):
            h = self.input_layernorm(hidden_states)
        new_cache = None
        if kv_cache is not None:
            h, new_cache = self.self_attn(h, rope_cos, rope_sin,
                                          attention_mask, kv_cache, offset,
                                          position_ids=position_ids,
                                          block_tables=block_tables,
                                          cache_lens=cache_lens,
                                          ragged_meta=ragged_meta)
        else:
            h = self.self_attn(h, rope_cos, rope_sin, attention_mask)
            # tag for the "save_attn" selective remat policy: keep the
            # attention output, replay only norms/MLP in backward
            from jax.ad_checkpoint import checkpoint_name
            h = apply_jax("attn_out_tag",
                          lambda a: checkpoint_name(a, "attn_out"), h)
        with component("norm"):     # the residual stream, then its norm
            h = residual + h
            residual = h
            h2 = self.post_attention_layernorm(h)
        h2 = self.mlp(h2)
        with component("norm"):
            out = residual + h2
        if kv_cache is not None:
            return out, new_cache
        return out


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.embed_tokens = VocabParallelEmbedding(
            config.vocab_size, config.hidden_size)
        from ..nn.layer.container import LayerList
        self.layers = LayerList(
            [LlamaDecoderLayer(config)
             for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        head_dim = config.hidden_size // config.num_attention_heads
        cos, sin = _rope_tables(config.max_position_embeddings, head_dim,
                                config.rope_theta)
        self._rope_cos = Tensor(cos)
        self._rope_sin = Tensor(sin)

    def forward(self, input_ids, attention_mask=None, position_ids=None,
                caches=None, offset=None, block_tables=None,
                cache_lens=None, ragged_meta=None):
        input_ids = batch_shard(input_ids)
        with component("embed"):
            h = self.embed_tokens(input_ids)
        if caches is not None:
            # decode path: full rope tables + per-layer kv caches
            # (dense [B, S, H, D] pairs, or — with block_tables — the
            # shared paged (k_pool, v_pool) per layer; with
            # ragged_meta, ONE packed mixed-batch row buffer)
            cos, sin = self._rope_cos, self._rope_sin
            new_caches = []
            for i, (layer, kv) in enumerate(zip(self.layers, caches)):
                with component(f"L{i}.attn"):
                    h, kv2 = layer(h, cos, sin, attention_mask,
                                   kv_cache=kv, offset=offset,
                                   position_ids=position_ids,
                                   block_tables=block_tables,
                                   cache_lens=cache_lens,
                                   ragged_meta=ragged_meta)
                new_caches.append(kv2)
            with component("norm"):
                return self.norm(h), new_caches
        l = h.shape[1]
        cos = _wrap_out(as_jax(self._rope_cos)[:l])
        sin = _wrap_out(as_jax(self._rope_sin)[:l])
        from ..distributed.recompute import recompute
        interval = max(getattr(self.config, "recompute_interval", 1), 1)
        for i, layer in enumerate(self.layers):
            if self.config.recompute and self.training \
                    and i % interval == 0:
                h = recompute(layer, h, cos, sin, attention_mask)
            else:
                h = layer(h, cos, sin, attention_mask)
        return self.norm(h)


class LlamaPretrainingCriterion(Layer):
    """Masked cross entropy over pre-shifted labels (PaddleNLP
    ``LlamaPretrainingCriterion`` parity: the DATASET shifts —
    ``labels[t]`` is the target for ``logits[t]``; the criterion never
    shifts internally. Round-3 fix: the previous internal shift made
    ported reference scripts silently train on t+2 targets)."""

    def __init__(self, config: LlamaConfig = None, ignore_index=-100):
        super().__init__()
        self.ignore_index = ignore_index

    def forward(self, logits, labels):
        def f(lg, lb):
            # CE via explicit row logsumexp instead of log_softmax: a
            # full log_softmax materializes TWO [B, L, V] f32 arrays
            # (~1 GB each at the bench shapes) where only [B, L] row
            # stats are needed. The max runs on the input dtype and the
            # f32 upcast happens on (lg - m), whose ONLY consumer is the
            # exp-sum reduction — XLA fuses it into the reduce, so no
            # vocab-size f32 array ever reaches HBM.
            m = jax.lax.stop_gradient(
                jnp.max(lg, axis=-1, keepdims=True))
            zs = (lg - m).astype(jnp.float32)
            lse = m[..., 0].astype(jnp.float32) + jnp.log(
                jnp.sum(jnp.exp(zs), axis=-1))
            lb_i = lb.astype(jnp.int32)
            picked = jnp.take_along_axis(
                lg, jnp.clip(lb_i, 0)[..., None],
                axis=-1)[..., 0].astype(jnp.float32)
            valid = lb_i != self.ignore_index
            loss = jnp.where(valid, lse - picked, 0.0)
            return jnp.sum(loss) / jnp.maximum(
                jnp.sum(valid.astype(jnp.float32)), 1.0)
        return apply_jax("llama_ce", f, logits, labels)


class LlamaForCausalLM(Layer, GenerationMixin):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = LlamaModel(config)
        if not config.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=True)
        self.criterion = LlamaPretrainingCriterion(config)

    def forward(self, input_ids, labels=None, attention_mask=None,
                position_ids=None, caches=None, offset=None,
                block_tables=None, cache_lens=None, ragged_meta=None,
                return_hidden=False):
        if caches is not None:
            h, new_caches = self.llama(input_ids, attention_mask,
                                       position_ids, caches=caches,
                                       offset=offset,
                                       block_tables=block_tables,
                                       cache_lens=cache_lens,
                                       ragged_meta=ragged_meta)
            with component("head"):
                logits = self._head_and_loss(h, None)
            return ((logits, h) if return_hidden else logits), new_caches
        h = self.llama(input_ids, attention_mask, position_ids)
        return self._head_and_loss(h, labels)

    def init_caches(self, batch_size: int, max_length: int):
        """Zeroed per-layer (k, v) caches [B, S, H_kv, D] for decode."""
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        dtype = jnp.dtype(cfg.dtype)
        return [
            (jnp.zeros((batch_size, max_length, cfg.num_key_value_heads,
                        head_dim), dtype),
             jnp.zeros((batch_size, max_length, cfg.num_key_value_heads,
                        head_dim), dtype))
            for _ in range(cfg.num_hidden_layers)
        ]

    def init_paged_caches(self, num_blocks: int, block_size: int,
                          sharding=None, kv_cache_dtype=None):
        """Zeroed per-layer paged (k_pool, v_pool), each
        [num_blocks, block_size, H_kv, D] — the shared serving cache
        (block 0 is the null block; see ``ops/paged_cache.py``).
        ``sharding``: tensor-parallel pool placement (normally
        ``ops.paged_cache.pool_sharding(mesh)`` — the kv_head split),
        so each shard materializes only its slice. ``kv_cache_dtype``:
        ``"int8"`` builds quantized ``QuantKV`` pools (int8 data +
        per-(block, position, head) absmax scales); None keeps the
        model dtype — bit-for-bit the pre-quantization layout."""
        from ..ops.paged_cache import init_pool
        cfg = self.config
        head_dim = cfg.hidden_size // cfg.num_attention_heads
        dtype = jnp.dtype(cfg.dtype) if kv_cache_dtype is None \
            else kv_cache_dtype
        return [
            init_pool(num_blocks, block_size, cfg.num_key_value_heads,
                      head_dim, dtype, sharding=sharding)
            for _ in range(cfg.num_hidden_layers)
        ]

    def _head_and_loss(self, h, labels):
        if self.config.tie_word_embeddings:
            from ..ops.linalg import matmul
            logits = matmul(h, self.llama.embed_tokens.weight,
                            transpose_y=True)
        else:
            logits = self.lm_head(h)
        if labels is not None:
            return self.criterion(logits, labels)
        return logits


class LlamaForCausalLMPipe(LlamaForCausalLM):
    """Pipeline-parallel Llama (``LlamaForCausalLMPipe`` parity —
    PaddleNLP ``llama/modeling_pp.py`` over fleet
    ``meta_parallel/pipeline_parallel.py``'s 1F1B schedule).

    TPU-first schedule: the homogeneous decoder stack runs through the
    shard_map + ppermute scan pipeline (``distributed/pipeline.py``);
    the heterogeneous first/last-stage work (embedding, final norm, head,
    loss) executes outside the ring in GSPMD land. Per-microbatch grad
    accumulation and the 1F1B/FThenB bookkeeping of the reference are
    subsumed by differentiating through the scan — the backward ring is
    the transposed ppermute, and XLA overlaps stage compute with the
    permutes. Parameter layout and state_dict are identical to
    ``LlamaForCausalLM`` (same sublayers), so pp=1 checkpoints load
    unchanged and numeric parity is testable layer-for-layer."""

    def __init__(self, config: LlamaConfig, num_micro_batches=None,
                 num_stages=None):
        super().__init__(config)
        self.num_micro_batches = num_micro_batches
        self._num_stages = num_stages

    def forward(self, input_ids, labels=None, attention_mask=None,
                position_ids=None):
        from ..distributed.shard_utils import current_mesh
        mesh = current_mesh()
        pp = self._num_stages or (
            mesh.shape.get("pp", 1) if mesh is not None else 1)
        n_layers = self.config.num_hidden_layers
        if pp <= 1 or mesh is None or mesh.shape.get("pp", 1) <= 1 \
                or n_layers % pp != 0 or attention_mask is not None:
            # attention_mask is not threaded through the pipeline stage
            # function — run the (numerically identical) sequential path
            if attention_mask is not None and pp > 1:
                import warnings
                warnings.warn(
                    "LlamaForCausalLMPipe: attention_mask given; running "
                    "the sequential (non-pipelined) path")
            return super().forward(input_ids, labels, attention_mask,
                                   position_ids)
        lps = n_layers // pp

        core = self.llama
        input_ids = batch_shard(input_ids)
        h = core.embed_tokens(input_ids)
        b, l = h.shape[0], h.shape[1]
        cos = as_jax(core._rope_cos)[:l]
        sin = as_jax(core._rope_sin)[:l]

        n_micro = self.num_micro_batches or pp
        n_micro = min(n_micro, b)
        while b % n_micro != 0:  # static python loop at trace time
            n_micro -= 1

        from ..jit import _LayerBinder
        binder = _LayerBinder(core.layers[0])
        param_tensors = [p for lay in core.layers
                         for _, p in _LayerBinder(lay).param_items]
        n_p = len(binder.param_items)
        recompute = self.config.recompute and self.training

        def one_layer(params_local, x, cos_a, sin_a, i):
            arrs = [p[i] for p in params_local]
            out, _ = binder.call(
                arrs, [], (_wrap_out(x), _wrap_out(cos_a),
                           _wrap_out(sin_a)), {})
            return as_jax(out)

        def stage_fn(params_local, x, cos_a, sin_a):
            f = one_layer
            if recompute:
                f = jax.checkpoint(one_layer, static_argnums=(4,))
            for i in range(lps):
                x = f(params_local, x, cos_a, sin_a, i)
            return x

        from ..distributed.pipeline import pipeline_apply

        def run_pipe(h_a, cos_a, sin_a, *flat):
            per = [flat[k * n_p:(k + 1) * n_p] for k in range(n_layers)]
            # leaves [pp, lps, ...] — stage-major stacking
            stacked = [
                jnp.stack([jnp.stack([per[s * lps + i][j]
                                      for i in range(lps)])
                           for s in range(pp)])
                for j in range(n_p)
            ]
            mbs = h_a.reshape((n_micro, h_a.shape[0] // n_micro)
                              + h_a.shape[1:])
            out = pipeline_apply(stage_fn, stacked, mbs, mesh=mesh,
                                 extra_inputs=(cos_a, sin_a))
            return out.reshape(h_a.shape)

        h = apply_jax("llama_pipeline", run_pipe, h,
                      _wrap_out(cos), _wrap_out(sin), *param_tensors)
        h = core.norm(h)
        return self._head_and_loss(h, labels)

"""LLM generation: KV-cache incremental decode + sampling
(reference: PaddleNLP ``paddlenlp/generation/utils.py`` GenerationMixin
— the entry point BASELINE.json's north star serves through).

TPU-first: the whole decode loop is ONE jitted program — prefill writes
the prompt K/V into static-shape caches, then a ``lax.while_loop``
feeds one token per step with a traced position offset, so there is a
single compilation per (batch, prompt-len, max-new) shape and a single
host sync at the end. Early exit when every sequence hit EOS happens
inside the while condition, not in Python.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import Tensor, as_jax, _wrap_out
from .. import monitor as _monitor

__all__ = ["GenerationConfig", "GenerationMixin", "LoadedGeneration", "load_generation"]

# decode-loop compile-cache observability: varied prompt lengths should
# HIT via the power-of-two bucketing below, not compile fresh
# executables (the serving bar is zero steady-state recompiles)
_gen_cache_events = _monitor.counter(
    "generate_jit_cache", "generate() decode-loop compile-cache decisions",
    labels=("model", "event"))


@dataclass
class GenerationConfig:
    max_new_tokens: int = 20
    # greedy_search | sampling | beam_search | group_beam_search
    decode_strategy: str = "greedy_search"
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    num_beams: int = 1
    num_beam_groups: int = 1
    diversity_rate: float = 0.0        # PaddleNLP group-beam penalty
    length_penalty: float = 0.0        # score / len**length_penalty
    early_stopping: bool = False
    eos_token_id: Optional[int] = None
    pad_token_id: Optional[int] = None
    seed: Optional[int] = None
    # dense | paged — paged decodes through the serving block-pool KV
    # layout (ops/paged_cache.py + the ragged paged-attention kernel)
    cache_impl: str = "dense"
    kv_block_size: int = 16            # paged cache block size
    # None/'auto' = pool in the model dtype (bit-for-bit the
    # pre-quantization layout); 'int8' = quantized block pool (int8
    # data + per-(block, position, head) absmax scales — half the KV
    # HBM stream per decode step). Paged cache only. Env twin:
    # PADDLE_TPU_KV_INT8 (0 = kill switch, 1 = on when unset here).
    kv_cache_dtype: Optional[str] = None
    # left-pad prompts up to power-of-two length buckets so varied
    # prompt lengths reuse ONE compiled decode loop per bucket
    pad_prompt_to_bucket: bool = True
    # speculative decoding (gamma > 0): draft gamma tokens per step and
    # verify them in one multi-token paged forward, emitting 1..gamma+1
    # tokens. 0 = off. Rides the paged cache; see
    # ``generation/speculative.py`` + docs/OPS.md "Speculative
    # decoding". Kill switch: PADDLE_TPU_SPECULATIVE=0.
    num_speculative_tokens: int = 0
    # longest suffix n-gram the model-free prompt-lookup drafter matches
    spec_ngram_max: int = 3


def _prompt_bucket(n: int, minimum: int = 8) -> int:
    """Smallest power-of-two bucket >= n (floor ``minimum``)."""
    b = int(minimum)
    while b < n:
        b *= 2
    return b


def _filter_logits(logits, *, do_sample, temperature, top_k, top_p):
    """The temperature/top-k/top-p logits pipeline, factored out so the
    speculative verify step can apply the SAME modification to draft
    and target logits (the rejection-sampling soundness requirement).
    Works on any [..., V] shape; returns f32 filtered logits.

    The knobs may be python numbers (the original static path — baked
    into the trace, short-circuited when inert, bit-for-bit the
    historical graphs) OR traced jax values (scalars, or per-row
    arrays broadcastable over ``logits``' leading dims after trailing
    axes are appended): the serving engine's per-slot sampling tensors
    and ``generate()``'s traced sampling operand ride the traced path,
    so a new sampling config reuses the SAME executable — no recompile
    class. Inert traced values (t=1, k=0, p=1) produce bitwise the
    static path's logits (divide by 1.0 is IEEE-identity; a disabled
    filter masks nothing), which is what pins per-slot == engine-global
    token-exactness when the knobs are uniform."""
    logits = logits.astype(jnp.float32)
    if not do_sample:
        return logits
    if all(isinstance(v, (int, float, bool))
           for v in (temperature, top_k, top_p)):
        if temperature != 1.0:
            logits = logits / max(temperature, 1e-6)
        if top_k:
            kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
            logits = jnp.where(logits < kth, -jnp.inf, logits)
        if top_p < 1.0:
            sorted_logits = jnp.flip(jnp.sort(logits, axis=-1),
                                     axis=-1)
            probs = jax.nn.softmax(sorted_logits, axis=-1)
            cum = jnp.cumsum(probs, axis=-1)
            # keep tokens until the cumulative prob of *previous* kept
            # ones exceeds top_p (always keeps the first)
            drop = cum - probs > top_p
            kept = jnp.where(drop, jnp.inf, sorted_logits)
            thresh = jnp.min(kept, axis=-1, keepdims=True)
            logits = jnp.where(logits < thresh, -jnp.inf, logits)
        return logits

    # -- traced-knob path (per-slot device tensors) -------------------
    def _bc(v):
        """Align a traced knob against logits' leading dims: trailing
        axes appended so [S] broadcasts over [S, G+1, V] windows."""
        v = jnp.asarray(v, jnp.float32)
        if v.ndim:
            v = v.reshape(v.shape + (1,) * (logits.ndim - 1 - v.ndim))
        return v

    t = _bc(temperature)
    logits = logits / jnp.maximum(t, 1e-6)[..., None]
    k = _bc(top_k).astype(jnp.int32)
    p = _bc(top_p)
    v_dim = logits.shape[-1]

    # each vocab-wide filter (a full sort + reductions) sits behind a
    # runtime lax.cond: an inert knob (k=0 / p=1 — the common default
    # config) SKIPS the sort at execution time, so moving the knobs
    # out of the trace costs the cheap config nothing — same
    # executable either way, and when a filter IS live its branch is
    # op-for-op the unconditional code (bitwise the static path)
    def _topk(lg):
        sorted_desc = jnp.flip(jnp.sort(lg, axis=-1), axis=-1)
        kth = jnp.take_along_axis(
            sorted_desc,
            jnp.broadcast_to(jnp.clip(k - 1, 0, v_dim - 1)[..., None],
                             lg.shape[:-1] + (1,)), axis=-1)
        return jnp.where((k[..., None] > 0) & (lg < kth),
                         -jnp.inf, lg)

    def _topp(lg):
        # sorts AFTER the top-k mask — the static path's op order, so
        # uniform traced knobs reproduce its values exactly
        sorted2 = jnp.flip(jnp.sort(lg, axis=-1), axis=-1)
        probs = jax.nn.softmax(sorted2, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # the (p < 1) row gate mirrors _topk's (k > 0): an inert row
        # sharing the batch with an active one must mask NOTHING —
        # without it, f32 cumsum overshoot past 1.0 can drop a p=1.0
        # row's tail tokens (cross-request interference)
        drop = (cum - probs > p[..., None]) & (p[..., None] < 1.0)
        kept = jnp.where(drop, jnp.inf, sorted2)
        thresh = jnp.min(kept, axis=-1, keepdims=True)
        return jnp.where(lg < thresh, -jnp.inf, lg)

    logits = jax.lax.cond(jnp.any(k > 0), _topk, lambda lg: lg,
                          logits)
    return jax.lax.cond(jnp.any(p < 1.0), _topp, lambda lg: lg,
                        logits)


def _select_token(logits, key, *, do_sample, temperature, top_k, top_p):
    """(token, logprob-of-token) for one step. logits: [B, V]."""
    logits = _filter_logits(logits, do_sample=do_sample,
                            temperature=temperature, top_k=top_k,
                            top_p=top_p)
    logp = jax.nn.log_softmax(logits, axis=-1)
    if do_sample:
        tok = jax.random.categorical(key, logits)
    else:
        tok = jnp.argmax(logits, axis=-1)
    tok = tok.astype(jnp.int32)
    picked = jnp.take_along_axis(logp, tok[:, None], axis=-1)[:, 0]
    return tok, picked


class GenerationMixin:
    """Adds ``generate()`` to a causal-LM Layer that implements the cache
    protocol: ``init_caches(batch, max_len)`` and
    ``forward(input_ids, caches=..., offset=...) -> (logits, caches)``."""

    # -- shared decode machinery (generate() and export_generation use
    # the SAME loop; any decode fix lands in both) -------------------

    def _check_lengths(self, prompt_len, max_new):
        max_pos = getattr(getattr(self, "config", None),
                          "max_position_embeddings", None)
        if max_pos is not None and prompt_len + max_new > max_pos:
            # beyond the rope/position tables the dynamic slices clamp
            # and silently reuse the last position — error instead
            from ..framework.errors import InvalidArgumentError
            raise InvalidArgumentError(
                f"prompt ({prompt_len}) + max_new_tokens ({max_new}) "
                f"exceeds max_position_embeddings ({max_pos})")

    def _bucket_eligible(self):
        """Prompt bucketing rides the left-padded (mask + per-row rope)
        path, so the model's forward must accept it; capacity-routed MoE
        is excluded because pad tokens would compete for expert capacity
        and perturb the real tokens' outputs."""
        import inspect
        sig = inspect.signature(type(self).forward).parameters
        if "attention_mask" not in sig or "position_ids" not in sig:
            return False
        cfg = getattr(self, "config", None)
        n_experts = getattr(cfg, "num_experts", 0) \
            or getattr(cfg, "n_routed_experts", 0)   # DeepSeek naming
        if n_experts and not getattr(cfg, "dropless", False):
            return False
        return True

    @staticmethod
    def _resolve_strategy(strategy):
        if strategy not in ("greedy_search", "sampling", "beam_search",
                            "group_beam_search"):
            raise NotImplementedError(
                f"decode_strategy {strategy!r}; supported: greedy_search, "
                "sampling, beam_search, group_beam_search")
        return strategy == "sampling"

    def _build_model_step(self, binder, buffers, want_hidden=False):
        def model_step(params_a, tok_ids, caches, off, mask=None,
                      pos=None, block_tables=None, cache_lens=None,
                      ragged_meta=None):
            # a layer's cache is a tuple of arrays: a (k, v) pair, or
            # the one array of a latent (MLA) cache
            t_caches = [tuple(_wrap_out(c) for c in layer)
                        for layer in caches]
            kwargs = {"caches": t_caches}
            if off is not None:
                kwargs["offset"] = _wrap_out(off)
            if mask is not None:
                kwargs["attention_mask"] = _wrap_out(mask)
            if pos is not None:
                kwargs["position_ids"] = _wrap_out(pos)
            if block_tables is not None:
                # paged decode: caches are the shared (k_pool, v_pool)
                kwargs["block_tables"] = _wrap_out(block_tables)
                kwargs["cache_lens"] = _wrap_out(cache_lens)
            if ragged_meta is not None:
                # ragged mixed batch: (q_lens, row_starts, row_slot,
                # row_pos, narrow_iota, win_iota) describing the
                # packed row buffer
                kwargs["ragged_meta"] = tuple(
                    _wrap_out(x) for x in ragged_meta)
            if want_hidden:
                # draft-head speculation needs the final hidden state
                # alongside the logits
                kwargs["return_hidden"] = True
            out, _ = binder.call(
                params_a, buffers, (_wrap_out(tok_ids),), kwargs)
            logits, new_caches = out
            new_caches = [tuple(as_jax(c) for c in layer)
                          for layer in new_caches]
            if want_hidden:
                logits, hidden = logits
                return (as_jax(logits), as_jax(hidden)), new_caches
            return as_jax(logits), new_caches
        return model_step

    def _build_run(self, binder, buffers, b, prompt_len, max_new,
                   select, eos, pad, with_scores, with_mask=False):
        """run(params, ids[, mask], key, samp) -> out ids [, scores]:
        prefill + one lax.while_loop with in-loop EOS early exit.
        ``samp`` is the traced [3] f32 sampling operand (temperature,
        top_k, top_p) — DATA, not part of the trace, so changing the
        sampling knobs reuses the same compiled loop. With
        ``with_mask`` (LEFT-padded batches): the [B, prompt] pad mask
        masks pad cache slots and re-bases each row's rope positions at
        its first real token (reference: PaddleNLP padded generation)."""

        model_step = self._build_model_step(binder, buffers)

        def run(params_a, ids_a, *rest):
            if with_mask:
                pad_mask, key, samp = rest
                pad_mask = pad_mask.astype(jnp.int32)
                full_mask = jnp.concatenate(
                    [pad_mask, jnp.ones((b, max_new), jnp.int32)], 1)
                n_real = jnp.sum(pad_mask, axis=1)          # [B]
                pos0 = jnp.maximum(
                    jnp.cumsum(pad_mask, axis=1) - 1, 0)    # [B, prompt]
            else:
                (key, samp) = rest
                full_mask, pos0, n_real = None, None, None
            caches = self.init_caches(b, prompt_len + max_new)
            logits, caches = model_step(params_a, ids_a, caches,
                                        jnp.zeros((), jnp.int32),
                                        mask=full_mask, pos=pos0)
            key, sub = jax.random.split(key)
            tok, logp = select(logits[:, -1, :], sub, samp)
            done = tok == eos
            out = jnp.full((b, max_new), pad, jnp.int32)
            out = out.at[:, 0].set(jnp.where(done, eos, tok))
            score = logp

            def cond(c):
                return (c[0] < max_new) & jnp.logical_not(jnp.all(c[4]))

            def body(c):
                i, tok, caches, out, done, score, key = c
                off = jnp.asarray(prompt_len - 1, jnp.int32) + i
                pos_i = None if not with_mask else \
                    (n_real + i - 1)[:, None].astype(jnp.int32)
                logits, caches = model_step(params_a, tok[:, None],
                                            caches, off,
                                            mask=full_mask, pos=pos_i)
                key, sub = jax.random.split(key)
                ntok, logp = select(logits[:, -1, :], sub, samp)
                ntok = jnp.where(done, jnp.int32(pad), ntok)
                score = score + jnp.where(done, 0.0, logp)
                out = jax.lax.dynamic_update_slice(
                    out, ntok[:, None], (jnp.int32(0), i))
                done = done | (ntok == eos)
                return (i + 1, ntok, caches, out, done, score, key)

            state = (jnp.int32(1), tok, caches, out, done, score, key)
            state = jax.lax.while_loop(cond, body, state)
            if with_scores:
                return state[3], state[5]
            return state[3]
        return run

    def _build_run_paged(self, binder, buffers, b, prompt_len, max_new,
                         select, eos, pad, with_scores, block_size,
                         kv_cache_dtype=None):
        """Paged-KV twin of ``_build_run``: prefill goes through the
        dense cached path (bit-identical numerics), its K/V scatter into
        a block pool (contiguous static block tables — generate() owns
        the whole pool, so no allocator), and the while-loop decodes
        through the ragged paged-attention path. Exercises the exact
        cache layout + kernels the serving engine runs, which is what
        the paged-vs-dense parity tests pin down."""
        from ..ops import paged_cache as _pc

        model_step = self._build_model_step(binder, buffers)
        mb = _pc.blocks_for(prompt_len + max_new, block_size)
        tables_np = (1 + np.arange(b * mb, dtype=np.int32)) \
            .reshape(b, mb)                    # block 0 stays null
        num_blocks = 1 + b * mb

        def run(params_a, ids_a, key, samp):
            tables = jnp.asarray(tables_np)
            # kwarg passed only when set, so pre-quantization
            # duck-typed models keep working on the default path
            pools = self.init_paged_caches(
                num_blocks, block_size,
                **({"kv_cache_dtype": kv_cache_dtype}
                   if kv_cache_dtype else {}))
            dense = self.init_caches(b, prompt_len)
            logits, dense = model_step(params_a, ids_a, dense,
                                       jnp.zeros((), jnp.int32))
            pools = [_pc.write_prefill(kp, vp, tables, dk, dv)
                     for (kp, vp), (dk, dv) in zip(pools, dense)]
            key, sub = jax.random.split(key)
            tok, logp = select(logits[:, -1, :], sub, samp)
            done = tok == eos
            out = jnp.full((b, max_new), pad, jnp.int32)
            out = out.at[:, 0].set(jnp.where(done, eos, tok))
            score = logp

            def cond(c):
                return (c[0] < max_new) & jnp.logical_not(jnp.all(c[4]))

            def body(c):
                i, tok, pools, out, done, score, key = c
                off = jnp.asarray(prompt_len - 1, jnp.int32) + i
                lens = jnp.full((b,), off, jnp.int32)
                logits, pools = model_step(params_a, tok[:, None], pools,
                                           None, block_tables=tables,
                                           cache_lens=lens)
                key, sub = jax.random.split(key)
                ntok, logp = select(logits[:, -1, :], sub, samp)
                ntok = jnp.where(done, jnp.int32(pad), ntok)
                score = score + jnp.where(done, 0.0, logp)
                out = jax.lax.dynamic_update_slice(
                    out, ntok[:, None], (jnp.int32(0), i))
                done = done | (ntok == eos)
                return (i + 1, ntok, pools, out, done, score, key)

            state = (jnp.int32(1), tok, pools, out, done, score, key)
            state = jax.lax.while_loop(cond, body, state)
            if with_scores:
                return state[3], state[5]
            return state[3]
        return run


    def generate(self, input_ids, generation_config: GenerationConfig = None,
                 max_new_tokens=None, max_length=None,
                 decode_strategy=None, temperature=None, top_k=None,
                 top_p=None, num_beams=None, num_beam_groups=None,
                 diversity_rate=None, length_penalty=None,
                 early_stopping=None, eos_token_id=None,
                 pad_token_id=None, seed=None, attention_mask=None,
                 cache_impl=None, pad_prompt_to_bucket=None,
                 num_speculative_tokens=None, draft_model=None,
                 spec_ngram_max=None, spec_tree=None,
                 kv_cache_dtype=None, **kwargs):
        """Returns ``(ids, scores)``: generated token ids
        [B, max_new_tokens] (pad-filled after EOS) and the summed
        log-probability of the chosen tokens per sequence (for beam
        strategies: the best hypothesis and its length-penalized
        score)."""
        if kwargs:
            # silently dropping generation options produces output that
            # looks valid but ignores the request — fail instead
            raise TypeError(
                f"generate() got unsupported options {sorted(kwargs)}; "
                "supported: max_new_tokens/max_length, decode_strategy "
                "(greedy_search|sampling|beam_search|group_beam_search), "
                "temperature, top_k, top_p, num_beams, num_beam_groups, "
                "diversity_rate, length_penalty, early_stopping, "
                "eos_token_id, pad_token_id, seed, cache_impl "
                "(dense|paged), pad_prompt_to_bucket, "
                "num_speculative_tokens, draft_model, spec_ngram_max, "
                "kv_cache_dtype (None|'int8')")
        cfg = generation_config or GenerationConfig()
        if max_length is not None and max_new_tokens is None:
            max_new_tokens = max_length  # PaddleNLP: length of generation
        max_new = int(max_new_tokens or cfg.max_new_tokens)
        strategy = decode_strategy or cfg.decode_strategy
        do_sample = self._resolve_strategy(strategy)
        temperature = cfg.temperature if temperature is None \
            else float(temperature)
        top_k = cfg.top_k if top_k is None else int(top_k)
        top_p = cfg.top_p if top_p is None else float(top_p)
        num_beams = cfg.num_beams if num_beams is None else int(num_beams)
        num_beam_groups = cfg.num_beam_groups if num_beam_groups is None \
            else int(num_beam_groups)
        diversity_rate = cfg.diversity_rate if diversity_rate is None \
            else float(diversity_rate)
        length_penalty = cfg.length_penalty if length_penalty is None \
            else float(length_penalty)
        early_stopping = cfg.early_stopping if early_stopping is None \
            else bool(early_stopping)
        eos = eos_token_id if eos_token_id is not None else cfg.eos_token_id
        pad = pad_token_id if pad_token_id is not None else cfg.pad_token_id
        eos = -1 if eos is None else int(eos)   # -1 never matches
        pad = (eos if eos >= 0 else 0) if pad is None else int(pad)
        seed = cfg.seed if seed is None else seed
        if seed is None:
            seed = int(np.random.randint(0, 2 ** 31 - 1))
        _explicit_cache_impl = cache_impl     # None unless caller-passed
        cache_impl = cache_impl or getattr(cfg, "cache_impl", "dense")
        if cache_impl not in ("dense", "paged"):
            raise ValueError(
                f"cache_impl {cache_impl!r}; supported: dense, paged")
        # -- KV-pool quantization (paged cache only) ------------------
        from ..ops import paged_cache as _pcq
        _kv_req = kv_cache_dtype if kv_cache_dtype is not None \
            else getattr(cfg, "kv_cache_dtype", None)
        if _kv_req not in (None, "auto"):
            # an EXPLICIT int8 request rides the paged layout (the
            # dense cache has no block pool to quantize) — auto-select
            # it like speculative decoding does, and reject an
            # explicit dense request instead of silently ignoring the
            # option
            _pcq.resolve_kv_cache_dtype(_kv_req)    # validate early
            if _explicit_cache_impl == "dense":
                raise ValueError(
                    "kv_cache_dtype requires the paged cache; it "
                    "cannot run with an explicit cache_impl='dense'")
            cache_impl = "paged"
        # env twin consulted only where a block pool exists — the
        # PADDLE_TPU_KV_INT8=1 fleet default must not flip dense
        # decode paths
        kv_dtype = _pcq.resolve_kv_cache_dtype(_kv_req) \
            if cache_impl == "paged" else None
        if pad_prompt_to_bucket is None:
            pad_prompt_to_bucket = getattr(cfg, "pad_prompt_to_bucket",
                                           True)

        ids = as_jax(input_ids).astype(jnp.int32)
        if ids.ndim == 1:
            ids = ids[None]
        b, prompt_len = ids.shape
        self._check_lengths(prompt_len, max_new)

        from ..jit import _LayerBinder
        binder = _LayerBinder(self)
        params = binder.param_arrays()
        buffers = binder.buffer_arrays()

        is_beam = strategy in ("beam_search", "group_beam_search")
        if attention_mask is not None:
            if is_beam:
                raise NotImplementedError(
                    "beam search with left-padded prompts "
                    "(attention_mask) — pad to equal length instead")
            import inspect
            params_sig = inspect.signature(type(self).forward).parameters
            if "position_ids" not in params_sig or \
                    "attention_mask" not in params_sig:
                raise NotImplementedError(
                    f"{type(self).__name__} does not support "
                    "left-padded generation (its forward lacks "
                    "attention_mask/position_ids kwargs)")
            mask_np = np.asarray(
                attention_mask.numpy()
                if hasattr(attention_mask, "numpy") else attention_mask)
            ids_shape = tuple(as_jax(input_ids).shape)
            if ids_shape and mask_np.ndim == 1:
                mask_np = mask_np[None]
            if tuple(mask_np.shape) != ids_shape:
                raise ValueError(
                    f"attention_mask shape {tuple(mask_np.shape)} must "
                    f"match input_ids shape {ids_shape}")
            if (np.diff(mask_np, axis=1) < 0).any() or \
                    (mask_np[:, -1] != 1).any():
                # right padding would put pad-token queries at the
                # position the decode loop reads logits from — silently
                # wrong continuations, so reject loudly
                raise ValueError(
                    "attention_mask must be LEFT-padded (each row: 0s "
                    "then 1s, last column 1)")
        # inapplicable-option guard (same policy as the unknown-kwargs
        # guard above: dropping a requested option silently is worse
        # than failing)
        if is_beam and (temperature != 1.0 or top_k or top_p != 1.0):
            raise ValueError(
                f"{strategy} is deterministic; temperature/top_k/top_p "
                "do not apply (use decode_strategy='sampling')")
        if strategy == "beam_search" and (num_beam_groups > 1
                                          or diversity_rate):
            raise ValueError(
                "num_beam_groups/diversity_rate require "
                "decode_strategy='group_beam_search'")
        if not is_beam and num_beams > 1:
            raise ValueError(
                f"num_beams={num_beams} requires decode_strategy="
                "'beam_search' or 'group_beam_search' "
                f"(got {strategy!r})")
        if cache_impl == "paged":
            if is_beam:
                raise NotImplementedError(
                    "cache_impl='paged' does not support beam search — "
                    "use the dense cache")
            if attention_mask is not None:
                raise NotImplementedError(
                    "cache_impl='paged' with left-padded prompts "
                    "(attention_mask) — use the dense cache, or the "
                    "serving engine (paddle_tpu.inference.ServingEngine)"
                    " which prefills each prompt at its own length")
            if not hasattr(self, "init_paged_caches"):
                raise NotImplementedError(
                    f"{type(self).__name__} does not implement "
                    "init_paged_caches (paged-KV decode)")
        # -- speculative decoding (rides the paged cache) -------------
        from .speculative import (SpecGenerator, draft_exclusion_reason,
                                  spec_exclusion_reason,
                                  speculative_enabled)
        gamma = int(cfg.num_speculative_tokens
                    if num_speculative_tokens is None
                    else num_speculative_tokens)
        if gamma < 0:
            raise ValueError(
                f"num_speculative_tokens must be >= 0, got {gamma}")
        if draft_model is not None and gamma == 0:
            raise ValueError(
                "draft_model requires num_speculative_tokens > 0")
        if spec_tree is not None:
            spec_tree = tuple(int(p) for p in spec_tree)
            if gamma == 0:
                raise ValueError(
                    "spec_tree requires num_speculative_tokens > 0")
            if len(spec_tree) != gamma:
                raise ValueError(
                    f"spec_tree has {len(spec_tree)} nodes; must equal "
                    f"num_speculative_tokens={gamma}")
        if not speculative_enabled():        # PADDLE_TPU_SPECULATIVE=0
            gamma = 0
            draft_model = None
            spec_tree = None
        if gamma:
            if is_beam:
                raise NotImplementedError(
                    "speculative decoding does not support beam search")
            if attention_mask is not None:
                raise NotImplementedError(
                    "speculative decoding with left-padded prompts "
                    "(attention_mask) — pad to equal length, or use "
                    "the serving engine")
            if _explicit_cache_impl == "dense":
                # same policy as the other inapplicable-option guards:
                # the speculative loop RIDES the paged layout, so an
                # explicit dense-cache request cannot be honored
                raise ValueError(
                    "num_speculative_tokens requires the paged cache; "
                    "it cannot run with an explicit cache_impl='dense'")
            reason = spec_exclusion_reason(self)
            if reason is None and draft_model is not None:
                reason = draft_exclusion_reason(self, draft_model)
            if reason is not None:
                raise NotImplementedError(
                    f"speculative decoding unavailable: {reason}")
            # speculated positions may overhang the final token by
            # up to gamma — they need rope/position-table room too
            self._check_lengths(prompt_len, max_new + gamma)
            ngram_max = int(cfg.spec_ngram_max if spec_ngram_max
                            is None else spec_ngram_max)
            # the speculative loop rides the paged pool, so the env
            # twin / config quantization request applies to it
            kv_dtype = _pcq.resolve_kv_cache_dtype(_kv_req)
            if not hasattr(self, "_generate_jit_cache"):
                self._generate_jit_cache = {}
            jit_key = ("spec", b, prompt_len, max_new, gamma,
                       do_sample, temperature, top_k, top_p, eos, pad,
                       id(draft_model) if draft_model is not None
                       else None, ngram_max,
                       int(getattr(cfg, "kv_block_size", 16)),
                       kv_dtype, spec_tree)
            runner = self._generate_jit_cache.get(jit_key)
            _label = type(self).__name__
            if runner is None:
                _gen_cache_events.labels(model=_label,
                                         event="miss").inc()
                runner = SpecGenerator(
                    self, binder, buffers, b, prompt_len, max_new,
                    gamma, do_sample=do_sample, temperature=temperature,
                    top_k=top_k, top_p=top_p, eos=eos, pad=pad,
                    block_size=int(getattr(cfg, "kv_block_size", 16)),
                    draft_model=draft_model, ngram_max=ngram_max,
                    kv_cache_dtype=kv_dtype, spec_tree=spec_tree)
                self._generate_jit_cache[jit_key] = runner
            else:
                _gen_cache_events.labels(model=_label,
                                         event="hit").inc()
            out, score = runner.run(params, ids, seed)
            return (_wrap_out(jnp.asarray(out)),
                    _wrap_out(jnp.asarray(score)))
        # power-of-two prompt bucketing: left-pad the prompt (masked,
        # per-row rope rebase — the proven padded path) so every prompt
        # length in a bucket reuses ONE compiled decode loop; verify
        # via the generate_jit_cache hit counters
        import os as _os
        if pad_prompt_to_bucket and not is_beam \
                and cache_impl == "dense" \
                and _os.environ.get("PADDLE_TPU_GENERATE_BUCKETS",
                                    "1") != "0" \
                and self._bucket_eligible():
            pb = _prompt_bucket(prompt_len)
            if pb != prompt_len:
                padc = pb - prompt_len
                ids = jnp.concatenate(
                    [jnp.full((b, padc), pad, jnp.int32), ids], axis=1)
                base = mask_np if attention_mask is not None \
                    else np.ones((b, prompt_len), np.int64)
                mask_np = np.concatenate(
                    [np.zeros((b, padc), base.dtype), base], axis=1)
                attention_mask = mask_np
                prompt_len = pb
        if is_beam:
            from .beam import build_beam_run
            groups = num_beam_groups if strategy == "group_beam_search" \
                else 1
            run = build_beam_run(
                self._build_model_step(binder, buffers),
                lambda bb: self.init_caches(bb, prompt_len + max_new),
                b, prompt_len, max_new, num_beams=num_beams,
                num_beam_groups=groups, diversity_rate=diversity_rate,
                length_penalty=length_penalty,
                early_stopping=early_stopping, eos=eos, pad=pad,
                with_scores=True)
            jit_key = (b, prompt_len, max_new, strategy, num_beams,
                       groups, diversity_rate, length_penalty,
                       early_stopping, eos, pad)
        else:
            # sampling knobs ride as a traced [3] operand (DATA, not
            # trace constants), so temperature/top_k/top_p changes
            # reuse ONE compiled decode loop — they are deliberately
            # NOT in the jit_key below (the ISSUE 13 recompile fix;
            # pinned by the generate_jit_cache counter test)
            select = lambda lg, k, samp: _select_token(
                lg, k, do_sample=do_sample, temperature=samp[0],
                top_k=samp[1], top_p=samp[2])
            if cache_impl == "paged":
                run = self._build_run_paged(
                    binder, buffers, b, prompt_len, max_new, select,
                    eos, pad, with_scores=True,
                    block_size=int(getattr(cfg, "kv_block_size", 16)),
                    kv_cache_dtype=kv_dtype)
            else:
                run = self._build_run(binder, buffers, b, prompt_len,
                                      max_new, select, eos, pad,
                                      with_scores=True,
                                      with_mask=attention_mask
                                      is not None)
            jit_key = (b, prompt_len, max_new, do_sample, eos, pad,
                       attention_mask is not None, cache_impl,
                       kv_dtype)

        if not hasattr(self, "_generate_jit_cache"):
            self._generate_jit_cache = {}
        jitted = self._generate_jit_cache.get(jit_key)
        _label = type(self).__name__
        if jitted is None:
            _gen_cache_events.labels(model=_label, event="miss").inc()
            jitted = jax.jit(run)
            self._generate_jit_cache[jit_key] = jitted
        else:
            _gen_cache_events.labels(model=_label, event="hit").inc()
        extra = () if is_beam else (jnp.asarray(
            [temperature, float(top_k), top_p], jnp.float32),)
        if attention_mask is not None:
            mask_arr = as_jax(attention_mask).astype(jnp.int32)
            out, score = jitted(params, ids, mask_arr,
                                jax.random.PRNGKey(seed), *extra)
        else:
            out, score = jitted(params, ids, jax.random.PRNGKey(seed),
                                *extra)
        return (_wrap_out(out.astype(jnp.int64)),
                _wrap_out(score))

    def export_generation(self, path, batch_size, prompt_len,
                          max_new_tokens, generation_config=None):
        """AOT-export the ENTIRE decode loop (prefill + lax.while_loop)
        as a serialized StableHLO module + params — the deployable LLM
        artifact the reference serves via AnalysisPredictor. Load with
        ``paddle_tpu.generation.load_generation(path)``; call with
        (ids [B, L] int32, seed int) -> generated ids."""
        import json
        import os
        cfg = generation_config or GenerationConfig()
        do_sample = self._resolve_strategy(cfg.decode_strategy)
        eos = -1 if cfg.eos_token_id is None else int(cfg.eos_token_id)
        pad = (eos if eos >= 0 else 0) if cfg.pad_token_id is None \
            else int(cfg.pad_token_id)
        b, prompt, max_new = int(batch_size), int(prompt_len), \
            int(max_new_tokens)
        self._check_lengths(prompt, max_new)

        from ..jit import _LayerBinder
        binder = _LayerBinder(self)
        params = binder.param_arrays()
        buffers = binder.buffer_arrays()

        if cfg.decode_strategy in ("beam_search", "group_beam_search"):
            from .beam import build_beam_run
            groups = cfg.num_beam_groups \
                if cfg.decode_strategy == "group_beam_search" else 1
            run = build_beam_run(
                self._build_model_step(binder, buffers),
                lambda bb: self.init_caches(bb, prompt + max_new),
                b, prompt, max_new, num_beams=cfg.num_beams,
                num_beam_groups=groups,
                diversity_rate=cfg.diversity_rate,
                length_penalty=cfg.length_penalty,
                early_stopping=cfg.early_stopping, eos=eos, pad=pad,
                with_scores=False)
        else:
            # the exported artifact BAKES its sampling config (it is a
            # fixed deployable); the traced samp operand is fed a dummy
            # the graph never reads
            select = lambda lg, k, _samp: _select_token(
                lg, k, do_sample=do_sample, temperature=cfg.temperature,
                top_k=cfg.top_k, top_p=cfg.top_p)
            run = self._build_run(binder, buffers, b, prompt, max_new,
                                  select, eos, pad, with_scores=False)

        def run_seeded(params_a, ids_a, seed):
            if cfg.decode_strategy in ("beam_search",
                                       "group_beam_search"):
                return run(params_a, ids_a, jax.random.PRNGKey(seed))
            return run(params_a, ids_a, jax.random.PRNGKey(seed),
                       jnp.zeros((3,), jnp.float32))

        seed_dtype = "int64" if jax.config.jax_enable_x64 else "int32"
        from jax import export as jexport
        exported = jexport.export(jax.jit(run_seeded))(
            [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in params],
            jax.ShapeDtypeStruct((b, prompt), jnp.int32),
            jax.ShapeDtypeStruct((), jnp.dtype(seed_dtype)))
        os.makedirs(os.path.dirname(os.path.abspath(path)) or ".",
                    exist_ok=True)
        with open(path + ".pdmodel", "wb") as f:
            f.write(exported.serialize())
        np.savez(path + ".params.npz",
                 **{f"p{i}": np.asarray(p)
                    for i, p in enumerate(params)})
        with open(path + ".json", "w") as f:
            json.dump({"batch": b, "prompt_len": prompt,
                       "max_new_tokens": max_new,
                       "n_params": len(params),
                       "seed_dtype": seed_dtype}, f)
        return path


class LoadedGeneration:
    """AOT generation artifact: (ids [B, L], seed) -> generated ids."""

    def __init__(self, path):
        import json
        from jax import export as jexport
        with open(path + ".pdmodel", "rb") as f:
            self._exported = jexport.deserialize(f.read())
        data = np.load(path + ".params.npz")
        with open(path + ".json") as f:
            self.meta = json.load(f)
        self._params = [jnp.asarray(data[f"p{i}"])
                        for i in range(self.meta["n_params"])]

    def __call__(self, input_ids, seed=0):
        ids = jnp.asarray(np.asarray(input_ids), jnp.int32)
        # the artifact records its baked seed dtype (the exporting
        # process's x64 mode — may differ from this process's)
        seed_dt = jnp.dtype(self.meta.get("seed_dtype", "int32"))
        out = self._exported.call(self._params, ids,
                                  jnp.asarray(seed, seed_dt))
        return np.asarray(out)


def load_generation(path) -> LoadedGeneration:
    return LoadedGeneration(path)

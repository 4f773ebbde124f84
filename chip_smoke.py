#!/usr/bin/env python3
"""The quickest proof that paddle-tpu still starts on the chip.

    python chip_smoke.py            # from the repo root, through the chip tool

One process, one TPU. It drives the two normal entry points once each at
published widths with random weights from a seed (depth cut to one
16 GB chip, printed):

- **serve** — ``ServingEngine`` over ``Qwen2ForCausalLM`` at the
  Qwen2-7B widths with the default ``ServingConfig`` path (ragged tick,
  chunked prefill, prefix cache, fused decode) answers a mixed-length
  request wave through ``submit()``/``run()``, bf16 KV then int8 KV, and
  every greedy token is checked against the model's plain (cache-free,
  XLA-attention) forward.
- **train** — ``TrainStep`` over a Qwen2 at the Qwen2-1.5B widths with
  AdamW, seq 2048 (flash kernel), fed by a ``DataLoader(num_workers=2)``.
- **kernels** — every Pallas entry point of ``paddle_tpu/ops/pallas``
  compiled BY MOSAIC at one production shape and compared with its XLA
  mirror.
- **four_chip** — with >= 4 devices: the serve phase at ``tp_degree=4``
  and one fleet hybrid (sharding 2 x mp 2) train step.

What each executable holds is read from the COMPILED program
(``monitor.kernel_census``) and checked against ``EXPECT`` below — not
what a gate intended. Every phase runs; if any raised, the run ends
non-zero with no result line. The last stdout line on success is the device
JSON the driver parses; with no TPU the script exits 4 after its first line
and prints no result.

``--phases`` selects phases for a debugging call (the driver runs all).
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time
import traceback

SEED = 0
PHASES = ("kernels", "serve", "train", "four_chip")

# --------------------------------------------------------------------------
# What is expected to run, per entry point, on one v5e chip. "mosaic" names
# the Pallas kernel(s) — by the ``kernel_scope`` name they are invoked under —
# that must appear as tpu_custom_call in the compiled program; "xla" carries
# the reason the entry point is NOT a kernel there.
# A compiled program that disagrees with this table fails the smoke.
# --------------------------------------------------------------------------
_ROW_DMA = (
    "Mosaic (jax 0.9.0 / libtpu 0.0.34) refuses the moe_gmm kernels' "
    "per-row async copies: 'Slice shape along dimension 0 must be aligned "
    "to tiling (8), but is 1' for the HBM gather source and the VMEM "
    "scatter tile alike (first chip run of PR 21). The gates "
    "(moe._use_fused_gmm, lora._use_lora_gmm) route nothing to them on a "
    "TPU backend: MoE takes the sorted megablox path, LoRA the einsum.")

_FLASH = ("flash_attention_fwd", "flash_attention_dq",
          "flash_attention_dkv")

EXPECT = {
    # -- the serving tick (ServingEngine, default config) ------------------
    "serve.ragged_paged_attention": ("mosaic", ("ragged_paged_attention",)),
    "serve.fused_norm_matmul": ("mosaic", ("fused_norm_matmul",)),
    "serve.fused_matmul_residual": ("mosaic", ("fused_matmul_residual",)),
    # (a model with slot state beside the paged KV, head size 64)
    "serve.slot_state.ragged_paged_attention": (
        "mosaic", ("ragged_paged_attention", "short_conv_taps")),
    # (a model whose slot state is a matrix a head, advanced by a scan)
    "serve.scan_state.delta_rule": (
        "mosaic", ("kda_recurrent", "kda_chunk", "short_conv_taps",
                   "ragged_paged_attention")),
    # -- the train step (TrainStep, seq 2048) ------------------------------
    "train.flash_attention": ("mosaic", _FLASH),
    # -- tensor-parallel serving (four-chip phase) -------------------------
    "tp.ragged_paged_attention": ("mosaic", ("ragged_paged_attention",)),
    "tp.fused_decode": (
        "xla", "an opaque pallas_call cannot be partitioned by GSPMD, so "
        "TP engines keep the unfused projections (ROADMAP S4)"),
    # -- every Pallas entry point, standalone (kernel phase) ---------------
    "kernel.flash_attention": ("mosaic", _FLASH),
    "kernel.flashmask_attention": ("mosaic", ("flashmask_attention_fwd",
                                              "flashmask_attention_dq",
                                              "flashmask_attention_dkv")),
    "kernel.paged_decode.bf16": ("mosaic", ("paged_decode_attention",)),
    "kernel.paged_decode.int8": ("mosaic", ("paged_decode_attention",)),
    "kernel.paged_verify.bf16": ("mosaic", ("paged_verify_attention",)),
    "kernel.paged_verify.int8": ("mosaic", ("paged_verify_attention",)),
    "kernel.ragged_paged.bf16": ("mosaic", ("ragged_paged_attention",)),
    "kernel.ragged_paged.int8": ("mosaic", ("ragged_paged_attention",)),
    "kernel.ragged_paged.d64": ("mosaic", ("ragged_paged_attention",)),
    "kernel.ragged_latent.bf16": ("mosaic", ("ragged_latent_attention",)),
    "kernel.kda_recurrent": ("mosaic", ("kda_recurrent",)),
    "kernel.kda_chunk": ("mosaic", ("kda_chunk",)),
    "kernel.short_conv_taps": ("mosaic", ("short_conv_taps",)),
    "kernel.short_conv_taps.3taps": ("mosaic", ("short_conv_taps",)),
    "kernel.norm_matmul.qkv_bias": ("mosaic", ("fused_norm_matmul",)),
    "kernel.norm_matmul.gate_up": ("mosaic", ("fused_norm_matmul",)),
    "kernel.matmul_residual.o_proj": ("mosaic", ("fused_matmul_residual",)),
    "kernel.matmul_residual.swiglu_down": ("mosaic",
                                           ("fused_matmul_residual",)),
    # (jax's own kernels: they surface under megablox's jitted gmm / tgmm)
    "kernel.megablox_gmm": ("mosaic", ("gmm", "tgmm")),
    "kernel.megablox_gmm.share": ("mosaic", ("gmm",)),
    "kernel.gather_gmm": ("xla", _ROW_DMA),
    "kernel.gather_gmm_swiglu": ("xla", _ROW_DMA),
    "kernel.scatter_gmm": ("xla", _ROW_DMA),
    "kernel.lora_gmm": ("xla", _ROW_DMA),
}

# Kernel-vs-mirror tolerance: max |kernel - mirror| over the mirror's max
# magnitude. Kernel and mirror both multiply bf16 (or, for f32 operands,
# XLA's default single-bf16-pass TPU dot) and accumulate f32, but round at
# different points (the paged kernels cast unnormalized probabilities to
# bf16, the mirrors normalized ones; flash backward recomputes p from the
# saved log-sum-exp), so they agree to a few bf16 ulps (2^-8 = 0.4%) of
# the output scale, not bitwise. 5% of scale is ~12 ulps: far above
# rounding, far below what a wrong block, mask or head routing produces
# (errors of the order of the scale itself).
KERNEL_TOL = 5e-2
# ... except where a kernel only MOVES values and sums them in its
# mirror's order (ops/short_conv.py): nothing may differ
KERNEL_EXACT = ("kernel.short_conv_taps", "kernel.short_conv_taps.3taps")

# Greedy-token check of the serve phase: each served token's logit under
# the plain forward must be within this of that position's max logit.
# Random 0.02-std weights give logits of std ~1.2 whose top-2 gap is
# often below bf16 noise (~0.03), so exact argmax agreement is not
# expected; a token from a wrong cache read sits several std below the
# max. int8 KV adds ~1% quantization noise to K/V, hence the wider bound.
LOGIT_TOL = {"bf16": 0.25, "int8": 0.5}

# -- sizes: each phase is a plain function of one of these -----------------
SERVE_FULL = dict(
    # Qwen2Config's defaults ARE the Qwen2-7B widths (models/qwen2.py):
    # hidden 3584, 28 query / 4 KV heads x 128, FFN 18944, vocab 151936,
    # QKV bias. Depth cut from 28 to 10: 6.8 GB of bf16 weights (2.18 GB
    # embedding + lm_head, 0.47 GB per layer) beside the KV pools — but
    # what bounds the depth is the BUILD: layers create their parameters
    # in f32 and ``model.to`` casts them, so the whole model stands in
    # f32 first (13.7 GB of the chip's 15.75 at 10 layers; 16 layers
    # ran out of HBM there, PR 21).
    widths=dict(), layers=10,
    engine=dict(),                      # the default ServingConfig
    int8_engine=dict(block_size=32),    # int8 sublane tile (ServingConfig doc)
    n_requests=16, n_int8_requests=8, max_new=(8, 24))
# one small engine of the family whose conv layers keep slot state
# (models/lfm2_moe.py): 4 heads x 64 over 2 kv heads, 8 experts top-2,
# conv / attention / conv / conv / conv — the same on the chip and in the
# CPU rehearsal
SLOT_STATE = dict(
    widths=dict(vocab=512, hidden=256, heads=4, kv_heads=2, dense_ffn=512,
                moe_ffn=128, experts=8, topk=2),
    engine=dict(num_slots=4, max_model_len=128, prefill_chunk=16),
    n_requests=8, max_new=(3, 6))
# one small engine of the family whose kda layers keep a matrix state a
# head (models/solar_open2.py) at the kernels' head size: gqa, kda, kda,
# kda; 2 kda heads x 128, attention 4 heads x 64 over 2 kv heads, 8
# experts top-2 all held and a shared one; chunks of 80 rows (a whole
# sub-chunk of the chunkwise form and a part of one); a pool large enough
# that an eighth of its bytes holds a few 0.4 MB snapshots of the state
SCAN_STATE = dict(
    widths=dict(vocab=512, hidden=256, layers=4, heads=4, kv_heads=2,
                head_dim=64, kda_heads=2, kda_head_dim=128, moe_ffn=128,
                experts=8, topk=2),
    engine=dict(num_slots=4, max_model_len=256, prefill_chunk=80,
                num_blocks=4096),
    n_requests=8, max_new=(3, 6))
SERVE_TINY = dict(
    widths=dict(vocab_size=512, hidden_size=256, intermediate_size=512,
                num_attention_heads=2, num_key_value_heads=1,
                max_position_embeddings=256),
    layers=2,
    engine=dict(num_slots=4, max_model_len=128, prefill_chunk=16),
    int8_engine=dict(num_slots=4, max_model_len=128, prefill_chunk=16,
                     block_size=32),
    n_requests=8, n_int8_requests=8, max_new=(3, 6))

TRAIN_FULL = dict(
    # Qwen2-1.5B: hidden 1536, 12 query / 2 KV heads x 128, FFN 8960,
    # vocab 151936, tied embeddings. Depth cut from 28 to 4: 420 M
    # parameters x 16 B (bf16 weight + grad, f32 master + 2 AdamW
    # moments) = 6.7 GB beside activations and a [2, 2048, 151936] logits.
    widths=dict(vocab_size=151936, hidden_size=1536,
                intermediate_size=8960, num_attention_heads=12,
                num_key_value_heads=2, tie_word_embeddings=True,
                max_position_embeddings=2048),
    layers=4, seq=2048, batch=2, steps=5, lr=1e-3)
TRAIN_TINY = dict(
    widths=dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                num_attention_heads=2, num_key_value_heads=1,
                tie_word_embeddings=True, max_position_embeddings=64),
    layers=2, seq=32, batch=2, steps=5, lr=1e-2)

FOUR_CHIP_FULL = dict(
    # (the model is built on one device before the engine shards it, so
    # TP does not buy depth here; 8 layers keep the 4x-charged call short)
    serve=dict(SERVE_FULL, layers=8, engine=dict(tp_degree=4),
               n_requests=8, n_int8_requests=0),
    train=dict(TRAIN_FULL, layers=2, seq=512, batch=4, steps=1),
    hybrid=dict(sharding_degree=2, mp_degree=2))
FOUR_CHIP_TINY = dict(
    serve=dict(SERVE_TINY, engine=dict(SERVE_TINY["engine"], tp_degree=4),
               widths=dict(SERVE_TINY["widths"], hidden_size=512,
                           num_attention_heads=4, num_key_value_heads=4),
               n_int8_requests=0),
    train=dict(TRAIN_TINY, steps=1, batch=4,
               widths=dict(TRAIN_TINY["widths"], num_attention_heads=4,
                           num_key_value_heads=2)),
    hybrid=dict(sharding_degree=2, mp_degree=2))


def say(phase, **fields):
    """One JSON line per observation (stdout, flushed: the chip tool
    shows only the tail, and a killed run should still leave its trail)."""
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


class CompileClock:
    """Seconds XLA spent compiling (cache retrieval included), from
    JAX's own compile events — what shrinks when the persistent cache is
    warm. One listener for the whole run; phases read deltas."""

    def __init__(self):
        import jax.monitoring
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return (self.seconds, self.compiles, self.cache_hits)

    def since(self, snap):
        return {"compile_s": round(self.seconds - snap[0], 2),
                "compiles": self.compiles - snap[1],
                "cache_hits": self.cache_hits - snap[2]}


def check_expected(name, mosaic_kernels, on_chip):
    """Hold one compiled program's Mosaic census against ``EXPECT``.
    Off the chip (CPU rehearsal: kernels interpreted or XLA) there is no
    Mosaic program to read, so only the table lookup is exercised."""
    outcome, detail = EXPECT[name]
    if not on_chip:
        return {"entry": name, "expected": outcome, "checked": False}
    if outcome == "mosaic":
        missing = [k for k in detail if not mosaic_kernels.get(k)]
        if missing:
            raise AssertionError(
                f"{name}: expected Mosaic kernel(s) {missing} in the "
                f"compiled program, found {mosaic_kernels}")
    else:
        if mosaic_kernels:
            raise AssertionError(
                f"{name}: expected XLA ({detail}), but the compiled "
                f"program holds Mosaic kernels {mosaic_kernels}")
    return {"entry": name, "expected": outcome, "checked": True}


def rel_err(got, ref):
    """max |got - ref| over max(|ref|, 1e-6), f32, across a pytree."""
    import jax
    import numpy as np
    worst = 0.0
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        g = np.asarray(g, np.float32)
        r = np.asarray(r, np.float32)
        if g.shape != r.shape:
            raise AssertionError(f"shape {g.shape} != mirror {r.shape}")
        if not np.all(np.isfinite(g)):
            raise AssertionError("non-finite kernel output")
        worst = max(worst, float(np.max(np.abs(g - r)))
                    / max(float(np.max(np.abs(r))), 1e-6))
    return worst


# ==========================================================================
# kernel phase
# ==========================================================================

def _paged_inputs(rng, dims, quant, q_shape):
    """Random pools (bf16, or int8 + scales via the writers' own
    quantizer), per-slot block tables and ragged lengths, and a query."""
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import paged_cache as pc
    s, hkv, d, bs, mb = (dims[k] for k in ("S", "Hkv", "D", "BS", "MB"))
    nb = 1 + s * mb

    def pool():
        x = jnp.asarray(rng.standard_normal((nb, bs, hkv, d)),
                        jnp.bfloat16)
        if not quant:
            return x
        return pc.QuantKV(*pc.kv_quantize(x))

    tables = jnp.asarray(1 + np.arange(s * mb, dtype=np.int32)
                         .reshape(s, mb))
    reach = mb * bs
    lens = jnp.asarray([max(1, (reach * (i + 1)) // (s + 1) - 3)
                        for i in range(s)], jnp.int32)
    q = jnp.asarray(rng.standard_normal(q_shape), jnp.bfloat16)
    return q, pool(), pool(), tables, lens


def kernel_cases(full, interpret=None):
    """``[(EXPECT key, build)]``; ``build(rng) -> (kernel_fn, mirror_fn,
    args)``. ``full`` picks production shapes (the smoke, and the CPU-side
    TPU cross-lowering test) or tiny ones (CPU rehearsal under the Pallas
    interpreter). ``interpret``: None lets each kernel decide from the
    backend; False forces a compiled kernel (cross-lowering)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.distributed import moe
    from paddle_tpu.ops import lora, paged_cache as pc
    from paddle_tpu.ops.pallas import (decode_fused as df,
                                       flash_attention as fa,
                                       flashmask_kernel as fmk, moe_gmm,
                                       paged_attention as pa)
    from paddle_tpu.ops.pallas.flash_attention_kernel import (
        pallas_flash_attention)
    bf16 = jnp.bfloat16
    cases = []

    # -- flash / flashmask, forward + backward (the train-step shapes) -----
    fl = dict(B=2, L=2048, H=12, Hkv=2, D=128) if full \
        else dict(B=1, L=256, H=2, Hkv=1, D=128)

    def flash_args(rng):
        shp = lambda h: (fl["B"], fl["L"], h, fl["D"])
        q = jnp.asarray(rng.standard_normal(shp(fl["H"])), bf16)
        k = jnp.asarray(rng.standard_normal(shp(fl["Hkv"])), bf16)
        v = jnp.asarray(rng.standard_normal(shp(fl["Hkv"])), bf16)
        ct = jnp.asarray(rng.standard_normal(shp(fl["H"])), jnp.float32)
        return q, k, v, ct

    def fwd_bwd(attn):
        def f(q, k, v, ct, *rest):
            def loss(q, k, v):
                return jnp.sum(attn(q, k, v, *rest).astype(jnp.float32)
                               * ct)
            out = attn(q, k, v, *rest)
            return (out,) + jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return f

    scale = 1.0 / math.sqrt(fl["D"])
    cases.append(("kernel.flash_attention", lambda rng: (
        fwd_bwd(lambda q, k, v: pallas_flash_attention(q, k, v,
                                                       causal=True)),
        fwd_bwd(lambda q, k, v: fa._xla_attention(q, k, v, None, True,
                                                  scale)),
        flash_args(rng))))

    def flashmask_args(rng):
        # document-causal mask, one bound per key column: rows at or
        # past the end of the column's document are masked
        cuts = np.arange(1, fl["L"] // 64)
        ends = np.sort(rng.choice(cuts, min(6, len(cuts)),
                                  replace=False)) * 64
        ends = np.concatenate([ends, [fl["L"]]])
        doc_end = ends[np.searchsorted(ends, np.arange(fl["L"]),
                                       side="right")]
        idx = np.broadcast_to(doc_end[None, None, :, None],
                              (fl["B"], fl["Hkv"], fl["L"], 1))
        return flash_args(rng) + (jnp.asarray(idx, jnp.int32),)

    cases.append(("kernel.flashmask_attention", lambda rng: (
        fwd_bwd(lambda q, k, v, idx: fmk.pallas_flashmask_attention(
            q, k, v, idx, causal=True)),
        fwd_bwd(lambda q, k, v, idx: fa._xla_attention(
            q, k, v, fa.flashmask_dense_bias(idx, fl["L"], True, q.dtype),
            False, scale)),
        flashmask_args(rng))))

    # -- paged attention: decode / verify / ragged x bf16 / int8 pools -----
    for tag, quant in (("bf16", False), ("int8", True), ("d64", False)):
        # the default engine's shapes (8 slots, 1024-token reach, block 16;
        # int8 pools at their sublane tile, block 32)
        pd = dict(S=8, H=28, Hkv=4, D=128, BS=32 if quant else 16) if full \
            else dict(S=4, H=4, Hkv=2, D=128, BS=32 if quant else 16)
        w_narrow, w_max = (4, 128) if full else (2, 8)
        if tag == "d64":
            # head size 64, two kv heads to a lane tile: the wide cell's
            # 128 slots, 32 query / 8 kv heads, one 256-row chunk
            pd = dict(S=128, H=32, Hkv=8, D=64, BS=16) if full \
                else dict(S=4, H=8, Hkv=4, D=64, BS=16)
            w_max = 256 if full else 8
        pd["MB"] = (1024 if full else 64) // pd["BS"]
        rows = pd["S"] * w_narrow + w_max

        def decode_build(rng, pd=pd, quant=quant):
            args = _paged_inputs(rng, pd, quant,
                                 (pd["S"], pd["H"], pd["D"]))
            return (lambda *a: pa.pallas_paged_attention(
                *a, interpret=interpret), pa._xla_paged_attention, args)

        def verify_build(rng, pd=pd, quant=quant, t=w_narrow):
            args = _paged_inputs(rng, pd, quant,
                                 (pd["S"], t, pd["H"], pd["D"]))
            return (lambda *a: pa.pallas_paged_verify_attention(
                *a, interpret=interpret), pa._xla_paged_verify, args)

        def ragged_build(rng, pd=pd, quant=quant, wn=w_narrow, w=w_max,
                         rows=rows):
            q, kp, vp, tables, lens = _paged_inputs(
                rng, pd, quant, (rows, pd["H"], pd["D"]))
            flat = pd["D"] % 128 != 0
            if flat:
                # head size 64: the pool is built flat (a position's kv
                # heads side by side in one row), the kernel reads two
                # heads a tile, the mirror plain heads again
                kp, vp = (p.reshape(*p.shape[:2], -1) for p in (kp, vp))
            # three ticks through the one kernel: a mixed one (one wide
            # prefill chunk, verify windows and decode rows, one idle
            # slot), the commonest one, decode only (a row a slot), and
            # tree verify windows (every other slot flagged, node k + 1
            # the child of node k // 2) beside a linear chunk
            mixed = np.asarray([1, wn] * (pd["S"] // 2), np.int64)
            mixed[0], mixed[-1] = w - 3, 0
            windows = np.full(pd["S"], wn, np.int64)
            windows[-1] = w // 2
            flags = jnp.asarray([1, 0] * (pd["S"] // 2), jnp.int32)
            tree = dict(tree_anc=tuple(k // 2 for k in range(wn - 1)),
                        tree_slots=flags)
            base = np.minimum(np.asarray(lens), pd["MB"] * pd["BS"] - w)
            ctx = jnp.asarray(base + 1, jnp.int32)
            ticks = []
            for q_lens, kw in ((mixed, {}), (np.ones(pd["S"], np.int64), {}),
                               (windows, tree)):
                row_slot, _pos, row_starts, _last = pc.ragged_row_meta(
                    q_lens, base, rows, pd["MB"] * pd["BS"])
                live = np.zeros(rows, bool)
                for s0, n in zip(row_starts, q_lens):
                    live[s0:s0 + n] = True
                ticks.append(tuple(
                    jnp.asarray(x, jnp.int32)
                    for x in (q_lens, row_starts, row_slot))
                    + (jnp.asarray(live)[:, None, None], kw))

            def kern(q, kp, vp, tables, ctx):
                return tuple(jnp.where(live, pa.pallas_ragged_paged_attention(
                    q, kp, vp, tables, ctx, ql, rs, w_max=w,
                    interpret=interpret, **kw), 0)  # pad rows are garbage
                    for ql, rs, _sl, live, kw in ticks)

            def mirror(q, kp, vp, tables, ctx):
                if flat:
                    kp, vp = (pa._unflat(p, pd["D"]) for p in (kp, vp))
                return tuple(jnp.where(live, pa._xla_ragged_paged(
                    q, kp, vp, tables, ctx, ql, rs, sl, wn, w, **kw), 0)
                    for ql, rs, sl, live, kw in ticks)
            return kern, mirror, (q, kp, vp, tables, ctx)

        if tag == "d64":    # the ragged kernel alone takes this head size
            cases.append(("kernel.ragged_paged.d64", ragged_build))
            continue
        cases += [(f"kernel.paged_decode.{tag}", decode_build),
                  (f"kernel.paged_verify.{tag}", verify_build),
                  (f"kernel.ragged_paged.{tag}", ragged_build)]

    # -- latent (MLA) attention over a one-array pool: 64 heads on one
    # latent of key width 576 (640 lanes), value = its first 512 --------
    ld = dict(S=32, H=64, W=576, V=512, BS=16, MB=512, w=512) if full \
        else dict(S=4, H=8, W=576, V=512, BS=16, MB=8, w=24)

    def latent_build(rng, ld=ld):
        s_, mb, bs, w = ld["S"], ld["MB"], ld["BS"], ld["w"]
        rows = s_ + w
        nb = 1 + s_ * mb
        (pool,) = pc.init_latent_pool(nb, bs, ld["W"], bf16)
        lanes = pool.shape[2]
        pool = jnp.asarray(
            rng.standard_normal((nb, bs, lanes)) * 0.5,
            bf16).at[..., ld["W"]:].set(0)
        tables = jnp.asarray(
            rng.permutation(np.arange(1, nb)).reshape(s_, mb), jnp.int32)
        q = jnp.asarray(rng.standard_normal((rows, ld["H"], lanes)),
                        bf16).at[..., ld["W"]:].set(0)
        lens = rng.integers(1, mb * bs - w, s_)
        # one-token slots (the kernel's narrow rung) whose context ends
        # a position short of a kv tile, on its edge, a position past
        # it and inside a block of the third, where the table holds them
        span = pa.LATENT_TILE[1]
        pins = [n for n in (span - 2, span - 1, span, 3 * span - 77)
                if n < mb * bs - w]
        lens[1:1 + len(pins)] = pins
        ctx = jnp.asarray(lens + 1, jnp.int32)
        # a mixed tick (one chunk, decode rows, one idle slot) and the
        # decode-only tick
        mixed = np.ones(s_, np.int64)
        mixed[0], mixed[-1] = w - 3, 0
        ticks = []
        for q_lens in (mixed, np.ones(s_, np.int64)):
            row_slot, _pos, row_starts, _last = pc.ragged_row_meta(
                q_lens, lens, rows, mb * bs)
            live = np.zeros(rows, bool)
            for s0, n in zip(row_starts, q_lens):
                live[s0:s0 + n] = True
            ticks.append(tuple(jnp.asarray(x, jnp.int32)
                               for x in (q_lens, row_starts, row_slot))
                         + (jnp.asarray(live)[:, None, None],))

        def kern(q, pool, tables, ctx):
            return tuple(jnp.where(
                live, pa.pallas_ragged_latent_attention(
                    q, pool, tables, ctx, ql, rs, w, ld["V"], 0.1,
                    interpret=interpret), 0)
                for ql, rs, _sl, live in ticks)

        def mirror(q, pool, tables, ctx):
            verify = lambda q4, t, l, _n: pa._xla_latent_verify(  # noqa
                q4, pool, t, l, ld["V"], 0.1)
            return tuple(jnp.where(live, pa._xla_ragged_lanes(
                q, verify, tables, ctx, ql, rs, sl, 1, w), 0)
                for ql, rs, sl, live in ticks)
        return kern, mirror, (q, pool, tables, ctx)

    cases.append(("kernel.ragged_latent.bf16", latent_build))

    # -- fused decode projections at the Qwen2-7B widths --------------------
    # rows = the default engine's packed width: 8 slots + one 128-row chunk
    r, d, ffn, kvw = (136, 3584, 18944, 512) if full else (8, 128, 256, 128)

    def mat(rng, *shape, std=1.0):
        return jnp.asarray(rng.standard_normal(shape) * std, bf16)

    def norm_mm_build(widths, biased):
        def build(rng):
            x, g = mat(rng, r, d), mat(rng, d, std=0.1) + 1
            ws = [mat(rng, d, n, std=0.02) for n in widths]
            bs = [mat(rng, n, std=0.1) if biased else None for n in widths]
            return (lambda x, g, ws, bs: df.pallas_norm_matmul(
                        x, g, None, ws, bs, eps=1e-6, kind="rms",
                        interpret=interpret),
                    lambda x, g, ws, bs: df._xla_norm_matmul(
                        x, g, None, ws, bs, eps=1e-6, kind="rms"),
                    (x, g, ws, bs))
        return build

    def mm_res_build(kdim, act):
        def build(rng):
            xs = [mat(rng, r, kdim) for _ in range(2 if act else 1)]
            w, res = mat(rng, kdim, d, std=0.02), mat(rng, r, d)
            return (lambda xs, w, res: df.pallas_matmul_residual(
                        xs, w, None, res, act=act, interpret=interpret),
                    lambda xs, w, res: df._xla_matmul_residual(
                        xs, w, None, res, act=act),
                    (xs, w, res))
        return build

    cases += [
        ("kernel.norm_matmul.qkv_bias", norm_mm_build((d, kvw, kvw), True)),
        ("kernel.norm_matmul.gate_up", norm_mm_build((ffn, ffn), False)),
        ("kernel.matmul_residual.o_proj", mm_res_build(d, None)),
        ("kernel.matmul_residual.swiglu_down", mm_res_build(ffn, "swiglu")),
    ]

    # -- MoE grouped matmuls (Qwen2-MoE expert widths: 2048 -> 1408) --------
    tok, topk, e, dm, f = (2048, 4, 16, 2048, 1408) if full \
        else (64, 2, 4, 128, 128)
    m = tok * topk
    gmm_interp = interpret if interpret is not None \
        else jax.default_backend() == "cpu"

    def routing(rng):
        expert = rng.integers(0, e, m)
        order = np.argsort(expert, kind="stable")   # sorted row -> pair
        gs = np.bincount(expert, minlength=e)
        return (jnp.asarray(order, jnp.int32),
                jnp.asarray(order // topk, jnp.int32),
                jnp.asarray(gs, jnp.int32))

    def gather_build(rng):
        _order, src, gs = routing(rng)
        x, w = mat(rng, tok, dm), mat(rng, e, dm, f, std=0.02)
        return (lambda x, src, w, gs: moe_gmm.gather_gmm(
                    x, src, w, gs, interpret=gmm_interp),
                lambda x, src, w, gs: jax.lax.ragged_dot(x[src], w, gs),
                (x, src, w, gs))

    def gather_swiglu_build(rng):
        _order, src, gs = routing(rng)
        x, w = mat(rng, tok, dm), mat(rng, e, dm, 2 * f, std=0.02)

        def mirror(x, src, w, gs):
            g, u = jnp.split(jax.lax.ragged_dot(
                x[src], w, gs, preferred_element_type=jnp.float32), 2, -1)
            return (jax.nn.silu(g) * u).astype(x.dtype)
        return (lambda x, src, w, gs: moe_gmm.gather_gmm_swiglu(
                    x, src, w, gs, interpret=gmm_interp),
                mirror, (x, src, w, gs))

    def scatter_build(rng):
        order, _src, gs = routing(rng)
        h, w = mat(rng, m, f), mat(rng, e, f, dm, std=0.02)
        return (lambda h, w, gs, order: moe_gmm.scatter_gmm(
                    h, w, gs, order, interpret=gmm_interp),
                lambda h, w, gs, order: jnp.zeros((m, dm), h.dtype)
                .at[order].set(jax.lax.ragged_dot(h, w, gs)),
                (h, w, gs, order))

    cases += [("kernel.gather_gmm", gather_build),
              ("kernel.gather_gmm_swiglu", gather_swiglu_build),
              ("kernel.scatter_gmm", scatter_build)]

    def megablox_build(rng):
        _order, _src, gs = routing(rng)
        lhs, rhs = mat(rng, m, dm), mat(rng, e, dm, f, std=0.02)
        ct = jnp.asarray(rng.standard_normal((m, f)), jnp.float32)

        def with_grads(mm):
            def fn(lhs, rhs, gs):
                loss = lambda a, b: jnp.sum(
                    mm(a, b, gs).astype(jnp.float32) * ct)
                return (mm(lhs, rhs, gs),) + jax.grad(
                    loss, argnums=(0, 1))(lhs, rhs)
            return fn
        return (with_grads(lambda a, b, gs: moe._gmm32(
                    a, b, gs, moe._GMM_TILING)),
                with_grads(jax.lax.ragged_dot), (lhs, rhs, gs))

    # megablox has no interpreter switch on this path: chip (or
    # cross-lowering) only
    def share_build(rng):
        """One chip's expert-parallel share: 16 groups of a few rows
        each at the head of a 4,352-pair buffer, the tail in no group,
        under the share's own tiling."""
        sm, sk, sn, se = (4352, 7168, 4096, 16) if full \
            else (256, 256, 256, 4)
        gs = jnp.asarray(rng.integers(0, 2 * sm // (16 * se), se),
                         jnp.int32)
        lhs, rhs = mat(rng, sm, sk), mat(rng, se, sk, sn, std=0.02)
        computed = (jnp.arange(sm) < jnp.sum(gs))[:, None]
        tiling = (moe._SHARE_TM, 1024, 1024)
        return (lambda a, b, g: jnp.where(
                    computed, moe._gmm32(a, b, g, tiling), 0),
                lambda a, b, g: jnp.where(
                    computed, jax.lax.ragged_dot(a, b, g), 0),
                (lhs, rhs, gs))

    if interpret is False or jax.default_backend() != "cpu":
        cases += [("kernel.megablox_gmm", megablox_build),
                  ("kernel.megablox_gmm.share", share_build)]

    # -- the delta rule over slot state: one-row seats and a chunk -----------
    # (the wide cell's tick: 96 seats of 64 heads x 128 x 128 float32,
    # one of them carrying a 256-row chunk, the others a row each)
    kd = dict(S=96, H=64, W=256) if full else dict(S=4, H=2, W=80)

    def tick_meta(seats, wide):
        """``q_lens, row_starts, row_slot, row_pos`` of a tick of
        ``seats + wide`` packed rows: seat 1 carries the chunk (from a
        held state), seat 2 is idle, seat 3 starts a request, the
        others decode."""
        q_lens = np.ones(seats, np.int64)
        q_lens[1], q_lens[2] = wide - 3, 0
        base = np.full(seats, 70, np.int64)
        base[3] = 0
        sl, pos, rs, _ = pc.ragged_row_meta(q_lens, base, seats + wide,
                                            10 ** 6)
        return [jnp.asarray(x, jnp.int32) for x in (q_lens, rs, sl, pos)]

    def kda_args(rng):
        s_, h_, w_, d_ = kd["S"], kd["H"], kd["W"], 128
        rows = s_ + w_
        unit = lambda x: x / np.sqrt((x * x).sum(-1, keepdims=True))
        q = unit(rng.standard_normal((rows, h_, d_))) * d_ ** -0.5
        k = unit(rng.standard_normal((rows, h_, d_)))
        v = rng.standard_normal((rows, h_, d_))
        g = -rng.uniform(1, 16, (1, h_, 1)) \
            * 10 ** rng.uniform(-3, -1, (rows, h_, d_))
        beta = rng.uniform(0, 2, (rows, h_))
        state = jnp.asarray(rng.standard_normal((s_ + 1, h_, d_, d_)),
                            jnp.float32).at[s_].set(0)
        ops = [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]
        return (*ops, state, *tick_meta(s_, w_),
                jnp.arange(1, dtype=jnp.int32),
                jnp.arange(w_, dtype=jnp.int32))

    def kda_case(kernel, mirror):
        def run(fn):
            return lambda q, k, v, g, beta, state, *meta: fn(
                q, k, v, g, beta, state, meta)
        return lambda rng: (
            run(lambda *a: kernel(*a, interpret=interpret)), run(mirror),
            kda_args(rng))

    from paddle_tpu.ops.pallas import delta_rule as dr
    cases += [
        ("kernel.kda_recurrent", kda_case(dr.pallas_kda_recurrent,
                                          dr._xla_recurrent)),
        ("kernel.kda_chunk", kda_case(dr.pallas_kda_chunk, dr._xla_chunk))]

    # -- the short convolution's tap reader over the same kind of tick -------
    # (the reasoning cell's: 97 seats x 3 stored taps x 24,576 channels,
    # 352 rows; the wide cell's: 129 x 2 x 2,048, 384 rows)
    def taps_case(seats, wide, channels, taps):
        def build(rng):
            from paddle_tpu.ops import short_conv as sc
            g = mat(rng, seats + wide, channels)
            state = mat(rng, seats + 1, taps - 1, channels).at[seats].set(0)
            return (lambda g_, s_, w_, *m: sc.pallas_ragged_taps(
                        g_, s_, w_, m, interpret=interpret),
                    lambda g_, s_, w_, *m: sc._xla_ragged_taps(g_, s_, w_, m),
                    (g, state, mat(rng, channels, taps),
                     *tick_meta(seats, wide)))
        return build

    cases += [
        ("kernel.short_conv_taps",
         taps_case(*((96, 256, 24576, 4) if full else (4, 12, 256, 4)))),
        ("kernel.short_conv_taps.3taps",
         taps_case(*((128, 256, 2048, 3) if full else (5, 15, 128, 3))))]

    # -- the LoRA grouped-matmul route (rank on 128 lanes) -------------------
    n_ad, rank = (9, 128) if full else (3, 128)

    def lora_build(rng):
        rows = jnp.asarray(rng.standard_normal((r, d)), jnp.float32)
        ad = jnp.asarray(rng.integers(0, n_ad, r), jnp.int32)
        a = jnp.asarray(rng.standard_normal((n_ad, d, rank)) * 0.02,
                        jnp.float32)
        b = jnp.asarray(rng.standard_normal((n_ad, rank, d)) * 0.02,
                        jnp.float32)
        mode = "interpret" if gmm_interp else "tpu"
        return (lambda *a_: lora._ragged_delta(*a_, mode),
                lambda *a_: lora._ragged_delta(*a_, False),
                (rows, ad, a, b))

    cases.append(("kernel.lora_gmm", lora_build))
    return cases


def _record_failure(name, exc):
    """Full traceback of a failed case, where the chip tool brings files
    back from (a Mosaic error outgrows the tail of the output)."""
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "chip_smoke_failures.log"),
              "a") as f:
        f.write(f"==== {name}\n")
        f.write("".join(traceback.format_exception(exc)))
    say("kernels", entry=name, error=repr(exc)[:600])


def kernel_phase(full, clock, on_chip):
    """Compile every Pallas entry point (by Mosaic on the chip) at one
    shape, read the kernel names out of the compiled program, run it and
    its XLA mirror on the same inputs, and compare. Entries ``EXPECT``
    lists as ``xla`` are not compiled on the chip — their gates must
    route nothing to them there. Every case runs even after one fails,
    so one call names every broken kernel; any failure fails the
    phase."""
    import jax
    import numpy as np
    from paddle_tpu import monitor
    from paddle_tpu.distributed import moe
    from paddle_tpu.ops import lora
    failed = []
    for name, build in kernel_cases(full):
        try:
            if on_chip and EXPECT[name][0] == "xla":
                say("kernels", entry=name, expected="xla",
                    reason=EXPECT[name][1])
                continue
            kern, mirror, args = build(np.random.default_rng(SEED))
            snap = clock.snapshot()
            compiled = jax.jit(kern).lower(*args).compile()
            census = monitor.kernel_census(compiled=compiled)
            got = jax.block_until_ready(compiled(*args))
            t0 = time.monotonic()
            jax.block_until_ready(compiled(*args))
            run_ms = 1e3 * (time.monotonic() - t0)
            ref = jax.block_until_ready(jax.jit(mirror)(*args))
            err = rel_err(got, ref)
            tol = 0.0 if name in KERNEL_EXACT else KERNEL_TOL
            # run_ms: one warm call, host clock — an observation of
            # scale, not a benchmark
            say("kernels", entry=name, err=round(err, 5), tol=tol,
                run_ms=round(run_ms, 2),
                mosaic_kernels=census["hlo_mosaic_kernels"],
                **clock.since(snap))
            if not err <= tol:
                raise AssertionError(
                    f"{name}: kernel differs from its XLA mirror by "
                    f"{err:.4g} of the mirror's scale (tolerance {tol})")
            if EXPECT[name][0] == "mosaic":
                check_expected(name, census["hlo_mosaic_kernels"], on_chip)
        except Exception as exc:    # keep going: name EVERY broken kernel
            _record_failure(name, exc)
            failed.append(name)
    if on_chip:
        # the gates behind the "xla" entries, at the shapes just skipped
        routed = {"moe._use_fused_gmm": moe._use_fused_gmm(8192, 2048, 1408),
                  "lora._use_lora_gmm": lora._use_lora_gmm(136, 3584, 128,
                                                           3584)}
        say("kernels", gates=routed)
        if any(routed.values()):
            failed.append(f"gates route to uncompilable kernels: {routed}")
    if failed:
        raise AssertionError(f"kernel phase failed for: {failed}")


# ==========================================================================
# serve phase
# ==========================================================================

def _build_qwen2(widths, layers):
    import paddle_tpu as paddle
    from paddle_tpu.models.qwen2 import Qwen2Config, Qwen2ForCausalLM
    cfg = Qwen2Config(num_hidden_layers=layers, dtype="bfloat16",
                      **widths)
    paddle.seed(SEED)
    model = Qwen2ForCausalLM(cfg)
    model.to(dtype="bfloat16")
    n_params = sum(int(math.prod(p.shape)) for p in model.parameters())
    return model, cfg, n_params


def _request_mix(rng, n, vocab, chunk, block, max_new):
    """``n >= 8`` seeded prompts of mixed length. The first eight are the
    structured ones: [0..3] share a three-block prefix (the prefix
    cache), [4] and [7] are identical (a full-prompt hit), [5] spans more
    than two prefill chunks, [6] just over one; the rest are random."""
    import numpy as np
    tok = lambda k: rng.integers(1, vocab, k, dtype=np.int64)
    prefix, twin = tok(3 * block), tok(2 * block + 1)
    lens = rng.integers(max(2, chunk // 16), chunk - 1, n)
    prompts = []
    for i in range(n):
        if i < 4:
            p = np.concatenate([prefix, tok(int(lens[i]) // 2 + 1)])
        elif i in (4, 7):
            p = twin.copy()
        elif i == 5:
            p = tok(2 * chunk + chunk // 3)
        elif i == 6:
            p = tok(chunk + chunk // 10 + 1)
        else:
            p = tok(int(lens[i]))
        prompts.append((p, int(rng.integers(max_new[0], max_new[1] + 1))))
    return prompts


def _serve_wave(eng, prompts, vocab):
    import numpy as np
    rids = [eng.submit(p.copy(), max_new_tokens=n) for p, n in prompts]
    done = eng.run()
    outs = []
    for rid, (p, n) in zip(rids, prompts):
        if rid not in done:
            raise AssertionError(f"request {rid} did not finish")
        toks = np.asarray(done[rid])
        if len(toks) != n:
            raise AssertionError(
                f"request {rid}: {len(toks)} tokens, asked for {n}")
        if toks.min() < 0 or toks.max() >= vocab:
            raise AssertionError(f"request {rid}: token out of range")
        outs.append(toks)
    return outs


_FUSED_KERNELS = ("fused_norm_matmul", "fused_matmul_residual")


def _serve_engine(model, vocab, engine_cfg, prompts, expect, clock,
                  on_chip, tag, observe=None):
    """One engine: warm-up waves (compile), then the request wave with
    zero new executables; ``observe`` runs while the engine is still
    live. Returns (prompt, tokens) of the two shortest requests for the
    reference check."""
    from paddle_tpu.inference import ServingConfig, ServingEngine
    t0 = time.monotonic()
    snap = clock.snapshot()
    eng = ServingEngine(model, ServingConfig(**engine_cfg))
    if eng.stats()["state_bytes"]:
        # slot state: the snapshot pair a prefix hit needs is built off
        # the hot path, as a deployment's scale-up warm does
        eng.warm_migration()
    # warm-up covers every path the wave takes: a multi-chunk prefill
    # beside a decoding slot, a prefix hit, a repeated prompt
    _serve_wave(eng, [prompts[5], prompts[0]], vocab)
    _serve_wave(eng, [prompts[1], prompts[4], prompts[7]], vocab)
    warm_s = time.monotonic() - t0
    compiled_after_warmup = eng.stats()["executables_compiled"]
    comp = clock.since(snap)

    t1 = time.monotonic()
    outs = _serve_wave(eng, prompts, vocab)
    wave_s = time.monotonic() - t1
    st = eng.stats()
    mosaic = eng.kernel_census()["decode"]["hlo_mosaic_kernels"]
    if observe is not None:
        observe()
    eng.shutdown()
    if st["executables_compiled"] != compiled_after_warmup:
        raise AssertionError(
            f"{tag}: executables_compiled went "
            f"{compiled_after_warmup} -> {st['executables_compiled']} "
            "after warm-up (a steady-state recompile)")
    if st["nonfinite_logits_ticks"]:
        raise AssertionError(
            f"{tag}: {st['nonfinite_logits_ticks']} non-finite ticks")
    checks = []
    for entry in expect:
        # an "xla" entry here is the fused projections: only THEIR
        # kernels must be absent (attention still is a kernel)
        seen = mosaic if EXPECT[entry][0] == "mosaic" else \
            {k: c for k, c in mosaic.items() if k in _FUSED_KERNELS}
        checks.append(check_expected(entry, seen, on_chip))
    if on_chip and st["kernel_fallbacks"] != 0:
        raise AssertionError(
            f"{tag}: kernel_fallbacks = {st['kernel_fallbacks']}, "
            "expected 0 (every serving kernel eligible at these widths)")
    say("serve", engine=tag, requests=len(prompts),
        tokens=int(sum(len(o) for o in outs)),
        warmup_s=round(warm_s, 1), wave_s=round(wave_s, 1),
        decode_steps=st["decode_steps"],
        executables_compiled=st["executables_compiled"],
        kernel_fallbacks=st["kernel_fallbacks"],
        kv_cache_dtype=st["kv_cache_dtype"],
        kv_pool_bytes=st["kv_pool_bytes"],
        prefix_tokens_reused=st["prefix_tokens_reused"],
        prefill_chunks=st["prefill_chunks"], cow_copies=st["cow_copies"],
        kernels_per_tick=st["kernels_per_tick"], mosaic_kernels=mosaic,
        expected=checks, **comp)
    short = sorted(range(len(prompts)),
                   key=lambda i: len(prompts[i][0]))[:2]
    return [(prompts[i][0], outs[i]) for i in short]


def _check_against_plain_forward(model, vocab, samples):
    """The repo's reference for a served token: the model's plain
    forward (no KV cache, no paged kernel, XLA attention at this length)
    over prompt + served tokens. Each served token must be (near-)argmax
    of the logits one position earlier."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.jit import StaticFunction
    fwd = StaticFunction(model.forward, layer=model)
    width = 16 * (1 + max(len(p) + len(t) for _, p, t in samples) // 16)
    worst = {}
    for kind, prompt, toks in samples:
        ids = np.zeros((1, width), np.int64)
        seq = np.concatenate([prompt, toks])
        ids[0, :len(seq)] = seq         # right-padded: causal, so inert
        logits = np.asarray(fwd(paddle.to_tensor(ids)).numpy(),
                            np.float32)[0]
        if logits.shape != (width, vocab):
            raise AssertionError(f"logits shape {logits.shape}")
        if not np.all(np.isfinite(logits)):
            raise AssertionError("non-finite reference logits")
        for i, t in enumerate(toks):
            row = logits[len(prompt) - 1 + i]
            gap = float(row.max() - row[t])
            worst[kind] = max(worst.get(kind, 0.0), gap)
            if gap > LOGIT_TOL[kind]:
                raise AssertionError(
                    f"{kind} KV: served token {i} sits {gap:.3f} below "
                    f"the plain forward's best logit (tolerance "
                    f"{LOGIT_TOL[kind]})")
    return {k: round(v, 4) for k, v in worst.items()}


def serve_phase(size, clock, on_chip, observe=None,
                expect=("serve.ragged_paged_attention",
                        "serve.fused_norm_matmul",
                        "serve.fused_matmul_residual")):
    import numpy as np
    from paddle_tpu.inference import ServingConfig
    model, cfg, n_params = _build_qwen2(size["widths"], size["layers"])
    model.eval()
    say("serve", model="Qwen2ForCausalLM", depth=size["layers"],
        hidden=cfg.hidden_size, heads=cfg.num_attention_heads,
        kv_heads=cfg.num_key_value_heads, ffn=cfg.intermediate_size,
        vocab=cfg.vocab_size, params=n_params, weight_bytes=2 * n_params)
    samples = []
    for kind, extra, n in (("bf16", size["engine"], size["n_requests"]),
                           ("int8", dict(size["int8_engine"],
                                         kv_cache_dtype="int8"),
                            size["n_int8_requests"])):
        if not n:
            continue
        sc = ServingConfig(**extra)
        prompts = _request_mix(np.random.default_rng(SEED), n,
                               cfg.vocab_size, sc.prefill_chunk,
                               sc.block_size, size["max_new"])
        got = _serve_engine(model, cfg.vocab_size, extra, prompts, expect,
                            clock, on_chip, kind, observe)
        samples += [(kind, p, t) for p, t in got]
        gc.collect()
    if size["engine"].get("tp_degree", 1) == 1:
        say("serve", logit_gap_max=_check_against_plain_forward(
            model, cfg.vocab_size, samples), tolerance=LOGIT_TOL)
        del model
        gc.collect()
        _serve_slot_state(clock, on_chip)
        _serve_scan_state(clock, on_chip)


def _serve_stateful(clock, on_chip, size, model, expect, tag, **fields):
    """An engine over a model with slot state beside the paged KV,
    through the same waves (chunked prompts, prefix hits cut back to a
    snapshot, a repeated prompt), checked against the model's plain
    forward."""
    import numpy as np
    from paddle_tpu.inference import ServingConfig
    model.eval()
    vocab = model.config.vocab_size
    sc = ServingConfig(**size["engine"])
    prompts = _request_mix(np.random.default_rng(SEED), size["n_requests"],
                           vocab, sc.prefill_chunk, sc.block_size,
                           size["max_new"])
    got = _serve_engine(model, vocab, size["engine"], prompts, (expect,),
                        clock, on_chip, tag)
    say("serve", model=type(model).__name__,
        logit_gap_max=_check_against_plain_forward(
            model, vocab, [("bf16", p, t) for p, t in got]),
        tolerance=LOGIT_TOL, **fields)


def _serve_scan_state(clock, on_chip):
    """Slot state that is a matrix a head, advanced by the delta rule's
    two kernels (one-row seats; a chunk through the chunkwise form);
    the plain forward it is checked against is a scan over tokens."""
    import paddle_tpu as paddle
    from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                               SolarOpen2ForCausalLM)
    paddle.seed(SEED)
    cfg = SolarOpen2Config.tiny(dtype="bfloat16", **SCAN_STATE["widths"])
    _serve_stateful(clock, on_chip, SCAN_STATE, SolarOpen2ForCausalLM(cfg),
                    "serve.scan_state.delta_rule", "scan_state",
                    gqa_layers=cfg.gqa_layers)


def _serve_slot_state(clock, on_chip):
    """Two kinds of state: the conv layers' rows a SLOT beside the
    attention layers' paged KV."""
    import paddle_tpu as paddle
    from paddle_tpu.models.lfm2_moe import (Lfm2MoeConfig,
                                            Lfm2MoeForCausalLM)
    paddle.seed(SEED)
    cfg = Lfm2MoeConfig.tiny(dtype="bfloat16", **SLOT_STATE["widths"])
    _serve_stateful(clock, on_chip, SLOT_STATE, Lfm2MoeForCausalLM(cfg),
                    "serve.slot_state.ragged_paged_attention", "slot_state",
                    layer_types=cfg.layer_types)


# ==========================================================================
# train phase
# ==========================================================================

class ChainCorpus:
    """Seeded synthetic corpus a few steps can learn from: every
    sequence follows ``t[i+1] = (3 t[i] + 2) mod 251`` from a random
    start, so the loss falls as soon as the model shifts mass onto the
    251 live tokens. Module-level and numpy-only: DataLoader workers
    import this file and unpickle it without touching JAX."""

    def __init__(self, n, seq, seed):
        import numpy as np
        rng = np.random.RandomState(seed)
        rows = [rng.randint(0, 251, size=(n, 1))]
        for _ in range(seq):
            rows.append((rows[-1] * 3 + 2) % 251)
        self.ids = np.concatenate(rows, axis=1).astype(np.int64)

    def __len__(self):
        return len(self.ids)

    def __getitem__(self, i):
        return self.ids[i, :-1], self.ids[i, 1:]


def _worker_must_stay_off_the_chip(worker_id):
    """DataLoader ``worker_init_fn``: a worker is a second process on a
    machine whose chip this trainer holds. It must start pinned to the
    CPU and must have imported the package without initialising any
    backend; a worker that fails here fails the loader, hence the
    phase."""
    from jax._src import xla_bridge
    if os.environ.get("JAX_PLATFORMS") != "cpu":
        raise RuntimeError(
            f"DataLoader worker {worker_id} started with JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS')!r}, not 'cpu'")
    if xla_bridge.backends_are_initialized():
        raise RuntimeError(
            f"DataLoader worker {worker_id}: a backend was initialised "
            "before the first batch")


def _train_steps(model, size, loader):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    opt = paddle.optimizer.AdamW(size["lr"], parameters=model.parameters(),
                                 multi_precision=True)
    step = TrainStep(model, lambda out, a, k: out, opt)
    losses, times = [], []
    for x, y in loader:
        t0 = time.monotonic()
        losses.append(float(step(x, y).numpy()))    # .numpy() syncs
        times.append(round(time.monotonic() - t0, 2))
    if len(losses) != size["steps"]:
        raise AssertionError(
            f"{len(losses)} batches arrived, expected {size['steps']}")
    if not np.all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    return step, losses, times


def _step_counters(step):
    from paddle_tpu import monitor
    return {m: monitor.counter(m, labels=("step",))
            .labels(step=step.telemetry_name).value()
            for m in ("train_step_compiles", "train_step_calls",
                      "train_step_fallback_recompiles")}


def train_phase(size, clock, on_chip):
    from paddle_tpu import monitor
    from paddle_tpu.io import DataLoader
    model, cfg, n_params = _build_qwen2(size["widths"], size["layers"])
    model.train()
    say("train", model="Qwen2ForCausalLM", depth=size["layers"],
        hidden=cfg.hidden_size, heads=cfg.num_attention_heads,
        kv_heads=cfg.num_key_value_heads, ffn=cfg.intermediate_size,
        vocab=cfg.vocab_size, params=n_params, seq=size["seq"],
        batch=size["batch"])
    # two spawned workers; they run with JAX_PLATFORMS=cpu and must
    # never reach for the chip this process holds. Worker processes need
    # the native shm ring (built from native/*.cc on first use); without
    # it the loader would quietly fall back to threads.
    from paddle_tpu import native
    if not native.is_available():
        native.ensure_built(verbose=True)       # raises with g++'s words
    loader = DataLoader(
        ChainCorpus(size["steps"] * size["batch"], size["seq"], SEED),
        batch_size=size["batch"], shuffle=False, num_workers=2,
        worker_init_fn=_worker_must_stay_off_the_chip)
    snap = clock.snapshot()
    step, losses, times = _train_steps(model, size, loader)
    counters = _step_counters(step)
    census = monitor.kernel_census(compiled=step._compiled)
    check = check_expected("train.flash_attention",
                           census["hlo_mosaic_kernels"], on_chip)
    say("train", losses=[round(v, 4) for v in losses], step_s=times,
        dataloader_workers=2, mosaic_kernels=census["hlo_mosaic_kernels"],
        hlo_kernels=census.get("hlo_kernels"), expected=check, **counters,
        **clock.since(snap))
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not decrease: {losses}")
    if counters["train_step_compiles"] != 1 \
            or counters["train_step_fallback_recompiles"] != 0:
        raise AssertionError(f"recompiles after step 1: {counters}")


# ==========================================================================
# four-chip phase
# ==========================================================================

def _bytes_in_use():
    import jax
    return [int((d.memory_stats() or {}).get("bytes_in_use", 0))
            for d in jax.devices()]


def four_chip_phase(size, clock, on_chip):
    """Tensor-parallel serving over four chips, then one fleet hybrid
    train step; every device must hold part of each."""
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.distributed.fleet import DistributedStrategy, fleet
    from paddle_tpu.jit import TrainStep
    n = len(jax.devices())
    if n < 4:
        print(f"four-chip phase: not run ({n} device)", flush=True)
        return

    def held(what):
        used = _bytes_in_use()
        say("four_chip", what=what, bytes_in_use=used)
        # (CPU devices keep no allocator statistics)
        if on_chip and not all(used[:4]):
            raise AssertionError(f"{what}: a device holds nothing: {used}")

    serve_phase(size["serve"], clock, on_chip,
                observe=lambda: held("tp_degree=4 serving"),
                expect=("tp.ragged_paged_attention", "tp.fused_decode"))
    gc.collect()

    strategy = DistributedStrategy()
    strategy.hybrid_configs = dict(
        dp_degree=1, pp_degree=1, sep_degree=1, **size["hybrid"])
    fleet.init(is_collective=True, strategy=strategy)
    try:
        ts = size["train"]
        model, cfg, n_params = _build_qwen2(ts["widths"], ts["layers"])
        model.train()
        dist_model = fleet.distributed_model(model)
        inner = getattr(dist_model, "_layers", dist_model)
        opt = fleet.distributed_optimizer(paddle.optimizer.AdamW(
            ts["lr"], parameters=inner.parameters(), multi_precision=True))
        step = TrainStep(inner, lambda out, a, k: out, opt._inner)
        data = ChainCorpus(ts["batch"], ts["seq"], SEED)
        snap = clock.snapshot()
        loss = float(step(paddle.to_tensor(data.ids[:, :-1]),
                          paddle.to_tensor(data.ids[:, 1:])).numpy())
        if not math.isfinite(loss):
            raise AssertionError(f"hybrid train step loss {loss}")
        say("four_chip", what="fleet hybrid train step",
            hybrid=size["hybrid"], depth=ts["layers"], params=n_params,
            seq=ts["seq"], batch=ts["batch"], loss=round(loss, 4),
            **_step_counters(step), **clock.since(snap))
        held("fleet hybrid train step")
    finally:
        denv.set_mesh(None)


# ==========================================================================

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated subset of %s (debugging calls; "
                         "the driver runs all)" % (PHASES,))
    args = ap.parse_args(argv)
    phases = [p for p in args.phases.split(",") if p]
    unknown = set(phases) - set(PHASES)
    if unknown:
        ap.error(f"unknown phase(s) {sorted(unknown)}")

    import jax
    from jax._src import xla_bridge
    from paddle_tpu.utils import compile_cache
    cache_dir = compile_cache.configure()
    # importing the package and placing the cache must not take a device
    # (a launcher or a DataLoader worker does exactly this much)
    import_took_a_device = xla_bridge.backends_are_initialized()
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"jax": jax.__version__, **device,
                      "compile_cache_dir": cache_dir,
                      "import_took_a_device": import_took_a_device}),
          flush=True)
    if import_took_a_device:
        print("chip_smoke: `import paddle_tpu` initialised a backend",
              file=sys.stderr)
        return 5
    if dev.platform != "tpu":
        print(f"chip_smoke: platform is {dev.platform!r}, not 'tpu' — "
              "this check only means something on the chip",
              file=sys.stderr)
        return 4

    clock = CompileClock()
    t0 = time.monotonic()
    run = {"kernels": lambda: kernel_phase(True, clock, True),
           "serve": lambda: serve_phase(SERVE_FULL, clock, True),
           "train": lambda: train_phase(TRAIN_FULL, clock, True),
           "four_chip": lambda: four_chip_phase(FOUR_CHIP_FULL, clock,
                                                True)}
    failed = []
    for name in phases:
        t1 = time.monotonic()
        snap = clock.snapshot()
        try:
            run[name]()
        except Exception:       # run the other phases too, then fail
            traceback.print_exc()
            failed.append(name)
        gc.collect()
        say(name, done=name not in failed,
            seconds=round(time.monotonic() - t1, 1), **clock.since(snap))
    say("all", seconds=round(time.monotonic() - t0, 1), failed=failed,
        **clock.since((0.0, 0, 0)))
    if failed:
        print(f"chip_smoke: FAILED phases: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark: Llama pretrain step MFU on the local chip.

Prints ONE compact JSON line FIRST: {"metric", "value", "unit",
"vs_baseline", "summary"} (kept well under 4KB so tail capture can't
truncate the headline), then writes full per-config detail to
``bench_detail.json`` next to this file.
vs_baseline = achieved MFU / 0.40 (the north-star target, BASELINE.md).

Headline value = the 8B-SHAPED config (hidden 4096 / ffn 14336 / 32
heads / GQA 8 / seq 4096, AdamW fp32 master weights) — the per-layer
shape of Llama-3-8B at the layer count that fits one chip's HBM.
``summary`` also covers the 500M base, the remat/depth regimes (16- and
32-layer anchors), MoE capacity + dropless, KV-cache decode, and the
continuous-batching serving engine (paged KV + ragged decode, aggregate
tok/s + p50/p99 per-token latency, bf16 and int8). Every
knob is env-tunable (BENCH_* vars). Training batches vary per step (a
4-batch rotating pool), so reported losses are real training signal.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np


def _peak_flops_per_chip() -> float:
    """bf16 peak of the local chip from the ONE table
    (``monitor.DEVICE_PEAKS``; an unknown device_kind raises there).
    MFU is a device metric: on the CPU backend there is no peak and no
    nominal stand-in, so the bench refuses to run."""
    from paddle_tpu import monitor
    peaks = monitor.device_peaks()
    if peaks is None:
        raise RuntimeError(
            "bench.py measures the chip and found only the CPU backend; "
            "run it through the chip tool")
    return peaks[0]


def _step_telemetry(step, step_time_s):
    """Telemetry block for one TrainStep config: the compiled-step
    accounting the monitor recorded at AOT-compile time (analytic
    FLOPs/step from XLA's cost model, peak HBM from memory_analysis,
    jaxpr collective census) plus the jit-cache counters. The analytic
    MFU counts remat recompute and optimizer/elementwise FLOPs that the
    6N closed form does not, so it sits above the bench MFU; their
    ratio is the compiled program's overhead factor (docs/OPS.md)."""
    from paddle_tpu import monitor
    name = step.telemetry_name
    rep = monitor.step_report(name) or {}
    mem = rep.get("memory") or {}

    def c(metric):
        return monitor.counter(metric, labels=("step",)) \
            .labels(step=name).value()

    amfu = monitor.analytic_mfu(name, step_time_s)
    return {
        "step_name": name,
        "analytic_flops_per_step": rep.get("flops"),
        "analytic_bytes_per_step": rep.get("bytes_accessed"),
        "analytic_mfu": None if amfu is None else round(amfu, 4),
        "peak_hbm_bytes": mem.get("peak_hbm_bytes"),
        "memory": mem,
        "collective_census": rep.get("collective_census", []),
        "cache": {
            "train_step_compiles": c("train_step_compiles"),
            "train_step_calls": c("train_step_calls"),
            "fallback_recompiles": c("train_step_fallback_recompiles"),
        },
    }


def _train_config(name, *, hidden, layers, heads, kv_heads, ffn, vocab,
                  seq, batch, steps, multi_precision=True,
                  remat="none", remat_interval=1, windows=1):
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    # remat: "none" wins when the config fits HBM (measured: 0.69 vs
    # 0.59 MFU at the 8B-shaped config); "dots"/"full" trade MFU for
    # memory via FLAGS_paddle_tpu_remat_policy. remat_interval=k remats
    # every k-th layer — k=2 with "full" measured best in the remat
    # regime (0.642 vs 0.637 dots / 0.574 full-all, same session)
    if remat != "none":
        paddle.set_flags({"FLAGS_paddle_tpu_remat_policy": remat})
    cfg = LlamaConfig(
        vocab_size=vocab, hidden_size=hidden, intermediate_size=ffn,
        num_hidden_layers=layers, num_attention_heads=heads,
        num_key_value_heads=kv_heads, max_position_embeddings=seq,
        recompute=remat != "none", recompute_interval=remat_interval,
        dtype="bfloat16")

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.train()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(),
                                 multi_precision=multi_precision)
    step = TrainStep(model, lambda out, a, k: out, opt)

    # a varying stream of batches (not one memorized batch): the loss
    # printed below is then a real training signal, and throughput is
    # measured under realistic input churn
    rng = np.random.RandomState(0)
    pool = []
    for _ in range(4):
        ids = rng.randint(0, vocab, (batch, seq)).astype(np.int64)
        labels = np.roll(ids, -1, axis=1)   # dataset-shifts convention
        pool.append((paddle.to_tensor(ids), paddle.to_tensor(labels)))

    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())

    loss = step(*pool[0])       # warmup/compile
    _ = float(loss.numpy())

    # run-to-run noise: time `windows` independent windows
    # and report the MEDIAN one (the headline config uses 3)
    times = []
    it = 0
    for _ in range(max(int(windows), 1)):
        # burn one untimed trial per window so a cold-cache/compile
        # straggler can never land inside the measurement (r5 weak #5)
        loss = step(*pool[it % len(pool)])
        it += 1
        _ = float(loss.numpy())
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(*pool[it % len(pool)])
            it += 1
        val = float(loss.numpy())   # forces completion
        times.append(time.perf_counter() - t0)
    dt = sorted(times)[len(times) // 2]

    tokens = batch * seq * steps
    tok_per_sec = tokens / dt
    # training flops/token: 6N (fwd+bwd matmuls) + 12*L*s*h attention
    flops_per_token = 6 * n_params + 12 * layers * seq * hidden
    mfu = tok_per_sec * flops_per_token / _peak_flops_per_chip()
    telemetry = _step_telemetry(step, dt / steps)
    # free this config's params/optimizer state before the next one
    # builds (three ~1B configs would otherwise exhaust HBM)
    import gc
    del step, opt, model, loss, pool
    gc.collect()
    return {
        "name": name,
        "mfu": round(mfu, 4),
        "telemetry": telemetry,
        "tokens_per_sec_per_chip": round(tok_per_sec, 1),
        "step_time_ms": round(1000 * dt / steps, 1),
        "n_params": n_params,
        "loss": round(val, 4),
        "master_weights": bool(multi_precision),
        "remat": remat,
        "config": {"hidden": hidden, "layers": layers, "heads": heads,
                   "kv_heads": kv_heads, "ffn": ffn, "seq": seq,
                   "batch": batch, "vocab": vocab},
    }


def _moe_bench(dropless=False):
    """Qwen2-MoE-shaped pretrain step: tokens/s/chip + MFU + router drop
    rate (single-chip scale of the 57B-A14B geometry: GQA attention,
    shared expert + 32 routed experts, top-4). ``dropless=True`` swaps
    the capacity-limited GShard dispatch for the grouped-matmul path
    (zero drops); since r6 BOTH modes run the sort-based grouped
    engine (megablox on TPU). The default expert width is h-scaled
    (1408 = 1.375h vs r5's 704): 1024-in 704-out matmuls starved the
    MXU — wider experts raise arithmetic intensity at the same
    active-param accounting."""
    import gc
    import paddle_tpu as paddle
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeConfig,
                                             Qwen2MoeForCausalLM)

    steps = int(os.environ.get("BENCH_MOE_STEPS", 5))
    cfg = Qwen2MoeConfig(
        vocab_size=32000,
        hidden_size=int(os.environ.get("BENCH_MOE_HIDDEN", 1024)),
        intermediate_size=int(os.environ.get("BENCH_MOE_FFN", 2816)),
        moe_intermediate_size=int(
            os.environ.get("BENCH_MOE_EFFN", 1408)),
        shared_expert_intermediate_size=int(
            os.environ.get("BENCH_MOE_SFFN", 2816)),
        num_hidden_layers=int(os.environ.get("BENCH_MOE_LAYERS", 4)),
        num_attention_heads=16, num_key_value_heads=8,
        num_experts=int(os.environ.get("BENCH_MOE_EXPERTS", 32)),
        num_experts_per_tok=int(os.environ.get("BENCH_MOE_TOPK", 4)),
        dropless=dropless,
        max_position_embeddings=2048, dtype="bfloat16")
    paddle.seed(0)
    model = Qwen2MoeForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.train()
    opt = paddle.optimizer.AdamW(1e-4, parameters=model.parameters(),
                                 multi_precision=True)
    step = TrainStep(model, lambda out, a, k: out, opt)

    batch, seq = int(os.environ.get("BENCH_MOE_BATCH", 4)), 2048
    rng = np.random.RandomState(0)
    pool = []
    for _ in range(4):      # varying stream, not one memorized batch
        ids = rng.randint(0, cfg.vocab_size,
                          (batch, seq)).astype(np.int64)
        pool.append((paddle.to_tensor(ids),
                     paddle.to_tensor(np.roll(ids, -1, axis=1))))
    x = pool[0][0]
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())

    drops = model.collect_drop_rates(x)

    from paddle_tpu.distributed.moe import moe_stats, reset_moe_stats
    reset_moe_stats()
    loss = step(*pool[0])
    _ = float(loss.numpy())
    kernel_stats = moe_stats()
    # run-to-run noise: median of 3 windows
    times = []
    it = 0
    for _ in range(3):
        # burn one untimed trial per window (r5 weak #5: cold trials
        # were landing inside the median's input)
        loss = step(*pool[it % len(pool)])
        it += 1
        _ = float(loss.numpy())
        t0 = time.perf_counter()
        for _ in range(steps):
            loss = step(*pool[it % len(pool)])
            it += 1
        val = float(loss.numpy())
        times.append(time.perf_counter() - t0)
    dt = sorted(times)[1]
    tok_per_sec = batch * seq * steps / dt
    # MoE MFU: only ACTIVE params do work per token — total minus the
    # (experts - top_k) routed experts each token never touches
    inactive = (cfg.num_experts - cfg.num_experts_per_tok) * \
        cfg.num_hidden_layers * 3 * cfg.hidden_size * \
        cfg.moe_intermediate_size
    active_params = n_params - inactive
    flops_per_token = 6 * active_params + \
        12 * cfg.num_hidden_layers * seq * cfg.hidden_size
    mfu = tok_per_sec * flops_per_token / _peak_flops_per_chip()
    out = {
        "moe_tokens_per_sec_per_chip": round(tok_per_sec, 1),
        "mfu": round(mfu, 4),
        "step_time_ms": round(1000 * dt / steps, 1),
        "n_params": n_params,
        "active_params": active_params,
        "dispatch": "dropless" if dropless else "gshard_capacity",
        # which grouped kernel the train step actually compiled
        # (megablox on TPU / ragged_dot fallback) + path counters
        "kernel_stats": kernel_stats,
        "drop_rate_mean": round(float(np.mean(drops)), 4),
        "drop_rate_per_block": [round(d, 4) for d in drops],
        "telemetry": _step_telemetry(step, dt / steps),
        "loss": round(val, 4),
        "config": {"hidden": cfg.hidden_size,
                   "experts": cfg.num_experts,
                   "top_k": cfg.num_experts_per_tok,
                   "layers": cfg.num_hidden_layers,
                   "batch": batch, "seq": seq},
    }
    del step, opt, model, loss, pool, x
    gc.collect()
    return out


def _moe_stage_profile():
    """Step-profile of ONE MoE block at the bench shapes, broken into
    the dispatch pipeline's stages: route+sort+gather (dispatch), the
    two grouped expert matmuls (expert_mm), and unsort+weighted-sum
    (combine) — so the remaining MoE-vs-dense MFU gap is attributable
    to a stage instead of a guess. Stages are jitted SEPARATELY, so
    boundaries materialize to HBM: the sum slightly exceeds the fused
    in-graph cost — use for attribution, not as a step time. a2a_ms is
    None on a single chip (the explicit all-to-all pair only exists
    inside the EP shard_map path; under a sharded run its cost is the
    profile's residual)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed import moe as M

    hidden = int(os.environ.get("BENCH_MOE_HIDDEN", 1024))
    effn = int(os.environ.get("BENCH_MOE_EFFN", 1408))
    experts = int(os.environ.get("BENCH_MOE_EXPERTS", 32))
    topk = int(os.environ.get("BENCH_MOE_TOPK", 4))
    tokens = int(os.environ.get("BENCH_MOE_BATCH", 4)) * 2048

    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(tokens, hidden)).astype(jnp.bfloat16)
    logits = jnp.asarray(rng.randn(tokens, experts)) \
        .astype(jnp.bfloat16)
    gu_w = jnp.asarray(0.02 * rng.randn(experts, hidden, 2 * effn)) \
        .astype(jnp.bfloat16)
    dn_w = jnp.asarray(0.02 * rng.randn(experts, effn, hidden)) \
        .astype(jnp.bfloat16)

    @jax.jit
    def route(x, logits):
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        tp, ti = jax.lax.top_k(probs, topk)
        flat_e = ti.astype(jnp.int32).reshape(-1)
        order, rank, counts = M._sort_pairs(flat_e, experts)
        gates = (tp / jnp.maximum(tp.sum(-1, keepdims=True), 1e-9)) \
            .astype(x.dtype)
        xs = jnp.take(x, order // topk, axis=0)
        return xs, counts, rank, order, gates

    @jax.jit
    def expert_mm(xs, counts):
        return M._expert_swiglu_grouped(xs, gu_w, dn_w, counts,
                                        xs.dtype)

    @jax.jit
    def combine(ys, rank, gates):
        picked = jnp.take(ys, rank, axis=0).reshape(tokens, topk, -1)
        return jnp.einsum("sk,skd->sd", gates, picked)

    def timeit(f, *args, n=20):
        r = jax.block_until_ready(f(*args))     # compile + warm
        r = jax.block_until_ready(f(*args))
        t0 = time.perf_counter()
        for _ in range(n):
            r = f(*args)
        jax.block_until_ready(r)
        return round((time.perf_counter() - t0) / n * 1000, 3)

    xs, counts, rank, order, gates = jax.block_until_ready(
        route(x, logits))
    ys = jax.block_until_ready(expert_mm(xs, counts))
    return {
        "tokens": tokens, "experts": experts, "top_k": topk,
        "hidden": hidden, "expert_ffn": effn,
        "dispatch_ms": timeit(route, x, logits),
        "expert_mm_ms": timeit(expert_mm, xs, counts),
        "combine_ms": timeit(combine, ys, rank, gates),
        "a2a_ms": None,
    }


def _flashmask_bench():
    """FlashMask compact-form kernel at 16k context: document-causal
    mask (8 docs) vs full causal, fwd+bwd. The dense-bias lowering is
    impossible at this length ([1, 1, 16k, 16k] f32 = 1 GB per mask
    head, [B, H, L, L] scores ~8 GB); the block-skip speedup is the
    sparsity FlashMask exists for."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas.flashmask_kernel import \
        pallas_flashmask_attention
    from paddle_tpu.ops.pallas.flash_attention_kernel import \
        pallas_flash_attention

    L, H, Hkv, D = 16384, 8, 4, 64
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(1, L, H, D), jnp.bfloat16)
    k = jnp.asarray(rng.randn(1, L, Hkv, D), jnp.bfloat16)
    v = jnp.asarray(rng.randn(1, L, Hkv, D), jnp.bfloat16)
    docs = np.linspace(0, L, 9).astype(np.int32)
    start = np.zeros(L, np.int32)
    for a, b in zip(docs[:-1], docs[1:]):
        start[a:b] = b
    idx = jnp.asarray(start)[None, None, :, None]

    def timeit(f, n=20):
        g = jax.grad(lambda q, k, v:
                     f(q, k, v).astype(jnp.float32).sum(),
                     argnums=(0, 1, 2))
        ww = jax.jit(lambda q, k, v: sum(
            jnp.sum(l.astype(jnp.float32)) for l in g(q, k, v)))
        float(ww(q, k, v))
        t0 = time.perf_counter()
        for _ in range(n):
            r = ww(q, k, v)
        float(r)
        return (time.perf_counter() - t0) / n * 1000

    doc_ms = timeit(lambda q, k, v: pallas_flashmask_attention(
        q, k, v, idx, causal=True))
    full_ms = timeit(lambda q, k, v: pallas_flash_attention(
        q, k, v, causal=True))
    return {
        "seq": L, "heads": H, "kv_heads": Hkv, "n_docs": 8,
        "doc_causal_fwdbwd_ms": round(doc_ms, 2),
        "full_causal_fwdbwd_ms": round(full_ms, 2),
        "block_skip_speedup": round(full_ms / doc_ms, 2),
    }


def _decode_bench():
    """KV-cache generate() throughput (tokens/sec, greedy): bf16 and
    weight-only int8 (``nn.quant.quantize_for_inference`` — the
    PaddleNLP predictor weight_only_int8 serving mode). Decode at this
    batch is weights-HBM-bound (BASELINE.md ceiling ~5060 tok/s bf16 at
    this shape), so int8 weights raise the ceiling ~2x.

    Parity is measured TEACHER-FORCED: one forward over the bf16-
    generated sequence through both models, comparing per-position
    argmax — trajectory comparison would compound a single early flip
    into total divergence and measure chaos, not quant quality (this
    is a random-weight model; its logit margins are already razor-thin).
    """
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.nn.quant import quantize_for_inference

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=8, num_attention_heads=16,
        num_key_value_heads=8, max_position_embeddings=1024,
        dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()
    batch, prompt, new = 8, 128, 256
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size,
                                           (batch, prompt))
    x = paddle.to_tensor(ids.astype(np.int64))

    def run_trials(n=5):
        # burn one untimed trial first: the first post-warmup generate
        # was still ~half the median (r5 weak #5) — never let it into
        # the median's input
        out, _ = model.generate(x, max_new_tokens=new)
        _ = out.numpy()
        vals = []
        for _ in range(n):                       # noise robust
            t0 = time.perf_counter()
            out, _ = model.generate(x, max_new_tokens=new)
            _ = out.numpy()
            vals.append(batch * new / (time.perf_counter() - t0))
        return vals, out

    for _ in range(2):                           # compile + cache warm
        model.generate(x, max_new_tokens=new)
    bf_vals, bf_out = run_trials()
    bf_seq = np.concatenate([ids, np.asarray(bf_out.numpy())], axis=1)

    def forced_argmax():
        logits = model(paddle.to_tensor(bf_seq.astype(np.int64)))
        return np.asarray(logits.numpy()).argmax(-1)

    am_bf = forced_argmax()
    n_conv = quantize_for_inference(model)
    am_q = forced_argmax()
    # agreement on the positions that PRODUCED the generated tokens
    region = slice(prompt - 1, prompt - 1 + new)
    parity = float((am_bf[:, region] == am_q[:, region]).mean())

    for _ in range(2):
        model.generate(x, max_new_tokens=new)
    q_vals, q_out = run_trials()
    traj = float((np.asarray(bf_out.numpy())
                  == np.asarray(q_out.numpy())).mean())
    return {"decode_tokens_per_sec": round(sorted(bf_vals)[2], 1),
            "decode_trials": [round(v, 1) for v in bf_vals],
            "int8_tokens_per_sec": round(sorted(q_vals)[2], 1),
            "int8_trials": [round(v, 1) for v in q_vals],
            "int8_layers_converted": n_conv,
            "int8_teacher_forced_parity": round(parity, 4),
            "int8_trajectory_match": round(traj, 4),
            "batch": batch, "prompt_len": prompt, "new_tokens": new}


def _serving_bench():
    """Continuous-batching serving throughput (the ISSUE-3 serving bar):
    a mixed-length request workload through ``ServingEngine`` — paged
    KV block pool, ragged decode attention, fixed-slot batched decode
    compiled once — reported as aggregate tok/s + p50/p99 per-token
    latency (a decode step IS one token for every active slot), against
    a single-stream (batch-1) ``generate()`` baseline, bf16 and
    weight-only int8 (fused mixed-dtype dot). ``recompiles_measured``
    must be 0: the steady-state decode executable never changes."""
    import gc
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.nn.quant import quantize_for_inference

    # the decode-bench model shape, so serving aggregate tok/s compares
    # directly against decode_tokens_per_sec
    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_SERVE_VOCAB", 32000)),
        hidden_size=int(os.environ.get("BENCH_SERVE_HIDDEN", 2048)),
        intermediate_size=int(os.environ.get("BENCH_SERVE_FFN", 5632)),
        num_hidden_layers=int(os.environ.get("BENCH_SERVE_LAYERS", 8)),
        num_attention_heads=16,
        num_key_value_heads=8, max_position_embeddings=1024,
        dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()

    slots = int(os.environ.get("BENCH_SERVE_SLOTS", 8))
    new = int(os.environ.get("BENCH_SERVE_NEW", 128))
    n_req = int(os.environ.get("BENCH_SERVE_REQS", 24))
    # mixed prompt lengths spanning prefill buckets + block boundaries
    plens = [32, 64, 96, 160, 224, 128, 48, 192]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (plens[i % len(plens)],))
               for i in range(n_req)]

    def run_engine(m):
        eng = ServingEngine(m, ServingConfig(
            num_slots=slots, block_size=32, max_model_len=512,
            max_new_tokens=new, min_prefill_bucket=32))
        # warmup: compile the decode step + every prefill bucket
        eng.serve([rng.randint(1, cfg.vocab_size, (p,))
                   for p in plens], max_new_tokens=4)
        compiles0 = eng.stats()["decode_compiles"]
        tokens0 = eng.stats()["tokens_total"]
        for p in prompts:
            eng.submit(p, new)
        step_ms = []
        t0 = time.perf_counter()
        while eng.num_queued or eng.num_active:
            s0 = time.perf_counter()
            eng.step()
            step_ms.append(1000 * (time.perf_counter() - s0))
        wall = time.perf_counter() - t0
        st = eng.stats()
        lat = np.sort(np.asarray(step_ms))
        return {
            "aggregate_tokens_per_sec":
                round((st["tokens_total"] - tokens0) / wall, 1),
            "p50_token_latency_ms": round(float(
                lat[len(lat) // 2]), 2),
            "p99_token_latency_ms": round(float(
                lat[min(len(lat) - 1, int(len(lat) * 0.99))]), 2),
            "decode_steps": st["decode_steps"],
            "recompiles_measured":
                st["decode_compiles"] - compiles0,
            "requests": n_req, "num_slots": slots,
            "max_new_tokens": new,
        }

    # single-stream baseline: one sequence end-to-end at a time
    ids1 = paddle.to_tensor(
        rng.randint(1, cfg.vocab_size, (1, 128)).astype(np.int64))
    for _ in range(2):
        model.generate(ids1, max_new_tokens=new)
    ss = []
    for _ in range(3):
        t0 = time.perf_counter()
        out, _ = model.generate(ids1, max_new_tokens=new)
        _ = out.numpy()
        ss.append(new / (time.perf_counter() - t0))
    single = round(sorted(ss)[1], 1)

    bf16 = run_engine(model)
    n_conv = quantize_for_inference(model)
    int8 = run_engine(model)
    out = {
        "single_stream_tokens_per_sec": single,
        "bf16": bf16,
        "int8": int8,
        "int8_layers_converted": n_conv,
        "batch_speedup_vs_single_stream": round(
            bf16["aggregate_tokens_per_sec"] / max(single, 1e-9), 2),
        "workload_prompt_lens": plens,
    }
    del model
    gc.collect()
    return out


def _kv_quant_bench():
    """int8-vs-fp KV pool A/B (the ISSUE-10 bar): the serving-bench
    workload through two otherwise identical engines — fp pool vs
    ``kv_cache_dtype="int8"`` (int8 data + per-(block, position, head)
    absmax scales, in-kernel dequant). Reports decode tok/s, the
    analytic KV bytes/step gauge (HBM bytes the attention streams —
    the quantity int8 halves), pool bytes, slots-at-fixed-pool-bytes
    (how many worst-case slots one fp-pool byte budget admits per
    dtype — the capacity axis), and the greedy token MATCH RATE (the
    >= 0.99 acceptance budget; quantization perturbs logits, so this
    is a rate, not bit parity). The match budget is measured on a
    briefly TRAINED chain-task model — peaked logits are what
    deployment accuracy means; the big bench model's random init has
    near-degenerate top-2 margins that flip under any perturbation of
    this size, and its worst-case rates are reported separately as
    ``*_random_init``. On CPU the tok/s arms are flagged
    ``cpu_proxy`` — dequant costs CPU FLOPs while the bandwidth win
    needs real HBM; bytes/capacity/match-rate numbers are
    backend-independent."""
    import gc
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine

    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_KV_QUANT_VOCAB", 32000)),
        hidden_size=int(os.environ.get("BENCH_KV_QUANT_HIDDEN", 2048)),
        intermediate_size=int(os.environ.get("BENCH_KV_QUANT_FFN",
                                             5632)),
        num_hidden_layers=int(os.environ.get("BENCH_KV_QUANT_LAYERS",
                                             8)),
        num_attention_heads=16,
        num_key_value_heads=8, max_position_embeddings=1024,
        dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()

    slots = int(os.environ.get("BENCH_KV_QUANT_SLOTS", 8))
    new = int(os.environ.get("BENCH_KV_QUANT_NEW", 64))
    n_req = int(os.environ.get("BENCH_KV_QUANT_REQS", 16))
    max_len = int(os.environ.get("BENCH_KV_QUANT_MAXLEN", 512))
    plens = [32, 64, 96, 160, 224, 128, 48, 192]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (plens[i % len(plens)],))
               for i in range(n_req)]

    def run_engine(kv_dtype):
        eng = ServingEngine(model, ServingConfig(
            num_slots=slots, block_size=32, max_model_len=max_len,
            max_new_tokens=new, kv_cache_dtype=kv_dtype))
        eng.serve(prompts[:2], max_new_tokens=4)        # warmup/compile
        tokens0 = eng.stats()["tokens_total"]
        compiles0 = eng.stats()["decode_compiles"]
        for p in prompts:
            eng.submit(p, new)
        t0 = time.perf_counter()
        while eng.num_queued or eng.num_active:
            eng.step()
        wall = time.perf_counter() - t0
        st = eng.stats()
        outs = eng.run()
        eng.shutdown()
        return {
            "aggregate_tokens_per_sec":
                round((st["tokens_total"] - tokens0) / wall, 1),
            "kv_cache_dtype": st["kv_cache_dtype"],
            "kv_pool_bytes": st["kv_pool_bytes"],
            "kv_bytes_per_step": st["kv_bytes_per_step"],
            "recompiles_measured":
                st["decode_compiles"] - compiles0,
        }, outs

    fp, fp_outs = run_engine(None)
    q8, q8_outs = run_engine("int8")
    # free-running sequence agreement: one early flip cascades (every
    # later token sees a different context), so this is the
    # pessimistic bound — reported, but the 0.99 budget is pinned on
    # the teacher-forced rate below
    tot = hit = 0
    for r in sorted(fp_outs):
        a, b = np.asarray(fp_outs[r]), np.asarray(q8_outs[r])
        tot += a.size
        hit += int((a == b).sum())
    seq_match = hit / max(tot, 1)
    # teacher-forced per-step agreement on the big RANDOM model: run
    # the SAME committed sequence (prompt + fp continuation) through
    # one multi-query paged forward per pool dtype — the chunk-prefill
    # body, every position attending the quantized (or fp) KV written
    # before it — and compare per-position argmax. Labeled
    # random-init: an untrained model's top-2 logit margins are
    # near-degenerate (any ~0.3% perturbation flips them), so this is
    # the worst-case context number, NOT the acceptance metric.
    from paddle_tpu.jit import _LayerBinder
    from paddle_tpu.ops.paged_cache import blocks_for
    import jax.numpy as jnp
    binder = _LayerBinder(model)
    step = model._build_model_step(binder, binder.buffer_arrays())
    params = binder.param_arrays()
    n_tf = int(os.environ.get("BENCH_KV_QUANT_TF_SEQS", 4))
    seqs = [np.concatenate([prompts[i],
                            np.asarray(fp_outs[sorted(fp_outs)[i]])])
            for i in range(min(n_tf, len(prompts)))]
    L = max(len(s) for s in seqs)
    mb = blocks_for(L, 32)
    tables = jnp.asarray(1 + np.arange(mb, dtype=np.int32))[None]

    def tf_argmax(kv_dtype):
        kw = {"kv_cache_dtype": kv_dtype} if kv_dtype else {}
        outs = []
        for s in seqs:
            pools = model.init_paged_caches(1 + mb, 32, **kw)
            ids = np.zeros((1, L), np.int32)
            ids[0, :len(s)] = s
            logits, _ = step(params, jnp.asarray(ids), pools, None,
                             block_tables=tables,
                             cache_lens=jnp.zeros((1,), jnp.int32))
            outs.append(np.asarray(
                jnp.argmax(logits[0, :len(s)], axis=-1)))
            del logits, pools
        return outs

    tf_fp = tf_argmax(None)
    tf_q8 = tf_argmax("int8")
    tf_tot = sum(a.size for a in tf_fp)
    tf_hit = sum(int((a == b).sum()) for a, b in zip(tf_fp, tf_q8))
    match_random = tf_hit / max(tf_tot, 1)
    del binder, step, params
    # the ACCEPTANCE metric (>= 0.99): greedy token match on a TRAINED
    # model — deployment accuracy is a property of peaked, trained
    # logits, which the big bench model's random init cannot exhibit
    # at CPU-trainable cost. A small chain-task model trains in
    # seconds, serves the same engine/kernel paths, and measures the
    # quantity the budget bounds (examples/llm_serving.py part 8
    # asserts the same bar).
    t_steps = int(os.environ.get("BENCH_KV_QUANT_TRAIN_STEPS", 120))
    t_vocab = 64
    paddle.seed(17)
    tcfg = LlamaConfig.tiny(vocab=t_vocab, hidden=64, layers=2,
                            heads=4, kv_heads=2, ffn=176)
    tmodel = LlamaForCausalLM(tcfg)
    from paddle_tpu.jit import TrainStep
    opt = paddle.optimizer.AdamW(3e-3, parameters=tmodel.parameters())
    tstep = TrainStep(tmodel, lambda out, a, k: out, opt)
    rng_t = np.random.RandomState(0)
    for _ in range(t_steps):
        start = rng_t.randint(0, t_vocab, (16, 1))
        rows = [start]
        for _ in range(24):
            rows.append((rows[-1] * 5 + 3) % t_vocab)
        ids = np.concatenate(rows, 1).astype(np.int64)
        tstep(paddle.to_tensor(ids[:, :-1]),
              labels=paddle.to_tensor(ids[:, 1:]))
    tmodel.eval()

    def chain_prompt(x, n):
        out = [x]
        for _ in range(n - 1):
            out.append((out[-1] * 5 + 3) % t_vocab)
        return np.asarray(out, np.int32)

    t_prompts = [chain_prompt(x, n) for x, n in
                 ((7, 9), (11, 17), (3, 33), (23, 12))]

    def run_tiny(kv_dtype):
        eng = ServingEngine(tmodel, ServingConfig(
            num_slots=2, block_size=32, max_model_len=96,
            kv_cache_dtype=kv_dtype))
        outs = eng.serve(list(t_prompts), max_new_tokens=16)
        eng.shutdown()
        return outs

    t_fp = run_tiny(None)
    t_q8 = run_tiny("int8")
    t_tot = sum(len(a) for a in t_fp)
    t_hit = sum(int((np.asarray(a) == np.asarray(b)).sum())
                for a, b in zip(t_fp, t_q8))
    match = t_hit / max(t_tot, 1)
    # capacity axis: worst-case slots one FP pool byte budget admits.
    # bytes per block = pool bytes / num_blocks; a slot's worst case
    # is blocks_for(max_model_len) blocks
    mb = blocks_for(max_len, 32)
    nb = 1 + slots * mb
    budget = fp["kv_pool_bytes"]
    slots_fp = budget // (mb * (fp["kv_pool_bytes"] // nb))
    slots_q8 = budget // (mb * (q8["kv_pool_bytes"] // nb))
    out = {
        "fp": fp,
        "int8": q8,
        # the acceptance metric: trained-model greedy match (>= 0.99)
        "token_match_rate": round(match, 4),
        "token_match_rate_trained_steps": t_steps,
        # context numbers on the big RANDOM-init bf16 model (worst
        # case: near-degenerate top-2 margins flip under any
        # perturbation of this size)
        "token_match_rate_random_init": round(match_random, 4),
        "sequence_match_rate_random_init": round(seq_match, 4),
        "pool_bytes_ratio": round(
            q8["kv_pool_bytes"] / fp["kv_pool_bytes"], 4),
        "kv_bytes_per_step_ratio": round(
            q8["kv_bytes_per_step"] / max(fp["kv_bytes_per_step"], 1),
            4),
        "slots_at_fixed_pool_bytes": {"fp": int(slots_fp),
                                      "int8": int(slots_q8)},
        "slots_ratio": round(slots_q8 / max(slots_fp, 1), 2),
        "speedup_tokens_per_sec": round(
            q8["aggregate_tokens_per_sec"]
            / max(fp["aggregate_tokens_per_sec"], 1e-9), 2),
        "workload_prompt_lens": plens,
        # the tok/s arms only show the HBM win on real TPU hardware
        "cpu_proxy": jax.default_backend() != "tpu",
    }
    del model
    gc.collect()
    return out


def _roofline_bench():
    """Per-tick roofline attribution (ISSUE 15): serve a short mixed
    workload and read ``stats()['roofline']`` — every executable's
    cost-model FLOPs / HBM bytes fused with the measured per-tick
    step time into live MFU, HBM-bandwidth utilization and a
    compute-vs-bandwidth-bound classification against the chip's
    published peaks (``device`` names the chip). The summary keys
    ``step_mfu``/``hbm_bw_util`` are trajectory-asserted every
    round."""
    import gc
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine

    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_ROOF_VOCAB", 32000)),
        hidden_size=int(os.environ.get("BENCH_ROOF_HIDDEN", 1024)),
        intermediate_size=int(os.environ.get("BENCH_ROOF_FFN", 2816)),
        num_hidden_layers=int(os.environ.get("BENCH_ROOF_LAYERS", 4)),
        num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=1024, dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()
    eng = ServingEngine(model, ServingConfig(
        num_slots=int(os.environ.get("BENCH_ROOF_SLOTS", 4)),
        block_size=32, max_model_len=512))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (n,))
               for n in (32, 64, 48, 96)]
    eng.serve(prompts,
              max_new_tokens=int(os.environ.get("BENCH_ROOF_NEW",
                                                16)))
    roof = eng.stats()["roofline"]
    eng.shutdown()
    tick = roof["tick_executable"]
    out = {
        "step_mfu": roof["step_mfu"],
        "hbm_bw_util": roof["step_hbm_bw_util"],
        "tick_executable": tick,
        "bound": roof["per_executable"].get(tick, {}).get("bound"),
        "ridge_flops_per_byte": roof["ridge_flops_per_byte"],
        "peak_flops_per_s": roof["peak_flops_per_s"],
        "peak_hbm_bytes_per_s": roof["peak_hbm_bytes_per_s"],
        "per_executable": roof["per_executable"],
        "device": roof["device"],
    }
    del model, eng
    gc.collect()
    return out


def _goodput_bench():
    """Goodput under SLO (the ISSUE-11 observability bar): the
    serving-bench model driven by the closed-loop load harness
    (``inference/loadgen.py``). A closed-loop capacity probe at full
    concurrency measures max sustainable QPS; the SLO is calibrated
    from the probe's own latencies (3x p50 TTFT/TPOT — env overrides
    ``BENCH_GOODPUT_SLO_TTFT_MS`` / ``BENCH_GOODPUT_SLO_ITL_MS`` for
    real fleets), and two OPEN-loop arms then offer {0.6, 1.2}x
    capacity — under and over the knee — reporting goodput (fraction
    of requests meeting the TTFT+TPOT SLO) and client-side TTFT/ITL
    p50/p99 vs offered load. The engine's always-on P² digests ride
    along as ``engine_digests_cumulative`` — the server-side view of
    the WHOLE session (warmup + capacity probe + both arms), so its
    tails sit above the 0.6x arm's client-side numbers by
    construction; compare per-arm latencies against the per-arm
    client reports, not against this. On CPU the absolute latencies
    are a structure proxy (``cpu_proxy``); the harness and the
    goodput-vs-load shape are backend-independent."""
    import gc
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.inference.loadgen import SLO, run_load

    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_GOODPUT_VOCAB", 32000)),
        hidden_size=int(os.environ.get("BENCH_GOODPUT_HIDDEN", 2048)),
        intermediate_size=int(os.environ.get("BENCH_GOODPUT_FFN",
                                             5632)),
        num_hidden_layers=int(os.environ.get("BENCH_GOODPUT_LAYERS",
                                             8)),
        num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=1024, dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()

    slots = int(os.environ.get("BENCH_GOODPUT_SLOTS", 8))
    new = int(os.environ.get("BENCH_GOODPUT_NEW", 32))
    n_req = int(os.environ.get("BENCH_GOODPUT_REQS", 24))
    plens = [32, 64, 96, 160, 128, 48]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size,
                           (plens[i % len(plens)],))
               for i in range(n_req)]

    eng = ServingEngine(model, ServingConfig(
        num_slots=slots, block_size=32, max_model_len=512,
        max_new_tokens=new))
    eng.serve([rng.randint(1, cfg.vocab_size, (p,)) for p in plens],
              max_new_tokens=4)     # warmup: compile the executable
    # 1) capacity: closed loop at full concurrency (self-throttling,
    # so this is the max sustainable request rate, not an SLO test)
    probe = run_load(eng, [p.copy() for p in prompts], mode="closed",
                     concurrency=slots, max_new_tokens=new)
    cap_qps = max(probe["achieved_qps"], 1e-3)
    # 2) SLO from the probe's own p50s (the 3x budget keeps goodput
    # non-trivial on any backend without hand-tuned absolute numbers)
    slo = SLO(
        ttft_ms=float(os.environ.get(
            "BENCH_GOODPUT_SLO_TTFT_MS",
            3.0 * max(probe["ttft_p50_ms"], 1.0))),
        itl_ms=float(os.environ.get(
            "BENCH_GOODPUT_SLO_ITL_MS",
            3.0 * max(probe["tpot_p50_ms"], 1.0))))
    # 3) open-loop arms under and over the capacity knee
    arms = {}
    for frac in (0.6, 1.2):
        rep = run_load(eng, [p.copy() for p in prompts],
                       qps=round(frac * cap_qps, 3), mode="open",
                       max_new_tokens=new, slo=slo, seed=1)
        arms[f"offered_{frac}x"] = rep
    target = arms["offered_0.6x"]
    st = eng.stats()
    eng.shutdown()
    out = {
        "capacity_probe": probe,
        "slo": {"ttft_ms": round(slo.ttft_ms, 3),
                "itl_ms": round(slo.itl_ms, 3)},
        **arms,
        "target_arm": "offered_0.6x",
        "goodput_at_qps": target["goodput"],
        "target_qps": target["offered_qps"],
        "ttft_p99_ms": target["ttft_p99_ms"],
        "itl_p99_ms": target["itl_p99_ms"],
        # server-side P² digests over the WHOLE session (warmup +
        # probe + both arms) — NOT comparable 1:1 with the target
        # arm's client-side percentiles
        "engine_digests_cumulative": {k: st[k] for k in
                                      ("ttft_ms", "itl_ms",
                                       "queue_wait_ms", "e2e_ms")},
        "requests_per_arm": n_req, "num_slots": slots,
        "max_new_tokens": new,
        "cpu_proxy": jax.default_backend() != "tpu",
    }
    del model, eng
    gc.collect()
    return out


def _health_bench():
    """Fleet health engine (the ISSUE-17 observability bar): two arms
    on a small serving model. The HEALTHY arm serves a steady workload
    under generous SLO budgets and pins the false-positive rate — no
    alert may fire and the health score must stay 1.0. The OVERLOAD
    arm pins sensitivity — an impossible SLO budget with short burn
    windows must trip the ``slo_fast_burn`` page within the run, and
    the auto-captured incident bundle (manifest + stats + journal)
    must be loadable back from a scratch ``PADDLE_TPU_INCIDENT_DIR``.
    Absolute latencies are backend-dependent (``cpu_proxy``); the
    detector arithmetic and the bundle format are not."""
    import gc
    import tempfile
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine

    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_HEALTH_VOCAB", 8000)),
        hidden_size=int(os.environ.get("BENCH_HEALTH_HIDDEN", 512)),
        intermediate_size=int(os.environ.get("BENCH_HEALTH_FFN",
                                             1408)),
        num_hidden_layers=int(os.environ.get("BENCH_HEALTH_LAYERS",
                                             4)),
        num_attention_heads=8, num_key_value_heads=4,
        max_position_embeddings=1024, dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()

    new = int(os.environ.get("BENCH_HEALTH_NEW", 16))
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (p,))
               for p in (32, 48, 64, 40, 56, 24, 64, 32)]
    base = dict(num_slots=4, block_size=16, max_model_len=256,
                max_new_tokens=new)

    # 1) healthy arm: generous budgets (first-wave TTFT includes the
    # compile on a cold engine) — the pin is ZERO alerts ever fired
    eng = ServingEngine(model, ServingConfig(
        **base, health_slo_ttft_ms=600000.0,
        health_slo_itl_ms=600000.0))
    for _ in range(2):      # second wave runs post-compile steady state
        eng.serve([p.copy() for p in prompts], max_new_tokens=new)
    st_ok = eng.stats()
    h_ok = eng.health()
    eng.shutdown()
    assert st_ok["alerts_fired_total"] == 0, (
        "healthy arm fired alerts", h_ok)
    assert st_ok["health_score"] == 1.0, st_ok["health_score"]

    # 2) overload arm: an SLO no backend can meet + short burn windows
    # so the page trips inside the run; incidents land in a scratch dir
    tmp = tempfile.mkdtemp(prefix="paddle_tpu_bench_incident_")
    prev = os.environ.get("PADDLE_TPU_INCIDENT_DIR")
    os.environ["PADDLE_TPU_INCIDENT_DIR"] = tmp
    try:
        eng2 = ServingEngine(model, ServingConfig(
            **base, health_slo_ttft_ms=1e-3, health_slo_itl_ms=1e-3,
            health_burn_fast_s=0.5, health_burn_slow_s=2.0,
            health_burn_min_requests=2))
        for _ in range(2):
            eng2.serve([p.copy() for p in prompts],
                       max_new_tokens=new)
        h = eng2.health()
        st_bad = eng2.stats()
        eng2.shutdown()
    finally:
        if prev is None:
            os.environ.pop("PADDLE_TPU_INCIDENT_DIR", None)
        else:
            os.environ["PADDLE_TPU_INCIDENT_DIR"] = prev
    fired = sorted({e["alert"] for e in h["journal"]
                    if e["state"] == "firing"})
    assert "slo_fast_burn" in fired, fired
    assert st_bad["incidents_captured"] >= 1, st_bad
    bundles = sorted(d for d in os.listdir(tmp)
                     if not d.startswith(".tmp-"))
    assert bundles, "overload arm captured no incident bundle"
    bdir = os.path.join(tmp, bundles[0])
    with open(os.path.join(bdir, "manifest.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(bdir, "stats.json")) as f:
        bstats = json.load(f)
    assert manifest["alert"] in fired, manifest
    assert "health_score" in bstats and "roofline" in bstats

    out = {
        "healthy": {
            "health_score": st_ok["health_score"],
            "alerts_fired_total": st_ok["alerts_fired_total"],
            "nonfinite_logits_ticks":
                st_ok["nonfinite_logits_ticks"],
        },
        "overload": {
            "alerts_fired_total": st_bad["alerts_fired_total"],
            "alerts_fired": fired,
            "burn_rate_fast": round(h["burn_rate"]["fast"], 3),
            "incidents_captured": st_bad["incidents_captured"],
            "incident_bundle": bundles[0],
            "bundle_files": sorted(os.listdir(bdir)),
        },
        # trajectory keys: alerts fired under overload (sensitivity)
        # and whether the bundle round-tripped (capture path health)
        "health_alerts_fired": st_bad["alerts_fired_total"],
        "health_incident_captured": bool(bundles),
        "cpu_proxy": jax.default_backend() != "tpu",
    }
    del model, eng, eng2
    gc.collect()
    return out


def _preempt_bench():
    """FIFO vs preemptive scheduling under mixed-priority overload
    (the ISSUE-14 bar): the same closed-loop workload — a few LONG
    low-priority requests arriving first, a majority of SHORT
    high-priority requests behind them, concurrency above the slot
    count so the queue never drains — served by two engines differing
    ONLY in ``enable_preemption``. The FIFO arm head-of-line-blocks
    the shorts behind the longs' prefills; the preemptive arm admits
    by priority and spills low-priority victims to the host-DRAM KV
    tier when the high class needs their slots. Reported: goodput at
    a fixed SLO (calibrated 4x/3x off an UNLOADED single-request
    probe, so 'good' means 'barely queued'), high-priority TTFT p99
    per arm, preemption/spill/restore counts and the measured
    recompute-vs-swap cost-model rates. On CPU absolute latencies are
    a structure proxy (``cpu_proxy``); the FIFO-vs-preemptive SHAPE
    (who waits behind whom) is backend-independent."""
    import gc
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.inference.loadgen import SLO, run_load

    # default shape is the CPU-proxy sweet spot: small enough that
    # tick time does not drown the scheduling signal (the thing under
    # test is who waits behind whom, not FLOPs) — raise via env on
    # real chips
    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_PREEMPT_VOCAB", 32000)),
        hidden_size=int(os.environ.get("BENCH_PREEMPT_HIDDEN", 512)),
        intermediate_size=int(os.environ.get("BENCH_PREEMPT_FFN",
                                             1408)),
        num_hidden_layers=int(os.environ.get("BENCH_PREEMPT_LAYERS",
                                             2)),
        num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=1024, dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()

    # class mix mirrors real tenant traffic: latency-sensitive shorts
    # are the MAJORITY (the goodput denominator), a few long batch
    # jobs are the head-of-line blockers whose preemption-stalled
    # TPOT is the accepted price
    slots = int(os.environ.get("BENCH_PREEMPT_SLOTS", 4))
    n_lo = int(os.environ.get("BENCH_PREEMPT_LO", 4))
    n_hi = int(os.environ.get("BENCH_PREEMPT_HI", 12))
    new = int(os.environ.get("BENCH_PREEMPT_NEW", 8))
    lo_len = int(os.environ.get("BENCH_PREEMPT_LO_LEN", 256))
    hi_len = int(os.environ.get("BENCH_PREEMPT_HI_LEN", 24))
    rng = np.random.RandomState(0)
    # longs FIRST (one per slot — the FIFO arm's head-of-line wall) on
    # an open-loop arrival schedule: they are admitted and RUNNING by
    # the time the shorts arrive, so the FIFO arm blocks the shorts
    # behind them while the preemptive arm must actually preempt to
    # serve them. Alternating long lengths put some longs in DECODE
    # (preemption spills their live blocks to the host tier and
    # swap/recompute-resumes them) and some mid-PREFILL (preempted to
    # a fresh requeue over their published blocks) — both victim
    # classes measured in one window.
    lo_lens = [lo_len if j % 2 == 0 else 2 * hi_len
               for j in range(n_lo)]
    prompts = [rng.randint(1, cfg.vocab_size, (n,))
               for n in lo_lens] + \
              [rng.randint(1, cfg.vocab_size, (hi_len,))
               for _ in range(n_hi)]
    prios = [0] * n_lo + [2] * n_hi

    # small per-tick prefill budget: a long prompt spreads over many
    # SHORT ticks instead of a few 0.5s ones, so admission decisions
    # (the thing under test) happen at a useful granularity and a
    # bypassing short's first token isn't gated on a monster launch
    pf_rows = int(os.environ.get("BENCH_PREEMPT_PF_ROWS", 64))

    def build(preempt):
        return ServingEngine(model, ServingConfig(
            num_slots=slots, block_size=32, max_model_len=512,
            max_new_tokens=new, ragged_prefill_rows=pf_rows,
            enable_preemption=preempt))

    # SLO calibration: one UNLOADED short request per class of
    # interest — the budget a request that never queued would meet
    probe_eng = build(False)
    probe = run_load(probe_eng,
                     [rng.randint(1, cfg.vocab_size, (hi_len,))
                      for _ in range(3)],
                     mode="closed", concurrency=1,
                     max_new_tokens=new)
    probe_eng.shutdown()
    # TTFT budget = 4x the unloaded first token plus ONE decode wave
    # (new x unloaded per-token): a short request may wait out one
    # batch of peers and still be "good", but waiting behind a LONG
    # prefill (the FIFO failure mode) blows it — the budget that
    # separates the arms by policy rather than by raw speed
    slo = SLO(
        ttft_ms=float(os.environ.get(
            "BENCH_PREEMPT_SLO_TTFT_MS",
            4.0 * max(probe["ttft_p50_ms"], 1.0)
            + new * max(probe["tpot_p50_ms"], 1.0))),
        itl_ms=float(os.environ.get(
            "BENCH_PREEMPT_SLO_ITL_MS",
            3.0 * max(probe["tpot_p50_ms"], 1.0))))

    # offered load: a burst WELL past the knee — 4x the slot count
    # times the single-stream short-request rate, so the whole mixed
    # window arrives while the longs are still mid-service (the
    # overload regime where scheduling policy decides who eats the
    # queueing delay; under-offered loads make both arms trivially
    # meet SLO and measure nothing)
    qps = float(os.environ.get("BENCH_PREEMPT_QPS", 0) or 0) or \
        4.0 * slots * max(probe["achieved_qps"], 0.2)
    arms = {}
    for name, preempt in (("fifo", False), ("preemptive", True)):
        eng = build(preempt)
        # warm the executables outside the timed window
        eng.serve([rng.randint(1, cfg.vocab_size, (hi_len,))],
                  max_new_tokens=4)
        rep = run_load(eng, [p.copy() for p in prompts],
                       qps=round(qps, 3), mode="open",
                       arrival="uniform", max_new_tokens=new,
                       slo=slo, priorities=list(prios))
        st = eng.stats()
        rep["engine"] = {k: st[k] for k in (
            "preemptions", "kv_blocks_spilled", "kv_blocks_restored",
            "preempt_swap_resumes", "preempt_recompute_resumes",
            "host_tier_bytes", "prefill_rows_per_s_est",
            "host_xfer_bytes_per_s_est", "preemption_enabled")}
        arms[name] = rep
        eng.shutdown()
        del eng
        gc.collect()

    fifo, pre = arms["fifo"], arms["preemptive"]
    hi_key = "2"
    out = {
        "workload": {"n_lo": n_lo, "n_hi": n_hi, "lo_len": lo_len,
                     "hi_len": hi_len, "max_new": new,
                     "num_slots": slots,
                     "offered_qps": round(qps, 3)},
        "slo": {"ttft_ms": round(slo.ttft_ms, 3),
                "itl_ms": round(slo.itl_ms, 3)},
        "unloaded_probe": probe,
        "fifo": fifo,
        "preemptive": pre,
        "goodput_fifo": fifo["goodput"],
        "goodput_preemptive": pre["goodput"],
        "goodput_delta": round(pre["goodput"] - fifo["goodput"], 4),
        "hi_ttft_p99_fifo_ms":
            fifo.get("by_priority", {}).get(hi_key,
                                            fifo)["ttft_p99_ms"],
        "hi_ttft_p99_preempt_ms":
            pre.get("by_priority", {}).get(hi_key,
                                           pre)["ttft_p99_ms"],
        "kv_blocks_spilled": pre["engine"]["kv_blocks_spilled"],
        "preemptions": pre["engine"]["preemptions"],
        "cpu_proxy": jax.default_backend() != "tpu",
    }
    del model
    gc.collect()
    return out


def _fusion_bench():
    """Decode-tick fusion A/B (the ISSUE-13 bar): fused vs unfused
    serving engines at the serving-bench shape. Two axes:

    - **throughput/latency** — aggregate tok/s + per-step launch
      p50/p99, fused ON vs OFF. On CPU the fused kernels take their
      bitwise-unfused XLA fallback, so both arms compile the SAME
      graph and the measured ratio is ~1.0 — flagged ``cpu_proxy``;
      the HBM win (per-layer activations staying in VMEM across the
      norm->QKV / attention->O-proj / MLP boundaries) is the real-TPU
      bar.
    - **kernel census** — the headline "kernel count per decode layer
      down" metric, measured: a reduced kernel-eligible shape compiled
      with the Pallas kernels ROUTED INTO the trace
      (``PADDLE_TPU_PAGED_KERNEL=interpret`` +
      ``PADDLE_TPU_FUSED_DECODE=interpret``), censused at the jaxpr
      launch-proxy level where a pallas_call is ONE launch whatever
      backend executes it. ``kernels_per_tick_ratio`` is
      fused/unfused; ``per_layer_ratio`` differences two depths so
      the head/sampling overhead cancels (measured 9 vs 14 launch
      roots per decoder layer = 0.64x; the optimized-HLO count on
      real TPU also absorbs the unfused arm's elementwise fusion
      kernels — rope, residual adds, swiglu, norm scales — which is
      the <= 0.6x bar).
    """
    import gc
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine

    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_FUSION_VOCAB", 32000)),
        hidden_size=int(os.environ.get("BENCH_FUSION_HIDDEN", 2048)),
        intermediate_size=int(os.environ.get("BENCH_FUSION_FFN",
                                             5632)),
        num_hidden_layers=int(os.environ.get("BENCH_FUSION_LAYERS",
                                             8)),
        num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=1024, dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()

    slots = int(os.environ.get("BENCH_FUSION_SLOTS", 8))
    new = int(os.environ.get("BENCH_FUSION_NEW", 64))
    n_req = int(os.environ.get("BENCH_FUSION_REQS", 16))
    plens = [32, 64, 96, 160, 128, 48]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size,
                           (plens[i % len(plens)],))
               for i in range(n_req)]

    def run_arm(fused):
        os.environ["PADDLE_TPU_FUSED_DECODE"] = "1" if fused else "0"
        try:
            eng = ServingEngine(model, ServingConfig(
                num_slots=slots, block_size=32, max_model_len=512,
                max_new_tokens=new))
            eng.serve([rng.randint(1, cfg.vocab_size, (p,))
                       for p in plens], max_new_tokens=4)   # warmup
            tokens0 = eng.stats()["tokens_total"]
            for p in prompts:
                eng.submit(p, new)
            step_ms = []
            t0 = time.perf_counter()
            while eng.num_queued or eng.num_active:
                s0 = time.perf_counter()
                eng.step()
                step_ms.append(1000 * (time.perf_counter() - s0))
            wall = time.perf_counter() - t0
            st = eng.stats()
            eng.shutdown()
            lat = np.sort(np.asarray(step_ms))
            return {
                "fused": fused,
                "aggregate_tokens_per_sec":
                    round((st["tokens_total"] - tokens0) / wall, 1),
                "step_launch_p50_ms": round(float(
                    lat[len(lat) // 2]), 2),
                "step_launch_p99_ms": round(float(
                    lat[min(len(lat) - 1, int(len(lat) * 0.99))]), 2),
                "kernels_per_tick": st["kernels_per_tick"],
                "kernel_launch_proxy_per_tick":
                    st["kernel_launch_proxy_per_tick"],
                "recompiles_measured": st["decode_compiles"] - 1,
            }
        finally:
            os.environ.pop("PADDLE_TPU_FUSED_DECODE", None)

    unfused = run_arm(False)
    gc.collect()
    fused = run_arm(True)
    gc.collect()

    # kernel-census arms: reduced kernel-ELIGIBLE shape, Pallas routed
    # into the trace so the census counts what TPU hardware launches
    def census_arm(mode, layers):
        os.environ["PADDLE_TPU_FUSED_DECODE"] = mode
        os.environ["PADDLE_TPU_PAGED_KERNEL"] = "interpret"
        try:
            paddle.seed(0)
            small = LlamaForCausalLM(LlamaConfig.tiny(
                vocab=1024, hidden=256, layers=layers, heads=4,
                kv_heads=2, ffn=512))
            small.eval()
            eng = ServingEngine(small, ServingConfig(
                num_slots=2, block_size=32, max_model_len=128))
            eng.serve([rng.randint(1, 1024, (9,))], max_new_tokens=2)
            st = eng.stats()
            eng.shutdown()
            return (st["kernel_launch_proxy_per_tick"],
                    st["kernels_per_tick"])
        finally:
            os.environ.pop("PADDLE_TPU_FUSED_DECODE", None)
            os.environ.pop("PADDLE_TPU_PAGED_KERNEL", None)

    off2, _ = census_arm("0", 2)
    off4, off_hlo = census_arm("0", 4)
    on2, _ = census_arm("interpret", 2)
    on4, on_hlo = census_arm("interpret", 4)
    per_layer_off = (off4 - off2) / 2.0
    per_layer_on = (on4 - on2) / 2.0
    return {
        "unfused": unfused,
        "fused": fused,
        "speedup_tokens_per_sec": round(
            fused["aggregate_tokens_per_sec"]
            / max(unfused["aggregate_tokens_per_sec"], 1e-9), 3),
        "census": {
            "launch_proxy_unfused": off4,
            "launch_proxy_fused": on4,
            "hlo_kernels_unfused": off_hlo,
            "hlo_kernels_fused": on_hlo,
            "launch_proxy_per_layer_unfused": per_layer_off,
            "launch_proxy_per_layer_fused": per_layer_on,
            "per_layer_ratio": round(
                per_layer_on / max(per_layer_off, 1e-9), 3),
        },
        "kernels_per_tick_ratio": round(on4 / max(off4, 1e-9), 3),
        # one CPU device: the fused arm runs the bitwise-unfused XLA
        # fallback, so tok/s parity is expected here — the VMEM/HBM
        # win needs real hardware; the census ratio above IS the
        # kernelized-graph measurement (<= 0.6x/layer is the TPU-HLO
        # bar, the jaxpr launch proxy is its conservative floor)
        "cpu_proxy": jax.default_backend() != "tpu",
    }


def _cluster_bench():
    """Engine replication + disaggregated prefill (the ISSUE-12 bar):
    the goodput-bench model behind ``EngineCluster``. Three axes:

    - **1 vs 2 decode replicas** on the mixed-length workload —
      aggregate tok/s and ``cluster_speedup``. The >= 1.5x bar is the
      real-hardware expectation (replicas own disjoint chips); on one
      CPU both replicas time-share the same device so the measured
      ratio is structure-only, flagged ``cpu_proxy`` (the TP-bench
      precedent).
    - **colocated vs disaggregated TTFT p99** under concurrent
      LONG-PREFILL load (closed loop at full concurrency, long
      prompts): the disaggregated decode replica's ticks carry no
      prefill rows and the prefill engine's chunks never wait behind
      decode batches — the isolation is measurable even on CPU.
    - **router affinity** on the multi-session conversation workload
      (``loadgen.conversation_workload``): ``affinity_hit_rate`` from
      the cluster's own router counters.
    """
    import gc
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig
    from paddle_tpu.inference.cluster import (ClusterConfig,
                                              EngineCluster)
    from paddle_tpu.inference.loadgen import (SLO, run_load,
                                              conversation_workload)

    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_CLUSTER_VOCAB", 32000)),
        hidden_size=int(os.environ.get("BENCH_CLUSTER_HIDDEN", 2048)),
        intermediate_size=int(os.environ.get("BENCH_CLUSTER_FFN",
                                             5632)),
        num_hidden_layers=int(os.environ.get("BENCH_CLUSTER_LAYERS",
                                             8)),
        num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=1024, dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()

    slots = int(os.environ.get("BENCH_CLUSTER_SLOTS", 4))
    new = int(os.environ.get("BENCH_CLUSTER_NEW", 32))
    n_req = int(os.environ.get("BENCH_CLUSTER_REQS", 16))
    chunk = int(os.environ.get("BENCH_CLUSTER_CHUNK", 128))
    plens = [32, 64, 96, 160, 128, 48]
    long_plens = [256, 320, 384, 288]       # the TTFT-isolation regime
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size,
                           (plens[i % len(plens)],))
               for i in range(n_req)]
    long_prompts = [rng.randint(1, cfg.vocab_size,
                                (long_plens[i % len(long_plens)],))
                    for i in range(n_req)]
    scfg = dict(num_slots=slots, block_size=32, max_model_len=512,
                max_new_tokens=new, prefill_chunk=chunk)

    def mk(replicas, prefill=0):
        cl = EngineCluster(
            model, ClusterConfig(num_replicas=replicas,
                                 prefill_replicas=prefill),
            ServingConfig(**scfg))
        # warm every replica: submitted upfront, the depth tiebreak
        # spreads cold prompts across them, compiling each
        cl.serve([rng.randint(1, cfg.vocab_size, (p,))
                  for p in plens * max(replicas, prefill)],
                 max_new_tokens=4)
        return cl

    def pump(cl, workload):
        """Concurrent-admission throughput pump (the serving-bench
        pattern, cluster-wide): tok/s from the cluster's own token
        counter over the drain wall-clock."""
        queue = [p.copy() for p in workload]
        tokens0 = cl.stats()["tokens_total"]
        execs0 = cl.stats()["executables_compiled"]
        t0 = time.perf_counter()
        while queue or cl.num_queued or cl.num_active:
            while queue and cl.num_queued < 2 * len(cl.engines):
                cl.submit(queue.pop(0), new)
            cl.step()
        wall = time.perf_counter() - t0
        st = cl.stats()
        return {
            "aggregate_tokens_per_sec":
                round((st["tokens_total"] - tokens0) / wall, 1),
            "recompiles_measured":
                st["executables_compiled"] - execs0,
            "requests": len(workload),
        }

    # -- axis 1: 1 vs 2 decode replicas ------------------------------
    cl1 = mk(1)
    one = pump(cl1, prompts)
    cl1.shutdown()
    cl2 = mk(2)
    two = pump(cl2, prompts)
    cl2.shutdown()

    # -- axis 2: colocated vs disaggregated TTFT under long prefills -
    # equal engine count (2 each) so the split is the only variable:
    # two colocated replicas vs one decode + one dedicated prefill
    slo = SLO(ttft_ms=1e9, itl_ms=1e9)      # measuring, not judging
    ttft = {}
    for name, (reps, pre) in (("colocated", (2, 0)),
                              ("disaggregated", (1, 1))):
        cl = mk(reps, pre)
        rep = run_load(cl, [p.copy() for p in long_prompts],
                       mode="closed", max_new_tokens=new, slo=slo)
        st = cl.stats()
        cl.shutdown()
        ttft[name] = {
            "ttft_p50_ms": rep["ttft_p50_ms"],
            "ttft_p99_ms": rep["ttft_p99_ms"],
            "itl_p99_ms": rep["itl_p99_ms"],
            "tokens_per_sec": rep["tokens_per_sec"],
            "kv_blocks_transferred": st["kv_blocks_transferred"],
        }

    # -- axis 3: conversation workload -> router affinity ------------
    conv, _sids = conversation_workload(
        4, 3, vocab=cfg.vocab_size, prefix_len=64, turn_len=32,
        seed=1)
    cla = mk(2)
    run_load(cla, conv, mode="closed", max_new_tokens=8, slo=slo)
    sta = cla.stats()
    cla.shutdown()

    out = {
        "one_replica": one,
        "two_replicas": two,
        "speedup_tokens_per_sec": round(
            two["aggregate_tokens_per_sec"]
            / max(one["aggregate_tokens_per_sec"], 1e-9), 3),
        "colocated": ttft["colocated"],
        "disaggregated": ttft["disaggregated"],
        "disagg_ttft_p99_reduction": round(
            ttft["colocated"]["ttft_p99_ms"]
            / max(ttft["disaggregated"]["ttft_p99_ms"], 1e-9), 3),
        "conversation_affinity_hit_rate":
            sta["router_affinity_hit_rate"],
        "conversation_affinity_hits": sta["router_affinity_hits"],
        "conversation_prefix_tokens_reused":
            sta["prefix_tokens_reused"],
        "num_slots": slots, "max_new_tokens": new,
        "requests": n_req, "workload_prompt_lens": plens,
        "long_prefill_lens": long_plens,
        "model_shape": {
            "hidden": cfg.hidden_size, "layers": cfg.num_hidden_layers,
            "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size},
        # one CPU device time-shares all replicas: the speedup arm is
        # structure-only off-TPU (the >= 1.5x bar is the real-chips
        # expectation); the TTFT-isolation and affinity axes are
        # backend-independent
        "cpu_proxy": jax.default_backend() != "tpu",
    }
    del model
    gc.collect()
    return out


def _autoscale_bench():
    """Elastic fleet autoscaling (the ISSUE-19 bar): the SAME
    sine-shaped open-loop workload (``loadgen.profile_arrivals`` —
    load that actually rises and falls, which a constant rate never
    does) through two fleets:

    - **fixed-2**: ``ClusterConfig(num_replicas=2)`` provisioned for
      the peak all the time — the capacity a fixed fleet burns through
      the trough;
    - **autoscaled 1..3**: the same two replicas with an
      ``AutoscaleConfig(min_replicas=1, max_replicas=3)`` armed; the
      policy drains down to one through each trough — LIVE-MIGRATING
      every resident session — and revives the retired replica into
      the next crest (revival reuses its compiled executables, so the
      cycle compiles nothing in steady state).

    The headline is **goodput per replica-tick** (SLO-good requests
    divided by the capacity consumed — ``stats()['replica_ticks']``
    counts one unit per live replica per cluster tick): elasticity
    wins when it serves the same SLO traffic on fewer replica-ticks.
    ``migration_p99_ms`` (export -> re-seated, the cluster's P²
    digest) prices the drain. One CPU time-shares all replicas, so
    absolute tok/s is structure-only (``cpu_proxy``) — the
    ticks-saved ratio is the backend-independent signal."""
    import gc
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig
    from paddle_tpu.inference.autoscale import AutoscaleConfig
    from paddle_tpu.inference.cluster import (ClusterConfig,
                                              EngineCluster)
    from paddle_tpu.inference.loadgen import SLO, run_load

    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_AS_VOCAB", 8000)),
        hidden_size=int(os.environ.get("BENCH_AS_HIDDEN", 768)),
        intermediate_size=int(os.environ.get("BENCH_AS_FFN", 2048)),
        num_hidden_layers=int(os.environ.get("BENCH_AS_LAYERS", 4)),
        num_attention_heads=12, num_key_value_heads=6,
        max_position_embeddings=512, dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()

    slots = int(os.environ.get("BENCH_AS_SLOTS", 4))
    new = int(os.environ.get("BENCH_AS_NEW", 24))
    n_req = int(os.environ.get("BENCH_AS_REQS", 48))
    qps = float(os.environ.get("BENCH_AS_QPS", 6.0))
    period = float(os.environ.get("BENCH_AS_PERIOD_S", 4.0))
    profile = {"kind": "sine", "period_s": period, "depth": 0.9}
    rng = np.random.RandomState(0)
    plens = [24, 48, 96, 32, 64, 40]
    prompts = [rng.randint(1, cfg.vocab_size,
                           (plens[i % len(plens)],))
               for i in range(n_req)]
    scfg = dict(num_slots=slots, block_size=16, max_model_len=256,
                max_new_tokens=new)
    slo = SLO(ttft_ms=float(os.environ.get("BENCH_AS_TTFT_MS", 4000)),
              itl_ms=float(os.environ.get("BENCH_AS_ITL_MS", 2000)))

    def mk(replicas, autoscale=None):
        cl = EngineCluster(
            model,
            ClusterConfig(num_replicas=replicas, autoscale=autoscale),
            ServingConfig(**scfg))
        # warm the STARTING replicas; an autoscale-spawned replica
        # warms itself off the hot path (that cost is part of what
        # the elastic arm is charged for)
        cl.serve([rng.randint(1, cfg.vocab_size, (p,))
                  for p in plens[:2 * replicas]], max_new_tokens=4)
        return cl

    def arm(cl):
        t0 = cl.stats()["replica_ticks"]
        rep = run_load(cl, [p.copy() for p in prompts], qps=qps,
                       mode="open", max_new_tokens=new, slo=slo,
                       qps_profile=profile, seed=3)
        st = cl.stats()
        cl.shutdown()
        ticks = st["replica_ticks"] - t0
        good = rep["goodput"] * rep["requests"]
        return {
            "goodput": rep["goodput"],
            "completed": rep["completed"],
            "replica_ticks": ticks,
            "good_per_kilo_replica_tick":
                round(1000.0 * good / max(ticks, 1), 4),
            "ttft_p99_ms": rep["ttft_p99_ms"],
            "itl_p99_ms": rep["itl_p99_ms"],
            "scale_ups": st["scale_ups"],
            "scale_downs": st["scale_downs"],
            "sessions_migrated": st["sessions_migrated"],
            "migration_ms": st["migration_ms"],
            "replicas_live_end": st["replicas_live"],
        }

    fixed = arm(mk(2))
    # knobs sized to the sine period and CPU tick rate: commit within
    # a fraction of a crest, but hold down long enough that one
    # compile-stall queue spike cannot ratchet the fleet to max (the
    # production default is minutes of cooldown; here ticks are ms)
    auto = arm(mk(2, AutoscaleConfig(
        min_replicas=1, max_replicas=3,
        up_queue_per_slot=1.0, up_occupancy=0.98,
        down_occupancy=0.45, down_queue_per_slot=0.05,
        hysteresis_ticks=3, cooldown_ticks=30)))

    # -- drain probe: the migration price, measured deterministically -
    # the policy arm may drain an already-empty replica (coldest-first
    # is WORKING when that happens), so the export->reseat latency is
    # priced on a forced mid-flight drain with residents on both sides
    clp = mk(2)
    for i in range(2 * slots):
        clp.submit(prompts[i % len(prompts)].copy(), new)
    for _ in range(4):
        clp.step()
    t0 = time.perf_counter()
    clp.scale_down()
    drain_wall_ms = round(1000.0 * (time.perf_counter() - t0), 3)
    clp.run()
    stp = clp.stats()
    clp.shutdown()
    probe = {
        "sessions_migrated": stp["sessions_migrated"],
        "migration_ms": stp["migration_ms"],
        "drain_wall_ms": drain_wall_ms,
    }

    out = {
        "fixed_2": fixed,
        "autoscaled_1_3": auto,
        "drain_probe": probe,
        "qps_profile": profile, "offered_qps": qps,
        "requests": n_req, "num_slots": slots,
        "max_new_tokens": new,
        # the acceptance headline: SLO-good work per unit of capacity
        # consumed — > 1.0 means elasticity beat peak provisioning
        "autoscale_goodput_delta": round(
            auto["good_per_kilo_replica_tick"]
            / max(fixed["good_per_kilo_replica_tick"], 1e-9), 4),
        "autoscale_replica_ticks_saved":
            fixed["replica_ticks"] - auto["replica_ticks"],
        # the policy arm's digest when its drains moved anyone, else
        # the forced-drain probe's — the reported price is always a
        # real export->reseat measurement
        "migration_p99_ms":
            auto["migration_ms"]["p99"]
            if auto["migration_ms"]["count"]
            else probe["migration_ms"]["p99"],
        "model_shape": {
            "hidden": cfg.hidden_size,
            "layers": cfg.num_hidden_layers,
            "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size},
        # one CPU device time-shares every replica AND the control
        # loop: tick counts and the goodput ratio are structure-only
        # off-TPU; on real chips replica-ticks are chip-seconds
        "cpu_proxy": jax.default_backend() != "tpu",
    }
    del model
    gc.collect()
    return out


def _spec_serving_bench():
    """Speculative serving throughput (the ISSUE-4 bar): a mixed-length
    REPETITIVE-text workload (tiled phrases — the prompt-lookup regime:
    code, quotes, retrieval) through ``ServingEngine`` at gamma in
    {2, 4}, n-gram and draft-model drafters, against the PR-3
    single-token serving baseline on the SAME workload and model.
    Reports aggregate tok/s, mean accepted length (emitted tokens per
    verify window — the >1.0 bar), acceptance rate, and
    ``recompiles_measured`` (must be 0: one verify executable serves
    every accept/reject mix)."""
    import gc
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine

    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_SPEC_VOCAB", 32000)),
        hidden_size=int(os.environ.get("BENCH_SPEC_HIDDEN", 2048)),
        intermediate_size=int(os.environ.get("BENCH_SPEC_FFN", 5632)),
        num_hidden_layers=int(os.environ.get("BENCH_SPEC_LAYERS", 8)),
        num_attention_heads=16,
        num_key_value_heads=8, max_position_embeddings=1024,
        dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()
    # 2-layer draft at a quarter the width — the "small compatible
    # model drafting for a larger one" mode (same vocab)
    dcfg = LlamaConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size // 4,
        intermediate_size=cfg.intermediate_size // 4,
        num_hidden_layers=2, num_attention_heads=8,
        num_key_value_heads=4, max_position_embeddings=1024,
        dtype="bfloat16")
    paddle.seed(1)
    draft = LlamaForCausalLM(dcfg)
    draft.to(dtype="bfloat16")
    draft.eval()

    slots = int(os.environ.get("BENCH_SPEC_SLOTS", 8))
    new = int(os.environ.get("BENCH_SPEC_NEW", 64))
    n_req = int(os.environ.get("BENCH_SPEC_REQS", 16))
    plens = [32, 64, 96, 160, 224, 128, 48, 192]
    rng = np.random.RandomState(0)

    def rep_prompt(n):
        phrase = rng.randint(1, cfg.vocab_size, (8,))
        return np.tile(phrase, n // 8)

    prompts = [rep_prompt(plens[i % len(plens)]) for i in range(n_req)]

    def run_engine(gamma, drafter="ngram", dm=None):
        eng = ServingEngine(model, ServingConfig(
            num_slots=slots, block_size=32, max_model_len=512,
            max_new_tokens=new, min_prefill_bucket=32,
            num_speculative_tokens=gamma, drafter=drafter),
            draft_model=dm)
        # warmup: compile the verify/decode step + prefill buckets
        eng.serve([rep_prompt(p) for p in plens], max_new_tokens=4)
        compiles0 = eng.stats()["decode_compiles"]
        tokens0 = eng.stats()["tokens_total"]
        steps0 = eng.stats()["decode_steps"]
        for p in prompts:
            eng.submit(p, new)
        t0 = time.perf_counter()
        while eng.num_queued or eng.num_active:
            eng.step()
        wall = time.perf_counter() - t0
        st = eng.stats()
        out = {
            "aggregate_tokens_per_sec":
                round((st["tokens_total"] - tokens0) / wall, 1),
            "decode_steps": st["decode_steps"] - steps0,
            "recompiles_measured": st["decode_compiles"] - compiles0,
        }
        if gamma:
            out["mean_accepted_len"] = round(
                st["spec_mean_accepted_len"], 3)
            out["acceptance_rate"] = round(
                st["spec_acceptance_rate"], 4)
        return out

    base = run_engine(0)
    results = {
        "baseline_single_token": base,
        "num_slots": slots, "max_new_tokens": new,
        "requests": n_req, "workload_prompt_lens": plens,
    }
    for gamma in (2, 4):
        for name, drafter, dm in ((f"ngram_g{gamma}", "ngram", None),
                                  (f"draft_model_g{gamma}", "model",
                                   draft)):
            r = run_engine(gamma, drafter, dm)
            r["speedup_vs_single_token"] = round(
                r["aggregate_tokens_per_sec"]
                / max(base["aggregate_tokens_per_sec"], 1e-9), 3)
            results[name] = r
    del model, draft
    gc.collect()
    return results


def _spec_tree_bench():
    """Tree vs linear speculation at the SAME verify node budget (the
    ISSUE-16 bar). A tiny Llama is TRAINED (Adam, fresh batches each
    step so it learns the transition statistics rather than memorizing
    sequences) on a first-order Markov corpus where every token has a
    0.6-majority and 0.4-minority successor. Under sampled verify the
    target really does take the minority branch 40% of the time, so a
    linear gamma=4 chain stalls at depth 1 whenever its single guess
    takes the wrong fork — while a tree spending one of the same 5
    nodes on the sibling fork covers BOTH successors and keeps the
    window alive. Reports mean accepted len per verify window and
    aggregate tok/s for both shapes; accepted-len is the structural
    claim (``cpu_proxy`` — wall-clock tok/s off-TPU only weakly
    rewards deeper acceptance because the tick is latency- not
    FLOP-bound on CPU)."""
    import gc
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine

    vocab = 12
    crng = np.random.RandomState(0)
    succ1 = crng.permutation(vocab)
    succ2 = (succ1 + 1 + crng.randint(0, vocab - 1, vocab)) % vocab

    def sample_seq(n, r):
        t = r.randint(vocab)
        out = [t]
        for _ in range(n - 1):
            t = int(succ1[t]) if r.rand() < 0.6 else int(succ2[t])
            out.append(t)
        return np.array(out, np.int64)

    paddle.seed(11)
    np.random.seed(11)
    cfg = LlamaConfig(
        vocab_size=vocab, hidden_size=64, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=4,
        num_key_value_heads=2, max_position_embeddings=256)
    model = LlamaForCausalLM(cfg)
    opt = paddle.optimizer.Adam(5e-3, parameters=model.parameters())
    trng = np.random.RandomState(1)
    steps = int(os.environ.get("BENCH_SPEC_TREE_STEPS", 50))
    for _ in range(steps):
        b = np.stack([sample_seq(49, trng) for _ in range(16)])
        loss = model(paddle.to_tensor(b[:, :-1]),
                     labels=paddle.to_tensor(b[:, 1:]))
        opt.clear_grad()
        loss.backward()
        opt.step()
    model.eval()

    new = int(os.environ.get("BENCH_SPEC_TREE_NEW", 32))
    n_req = int(os.environ.get("BENCH_SPEC_TREE_REQS", 8))
    prompts = [sample_seq(48, np.random.RandomState(100 + i))
               for i in range(n_req)]

    def run_engine(spec_tree):
        eng = ServingEngine(model, ServingConfig(
            num_slots=4, block_size=16, max_model_len=128,
            max_new_tokens=new, num_speculative_tokens=4,
            spec_tree=spec_tree, spec_ngram_max=1,
            decode_strategy="sampling", temperature=1.0, seed=5))
        eng.serve(prompts[:2], max_new_tokens=4)   # warmup/compile
        st0 = eng.stats()
        for p in prompts:
            eng.submit(p, new)
        t0 = time.perf_counter()
        while eng.num_queued or eng.num_active:
            eng.step()
        wall = time.perf_counter() - t0
        st = eng.stats()
        return {
            "aggregate_tokens_per_sec":
                round((st["tokens_total"] - st0["tokens_total"])
                      / wall, 1),
            "mean_accepted_len": round(st["spec_mean_accepted_len"],
                                       3),
            "acceptance_rate": round(st["spec_acceptance_rate"], 4),
            "verify_node_budget": st["spec_tree_nodes"] or 5,
            "recompiles_measured":
                st["decode_compiles"] - st0["decode_compiles"],
        }

    linear = run_engine(None)
    # depth-3 spine + one sibling fork off the root: 5 verify nodes,
    # exactly the linear gamma=4 budget
    tree = run_engine((0, 0, 1, 3))
    out = {
        "train_steps": steps, "final_loss": round(float(loss), 4),
        "linear_g4": linear, "tree_g4": tree,
        "tree_topology": [0, 0, 1, 3],
        "accept_len_delta": round(tree["mean_accepted_len"]
                                  - linear["mean_accepted_len"], 3),
        "cpu_proxy": jax.default_backend() != "tpu",
    }
    del model
    gc.collect()
    return out


def _prefix_serving_bench():
    """Prefix-cached serving throughput (the ISSUE-5 bar): N requests
    sharing one long system prompt (distinct short suffixes — the
    multi-tenant chat / few-shot-header regime) through the content-
    addressed block cache + the ONE fixed-chunk prefill executable,
    against the cold-cache baseline (prefix caching off, same engine
    otherwise). Reports aggregate tok/s, time-to-first-token p50/p99
    (submit -> first streamed token, the latency prefix reuse
    actually buys), prefix hit rate, and ``recompiles_measured``
    (prefill + decode executables after warmup — must be 0: one chunk
    executable serves every prompt length)."""
    import gc
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine

    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_SERVE_PREFIX_VOCAB",
                                      32000)),
        hidden_size=int(os.environ.get("BENCH_SERVE_PREFIX_HIDDEN",
                                       2048)),
        intermediate_size=int(os.environ.get("BENCH_SERVE_PREFIX_FFN",
                                             5632)),
        num_hidden_layers=int(os.environ.get(
            "BENCH_SERVE_PREFIX_LAYERS", 8)),
        num_attention_heads=16,
        num_key_value_heads=8, max_position_embeddings=1024,
        dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()

    slots = int(os.environ.get("BENCH_SERVE_PREFIX_SLOTS", 8))
    new = int(os.environ.get("BENCH_SERVE_PREFIX_NEW", 32))
    n_req = int(os.environ.get("BENCH_SERVE_PREFIX_REQS", 16))
    plen = int(os.environ.get("BENCH_SERVE_PREFIX_LEN", 256))
    tail = int(os.environ.get("BENCH_SERVE_PREFIX_TAIL", 16))
    chunk = int(os.environ.get("BENCH_SERVE_PREFIX_CHUNK", 128))
    rng = np.random.RandomState(0)
    sysp = rng.randint(1, cfg.vocab_size, (plen,))
    prompts = [np.concatenate(
        [sysp, rng.randint(1, cfg.vocab_size, (tail,))])
        for _ in range(n_req)]

    def run_engine(enable_cache):
        first = {}
        eng = ServingEngine(model, ServingConfig(
            num_slots=slots, block_size=32, max_model_len=512,
            max_new_tokens=new, prefill_chunk=chunk,
            enable_prefix_cache=enable_cache),
            stream_callback=lambda rid, tok:
            first.setdefault(rid, time.perf_counter()))
        # warmup: compile the chunk + decode executables; in cached
        # mode this also seeds the shared prefix (retirement publishes
        # its blocks), which is exactly the steady state measured
        eng.serve([np.concatenate(
            [sysp, rng.randint(1, cfg.vocab_size, (tail,))])],
            max_new_tokens=4)
        st0 = eng.stats()
        compiles0 = st0["prefill_compiles"] + st0["decode_compiles"]
        tokens0 = st0["tokens_total"]
        first.clear()
        submit_t = {}
        for p in prompts:
            rid = eng.submit(p, new)
            submit_t[rid] = time.perf_counter()
        t0 = time.perf_counter()
        while eng.num_queued or eng.num_active:
            eng.step()
        wall = time.perf_counter() - t0
        st = eng.stats()
        ttft = np.sort(np.asarray(
            [1000.0 * (first[r] - submit_t[r]) for r in submit_t]))
        return {
            "aggregate_tokens_per_sec":
                round((st["tokens_total"] - tokens0) / wall, 1),
            "ttft_p50_ms": round(float(ttft[len(ttft) // 2]), 2),
            "ttft_p99_ms": round(float(
                ttft[min(len(ttft) - 1, int(len(ttft) * 0.99))]), 2),
            "prefix_hit_rate": round(st["prefix_hit_rate"], 4),
            "prefix_tokens_reused": st["prefix_tokens_reused"],
            "cow_copies": st["cow_copies"],
            "cache_evictions": st["cache_evictions"],
            "prefill_chunks": st["prefill_chunks"],
            "recompiles_measured":
                st["prefill_compiles"] + st["decode_compiles"]
                - compiles0,
        }

    cold = run_engine(False)
    warm = run_engine(True)
    out = {
        "cold_cache": cold,
        "prefix_cached": warm,
        "speedup_tokens_per_sec": round(
            warm["aggregate_tokens_per_sec"]
            / max(cold["aggregate_tokens_per_sec"], 1e-9), 3),
        "ttft_p50_reduction": round(
            cold["ttft_p50_ms"] / max(warm["ttft_p50_ms"], 1e-9), 3),
        "num_slots": slots, "requests": n_req,
        "shared_prefix_len": plen, "suffix_len": tail,
        "max_new_tokens": new, "prefill_chunk": chunk,
    }
    del model
    gc.collect()
    return out


def _tp_serving_bench_impl():
    """Tensor-parallel serving scaling (the ISSUE-6 bar): the SAME
    mixed-length workload through ``ServingEngine`` at tp in {1, 2, 4}
    — every executable sharded over the ``mp`` mesh axis, KV pool split
    on kv_heads, one explicit logits all_gather per step. Reports
    aggregate tok/s, p50/p99 step latency, ``recompiles_measured``
    (must stay 0 under TP), per-step collective payload bytes, and
    scaling efficiency vs tp=1. On a CPU host-device mesh the absolute
    ratios are a STRUCTURE proxy only (shared cores, software
    collectives — flagged ``cpu_mesh_proxy``); the >= 1.6x tp=2 bar is
    a real-multi-chip expectation, like the MULTICHIP axis table."""
    import gc
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine

    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_TP_VOCAB", 32000)),
        hidden_size=int(os.environ.get("BENCH_TP_HIDDEN", 1024)),
        intermediate_size=int(os.environ.get("BENCH_TP_FFN", 2816)),
        num_hidden_layers=int(os.environ.get("BENCH_TP_LAYERS", 4)),
        num_attention_heads=16,
        num_key_value_heads=8, max_position_embeddings=1024,
        dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()

    slots = int(os.environ.get("BENCH_TP_SLOTS", 8))
    new = int(os.environ.get("BENCH_TP_NEW", 64))
    n_req = int(os.environ.get("BENCH_TP_REQS", 16))
    plens = [32, 64, 96, 48, 128, 24]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size,
                           (plens[i % len(plens)],))
               for i in range(n_req)]
    n_dev = len(jax.devices())
    degrees = [t for t in (1, 2, 4)
               if t <= n_dev and cfg.num_key_value_heads % t == 0]

    def run_engine(tp):
        eng = ServingEngine(model, ServingConfig(
            num_slots=slots, block_size=32, max_model_len=512,
            max_new_tokens=new, tp_degree=tp))
        eng.serve([rng.randint(1, cfg.vocab_size, (p,))
                   for p in plens[:2]], max_new_tokens=4)
        compiles0 = eng.stats()["decode_compiles"]
        tokens0 = eng.stats()["tokens_total"]
        for p in prompts:
            eng.submit(p, new)
        step_ms = []
        t0 = time.perf_counter()
        while eng.num_queued or eng.num_active:
            s0 = time.perf_counter()
            eng.step()
            step_ms.append(1000 * (time.perf_counter() - s0))
        wall = time.perf_counter() - t0
        st = eng.stats()
        lat = np.sort(np.asarray(step_ms))
        out = {
            "aggregate_tokens_per_sec":
                round((st["tokens_total"] - tokens0) / wall, 1),
            "p50_token_latency_ms": round(float(
                lat[len(lat) // 2]), 2),
            "p99_token_latency_ms": round(float(
                lat[min(len(lat) - 1, int(len(lat) * 0.99))]), 2),
            "recompiles_measured":
                st["decode_compiles"] - compiles0,
            "tp_degree": st["tp_degree"],
        }
        if tp > 1:
            out["collective_bytes_per_step"] = \
                st["tp_collective_bytes_per_step"]
            out["pool_bytes_per_shard"] = st["tp_pool_bytes_per_shard"]
        eng.shutdown()
        return out

    out = {"devices": n_dev,
           "cpu_mesh_proxy": jax.default_backend() == "cpu",
           "requests": n_req, "num_slots": slots,
           "max_new_tokens": new}
    base = None
    for tp in degrees:
        r = run_engine(tp)
        if tp == 1:
            base = r["aggregate_tokens_per_sec"]
        else:
            r["speedup_vs_tp1"] = round(
                r["aggregate_tokens_per_sec"] / max(base, 1e-9), 3)
            r["scaling_efficiency"] = round(
                r["speedup_vs_tp1"] / tp, 3)
        out[f"tp{tp}"] = r
    del model
    gc.collect()
    return out


def _tp_serving_bench():
    """The TP serving bench needs >= 4 devices of the backend being
    measured; with fewer it says so — it never reruns itself on a CPU
    mesh and files that under ``serving_tp``."""
    import jax
    n = len(jax.devices())
    if n < 4:
        return {"skipped": "needs 4 devices", "devices": n}
    return _tp_serving_bench_impl()


def _ragged_serving_bench():
    """Ragged mixed-batch serving (the ISSUE-7 bar): a mixed-length
    workload with CONCURRENT admissions — requests keep arriving while
    earlier ones decode, the regime where the legacy path interleaves
    chunk executables between decode launches — through the ONE ragged
    executable vs the per-width zoo (``PADDLE_TPU_RAGGED_BATCH=0``,
    interleaved prefill). Reports aggregate tok/s, per-step host
    launch ms (p50/p99 of ``eng.step()`` wall time — every launch +
    dispatch round-trip of a tick), ``executables_compiled`` and
    ``recompiles_measured`` (must be 0 after warmup on BOTH paths),
    plus a speculative (gamma=2 n-gram) pairing on repetitive text."""
    import gc
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine

    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_RAGGED_VOCAB", 32000)),
        hidden_size=int(os.environ.get("BENCH_RAGGED_HIDDEN", 2048)),
        intermediate_size=int(os.environ.get("BENCH_RAGGED_FFN", 5632)),
        num_hidden_layers=int(os.environ.get("BENCH_RAGGED_LAYERS", 8)),
        num_attention_heads=16,
        num_key_value_heads=8, max_position_embeddings=1024,
        dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()

    slots = int(os.environ.get("BENCH_RAGGED_SLOTS", 8))
    new = int(os.environ.get("BENCH_RAGGED_NEW", 48))
    n_req = int(os.environ.get("BENCH_RAGGED_REQS", 24))
    chunk = int(os.environ.get("BENCH_RAGGED_CHUNK", 64))
    plens = [32, 64, 96, 160, 224, 128, 48, 192]
    rng = np.random.RandomState(0)

    def rep_prompt(n):
        phrase = rng.randint(1, cfg.vocab_size, (8,))
        return np.tile(phrase, n // 8)

    # prompts built ONCE per workload so ragged and legacy (and the
    # spec pairing) are measured on IDENTICAL requests — n-gram
    # acceptance depends on prompt content, so a fresh draw per engine
    # would conflate path difference with workload difference
    workloads = {}
    for rep in (False, True):
        mk = rep_prompt if rep else \
            (lambda n: rng.randint(1, cfg.vocab_size, (n,)))
        workloads[rep] = ([mk(plens[i % len(plens)])
                           for i in range(n_req)],
                          [mk(p) for p in plens])       # + warmup set

    def run_engine(ragged, gamma=0, repetitive=False):
        os.environ["PADDLE_TPU_RAGGED_BATCH"] = "1" if ragged else "0"
        try:
            prompts, warm = workloads[repetitive]
            eng = ServingEngine(model, ServingConfig(
                num_slots=slots, block_size=32, max_model_len=512,
                max_new_tokens=new, min_prefill_bucket=32,
                prefill_chunk=chunk, num_speculative_tokens=gamma,
                # legacy comparison point: the interleaved scheduler
                # (chunk execs between decode steps); ragged ignores it
                max_prefill_chunks_per_step=0 if ragged else 1))
            eng.serve([p.copy() for p in warm],
                      max_new_tokens=4)                      # warmup
            st0 = eng.stats()
            comp0 = st0["executables_compiled"]
            tokens0 = st0["tokens_total"]
            queue = [p.copy() for p in prompts]
            step_ms = []
            t0 = time.perf_counter()
            while queue or eng.num_queued or eng.num_active:
                # concurrent admissions: keep the queue primed so
                # prefill work is ALWAYS pending alongside decode
                while queue and eng.num_queued < 2:
                    eng.submit(queue.pop(0), new)
                s0 = time.perf_counter()
                eng.step()
                step_ms.append(1000 * (time.perf_counter() - s0))
            wall = time.perf_counter() - t0
            st = eng.stats()
            eng.shutdown()
            lat = np.sort(np.asarray(step_ms))
            return {
                "aggregate_tokens_per_sec":
                    round((st["tokens_total"] - tokens0) / wall, 1),
                "step_launch_ms_p50": round(float(
                    lat[len(lat) // 2]), 2),
                "step_launch_ms_p99": round(float(
                    lat[min(len(lat) - 1, int(len(lat) * 0.99))]), 2),
                "steps": len(step_ms),
                "executables_compiled": st["executables_compiled"],
                "recompiles_measured":
                    st["executables_compiled"] - comp0,
                "ragged_batch": st["ragged_batch"],
            }
        finally:
            os.environ.pop("PADDLE_TPU_RAGGED_BATCH", None)

    ragged = run_engine(True)
    legacy = run_engine(False)
    spec_ragged = run_engine(True, gamma=2, repetitive=True)
    spec_legacy = run_engine(False, gamma=2, repetitive=True)
    out = {
        "ragged": ragged,
        "legacy_interleaved": legacy,
        "spec_ragged": spec_ragged,
        "spec_legacy_interleaved": spec_legacy,
        "speedup_tokens_per_sec": round(
            ragged["aggregate_tokens_per_sec"]
            / max(legacy["aggregate_tokens_per_sec"], 1e-9), 3),
        "spec_speedup_tokens_per_sec": round(
            spec_ragged["aggregate_tokens_per_sec"]
            / max(spec_legacy["aggregate_tokens_per_sec"], 1e-9), 3),
        "executables_collapsed": (
            f"{legacy['executables_compiled']} -> "
            f"{ragged['executables_compiled']}"),
        "num_slots": slots, "max_new_tokens": new,
        "requests": n_req, "prefill_chunk": chunk,
        "workload_prompt_lens": plens,
    }
    del model
    gc.collect()
    return out


def _async_bench():
    """Async tick pipeline (the ISSUE-20 bar): the SAME decode-heavy
    workload through the blocking loop (``async_depth=0``) and the
    depth-1 dispatch-ahead pipeline (``async_depth=1``), single
    engine AND a 2-replica cluster (serial replica ticking vs
    dispatch-all-then-commit-all). The pipeline's win is evicting the
    host from the device's critical path — commit bookkeeping,
    digests, tracing and the token fetch overlap the next tick's
    execution — so the measurable headline is ``host_gap_ms`` (the
    dispatch→dispatch host time the device sees) and the aggregate
    tok/s ratio. Caveat the proxy honestly: overlap converts host
    idle/blocked time into device progress, which requires host and
    device to run CONCURRENTLY — true on any real accelerator and on
    a multi-core CPU proxy, but on a single-core container
    (``cpu_cores: 1``) the XLA compute threads and the host thread
    time-share one core, total CPU work is the wall clock, and the
    measured ratio pins near 1.0 regardless of pipeline structure
    (the residual win is the per-tick host packing the device-
    resident carry eliminates). The >= 1.15x two-replica bar is
    therefore a multi-core/accelerator assertion; ``cpu_cores`` in
    the output says which regime this run measured."""
    import gc
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.inference.cluster import (ClusterConfig,
                                              EngineCluster)

    # sized so host bookkeeping and the device tick are comparable —
    # the regime where overlap pays; a huge model would bury the host
    # in device time and a toy one has nothing to hide the host
    # behind. fp32 on purpose: the CPU proxy emulates bf16 slowly,
    # which inflates the device tick and drowns the host fraction the
    # pipeline exists to hide. Many slots (16) keeps the O(slots)
    # per-tick commit bookkeeping a visible slice of the gap.
    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_ASYNC_VOCAB", 4096)),
        hidden_size=int(os.environ.get("BENCH_ASYNC_HIDDEN", 256)),
        intermediate_size=int(os.environ.get("BENCH_ASYNC_FFN", 704)),
        num_hidden_layers=int(os.environ.get("BENCH_ASYNC_LAYERS", 2)),
        num_attention_heads=8, num_key_value_heads=4,
        max_position_embeddings=512)
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.eval()

    slots = int(os.environ.get("BENCH_ASYNC_SLOTS", 16))
    new = int(os.environ.get("BENCH_ASYNC_NEW", 32))
    n_req = int(os.environ.get("BENCH_ASYNC_REQS", 32))
    plens = [24, 40, 56, 32]
    rng = np.random.RandomState(0)
    warm = [rng.randint(1, cfg.vocab_size, (p,)) for p in plens]

    def drain(target, workload):
        """Submit everything up front (decode-heavy steady state —
        the pipeline's regime) and drain on step()."""
        tokens0 = target.stats()["tokens_total"]
        execs0 = target.stats()["executables_compiled"]
        for p in workload:
            target.submit(p.copy(), new)
        t0 = time.perf_counter()
        while target.num_queued or target.num_active:
            target.step()
        wall = time.perf_counter() - t0
        st = target.stats()
        hg = st.get("host_gap_ms")
        if hg is None:              # cluster: slowest replica's digest
            hg = max((r["host_gap_ms"] for r in st["replicas"] if r),
                     key=lambda d: d["p50"],
                     default={"p50": 0.0, "p99": 0.0})
        return {
            "aggregate_tokens_per_sec":
                round((st["tokens_total"] - tokens0) / wall, 1),
            "host_gap_ms_p50": hg["p50"],
            "host_gap_ms_p99": hg["p99"],
            "async_depth": st["async_depth"],
            "pipeline_flushes": st["pipeline_flushes"],
            "recompiles_measured":
                st["executables_compiled"] - execs0,
        }

    def fresh(n, seed):
        """A fresh workload per drain — repeating identical prompts
        would hit the prefix cache and erase the prefill phase,
        changing the regime between repetitions."""
        r = np.random.RandomState(seed)
        return [r.randint(1, cfg.vocab_size, (plens[i % len(plens)],))
                for i in range(n)]

    def build_engine(depth):
        eng = ServingEngine(model, ServingConfig(
            num_slots=slots, block_size=16, max_model_len=256,
            max_new_tokens=new, async_depth=depth))
        eng.serve([p.copy() for p in warm], max_new_tokens=4)
        return eng

    def build_cluster(depth):
        cl = EngineCluster(
            model, ClusterConfig(num_replicas=2),
            ServingConfig(num_slots=slots, block_size=16,
                          max_model_len=256, max_new_tokens=new,
                          async_depth=depth))
        cl.serve([rng.randint(1, cfg.vocab_size, (p,))
                  for p in plens * 2], max_new_tokens=4)
        return cl

    def duel(base, cand, n, reps=3):
        """Alternate drains between the two warm targets and keep
        each side's best. Host-scheduler drift on the CPU proxy moves
        absolute tok/s by 10-20% over seconds — back-to-back
        alternation puts both arms inside every drift window, so the
        *ratio* stays meaningful where sequential measurement of one
        full arm then the other does not."""
        b_runs, c_runs = [], []
        for i in range(reps):
            b_runs.append(drain(base, fresh(n, 100 + i)))
            c_runs.append(drain(cand, fresh(n, 200 + i)))
        key = lambda r: r["aggregate_tokens_per_sec"]
        return max(b_runs, key=key), max(c_runs, key=key)

    eng0, eng1 = build_engine(0), build_engine(1)
    sync_eng, async_eng = duel(eng0, eng1, n_req)
    eng0.shutdown()
    eng1.shutdown()
    cl0, cl1 = build_cluster(0), build_cluster(1)
    serial_cl, overlap_cl = duel(cl0, cl1, 2 * n_req)
    cl0.shutdown()
    cl1.shutdown()
    out = {
        "engine_sync": sync_eng,
        "engine_async": async_eng,
        "async_tokens_per_sec":
            async_eng["aggregate_tokens_per_sec"],
        "async_speedup": round(
            async_eng["aggregate_tokens_per_sec"]
            / max(sync_eng["aggregate_tokens_per_sec"], 1e-9), 3),
        "cluster_serial": serial_cl,
        "cluster_overlapped": overlap_cl,
        "async_cluster_tokens_per_sec":
            overlap_cl["aggregate_tokens_per_sec"],
        "async_cluster_speedup": round(
            overlap_cl["aggregate_tokens_per_sec"]
            / max(serial_cl["aggregate_tokens_per_sec"], 1e-9), 3),
        "host_gap_ms_p50": async_eng["host_gap_ms_p50"],
        "num_slots": slots, "max_new_tokens": new,
        "requests": n_req, "workload_prompt_lens": plens,
        "model_shape": {
            "hidden": cfg.hidden_size,
            "layers": cfg.num_hidden_layers,
            "ffn": cfg.intermediate_size, "vocab": cfg.vocab_size},
        "cpu_proxy": jax.default_backend() != "tpu",
        "cpu_cores": len(os.sched_getaffinity(0))
        if hasattr(os, "sched_getaffinity") else (os.cpu_count() or 1),
    }
    del model
    gc.collect()
    return out


def _moe_serving_bench():
    """MoE through the serving engine (the ISSUE-8 'excluded ->
    served, measured' bar): a mixed-length workload on a dropless
    Qwen2-MoE — ragged mixed-batch path vs the legacy per-width zoo —
    reporting aggregate tok/s, executables compiled and recompiles
    (must be 0 after warmup), plus the decode-time routing telemetry
    (entropy, expert-load max) the monitor tap observes."""
    import gc
    import paddle_tpu as paddle
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeConfig,
                                             Qwen2MoeForCausalLM)
    from paddle_tpu.inference import ServingConfig, ServingEngine

    cfg = Qwen2MoeConfig(
        vocab_size=int(os.environ.get("BENCH_MOE_SERVE_VOCAB", 32000)),
        hidden_size=int(os.environ.get("BENCH_MOE_SERVE_HIDDEN", 1024)),
        intermediate_size=int(
            os.environ.get("BENCH_MOE_SERVE_FFN", 2816)),
        moe_intermediate_size=int(
            os.environ.get("BENCH_MOE_SERVE_EFFN", 1408)),
        shared_expert_intermediate_size=int(
            os.environ.get("BENCH_MOE_SERVE_SFFN", 1408)),
        num_hidden_layers=int(
            os.environ.get("BENCH_MOE_SERVE_LAYERS", 4)),
        num_attention_heads=16, num_key_value_heads=8,
        num_experts=int(os.environ.get("BENCH_MOE_SERVE_EXPERTS", 16)),
        num_experts_per_tok=int(
            os.environ.get("BENCH_MOE_SERVE_TOPK", 4)),
        dropless=True, max_position_embeddings=1024, dtype="bfloat16")
    paddle.seed(0)
    model = Qwen2MoeForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()

    slots = int(os.environ.get("BENCH_MOE_SERVE_SLOTS", 8))
    new = int(os.environ.get("BENCH_MOE_SERVE_NEW", 32))
    n_req = int(os.environ.get("BENCH_MOE_SERVE_REQS", 16))
    # MoE rows are expensive (every padded row routes through the
    # dispatch sort + grouped matmuls, unlike a dense MLP whose pad
    # rows are nearly free on the MXU), so the ragged engine runs a
    # DECODE-TUNED prefill row budget by default — the OPS.md
    # "small for decode-heavy fleets" guidance, measurable here
    rrows = int(os.environ.get("BENCH_MOE_SERVE_RAGGED_ROWS", 16))
    plens = [24, 48, 96, 160, 64, 128, 32, 80]
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size,
                           (plens[i % len(plens)],)).astype(np.int32)
               for i in range(n_req)]
    warm = [rng.randint(1, cfg.vocab_size, (p,)).astype(np.int32)
            for p in plens[:4]]

    def run_engine(ragged):
        eng = ServingEngine(model, ServingConfig(
            num_slots=slots, block_size=32, max_model_len=512,
            max_new_tokens=new, prefill_chunk=64,
            ragged_prefill_rows=rrows, ragged_batch=ragged))
        eng.serve([p.copy() for p in warm], max_new_tokens=4)
        st0 = eng.stats()
        queue = [p.copy() for p in prompts]
        t0 = time.perf_counter()
        while queue or eng.num_queued or eng.num_active:
            while queue and eng.num_queued < 2:
                eng.submit(queue.pop(0), new)
            eng.step()
        wall = time.perf_counter() - t0
        st = eng.stats()
        eng.shutdown()
        return {
            "aggregate_tokens_per_sec": round(
                (st["tokens_total"] - st0["tokens_total"]) / wall, 1),
            "executables_compiled": st["executables_compiled"],
            "recompiles_measured": st["executables_compiled"]
            - st0["executables_compiled"],
            "moe_routing_entropy": round(st["moe_routing_entropy"], 4),
            "moe_expert_load_max": round(st["moe_expert_load_max"], 4),
            "moe_dispatches": st["moe_dispatches"],
            "moe_fused_gmm": st["moe_fused_gmm"],
        }

    ragged = run_engine(True)
    legacy = run_engine(False)
    try:
        import jax
        backend = jax.default_backend()
    except Exception:
        backend = "unknown"
    out = {
        "ragged": ragged,
        "legacy": legacy,
        "speedup_tokens_per_sec": round(
            ragged["aggregate_tokens_per_sec"]
            / max(legacy["aggregate_tokens_per_sec"], 1e-9), 3),
        # CPU caveat: every padded ragged row pays LINEAR cost in the
        # MoE dispatch + lm_head on CPU, so the one-executable path
        # can trail the per-width zoo here; on TPU pad rows ride the
        # MXU width (near-free) and the launch collapse dominates —
        # read the ragged-vs-legacy delta as hardware-dependent and
        # tune ServingConfig(ragged_prefill_rows) per fleet
        "cpu_row_cost_proxy": backend != "tpu",
        "num_slots": slots, "max_new_tokens": new, "requests": n_req,
        "ragged_prefill_rows": rrows,
        "workload_prompt_lens": plens,
        "config": {"hidden": cfg.hidden_size,
                   "experts": cfg.num_experts,
                   "top_k": cfg.num_experts_per_tok,
                   "layers": cfg.num_hidden_layers},
    }
    del model
    gc.collect()
    return out


def _moe_fused_bench():
    """Fused-dispatch vs sorted grouped-matmul training A/B at the r05
    MoE bench config (the MFU-gap attack tracked every round): the
    SAME ``_moe_bench(dropless=True)`` measurement with
    ``PADDLE_TPU_MOE_FUSED_GMM`` forced on vs off. On a non-TPU
    backend both arms run the sorted ragged_dot path (the fused
    kernels require the hardware) — the block is then a structural
    proxy flagged ``cpu_proxy`` with delta ~1.0, exactly like the TP
    bench's ``cpu_mesh_proxy``; on real TPU the delta IS the fusion
    win and ``kernel_stats`` proves which kernel each arm compiled.
    Knobs: ``BENCH_MOE_FUSED_STEPS`` (and the BENCH_MOE_* shape knobs
    ``_moe_bench`` reads)."""
    import jax
    prev = os.environ.get("PADDLE_TPU_MOE_FUSED_GMM")
    steps_override = os.environ.get("BENCH_MOE_FUSED_STEPS")
    prev_steps = os.environ.get("BENCH_MOE_STEPS")
    try:
        if steps_override is not None:
            os.environ["BENCH_MOE_STEPS"] = steps_override
        os.environ["PADDLE_TPU_MOE_FUSED_GMM"] = "1"
        fused = _moe_bench(dropless=True)
        os.environ["PADDLE_TPU_MOE_FUSED_GMM"] = "0"
        sorted_ = _moe_bench(dropless=True)
    finally:
        if prev is None:
            os.environ.pop("PADDLE_TPU_MOE_FUSED_GMM", None)
        else:
            os.environ["PADDLE_TPU_MOE_FUSED_GMM"] = prev
        if prev_steps is None:
            os.environ.pop("BENCH_MOE_STEPS", None)
        else:
            os.environ["BENCH_MOE_STEPS"] = prev_steps
    try:
        backend = jax.default_backend()
    except Exception:
        backend = "unknown"
    return {
        "fused": fused,
        "sorted": sorted_,
        "mfu_delta": round(fused["mfu"] - sorted_["mfu"], 4),
        "speedup_tokens_per_sec": round(
            fused["moe_tokens_per_sec_per_chip"]
            / max(sorted_["moe_tokens_per_sec_per_chip"], 1e-9), 3),
        "backend": backend,
        # off-TPU the fused kernels never arm — both arms are the
        # sorted path and this block only pins the harness structure
        "cpu_proxy": backend != "tpu",
    }


def _lora_bench():
    """Batched multi-LoRA serving (the ISSUE-18 bar): a mixed-tenant
    workload — requests round-robined over N adapters — served as ONE
    mixed-adapter ragged batch (per-slot adapter ids, grouped delta
    matmuls) vs SEQUENTIAL per-adapter serving (each tenant's requests
    drained alone, the one-adapter-at-a-time deployment batching
    replaces). Both arms run identical requests on the same engine
    shape; the batched arm's win is slot occupancy — cross-tenant rows
    share every tick. Off-TPU the absolute tok/s is a structure proxy
    (``cpu_proxy``), but batched >= sequential holds on CPU too
    because the per-tick launch overhead amortizes across tenants.
    Also pinned: ZERO steady-state recompiles while adapters churn
    through a resident window SMALLER than the tenant count (LRU
    spills to the host tier and back, values swap at fixed shapes),
    and the resident/swap trajectory the stats() keys report."""
    import gc
    import jax
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine

    cfg = LlamaConfig(
        vocab_size=int(os.environ.get("BENCH_LORA_VOCAB", 8000)),
        hidden_size=int(os.environ.get("BENCH_LORA_HIDDEN", 1024)),
        intermediate_size=int(os.environ.get("BENCH_LORA_FFN", 2816)),
        num_hidden_layers=int(os.environ.get("BENCH_LORA_LAYERS", 4)),
        num_attention_heads=8, num_key_value_heads=4,
        max_position_embeddings=512, dtype="bfloat16")
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    model.to(dtype="bfloat16")
    model.eval()

    slots = int(os.environ.get("BENCH_LORA_SLOTS", 8))
    new = int(os.environ.get("BENCH_LORA_NEW", 32))
    n_adapters = int(os.environ.get("BENCH_LORA_ADAPTERS", 4))
    n_req = int(os.environ.get("BENCH_LORA_REQS", 16))
    rank = int(os.environ.get("BENCH_LORA_RANK", 16))
    plens = [32, 64, 96, 48]
    rng = np.random.RandomState(0)
    # identical requests for both arms: (prompt, adapter) pairs,
    # tenants round-robined so the batched arm always mixes adapters
    reqs = [(rng.randint(1, cfg.vocab_size, (plens[i % len(plens)],)),
             1 + i % n_adapters) for i in range(n_req)]

    def weights(seed):
        r = np.random.RandomState(seed)
        h = cfg.hidden_size
        kv = h * cfg.num_key_value_heads // cfg.num_attention_heads
        return {n: (r.normal(0, 0.02, (h, rank)).astype(np.float32),
                    r.normal(0, 0.02, (rank, kv if n in
                             ("k_proj", "v_proj") else h))
                    .astype(np.float32))
                for n in ("q_proj", "k_proj", "v_proj", "o_proj")}

    def mk_engine(max_adapters):
        eng = ServingEngine(model, ServingConfig(
            num_slots=slots, block_size=32, max_model_len=256,
            max_new_tokens=new, prefill_chunk=64,
            lora_rank=rank, max_adapters=max_adapters))
        for aid in range(1, n_adapters + 1):
            eng.load_adapter(aid, weights(100 + aid))
        # warmup: compile the ONE tick executable off the clock
        eng.submit(rng.randint(1, cfg.vocab_size, (16,)), 4,
                   adapter_id=1)
        eng.run()
        return eng

    def measure(eng, groups):
        """Serve ``groups`` (list of request lists, drained one group
        at a time) and return tok/s + compile/residency accounting."""
        st0 = eng.stats()
        tokens0, comp0 = st0["tokens_total"], st0[
            "executables_compiled"]
        resident_traj = [st0["lora_adapters_resident"]]
        t0 = time.perf_counter()
        for group in groups:
            for prompt, aid in group:
                eng.submit(prompt.copy(), new, adapter_id=aid)
            eng.run()
            resident_traj.append(
                eng.stats()["lora_adapters_resident"])
        wall = time.perf_counter() - t0
        st = eng.stats()
        return {
            "aggregate_tokens_per_sec":
                round((st["tokens_total"] - tokens0) / wall, 1),
            "wall_s": round(wall, 3),
            "executables_compiled": st["executables_compiled"],
            "recompiles_measured":
                st["executables_compiled"] - comp0,
            "lora_adapters_resident": st["lora_adapters_resident"],
            "lora_adapter_swaps": st["lora_adapter_swaps"],
            "lora_host_tier_bytes": st["lora_host_tier_bytes"],
            "adapters_resident_trajectory": resident_traj,
        }

    # batched arm: every tenant in flight at once, one ragged batch
    eng = mk_engine(max_adapters=n_adapters)
    batched = measure(eng, [reqs])
    eng.shutdown()
    # sequential arm: one tenant at a time (same engine shape), the
    # per-adapter deployment the batched path replaces
    eng = mk_engine(max_adapters=n_adapters)
    by_tenant = [[r for r in reqs if r[1] == aid]
                 for aid in range(1, n_adapters + 1)]
    sequential = measure(eng, by_tenant)
    eng.shutdown()
    # churn arm: resident window SMALLER than the tenant count — LRU
    # spill/reload on a live engine, still zero recompiles
    eng = mk_engine(max_adapters=max(2, n_adapters // 2))
    churn = measure(eng, by_tenant)
    eng.shutdown()
    out = {
        "batched": batched,
        "sequential": sequential,
        "churn_small_window": churn,
        "batched_speedup": round(
            batched["aggregate_tokens_per_sec"]
            / max(sequential["aggregate_tokens_per_sec"], 1e-9), 3),
        "churn_recompiles": churn["recompiles_measured"],
        "num_adapters": n_adapters, "rank": rank,
        "num_slots": slots, "requests": n_req,
        "cpu_proxy": jax.default_backend() != "tpu",
    }
    del model
    gc.collect()
    return out


def main():
    from paddle_tpu.utils import compile_cache
    compile_cache.configure()
    _peak_flops_per_chip()      # no chip, or an unknown one: stop here
    steps = int(os.environ.get("BENCH_STEPS", 10))
    base = _train_config(
        "base_500m",
        hidden=int(os.environ.get("BENCH_HIDDEN", 2048)),
        layers=int(os.environ.get("BENCH_LAYERS", 8)),
        heads=int(os.environ.get("BENCH_HEADS", 16)),
        kv_heads=int(os.environ.get("BENCH_KV_HEADS", 8)),
        ffn=int(os.environ.get("BENCH_FFN", 5632)),
        vocab=int(os.environ.get("BENCH_VOCAB", 32000)),
        seq=int(os.environ.get("BENCH_SEQ", 2048)),
        batch=int(os.environ.get("BENCH_BATCH", 8)),
        steps=steps,
        remat=os.environ.get("BENCH_REMAT", "none"))
    large = _train_config(
        "llama8b_shaped",
        hidden=int(os.environ.get("BENCH_L_HIDDEN", 4096)),
        layers=int(os.environ.get("BENCH_L_LAYERS", 4)),
        heads=int(os.environ.get("BENCH_L_HEADS", 32)),
        kv_heads=int(os.environ.get("BENCH_L_KV_HEADS", 8)),
        ffn=int(os.environ.get("BENCH_L_FFN", 14336)),
        vocab=int(os.environ.get("BENCH_L_VOCAB", 32000)),
        seq=int(os.environ.get("BENCH_L_SEQ", 4096)),
        batch=int(os.environ.get("BENCH_L_BATCH", 2)),
        steps=max(steps // 2, 3),
        remat=os.environ.get("BENCH_L_REMAT", "none"),
        windows=int(os.environ.get("BENCH_L_WINDOWS", 3)))
    remat_regime = _train_config(
        "llama8b_shaped_remat",
        hidden=int(os.environ.get("BENCH_L_HIDDEN", 4096)),
        layers=int(os.environ.get("BENCH_L_LAYERS", 4)),
        heads=int(os.environ.get("BENCH_L_HEADS", 32)),
        kv_heads=int(os.environ.get("BENCH_L_KV_HEADS", 8)),
        ffn=int(os.environ.get("BENCH_L_FFN", 14336)),
        vocab=int(os.environ.get("BENCH_L_VOCAB", 32000)),
        seq=int(os.environ.get("BENCH_L_SEQ", 4096)),
        batch=int(os.environ.get("BENCH_L_BATCH", 2)),
        steps=max(steps // 2, 3),
        remat=os.environ.get("BENCH_R_REMAT", "full"),
        remat_interval=int(os.environ.get("BENCH_R_INTERVAL", 2)))
    # depth-stability evidence: a 16-layer stack that NEEDS remat (the
    # regime a full-depth 8B lives in) — per-layer shape of the 1B class
    try:
        deep = _train_config(
            "deep_16layer_remat",
            hidden=int(os.environ.get("BENCH_D_HIDDEN", 2048)),
            layers=int(os.environ.get("BENCH_D_LAYERS", 16)),
            heads=16, kv_heads=8,
            ffn=int(os.environ.get("BENCH_D_FFN", 5632)),
            vocab=32000,
            seq=int(os.environ.get("BENCH_D_SEQ", 4096)),
            batch=int(os.environ.get("BENCH_D_BATCH", 4)),
            steps=max(steps // 2, 3),
            # save_attn beats full at depth (r4 sweep: 0.5595 vs 0.5487
            # same-session — flash-attn outputs are never replayed)
            remat=os.environ.get("BENCH_D_REMAT", "save_attn"),
            remat_interval=int(os.environ.get("BENCH_D_INTERVAL", 2)))
    except Exception as exc:
        deep = {"error": repr(exc)}
    # 32-layer depth anchor (~660M params): full real-model depth at
    # the per-layer shape class of a 1B, the regime the 8B projection
    # extrapolates from
    try:
        deep32 = _train_config(
            "deep_32layer_remat",
            hidden=int(os.environ.get("BENCH_D32_HIDDEN", 1280)),
            layers=int(os.environ.get("BENCH_D32_LAYERS", 32)),
            heads=10, kv_heads=5,
            ffn=int(os.environ.get("BENCH_D32_FFN", 3456)),
            vocab=32000,
            seq=int(os.environ.get("BENCH_D32_SEQ", 4096)),
            batch=int(os.environ.get("BENCH_D32_BATCH", 4)),
            steps=max(steps // 2, 3),
            remat=os.environ.get("BENCH_D32_REMAT", "save_attn"),
            remat_interval=int(os.environ.get("BENCH_D32_INTERVAL", 2)))
    except Exception as exc:
        deep32 = {"error": repr(exc)}
    try:
        moe = _moe_bench()
    except Exception as exc:   # aux benches must not sink the metric
        moe = {"error": repr(exc)}
    try:
        moe_dropless = _moe_bench(dropless=True)
    except Exception as exc:
        moe_dropless = {"error": repr(exc)}
    try:
        moe_profile = _moe_stage_profile()
    except Exception as exc:
        moe_profile = {"error": repr(exc)}
    try:
        moe_fused = _moe_fused_bench()
    except Exception as exc:
        moe_fused = {"error": repr(exc)}
    try:
        moe_serving = _moe_serving_bench()
    except Exception as exc:
        moe_serving = {"error": repr(exc)}
    try:
        decode = _decode_bench()
    except Exception as exc:
        decode = {"error": repr(exc)}
    try:
        serving = _serving_bench()
    except Exception as exc:
        serving = {"error": repr(exc)}
    try:
        speculative = _spec_serving_bench()
    except Exception as exc:
        speculative = {"error": repr(exc)}
    try:
        spec_tree = _spec_tree_bench()
    except Exception as exc:
        spec_tree = {"error": repr(exc)}
    try:
        serving_prefix = _prefix_serving_bench()
    except Exception as exc:
        serving_prefix = {"error": repr(exc)}
    try:
        serving_tp = _tp_serving_bench()
    except Exception as exc:
        serving_tp = {"error": repr(exc)}
    try:
        serving_ragged = _ragged_serving_bench()
    except Exception as exc:
        serving_ragged = {"error": repr(exc)}
    try:
        kv_quant = _kv_quant_bench()
    except Exception as exc:
        kv_quant = {"error": repr(exc)}
    try:
        goodput = _goodput_bench()
    except Exception as exc:
        goodput = {"error": repr(exc)}
    try:
        roofline = _roofline_bench()
    except Exception as exc:
        roofline = {"error": repr(exc)}
    try:
        cluster = _cluster_bench()
    except Exception as exc:
        cluster = {"error": repr(exc)}
    try:
        fusion = _fusion_bench()
    except Exception as exc:
        fusion = {"error": repr(exc)}
    try:
        preempt = _preempt_bench()
    except Exception as exc:
        preempt = {"error": repr(exc)}
    try:
        flashmask = _flashmask_bench()
    except Exception as exc:
        flashmask = {"error": repr(exc)}
    try:
        health = _health_bench()
    except Exception as exc:
        health = {"error": repr(exc)}
    try:
        lora = _lora_bench()
    except Exception as exc:
        lora = {"error": repr(exc)}
    try:
        autoscale = _autoscale_bench()
    except Exception as exc:
        autoscale = {"error": repr(exc)}
    try:
        serving_async = _async_bench()
    except Exception as exc:
        serving_async = {"error": repr(exc)}

    detail = {"large": large, "base": base,
              "remat_regime": remat_regime, "deep": deep,
              "deep32": deep32, "moe": moe,
              "moe_dropless": moe_dropless,
              "moe_profile": moe_profile,
              "moe_fused": moe_fused,
              "moe_serving": moe_serving,
              "decode": decode,
              "serving": serving,
              "speculative": speculative,
              "spec_tree": spec_tree,
              "serving_prefix": serving_prefix,
              "serving_tp": serving_tp,
              "serving_ragged": serving_ragged,
              "kv_quant": kv_quant,
              "goodput": goodput,
              "roofline": roofline,
              "cluster": cluster,
              "fusion": fusion,
              "preempt": preempt,
              "flashmask": flashmask,
              "health": health,
              "lora": lora,
              "autoscale": autoscale,
              "serving_async": serving_async,
              # headline config's compiled-step accounting (analytic
              # FLOPs/step, peak HBM, collective census, cache counts)
              "telemetry": large.get("telemetry")
              if isinstance(large, dict) else None}
    # headline FIRST and compact (<4KB) so driver tail-capture can
    # never truncate "value"; full per-config detail goes to a file
    result = {
        "metric": "llama_pretrain_mfu",
        "value": large["mfu"],
        "unit": "fraction_of_peak",
        "vs_baseline": round(large["mfu"] / 0.40, 4),
        "summary": {
            k: (v.get("mfu") if isinstance(v, dict) else None)
            for k, v in detail.items()
            if k not in ("decode", "serving", "speculative",
                         "spec_tree",
                         "serving_prefix", "serving_tp",
                         "serving_ragged", "kv_quant", "goodput",
                         "roofline", "cluster", "fusion", "preempt",
                         "flashmask", "health", "lora", "autoscale",
                         "serving_async",
                         "moe_profile", "moe_fused", "moe_serving")
        } | {"decode_tokens_per_sec":
             decode.get("decode_tokens_per_sec")
             if isinstance(decode, dict) else None,
             "serving_tokens_per_sec":
             serving.get("bf16", {}).get("aggregate_tokens_per_sec")
             if isinstance(serving, dict) else None,
             "serving_int8_tokens_per_sec":
             serving.get("int8", {}).get("aggregate_tokens_per_sec")
             if isinstance(serving, dict) else None,
             "spec_serving_tokens_per_sec":
             speculative.get("ngram_g4", {}).get(
                 "aggregate_tokens_per_sec")
             if isinstance(speculative, dict) else None,
             "spec_mean_accepted_len":
             speculative.get("ngram_g4", {}).get("mean_accepted_len")
             if isinstance(speculative, dict) else None,
             "spec_tree_accept_len":
             spec_tree.get("tree_g4", {}).get("mean_accepted_len")
             if isinstance(spec_tree, dict) else None,
             "spec_tree_tokens_per_sec":
             spec_tree.get("tree_g4", {}).get(
                 "aggregate_tokens_per_sec")
             if isinstance(spec_tree, dict) else None,
             "prefix_serving_speedup":
             serving_prefix.get("speedup_tokens_per_sec")
             if isinstance(serving_prefix, dict) else None,
             "prefix_ttft_p50_reduction":
             serving_prefix.get("ttft_p50_reduction")
             if isinstance(serving_prefix, dict) else None,
             "prefix_hit_rate":
             serving_prefix.get("prefix_cached", {}).get(
                 "prefix_hit_rate")
             if isinstance(serving_prefix, dict) else None,
             "tp2_serving_tokens_per_sec":
             serving_tp.get("tp2", {}).get("aggregate_tokens_per_sec")
             if isinstance(serving_tp, dict) else None,
             "tp2_serving_speedup":
             serving_tp.get("tp2", {}).get("speedup_vs_tp1")
             if isinstance(serving_tp, dict) else None,
             "tp4_serving_speedup":
             serving_tp.get("tp4", {}).get("speedup_vs_tp1")
             if isinstance(serving_tp, dict) else None,
             "ragged_serving_tokens_per_sec":
             serving_ragged.get("ragged", {}).get(
                 "aggregate_tokens_per_sec")
             if isinstance(serving_ragged, dict) else None,
             "ragged_serving_speedup":
             serving_ragged.get("speedup_tokens_per_sec")
             if isinstance(serving_ragged, dict) else None,
             "ragged_executables_compiled":
             serving_ragged.get("ragged", {}).get(
                 "executables_compiled")
             if isinstance(serving_ragged, dict) else None,
             "flashmask_16k_block_skip_speedup":
             flashmask.get("block_skip_speedup")
             if isinstance(flashmask, dict) else None,
             "moe_fused_mfu":
             moe_fused.get("fused", {}).get("mfu")
             if isinstance(moe_fused, dict) else None,
             "moe_fused_mfu_delta":
             moe_fused.get("mfu_delta")
             if isinstance(moe_fused, dict) else None,
             "moe_serving_tokens_per_sec":
             moe_serving.get("ragged", {}).get(
                 "aggregate_tokens_per_sec")
             if isinstance(moe_serving, dict) else None,
             "moe_serving_recompiles":
             moe_serving.get("ragged", {}).get("recompiles_measured")
             if isinstance(moe_serving, dict) else None,
             "kv_quant_tokens_per_sec":
             kv_quant.get("int8", {}).get("aggregate_tokens_per_sec")
             if isinstance(kv_quant, dict) else None,
             "kv_quant_speedup":
             kv_quant.get("speedup_tokens_per_sec")
             if isinstance(kv_quant, dict) else None,
             "kv_quant_match_rate":
             kv_quant.get("token_match_rate")
             if isinstance(kv_quant, dict) else None,
             "kv_quant_pool_ratio":
             kv_quant.get("pool_bytes_ratio")
             if isinstance(kv_quant, dict) else None,
             "kv_quant_slots_ratio":
             kv_quant.get("slots_ratio")
             if isinstance(kv_quant, dict) else None,
             "goodput_at_qps":
             goodput.get("goodput_at_qps")
             if isinstance(goodput, dict) else None,
             "goodput_target_qps":
             goodput.get("target_qps")
             if isinstance(goodput, dict) else None,
             "ttft_p99_ms":
             goodput.get("ttft_p99_ms")
             if isinstance(goodput, dict) else None,
             "itl_p99_ms":
             goodput.get("itl_p99_ms")
             if isinstance(goodput, dict) else None,
             "step_mfu":
             roofline.get("step_mfu")
             if isinstance(roofline, dict) else None,
             "hbm_bw_util":
             roofline.get("hbm_bw_util")
             if isinstance(roofline, dict) else None,
             "roofline_device":
             roofline.get("device")
             if isinstance(roofline, dict) else None,
             "cluster_tokens_per_sec":
             cluster.get("two_replicas", {}).get(
                 "aggregate_tokens_per_sec")
             if isinstance(cluster, dict) else None,
             "cluster_speedup":
             cluster.get("speedup_tokens_per_sec")
             if isinstance(cluster, dict) else None,
             "cluster_ttft_p99_ms":
             cluster.get("disaggregated", {}).get("ttft_p99_ms")
             if isinstance(cluster, dict) else None,
             "cluster_affinity_hit_rate":
             cluster.get("conversation_affinity_hit_rate")
             if isinstance(cluster, dict) else None,
             "fusion_tokens_per_sec":
             fusion.get("fused", {}).get("aggregate_tokens_per_sec")
             if isinstance(fusion, dict) else None,
             "fusion_speedup":
             fusion.get("speedup_tokens_per_sec")
             if isinstance(fusion, dict) else None,
             "kernels_per_tick_ratio":
             fusion.get("kernels_per_tick_ratio")
             if isinstance(fusion, dict) else None,
             "preempt_goodput_delta":
             preempt.get("goodput_delta")
             if isinstance(preempt, dict) else None,
             "preempt_ttft_p99_ms":
             preempt.get("hi_ttft_p99_preempt_ms")
             if isinstance(preempt, dict) else None,
             "kv_blocks_spilled":
             preempt.get("kv_blocks_spilled")
             if isinstance(preempt, dict) else None,
             "health_alerts_fired":
             health.get("health_alerts_fired")
             if isinstance(health, dict) else None,
             "health_incident_captured":
             health.get("health_incident_captured")
             if isinstance(health, dict) else None,
             "lora_tokens_per_sec":
             lora.get("batched", {}).get("aggregate_tokens_per_sec")
             if isinstance(lora, dict) else None,
             "lora_batched_speedup":
             lora.get("batched_speedup")
             if isinstance(lora, dict) else None,
             "lora_adapters_resident":
             lora.get("batched", {}).get("lora_adapters_resident")
             if isinstance(lora, dict) else None,
             "lora_churn_recompiles":
             lora.get("churn_recompiles")
             if isinstance(lora, dict) else None,
             "autoscale_goodput_delta":
             autoscale.get("autoscale_goodput_delta")
             if isinstance(autoscale, dict) else None,
             "autoscale_replica_ticks_saved":
             autoscale.get("autoscale_replica_ticks_saved")
             if isinstance(autoscale, dict) else None,
             "migration_p99_ms":
             autoscale.get("migration_p99_ms")
             if isinstance(autoscale, dict) else None,
             "async_tokens_per_sec":
             serving_async.get("async_tokens_per_sec")
             if isinstance(serving_async, dict) else None,
             "async_speedup":
             serving_async.get("async_speedup")
             if isinstance(serving_async, dict) else None,
             "async_cluster_speedup":
             serving_async.get("async_cluster_speedup")
             if isinstance(serving_async, dict) else None,
             "host_gap_ms_p50":
             serving_async.get("host_gap_ms_p50")
             if isinstance(serving_async, dict) else None},
    }
    # trajectory contract (ISSUE 11/12 CI satellites): the goodput SLO
    # and cluster keys must be present in every round's summary — fail
    # loudly if a refactor drops them instead of silently losing the
    # trend line
    for k in ("goodput_at_qps", "ttft_p99_ms", "itl_p99_ms",
              "cluster_tokens_per_sec", "cluster_speedup",
              "cluster_ttft_p99_ms", "cluster_affinity_hit_rate",
              "fusion_tokens_per_sec", "fusion_speedup",
              "kernels_per_tick_ratio", "preempt_goodput_delta",
              "preempt_ttft_p99_ms", "kv_blocks_spilled",
              "step_mfu", "hbm_bw_util", "roofline_device",
              "spec_tree_accept_len", "spec_tree_tokens_per_sec",
              "health_alerts_fired", "health_incident_captured",
              "lora_tokens_per_sec", "lora_batched_speedup",
              "lora_adapters_resident", "lora_churn_recompiles",
              "autoscale_goodput_delta",
              "autoscale_replica_ticks_saved", "migration_p99_ms",
              "async_tokens_per_sec", "async_speedup",
              "async_cluster_speedup", "host_gap_ms_p50"):
        assert k in result["summary"], f"bench summary lost {k!r}"
    print(json.dumps(result))
    try:
        here = os.path.dirname(os.path.abspath(__file__))
        with open(os.path.join(here, "bench_detail.json"), "w") as f:
            json.dump(detail, f, indent=1)
    except OSError:
        pass
    # a block that raised is recorded above so the others still report,
    # but the run did not succeed
    errored = sorted(k for k, v in detail.items()
                     if isinstance(v, dict) and "error" in v)
    if errored:
        import sys
        print(f"bench.py: {len(errored)} block(s) errored: {errored}",
              file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()

"""Continuous-batching serving engine + paged KV cache (ISSUE 3):
paged-vs-dense greedy parity across mixed prompt lengths, scheduler
properties (every request completes exactly once, no block-pool leaks),
zero steady-state recompiles, generate() prompt bucketing, the ragged
Pallas kernel in interpret mode, and the fused int8 decode matmul."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


@pytest.fixture
def llama_tiny():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           kv_heads=2, ffn=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _dense_ref(model, prompt, n):
    out, _ = model.generate(paddle.to_tensor(
        np.asarray(prompt, np.int64)[None]), max_new_tokens=n)
    return np.asarray(out.numpy())[0]


# ---------------------------------------------------------------- paged
# cache primitives


def test_block_allocator_reuse_and_errors():
    from paddle_tpu.ops.paged_cache import BlockAllocator
    a = BlockAllocator(8)              # blocks 1..7 usable
    assert a.free_blocks == 7
    got = a.alloc(7)
    assert sorted(got) == list(range(1, 8))
    with pytest.raises(RuntimeError, match="exhausted"):
        a.alloc(1)
    a.free(got[:3])
    assert a.free_blocks == 3
    with pytest.raises(ValueError, match="double free"):
        a.free([got[0]])
    with pytest.raises(ValueError, match="invalid"):
        a.free([0])                    # the null block is never freed
    # prefix-cache back-compat contract: a freed-but-PUBLISHED block
    # parks in the LRU cache yet still counts as free (admission
    # reservations see it; alloc reclaims it transparently)
    a.publish(got[3], b"h3")
    a.free([got[3]])
    assert a.free_blocks == 4 and a.cached_blocks == 1
    assert a.lookup(b"h3") == got[3]
    a.alloc(4)                         # eviction makes it allocatable
    assert a.lookup(b"h3") is None and a.evictions == 1


def test_paged_write_gather_roundtrip():
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    rng = np.random.RandomState(0)
    BS, MB, H, D = 4, 3, 2, 8
    kp, vp = pc.init_pool(1 + 2 * MB, BS, H, D, jnp.float32)
    tables = jnp.asarray(
        (1 + np.arange(2 * MB, dtype=np.int32)).reshape(2, MB))
    k = jnp.asarray(rng.randn(2, 10, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(2, 10, H, D), jnp.float32)
    kp, vp = pc.write_prefill(kp, vp, tables, k, v,
                              n_real=np.asarray([10, 7]))
    dense_k = pc.gather_dense(kp, tables)
    np.testing.assert_allclose(np.asarray(dense_k[0, :10]),
                               np.asarray(k[0]))
    np.testing.assert_allclose(np.asarray(dense_k[1, :7]),
                               np.asarray(k[1, :7]))
    # row 1 positions >= 7 went to the null block, not its own blocks
    assert not np.allclose(np.asarray(dense_k[1, 7:10]),
                           np.asarray(k[1, 7:10]))
    # decode write lands at each slot's own position
    k1 = jnp.asarray(rng.randn(2, H, D), jnp.float32)
    v1 = jnp.asarray(rng.randn(2, H, D), jnp.float32)
    kp, vp = pc.write_decode(kp, vp, tables,
                             jnp.asarray([10, 7], jnp.int32), k1, v1)
    dense_k = pc.gather_dense(kp, tables)
    np.testing.assert_allclose(np.asarray(dense_k[0, 10]),
                               np.asarray(k1[0]))
    np.testing.assert_allclose(np.asarray(dense_k[1, 7]),
                               np.asarray(k1[1]))


def test_pallas_paged_kernel_matches_fallback_interpret():
    """The ragged TPU kernel (run in interpret mode on CPU) must agree
    with the gather fallback on ragged lengths + GQA."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    from paddle_tpu.ops.pallas import paged_attention as pa
    if pa.pallas_paged_attention is None:
        pytest.skip("pallas unavailable on this jax build")
    rng = np.random.RandomState(0)
    S, H, Hkv, D, BS, MB = 3, 8, 4, 64, 8, 4
    NB = 1 + S * MB
    kp = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
    vp = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
    tables = np.zeros((S, MB), np.int32)
    lens = np.asarray([5, 17, 29], np.int32)
    alloc = pc.BlockAllocator(NB)
    for s in range(S):
        n = pc.blocks_for(int(lens[s]), BS)
        tables[s, :n] = alloc.alloc(n)
    q = jnp.asarray(rng.randn(S, H, D), jnp.float32)
    ref = pa._xla_paged_attention(q, kp, vp, jnp.asarray(tables),
                                  jnp.asarray(lens))
    out = pa.pallas_paged_attention(q, kp, vp, jnp.asarray(tables),
                                    jnp.asarray(lens), interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# ------------------------------------------------------------ generate()
# paged + bucketing


def test_generate_paged_matches_dense(llama_tiny):
    """generate(cache_impl='paged') must reproduce the dense decode
    token-for-token (the block-pool layout is a pure re-layout)."""
    ids = np.random.RandomState(0).randint(0, 128, (2, 9)) \
        .astype(np.int64)
    dense, sd = llama_tiny.generate(paddle.to_tensor(ids),
                                    max_new_tokens=6)
    paged, sp = llama_tiny.generate(paddle.to_tensor(ids),
                                    max_new_tokens=6,
                                    cache_impl="paged")
    np.testing.assert_array_equal(dense.numpy(), paged.numpy())
    np.testing.assert_allclose(np.asarray(sd.numpy()),
                               np.asarray(sp.numpy()), atol=1e-4)


def test_generate_paged_rejects_beam_and_mask(llama_tiny):
    ids = paddle.to_tensor(np.zeros((1, 4), np.int64))
    with pytest.raises(NotImplementedError, match="beam"):
        llama_tiny.generate(ids, decode_strategy="beam_search",
                            num_beams=2, max_new_tokens=2,
                            cache_impl="paged")
    with pytest.raises(NotImplementedError, match="left-padded"):
        llama_tiny.generate(ids, max_new_tokens=2, cache_impl="paged",
                            attention_mask=paddle.to_tensor(
                                np.ones((1, 4), np.int64)))


def test_generate_bucketing_reuses_executable(llama_tiny):
    """Prompt lengths in one power-of-two bucket share ONE compiled
    decode loop: the second length must be a jit-cache HIT (the r5 gap:
    every exact length compiled fresh)."""
    c = monitor.counter("generate_jit_cache", labels=("model", "event"))
    rng = np.random.RandomState(3)

    def counts():
        return (c.labels(model="LlamaForCausalLM", event="miss").value(),
                c.labels(model="LlamaForCausalLM", event="hit").value())

    ids9 = rng.randint(0, 128, (2, 9)).astype(np.int64)
    llama_tiny.generate(paddle.to_tensor(ids9), max_new_tokens=4)
    m0, h0 = counts()
    for plen in (10, 12, 15):          # all bucket to 16, like 9
        ids = rng.randint(0, 128, (2, plen)).astype(np.int64)
        llama_tiny.generate(paddle.to_tensor(ids), max_new_tokens=4)
    m1, h1 = counts()
    assert m1 == m0, "bucketed prompt lengths must not recompile"
    assert h1 == h0 + 3


def test_generate_bucketing_matches_exact(llama_tiny):
    """Bucketing must not change the generated tokens (it rides the
    proven left-padded path)."""
    ids = np.random.RandomState(5).randint(0, 128, (2, 11)) \
        .astype(np.int64)
    bucketed, _ = llama_tiny.generate(paddle.to_tensor(ids),
                                      max_new_tokens=5)
    exact, _ = llama_tiny.generate(paddle.to_tensor(ids),
                                   max_new_tokens=5,
                                   pad_prompt_to_bucket=False)
    np.testing.assert_array_equal(bucketed.numpy(), exact.numpy())


# -------------------------------------------------------------- serving
# engine


def test_serving_parity_mixed_lengths(llama_tiny):
    """Batch-served greedy tokens must match each prompt generated alone
    through the dense cache — token for token, across prompt lengths
    that span buckets and block boundaries."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int64)
               for n in (5, 9, 13, 7, 21, 3)]
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=3, block_size=8, max_model_len=64, max_new_tokens=6))
    outs = eng.serve(prompts, max_new_tokens=6)
    for p, got in zip(prompts, outs):
        ref = _dense_ref(llama_tiny, p, 6)
        np.testing.assert_array_equal(got, ref[:len(got)])


def test_serving_scheduler_property(llama_tiny):
    """Scheduler invariants under slot + block pressure: every submitted
    request completes exactly once, streamed tokens equal the returned
    tokens, and the block pool drains to empty (no leaks)."""
    rng = np.random.RandomState(1)
    cfg = ServingConfig(num_slots=2, block_size=8, max_model_len=48,
                        num_blocks=13)
    streamed = {}
    eng = ServingEngine(
        llama_tiny, cfg,
        stream_callback=lambda rid, t: streamed.setdefault(rid, [])
        .append(t))
    rids = []
    lens = [3, 11, 6, 17, 9, 2, 14, 5]
    news = [4, 7, 1, 5, 3, 8, 2, 6]
    for n, mn in zip(lens, news):
        rids.append(eng.submit(rng.randint(1, 128, (n,)), mn))
    done = eng.run()
    assert sorted(done) == sorted(rids), "each request completes once"
    for rid, mn in zip(rids, news):
        assert 1 <= len(done[rid]) <= mn
        assert streamed[rid] == list(done[rid])
    st = eng.stats()
    assert st["active"] == 0 and st["queued"] == 0
    assert st["reserved_blocks"] == 0
    assert st["free_blocks"] == cfg.num_blocks - 1, "block-pool leak"
    assert st["requests_completed"] == len(rids)


def test_serving_zero_steadystate_recompiles(llama_tiny):
    """The serving bar: after warmup, the decode executable never
    recompiles — the compile counter stays at 1 while the step counter
    keeps growing (fixed-slot static shapes)."""
    rng = np.random.RandomState(2)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64))
    eng.serve([rng.randint(1, 128, (n,)) for n in (4, 9)],
              max_new_tokens=4)
    st0 = eng.stats()
    assert st0["decode_compiles"] == 1
    # second wave: different lengths/occupancy mixes, same executable
    eng.serve([rng.randint(1, 128, (n,)) for n in (13, 2, 7)],
              max_new_tokens=5)
    st1 = eng.stats()
    assert st1["decode_compiles"] == 1, "steady-state recompile"
    assert st1["decode_steps"] > st0["decode_steps"]


def test_serving_eos_retires_slot(llama_tiny):
    rng = np.random.RandomState(4)
    prompt = rng.randint(1, 128, (5,))
    first = int(_dense_ref(llama_tiny, prompt, 1)[0])
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        eos_token_id=first))
    (out,) = eng.serve([prompt], max_new_tokens=8)
    assert out.tolist() == [first]     # stopped right at EOS
    assert eng.stats()["free_blocks"] == eng._alloc.num_blocks - 1


def test_serving_gpt_family(llama_tiny):
    """GPT rides the same paged path (MHA, learned positions)."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(3)
    m = GPTForCausalLM(GPTConfig.tiny(vocab=96, hidden=64, layers=2,
                                      heads=4))
    m.eval()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 96, (n,)).astype(np.int64)
               for n in (5, 11, 8)]
    eng = ServingEngine(m, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64))
    outs = eng.serve(prompts, max_new_tokens=4)
    for p, got in zip(prompts, outs):
        ref = _dense_ref(m, p, 4)
        np.testing.assert_array_equal(got, ref[:len(got)])


def test_serving_streaming_mode_drops_results(llama_tiny):
    """``retain_results=False`` (long-lived streaming deployments):
    tokens reach the callback but retirement drops the per-request
    buffer — nothing accumulates, ``run()`` returns {}."""
    rng = np.random.RandomState(13)
    streamed = {}
    eng = ServingEngine(
        llama_tiny,
        ServingConfig(num_slots=2, block_size=8, max_model_len=64,
                      retain_results=False),
        stream_callback=lambda rid, t: streamed.setdefault(rid, [])
        .append(t))
    rids = [eng.submit(rng.randint(1, 128, (n,)), 4) for n in (5, 9, 3)]
    done = eng.run()
    assert done == {}
    assert eng._done == {} and eng._results == {}
    for rid in rids:
        assert 1 <= len(streamed[rid]) <= 4
    assert eng.stats()["requests_completed"] == 3


def test_serving_validates_requests(llama_tiny):
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=32))
    with pytest.raises(ValueError, match="max_model_len"):
        eng.submit(np.arange(1, 30), max_new_tokens=8)
    with pytest.raises(ValueError, match="empty"):
        eng.submit([])
    import paddle_tpu.nn as nn
    with pytest.raises(TypeError):
        ServingEngine(nn.Linear(4, 4))


def test_serving_telemetry_in_jsonl(tmp_path, llama_tiny):
    """The serving gauges/histograms/counters land in the monitor JSONL
    export (the ops-dashboard contract)."""
    import json
    rng = np.random.RandomState(6)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64))
    eng.serve([rng.randint(1, 128, (n,)) for n in (4, 12, 6)],
              max_new_tokens=4)
    path = monitor.export_jsonl(str(tmp_path / "metrics.jsonl"))
    names = {json.loads(line)["name"] for line in open(path)}
    for want in ("serving_slot_occupancy", "serving_batch_utilization",
                 "serving_queue_wait_ms", "serving_tokens_total",
                 "serving_decode_steps", "serving_decode_compiles",
                 "serving_requests_completed", "generate_jit_cache"):
        assert want in names, f"{want} missing from JSONL export"


# ----------------------------------------------------------- fused int8


def test_weight_only_int8_fused_matches_dequant():
    """The fused mixed-dtype dot (int8 weights straight into
    lax.dot_general, scale post-matmul) must match the explicit
    dequantize-then-matmul reference."""
    rng = np.random.RandomState(0)
    W = paddle.to_tensor(rng.randn(64, 48).astype(np.float32))
    x = paddle.to_tensor(rng.randn(4, 64).astype(np.float32))
    bias = paddle.to_tensor(rng.randn(48).astype(np.float32))
    qw, s = paddle.nn.quant.weight_quantize(W, "weight_only_int8")
    ref_w = paddle.nn.quant.weight_dequantize(qw, s,
                                              out_dtype="float32")
    ref = np.asarray(x.numpy()) @ np.asarray(ref_w.numpy()) \
        + np.asarray(bias.numpy())
    y = paddle.nn.quant.weight_only_linear(x, qw, bias, s)
    np.testing.assert_allclose(np.asarray(y.numpy()), ref,
                               rtol=1e-4, atol=1e-4)


def test_int8_teacher_forced_trajectory_floor(llama_tiny):
    """The fused int8 path's greedy trajectory agreement with bf16 must
    stay >= the r5 bench value (int8_trajectory_match = 0.1665 in
    BENCH_r05.json) — the satellite regression pin for the fused
    rewrite. Teacher-forced argmax agreement is also pinned (the
    less-chaotic metric the bench reports alongside)."""
    from paddle_tpu.nn.quant import quantize_for_inference
    ids = np.random.RandomState(8).randint(0, 128, (4, 12)) \
        .astype(np.int64)
    x = paddle.to_tensor(ids)
    bf_out, _ = llama_tiny.generate(x, max_new_tokens=16)
    bf_seq = np.concatenate([ids, np.asarray(bf_out.numpy())], axis=1)
    logits_bf = llama_tiny(paddle.to_tensor(bf_seq)).numpy()
    n = quantize_for_inference(llama_tiny)
    assert n > 0
    logits_q = llama_tiny(paddle.to_tensor(bf_seq)).numpy()
    forced = float((np.asarray(logits_bf).argmax(-1)
                    == np.asarray(logits_q).argmax(-1)).mean())
    q_out, _ = llama_tiny.generate(x, max_new_tokens=16)
    traj = float((np.asarray(bf_out.numpy())
                  == np.asarray(q_out.numpy())).mean())
    assert forced >= 0.9, f"teacher-forced parity collapsed: {forced}"
    assert traj >= 0.1665, f"trajectory match below r5 floor: {traj}"


def test_serving_int8_quantized_model():
    """The engine serves a weight-only-int8 model through the same
    compiled decode step (the production int8 serving mode)."""
    from paddle_tpu.nn.quant import quantize_for_inference
    paddle.seed(11)
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           kv_heads=2, ffn=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    quantize_for_inference(m)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int64)
               for n in (6, 10)]
    eng = ServingEngine(m, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64))
    outs = eng.serve(prompts, max_new_tokens=4)
    for p, got in zip(prompts, outs):
        ref = _dense_ref(m, p, 4)
        np.testing.assert_array_equal(got, ref[:len(got)])


def test_no_path_switches(llama_tiny, monkeypatch):
    """The ragged tick is the engine, not the default of a switch: the
    options that used to select another engine are unknown fields, and
    their environment twins are not read — a two-wave serve still ends
    at ONE executable with every tick on the ragged path."""
    with pytest.raises(TypeError):
        ServingConfig(ragged_batch=False)
    with pytest.raises(TypeError):
        ServingConfig(chunked_prefill=False)
    monkeypatch.setenv("PADDLE_TPU_RAGGED_BATCH", "0")
    monkeypatch.setenv("PADDLE_TPU_CHUNKED_PREFILL", "0")
    rng = np.random.RandomState(21)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64, prefill_chunk=8))
    for lens in ((5, 12, 21), (9, 3, 17)):
        eng.serve([rng.randint(1, 128, (n,)) for n in lens],
                  max_new_tokens=4)
    st = eng.stats()
    ticks = [e["args"] for e in eng.tracer.events()
             if e["name"] == "tick"]
    eng.shutdown()
    assert st["executables_compiled"] == 1
    assert st["prefill_compiles"] == 0
    assert ticks and all(t["path"] == "ragged" for t in ticks)

"""Batched multi-LoRA serving (ISSUE 18): AdapterPool lifecycle
(register / LRU residency / refcount pinning / eviction refusal /
int8 quant), grouped-matmul interpret-mode parity with the einsum
fallback, mixed-adapter batched decode greedy TOKEN-EXACT vs solo
per-adapter runs (Llama + GPT + lora_targets="all" + int8 KV pools +
spec-ngram + TP=2 + fused-decode interpret + the cluster, colocated
AND disaggregated), exactly ONE steady-state tick executable with
zero recompiles across adapter churn, the ``PADDLE_TPU_LORA=0`` kill
switch (bit-parity with ``lora_rank=0``), lifecycle edges
(unknown-adapter rejection, mid-request eviction blocked,
preempt-then-resume exactness, failure-drain adapter preservation),
and the loadgen ``by_adapter`` report.

Tier-1 guard: every test here must run in the standard
``-m 'not slow'`` sweep — ``test_tier1_no_slow_marker`` pins that.

Authoring note: adapter weights are drawn at N(0, 0.3) — at the tiny
model's scale, N(0, 0.05)-style deltas are too small to flip a greedy
argmax, and a LoRA test that never changes a token tests nothing.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.inference.cluster import ClusterConfig, EngineCluster
from paddle_tpu.inference.loadgen import SLO, run_load
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops import lora as _lora


@pytest.fixture(scope="module")
def llama_tiny():
    paddle.seed(7)
    # kv_heads=4 so tp_degree=2 divides evenly
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           kv_heads=4, ffn=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture(scope="module")
def gpt_tiny():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(11)
    m = GPTForCausalLM(GPTConfig.tiny(vocab=96, hidden=64, layers=2,
                                      heads=4))
    m.eval()
    return m


def _w(seed, rank=4, d=64, names=("q_proj", "k_proj", "v_proj",
                                  "o_proj")):
    """Leaf-name adapter weights (broadcast to every matching layer),
    N(0, 0.3) so greedy tokens actually move on the tiny model."""
    rng = np.random.RandomState(seed)
    out = {}
    for n in names:
        if n == "qkv_proj":                      # GPT fused QKV
            out[n] = (rng.normal(0, 0.3, (d, rank)).astype(np.float32),
                      rng.normal(0, 0.3,
                                 (rank, 3 * d)).astype(np.float32))
        else:
            out[n] = (rng.normal(0, 0.3, (d, rank)).astype(np.float32),
                      rng.normal(0, 0.3, (rank, d)).astype(np.float32))
    return out


_GPT_NAMES = ("qkv_proj", "out_proj")
_PROMPT_LENS = (9, 11, 7)


def _prompts(vocab=128, lens=_PROMPT_LENS, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, (n,)).astype(np.int64) for n in lens]


def _scfg(**kw):
    base = dict(num_slots=4, block_size=8, max_model_len=64,
                prefill_chunk=8, lora_rank=4, max_adapters=4,
                eos_token_id=None)
    base.update(kw)
    return ServingConfig(**base)


def _load(engine_or_cluster, names=("q_proj", "k_proj", "v_proj",
                                    "o_proj")):
    engine_or_cluster.load_adapter(1, _w(101, names=names))
    engine_or_cluster.load_adapter(2, _w(202, names=names))


def _serve_one(model, prompt, aid, max_new=6, names=("q_proj",
               "k_proj", "v_proj", "o_proj"), **cfg_kw):
    eng = ServingEngine(model, _scfg(**cfg_kw))
    _load(eng, names)
    rid = eng.submit(prompt.copy(), max_new, adapter_id=aid)
    done = eng.run()
    eng.shutdown()
    return done[rid]


# solo references are the dominant cost here: compute each ONCE per
# (model, config) workload and share across the batched / cluster /
# TP / spec tests that compare against the same solo runs
_SOLO = {}


def _solo_refs(model, key, max_new=6, names=("q_proj", "k_proj",
               "v_proj", "o_proj"), **cfg_kw):
    if key not in _SOLO:
        vocab = 96 if key.startswith("gpt") else 128
        prompts = _prompts(vocab=vocab)
        _SOLO[key] = [
            _serve_one(model, prompts[i], aid, max_new=max_new,
                       names=names, **cfg_kw)
            for i, aid in ((0, 1), (1, 2), (2, None))]
    return _SOLO[key]


def _batched(target, prompts, max_new=6,
             aids=(1, 2, None)):
    rids = [target.submit(p.copy(), max_new, adapter_id=a)
            for p, a in zip(prompts, aids)]
    done = target.run()
    return [done[r] for r in rids]


# ------------------------------------------------------------- pool units


def test_pool_lifecycle_lru_refcount_evict():
    specs = [("m.q_proj", "q_proj", 8, 8)]
    pool = _lora.AdapterPool(specs, 2, max_resident=2)
    for aid in (1, 2, 3):
        pool.register(aid, {"q_proj": (np.ones((8, 2), np.float32),
                                       np.ones((2, 8), np.float32))})
    assert pool.known(1) and not pool.known(9)
    assert pool.n_resident == 0 and pool.host_tier_bytes > 0
    r1 = pool.acquire(1)
    r2 = pool.acquire(2)
    assert r1 != r2 and 0 not in (r1, r2)       # row 0 = null adapter
    # window full, both pinned: a third tenant cannot seat
    assert pool.acquire(3) is None
    # mid-request eviction is refused while pinned
    with pytest.raises(ValueError, match="pinned"):
        pool.evict(1)
    # releasing 1 makes it the LRU victim for 3
    pool.release(1)
    r3 = pool.acquire(3)
    assert r3 == r1 and pool.swaps == 1
    assert not pool.resident(1) and pool.resident(3)
    # re-acquiring a resident adapter bumps the refcount, same row
    assert pool.acquire(2) == r2 and pool.refcount(2) == 2
    pool.release(2)
    pool.release(2)
    pool.evict(2)                               # unpinned: allowed
    assert pool.swaps == 2 and not pool.resident(2)
    with pytest.raises(KeyError):
        pool.acquire(9)


def test_pool_register_validation():
    specs = [("m.q_proj", "q_proj", 8, 8)]
    pool = _lora.AdapterPool(specs, 2, max_resident=2)
    with pytest.raises(ValueError, match="expects A"):
        pool.register(1, {"q_proj": (np.ones((4, 2), np.float32),
                                     np.ones((2, 8), np.float32))})
    with pytest.raises(ValueError, match="no target module"):
        pool.register(1, {"nope": (np.ones((8, 2), np.float32),
                                   np.ones((2, 8), np.float32))})
    # hot-reload: re-register while resident rewrites the stack row
    pool.register(1, {"q_proj": (np.ones((8, 2), np.float32),
                                 np.ones((2, 8), np.float32))})
    row = pool.acquire(1)
    v0 = pool.version
    pool.register(1, {"q_proj": (2 * np.ones((8, 2), np.float32),
                                 np.ones((2, 8), np.float32))})
    assert pool.version > v0
    np.testing.assert_array_equal(pool.operand()[0][0][row],
                                  2 * np.ones((8, 2), np.float32))


def test_pool_int8_quant_rows():
    rng = np.random.RandomState(0)
    A = rng.randn(8, 2).astype(np.float32)
    B = rng.randn(2, 8).astype(np.float32)
    pool = _lora.AdapterPool([("m.q_proj", "q_proj", 8, 8)], 2,
                             max_resident=2, quant=True)
    pool.register(1, {"q_proj": (A, B)})
    row = pool.acquire(1)
    aq, asc, bq, bsc = pool.operand()[0]
    assert aq.dtype == np.int8 and bq.dtype == np.int8
    # absmax int8: dequantized rows within half a quantization step
    np.testing.assert_allclose(aq[row].astype(np.float32) * asc[row],
                               A, atol=float(asc[row].max()) / 2 + 1e-7)
    np.testing.assert_allclose(bq[row].astype(np.float32) * bsc[row],
                               B, atol=float(bsc[row].max()) / 2 + 1e-7)
    # the null row stays an exact-zero delta
    assert not aq[0].any() and not bq[0].any()


def test_ragged_delta_gmm_interpret_matches_einsum():
    """The grouped-matmul kernel path (Pallas interpreter) is bitwise
    the einsum fallback at an aligned shape — batched-vs-solo
    exactness cannot depend on which backend ran."""
    import jax.numpy as jnp
    rng = np.random.RandomState(3)
    rows = jnp.asarray(rng.randn(8, 128), jnp.float32)
    ra = jnp.asarray(np.array([0, 2, 1, 1, 0, 2, 2, 1], np.int32))
    A = jnp.asarray(rng.randn(3, 128, 8), jnp.float32)
    B = jnp.asarray(rng.randn(3, 8, 128), jnp.float32)
    ref = _lora._ragged_delta(rows, ra, A, B, False)
    out = _lora._ragged_delta(rows, ra, A, B, "interpret")
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))


def test_use_lora_gmm_gate(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_LORA_GMM", "0")
    assert _lora._use_lora_gmm(8, 128, 8, 128) is False
    monkeypatch.setenv("PADDLE_TPU_LORA_GMM", "interpret")
    assert _lora._use_lora_gmm(8, 128, 8, 128) == "interpret"
    assert _lora._use_lora_gmm(8, 64, 8, 128) is False   # misaligned
    monkeypatch.setenv("PADDLE_TPU_LORA_GMM", "1")
    assert _lora._use_lora_gmm(8, 128, 8, 128) is False  # CPU backend


# ------------------------------------------- batched vs solo exactness


def test_batched_matches_solo_llama(llama_tiny):
    """The tentpole bar: one mixed-adapter ragged batch (tenant 1,
    tenant 2, base-model rider) is greedy token-exact vs three solo
    runs, through ONE tick executable."""
    refs = _solo_refs(llama_tiny, "llama")
    eng = ServingEngine(llama_tiny, _scfg())
    _load(eng)
    outs = _batched(eng, _prompts())
    st = eng.stats()
    eng.shutdown()
    for i, (got, ref) in enumerate(zip(outs, refs)):
        np.testing.assert_array_equal(got, ref,
                                      err_msg=f"request {i} diverged")
    assert st["executables_compiled"] == 1
    assert st["lora_enabled"] is True
    assert st["lora_adapters_resident"] == 2


def test_batched_matches_solo_llama_all_targets(llama_tiny):
    """lora_targets='all' routes MLP projections through the hook
    (incl. the fused down-proj epilogue fallback)."""
    names = ("q_proj", "o_proj", "gate_proj", "up_proj", "down_proj")
    # gate/up: [64 -> 4] A with [4 -> 128] B; down: [128 -> 64]
    rng = np.random.RandomState(77)

    def mk(seed):
        r = np.random.RandomState(seed)
        w = {}
        for n in names:
            d = 128 if n == "down_proj" else 64
            out = 128 if n in ("gate_proj", "up_proj") else 64
            w[n] = (r.normal(0, 0.3, (d, 4)).astype(np.float32),
                    r.normal(0, 0.3, (4, out)).astype(np.float32))
        return w

    del rng
    prompts = _prompts(lens=(9, 7))

    def solo(aid, p):
        eng = ServingEngine(llama_tiny, _scfg(lora_targets="all"))
        eng.load_adapter(1, mk(301))
        eng.load_adapter(2, mk(302))
        rid = eng.submit(p.copy(), 6, adapter_id=aid)
        done = eng.run()
        eng.shutdown()
        return done[rid]

    refs = [solo(1, prompts[0]), solo(2, prompts[1])]
    eng = ServingEngine(llama_tiny, _scfg(lora_targets="all"))
    eng.load_adapter(1, mk(301))
    eng.load_adapter(2, mk(302))
    outs = _batched(eng, prompts, aids=(1, 2))
    eng.shutdown()
    np.testing.assert_array_equal(outs[0], refs[0])
    np.testing.assert_array_equal(outs[1], refs[1])


def test_batched_matches_solo_gpt(gpt_tiny):
    """GPT's fused-QKV projection (one qkv_proj target, 3*d out) +
    out_proj, batched two tenants vs solo."""
    p = _prompts(vocab=96, lens=(9, 7))
    refs = [_serve_one(gpt_tiny, p[0], 1, names=_GPT_NAMES),
            _serve_one(gpt_tiny, p[1], 2, names=_GPT_NAMES)]
    eng = ServingEngine(gpt_tiny, _scfg())
    _load(eng, _GPT_NAMES)
    outs = _batched(eng, p, aids=(1, 2))
    st = eng.stats()
    eng.shutdown()
    for i, (got, ref) in enumerate(zip(outs, refs)):
        np.testing.assert_array_equal(got, ref,
                                      err_msg=f"gpt request {i}")
    assert st["executables_compiled"] == 1


def test_batched_matches_solo_int8_kv(llama_tiny):
    """Mixed-adapter batching composes with the int8 KV pool: both
    sides quantized, still token-exact."""
    p = _prompts(lens=(9, 7))
    refs = [_serve_one(llama_tiny, p[0], 1, kv_cache_dtype="int8"),
            _serve_one(llama_tiny, p[1], 2, kv_cache_dtype="int8")]
    eng = ServingEngine(llama_tiny, _scfg(kv_cache_dtype="int8"))
    _load(eng)
    outs = _batched(eng, p, aids=(1, 2))
    eng.shutdown()
    for got, ref in zip(outs, refs):
        np.testing.assert_array_equal(got, ref)


def test_spec_ngram_lora_token_exact(llama_tiny):
    """Greedy n-gram speculation under LoRA is token-exact vs the
    PLAIN LoRA solo runs (greedy spec == plain decode by
    construction — pinned in test_speculative.py)."""
    refs = _solo_refs(llama_tiny, "llama")
    eng = ServingEngine(llama_tiny, _scfg(num_speculative_tokens=2))
    _load(eng)
    outs = _batched(eng, _prompts())
    eng.shutdown()
    for got, ref in zip(outs, refs):
        np.testing.assert_array_equal(got, ref)


def test_tp2_lora_token_exact(llama_tiny):
    """TP=2 sharded mixed-adapter batch vs the single-device LoRA
    solo runs (the engine pins the einsum delta path under GSPMD)."""
    refs = _solo_refs(llama_tiny, "llama")
    eng = ServingEngine(llama_tiny, _scfg(tp_degree=2))
    _load(eng)
    outs = _batched(eng, _prompts())
    st = eng.stats()
    eng.shutdown()
    assert st["tp_degree"] == 2
    for got, ref in zip(outs, refs):
        np.testing.assert_array_equal(got, ref)


def test_fused_decode_modes_agree(llama_tiny, monkeypatch):
    """The fused decode tick composes with the LoRA hook: interpret-
    mode fused kernels and the unfused graph emit identical tokens
    for the same mixed-adapter batch."""
    outs = {}
    for mode in ("0", "interpret"):
        monkeypatch.setenv("PADDLE_TPU_FUSED_DECODE", mode)
        eng = ServingEngine(llama_tiny, _scfg())
        _load(eng)
        outs[mode] = _batched(eng, _prompts())
        eng.shutdown()
    monkeypatch.delenv("PADDLE_TPU_FUSED_DECODE")
    for got, ref in zip(outs["interpret"], outs["0"]):
        np.testing.assert_array_equal(got, ref)


def test_lora_quant_pool_batched_matches_solo(llama_tiny):
    """lora_quant=True (int8 adapter stacks): solo and batched run
    the SAME dequantized weights, so exactness still holds."""
    p = _prompts(lens=(9, 11))
    ref = _serve_one(llama_tiny, p[0], 1, lora_quant=True)
    eng = ServingEngine(llama_tiny, _scfg(lora_quant=True))
    _load(eng)
    outs = _batched(eng, p, aids=(1, 2))
    eng.shutdown()
    np.testing.assert_array_equal(outs[0], ref)


# ----------------------------------------------- churn + kill switches


def test_adapter_churn_zero_recompiles(llama_tiny):
    """The perf claim: churning 4 adapters through a 2-row resident
    window (LRU spill to the host tier and back) never recompiles —
    the tick executable count stays at 1 — and a spilled adapter
    re-seated later reproduces its tokens exactly."""
    eng = ServingEngine(llama_tiny, _scfg(max_adapters=2))
    for aid in (1, 2, 3, 4):
        eng.load_adapter(aid, _w(100 + aid))
    p = _prompts(lens=(9,))[0]
    first = {}
    for aid in (1, 2, 3, 4):
        rid = eng.submit(p.copy(), 6, adapter_id=aid)
        first[aid] = eng.run()[rid]
    st = eng.stats()
    assert st["executables_compiled"] == 1, "adapter churn recompiled"
    assert st["lora_adapter_swaps"] >= 2
    assert st["lora_host_tier_bytes"] > 0
    # churn BACK to the evicted first tenant: same tokens, still 1 exe
    rid = eng.submit(p.copy(), 6, adapter_id=1)
    again = eng.run()[rid]
    st = eng.stats()
    eng.shutdown()
    np.testing.assert_array_equal(again, first[1])
    assert st["executables_compiled"] == 1
    # distinct tenants decode distinct continuations
    assert len({tuple(v.tolist()) for v in first.values()}) > 1


def test_unknown_adapter_rejected(llama_tiny):
    eng = ServingEngine(llama_tiny, _scfg())
    _load(eng)
    with pytest.raises(ValueError, match="unknown adapter_id"):
        eng.submit(_prompts()[0], 4, adapter_id=7)
    eng.shutdown()
    # an engine without LoRA configured rejects adapter submits too
    base = ServingEngine(llama_tiny, _scfg(lora_rank=0))
    with pytest.raises(ValueError, match="lora_rank"):
        base.submit(_prompts()[0], 4, adapter_id=1)
    base.shutdown()


def test_kill_switch_bit_parity(llama_tiny, monkeypatch):
    """PADDLE_TPU_LORA=0 beats ServingConfig(lora_rank=4): the engine
    builds the bit-identical base tick (same tokens as lora_rank=0),
    reports lora off, and rejects adapter submits."""
    prompts = _prompts(lens=(9, 7))
    base = ServingEngine(llama_tiny, _scfg(lora_rank=0))
    ref = base.serve([p.copy() for p in prompts], max_new_tokens=6)
    base.shutdown()
    monkeypatch.setenv("PADDLE_TPU_LORA", "0")
    eng = ServingEngine(llama_tiny, _scfg())
    outs = eng.serve([p.copy() for p in prompts], max_new_tokens=6)
    st = eng.stats()
    with pytest.raises(ValueError):
        eng.submit(prompts[0], 4, adapter_id=1)
    with pytest.raises(ValueError):
        eng.load_adapter(1, _w(101))
    eng.shutdown()
    assert st["lora_enabled"] is False
    assert st["lora_adapters_resident"] == 0
    for got, want in zip(outs, ref):
        np.testing.assert_array_equal(got, want)


def test_stats_keys_always_present(llama_tiny):
    """The four lora_* stats keys ride every engine's stats() — LoRA
    configured or not — so dashboards never key-error."""
    eng = ServingEngine(llama_tiny, _scfg(lora_rank=0))
    st = eng.stats()
    eng.shutdown()
    assert st["lora_enabled"] is False
    assert st["lora_adapters_resident"] == 0
    assert st["lora_adapter_swaps"] == 0
    assert st["lora_host_tier_bytes"] == 0


# --------------------------------------------------- lifecycle edges


def test_evict_blocked_mid_request(llama_tiny):
    """An adapter serving an in-flight slot is refcount-pinned: evict
    refuses until the request retires, then succeeds."""
    eng = ServingEngine(llama_tiny, _scfg())
    _load(eng)
    eng.submit(_prompts()[0], 8, adapter_id=1)
    for _ in range(3):          # admit + a few ticks: pinned now
        eng.step()
    assert eng._lora_pool.refcount(1) == 1
    with pytest.raises(ValueError, match="pinned"):
        eng._lora_pool.evict(1)
    eng.run()                   # retire -> released (stays resident)
    assert eng._lora_pool.refcount(1) == 0
    eng._lora_pool.evict(1)
    assert not eng.adapter_resident(1)
    eng.shutdown()


def test_preempt_resume_lora_token_exact(llama_tiny):
    """A preempted-then-resumed LoRA request keeps its adapter across
    the spill (the pin is released at preemption and re-acquired at
    resume) and stays token-exact vs a never-preempted run."""
    rng = np.random.RandomState(5)
    lo = rng.randint(1, 128, (20,)).astype(np.int64)
    h1 = rng.randint(1, 128, (9,)).astype(np.int64)
    h2 = rng.randint(1, 128, (7,)).astype(np.int64)
    kw = dict(num_slots=2, max_model_len=96)
    # never-preempted reference: ample slots, zero contention
    ref_eng = ServingEngine(llama_tiny, _scfg(num_slots=4,
                                              max_model_len=96))
    _load(ref_eng)
    r = [ref_eng.submit(p.copy(), 12, adapter_id=a)
         for p, a in ((lo, 1), (h1, 2), (h2, None))]
    ref_done = ref_eng.run()
    ref_eng.shutdown()
    # contention run: the low-priority LoRA request streams alone,
    # then two high-priority arrivals preempt it
    eng = ServingEngine(llama_tiny, _scfg(**kw))
    _load(eng)
    rids = [eng.submit(lo.copy(), 12, adapter_id=1, priority=0)]
    for _ in range(4):
        eng.step()
    rids.append(eng.submit(h1.copy(), 12, adapter_id=2, priority=2))
    rids.append(eng.submit(h2.copy(), 12, priority=2))
    done = eng.run()
    st = eng.stats()
    eng.shutdown()
    assert st["preemptions"] >= 1, "workload never preempted"
    for rid, ref_rid in zip(rids, r):
        np.testing.assert_array_equal(done[rid], ref_done[ref_rid])


# ------------------------------------------------------------- cluster


def test_cluster_colocated_and_failure_drain(llama_tiny):
    """Routed mixed-adapter serving across 2 replicas is token-exact
    vs solo, rolls the lora_* stats up, and a failure drain requeues
    a queued request WITH its adapter id onto the survivor."""
    refs = _solo_refs(llama_tiny, "llama")
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg())
    _load(cl)
    outs = _batched(cl, _prompts())
    st = cl.stats()
    cl.shutdown()
    for got, ref in zip(outs, refs):
        np.testing.assert_array_equal(got, ref)
    assert st["lora_enabled"] is True
    assert st["lora_adapters_resident"] >= 2
    assert "lora_adapter_swaps" in st and "lora_host_tier_bytes" in st
    # failure drain BEFORE any tick: all requests still queued, so
    # every one re-routes (with its adapter) and completes exactly
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg())
    _load(cl)
    rids = [cl.submit(p.copy(), 6, adapter_id=a)
            for p, a in zip(_prompts(), (1, 2, None))]
    cl.fail_replica(0)
    done = cl.run()
    cl.shutdown()
    for rid, ref in zip(rids, refs):
        np.testing.assert_array_equal(done[rid], ref)


def test_cluster_disaggregated_lora(llama_tiny):
    """Disaggregated prefill -> decode handoffs carry the adapter id:
    the prefill tier computes adapter-colored prompt KV on its ragged
    tick and the decode replica re-pins the same adapter."""
    refs = _solo_refs(llama_tiny, "llama")
    cl = EngineCluster(llama_tiny,
                       ClusterConfig(num_replicas=1,
                                     prefill_replicas=1),
                       _scfg())
    _load(cl)
    outs = _batched(cl, _prompts())
    cl.shutdown()
    for i, (got, ref) in enumerate(zip(outs, refs)):
        np.testing.assert_array_equal(
            got, ref, err_msg=f"disaggregated request {i}")


# ------------------------------------------------------------- loadgen


def test_loadgen_by_adapter(llama_tiny, tmp_path):
    """adapter_ids= forwards to submit(adapter_id=), the report gains
    a by_adapter breakdown (base rows under 'base'), and NDJSON rows
    carry the adapter field."""
    eng = ServingEngine(llama_tiny, _scfg())
    _load(eng)
    prompts = _prompts(lens=(9, 11, 7, 5))
    path = str(tmp_path / "records.ndjson")
    rep = run_load(eng, prompts, mode="closed", concurrency=4,
                   max_new_tokens=4, slo=SLO(ttft_ms=1e6, itl_ms=1e6),
                   adapter_ids=[1, 2, None, 1], record_path=path)
    eng.shutdown()
    assert rep["completed"] == 4
    assert set(rep["by_adapter"]) == {"1", "2", "base"}
    assert rep["by_adapter"]["1"]["requests"] == 2
    assert rep["by_adapter"]["base"]["goodput"] == 1.0
    rows = [json.loads(l) for l in open(path)]
    assert sorted(r["adapter"] for r in rows
                  if r["adapter"] is not None) == [1, 1, 2]
    assert sum(r["adapter"] is None for r in rows) == 1
    # length mismatch is rejected up front
    with pytest.raises(ValueError, match="adapter_ids"):
        run_load(eng, prompts, mode="closed", concurrency=4,
                 adapter_ids=[1])


# ---------------------------------------------------------- tier-1 pin


def test_tier1_no_slow_marker():
    """CI satellite: this file must run in the standard tier-1 sweep —
    no test here may carry (or be conftest-assigned) the slow marker,
    and the interpret-mode kernel parity test must be present."""
    import conftest
    here = open(__file__).read()
    assert "pytest.mark.slow" not in here.replace(
        '"pytest.mark.slow"', "")
    names = [ln.split("(")[0][4:] for ln in here.splitlines()
             if ln.startswith("def test_")]
    assert "test_ragged_delta_gmm_interpret_matches_einsum" in names
    overlap = set(names) & set(conftest._SLOW_TESTS)
    assert not overlap, f"tier-1 lora tests marked slow: {overlap}"

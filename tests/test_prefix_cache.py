"""Prefix-cached paged KV + chunked prefill (ISSUE 5): content-hashed
block reuse (refcounts, hash->block index, LRU eviction), copy-on-write
on shared-block appends, the ONE fixed-chunk prefill executable
(zero steady-state prefill recompiles), greedy token exactness with
prefix caching ON vs OFF (Llama / GPT / int8 / speculative), the
chunk-attention kernel in interpret mode, the kill switch
(``PADDLE_TPU_PREFIX_CACHE=0``), and ``BlockAllocator.check_leaks`` at
engine shutdown.

Tier-1 guard: every test here must run in the standard
``-m 'not slow'`` sweep — ``test_tier1_no_slow_marker`` pins that.
"""

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


def _mk_engine(model, **kw):
    base = dict(num_slots=2, block_size=8, max_model_len=96,
                prefill_chunk=8)
    base.update(kw)
    return ServingEngine(model, ServingConfig(**base))


def _shared_prefix_prompts(rng, vocab=128, prefix_len=24,
                           tails=(5, 9, 3)):
    sysp = rng.randint(1, vocab, (prefix_len,))
    return [np.concatenate([sysp, rng.randint(1, vocab, (t,))])
            for t in tails]


# ----------------------------------------------------------- allocator
# refcount / hash-index / LRU invariants


def test_allocator_refcount_publish_lru_property():
    """Random interleaving of alloc / ref / free / publish never leaks
    a block, never frees a block with live references, and keeps the
    free + cached + referenced partition exact (check_leaks passes at
    every quiescent point)."""
    from paddle_tpu.ops.paged_cache import BlockAllocator, chain_hashes
    rng = np.random.RandomState(0)
    a = BlockAllocator(17)                  # blocks 1..16
    live = {}                               # block -> our refcount
    published = {}                          # hash -> block
    next_tag = [0]

    def fresh_hash():
        next_tag[0] += 1
        return chain_hashes(b"prop", [next_tag[0]] * 4, 4)[0]

    for _ in range(400):
        op = rng.randint(4)
        if op == 0 and a.free_blocks:       # alloc 1..3
            n = min(1 + rng.randint(3), a.free_blocks)
            for b in a.alloc(n):
                live[b] = live.get(b, 0) + 1
        elif op == 1 and live:              # free one reference
            b = list(live)[rng.randint(len(live))]
            a.free([b])
            live[b] -= 1
            if not live[b]:
                del live[b]
        elif op == 2 and live:              # publish a live block
            b = list(live)[rng.randint(len(live))]
            h = fresh_hash()
            if a.publish(b, h):
                published[h] = b
        elif op == 3 and published:         # lookup + ref a cached one
            h = list(published)[rng.randint(len(published))]
            b = a.lookup(h)
            if b is not None:
                a.ref(b)
                live[b] = live.get(b, 0) + 1
        # prune published entries the LRU has evicted
        published = {h: b for h, b in published.items()
                     if a.lookup(h) == b}
        a.check_leaks(live)
    # over-freeing must be rejected while references are consistent
    if live:
        b = next(iter(live))
        a.free([b] * live.pop(b))
        with pytest.raises(ValueError, match="double free"):
            a.free([b])


def test_allocator_eviction_is_lru_ordered():
    from paddle_tpu.ops.paged_cache import BlockAllocator
    a = BlockAllocator(5)                   # 4 usable
    blocks = a.alloc(4)
    for i, b in enumerate(blocks):
        a.publish(b, bytes([i]))
    # free in a known order -> cache order b0, b1, b2, b3 (b0 oldest)
    for b in blocks:
        a.free([b])
    assert a.cached_blocks == 4 and a.free_blocks == 4
    got = a.alloc(2)                        # evicts the two oldest
    assert a.evictions == 2
    assert a.lookup(bytes([0])) is None
    assert a.lookup(bytes([1])) is None
    assert a.lookup(bytes([2])) == blocks[2]
    assert a.lookup(bytes([3])) == blocks[3]
    assert sorted(got) == sorted(blocks[:2])


def test_chain_hashes_prefix_sensitivity():
    """Equal hashes must imply equal prefixes THROUGH the block: a
    change anywhere earlier changes every later hash (and the seed
    partitions models)."""
    from paddle_tpu.ops.paged_cache import chain_hashes
    toks = list(range(40))
    h = chain_hashes(b"m1", toks, 8)
    assert len(h) == 5                      # full blocks only
    assert chain_hashes(b"m1", toks[:17], 8) == h[:2]
    mut = list(toks)
    mut[3] += 1                             # early mutation
    h2 = chain_hashes(b"m1", mut, 8)
    assert all(x != y for x, y in zip(h, h2))
    assert chain_hashes(b"m2", toks, 8)[0] != h[0]


def test_write_tokens_overflow_routes_to_null_block():
    """Chunk-prefill pad positions past the table's reach must land in
    the null block, NOT clamp onto the slot's last real block."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    rng = np.random.RandomState(3)
    S, T, H, D, BS, MB = 1, 6, 2, 4, 4, 2
    kp, vp = pc.init_pool(1 + MB, BS, H, D, jnp.float32)
    tables = jnp.asarray([[1, 2]], jnp.int32)
    k = jnp.asarray(rng.randn(S, T, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(S, T, H, D), jnp.float32)
    # write starts at position 5: tokens land at 5..10 but the table
    # only covers 8 positions — 6..7 in-table, 8..10 overflow
    kp2, _ = pc.write_tokens(kp, vp, tables, jnp.asarray([5]), k, v)
    dense = np.asarray(pc.gather_dense(kp2, tables))[0]
    np.testing.assert_array_equal(dense[5], np.asarray(k[0, 0]))
    np.testing.assert_array_equal(dense[7], np.asarray(k[0, 2]))
    # block 1 position 0..1 (cache positions 4 and the like) untouched
    assert not dense[:5].any()
    # the overflow went to block 0 (null), never to blocks 1/2
    assert np.asarray(kp2)[0].any()


# ------------------------------------------------- engine-level reuse +
# copy-on-write + eviction


def test_prefix_reuse_and_exactness_shared_system_prompt(llama_tiny):
    """The headline behavior: requests sharing a system prompt reuse
    its blocks (hit rate > 0, suffix-only prefill) and the greedy
    tokens are EXACTLY the cold-cache outputs."""
    rng = np.random.RandomState(0)
    prompts = _shared_prefix_prompts(rng)
    cold = _mk_engine(llama_tiny, enable_prefix_cache=False)
    want = cold.serve(list(prompts), max_new_tokens=6)
    want += cold.serve(list(prompts), max_new_tokens=6)
    cold.shutdown()
    assert cold.stats()["prefix_tokens_reused"] == 0

    eng = _mk_engine(llama_tiny)
    got = eng.serve(list(prompts), max_new_tokens=6)
    got += eng.serve(list(prompts), max_new_tokens=6)
    st = eng.stats()
    eng.shutdown()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert st["prefix_blocks_reused"] > 0
    assert st["prefix_tokens_reused"] > 0
    assert 0.0 < st["prefix_hit_rate"] < 1.0
    assert st["cached_blocks"] > 0
    # one engine, ONE executable total — the ragged step subsumed the
    # prefill path entirely (no separate chunk exec, no bucket zoo)
    assert st["executables_compiled"] == 1
    assert st["prefill_compiles"] == 0


def test_cow_never_mutates_shared_block(llama_tiny):
    """A full-prompt hit appends the recomputed last token into a
    SHARED block: the engine must COW-duplicate it — the published
    block's bytes are identical before and after the reusing
    request."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    rng = np.random.RandomState(5)
    prompt = rng.randint(1, 128, (16,))     # exact block multiple
    eng = _mk_engine(llama_tiny, num_slots=1)
    (r1,) = eng.serve([prompt], max_new_tokens=4)
    assert eng.stats()["cow_copies"] == 0
    # the prompt's two full blocks are now published + cached
    hashes = pc.chain_hashes(eng._fp, prompt, eng._bs)
    shared = [eng._alloc.lookup(h) for h in hashes]
    assert all(b is not None for b in shared)
    before = [np.asarray(eng._pools[0][0][b]).copy() for b in shared]
    (r2,) = eng.serve([prompt], max_new_tokens=4)
    st = eng.stats()
    eng.shutdown()
    np.testing.assert_array_equal(r1, r2)
    assert st["cow_copies"] >= 1, "full-prompt hit must COW"
    after = [np.asarray(eng._pools[0][0][b]) for b in shared]
    for b, x, y in zip(shared, before, after):
        np.testing.assert_array_equal(x, y), f"shared block {b} mutated"


def test_eviction_under_pressure_admission_succeeds(llama_tiny):
    """A pool too small to hold the cache + a new request must evict
    LRU cached blocks transparently — admission never fails and the
    drained pool accounts for every block."""
    rng = np.random.RandomState(6)
    eng = _mk_engine(llama_tiny, num_slots=1, max_model_len=48,
                     num_blocks=9)
    for _ in range(6):                       # distinct prompts: the
        eng.serve([rng.randint(1, 128, (17,))],  # cache fills + churns
                  max_new_tokens=4)
    st = eng.stats()
    eng.shutdown()                           # check_leaks inside
    assert st["cache_evictions"] > 0, "pressure must evict"
    assert st["requests_completed"] == 6
    assert st["free_blocks"] == 8            # free + cached, no leaks
    assert st["reserved_blocks"] == 0


def test_scheduler_property_with_prefix_cache(llama_tiny):
    """The PR-3 scheduler property, now with shared prefixes + block
    sharing in play: every request completes exactly once under slot +
    block pressure, streamed == returned, allocator drains clean."""
    rng = np.random.RandomState(1)
    sysp = rng.randint(1, 128, (16,))
    streamed = {}
    eng = ServingEngine(
        llama_tiny,
        ServingConfig(num_slots=2, block_size=8, max_model_len=48,
                      num_blocks=15, prefill_chunk=8),
        stream_callback=lambda rid, t: streamed.setdefault(rid, [])
        .append(t))
    rids, news = [], [4, 7, 1, 5, 3, 8, 2, 6]
    for n, mn in zip([3, 18, 6, 17, 20, 2, 19, 5], news):
        p = np.concatenate([sysp, rng.randint(1, 128, (n,))]) \
            if n >= 16 else rng.randint(1, 128, (n,))
        rids.append(eng.submit(p, mn))
    done = eng.run()
    st = eng.stats()
    eng.shutdown()
    assert sorted(done) == sorted(rids)
    for rid, mn in zip(rids, news):
        assert 1 <= len(done[rid]) <= mn
        assert streamed[rid] == list(done[rid])
    assert st["active"] == 0 and st["queued"] == 0
    assert st["reserved_blocks"] == 0
    assert st["free_blocks"] == 14, "block leak (free + cached)"


# --------------------------------------------- exactness across models,
# speculative decoding, and the interleaved scheduler


def test_prefix_exactness_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(3)
    m = GPTForCausalLM(GPTConfig.tiny(vocab=96, hidden=64, layers=2,
                                      heads=4))
    m.eval()
    rng = np.random.RandomState(2)
    prompts = _shared_prefix_prompts(rng, vocab=96)
    cold = _mk_engine(m, enable_prefix_cache=False)
    want = cold.serve(list(prompts), max_new_tokens=4)
    want += cold.serve(list(prompts), max_new_tokens=4)
    eng = _mk_engine(m)
    got = eng.serve(list(prompts), max_new_tokens=4)
    got += eng.serve(list(prompts), max_new_tokens=4)
    st = eng.stats()
    eng.shutdown()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert st["prefix_tokens_reused"] > 0


def test_prefix_exactness_int8():
    from paddle_tpu.nn.quant import quantize_for_inference
    paddle.seed(11)
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           kv_heads=2, ffn=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    quantize_for_inference(m)
    rng = np.random.RandomState(9)
    prompts = _shared_prefix_prompts(rng)
    cold = _mk_engine(m, enable_prefix_cache=False)
    want = cold.serve(list(prompts), max_new_tokens=4)
    want += cold.serve(list(prompts), max_new_tokens=4)
    eng = _mk_engine(m)
    got = eng.serve(list(prompts), max_new_tokens=4)
    got += eng.serve(list(prompts), max_new_tokens=4)
    st = eng.stats()
    eng.shutdown()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert st["prefix_tokens_reused"] > 0


def test_prefix_exactness_with_speculative(llama_tiny):
    """Shared prefix + the speculative verify/rollback machinery: the
    greedy stream must match prefix caching OFF token for token, while
    blocks are actually being reused (the rollback-garbage-vs-publish
    interplay: only positions < cache_len are ever hashed)."""
    rng = np.random.RandomState(4)
    pattern = rng.randint(1, 128, (8,))
    sysp = np.tile(pattern, 3)               # repetitive -> drafts hit
    prompts = [np.concatenate([sysp, rng.randint(1, 128, (t,))])
               for t in (4, 7)]
    cold = _mk_engine(llama_tiny, enable_prefix_cache=False,
                      num_speculative_tokens=3)
    want = cold.serve(list(prompts), max_new_tokens=8)
    want += cold.serve(list(prompts), max_new_tokens=8)
    eng = _mk_engine(llama_tiny, num_speculative_tokens=3)
    got = eng.serve(list(prompts), max_new_tokens=8)
    got += eng.serve(list(prompts), max_new_tokens=8)
    st = eng.stats()
    eng.shutdown()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert st["prefix_blocks_reused"] > 0


def test_prefill_row_budget_spreads_prompt_same_tokens(llama_tiny):
    """A small ``ragged_prefill_rows`` spreads a prompt's rows across
    many more engine ticks (decode keeps running for admitted slots)
    without changing a single emitted token."""
    rng = np.random.RandomState(8)
    lens = (21, 5, 33, 9)
    prompts = [rng.randint(1, 128, (n,)) for n in lens]
    wide = _mk_engine(llama_tiny)
    want = wide.serve(list(prompts), max_new_tokens=5)
    st_wide = wide.stats()
    wide.shutdown()
    eng = _mk_engine(llama_tiny, ragged_prefill_rows=3)
    got = eng.serve(list(prompts), max_new_tokens=5)
    st = eng.stats()
    eng.shutdown()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    # at most 3 prompt rows ride a tick: every prompt takes at least
    # ceil(n / 3) of them, where the default budget takes ceil(n / 8)
    assert st["prefill_chunks"] >= sum(-(-n // 3) for n in lens)
    assert st["prefill_chunks"] > st_wide["prefill_chunks"]
    assert st["requests_completed"] == 4


# ------------------------------------------ one executable + kill
# switch


def test_zero_steadystate_prefill_recompiles(llama_tiny):
    """The tentpole compile bar: ONE chunked-prefill executable serves
    every prompt length — across waves of varied lengths the per-engine
    prefill compile count stays at 1 (and decode at 1)."""
    rng = np.random.RandomState(2)
    eng = _mk_engine(llama_tiny)
    eng.serve([rng.randint(1, 128, (n,)) for n in (4, 9, 23)],
              max_new_tokens=4)
    st0 = eng.stats()
    assert st0["executables_compiled"] == 1
    eng.serve([rng.randint(1, 128, (n,)) for n in (13, 2, 31, 7)],
              max_new_tokens=5)
    st1 = eng.stats()
    eng.shutdown()
    assert st1["executables_compiled"] == 1, \
        "steady-state recompile (ragged step must stay ONE executable)"
    assert st1["decode_compiles"] == 1
    assert st1["prefill_chunks"] > st0["prefill_chunks"]


def test_draft_model_prefill_is_one_executable(llama_tiny):
    """With a draft model the old path compiled a prefill zoo PER
    MODEL; chunked prefill is exactly two executables (target +
    draft), and greedy tokens still match the cold path."""
    paddle.seed(13)
    draft = LlamaForCausalLM(LlamaConfig.tiny(
        vocab=128, hidden=32, layers=1, heads=2, kv_heads=2, ffn=64))
    draft.eval()
    rng = np.random.RandomState(3)
    sysp = rng.randint(1, 128, (16,))
    prompts = [np.concatenate([sysp, rng.randint(1, 128, (t,))])
               for t in (5, 11)]

    def build(**kw):
        return ServingEngine(
            llama_tiny,
            ServingConfig(num_slots=2, block_size=8, max_model_len=96,
                          prefill_chunk=8, num_speculative_tokens=2,
                          drafter="model", **kw),
            draft_model=draft)

    cold = build(enable_prefix_cache=False)
    want = cold.serve(list(prompts), max_new_tokens=6)
    want += cold.serve(list(prompts), max_new_tokens=6)
    eng = build()
    got = eng.serve(list(prompts), max_new_tokens=6)
    got += eng.serve(list(prompts), max_new_tokens=6)
    st = eng.stats()
    eng.shutdown()
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    # ragged step + fused draft step (prime + proposal scan): exactly
    # two executables, down from the per-model zoo
    assert st["executables_compiled"] == 2
    assert st["prefix_blocks_reused"] > 0


def test_kill_switch_prefix_cache(llama_tiny, monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_PREFIX_CACHE", "0")
    rng = np.random.RandomState(0)
    prompts = _shared_prefix_prompts(rng)
    eng = _mk_engine(llama_tiny)             # config asks for caching
    eng.serve(list(prompts), max_new_tokens=4)
    eng.serve(list(prompts), max_new_tokens=4)
    st = eng.stats()
    eng.shutdown()
    assert st["prefix_cache_enabled"] is False
    assert st["prefix_blocks_reused"] == 0
    assert st["cached_blocks"] == 0
    assert st["prefill_chunks"] > 0          # chunking unaffected


# -------------------------------------------- kernel parity + telemetry


def test_chunk_attention_kernel_matches_fallback_interpret():
    """Tier-1 guard: the multi-query kernel at CHUNK width (T = chunk
    rows, nonzero prior cached context — exactly the chunked-prefill
    shape) agrees with the gather fallback in interpret mode."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    from paddle_tpu.ops.pallas import paged_attention as pa
    if pa.pallas_paged_verify_attention is None:
        pytest.skip("pallas unavailable on this jax build")
    rng = np.random.RandomState(0)
    S, T, H, Hkv, D, BS, MB = 2, 8, 8, 4, 64, 8, 6
    NB = 1 + S * MB
    kp = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
    vp = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
    tables = np.zeros((S, MB), np.int32)
    # lens + 1 = chunk starts: one mid-prompt, one continuing a long
    # cached prefix (the prefix-reuse regime)
    lens = np.asarray([6, 25], np.int32)
    alloc = pc.BlockAllocator(NB)
    for s in range(S):
        n = pc.blocks_for(int(lens[s]) + T - 1, BS)
        tables[s, :n] = alloc.alloc(n)
    q = jnp.asarray(rng.randn(S, T, H, D), jnp.float32)
    ref = pa._xla_paged_verify(q, kp, vp, jnp.asarray(tables),
                               jnp.asarray(lens))
    out = pa.pallas_paged_verify_attention(
        q, kp, vp, jnp.asarray(tables), jnp.asarray(lens),
        interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_prefix_telemetry_in_stats_and_jsonl(tmp_path, llama_tiny):
    import json
    rng = np.random.RandomState(12)
    prompts = _shared_prefix_prompts(rng)
    eng = _mk_engine(llama_tiny)
    eng.serve(list(prompts), max_new_tokens=4)
    eng.serve(list(prompts), max_new_tokens=4)
    st = eng.stats()
    eng.shutdown()
    for k in ("prefix_blocks_reused", "prefix_tokens_reused",
              "prefix_hit_rate", "cow_copies", "cache_evictions",
              "cached_blocks", "prefill_compiles", "prefill_chunks"):
        assert k in st
    path = monitor.export_jsonl(str(tmp_path / "metrics.jsonl"))
    names = {json.loads(line)["name"] for line in open(path)}
    for want in ("serving_prefix_blocks_reused",
                 "serving_prefix_tokens_reused", "serving_cow_copies",
                 "serving_cache_evictions", "serving_prefix_hit_rate"):
        assert want in names, f"{want} missing from JSONL export"


def test_tier1_no_slow_marker():
    """CI guard (the PR-4 pattern): every prefix-cache test runs in the
    tier-1 ``-m 'not slow'`` sweep, the chunk-attention kernel parity
    test exists, and engine shutdown leak-checking is exercised."""
    import tests.conftest as c
    here = open(__file__).read()
    assert "pytest.mark.slow" not in here.replace(
        '"pytest.mark.slow"', "")
    names = [ln.split("(")[0][4:] for ln in here.splitlines()
             if ln.startswith("def test_")]
    overlap = set(names) & set(c._SLOW_TESTS)
    assert not overlap, f"tier-1 prefix-cache tests marked slow: " \
                        f"{overlap}"
    assert "test_chunk_attention_kernel_matches_fallback_interpret" \
        in names
    assert here.count(".shutdown()") >= 10, \
        "engine shutdown (check_leaks) must guard these tests"

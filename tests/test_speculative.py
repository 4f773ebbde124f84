"""Speculative decoding on the paged KV cache (ISSUE 4): multi-query
verify kernel interpret-mode parity, O(1) rollback correctness
(lengths/blocks/tables vs a from-scratch prefill), greedy token
exactness vs plain ``generate()`` (Llama + GPT + int8 + the serving
engine), rejection-sampling distribution soundness (chi-squared), the
n-gram drafter, zero steady-state recompiles, and the kill switch.

Tier-1 guard: every test here must run in the standard
``-m 'not slow'`` sweep — ``test_tier1_no_slow_marker`` pins that.
"""
import os

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


@pytest.fixture
def llama_draft():
    """A smaller compatible model drafting for ``llama_tiny`` (same
    vocab, half the width, one layer)."""
    paddle.seed(13)
    cfg = LlamaConfig.tiny(vocab=128, hidden=32, layers=1, heads=2,
                           kv_heads=2, ffn=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _ref(model, prompt, n, **kw):
    out, sc = model.generate(
        paddle.to_tensor(np.asarray(prompt, np.int64)[None]),
        max_new_tokens=n, **kw)
    return np.asarray(out.numpy())[0], np.asarray(sc.numpy())[0]


# ------------------------------------------------------------ multi-query
# verify kernel + cache primitives


def test_verify_kernel_matches_fallback_interpret():
    """Tier-1 guard: the multi-query Pallas verify kernel (interpret
    mode under JAX_PLATFORMS=cpu) agrees with the gather fallback on
    ragged lengths + GQA + a causal window."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    from paddle_tpu.ops.pallas import paged_attention as pa
    if pa.pallas_paged_verify_attention is None:
        pytest.skip("pallas unavailable on this jax build")
    rng = np.random.RandomState(0)
    S, T, H, Hkv, D, BS, MB = 3, 4, 8, 4, 64, 8, 5
    NB = 1 + S * MB
    kp = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
    vp = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
    tables = np.zeros((S, MB), np.int32)
    lens = np.asarray([5, 17, 29], np.int32)
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


def test_verify_window_rows_match_single_token_decode():
    """Window token t must see exactly ``lens + t`` positions: each row
    of the multi-query fallback equals a single-token decode at that
    bound — BITWISE, which is what makes greedy acceptance
    token-exact."""
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    rng = np.random.RandomState(1)
    S, T, H, Hkv, D, BS, MB = 2, 3, 4, 2, 16, 8, 4
    NB = 1 + S * MB
    kp = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
    vp = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
    tables = jnp.asarray(
        (1 + np.arange(S * MB, dtype=np.int32)).reshape(S, MB))
    lens = jnp.asarray([6, 11], jnp.int32)
    q = jnp.asarray(rng.randn(S, T, H, D), jnp.float32)
    win = pa._xla_paged_verify(q, kp, vp, tables, lens)
    for t in range(T):
        one = pa._xla_paged_attention(q[:, t], kp, vp, tables, lens + t)
        np.testing.assert_array_equal(np.asarray(win[:, t]),
                                      np.asarray(one))


def test_write_tokens_matches_sequential_write_decode():
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    rng = np.random.RandomState(2)
    S, T, H, D, BS, MB = 2, 3, 2, 8, 4, 4
    kp0, vp0 = pc.init_pool(1 + S * MB, BS, H, D, jnp.float32)
    tables = jnp.asarray(
        (1 + np.arange(S * MB, dtype=np.int32)).reshape(S, MB))
    lens = jnp.asarray([3, 6], jnp.int32)
    k = jnp.asarray(rng.randn(S, T, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(S, T, H, D), jnp.float32)
    kp1, vp1 = pc.write_tokens(kp0, vp0, tables, lens, k, v)
    kp2, vp2 = kp0, vp0
    for t in range(T):
        kp2, vp2 = pc.write_decode(kp2, vp2, tables, lens + t,
                                   k[:, t], v[:, t])
    np.testing.assert_array_equal(np.asarray(kp1), np.asarray(kp2))
    np.testing.assert_array_equal(np.asarray(vp1), np.asarray(vp2))


def test_ngram_propose_prompt_lookup():
    from paddle_tpu.generation.speculative import ngram_propose
    #          0  1  2  3  4  5  6  7
    history = [5, 6, 7, 8, 9, 5, 6, 7]
    # suffix 3-gram (5,6,7) recurs at 0 -> continue 8, 9, 5
    assert ngram_propose(history, 3, max_ngram=3) == [8, 9, 5]
    # short continuation pads by repeating its last token
    assert ngram_propose([1, 2, 9, 1, 2], 4) == [9, 1, 2, 2]
    # no match: repeat the last token
    assert ngram_propose([1, 2, 3], 2) == [3, 3]
    # deterministic on degenerate single-token history
    assert ngram_propose([4], 2) == [4, 4]


# ------------------------------------------------------ greedy exactness


def test_spec_generate_token_exact_llama(llama_tiny):
    """Greedy speculative output must equal plain generate() token for
    token (and score for score) at every gamma — accepted or rejected,
    the emitted chain IS the target's own argmax chain."""
    ids = np.random.RandomState(0).randint(0, 128, (2, 9)) \
        .astype(np.int64)
    ref, sref = llama_tiny.generate(paddle.to_tensor(ids),
                                    max_new_tokens=10)
    for g in (1, 3):
        out, s = llama_tiny.generate(paddle.to_tensor(ids),
                                     max_new_tokens=10,
                                     num_speculative_tokens=g)
        np.testing.assert_array_equal(ref.numpy(), out.numpy())
        np.testing.assert_allclose(np.asarray(sref.numpy()),
                                   np.asarray(s.numpy()), atol=1e-4)


def test_spec_generate_token_exact_draft_model(llama_tiny, llama_draft):
    ids = np.random.RandomState(3).randint(0, 128, (2, 7)) \
        .astype(np.int64)
    ref, _ = llama_tiny.generate(paddle.to_tensor(ids),
                                 max_new_tokens=8)
    out, _ = llama_tiny.generate(paddle.to_tensor(ids),
                                 max_new_tokens=8,
                                 num_speculative_tokens=2,
                                 draft_model=llama_draft)
    np.testing.assert_array_equal(ref.numpy(), out.numpy())


def test_spec_generate_token_exact_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(3)
    m = GPTForCausalLM(GPTConfig.tiny(vocab=96, hidden=64, layers=2,
                                      heads=4))
    m.eval()
    ids = np.random.RandomState(5).randint(1, 96, (2, 7)) \
        .astype(np.int64)
    ref, _ = m.generate(paddle.to_tensor(ids), max_new_tokens=8)
    out, _ = m.generate(paddle.to_tensor(ids), max_new_tokens=8,
                        num_speculative_tokens=2)
    np.testing.assert_array_equal(ref.numpy(), out.numpy())


def test_spec_generate_token_exact_int8(llama_tiny):
    from paddle_tpu.nn.quant import quantize_for_inference
    assert quantize_for_inference(llama_tiny) > 0
    ids = np.random.RandomState(8).randint(0, 128, (1, 11)) \
        .astype(np.int64)
    ref, _ = llama_tiny.generate(paddle.to_tensor(ids),
                                 max_new_tokens=8)
    out, _ = llama_tiny.generate(paddle.to_tensor(ids),
                                 max_new_tokens=8,
                                 num_speculative_tokens=3)
    np.testing.assert_array_equal(ref.numpy(), out.numpy())


def test_spec_generate_eos_inside_window(llama_tiny):
    """EOS found mid-window truncates exactly like the sequential
    loop: the EOS is emitted, everything after is pad."""
    rng = np.random.RandomState(4)
    ids = rng.randint(0, 128, (1, 6)).astype(np.int64)
    base, _ = llama_tiny.generate(paddle.to_tensor(ids),
                                  max_new_tokens=10)
    eos = int(np.asarray(base.numpy())[0, 3])   # hit at step 4
    ref, _ = llama_tiny.generate(paddle.to_tensor(ids),
                                 max_new_tokens=10, eos_token_id=eos)
    out, _ = llama_tiny.generate(paddle.to_tensor(ids),
                                 max_new_tokens=10, eos_token_id=eos,
                                 num_speculative_tokens=4)
    np.testing.assert_array_equal(ref.numpy(), out.numpy())


def test_spec_kill_switch(llama_tiny, monkeypatch):
    """PADDLE_TPU_SPECULATIVE=0 forces the plain decode path (the
    emergency lever documented in docs/OPS.md)."""
    monkeypatch.setenv("PADDLE_TPU_SPECULATIVE", "0")
    ids = np.random.RandomState(1).randint(0, 128, (1, 5)) \
        .astype(np.int64)
    ref, _ = llama_tiny.generate(paddle.to_tensor(ids),
                                 max_new_tokens=6)
    out, _ = llama_tiny.generate(paddle.to_tensor(ids),
                                 max_new_tokens=6,
                                 num_speculative_tokens=4)
    np.testing.assert_array_equal(ref.numpy(), out.numpy())
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        num_speculative_tokens=4))
    assert eng._gamma == 0          # engine fell back to plain decode


def test_spec_rejects_invalid_configs(llama_tiny, llama_draft):
    ids = paddle.to_tensor(np.ones((1, 4), np.int64))
    with pytest.raises(NotImplementedError, match="beam"):
        llama_tiny.generate(ids, decode_strategy="beam_search",
                            num_beams=2, max_new_tokens=2,
                            num_speculative_tokens=2)
    with pytest.raises(ValueError, match="num_speculative_tokens"):
        llama_tiny.generate(ids, max_new_tokens=2,
                            num_speculative_tokens=-1)
    with pytest.raises(ValueError, match="draft_model"):
        llama_tiny.generate(ids, max_new_tokens=2,
                            draft_model=llama_draft)
    with pytest.raises(ValueError, match="paged"):
        # the speculative loop rides the paged cache; an explicit
        # dense-cache request cannot be honored silently
        llama_tiny.generate(ids, max_new_tokens=2, cache_impl="dense",
                            num_speculative_tokens=2)
    with pytest.raises(ValueError, match="drafter"):
        ServingEngine(llama_tiny, ServingConfig(
            num_speculative_tokens=2, drafter="model"))
    # capacity-routed MoE is excluded (window tokens would compete for
    # expert capacity — same reasoning as prompt bucketing)
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeConfig,
                                             Qwen2MoeForCausalLM)
    paddle.seed(1)
    moe = Qwen2MoeForCausalLM(Qwen2MoeConfig.tiny())
    moe.eval()
    with pytest.raises(NotImplementedError):
        moe.generate(ids, max_new_tokens=2, num_speculative_tokens=2)


# ------------------------------------------------------- serving engine


def test_spec_serving_parity_mixed_lengths(llama_tiny):
    """Speculatively-served greedy tokens == each prompt generated
    alone through the dense cache, across slot/block pressure and both
    drafters."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int64)
               for n in (5, 9, 13, 7, 21, 3)]
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=3, block_size=8, max_model_len=64, max_new_tokens=8,
        num_speculative_tokens=3))
    outs = eng.serve(prompts, max_new_tokens=8)
    for p, got in zip(prompts, outs):
        ref, _ = _ref(llama_tiny, p, 8)
        np.testing.assert_array_equal(got, ref[:len(got)])
    st = eng.stats()
    assert st["decode_compiles"] == 1
    assert st["spec_tokens_proposed"] > 0
    assert st["free_blocks"] == eng._alloc.num_blocks - 1


def test_spec_serving_parity_draft_model(llama_tiny, llama_draft):
    rng = np.random.RandomState(6)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int64)
               for n in (6, 11, 4)]
    eng = ServingEngine(
        llama_tiny,
        ServingConfig(num_slots=2, block_size=8, max_model_len=64,
                      num_speculative_tokens=2,
                      drafter="model"),
        draft_model=llama_draft)
    outs = eng.serve(prompts, max_new_tokens=6)
    for p, got in zip(prompts, outs):
        ref, _ = _ref(llama_tiny, p, 6)
        np.testing.assert_array_equal(got, ref[:len(got)])
    assert eng.stats()["decode_compiles"] == 1


def test_spec_serving_zero_steadystate_recompiles(llama_tiny):
    """The PR-3 serving bar extends to speculative mode: ONE verify
    executable over waves of different lengths/occupancy — accept and
    reject mixes live in array values, never in shapes."""
    rng = np.random.RandomState(2)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        num_speculative_tokens=2))
    eng.serve([rng.randint(1, 128, (n,)) for n in (4, 9)],
              max_new_tokens=4)
    st0 = eng.stats()
    assert st0["decode_compiles"] == 1
    eng.serve([rng.randint(1, 128, (n,)) for n in (13, 2, 7)],
              max_new_tokens=5)
    st1 = eng.stats()
    assert st1["decode_compiles"] == 1, "steady-state recompile"
    assert st1["decode_steps"] > st0["decode_steps"]


def test_spec_serving_streams_every_token(llama_tiny):
    """Multi-token steps stream token-by-token through the ordinary
    callback, and streamed == returned for every request."""
    rng = np.random.RandomState(9)
    streamed = {}
    eng = ServingEngine(
        llama_tiny,
        ServingConfig(num_slots=2, block_size=8, max_model_len=64,
                      num_speculative_tokens=3),
        stream_callback=lambda rid, t: streamed.setdefault(rid, [])
        .append(t))
    rids = [eng.submit(rng.randint(1, 128, (n,)), mn)
            for n, mn in [(3, 5), (11, 7), (6, 2), (17, 4)]]
    done = eng.run()
    assert sorted(done) == sorted(rids)
    for rid in rids:
        assert streamed[rid] == list(done[rid])


def test_spec_serving_gpt(llama_tiny):
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(3)
    m = GPTForCausalLM(GPTConfig.tiny(vocab=96, hidden=64, layers=2,
                                      heads=4))
    m.eval()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 96, (n,)).astype(np.int64)
               for n in (5, 11, 8)]
    eng = ServingEngine(m, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        num_speculative_tokens=2))
    outs = eng.serve(prompts, max_new_tokens=4)
    for p, got in zip(prompts, outs):
        ref, _ = _ref(m, p, 4)
        np.testing.assert_array_equal(got, ref[:len(got)])


def test_spec_serving_int8(llama_tiny):
    from paddle_tpu.nn.quant import quantize_for_inference
    quantize_for_inference(llama_tiny)
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int64)
               for n in (6, 10)]
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        num_speculative_tokens=2))
    outs = eng.serve(prompts, max_new_tokens=4)
    for p, got in zip(prompts, outs):
        ref, _ = _ref(llama_tiny, p, 4)
        np.testing.assert_array_equal(got, ref[:len(got)])


def test_spec_acceptance_on_repetitive_text(llama_tiny):
    """The n-gram drafter must actually WIN on repetitive text: mean
    accepted length (emitted tokens per verify window) > 1.0 — the
    speculative speedup bar (greedy decode loops, prompt lookup rides
    the loop)."""
    pattern = np.asarray([17, 42, 99, 7, 63], np.int64)
    prompts = [np.tile(pattern, 6), np.tile(pattern[::-1], 5)]
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=160,
        num_speculative_tokens=4))
    eng.serve(prompts, max_new_tokens=32)
    st = eng.stats()
    assert st["spec_mean_accepted_len"] > 1.0, st
    assert st["spec_tokens_accepted"] > 0


# ------------------------------------------------- rollback correctness


def test_spec_rollback_blocks_and_cache_match_fresh_prefill(llama_tiny):
    """The rollback property pin: drive a speculative engine step by
    step; after EVERY step each active slot's (a) block table holds at
    least ``blocks_for(cache_len)`` and at most
    ``blocks_for(cache_len + gamma + 1)`` live blocks (committed
    coverage, bounded overhang — anything past the next window's reach
    is returned to the allocator; a mid-prefill slot instead holds its
    admission allocation ``blocks_for(prompt)``) with a null tail, and
    (b) the layer-0 K cache prefix equals a from-scratch prefill of
    the committed tokens, token for token."""
    import jax.numpy as jnp
    from paddle_tpu.jit import _LayerBinder
    from paddle_tpu.ops import paged_cache as pc

    rng = np.random.RandomState(11)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        num_speculative_tokens=3))
    for n, mn in [(5, 9), (12, 7), (3, 8), (9, 5)]:
        eng.submit(rng.randint(1, 128, (n,)), mn)

    binder = _LayerBinder(llama_tiny)
    step_fn = llama_tiny._build_model_step(binder,
                                           binder.buffer_arrays())
    params = binder.param_arrays()

    def fresh_prefill_k0(tokens):
        """Layer-0 K for ``tokens`` written into a fresh pool through a
        fresh contiguous table — the from-scratch reference."""
        n = len(tokens)
        mb = pc.blocks_for(n, eng._bs)
        pools = llama_tiny.init_paged_caches(1 + mb, eng._bs)
        dense = llama_tiny.init_caches(1, n)
        _, dense = step_fn(
            params, jnp.asarray(np.asarray(tokens, np.int32))[None],
            dense, jnp.zeros((), jnp.int32))
        table = jnp.asarray(1 + np.arange(mb, dtype=np.int32))[None]
        kp, vp = pools[0]
        kp, _ = pc.write_prefill(kp, vp, table, *dense[0])
        return np.asarray(pc.gather_dense(kp, table))[0, :n]

    steps = 0
    while eng.num_queued or eng.num_active:
        eng.step()
        steps += 1
        for i, slot in enumerate(eng._slots):
            if slot is None:
                assert not eng._tables[i].any()
                continue
            need = pc.blocks_for(slot.cache_len, eng._bs)
            if slot.pend_pos is not None:
                # mid-prefill (ragged chunks land across ticks): the
                # slot keeps its whole-prompt admission allocation and
                # the cache covers exactly the prompt prefix so far
                cap = pc.blocks_for(int(slot.prompt.size), eng._bs)
                committed = slot.history[:slot.cache_len]
            else:
                cap = pc.blocks_for(slot.cache_len + eng._gamma + 1,
                                    eng._bs)
                # committed = prompt + emitted minus the pending one
                committed = slot.history[:-1]
            assert need <= len(slot.blocks) <= cap, \
                "window overhang blocks not trimmed"
            held = len(slot.blocks)
            assert list(eng._tables[i, :held]) == slot.blocks
            assert not eng._tables[i, held:].any()
            assert len(committed) == slot.cache_len
            if slot.cache_len == 0:
                continue
            live = np.asarray(pc.gather_dense(
                eng._pools[0][0],
                jnp.asarray(eng._tables[i][None])))[0, :slot.cache_len]
            np.testing.assert_allclose(
                live, fresh_prefill_k0(committed), rtol=1e-5,
                atol=1e-5)
    assert steps > 2
    st = eng.stats()
    assert st["free_blocks"] == eng._alloc.num_blocks - 1, "block leak"
    assert st["reserved_blocks"] == 0


def test_spec_scheduler_property_interleaved(llama_tiny):
    """Scheduler invariants under slot + block pressure WITH
    speculation: every request completes exactly once, 1 <= emitted <=
    max_new, streamed == returned, pool drains to empty, reservations
    return to zero."""
    rng = np.random.RandomState(1)
    cfg = ServingConfig(num_slots=2, block_size=8, max_model_len=48,
                        num_blocks=17, num_speculative_tokens=2)
    streamed = {}
    eng = ServingEngine(
        llama_tiny, cfg,
        stream_callback=lambda rid, t: streamed.setdefault(rid, [])
        .append(t))
    rids, news = [], [4, 7, 1, 5, 3, 8, 2, 6]
    for n, mn in zip([3, 11, 6, 17, 9, 2, 14, 5], news):
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


# --------------------------------------------------- sampling soundness


def test_rejection_sampling_preserves_target_distribution():
    """Chi-squared pin of the rejection-sampling theorem on a toy
    'model' (a stub step with fixed logits): the token emitted at a
    verify position must be distributed EXACTLY as the (filtered)
    target distribution, for both the one-hot (n-gram) and real draft
    distributions — including deliberately terrible drafts."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.generation.speculative import build_verify_step

    V, G, S = 8, 2, 4000
    rng = np.random.RandomState(0)
    logits_row = rng.randn(G + 1, V).astype(np.float32) * 1.5
    target_p = np.exp(logits_row) / np.exp(logits_row).sum(-1,
                                                           keepdims=True)

    def stub_step(params, toks, pools, off, block_tables=None,
                  cache_lens=None):
        s = toks.shape[0]
        return jnp.broadcast_to(jnp.asarray(logits_row),
                                (s, G + 1, V)), pools

    def chi2(counts, probs):
        exp = probs * counts.sum()
        keep = exp > 5
        return float(((counts[keep] - exp[keep]) ** 2
                      / exp[keep]).sum()), int(keep.sum())

    # draft q: a deliberately bad distribution (mass on wrong tokens)
    q_row = rng.dirichlet(np.full(V, 0.3), size=G).astype(np.float32)
    for onehot in (True, False):
        verify = jax.jit(build_verify_step(
            stub_step, gamma=G, do_sample=True, temperature=1.0,
            top_k=0, top_p=1.0, onehot_draft=onehot))
        key = jax.random.PRNGKey(42)
        if onehot:
            # n-gram drafts: an arbitrary fixed proposal per position
            toks = np.tile(np.asarray([[0, 3, 5]], np.int32), (S, 1))
            out, accept, _, _ = verify(None, None, None,
                                       jnp.zeros((S,), jnp.int32),
                                       jnp.asarray(toks), key)
        else:
            kd, key = jax.random.split(key)
            draft = jax.random.categorical(
                kd, jnp.log(jnp.asarray(q_row))[None].repeat(S, 0))
            toks = jnp.concatenate(
                [jnp.zeros((S, 1), jnp.int32),
                 draft.astype(jnp.int32)], axis=1)
            dq = jnp.broadcast_to(jnp.asarray(q_row), (S, G, V))
            out, accept, _, _ = verify(None, None, None,
                                       jnp.zeros((S,), jnp.int32),
                                       toks, dq, key)
        out = np.asarray(out)
        accept = np.asarray(accept)
        # position 0 output is ALWAYS emitted -> marginal must be p_0
        counts = np.bincount(out[:, 0], minlength=V).astype(np.float64)
        stat, dof = chi2(counts, target_p[0])
        # 99.9th percentile of chi2 with <= 7 dof is < 25
        assert stat < 25, (onehot, stat, counts)
        # all-accepted rows emit the bonus token -> must follow p_G
        full = accept.all(axis=1)
        if full.sum() > 400:
            counts = np.bincount(out[full, G],
                                 minlength=V).astype(np.float64)
            stat, dof = chi2(counts, target_p[G])
            assert stat < 25, (onehot, stat)


def test_spec_sampling_matches_target_frequencies_e2e(llama_tiny):
    """End-to-end distribution check on a real model: the first
    verify-emitted token's frequencies under speculative sampling are
    chi-squared-tested against the EXACT marginal computed from the
    model's own filtered probabilities (sum over first-token candidates
    of p(t1) * p(t2 | t1) — the distribution plain sampling follows by
    construction)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.generation import _filter_logits

    temp, tk = 0.8, 16
    ids = np.random.RandomState(2).randint(0, 128, (1, 6)) \
        .astype(np.int64)
    x = paddle.to_tensor(ids)

    def filtered_probs(logits):
        return np.asarray(jax.nn.softmax(_filter_logits(
            jnp.asarray(logits), do_sample=True, temperature=temp,
            top_k=tk, top_p=1.0), axis=-1))

    p1 = filtered_probs(
        np.asarray(llama_tiny(x).numpy())[0, -1])        # [V]
    cand = np.nonzero(p1 > 1e-9)[0]
    seqs = np.concatenate(
        [np.tile(ids, (len(cand), 1)), cand[:, None]], axis=1)
    p2 = filtered_probs(
        np.asarray(llama_tiny(paddle.to_tensor(seqs)).numpy())[:, -1])
    marginal = (p1[cand][:, None] * p2).sum(0)           # [V]

    N = 300
    counts = np.zeros(128)
    for s in range(N):
        out, _ = llama_tiny.generate(
            x, seed=s, max_new_tokens=2, decode_strategy="sampling",
            temperature=temp, top_k=tk, num_speculative_tokens=2)
        counts[int(np.asarray(out.numpy())[0, 1])] += 1
    exp = marginal * N
    keep = exp > 5
    stat = float(((counts[keep] - exp[keep]) ** 2 / exp[keep]).sum())
    # ~99.9th percentile of chi2 at the surviving dof (< ~25 bins)
    assert stat < 55, f"chi2 {stat} over {int(keep.sum())} bins"
    # nothing lands outside the filtered support
    assert counts[~(marginal > 0)].sum() == 0


# ----------------------------------------------------- telemetry + CI


def test_spec_telemetry_in_stats_and_jsonl(tmp_path, llama_tiny):
    """The ISSUE-4 monitor satellites: accepted-length histogram,
    proposed/accepted counters and the acceptance-rate gauge reach both
    stats() and the JSONL export."""
    import json
    rng = np.random.RandomState(6)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        num_speculative_tokens=2))
    eng.serve([rng.randint(1, 128, (n,)) for n in (4, 12, 6)],
              max_new_tokens=4)
    st = eng.stats()
    for k in ("spec_tokens_proposed", "spec_tokens_accepted",
              "spec_acceptance_rate", "spec_mean_accepted_len"):
        assert k in st
    assert st["spec_tokens_proposed"] > 0
    assert st["spec_mean_accepted_len"] >= 1.0
    path = monitor.export_jsonl(str(tmp_path / "metrics.jsonl"))
    names = {json.loads(line)["name"] for line in open(path)}
    for want in ("serving_spec_accepted_len", "spec_tokens_proposed",
                 "spec_tokens_accepted", "serving_spec_acceptance_rate"):
        assert want in names, f"{want} missing from JSONL export"


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
    assert "test_verify_kernel_matches_fallback_interpret" in names
    overlap = set(names) & set(conftest._SLOW_TESTS)
    assert not overlap, f"tier-1 speculative tests marked slow: {overlap}"

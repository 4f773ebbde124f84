"""Tensor-parallel sharded serving (ISSUE 6): every serving executable
— batched decode, fixed-gamma verify, fixed-chunk prefill, draft loop,
COW — sharded over a Mesh(("mp",)) axis on the conftest 8-CPU-device
mesh. TP=2/4 engine output must be TOKEN-EXACT vs single-device greedy
across Llama/GPT/int8/speculative/prefix-cache-ON, with zero
steady-state recompiles, exactly one explicit logits all_gather per
decode step (jaxpr census), a bit-for-bit kill switch, and the host
scheduler/allocator invariants (leak sweep) unchanged under TP.

Runtime discipline: single-device reference outputs are computed ONCE
per workload and shared across tests (`_ref_tokens`), and speculative
engines are compared against the PLAIN single-device reference (greedy
spec is token-exact vs plain decode by construction — pinned in
test_speculative.py), so the file stays inside the tier-1 budget.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


@pytest.fixture(scope="module")
def llama_tiny():
    paddle.seed(7)
    # kv_heads=4 so tp divides at both 2 and 4
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           kv_heads=4, ffn=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


_MIXED_LENS = (5, 9, 13, 21)
_REP = [np.tile([5, 9, 13], 6).astype(np.int64),
        np.tile([7, 11], 8).astype(np.int64)]
_REF_CACHE = {}


def _prompts(seed, vocab, lens):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, (n,)).astype(np.int64) for n in lens]


def _serve(model, tp, prompts, max_new=6, draft=None, **cfg_kw):
    eng = ServingEngine(
        model, ServingConfig(num_slots=2, block_size=8,
                             max_model_len=64, tp_degree=tp, **cfg_kw),
        draft_model=draft)
    outs = eng.serve(list(prompts), max_new_tokens=max_new)
    st = eng.stats()
    census = eng.collective_census()
    eng.shutdown()                       # allocator leak sweep under TP
    return outs, st, census


def _ref_tokens(model, key, prompts, max_new=6, **cfg_kw):
    """Single-device greedy reference, computed once per workload."""
    if key not in _REF_CACHE:
        outs, st, _ = _serve(model, 1, prompts, max_new=max_new,
                             **cfg_kw)
        assert st["tp_degree"] == 1
        _REF_CACHE[key] = outs
    return _REF_CACHE[key]


def _assert_exact(ref, got, tag):
    for i, (a, b) in enumerate(zip(ref, got)):
        assert a.tolist() == b.tolist(), \
            f"{tag}: request {i} diverged: {a.tolist()} vs {b.tolist()}"


# ----------------------------------------------------------- exactness


def test_tp2_exact_recompiles_census(llama_tiny):
    """The tentpole bar at TP=2: token-exact vs single-device over TWO
    waves (zero steady-state recompiles under TP), and the decode
    executable's jaxpr census shows EXACTLY ONE explicit collective —
    the logits all_gather over mp — whose per-shard payload
    (S * V/tp * 4 bytes) feeds the per-step counter."""
    prompts = _prompts(0, 128, _MIXED_LENS)
    wave2 = _prompts(10, 128, (13, 2, 7))
    ref = _ref_tokens(llama_tiny, "mixed", prompts)
    ref2 = _ref_tokens(llama_tiny, "mixed2", wave2, max_new=4)

    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64, tp_degree=2))
    got = eng.serve(list(prompts), max_new_tokens=6)
    _assert_exact(ref, got, "tp=2 wave 1")
    st0 = eng.stats()
    assert st0["decode_compiles"] == 1 and st0["tp_degree"] == 2
    got2 = eng.serve(list(wave2), max_new_tokens=4)
    _assert_exact(ref2, got2, "tp=2 wave 2")
    st = eng.stats()
    assert st["decode_compiles"] == 1, "steady-state recompile under TP"
    assert st["decode_steps"] > st0["decode_steps"]

    rows = [r for r in eng.collective_census()["decode"]
            if r["op"] != "sharding_constraint"]
    assert len(rows) == 1, f"expected one explicit collective: {rows}"
    assert rows[0]["op"] == "all_gather" and rows[0]["axis"] == "mp"
    assert rows[0]["count"] == 1
    assert rows[0]["bytes"] == 2 * (128 // 2) * 4   # S * V/tp * f32
    assert st["tp_collective_bytes_per_step"] == rows[0]["bytes"]
    assert st["tp_collective_bytes_total"] == \
        rows[0]["bytes"] * st["decode_steps"]
    eng.shutdown()


def test_tp4_exact(llama_tiny):
    """TP=4 (kv_heads/tp == 1): same tokens, quarter pool per shard."""
    prompts = _prompts(0, 128, _MIXED_LENS)
    ref = _ref_tokens(llama_tiny, "mixed", prompts)
    got, st, _ = _serve(llama_tiny, 4, prompts)
    _assert_exact(ref, got, "tp=4")
    assert st["tp_degree"] == 4
    assert st["tp_pool_bytes_per_shard"] > 0


def test_tp_gpt_family():
    """GPT (MHA, fused qkv, learned positions, tied-embedding logits)
    rides the same sharded path token-exactly."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(3)
    m = GPTForCausalLM(GPTConfig.tiny(vocab=96, hidden=32, layers=2,
                                      heads=4))
    m.eval()
    prompts = _prompts(5, 96, (5, 11, 8))
    ref, _, _ = _serve(m, 1, prompts, max_new=4)
    got, _, _ = _serve(m, 2, prompts, max_new=4)
    _assert_exact(ref, got, "gpt tp=2")


def test_tp_int8_quantized():
    """Weight-only-int8 serving under TP: quantized weights carry no
    sharding specs (replicated), GSPMD re-shards activations around
    them — tokens stay exact vs the single-device int8 engine."""
    from paddle_tpu.nn.quant import quantize_for_inference
    paddle.seed(11)
    cfg = LlamaConfig.tiny(vocab=128, hidden=32, layers=2, heads=4,
                           kv_heads=4, ffn=64)
    m = LlamaForCausalLM(cfg)
    m.eval()
    quantize_for_inference(m)
    prompts = _prompts(9, 128, (6, 10))
    ref, _, _ = _serve(m, 1, prompts, max_new=4)
    got, _, _ = _serve(m, 2, prompts, max_new=4)
    _assert_exact(ref, got, "int8 tp=2")


def test_tp_speculative_ngram(llama_tiny):
    """Speculative serving under TP (verify + rollback + trim on the
    sharded pool): greedy spec output is the target's own greedy chain,
    so it must equal the PLAIN single-device engine token-for-token;
    the verify executable census shows exactly one logits all_gather."""
    ref = _ref_tokens(llama_tiny, "rep", _REP)
    got, st, census = _serve(llama_tiny, 2, _REP,
                             num_speculative_tokens=2)
    _assert_exact(ref, got, "spec tp=2")
    assert st["spec_tokens_proposed"] > 0
    gathers = [r for r in census["verify"]
               if r["op"] == "all_gather" and r["axis"] == "mp"]
    assert len(gathers) == 1 and gathers[0]["count"] == 1


def test_tp_speculative_draft_model(llama_tiny):
    """Draft-model drafting under TP: the draft loop shares the same
    replicated block tables and its own kv_head-sharded pool slice;
    output still equals the plain single-device chain."""
    paddle.seed(13)
    draft = LlamaForCausalLM(LlamaConfig.tiny(
        vocab=128, hidden=32, layers=1, heads=4, kv_heads=4, ffn=64))
    draft.eval()
    ref = _ref_tokens(llama_tiny, "rep", _REP)
    got, st, census = _serve(llama_tiny, 2, _REP, draft=draft,
                             num_speculative_tokens=2, drafter="model")
    _assert_exact(ref, got, "spec draft tp=2")

    def mp_bytes(name):
        return sum(r["bytes"] for r in census[name]
                   if r["op"] == "all_gather" and r["axis"] == "mp")
    # the draft gather runs gamma+1 times inside its scan (census walks
    # the body once) — per-step bytes must count every iteration
    assert st["tp_collective_bytes_per_step"] == \
        mp_bytes("verify") + 3 * mp_bytes("draft")


def test_tp_prefix_cache_sharing(llama_tiny):
    """Prefix caching composes with TP for free (global block ids, one
    host allocator, every shard indexed by the same tables): a second
    wave of shared-prefix prompts hits the cache under TP and the
    served tokens stay exact vs the single-device engine."""
    rng = np.random.RandomState(2)
    sysp = rng.randint(1, 128, (24,))
    prompts = [np.concatenate([sysp, rng.randint(1, 128, (k,))])
               for k in (3, 5, 7)]

    def waves(tp):
        eng = ServingEngine(llama_tiny, ServingConfig(
            num_slots=2, block_size=8, max_model_len=64, tp_degree=tp,
            prefill_chunk=16))
        outs = eng.serve(list(prompts), max_new_tokens=4)
        outs += eng.serve(list(prompts), max_new_tokens=4)
        st = eng.stats()
        eng.shutdown()                   # leak sweep with cached blocks
        return outs, st

    ref, _ = waves(1)
    got, st = waves(2)
    _assert_exact(ref, got, "prefix tp=2")
    assert st["prefix_hit_rate"] > 0.3
    assert st["prefix_blocks_reused"] > 0


def test_tp_sampling_parity(llama_tiny):
    """Satellite: the sampling PRNG key is replicated (never per-shard
    split), so do_sample=True AND rejection-sampling speculative decode
    draw the SAME tokens as the single-device engine from the same seed
    — sampling consumes the gathered (replicated) logits everywhere."""
    prompts = _prompts(4, 128, (5, 9))
    kw = dict(decode_strategy="sampling", temperature=0.9, top_k=20,
              seed=5)
    ref, _, _ = _serve(llama_tiny, 1, prompts, **kw)
    got, _, _ = _serve(llama_tiny, 2, prompts, **kw)
    _assert_exact(ref, got, "sampling tp=2")
    # rejection-sampling speculative window, same discipline
    kw = dict(num_speculative_tokens=2, decode_strategy="sampling",
              temperature=0.8, seed=3)
    ref, _, _ = _serve(llama_tiny, 1, _REP, max_new=4, **kw)
    got, _, _ = _serve(llama_tiny, 2, _REP, max_new=4, **kw)
    _assert_exact(ref, got, "spec sampling tp=2")


def test_sharded_step_matches_single_program():
    """Kernel-layer pin: ``sharded_ragged_attention_step`` (shard_map
    over mp, per-shard kv_head slice) equals the single-program
    ``ragged_attention_step`` on the same pool/tables over a packed
    batch of every width — a decode row, a verify window, a chunk."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.ops import paged_cache as pc
    from paddle_tpu.ops.pallas import paged_attention as pa
    rng = np.random.RandomState(0)
    S, H, Hkv, D, BS, MB, WN, W = 3, 4, 4, 16, 8, 4, 3, 8
    NB = 1 + S * MB
    R = S * WN + W
    tables = jnp.asarray(
        (1 + np.arange(S * MB, dtype=np.int32)).reshape(S, MB))
    base = np.asarray([5, 11, 8], np.int64)
    q_lens = np.asarray([1, 3, 8], np.int64)
    row_slot, row_pos, row_starts, _ = pc.ragged_row_meta(
        q_lens, base, R, MB * BS)
    mesh = Mesh(np.array(jax.devices()[:2]), ("mp",))
    kp = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
    vp = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
    qh = jnp.asarray(rng.randn(R, H, D), jnp.float32)
    kh = jnp.asarray(rng.randn(R, Hkv, D), jnp.float32)
    vh = jnp.asarray(rng.randn(R, Hkv, D), jnp.float32)
    args = (qh, kh, vh, kp, vp, tables, jnp.asarray(base),
            jnp.asarray(q_lens), jnp.asarray(row_starts),
            jnp.asarray(row_slot), jnp.asarray(row_pos),
            jnp.arange(WN, dtype=jnp.int32),
            jnp.arange(W, dtype=jnp.int32))
    ref, rk, rv = pa.ragged_attention_step(*args, sm_scale=0.25)
    denv.set_mesh(mesh)
    try:
        out, ok, ov = pa.sharded_ragged_attention_step(
            *args, sm_scale=0.25)
    finally:
        denv.set_mesh(None)
    live = np.asarray(row_pos) < MB * BS
    np.testing.assert_allclose(np.asarray(out)[live],
                               np.asarray(ref)[live],
                               rtol=1e-6, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(ok), np.asarray(rk))
    np.testing.assert_array_equal(np.asarray(ov), np.asarray(rv))


# -------------------------------------------------- switches + errors


def test_tp_pool_kill_switch_telemetry(tmp_path, llama_tiny, monkeypatch):
    """Three satellites on one engine pair: (1) the pool really is
    split on kv_heads (sharding spec + per-shard bytes + slice helper);
    (2) TP telemetry lands in stats() and the JSONL export; (3)
    PADDLE_TPU_SERVE_TP=0 restores the single-device path bit-for-bit
    (tp_degree reported 1, no census, identical tokens)."""
    import json
    prompts = _prompts(0, 128, _MIXED_LENS)
    ref = _ref_tokens(llama_tiny, "mixed", prompts)

    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64, tp_degree=2))
    kp, _ = eng._pools[0]
    assert tuple(kp.sharding.spec) == (None, None, "mp", None)
    shard = kp.addressable_shards[0].data
    assert shard.shape[2] == kp.shape[2] // 2
    from paddle_tpu.ops.paged_cache import pool_head_slice
    assert pool_head_slice(np.asarray(kp), 0, 2).shape == shard.shape
    got = eng.serve(list(prompts), max_new_tokens=6)
    _assert_exact(ref, got, "tp=2 telemetry engine")
    st = eng.stats()
    assert st["tp_collective_bytes_per_step"] > 0
    assert st["tp_pool_bytes_per_shard"] * 2 == sum(
        int(k.nbytes) + int(v.nbytes) for k, v in eng._pools)
    eng.shutdown()
    path = monitor.export_jsonl(str(tmp_path / "metrics.jsonl"))
    names = {json.loads(line)["name"] for line in open(path)}
    for want in ("serving_tp_degree", "serving_tp_collective_bytes",
                 "serving_tp_pool_bytes_per_shard"):
        assert want in names, f"{want} missing from JSONL export"

    monkeypatch.setenv("PADDLE_TPU_SERVE_TP", "0")
    got, st, census = _serve(llama_tiny, 4, prompts)
    _assert_exact(ref, got, "kill switch")
    assert st["tp_degree"] == 1
    # keys stay present (0) so stats() consumers survive the rollback
    assert st["tp_collective_bytes_per_step"] == 0
    assert st["tp_collective_bytes_total"] == 0
    assert census == {}


def test_tp_invalid_degrees(llama_tiny):
    """Satellite: broken tp_degree values are rejected with a clear
    error at config/engine construction, not a shard_map shape crash."""
    with pytest.raises(ValueError, match="positive int"):
        ServingConfig(tp_degree=0)
    with pytest.raises(ValueError, match="positive int"):
        ServingConfig(tp_degree=-2)
    with pytest.raises(ValueError, match="num_kv_heads"):
        ServingEngine(llama_tiny, ServingConfig(tp_degree=3))
    with pytest.raises(ValueError, match="devices"):
        ServingEngine(llama_tiny, ServingConfig(tp_degree=16))


def test_tp_scheduler_property_with_sharing(llama_tiny):
    """Scheduler invariants under TP + slot/block pressure + prefix
    sharing: every request completes exactly once, streamed == returned,
    the pool drains, and the shutdown leak sweep passes (cached blocks
    + free + live partition intact)."""
    rng = np.random.RandomState(1)
    sysp = rng.randint(1, 128, (16,))
    cfg = ServingConfig(num_slots=2, block_size=8, max_model_len=48,
                        num_blocks=15, tp_degree=2, prefill_chunk=16)
    streamed = {}
    eng = ServingEngine(
        llama_tiny, cfg,
        stream_callback=lambda rid, t: streamed.setdefault(rid, [])
        .append(t))
    rids = []
    lens = [3, 11, 6, 2, 9, 5]
    news = [4, 6, 1, 5, 3, 6]
    for n, mn in zip(lens, news):
        p = np.concatenate([sysp, rng.randint(1, 128, (n,))]) \
            if n % 2 else rng.randint(1, 128, (n,))
        rids.append(eng.submit(p, mn))
    done = eng.run()
    assert sorted(done) == sorted(rids)
    for rid, mn in zip(rids, news):
        assert 1 <= len(done[rid]) <= mn
        assert streamed[rid] == list(done[rid])
    st = eng.stats()
    assert st["active"] == 0 and st["queued"] == 0
    assert st["reserved_blocks"] == 0
    assert st["free_blocks"] == cfg.num_blocks - 1
    assert eng.shutdown() is True


def test_tier1_no_slow_marker():
    """This file must stay in the tier-1 (-m 'not slow') budget and
    keep the TP exactness + census + shutdown coverage present."""
    import tests.conftest as c
    here = open(__file__).read()
    for name in ("test_tp2_exact_recompiles_census", "test_tp4_exact"):
        assert name in here
        assert name not in c._SLOW_TESTS
    assert "eng.shutdown()" in here

"""Quantized paged KV cache (ISSUE 10): int8 block pool + per-(block,
position, head) absmax scales (``ops.paged_cache.QuantKV``) —
quant/dequant round-trip bounds, quantize-on-store through every write
path, fallback-vs-interpret kernel parity at the decode / verify /
ragged widths, engine-level token-match-rate floors vs the fp pool
across Llama / GPT / spec-ngram, int8 EXACTNESS across engine features
(prefix cache ON/OFF, ragged ON/OFF, TP=2 — stored bytes are a pure
function of the tokens, so the int8 world is as deterministic as fp),
COW-on-quantized-block byte checks, the ``PADDLE_TPU_KV_INT8`` kill
switch (bit-for-bit fp pool), zero steady-state recompiles, the pool
byte-ratio bar (int8 <= 0.55x fp16 at identical shape), and the
always-present stats()/JSONL telemetry keys.

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

import jax.numpy as jnp

from paddle_tpu.ops import paged_cache as pc
from paddle_tpu.ops.pallas import paged_attention as pa

# random tiny models have small argmax margins, so a handful of token
# flips under int8 noise is expected — the bench pins the >=0.99 bar
# on the realistic serving workload; this floor catches regressions
# (observed match rate on these models: 1.0)
MATCH_FLOOR = 0.9


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


def _serve(model, prompts, max_new=6, **kw):
    eng = _mk_engine(model, **kw)
    outs = eng.serve(list(prompts), max_new_tokens=max_new)
    st = eng.stats()
    eng.shutdown()
    return outs, st


def _prompts(seed=0, vocab=128, lens=(7, 13, 21, 9)):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, (n,)) for n in lens]


def _match_rate(a_list, b_list):
    tot = hit = 0
    for a, b in zip(a_list, b_list):
        tot += len(a)
        hit += int(np.sum(np.asarray(a) == np.asarray(b)))
    return hit / max(tot, 1)


def _assert_exact(ref, got, tag):
    for i, (a, b) in enumerate(zip(ref, got)):
        assert np.asarray(a).tolist() == np.asarray(b).tolist(), \
            f"{tag}: request {i} diverged"


# --------------------------------------------------------- quant units


def test_quantize_roundtrip_bounds():
    """Symmetric absmax int8: per-element round-trip error is bounded
    by half a quantization step (scale / 2), zero rows survive
    exactly, and extremes map to +-127."""
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(6, 3, 64) * rng.exponential(
        size=(6, 3, 1)), jnp.float32)
    q, s = pc.kv_quantize(x)
    assert q.dtype == jnp.int8 and s.dtype == jnp.float32
    assert s.shape == x.shape[:-1]
    back = pc.kv_dequantize(q, s)
    err = np.abs(np.asarray(back) - np.asarray(x))
    bound = np.asarray(s)[..., None] * 0.5 + 1e-12
    assert (err <= bound).all()
    # absmax element hits +-127 exactly
    flat_q = np.abs(np.asarray(q)).reshape(-1, 64)
    assert (flat_q.max(axis=-1) == 127).all()
    # zero rows: scale 0, exact-zero round trip
    q0, s0 = pc.kv_quantize(jnp.zeros((2, 64), jnp.float32))
    assert float(np.abs(np.asarray(s0)).max()) == 0.0
    assert float(np.abs(np.asarray(
        pc.kv_dequantize(q0, s0))).max()) == 0.0


def test_store_helper_every_write_path():
    """All four write paths quantize-on-store through the shared
    ``_store``: values land within the round-trip bound at the right
    (block, position), and past-reach positions null-route for data
    AND scales."""
    rng = np.random.RandomState(1)
    S, MB, BS, H, D = 2, 3, 8, 2, 64
    NB = 1 + S * MB
    kp, vp = pc.init_pool(NB, BS, H, D, "int8")
    assert isinstance(kp, pc.QuantKV)
    tables = jnp.asarray(
        (1 + np.arange(S * MB, dtype=np.int32)).reshape(S, MB))

    def check(pool, want, b, o):
        got = np.asarray(pc.kv_dequantize(pool.data, pool.scale))[b, o]
        np.testing.assert_allclose(
            got, want, atol=float(np.abs(want).max()) / 127.0 + 1e-6)

    # write_decode at position 5 of each slot
    k1 = jnp.asarray(rng.randn(S, H, D), jnp.float32)
    kp, vp = pc.write_decode(kp, vp, tables,
                             jnp.full((S,), 5, jnp.int32), k1, k1)
    check(kp, np.asarray(k1[0]), 1, 5)
    check(kp, np.asarray(k1[1]), 1 + MB, 5)
    # write_tokens spanning a block boundary (positions 6..9)
    k2 = jnp.asarray(rng.randn(S, 4, H, D), jnp.float32)
    kp, vp = pc.write_tokens(kp, vp, tables,
                             jnp.full((S,), 6, jnp.int32), k2, k2)
    check(kp, np.asarray(k2[0, 0]), 1, 6)
    check(kp, np.asarray(k2[0, 3]), 2, 1)
    # write_rows with a pad row at the overflow position: the null
    # block absorbs it, live blocks (and scales) untouched
    before = (np.asarray(kp.data).copy(), np.asarray(kp.scale).copy())
    k3 = jnp.asarray(rng.randn(2, H, D), jnp.float32)
    kp, vp = pc.write_rows(kp, vp, tables,
                           jnp.asarray([0, 0], jnp.int32),
                           jnp.asarray([10, MB * BS], jnp.int32),
                           k3, k3)
    check(kp, np.asarray(k3[0]), 2, 2)
    assert (np.asarray(kp.data)[1:] != before[0][1:]).sum() <= H * D
    # write_prefill with n_real masking
    kp2, vp2 = pc.init_pool(NB, BS, H, D, "int8")
    k4 = jnp.asarray(rng.randn(S, 10, H, D), jnp.float32)
    kp2, vp2 = pc.write_prefill(kp2, vp2, tables, k4, k4,
                                n_real=jnp.asarray([10, 3]))
    check(kp2, np.asarray(k4[0, 9]), 2, 1)
    # slot 1 position 3.. masked to the null block
    assert float(np.abs(np.asarray(kp2.scale)[1 + MB, 3:]).max()) == 0.0


def test_pool_bytes_ratio_vs_fp16():
    """The acceptance bar: int8 pool (data + scales) <= 0.55x the fp16
    pool bytes at identical (NB, BS, Hkv, D)."""
    q = pc.init_pool(33, 32, 4, 64, "int8")
    f = pc.init_pool(33, 32, 4, 64, jnp.float16)
    ratio = pc.pool_bytes([q]) / pc.pool_bytes([f])
    assert ratio <= 0.55, ratio


def test_cow_copies_data_and_scales():
    """``copy_blocks`` on a quantized pool duplicates int8 data AND
    scales; the source block's bytes are untouched (the COW
    contract)."""
    rng = np.random.RandomState(2)
    kp, vp = pc.init_pool(5, 8, 2, 64, "int8")
    tables = jnp.asarray([[1, 2]], jnp.int32)
    k = jnp.asarray(rng.randn(1, 16, 2, 64), jnp.float32)
    kp, vp = pc.write_prefill(kp, vp, tables, k, k)
    src_d = np.asarray(kp.data)[1].copy()
    src_s = np.asarray(kp.scale)[1].copy()
    [(kp2, vp2)] = pc.copy_blocks([(kp, vp)], jnp.int32(1),
                                  jnp.int32(3))
    np.testing.assert_array_equal(np.asarray(kp2.data)[3], src_d)
    np.testing.assert_array_equal(np.asarray(kp2.scale)[3], src_s)
    np.testing.assert_array_equal(np.asarray(kp2.data)[1], src_d)
    np.testing.assert_array_equal(np.asarray(kp2.scale)[1], src_s)


# ------------------------------------------- kernel-vs-fallback parity


def _quant_pools(rng, S=2, MB=4, BS=8, Hkv=2, D=64,
                 lens=(11, 25)):
    NB = 1 + S * MB
    kp, vp = pc.init_pool(NB, BS, Hkv, D, "int8")
    tables = jnp.asarray(
        (1 + np.arange(S * MB, dtype=np.int32)).reshape(S, MB))
    for t in range(max(lens)):
        live = jnp.asarray([t if t < n else BS * MB
                            for n in lens], jnp.int32)
        kp, vp = pc.write_rows(
            kp, vp, tables, jnp.arange(S, dtype=jnp.int32), live,
            jnp.asarray(rng.randn(S, Hkv, D), jnp.float32),
            jnp.asarray(rng.randn(S, Hkv, D), jnp.float32))
    return kp, vp, tables, jnp.asarray(lens, jnp.int32)


def test_kernel_parity_decode_width():
    if pa.pallas_paged_attention is None:
        pytest.skip("pallas unavailable on this jax build")
    rng = np.random.RandomState(3)
    kp, vp, tables, lens = _quant_pools(rng)
    q = jnp.asarray(rng.randn(2, 4, 64), jnp.float32)
    ref = pa._xla_paged_attention(q, kp, vp, tables, lens)
    out = pa.pallas_paged_attention(q, kp, vp, tables, lens,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kernel_parity_verify_width():
    if pa.pallas_paged_verify_attention is None:
        pytest.skip("pallas unavailable on this jax build")
    rng = np.random.RandomState(4)
    kp, vp, tables, lens = _quant_pools(rng)
    q = jnp.asarray(rng.randn(2, 3, 4, 64), jnp.float32)
    ref = pa._xla_paged_verify(q, kp, vp, tables, lens)
    out = pa.pallas_paged_verify_attention(q, kp, vp, tables, lens,
                                           interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_kernel_parity_ragged_width():
    """Ragged mixed batch over an int8 pool: a decode row, a verify
    window and a wide chunk slot in one packed buffer — interpret-mode
    kernel vs the two-lane gather fallback."""
    if pa.pallas_ragged_paged_attention is None:
        pytest.skip("pallas unavailable on this jax build")
    rng = np.random.RandomState(5)
    S, MB, BS = 3, 4, 8
    kp, vp, tables, _ = _quant_pools(rng, S=S, lens=(9, 17, 4))
    q_lens = np.asarray([1, 3, 8], np.int64)
    base = np.asarray([9, 17, 4], np.int64)
    R, W = 16, 8
    row_slot, row_pos, row_starts, _ = pc.ragged_row_meta(
        q_lens, base, R, MB * BS)
    q = jnp.asarray(rng.randn(R, 4, 64), jnp.float32)
    ctx = jnp.asarray(base + 1, jnp.int32)
    ref = pa._xla_ragged_paged(q, kp, vp, tables, ctx,
                               jnp.asarray(q_lens),
                               jnp.asarray(row_starts),
                               jnp.asarray(row_slot), 3, W)
    out = pa.pallas_ragged_paged_attention(
        q, kp, vp, tables, ctx, jnp.asarray(q_lens),
        jnp.asarray(row_starts), w_max=W, interpret=True)
    for s, n in enumerate(map(int, q_lens)):
        s0 = int(row_starts[s])
        np.testing.assert_allclose(
            np.asarray(out[s0:s0 + n]), np.asarray(ref[s0:s0 + n]),
            rtol=1e-5, atol=1e-5, err_msg=f"slot {s}")


# -------------------------------------------------- engine-level tests


def test_engine_match_rate_llama(llama_tiny):
    prompts = _prompts()
    fp, st_fp = _serve(llama_tiny, prompts)
    q8, st_q8 = _serve(llama_tiny, prompts, kv_cache_dtype="int8")
    assert st_fp["kv_cache_dtype"] == "float32"
    assert st_q8["kv_cache_dtype"] == "int8"
    assert _match_rate(fp, q8) >= MATCH_FLOOR
    # the quantization win is visible in the telemetry: pool and
    # per-step bytes drop by ~2x
    assert st_q8["kv_pool_bytes"] < 0.6 * st_fp["kv_pool_bytes"]
    assert 0 < st_q8["kv_bytes_per_step"] \
        < 0.6 * st_fp["kv_bytes_per_step"]


def test_engine_match_rate_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(3)
    m = GPTForCausalLM(GPTConfig.tiny(vocab=96, hidden=64, layers=2,
                                      heads=4))
    m.eval()
    prompts = _prompts(seed=2, vocab=96, lens=(5, 11, 17))
    fp, _ = _serve(m, prompts)
    q8, st = _serve(m, prompts, kv_cache_dtype="int8")
    assert st["kv_cache_dtype"] == "int8"
    assert _match_rate(fp, q8) >= MATCH_FLOOR


def test_engine_int8_exact_prefix_cache(llama_tiny):
    """WITHIN the int8 world the engine stays deterministic: a prefix
    cache hit maps blocks holding bitwise the int8 the cold path
    recomputes (quantize-on-store is a pure function of the tokens),
    so warm == cold token-exact."""
    rng = np.random.RandomState(6)
    sysp = rng.randint(1, 128, (24,))
    prompts = [np.concatenate([sysp, rng.randint(1, 128, (t,))])
               for t in (5, 9, 3)]
    cold, _ = _serve(llama_tiny, prompts, kv_cache_dtype="int8",
                     enable_prefix_cache=False)
    eng = _mk_engine(llama_tiny, kv_cache_dtype="int8")
    warm1 = eng.serve(list(prompts), max_new_tokens=6)
    warm2 = eng.serve(list(prompts), max_new_tokens=6)
    st = eng.stats()
    eng.shutdown()
    assert st["prefix_blocks_reused"] > 0
    _assert_exact(cold, warm1, "int8 cold vs first wave")
    _assert_exact(cold, warm2, "int8 cold vs cached wave")


def test_engine_int8_matches_paged_generate(llama_tiny):
    """The engine over an int8 pool emits what ``generate(cache_impl=
    "paged", kv_cache_dtype="int8")`` emits for each prompt alone: the
    per-width paged loop is an independent reference (dense prefill
    scattered into the pool, one decode row a step) that stores and
    reads the same quantized bytes."""
    prompts = _prompts(seed=7)
    got, st = _serve(llama_tiny, prompts, kv_cache_dtype="int8")
    assert st["kv_cache_dtype"] == "int8"
    want = []
    for p in prompts:
        out, _ = llama_tiny.generate(
            paddle.to_tensor(p[None].astype(np.int64)),
            max_new_tokens=6, cache_impl="paged",
            kv_cache_dtype="int8", decode_strategy="greedy_search")
        want.append(np.asarray(out.numpy())[0])
    _assert_exact(want, got, "int8 engine vs paged generate")


def test_engine_int8_spec_ngram(llama_tiny):
    """Speculative verify/rollback over quantized pools: greedy spec
    output IS the plain greedy chain, so int8-spec == int8-plain
    token-exact; and it stays near the fp chain."""
    rng = np.random.RandomState(8)
    base = rng.randint(1, 128, (6,))
    prompts = [np.tile(base, 4)[:n] for n in (17, 23)]
    plain, _ = _serve(llama_tiny, prompts, kv_cache_dtype="int8")
    spec, st = _serve(llama_tiny, prompts, kv_cache_dtype="int8",
                      num_speculative_tokens=2)
    assert st["kv_cache_dtype"] == "int8"
    assert st["spec_tokens_proposed"] > 0
    _assert_exact(plain, spec, "int8 spec vs int8 plain")
    fp, _ = _serve(llama_tiny, prompts)
    assert _match_rate(fp, spec) >= MATCH_FLOOR


def test_engine_int8_tp2_exact():
    """TP=2 over quantized pools (scale pool sharded on the same
    kv_head cut): token-exact vs the single-device int8 engine."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices (conftest CPU mesh)")
    paddle.seed(7)
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           kv_heads=4, ffn=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    prompts = _prompts(seed=9, lens=(5, 13))
    ref, _ = _serve(m, prompts, kv_cache_dtype="int8")
    tp, st = _serve(m, prompts, kv_cache_dtype="int8", tp_degree=2)
    assert st["tp_degree"] == 2
    assert st["kv_cache_dtype"] == "int8"
    # the scale pool's bytes shard with the data pool
    assert st["tp_pool_bytes_per_shard"] * 2 == st["kv_pool_bytes"]
    _assert_exact(ref, tp, "int8 tp2 vs single-device")


def test_kill_switch_bit_parity(llama_tiny, monkeypatch):
    """PADDLE_TPU_KV_INT8=0 beats an explicit 'int8' config: the pool
    is the plain fp array and outputs are bitwise the default
    engine's."""
    prompts = _prompts(seed=10)
    ref, st_ref = _serve(llama_tiny, prompts)
    monkeypatch.setenv("PADDLE_TPU_KV_INT8", "0")
    off, st_off = _serve(llama_tiny, prompts, kv_cache_dtype="int8")
    assert st_off["kv_cache_dtype"] == st_ref["kv_cache_dtype"] \
        == "float32"
    assert st_off["kv_pool_bytes"] == st_ref["kv_pool_bytes"]
    _assert_exact(ref, off, "kill switch vs default")
    # and the env twin turns int8 ON when the config leaves it open
    monkeypatch.setenv("PADDLE_TPU_KV_INT8", "1")
    on, st_on = _serve(llama_tiny, prompts)
    assert st_on["kv_cache_dtype"] == "int8"
    assert _match_rate(ref, on) >= MATCH_FLOOR


def test_default_path_untouched(llama_tiny):
    """No config, no env: the pool is a plain array in the model dtype
    (the pre-quantization layout, structurally bit-for-bit)."""
    eng = _mk_engine(llama_tiny)
    kp, vp = eng._pools[0]
    assert not isinstance(kp, pc.QuantKV)
    assert jnp.dtype(kp.dtype) == jnp.float32
    assert eng.stats()["kv_cache_dtype"] == "float32"
    eng.shutdown()
    with pytest.raises(ValueError):
        _mk_engine(llama_tiny, kv_cache_dtype="fp7")


def test_zero_steady_state_recompiles_int8(llama_tiny):
    eng = _mk_engine(llama_tiny, kv_cache_dtype="int8")
    eng.serve(_prompts(seed=11), max_new_tokens=4)
    st1 = eng.stats()
    eng.serve(_prompts(seed=12, lens=(6, 15, 10, 20)),
              max_new_tokens=4)
    st2 = eng.stats()
    eng.shutdown()
    assert st2["executables_compiled"] == st1["executables_compiled"] \
        == 1
    assert st2["decode_compiles"] == 1


def test_generate_kv_cache_dtype(llama_tiny):
    """generate(kv_cache_dtype='int8') rides the paged loop; an
    explicit dense cache cannot honor it."""
    ids = paddle.to_tensor(
        np.random.RandomState(13).randint(1, 128, (1, 12))
        .astype(np.int64))
    fp, _ = llama_tiny.generate(ids, max_new_tokens=6,
                                cache_impl="paged")
    q8, _ = llama_tiny.generate(ids, max_new_tokens=6,
                                kv_cache_dtype="int8")
    assert _match_rate([fp.numpy()[0]], [q8.numpy()[0]]) >= MATCH_FLOOR
    with pytest.raises(ValueError):
        llama_tiny.generate(ids, max_new_tokens=4, cache_impl="dense",
                            kv_cache_dtype="int8")


def test_stats_and_jsonl_keys(tmp_path, llama_tiny):
    import json
    _, st = _serve(llama_tiny, _prompts(seed=14, lens=(5, 9)),
                   kv_cache_dtype="int8")
    for k in ("kv_cache_dtype", "kv_pool_bytes", "kv_bytes_per_step"):
        assert k in st
    # fp engines carry the SAME keys (consumers never KeyError)
    _, st_fp = _serve(llama_tiny, _prompts(seed=14, lens=(5,)))
    for k in ("kv_cache_dtype", "kv_pool_bytes", "kv_bytes_per_step"):
        assert k in st_fp
    path = monitor.export_jsonl(str(tmp_path / "metrics.jsonl"))
    names = {json.loads(line)["name"] for line in open(path)}
    for want in ("serving_kv_pool_bytes", "serving_kv_bytes_per_step",
                 "serving_kv_cache_dtype"):
        assert want in names, f"{want} missing from JSONL export"


def test_tier1_no_slow_marker():
    """CI guard (the PR-4 pattern): every kv-quant test runs in the
    tier-1 sweep, the three kernel-parity widths exist, and engine
    shutdown leak-checking is exercised."""
    import tests.conftest as c
    here = os.path.basename(__file__).replace(".py", "")
    assert not any(t.startswith(here) for t in c._SLOW_TESTS)
    names = {k for k in globals() if k.startswith("test_")}
    for want in ("test_kernel_parity_decode_width",
                 "test_kernel_parity_verify_width",
                 "test_kernel_parity_ragged_width",
                 "test_kill_switch_bit_parity"):
        assert want in names
    import inspect
    src = inspect.getsource(_serve)
    assert "shutdown" in src

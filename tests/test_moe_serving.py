"""MoE in production (ISSUE 8): fused-dispatch grouped matmul parity
(fwd + VJP, interpret mode) and MoE through the paged/ragged serving
engine — Qwen2-MoE/DeepSeek-MoE greedy token-exact vs the dense cached
forward, spec-ngram on dropless MoE, zero steady-state recompiles,
kill switch, validation, telemetry."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.distributed import moe as M


def _routing(rng, s, e, k):
    """Host-side routing fixture shared by the kernel parity tests:
    stable expert-major sort of random top-k picks, exactly the
    dispatch `_grouped_dispatch` derives."""
    flat_e = rng.randint(0, e, s * k).astype(np.int32)
    order = np.argsort(flat_e, kind="stable").astype(np.int32)
    counts = np.bincount(flat_e, minlength=e).astype(np.int32)
    return order, (order // k).astype(np.int32), counts


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_gmm_interpret_parity_fwd(dtype):
    """Gather-on-read + swiglu-epilogue + scatter-on-write kernels
    reproduce the pack+gmm reference (sorted take -> ragged_dot ->
    unsort scatter) under the Pallas interpreter."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import moe_gmm as G

    rng = np.random.RandomState(0)
    s, d, f, e, k = 64, 64, 128, 8, 2
    m = s * k
    dt = jnp.dtype(dtype)
    x = jnp.asarray(rng.randn(s, d), dt)
    gu = jnp.asarray(0.1 * rng.randn(e, d, 2 * f), dt)
    dn = jnp.asarray(0.1 * rng.randn(e, f, d), dt)
    order, src, counts = _routing(rng, s, e, k)
    gs = jnp.asarray(counts)

    xs = jnp.take(x, jnp.asarray(src), axis=0)
    gu_ref = jax.lax.ragged_dot(xs, gu, gs)
    g_, u_ = jnp.split(gu_ref, 2, axis=-1)
    h_ref = (jax.nn.silu(g_.astype(jnp.float32)).astype(dt) * u_)
    ys_ref = jax.lax.ragged_dot(h_ref, dn, gs)
    ys_tok_ref = np.zeros((m, d), np.float32)
    ys_tok_ref[order] = np.asarray(ys_ref, np.float32)

    h = G.gather_gmm_swiglu(x, jnp.asarray(src), gu, gs,
                            interpret=True)
    ys_tok = G.scatter_gmm(h, dn, gs, jnp.asarray(order),
                           interpret=True)
    tol = 1e-5 if dtype == "float32" else 0.1
    np.testing.assert_allclose(np.asarray(h, np.float32),
                               np.asarray(h_ref, np.float32),
                               atol=tol, rtol=tol)
    np.testing.assert_allclose(np.asarray(ys_tok, np.float32),
                               ys_tok_ref, atol=tol, rtol=tol)
    # the plain gather gmm (no epilogue) and the transposed variants
    # the backward replays
    o1 = G.gather_gmm(x, jnp.asarray(src), gu, gs, interpret=True)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(gu_ref, np.float32),
                               atol=tol, rtol=tol)
    o2 = G.gather_gmm(jnp.asarray(ys_tok_ref, dt), jnp.asarray(order),
                      dn, gs, transpose_rhs=True, interpret=True)
    ref2 = jax.lax.ragged_dot(jnp.asarray(ys_tok_ref, dt)[order],
                              dn.swapaxes(1, 2), gs)
    np.testing.assert_allclose(np.asarray(o2, np.float32),
                               np.asarray(ref2, np.float32),
                               atol=tol * 30, rtol=tol * 30)


def test_fused_dispatch_parity_fwd_and_vjp():
    """The WIRED fused path (``PADDLE_TPU_MOE_FUSED_GMM=interpret``
    through ``moe_dispatch_combine_dropless``) matches the sorted
    pack+gmm path it replaces — outputs AND all four gradients (x,
    gate_up, down, router logits), i.e. the custom VJP replaying
    gather/scatter backward is the same function."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(1)
    s, d, f, e, k = 128, 128, 128, 8, 2
    x = jnp.asarray(rng.randn(s, d).astype(np.float32))
    logits = jnp.asarray(rng.randn(s, e).astype(np.float32))
    gu = jnp.asarray((0.1 * rng.randn(e, d, 2 * f)).astype(np.float32))
    dn = jnp.asarray((0.1 * rng.randn(e, f, d)).astype(np.float32))

    def loss(x, gu, dn, logits):
        y, aux = M.moe_dispatch_combine_dropless(x, logits, e, k, gu,
                                                 dn)
        return jnp.sum(y * y) + aux, y

    grad = jax.value_and_grad(loss, argnums=(0, 1, 2, 3),
                              has_aux=True)
    old = os.environ.get("PADDLE_TPU_MOE_FUSED_GMM")
    try:
        os.environ["PADDLE_TPU_MOE_FUSED_GMM"] = "0"
        (l0, y0), g0 = grad(x, gu, dn, logits)
        os.environ["PADDLE_TPU_MOE_FUSED_GMM"] = "interpret"
        (l1, y1), g1 = grad(x, gu, dn, logits)
        assert M.MOE_STATS["grouped_mm_kernel"] is not None
    finally:
        if old is None:
            os.environ.pop("PADDLE_TPU_MOE_FUSED_GMM", None)
        else:
            os.environ["PADDLE_TPU_MOE_FUSED_GMM"] = old
    np.testing.assert_allclose(float(l1), float(l0), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y0),
                               atol=1e-4, rtol=1e-4)
    for name, a, b in zip(("dx", "dgate_up", "ddown", "dlogits"), g0,
                          g1):
        scale = max(float(jnp.abs(a).max()), 1.0)
        np.testing.assert_allclose(
            np.asarray(b) / scale, np.asarray(a) / scale,
            atol=2e-5, rtol=2e-5, err_msg=name)


def test_fused_kernel_reflects_in_moe_stats():
    """A forward through the fused path stamps ``MOE_STATS`` with the
    fused kernel name at trace time (the bench/ops 'which kernel did I
    compile' contract extends to the fused engine)."""
    import jax.numpy as jnp

    rng = np.random.RandomState(2)
    s, d, f, e, k = 128, 128, 128, 4, 2
    x = jnp.asarray(rng.randn(s, d).astype(np.float32))
    logits = jnp.asarray(rng.randn(s, e).astype(np.float32))
    gu = jnp.asarray((0.1 * rng.randn(e, d, 2 * f)).astype(np.float32))
    dn = jnp.asarray((0.1 * rng.randn(e, f, d)).astype(np.float32))
    old = os.environ.get("PADDLE_TPU_MOE_FUSED_GMM")
    try:
        os.environ["PADDLE_TPU_MOE_FUSED_GMM"] = "interpret"
        M.reset_moe_stats()
        M.moe_dispatch_combine_dropless(x, logits, e, k, gu, dn)
        assert M.MOE_STATS["grouped_mm_kernel"] == "fused_gmm"
        assert M.MOE_STATS["grouped_mm_calls"] >= 2
    finally:
        if old is None:
            os.environ.pop("PADDLE_TPU_MOE_FUSED_GMM", None)
        else:
            os.environ["PADDLE_TPU_MOE_FUSED_GMM"] = old


def test_fused_kill_switch_bit_exact():
    """``PADDLE_TPU_MOE_FUSED_GMM=0`` pins the sort->pack->gmm path
    bit-for-bit: it wins over the config/env fused request (the fused
    kernels are never traced), and the output is BITWISE the default
    CPU path's."""
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    s, d, f, e, k = 128, 128, 128, 4, 2
    x = jnp.asarray(rng.randn(s, d).astype(np.float32))
    logits = jnp.asarray(rng.randn(s, e).astype(np.float32))
    gu = jnp.asarray((0.1 * rng.randn(e, d, 2 * f)).astype(np.float32))
    dn = jnp.asarray((0.1 * rng.randn(e, f, d)).astype(np.float32))
    old = os.environ.get("PADDLE_TPU_MOE_FUSED_GMM")
    try:
        os.environ.pop("PADDLE_TPU_MOE_FUSED_GMM", None)
        y_default, _ = M.moe_dispatch_combine_dropless(
            x, logits, e, k, gu, dn)
        os.environ["PADDLE_TPU_MOE_FUSED_GMM"] = "0"
        assert not M.moe_fused_enabled()
        # the kill switch beats an explicit fused=True request
        assert M._use_fused_gmm(s * k, d, f, fused=True) is False
        M.reset_moe_stats()
        y_killed, _ = M.moe_dispatch_combine_dropless(
            x, logits, e, k, gu, dn, fused=True)
        assert M.MOE_STATS["grouped_mm_kernel"] == "ragged_dot"
        assert (np.asarray(y_killed) == np.asarray(y_default)).all()
    finally:
        if old is None:
            os.environ.pop("PADDLE_TPU_MOE_FUSED_GMM", None)
        else:
            os.environ["PADDLE_TPU_MOE_FUSED_GMM"] = old


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------

def _tiny_qwen2_moe(dropless=True, **kw):
    from paddle_tpu.models.qwen2_moe import (Qwen2MoeConfig,
                                             Qwen2MoeForCausalLM)
    paddle.seed(7)
    cfg = Qwen2MoeConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                              kv_heads=2, moe_ffn=32, shared_ffn=48,
                              experts=4, topk=2)
    cfg.dropless = dropless
    for k_, v in kw.items():
        setattr(cfg, k_, v)
    m = Qwen2MoeForCausalLM(cfg)
    m.eval()
    return m


def _dense_refs(model, prompts, max_new):
    outs = []
    for p in prompts:
        out, _ = model.generate(
            paddle.to_tensor(p[None].astype(np.int64)),
            max_new_tokens=max_new, cache_impl="dense",
            decode_strategy="greedy_search")
        outs.append(np.asarray(out.numpy())[0])
    return outs


def test_qwen2_moe_engine_greedy_exact():
    """Qwen2-MoE (dropless) serves through ``ServingEngine`` greedy
    token-exact vs ``generate(cache_impl="dense")``."""
    from paddle_tpu.inference import ServingConfig, ServingEngine
    model = _tiny_qwen2_moe()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
               for n in (5, 9, 13)]
    refs = _dense_refs(model, prompts, 6)
    eng = ServingEngine(model, ServingConfig(
        num_slots=3, block_size=4, max_model_len=64,
        max_new_tokens=6, prefill_chunk=8))
    outs = eng.serve([p.copy() for p in prompts], max_new_tokens=6)
    st = eng.stats()
    eng.shutdown()
    for o, r in zip(outs, refs):
        assert (np.asarray(o) == r).all(), (o, r)
    assert st["moe"] is True
    assert st["moe_dispatches"] > 0
    assert st["moe_routing_entropy"] > 0.0
    assert st["moe_expert_load_max"] > 0.0


def test_deepseek_moe_engine_greedy_exact():
    """DeepSeek-MoE (fine-grained experts + ungated shared experts,
    first layer dense) through the engine == dense cached forward."""
    from paddle_tpu.models.deepseek_moe import (DeepseekMoeConfig,
                                                DeepseekMoeForCausalLM)
    from paddle_tpu.inference import ServingConfig, ServingEngine
    paddle.seed(5)
    cfg = DeepseekMoeConfig.tiny(vocab=128, hidden=64, layers=2,
                                 heads=4, kv_heads=4, moe_ffn=32,
                                 dense_ffn=48, experts=4, shared=1,
                                 topk=2)
    cfg.dropless = True
    model = DeepseekMoeForCausalLM(cfg)
    model.eval()
    rng = np.random.RandomState(1)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
               for n in (6, 11)]
    refs = _dense_refs(model, prompts, 5)
    eng = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=4, max_model_len=64, max_new_tokens=5,
        prefill_chunk=8))
    outs = eng.serve([p.copy() for p in prompts], max_new_tokens=5)
    eng.shutdown()
    for o, r in zip(outs, refs):
        assert (np.asarray(o) == r).all()


def test_spec_ngram_on_dropless_moe_token_exact():
    """The speculative-verify exclusion lifts for dropless MoE: a
    gamma=2 n-gram engine emits exactly the plain engine's greedy
    chain (per-row dropless routing cannot see the other window
    rows)."""
    from paddle_tpu.inference import ServingConfig, ServingEngine
    model = _tiny_qwen2_moe()
    rng = np.random.RandomState(2)
    phrase = rng.randint(1, 128, (4,))
    prompts = [np.tile(phrase, 4).astype(np.int32) for _ in range(3)]
    eng = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=4, max_model_len=64, max_new_tokens=8,
        prefill_chunk=8))
    refs = eng.serve([p.copy() for p in prompts], max_new_tokens=8)
    eng.shutdown()
    eng2 = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=4, max_model_len=64, max_new_tokens=8,
        prefill_chunk=8, num_speculative_tokens=2))
    outs = eng2.serve([p.copy() for p in prompts], max_new_tokens=8)
    st = eng2.stats()
    eng2.shutdown()
    assert st["spec_tokens_proposed"] > 0
    for o, r in zip(outs, refs):
        assert (np.asarray(o) == np.asarray(r)).all()


def test_moe_engine_zero_steady_state_recompiles():
    """The ragged MoE engine compiles ONE executable and serves two
    request waves (fresh admissions mid-flight) without ever building
    another."""
    from paddle_tpu.inference import ServingConfig, ServingEngine
    model = _tiny_qwen2_moe()
    rng = np.random.RandomState(3)
    eng = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=4, max_model_len=64, max_new_tokens=6,
        prefill_chunk=8))
    eng.serve([rng.randint(1, 128, (n,)).astype(np.int32)
               for n in (5, 9)], max_new_tokens=6)
    st0 = eng.stats()
    assert st0["executables_compiled"] == 1
    eng.serve([rng.randint(1, 128, (n,)).astype(np.int32)
               for n in (12, 4, 8)], max_new_tokens=6)
    st1 = eng.stats()
    eng.shutdown()
    assert st1["executables_compiled"] == st0["executables_compiled"]
    assert st1["decode_compiles"] == 1


def test_capacity_moe_engine_rejected():
    """Capacity-routed MoE stays excluded from serving, with an error
    that names the fix (dropless routing) — never a silent wrong
    logit."""
    from paddle_tpu.inference import ServingConfig, ServingEngine
    model = _tiny_qwen2_moe(dropless=False)
    with pytest.raises(NotImplementedError, match="dropless"):
        ServingEngine(model, ServingConfig(num_slots=2,
                                           max_model_len=64))


def test_moe_tp_divisibility_validated():
    """``tp_degree`` must divide ``moe_intermediate_size`` (the
    stacked expert ffn shard dim) — rejected at engine construction,
    before any compile."""
    from paddle_tpu.inference import ServingConfig, ServingEngine
    model = _tiny_qwen2_moe(moe_intermediate_size=33)
    # heads (4), kv_heads (2) and vocab (128) all divide 2; the expert
    # width (33) does not — the MoE check must be the one that fires
    with pytest.raises(ValueError, match="moe_intermediate_size"):
        ServingEngine(model, ServingConfig(num_slots=2,
                                           max_model_len=64,
                                           tp_degree=2))


def test_moe_engine_tp2_token_exact():
    """Dropless MoE under tensor-parallel serving (tp_degree=2 on the
    8-CPU-device mesh): stacked expert weights shard their ffn dim
    over mp, the dispatch takes the GSPMD ragged_dot lowering (opaque
    Pallas kernels stay off sharded traces), and greedy tokens equal
    the single-device engine's."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    from paddle_tpu.inference import ServingConfig, ServingEngine
    model = _tiny_qwen2_moe()
    rng = np.random.RandomState(4)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int32)
               for n in (5, 10)]
    eng = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=4, max_model_len=64, max_new_tokens=5,
        prefill_chunk=8))
    refs = eng.serve([p.copy() for p in prompts], max_new_tokens=5)
    eng.shutdown()
    eng_tp = ServingEngine(model, ServingConfig(
        num_slots=2, block_size=4, max_model_len=64, max_new_tokens=5,
        prefill_chunk=8, tp_degree=2))
    outs = eng_tp.serve([p.copy() for p in prompts], max_new_tokens=5)
    st = eng_tp.stats()
    eng_tp.shutdown()
    assert st["tp_degree"] == 2 and st["moe"] is True
    assert st["moe_dispatches"] > 0      # the tap observes under TP too
    for o, r in zip(outs, refs):
        assert (np.asarray(o) == np.asarray(r)).all()


def test_moe_stats_keys_always_present_and_jsonl(tmp_path):
    """The moe_* stats keys exist on NON-MoE engines too (False/0.0 —
    mixed fleets never KeyError), and the routing metrics land in the
    JSONL export."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.inference import ServingConfig, ServingEngine
    paddle.seed(0)
    dense = LlamaForCausalLM(LlamaConfig.tiny(vocab=64, hidden=32,
                                              layers=1, heads=4,
                                              kv_heads=2, ffn=64))
    dense.eval()
    eng = ServingEngine(dense, ServingConfig(
        num_slots=1, block_size=4, max_model_len=32, max_new_tokens=3,
        prefill_chunk=4))
    eng.serve([np.asarray([1, 2, 3], np.int32)], max_new_tokens=3)
    st = eng.stats()
    eng.shutdown()
    for key in ("moe", "moe_fused_gmm", "moe_routing_entropy",
                "moe_expert_load_max", "moe_dispatches"):
        assert key in st, key
    assert st["moe"] is False
    assert st["moe_dispatches"] == 0

    model = _tiny_qwen2_moe()
    eng2 = ServingEngine(model, ServingConfig(
        num_slots=1, block_size=4, max_model_len=32, max_new_tokens=3,
        prefill_chunk=4))
    eng2.serve([np.asarray([3, 2, 1], np.int32)], max_new_tokens=3)
    st2 = eng2.stats()
    eng2.shutdown()
    assert st2["moe"] is True and st2["moe_dispatches"] > 0
    # honest fused stat: reports whether the fused kernel actually
    # TRACED into an executable — never on a CPU backend
    assert st2["moe_fused_gmm"] is False
    path = monitor.export_jsonl(str(tmp_path / "metrics.jsonl"))
    names = {json.loads(line)["name"] for line in open(path)}
    assert "serving_moe_expert_load" in names
    assert "serving_moe_routing_entropy" in names
    # telemetry opt-out: executables trace without the tap — zero
    # callbacks, keys still present
    eng3 = ServingEngine(model, ServingConfig(
        num_slots=1, block_size=4, max_model_len=32, max_new_tokens=3,
        prefill_chunk=4, moe_telemetry=False))
    eng3.serve([np.asarray([2, 3, 4], np.int32)], max_new_tokens=3)
    st3 = eng3.stats()
    eng3.shutdown()
    assert st3["moe_dispatches"] == 0
    assert st3["moe_routing_entropy"] == 0.0


def test_routing_tap_masks_pad_rows():
    """The serving telemetry tap counts LIVE rows only: with a
    ``serving_rows_mask`` armed, pad rows of the fixed-shape serving
    buffers (which all route identically) are excluded, so a lightly
    loaded tick cannot read as hot-expert skew."""
    import jax.numpy as jnp

    captured = []

    def sink(load, ent):
        captured.append((np.asarray(load), float(ent)))

    s, d, f, e, k = 8, 16, 16, 4, 2
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(s, d).astype(np.float32))
    # live rows 0..3 route to experts {1, 2}; pad rows 4..7 to {0, 3}
    logits = np.full((s, e), -10.0, np.float32)
    logits[:4, 1] = 5.0
    logits[:4, 2] = 4.0
    logits[4:, 0] = 5.0
    logits[4:, 3] = 4.0
    gu = jnp.asarray((0.1 * rng.randn(e, d, 2 * f)).astype(np.float32))
    dn = jnp.asarray((0.1 * rng.randn(e, f, d)).astype(np.float32))
    mask = jnp.asarray([True] * 4 + [False] * 4)
    with M.serving_stats_tap(sink), M.serving_rows_mask(mask):
        y, _ = M.moe_dispatch_combine_dropless(
            x, jnp.asarray(logits), e, k, gu, dn)
    np.asarray(y)                      # force execution -> callback
    assert captured, "tap did not fire"
    load, ent = captured[0]
    assert load[0] == 0.0 and load[3] == 0.0, load   # pads excluded
    np.testing.assert_allclose(load[1], 0.5, atol=1e-6)
    np.testing.assert_allclose(load[2], 0.5, atol=1e-6)
    # without the mask the pad experts would dominate the same tick
    captured.clear()
    with M.serving_stats_tap(sink):
        y2, _ = M.moe_dispatch_combine_dropless(
            x, jnp.asarray(logits), e, k, gu, dn)
    np.asarray(y2)
    assert captured[0][0][0] > 0.0


def test_generate_bucketing_lifted_for_dropless_moe():
    """Prompt bucketing (PR 3's capacity-MoE exclusion) admits
    dropless MoE: left-pad rows route per-row, so pads cannot perturb
    real tokens."""
    model = _tiny_qwen2_moe()
    assert model._bucket_eligible()
    assert not _tiny_qwen2_moe(dropless=False)._bucket_eligible()


def test_tier1_no_slow_marker():
    """CI guard (the PR-4..7 pattern): every MoE-serving test runs in
    the tier-1 ``-m 'not slow'`` sweep, the fused-kernel parity tests
    are present, and each engine is torn down through shutdown()'s
    allocator leak sweep."""
    import tests.conftest as c
    here = open(__file__).read()
    assert "pytest.mark.slow" not in here.replace(
        '"pytest.mark.slow"', "")
    names = [ln.split("(")[0][4:] for ln in here.splitlines()
             if ln.startswith("def test_")]
    overlap = set(names) & set(c._SLOW_TESTS)
    assert not overlap, f"tier-1 MoE-serving tests marked slow: {overlap}"
    assert "test_fused_gmm_interpret_parity_fwd" in names
    assert "test_fused_dispatch_parity_fwd_and_vjp" in names
    assert here.count(".shutdown()") >= 6, \
        "engine shutdown (check_leaks) must guard these tests"

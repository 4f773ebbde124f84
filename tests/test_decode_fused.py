"""Mega-kernelized decode tick (ISSUE 13): fused norm->QKV /
attention-epilogue->O-projection / norm->gate-up / swiglu->down Pallas
kernels (``ops/pallas/decode_fused.py``), the in-executable sampling
head with per-slot (temperature, top_k, top_p) device tensors, the
``generate()`` sampling-knobs-out-of-the-jit-key recompile fix, and
the ``monitor.kernel_census`` observability layer.

Covered: interpret-mode kernel-vs-fallback parity for both fused
bodies at decode/verify/chunk row widths (fp32 + bf16, RMSNorm +
LayerNorm, with/without biases), engine-level greedy token-exactness
fused ON vs OFF across Llama / GPT / int8 pools / speculative n-gram /
TP=2 / the cluster (and interpret mode — the REAL kernels in the
traced graph — against OFF), the ``PADDLE_TPU_FUSED_DECODE=0`` kill
switch beating an explicit config True, zero steady-state recompiles
ACROSS DISTINCT SAMPLING CONFIGS (the deleted recompile class),
per-request sampling plumbing (``submit(temperature/top_k/top_p)`` —
top_k=1 rows reproduce the greedy engine token-for-token, validation
on greedy engines), the disaggregated handoff carrying the knobs, the
kernel census (launch-proxy collapse measured with interpret-routed
kernels), and the ``generate_jit_cache`` one-executable pin.

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
from paddle_tpu.ops.pallas import decode_fused as df

jnp = pytest.importorskip("jax.numpy")
import jax  # noqa: E402


@pytest.fixture
def llama_tiny():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           kv_heads=2, ffn=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


@pytest.fixture
def llama_eligible():
    """Kernel-eligible shape (head_dim 128, 128-multiple widths) for
    interpret-mode engine runs and the census collapse."""
    paddle.seed(7)
    cfg = LlamaConfig.tiny(vocab=256, hidden=256, layers=2, heads=2,
                           kv_heads=1, ffn=512)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _prompts(vocab=128, lens=(5, 11, 19)):
    rng = np.random.RandomState(0)
    return [rng.randint(1, vocab, (n,)) for n in lens]


def _serve(model, prompts, monkeypatch, mode="1", max_new=6,
           waves=1, draft=None, submit_kw=None, **kw):
    """Serve ``prompts`` with the fused mode forced via env; returns
    (outputs, stats, kernel_census)."""
    monkeypatch.setenv("PADDLE_TPU_FUSED_DECODE", mode)
    base = dict(num_slots=2, block_size=8, max_model_len=96,
                prefill_chunk=8)
    base.update(kw)
    eng = ServingEngine(model, ServingConfig(**base), draft_model=draft)
    outs = []
    for _ in range(waves):
        if submit_kw:
            rids = [eng.submit(p.copy(), max_new, **submit_kw)
                    for p in prompts]
            done = eng.run()
            outs += [done[r] for r in rids]
        else:
            outs += eng.serve([p.copy() for p in prompts],
                              max_new_tokens=max_new)
    st = eng.stats()
    kc = eng.kernel_census()
    eng.shutdown()
    return outs, st, kc


def _assert_equal(a, b, tag):
    assert len(a) == len(b)
    for i, (x, y) in enumerate(zip(a, b)):
        np.testing.assert_array_equal(
            x, y, err_msg=f"{tag}: request {i} diverged")


# --------------------------------------------------------- kernel parity


@pytest.mark.parametrize("rows", [2, 6, 24])     # decode/verify/chunk
@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_norm_matmul_kernel_matches_fallback_interpret(rows, dtype):
    """Both norm flavors, multi-weight (the QKV triple with one bias)
    — interpret-mode kernel vs the bitwise-unfused XLA fallback at all
    three serving row widths."""
    rng = np.random.RandomState(rows)
    dt = jnp.dtype(dtype)
    d = 64
    x = jnp.asarray(rng.randn(rows, d), dt)
    g = jnp.asarray(1 + 0.1 * rng.randn(d), dt)
    beta = jnp.asarray(0.1 * rng.randn(d), dt)
    ws = [jnp.asarray(rng.randn(d, n) / 8, dt) for n in (128, 64, 64)]
    bs = [jnp.asarray(rng.randn(128) / 8, dt), None, None]
    tol = 1e-5 if dt == jnp.float32 else 3e-2
    for kind, b_ in (("rms", None), ("ln", beta)):
        ref = df._xla_norm_matmul(x, g, b_, ws, bs, eps=1e-6,
                                  kind=kind)
        got = df.pallas_norm_matmul(x, g, b_, ws, bs, eps=1e-6,
                                    kind=kind, interpret=True)
        for r, o in zip(ref, got):
            np.testing.assert_allclose(
                np.asarray(r, np.float32), np.asarray(o, np.float32),
                atol=tol, rtol=tol, err_msg=f"{kind} rows={rows}")


@pytest.mark.parametrize("act,n_in", [(None, 1), ("swiglu", 2),
                                      ("gelu_tanh", 1)])
def test_matmul_residual_kernel_matches_fallback_interpret(act, n_in):
    """O-projection / swiglu->down / gelu->linear2 epilogue kernel vs
    the bitwise-unfused fallback (bias + residual included)."""
    rng = np.random.RandomState(3)
    for rows in (2, 24):
        xs = [jnp.asarray(rng.randn(rows, 256) / 8, jnp.float32)
              for _ in range(n_in)]
        w = jnp.asarray(rng.randn(256, 128) / 8, jnp.float32)
        b = jnp.asarray(rng.randn(128) / 8, jnp.float32)
        res = jnp.asarray(rng.randn(rows, 128), jnp.float32)
        ref = df._xla_matmul_residual(xs, w, b, res, act=act)
        got = df.pallas_matmul_residual(xs, w, b, res, act=act,
                                        interpret=True)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   atol=1e-5, rtol=1e-5)


# ------------------------------------------------- engine token parity


def test_fused_on_off_token_exact_llama(llama_tiny, monkeypatch):
    """Fused ON vs OFF greedy token-exact (CPU: the fallback IS the
    unfused graph — bit-for-bit by construction), two waves so the
    prefix cache and steady-state decode both ride the fused trace."""
    off, st_off, _ = _serve(llama_tiny, _prompts(), monkeypatch,
                            mode="0", waves=2)
    on, st_on, _ = _serve(llama_tiny, _prompts(), monkeypatch,
                          mode="1", waves=2)
    _assert_equal(off, on, "llama fused on/off")
    assert st_off["fused_decode"] is False
    assert st_on["fused_decode"] is True
    assert st_on["fused_decode_mode"] == "kernel"


def test_fused_on_off_token_exact_gpt(monkeypatch):
    """GPT (LayerNorm + single fused QKV + biased MLP): fused ON vs
    OFF and interpret-mode vs OFF, token-exact."""
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(3)
    m = GPTForCausalLM(GPTConfig.tiny(vocab=128, hidden=128, layers=2,
                                      heads=4))
    m.eval()
    prompts = _prompts()
    off, _, _ = _serve(m, prompts, monkeypatch, mode="0")
    on, _, _ = _serve(m, prompts, monkeypatch, mode="1")
    itp, _, _ = _serve(m, prompts, monkeypatch, mode="interpret")
    _assert_equal(off, on, "gpt fused on/off")
    _assert_equal(off, itp, "gpt fused interpret/off")


def test_fused_interpret_token_exact_llama(llama_eligible,
                                           monkeypatch):
    """Interpret mode puts the REAL fused kernels in the traced graph
    (plus the paged-attention kernels via
    PADDLE_TPU_PAGED_KERNEL=interpret) — greedy output must still
    match the unfused engine token-for-token."""
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "interpret")
    prompts = _prompts(vocab=256)
    off, _, _ = _serve(llama_eligible, prompts, monkeypatch, mode="0",
                       block_size=32)
    itp, st, _ = _serve(llama_eligible, prompts, monkeypatch,
                        mode="interpret", block_size=32)
    _assert_equal(off, itp, "llama interpret/off")
    assert st["fused_decode_mode"] == "interpret"


def test_fused_interpret_token_exact_int8(llama_eligible,
                                          monkeypatch):
    """Int8 KV pools under the fused interpret graph: dequant stays
    in-kernel on the attention side, the fused projections ride
    around it — token-exact vs the unfused int8 engine."""
    prompts = _prompts(vocab=256)
    off, _, _ = _serve(llama_eligible, prompts, monkeypatch, mode="0",
                       block_size=32, kv_cache_dtype="int8")
    itp, st, _ = _serve(llama_eligible, prompts, monkeypatch,
                        mode="interpret", block_size=32,
                        kv_cache_dtype="int8")
    _assert_equal(off, itp, "int8 interpret/off")
    assert st["kv_cache_dtype"] == "int8"


def test_fused_spec_ngram_token_exact(llama_tiny, monkeypatch):
    """Speculative n-gram (gamma=2 — the verify width) fused ON vs
    OFF token-exact; the verify window's sampling head runs on the
    per-slot tensors inside the one ragged executable."""
    reps = [np.tile(np.arange(1, 7, dtype=np.int64), 4)[:20]
            for _ in range(2)]
    off, _, _ = _serve(llama_tiny, reps, monkeypatch, mode="0",
                       num_speculative_tokens=2)
    on, st, _ = _serve(llama_tiny, reps, monkeypatch, mode="1",
                       num_speculative_tokens=2)
    _assert_equal(off, on, "spec fused on/off")
    assert st["spec_tokens_proposed"] > 0


def test_fused_tp2_token_exact(llama_tiny, monkeypatch):
    """TP=2 with fused_decode requested: the GSPMD gate keeps the
    projections unfused inside the TP trace (an opaque pallas_call
    cannot be partitioned) and output stays token-exact vs the
    single-device fused engine."""
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    prompts = _prompts()
    ref, _, _ = _serve(llama_tiny, prompts, monkeypatch, mode="1")
    tp, st, _ = _serve(llama_tiny, prompts, monkeypatch, mode="1",
                       tp_degree=2)
    _assert_equal(ref, tp, "tp2 fused")
    assert st["tp_degree"] == 2


def test_fused_cluster_token_exact(llama_tiny, monkeypatch):
    """Two routed replicas with fusion ON match a fusion-OFF single
    engine; per-request sampling knobs forward through the cluster's
    router (top_k=1 == greedy)."""
    from paddle_tpu.inference import ClusterConfig, EngineCluster
    prompts = _prompts()
    ref, _, _ = _serve(llama_tiny, prompts, monkeypatch, mode="0")
    monkeypatch.setenv("PADDLE_TPU_FUSED_DECODE", "1")
    cl = EngineCluster(
        llama_tiny, ClusterConfig(num_replicas=2),
        ServingConfig(num_slots=2, block_size=8, max_model_len=96,
                      prefill_chunk=8, decode_strategy="sampling",
                      temperature=1.7, seed=11))
    rids = [cl.submit(p.copy(), 6, temperature=1e-6, top_k=1)
            for p in prompts]
    done = cl.run()
    got = [done[r] for r in rids]
    cl.shutdown()
    _assert_equal(ref, got, "cluster fused + per-request top_k=1")


def test_kill_switch_env_beats_config(llama_tiny, monkeypatch):
    """PADDLE_TPU_FUSED_DECODE=0 beats ServingConfig(
    fused_decode=True): the engine reports fused off and produces the
    unfused tokens bit-for-bit."""
    prompts = _prompts()
    monkeypatch.setenv("PADDLE_TPU_FUSED_DECODE", "0")
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=96, prefill_chunk=8,
        fused_decode=True))
    killed = eng.serve([p.copy() for p in prompts], max_new_tokens=6)
    st = eng.stats()
    eng.shutdown()
    assert st["fused_decode"] is False
    off, _, _ = _serve(llama_tiny, prompts, monkeypatch, mode="0")
    _assert_equal(off, killed, "kill switch")
    # config False with env unset is also off
    monkeypatch.delenv("PADDLE_TPU_FUSED_DECODE", raising=False)
    assert df.resolve_fused_mode(False) is None
    assert df.resolve_fused_mode(True) == "kernel"


# ------------------------------------- per-slot sampling + recompiles


def test_per_request_sampling_topk1_matches_greedy(llama_tiny,
                                                   monkeypatch):
    """submit(temperature/top_k/top_p) lands in the per-slot tensors:
    top_k=1 rows reproduce the greedy engine token-for-token even on
    an engine whose GLOBAL config is hot sampling."""
    prompts = _prompts()
    ref, _, _ = _serve(llama_tiny, prompts, monkeypatch, mode="1")
    got, st, _ = _serve(
        llama_tiny, prompts, monkeypatch, mode="1",
        decode_strategy="sampling", temperature=1.9, top_p=0.8,
        seed=13, submit_kw=dict(temperature=1e-6, top_k=1))
    _assert_equal(ref, got, "per-request top_k=1 vs greedy")


def test_uniform_per_slot_matches_engine_global(llama_tiny,
                                                monkeypatch):
    """Per-request knobs EQUAL to the engine defaults draw the same
    tokens as not passing them at all (the inert-traced-knob bitwise
    guarantee of _filter_logits)."""
    prompts = _prompts()
    kw = dict(decode_strategy="sampling", temperature=0.8, top_k=5,
              top_p=0.9, seed=21)
    a, _, _ = _serve(llama_tiny, prompts, monkeypatch, mode="1", **kw)
    b, _, _ = _serve(llama_tiny, prompts, monkeypatch, mode="1",
                     submit_kw=dict(temperature=0.8, top_k=5,
                                    top_p=0.9), **kw)
    _assert_equal(a, b, "uniform per-slot vs engine-global")


def test_filter_logits_per_row_isolation():
    """A row with inert knobs sharing a batch with an active row must
    be filtered NOT AT ALL (cross-request isolation): without the
    per-row (p < 1) gate, f32 cumsum overshoot past 1.0 masks a
    p=1.0 row's tail tokens when a neighbor's top-p branch runs."""
    from paddle_tpu.generation import _filter_logits
    rng = np.random.RandomState(0)
    lg = jnp.asarray(rng.randn(2, 257), jnp.float32)
    out = _filter_logits(
        lg, do_sample=True,
        temperature=jnp.asarray([1.0, 0.7], jnp.float32),
        top_k=jnp.asarray([0.0, 3.0], jnp.float32),
        top_p=jnp.asarray([1.0, 0.5], jnp.float32))
    # row 0 (inert knobs): untouched — bitwise the raw logits
    np.testing.assert_array_equal(np.asarray(out[0]),
                                  np.asarray(lg[0]))
    # row 1 (active): top_k=3 keeps at most 3 finite entries
    assert int(np.isfinite(np.asarray(out[1])).sum()) <= 3


def test_zero_recompiles_across_sampling_configs(llama_tiny,
                                                 monkeypatch):
    """THE deleted recompile class: waves with three DISTINCT
    per-request sampling configs ride ONE executable — zero
    steady-state recompiles, executables_compiled stays 1."""
    monkeypatch.setenv("PADDLE_TPU_FUSED_DECODE", "1")
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=96, prefill_chunk=8,
        decode_strategy="sampling", seed=3))
    prompts = _prompts()
    for kw in (dict(), dict(temperature=0.5, top_k=3),
               dict(temperature=1.3, top_p=0.7, top_k=9)):
        for p in prompts:
            eng.submit(p.copy(), 5, **kw)
        eng.run()
    st = eng.stats()
    eng.shutdown()
    assert st["decode_compiles"] == 1
    assert st["executables_compiled"] == 1


def test_submit_sampling_validation(llama_tiny):
    """Greedy engines reject per-request sampling knobs (argmax would
    silently ignore them); out-of-range values reject on sampling
    engines too."""
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=96))
    with pytest.raises(ValueError, match="decode_strategy"):
        eng.submit([1, 2, 3], 4, temperature=0.5)
    eng.shutdown()
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=96,
        decode_strategy="sampling"))
    with pytest.raises(ValueError, match="top_p"):
        eng.submit([1, 2, 3], 4, top_p=1.5)
    with pytest.raises(ValueError, match="top_k"):
        eng.submit([1, 2, 3], 4, top_k=-1)
    rid = eng.submit([1, 2, 3], 4, temperature=0.5, top_k=2,
                     top_p=0.9)
    eng.run()
    eng.shutdown()


def test_disagg_handoff_carries_sampling(llama_tiny, monkeypatch):
    """Disaggregated prefill -> decode: the PrefilledRequest payload
    carries the request's sampling knobs, so the decode replica
    continues under the SAME per-slot values (top_k=1 == greedy,
    across the handoff)."""
    from paddle_tpu.inference import ClusterConfig, EngineCluster
    prompts = _prompts()
    ref, _, _ = _serve(llama_tiny, prompts, monkeypatch, mode="1")
    monkeypatch.setenv("PADDLE_TPU_FUSED_DECODE", "1")
    cl = EngineCluster(
        llama_tiny, ClusterConfig(num_replicas=1, prefill_replicas=1),
        ServingConfig(num_slots=2, block_size=8, max_model_len=96,
                      prefill_chunk=8, decode_strategy="sampling",
                      temperature=1.9, seed=5))
    rids = [cl.submit(p.copy(), 6, temperature=1e-6, top_k=1)
            for p in prompts]
    done = cl.run()
    got = [done[r] for r in rids]
    st = cl.stats()
    cl.shutdown()
    assert st["kv_blocks_transferred"] > 0
    _assert_equal(ref, got, "disagg handoff sampling")


# --------------------------------------------------------- kernel census


def test_kernel_census_collapse(llama_eligible, monkeypatch):
    """The headline metric is MEASURED: with the Pallas kernels routed
    into the traced graph (interpret), the fused tick's jaxpr-level
    launch proxy drops vs the unfused tick (pallas_call counts ONE
    launch; its in-kernel ops are not separate thunks), and the HLO
    census carries per-op rows. Per-layer the collapse is 14 -> 9
    launch roots (0.64x; the optimized-HLO count on real TPU absorbs
    the elementwise fusion kernels too — the <= 0.6x bar)."""
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "interpret")
    prompts = _prompts(vocab=256, lens=(5, 9))
    _, st_off, kc_off = _serve(llama_eligible, prompts, monkeypatch,
                               mode="0", block_size=32, max_new=3)
    _, st_on, kc_on = _serve(llama_eligible, prompts, monkeypatch,
                             mode="interpret", block_size=32,
                             max_new=3)
    off_p = st_off["kernel_launch_proxy_per_tick"]
    on_p = st_on["kernel_launch_proxy_per_tick"]
    assert off_p > 0 and on_p > 0
    assert on_p < off_p, (on_p, off_p)
    assert on_p / off_p < 0.85, (on_p, off_p)
    assert kc_on["decode"]["launch_by_op"].get("pallas_call", 0) >= 8
    # HLO view present on both arms (entry instruction counts)
    assert st_off["kernels_per_tick"] > 0
    assert st_on["kernels_per_tick"] > 0
    # the gauge mirrors the tick executable's HLO count
    g = monitor.gauge("serving_kernels_per_tick", "")
    assert g.value() == st_on["kernels_per_tick"]


# ------------------------------------------------ generate() jit cache


def test_generate_jit_cache_across_sampling_configs(llama_tiny):
    """ISSUE 13 satellite: sampling knobs left the generate() jit_key
    — three distinct configs compile ONE decode loop (1 miss, then
    hits), and sampling with top_k=1 reproduces greedy (the traced
    knob path is value-identical to the baked path)."""
    rng = np.random.RandomState(0)
    ids = paddle.to_tensor(rng.randint(1, 128, (1, 12)).astype(
        np.int64))
    c = monitor.counter("generate_jit_cache", "",
                        labels=("model", "event"))

    def ev(e):
        return c.labels(model="LlamaForCausalLM", event=e).value()

    m0, h0 = ev("miss"), ev("hit")
    llama_tiny.generate(ids, max_new_tokens=4,
                        decode_strategy="sampling", seed=3)
    llama_tiny.generate(ids, max_new_tokens=4,
                        decode_strategy="sampling", temperature=0.7,
                        top_k=5, top_p=0.9, seed=3)
    llama_tiny.generate(ids, max_new_tokens=4,
                        decode_strategy="sampling", temperature=0.2,
                        seed=3)
    assert ev("miss") - m0 == 1
    assert ev("hit") - h0 == 2
    greedy, _ = llama_tiny.generate(ids, max_new_tokens=6, seed=0)
    k1, _ = llama_tiny.generate(ids, max_new_tokens=6,
                                decode_strategy="sampling", top_k=1,
                                seed=0)
    assert greedy.numpy().tolist() == k1.numpy().tolist()
    # the paged loop shares the traced-knob select
    k1p, _ = llama_tiny.generate(ids, max_new_tokens=6,
                                 cache_impl="paged",
                                 decode_strategy="sampling", top_k=1,
                                 seed=0)
    assert greedy.numpy().tolist() == k1p.numpy().tolist()


# --------------------------------------------------------------- guard


def test_tier1_no_slow_marker():
    """CI guard (the PR-4/5 pattern): every decode-fusion test runs in
    the tier-1 ``-m 'not slow'`` sweep and the kernel parity tests are
    present."""
    import tests.conftest as c
    here = open(__file__).read()
    assert "pytest.mark.slow" not in here.replace(
        '"pytest.mark.slow"', "")
    names = [ln.split("(")[0][4:] for ln in here.splitlines()
             if ln.startswith("def test_")]
    overlap = set(names) & set(c._SLOW_TESTS)
    assert not overlap, f"tier-1 fused tests marked slow: {overlap}"
    assert "test_norm_matmul_kernel_matches_fallback_interpret" \
        in names
    assert "test_matmul_residual_kernel_matches_fallback_interpret" \
        in names
    # every engine is torn down (allocator leak sweep guards these)
    assert here.count(".shutdown()") >= 6

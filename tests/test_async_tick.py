"""Async tick pipeline (ISSUE 20): depth-1 dispatch-ahead with
device-resident decode state. The contract under test is EXACTNESS —
``async_depth=1`` must be greedy token-exact vs ``async_depth=0``
across the whole engine matrix (fp / int8 KV / spec n-gram / spec
tree / LoRA / TP=2 / GPT / colocated + disaggregated cluster),
because the pipelined tick consumes the SAME executable's own carry
outputs instead of a host round-trip. Also pinned here: the
``PADDLE_TPU_ASYNC_TICK`` kill switch (env "0" beats the config, env
"1" arms the default), zero steady-state recompiles across waves
(``executables_compiled`` stays at the ragged baseline of 1),
pipeline flush correctness on every slot-composition event
(admission, preemption, migration, cancel), EOS-overrun tokens
dropped exactly at commit, the non-finite-logits health probe firing
through the NON-blocking fetch, and the new always-present stats
keys (``async_depth`` / ``pipeline_flushes`` / ``host_gap_ms``).

Tier-1 guard: every test here must run in the standard
``-m 'not slow'`` sweep — ``test_tier1_no_slow_marker`` pins that.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.inference.cluster import ClusterConfig, EngineCluster
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

jnp = pytest.importorskip("jax.numpy")


@pytest.fixture(scope="module")
def llama_tiny():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           kv_heads=2, ffn=128)
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


def _scfg(**kw):
    base = dict(num_slots=2, block_size=8, max_model_len=64,
                prefill_chunk=8)
    base.update(kw)
    return ServingConfig(**base)


def _prompts(vocab=128, lens=(9, 5, 12), seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(1, vocab, (n,)).astype(np.int64) for n in lens]


def _serve(model, prompts, depth, max_new=8, **cfg_kw):
    eng = ServingEngine(model, _scfg(async_depth=depth, **cfg_kw))
    out = eng.serve([p.copy() for p in prompts],
                    max_new_tokens=max_new)
    st = eng.stats()
    eng.shutdown()
    return out, st


def _assert_equal(a, b, tag):
    assert len(a) == len(b), tag
    for i, (x, y) in enumerate(zip(a, b)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y),
                                      err_msg=f"{tag} request {i}")


# ------------------------------------------------- parity matrix


@pytest.mark.parametrize("variant", ["fp", "int8", "spec_ngram",
                                     "spec_tree"])
def test_parity_matrix_llama(llama_tiny, variant):
    """async ON == OFF greedy token-exact, per engine variant, with
    the one-executable collapse intact in BOTH modes (the carry
    outputs ride the ONE tick executable — they never add one)."""
    kw = {"fp": {},
          "int8": dict(kv_cache_dtype="int8"),
          "spec_ngram": dict(num_speculative_tokens=2),
          "spec_tree": dict(num_speculative_tokens=2,
                            spec_tree=(0, 1))}[variant]
    on, st_on = _serve(llama_tiny, _prompts(), 1, **kw)
    off, st_off = _serve(llama_tiny, _prompts(), 0, **kw)
    _assert_equal(off, on, f"llama {variant} async on/off")
    assert st_on["async_depth"] == 1 and st_off["async_depth"] == 0
    assert st_on["executables_compiled"] == \
        st_off["executables_compiled"] == 1
    if variant == "fp":             # g==0: the pipeline actually ran
        assert st_on["host_gap_ms"]["count"] > 0
        assert st_on["tokens_total"] == st_off["tokens_total"]


def test_parity_gpt(gpt_tiny):
    """GPT (LayerNorm + fused QKV + biased MLP): same carry graph,
    token-exact."""
    on, st_on = _serve(gpt_tiny, _prompts(vocab=96), 1)
    off, _ = _serve(gpt_tiny, _prompts(vocab=96), 0)
    _assert_equal(off, on, "gpt async on/off")
    assert st_on["executables_compiled"] == 1


def test_parity_lora(llama_tiny):
    """Multi-LoRA: the per-slot adapter row travels IN the carry, so
    a pipelined tick keeps each slot pinned to its adapter."""
    names = ("q_proj", "o_proj")    # square on kv_heads=2 tiny
    rng = np.random.RandomState(101)
    w = {n: (rng.normal(0, 0.3, (64, 4)).astype(np.float32),
             rng.normal(0, 0.3, (4, 64)).astype(np.float32))
         for n in names}
    outs = {}
    for depth in (1, 0):
        eng = ServingEngine(llama_tiny, _scfg(
            async_depth=depth, lora_rank=4, max_adapters=2))
        eng.load_adapter(1, w)
        rids = [eng.submit(p.copy(), 6, adapter_id=a)
                for p, a in zip(_prompts(), (1, None, 1))]
        done = eng.run()
        outs[depth] = [done[r] for r in rids]
        if depth == 1:
            assert eng.stats()["executables_compiled"] == 1
        eng.shutdown()
    _assert_equal(outs[0], outs[1], "lora async on/off")


def test_parity_tp2(llama_tiny):
    """TP=2: carry arrays pinned replicated across the mesh — the
    pipelined dispatch's input shardings match the AOT signature."""
    on, st_on = _serve(llama_tiny, _prompts(), 1, tp_degree=2)
    off, _ = _serve(llama_tiny, _prompts(), 0, tp_degree=2)
    _assert_equal(off, on, "tp2 async on/off")
    assert st_on["tp_degree"] == 2
    assert st_on["executables_compiled"] == 1


@pytest.mark.parametrize("disagg", [False, True])
def test_parity_cluster(llama_tiny, disagg):
    """Cluster dispatch-all-then-commit-all: colocated and
    prefill/decode-disaggregated fleets stay token-exact vs sync
    replica ticking, with the fleet stats roll-ups present."""
    def run(depth):
        scfg = _scfg(async_depth=depth)
        ccfg = ClusterConfig(num_replicas=2,
                             prefill_replicas=1 if disagg else 0)
        cl = EngineCluster(llama_tiny, ccfg, scfg)
        rids = [cl.submit(p.copy(), 6) for p in _prompts()]
        done = cl.run()
        st = cl.stats()
        cl.shutdown()
        return [done[r] for r in rids], st
    on, st_on = run(1)
    off, st_off = run(0)
    _assert_equal(off, on, f"cluster disagg={disagg} async on/off")
    assert st_on["async_depth"] == 1 and st_off["async_depth"] == 0
    assert st_on["executables_compiled"] == \
        st_off["executables_compiled"]
    assert st_off["pipeline_flushes"] == 0


# --------------------------------------------- kill switch / arming


def test_kill_switch_and_env_arming(llama_tiny, monkeypatch):
    """``PADDLE_TPU_ASYNC_TICK=0`` beats ``async_depth=1`` bit-for-bit
    (same tokens, same executable census, depth reported 0), and
    env "1" arms the default (``async_depth=None``) engine."""
    off, st_off = _serve(llama_tiny, _prompts(), 0)
    monkeypatch.setenv("PADDLE_TPU_ASYNC_TICK", "0")
    killed, st_k = _serve(llama_tiny, _prompts(), 1)
    _assert_equal(off, killed, "kill switch vs sync")
    assert st_k["async_depth"] == 0
    assert st_k["pipeline_flushes"] == 0
    assert st_k["executables_compiled"] == st_off["executables_compiled"]
    monkeypatch.setenv("PADDLE_TPU_ASYNC_TICK", "1")
    armed, st_a = _serve(llama_tiny, _prompts(), None)
    _assert_equal(off, armed, "env-armed vs sync")
    assert st_a["async_depth"] == 1


def test_async_depth_validation(llama_tiny):
    with pytest.raises(ValueError, match="async_depth"):
        _scfg(async_depth=2)
    with pytest.raises(ValueError, match="async_depth"):
        _scfg(async_depth=True)


# ------------------------------------------------ steady-state pins


def test_zero_steady_state_recompiles_two_waves(llama_tiny):
    """Two waves through one async engine: the executable census is
    pinned at 1 after wave 1 and STAYS 1 — the pipelined dispatch
    reuses the AOT tick executable, never traces a second one."""
    eng = ServingEngine(llama_tiny, _scfg(async_depth=1))
    eng.serve([p.copy() for p in _prompts()], max_new_tokens=6)
    assert eng.stats()["executables_compiled"] == 1
    steps1 = eng.stats()["decode_steps"]
    eng.serve([p.copy() for p in _prompts(seed=5)], max_new_tokens=6)
    st = eng.stats()
    assert st["executables_compiled"] == 1
    assert st["decode_steps"] > steps1
    assert st["host_gap_ms"]["count"] > 0
    eng.shutdown()


# ------------------------------------------------- flush correctness


def test_flush_on_staggered_admission(llama_tiny):
    """A request arriving mid-pipeline flushes (commit the in-flight
    tick) before the admission tick, so the composition every device
    tick sees — and therefore every greedy token — matches the sync
    schedule exactly."""
    def run(depth):
        eng = ServingEngine(llama_tiny, _scfg(async_depth=depth))
        p0, p1 = _prompts(lens=(9, 7))
        rids = [eng.submit(p0.copy(), 10)]
        for _ in range(4):
            eng.step()
        rids.append(eng.submit(p1.copy(), 8))
        done = eng.run()
        st = eng.stats()
        eng.shutdown()
        return [done[r] for r in rids], st
    on, st_on = run(1)
    off, _ = run(0)
    _assert_equal(off, on, "staggered admission async on/off")
    assert st_on["pipeline_flushes"] >= 1


def test_flush_on_preemption_storm(llama_tiny):
    """The canonical preemption workload (one long low-priority
    request, two high-priority arrivals on a 2-slot engine): the
    preemption drains the pipeline first, and the resumed stream is
    token-exact vs the sync engine under the SAME schedule."""
    def run(depth):
        eng = ServingEngine(llama_tiny, _scfg(
            async_depth=depth, max_model_len=96))
        rng = np.random.RandomState(3)
        lo = rng.randint(1, 128, (20,))
        h1, h2 = rng.randint(1, 128, (9,)), rng.randint(1, 128, (7,))
        rids = [eng.submit(lo.copy(), 12, priority=0)]
        for _ in range(4):
            eng.step()
        rids.append(eng.submit(h1.copy(), 12, priority=2))
        rids.append(eng.submit(h2.copy(), 12, priority=2))
        done = eng.run()
        st = eng.stats()
        eng.shutdown()
        return [done[r] for r in rids], st
    on, st_on = run(1)
    off, st_off = run(0)
    _assert_equal(off, on, "preemption storm async on/off")
    assert st_on["preemptions"] >= 1 and st_off["preemptions"] >= 1


def test_migration_flushes_and_stays_token_exact(llama_tiny):
    """export_session mid-pipeline commits the in-flight tick before
    packaging the slot, and admit_migrated flushes the TARGET's
    pipeline before seating — the migrated stream (source tokens +
    target tokens) equals the never-migrated reference."""
    ref, _ = _serve(llama_tiny, _prompts(lens=(9,)), 0, max_new=10)
    got = []
    cb = lambda rid, tok: got.append(int(tok))
    src = ServingEngine(llama_tiny, _scfg(async_depth=1),
                        stream_callback=cb)
    dst = ServingEngine(llama_tiny, _scfg(async_depth=1),
                        stream_callback=cb)
    src.submit(_prompts(lens=(9,))[0].copy(), 10)
    for _ in range(4):
        src.step()
    rec = src.export_session(0)
    assert src.num_active == 0
    assert dst.admit_migrated(rec) is not None
    dst.run()
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(ref[0]),
                                  err_msg="migrated stream")
    assert src.shutdown() and dst.shutdown()


def test_cancel_mid_pipeline(llama_tiny):
    """cancel() drains the pipeline before tearing the slot down: the
    cancelled request's PARTIAL stream (the tokens committed at the
    flush point) and the survivor's full stream both match the sync
    engine under the same schedule."""
    def run(depth):
        eng = ServingEngine(llama_tiny, _scfg(async_depth=depth))
        p0, p1 = _prompts(lens=(9, 7))
        r0 = eng.submit(p0.copy(), 12)
        r1 = eng.submit(p1.copy(), 12)
        for _ in range(4):
            eng.step()
        assert eng.cancel(r0)
        done = eng.run()
        st = eng.stats()
        eng.shutdown(check_leaks=True)
        assert done[r0].size < 12       # actually cut mid-decode
        return [done[r0], done[r1]], st
    on, st_on = run(1)
    off, _ = run(0)
    _assert_equal(off, on, "cancel partial + survivor")
    assert st_on["pipeline_flushes"] >= 1
    assert st_on["requests_cancelled"] == 1


# ------------------------------------------------------ EOS overrun


def test_eos_overrun_token_dropped_exactly(llama_tiny):
    """When EOS lands while tick N+1 is already in flight, the
    overrun token from the retired slot is dropped at commit: async
    output == sync output (which stops at EOS), and the token
    accounting matches — the speculative extra tick leaks nothing."""
    base, _ = _serve(llama_tiny, _prompts(lens=(9,)), 0, max_new=10)
    stream = [int(t) for t in np.asarray(base[0])]
    eos = stream[4]                 # force a mid-stream EOS retire
    on, st_on = _serve(llama_tiny, _prompts(lens=(9,)), 1,
                       max_new=10, eos_token_id=eos)
    off, st_off = _serve(llama_tiny, _prompts(lens=(9,)), 0,
                         max_new=10, eos_token_id=eos)
    _assert_equal(off, on, "eos overrun async on/off")
    assert len(np.asarray(on[0])) < 10      # EOS actually cut it
    assert st_on["tokens_total"] == st_off["tokens_total"]


# ------------------------------------------------- health under async


def test_nonfinite_probe_fires_under_async(llama_tiny):
    """ISSUE 20 satellite: the non-finite-logits probe now rides the
    async copy (fetched at COMMIT, off the dispatch path) — NaN
    params must still trip the page alert under async_depth=1 with
    the executable census unchanged."""
    paddle.seed(0)
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           kv_heads=2, ffn=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    import jax
    eng = ServingEngine(m, _scfg(async_depth=1))
    leaves, treedef = jax.tree_util.tree_flatten(eng._params)
    k = max(range(len(leaves)), key=lambda i: leaves[i].size)
    leaves[k] = jnp.full_like(leaves[k], jnp.nan)
    eng._params = jax.tree_util.tree_unflatten(treedef, leaves)
    eng.submit(_prompts(lens=(9,))[0].copy(), 4)
    eng.run()
    st = eng.stats()
    assert st["nonfinite_logits_ticks"] > 0
    assert "nonfinite_logits" in eng.health()["alerts_firing"]
    assert st["executables_compiled"] == 1
    eng.shutdown(check_leaks=False)


# ------------------------------------------------------- stats keys


def test_stats_keys_always_present(llama_tiny):
    """The ISSUE 20 keys are part of the always-present contract: a
    plain SYNC engine and a 1-replica cluster report them (zeros /
    empty digest), so dashboards never KeyError across configs."""
    eng = ServingEngine(llama_tiny, _scfg())
    st = eng.stats()
    assert st["async_depth"] == 0
    assert st["pipeline_flushes"] == 0
    assert st["host_gap_ms"]["count"] >= 0
    eng.shutdown()
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=1),
                       _scfg())
    cst = cl.stats()
    assert cst["async_depth"] == 0 and cst["pipeline_flushes"] == 0
    cl.shutdown()


# ------------------------------------------------------------- guard


def test_tier1_no_slow_marker():
    """CI guard (the PR-4/5 pattern): every async-tick test runs in
    the tier-1 ``-m 'not slow'`` sweep."""
    import tests.conftest as c
    here = open(__file__).read()
    assert "pytest.mark.slow" not in here.replace(
        '"pytest.mark.slow"', "")
    names = [ln.split("(")[0][4:] for ln in here.splitlines()
             if ln.startswith("def test_")]
    overlap = set(names) & set(c._SLOW_TESTS)
    assert not overlap, overlap

"""Data-parallel engine replication + disaggregated prefill (ISSUE
12): session-affine routing on the shared prompt->block-hash walk
(router hits == admission hits, reuse tokens match the single-engine
prefix-cache path), token-exact greedy parity cluster(N=2) vs one
engine, disaggregated prefill->decode KV streaming token-exact vs
colocated (fp AND int8 pools — data + scales transfer bytewise), zero
steady-state recompiles per replica, the failure drain, the
``PADDLE_TPU_CLUSTER=0`` kill switch, cluster-aggregate ``stats()``
rollups, and the loadgen harness driving a cluster through the
multi-session conversation workload.

Tier-1 guard: every test here must run in the standard
``-m 'not slow'`` sweep — ``test_tier1_no_slow_marker`` pins that.
"""
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.inference.cluster import ClusterConfig, EngineCluster
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


@pytest.fixture
def llama_tiny():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           kv_heads=2, ffn=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _scfg(**kw):
    base = dict(num_slots=2, block_size=8, max_model_len=96,
                prefill_chunk=8)
    base.update(kw)
    return ServingConfig(**base)


def _prompts(rng, lens=(11, 19, 5, 26), vocab=128):
    return [rng.randint(1, vocab, (n,)) for n in lens]


# ------------------------------------------------------- transfer unit


def test_export_import_roundtrip_bytes_fp_and_int8():
    """The disaggregated transfer unit: exported blocks import into a
    FRESH pool bitwise — fp pools byte-for-byte, int8 pools data AND
    scales byte-for-byte (a block's bytes are self-contained thanks to
    the per-row scales). Pad ids (the null block) never clobber real
    blocks on the importer."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    rng = np.random.RandomState(0)
    BS, H, D, NB = 8, 2, 16, 7
    for dtype in (jnp.float32, "int8"):
        src = [pc.init_pool(NB, BS, H, D, dtype) for _ in range(2)]
        tables = jnp.asarray(np.array([[1, 2, 3]], np.int32))
        k = jnp.asarray(rng.randn(1, 3 * BS, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(1, 3 * BS, H, D), jnp.float32)
        src = [pc.write_prefill(kp, vp, tables, k, v)
               for kp, vp in src]
        ids = jnp.asarray(np.array([1, 2, 3, 0, 0], np.int32))  # pad 0
        payload = pc.export_blocks(src, ids)
        dst = [pc.init_pool(NB, BS, H, D, dtype) for _ in range(2)]
        # poison a non-target block to prove import only touches ids
        dst = [pc.write_prefill(kp, vp,
                                jnp.asarray(np.array([[5]], np.int32)),
                                k[:, :BS], v[:, :BS])
               for kp, vp in dst]
        before5 = [np.asarray(kp.data[5] if dtype == "int8" else kp[5])
                   for kp, _ in dst]
        dst = pc.import_blocks(dst, ids, payload)
        for (sk, sv), (dk, dv) in zip(src, dst):
            for s, d in ((sk, dk), (sv, dv)):
                if dtype == "int8":
                    np.testing.assert_array_equal(
                        np.asarray(s.data[1:4]), np.asarray(d.data[1:4]))
                    np.testing.assert_array_equal(
                        np.asarray(s.scale[1:4]),
                        np.asarray(d.scale[1:4]))
                else:
                    np.testing.assert_array_equal(
                        np.asarray(s[1:4]), np.asarray(d[1:4]))
        for b5, (dk, _) in zip(before5, dst):
            np.testing.assert_array_equal(
                b5, np.asarray(dk.data[5] if dtype == "int8"
                               else dk[5]))


def test_import_blocks_dtype_mismatch_rejected():
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    ids = jnp.asarray(np.array([1], np.int32))
    fp = [pc.init_pool(3, 4, 1, 8, jnp.float32)]
    q8 = [pc.init_pool(3, 4, 1, 8, "int8")]
    with pytest.raises(TypeError, match="kv_cache_dtype"):
        pc.import_blocks(fp, ids, pc.export_blocks(q8, ids))
    with pytest.raises(TypeError, match="kv_cache_dtype"):
        pc.import_blocks(q8, ids, pc.export_blocks(fp, ids))


# ------------------------------------------- shared hash walk (router)


def test_router_hashes_identical_to_engine_admission(llama_tiny):
    """Satellite 1: the router's prompt->hash walk IS admission's —
    ``prompt_block_hashes`` seeded by ``model_fingerprint`` reproduces
    the engine's published hashes exactly, so ``published_overlap``
    counts precisely the blocks a subsequent admission would map."""
    from paddle_tpu.ops import paged_cache as pc
    rng = np.random.RandomState(3)
    eng = ServingEngine(llama_tiny, _scfg())
    prompt = rng.randint(1, 128, (24,))          # 3 full blocks
    eng.serve([prompt.copy()], max_new_tokens=4)
    fp = pc.model_fingerprint(llama_tiny)
    assert fp == eng._fp
    hashes = list(pc.prompt_block_hashes(fp, prompt, 8))
    assert hashes == pc.chain_hashes(fp, prompt, 8)
    assert eng.published_overlap(hashes) == 3
    # a mutated first token kills the whole chain (prefix soundness)
    mut = prompt.copy()
    mut[0] = (mut[0] + 1) % 127 + 1
    assert eng.published_overlap(
        list(pc.prompt_block_hashes(fp, mut, 8))) == 0
    # the probe agrees with what admission then actually reuses
    st0 = eng.stats()["prefix_tokens_reused"]
    eng.serve([np.concatenate([prompt, rng.randint(1, 128, (5,))])],
              max_new_tokens=4)
    assert eng.stats()["prefix_tokens_reused"] - st0 == 24
    eng.shutdown()


# ----------------------------------------------------- routed replicas


def test_cluster_token_exact_vs_single_engine(llama_tiny):
    """Greedy outputs are token-exact cluster(N=2) vs one engine for
    EVERY request — replication is a pure capacity knob."""
    rng = np.random.RandomState(0)
    prompts = _prompts(rng)
    eng = ServingEngine(llama_tiny, _scfg())
    ref = eng.serve([p.copy() for p in prompts], max_new_tokens=6)
    eng.shutdown()
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg())
    out = cl.serve([p.copy() for p in prompts], max_new_tokens=6)
    for a, b in zip(out, ref):
        assert a.tolist() == b.tolist()
    st = cl.stats()
    assert st["router_requests"] == len(prompts)
    assert st["tokens_total"] == sum(len(r) for r in ref)
    cl.shutdown()


def test_router_affinity_same_session(llama_tiny):
    """The affinity property: a session's turn 2 lands on the replica
    that served (and published) turn 1, reuses exactly the blocks a
    single engine's prefix cache would, and counts a
    ``serving_router_affinity_hits`` event; an unrelated cold prompt
    load-balances to the OTHER replica meanwhile."""
    rng = np.random.RandomState(1)
    turn1 = rng.randint(1, 128, (24,))           # 3 full blocks
    turn2 = np.concatenate([turn1, rng.randint(1, 128, (8,))])
    # single-engine reference for the reuse accounting
    eng = ServingEngine(llama_tiny, _scfg())
    eng.serve([turn1.copy()], max_new_tokens=4)
    eng.serve([turn2.copy()], max_new_tokens=4)
    ref_reuse = eng.stats()["prefix_tokens_reused"]
    eng.shutdown()

    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg())
    cl.serve([turn1.copy()], max_new_tokens=4)   # cold -> replica 0
    hits0 = cl.stats()["router_affinity_hits"]
    assert hits0 == 0
    cl.serve([turn2.copy()], max_new_tokens=4)   # affine -> replica 0
    st = cl.stats()
    assert st["router_affinity_hits"] == 1
    # turn 2 reused blocks live on replica 0 — and exactly as many
    # tokens as the single-engine prefix-cache path reused
    assert st["replicas"][0]["prefix_tokens_reused"] == ref_reuse
    assert st["replicas"][1]["prefix_tokens_reused"] == 0
    assert st["prefix_tokens_reused"] == ref_reuse
    # cold traffic still load-balances: replica 0 is busier history-
    # wise but idle now; submit two cold prompts back to back and
    # check they spread by queue depth
    ra = cl.submit(rng.randint(1, 128, (9,)), 3)
    rb = cl.submit(rng.randint(1, 128, (9,)), 3)
    owners = {cl._owner[ra][0], cl._owner[rb][0]}
    assert owners == {0, 1}
    cl.run()
    cl.shutdown()


def test_cluster_kill_switch(llama_tiny, monkeypatch):
    """PADDLE_TPU_CLUSTER=0 collapses any config to ONE colocated
    replica whose outputs are bit-identical to a plain engine."""
    rng = np.random.RandomState(2)
    prompts = _prompts(rng, lens=(11, 19))
    eng = ServingEngine(llama_tiny, _scfg())
    ref = eng.serve([p.copy() for p in prompts], max_new_tokens=5)
    eng.shutdown()
    monkeypatch.setenv("PADDLE_TPU_CLUSTER", "0")
    cl = EngineCluster(llama_tiny,
                       ClusterConfig(num_replicas=3,
                                     prefill_replicas=2), _scfg())
    st = cl.stats()
    assert st["num_replicas"] == 1 and st["prefill_replicas"] == 0
    assert not st["disaggregated"] and not st["cluster_enabled"]
    assert len(cl.engines) == 1
    assert cl.engines[0].stats()["role"] == "both"
    out = cl.serve([p.copy() for p in prompts], max_new_tokens=5)
    for a, b in zip(out, ref):
        assert a.tolist() == b.tolist()
    cl.shutdown()


def test_failure_drains_queue_to_router(llama_tiny):
    """A failed replica's queued requests re-route to the survivors
    with their global ids preserved; every submitted request still
    completes exactly once."""
    rng = np.random.RandomState(4)
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg())
    rids = [cl.submit(rng.randint(1, 128, (9,)), 4) for _ in range(6)]
    cl.step()
    cl.fail_replica(0)
    st = cl.stats()
    assert st["failed_replicas"] == [0]
    done = cl.run()
    assert set(done) == set(rids)
    # in-flight requests on the failed replica terminated with the
    # tokens already streamed; re-routed ones decoded fully
    assert sum(len(v) == 4 for v in done.values()) >= 4
    cl.shutdown()


# ------------------------------------------------ disaggregated serving


def test_disaggregated_token_exact_vs_colocated(llama_tiny):
    """Prefill on a role="prefill" engine + KV streaming into a decode
    replica produces token-for-token the colocated engine's greedy
    output, and the transfer is observable (kv_blocks_transferred >
    0, prefills_exported on the prefill tier)."""
    rng = np.random.RandomState(5)
    prompts = _prompts(rng)
    eng = ServingEngine(llama_tiny, _scfg())
    ref = eng.serve([p.copy() for p in prompts], max_new_tokens=6)
    eng.shutdown()
    cl = EngineCluster(llama_tiny,
                       ClusterConfig(num_replicas=1,
                                     prefill_replicas=1), _scfg())
    out = cl.serve([p.copy() for p in prompts], max_new_tokens=6)
    for a, b in zip(out, ref):
        assert a.tolist() == b.tolist()
    st = cl.stats()
    expect_blocks = sum(-(-len(p) // 8) for p in prompts)
    assert st["kv_blocks_transferred"] == expect_blocks
    pre = st["replicas"][1]
    assert pre["role"] == "prefill"
    assert pre["prefills_exported"] == len(prompts)
    assert pre["kv_blocks_exported"] == expect_blocks
    assert st["replicas"][0]["kv_blocks_imported"] == expect_blocks
    cl.shutdown()


def test_disaggregated_int8_token_exact(llama_tiny):
    """The int8 pool transfers as data + scales, so disaggregated
    greedy decode is token-exact vs a colocated int8 engine."""
    rng = np.random.RandomState(6)
    prompts = _prompts(rng, lens=(11, 19, 26))
    eng = ServingEngine(llama_tiny, _scfg(kv_cache_dtype="int8"))
    ref = eng.serve([p.copy() for p in prompts], max_new_tokens=6)
    eng.shutdown()
    cl = EngineCluster(llama_tiny,
                       ClusterConfig(num_replicas=1,
                                     prefill_replicas=1),
                       _scfg(kv_cache_dtype="int8"))
    out = cl.serve([p.copy() for p in prompts], max_new_tokens=6)
    for a, b in zip(out, ref):
        assert a.tolist() == b.tolist()
    assert cl.stats()["kv_blocks_transferred"] > 0
    for rep in cl.stats()["replicas"]:
        assert rep["kv_cache_dtype"] == "int8"
    cl.shutdown()


def test_disaggregated_multi_turn_prefill_cache(llama_tiny):
    """In disaggregated mode the handoff PUBLISHES the prompt's blocks
    on the prefill engine before freeing them, so a session's next
    turn routes back there (affinity over the prefill tier) and
    prefills only its suffix."""
    rng = np.random.RandomState(7)
    turn1 = rng.randint(1, 128, (24,))
    turn2 = np.concatenate([turn1, rng.randint(1, 128, (8,))])
    cl = EngineCluster(llama_tiny,
                       ClusterConfig(num_replicas=1,
                                     prefill_replicas=2), _scfg())
    cl.serve([turn1.copy()], max_new_tokens=4)
    cl.serve([turn2.copy()], max_new_tokens=4)
    st = cl.stats()
    assert st["router_affinity_hits"] == 1
    pre = [st["replicas"][i] for i in (1, 2)]
    assert sum(r["prefix_tokens_reused"] for r in pre) == 24
    cl.shutdown()


def test_prefill_role_validation(llama_tiny):
    with pytest.raises(ValueError, match="role"):
        ServingConfig(role="verify")
    with pytest.raises(NotImplementedError, match="prefill-role"):
        ServingEngine(llama_tiny,
                      _scfg(role="prefill", num_speculative_tokens=2))
    # disaggregated + draft model: the draft pool is not in the
    # payload — rejected at cluster construction with the fix named
    with pytest.raises(NotImplementedError, match="draft"):
        EngineCluster(llama_tiny,
                      ClusterConfig(num_replicas=1,
                                    prefill_replicas=1),
                      _scfg(num_speculative_tokens=2,
                            drafter="model"),
                      draft_model=llama_tiny)


def test_disaggregated_ngram_spec_token_exact(llama_tiny):
    """n-gram speculation composes with disaggregation: the decode
    replica verifies windows (its drafter corpus — prompt + first
    token — rides the handoff), the prefill tier runs gamma=0, and
    greedy output stays token-exact (spec greedy IS the plain
    chain)."""
    rng = np.random.RandomState(11)
    prompts = _prompts(rng, lens=(11, 19))
    eng = ServingEngine(llama_tiny, _scfg())
    ref = eng.serve([p.copy() for p in prompts], max_new_tokens=6)
    eng.shutdown()
    cl = EngineCluster(llama_tiny,
                       ClusterConfig(num_replicas=1,
                                     prefill_replicas=1),
                       _scfg(num_speculative_tokens=2))
    out = cl.serve([p.copy() for p in prompts], max_new_tokens=6)
    for a, b in zip(out, ref):
        assert a.tolist() == b.tolist()
    st = cl.stats()
    assert st["replicas"][0]["spec_tokens_proposed"] > 0
    assert "spec_tokens_proposed" not in st["replicas"][1]  # gamma=0
    cl.shutdown()


def test_disaggregated_prefill_tier_failure_falls_back(llama_tiny):
    """When the WHOLE prefill tier fails, decode replicas (full
    engines) take over end-to-end — the cluster only raises when no
    replica survives."""
    rng = np.random.RandomState(12)
    cl = EngineCluster(llama_tiny,
                       ClusterConfig(num_replicas=1,
                                     prefill_replicas=1), _scfg())
    rids = [cl.submit(rng.randint(1, 128, (9,)), 4) for _ in range(3)]
    cl.fail_replica(1)                  # the only prefill engine
    rids.append(cl.submit(rng.randint(1, 128, (9,)), 4))
    done = cl.run()
    assert set(done) == set(rids)
    assert all(len(v) == 4 for v in done.values())
    cl.shutdown()


def test_disaggregated_decode_tier_failure_graceful(llama_tiny):
    """A fully-failed DECODE tier cannot be served around (prefill
    engines never decode): in-flight requests terminate gracefully
    with the tokens already streamed, run() drains instead of raising
    or hanging, and new submits raise a clear error."""
    rng = np.random.RandomState(14)
    cl = EngineCluster(llama_tiny,
                       ClusterConfig(num_replicas=1,
                                     prefill_replicas=1), _scfg())
    rids = [cl.submit(rng.randint(1, 128, (9,)), 4) for _ in range(2)]
    cl.fail_replica(0)                  # the only decode replica
    with pytest.warns(UserWarning, match="decode replicas failed"):
        done = cl.run()                 # drains, no hang, no raise
    assert set(done) == set(rids)
    # each request got at most its prefill-produced first token
    assert all(len(v) <= 1 for v in done.values())
    with pytest.raises(RuntimeError, match="decode replicas failed"):
        cl.submit(rng.randint(1, 128, (9,)), 4)
    cl.shutdown()


def test_disaggregated_rejects_unservable_reservation(llama_tiny):
    """A request whose decode-side worst-case reservation can never
    fit any decode pool is rejected at cluster submit() — mirroring
    the single-engine check — instead of pending forever after
    prefill."""
    rng = np.random.RandomState(13)
    cl = EngineCluster(llama_tiny,
                       ClusterConfig(num_replicas=1,
                                     prefill_replicas=1),
                       _scfg(num_blocks=6))   # 5 usable blocks
    with pytest.raises(ValueError, match="decode"):
        cl.submit(rng.randint(1, 128, (24,)), 32)   # needs 7 blocks
    # a servable request still flows end to end
    out = cl.serve([rng.randint(1, 128, (9,))], max_new_tokens=4)
    assert len(out[0]) == 4
    cl.shutdown()


# ------------------------------------------- steady state + accounting


def test_zero_steady_state_recompiles_per_replica(llama_tiny):
    """After one warm wave, a second wave (colocated AND
    disaggregated) compiles NOTHING new on any replica — the
    export/import transfer executables are fixed-width and compile
    exactly once each."""
    rng = np.random.RandomState(8)
    for ccfg in (ClusterConfig(num_replicas=2),
                 ClusterConfig(num_replicas=1, prefill_replicas=1)):
        cl = EngineCluster(llama_tiny, ccfg, _scfg())
        cl.serve(_prompts(rng), max_new_tokens=5)        # warm wave
        execs0 = [e.stats()["executables_compiled"]
                  for e in cl.engines]
        cl.serve(_prompts(rng, lens=(7, 22, 13, 18)),
                 max_new_tokens=5)                       # steady wave
        execs1 = [e.stats()["executables_compiled"]
                  for e in cl.engines]
        assert execs1 == execs0, (ccfg, execs0, execs1)
        cl.shutdown()


def test_cluster_stats_rollup_and_metrics(llama_tiny):
    """Cluster ``stats()`` carries per-replica dicts plus the rolled-
    up routing/transfer/latency keys, and the router metrics are
    registered in the monitor registry."""
    rng = np.random.RandomState(9)
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg())
    cl.serve(_prompts(rng, lens=(9, 17)), max_new_tokens=4)
    st = cl.stats()
    for key in ("num_replicas", "prefill_replicas", "disaggregated",
                "router_requests", "router_affinity_hits",
                "router_affinity_hit_rate", "kv_blocks_transferred",
                "tokens_total", "requests_completed", "decode_steps",
                "executables_compiled", "ttft_ms", "itl_ms", "e2e_ms",
                "replicas", "pending_handoffs", "failed_replicas"):
        assert key in st, key
    assert len(st["replicas"]) == 2
    assert st["requests_completed"] == 2
    # rolled-up client-side digests observed every token
    assert st["ttft_ms"]["count"] == 2
    assert st["e2e_ms"]["count"] == 2
    assert st["itl_ms"]["count"] == 2 * 3     # 4 tokens -> 3 gaps
    reg = monitor.get_registry()._metrics
    for name in ("serving_router_affinity_hits",
                 "serving_router_queue_depth",
                 "serving_kv_blocks_transferred"):
        assert name in reg, name
    # engine stats carry the disagg keys even on a standalone fleet
    rep = st["replicas"][0]
    for key in ("role", "prefills_exported", "kv_blocks_exported",
                "kv_blocks_imported"):
        assert key in rep, key
    cl.shutdown()


def test_loadgen_cluster_conversation_affinity(llama_tiny):
    """Satellite 2 end-to-end: the goodput harness drives a CLUSTER
    through the multi-session conversation workload — every request
    completes, and the growing per-session prefixes produce router
    affinity hits under load."""
    from paddle_tpu.inference.loadgen import (SLO, run_load,
                                              conversation_workload)
    prompts, session_ids = conversation_workload(
        3, 3, vocab=128, prefix_len=16, turn_len=8, seed=1)
    assert len(prompts) == 9 and len(session_ids) == 9
    # turn t+1 of a session extends turn t (the prefix property)
    assert prompts[3][:prompts[0].size].tolist() == \
        prompts[0].tolist()
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg())
    rep = run_load(cl, prompts, mode="closed", max_new_tokens=4,
                   slo=SLO(ttft_ms=60000.0, itl_ms=60000.0))
    assert rep["completed"] == len(prompts)
    assert rep["goodput"] == 1.0          # SLO generous on CPU
    st = cl.stats()
    assert st["router_affinity_hits"] > 0
    assert st["requests_completed"] == len(prompts)
    cl.shutdown()


def test_tier1_no_slow_marker():
    """CI guard (the PR-4/5 pattern): every cluster test runs in the
    tier-1 ``-m 'not slow'`` sweep, the transfer byte-parity test is
    present, and every cluster/engine is torn down through the
    leak-sweeping ``shutdown()``."""
    import tests.conftest as c
    here = open(__file__).read()
    assert "pytest.mark.slow" not in here.replace(
        '"pytest.mark.slow"', "")
    names = [ln.split("(")[0][4:] for ln in here.splitlines()
             if ln.startswith("def test_")]
    overlap = set(names) & set(c._SLOW_TESTS)
    assert not overlap, f"tier-1 cluster tests marked slow: {overlap}"
    assert "test_export_import_roundtrip_bytes_fp_and_int8" in names
    assert "test_disaggregated_token_exact_vs_colocated" in names
    assert here.count(".shutdown()") >= 10, \
        "cluster shutdown (leak sweep) must guard these tests"

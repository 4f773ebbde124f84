"""SLO-aware preemptive scheduling + host-DRAM KV block tier (ISSUE
14): the ``ops/paged_cache.HostKVTier`` spill/restore byte roundtrip
(fp AND int8 — data + per-row scales), preempted-then-resumed requests
greedy token-exact vs never-preempted on BOTH resume paths
(swap-restore and recompute-re-prefill) across Llama / GPT / int8
pools / speculative n-gram / TP=2 / the cluster, the priority-ordering
property (every request completes exactly once; high-priority first
tokens land before low under pressure), allocator ``check_leaks``
across a preemption storm, zero steady-state recompiles with
preemption active, the ``PADDLE_TPU_PREEMPT=0`` kill switch
(bit-parity with ``enable_preemption=False``), queue timeouts
(outcome="timeout"), load shedding (outcome="shed" +
``QueueShedError``), in-flight ``cancel()`` (engine and cluster), the
LRU-eviction spill -> prefix-hit restore path, and the new
stats()/registry keys.

Tier-1 guard: every test here must run in the standard
``-m 'not slow'`` sweep — ``test_tier1_no_slow_marker`` pins that.
"""
import os
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference import (QueueShedError, ServingConfig,
                                  ServingEngine)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

jnp = pytest.importorskip("jax.numpy")


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


def _wl(rng, vocab=128):
    """One low-priority long request + two high-priority short ones —
    the canonical preemption workload."""
    return (rng.randint(1, vocab, (20,)), rng.randint(1, vocab, (9,)),
            rng.randint(1, vocab, (7,)))


def _reference(model, prompts, max_new=12, **cfg_kw):
    """Never-preempted reference: ample slots, zero contention."""
    eng = ServingEngine(model, _scfg(num_slots=len(prompts) + 1,
                                     **cfg_kw))
    out = eng.serve([p.copy() for p in prompts], max_new_tokens=max_new)
    eng.shutdown()
    return out


def _preempt_run(model, prompts, max_new=12, warm_ticks=4, **cfg_kw):
    """Drive the preemption scenario: the low-priority request streams
    a few ticks alone, then two high-priority arrivals force a slot
    preemption. Returns (per-request tokens in prompt order, stats)."""
    eng = ServingEngine(model, _scfg(**cfg_kw))
    lo, h1, h2 = prompts
    rids = [eng.submit(lo.copy(), max_new, priority=0)]
    for _ in range(warm_ticks):
        eng.step()
    rids.append(eng.submit(h1.copy(), max_new, priority=2))
    rids.append(eng.submit(h2.copy(), max_new, priority=2))
    done = eng.run()
    st = eng.stats()
    eng.shutdown()
    return [done[r] for r in rids], st


# --------------------------------------------------- host-DRAM tier


def _owned_bytes(payload):
    """Host memory a tier payload keeps alive: the buffers its arrays
    are views of, each counted once."""
    from paddle_tpu.ops import paged_cache as pc
    roots = {}
    for rows in payload:
        for x in rows:
            halves = (x.data, x.scale) if isinstance(x, pc.QuantKV) \
                else (x,)
            for a in halves:
                while isinstance(a.base, np.ndarray):
                    a = a.base
                roots[id(a)] = int(a.nbytes)
    return sum(roots.values())


def test_host_tier_roundtrip_bytes_fp_and_int8():
    """Spill -> host DRAM -> restore is a byte roundtrip: fp payloads
    byte-for-byte, int8 payloads data AND scales byte-for-byte (the
    per-row scales make a block's bytes self-contained), through the
    same export/import executables the disaggregated handoff uses plus
    the tier's slice/pad framing."""
    from paddle_tpu.ops import paged_cache as pc
    rng = np.random.RandomState(0)
    BS, H, D, NB, M = 8, 2, 16, 7, 5
    for dtype in (jnp.float32, "int8"):
        src = [pc.init_pool(NB, BS, H, D, dtype) for _ in range(2)]
        tables = jnp.asarray(np.array([[1, 2, 3]], np.int32))
        k = jnp.asarray(rng.randn(1, 3 * BS, H, D), jnp.float32)
        v = jnp.asarray(rng.randn(1, 3 * BS, H, D), jnp.float32)
        src = [pc.write_prefill(kp, vp, tables, k, v)
               for kp, vp in src]
        ids = jnp.asarray(np.array([1, 2, 3, 0, 0], np.int32))
        host = pc.payload_rows(
            pc.payload_to_host(pc.export_blocks(src, ids)), 3)
        nbytes = pc.payload_nbytes(host)
        assert nbytes > 0
        tier = pc.HostKVTier(4 * nbytes)
        assert tier.put(("victim", 0), host, nbytes)
        assert tier.bytes_used == nbytes and tier.spills == 1
        back = tier.pop(("victim", 0))
        assert tier.restores == 1 and tier.bytes_used == 0
        dst = [pc.init_pool(NB, BS, H, D, dtype) for _ in range(2)]
        dst = pc.import_blocks(dst, ids, pc.payload_pad(back, M))
        for (sk, sv), (dk, dv) in zip(src, dst):
            for s, d in ((sk, dk), (sv, dv)):
                if dtype == "int8":
                    np.testing.assert_array_equal(
                        np.asarray(s.data[1:4]),
                        np.asarray(d.data[1:4]))
                    np.testing.assert_array_equal(
                        np.asarray(s.scale[1:4]),
                        np.asarray(d.scale[1:4]))
                else:
                    np.testing.assert_array_equal(
                        np.asarray(s[1:4]), np.asarray(d[1:4]))


def test_host_tier_lru_capacity_and_drops():
    from paddle_tpu.ops import paged_cache as pc
    tier = pc.HostKVTier(100)
    a = [(np.zeros(40, np.int8), np.zeros(0, np.int8))]
    assert tier.put("a", a, 40) and tier.put("b", a, 40)
    assert tier.bytes_used == 80 and len(tier) == 2
    assert tier.put("c", a, 40)            # evicts "a" (oldest)
    assert "a" not in tier and "b" in tier and "c" in tier
    assert tier.bytes_used == 80 and tier.drops == 1
    assert tier.get("b") is not None       # MRU touch
    assert tier.put("d", a, 40)            # now evicts "c", not "b"
    assert "b" in tier and "c" not in tier
    assert not tier.put("huge", a, 101)    # refused outright
    assert tier.drops == 3
    assert tier.pop("missing") is None
    assert tier.pop("b", restore=False) is not None
    assert tier.restores == 0              # discard, not a restore
    with pytest.raises(ValueError, match="positive"):
        pc.HostKVTier(0)


@pytest.mark.parametrize("kind", ["fp", "int8", "latent"])
def test_stacked_export_is_the_block_and_roundtrips(kind):
    """The eviction spill's gather (``export_stacked``): one array per
    dtype whatever the pool kind — k and v of every layer in one, an
    int8 pool's scales in a second, a latent pool's one array a layer
    in one — whose host copy, seen through ``stacked_payload``, is the
    per-layer payload ``import_blocks`` takes: byte-for-byte what the
    fixed-width export gives for the same block, owning those bytes
    and no more."""
    from paddle_tpu.ops import paged_cache as pc
    rng = np.random.RandomState(1)
    BS, NB, M = 8, 7, 4
    if kind == "latent":
        src = [(pc.init_latent_pool(NB, BS, 128, jnp.float32)[0].at[1:4]
                .set(rng.randn(3, BS, 128).astype(np.float32)),)
               for _ in range(3)]
    else:
        dtype = "int8" if kind == "int8" else jnp.float32
        tables = jnp.asarray(np.array([[1, 2, 3]], np.int32))
        src = []
        for _ in range(2):
            kv = [jnp.asarray(rng.randn(1, 3 * BS, 2, 16), jnp.float32)
                  for _ in range(2)]
            src.append(pc.write_prefill(
                *pc.init_pool(NB, BS, 2, 16, dtype), tables, *kv))
    stacked = [np.asarray(a) for a in pc.export_stacked(
        src, jnp.asarray(np.array([2], np.int32)))]
    assert len(stacked) == (2 if kind == "int8" else 1)
    got = pc.stacked_payload(pc.stacked_layout(src), stacked)
    ids = jnp.asarray(np.array([2, 0, 0, 0], np.int32))
    want = pc.payload_rows(pc.payload_to_host(
        pc.export_blocks(src, ids)), 1)
    assert pc.payload_nbytes(got) == pc.payload_nbytes(want) \
        == sum(a.nbytes for a in stacked) == _owned_bytes(got)
    dst = pc.import_blocks(
        [pc._each(jnp.zeros_like, layer) if kind != "int8" else
         pc.init_pool(NB, BS, 2, 16, "int8") for layer in src],
        ids, pc.payload_pad(got, M))
    for s_layer, d_layer, w_rows in zip(src, dst, want):
        for sp, dp, w in zip(s_layer, d_layer, w_rows):
            halves = [(sp.data, dp.data, w.data),
                      (sp.scale, dp.scale, w.scale)] \
                if kind == "int8" else [(sp, dp, w)]
            for a, b, c in halves:
                np.testing.assert_array_equal(np.asarray(a[2]),
                                              np.asarray(b[2]))
                np.testing.assert_array_equal(np.asarray(a[2]),
                                              np.asarray(c[0]))


# ------------------------------------- preempted == never-preempted


def test_preempt_resume_token_exact_swap_and_recompute(llama_tiny):
    """The tentpole exactness pin: a preempted-then-resumed request's
    FULL token stream equals the never-preempted reference, on the
    swap-restore path AND the recompute path (forced via
    ``preempt_resume``), with the spill/restore counters proving each
    path actually ran."""
    rng = np.random.RandomState(3)
    prompts = _wl(rng)
    ref = _reference(llama_tiny, prompts)
    for policy in ("swap", "recompute"):
        got, st = _preempt_run(llama_tiny, prompts,
                               preempt_resume=policy)
        assert st["preemptions"] >= 1, policy
        assert st["kv_blocks_spilled"] >= 1, policy
        if policy == "swap":
            assert st["preempt_swap_resumes"] >= 1
            assert st["kv_blocks_restored"] >= 1
        else:
            assert st["preempt_recompute_resumes"] >= 1
        for a, b in zip(got, ref):
            assert a.tolist() == b.tolist(), policy


def test_preempt_resume_token_exact_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(11)
    m = GPTForCausalLM(GPTConfig.tiny(vocab=96, hidden=64, layers=2,
                                      heads=4))
    m.eval()
    rng = np.random.RandomState(5)
    prompts = _wl(rng, vocab=96)
    ref = _reference(m, prompts)
    got, st = _preempt_run(m, prompts, preempt_resume="auto")
    assert st["preemptions"] >= 1
    for a, b in zip(got, ref):
        assert a.tolist() == b.tolist()


def test_preempt_resume_token_exact_int8(llama_tiny):
    """int8 pools: the spilled payload carries data + per-row scales,
    so a swap-restored block dequantizes bitwise and the resumed
    stream stays exact within the int8 world."""
    rng = np.random.RandomState(9)
    prompts = _wl(rng)
    kw = dict(block_size=32, kv_cache_dtype="int8")
    ref = _reference(llama_tiny, prompts, **kw)
    got, st = _preempt_run(llama_tiny, prompts,
                           preempt_resume="swap", **kw)
    assert st["preemptions"] >= 1 and st["kv_blocks_restored"] >= 1
    for a, b in zip(got, ref):
        assert a.tolist() == b.tolist()


def test_preempt_resume_token_exact_spec_ngram(llama_tiny):
    """Speculative n-gram engines preempt too: the verify-window
    overhang blocks are trimmed before the spill (they hold rolled-
    back garbage), and the resumed chain stays the target's greedy
    chain."""
    rng = np.random.RandomState(13)
    prompts = _wl(rng)
    kw = dict(num_speculative_tokens=2)
    ref = _reference(llama_tiny, prompts, **kw)
    for policy in ("swap", "recompute"):
        got, st = _preempt_run(llama_tiny, prompts,
                               preempt_resume=policy, **kw)
        assert st["preemptions"] >= 1, policy
        for a, b in zip(got, ref):
            assert a.tolist() == b.tolist(), policy


def test_preempt_resume_token_exact_tp2(llama_tiny):
    """TP=2: the spill gathers the SHARDED pools to host and the
    restore re-places every payload array under the pool's kv_head
    sharding — resumed output stays token-exact vs the single-device
    never-preempted reference."""
    import jax
    if len(jax.devices()) < 2:
        pytest.skip("needs >= 2 devices")
    rng = np.random.RandomState(17)
    prompts = _wl(rng)
    ref = _reference(llama_tiny, prompts)
    got, st = _preempt_run(llama_tiny, prompts, preempt_resume="swap",
                           tp_degree=2)
    assert st["preemptions"] >= 1 and st["preempt_swap_resumes"] >= 1
    for a, b in zip(got, ref):
        assert a.tolist() == b.tolist()


def test_preempt_resume_token_exact_cluster(llama_tiny):
    """Cluster: ``submit(priority=)`` forwards to the owning replica,
    whose preemptive scheduler spills/resumes locally — cluster output
    stays token-exact vs the never-preempted single engine."""
    from paddle_tpu.inference.cluster import (ClusterConfig,
                                              EngineCluster)
    rng = np.random.RandomState(21)
    lo, h1, h2 = _wl(rng)
    ref = _reference(llama_tiny, (lo, h1, h2))
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg(num_slots=1))
    rids = [cl.submit(lo.copy(), 12, priority=0)]
    for _ in range(4):
        cl.step()
    rids.append(cl.submit(h1.copy(), 12, priority=2))
    rids.append(cl.submit(h2.copy(), 12, priority=2))
    done = cl.run()
    st = cl.stats()
    assert st["preemptions"] >= 1 and st["kv_blocks_spilled"] >= 1
    for r, b in zip(rids, ref):
        assert done[r].tolist() == b.tolist()
    cl.shutdown()


# ------------------------------------------------ scheduling policy


def test_double_preemption_mid_reprefill_keeps_continuation(
        llama_tiny):
    """A victim preempted AGAIN while recompute-re-prefilling its
    context must carry its original continuation (last_token /
    n_emitted) through the second preemption — requeuing it as a
    fresh request would reset n_emitted and overrun the client's
    stream past max_new."""
    rng = np.random.RandomState(61)
    lo = rng.randint(1, 128, (24,))
    his = [rng.randint(1, 128, (9,)) for _ in range(4)]
    ref = _reference(llama_tiny, [lo] + his, max_new=10)
    eng = ServingEngine(llama_tiny, _scfg(
        ragged_prefill_rows=4, preempt_resume="recompute",
        enable_prefix_cache=False))     # full-length re-prefill over
    #                                     many ticks: catchable mid-way
    rids = [eng.submit(lo.copy(), 10, priority=0)]
    for _ in range(9):
        eng.step()                      # prefill done, a few tokens
    rids.append(eng.submit(his[0].copy(), 10, priority=2))
    rids.append(eng.submit(his[1].copy(), 10, priority=2))
    n_re = 0
    for _ in range(300):
        eng.step()
        lo_slot = [s for s in eng._slots
                   if s is not None and s.rid == rids[0]]
        if lo_slot and lo_slot[0].pend_pos is not None \
                and lo_slot[0].resume is not None and n_re < 2:
            # lo is MID-re-prefill with its continuation attached:
            # submit another high-priority request to preempt it again
            n_re += 1
            rids.append(eng.submit(his[1 + n_re].copy(), 10,
                                   priority=2))
        if not eng._queue and eng.num_active == 0:
            break
    done = eng.run()
    st = eng.stats()
    assert n_re >= 1, "repro never caught the slot mid-re-prefill"
    assert st["preemptions"] >= 2
    assert done[rids[0]].size == 10     # NOT n_emitted + max_new
    assert done[rids[0]].tolist() == ref[0].tolist()
    for rid in rids[1:]:
        assert done[rid].size == 10
    eng.shutdown()


def test_priority_ordering_property(llama_tiny):
    """Under slot pressure every request still completes exactly once
    with its full token budget, and high-priority requests reach their
    FIRST token before lower classes (TTFT isolation — measured by
    stream arrival order, not wall clock)."""
    rng = np.random.RandomState(25)
    first_seen = {}
    order = []

    def cb(rid, tok):
        if rid not in first_seen:
            first_seen[rid] = len(order)
            order.append(rid)

    eng = ServingEngine(llama_tiny, _scfg(num_slots=2),
                        stream_callback=cb)
    rids, prios = [], {}
    for j in range(8):
        p = (0, 0, 1, 2)[j % 4]
        r = eng.submit(rng.randint(1, 128, (6 + 3 * (j % 3),)), 6,
                       priority=p)
        rids.append(r)
        prios[r] = p
    done = eng.run()
    assert sorted(done) == sorted(rids)            # exactly once
    for r in rids:
        assert done[r].size == 6, (r, done[r])     # full budget
    hi = [first_seen[r] for r in rids if prios[r] == 2]
    lo = [first_seen[r] for r in rids if prios[r] == 0]
    assert np.mean(hi) < np.mean(lo), (hi, lo)
    eng.shutdown()


def test_preemption_storm_check_leaks(llama_tiny):
    """A tight overcommitted pool under mixed priorities: preemptions,
    spills and resumes churn block ownership hard — afterwards the
    allocator's free/cached/referenced partition must still be exact
    and every request complete exactly once."""
    rng = np.random.RandomState(29)
    eng = ServingEngine(llama_tiny, _scfg(
        num_slots=3, num_blocks=1 + 8,      # ~2 worst-case residents:
        admission_watermark_blocks=1))      # 3 slots force overcommit
    rids = []
    for j in range(9):
        # staggered arrivals: later (often higher-priority) requests
        # land while earlier ones hold slots/blocks — slot AND block
        # pressure preemptions both fire
        rids.append(eng.submit(rng.randint(1, 128, (12 + 4 * (j % 2),)),
                               8, priority=j % 3))
        eng.step()
        eng.step()
    done = eng.run()
    st = eng.stats()
    assert sorted(done) == sorted(rids)
    for r in rids:
        assert done[r].size == 8
    assert st["preemptions"] >= 1, st["preemptions"]
    eng.shutdown()          # check_leaks sweeps the partition
    if eng._host_tier is not None:
        # no victim payload may outlive its request
        assert not any(k[0] == "victim" for k in
                       eng._host_tier._items)


def test_zero_steady_state_recompiles_with_preemption(llama_tiny):
    """Preemption adds NO executables past the shared export/import
    pair: a second preemption wave compiles nothing."""
    rng = np.random.RandomState(33)
    prompts = _wl(rng)
    eng = ServingEngine(llama_tiny, _scfg(preempt_resume="swap"))

    def wave():
        lo, h1, h2 = prompts
        eng.submit(lo.copy(), 12, priority=0)
        for _ in range(4):
            eng.step()
        eng.submit(h1.copy(), 12, priority=2)
        eng.submit(h2.copy(), 12, priority=2)
        eng.run()

    wave()
    n1 = eng.stats()["executables_compiled"]
    assert eng.stats()["preemptions"] >= 1
    wave()
    st = eng.stats()
    assert st["executables_compiled"] == n1, \
        "a preemption wave must not compile new executables"
    assert st["preemptions"] >= 2
    eng.shutdown()


def test_kill_switch_bit_parity(llama_tiny, monkeypatch):
    """PADDLE_TPU_PREEMPT=0 beats an explicit enable_preemption=True:
    priorities are ignored, nothing spills, and the served tokens are
    bit-identical to an enable_preemption=False engine."""
    rng = np.random.RandomState(37)
    prompts = _wl(rng)

    def run_wl(e):
        lo, h1, h2 = prompts
        rids = [e.submit(lo.copy(), 8, priority=0)]
        e.step()
        rids.append(e.submit(h1.copy(), 8, priority=5))
        rids.append(e.submit(h2.copy(), 8, priority=5))
        done = e.run()
        return [done[r].tolist() for r in rids]

    eng = ServingEngine(llama_tiny, _scfg(enable_preemption=False))
    ref = run_wl(eng)
    assert eng.stats()["preemption_enabled"] is False
    eng.shutdown()
    monkeypatch.setenv("PADDLE_TPU_PREEMPT", "0")
    eng = ServingEngine(llama_tiny, _scfg(enable_preemption=True))
    got = run_wl(eng)
    st = eng.stats()
    assert st["preemption_enabled"] is False
    assert st["preemptions"] == 0 and st["kv_blocks_spilled"] == 0
    eng.shutdown()
    assert got == ref


# ------------------------------------- timeouts / shedding / cancel


def test_queue_timeout_outcome(llama_tiny):
    h = monitor.histogram("serving_queue_wait_ms",
                          labels=("outcome",))
    before = h.labels(outcome="timeout").value()["count"]
    rng = np.random.RandomState(41)
    eng = ServingEngine(llama_tiny, _scfg(num_slots=1))
    r0 = eng.submit(rng.randint(1, 128, (20,)), 20)
    eng.step()
    r1 = eng.submit(rng.randint(1, 128, (6,)), 4,
                    max_queue_wait_ms=1.0)
    time.sleep(0.01)
    done = eng.run()
    st = eng.stats()
    assert st["requests_timed_out"] == 1
    assert done[r1].size == 0              # stream never started
    assert done[r0].size == 20             # survivor unaffected
    assert h.labels(outcome="timeout").value()["count"] - before == 1
    assert r1 not in eng._submit_t
    eng.shutdown()


def test_shed_queue_depth(llama_tiny):
    h = monitor.histogram("serving_queue_wait_ms",
                          labels=("outcome",))
    before = h.labels(outcome="shed").value()["count"]
    rng = np.random.RandomState(45)
    eng = ServingEngine(llama_tiny, _scfg(num_slots=1,
                                          shed_queue_depth=1))
    eng.submit(rng.randint(1, 128, (8,)), 4)
    eng.step()                              # occupies the slot
    eng.submit(rng.randint(1, 128, (8,)), 4)    # queued (depth 1)
    with pytest.raises(QueueShedError, match="shed threshold"):
        eng.submit(rng.randint(1, 128, (8,)), 4)
    st = eng.stats()
    assert st["requests_shed"] == 1
    assert h.labels(outcome="shed").value()["count"] - before == 1
    eng.run()
    eng.shutdown()


def test_cancel_inflight_frees_blocks_and_streams_partial(llama_tiny):
    rng = np.random.RandomState(49)
    eng = ServingEngine(llama_tiny, _scfg())
    r0 = eng.submit(rng.randint(1, 128, (12,)), 20)
    for _ in range(3):
        eng.step()
    free0 = eng.stats()["free_blocks"]
    e2e0 = eng.stats()["e2e_ms"]["count"]
    assert eng.cancel(r0) is True
    st = eng.stats()
    assert st["free_blocks"] > free0       # blocks freed mid-decode
    assert st["requests_cancelled"] == 1
    assert st["e2e_ms"]["count"] == e2e0 + 1
    done = eng.run()
    assert 1 <= done[r0].size < 20         # partial stream surfaced
    assert eng.cancel(r0) is False
    eng.shutdown()                          # leak sweep


def test_cancel_inflight_cluster_forwards(llama_tiny):
    from paddle_tpu.inference.cluster import (ClusterConfig,
                                              EngineCluster)
    rng = np.random.RandomState(53)
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg())
    g0 = cl.submit(rng.randint(1, 128, (12,)), 20)
    for _ in range(3):
        cl.step()
    assert cl.cancel(g0) is True
    assert cl.cancel(g0) is False
    done = cl.run()
    assert g0 in done and 1 <= done[g0].size < 20
    cl.shutdown()


# ----------------------------------------- eviction spill / restore


@pytest.mark.parametrize("pool_kw", [
    {}, dict(kv_cache_dtype="int8"), dict(tp_degree=2)],
    ids=["fp", "int8", "tp2"])
def test_evicted_published_block_restores_from_host_tier(llama_tiny,
                                                         pool_kw):
    """The hierarchical-KV half beyond preemption: LRU-evicted
    published blocks spill their bytes to the host tier, and a later
    prompt whose prefix hashes to them RESTORES instead of
    re-prefilling — token-exact, with the spill/restore counters
    pinned. The spill gathers the evicted block alone (an int8 pool's
    data AND scales, a TP pool's shards assembled): what crossed to
    the host is what the tier booked, and an entry keeps no buffer
    alive beyond its own block."""
    import jax
    if pool_kw.get("tp_degree", 1) > len(jax.devices()):
        pytest.skip("needs >= 2 devices")
    rng = np.random.RandomState(57)
    eng = ServingEngine(llama_tiny, _scfg(
        num_slots=1, max_model_len=48, num_blocks=5, **pool_kw))
    pA = rng.randint(1, 128, (16,))         # 2 full publishable blocks
    outA = eng.serve([pA.copy()], max_new_tokens=6)[0]
    eng.serve([rng.randint(1, 128, (16,))], max_new_tokens=6)
    st1 = eng.stats()
    assert st1["cache_evictions"] >= 1
    assert st1["kv_blocks_spilled"] >= 1
    assert st1["host_tier_bytes"] > 0
    # copied over stored is 1: nothing dropped yet, so the tier holds
    # every byte that crossed
    assert st1["kv_spill_bytes_copied"] == st1["host_tier_bytes"]
    assert not eng._spill_pending
    for payload, nbytes, _ in eng._host_tier._items.values():
        assert _owned_bytes(payload) == nbytes
    outA2 = eng.serve([pA.copy()], max_new_tokens=6)[0]
    st2 = eng.stats()
    assert st2["kv_blocks_restored"] >= 1
    assert outA2.tolist() == outA.tolist()
    eng.shutdown()


def _full_of_published(llama_tiny):
    """An engine whose pool holds prompt A's two published blocks as
    the oldest of its LRU list and too few plain-free blocks for the
    next admission: ``(engine, prompt A, A's tokens, rng)``."""
    rng = np.random.RandomState(61)
    eng = ServingEngine(llama_tiny, _scfg(
        num_slots=2, max_model_len=48, num_blocks=8))
    pA = rng.randint(1, 128, (16,))
    outA = eng.serve([pA.copy()], max_new_tokens=6)[0]
    eng.serve([rng.randint(1, 128, (24,))], max_new_tokens=4)
    assert eng.stats()["cache_evictions"] == 0
    return eng, pA, outA, rng


def test_block_evicted_and_looked_up_in_one_admit_restores(llama_tiny):
    """One ``admit`` seats a fresh prompt, whose allocation evicts
    prompt A's published blocks (their spills launched, not yet on the
    host), and then prompt A itself: its lookup misses the device
    index and must still hit the tier — the launched spills are taken
    in before the tier is read, inside that same ``admit``."""
    eng, pA, outA, rng = _full_of_published(llama_tiny)
    eng.submit(rng.randint(1, 128, (24,)), 4)
    rid = eng.submit(pA.copy(), 6)
    eng.step()
    st = eng.stats()
    assert st["kv_blocks_spilled"] >= 2 and st["kv_blocks_restored"] == 2
    ev = [e for e in eng.tracer.events() if e["tid"] == 0]
    (admit,) = [e for e in ev if e["name"] == "admit"
                and e["args"]["admitted"] == 2]
    inside = [e for e in ev if e["name"] == "spill"
              and admit["t0"] <= e["t0"]
              and e["t0"] + e["dur"] <= admit["t0"] + admit["dur"]]
    assert sum("block" in e["args"] for e in inside) >= 2
    assert sum(e["args"].get("drained", 0) for e in inside) >= 2
    assert eng.run()[rid].tolist() == outA.tolist()
    eng.shutdown()


def test_warm_migration_builds_the_spill_gather(llama_tiny):
    """``warm_migration()`` leaves the eviction spill's one-block
    gather built beside the export/import pair: a run of evictions
    then compiles nothing."""
    eng, _, _, rng = _full_of_published(llama_tiny)
    eng.warm_migration()
    st0 = eng.stats()
    for _ in range(3):
        eng.serve([rng.randint(1, 128, (24,))], max_new_tokens=4)
    st = eng.stats()
    assert st["kv_blocks_spilled"] - st0["kv_blocks_spilled"] >= 3
    assert st["executables_compiled"] == st0["executables_compiled"]
    eng.shutdown()


@pytest.mark.parametrize("closer", ["shutdown", "purge_published",
                                    "export_session", "idle_step"])
def test_launched_spills_are_taken_in_before_the_tier_is_read(
        llama_tiny, closer):
    """A spill launched outside any tick (here by a bare allocation,
    as ``admit_prefilled`` / ``admit_migrated`` make them) is on the
    host before ``shutdown()``, ``purge_published()`` or a session
    export return, and after a ``step()`` that launched nothing (no
    ``commit`` ran to take it in: the tick's own closing drain did):
    nothing stays pending, and every byte launched is accounted."""
    eng, _, _, rng = _full_of_published(llama_tiny)
    eng.submit(rng.randint(1, 128, (9,)), 8)
    for _ in range(3):
        eng.step()                  # slot 0 decodes; nothing evicted
    if closer == "idle_step":
        eng.run()                   # nothing seated, nothing queued
    st = eng.stats()
    assert st["cache_evictions"] == st["kv_spill_bytes_copied"] == 0
    eng._alloc.free(eng._alloc.alloc(eng._alloc.free_blocks))
    n = len(eng._spill_pending)
    assert n >= 2
    if closer == "idle_step":
        steps = st["decode_steps"]
        eng.step()
        assert eng.stats()["decode_steps"] == steps     # no launch
    else:
        getattr(eng, closer)(
            *((0,) if closer == "export_session" else ()))
    assert not eng._spill_pending
    assert eng.stats()["kv_spill_bytes_copied"] == n * eng._spill_nbytes
    if closer == "purge_published":
        assert not any(k[0] == "pub" for k in eng._host_tier._items)
        assert eng.stats()["host_tier_bytes"] == 0
    if closer != "shutdown":
        eng.run()
        eng.shutdown()


# --------------------------------------------------- observability


def test_stats_and_registry_keys(llama_tiny):
    eng = ServingEngine(llama_tiny, _scfg())
    st = eng.stats()
    for k in ("preemption_enabled", "preemptions",
              "kv_blocks_spilled", "kv_blocks_restored",
              "host_tier_bytes", "host_tier_capacity_bytes",
              "preempt_swap_resumes", "preempt_recompute_resumes",
              "prefill_rows_per_s_est", "host_xfer_bytes_per_s_est",
              "requests_shed", "requests_timed_out",
              "requests_cancelled"):
        assert k in st, k
    assert st["preemption_enabled"] is True
    names = monitor.get_registry()._metrics
    for n in ("serving_preemptions", "serving_kv_blocks_spilled",
              "serving_kv_blocks_restored", "serving_host_tier_bytes"):
        assert n in names, n
    # router depth weighting: lower-priority work is discounted
    eng.submit(np.arange(1, 9), 4, priority=0)
    assert eng.queue_depth() == 1
    assert eng.queue_depth(priority=1) == 0.25
    assert eng.queue_depth(priority=0) == 1.0
    eng.run()
    eng.shutdown()


def test_config_validation():
    with pytest.raises(ValueError, match="preempt_resume"):
        ServingConfig(preempt_resume="maybe")
    with pytest.raises(ValueError, match="host_kv_tier_bytes"):
        ServingConfig(host_kv_tier_bytes=-1)
    with pytest.raises(ValueError, match="shed_queue_depth"):
        ServingConfig(shed_queue_depth=0)


def test_submit_validation(llama_tiny):
    eng = ServingEngine(llama_tiny, _scfg())
    with pytest.raises(ValueError, match="priority"):
        eng.submit(np.arange(1, 9), 4, priority="high")
    with pytest.raises(ValueError, match="max_queue_wait_ms"):
        eng.submit(np.arange(1, 9), 4, max_queue_wait_ms=0)
    eng.shutdown()


# -------------------------------------------------------- CI guard


def test_tier1_no_slow_marker(request):
    """This file IS the tier-1 coverage for preemptive scheduling —
    none of it may carry the slow marker, the exactness pin must
    exist, and the engine paths above all sweep shutdown()."""
    import ast
    import os as _os
    path = _os.path.join(_os.path.dirname(__file__),
                         "test_preemption.py")
    with open(path) as f:
        tree = ast.parse(f.read())
    names = [n.name for n in ast.walk(tree)
             if isinstance(n, ast.FunctionDef)
             and n.name.startswith("test_")]
    assert "test_preempt_resume_token_exact_swap_and_recompute" \
        in names
    assert "test_preemption_storm_check_leaks" in names
    from tests.conftest import _SLOW_TESTS
    marked = [n for n in names if n in _SLOW_TESTS]
    assert not marked, f"tier-1 preemption tests marked slow: {marked}"

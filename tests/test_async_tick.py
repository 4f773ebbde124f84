"""Async tick pipeline (ISSUE 20; ISSUE 29: the way the engine ticks).
Every tick without speculation is launched BEFORE the last one's
tokens are fetched: the host packs it from committed state plus what
the tick in flight does to it, and the decode ids it does not have yet
are read from that tick's output on the device. The contract under
test is EXACTNESS — the default engine (``async_depth`` unset, or 1)
must be token-exact against the blocking loop (``async_depth=0``)
across the whole engine matrix (fp / int8 KV / spec n-gram / spec tree
/ LoRA / TP=2 / GPT / colocated + disaggregated cluster) AND across
the mixes that used to drain the pipeline: staggered admissions with a
waiting queue, a prompt prefilled over several chunks beside decoding
slots, budget retirements with the queue non-empty, an EOS hit while
the next tick is in flight, a sampled request. Also pinned here: that
such a mix launches nearly all of its ticks ahead and compiles one
executable; the ``PADDLE_TPU_ASYNC_TICK=0`` kill switch; zero
steady-state recompiles across waves; what still drains the pipeline
(cancel, preemption, migration, a handoff); EOS-overrun tokens dropped
exactly at commit; the non-finite-logits health probe through the
lagging fetch; and the always-present stats keys (``async_depth`` /
``pipeline_flushes`` / ``host_gap_ms``).

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


def _launches(eng):
    """How each tick of ``eng`` was launched, in order."""
    return [e["args"]["dispatch"] for e in eng.tracer.events()
            if e["name"] == "launch" and e["ph"] == "X"]


# ------------------------------------------------- parity matrix


@pytest.mark.parametrize("variant", ["fp", "int8", "spec_ngram",
                                     "spec_tree"])
def test_parity_matrix_llama(llama_tiny, variant):
    """async ON == OFF greedy token-exact, per engine variant, with
    the one-executable collapse intact in BOTH modes (the tokens of
    the tick before are one more operand of the ONE tick executable).
    A speculating engine's proposals need the committed history: it
    keeps the blocking tick and says so (depth 0)."""
    kw = {"fp": {},
          "int8": dict(kv_cache_dtype="int8"),
          "spec_ngram": dict(num_speculative_tokens=2),
          "spec_tree": dict(num_speculative_tokens=2,
                            spec_tree=(0, 1))}[variant]
    on, st_on = _serve(llama_tiny, _prompts(), 1, **kw)
    off, st_off = _serve(llama_tiny, _prompts(), 0, **kw)
    _assert_equal(off, on, f"llama {variant} async on/off")
    ahead = variant in ("fp", "int8")
    assert st_on["async_depth"] == (1 if ahead else 0)
    assert st_off["async_depth"] == 0
    assert st_on["executables_compiled"] == \
        st_off["executables_compiled"] == 1
    assert st_on["pipeline_flushes"] == 0
    if ahead:                       # g==0: the pipeline actually ran
        assert st_on["host_gap_ms"]["count"] > 0
        assert st_on["tokens_total"] == st_off["tokens_total"]
        # budget retirements free their seat at the dispatch, so the
        # schedule is the blocking loop's, tick for tick
        assert st_on["decode_steps"] == st_off["decode_steps"]
        assert st_on["prefill_chunks"] == st_off["prefill_chunks"]


def test_parity_gpt(gpt_tiny):
    """GPT (LayerNorm + fused QKV + biased MLP): same graph,
    token-exact."""
    on, st_on = _serve(gpt_tiny, _prompts(vocab=96), 1)
    off, _ = _serve(gpt_tiny, _prompts(vocab=96), 0)
    _assert_equal(off, on, "gpt async on/off")
    assert st_on["executables_compiled"] == 1


def test_parity_lora(llama_tiny):
    """Multi-LoRA: the per-slot adapter row rides the slots pack of
    every tick, so a tick launched ahead keeps each slot pinned to its
    adapter."""
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
            assert _launches(eng).count("carry") > 0
        eng.shutdown()
    _assert_equal(outs[0], outs[1], "lora async on/off")


def test_parity_tp2(llama_tiny):
    """TP=2: the tokens a tick leaves on the device are pinned
    replicated across the mesh — as the next tick's operand their
    sharding matches the AOT signature."""
    on, st_on = _serve(llama_tiny, _prompts(), 1, tp_degree=2)
    off, _ = _serve(llama_tiny, _prompts(), 0, tp_degree=2)
    _assert_equal(off, on, "tp2 async on/off")
    assert st_on["tp_degree"] == 2
    assert st_on["executables_compiled"] == 1


@pytest.mark.parametrize("disagg", [False, True])
def test_parity_cluster(llama_tiny, disagg):
    """Cluster dispatch-all-then-commit-all: colocated and
    prefill/decode-disaggregated fleets stay token-exact vs blocking
    replica ticking, with the fleet stats roll-ups present. The
    disaggregated fleet's handoffs (pop on the prefill replica, seat
    on the decode replica) drain those engines' pipelines."""
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


# ------------------------------- the mixes that used to drain it


def _mix(name):
    """``(config, schedule)`` of one mix: ``schedule`` is a list of
    ``(steps to run first, prompt, max_new, submit kwargs)``."""
    rng = np.random.RandomState(17)
    p = lambda n: rng.randint(1, 128, (n,)).astype(np.int64)
    if name == "staggered_queue":
        # five arrivals on two slots, two steps apart: there is always
        # a queue, and admissions land beside decoding slots
        return {}, [(2 * k, p(n), 6 + k, {})
                    for k, n in enumerate((9, 5, 12, 7, 10))]
    if name == "chunked_prompt":
        # a 40-token prompt rides five 8-row chunks beside a decoding
        # slot, and a second long prompt trickles behind it
        return dict(max_model_len=96), [
            (0, p(6), 14, {}), (3, p(40), 5, {}), (0, p(27), 5, {})]
    if name == "budget_retire_queue":
        # short budgets and a deep queue: a seat changes hands every
        # few ticks
        return {}, [(0, p(5 + k % 4), 2 + k % 3, {}) for k in range(9)]
    if name == "eos_mid_pipeline":
        # (the test picks the EOS from what these streams hold:)
        # whichever streams hit it retire one tick after the device
        # has already been given their next row
        return {}, [(k, p(6 + k), 16, {}) for k in range(5)]
    if name == "sampled":
        # temperature > 0: the key is split once a dispatch, in
        # dispatch order, and the schedule is the blocking loop's
        return dict(decode_strategy="sampling", temperature=0.9,
                    top_k=20, seed=5), [
            (2 * k, p(n), 7, dict(temperature=t))
            for k, (n, t) in enumerate(((9, 0.7), (5, None), (12, 1.3),
                                        (7, None)))]
    raise KeyError(name)


def _run_mix(model, name, depth, **over):
    kw, schedule = _mix(name)
    eng = ServingEngine(model, _scfg(async_depth=depth, **kw, **over))
    rids = []
    for steps, prompt, max_new, skw in schedule:
        for _ in range(steps):
            eng.step()
        rids.append(eng.submit(prompt.copy(), max_new, **skw))
    done = eng.run()
    st = eng.stats()
    how = _launches(eng)
    assert eng.shutdown()           # the allocator's invariants hold
    return [done[r] for r in rids], st, how


MIXES = ["staggered_queue", "chunked_prompt", "budget_retire_queue",
         "eos_mid_pipeline", "sampled"]


@pytest.mark.parametrize("mix", MIXES)
def test_parity_mixes(llama_tiny, mix):
    """ON == OFF token-exact through admissions, chunked prefill,
    budget retirements, EOS hits and sampling, none of which drains
    the pipeline any more; one executable either way."""
    over = {}
    if mix == "eos_mid_pipeline":
        free, _, _ = _run_mix(llama_tiny, mix, 0)
        mids = [int(t) for s in free for t in np.asarray(s)[3:12]]
        over["eos_token_id"] = max(set(mids), key=mids.count)
    on, st_on, how = _run_mix(llama_tiny, mix, 1, **over)
    off, st_off, _ = _run_mix(llama_tiny, mix, 0, **over)
    _assert_equal(off, on, f"mix {mix} async on/off")
    assert st_on["pipeline_flushes"] == 0
    assert st_on["executables_compiled"] == \
        st_off["executables_compiled"] == 1
    assert st_on["tokens_total"] == st_off["tokens_total"]
    assert st_on["requests_completed"] == st_off["requests_completed"]
    assert how.count("carry") > how.count("packed")
    if mix == "eos_mid_pipeline":
        # the mix does hit its EOS, and an EOS costs the device a row
        # the host could not know was dead — never a token
        assert any(len(np.asarray(t)) < 16 for t in on)
    else:
        assert st_on["decode_steps"] == st_off["decode_steps"]
        assert st_on["prefill_chunks"] == st_off["prefill_chunks"]


def test_mix_launches_ahead_and_compiles_once(llama_tiny):
    """The new rule, counted: a mix of staggered admissions, chunked
    prefill and budget retirements under a waiting queue launches
    over 80% of its ticks with another still uncommitted
    (``dispatch="carry"``: what ``pipelined_tick_share`` reads), every
    launch is one of the two kinds, and no second executable is ever
    compiled."""
    rng = np.random.RandomState(23)
    eng = ServingEngine(llama_tiny, _scfg(max_model_len=96))
    assert eng.stats()["async_depth"] == 1      # the default
    lens = (9, 30, 5, 12, 26, 7, 10, 6, 18, 8)
    for k, n in enumerate(lens):
        eng.submit(rng.randint(1, 128, (n,)), 4 + k % 5)
        eng.step()
    eng.run()
    st = eng.stats()
    how = _launches(eng)
    assert len(how) == st["decode_steps"] > 30
    assert set(how) == {"carry", "packed"}
    assert how.count("carry") > 0.8 * len(how)
    assert how[0] == "packed"       # nothing was in flight before it
    assert st["executables_compiled"] == 1
    assert st["pipeline_flushes"] == 0
    assert st["requests_completed"] == len(lens)
    # each tick's span says how that tick was launched
    ticks = [e["args"]["dispatch"] for e in eng.tracer.events()
             if e["name"] == "tick"]
    assert ticks == how
    eng.shutdown()


# --------------------------------------------- kill switch / arming


def test_kill_switch_and_env_arming(llama_tiny, monkeypatch):
    """``PADDLE_TPU_ASYNC_TICK=0`` beats ``async_depth=1`` bit-for-bit
    (same tokens, same executable census, depth reported 0); an engine
    that leaves the field unset dispatches ahead, with or without the
    variable's old "1"."""
    off, st_off = _serve(llama_tiny, _prompts(), 0)
    monkeypatch.setenv("PADDLE_TPU_ASYNC_TICK", "0")
    killed, st_k = _serve(llama_tiny, _prompts(), 1)
    _assert_equal(off, killed, "kill switch vs sync")
    assert st_k["async_depth"] == 0
    assert st_k["pipeline_flushes"] == 0
    assert st_k["executables_compiled"] == st_off["executables_compiled"]
    unset, st_u = _serve(llama_tiny, _prompts(), None)
    assert st_u["async_depth"] == 0             # the switch is still on
    for env in ("1", None):
        if env is None:
            monkeypatch.delenv("PADDLE_TPU_ASYNC_TICK")
        else:
            monkeypatch.setenv("PADDLE_TPU_ASYNC_TICK", env)
        armed, st_a = _serve(llama_tiny, _prompts(), None)
        _assert_equal(off, armed, f"default (env {env}) vs sync")
        assert st_a["async_depth"] == 1


def test_async_depth_validation(llama_tiny):
    with pytest.raises(ValueError, match="async_depth"):
        _scfg(async_depth=2)
    with pytest.raises(ValueError, match="async_depth"):
        _scfg(async_depth=True)


# ------------------------------------------------ steady-state pins


def test_zero_steady_state_recompiles_two_waves(llama_tiny):
    """Two waves through one async engine: the executable census is
    pinned at 1 after wave 1 and STAYS 1 — a tick launched ahead
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


# ------------------------------------------ what drains, what rides


def test_staggered_admission_rides_the_pipeline(llama_tiny):
    """A request arriving while a tick is in flight is admitted by the
    NEXT dispatch, beside that tick: nothing is drained, its prompt
    rows ride a tick launched ahead, and every greedy token matches
    the blocking schedule exactly. (Before ISSUE 29 an admission
    flushed the pipeline; this test pinned that.)"""
    def run(depth):
        eng = ServingEngine(llama_tiny, _scfg(async_depth=depth))
        p0, p1 = _prompts(lens=(9, 7))
        rids = [eng.submit(p0.copy(), 10)]
        for _ in range(4):
            eng.step()
        n0 = len(_launches(eng))
        rids.append(eng.submit(p1.copy(), 8))
        done = eng.run()
        st = eng.stats()
        how = _launches(eng)[n0:]
        eng.shutdown()
        return [done[r] for r in rids], st, how
    on, st_on, how = run(1)
    off, st_off, _ = run(0)
    _assert_equal(off, on, "staggered admission async on/off")
    assert st_on["pipeline_flushes"] == 0
    assert how[0] == "carry"        # the admission's own tick
    assert st_on["decode_steps"] == st_off["decode_steps"]


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
    assert st_on["pipeline_flushes"] >= 1


def test_growth_preemption_inside_a_dispatch(llama_tiny):
    """An overcommitted pool runs dry while a tick is in flight: the
    growth that finds it dry commits that tick first, preempts on
    committed state, and packs the tick again from there — tokens
    exact, nothing leaked."""
    def run(depth):
        eng = ServingEngine(llama_tiny, _scfg(
            async_depth=depth, num_slots=3, num_blocks=9,
            enable_preemption=True, admission_watermark_blocks=0,
            max_model_len=64, enable_prefix_cache=False))
        rng = np.random.RandomState(9)
        rids = [eng.submit(rng.randint(1, 128, (n,)), 20)
                for n in (7, 6, 5)]
        done = eng.run()
        st = eng.stats()
        assert eng.shutdown()
        return [done[r] for r in rids], st
    on, st_on = run(1)
    off, st_off = run(0)
    _assert_equal(off, on, "growth preemption async on/off")
    assert st_on["preemptions"] >= 1 and st_off["preemptions"] >= 1
    assert st_on["pipeline_flushes"] >= 1


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
    assert src.stats()["pipeline_flushes"] == 1
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


def test_step_returns_what_a_drain_committed(llama_tiny):
    """Tokens a drain commits reach their callback at the drain and
    the caller of the next ``step()`` in its return: nothing a client
    is owed goes missing from either stream."""
    got, ret = [], []
    eng = ServingEngine(llama_tiny, _scfg(),
                        stream_callback=lambda r, t: got.append((r, t)))
    r0 = eng.submit(_prompts(lens=(9,))[0].copy(), 12)
    r1 = eng.submit(_prompts(lens=(7,))[0].copy(), 12)
    for _ in range(4):
        ret.extend(eng.step())
    assert eng.cancel(r1)
    while eng.num_active or eng.num_queued:
        ret.extend(eng.step())
    assert [(r, int(t)) for r, t in ret] == \
        [(r, int(t)) for r, t in got]
    assert sum(r == r0 for r, _ in ret) == 12
    eng.shutdown()


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
    # the row the device was given before the host saw the EOS
    assert st_on["decode_steps"] == st_off["decode_steps"] + 1


# ------------------------------------------------- health under async


def test_nonfinite_probe_fires_under_async(llama_tiny):
    """ISSUE 20 satellite: the non-finite-logits probe rides the
    lagging fetch (taken at COMMIT, off the dispatch path) — NaN
    params must still trip the page alert on the default engine with
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
    plain engine, a blocking one and a 1-replica cluster report them
    (zeros / empty digest), so dashboards never KeyError across
    configs. The depth is the one the engine runs: 1 by default."""
    eng = ServingEngine(llama_tiny, _scfg())
    st = eng.stats()
    assert st["async_depth"] == 1
    assert st["pipeline_flushes"] == 0
    assert st["host_gap_ms"]["count"] >= 0
    eng.shutdown()
    eng = ServingEngine(llama_tiny, _scfg(async_depth=0))
    assert eng.stats()["async_depth"] == 0
    eng.shutdown()
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=1),
                       _scfg())
    cst = cl.stats()
    assert cst["async_depth"] == 1 and cst["pipeline_flushes"] == 0
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

"""Elastic fleet autoscaling + live KV session migration (ISSUE 19):
the ``AutoscalePolicy`` control loop (hysteresis, cooldown, ANY-up /
ALL-down trigger logic, fleet bounds, the disaggregated prefill:decode
retune), the loadgen shaped-load profiles, and the cluster chaos
suite — scale-down drains that live-migrate every resident session
TOKEN-EXACT vs never-migrated (fp, int8 KV, n-gram speculation, and a
resident LoRA adapter), scale-up under burst admitting the queued
backlog, a target replica dying mid-migration (aborts cleanly, the
session re-seats elsewhere), the payload-loss recompute degrade, zero
steady-state recompiles across a scale cycle, the
``PADDLE_TPU_AUTOSCALE=0`` kill switch (bit-parity with a fixed-N
fleet), the fail_replica published-prefix purge regression, cancel of
an in-transit migration, and priority-aware cluster rebalancing.

Tier-1 guard: every test here must run in the standard
``-m 'not slow'`` sweep — ``test_tier1_no_slow_marker`` pins that.
"""
import json

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.inference.autoscale import (AutoscaleConfig,
                                            AutoscalePolicy)
from paddle_tpu.inference.cluster import ClusterConfig, EngineCluster
from paddle_tpu.inference.loadgen import (profile_arrivals, run_load,
                                          _profile_rate)
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.ops import paged_cache as _pc


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


def _prompts(rng, lens=(11, 19, 9, 14), vocab=128):
    return [rng.randint(1, vocab, (n,)) for n in lens]


def _lora_w(seed, rank=4, d=64, names=("q_proj", "o_proj")):
    # q/o only: k/v project to the GQA width on this fixture
    rng = np.random.RandomState(seed)
    return {n: (rng.normal(0, 0.3, (d, rank)).astype(np.float32),
                rng.normal(0, 0.3, (rank, d)).astype(np.float32))
            for n in names}


# ------------------------------------------------------ policy (unit)


def _sig(replicas=2, slots=4, active=0, queued=0, burn=0.0, busy=0.0):
    return {"replicas": replicas, "slots": slots, "active": active,
            "queued": queued, "burn_fast": burn, "busy": busy}


def test_policy_hysteresis_then_cooldown():
    """A breach must hold ``hysteresis_ticks`` CONSECUTIVE ticks to
    act, any action opens a ``cooldown_ticks`` hold-down, and one
    clean tick resets the streak."""
    pol = AutoscalePolicy(AutoscaleConfig(
        max_replicas=4, hysteresis_ticks=3, cooldown_ticks=5))
    hot = _sig(queued=8)                    # 2 queued/slot >= 0.5
    assert pol.decide(hot) == "hold"
    assert pol.decide(hot) == "hold"
    assert pol.decide(hot) == "up"          # 3rd consecutive breach
    for _ in range(5):                      # cooldown absorbs breaches
        assert pol.decide(hot) == "hold"
    # the streak accumulated THROUGH the cooldown: a pressure that
    # outlives the hold-down acts the very next tick
    assert pol.decide(hot) == "up"
    # a single clean tick resets the streak
    pol2 = AutoscalePolicy(AutoscaleConfig(hysteresis_ticks=3,
                                           cooldown_ticks=0))
    pol2.decide(hot), pol2.decide(hot)
    assert pol2.decide(_sig()) == "hold"    # breach streak broken
    assert pol2.decide(hot) == "hold"
    assert pol2.decide(hot) == "hold"
    assert pol2.decide(hot) == "up"
    st = pol2.state()
    assert st["decisions"]["up"] == 1 and st["cooldown_remaining"] == 0


def test_policy_any_up_all_down_and_bounds():
    """ANY up-trigger scales up (queue, occupancy, SLO burn, roofline
    busy each fire alone); scale-down needs occupancy AND queue BOTH
    under their floors; the fleet never leaves [min, max]."""
    mk = lambda: AutoscalePolicy(AutoscaleConfig(
        min_replicas=1, max_replicas=4, hysteresis_ticks=1,
        cooldown_ticks=0))
    for kw in (dict(queued=8), dict(active=4), dict(burn=20.0),
               dict(busy=0.99)):
        assert mk().decide(_sig(**kw)) == "up", kw
    # down: occupancy floor alone is NOT enough when the queue holds
    pol = mk()
    assert pol.decide(_sig(active=0, queued=1)) == "hold"
    assert pol.decide(_sig(active=0, queued=0)) == "down"
    # bounds clamp both directions even with the trigger held
    assert mk().decide(_sig(replicas=4, queued=40)) == "hold"
    assert mk().decide(_sig(replicas=1, active=0, queued=0)) == "hold"


def test_policy_prefill_retune_and_validation():
    """``decide_prefill`` retunes the prefill:decode ratio from the
    prefill tier's queue-per-slot (the prompt-length-mix pressure
    signal), shares the action cooldown, and bad configs raise."""
    pol = AutoscalePolicy(AutoscaleConfig(
        hysteresis_ticks=2, cooldown_ticks=0,
        min_prefill_replicas=1, max_prefill_replicas=3))
    psig = {"prefill_replicas": 1, "prefill_slots": 2,
            "prefill_active": 0, "prefill_queued": 4}
    assert pol.decide_prefill(psig) == "hold"
    assert pol.decide_prefill(psig) == "up"
    idle = {"prefill_replicas": 2, "prefill_slots": 4,
            "prefill_active": 0, "prefill_queued": 0}
    assert pol.decide_prefill(idle) == "hold"
    assert pol.decide_prefill(idle) == "down"
    assert pol.state()["decisions"]["prefill_up"] == 1
    # bounds: a 0-max config never touches the prefill tier
    off = AutoscalePolicy(AutoscaleConfig(hysteresis_ticks=1))
    assert off.decide_prefill(psig) == "hold"
    for bad in (dict(min_replicas=0), dict(max_replicas=0),
                dict(min_prefill_replicas=2, max_prefill_replicas=1),
                dict(hysteresis_ticks=0), dict(cooldown_ticks=-1)):
        with pytest.raises(ValueError):
            AutoscaleConfig(**bad)


# -------------------------------------------------- loadgen profiles


def test_profile_arrivals_seeded_and_shaped():
    """Shaped arrival offsets are monotone, reproducible per seed,
    and actually shaped: a ramp's early gaps dwarf its late gaps, a
    step's first half-period packs more arrivals than its second."""
    prof = {"kind": "ramp", "ramp_s": 30.0, "start_frac": 0.05}
    a = profile_arrivals(64, 4.0, prof, seed=3)
    b = profile_arrivals(64, 4.0, prof, seed=3)
    assert np.array_equal(a, b) and a.shape == (64,)
    assert np.all(np.diff(a) >= 0)
    assert not np.array_equal(a, profile_arrivals(64, 4.0, prof,
                                                  seed=4))
    gaps = np.diff(a)
    assert gaps[:16].mean() > 2.0 * gaps[-16:].mean()
    step = {"kind": "step", "period_s": 10.0, "high": 4.0,
            "low": 0.25}
    s = profile_arrivals(200, 2.0, step, seed=0)
    in_burst = ((s % 10.0) < 5.0).mean()
    assert in_burst > 0.7                   # bursts absorb most mass
    # λ(t) itself: sine peaks mid-period, floors at 5% of base
    sine = {"kind": "sine", "period_s": 4.0, "depth": 1.0}
    assert _profile_rate(sine, 2.0, 1.0) == pytest.approx(4.0)
    assert _profile_rate(sine, 2.0, 3.0) == pytest.approx(0.1)
    with pytest.raises(ValueError):
        _profile_rate({"kind": "sawtooth"}, 1.0, 0.0)


def test_loadgen_profile_rows_report_and_guards(llama_tiny, tmp_path):
    """``run_load(qps_profile=...)`` echoes the profile in the report
    and on EVERY NDJSON row; without a profile the rows carry no
    ``qps_profile`` key (byte-identical to the fixed-QPS format); a
    closed loop rejects the knob outright."""
    rng = np.random.RandomState(5)
    eng = ServingEngine(llama_tiny, _scfg())
    with pytest.raises(ValueError):
        run_load(eng, _prompts(rng, lens=(7, 9)), mode="closed",
                 concurrency=2, qps=4.0,
                 qps_profile={"kind": "sine"})
    prof = {"kind": "step", "period_s": 0.4, "high": 3.0, "low": 0.5}
    p1 = tmp_path / "shaped.ndjson"
    rep = run_load(eng, _prompts(rng, lens=(7, 9, 11)), qps=40.0,
                   max_new_tokens=3, qps_profile=prof,
                   record_path=str(p1), seed=1)
    assert rep["qps_profile"] == prof
    rows = [json.loads(ln) for ln in p1.read_text().splitlines()]
    assert len(rows) == 3
    assert all(r["qps_profile"] == prof for r in rows)
    p2 = tmp_path / "fixed.ndjson"
    rep2 = run_load(eng, _prompts(rng, lens=(7, 9)), qps=40.0,
                    max_new_tokens=3, record_path=str(p2), seed=1)
    assert "qps_profile" not in rep2
    assert all("qps_profile" not in json.loads(ln)
               for ln in p2.read_text().splitlines())
    eng.shutdown()


# -------------------------------------- live migration: token-exact


def _drain_mid_decode(cl, rids, max_new):
    """Tick until at least one request has streamed a token but none
    finished, then drain the coldest replica."""
    for _ in range(24):
        cl.step()
        toks = [len(cl._tokens[r]) for r in rids]
        if max(toks) >= 1 and max(toks) < max_new:
            break
    return cl.scale_down()


@pytest.mark.parametrize("variant", ["fp", "int8", "spec", "lora"])
def test_scale_down_drain_token_exact(llama_tiny, variant):
    """THE migration bar: a scale-down drain live-migrates every
    resident session and greedy output stays token-exact vs a
    never-migrated single engine — for fp KV, int8 KV (payload = data
    + per-row scales), n-gram speculation (the drafter corpus rebuilds
    from the migrated history), and a resident LoRA adapter (the pin
    re-acquires on the target)."""
    kw = {"int8": dict(kv_cache_dtype="int8"),
          "spec": dict(num_speculative_tokens=2),
          "lora": dict(lora_rank=4, max_adapters=4)}.get(variant, {})
    rng = np.random.RandomState(13)
    prompts = _prompts(rng)
    max_new = 8
    sub = dict(adapter_id=1) if variant == "lora" else {}

    eng = ServingEngine(llama_tiny, _scfg(**kw))
    if variant == "lora":
        eng.load_adapter(1, _lora_w(101))
    refs = []
    for p in prompts:
        rid = eng.submit(p.copy(), max_new, **sub)
        done = eng.run()
        refs.append(done[rid].tolist())
    eng.shutdown()

    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg(**kw))
    if variant == "lora":
        cl.load_adapter(1, _lora_w(101))
    rids = [cl.submit(p.copy(), max_new, **sub) for p in prompts]
    dropped = _drain_mid_decode(cl, rids, max_new)
    done = cl.run()
    for rid, ref in zip(rids, refs):
        assert done[rid].tolist() == ref, variant
    st = cl.stats()
    assert st["sessions_migrated"] >= 1
    assert st["scale_downs"] == 1 and st["replicas_live"] == 1
    assert dropped in st["removed_replicas"]
    assert st["migration_ms"]["count"] == st["sessions_migrated"]
    # the drained replica's affinity surface is gone
    assert cl.engines[dropped].published_overlap(
        list(_pc.prompt_block_hashes(cl._router._fp, prompts[0],
                                     cl._router._bs))) == 0
    cl.shutdown()


def test_scale_up_under_burst_admits_backlog(llama_tiny):
    """The automatic loop end-to-end: a queue burst trips the policy
    after its hysteresis, the fleet grows to max_replicas, and the
    EXISTING backlog spreads onto the new replica (``shed_queued`` →
    router) — the burst drains through both replicas, every request
    completes in full, and the new replica provably served some."""
    burst = AutoscaleConfig(min_replicas=1, max_replicas=2,
                            up_queue_per_slot=0.5,
                            hysteresis_ticks=2, cooldown_ticks=64)
    cl = EngineCluster(llama_tiny,
                       ClusterConfig(num_replicas=1, autoscale=burst),
                       _scfg())
    rng = np.random.RandomState(3)
    rids = [cl.submit(rng.randint(1, 128, (9,)), 4)
            for _ in range(8)]
    done = cl.run()
    assert set(done) == set(rids)
    assert all(len(done[r]) == 4 for r in rids)
    st = cl.stats()
    assert st["scale_ups"] == 1 and st["replicas_live"] == 2
    assert st["autoscale"]["decisions"]["up"] == 1
    assert st["replicas"][1]["requests_completed"] > 0
    cl.shutdown()


def test_kill_during_migration_fails_target_resumes_elsewhere(
        llama_tiny):
    """Chaos: the COLDEST survivor dies while admitting a migrated
    session. The cluster fails it mid-migration, re-derives the live
    set, and the session seats on the next candidate — still
    token-exact; the poisoned replica lands in failed_replicas."""
    rng = np.random.RandomState(17)
    prompts = _prompts(rng, lens=(11, 19))
    max_new = 8
    eng = ServingEngine(llama_tiny, _scfg())
    refs = [eng.serve([p.copy()], max_new)[0].tolist()
            for p in prompts]
    eng.shutdown()

    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=3),
                       _scfg())
    rids = [cl.submit(p.copy(), max_new) for p in prompts]
    for _ in range(24):
        cl.step()
        if all(len(cl._tokens[r]) >= 1 for r in rids):
            break
    src = cl._owner[rids[0]][0]
    # the empty replica is the coldest: it will be tried first — and
    # it dies on admission
    busy = {cl._owner[r][0] for r in rids}
    (idle,) = set(cl._decode_idx) - busy

    def _boom(rec):
        raise RuntimeError("injected: replica died mid-import")

    cl.engines[idle].admit_migrated = _boom
    cl.scale_down(src)
    st = cl.stats()
    assert idle in st["failed_replicas"]
    assert st["sessions_migrated"] >= 1     # re-seated on survivor
    done = cl.run()
    for rid, ref in zip(rids, refs):
        assert done[rid].tolist() == ref
    cl.shutdown()


def test_migration_payload_loss_degrades_to_recompute(llama_tiny):
    """A migration whose KV payload is lost (the kill-mid-transfer
    shape) degrades to the recompute path: the target re-prefills the
    context and restores the continuation — still token-exact."""
    rng = np.random.RandomState(19)
    prompts = _prompts(rng, lens=(11, 19))
    max_new = 8
    eng = ServingEngine(llama_tiny, _scfg())
    refs = [eng.serve([p.copy()], max_new)[0].tolist()
            for p in prompts]
    eng.shutdown()

    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg())
    rids = [cl.submit(p.copy(), max_new) for p in prompts]
    for _ in range(24):
        cl.step()
        if all(len(cl._tokens[r]) >= 1 for r in rids):
            break
    src = cl._owner[rids[0]][0]
    hot = cl.engines[src]
    orig = hot.export_session

    def _lossy(i):
        rec = orig(i)
        rec.payload = None                  # the bytes died in flight
        return rec

    hot.export_session = _lossy
    cl.scale_down(src)
    done = cl.run()
    for rid, ref in zip(rids, refs):
        assert done[rid].tolist() == ref
    cl.shutdown()


def test_zero_recompiles_across_scale_cycle(llama_tiny):
    """Steady-state elasticity compiles NOTHING: after one full
    drain → migrate → revive cycle (which builds the fixed-width
    export/import pair once), a second identical cycle leaves every
    replica's ``executables_compiled`` exactly where it was."""
    rng = np.random.RandomState(23)
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg())
    cl.serve(_prompts(rng), max_new_tokens=5)           # warm wave

    def _cycle():
        rids = [cl.submit(p.copy(), 8)
                for p in _prompts(rng, lens=(11, 19))]
        for _ in range(24):
            cl.step()
            if all(len(cl._tokens[r]) >= 1 for r in rids):
                break
        idx = cl.scale_down(1)
        cl.run()
        assert cl.scale_up() == idx                     # revived
        return idx

    _cycle()                                # builds the migration pair
    execs0 = [e.stats()["executables_compiled"] for e in cl.engines]
    _cycle()
    execs1 = [e.stats()["executables_compiled"] for e in cl.engines]
    assert execs1 == execs0, (execs0, execs1)
    st = cl.stats()
    assert st["scale_downs"] == 2 and st["scale_ups"] == 2
    assert st["replicas_live"] == 2 and not st["removed_replicas"]
    cl.shutdown()


def test_autoscale_kill_switch_bit_parity(llama_tiny, monkeypatch):
    """PADDLE_TPU_AUTOSCALE=0 beats an explicit (and aggressive)
    policy config: the cluster runs as a fixed-N fleet, never scales,
    and its outputs are bit-identical to one configured without a
    policy."""
    rng = np.random.RandomState(29)
    prompts = _prompts(rng)
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg())
    ref = cl.serve([p.copy() for p in prompts], max_new_tokens=5)
    cl.shutdown()
    monkeypatch.setenv("PADDLE_TPU_AUTOSCALE", "0")
    hair = AutoscaleConfig(min_replicas=1, max_replicas=4,
                           up_queue_per_slot=0.01, down_occupancy=0.9,
                           down_queue_per_slot=0.9,
                           hysteresis_ticks=1, cooldown_ticks=0)
    cl2 = EngineCluster(llama_tiny,
                        ClusterConfig(num_replicas=2, autoscale=hair),
                        _scfg())
    out = cl2.serve([p.copy() for p in prompts], max_new_tokens=5)
    for a, b in zip(out, ref):
        assert a.tolist() == b.tolist()
    st = cl2.stats()
    assert st["autoscale"] is None
    assert st["scale_ups"] == 0 and st["scale_downs"] == 0
    assert st["replicas_live"] == 2
    cl2.shutdown()


# ----------------------------------------- router/affinity hygiene


def test_fail_replica_purges_published_prefixes(llama_tiny):
    """Regression (ISSUE 19 satellite): killing a replica wipes its
    published-prefix surface — ``published_overlap`` scores 0 on the
    corpse — and a session's turn 2 routes to a survivor and
    completes."""
    rng = np.random.RandomState(31)
    turn1 = rng.randint(1, 128, (24,))          # 3 full blocks
    turn2 = np.concatenate([turn1, rng.randint(1, 128, (8,))])
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg())
    r1 = cl.submit(turn1.copy(), 4)
    owner = cl._owner[r1][0]
    cl.run()
    hashes = list(_pc.prompt_block_hashes(cl._router._fp, turn1,
                                         cl._router._bs))
    assert cl.engines[owner].published_overlap(hashes) >= 1
    cl.fail_replica(owner)
    assert cl.engines[owner].published_overlap(hashes) == 0
    r2 = cl.submit(turn2.copy(), 4)
    assert cl._owner[r2][0] != owner
    done = cl.run()
    assert len(done[r2]) == 4
    cl.shutdown()


def test_cancel_in_transit_migration(llama_tiny):
    """A migrated session parked between replicas (every candidate
    says "not right now") is still cancellable: the record drops, the
    request terminates with the tokens already streamed, and the rest
    of the drain completes."""
    rng = np.random.RandomState(37)
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=2),
                       _scfg())
    rids = [cl.submit(p.copy(), 8)
            for p in _prompts(rng, lens=(11, 19))]
    for _ in range(24):
        cl.step()
        if all(len(cl._tokens[r]) >= 1 for r in rids):
            break
    src = cl._owner[rids[0]][0]
    (dst,) = set(cl._decode_idx) - {src}
    surv = cl.engines[dst]
    orig = surv.admit_migrated
    surv.admit_migrated = lambda rec: None      # "no capacity" forever
    cl.scale_down(src)
    st = cl.stats()
    assert st["pending_migrations"] >= 1
    parked = [g for g, _ in cl._pending_mig]
    victim = parked[0]
    assert cl.cancel(victim) is True
    assert victim not in [g for g, _ in cl._pending_mig]
    surv.admit_migrated = orig                  # capacity returns
    done = cl.run()
    assert set(done) == set(rids)
    survivors = [r for r in rids if r != victim]
    assert all(len(done[r]) == 8 for r in survivors)
    assert len(done[victim]) < 8                # streamed-so-far only
    cl.shutdown()


def test_rebalance_sheds_lowest_priority_to_coldest(llama_tiny):
    """Cluster rebalancing: when one replica runs >= 2 sessions
    deeper than the coldest, the hot replica's LOWEST-priority
    session live-migrates over — and both streams stay token-exact."""
    rng = np.random.RandomState(41)
    pa, pb = _prompts(rng, lens=(11, 19))
    max_new = 10
    eng = ServingEngine(llama_tiny, _scfg())
    ref_a = eng.serve([pa.copy()], max_new)[0].tolist()
    ref_b = eng.serve([pb.copy()], max_new)[0].tolist()
    eng.shutdown()

    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=1),
                       _scfg())
    ra = cl.submit(pa.copy(), max_new, priority=5)
    rb = cl.submit(pb.copy(), max_new)          # priority 0: victim
    for _ in range(24):
        cl.step()
        if all(len(cl._tokens[r]) >= 1 for r in (ra, rb)):
            break
    new = cl.scale_up()                         # cold and empty
    assert cl.rebalance() == 1
    assert cl._owner[rb][0] == new              # lowest priority moved
    assert cl._owner[ra][0] == 0                # high-priority stayed
    done = cl.run()
    assert done[ra].tolist() == ref_a
    assert done[rb].tolist() == ref_b
    assert cl.stats()["sessions_migrated"] == 1
    cl.shutdown()


def test_scale_guards_and_stats_surface(llama_tiny):
    """API guards (can't drain the last decode replica, bad indices
    and roles raise) and the always-present elastic stats surface on
    a plain fixed-N cluster."""
    cl = EngineCluster(llama_tiny, ClusterConfig(num_replicas=1),
                       _scfg())
    with pytest.raises(RuntimeError):
        cl.scale_down()
    with pytest.raises(ValueError):
        cl.scale_down(7)
    with pytest.raises(ValueError):
        cl.scale_up(role="gpu")
    with pytest.raises(ValueError):
        cl.scale_up(role="prefill")     # colocated: no prefill tier
    st = cl.stats()
    for k in ("replicas_live", "removed_replicas", "scale_ups",
              "scale_downs", "sessions_migrated",
              "pending_migrations", "migration_ms", "replica_ticks",
              "mean_prompt_len", "autoscale"):
        assert k in st, k
    assert st["replicas_live"] == 1 and st["autoscale"] is None
    assert st["migration_ms"]["count"] == 0
    assert st["removed_replicas"] == []
    cl.step()
    assert cl.stats()["replica_ticks"] == 1
    cl.shutdown()


def test_tier1_no_slow_marker():
    """CI guard (the PR-4/5 pattern): every autoscale test runs in
    the tier-1 ``-m 'not slow'`` sweep, the token-exact drain matrix
    is present, and every cluster/engine tears down through the
    leak-sweeping ``shutdown()``."""
    import tests.conftest as c
    here = open(__file__).read()
    assert "pytest.mark.slow" not in here.replace(
        '"pytest.mark.slow"', "")
    names = [ln.split("(")[0][4:] for ln in here.splitlines()
             if ln.startswith("def test_")]
    overlap = set(names) & set(c._SLOW_TESTS)
    assert not overlap, f"tier-1 autoscale tests marked slow: {overlap}"
    assert "test_scale_down_drain_token_exact" in names
    assert "test_zero_recompiles_across_scale_cycle" in names
    assert here.count(".shutdown()") >= 12, \
        "cluster shutdown (leak sweep) must guard these tests"

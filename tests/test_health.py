"""Fleet health engine (ISSUE 17): detector units, the alert state
machine, incident capture, and the engine/cluster wiring — including
the acceptance pins: PADDLE_TPU_HEALTH=0 bit-for-bit inertness on a
disaggregated cluster, the healthy-steady-state false-positive pin vs
the injected-stall/overload firing pin, and the zero-new-executables
pin for the non-finite-logits probe.
"""
import json
import os
import time
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.inference.serving import ServingConfig, ServingEngine
from paddle_tpu.inference.cluster import ClusterConfig, EngineCluster
from paddle_tpu.monitor.health import (
    ALERT_SEVERITY, BurnRateMonitor, CollapseDetector, EwmaSpikeDetector,
    HealthMonitor, IncidentCapture, RatioDetector, StormDetector,
    TrendDetector)


class _Clock:
    """Deterministic monotonic clock for detector units."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def tick(self, dt):
        self.t += dt
        return self.t


# --------------------------------------------------- detector units


def test_burn_rate_fires_on_sustained_violations():
    clk = _Clock()
    b = BurnRateMonitor(fast_s=5.0, slow_s=60.0, budget=0.01,
                        threshold=2.0, min_requests=4, clock=clk)
    for _ in range(10):
        clk.tick(0.2)
        b.observe(False)            # 100% violations: burn = 100x
    f = b.firing()
    assert f["fast"] and f["slow"]
    r = b.rates()
    assert r["fast"] == pytest.approx(100.0)
    assert r["n_fast"] == 10


def test_burn_rate_blip_does_not_page():
    """One violation in a healthy stream: the slow window stays under
    threshold, so the fast alert (which needs BOTH) cannot fire."""
    clk = _Clock()
    b = BurnRateMonitor(fast_s=5.0, slow_s=60.0, budget=0.1,
                        threshold=2.0, min_requests=4, clock=clk)
    for i in range(100):
        clk.tick(0.5)
        b.observe(i != 99)          # a single trailing violation
    f = b.firing()
    assert not f["fast"] and not f["slow"]
    # the window prunes: events older than slow_s are gone
    assert b.rates()["n_slow"] <= 60.0 / 0.5 + 1


def test_burn_rate_needs_min_requests():
    clk = _Clock()
    b = BurnRateMonitor(fast_s=5.0, slow_s=60.0, budget=0.01,
                        threshold=2.0, min_requests=8, clock=clk)
    for _ in range(3):
        clk.tick(0.1)
        b.observe(False)
    assert not b.firing()["fast"]   # 3 < min_requests


def test_spike_detector_needs_run_and_warmup():
    d = EwmaSpikeDetector(alpha=0.3, k=6.0, min_ratio=4.0,
                          warmup=10, consecutive=3)
    for _ in range(20):
        assert not d.observe(0.01)
    assert not d.observe(1.0)       # run of 1
    assert not d.observe(1.0)       # run of 2
    assert d.observe(1.0)           # run of 3 -> firing
    # spiking samples stay OUT of the baseline (outlier rejection):
    # the alert holds while the stall persists...
    assert d.observe(1.0)
    # ...and clears the moment latency returns to baseline
    assert not d.observe(0.01)


def test_spike_detector_quiet_during_warmup():
    d = EwmaSpikeDetector(warmup=10, consecutive=1)
    assert not d.observe(0.01)
    assert not d.observe(100.0)     # sample 2 < warmup: never fires


def test_trend_detector_monotone_growth_only():
    d = TrendDetector(window=4, min_depth=4, min_growth=3)
    assert not d.observe(1)
    assert not d.observe(2)
    assert not d.observe(3)
    assert d.observe(5)             # full, monotone, +4 >= 3, >= 4
    assert not d.observe(4)         # dipped: not monotone
    for v in (4, 5, 6):
        d.observe(v)
    assert not d.observe(6)         # 6-4=2 < min_growth


def test_storm_detector_windows_and_prunes():
    clk = _Clock()
    d = StormDetector(window_s=10.0, threshold=5, clock=clk)
    assert not d.observe(3)
    clk.tick(1.0)
    assert d.observe(2)             # 5 in window
    clk.tick(20.0)                  # everything pruned
    assert not d.observe(1)


def test_collapse_detector_fires_on_fast_drop():
    d = CollapseDetector(alpha_fast=0.5, alpha_slow=0.02,
                         ratio=0.5, warmup=5)
    for _ in range(30):
        assert not d.observe(4.0)   # steady baseline
    fired = False
    for _ in range(10):
        fired = fired or d.observe(1.0)     # collapse to 1 token/tick
    assert fired
    # a baseline under the 1.0 floor never "collapses"
    d2 = CollapseDetector(warmup=2)
    for _ in range(20):
        assert not d2.observe(0.5)


def test_ratio_detector_thrash():
    clk = _Clock()
    d = RatioDetector(window_s=30.0, ratio=1.0, min_events=4, clock=clk)
    assert not d.observe(2, 5)      # completions dominate
    clk.tick(1.0)
    assert d.observe(4, 0)          # 6 preempts > 5 completions, >= 4
    clk.tick(60.0)
    assert not d.observe(0, 0)      # window drained


# ------------------------------------------ monitor + state machine


def test_monitor_journal_and_fired_total():
    clk = _Clock()
    h = HealthMonitor(burn_min_requests=2, clock=clk)
    assert h.score() == 1.0 and h.firing() == []
    for _ in range(4):
        clk.tick(0.1)
        h.on_request(False)
    clk.tick(0.1)
    h.on_tick(tick_s=0.01, queued=0, step_ema_s=0.01)
    assert "slo_fast_burn" in h.firing()
    assert "slo_slow_burn" in h.firing()
    assert h.fired_total == 2
    # page 0.5 + warn 0.15 in penalties
    assert h.score() == pytest.approx(1.0 - 0.5 - 0.15)
    states = [(e["alert"], e["state"]) for e in h.journal]
    assert ("slo_fast_burn", "firing") in states
    # recovery: met requests flush the windows after they prune
    clk.tick(120.0)
    for _ in range(10):
        clk.tick(0.1)
        h.on_request(True)
    h.on_tick(tick_s=0.01, queued=0, step_ema_s=0.01)
    assert h.firing() == []
    assert h.score() == 1.0
    states = [(e["alert"], e["state"]) for e in h.journal]
    assert ("slo_fast_burn", "ok") in states
    assert h.fired_total == 2       # ok->firing only
    snap = h.snapshot()
    assert snap["alerts"]["slo_fast_burn"]["severity"] == "page"
    assert snap["health_score"] == 1.0


def test_monitor_compile_tick_excluded_from_spike_and_watchdog():
    clk = _Clock()
    h = HealthMonitor(watchdog_mult=2.0, watchdog_floor_s=0.05,
                      clock=clk)
    for _ in range(20):
        clk.tick(0.01)
        h.on_tick(tick_s=0.01, queued=0, step_ema_s=0.01)
    # a 30s compile tick: no spike, no stuck_tick, watchdog clean
    clk.tick(30.0)
    h.on_tick(tick_s=30.0, queued=0, step_ema_s=0.01, compiled=True)
    assert "tick_latency_spike" not in h.firing()
    assert "stuck_tick" not in h.firing()
    assert not h.watchdog_check(step_ema_s=0.01)
    # the same tick NOT flagged as compile blows the deadline
    clk.tick(30.0)
    h.on_tick(tick_s=30.0, queued=0, step_ema_s=0.01)
    assert "stuck_tick" in h.firing()
    assert h.watchdog_check(step_ema_s=0.01)


def test_monitor_cumulative_counters_are_diffed():
    clk = _Clock()
    h = HealthMonitor(recompile_threshold=4, clock=clk)
    # cumulative compiles 0 -> 10 at construction-like first tick
    # counts as 10 fresh compiles; repeating the SAME total adds none
    clk.tick(0.1)
    h.on_tick(tick_s=0.01, queued=0, step_ema_s=0.01, compiles=2)
    assert "recompile_storm" not in h.firing()
    clk.tick(0.1)
    h.on_tick(tick_s=0.01, queued=0, step_ema_s=0.01, compiles=2)
    assert "recompile_storm" not in h.firing()
    clk.tick(0.1)
    h.on_tick(tick_s=0.01, queued=0, step_ema_s=0.01, compiles=6)
    assert "recompile_storm" in h.firing()


def test_monitor_incident_and_profile_hooks_fire_once(tmp_path):
    clk = _Clock()
    calls = []
    inc = IncidentCapture(out_dir=str(tmp_path), min_interval_s=0.0,
                          clock=clk)
    h = HealthMonitor(clock=clk, stats_cb=lambda: {"k": 1},
                      trace_cb=lambda: None,
                      profile_cb=lambda: calls.append(1),
                      incident=inc)
    clk.tick(1.0)
    h.on_tick(tick_s=0.01, queued=0, step_ema_s=0.01, nonfinite=True)
    assert h.firing() == ["nonfinite_logits"]
    assert inc.captured == 1 and calls == [1]
    # still firing next tick: no re-capture (transition-edge only)
    clk.tick(1.0)
    h.on_tick(tick_s=0.01, queued=0, step_ema_s=0.01, nonfinite=True)
    assert inc.captured == 1 and calls == [1]
    bundle = [d for d in os.listdir(tmp_path)
              if d.startswith("incident-")]
    assert len(bundle) == 1
    j = (tmp_path / bundle[0] / "journal.ndjson").read_text()
    rows = [json.loads(x) for x in j.splitlines()]
    assert rows[-1]["alert"] == "nonfinite_logits"
    assert rows[-1]["severity"] == "page"


# ------------------------------------------------- incident capture


def test_incident_capture_rate_limit_and_bound(tmp_path):
    clk = _Clock()
    inc = IncidentCapture(out_dir=str(tmp_path), min_interval_s=10.0,
                          max_incidents=2, clock=clk)
    clk.tick(1.0)
    p1 = inc.maybe_capture("a", "warn", stats_cb=lambda: {"x": 1},
                           journal=[{"alert": "a"}])
    assert p1 is not None and os.path.isdir(p1)
    assert json.load(open(os.path.join(p1, "stats.json")))["x"] == 1
    clk.tick(1.0)                   # rate-limited
    assert inc.maybe_capture("b", "warn") is None
    clk.tick(20.0)
    p2 = inc.maybe_capture("b", "warn")
    clk.tick(20.0)
    p3 = inc.maybe_capture("c", "page")
    assert inc.captured == 3
    left = sorted(d for d in os.listdir(tmp_path)
                  if d.startswith("incident-"))
    assert len(left) == 2           # bounded: oldest pruned
    assert os.path.basename(p2) in left
    assert os.path.basename(p3) in left
    # atomic: no .tmp- staging dirs survive
    assert not any(d.startswith(".tmp-") for d in os.listdir(tmp_path))
    man = json.load(open(os.path.join(p3, "manifest.json")))
    assert man["alert"] == "c" and man["severity"] == "page"


def test_incident_capture_disabled_without_dir(monkeypatch):
    monkeypatch.delenv("PADDLE_TPU_INCIDENT_DIR", raising=False)
    inc = IncidentCapture()
    assert inc.maybe_capture("a", "warn") is None
    assert inc.captured == 0


# ----------------------------------------------------- engine wiring


def _model():
    paddle.seed(0)
    cfg = LlamaConfig(vocab_size=128, hidden_size=64,
                      intermediate_size=128, num_hidden_layers=2,
                      num_attention_heads=4, num_key_value_heads=2,
                      max_position_embeddings=1024)
    return LlamaForCausalLM(cfg)


def _scfg(**kw):
    # generous SLOs by default: first-wave TTFT includes the compile
    # seconds on CPU, which must NOT read as an SLO violation in the
    # healthy arms
    kw.setdefault("num_slots", 4)
    kw.setdefault("max_new_tokens", 6)
    kw.setdefault("max_model_len", 64)
    kw.setdefault("health_slo_ttft_ms", 600000.0)
    kw.setdefault("health_slo_itl_ms", 600000.0)
    return ServingConfig(**kw)


def test_engine_healthy_steady_state_fires_zero_alerts():
    """The false-positive pin: a healthy serve fires NOTHING."""
    eng = ServingEngine(_model(), _scfg())
    rng = np.random.RandomState(0)
    eng.serve([rng.randint(1, 128, (9,)) for _ in range(8)])
    st = eng.stats()
    assert st["health_score"] == 1.0
    assert st["alerts_firing"] == 0
    assert st["alerts_fired_total"] == 0
    assert st["incidents_captured"] == 0
    assert st["nonfinite_logits_ticks"] == 0
    h = eng.health()
    assert h["alerts_firing"] == [] and h["journal"] == []
    assert h["burn_rate"]["fast"] == 0.0    # every request met its SLO
    assert not eng.watchdog_stuck()
    assert eng.shutdown()


def test_engine_health_off_keys_and_none():
    cfg = _scfg()
    cfg.health = False
    eng = ServingEngine(_model(), cfg)
    rng = np.random.RandomState(0)
    eng.serve([rng.randint(1, 128, (9,))])
    st = eng.stats()
    assert st["health_score"] == 1.0 and st["alerts_firing"] == 0
    assert st["alerts_fired_total"] == 0
    assert st["incidents_captured"] == 0
    assert st["nonfinite_logits_ticks"] == 0
    assert eng.health() is None
    assert not eng.watchdog_stuck()
    assert eng.shutdown()


def test_health_kill_switch_bit_for_bit_on_disagg_cluster(
        tmp_path, monkeypatch):
    """The acceptance pin: PADDLE_TPU_HEALTH=0 on a disaggregated
    cluster — tokens AND executables_compiled identical, health() and
    incident capture -> None/absent. Both arms run a TIGHT SLO with
    an incident dir armed, so the OFF arm proves the whole alerting/
    capture path is truly inert, not just idle."""
    model = _model()
    monkeypatch.setenv("PADDLE_TPU_INCIDENT_DIR", str(tmp_path))

    def arm(off):
        if off:
            monkeypatch.setenv("PADDLE_TPU_HEALTH", "0")
        else:
            monkeypatch.delenv("PADDLE_TPU_HEALTH", raising=False)
        cl = EngineCluster(
            model, ClusterConfig(num_replicas=1, prefill_replicas=1),
            _scfg(health_slo_ttft_ms=1e-3, health_slo_itl_ms=1e-3,
                  health_burn_fast_s=0.5, health_burn_slow_s=2.0,
                  health_burn_min_requests=2))
        rng = np.random.RandomState(3)
        rids = [cl.submit(rng.randint(1, 128, (9,)), 6)
                for _ in range(6)]
        done = cl.run()
        st = cl.stats()
        out = ([tuple(done[r].tolist()) for r in rids],
               st["executables_compiled"])
        health = cl.health()
        cl.shutdown()
        return out, st, health

    on, st_on, h_on = arm(off=False)
    bundles_on = {d for d in os.listdir(tmp_path)
                  if d.startswith("incident-")}
    off, st_off, h_off = arm(off=True)
    bundles_off = {d for d in os.listdir(tmp_path)
                   if d.startswith("incident-")} - bundles_on
    assert on == off                # tokens + executables_compiled
    # the ON arm actually exercised the path: the 1 microsecond SLO is
    # unmeetable, the fast-burn alert fired and captured a bundle
    assert st_on["alerts_fired_total"] > 0
    assert "slo_fast_burn" in h_on["alerts_firing"] \
        or st_on["incidents_captured"] > 0
    assert bundles_on
    # the OFF arm is inert: no health object, no alerts, no bundles
    assert h_off is None
    assert st_off["alerts_fired_total"] == 0
    assert st_off["health_score"] == 1.0
    assert not bundles_off


def test_nonfinite_probe_zero_new_executables_and_fires():
    """NaN params poison the logits: the in-executable probe flags
    every tick, the page-severity alert fires, and executables_compiled
    stays at the ragged baseline of 1 — the probe rides the tick
    executable, it never adds one."""
    import jax
    import jax.numpy as jnp
    eng = ServingEngine(_model(), _scfg())
    leaves, treedef = jax.tree_util.tree_flatten(eng._params)
    k = max(range(len(leaves)), key=lambda i: leaves[i].size)
    leaves[k] = jnp.full_like(leaves[k], jnp.nan)
    eng._params = jax.tree_util.tree_unflatten(treedef, leaves)
    rng = np.random.RandomState(0)
    eng.submit(rng.randint(1, 128, (9,)), 4)
    eng.run()
    st = eng.stats()
    assert st["nonfinite_logits_ticks"] > 0
    assert "nonfinite_logits" in eng.health()["alerts_firing"]
    assert ALERT_SEVERITY["nonfinite_logits"] == "page"
    assert st["executables_compiled"] == 1
    eng.shutdown(check_leaks=False)


def test_spec_engine_healthy_and_zero_extra_executables():
    """gamma>0: the probe rides the verify executable (the nf output
    slides before pools in the unpack) — healthy serve, no alerts,
    and the one-executable collapse holds."""
    eng = ServingEngine(_model(), _scfg(num_speculative_tokens=2))
    rng = np.random.RandomState(1)
    outs = eng.serve([rng.randint(1, 128, (9,)) for _ in range(4)])
    st = eng.stats()
    assert all(len(o) == 6 for o in outs)
    assert st["alerts_firing"] == 0 and st["health_score"] == 1.0
    assert st["nonfinite_logits_ticks"] == 0
    assert st["executables_compiled"] == 1
    assert eng.shutdown()


def test_overload_fires_fast_burn_and_captures(tmp_path, monkeypatch):
    """The overload half of the acceptance pin, single-engine form:
    an unmeetable SLO burns the budget at 100x, the fast-burn alert
    fires, and a loadable incident bundle lands on disk."""
    monkeypatch.setenv("PADDLE_TPU_INCIDENT_DIR", str(tmp_path))
    eng = ServingEngine(_model(), _scfg(
        health_slo_ttft_ms=1e-3, health_slo_itl_ms=1e-3,
        health_burn_fast_s=0.5, health_burn_slow_s=2.0,
        health_burn_min_requests=2))
    rng = np.random.RandomState(2)
    eng.serve([rng.randint(1, 128, (9,)) for _ in range(8)])
    st = eng.stats()
    assert st["alerts_fired_total"] > 0
    h = eng.health()
    fired = {e["alert"] for e in h["journal"]}
    assert "slo_fast_burn" in fired
    assert st["incidents_captured"] >= 1
    bundles = [d for d in os.listdir(tmp_path)
               if d.startswith("incident-")]
    assert bundles
    man = json.load(open(tmp_path / bundles[0] / "manifest.json"))
    assert man["alert"] in ALERT_SEVERITY
    full = json.load(open(tmp_path / bundles[0] / "stats.json"))
    assert "roofline" in full and "health_score" in full
    eng.shutdown()


# ---------------------------------------------------- cluster wiring


def test_cluster_watchdog_drains_stuck_replica(tmp_path, monkeypatch):
    """The injected-stall acceptance pin: one replica's ticks are
    artificially wedged past the watchdog deadline — the sweep fails
    it through the existing drain path, its work completes on the
    survivor, and the stuck_tick incident bundle lands on disk."""
    monkeypatch.setenv("PADDLE_TPU_INCIDENT_DIR", str(tmp_path))
    cl = EngineCluster(_model(), ClusterConfig(num_replicas=2),
                       _scfg(num_slots=2, max_new_tokens=4,
                             health_watchdog_floor_s=0.05,
                             health_watchdog_mult=1.0))
    eng1 = cl.engines[1]
    orig = eng1._tick_dispatch
    calls = []

    def slow():
        # wedged from its third tick on: the engine dispatches ahead,
        # so a stall on the host lies inside the launch -> sync time
        # of the tick in flight and would raise the step EMA that the
        # deadline is a multiple of, were every tick stalled from the
        # first
        calls.append(1)
        if len(calls) > 2:
            time.sleep(0.12)        # > deadline, inside the tick's timer
        return orig()

    eng1._tick_dispatch = slow      # both step() and tick_dispatch()
    rng = np.random.RandomState(5)
    rids = [cl.submit(rng.randint(1, 128, (9,)), 4) for _ in range(6)]
    with pytest.warns(UserWarning, match="watchdog"):
        done = cl.run()
    assert set(done) == set(rids)   # survivor served everything
    st = cl.stats()
    assert st["failed_replicas"] == [1]
    assert st["replicas"][1] is None
    rep1 = cl.engines[1].health()
    assert "stuck_tick" in {e["alert"] for e in rep1["journal"]}
    bundles = [d for d in os.listdir(tmp_path)
               if d.startswith("incident-")]
    assert any("stuck_tick" in b for b in bundles)
    # the cluster-level bundle's stats.json is the fleet snapshot and
    # must itself have survived the failed replica (satellite 1)
    for b in bundles:
        p = tmp_path / b / "stats.json"
        if p.exists():
            json.load(open(p))
    cl.shutdown(check_leaks=False)


def test_cluster_stats_tolerates_torn_down_replica():
    """Satellite 1: a replica whose stats() raises mid-snapshot is
    skipped in roll-ups with a failed_replicas annotation instead of
    taking the fleet snapshot down."""
    cl = EngineCluster(_model(), ClusterConfig(num_replicas=2), _scfg())
    rng = np.random.RandomState(7)
    cl.submit(rng.randint(1, 128, (9,)), 4)
    cl.run()
    baseline = cl.stats()
    assert baseline["failed_replicas"] == []
    assert baseline["replicas"][0] is not None

    def boom():
        raise RuntimeError("torn down mid-snapshot")

    cl.engines[1].stats = boom
    st = cl.stats()
    assert st["failed_replicas"] == [1]
    assert st["replicas"][1] is None
    assert st["tokens_total"] == baseline["tokens_total"]
    assert st["roofline"]["busiest_replica"] in (0, None)
    # health roll-up still present
    assert "health_score" in st and "alerts_firing" in st
    cl.shutdown(check_leaks=False)


def test_cluster_health_rolls_up_min_score_and_union():
    cl = EngineCluster(_model(), ClusterConfig(num_replicas=2), _scfg())
    rng = np.random.RandomState(8)
    cl.submit(rng.randint(1, 128, (9,)), 4)
    cl.run()
    h = cl.health()
    assert h["health_score"] == 1.0
    assert h["alerts_firing"] == [] and h["failed_replicas"] == []
    assert len(h["replicas"]) == 2
    # degrade one replica directly through its monitor
    cl.engines[0]._health._set("queue_depth_growth", True, 9.0)
    h = cl.health()
    assert h["health_score"] == pytest.approx(0.85)
    assert h["alerts_firing"] == ["queue_depth_growth"]
    cl.shutdown()


# ------------------------------------------------- config validation


@pytest.mark.parametrize("kw,msg", [
    (dict(health_slo_target=1.5), "health_slo_target"),
    (dict(health_slo_target=0.0), "health_slo_target"),
    (dict(health_burn_fast_s=60.0, health_burn_slow_s=5.0),
     "health_burn_fast_s"),
    (dict(health_watchdog_floor_s=0.0), "health_watchdog_floor_s"),
    (dict(health_watchdog_mult=0.5), "health_watchdog_mult"),
])
def test_config_validation(kw, msg):
    with pytest.raises(ValueError, match=msg):
        ServingConfig(**kw)


# ------------------------------------------------- loadgen satellite


def test_loadgen_records_carry_slo_met(tmp_path):
    from paddle_tpu.inference import loadgen
    eng = ServingEngine(_model(), _scfg())
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, 128, (8,)) for _ in range(5)]
    path = str(tmp_path / "records.ndjson")
    rep = loadgen.run_load(
        eng, prompts, mode="closed", max_new_tokens=4,
        slo=loadgen.SLO(ttft_ms=600000.0, itl_ms=600000.0),
        record_path=path)
    rows = [json.loads(x) for x in open(rep["record_path"])]
    assert len(rows) == 5
    assert all(isinstance(r["slo_met"], bool) for r in rows)
    assert all(r["slo_met"] for r in rows)      # generous SLO: all met
    # offline burn-rate recomputation is possible from the rows alone
    viol = sum(not r["slo_met"] for r in rows) / len(rows)
    assert viol == 0.0
    eng.shutdown()


def test_alert_registry_complete():
    assert len(ALERT_SEVERITY) == 10
    assert set(ALERT_SEVERITY.values()) <= {"page", "warn"}
    assert ALERT_SEVERITY["stuck_tick"] == "page"
    assert ALERT_SEVERITY["slo_slow_burn"] == "warn"

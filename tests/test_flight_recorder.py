"""Fleet flight recorder (ISSUE 15): cross-replica distributed
tracing — merged Chrome/Perfetto trace with one pid per replica,
cluster-global request ids end-to-end, export->import handoff flow
links, preempt/spill/resume marks under the global rid — plus
per-tick roofline attribution (``stats()['roofline']`` on every step
path, ``serving_step_mfu``/``serving_hbm_bw_util`` gauges), bounded
on-demand profiling windows (engine + cluster-forwarded), tracer
ring-drop accounting, the loadgen NDJSON record export, and the
``PADDLE_TPU_TRACE=0`` kill-switch bit-parity + zero-recompile pins."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor import tracing as _tracing
from paddle_tpu.monitor.tracing import ProfilerWindow, Tracer
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


def _prompts(rng, lens):
    return [rng.randint(1, 128, (n,)) for n in lens]


# ------------------------------------------------------------ tracer


def test_tracer_flow_events_chrome_schema():
    """Flow start/finish export as ph "s"/"f" with a shared top-level
    id (the Perfetto arrow contract), the finish binding to its
    enclosing slice (bp="e"); ids from next_flow_id are unique."""
    tr = Tracer("flows")
    with tr.span("exporter", tid=1):
        fid = _tracing.next_flow_id()
        tr.flow("kv handoff", tid=1, flow_id=fid, phase="s",
                args={"rid": 3})
    with tr.span("importer", tid=2):
        tr.flow("kv handoff", tid=2, flow_id=fid, phase="f",
                args={"rid": 3})
    evs = tr.chrome_events()
    s = [e for e in evs if e["ph"] == "s"]
    f = [e for e in evs if e["ph"] == "f"]
    assert len(s) == 1 and len(f) == 1
    assert s[0]["id"] == f[0]["id"] == fid
    assert f[0]["bp"] == "e"
    assert "flow_id" not in (s[0].get("args") or {})  # lifted to id
    assert s[0]["args"]["rid"] == 3
    assert "s" not in s[0] or s[0].get("s") != "t"  # not an instant
    assert _tracing.next_flow_id() > fid
    with pytest.raises(ValueError, match="phase"):
        tr.flow("x", phase="t")
    json.dumps(tr.chrome_trace())


def test_tracer_ring_drop_counter_metric():
    """The ring's silent truncation is now a metric: every overwrite
    bumps the process-wide trace_events_dropped counter AND the
    per-tracer dropped property (the observer observes itself)."""
    c = monitor.counter("trace_events_dropped")
    before = c.value()
    tr = Tracer("droppy", capacity=16)
    for i in range(50):
        tr.emit(f"e{i}")
    assert tr.dropped == 34
    assert c.value() - before == 34


def test_engine_stats_trace_events_dropped(llama_tiny, monkeypatch):
    """An engine whose ring wraps reports the loss in stats()
    (trace_events_dropped > 0); a roomy ring reports 0."""
    monkeypatch.setenv("PADDLE_TPU_TRACE_EVENTS", "32")
    rng = np.random.RandomState(3)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        prefill_chunk=16))
    assert eng.tracer.capacity == 32
    eng.serve(_prompts(rng, (6, 20, 9, 14)), max_new_tokens=6)
    st = eng.stats()
    eng.shutdown()
    assert st["trace_events_dropped"] > 0
    assert st["trace_events"] == 32          # ring stayed bounded


# ---------------------------------------- merged cross-replica trace


def _disagg_cluster(model, rid_offset=0, **scfg):
    cl = EngineCluster(
        model, ClusterConfig(num_replicas=1, prefill_replicas=1),
        ServingConfig(num_slots=2, block_size=8, max_model_len=64,
                      prefill_chunk=16, **scfg))
    # skew the GLOBAL id namespace away from the replicas' local rid
    # counters so the rewrite is observable (locals start at 0 on
    # every engine; equal ids would vacuously "match")
    cl._next_rid += rid_offset
    return cl


def test_merged_disagg_trace_one_pid_per_replica(llama_tiny):
    """ONE merged Chrome trace from a disaggregated run: distinct
    pids per replica (+ the router lane), process names rewritten to
    replica<i>:<role>, router route spans carrying the global rid,
    handoff flow links resolving across pids, and one global
    request's spans visible on BOTH the prefill and decode pids —
    router -> prefill -> handoff -> decode under one rid."""
    rng = np.random.RandomState(5)
    cl = _disagg_cluster(llama_tiny, rid_offset=100)
    rids = [cl.submit(p, 4) for p in _prompts(rng, (6, 12, 9))]
    done = cl.run()
    assert sorted(done) == sorted(rids) and min(rids) >= 100
    doc = cl.export_trace()
    evs = doc["traceEvents"]
    json.dumps(doc)                                  # loadable
    # one pid per replica plus the cluster's own router lane
    procs = {e["pid"]: e["args"]["name"] for e in evs
             if e["ph"] == "M" and e["name"] == "process_name"}
    assert len(procs) == 3
    names = set(procs.values())
    assert "replica0:decode" in names
    assert "replica1:prefill" in names
    assert "EngineCluster" in names
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    cluster_pid = next(p for p, n in procs.items()
                       if n == "EngineCluster")
    prefill_pid = next(p for p, n in procs.items()
                       if n == "replica1:prefill")
    decode_pid = next(p for p, n in procs.items()
                      if n == "replica0:decode")
    # router-decision spans: one per submit, global rid, on the
    # cluster lane
    routes = by_name["route"]
    assert len(routes) == len(rids)
    assert {e["args"]["rid"] for e in routes} == set(rids)
    assert all(e["pid"] == cluster_pid for e in routes)
    assert all(e["args"]["replica"] == 1 for e in routes)  # prefill
    placed = by_name["handoff placed"]
    assert {e["args"]["rid"] for e in placed} == set(rids)
    # handoff flow links: every start has exactly one finish with the
    # SAME id on a DIFFERENT pid (prefill -> decode), rid global
    starts = [e for e in evs if e["ph"] == "s"]
    finishes = {e["id"]: e for e in evs if e["ph"] == "f"}
    assert len(starts) == len(rids)
    for s in starts:
        f = finishes[s["id"]]
        assert s["pid"] == prefill_pid and f["pid"] == decode_pid
        assert s["args"]["rid"] == f["args"]["rid"]
        assert s["args"]["rid"] in rids
    # one request end-to-end: its rewritten req<gid> spans exist on
    # BOTH replica pids, and its per-tick spans carry the global rid
    g = rids[0]
    req_pids = {e["pid"] for e in evs
                if e["name"] == f"req{g}" and e["ph"] == "X"}
    assert req_pids == {prefill_pid, decode_pid}
    chunk = [e for e in by_name["prefill chunk"]
             if e["args"]["rid"] == g]
    assert chunk and all(e["pid"] == prefill_pid for e in chunk)
    dec = [e for e in by_name["decode tick"]
           if e["args"]["rid"] == g]
    assert dec and all(e["pid"] == decode_pid for e in dec)
    # no stale LOCAL ids survived in rid-carrying events of mapped
    # requests: every rid arg on replica pids is in the global range
    for e in evs:
        a = e.get("args") or {}
        if "rid" in a and e["pid"] != cluster_pid \
                and e["name"] != "submit":
            assert a["rid"] >= 100, e
    # cluster roofline headline: BOTH numbers from the ONE busiest
    # replica — never a per-metric max mixing replicas (which could
    # describe a utilization pair no replica exhibits); either
    # replica may win (the prefill tier's chunk rows ride its own
    # ragged tick executable), the invariant is the pairing
    st = cl.stats()
    roof = st["roofline"]
    rep = st["replicas"][roof["busiest_replica"]]["roofline"]
    assert roof["device"] == rep["device"] == "cpu"
    # tier-1 runs on CPU: no device peak, so no utilization
    assert roof["step_mfu"] is rep["step_mfu"] is None
    assert roof["step_hbm_bw_util"] is rep["step_hbm_bw_util"] is None
    cl.shutdown()


def test_rid_history_bounded_and_trace_gated(llama_tiny,
                                             monkeypatch):
    """The (replica, local rid) -> global rid rewrite history is
    FIFO-bounded (a rid older than every ring's reach can never need
    rewriting) and is NOT populated under the trace kill switch — a
    long-lived killed fleet accumulates nothing."""
    rng = np.random.RandomState(31)
    cl = EngineCluster(
        llama_tiny, ClusterConfig(num_replicas=1),
        ServingConfig(num_slots=2, block_size=8, max_model_len=64,
                      prefill_chunk=16))
    cl._hist_cap = 3
    for _ in range(3):
        cl.submit(rng.randint(1, 128, (6,)), 2)
        cl.run()
    for _ in range(2):
        cl.submit(rng.randint(1, 128, (6,)), 2)
        cl.run()
    assert len(cl._l2g_hist) == 3                 # pruned, oldest out
    assert set(cl._l2g_hist.values()) == {2, 3, 4}
    cl.shutdown()
    monkeypatch.setenv("PADDLE_TPU_TRACE", "0")
    cl0 = EngineCluster(
        llama_tiny, ClusterConfig(num_replicas=1),
        ServingConfig(num_slots=2, block_size=8, max_model_len=64,
                      prefill_chunk=16))
    cl0.submit(rng.randint(1, 128, (6,)), 2)
    cl0.run()
    assert cl0._l2g_hist == {}                    # dead weight gated
    cl0.shutdown()


def test_export_trace_writes_perfetto_file(llama_tiny, tmp_path):
    rng = np.random.RandomState(7)
    cl = _disagg_cluster(llama_tiny)
    cl.submit(rng.randint(1, 128, (9,)), 3)
    cl.run()
    p = cl.export_trace(str(tmp_path / "fleet.json"))
    doc = json.load(open(p))
    assert doc["traceEvents"]
    assert doc["displayTimeUnit"] == "ms"
    cl.shutdown()


def test_preempt_spill_resume_spans_share_global_rid(llama_tiny):
    """A preempted request's preempt (spill) and resume marks land on
    its replica's lane with the CLUSTER-global rid after the rewrite
    — the merged timeline shows one request id across its whole
    preempted life (and the stream stays token-exact, pinned
    elsewhere; here we pin the trace schema)."""
    rng = np.random.RandomState(9)
    cl = EngineCluster(
        llama_tiny, ClusterConfig(num_replicas=1),
        ServingConfig(num_slots=2, block_size=8, max_model_len=96,
                      prefill_chunk=16))
    cl._next_rid += 500
    lo = cl.submit(rng.randint(1, 128, (20,)), 8, priority=0)
    for _ in range(3):
        cl.step()
    hi = [cl.submit(p, 6, priority=2)
          for p in _prompts(rng, (12, 9))]
    done = cl.run()
    assert sorted(done) == sorted([lo] + hi)
    st = cl.stats()
    assert st["preemptions"] >= 1
    evs = cl.export_trace()["traceEvents"]
    pre = [e for e in evs if e["name"] == "preempt"]
    res = [e for e in evs if e["name"] in ("resume", "resumed")]
    assert pre and res
    assert all(e["args"]["rid"] == lo for e in pre)
    assert any(e["args"]["rid"] == lo for e in res)
    # same pid (the victim's replica), global id — the spill/resume
    # pair joins against the request's other spans by rid
    assert {e["pid"] for e in pre} == {e["pid"] for e in res
                                       if e["args"]["rid"] == lo}
    cl.shutdown()


def test_trace_kill_switch_cluster_bit_parity(llama_tiny,
                                              monkeypatch):
    """PADDLE_TPU_TRACE=0 keeps the WHOLE recorder inert on a
    disaggregated cluster: identical tokens, identical executable
    counts (zero steady-state recompiles both ways), no tracers, no
    merged trace, profile() a refused no-op, drop accounting zero."""
    rng = np.random.RandomState(11)
    prompts = _prompts(rng, (6, 14, 9))

    def serve():
        cl = _disagg_cluster(llama_tiny)
        rids = [cl.submit(p.copy(), 5) for p in prompts]
        done = cl.run()
        rids2 = [cl.submit(p.copy(), 5) for p in prompts]
        done2 = cl.run()
        st = cl.stats()
        cl.shutdown()
        toks = [done[r].tolist() for r in rids] \
            + [done2[r].tolist() for r in rids2]
        return toks, st, cl

    on, st_on, _ = serve()
    monkeypatch.setenv("PADDLE_TPU_TRACE", "0")
    off, st_off, cl_off = serve()
    assert on == off, "trace kill switch changed served tokens"
    assert st_on["tracing"] is True
    assert st_off["tracing"] is False
    assert st_off["trace_events_dropped"] == 0
    assert st_off["profile_captures"] == 0
    # same executables, second wave compiled nothing, either way
    assert st_off["executables_compiled"] == \
        st_on["executables_compiled"]
    assert cl_off.export_trace() is None
    assert cl_off.profile(2, "/tmp/never") is None
    for rep in st_off["replicas"]:
        assert rep["tracing"] is False
        assert rep["trace_events_dropped"] == 0


# ----------------------------------------------------------- roofline


def test_roofline_stats_ragged_engine(llama_tiny):
    """The default (ragged) engine reports per-executable cost-model
    FLOPs / bytes fused with the measured tick time. Tier-1 runs on
    CPU, which has no device peak: every peak-derived field (MFU,
    HBM-bandwidth utilization, bound, peaks, ridge) is None there —
    never a number against a nominal peak."""
    rng = np.random.RandomState(13)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        prefill_chunk=16))
    roof0 = eng.stats()["roofline"]
    assert roof0["step_mfu"] is None and roof0["per_executable"] == {}
    eng.serve(_prompts(rng, (6, 14, 9)), max_new_tokens=5)
    roof = eng.stats()["roofline"]
    eng.shutdown()
    assert roof["device"] == "cpu"
    assert roof["tick_executable"] == "decode"
    for k in ("step_mfu", "step_hbm_bw_util", "peak_flops_per_s",
              "peak_hbm_bytes_per_s", "ridge_flops_per_byte"):
        assert roof[k] is None, k
    row = roof["per_executable"]["decode"]
    assert row["flops"] > 0 and row["bytes_accessed"] > 0
    assert row["arithmetic_intensity"] == pytest.approx(
        row["flops"] / row["bytes_accessed"], rel=1e-3)
    assert row["ticks"] > 0 and row["step_time_ms"] > 0
    assert row["bound"] is None and row["mfu"] is None \
        and row["hbm_bw_util"] is None


def test_roofline_against_device_peaks(llama_tiny, monkeypatch):
    """With a device peak (the v5e row of monitor.DEVICE_PEAKS, handed
    to the engine as the chip would) the same block carries MFU,
    bandwidth utilization and the bound against the ridge point, and
    the headline gauges track the tick executable."""
    flops, bw, _src = monitor.DEVICE_PEAKS["TPU v5 lite"]
    monkeypatch.setattr(monitor, "device_peaks", lambda: (flops, bw))
    rng = np.random.RandomState(13)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        prefill_chunk=16))
    assert eng.stats()["roofline"]["step_mfu"] == 0.0
    eng.serve(_prompts(rng, (6, 14, 9)), max_new_tokens=5)
    roof = eng.stats()["roofline"]
    eng.shutdown()
    assert roof["peak_flops_per_s"] == flops
    assert roof["step_mfu"] > 0.0
    assert roof["step_hbm_bw_util"] > 0.0
    assert roof["ridge_flops_per_byte"] == pytest.approx(flops / bw)
    row = roof["per_executable"]["decode"]
    assert row["bound"] == ("compute" if row["arithmetic_intensity"]
                            >= roof["ridge_flops_per_byte"]
                            else "bandwidth")
    # like with like: the block rounds its shares to 6 decimals, and at
    # this size the share is a few units of the last one
    want = row["flops"] / (row["step_time_ms"] / 1000.0) / flops
    assert row["mfu"] == pytest.approx(round(want, 6), abs=1e-6)
    assert monitor.gauge("serving_step_mfu").value() > 0.0
    assert monitor.gauge("serving_hbm_bw_util").value() > 0.0


def test_roofline_stats_speculative_engine(llama_tiny):
    """A speculative engine attributes its tick to the ``verify``
    executable: the roofline block follows the tick's name."""
    rng = np.random.RandomState(17)
    phrase = rng.randint(1, 128, (6,))
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        prefill_chunk=16, num_speculative_tokens=2))
    eng.serve([np.tile(phrase, 4), np.tile(phrase, 3)],
              max_new_tokens=5)
    roof = eng.stats()["roofline"]
    eng.shutdown()
    assert roof["tick_executable"] == "verify"
    assert roof["per_executable"]["verify"]["bytes_accessed"] > 0.0
    assert roof["per_executable"]["verify"]["ticks"] > 0


def test_roofline_accounting_compiles_nothing(llama_tiny):
    """The roofline fuses ALREADY-compiled executables' cost analyses
    with host timestamps: two waves stay at one executable, zero
    steady-state recompiles (the whole recorder is host-side)."""
    rng = np.random.RandomState(19)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        prefill_chunk=16))
    eng.serve(_prompts(rng, (6, 9)), max_new_tokens=4)
    st1 = eng.stats()
    eng.serve(_prompts(rng, (7, 11)), max_new_tokens=4)
    st2 = eng.stats()
    eng.shutdown()
    assert st1["executables_compiled"] == 1
    assert st2["executables_compiled"] == 1
    assert st2["roofline"]["per_executable"]["decode"]["ticks"] \
        > st1["roofline"]["per_executable"]["decode"]["ticks"]


# ------------------------------------------------- profiling windows


def test_profiler_window_mechanics(monkeypatch, tmp_path):
    """Window lifecycle with injected hooks: start fires once before
    the first armed tick, stop after the Nth, captures count; arming
    twice raises; no dir raises; PADDLE_TPU_PROFILE_DIR supplies the
    default; the PADDLE_TPU_TRACE=0 kill switch refuses."""
    calls = []
    w = ProfilerWindow(start=lambda d: calls.append(("start", d)),
                       stop=lambda: calls.append(("stop",)))
    with pytest.raises(ValueError, match="dir"):
        w.arm(2)
    assert w.arm(2, str(tmp_path)) == str(tmp_path)
    with pytest.raises(RuntimeError, match="already"):
        w.arm(1, str(tmp_path))
    with pytest.raises(ValueError, match="n_ticks"):
        ProfilerWindow().arm(0, str(tmp_path))
    assert w.pending == 2
    for _ in range(2):
        w.tick_begin()
        w.tick_end()
    assert calls == [("start", str(tmp_path)), ("stop",)]
    assert w.pending == 0 and w.captures == 1
    assert w.last_dir == str(tmp_path)
    w.tick_begin()                      # idle: no-ops
    w.tick_end()
    assert calls == [("start", str(tmp_path)), ("stop",)]
    monkeypatch.setenv("PADDLE_TPU_PROFILE_DIR", str(tmp_path / "e"))
    w2 = ProfilerWindow(start=lambda d: calls.append(("start", d)),
                        stop=lambda: calls.append(("stop",)))
    assert w2.arm(1) == str(tmp_path / "e")     # env default
    # a failing stop disarms but is NOT a completed capture (the
    # captures counter only reports profiles actually written)
    w3 = ProfilerWindow(start=lambda d: None,
                        stop=lambda: (_ for _ in ()).throw(
                            RuntimeError("disk full")))
    w3.arm(1, str(tmp_path))
    w3.tick_begin()
    with pytest.warns(UserWarning, match="stop failed"):
        w3.tick_end()
    assert w3.captures == 0 and w3.pending == 0
    assert w3.last_dir is None
    assert w3.arm(1, str(tmp_path))             # re-armable after
    monkeypatch.setenv("PADDLE_TPU_TRACE", "0")
    assert ProfilerWindow().arm(3, str(tmp_path)) is None


def test_engine_and_cluster_profile_windows(llama_tiny, tmp_path):
    """engine.profile(n) brackets exactly the next n engine ticks;
    EngineCluster.profile(n) brackets n CLUSTER ticks (one process-
    wide capture covering every replica); stats() reports the
    completed captures."""
    rng = np.random.RandomState(23)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        prefill_chunk=16))
    calls = []
    eng._prof = ProfilerWindow(
        start=lambda d: calls.append(("start", d)),
        stop=lambda: calls.append(("stop",)))
    eng.submit(rng.randint(1, 128, (6,)), 6)
    assert eng.profile(2, str(tmp_path / "p")) == str(tmp_path / "p")
    assert eng.stats()["profile_ticks_remaining"] == 2
    eng.step()
    assert calls == [("start", str(tmp_path / "p"))]
    eng.run()
    st = eng.stats()
    eng.shutdown()
    assert calls == [("start", str(tmp_path / "p")), ("stop",)]
    assert st["profile_captures"] == 1
    assert st["profile_ticks_remaining"] == 0

    cl = _disagg_cluster(llama_tiny)
    ccalls = []
    cl._prof = ProfilerWindow(
        start=lambda d: ccalls.append(("start", d)),
        stop=lambda: ccalls.append(("stop",)))
    cl.submit(rng.randint(1, 128, (9,)), 4)
    cl.profile(3, str(tmp_path / "c"))
    cl.run()
    st = cl.stats()
    cl.shutdown()
    assert ccalls == [("start", str(tmp_path / "c")), ("stop",)]
    assert st["profile_captures"] == 1


# ------------------------------------------------- loadgen NDJSON


def test_loadgen_record_export_joins_cluster(llama_tiny, tmp_path):
    """run(record_path=) writes one NDJSON row per request — submit /
    first-token / last-token monotonic timestamps, priority, outcome,
    and the ROUTED replica id (cluster targets) — so offline analysis
    joins load-gen records against the merged trace."""
    from paddle_tpu.inference.loadgen import run_load
    rng = np.random.RandomState(29)
    cl = EngineCluster(
        llama_tiny, ClusterConfig(num_replicas=2),
        ServingConfig(num_slots=2, block_size=8, max_model_len=64,
                      prefill_chunk=16))
    prompts = _prompts(rng, (6, 9, 12, 7))
    path = str(tmp_path / "records.ndjson")
    rep = run_load(cl, prompts, mode="closed", concurrency=2,
                   max_new_tokens=4, priorities=[0, 1, 0, 1],
                   record_path=path)
    cl.shutdown()
    assert rep["record_path"] == path
    rows = [json.loads(ln) for ln in open(path)]
    assert len(rows) == len(prompts)
    assert [r["rid"] for r in rows] == sorted(r["rid"] for r in rows)
    for r in rows:
        assert r["outcome"] == "completed"
        assert r["replica"] in (0, 1)
        assert r["priority"] in (0, 1)
        assert r["submit_t_s"] <= r["first_token_t_s"] \
            <= r["last_token_t_s"]
        assert r["n_tokens"] == 4
        assert r["ttft_ms"] >= 0 and r["e2e_ms"] >= r["ttft_ms"]
    # plain engine target: replica is null (no router in the path)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        prefill_chunk=16))
    path2 = str(tmp_path / "engine.ndjson")
    run_load(eng, prompts[:2], mode="closed", concurrency=2,
             max_new_tokens=3, record_path=path2)
    eng.shutdown()
    rows = [json.loads(ln) for ln in open(path2)]
    assert len(rows) == 2
    assert all(r["replica"] is None for r in rows)


# ------------------------------------------------------------- guard


def test_tier1_no_slow_marker():
    """CI guard (the PR-4/5 pattern): every flight-recorder test runs
    in the tier-1 ``-m 'not slow'`` sweep, the merged-trace schema
    test is present, and engines/clusters tear down through the
    leak-sweeping ``shutdown()``."""
    import tests.conftest as c
    here = open(__file__).read()
    assert "pytest.mark.slow" not in here.replace(
        '"pytest.mark.slow"', "")
    names = [ln.split("(")[0][4:] for ln in here.splitlines()
             if ln.startswith("def test_")]
    overlap = set(names) & set(c._SLOW_TESTS)
    assert not overlap, \
        f"tier-1 flight-recorder tests marked slow: {overlap}"
    assert "test_merged_disagg_trace_one_pid_per_replica" in names
    assert "test_trace_kill_switch_cluster_bit_parity" in names
    assert here.count(".shutdown()") >= 10, \
        "shutdown (leak sweep) must guard these tests"

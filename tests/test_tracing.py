"""Request-lifecycle tracing, streaming SLO digests, and the goodput
harness (ISSUE 11): P² digest accuracy vs numpy, tracer ring-buffer
bounding + Chrome trace-event schema + slot/tid mapping over a mixed
ragged wave, the ``PADDLE_TPU_TRACE=0`` kill switch (bit-for-bit inert,
zero steady-state recompiles, span-free hot path), always-present
``stats()`` latency keys across fp/int8/spec/TP engines, terminal
queue-wait outcomes (no survivor bias), Prometheus exposition, and a
tiny-scale goodput-bench smoke. Tick phases (ISSUE 24): what the host
does inside one ragged tick, as spans that are also profiler
annotations, and tracers that outlive their engine."""
import gc
import json
import os
import subprocess
import sys

import time

import jax
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor import tracing
from paddle_tpu.monitor.digest import LatencyDigest, P2Quantile
from paddle_tpu.monitor.registry import Registry
from paddle_tpu.monitor.tracing import Tracer
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


@pytest.fixture
def llama_tiny():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           kv_heads=2, ffn=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


# ------------------------------------------------------------- P² digest


def test_p2_digest_accuracy_vs_numpy():
    """P² p50/p95/p99 track numpy percentiles on known distributions
    (the documented accuracy bound: a few % of the stream's range)."""
    rng = np.random.RandomState(0)
    for data in (rng.uniform(0.0, 100.0, 4000),
                 rng.exponential(10.0, 4000),
                 rng.normal(50.0, 10.0, 4000)):
        d = LatencyDigest()
        for x in data:
            d.observe(x)
        s = d.summary()
        tol = 0.03 * (data.max() - data.min())
        for q, key in ((50, "p50"), (95, "p95"), (99, "p99")):
            true = float(np.percentile(data, q))
            assert abs(s[key] - true) <= tol, \
                f"{key}: est {s[key]} vs true {true} (tol {tol})"
        assert s["count"] == len(data)
        assert abs(s["mean"] - data.mean()) < 1e-6 * max(
            1.0, abs(data.mean())) + 1e-3
        assert s["min"] == data.min() and s["max"] == data.max()


def test_p2_digest_small_n_exact_and_empty():
    """Below 5 observations the digest IS the sorted sample (linear
    interpolation, numpy's default); empty summaries are fully keyed
    zeros so stats() consumers never KeyError on an idle engine."""
    d = LatencyDigest()
    assert d.summary() == {"count": 0, "mean": 0.0, "min": 0.0,
                           "max": 0.0, "p50": 0.0, "p95": 0.0,
                           "p99": 0.0}
    data = [7.0, 1.0, 5.0]
    for x in data:
        d.observe(x)
    s = d.summary()
    for q, key in ((50, "p50"), (95, "p95"), (99, "p99")):
        np.testing.assert_allclose(s[key], np.percentile(data, q),
                                   rtol=1e-12)
    with pytest.raises(ValueError):
        P2Quantile(1.5)
    with pytest.raises(KeyError):
        d.quantile(0.25)


# ---------------------------------------------------------------- tracer


def test_tracer_ring_buffer_bounds_memory():
    tr = Tracer("ring", capacity=32)
    for i in range(100):
        tr.emit(f"e{i}", tid=0)
    assert len(tr) == 32
    assert tr.dropped == 68
    names = [e["name"] for e in tr.events()]
    assert names[0] == "e68" and names[-1] == "e99"  # oldest dropped
    tr.clear()
    assert len(tr) == 0 and tr.dropped == 0


def test_tracer_env_capacity(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_TRACE_EVENTS", "64")
    assert Tracer("cap").capacity == 64
    assert Tracer("cap1", rows=128).capacity == 64   # the variable rules
    monkeypatch.setenv("PADDLE_TPU_TRACE_EVENTS", "bogus")
    assert Tracer("cap2").capacity == 262144


@pytest.mark.parametrize("rows, capacity", [
    (0, 262144), (8, 262144), (32, 262144), (128, 1048576)])
def test_tracer_capacity_grows_with_its_owner_s_rows(monkeypatch, rows,
                                                     capacity):
    """A serving engine leaves a span a decoding slot a tick: at 128
    slots the default ring held 46 s of a 24 ms tick, less than one
    benchmark window, and every whole-window reader found it wrapped."""
    monkeypatch.delenv("PADDLE_TPU_TRACE_EVENTS", raising=False)
    assert Tracer("rows", rows=rows).capacity == capacity


def test_tracer_chrome_schema_nesting_and_ndjson(tmp_path):
    """Spans nest by time containment, the Chrome export carries the
    required keys (ph/pid/tid/ts/dur in integer us), metadata rows name
    the process and threads, and the NDJSON twin parses per-line."""
    tr = Tracer("schema")
    tr.set_thread(0, "engine")
    with tr.span("outer", tid=0, depth=0):
        with tr.span("inner", tid=0, depth=1):
            tr.instant("mark", tid=0)
    doc = tr.chrome_trace()
    json.dumps(doc)                              # serializable
    evs = doc["traceEvents"]
    meta = [e for e in evs if e["ph"] == "M"]
    assert {"process_name", "thread_name"} <= {m["name"] for m in meta}
    xs = {e["name"]: e for e in evs if e["ph"] == "X"}
    assert set(xs) == {"outer", "inner"}
    for e in xs.values():
        assert isinstance(e["ts"], int) and isinstance(e["dur"], int)
        assert e["pid"] == tr.pid and e["tid"] == 0
    # containment: inner ⊆ outer (the viewer nests by this)
    o, i = xs["outer"], xs["inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"]
    mark = [e for e in evs if e["ph"] == "i"][0]
    assert i["ts"] <= mark["ts"] <= i["ts"] + i["dur"]
    # begin/end explicit API folds extra args in at end()
    tok = tr.begin("late", tid=0, a=1)
    tr.end(tok, b=2)
    assert tr.events()[-1]["args"] == {"a": 1, "b": 2}
    path = tr.dump_ndjson(str(tmp_path / "t.ndjson"))
    recs = [json.loads(line) for line in open(path)]
    assert {r["name"] for r in recs} >= {"outer", "inner", "mark"}
    cpath = tr.dump_chrome_trace(str(tmp_path / "t.json"))
    assert json.load(open(cpath))["traceEvents"]


# ------------------------------------------- engine lifecycle tracing


def _mixed_wave(engine, prompts, max_new):
    """Serve with CONCURRENT admission (requests keep arriving while
    earlier ones decode — the regime where prefill rows interleave
    decode rows in the ragged step)."""
    queue = [np.asarray(p) for p in prompts]
    while queue or engine.num_queued or engine.num_active:
        while queue and engine.num_queued < 2:
            engine.submit(queue.pop(0), max_new)
        if engine.num_queued or engine.num_active:
            engine.step()
    done, engine._done = dict(engine._done), {}
    return done


def test_engine_trace_spans_mixed_ragged_wave(llama_tiny):
    """A mixed wave produces the full span taxonomy — queued spans,
    admit instants (prefix-hit annotated), prefill-chunk + decode-tick
    spans on the owning slot's tid, request spans containing them, and
    engine tick spans with occupancy/fallback args — and the Chrome
    export is loadable with the documented slot/tid mapping."""
    rng = np.random.RandomState(3)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64, prefill_chunk=16))
    prompts = [rng.randint(1, 128, (n,)) for n in (6, 20, 9, 14)]
    _mixed_wave(eng, prompts, 5)
    tr = eng.tracer
    assert tr is not None
    evs = tr.events()
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"].split("[")[0], []).append(e)

    ticks = by_name["tick"]
    assert ticks and all(e["tid"] == 0 for e in ticks)
    for e in ticks:
        assert e["args"]["exec"] == "decode"
        assert 0.0 <= e["args"]["occupancy"] <= 1.0
        assert e["args"]["kernel_fallbacks"] == 0      # CPU fallback=0
        assert e["dur"] > 0
    decodes = by_name["decode tick"]
    assert decodes and all(e["tid"] in (1, 2) for e in decodes)
    assert all(e["args"]["rows"] == 1 for e in decodes)
    chunks = by_name["prefill chunk"]
    assert chunks and all(e["tid"] in (1, 2) for e in chunks)
    # (tid 0 carries a tick PHASE of the same name, a span)
    admits = [e for e in by_name["admit"] if e["ph"] == "i"]
    assert len(admits) == len(prompts)
    assert all("prefix_hit" in e["args"] for e in admits)
    queued = [e for e in evs if e["name"].endswith(" queued")]
    assert len(queued) == len(prompts)
    assert all(e["tid"] == 3 for e in queued)          # queue tid
    assert all(e["args"]["outcome"] == "admitted" for e in queued)
    # spans of one request share an identifier a reader need not parse
    assert all(e["name"] == f"req{e['args']['rid']} queued"
               for e in queued)
    # request spans contain their slot's per-tick spans (same tid,
    # time containment — what Perfetto renders as nesting)
    reqs = {e["name"]: e for e in evs
            if e["name"].startswith("req")
            and not e["name"].endswith("queued")}
    assert len(reqs) == len(prompts)
    for e in decodes + chunks:
        rid = e["args"]["rid"]
        parent = reqs[f"req{rid}"]
        assert parent["tid"] == e["tid"]
        assert parent["t0"] <= e["t0"] + 1e-9
        assert e["t0"] + e["dur"] <= parent["t0"] + parent["dur"] \
            + 1e-9
    # the merged Chrome doc loads and only uses the documented tids
    doc = eng.tracer.chrome_trace()
    json.dumps(doc)
    tids = {e["tid"] for e in doc["traceEvents"] if e["ph"] != "M"}
    assert tids <= {0, 1, 2, 3}
    eng.shutdown()


PHASES = ("admit", "grow", "spill", "pack", "launch", "fetch", "commit")


def _phases(tracer):
    """The tick-phase spans of a tracer, by start."""
    return sorted((e for e in tracer.events()
                   if e["tid"] == 0 and e["name"] in PHASES
                   and e["ph"] == "X"), key=lambda e: e["t0"])


def _record_annotations(monkeypatch):
    """Stand a recorder in for ``jax.profiler.TraceAnnotation``; returns
    the list of names entered."""
    entered = []

    class Recorder:
        def __init__(self, name, **_kw):
            self.name = name

        def __enter__(self):
            entered.append(self.name)
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Recorder)
    return entered


def test_tick_phases_cover_every_ragged_tick(llama_tiny):
    """A mixed ragged wave: every tick has its pack / launch / fetch /
    commit, every admission lies in an ``admit`` phase, every phase
    names its tick, and phases only ever overlap by nesting. A tick's
    fetch and commit run after the NEXT tick's launch (the engine
    dispatches ahead); tick by tick the order is the same."""
    rng = np.random.RandomState(3)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64, prefill_chunk=16))
    prompts = [rng.randint(1, 128, (n,)) for n in (6, 20, 9, 14)]
    _mixed_wave(eng, prompts, 5)
    phases = _phases(eng.tracer)
    ticks = [e for e in eng.tracer.events() if e["name"] == "tick"]
    n_ticks = eng.stats()["decode_steps"]
    assert len(ticks) == n_ticks > 0
    assert [e["args"]["dispatch"] for e in ticks][:2] == \
        ["packed", "carry"]
    assert all(isinstance(e["args"]["tick"], int) for e in phases)
    by_tick = {}
    for e in phases:
        by_tick.setdefault(e["args"]["tick"], []).append(e["name"])
    for n in range(n_ticks):
        for name in ("pack", "launch", "fetch", "commit"):
            assert by_tick[n].count(name) == 1, (n, by_tick[n])
        # one tick's phases, in the order the host runs them
        order = [p for p in by_tick[n] if p in PHASES[3:] or p == "admit"]
        assert order == ["admit", "pack", "launch", "fetch", "commit"]
    admits = [e for e in phases if e["name"] == "admit"]
    assert sum(e["args"]["admitted"] for e in admits) == len(prompts)
    assert all(e["args"]["queued"] >= 0 for e in admits)
    launches = [e for e in phases if e["name"] == "launch"]
    assert [e["args"]["dispatch"] for e in launches] == \
        [e["args"]["dispatch"] for e in ticks]
    # the host's order: tick n+1 is launched before tick n is fetched
    seq = [(e["name"], e["args"]["tick"]) for e in phases
           if e["name"] in ("launch", "fetch")]
    for n in range(n_ticks - 1):
        if launches[n + 1]["args"]["dispatch"] == "carry":
            assert seq.index(("launch", n + 1)) < seq.index(("fetch", n))
    commits = [e for e in phases if e["name"] == "commit"]
    assert sum(e["args"]["tokens"] for e in commits) == 5 * len(prompts)
    assert not any(e["args"]["flush"] for e in commits)
    assert all(e["args"]["rows"] > 0 for e in phases if e["name"] == "pack")
    # one host thread: a phase starts after the one before it ended,
    # unless it is a spill inside an admit or a grow (the launch) or
    # inside a commit (the launched spills taken in)
    for a, b in zip(phases, phases[1:]):
        if b["t0"] < a["t0"] + a["dur"]:
            assert b["name"] == "spill" \
                and a["name"] in ("admit", "grow", "commit")
            assert b["t0"] + b["dur"] <= a["t0"] + a["dur"]
    eng.shutdown()


def test_paged_tick_span_carries_attn_narrow(llama_tiny):
    """Over a paged (not latent) pool every ``tick`` span carries
    ``attn_narrow``: the live units of one-token tiles, which the
    ragged kernel walks narrow — none of a lone prompt chunk's, all of a
    decode-only tick's, and a trickling prompt's row beside a chunk."""
    rng = np.random.RandomState(4)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64, prefill_chunk=16))
    eng.serve([rng.randint(1, 128, (6,))], max_new_tokens=3)
    _mixed_wave(eng, [rng.randint(1, 128, (n,)) for n in (6, 20)], 5)
    ticks = [e["args"] for e in eng.tracer.events() if e["name"] == "tick"]
    eng.shutdown()
    assert ticks and all(0 <= a["attn_narrow"] <= a["attn_live"]
                         for a in ticks)
    assert ticks[0]["attn_narrow"] == 0          # the lone prompt's chunk
    assert ticks[1]["attn_narrow"] == ticks[1]["attn_live"] > 0
    # the wave's first tick: one prompt's chunk, the other's first row
    assert 0 < ticks[3]["attn_narrow"] < ticks[3]["attn_live"]


@pytest.mark.parametrize("tier_bytes, stored", [(64 << 20, True),
                                                (64, False)])
def test_spill_phase_per_evicted_block(llama_tiny, tier_bytes, stored):
    """A tiny pool that fills with published blocks: every eviction is
    one ``spill`` span (the one that carries ``block``) inside an
    ``admit`` or a ``grow``, whether the host tier took the block (the
    counter rises) or refused it (nothing is launched). The launched
    ones are taken in under the same name: a ``spill`` span with
    ``drained`` inside the tick's ``commit``."""
    rng = np.random.RandomState(23)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64, prefill_chunk=16,
        host_kv_tier_bytes=tier_bytes))
    st0 = eng.stats()
    for _ in range(8):      # one at a time: no pressure, no preemption
        eng.serve([rng.randint(1, 128, (30,))], max_new_tokens=4)
    st = eng.stats()
    assert st["preemptions"] == 0
    phases = _phases(eng.tracer)
    spills = [e for e in phases
              if e["name"] == "spill" and "block" in e["args"]]
    drains = [e for e in phases
              if e["name"] == "spill" and "block" not in e["args"]]
    assert spills and all(e["args"]["stored"] is stored for e in spills)
    refused = sum(not e["args"]["stored"] for e in spills)
    assert len(spills) == \
        st["kv_blocks_spilled"] - st0["kv_blocks_spilled"] + refused
    assert all(e["args"]["bytes"] > 0 and e["args"]["block"] > 0
               for e in spills)
    # the block alone crosses to the host, or nothing does
    assert all(e["args"]["copied"] ==
               (e["args"]["bytes"] if stored else 0) for e in spills)
    assert sum(e["args"]["drained"] for e in drains) == \
        len(spills) - refused
    assert st["kv_spill_bytes_copied"] == \
        sum(e["args"]["copied"] for e in spills)
    for e in drains:
        outer = [p for p in phases if p["name"] == "commit"
                 and p["t0"] <= e["t0"]
                 and e["t0"] + e["dur"] <= p["t0"] + p["dur"]]
        assert len(outer) == 1
        assert outer[0]["args"]["tick"] == e["args"]["tick"]
    for e in spills:
        outer = [p for p in phases if p["name"] in ("admit", "grow")
                 and p["t0"] <= e["t0"]
                 and e["t0"] + e["dur"] <= p["t0"] + p["dur"]]
        assert len(outer) == 1
        assert outer[0]["args"]["tick"] == e["args"]["tick"]
    grown = sum(e["args"]["blocks"] for e in phases if e["name"] == "grow")
    assert grown > 0
    eng.shutdown()


def test_ticks_launched_ahead_say_so(llama_tiny):
    """The default engine launches every tick but the first with the
    one before still uncommitted, and both the launch and its tick say
    so (``dispatch="carry"``; ``"packed"`` where nothing was in
    flight); only a commit that drains the pipeline — here for a
    cancel — is marked as a flush."""
    rng = np.random.RandomState(29)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64, prefill_chunk=16))
    eng.serve([rng.randint(1, 128, (n,)) for n in (6, 9)],
              max_new_tokens=12)
    phases = _phases(eng.tracer)
    how = [e["args"]["dispatch"] for e in phases if e["name"] == "launch"]
    assert how[0] == "packed" and set(how[1:]) == {"carry"}
    ticks = {e["args"]["tick"]: e["args"]["dispatch"]
             for e in phases if e["name"] == "launch"}
    assert sorted(ticks) == list(range(eng.stats()["decode_steps"]))
    spans = {e["args"]["tick"] for e in phases if e["name"] == "commit"}
    for e in eng.tracer.events():
        if e["name"] == "tick":
            assert e["args"]["dispatch"] in ("packed", "carry")
    assert spans == set(ticks)
    commits = [e for e in phases if e["name"] == "commit"]
    assert len(commits) == len(how)
    assert not any(e["args"]["flush"] for e in commits)
    rid = eng.submit(rng.randint(1, 128, (7,)), 12)
    for _ in range(3):
        eng.step()
    assert eng.cancel(rid)
    commits = [e for e in _phases(eng.tracer) if e["name"] == "commit"]
    assert [e["args"]["flush"] for e in commits[len(how):]] == \
        [False, False, True]
    eng.shutdown()


def test_phase_annotations_mirror_the_ring(llama_tiny, monkeypatch):
    """What reaches a profiler capture is what the ring holds: the
    same names, prefixed, in the same order."""
    entered = _record_annotations(monkeypatch)
    rng = np.random.RandomState(31)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64, prefill_chunk=16))
    for _ in range(5):
        eng.serve([rng.randint(1, 128, (30,))], max_new_tokens=4)
    names = [e["name"] for e in _phases(eng.tracer)]
    assert "spill" in names and "admit" in names
    assert entered == ["paddle_tpu:" + n for n in names]
    assert tracing.PHASE_PREFIX == "paddle_tpu:"
    eng.shutdown()


def test_tracer_phase_is_a_span_and_an_annotation(monkeypatch):
    entered = _record_annotations(monkeypatch)
    tr = Tracer("phases")
    with tr.phase("launch", tick=3, dispatch="packed"):
        pass
    ph = tr.phase("pack", tick=4).begin()
    ph.end(rows=7)
    a, b = tr.events()
    assert (a["ph"], a["name"], a["tid"]) == ("X", "launch", 0)
    assert a["args"] == {"tick": 3, "dispatch": "packed"}
    assert b["args"] == {"tick": 4, "rows": 7} and b["t0"] >= a["t0"]
    assert entered == ["paddle_tpu:launch", "paddle_tpu:pack"]


def test_retired_tracers_outlive_their_engines(llama_tiny):
    """``shutdown()`` hands the tracer over: the process-wide dump still
    sees an engine that was deleted and collected — the last 4 of them."""
    pids = []
    for _ in range(5):
        eng = ServingEngine(llama_tiny, ServingConfig(
            num_slots=2, block_size=8, max_model_len=64,
            prefill_chunk=16))
        eng.serve([np.arange(1, 7)], max_new_tokens=2)
        pids.append(eng.tracer.pid)
        eng.shutdown()
        eng.shutdown()          # handing over twice keeps one reference
        del eng
        gc.collect()
    live = {t.pid: t for t in tracing.live_tracers()}
    assert set(pids[1:]) <= set(live) and pids[0] not in live
    assert any(e["name"] == "tick" for e in live[pids[-1]].events())


def test_tick_phase_recording_stays_under_its_budget():
    """The recording calls of one tick (eight phases, their args) cost
    well under 100 microseconds of host time: the best of 5 rounds of
    200 ticks, so that a loaded machine does not decide it."""
    tr = Tracer("cost", capacity=4096)
    best = float("inf")
    for _ in range(5):
        t0 = time.perf_counter()
        for n in range(200):
            ph = tr.phase("admit", tick=n).begin()
            sp = tr.phase("spill", tick=n, block=5).begin()
            sp.end(bytes=4096, stored=True)
            ph.end(admitted=1, queued=3)
            ph = tr.phase("grow", tick=n).begin()
            ph.end(blocks=1)
            ph = tr.phase("pack", tick=n).begin()
            ph.end(rows=136)
            with tr.phase("launch", tick=n, dispatch="packed"):
                pass
            ph = tr.phase("fetch", tick=n).begin()
            ph.end()
            ph = tr.phase("commit", tick=n, flush=False).begin()
            ph.end(tokens=8)
        best = min(best, (time.perf_counter() - t0) / 200)
    assert best < 100e-6, f"{best * 1e6:.1f} us a tick"


def test_engine_trace_spec_accepted_len(llama_tiny):
    """Speculative wave: verify-tick spans carry rows=gamma+1 and the
    per-window accepted_len the commit actually emitted."""
    rng = np.random.RandomState(5)
    phrase = rng.randint(1, 128, (6,))
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64, prefill_chunk=16,
        num_speculative_tokens=2))
    eng.serve([np.tile(phrase, 4), np.tile(phrase, 3)],
              max_new_tokens=6)
    verifies = [e for e in eng.tracer.events()
                if e["name"] == "verify tick"]
    assert verifies
    for e in verifies:
        assert e["args"]["rows"] == 3
        assert 1 <= e["args"]["accepted_len"] <= 3
    ticks = [e for e in eng.tracer.events() if e["name"] == "tick"]
    assert all(e["args"]["exec"] == "verify" for e in ticks)
    eng.shutdown()


def test_trace_kill_switch_bit_for_bit_inert(llama_tiny, monkeypatch):
    """PADDLE_TPU_TRACE=0 leaves the hot path span-free (no tracer on
    the engine at all) with IDENTICAL tokens, executable counts, and
    zero steady-state recompiles — and the always-on digests still
    run."""
    rng = np.random.RandomState(11)
    prompts = [rng.randint(1, 128, (n,)) for n in (6, 14, 9)]
    entered = _record_annotations(monkeypatch)

    def serve():
        eng = ServingEngine(llama_tiny, ServingConfig(
            num_slots=2, block_size=8, max_model_len=64,
            prefill_chunk=16))
        outs = eng.serve([p.copy() for p in prompts], max_new_tokens=5)
        st1 = eng.stats()
        eng.serve([p.copy() for p in prompts], max_new_tokens=5)
        st2 = eng.stats()
        eng.shutdown()
        return [o.tolist() for o in outs], st1, st2

    on, st_on, _ = serve()
    assert entered
    del entered[:]
    monkeypatch.setenv("PADDLE_TPU_TRACE", "0")
    off, st_off1, st_off2 = serve()
    assert on == off, "trace kill switch changed served tokens"
    assert entered == [], "a killed engine entered a profiler annotation"
    assert st_off1["tracing"] is False
    assert st_off1["trace_events"] == 0
    assert st_on["tracing"] is True and st_on["trace_events"] > 0
    assert st_off1["executables_compiled"] == \
        st_on["executables_compiled"] == 1
    # steady state: the second wave recompiled nothing
    assert st_off2["executables_compiled"] == 1
    assert st_off2["decode_compiles"] == st_off1["decode_compiles"]
    # digests are independent of the trace switch
    assert st_off2["ttft_ms"]["count"] == 2 * len(prompts)


def test_stats_latency_keys_always_present_across_variants(llama_tiny):
    """fp / int8 / speculative / TP engines all report the four P²
    latency summaries with the full key set — before AND after
    traffic."""
    import jax
    rng = np.random.RandomState(13)
    prompts = [rng.randint(1, 128, (n,)) for n in (6, 11)]
    keys = ("ttft_ms", "itl_ms", "queue_wait_ms", "e2e_ms")
    subkeys = {"count", "mean", "min", "max", "p50", "p95", "p99"}
    variants = [{}, {"kv_cache_dtype": "int8"},
                {"num_speculative_tokens": 2}]
    if len(jax.devices()) >= 2:
        variants.append({"tp_degree": 2})
    for kw in variants:
        eng = ServingEngine(llama_tiny, ServingConfig(
            num_slots=2, block_size=8, max_model_len=64,
            prefill_chunk=16, **kw))
        st0 = eng.stats()
        for k in keys:
            assert set(st0[k]) == subkeys, (kw, k)
            assert st0[k]["count"] == 0
        eng.serve([p.copy() for p in prompts], max_new_tokens=4)
        st = eng.stats()
        eng.shutdown()
        assert st["ttft_ms"]["count"] == len(prompts), kw
        assert st["e2e_ms"]["count"] == len(prompts), kw
        assert st["itl_ms"]["count"] > 0, kw
        assert st["queue_wait_ms"]["count"] == len(prompts), kw
        for k in keys:
            s = st[k]
            assert s["min"] - 1e-9 <= s["p50"] <= s["max"] + 1e-9, \
                (kw, k, s)
            assert s["p99"] <= s["max"] + 1e-9, (kw, k, s)


def test_ttft_digest_matches_client_side_view(llama_tiny):
    """The engine's TTFT digest must agree with what a streaming
    client measures (both clock the same _emit moment, so the gap is
    digest error + callback overhead only)."""
    import time
    rng = np.random.RandomState(17)
    submit_t, first_t = {}, {}

    def cb(rid, tok):
        first_t.setdefault(rid, time.monotonic())

    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        prefill_chunk=16), stream_callback=cb)
    # warm first so compile time doesn't dominate the distribution
    eng.serve([rng.randint(1, 128, (8,))], max_new_tokens=2)
    first_t.clear()
    for n in (6, 9, 12, 7, 10, 8):
        rid = eng.submit(rng.randint(1, 128, (n,)), 4)
        submit_t[rid] = time.monotonic()
    d0 = eng.stats()["ttft_ms"]["count"]
    eng.run()
    st = eng.stats()
    eng.shutdown()
    client = np.asarray(sorted(
        1000.0 * (first_t[r] - submit_t[r]) for r in submit_t))
    assert st["ttft_ms"]["count"] - d0 == len(client)
    # engine p50 over the whole digest includes the warmup request;
    # compare against the client median loosely (digest error bound)
    eng_p50 = st["ttft_ms"]["p50"]
    cli_p50 = float(np.median(client))
    assert abs(eng_p50 - cli_p50) <= max(0.5 * cli_p50, 10.0), \
        (eng_p50, cli_p50)


def test_queue_wait_terminal_outcomes_no_survivor_bias(llama_tiny):
    """Every queue exit path leaves a labeled observation: admitted,
    cancelled (new cancel() API), rejected (submit validation), and
    shutdown (still queued at teardown) — and the engine-local digest
    counts them all."""
    h = monitor.histogram("serving_queue_wait_ms", labels=("outcome",))

    def count(outcome):
        return h.labels(outcome=outcome).value()["count"]

    before = {oc: count(oc) for oc in
              ("admitted", "cancelled", "rejected", "shutdown")}
    rng = np.random.RandomState(19)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=1, block_size=8, max_model_len=64,
        prefill_chunk=16))
    r1 = eng.submit(rng.randint(1, 128, (6,)), 3)
    r2 = eng.submit(rng.randint(1, 128, (7,)), 3)
    r3 = eng.submit(rng.randint(1, 128, (8,)), 3)
    assert eng.cancel(r3) is True          # still queued -> removed
    assert eng.cancel(r3) is False         # already gone
    with pytest.raises(ValueError, match="empty"):
        eng.submit([])                     # rejected
    eng.step()                             # admits r1 (1 slot)
    # admitted requests ARE cancellable since the preemptive-scheduler
    # round (slot retired mid-decode, blocks freed, partial result) —
    # their queue-wait was already observed as "admitted"
    assert eng.cancel(r1) is True
    assert eng.cancel(r1) is False         # already gone
    eng.shutdown()                         # r2 still queued
    assert count("admitted") - before["admitted"] == 1
    assert count("cancelled") - before["cancelled"] == 1
    assert count("rejected") - before["rejected"] == 1
    assert count("shutdown") - before["shutdown"] == 1
    st = eng.stats()
    assert st["queue_wait_ms"]["count"] == 4
    assert st["requests_cancelled"] == 1   # the in-flight cancel
    assert r2 not in eng._submit_t         # no leaked bookkeeping
    assert r1 not in eng._submit_t


# ------------------------------------------------------------ goodput


def test_goodput_loadgen_smoke(llama_tiny):
    """Open- and closed-loop harness at tiny scale: every request
    completes, the report carries the SLO/goodput keys, and an
    impossible SLO yields goodput 0 (the metric actually gates)."""
    from paddle_tpu.inference.loadgen import (SLO, poisson_arrivals,
                                              run_load,
                                              uniform_arrivals)
    rng = np.random.RandomState(23)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        prefill_chunk=16))
    eng.serve([rng.randint(1, 128, (8,))], max_new_tokens=2)  # warm
    prompts = [rng.randint(1, 128, (6 + (i % 3) * 4,))
               for i in range(6)]
    rep = run_load(eng, prompts, qps=200.0, mode="open",
                   max_new_tokens=4, slo=SLO(ttft_ms=1e5, itl_ms=1e5))
    assert rep["completed"] == rep["requests"] == len(prompts)
    assert rep["goodput"] == 1.0
    assert rep["offered_qps"] == 200.0
    for k in ("ttft_p50_ms", "ttft_p99_ms", "itl_p50_ms",
              "itl_p99_ms", "tpot_p99_ms", "e2e_p99_ms",
              "achieved_qps", "tokens_per_sec", "wall_s"):
        assert k in rep and rep[k] >= 0
    # an impossible SLO scores zero — goodput is a real gate
    rep0 = run_load(eng, prompts, mode="closed", concurrency=2,
                    max_new_tokens=4,
                    slo=SLO(ttft_ms=1e-6, itl_ms=1e-6))
    assert rep0["completed"] == len(prompts) and rep0["goodput"] == 0.0
    eng.shutdown()
    # arrival schedules: monotone, at the requested mean rate
    arr = poisson_arrivals(500, qps=10.0, seed=0)
    assert np.all(np.diff(arr) > 0)
    assert abs(arr[-1] - 50.0) < 15.0      # ~n/qps
    uni = uniform_arrivals(10, qps=5.0)
    np.testing.assert_allclose(np.diff(uni), 0.2)
    with pytest.raises(ValueError, match="qps"):
        run_load(eng, prompts, mode="open")
    with pytest.raises(ValueError, match="mode"):
        run_load(eng, prompts, mode="sideways")


# --------------------------------------------------------- prometheus


def test_prometheus_text_format_and_mangling():
    """Counter/gauge/histogram/info render in the exposition format:
    cumulative le buckets, _sum/_count, label escaping, and the
    documented name-mangling (bad chars -> _, leading digit
    prefixed)."""
    reg = Registry()
    reg.counter("hits.total", "requests", labels=("fn",)) \
        .labels(fn='a"b').inc(2)
    reg.gauge("9depth", "queue depth").set(1.5)
    h = reg.histogram("lat_ms", "latency", buckets=(1.0, 5.0))
    h.observe(0.5)
    h.observe(7.0)
    reg.info("kern", "last kernel").set({"name": "megablox"})
    text = reg.prometheus_text()
    assert "# TYPE hits_total counter" in text
    assert 'hits_total{fn="a\\"b"} 2' in text
    assert "# TYPE _9depth gauge" in text and "_9depth 1.5" in text
    assert "# TYPE lat_ms histogram" in text
    assert 'lat_ms_bucket{le="1"} 1' in text
    assert 'lat_ms_bucket{le="5"} 1' in text       # cumulative
    assert 'lat_ms_bucket{le="+Inf"} 2' in text
    assert "lat_ms_sum 7.5" in text and "lat_ms_count 2" in text
    assert "# TYPE kern_info gauge" in text
    assert "megablox" in text
    # every line is a comment or `name{labels} value`
    for line in text.strip().splitlines():
        assert line.startswith("#") or len(line.rsplit(" ", 1)) == 2


def test_prometheus_atexit_twin(tmp_path):
    """PADDLE_TPU_METRICS_PROM=<path> writes the text exposition at
    interpreter exit, next to the JSONL export (both from one fresh
    process)."""
    prom = tmp_path / "m.prom"
    env = dict(os.environ,
               PADDLE_TPU_METRICS_PROM=str(prom),
               PADDLE_TPU_METRICS_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    code = ("from paddle_tpu import monitor; "
            "monitor.counter('prom_exit_probe', 'x', labels=('k',))"
            ".labels(k='v').inc(3)")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))), timeout=240)
    text = prom.read_text()
    assert 'prom_exit_probe{k="v"} 3' in text
    assert "# TYPE prom_exit_probe counter" in text
    jsonls = [f for f in os.listdir(tmp_path) if f.endswith(".jsonl")]
    assert jsonls, "JSONL twin missing"


def test_prometheus_dump_of_live_registry(tmp_path, llama_tiny):
    """monitor.prometheus_dump() renders the REAL process registry —
    serving histograms come out as cumulative bucket series."""
    rng = np.random.RandomState(29)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=2, block_size=8, max_model_len=64,
        prefill_chunk=16))
    eng.serve([rng.randint(1, 128, (6,))], max_new_tokens=3)
    eng.shutdown()
    path = monitor.prometheus_dump(str(tmp_path / "live.prom"))
    text = open(path).read()
    assert "# TYPE serving_queue_wait_ms histogram" in text
    assert 'serving_queue_wait_ms_bucket{outcome="admitted",le="+Inf"}' \
        in text
    assert "serving_ttft_ms" in text
    assert monitor.prometheus_dump(None) is None  # env unset -> no-op

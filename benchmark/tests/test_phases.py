"""The readers of the program's tick-phase spans: self time, the split of
the device's idle time by phase, and what they give where there is
nothing to read. Hand-built spans and device ops, then the tiny cells."""
import json
import os

import pytest

from benchmark.lib import phases, readers, xplane
from conftest import ROOT, run_cell, _write

HOST = ["admit", "grow", "pack", "commit"]
WAIT = ["launch", "fetch"]
NEW_SAT = {"spill_share.sat", "host_share.sat", "pipelined_tick_share.sat",
           "idle_in_spill.sat", "idle_in_host.sat", "idle_in_wait.sat"}
NEW_RATE = {"spill_share.rate", "idle_in_spill.rate"}


class FakeTracer:
    def __init__(self, events, dropped=0):
        self._events, self.dropped = events, dropped

    def events(self):
        return list(self._events)


def ev(name, t0, t1, tid=0, **args):
    return {"ph": "X", "name": name, "tid": tid, "t0": t0, "dur": t1 - t0,
            "args": dict(args) or None}


def one_tick(at, tick, dispatch="packed"):
    """admit 0-30 with a spill 10-20 inside, grow 30-34, pack 34-40,
    launch 40-42, fetch 42-90, commit 90-100 (milliseconds after at)."""
    ms = lambda a, b, name, **kw: ev(name, at + a / 1e3, at + b / 1e3,  # noqa
                                     tick=tick, **kw)
    # the ring holds spans in the order they ended: the spill first
    return [ms(10, 20, "spill", block=3, bytes=4096, stored=True),
            ms(0, 30, "admit", admitted=1, queued=2),
            ms(30, 34, "grow", blocks=1), ms(34, 40, "pack", rows=9),
            ms(40, 42, "launch", dispatch=dispatch), ms(42, 90, "fetch"),
            ms(90, 100, "commit", tokens=8, flush=False)]


def test_flatten_keeps_self_time():
    segs = phases.flatten([(0.0, 30.0, "admit"), (10.0, 20.0, "spill"),
                           (30.0, 34.0, "grow"), (40.0, 50.0, "fetch")])
    assert segs == [(0.0, 10.0, "admit"), (10.0, 20.0, "spill"),
                    (20.0, 30.0, "admit"), (30.0, 34.0, "grow"),
                    (40.0, 50.0, "fetch")]
    took = phases.seconds_by_phase(segs, 5.0, 45.0)
    assert took == {"admit": 15.0, "spill": 10.0, "grow": 4.0, "fetch": 5.0}


def test_shares_cut_spans_at_the_windows_edges():
    events = one_tick(100.0, 0) + one_tick(100.1, 1, "carry") + [
        ev("tick", 100.0, 100.1, exec="decode"),
        ev("req7 queued", 99.0, 100.005, tid=9, rid=7, outcome="admitted"),
        ev("req8 queued", 99.0, 100.105, tid=9, rid=8, outcome="cancelled"),
        ev("req9 queued", 100.0, 100.135, tid=9, rid=9, outcome="admitted")]
    # the window: the second half of tick 0 and the first half of tick 1
    run = readers.Run(t_open=100.05, t_close=100.15, phase_events=events)
    assert phases.phase_share(run, ["spill"]) == pytest.approx(10.0)
    # tick 0: commit 10 ms; tick 1: admit 20 of 30, grow 4, pack 6
    assert phases.phase_share(run, HOST) == pytest.approx(40.0)
    assert phases.carry_share(run) == pytest.approx(100.0)
    assert phases.queue_wait_mean_ms(run) == pytest.approx(135.0)
    whole = readers.Run(t_open=100.0, t_close=100.2, phase_events=events)
    assert phases.carry_share(whole) == pytest.approx(50.0)
    assert phases.queue_wait_mean_ms(whole) == pytest.approx(
        (1005.0 + 135.0) / 2)


def trace_of(ops, t0, t0_ns, t1_ns):
    tr = readers.Trace.__new__(readers.Trace)
    tr.ops, tr.spans = {"/device:TPU:0": ops}, []
    tr.t0, tr.t0_ns, tr.t1_ns = t0, t0_ns, t1_ns
    tr.t1 = t0 + (t1_ns - t0_ns) / 1e9
    return tr


def op(start_ms, end_ms, base_ns):
    return xplane.Event("/device:TPU:0", "XLA Ops", "%fusion = f32[]",
                        base_ns + start_ms * 1e6,
                        (end_ms - start_ms) * 1e6, "")


def test_idle_is_charged_to_the_innermost_phase_and_sums_to_idle_share():
    base = 5e12         # the trace's clock has another origin
    # the device works 41-88 ms of tick 0 (launched at 40, fetched at 90)
    # and 141-188 ms of tick 1; the trace ends 10 ms after tick 1
    ops = [op(41, 60, base), op(60, 88, base), op(141, 188, base)]
    run = readers.Run(t_open=99.0, t_close=101.0,
                      phase_events=one_tick(100.0, 0) + one_tick(100.1, 1),
                      trace=trace_of(ops, 100.0, base, base + 210e6))
    spill = phases.idle_share_in(run, ["spill"])
    host = phases.idle_share_in(run, HOST, unphased=True)
    wait = phases.idle_share_in(run, WAIT)
    # per tick: spill 10; admit 20 + grow 4 + pack 6 + commit 10 = 40;
    # launch 1 + fetch 2 = 3; and the 10 ms after the last tick: no phase
    assert spill == pytest.approx(100 * 20 / 210)
    assert host == pytest.approx(100 * (80 + 10) / 210)
    assert wait == pytest.approx(100 * 6 / 210)
    assert spill + host + wait == pytest.approx(
        readers.device_idle_share(run))
    assert phases.idle_share_in(run, HOST) == pytest.approx(100 * 80 / 210)
    # an untraced run has no idle time to split
    assert phases.idle_share_in(readers.Run(
        t_open=99.0, t_close=101.0, phase_events=run.phase_events),
        ["spill"]) is None


def test_no_tracer_or_a_wrapped_ring_gives_nothing():
    events = one_tick(100.0, 0)
    find = lambda *tracers: phases.find_events(     # noqa: E731
        99.0, 101.0, live_tracers=lambda: list(tracers))
    assert find() is None
    assert find(FakeTracer(events)) == events
    # another engine's tracer, whose phases lie outside the interval
    assert find(FakeTracer(one_tick(50.0, 0)), FakeTracer(events)) == events
    assert find(FakeTracer(events), FakeTracer(events)) is None
    # spans that are no phases: a program from before the phases
    assert find(FakeTracer([ev("tick", 100.0, 100.1, exec="decode")])) is None
    # a ring that overwrote events since the interval began
    assert find(FakeTracer(events, dropped=3)) is None
    old = [ev("tick", 90.0, 90.1, exec="decode")]
    assert find(FakeTracer(old + events, dropped=3)) == old + events
    for reader, args in ((phases.phase_share, {"phases": ["spill"]}),
                         (phases.carry_share, {}),
                         (phases.idle_share_in, {"phases": ["spill"]}),
                         (phases.queue_wait_mean_ms, {})):
        assert reader(readers.Run(t_open=99.0, t_close=101.0,
                                  phase_events=None), **args) is None


def list_new_metrics(tree, cell, names):
    """The cell files are not this PR's to edit: the new metric files
    list the real cells. The tiny cell lists them itself."""
    from benchmark import run
    base = run.read_json("workloads", cell + ".json")
    _write(tree, f"workloads/{cell}.json",
           dict(base, metrics=base["metrics"] + sorted(names)))


def test_traced_tiny_cells_print_the_new_metrics(tiny_tree, capsys):
    list_new_metrics(tiny_tree, "tiny-sat", NEW_SAT)
    res, _logs, _err = run_cell(capsys, "tiny-sat", seconds=2.0, trace=1)
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert NEW_SAT <= set(got)
    assert all(res["metrics"][k]["unit"] == "%" for k in NEW_SAT)
    assert got["idle_in_spill.sat"] + got["idle_in_host.sat"] \
        + got["idle_in_wait.sat"] == pytest.approx(
            got["device_idle_share.sat"], abs=1e-6)
    assert 0 < got["host_share.sat"] < 100 and got["idle_in_wait.sat"] > 0
    # the phases lie inside engine.step(): less than its share of the window
    assert got["host_share.sat"] + got["spill_share.sat"] < 100
    assert got["pipelined_tick_share.sat"] == 0     # async_depth is unset
    list_new_metrics(tiny_tree, "tiny-rate", NEW_RATE)
    res, _logs, _err = run_cell(capsys, "tiny-rate", seconds=2.0, trace=1)
    assert NEW_RATE <= set(res["metrics"])
    assert res["metrics"]["spill_share.rate"]["value"] >= 0


def test_observed_queue_wait_and_the_kill_switch(tiny_tree, capsys,
                                                 monkeypatch):
    from benchmark import run
    path = os.path.join(tiny_tree, "metrics", "queue_wait_mean_ms.rate.json")
    _write(tiny_tree, "metrics/queue_wait_mean_ms.rate.json",
           dict(json.load(open(path)), workloads=["tiny-rate"]))
    list_new_metrics(tiny_tree, "tiny-rate", NEW_RATE)
    res, _logs, _err = run_cell(capsys, "tiny-rate", seconds=2.0, trace=1)
    assert res["observed"]["queue_wait_mean_ms.rate"]["value"] > 0
    assert res["observed"]["queue_wait_mean_ms.rate"]["unit"] == "ms"
    before = set(res["metrics"])
    # the program's tracer switched off: the run still ends with a result
    # line, the metrics that read its spans are left out, nothing else
    monkeypatch.setenv("PADDLE_TPU_TRACE", "0")
    res, _logs, _err = run_cell(capsys, "tiny-rate", seconds=2.0, trace=1)
    assert res["correct"] is True
    assert set(res["metrics"]) == before - NEW_RATE
    assert res["observed"]["queue_wait_mean_ms.rate"]["value"] is None


def test_new_metric_files_match_benchmark_json():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    listed = {m["name"]: m for m in spec["per_layer"]}
    assert NEW_SAT | NEW_RATE <= set(listed)
    # appended after the entries that were there, in name order
    tail = [m["name"] for m in spec["per_layer"]][-8:]
    assert tail == sorted(NEW_SAT | NEW_RATE)
    from benchmark.lib import harness
    for name in NEW_SAT | NEW_RATE:
        f = harness.read_json("metrics", name + ".json")
        assert f["workloads"] == listed[name]["workloads"]
        assert callable(harness.find_function(f["reader"]))
        assert f["source"] == ("device_trace" if name.startswith("idle_in")
                               else "program_span")

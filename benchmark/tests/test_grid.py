"""The reader of the ragged attention's index-space counter: hand-built
``tick`` spans with and without the two arguments, the window's edges,
and the metric's file against ``BENCHMARK.json``."""
import json
import os

import pytest

from benchmark.lib import grid, harness, readers
from conftest import ROOT
from test_phases import ev, one_tick

NAME = "ragged_grid_live_share.sat"


def tick(at, n, **args):
    """The ``tick`` span of tick ``n``: 100 ms from ``at``."""
    return ev("tick", at, at + 0.1, exec="decode", path="ragged", **args)


def test_live_share_sums_the_ticks_that_ended_in_the_window():
    events = (one_tick(100.0, 0) + [tick(100.0, 0, attn_units=100,
                                         attn_live=32)]
              + one_tick(100.1, 1) + [tick(100.1, 1, attn_units=164,
                                           attn_live=96)]
              + one_tick(100.2, 2) + [tick(100.2, 2, attn_units=100,
                                           attn_live=40)]
              # a slot's row, not the engine's: never read
              + [ev("tick", 100.0, 100.1, tid=3, attn_units=7, attn_live=7)])
    whole = readers.Run(t_open=99.0, t_close=101.0, phase_events=events)
    assert grid.live_share(whole) == pytest.approx(100 * 168 / 364)
    # ticks 0 and 1 end inside; tick 2 ends at 100.3, past the close
    cut = readers.Run(t_open=100.05, t_close=100.25, phase_events=events)
    assert grid.live_share(cut) == pytest.approx(100 * 128 / 264)
    # a window that holds no tick's end
    assert grid.live_share(readers.Run(t_open=100.21, t_close=100.25,
                                       phase_events=events)) is None


def test_spans_without_the_counter_give_nothing():
    """The parent commit under this PR's benchmark files: ``tick``
    spans, phases and all, but no ``attn_units`` on them."""
    events = one_tick(100.0, 0) + [tick(100.0, 0, rows=9)]
    run = readers.Run(t_open=99.0, t_close=101.0, phase_events=events)
    assert grid.live_share(run) is None
    # no tracer at all, or a ring that wrapped
    assert grid.live_share(readers.Run(t_open=99.0, t_close=101.0,
                                       phase_events=None)) is None
    # a program that counts on some ticks only (none dispatched idle)
    events += one_tick(100.1, 1) + [tick(100.1, 1, attn_units=50,
                                         attn_live=10)]
    assert grid.live_share(readers.Run(
        t_open=99.0, t_close=101.0, phase_events=events)) == pytest.approx(20)


def test_metric_file_matches_benchmark_json():
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    entry = spec["per_layer"][-1]           # appended, nothing moved
    assert entry["name"] == NAME
    f = harness.read_json("metrics", NAME + ".json")
    for key in ("unit", "better", "source", "layer", "moves", "workloads"):
        assert f[key] == entry[key]
    assert f["tier"] == "per_layer" and f["reader"] == "lib.grid:live_share"
    assert harness.find_function(f["reader"]) is grid.live_share
    assert entry["workloads"] == ["chat-sat.qwen2-7b.d10"]
    assert entry["moves"] in {m["name"] for m in spec["end_to_end"]}


def test_traced_tiny_cell_prints_the_live_share(tiny_tree, capsys,
                                                monkeypatch):
    from conftest import run_cell
    from test_phases import list_new_metrics
    list_new_metrics(tiny_tree, "tiny-sat", {NAME})
    res, _logs, _err = run_cell(capsys, "tiny-sat", seconds=2.0, trace=1)
    got = res["metrics"][NAME]
    assert got["unit"] == "%" and 0 < got["value"] <= 100
    # the program's tracer switched off: left out, and nothing else is
    before = set(res["metrics"])
    monkeypatch.setenv("PADDLE_TPU_TRACE", "0")
    res, _logs, _err = run_cell(capsys, "tiny-sat", seconds=2.0, trace=1)
    assert res["correct"] is True
    assert set(res["metrics"]) == before - {NAME}

"""``lib/components.py`` on hand-built events and maps (the join, the
tie-break, self time, the window's edges, ``None`` over the 5% line),
the new metric files against ``BENCHMARK.json`` BY NAME, and a traced
tiny cell whose line carries (or leaves out) the new metrics."""
import json
import os
import types

import pytest

from conftest import ROOT, run_cell

from benchmark.lib import components, xplane
from paddle_tpu.monitor import tracing

CELLS = {"sat": "chat-sat.qwen2-7b.d10",
         "lp": "longprompt-sat.gigachat3.1-702b.ep16.d5",
         "cw": "chat-wide-sat.lfm2-24b-a2b.d9",
         "rw": "reason-wide-sat.solar-open2-250b.ep16.d8"}


def _row(name, component, shape="f32[8]", layer=None):
    return {"name": name, "opcode": "fusion", "shape": shape, "bytes": 32,
            "component": component, "layer": layer, "also": []}


def _ev(name, start, dur, shape="f32[8]{0}", plane="/device:TPU:0"):
    return xplane.Event(plane, "XLA Ops",
                        f"%{name} = {shape} fusion(%p)", start, dur, "")


CMAP = {
    "decode": [_row("fusion.1", "mixer.in", layer="L0.gqa"),
               _row("fusion.2", "norm", layer="L1.kda"),
               _row("gmm.3", "kernel:gmm", "bf16[16,8]", "L1.kda"),
               _row("fusion.4", "unnamed"),
               _row("while.5", "moe.experts", "(u32[],f32[2])", "L1.kda"),
               _row("lt.6", "moe.gate", "pred[]", "L1.kda")],
    # another executable re-uses two names: one with another shape,
    # one with the same shape and another component
    "state_snapshot": [_row("fusion.1", "cache", "f32[4,2]"),
                       _row("fusion.2", "cache")],
}


def test_join_by_name_then_shape_and_never_across_components():
    by_name = components.index(CMAP)
    assert components.join(_ev("gmm.3", 0, 1, "bf16[16,8]{1,0}"),
                           by_name)["component"] == "kernel:gmm"
    # the shape decides where two executables share a name
    assert components.join(_ev("fusion.1", 0, 1), by_name)["component"] \
        == "mixer.in"
    assert components.join(_ev("fusion.1", 0, 1, "f32[4,2]{1,0:T(2,128)}"),
                           by_name)["component"] == "cache"
    # same name, same shape, two components: joins nothing
    assert components.join(_ev("fusion.2", 0, 1), by_name) is None
    assert components.join(_ev("fusion.99", 0, 1), by_name) is None
    assert components.shape_of(_ev(
        "while.5", 0, 1, "(u32[]{:T(128)}, /*index=1*/f32[2]{0})")) \
        == "(u32[],f32[2])"


def test_self_times_add_up_to_busy_time_inside_the_window():
    ops = [_ev("fusion.1", 0, 10), _ev("while.5", 20, 50),
           _ev("lt.6", 25, 5), _ev("gmm.3", 40, 40),   # runs past while.5
           _ev("fusion.4", 95, 10)]
    got = components.self_times(ops, 5, 100)
    assert got == [5, 15, 5, 40, 5]
    assert sum(got) == sum(b - a for a, b in xplane.union(ops, 5, 100))
    assert components.self_times(ops, 200, 300) == [0, 0, 0, 0, 0]


def _run(ops, cmap=CMAP, t0=0, t1=1000):
    trace = types.SimpleNamespace(ops={"/device:TPU:0": ops}, t0_ns=t0,
                                  t1_ns=t1)
    return types.SimpleNamespace(trace=trace, component_map=cmap)


def test_readers_sum_by_component_and_kind():
    ops = []
    for tick in range(4):           # four ticks of one executable
        at = tick * 200
        ops += [_ev("fusion.1", at, 50), _ev("gmm.3", at + 50, 100,
                                             "bf16[16,8]{1,0}"),
                _ev("while.5", at + 150, 30, "(u32[], f32[2]{0})"),
                _ev("lt.6", at + 160, 10, "pred[]"),
                _ev("fusion.4", at + 180, 2)]
    ops.append(_ev("fusion.99", 900, 10))       # joins nothing
    run = _run(ops)
    got = components.reduce(run)
    assert got["ticks"] == 4
    assert got["busy_ns"] == 4 * 182 + 10
    assert got["by_component"] == {
        "mixer.in": 200, "kernel:gmm": 400, "moe.experts": 80,
        "moe.gate": 40, "unnamed": 8, "unattributed": 10}
    assert got["by_kind"] == {"gqa": {"mixer.in": 200},
                              "kda": {"kernel:gmm": 400, "moe.experts": 80,
                                      "moe.gate": 40}}
    # glue: neither a kernel nor a product against a weight (what
    # stays under moe.experts beside the gmm kernel is glue too)
    assert components.WEIGHTS == ("mixer.in", "mixer.out", "ffn", "head")
    assert components.glue_share(run) == pytest.approx(
        100.0 * (80 + 40 + 8 + 10) / 738)
    assert components.unattributed_share(run) == pytest.approx(
        100.0 * 10 / 738)
    table = components.ms_by_component(run)
    assert table["ticks"] == 4 and list(table["all"])[0] == "kernel:gmm"
    assert table["all"]["kernel:gmm"] == pytest.approx(100e-6)
    assert sum(table["all"].values()) == pytest.approx(table["busy_ms"])
    assert table["kda"]["moe.gate"] == pytest.approx(10e-6)


def test_none_over_the_line_and_without_map_or_trace():
    ops = [_ev("fusion.1", 0, 94), _ev("fusion.4", 94, 3),
           _ev("fusion.99", 97, 3)]             # 6% unknown
    run = _run(ops)
    assert components.glue_share(run) is None
    assert components.unattributed_share(run) == pytest.approx(3.0)
    ops = [_ev("fusion.1", 0, 114), _ev("fusion.4", 114, 3),
           _ev("fusion.99", 117, 3)]            # 5%: still read
    assert components.glue_share(_run(ops)) == pytest.approx(5.0)
    # a program that writes no map (the parent), a run with no trace
    assert components.glue_share(_run(ops, cmap=None)) is None
    assert components.ms_by_component(_run(ops, cmap=None)) is None
    no_trace = types.SimpleNamespace(trace=None)
    assert components.glue_share(no_trace) is None
    assert components.unattributed_share(no_trace) is None


def test_find_map_wants_exactly_one_tracer_with_a_map_in_the_window():
    class Old:                      # a tracer of the parent: no notes
        def events(self):
            return [_phase(10.0)]

    class New:
        def __init__(self, held, at):
            self.held, self.at = held, at

        def annotations(self):
            return self.held

        def events(self):
            return [_phase(self.at),
                    {"ph": "X", "tid": 3, "name": "decode tick",
                     "t0": 10.0, "dur": 1.0, "args": {}}]

    one = New({"component_map": CMAP}, 10.0)
    earlier = New({"component_map": {"decode": []}}, 2.0)   # shut down
    assert components.find_map(
        9.0, 12.0, lambda: [Old(), New({}, 10.0), earlier, one]) is CMAP
    assert components.find_map(9.0, 12.0, lambda: [Old(), earlier]) is None
    assert components.find_map(9.0, 12.0, lambda: [one, one]) is None
    assert components.find_map(1.0, 2.5, lambda: [earlier, one]) \
        == {"decode": []}


def _phase(t0):
    return {"ph": "X", "tid": 0, "name": "launch", "t0": t0, "dur": 0.5,
            "args": {"tick": 1}}


def _real(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


@pytest.mark.parametrize("suffix", sorted(CELLS))
def test_metric_files_match_benchmark_json_by_name(suffix):
    from benchmark.lib import harness
    bench = _real("BENCHMARK.json")
    listed = {m["name"]: m for m in bench["per_layer"]}
    on_disk = _real("benchmark", "metrics", f"glue_share.{suffix}.json")
    entry = listed[on_disk["name"]]
    assert entry == {k: on_disk[k] for k in
                     ("name", "unit", "better", "source", "layer", "moves",
                      "workloads")}
    assert entry["workloads"] == [CELLS[suffix]] \
        and on_disk["tier"] == "per_layer" \
        and on_disk["args"] == {}
    assert callable(harness.find_function(on_disk["reader"]))
    everything = {m["name"] for key in ("end_to_end", "per_layer")
                  for m in bench[key]}
    for name in ("device_ms_by_component", "unattributed_share"):
        observed = _real("benchmark", "metrics", f"{name}.{suffix}.json")
        assert observed["tier"] == "observed" \
            and observed["workloads"] == [CELLS[suffix]] \
            and observed["name"] not in everything
        assert callable(harness.find_function(observed["reader"]))


def test_traced_tiny_cell_reads_the_map_or_leaves_the_metrics_out(
        tiny_tree, capsys):
    """The real metric files through ``run.main`` at a tiny size: the
    program's map reaches the readers through ``live_tracers()``; on the
    CPU stand-in for the device's line the events need not join, and
    then the metric is left out, never raised."""
    cell = _real("benchmark", "workloads", CELLS["sat"] + ".json")
    tiny = _real(tiny_tree, "workloads", "tiny-sat.json")
    with open(os.path.join(tiny_tree, "workloads",
                           CELLS["sat"] + ".json"), "w") as f:
        json.dump(dict(tiny, metrics=cell["metrics"]), f)
    res, _logs, _err = run_cell(capsys, CELLS["sat"], trace=1)
    # (a process keeps the tracers of earlier tests' engines too: the
    # newest is this run's)
    cmap = tracing.live_tracers()[-1].annotations()["component_map"]
    assert "decode" in cmap
    share = res["metrics"].get("glue_share.sat")
    assert share is None or 0.0 <= share["value"] <= 100.0
    assert set(res["observed"]) >= {"device_ms_by_component.sat",
                                    "unattributed_share.sat"}
    res, _logs, _err = run_cell(capsys, CELLS["sat"], trace=0)
    assert "glue_share.sat" not in res["metrics"]
    assert res["observed"]["unattributed_share.sat"]["value"] is None

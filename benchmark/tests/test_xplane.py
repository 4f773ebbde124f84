"""The reduction from a trace to busy time, kernel time and gaps, on a
hand-built fixture whose answers are known by hand."""
import json
import os

import pytest

from benchmark.lib import readers, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
SCOPES = ["ragged_paged_attention", "fused_norm_matmul",
          "fused_matmul_residual"]


@pytest.fixture()
def events():
    rows = json.load(open(os.path.join(HERE, "data",
                                       "trace_events.json")))["events"]
    evs = [xplane.Event(*r) for r in rows]
    return [e for e in evs if xplane.bench_lines(e.plane, e.line)]


def test_only_the_instruction_line_of_a_chip_counts(events):
    ops = xplane.device_ops(events)
    assert list(ops) == ["/device:TPU:0"]
    assert len(ops["/device:TPU:0"]) == 7       # no module, no async view
    assert [s.name for s in xplane.host_spans(events)] == [
        "bench:engine.step", "bench:generator.wait", "bench:engine.step"]


def test_busy_is_the_union_of_intervals(events):
    ops = xplane.device_ops(events)["/device:TPU:0"]
    # tick 1: 1000..101000 without a hole; tick 2: 151000..251000 with
    # slice.1 (230000..245000) inside the kernel before it (221000..251000)
    assert xplane.union(ops) == [[1000, 101000], [151000, 251000]]
    assert xplane.busy_seconds(ops) == pytest.approx(200e-6)
    assert xplane.busy_seconds(ops, 0, 200000) == pytest.approx(149e-6)


def test_kernel_time_by_scope_in_name_or_stats(events):
    ops = xplane.device_ops(events)["/device:TPU:0"]
    assert xplane.scope_seconds(ops, ["ragged_paged_attention"]) == (
        pytest.approx(120e-6), 2)
    assert xplane.scope_seconds(ops, ["fused_norm_matmul",
                                      "fused_matmul_residual"]) == (
        pytest.approx(60e-6), 2)
    assert xplane.scope_seconds(ops, ["flash_attention_fwd"]) == (0.0, 0)
    # within a window: by start time
    assert xplane.scope_seconds(ops, ["ragged_paged_attention"],
                                0, 150000)[1] == 1


def test_top_ops_carry_scope_or_instruction_names(events):
    ops = xplane.device_ops(events)["/device:TPU:0"]
    top = xplane.top_ops(ops, SCOPES)
    assert [k for k, _v in top] == [
        "ragged_paged_attention", "fused_matmul_residual",
        "fusion.7 bf16[136,3584]", "slice.1 bf16[8]"]
    assert top[0][1] == pytest.approx(120e-6)


def test_idle_gaps_go_to_the_span_that_covers_them(events):
    ops = xplane.device_ops(events)["/device:TPU:0"]
    spans = xplane.host_spans(events)
    gaps = dict(xplane.idle_gaps(ops, spans, 0, 260000))
    # 0..1000 and 101000..151000 (20 us of it in engine.step, 25 in
    # generator.wait: the wait covers most) and 251000..260000 (no span)
    assert gaps == {"generator.wait": pytest.approx(50e-6),
                    "engine.step": pytest.approx(1e-6),
                    "host": pytest.approx(9e-6)}


def test_trace_object_gives_the_idle_share(events):
    sink = [("engine.step", 10.0, 10.00012), ("generator.wait", 10.000121,
                                              10.000146),
            ("engine.step", 10.000147, 10.00025)]
    tr = readers.Trace(events, sink, traced_from=9.5)
    assert tr.window_s == pytest.approx(250e-6)         # first span .. last
    assert tr.busy_s() == pytest.approx(199e-6)         # 1000..250000 busy
    run = readers.Run(trace=tr)
    assert readers.device_idle_share(run) == pytest.approx(
        100 * (1 - 199 / 250))
    bd = tr.breakdown(SCOPES)
    assert bd["device_ops"][0][0] == "ragged_paged_attention"
    assert bd["idle_gaps"][0][0] == "generator.wait"


def test_a_trace_without_device_operations_is_refused(events):
    host_only = [e for e in events if e.plane == "/host:CPU"]
    with pytest.raises(RuntimeError, match="no operation ran"):
        readers.Trace(host_only, [("engine.step", 1.0, 2.0)], 0.5)


def test_clocks_must_align_on_the_first_span(events):
    with pytest.raises(RuntimeError, match="clocks cannot be aligned"):
        readers.Trace(events, [("train.step", 1.0, 2.0)], 0.5)


def test_kernel_roofline_reads_its_passes_off_the_trace(events):
    sink = [("engine.step", 10.0, 10.00012), ("engine.step", 10.000147,
                                              10.00025)]
    cfg = dict(hidden_size=256, intermediate_size=512, vocab_size=512,
               num_attention_heads=2, num_key_value_heads=1,
               num_hidden_layers=1)
    run = readers.Run(trace=readers.Trace(events, sink, 9.5), cfg=cfg,
                      cell={"engine": {}}, chips=1,
                      device_kind="TPU v5 lite", records=[])
    w = 2 * 256 * 256 + 2 * 256 * 128 + 3 * 256 * 512
    # two calls, one a layer a tick: two ticks' reads of every weight,
    # no live row in the interval; 60 us of kernel time
    want = 100 * (2 * w * 2 / 819e9) / 60e-6
    assert readers.kernel_roofline(
        run, ["fused_norm_matmul", "fused_matmul_residual"],
        "lib.readers:work_fused_proj") == pytest.approx(want)
    # a kernel that the trace does not hold is left out, not read as 0
    assert readers.kernel_roofline(run, ["flash_attention_fwd"],
                                   "lib.readers:work_flash_attn", 3) is None
    # work counted too high for the time raises
    with pytest.raises(ArithmeticError):
        readers.kernel_roofline(
            dict_run(run, hidden_size=8192, intermediate_size=32768),
            ["fused_matmul_residual"], "lib.readers:work_fused_proj")


def dict_run(run, **cfg):
    other = readers.Run(**run.__dict__)
    other.cfg = dict(run.cfg, **cfg)
    return other

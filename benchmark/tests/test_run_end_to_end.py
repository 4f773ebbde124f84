"""run.py end to end at the tiny preset: the result line's keys, the
metrics each kind of run reports, new files found by name alone, and no
result off a TPU."""
import json
import os
import subprocess
import sys

import pytest

from conftest import ROOT, known_fault_only, run_cell, _write

KEYS = {"correct", "attempted", "failed", "metrics", "device", "observed",
        "seconds", "compared"}


def test_serve_cell_end_to_end(tiny_tree, capsys):
    res, logs, err = run_cell(capsys, "tiny-sat")
    assert set(res) == KEYS and list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "out_tok_per_s"}
    assert res["metrics"]["out_tok_per_s"]["unit"] == "tokens/s"
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes",
                                  "memory_window_bytes"}
    closed = next(l for l in logs if l.get("window") == "closed")
    assert closed["compiles_in_window"] == 0
    assert closed["counters"]["executables_compiled"] == 0
    # every number compared stands beside its limit, on stderr too
    for name, row in res["compared"].items():
        assert set(row) == {"value", "limit"}
        assert f"compared {name} = " in err


def test_open_loop_cell_reports_gaps_and_tails(tiny_tree, capsys):
    res, _logs, _err = run_cell(capsys, "tiny-rate", seconds=2.0)
    assert set(res["metrics"]) == {"setup_s", "itl_mean_ms"}
    assert res["correct"] is True
    assert res["attempted"] > 0 and res["failed"] == 0
    # time to a first token can carry no bound at this few requests and
    # moves no bounded metric: observed in every run, in no metric list
    assert set(res["observed"]) == {"ttft_mean_ms.rate", "ttft_p90_ms.rate",
                                    "gen_late_p95_ms.rate"}
    assert res["observed"]["ttft_mean_ms.rate"]["value"] > 0
    res, _logs, _err = run_cell(capsys, "tiny-rate", seconds=2.0, trace=1)
    assert {"itl_p95_ms.rate", "prefill_tick_share.rate", "tick_ms.rate",
            "device_idle_share.rate"} == set(res["metrics"])
    assert len(res["observed"]) == 3


def test_train_cell_end_to_end(tiny_tree, capsys):
    res, logs, _err = run_cell(capsys, "tiny-train")
    assert set(res) == KEYS
    known_fault_only(res, logs)
    assert set(res["metrics"]) == {"setup_s", "train_tok_per_s_chip"}
    assert res["attempted"] > 0
    assert {"loss_gap_1", "grad_norm_gap", "change_norm_gap",
            "feed_mismatch"} <= set(res["compared"])


def test_traced_run_reports_per_layer_metrics_and_breakdown(tiny_tree,
                                                            capsys):
    res, _logs, _err = run_cell(capsys, "tiny-sat", seconds=2.0, trace=1)
    assert set(res) == KEYS | {"breakdown"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    got = set(res["metrics"])
    assert {"tick_ms.sat", "batch_occupancy.sat", "tick_mfu.sat",
            "device_idle_share.sat"} <= got
    # no kernel scope is in a CPU trace: a roofline that finds nothing to
    # read is left out, never printed as 0
    assert "ragged_attn_roofline.sat" not in got
    assert "setup_s" not in got and "out_tok_per_s" not in got
    bd = res["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10


def test_new_cell_config_traffic_and_metric_are_found_by_name(tiny_tree,
                                                              capsys):
    """A later PR adds files and edits none: a configuration, a mix, a
    cell and a metric that lists the cell."""
    from benchmark import run
    base = run.read_json("workloads", "tiny-sat.json")
    _write(tiny_tree, "configs/tiny-wide.json",
           dict(run.read_json("configs", "tiny.json"), intermediate_size=768))
    _write(tiny_tree, "traffic/tiny-five.json",
           dict(run.read_json("traffic", "tiny-sat.json"), clients=5))
    _write(tiny_tree, "workloads/tiny-new.json",
           dict(base, config="tiny-wide", traffic="tiny-five"))
    _write(tiny_tree, "metrics/tokens_per_tick.new.json", {
        "name": "tokens_per_tick.new", "tier": "end_to_end",
        "unit": "tokens/tick", "better": "higher",
        "source": "program_counter", "reader": "lib.readers:counter_ratio",
        "args": {"num": "tokens_total", "den": "decode_steps"},
        "workloads": ["tiny-new"]})
    res, _logs, _err = run_cell(capsys, "tiny-new")
    assert res["correct"] is True
    assert res["metrics"]["tokens_per_tick.new"]["value"] > 0


FAMILY = '''"""A second family: its own builder, leaves and reference run."""
from benchmark.models import qwen2

CALLS = []


def build(cfg, seed, training):
    CALLS.append("build")
    return qwen2.build(cfg, seed, training)


def served_logits(*a, **kw):
    CALLS.append("served_logits")
    return qwen2.served_logits(*a, **kw)
'''

KIND = '''"""A second kind of job, driven as a serving cell is."""
from benchmark.kinds import serve


def run(ctx):
    ctx.run.kind_file = __file__
    return serve.run(ctx)
'''

READER = '''"""A reader and a kernel's work function of a later PR's own."""


def requests_seen(run, scale):
    return scale * len(run.records) if run.records else None


def work_one_flop(run, passes):
    return 1.0, 1.0
'''


def test_new_family_kind_reader_and_work_function_are_new_files(tiny_tree,
                                                                capsys):
    """The next model family, kind of job, reader and work function come
    as files of their own, named in data files; no file that is there is
    edited (the tree is the tests' own copy, the modules the real ones)."""
    import sys
    from benchmark import run
    from benchmark.lib import harness, readers
    before = {}
    for folder, _dirs, files in os.walk(harness.PKG):
        if "__pycache__" not in folder:
            before.update({os.path.join(folder, f): os.path.getmtime(
                os.path.join(folder, f)) for f in files})
    for rel, text in (("models/tiny2.py", FAMILY), ("kinds/serve2.py", KIND),
                      ("lib/readers_new.py", READER)):
        path = os.path.join(tiny_tree, rel)
        os.makedirs(os.path.dirname(path), exist_ok=True)
        with open(path, "w") as f:
            f.write(text)
    _write(tiny_tree, "configs/tiny2.json",
           dict(run.read_json("configs", "tiny.json"), model_type="tiny2"))
    _write(tiny_tree, "workloads/tiny2-sat.json",
           dict(run.read_json("workloads", "tiny-sat.json"), config="tiny2",
                kind="serve2"))
    _write(tiny_tree, "metrics/requests_seen.new.json", {
        "name": "requests_seen.new", "tier": "end_to_end", "unit": "requests",
        "better": "higher", "source": "host_clock",
        "reader": "lib.readers_new:requests_seen", "args": {"scale": 2},
        "workloads": ["tiny2-sat"]})
    res, _logs, _err = run_cell(capsys, "tiny2-sat")
    assert res["correct"] is True
    family = sys.modules["benchmark_tree.models.tiny2"]
    assert family.CALLS == ["build", "served_logits"]
    assert res["metrics"]["requests_seen.new"]["value"] >= 2
    # a roofline whose work function is the new file's
    assert harness.find_function("lib.readers_new:work_one_flop")(
        None, 1) == (1.0, 1.0)
    assert harness.find("lib.readers") is readers   # the real one, not a copy
    after = {p: os.path.getmtime(p) for p in before}
    assert after == before


def test_no_result_line_off_a_tpu():
    """The command itself, on this machine's CPU: another exit code than
    0 and no result."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "chat-sat.qwen2-7b.d10", "--seed", "1", "--seconds",
         "1", "--trace", "0"], cwd=ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert not any(l.startswith("{") and '"correct"' in l
                   for l in p.stdout.splitlines())
    assert "not 'tpu'" in p.stderr


def test_unknown_device_kind_is_an_error():
    from benchmark.lib import peaks
    assert peaks.for_device("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(LookupError):
        peaks.for_device("TPU v9 imaginary")


def test_benchmark_json_names_files_that_exist():
    path = os.path.join(ROOT, "BENCHMARK.json")
    if not os.path.exists(path):
        pytest.skip("BENCHMARK.json is written once the cells are proven")
    spec = json.load(open(path))
    from benchmark import run
    import re
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert 1 <= spec["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in spec[group]]
        assert len(set(names)) == len(names) and all(map(name.match, names))
    for e in spec["configs"] + spec["workloads"]:
        assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert unit.match(m["unit"]) and m["better"] in ("lower", "higher")
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in spec["end_to_end"]}
    assert "setup_s" in e2e
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and set(m) == {
            "name", "unit", "better", "source", "layer", "moves", "workloads"}
    four = sum(w["chips"] == 4 for w in spec["workloads"])
    assert four <= max(1, len(spec["workloads"]) // 4)
    for c in spec["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["source"] == c["source"]
    for w in spec["workloads"]:
        cell, _cfg, _mix = run.load_cell(w["name"])
        assert (cell["config"], cell["traffic"], cell["chips"]) == (
            w["config"], w["traffic"], w["chips"])
        for tier in ("end_to_end", "per_layer"):
            listed = {m["name"] for m in spec[tier]
                      if w["name"] in m.get("workloads", [w["name"]])}
            found = {m["name"] for m in run.load_metrics(
                w["name"], cell["metrics"], tier)}
            assert listed == found, (w["name"], tier)
    for tier in ("end_to_end", "per_layer"):
        for m in spec[tier]:
            f = run.read_json("metrics", m["name"] + ".json")
            for k in ("unit", "better", "source"):
                assert f[k] == m[k], (m["name"], k)
            if tier == "per_layer":
                assert (f["layer"], f["moves"]) == (m["layer"], m["moves"])

"""The new cell's files load end to end: the real cell, traffic and
metric files of ``chat-wide-sat.lfm2-24b-a2b.d9`` driven through
``run.main`` on the CPU stand-in device, at a tiny configuration of the
same ``model_type`` and a tiny engine (the sizes are the chip's; nothing
else of the files is changed)."""
import json
import os

from conftest import ROOT, run_cell, _write
from test_reference_lfm2_moe import TINY_LFM2

CELL = "chat-wide-sat.lfm2-24b-a2b.d9"


def _real(*parts):
    return json.load(open(os.path.join(ROOT, "benchmark", *parts)))


def _tiny_copy(tree):
    cell = _real("workloads", CELL + ".json")
    mix = _real("traffic", cell["traffic"] + ".json")
    _write(tree, f"configs/{cell['config']}.json", TINY_LFM2)
    _write(tree, f"traffic/{cell['traffic']}.json", dict(
        mix, pool=8, clients=6, max_total=64,
        prompt_len=dict(median=20, sigma=0.6, min=4, max=40),
        output_len=dict(median=5, sigma=0.4, min=2, max=8)))
    _write(tree, f"workloads/{CELL}.json", dict(
        cell,
        engine=dict(cell["engine"], num_slots=4, max_model_len=64,
                    prefill_chunk=16),
        warmup=dict(requests=[[40, 3], [10, 4]], lead_s=1.0),
        trace=dict(cell["trace"], start_s=0.3, seconds=1.0),
        check=dict(cell["check"], requests=4, min_tokens=20, rows_cap=64,
                   limits=dict(cell["check"]["limits"],
                               logit_gap_max=0.05, logit_gap_p75=0.01,
                               logit_gap_p90=0.02))))
    return cell


def test_cell_files_are_the_issue_s():
    from benchmark.lib import harness, traffic
    cell = _real("workloads", CELL + ".json")
    mix = _real("traffic", "chat-wide-sat.json")
    assert cell["engine"] == {"num_slots": 128, "max_model_len": 4096,
                              "block_size": 16, "prefill_chunk": 256,
                              "host_kv_tier_bytes": 0}
    assert (mix["loop"], mix["clients"], mix["pool"], mix["max_total"]) \
        == ("closed", 192, 64, 4096)
    assert mix["prompt_len"] == {"median": 256, "sigma": 0.9, "min": 32,
                                 "max": 3072}
    assert mix["output_len"] == {"median": 256, "sigma": 0.6, "min": 32,
                                 "max": 1024}
    sizes = traffic.size_pool(mix)
    assert min(p for p, _o in sizes) >= 32 \
        and max(p + o for p, o in sizes) <= mix["max_total"] \
        == cell["engine"]["max_model_len"]
    # warm-up: the long prompt, then a request a seat
    assert cell["warmup"]["requests"][0] == [3000, 8]
    assert len(cell["warmup"]["requests"]) == 128
    assert cell["check"]["requests"] == 8 \
        and cell["check"]["min_tokens"] == 300
    assert cell["trace"]["scopes"] == ["ragged_paged_attention", "gmm"]
    # every listed metric file names a reader (and a work function)
    # that resolves
    for name in cell["metrics"]:
        m = _real("metrics", name + ".json")
        assert callable(harness.find_function(m["reader"]))
        if "work" in m["args"]:
            assert callable(harness.find_function(m["args"]["work"]))
    bench = _real("..", "BENCHMARK.json")
    listed = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    on_disk = [m for m in cell["metrics"] if m.endswith(".cw")
               and _real("metrics", m + ".json")["tier"] == "per_layer"]
    assert sorted(listed) == sorted(on_disk) and len(listed) == 14
    # the readers are the ones the other cells' metrics name: the family
    # brings its FLOP count, its attention work and its routing figure
    own = {m: _real("metrics", m + ".json")["reader"]
           for m in cell["metrics"] if m.endswith(".cw")}
    assert {m for m, r in own.items() if r.startswith("lib.lfm2_moe:")} \
        == {"tick_mfu.cw", "expert_load_max_over_mean.cw"}
    assert cell["trace"]["start_s"] == 2 and cell["trace"]["seconds"] == 4
    # the comparison: the worst row's gap and the typical row's, each
    # with a limit (kinds/serve_typical.py)
    assert cell["kind"] == "serve_typical"
    assert set(cell["check"]["quantiles"]) | {
        "logit_gap_max", "wrong_answers", "unchecked"} \
        == set(cell["check"]["limits"])
    # (by name, not by place: later PRs append after these)
    listed_cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert listed_cell == {"name": CELL, "config": cell["config"],
                           "traffic": "chat-wide-sat", "chips": 1,
                           "why": cell["why"]}
    assert cell["config"] in [c["name"] for c in bench["configs"]]
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_per_s")
    assert CELL in out["workloads"]


def test_cell_runs_end_to_end_on_the_stand_in_device(tiny_tree, capsys):
    cell = _tiny_copy(tiny_tree)
    res, logs, _err = run_cell(capsys, CELL, seconds=2.0,
                               hooks={"control": True})
    assert res["correct"] is True and res["failed"] == 0
    # the control goes through the same verdict with the same numbers
    low = next(l for l in logs if l.get("reading") == "control_lowp")
    assert set(low["numbers"]) == set(res["compared"])
    assert any(l.get("gap_summary") == "control_lowp" for l in logs)
    assert set(res["metrics"]) == {"setup_s", "out_tok_per_s"}
    assert set(res["compared"]) == set(cell["check"]["limits"])
    gaps = next(l for l in logs if l.get("gap_summary") == "run")
    assert gaps["n"] >= 20 and gaps["p50"] <= gaps["p99"] <= gaps["max"] \
        == res["compared"]["logit_gap_max"]["value"]
    closed = next(l for l in logs if l.get("window") == "closed")
    assert closed["compiles_in_window"] == 0
    assert closed["counters"]["executables_compiled"] == 0
    assert closed["counters"]["state_seats_started"] > 0
    assert closed["counters"]["prefix_tokens_cut_for_state"] == 0
    assert set(res["observed"]) == {"moe_pairs_per_row.cw",
                                    "expert_load_max_over_mean.cw"}
    # every expert is held: each of a row's pairs is local (2 a row at
    # the tiny size, 4.0 at the published one)
    assert res["observed"]["moe_pairs_per_row.cw"]["value"] == 2.0
    assert res["observed"]["expert_load_max_over_mean.cw"]["value"] >= 1.0
    res, _logs, _err = run_cell(capsys, CELL, seconds=2.0, trace=1)
    got = set(res["metrics"])
    # (the stand-in device reports no memory: the two HBM readers find
    # nothing to read)
    assert {"tick_mfu.cw", "tick_ms.cw", "device_idle_share.cw",
            "batch_occupancy.cw", "host_share.cw",
            "ragged_grid_live_share.cw", "pipelined_tick_share.cw",
            "idle_in_host.cw", "idle_in_wait.cw", "idle_in_spill.cw"} <= got
    # no kernel scope is in a CPU trace: the rooflines are left out
    assert not {"ragged_attn_roofline.cw", "moe_gmm_roofline.cw"} & got
    assert set(cell["metrics"]) >= got


def test_stale_state_fault_reaches_the_conv_layers(monkeypatch):
    """``tools/state_fault.py`` plants what it says: a seat's first
    rows at position 0 read what the seat's last occupant left instead
    of zeros, and nothing else moves."""
    import jax.numpy as jnp
    import numpy as np
    import paddle_tpu as paddle
    from benchmark.models import lfm2_moe as family
    from benchmark.tools import state_fault
    from paddle_tpu.models import lfm2_moe
    from paddle_tpu.ops import paged_cache as pc
    conv = family.build(TINY_LFM2, 7, training=False).model.layers[0].conv
    x = paddle.to_tensor(np.random.default_rng(0).normal(
        size=(1, 6, 256)).astype(np.float32)).astype("bfloat16")
    left = (pc.SlotState(jnp.ones((3, 2, 256), jnp.bfloat16)),)
    # slot 0 starts a request (positions 0-2), slot 1 goes on at 40-41
    meta = tuple(jnp.asarray(a, jnp.int32) for a in (
        [3, 2], [0, 3], [0, 0, 0, 1, 1, 0], [0, 1, 2, 40, 41, 64]))

    def run():
        out, (state,) = conv.forward_paged(x, left, meta)
        return np.asarray(out.numpy(), np.float32)[0], state

    sound, kept = run()
    whole = np.asarray(conv(x[:, :3]).numpy(), np.float32)[0]
    np.testing.assert_array_equal(sound[:3], whole)
    monkeypatch.setattr(lfm2_moe.Lfm2ShortConv, "forward_paged",
                        lfm2_moe.Lfm2ShortConv.forward_paged)
    state_fault.stale_state()
    faulty, kept_f = run()
    assert np.abs(faulty[:2] - sound[:2]).max() > 0
    np.testing.assert_array_equal(faulty[2:5], sound[2:5])
    np.testing.assert_array_equal(np.asarray(kept_f.data, np.float32),
                                  np.asarray(kept.data, np.float32))


def test_route_probe_counts_rows_by_flipped_layers():
    """``tools/route_probe.py`` at the tiny size: the program's and the
    reference's chosen experts are compared layer by layer and row by
    row, every row lands in one class, and the float8 control flips
    more rows than the bf16 program does."""
    from benchmark.models import lfm2_moe as family
    from benchmark.tools import route_probe
    out, kept = route_probe.probe(family, TINY_LFM2, 2**31 + 5, 64)
    assert out["rows"] == 64 and out["expert_layers"] == 4
    for side in ("program", "control_lowp"):
        got = out[side]
        assert sum(c["rows"] for c in got["by_layers_flipped"].values()) == 64
        assert got["rows_with_a_flipped_layer"] \
            == 64 - got["by_layers_flipped"].get("0", {"rows": 0})["rows"]
        assert len(kept[side]["gaps"]) == len(kept[side]["flips"]) == 64
        assert got["gaps"]["p100"] == max(kept[side]["gaps"])
    assert out["control_lowp"]["flipped_layer_rows"] \
        > out["program"]["flipped_layer_rows"]

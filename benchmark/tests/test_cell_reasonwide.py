"""The new cell's files load end to end: the real cell, traffic and
metric files of ``reason-wide-sat.solar-open2-250b.ep16.d8`` driven
through ``run.main`` on the CPU stand-in device, at a tiny configuration
of the same ``model_type`` and a tiny engine (the sizes are the chip's;
nothing else of the files is changed)."""
import json
import os

from conftest import ROOT, run_cell, _write
from test_reference_solar_open2 import TINY_SOLAR

CELL = "reason-wide-sat.solar-open2-250b.ep16.d8"


def _real(*parts):
    return json.load(open(os.path.join(ROOT, "benchmark", *parts)))


def _tiny_copy(tree):
    cell = _real("workloads", CELL + ".json")
    mix = _real("traffic", cell["traffic"] + ".json")
    _write(tree, f"configs/{cell['config']}.json", TINY_SOLAR)
    _write(tree, f"traffic/{cell['traffic']}.json", dict(
        mix, pool=8, clients=6, max_total=128,
        prompt_len=dict(median=30, sigma=0.6, min=4, max=90),
        output_len=dict(median=5, sigma=0.4, min=2, max=8)))
    _write(tree, f"workloads/{CELL}.json", dict(
        cell,
        engine=dict(cell["engine"], num_slots=4, max_model_len=128,
                    prefill_chunk=80, num_blocks=40),
        warmup=dict(requests=[[100, 3], [10, 4]], lead_s=1.0),
        trace=dict(cell["trace"], start_s=0.3, seconds=1.0),
        check=dict(cell["check"], requests=4, min_tokens=20, rows_cap=64,
                   limits=dict(cell["check"]["limits"],
                               logit_gap_max=0.05, logit_gap_p75=0.01,
                               logit_gap_p90=0.02))))
    return cell


def test_cell_files_are_the_issue_s():
    from benchmark.lib import harness, traffic
    cell = _real("workloads", CELL + ".json")
    mix = _real("traffic", "reason-wide-sat.json")
    assert cell["engine"] == {"num_slots": 96, "max_model_len": 6144,
                              "block_size": 16, "prefill_chunk": 256,
                              "num_blocks": 16384, "host_kv_tier_bytes": 0}
    assert (mix["loop"], mix["clients"], mix["pool"], mix["max_total"]) \
        == ("closed", 144, 48, 6144)
    assert mix["prompt_len"] == {"median": 512, "sigma": 0.8, "min": 64,
                                 "max": 4096}
    assert mix["output_len"] == {"median": 512, "sigma": 0.6, "min": 128,
                                 "max": 2048}
    sizes = traffic.size_pool(mix)
    assert min(p for p, _o in sizes) >= 64 \
        and max(p + o for p, o in sizes) <= mix["max_total"] \
        == cell["engine"]["max_model_len"]
    assert mix["clients"] * 2 == 3 * cell["engine"]["num_slots"]
    # warm-up: the long prompt, then more requests than seats
    assert cell["warmup"]["requests"][0] == [4000, 8]
    assert len(cell["warmup"]["requests"]) > cell["engine"]["num_slots"]
    # the longest finished request and 299 tokens of others fit the cap
    assert cell["check"]["rows_cap"] >= 2048 + cell["check"]["min_tokens"]
    assert cell["trace"]["scopes"] == ["kda_recurrent", "kda_chunk",
                                       "ragged_paged_attention", "gmm"]
    for name in cell["metrics"]:
        m = _real("metrics", name + ".json")
        assert callable(harness.find_function(m["reader"]))
        if "work" in m["args"]:
            assert callable(harness.find_function(m["args"]["work"]))
    # entries are looked up by NAME, not by place: later PRs append
    bench = _real("..", "BENCHMARK.json")
    listed = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    on_disk = [m for m in cell["metrics"] if m.endswith(".rw")
               and _real("metrics", m + ".json")["tier"] == "per_layer"]
    assert sorted(listed) == sorted(on_disk) and len(listed) == 15
    for name in listed:
        m, entry = _real("metrics", name + ".json"), next(
            e for e in bench["per_layer"] if e["name"] == name)
        assert entry == {k: m[k] for k in entry}
        assert entry["workloads"] == [CELL] \
            and entry["moves"] == "out_tok_per_s"
    assert cell["kind"] == "serve_typical"
    assert set(cell["check"]["quantiles"]) | {
        "logit_gap_max", "wrong_answers", "unchecked"} \
        == set(cell["check"]["limits"])
    listed_cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert listed_cell == {"name": CELL, "config": cell["config"],
                           "traffic": "reason-wide-sat", "chips": 1,
                           "why": cell["why"]}
    listed_cfg = next(c for c in bench["configs"]
                      if c["name"] == cell["config"])
    assert sorted(listed_cfg["reduced"]) == sorted(
        _real("configs", cell["config"] + ".json")["reduced"])
    out = next(m for m in bench["end_to_end"] if m["name"] == "out_tok_per_s")
    assert CELL in out["workloads"]


def test_cell_runs_end_to_end_on_the_stand_in_device(tiny_tree, capsys):
    cell = _tiny_copy(tiny_tree)
    res, logs, _err = run_cell(capsys, CELL, seconds=2.0,
                               hooks={"control": True})
    assert res["correct"] is True and res["failed"] == 0
    low = next(l for l in logs if l.get("reading") == "control_lowp")
    assert set(low["numbers"]) == set(res["compared"])
    assert set(res["metrics"]) == {"setup_s", "out_tok_per_s"}
    assert set(res["compared"]) == set(cell["check"]["limits"])
    closed = next(l for l in logs if l.get("window") == "closed")
    assert closed["compiles_in_window"] == 0
    assert closed["counters"]["executables_compiled"] == 0
    assert closed["counters"]["state_seats_started"] > 0
    assert set(res["observed"]) == {"moe_pairs_per_row.rw",
                                    "expert_load_max_over_mean.rw"}
    # 4 of 16 experts held, top-2: 0.5 local pairs a row if even
    assert 0.2 < res["observed"]["moe_pairs_per_row.rw"]["value"] < 0.9
    res, _logs, _err = run_cell(capsys, CELL, seconds=2.0, trace=1)
    got = set(res["metrics"])
    assert {"tick_mfu.rw", "tick_ms.rw", "device_idle_share.rw",
            "batch_occupancy.rw", "host_share.rw",
            "ragged_grid_live_share.rw", "pipelined_tick_share.rw",
            "idle_in_host.rw", "idle_in_wait.rw"} <= got
    # no kernel scope is in a CPU trace: the rooflines are left out
    assert not {"ragged_attn_roofline.rw", "moe_gmm_roofline.rw",
                "kda_recurrent_roofline.rw", "kda_chunk_roofline.rw"} & got
    assert set(cell["metrics"]) >= got


def test_kda_work_readers_count_the_tick_spans(tiny_tree, capsys):
    """The delta rule's work functions read ``kda_seats`` and
    ``kda_chunk_rows`` off the program's ``tick`` spans; a program
    without them gives None (the metric is left out)."""
    import types
    from benchmark.lib import solar_open2 as lib
    ticks = [{"kda_seats": 3, "kda_chunk_rows": 80},
             {"kda_seats": 4, "kda_chunk_rows": 0}]
    events = [{"name": "tick", "tid": 0, "ph": "X", "t0": 1.0 + i,
               "dur": 0.5, "args": t} for i, t in enumerate(ticks)]
    run = types.SimpleNamespace(
        cfg=TINY_SOLAR, phase_events=events, trace=object(),
        interval=lambda: (0.0, 10.0))
    heads, d = 4, 16
    state, row = heads * d * d * 4, (5 * heads * d + heads) * 2
    assert lib.work_kda_recurrent(run, 0) == (
        6 * 7 * 7 * d * d * heads, 6 * 7 * (2 * state + row))
    assert lib.work_kda_chunk(run, 0) == (
        6 * 80 * (6 * 64 * d * d + 8 * 64 * 64 * d) // 64 * heads,
        6 * (2 * state + 80 * row))
    bare = types.SimpleNamespace(
        cfg=TINY_SOLAR, trace=object(), interval=lambda: (0.0, 10.0),
        phase_events=[dict(events[0], args={"rows": 3})])
    assert lib.kda_roofline(bare, ["kda_chunk"],
                            "lib.solar_open2:work_kda_chunk") is None


def test_planted_faults_reach_the_recurrent_state(monkeypatch):
    """``tools/kda_fault.py`` plants what it says: a fresh seat starts
    from its last occupant's matrix state; the state's values are
    bfloat16's."""
    import jax.numpy as jnp
    import numpy as np
    from benchmark.tools import kda_fault
    from paddle_tpu.models import solar_open2
    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((4, 2, 16)), jnp.float32)
               for _ in range(3))
    g = -jnp.asarray(rng.uniform(0.01, 1, (4, 2, 16)), jnp.float32)
    beta = jnp.ones((4, 2), jnp.float32)
    state = jnp.ones((3, 2, 16, 16), jnp.float32).at[2].set(0)
    # slot 0 starts a request (positions 0-1), slot 1 goes on at 40
    meta = tuple(jnp.asarray(a, jnp.int32) for a in (
        [2, 1], [0, 2], [0, 0, 1, 0], [0, 1, 40, 999], [0], [0, 1, 2, 3]))
    sound = solar_open2.kda_step
    sound_o, sound_s = sound(q, k, v, g, beta, state, meta)
    got = {}
    for fault, plant in kda_fault.FAULTS.items():
        monkeypatch.setattr(solar_open2, "kda_step", sound)
        plant()
        got[fault] = solar_open2.kda_step(q, k, v, g, beta, state, meta)
    o, s = got["stale_state"]
    assert np.abs(np.asarray(o[:2] - sound_o[:2])).max() > 1e-3
    np.testing.assert_array_equal(np.asarray(o[2]), np.asarray(sound_o[2]))
    np.testing.assert_array_equal(np.asarray(s[1]), np.asarray(sound_s[1]))
    o, s = got["bf16_state"]
    np.testing.assert_array_equal(np.asarray(o), np.asarray(sound_o))
    np.testing.assert_array_equal(
        np.asarray(s), np.asarray(sound_s.astype(jnp.bfloat16), np.float32))
    assert np.abs(np.asarray(s - sound_s)).max() > 0

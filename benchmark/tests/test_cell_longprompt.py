"""The new cell's files load end to end: the real cell, traffic and
metric files of ``longprompt-sat.gigachat3.1-702b.ep16.d5`` driven
through ``run.main`` on the CPU stand-in device, at a tiny configuration
of the same ``model_type`` and a tiny engine (the sizes are the chip's;
nothing else of the files is changed)."""
import json
import os

from conftest import ROOT, run_cell, _write
from test_reference_deepseek_v3 import TINY_V3

CELL = "longprompt-sat.gigachat3.1-702b.ep16.d5"


def _real(*parts):
    return json.load(open(os.path.join(ROOT, "benchmark", *parts)))


def _tiny_copy(tree):
    cell = _real("workloads", CELL + ".json")
    mix = _real("traffic", cell["traffic"] + ".json")
    _write(tree, f"configs/{cell['config']}.json", TINY_V3)
    _write(tree, f"traffic/{cell['traffic']}.json", dict(
        mix, pool=8, clients=6, max_total=64,
        prompt_len=dict(median=20, sigma=0.6, min=4, max=40),
        output_len=dict(median=5, sigma=0.4, min=2, max=8)))
    _write(tree, f"workloads/{CELL}.json", dict(
        cell,
        engine=dict(cell["engine"], num_slots=4, max_model_len=64,
                    prefill_chunk=16, num_blocks=64),
        warmup=dict(requests=[[40, 3], [10, 4]], lead_s=1.0),
        trace=dict(cell["trace"], start_s=0.3, seconds=1.0),
        check=dict(cell["check"], requests=4, min_tokens=20, rows_cap=64)))
    return cell


def test_cell_files_are_the_issue_s():
    cell = _real("workloads", CELL + ".json")
    mix = _real("traffic", "longprompt-sat.json")
    assert cell["engine"] == {"num_slots": 32, "max_model_len": 8192,
                              "block_size": 16, "prefill_chunk": 512,
                              "num_blocks": 49152, "host_kv_tier_bytes": 0}
    assert (mix["loop"], mix["clients"], mix["pool"], mix["max_total"]) \
        == ("closed", 48, 32, 8192)
    assert mix["prompt_len"] == {"median": 3072, "sigma": 0.7, "min": 512,
                                 "max": 7680}
    assert mix["output_len"] == {"median": 128, "sigma": 0.6, "min": 16,
                                 "max": 512}
    assert cell["check"]["requests"] == 8 \
        and cell["check"]["min_tokens"] == 300
    bench = _real("..", "BENCHMARK.json")
    listed = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    on_disk = [m for m in cell["metrics"] if m.endswith(".lp")
               and _real("metrics", m + ".json")["tier"] == "per_layer"]
    assert sorted(listed) == sorted(on_disk) and len(listed) == 10
    assert [w["name"] for w in bench["workloads"]][-1] == CELL
    assert all(w["chips"] == 1 for w in bench["workloads"])
    cfg = _real("configs", cell["config"] + ".json")
    from benchmark.lib import traffic
    sizes = traffic.size_pool(mix)
    assert min(p for p, _o in sizes) >= 512 \
        and max(p + o for p, o in sizes) <= 8192
    assert cfg["vocab_size"] == 16032 and cfg["n_routed_experts"] == 16


def test_config_holds_the_catalog_row_but_for_what_is_reduced():
    cfg = _real("configs", "gigachat3.1-702b.ep16.d5.json")
    published = {"vocab_size": 128256, "num_hidden_layers": 64,
                 "first_k_dense_replace": 3, "n_routed_experts": 256}
    assert set(cfg["reduced"]) == set(published)
    for key, value in published.items():
        assert cfg["reduced"][key]["published"] == value
        assert cfg["reduced"][key]["here"] == cfg[key]
    widths = dict(hidden_size=7168, intermediate_size=18432,
                  moe_intermediate_size=2048, num_attention_heads=64,
                  q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=192, n_group=8,
                  topk_group=4, num_experts_per_tok=8,
                  routed_scaling_factor=2.5, rope_theta=100000)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["deployment"]["expert_parallel"] * cfg["n_routed_experts"] \
        == 256
    assert "num_nextn_predict_layers" in cfg["not_served"]


def test_cell_runs_end_to_end_on_the_stand_in_device(tiny_tree, capsys):
    cell = _tiny_copy(tiny_tree)
    res, logs, _err = run_cell(capsys, CELL, seconds=2.0)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "out_tok_per_s"}
    assert res["compared"]["logit_gap_max"]["value"] < 0.05
    closed = next(l for l in logs if l.get("window") == "closed")
    assert closed["compiles_in_window"] == 0
    assert closed["counters"]["executables_compiled"] == 0
    assert closed["counters"]["moe_pairs_local"] > 0
    assert set(res["observed"]) == {"moe_pairs_per_row.lp",
                                    "expert_load_max_over_mean.lp"}
    # 4 of 16 experts held, 4 chosen a row: about one pair a row
    assert 0.5 < res["observed"]["moe_pairs_per_row.lp"]["value"] < 1.5
    assert res["observed"]["expert_load_max_over_mean.lp"]["value"] >= 1.0
    res, _logs, _err = run_cell(capsys, CELL, seconds=2.0, trace=1)
    got = set(res["metrics"])
    # (the stand-in device reports no memory: the two HBM readers find
    # nothing to read)
    assert {"tick_mfu.lp", "tick_ms.lp", "device_idle_share.lp",
            "batch_occupancy.lp", "prefill_tick_share.lp",
            "host_share.lp"} <= got
    # no kernel scope is in a CPU trace: the rooflines are left out
    assert not {"mla_attn_roofline.lp", "moe_gmm_roofline.lp"} & got
    assert set(cell["metrics"]) >= got

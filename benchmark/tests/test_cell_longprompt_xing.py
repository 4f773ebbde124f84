"""The Xing cell's files load end to end: the real cell, traffic and
metric files of ``longprompt-sat.xing4.0-29b-a4b.d6`` driven through
``run.main`` on the CPU stand-in device, at a tiny configuration of the
same ``model_type`` and a tiny engine (the sizes are the chip's; nothing
else of the files is changed); and the configuration file against the
catalog row it was cut from."""
import json
import os

import numpy as np

from conftest import ROOT, run_cell, _write

CELL = "longprompt-sat.xing4.0-29b-a4b.d6"
TINY_XING = dict(
    model_type="xing4_0", vocab_size=512, hidden_size=128,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=32, kv_lora_rank=32,
    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=24,
    n_shared_experts=1, n_routed_experts=8, num_experts_per_tok=4,
    n_group=1, topk_group=1, norm_topk_prob=True, routed_scaling_factor=2.0,
    first_k_dense_replace=1, max_position_embeddings=8192, rms_norm_eps=1e-6,
    rope_theta=10000, tie_word_embeddings=False,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=64,
                      type="yarn"),
    hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
    mhc_h_res_clamp_max=30, deployment=dict(expert_parallel=1, rank=0))


def _real(*parts):
    return json.load(open(os.path.join(ROOT, "benchmark", *parts)))


def _tiny_copy(tree):
    cell = _real("workloads", CELL + ".json")
    mix = _real("traffic", cell["traffic"] + ".json")
    _write(tree, f"configs/{cell['config']}.json", TINY_XING)
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


def test_config_holds_the_catalog_row_but_for_what_is_reduced():
    cfg = _real("configs", "xing4.0-29b-a4b.d6.json")
    published = {"num_hidden_layers": 40, "first_k_dense_replace": 2}
    assert set(cfg["reduced"]) == set(published)
    for key, value in published.items():
        assert cfg["reduced"][key]["published"] == value
        assert cfg["reduced"][key]["here"] == cfg[key]
    widths = dict(hidden_size=3584, intermediate_size=9216,
                  moe_intermediate_size=1024, num_attention_heads=32,
                  q_lora_rank=768, kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=64,
                  num_experts_per_tok=4, n_shared_experts=1, n_group=1,
                  topk_group=1, routed_scaling_factor=2, vocab_size=131072,
                  hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
                  mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["deployment"]["expert_parallel"] == 1
    assert "num_nextn_predict_layers" in cfg["not_served"]
    assert len(cfg["assumed"]) >= 4


def test_cell_files_are_the_issue_s():
    cell = _real("workloads", CELL + ".json")
    assert cell["engine"] == {"num_slots": 32, "max_model_len": 8192,
                              "block_size": 16, "prefill_chunk": 1024,
                              "num_blocks": 24576, "host_kv_tier_bytes": 0}
    assert cell["traffic"] == "longprompt-sat" and cell["chips"] == 1
    assert cell["kind"] == "serve_typical"
    assert cell["trace"]["scopes"] == ["ragged_latent_attention", "gmm",
                                       "hc_mix"]
    bench = _real("..", "BENCHMARK.json")
    listed = [m["name"] for m in bench["per_layer"] if CELL in m["workloads"]]
    on_disk = [m for m in cell["metrics"] if m.endswith(".lx")
               and _real("metrics", m + ".json")["tier"] == "per_layer"]
    assert sorted(listed) == sorted(on_disk) and len(listed) == 12
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "out_tok_per_s")["workloads"]
    assert CELL in [w["name"] for w in bench["workloads"]]


def test_cell_runs_end_to_end_on_the_stand_in_device(tiny_tree, capsys):
    cell = _tiny_copy(tiny_tree)
    res, logs, _err = run_cell(capsys, CELL, seconds=2.0)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "out_tok_per_s"}
    closed = next(l for l in logs if l.get("window") == "closed")
    assert closed["compiles_in_window"] == 0
    assert closed["counters"]["executables_compiled"] == 0
    # every expert held: all of a row's 4 pairs are local
    assert res["observed"]["moe_pairs_per_row.lx"]["value"] == 4.0
    res, _logs, _err = run_cell(capsys, CELL, seconds=2.0, trace=1)
    got = set(res["metrics"])
    assert {"tick_mfu.lx", "tick_ms.lx", "device_idle_share.lx",
            "batch_occupancy.lx", "prefill_tick_share.lx",
            "host_share.lx"} <= got
    # no kernel scope is in a CPU trace: the rooflines are left out
    assert not {"mla_attn_roofline.lx", "moe_gmm_roofline.lx",
                "hc_mix_roofline.lx"} & got
    assert set(cell["metrics"]) >= got


def test_hc_work_reads_the_tick_spans(tiny_tree, capsys):
    """``work_hc_mix`` over a traced tiny run: the ``tick`` spans'
    ``hc_rows`` are 6 a live row (3 layers), and the work is the
    implementation-free count of that many row-connections."""
    from benchmark.lib import readers, xing4_0 as hcw
    _tiny_copy(tiny_tree)
    seen = {}
    orig = readers.Run.__init__

    def init(self, **kw):
        orig(self, **kw)
        seen["run"] = self

    readers.Run.__init__ = init
    try:
        run_cell(capsys, CELL, seconds=2.0, trace=1)
    finally:
        readers.Run.__init__ = orig
    run = seen["run"]
    ticks = hcw._hc_ticks(run, *run.interval())
    assert ticks and all(t["hc_rows"] % 6 == 0 for t in ticks)
    flops, nbytes = hcw.work_hc_mix(run, None)
    rows = sum(t["hc_rows"] for t in ticks)
    assert flops == rows * hcw.hc_flops_row(TINY_XING)
    assert np.isclose(nbytes, rows / 6 * hcw.hc_bytes_row(TINY_XING)
                      + len(ticks) * 6 * hcw.hc_param_bytes(TINY_XING))

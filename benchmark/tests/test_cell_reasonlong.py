"""The Phi-4-mini-flash cell's files load end to end: the real cell,
traffic and metric files of ``reason-long-sat.phi4-mini-flash-3.8b.d32``
driven through ``run.main`` on the CPU stand-in device, at a tiny
configuration of the same ``model_type`` and a tiny engine (the sizes
are the chip's; nothing else of the files is changed); the
configuration file against the catalog row it was taken from; and the
work functions against the tick spans a traced tiny run records."""
import json
import os

from conftest import ROOT, run_cell, _write

CELL = "reason-long-sat.phi4-mini-flash-3.8b.d32"
TINY_PHI = dict(
    model_type="phi4flash", vocab_size=512, hidden_size=128,
    intermediate_size=256, num_hidden_layers=8, num_attention_heads=8,
    num_key_value_heads=4, sliding_window=16, mb_per_layer=2,
    layer_norm_eps=1e-5, tie_word_embeddings=True, mamba_d_state=16,
    mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
    max_position_embeddings=4096)
# the catalog row's config (model-configs guide, architectures.jsonl)
CATALOG = {"embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
           "intermediate_size": 10240, "layer_norm_eps": 1e-05,
           "max_position_embeddings": 262144, "mb_per_layer": 2,
           "model_type": "phi4flash", "num_attention_heads": 40,
           "num_hidden_layers": 32, "num_key_value_heads": 20,
           "resid_pdrop": 0, "sliding_window": 512,
           "tie_word_embeddings": True, "mlp_bias": False,
           "lm_head_bias": False, "vocab_size": 200064}


def _real(*parts):
    return json.load(open(os.path.join(ROOT, "benchmark", *parts)))


def _tiny_copy(tree):
    cell = _real("workloads", CELL + ".json")
    mix = _real("traffic", cell["traffic"] + ".json")
    _write(tree, f"configs/{cell['config']}.json", TINY_PHI)
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


def test_config_is_the_catalog_row_whole():
    from benchmark.lib import phi4flash as lib
    cfg = _real("configs", "phi4-mini-flash-3.8b.d32.json")
    assert {k: cfg[k] for k in CATALOG} == CATALOG
    assert cfg["reduced"] == []
    assert lib.total_params(cfg) == 3_852_457_984
    assert len(cfg["assumed"]) >= 8


def test_cell_files_are_the_issue_s():
    cell = _real("workloads", CELL + ".json")
    assert cell["engine"] == {"num_slots": 96, "max_model_len": 8192,
                              "block_size": 16, "prefill_chunk": 256,
                              "num_blocks": 24576, "host_kv_tier_bytes": 0}
    assert cell["traffic"] == "reason-long-sat" and cell["chips"] == 1
    mix = _real("traffic", "reason-long-sat.json")
    assert (mix["clients"], mix["pool"], mix["max_total"]) == (144, 48, 8192)
    bench = _real("..", "BENCHMARK.json")
    assert CELL in next(m for m in bench["end_to_end"]
                        if m["name"] == "out_tok_per_s")["workloads"]
    listed = {m["name"] for m in bench["per_layer"] if CELL in m["workloads"]}
    on_disk = {m for m in cell["metrics"] if m.endswith(".rl")
               and _real("metrics", m + ".json")["tier"] == "per_layer"}
    assert listed == on_disk and len(listed) == 16


def test_cell_runs_end_to_end_on_the_stand_in_device(tiny_tree, capsys):
    cell = _tiny_copy(tiny_tree)
    res, logs, _err = run_cell(capsys, CELL, seconds=2.0)
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "out_tok_per_s"}
    closed = next(line for line in logs if line.get("window") == "closed")
    assert closed["compiles_in_window"] == 0
    res, _logs, _err = run_cell(capsys, CELL, seconds=2.0, trace=1)
    got = set(res["metrics"])
    assert {"tick_mfu.rl", "tick_ms.rl", "device_idle_share.rl",
            "batch_occupancy.rl", "host_share.rl",
            "cross_row_share.rl"} <= got
    # no kernel scope is in a CPU trace: the rooflines are left out
    assert not {"shared_kv_attn_roofline.rl", "window_attn_roofline.rl",
                "ssm_recurrent_roofline.rl", "ssm_chunk_roofline.rl"} & got
    assert 0 < res["metrics"]["cross_row_share.rl"]["value"] <= 100
    assert set(cell["metrics"]) >= got


def test_work_reads_the_tick_spans(tiny_tree, capsys):
    """The work functions over a traced tiny run: every ``tick`` span
    carries the family's counts, and the work is their
    implementation-free count."""
    from benchmark.lib import readers, phi4flash as lib
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
    ticks = lib._ticks(run, *run.interval())
    assert ticks and all(t["cross_rows"] <= 4 for t in ticks)
    flops, nbytes = lib.work_window(run, None)
    assert flops == lib.attn_flops_position(TINY_PHI) * sum(
        t["window_positions"] for t in ticks)
    seats = sum(t["ssm_seats"] for t in ticks)
    flops, nbytes = lib.work_ssm_recurrent(run, None)
    # 3 mamba layers (0, 2 and the memory layer 4), E 256, S 16
    assert flops == 3 * seats * 7 * 256 * 16
    assert nbytes == 3 * seats * (2 * 256 * 16 * 4 + (3 * 256 + 32) * 4)

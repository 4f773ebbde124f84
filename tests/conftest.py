"""Test harness config.

The unit suite runs on a deterministic 8-device CPU mesh (fast compiles +
multi-device sharding coverage — SURVEY.md §4's "multi-node simulated
locally" pattern): the platform and device count are set through
jax.config before the first device access. The chip is never a test
backend — one chip belongs to one process, and the suite needs eight
devices and spawns subprocesses; ``python chip_smoke.py`` through the
chip tool is the on-chip check.
"""
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)

import numpy as np
import pytest

# Suite tiering: tests measured >=~9s on the 8-device CPU mesh (r4
# --durations sweep) carry the ``slow`` marker. The FULL suite is the
# default; ``pytest -m "not slow"`` is the <8-min iteration tier.
# r6 re-sweep: rounds 4-6 added serving/spec/MoE tests without
# re-measuring — the >=~15s outliers from the r6 --durations run moved
# here so the tier keeps fitting its budget. (test_speculative.py's
# 61s rollback property stays tier-1: that file's own
# test_tier1_no_slow_marker guard pins every spec test to the tier.)
# r7 re-sweep (ragged mixed-batch serving): tier-1 measured 779s with
# the new test_ragged_batch.py aboard (slowest new test 6.6s — under
# the ~9s line), so no new entries.
# r8 re-sweep (MoE serving + fused dispatch): tier-1 measured 647-813s
# across two solo runs with the 16 new test_moe_serving.py tests
# aboard (562 passed; slowest new test 9.1s — the qwen2 ragged-ON/OFF
# engine pairing, right AT the line but the tier keeps >=57s of
# headroom), so no new entries.
# r10 re-sweep (int8 KV quantization): tier-1 measured 598s at the
# session baseline; the 19 new test_kv_quant.py tests add ~36s
# (slowest new test 3.7s — engine match-rate on GPT), and the two
# triaged pre-existing failures now pass (binomial x64 widen, fused
# MHA non-degenerate loss) with the interleaved-1F1B parity xfailed
# (tracked in test_pipeline.py). No new entries.
# r11 re-sweep (request tracing + SLO digests + goodput harness):
# the 15 new test_tracing.py tests + the test_metrics_docs.py lint
# guard measured ~19s total in a solo run; the slowest are the two
# fresh-interpreter subprocess probes (prometheus atexit twin,
# metric-docs registry walk — ~5-7s each), both under the ~9s line,
# so no new entries and tier-1 keeps its headroom under the 870s
# budget.
# r12 re-sweep (engine replication + disaggregated prefill): the 19
# new test_cluster.py tests measured ~36s total in a solo run
# (slowest 8.5s — the int8 disaggregated parity pairing, AT the line
# but each test builds 2-3 tiny engines so the cost is compile-bound
# and stable); no new entries, tier-1 measured 617s solo with the
# file aboard (618 passed) — ~250s of headroom under the 870s budget.
# r13 re-sweep (mega-kernelized decode tick + per-slot sampling): the
# 25 new test_decode_fused.py tests measured ~50s total solo (slowest
# 5.8s — the generate() jit-cache pin, which compiles one dense + one
# paged decode loop; everything else 2-5s tiny-engine compiles), all
# far under the ~9s line — no new entries. Existing serving tests pay
# a few extra ms per compile for the kernel census (HLO text parse);
# not measurable against the compile itself.
# r14 re-sweep (preemptive scheduling + host-DRAM KV tier): the 21
# new test_preemption.py tests measured ~35s total solo (slowest
# ~4s — the TP=2 swap-resume pairing; everything else 1-3s
# tiny-engine compiles), all far under the ~9s line — no new
# entries. test_tracing.py's outcome-labels test was updated in
# place (in-flight cancel now succeeds), no timing change.
# r15 re-sweep (fleet flight recorder): the 15 new
# test_flight_recorder.py tests measured ~25s total solo (slowest
# ~4s — the disaggregated merged-trace schema test building a 1+1
# cluster; profiler-window tests are pure host code), and the new
# stats-docs lint in test_metrics_docs.py is one more ~5s
# fresh-interpreter probe — all far under the ~9s line, no new
# entries. The per-compile executable_cost capture (cost_analysis on
# an already-compiled executable) is not measurable against the
# compile itself.
# r16 re-sweep (tree-structured speculation): the full
# test_spec_tree.py file measured ~72s solo, which — on top of the
# r13-r15 growth — pushed tier-1 past its 870s budget, so four tests
# carry in-file ``@pytest.mark.slow`` markers instead of entries
# here: the trained-chain accepted-length demonstration (trains a
# tiny model; the bench repeats the same demonstration at full
# scale) and the three heaviest parity pairings (chain-tree
# cluster+disagg 8.9s, generate()-level 5.8s, GPT engine 5.8s —
# each builds 2-4 engines and duplicates tier-1 coverage kept by the
# Llama/int8/TP=2/heads-disagg pairings). Remaining tier-1 cost
# ~45s, slowest ~6s.
# r17 re-sweep (fleet health engine): the 31 new test_health.py tests
# measured ~20s total solo (slowest ~3s — the HEALTH=0 bit-for-bit
# parity pinning a 1+1 disagg cluster twice; detector/incident units
# are pure host code on fake clocks), all far under the ~9s line — no
# new entries. The nf-logits probe rides the existing tick executable
# (one extra `any(~isfinite)` output), so serving tests pay no
# additional compile; A/B of test_serving.py with the monitor
# on/off/pre-PR landed inside run-to-run noise (+-8s on 60s), so the
# per-tick host work (detector updates, gauge sets, nf fetch) is not
# measurable either. Calibration caveat for future sweeps: the r17
# numbers came from a 1-CPU container where XLA's compile pool
# serializes — the full tier-1 measured ~1160s there (732 passed)
# while the multi-core boxes behind the earlier notes fit the 870s
# budget; compare durations against same-box baselines, not against
# the absolute seconds recorded above.
# r18 re-sweep (batched multi-LoRA serving): the 24 new test_lora.py
# tests measured ~71s total solo, slowest ~7s (the adapter-churn
# zero-recompile pin — 4 adapters through a 2-row pool plus a
# churn-back equivalence serve) — all under the ~9s line, so no new
# entries and no in-file markers. Costs are dominated by engine
# construction; the solo-reference serves are shared across the
# batched/spec/TP/cluster parity tests via a module-level cache, so
# adding a parity pairing reuses refs instead of re-serving them.
#
# r19 re-sweep (elastic autoscaling + live migration): the 19 new
# test_autoscale.py tests measured ~49s total solo, slowest ~6s (the
# int8 arm of the token-exact drain matrix — a solo reference engine
# plus a 2-replica cluster per variant) — all well under the ~9s
# line, so no in-file markers. The policy and loadgen-profile tests
# are model-free (<1s combined); the chaos tests keep max_new small
# and reuse one 2-replica cluster per scenario, so the budget stays
# engine-construction-bound. The accumulated r13-r19 growth did push
# the whole tier past its budget, so this round also re-tiers (the
# r16 pattern): a full --durations sweep on the session box (1-CPU,
# the r17 caveat class — 776 passed, 0 failed, 1100s) moved the 12
# heaviest unpinned tests below into the slow set, each a parity
# pairing or demo whose subsystem keeps cheaper tier-1 coverage
# (beam4-vs-numpy keeps 6 beam tests; chrome-trace-load keeps the
# handler/format/xplane trio; the TP sampling/sharded-step/int8
# trims keep the guard-pinned tp2-census + tp4-exact pair; the int8
# serving trims keep the kv-quant kernel parities and engine
# pairings; the qwen2 left-pad + predictor trims keep the Llama
# left-pad + predictor-beam paths). 12 moved < 19 added, so the
# tier's test count still grows this round. Durations annotated
# below are from the 1-CPU sweep; multi-core boxes run ~40-60% of
# that. Post-trim the tier measured 1015s on the same 1-CPU box
# (764 passed, 0 failed) — i.e. back inside budget everywhere but
# the serialized-compile 1-CPU class.
#
# r20 re-sweep (async tick pipeline): the 20 new test_async_tick.py
# tests measured ~77s total solo on the 1-CPU box, slowest 6.9s (the
# spec-tree arm of the async==sync parity matrix — a dual serve per
# arm) — all under the ~9s line, so no new entries and no in-file
# markers. Costs are dominated by the dual sync/async serves each
# parity case runs; the tiny Llama/GPT models are module-scoped
# fixtures, so adding a parity arm reuses the model build. The async
# engine itself adds no compile cost to other suites: depth-1 shares
# the sync ragged executable (executables_compiled stays 1, pinned by
# the matrix).
_SLOW_TESTS = {
    # r19 re-tier (1-CPU durations; see note above):
    "test_export_chrome_trace_loadable",                        # 10.5s
    "test_generation_predictor",                                # 9.8s
    "test_tp_sampling_parity",                                  # 9.5s
    "test_int8_teacher_forced_trajectory_floor",                # 8.8s
    "test_sharded_step_matches_single_program",                 # 8.4s
    "test_serving_gpt_family",                                  # 8.3s
    "test_beam4_matches_numpy_reference",                       # 8.1s
    "test_dryrun_moe_ep_metrics_export",                        # 7.6s
    "test_serving_int8_quantized_model",                        # 5.7s
    "test_quantize_for_inference_swaps_and_generates",          # 5.4s
    "test_left_padded_generate_qwen2_moe",                      # 4.8s
    "test_tp_int8_quantized",                                   # 4.2s
    # pre-r19 entries:
    "test_beam_equals_exhaustive_when_beam_is_vocab",           # 50s
    "test_ep_dropless_vs_capacity_loss_parity",                 # 35s
    "test_ep_dropless_output_matches_single_device",            # 35s
    "test_dropless_trains_and_reports_zero_drop",               # 24s
    "test_dropless_matches_padded_when_nothing_drops",          # 23s
    "test_trace_summary_has_op_table",                          # 15s
    "test_pipeline_parallel_train_batch_engine",
    "test_llama_pipe_grads_match_nonpipe",
    "test_moe_generate_smoke",
    "test_ring_attention_zigzag_matches_reference",
    "test_llama_greedy_matches_full_forward",
    "test_launch_hang_detection_restarts",
    "test_bert_pretrain_finetune_script",
    "test_gpt_greedy_matches_full_forward",
    "test_llama_pipe_loss_matches_nonpipe",
    "test_dryrun_multichip_8",
    "test_bert_script_amp_path",
    "test_zero_stage2_trains_at_parity_with_stage1",
    "test_qwen2_moe_recompute_trains",
    "test_cross_process_collectives",
    "test_gpt_pretrain_generate_script",
    "test_llama_pipe_trainstep_jit",
    "test_qwen2_moe_aux_loss_and_grads",
    "test_qwen2_moe_expert_parallel_mesh",
    "test_dataloader_mp_matches_serial",
    "test_three_gates_distinct_in_layer",
    "test_dataparallel_loss_parity_vs_single_process",
    "test_backward_matches_xla",
    "test_visualdl_callback_writes_scalars",
    "test_dataloader_mp_killed_worker_raises",
    "test_bert_classification_trains",
    "test_rpc_two_workers",
    "test_eos_stops_and_pads",
    "test_dataloader_multiprocess_workers",
    "test_llama_recompute_matches",
    "test_launch_failure_exhausts_restarts",
    "test_env_elastic_heartbeat_wiring",
    "test_pipeline_layer_engine_matches_sequential",
    "test_qwen2_moe_tiny_trains",
    "test_launch_elastic_restart",
    "test_dataloader_mp_worker_error_propagates",
    "test_lenet_fit_loss_decreases",
    "test_dataloader_mp_iterable_worker_sharding",
    "test_interleaved_1f1b_pp4_v2_matches_sequential_grads",
    "test_1f1b_train_matches_sequential_grads",
    "test_ulysses_attention_grad",
    "test_moe_routes_and_backprops",
    "test_export_generation_roundtrip",
    "test_1f1b_via_pipeline_parallel_train_batch",
    "test_deepseek_moe_tiny_trains",
}


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running (scan-heavy pipeline/moe/"
        "subprocess) tests; deselect with -m 'not slow'")


def pytest_collection_modifyitems(config, items):
    for item in items:
        if item.name.split("[")[0] in _SLOW_TESTS:
            item.add_marker(pytest.mark.slow)


@pytest.fixture(autouse=True)
def _seed():
    import paddle_tpu as paddle
    paddle.seed(2024)
    np.random.seed(2024)
    yield

"""The shape functions against hand counts at the published widths, and
a share of a peak over 100% raises instead of printing."""
import json
import os

import pytest

from conftest import ROOT
from benchmark.lib import peaks, readers, work


def cfg(name):
    return json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                       name + ".json")))


Q7 = cfg("qwen2-7b.d10")        # hidden 3584, 28/4 heads x 128, FFN 18944
Q15 = cfg("qwen2-1.5b.d4")      # hidden 1536, 12/2 heads x 128, FFN 8960

# weights one token is multiplied with in one decoder layer:
# q and o (h x h each), k and v (h x 4*128 each), gate, up, down (h x f)
Q7_LAYER = 2 * 3584 * 3584 + 2 * 3584 * 512 + 3 * 3584 * 18944
Q15_LAYER = 2 * 1536 * 1536 + 2 * 1536 * 256 + 3 * 1536 * 8960


def test_matmul_parameters():
    assert Q7_LAYER == 233_046_016 and Q15_LAYER == 46_792_704
    assert work.layer_matmul_params(Q7) == Q7_LAYER
    assert work.layer_matmul_params(Q15) == Q15_LAYER
    assert work.head_params(Q7) == 3584 * 152064
    assert work.matmul_params(Q7) == 10 * Q7_LAYER + 3584 * 152064
    # tied: the embedding counts once, as the head
    assert work.matmul_params(Q15) == 4 * Q15_LAYER + 1536 * 151936 \
        == 420_544_512


def test_serve_tokens_counts_rows_context_and_emits():
    # a 300-token prompt prefilled, then output tokens 0..4 seen: token 0
    # comes off the prefill, tokens 1..4 off decode rows at contexts
    # 301..304
    assert list(work.serve_tokens([(300, True, 0, 5)])) == [
        (300 + 4, 300 * 301 // 2 + 301 + 302 + 303 + 304, 5)]
    # the middle of a decode only: tokens 10..12 at contexts 110..112
    assert list(work.serve_tokens([(100, False, 10, 3)])) == [
        (3, 110 + 111 + 112, 3)]


def test_tick_flops_hand_count():
    rows, ctx, emits = 304, 45150 + 1210, 5
    want = (2 * rows * 10 * Q7_LAYER            # every layer's matmuls
            + 2 * emits * 3584 * 152064         # the head, emitting rows
            + 10 * 4 * 28 * 128 * ctx)          # QK^T and PV, live context
    assert work.tick_flops(Q7, [(300, True, 0, 5)]) == want


def test_ragged_attention_counts_live_rows_only():
    flops, nbytes = work.ragged_attn_work(Q7, [(300, True, 0, 5)], 128)
    assert flops == 10 * 4 * 28 * 128 * (45150 + 1210)
    kv_tok = 2 * 4 * 128 * 2            # K and V of one token, bf16
    q_io = 304 * 2 * 28 * 128 * 2       # q in, o out, per row
    prefill_kv = 128 + 256 + 300        # each chunk reads up to its end
    decode_kv = 301 + 302 + 303 + 304
    assert nbytes == 10 * (q_io + kv_tok * (prefill_kv + decode_kv))
    # nothing of the padded grid: an idle engine needs no work at all
    assert work.ragged_attn_work(Q7, [], 128) == (0, 0)


def test_fused_projections_hand_count():
    flops, nbytes = work.fused_proj_work(Q7, rows=20, ticks=3)
    assert flops == 2 * 20 * 10 * Q7_LAYER
    acts = 20 * (4 * 3584 + 3 * 18944 + 2 * 3584) * 2
    assert nbytes == 10 * (3 * Q7_LAYER * 2 + acts)


def test_flash_attention_causal_forward_and_backward():
    flops, nbytes = work.flash_attn_work(Q15, batch=2, seq=2048)
    fwd = 4 * 2 * 12 * 2048 * 2048 * 128 // 2       # causal half
    assert fwd == 25_769_803_776
    assert flops == 4 * 3 * fwd                     # 4 layers, fwd + 2x bwd
    qo, kv = 2 * 2048 * 12 * 128 * 2, 2 * 2048 * 2 * 128 * 2
    assert nbytes == 4 * ((2 * qo + 2 * kv) + (4 * qo + 4 * kv)) \
        == 352_321_536


def test_train_step_flops_per_token():
    attn = 4 * 4 * 1024.5 * 12 * 128                # 4 layers, mean context
    want = 6 * 420_544_512 + 3 * attn
    assert work.train_flops_token(Q15, 2048) == pytest.approx(want)
    assert want == pytest.approx(2.5988e9, rel=1e-4)    # ~2.6 GFLOP a token


def test_share_over_100_percent_raises():
    assert peaks.share(1.0, 2.0, "x") == 50.0
    assert peaks.share(1.0, 0.0, "x") is None
    with pytest.raises(ArithmeticError):
        peaks.share(2.1, 2.0, "x")


def test_mfu_reader_raises_rather_than_print_over_100():
    run = readers.Run(kind="train", cfg=Q15, mix={"seq_len": 2048},
                      chips=1, device_kind="TPU v5 lite", steps=1000,
                      tokens_per_step=4096, t_open=0.0, t_close=1.0)
    with pytest.raises(ArithmeticError):
        readers.step_mfu(run)       # 4.1 M tokens/s would be 54x the peak
    run.steps = 12                  # ~49k tokens/s
    assert 60.0 < readers.step_mfu(run) < 70.0


def test_reader_with_nothing_to_read_returns_none():
    run = readers.Run(kind="serve", cfg=Q7, mix={}, cell={"engine": {}},
                      chips=1, device_kind="TPU v5 lite", t_open=0.0,
                      t_close=1.0)
    from benchmark import run as bench
    for m in ("out_tokens_per_s", "tick_mfu", "batch_occupancy",
              "device_idle_share"):
        assert bench.read_metric({"reader": "lib.readers:" + m}, run) is None
    for of in ("window", "process", "build"):
        assert readers.hbm_gb(run, of) is None
    assert readers.kernel_roofline(
        run, ["x"], "lib.readers:work_ragged_attn") is None
    with pytest.raises(AttributeError):
        bench.read_metric({"reader": "lib.readers:no_such_reader"}, run)
    with pytest.raises(ImportError):
        bench.read_metric({"reader": "lib.no_such_module:f"}, run)

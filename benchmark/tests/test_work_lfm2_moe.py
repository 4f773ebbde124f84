"""``lib/lfm2_moe.py``'s counts against hand-worked cases at the
published widths of ``lfm2-24b-a2b.d9``."""
import json
import os

from conftest import ROOT
from benchmark.lib import lfm2_moe, mla_moe

L = json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                "lfm2-24b-a2b.d9.json")))
CONV = 2048 * 6144 + 3 * 2048 + 2048 * 2048
ATTN = 2048 * 2048 + 2 * 2048 * 512 + 2048 * 2048 + 2 * 64
EXPERT = 3 * 2048 * 1536
DENSE = 3 * 2048 * 11776
GATE = 2048 * 64 + 64


def test_parameters_are_the_issue_s_arithmetic():
    assert (CONV, ATTN, EXPERT) == (16_783_360, 10_485_888, 9_437_184)
    assert (GATE, DENSE) == (131_136, 72_351_744)
    assert lfm2_moe.conv_params(L) == CONV
    assert lfm2_moe.attn_params(L) == ATTN
    assert lfm2_moe.expert_params(L) == EXPERT
    assert lfm2_moe.attn_layers(L) == 2
    # 7 conv and 2 attention mixers, one dense FFN, 8 gates
    assert lfm2_moe.row_params(L) == 7 * CONV + 2 * ATTN + DENSE + 8 * GATE
    # with all 64 experts of 8 layers, the norms and the tied embedding:
    # the 5,177,950,976 parameters of the cut
    total = lfm2_moe.row_params(L) + 8 * 64 * EXPERT + 9 * 2 * 2048 \
        + 65536 * 2048 + 2048
    assert total == 5_177_950_976
    assert lfm2_moe.kv_bytes_token(L) == 4096
    assert lfm2_moe.state_bytes_slot(L) == 57_344


def test_tick_flops_hand_count():
    # a 1000-token prompt prefilled and 3 tokens out: 1002 rows, token 0
    # off the prefill, contexts 1001 and 1002 for the decode rows; four
    # pairs a row an expert layer
    reqs = [(1000, True, 0, 3)]
    rows, ctx, emits = 1002, 1000 * 1001 // 2 + 1001 + 1002, 3
    pairs = rows * 4 * 8
    want = (2 * rows * lfm2_moe.row_params(L) + 2 * emits * 2048 * 65536
            + 2 * 4 * 32 * 64 * ctx + 2 * pairs * EXPERT)
    assert lfm2_moe.tick_flops(L, reqs, pairs) == want
    # the experts' part is the counter's, not an expectation
    assert lfm2_moe.tick_flops(L, reqs, 0) == want - 2 * pairs * EXPERT


def test_attention_work_counts_the_two_attention_layers():
    # one decode row at context 4097 (prompt 4096, output token 1): K
    # and V of 8 heads x 64 read once in each of the 2 attention layers
    flops, nbytes = lfm2_moe.ragged_attn_work(L, [(4096, False, 1, 1)], 256)
    assert flops == 2 * 4 * 32 * 64 * 4097
    assert nbytes == 2 * (4097 * 2 * 8 * 64 * 2 + 2 * 32 * 64 * 2)
    assert nbytes == 4097 * lfm2_moe.kv_bytes_token(L) + 2 * 8192
    # a 600-token prompt in chunks of 256: read up to each chunk's end
    flops, nbytes = lfm2_moe.ragged_attn_work(L, [(600, True, 0, 1)], 256)
    assert flops == 2 * 4 * 32 * 64 * (600 * 601 // 2)
    assert nbytes == (256 + 512 + 600) * 4096 + 600 * 2 * 8192


def test_grouped_matmul_work_is_the_shared_function_s():
    # a full tick: 384 rows x 4 pairs in each of 8 layers, all 64 touched
    pairs, touched = 8 * 1536, 8 * 64
    flops, nbytes = mla_moe.moe_gmm_work(L, pairs, touched)
    assert flops == 2 * pairs * EXPERT
    assert EXPERT * 2 == 18_874_368
    assert nbytes == touched * 18_874_368 + pairs * 2 * 2048 * 2

"""The parameter, byte and FLOP numbers of ``solar-open2-250b.ep16.d8``
(ISSUE 32), hand-worked, against ``lib/solar_open2.py``."""
import json
import os

from conftest import ROOT
from benchmark.lib import solar_open2 as lib


def _cfg():
    return json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "solar-open2-250b.ep16.d8.json")))


def test_parameters_of_the_cut():
    cfg = _cfg()
    # q, k, v, o 4 x 4096 x 8192; decay and gate 2 x (4096 x 128 + 128
    # x 8192); b_proj 4096 x 64; three filters 3 x 8192 x 4; A_log 64,
    # dt_bias 8192, o_norm 128
    assert lib.kda_params(cfg) == 134217728 + 3145728 + 262144 + 98304 \
        + 8384 == 137732288
    # q, gate, o 3 x 4096 x 8192; k, v 2 x 4096 x 1024
    assert lib.gqa_params(cfg) == 100663296 + 8388608 == 109051904
    assert lib.expert_params(cfg) == 3 * 4096 * 1280 == 15728640
    # 20 held + 1 shared expert, the 320-wide router and its bias, two
    # norms
    assert lib.rest_params(cfg) == 21 * 15728640 + 4096 * 320 + 320 \
        + 8192 == 331620672
    assert lib.kda_params(cfg) + lib.rest_params(cfg) == 469352960
    assert lib.gqa_params(cfg) + lib.rest_params(cfg) == 440672576
    assert lib.total_params(cfg) == 2 * (440672576 + 3 * 469352960) \
        + 2 * 24576 * 4096 + 4096 == 3898793600
    assert (lib.kda_layers(cfg), lib.gqa_layers(cfg)) == (6, 2)


def test_bytes_a_slot_and_a_token():
    cfg = _cfg()
    # 6 layers x (64 x 128 x 128 x 4 B + 3 x 24576 x 2 B)
    assert lib.state_bytes_slot(cfg) == 6 * (4194304 + 147456) == 26050560
    assert lib.state_bytes_layer(cfg) == 4194304
    # 2 gqa layers x K and V x 8 heads x 128 x 2 B
    assert lib.kv_bytes_token(cfg) == 8192
    # q, k, v, g, o of 64 x 128 values and beta's 64, at 2 B
    assert lib.row_operand_bytes(cfg) == (5 * 8192 + 64) * 2 == 82048


def test_flops_of_the_two_forms():
    cfg = _cfg()
    # 7 d^2 a head a row: decay 1, S^T k 2, the update 2, S^T q 2
    assert lib.recurrent_flops_row(cfg) == 7 * 16384 * 64 == 7340032
    # a 64-row sub-chunk of one head: 6 C d^2 + 8 C^2 d
    assert 6 * 64 * 16384 + 8 * 4096 * 128 == 6291456 + 4194304 == 10485760
    assert lib.chunk_flops_row(cfg) == 10485760 // 64 * 64 == 10485760
    # 96 one-row seats of one layer: state once each way, the rows
    assert lib.kda_recurrent_work(cfg, 96) == (
        96 * 7340032, 96 * (2 * 4194304 + 82048))
    # one 256-row chunk of one layer: memory-bound on a v5e (36 us of
    # bytes against 14 us of FLOPs)
    flops, nbytes = lib.kda_chunk_work(cfg, 1, 256)
    assert (flops, nbytes) == (256 * 10485760, 2 * 4194304 + 256 * 82048)
    assert nbytes / 819e9 > flops / 197e12


def test_tick_flops_by_hand():
    cfg = _cfg()
    # one request: 100 prompt rows prefilled, output tokens 0..9; 40
    # local pairs
    reqs = [(100, True, 0, 10)]
    rows, emits = 100 + 9, 10
    ctx = 100 * 101 // 2 + 9 * 100 + (1 + 9) * 9 // 2
    per_row = 2 * (6 * 137732288 + 2 * 109051904
                   + 8 * (15728640 + 4096 * 320)) + 6 * 7340032
    want = rows * per_row + 2 * emits * 4096 * 24576 \
        + 2 * 4 * 64 * 128 * ctx + 2 * 40 * 15728640
    assert lib.tick_flops(cfg, reqs, 40) == want
    # the gqa layers' attention work is at head size 128, 2 layers
    flops, nbytes = lib.ragged_attn_work(cfg, reqs, 256)
    assert flops == 2 * 4 * 64 * 128 * ctx
    assert nbytes == 2 * (rows * 2 * 64 * 128 * 2
                          + 4096 * (100 + 9 * 100 + 45))

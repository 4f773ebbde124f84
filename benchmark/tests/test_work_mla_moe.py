"""``lib/mla_moe.py``'s counts against hand-worked cases at the published
widths of ``gigachat3.1-702b.ep16.d5``."""
import json
import os

import pytest

from conftest import ROOT
from benchmark.lib import mla_moe


def cfg(name):
    return json.load(open(os.path.join(ROOT, "benchmark", "configs",
                                       name + ".json")))


G = cfg("gigachat3.1-702b.ep16.d5")
# hidden 7168, 64 heads, q rank 1536, kv rank 512, 128 | 64 | 192 head dims
ATTN = (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576 + 512 * 64 * 320
        + 64 * 192 * 7168)
EXPERT = 3 * 7168 * 2048
DENSE = 3 * 7168 * 18432
GATE = 7168 * 256


def test_parameters_are_the_issue_s_arithmetic():
    assert ATTN == 132_579_328 and EXPERT == 44_040_192
    assert mla_moe.attn_params(G) == ATTN
    assert mla_moe.expert_params(G) == EXPERT
    # one dense layer and four expert layers: attention everywhere, the
    # dense FFN once, shared expert and the 256-wide gate four times
    assert mla_moe.row_params(G) == 5 * ATTN + DENSE + 4 * (EXPERT + GATE)
    # with the 16 experts held a layer and the sliced embedding and
    # head: the 4.29 B parameters of the cut
    total = mla_moe.row_params(G) + 4 * 16 * EXPERT + 2 * 7168 * 16032
    assert round(total / 1e9, 2) == 4.29


def test_tick_flops_hand_count():
    # a 1000-token prompt prefilled and 3 tokens out: 1002 rows, token 0
    # off the prefill, contexts 1001 and 1002 for the decode rows
    reqs = [(1000, True, 0, 3)]
    rows, ctx, emits = 1002, 1000 * 1001 // 2 + 1001 + 1002, 3
    want = (2 * rows * mla_moe.row_params(G) + 2 * emits * 7168 * 16032
            + 5 * 2 * 64 * (192 + 192) * ctx + 2 * 501 * EXPERT)
    assert mla_moe.tick_flops(G, reqs, pairs_local=501) == want
    # the experts' part is the counter's, not an expectation
    assert mla_moe.tick_flops(G, reqs, 0) == want - 2 * 501 * EXPERT


def test_mla_attention_work_is_the_absorbed_form():
    # one decode row at context 4097 (prompt 4096, output token 1)
    flops, nbytes = mla_moe.mla_attn_work(G, [(4096, False, 1, 1)], 512)
    assert flops == 5 * 2 * 64 * (576 + 512) * 4097
    assert nbytes == 5 * (4097 * 1152 + 64 * (576 + 512) * 2)
    # a 1100-token prompt in chunks of 512: the latent is read up to
    # each chunk's end (512, 1024, 1100), once a chunk
    flops, nbytes = mla_moe.mla_attn_work(G, [(1100, True, 0, 1)], 512)
    assert flops == 5 * 2 * 64 * 1088 * (1100 * 1101 // 2)
    assert nbytes == 5 * ((512 + 1024 + 1100) * 1152
                          + 1100 * 64 * 1088 * 2)


def test_grouped_matmul_work_follows_pairs_and_touched_experts():
    flops, nbytes = mla_moe.moe_gmm_work(G, pairs=272, touched=60)
    assert flops == 2 * 3 * 7168 * 2048 * 272
    assert nbytes == 60 * EXPERT * 2 + 272 * 2 * 7168 * 2
    assert EXPERT * 2 == pytest.approx(88.1e6, rel=1e-3)


class _Run:
    def __init__(self, events, **kw):
        self.phase_events = events
        self.__dict__.update(kw)


def _tick(t, **args):
    return {"name": "tick", "tid": 0, "ph": "X", "t0": t, "dur": 0.01,
            "args": dict(args, exec="decode")}


def test_readers_leave_the_metric_out_without_the_program_s_counts():
    run = _Run([_tick(1.0, rows=5)], cfg=G, t_open=0.0, t_close=9.0,
               counters={"decode_steps": 3}, records=[], trace=None)
    assert mla_moe.tick_mfu(run) is None
    assert mla_moe.expert_load_max_over_mean(run) is None
    assert mla_moe.moe_gmm_roofline(run, ["megablox_gmm"]) is None
    run = _Run(None, cfg=G, t_open=0.0, t_close=9.0, counters={},
               records=[], trace=None)
    assert mla_moe.expert_load_max_over_mean(run) is None


def test_expert_load_reader_weights_ticks_by_their_pairs():
    ticks = [_tick(1.0, moe_pairs=128, moe_touched=60, moe_hot=4),
             _tick(2.0, moe_pairs=64, moe_touched=40, moe_hot=5),
             _tick(20.0, moe_pairs=64, moe_touched=40, moe_hot=50)]
    run = _Run(ticks, cfg=G, t_open=0.0, t_close=9.0)
    # 4 expert layers x 16 held = 64 groups; the third tick is outside
    assert mla_moe.expert_load_max_over_mean(run) == (4 + 5) * 64 / 192

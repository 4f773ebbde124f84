"""The one traffic generator: the same sizes and gaps in the same order
for every seed, token ids from the seed, seeds past 2**31."""
import numpy as np
import pytest

from benchmark.lib import traffic, weights

CHAT = dict(pool=48, prompt_len=dict(median=128, sigma=0.8, min=16, max=768),
            output_len=dict(median=32, sigma=0.6, min=8, max=192),
            max_total=1024)


def test_lognormal_quantiles_are_the_distribution():
    q = traffic.lognormal_quantiles(CHAT["prompt_len"], 48)
    assert len(q) == 48 and q.min() >= 16 and q.max() <= 768
    assert list(q) == sorted(q)
    assert abs(np.median(q) - 128) <= 4
    # sigma 0.8: the 84th percentile is about median * e^0.8
    assert q[int(0.84 * 48)] == pytest.approx(128 * np.exp(0.8), rel=0.1)


def test_exponential_gaps_have_the_rate_as_their_mean():
    g = traffic.exponential_quantiles(0.5, 40)
    assert g.mean() == pytest.approx(2.0)
    assert g.sum() == pytest.approx(80.0)       # one epoch is pool / rate
    assert (g > 0).all()


def test_every_seed_offers_the_same_work_at_the_same_moments():
    mix = dict(CHAT, loop="open", rate_per_s=0.5)
    a = traffic.RequestStream(mix, 1000, 1)
    b = traffic.RequestStream(mix, 1000, 2**31 + 99)
    ea = [a.next() for _ in range(100)]
    eb = [b.next() for _ in range(100)]
    assert [(len(p), o, g) for p, o, g in ea] == \
        [(len(p), o, g) for p, o, g in eb]
    assert not np.array_equal(ea[0][0], eb[0][0])       # other token ids
    # one epoch is the pool itself, each size and each gap once
    assert sorted((len(p), o) for p, o, _g in ea[:48]) == \
        sorted(traffic.size_pool(mix))
    assert sorted(g for _p, _o, g in ea[:48]) == pytest.approx(
        sorted(traffic.exponential_quantiles(0.5, 48)))
    assert all(len(p) + o <= 1024 for p, o, _g in ea)
    # past one epoch the order changes: no 48-periodic replay
    assert [len(p) for p, _o, _g in ea[:48]] != \
        [len(p) for p, _o, _g in ea[48:96]]


def test_stream_is_reproducible_from_the_seed():
    mix = dict(CHAT, loop="closed", clients=16)
    a = traffic.RequestStream(mix, 152064, 2**31 + 5)
    b = traffic.RequestStream(mix, 152064, 2**31 + 5)
    for _ in range(100):            # past one epoch
        (pa, oa, ga), (pb, ob, gb) = a.next(), b.next()
        assert np.array_equal(pa, pb) and (oa, ga) == (ob, gb) and ga == 0.0
        assert pa.min() >= 1 and pa.max() < 152064


def test_token_rows_differ_and_repeat_from_the_seed():
    rows = traffic.TokenRows(64, 512, 2**31 + 1)
    x0, y0 = rows[0]
    x1, _ = rows[1]
    assert np.array_equal(x0[1:], y0[:-1])          # labels: the next token
    assert not np.array_equal(x0, x1)
    again = traffic.TokenRows(64, 512, 2**31 + 1)[0]
    assert np.array_equal(again[0], x0)
    assert not np.array_equal(traffic.TokenRows(64, 512, 7)[0][0], x0)


def test_weights_are_a_function_of_seed_and_leaf_name():
    shapes = {"llama.layers.0.mlp.up_proj.weight": (8, 16),
              "llama.layers.0.input_layernorm.weight": (8,),
              "llama.layers.1.mlp.up_proj.weight": (8, 16)}
    a = weights.make(shapes, 2**31 + 3)
    one = weights.make({"llama.layers.1.mlp.up_proj.weight": (8, 16)},
                       2**31 + 3)
    k = "llama.layers.1.mlp.up_proj.weight"
    assert np.array_equal(np.asarray(a[k], np.float32),
                          np.asarray(one[k], np.float32))
    assert not np.array_equal(
        np.asarray(a[k], np.float32),
        np.asarray(a["llama.layers.0.mlp.up_proj.weight"], np.float32))
    assert np.all(np.asarray(
        a["llama.layers.0.input_layernorm.weight"], np.float32) == 1.0)
    other = weights.make(shapes, 3)         # 2**31 + 3 is not 3
    assert not np.array_equal(np.asarray(a[k], np.float32),
                              np.asarray(other[k], np.float32))

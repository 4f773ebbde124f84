"""The lfm2_moe family's plain reference against a second, differently
written evaluation of one ``conv`` layer and one expert layer at a tiny
size (numpy, float64, loops over positions and tokens), against the
program's model in the served precision, and the float8 control. (The
float32 comparisons and the engine's slot state are tier-1:
``tests/test_lfm2_moe.py``.)"""
import json
import os

import numpy as np

from conftest import ROOT

TINY_LFM2 = dict(
    model_type="lfm2_moe", vocab_size=512, hidden_size=256,
    intermediate_size=192, moe_intermediate_size=64, num_hidden_layers=5,
    num_attention_heads=4, num_key_value_heads=2, num_experts=8,
    num_experts_per_tok=2, num_dense_layers=1,
    layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    conv_L_cache=3, conv_bias=False, norm_eps=1e-5, norm_topk_prob=True,
    use_expert_bias=True, routed_scaling_factor=1,
    max_position_embeddings=4096,
    rope_parameters=dict(rope_theta=1000000, rope_type="default"),
    tie_word_embeddings=True, deployment=dict(expert_parallel=1))
SEED = 2**31 + 13
H = TINY_LFM2["hidden_size"]    # 2 kv heads x 64: a flat pool's lane tile


def _layer(i):
    import jax.numpy as jnp
    from benchmark.lib import weights
    from benchmark.models import lfm2_moe as family
    pre = f"model.layers.{i}."
    w = weights.make(family.layer_shapes(TINY_LFM2, i), SEED, jnp.float32)
    return {k[len(pre):]: v for k, v in w.items()}


def _np(w):
    return {k: np.asarray(v, np.float64) for k, v in w.items()}


def test_conv_layer_against_a_loop_over_positions():
    import jax.numpy as jnp
    from benchmark.reference import lfm2_moe as ref
    w = _layer(2)
    x = np.random.default_rng(0).normal(size=(11, H))
    got = np.asarray(ref.short_conv(jnp.asarray(x, jnp.float32), w,
                                    TINY_LFM2, False))
    n = _np(w)
    bcz = x @ n["conv.in_proj.weight"]
    b, c, z = bcz[:, :H], bcz[:, H:2 * H], bcz[:, 2 * H:]
    out = np.zeros_like(x)
    for t in range(11):
        acc = np.zeros(H)
        for j in range(3):
            src = t - 2 + j
            if src >= 0:
                acc += n["conv.conv.weight"][:, j] * b[src] * z[src]
        out[t] = (c[t] * acc) @ n["conv.out_proj.weight"]
    np.testing.assert_allclose(got, out, atol=2e-5)


def test_expert_layer_against_a_loop_over_tokens():
    import jax.numpy as jnp
    from benchmark.reference import lfm2_moe as ref
    w = _layer(3)
    x = np.random.default_rng(1).normal(size=(9, H))
    got = np.asarray(ref.experts(jnp.asarray(x, jnp.float32), w, TINY_LFM2,
                                 False))
    n = _np(w)
    out = np.zeros_like(x)
    for t in range(9):
        s = 1.0 / (1.0 + np.exp(-(x[t] @ n["feed_forward.gate.weight"])))
        order = np.argsort(-(s + n["feed_forward.expert_bias"]),
                           kind="stable")[:2]
        for e in order:
            gu = x[t] @ n["feed_forward.experts.gate_up_proj"][e]
            g, u = gu[:64], gu[64:]
            y = (g / (1.0 + np.exp(-g)) * u) \
                @ n["feed_forward.experts.down_proj"][e]
            out[t] += s[e] / (s[order].sum() + 1e-6) * y
    np.testing.assert_allclose(got, out, atol=2e-5)


def test_forward_logits_match_the_model_and_the_control_departs():
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from benchmark.lib import weights
    from benchmark.models import lfm2_moe as family
    from benchmark.reference import lfm2_moe as ref
    ids = np.random.default_rng(2).integers(0, 512, (2, 64))
    model = family.build(TINY_LFM2, SEED, training=False)
    got = np.asarray(model(paddle.to_tensor(ids)).numpy(), np.float32)
    w = weights.make(family.leaf_shapes(TINY_LFM2), SEED)
    small = family._small(TINY_LFM2)
    want = np.asarray(ref.forward(w, jnp.asarray(ids), small))
    low = np.asarray(ref.forward(w, jnp.asarray(ids), small, lowp=True))
    assert got.shape == want.shape == (2, 64, 512)
    # bf16 program against the f32 reference
    assert np.abs(got - want).max() < 0.05 * np.abs(want).max()
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.9
    # one precision down, the same mathematics lies further off than
    # the program does
    assert np.abs(low - want).max() > 2 * np.abs(got - want).max()


def test_configuration_keeps_the_published_three_to_one():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "lfm2-24b-a2b.d9.json")))
    kinds = cfg["layer_types"]
    assert len(kinds) == cfg["num_hidden_layers"] == 9
    assert kinds[0] == "conv" and cfg["num_dense_layers"] == 1
    sparse = kinds[1:]
    assert sparse == ["full_attention", "conv", "conv", "conv"] * 2
    # published: 30 conv to 10 attention
    assert sparse.count("conv") == 3 * sparse.count("full_attention")
    assert set(cfg["reduced"]) == {"num_hidden_layers", "num_dense_layers",
                                   "layer_types"}
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 40
    assert cfg["reduced"]["num_dense_layers"]["published"] == 2
    widths = dict(hidden_size=2048, intermediate_size=11776,
                  moe_intermediate_size=1536, num_attention_heads=32,
                  num_key_value_heads=8, num_experts=64,
                  num_experts_per_tok=4, vocab_size=65536, conv_L_cache=3,
                  norm_eps=1e-5, routed_scaling_factor=1)
    assert {k: cfg[k] for k in widths} == widths
    assert cfg["deployment"]["expert_parallel"] == 1
    for key in ("source", "precision", "assumed"):
        assert cfg[key]

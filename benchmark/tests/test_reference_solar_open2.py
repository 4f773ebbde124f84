"""The plain reference of the ``solar_open2`` family against a second,
differently written evaluation: its ``kda`` mixer with the recurrence
unrolled in numpy float64, token by token and head by head, and its
convolution as a direct sum; and the configuration file's layer kinds
in the published 3 : 1."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np

from conftest import ROOT
from benchmark.models import solar_open2 as family
from benchmark.reference import solar_open2 as ref

TINY_SOLAR = dict(
    model_type="solar_open2", vocab_size=512, hidden_size=64,
    intermediate_size=96, moe_intermediate_size=32, num_hidden_layers=8,
    num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    gqa_interval=3, gqa_layers=[0, 4],
    linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                            num_heads=4, num_kv_heads=None),
    kda_use_full_proj=False, kda_allow_neg_eigval=True, use_rope=False,
    use_gqa_gate=True, n_routed_experts=4, n_shared_experts=1,
    num_experts_per_tok=2, norm_topk_prob=True, routed_scaling_factor=1,
    first_k_dense_replace=0, max_position_embeddings=8192,
    rms_norm_eps=1e-5, tie_word_embeddings=False,
    deployment=dict(expert_parallel=4, rank=0))


def _kda_float64(x, w, cfg):
    """The mixer as loops over tokens, heads and taps, in float64."""
    la = cfg["linear_attn_config"]
    heads, d, taps = la["num_heads"], la["head_dim"], \
        la["short_conv_kernel_size"]
    w = {k: np.asarray(v, np.float64) for k, v in w.items()}
    p = "linear_attn."
    t = len(x)

    def silu(a):
        return a / (1.0 + np.exp(-a))

    def branch(name):
        y = x @ w[p + name + "_proj.weight"]
        filt = w[p + name + "_conv1d.weight"]
        out = np.zeros_like(y)
        for i in range(t):
            for j in range(taps):
                src = i - (taps - 1) + j
                if src >= 0:
                    out[i] += filt[:, j] * y[src]
        return silu(out).reshape(t, heads, d)

    def unit(a):
        return a / np.sqrt((a * a).sum(-1, keepdims=True) + 1e-6)

    q, k, v = unit(branch("q")) * d ** -0.5, unit(branch("k")), branch("v")
    dt = x @ w[p + "f_a_proj.weight"] @ w[p + "f_b_proj.weight"] \
        + w[p + "dt_bias"]
    g = -np.exp(w[p + "A_log"])[None, :, None] \
        * np.log1p(np.exp(dt)).reshape(t, heads, d)
    beta = 2.0 / (1.0 + np.exp(-(x @ w[p + "b_proj.weight"])))
    o = np.zeros((t, heads, d))
    for h in range(heads):
        s = np.zeros((d, d))
        for i in range(t):
            s = np.exp(g[i, h])[:, None] * s
            s = (np.eye(d) - beta[i, h] * np.outer(k[i, h], k[i, h])) @ s \
                + beta[i, h] * np.outer(k[i, h], v[i, h])
            o[i, h] = s.T @ q[i, h]
    o = o / np.sqrt((o * o).mean(-1, keepdims=True) + cfg["rms_norm_eps"]) \
        * w[p + "o_norm.weight"]
    gate = 1.0 / (1.0 + np.exp(-(x @ w[p + "g_a_proj.weight"]
                                 @ w[p + "g_b_proj.weight"])))
    return (o.reshape(t, heads * d) * gate) @ w[p + "o_proj.weight"]


def test_kda_layer_against_the_unrolled_float64_recurrence():
    cfg = TINY_SOLAR
    pre = "model.layers.1."
    w = {k[len(pre):]: v for k, v in family.make_leaves(
        family.layer_shapes(cfg, 1), 2**31 + 3).items()}
    x = np.random.default_rng(0).standard_normal((50, 64))
    with jax.default_matmul_precision("highest"):
        got = np.asarray(ref.kda(jnp.asarray(x, jnp.float32), w,
                                 family._small(cfg), False))
    want = _kda_float64(x, w, cfg)
    assert np.abs(want).max() > 1e-3
    np.testing.assert_allclose(got, want, atol=2e-6 * np.abs(want).max()
                               + 1e-7)


def test_decay_leaves_cover_the_published_range():
    w = family.make_leaves({"model.layers.1.linear_attn.A_log": (4096,),
                            "model.layers.1.linear_attn.dt_bias": (4096,),
                            "model.layers.1.linear_attn.b_proj.weight":
                                (8, 8)}, 5)
    a = np.exp(np.asarray(w["model.layers.1.linear_attn.A_log"], np.float64))
    dt = np.log1p(np.exp(np.asarray(
        w["model.layers.1.linear_attn.dt_bias"], np.float64)))
    assert 0.99 <= a.min() < 1.2 and 15.5 < a.max() <= 16.1
    assert 9e-4 <= dt.min() < 1.2e-3 and 0.085 < dt.max() <= 0.102
    # every other leaf is weights.make's own
    assert abs(float(np.asarray(
        w["model.layers.1.linear_attn.b_proj.weight"],
        np.float32).std()) - 0.02) < 0.01


def test_configuration_file_keeps_the_published_ratio_and_widths():
    cfg = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "solar-open2-250b.ep16.d8.json")))
    kinds = ["gqa" if i in cfg["gqa_layers"] else "kda"
             for i in range(cfg["num_hidden_layers"])]
    assert kinds == ["gqa", "kda", "kda", "kda"] * 2
    assert kinds.count("kda") == 3 * kinds.count("gqa")
    assert cfg["reduced"]["gqa_layers"]["published"] == list(range(0, 48, 4))
    assert (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["num_experts_per_tok"], cfg["n_shared_experts"]) \
        == (4096, 1280, 8, 1)
    assert cfg["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert (cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["head_dim"], cfg["use_rope"]) == (64, 8, 128, False)
    assert family.share(cfg) == (320, 0, 20)
    assert sorted(cfg["reduced"]) == ["gqa_layers", "n_routed_experts",
                                      "num_hidden_layers", "vocab_size"]
    # every number of the catalog row's config, under the same key
    row = next(json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")
        if '"Solar-Open2-250B"' in line) \
        if os.path.exists("/opt/skills/guides/model-configs/"
                          "architectures.jsonl") else None
    if row is not None:
        for key, value in row["config"].items():
            if key not in cfg["reduced"]:
                assert cfg[key] == value, key
        assert cfg["source"] == row["source_url"]

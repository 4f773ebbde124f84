"""The deepseek_v3 family's plain reference against the program's model
at a tiny configuration of the same ``model_type``: forward logits in
the served precision, the float8 control, and the chip's share. (The
float32 comparisons, the cache path and the share test are tier-1:
``tests/test_deepseek_v3.py``.)"""
import numpy as np

TINY_V3 = dict(
    model_type="deepseek_v3", vocab_size=512, hidden_size=128,
    intermediate_size=192, moe_intermediate_size=64, num_hidden_layers=3,
    num_attention_heads=4, q_lora_rank=64, kv_lora_rank=64,
    qk_nope_head_dim=32, qk_rope_head_dim=16, v_head_dim=48,
    n_shared_experts=1, n_routed_experts=4, num_experts_per_tok=4,
    n_group=4, topk_group=2, norm_topk_prob=True, routed_scaling_factor=2.5,
    first_k_dense_replace=1, max_position_embeddings=4096, rms_norm_eps=1e-6,
    rope_theta=100000,
    rope_scaling=dict(beta_fast=32, beta_slow=1, factor=64, mscale=1,
                      mscale_all_dim=1, original_max_position_embeddings=64,
                      rope_type="yarn"),
    tie_word_embeddings=False, deployment=dict(expert_parallel=4, rank=2))


def _both(cfg, seed, ids):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from benchmark.lib import weights
    from benchmark.models import deepseek_v3 as family
    from benchmark.reference import deepseek_v3 as ref
    model = family.build(cfg, seed, training=False)
    got = np.asarray(model(paddle.to_tensor(ids)).numpy(), np.float32)
    w = weights.make(family.leaf_shapes(cfg), seed)
    small = family._small(cfg)
    return got, np.asarray(ref.forward(w, jnp.asarray(ids), small)), \
        np.asarray(ref.forward(w, jnp.asarray(ids), small, lowp=True))


def test_forward_logits_match_the_model_and_the_control_departs():
    ids = np.random.default_rng(0).integers(0, 512, (2, 64))
    got, want, low = _both(TINY_V3, 2**31 + 11, ids)
    assert got.shape == want.shape == (2, 64, 512)
    # bf16 program against the f32 reference
    scale = np.abs(want).max()
    assert np.abs(got - want).max() < 0.05 * scale
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.9
    # one precision down, the same mathematics lies further off than
    # the program does
    assert np.abs(low - want).max() > 2 * np.abs(got - want).max()


def test_leaves_are_the_share():
    from benchmark.models import deepseek_v3 as family
    shapes = family.leaf_shapes(TINY_V3)
    assert family.share(TINY_V3) == (16, 8, 4)
    assert shapes["model.layers.1.mlp.gate.weight"] == (128, 16)
    assert shapes["model.layers.1.mlp.gate.e_score_correction_bias"] == (16,)
    assert shapes["model.layers.1.mlp.experts.gate_up_proj"] == (4, 128, 128)
    assert shapes["model.layers.2.mlp.experts.down_proj"] == (4, 64, 128)
    assert "model.layers.0.mlp.gate_proj.weight" in shapes
    assert "model.layers.0.mlp.gate.weight" not in shapes
    assert shapes["model.layers.0.self_attn.kv_a_proj_with_mqa.weight"] \
        == (128, 64 + 16)
    assert shapes["model.layers.0.self_attn.kv_b_proj.weight"] \
        == (64, 4 * (32 + 48))
    assert shapes["model.layers.0.self_attn.o_proj.weight"] == (4 * 48, 128)


def test_served_logits_pick_the_rows_that_predicted_served_tokens():
    import jax.numpy as jnp
    from benchmark.lib import weights
    from benchmark.models import deepseek_v3 as family
    from benchmark.reference import deepseek_v3 as ref
    seed = 7
    rng = np.random.default_rng(1)
    samples = [(rng.integers(1, 512, 20), rng.integers(1, 512, 5)),
               (rng.integers(1, 512, 33), rng.integers(1, 512, 3))]
    logits, served = family.served_logits(TINY_V3, seed, samples, 4, 64, 16)
    assert logits.shape == (8, 512)
    assert served.tolist() == [int(t) for _p, toks in samples for t in toks]
    w = weights.make(family.leaf_shapes(TINY_V3), seed)
    seq = np.concatenate(samples[1])
    full = np.asarray(ref.forward(w, jnp.asarray(seq[None]),
                                  family._small(TINY_V3)))[0]
    np.testing.assert_allclose(np.asarray(logits)[5:], full[32:35],
                               atol=1e-5)

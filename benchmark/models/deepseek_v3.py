"""The DeepSeek-V3 family (``"model_type": "deepseek_v3"``: DeepSeek-V3 /
R1, GigaChat3): latent attention, sigmoid group-limited routing, one
shared expert — served as ONE CHIP'S SHARE of an expert-parallel
deployment.

A configuration of this family states its deployment (``"deployment":
{"expert_parallel": n, "rank": r}``): ``n`` chips share each expert
layer, attention replicated; ``n_routed_experts`` in the file counts the
experts HELD here, the gate keeps the published width
``n_routed_experts * n`` and this chip holds experts ``r * held ..
(r + 1) * held``. The program (``paddle_tpu.models.deepseek_v3``) and
the plain reference (``benchmark/reference/deepseek_v3.py``) are given
the same share and the same sliced vocabulary.

What a kind calls: ``build``, ``leaf_shapes``, ``served_logits`` (see
``models/qwen2.py``). Serving only: the family has no training cell.
"""
from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights
from benchmark.reference import deepseek_v3 as ref

CFG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "n_shared_experts", "num_experts_per_tok", "n_group",
            "topk_group", "norm_topk_prob", "routed_scaling_factor",
            "first_k_dense_replace", "max_position_embeddings",
            "rms_norm_eps", "rope_theta", "rope_scaling",
            "tie_word_embeddings")
# reference sequences are padded to a multiple of this many rows
SEQ_BUCKET = 2048


def share(cfg):
    """``(gate width, first expert held, experts held)``."""
    dep = cfg["deployment"]
    held = cfg["n_routed_experts"]
    return held * dep["expert_parallel"], held * dep["rank"], held


# -- leaves ------------------------------------------------------------------

def leaf_shapes(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    if cfg["tie_word_embeddings"]:
        raise ValueError("deepseek_v3: a tied head is not published")
    out = {"model.embed_tokens.weight": (v, h), "model.norm.weight": (h,),
           "lm_head.weight": (h, v)}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_shapes(cfg, i))
    return out


def layer_shapes(cfg, i):
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    p = f"model.layers.{i}."
    out = {
        p + "input_layernorm.weight": (h,),
        p + "post_attention_layernorm.weight": (h,),
        p + "self_attn.q_a_proj.weight": (h, qr),
        p + "self_attn.q_a_layernorm.weight": (qr,),
        p + "self_attn.q_b_proj.weight": (qr, nh * (dn + dr)),
        p + "self_attn.kv_a_proj_with_mqa.weight": (h, kvr + dr),
        p + "self_attn.kv_a_layernorm.weight": (kvr,),
        p + "self_attn.kv_b_proj.weight": (kvr, nh * (dn + dv)),
        p + "self_attn.o_proj.weight": (nh * dv, h)}
    if i < cfg["first_k_dense_replace"]:
        f = cfg["intermediate_size"]
        out.update({p + "mlp.gate_proj.weight": (h, f),
                    p + "mlp.up_proj.weight": (h, f),
                    p + "mlp.down_proj.weight": (f, h)})
        return out
    width, _first, held = share(cfg)
    f = cfg["moe_intermediate_size"]
    fs = f * cfg["n_shared_experts"]
    out.update({
        p + "mlp.gate.weight": (h, width),
        p + "mlp.gate.e_score_correction_bias": (width,),
        p + "mlp.experts.gate_up_proj": (held, h, 2 * f),
        p + "mlp.experts.down_proj": (held, f, h),
        p + "mlp.shared_experts.gate_proj.weight": (h, fs),
        p + "mlp.shared_experts.up_proj.weight": (h, fs),
        p + "mlp.shared_experts.down_proj.weight": (fs, h)})
    return out


# -- the program under test ----------------------------------------------------

def build(cfg, seed, training):
    """The model through its normal constructor (every leaf created in
    bf16; ``initializer_range`` 0 makes its own initialisation zeros,
    which costs no random draw and is dropped anyway), then every
    parameter replaced by the seed's bf16 weights: one device call a
    layer, that layer's zeros dropped first."""
    if training:
        raise NotImplementedError("deepseek_v3: no training cell")
    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                               DeepseekV3ForCausalLM)
    width, first, held = share(cfg)
    paddle.seed(0)
    model = DeepseekV3ForCausalLM(DeepseekV3Config(
        dtype="bfloat16", initializer_range=0.0, n_routed_experts=width,
        expert_first=first, expert_count=held,
        **{k: cfg[k] for k in CFG_KEYS}))
    params = dict(model.named_parameters())
    shapes = leaf_shapes(cfg)
    if {k: tuple(v.shape) for k, v in params.items()} != shapes:
        raise RuntimeError("the model's parameters are not the "
                           "configuration's leaves")
    groups = [layer_shapes(cfg, i) for i in range(cfg["num_hidden_layers"])]
    groups.append({k: v for k, v in shapes.items()
                   if not k.startswith("model.layers.")})
    for group in groups:
        for name in group:
            params[name]._data = jnp.zeros((), jnp.bfloat16)
        gc.collect()
        for name, leaf in weights.make(group, seed).items():
            params[name]._data = leaf
    model.eval()
    return model


# -- the reference, run for the comparison --------------------------------------

def _small(cfg):
    width, first, _held = share(cfg)
    return dict({k: cfg[k] for k in CFG_KEYS}, gate_width=width,
                expert_first=first)


def _static(cfg):
    """The configuration as a hashable static argument."""
    return tuple(sorted(
        (k, tuple(sorted(v.items())) if isinstance(v, dict) else v)
        for k, v in cfg.items()))


@functools.partial(jax.jit, static_argnames=("cfg_items", "dense", "lowp"))
def _layer(h, w, cfg_items, dense, lowp):
    cfg = {k: dict(v) if isinstance(v, tuple) else v for k, v in cfg_items}
    return ref.layer_forward(h, w, cfg, dense, lowp)


def served_logits(cfg, seed, samples, batch, pad_to, rows_cap, lowp=False):
    """Reference logits at every position that produced a served token
    (``models/qwen2.py::served_logits``). Each sample is run once over
    prompt + served tokens, right-padded (causal, so padding is inert)
    to the next multiple of ``SEQ_BUCKET`` rows and run on its own — 8
    sequences of 8,192 never stand in memory together, and a short
    sample does not pay for the longest; weights come from the seed a
    layer at a time. Returns (logits [n, V] on the device, served token
    ids [n])."""
    small = _small(cfg)
    if len(samples) > batch:
        raise ValueError("more samples than the reference's batch")
    table = weights.make({"model.embed_tokens.weight": (
        cfg["vocab_size"], cfg["hidden_size"])}, seed)[
            "model.embed_tokens.weight"]
    hs, rows, served = [], [], []
    for prompt, toks in samples:
        seq = np.concatenate([np.asarray(prompt), np.asarray(toks)])
        if len(seq) > pad_to:
            raise ValueError(f"a sample of {len(seq)} rows, reach {pad_to}")
        bucket = min(SEQ_BUCKET, pad_to)
        ids = np.zeros((1, -(-len(seq) // bucket) * bucket), np.int32)
        ids[0, :len(seq)] = seq
        hs.append(ref.embed(jnp.asarray(ids), table))
        rows.append(len(prompt) - 1 + np.arange(len(toks)))
        served.extend(int(t) for t in toks)
    if len(served) > rows_cap:
        raise ValueError(f"{len(served)} served tokens to check, "
                         f"cap {rows_cap}")
    items = _static(small)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        w = {k[len(pre):]: v
             for k, v in weights.make(layer_shapes(cfg, i), seed).items()}
        dense = i < cfg["first_k_dense_replace"]
        hs = [_layer(h, w, items, dense, lowp) for h in hs]
        del w
    h_rows = jnp.concatenate([h[0][jnp.asarray(r)]
                              for h, r in zip(hs, rows)])
    h_rows = jnp.pad(h_rows, ((0, rows_cap - len(served)), (0, 0)))
    del hs
    tw = weights.make({"model.norm.weight": (cfg["hidden_size"],),
                       "lm_head.weight": (cfg["hidden_size"],
                                          cfg["vocab_size"])}, seed)
    head = jax.jit(functools.partial(ref.head, cfg=small, lowp=lowp))
    logits = head(h_rows, tw["model.norm.weight"],
                  tw["lm_head.weight"])[:len(served)]
    return logits, np.asarray(served, np.int32)

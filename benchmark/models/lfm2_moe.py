"""The LFM2-MoE family (``"model_type": "lfm2_moe"``: LFM2-8B-A1B,
LFM2-24B-A2B): gated short-convolution layers and grouped-query
attention layers (head size 64, QK-norm) in the order the
configuration's ``layer_types`` gives, a sigmoid top-k router with a
choice bias over experts that are ALL held on the chip, a tied head.

A configuration of this family states its deployment (``"deployment":
{"expert_parallel": 1, ...}``): each layer whole on its chip. The
program (``paddle_tpu.models.lfm2_moe``) and the plain reference
(``benchmark/reference/lfm2_moe.py``) get the same widths and the same
seeded leaves.

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
from benchmark.reference import lfm2_moe as ref

# the configuration file's keys that the program's config takes as they
# are (rope_theta comes out of rope_parameters)
CFG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "num_experts",
            "num_experts_per_tok", "num_dense_layers", "conv_L_cache",
            "conv_bias", "norm_eps", "norm_topk_prob", "use_expert_bias",
            "routed_scaling_factor", "max_position_embeddings",
            "tie_word_embeddings")
# what the reference reads
REF_KEYS = CFG_KEYS + ("layer_types", "rope_parameters")
# reference sequences are padded to a multiple of this many rows
SEQ_BUCKET = 1024


# -- leaves ------------------------------------------------------------------

def leaf_shapes(cfg):
    if not cfg["tie_word_embeddings"]:
        raise ValueError("lfm2_moe: an untied head is not published")
    if cfg["deployment"]["expert_parallel"] != 1:
        raise ValueError("lfm2_moe: every expert is held on the chip")
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"model.embed_tokens.weight": (v, h),
           "model.embedding_norm.weight": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_shapes(cfg, i))
    return out


def layer_shapes(cfg, i):
    h, nh, nkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    d = h // nh
    p = f"model.layers.{i}."
    out = {p + "operator_norm.weight": (h,), p + "ffn_norm.weight": (h,)}
    if cfg["layer_types"][i] == "full_attention":
        out.update({
            p + "self_attn.q_proj.weight": (h, nh * d),
            p + "self_attn.k_proj.weight": (h, nkv * d),
            p + "self_attn.v_proj.weight": (h, nkv * d),
            p + "self_attn.out_proj.weight": (nh * d, h),
            p + "self_attn.q_layernorm.weight": (d,),
            p + "self_attn.k_layernorm.weight": (d,)})
    else:
        out.update({
            p + "conv.in_proj.weight": (h, 3 * h),
            p + "conv.conv.weight": (h, cfg["conv_L_cache"]),
            p + "conv.out_proj.weight": (h, h)})
    if i < cfg["num_dense_layers"]:
        f = cfg["intermediate_size"]
        out.update({p + "feed_forward.w1.weight": (h, f),
                    p + "feed_forward.w3.weight": (h, f),
                    p + "feed_forward.w2.weight": (f, h)})
        return out
    e, f = cfg["num_experts"], cfg["moe_intermediate_size"]
    out.update({
        p + "feed_forward.gate.weight": (h, e),
        p + "feed_forward.expert_bias": (e,),
        p + "feed_forward.experts.gate_up_proj": (e, h, 2 * f),
        p + "feed_forward.experts.down_proj": (e, f, h)})
    return out


# -- the program under test ----------------------------------------------------

def build(cfg, seed, training):
    """The model through its normal constructor (every leaf created in
    bf16; ``initializer_range`` 0 makes its own initialisation zeros,
    which costs no random draw and is dropped anyway), then every
    parameter replaced by the seed's bf16 weights: one device call a
    layer, that layer's zeros dropped first."""
    if training:
        raise NotImplementedError("lfm2_moe: no training cell")
    import paddle_tpu as paddle
    from paddle_tpu.models.lfm2_moe import (Lfm2MoeConfig,
                                            Lfm2MoeForCausalLM)
    paddle.seed(0)
    model = Lfm2MoeForCausalLM(Lfm2MoeConfig(
        dtype="bfloat16", initializer_range=0.0,
        layer_types=tuple(cfg["layer_types"]),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
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
    return {k: cfg[k] for k in REF_KEYS}


def _static(cfg):
    """The configuration as a hashable static argument."""
    def freeze(v):
        if isinstance(v, dict):
            return ("dict", tuple(sorted(v.items())))
        return tuple(v) if isinstance(v, list) else v
    return tuple(sorted((k, freeze(v)) for k, v in cfg.items()))


def _thaw(items):
    return {k: dict(v[1]) if isinstance(v, tuple) and v[:1] == ("dict",)
            else v for k, v in items}


@functools.partial(jax.jit,
                   static_argnames=("cfg_items", "kind", "dense", "lowp"))
def _layer(h, w, cfg_items, kind, dense, lowp):
    return ref.layer_forward(h, w, _thaw(cfg_items), kind, dense, lowp)


def served_logits(cfg, seed, samples, batch, pad_to, rows_cap, lowp=False):
    """Reference logits at every position that produced a served token
    (``models/qwen2.py::served_logits``). Each sample is run once over
    prompt + served tokens, right-padded (causal, so padding is inert)
    to the next multiple of ``SEQ_BUCKET`` rows and run on its own;
    weights come from the seed a layer at a time, so one expert layer's
    float32 copy (2.4 GB at the published widths) stands beside nothing
    but the hidden states. Returns (logits [n, V] on the device, served
    token ids [n])."""
    small = _small(cfg)
    if len(samples) > batch:
        raise ValueError("more samples than the reference's batch")
    name = "model.embed_tokens.weight"
    table = weights.make({name: leaf_shapes(cfg)[name]}, seed)[name]
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
        hs = [_layer(h, w, items, cfg["layer_types"][i],
                     i < cfg["num_dense_layers"], lowp) for h in hs]
        del w
    h_rows = jnp.concatenate([h[0][jnp.asarray(r)]
                              for h, r in zip(hs, rows)])
    h_rows = jnp.pad(h_rows, ((0, rows_cap - len(served)), (0, 0)))
    del hs
    norm = "model.embedding_norm.weight"
    w_norm = weights.make({norm: (cfg["hidden_size"],)}, seed)[norm]
    head = jax.jit(functools.partial(ref.head, cfg=small, lowp=lowp))
    logits = head(h_rows, w_norm, table)[:len(served)]
    return logits, np.asarray(served, np.int32)

"""The dense Qwen2 family (``"model_type": "qwen2"``).

A family's file is found by the ``model_type`` of a configuration and
holds everything that belongs to one architecture: how the program's
model is built, the names and shapes of its leaves, and how the plain
reference (``benchmark/reference/qwen2.py``) is run for the comparison
that decides ``correct``. A new family is a new file here, with its
reference beside the others; nothing else names an architecture.

What a kind (``benchmark/kinds/``) calls:

    build(cfg, seed, training)                 the program's model
    leaf_shapes(cfg)                           {state_dict name: shape}
    served_logits(cfg, seed, samples, ...)     serve: reference logits
    train_reference(cfg, seed, batches, opt)   train: losses, norms

The reference runs once the window has closed, the peak has been read
and the program's state is freed. Weights come from the seed again, one
layer at a time when serving, so the f32 reference fits beside nothing.
"""
from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights
from benchmark.reference import qwen2 as ref

CFG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "rms_norm_eps", "rope_theta",
            "tie_word_embeddings")


# -- leaves ------------------------------------------------------------------

def leaf_shapes(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    out = {"llama.embed_tokens.weight": (v, h), "llama.norm.weight": (h,)}
    if not cfg["tie_word_embeddings"]:
        out["lm_head.weight"] = (h, v)
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_shapes(cfg, i))
    return out


def layer_shapes(cfg, i):
    h, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = h // cfg["num_attention_heads"]
    kvw = cfg["num_key_value_heads"] * hd
    p = f"llama.layers.{i}."
    return {
        p + "input_layernorm.weight": (h,),
        p + "self_attn.q_proj.weight": (h, h), p + "self_attn.q_proj.bias": (h,),
        p + "self_attn.k_proj.weight": (h, kvw),
        p + "self_attn.k_proj.bias": (kvw,),
        p + "self_attn.v_proj.weight": (h, kvw),
        p + "self_attn.v_proj.bias": (kvw,),
        p + "self_attn.o_proj.weight": (h, h),
        p + "post_attention_layernorm.weight": (h,),
        p + "mlp.gate_proj.weight": (h, f), p + "mlp.up_proj.weight": (h, f),
        p + "mlp.down_proj.weight": (f, h)}


# -- the program under test ----------------------------------------------------

def build(cfg, seed, training):
    """The model through its normal constructor, then every parameter
    replaced by the seed's bf16 weights (one device call). The
    constructor's own f32 initialisation is dropped first: it and the
    bf16 weights do not fit a chip together at the 7B widths."""
    import paddle_tpu as paddle
    from paddle_tpu.models.qwen2 import Qwen2Config, Qwen2ForCausalLM
    paddle.seed(0)
    model = Qwen2ForCausalLM(Qwen2Config(
        dtype="bfloat16",
        max_position_embeddings=cfg["max_position_embeddings"],
        **{k: cfg[k] for k in CFG_KEYS}))
    params = dict(model.named_parameters())
    shapes = leaf_shapes(cfg)
    if {k: tuple(v.shape) for k, v in params.items()} != shapes:
        raise RuntimeError("the model's parameters are not the "
                           "configuration's leaves")
    for p in params.values():
        p._data = jnp.zeros((), jnp.bfloat16)
    gc.collect()
    made = weights.make(shapes, seed)
    for name, p in params.items():
        p._data = made[name]
    del made
    model.train() if training else model.eval()
    return model


# -- the reference, run for the comparison --------------------------------------

def _small(cfg):
    return {k: cfg[k] for k in CFG_KEYS}


def served_logits(cfg, seed, samples, batch, pad_to, rows_cap, lowp=False):
    """Reference logits at every position that produced a served token.

    ``samples``: [(prompt_ids, served_tokens)]. Each is run once over
    prompt + served tokens (right-padded: causal, so padding is inert),
    ``batch`` sequences of ``pad_to`` at a time; the head runs only on the
    rows that predicted a served token. Returns (logits [n, V] on the
    device, served token ids [n])."""
    cfg = _small(cfg)
    if len(samples) > batch:
        raise ValueError("more samples than the reference's batch")
    ids = np.zeros((batch, pad_to), np.int32)
    rows, served = [], []
    for b, (prompt, toks) in enumerate(samples):
        seq = np.concatenate([np.asarray(prompt), np.asarray(toks)])
        ids[b, :len(seq)] = seq
        for i, t in enumerate(toks):
            rows.append(b * pad_to + len(prompt) - 1 + i)
            served.append(int(t))
    if len(rows) > rows_cap:
        raise ValueError(f"{len(rows)} served tokens to check, cap {rows_cap}")
    layer = jax.jit(functools.partial(ref.layer_forward, cfg=cfg, lowp=lowp))
    head = jax.jit(functools.partial(ref.head, cfg=cfg, lowp=lowp))
    emb = weights.make({"llama.embed_tokens.weight":
                        (cfg["vocab_size"], cfg["hidden_size"])}, seed)
    table = emb["llama.embed_tokens.weight"]
    h = ref.embed(jnp.asarray(ids), table)
    for i in range(cfg["num_hidden_layers"]):
        w = weights.make(layer_shapes(cfg, i), seed)
        h = layer(h, {k.split(f"layers.{i}.")[1]: v for k, v in w.items()})
        del w
    pick = np.zeros(rows_cap, np.int32)
    pick[:len(rows)] = rows
    h_rows = h.reshape(batch * pad_to, -1)[jnp.asarray(pick)]
    del h
    tail = {"llama.norm.weight": (cfg["hidden_size"],)}
    if cfg["tie_word_embeddings"]:
        w_head = table.T
    else:
        tail["lm_head.weight"] = (cfg["hidden_size"], cfg["vocab_size"])
    tw = weights.make(tail, seed)
    if not cfg["tie_word_embeddings"]:
        w_head = tw["lm_head.weight"]
    logits = head(h_rows, tw["llama.norm.weight"], w_head)[:len(rows)]
    return logits, np.asarray(served, np.int32)


def train_reference(cfg, seed, batches, opt, **variant):
    """AdamW steps of the reference from the seed's weights, one per
    batch. ``variant``: ``lowp`` (the control) or a planted fault
    (``half_batch``, ``no_master``), see ``reference/qwen2.py``."""
    cfg = _small(cfg)
    w = weights.make(leaf_shapes(cfg), seed)
    losses, gnorm, change = ref.train(w, batches, cfg, opt, **variant)
    return {"losses": losses, "gnorm": gnorm, "change": change}

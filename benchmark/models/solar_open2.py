"""The Solar-Open2 family (``"model_type": "solar_open2"``:
Solar-Open2-250B): gated delta-rule linear attention (KDA) whose matrix
state a head is slot state, gated NoPE grouped-query attention in the
layers ``gqa_layers`` names, a sigmoid top-k router with a choice bias
and one shared expert in every layer — served as ONE CHIP'S SHARE of an
expert-parallel deployment.

A configuration of this family states its deployment (``"deployment":
{"expert_parallel": n, "rank": r}``): ``n`` chips share each layer's
experts, attention, state and the shared expert on every chip;
``n_routed_experts`` in the file counts the experts HELD here, the gate
keeps the published width ``n_routed_experts * n`` and this chip holds
experts ``r * held .. (r + 1) * held``. The program
(``paddle_tpu.models.solar_open2``) and the plain reference
(``benchmark/reference/solar_open2.py``) are given the same share, the
same sliced vocabulary and the same seeded leaves.

The leaves are ``benchmark/lib/weights.py``'s (N(0, 0.02), ones for
``*norm.weight``) but for the two that set the decay, whose N(0, 0.02)
draws ``z`` are mapped onto the published initialisation's range, for
the program and the reference alike: ``A = 1 + 15 Phi(z / 0.02)``
(``A_log`` its logarithm) and ``dt = 10^(-3 + 2 Phi(z / 0.02))``
(``dt_bias`` its inverse softplus), so a channel's decay a token runs
from ``exp(-1.6)`` to ``exp(-0.001)`` and state carried over hundreds of
tokens matters to the logits.

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
from benchmark.reference import solar_open2 as ref

# the configuration file's keys that the program's config takes as they
# are
CFG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "gqa_interval", "linear_attn_config", "kda_use_full_proj",
            "kda_allow_neg_eigval", "use_rope", "use_gqa_gate",
            "n_shared_experts", "num_experts_per_tok", "norm_topk_prob",
            "routed_scaling_factor", "first_k_dense_replace",
            "max_position_embeddings", "rms_norm_eps",
            "tie_word_embeddings")
# reference sequences are padded to a multiple of this many rows
SEQ_BUCKET = 1024


def share(cfg):
    """``(gate width, first expert held, experts held)``."""
    dep = cfg["deployment"]
    held = cfg["n_routed_experts"]
    return held * dep["expert_parallel"], held * dep["rank"], held


def low_rank(cfg):
    """Width of the decay's and the gate's low-rank projections."""
    return cfg.get("kda_low_rank") or cfg["linear_attn_config"]["head_dim"]


# -- leaves ------------------------------------------------------------------

def leaf_shapes(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    if cfg["tie_word_embeddings"]:
        raise ValueError("solar_open2: a tied head is not published")
    out = {"model.embed_tokens.weight": (v, h), "model.norm.weight": (h,),
           "lm_head.weight": (h, v)}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_shapes(cfg, i))
    return out


def layer_shapes(cfg, i):
    h = cfg["hidden_size"]
    p = f"model.layers.{i}."
    out = {p + "input_layernorm.weight": (h,),
           p + "post_attention_layernorm.weight": (h,)}
    if i in cfg["gqa_layers"]:
        nh, nkv, d = (cfg["num_attention_heads"],
                      cfg["num_key_value_heads"], cfg["head_dim"])
        out.update({
            p + "self_attn.q_proj.weight": (h, nh * d),
            p + "self_attn.k_proj.weight": (h, nkv * d),
            p + "self_attn.v_proj.weight": (h, nkv * d),
            p + "self_attn.g_proj.weight": (h, nh * d),
            p + "self_attn.o_proj.weight": (nh * d, h)})
    else:
        la = cfg["linear_attn_config"]
        heads, d, taps = (la["num_heads"], la["head_dim"],
                          la["short_conv_kernel_size"])
        hd, rank = heads * d, low_rank(cfg)
        q = p + "linear_attn."
        for name in "qkv":
            out[q + name + "_proj.weight"] = (h, hd)
            out[q + name + "_conv1d.weight"] = (hd, taps)
        out.update({
            q + "f_a_proj.weight": (h, rank),
            q + "f_b_proj.weight": (rank, hd),
            q + "g_a_proj.weight": (h, rank),
            q + "g_b_proj.weight": (rank, hd),
            q + "b_proj.weight": (h, heads),
            q + "A_log": (heads,), q + "dt_bias": (hd,),
            q + "o_norm.weight": (d,),
            q + "o_proj.weight": (hd, h)})
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


@jax.jit
def _decay_range(z):
    """A N(0, 0.02) draw as ``(A_log, dt_bias)`` on the published
    initialisation's range: ``A`` uniform on 1..16, ``dt`` log-uniform
    on 1e-3..1e-1."""
    u = jax.scipy.stats.norm.cdf(z.astype(jnp.float32)
                                 / jnp.float32(weights.STD))
    dt = jnp.float32(10.0) ** (2.0 * u - 3.0)
    return (jnp.log(1.0 + 15.0 * u).astype(z.dtype),
            (dt + jnp.log(-jnp.expm1(-dt))).astype(z.dtype))


def make_leaves(shapes, seed):
    """``weights.make``, the two decay leaves mapped onto their range."""
    out = weights.make(shapes, seed)
    for name, leaf in out.items():
        if name.endswith("linear_attn.A_log"):
            out[name] = _decay_range(leaf)[0]
        elif name.endswith("linear_attn.dt_bias"):
            out[name] = _decay_range(leaf)[1]
    return out


# -- the program under test ----------------------------------------------------

def build(cfg, seed, training):
    """The model through its normal constructor (every leaf created in
    bf16; ``initializer_range`` 0 makes its own initialisation zeros,
    which costs no random draw and is dropped anyway), then every
    parameter replaced by the seed's bf16 weights: one device call a
    layer, that layer's zeros dropped first."""
    if training:
        raise NotImplementedError("solar_open2: no training cell")
    import paddle_tpu as paddle
    from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                               SolarOpen2ForCausalLM)
    width, first, held = share(cfg)
    paddle.seed(0)
    model = SolarOpen2ForCausalLM(SolarOpen2Config(
        dtype="bfloat16", initializer_range=0.0, n_routed_experts=width,
        expert_first=first, expert_count=held,
        gqa_layers=tuple(cfg["gqa_layers"]), kda_low_rank=low_rank(cfg),
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
        for name, leaf in make_leaves(group, seed).items():
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
        (k, ("dict", tuple(sorted(v.items()))) if isinstance(v, dict)
         else v) for k, v in cfg.items()))


def _thaw(items):
    return {k: dict(v[1]) if isinstance(v, tuple) and v[:1] == ("dict",)
            else v for k, v in items}


@functools.partial(jax.jit, static_argnames=("cfg_items", "is_gqa", "lowp"))
def _layer(h, w, cfg_items, is_gqa, lowp):
    return ref.layer_forward(h, w, _thaw(cfg_items), is_gqa, lowp)


def served_logits(cfg, seed, samples, batch, pad_to, rows_cap, lowp=False):
    """Reference logits at every position that produced a served token
    (``models/qwen2.py::served_logits``). Each sample is run once over
    prompt + served tokens, right-padded (causal, so padding is inert)
    to the next multiple of ``SEQ_BUCKET`` rows and run on its own;
    weights come from the seed a layer at a time. Returns (logits [n,
    V] on the device, served token ids [n])."""
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
             for k, v in make_leaves(layer_shapes(cfg, i), seed).items()}
        hs = [_layer(h, w, items, i in cfg["gqa_layers"], lowp)
              for h in hs]
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

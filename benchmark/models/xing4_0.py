"""The Xing4.0 family (``"model_type": "xing4_0"``): the DeepSeek-V3 block
(latent attention, sigmoid ``noaux_tc`` routing, one shared expert)
under a residual of ``hc_mult`` streams joined by manifold-constrained
hyper-connections (``paddle_tpu.models.xing4_0``; the plain reference
``benchmark/reference/xing4_0.py``).

The configuration states its deployment as ``deepseek_v3``'s does
(``"deployment": {"expert_parallel": 1, "rank": 0}``: every expert held)
so that ``lib/mla_moe.py``'s readers apply unchanged.

Weights: the benchmark's seeded leaves (``lib/weights.py``), except the
connections' ``base`` (N(0, 0.02) x 50 = N(0, 1)) and ``scale`` (ones):
with ``base`` near 0 and ``scale`` small the four streams would stay
alike and ``H_res`` would act as the identity, invisible to any
comparison (``leaves``).

What a kind calls: ``build``, ``leaf_shapes``, ``served_logits`` (see
``models/qwen2.py``). Serving only.
"""
from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

from benchmark.lib import weights
from benchmark.models import deepseek_v3 as ds
from benchmark.reference import xing4_0 as ref

HC_KEYS = ("hc_mult", "hc_sinkhorn_iters", "hc_eps",
           "mhc_h_res_clamp_min", "mhc_h_res_clamp_max")
CFG_KEYS = ds.CFG_KEYS + HC_KEYS
SEQ_BUCKET = ds.SEQ_BUCKET
BASE_STD = 1.0          # of a connection's ``base``


# -- leaves ------------------------------------------------------------------

def hc_shapes(cfg, prefix):
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    k = 2 * n + n * n
    return {prefix + "fn": (k, n * c), prefix + "base": (k,),
            prefix + "scale": (3,)}


def layer_shapes(cfg, i):
    p = f"model.layers.{i}."
    out = ds.layer_shapes(cfg, i)
    out.update(hc_shapes(cfg, p + "hc_attn."))
    out.update(hc_shapes(cfg, p + "hc_ffn."))
    return out


def leaf_shapes(cfg):
    out = {k: v for k, v in ds.leaf_shapes(cfg).items()
           if not k.startswith("model.layers.")}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_shapes(cfg, i))
    return out


def leaves(shapes, seed):
    """``weights.make``'s leaves, a connection's ``base`` scaled to
    N(0, 1) and its ``scale`` set to ones (the module docstring)."""
    out = weights.make(shapes, seed)
    for name in out:
        if name.endswith(".base"):
            out[name] = (out[name].astype(jnp.float32)
                         * (BASE_STD / weights.STD)).astype(jnp.bfloat16)
        elif name.endswith(".scale"):
            out[name] = jnp.ones_like(out[name])
    return out


# -- the program under test ----------------------------------------------------

def build(cfg, seed, training):
    """As ``deepseek_v3.build``: the model through its normal
    constructor, every leaf created in bf16 (zeros), then replaced by
    the seed's a layer at a time."""
    if training:
        raise NotImplementedError("xing4_0: no training cell")
    import paddle_tpu as paddle
    from paddle_tpu.models.xing4_0 import Xing40Config, Xing40ForCausalLM
    width, first, held = ds.share(cfg)
    paddle.seed(0)
    model = Xing40ForCausalLM(Xing40Config(
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
        for name, leaf in leaves(group, seed).items():
            params[name]._data = leaf
    model.eval()
    return model


# -- the reference, run for the comparison --------------------------------------

def _small(cfg):
    return dict(ds._small(cfg), **{k: cfg[k] for k in HC_KEYS})


@functools.partial(jax.jit, static_argnames=("cfg_items", "dense", "lowp"))
def _layer(h, w, cfg_items, dense, lowp):
    cfg = {k: dict(v) if isinstance(v, tuple) else v for k, v in cfg_items}
    return ref.layer_forward(h, w, cfg, dense, lowp)


def served_logits(cfg, seed, samples, batch, pad_to, rows_cap, lowp=False):
    """Reference logits at every position that produced a served token,
    as ``deepseek_v3.served_logits`` takes them (each sample on its own,
    padded to a multiple of ``SEQ_BUCKET`` rows, the weights a layer at
    a time), over the reference's ``hc_mult`` streams. Returns (logits
    [n, V] on the device, served token ids [n])."""
    small = _small(cfg)
    if len(samples) > batch:
        raise ValueError("more samples than the reference's batch")
    table = leaves({"model.embed_tokens.weight": (
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
        hs.append(ref.embed(jnp.asarray(ids), table, small))
        rows.append(len(prompt) - 1 + np.arange(len(toks)))
        served.extend(int(t) for t in toks)
    if len(served) > rows_cap:
        raise ValueError(f"{len(served)} served tokens to check, "
                         f"cap {rows_cap}")
    items = ds._static(small)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        w = {k[len(pre):]: v
             for k, v in leaves(layer_shapes(cfg, i), seed).items()}
        dense = i < cfg["first_k_dense_replace"]
        hs = [_layer(h, w, items, dense, lowp) for h in hs]
        del w
    h_rows = jnp.concatenate([h[0][jnp.asarray(r)]
                              for h, r in zip(hs, rows)])
    h_rows = jnp.pad(h_rows, ((0, rows_cap - len(served)), (0, 0), (0, 0)))
    del hs
    tw = leaves({"model.norm.weight": (cfg["hidden_size"],),
                 "lm_head.weight": (cfg["hidden_size"],
                                    cfg["vocab_size"])}, seed)
    head = jax.jit(functools.partial(ref.head, cfg=small, lowp=lowp))
    logits = head(h_rows, tw["model.norm.weight"],
                  tw["lm_head.weight"])[:len(served)]
    return logits, np.asarray(served, np.int32)

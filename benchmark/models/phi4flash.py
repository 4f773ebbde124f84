"""The Phi-4-mini-flash family (``"model_type": "phi4flash"``:
Phi-4-mini-flash-reasoning): the SambaY decoder-hybrid-decoder — Mamba
layers and sliding-window differential attention in the self-decoder,
one full-attention layer whose K/V is the only full-length cache, and a
cross-decoder of gated memory units and cross-attention over that cache
— served WHOLE on one chip.

The program (``paddle_tpu.models.phi4flash``) and the plain reference
(``benchmark/reference/phi4flash.py``) are given the same seeded leaves.
They are ``benchmark/lib/weights.py``'s (N(0, 0.02), ones for
``*norm.weight``) but for the leaves whose published initialisation
Mamba's own scan needs, mapped from their draws ``z`` (``u = Phi(z /
0.02)``, uniform on (0, 1)), program and reference alike:

- ``mixer.A_log`` = ``log(1 .. S)`` in every channel (S4D-real), no draw;
- ``mixer.dt_proj.bias`` = the inverse softplus of ``dt = 10^(-3 + 2
  u)``, log-uniform on 1e-3 .. 1e-1;
- ``mixer.D`` ones;
- ``mixer.conv1d.weight`` = ``u - 0.5``, uniform on (-0.5, 0.5): a
  depthwise filter's default initialisation at 4 taps (fan-in 4). At
  N(0, 0.02) the filter would shrink ``x`` fifty-fold and the scan's
  part of a Mamba layer's output to about a hundredth;
- every ``subln.weight`` ones (a norm gain whatever its leaf's name).

With the benchmark's N(0, 0.02) for ``dt``'s bias, ``dt`` would be ~0.69
and every channel would forget within ~10 tokens: no fault of the state
carried between ticks could be seen.

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
from benchmark.reference import phi4flash as ref

# the configuration file's keys that the program's config takes as they
# are
CFG_KEYS = ("vocab_size", "hidden_size", "intermediate_size",
            "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "sliding_window", "mb_per_layer",
            "layer_norm_eps", "tie_word_embeddings", "mamba_d_state",
            "mamba_d_conv", "mamba_expand", "mamba_dt_rank",
            "max_position_embeddings")
# reference sequences are padded to a multiple of this many rows
SEQ_BUCKET = 1024


# -- leaves -------------------------------------------------------------------

def leaf_shapes(cfg):
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    if not cfg["tie_word_embeddings"]:
        raise ValueError("phi4flash: an untied head is not published")
    out = {"model.embed_tokens.weight": (v, h),
           "model.final_layernorm.weight": (h,),
           "model.final_layernorm.bias": (h,)}
    for i in range(cfg["num_hidden_layers"]):
        out.update(layer_shapes(cfg, i))
    return out


def layer_shapes(cfg, i):
    c, f = cfg["hidden_size"], cfg["intermediate_size"]
    e, s, rank, taps = ref.mamba_dims(cfg)
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = c // h
    p = f"model.layers.{i}."
    out = {p + "input_layernorm.weight": (c,),
           p + "input_layernorm.bias": (c,),
           p + "post_attention_layernorm.weight": (c,),
           p + "post_attention_layernorm.bias": (c,),
           p + "mlp.fc1.weight": (c, 2 * f),
           p + "mlp.fc2.weight": (f, c)}
    kind = ref.layer_kind(cfg, i)
    if kind == "mamba":
        q = p + "mixer."
        out.update({
            q + "in_proj.weight": (c, 2 * e),
            q + "conv1d.weight": (e, taps), q + "conv1d.bias": (e,),
            q + "x_proj.weight": (e, rank + 2 * s),
            q + "dt_proj.weight": (rank, e), q + "dt_proj.bias": (e,),
            q + "A_log": (e, s), q + "D": (e,),
            q + "out_proj.weight": (e, c)})
    elif kind == "gmu":
        out.update({p + "mixer.in_proj.weight": (c, e),
                    p + "mixer.out_proj.weight": (e, c)})
    else:
        q = p + "attn."
        if kind == "cross":
            out[q + "Wq.weight"] = (c, h * d)
        else:
            out[q + "Wqkv.weight"] = (c, (h + 2 * hkv) * d)
        out.update({q + "out_proj.weight": (h * d, c),
                    q + "subln.weight": (2 * d,)})
        for name in ("q1", "k1", "q2", "k2"):
            out[q + "lambda_" + name] = (d,)
    return out


@jax.jit
def _uniform(z):
    """A N(0, 0.02) draw as a uniform draw on (0, 1), float32."""
    return jax.scipy.stats.norm.cdf(z.astype(jnp.float32)
                                    / jnp.float32(weights.STD))


@jax.jit
def _dt_bias(z):
    dt = jnp.float32(10.0) ** (2.0 * _uniform(z) - 3.0)
    return (dt + jnp.log(-jnp.expm1(-dt))).astype(z.dtype)


def make_leaves(shapes, seed):
    """``weights.make``, Mamba's leaves and the norm gains mapped as the
    module docstring says."""
    out = weights.make(shapes, seed)
    for name, leaf in out.items():
        if name.endswith("mixer.A_log"):
            out[name] = jnp.broadcast_to(
                jnp.log(jnp.arange(1, leaf.shape[1] + 1, dtype=jnp.float32)),
                leaf.shape).astype(leaf.dtype)
        elif name.endswith("mixer.dt_proj.bias"):
            out[name] = _dt_bias(leaf)
        elif name.endswith("mixer.D") or name.endswith("subln.weight"):
            out[name] = jnp.ones(leaf.shape, leaf.dtype)
        elif name.endswith("mixer.conv1d.weight"):
            out[name] = (_uniform(leaf) - 0.5).astype(leaf.dtype)
    return out


# -- the program under test ---------------------------------------------------

def build(cfg, seed, training):
    """The model through its normal constructor (every leaf created in
    bf16; ``initializer_range`` 0 makes its own initialisation zeros or
    ones, which costs no random draw and is dropped anyway), then every
    parameter replaced by the seed's bf16 weights: one device call a
    layer, that layer's zeros dropped first."""
    if training:
        raise NotImplementedError("phi4flash: no training cell")
    import paddle_tpu as paddle
    from paddle_tpu.models.phi4flash import (Phi4FlashConfig,
                                             Phi4FlashForCausalLM)
    paddle.seed(0)
    model = Phi4FlashForCausalLM(Phi4FlashConfig(
        dtype="bfloat16", initializer_range=0.0,
        **{k: cfg[k] for k in CFG_KEYS if k in cfg}))
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


# -- the reference, run for the comparison ------------------------------------

def _small(cfg):
    return {k: cfg[k] for k in CFG_KEYS if k in cfg}


@functools.partial(jax.jit, static_argnames=("cfg_items", "kind", "memory",
                                             "lowp"))
def _layer(h, m, k, v, w, init, cfg_items, kind, memory, lowp):
    return ref.layer_forward(h, m, k, v, w, dict(cfg_items), kind, init,
                             memory, lowp)


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
    states, rows, served = [], [], []
    for prompt, toks in samples:
        seq = np.concatenate([np.asarray(prompt), np.asarray(toks)])
        if len(seq) > pad_to:
            raise ValueError(f"a sample of {len(seq)} rows, reach {pad_to}")
        bucket = min(SEQ_BUCKET, pad_to)
        ids = np.zeros((1, -(-len(seq) // bucket) * bucket), np.int32)
        ids[0, :len(seq)] = seq
        states.append((ref.embed(jnp.asarray(ids), table),
                       *ref.carry(cfg, *ids.shape)))
        rows.append(len(prompt) - 1 + np.arange(len(toks)))
        served.extend(int(t) for t in toks)
    if len(served) > rows_cap:
        raise ValueError(f"{len(served)} served tokens to check, "
                         f"cap {rows_cap}")
    items = tuple(sorted(small.items()))
    n = cfg["num_hidden_layers"]
    for i in range(n):
        pre = f"model.layers.{i}."
        w = {k[len(pre):]: v
             for k, v in make_leaves(layer_shapes(cfg, i), seed).items()}
        init = jnp.float32(ref.lambda_init(i))
        states = [_layer(*st, w, init, items, ref.layer_kind(cfg, i),
                         i == n // 2, lowp) for st in states]
        del w
    h_rows = jnp.concatenate([st[0][0][jnp.asarray(r)]
                              for st, r in zip(states, rows)])
    # padded to a whole bucket of rows, not to ``rows_cap``: at 200,064
    # float32 logits a row, the control's two arrays of ``rows_cap``
    # rows beside the table would not fit the chip
    pad = min(rows_cap, -(-len(served) // SEQ_BUCKET) * SEQ_BUCKET)
    h_rows = jnp.pad(h_rows, ((0, pad - len(served)), (0, 0)))
    del states
    tw = make_leaves({"model.final_layernorm.weight": (cfg["hidden_size"],),
                      "model.final_layernorm.bias": (cfg["hidden_size"],)},
                     seed)
    head = jax.jit(functools.partial(ref.head, cfg=small, lowp=lowp))
    logits = head(h_rows, tw["model.final_layernorm.weight"],
                  tw["model.final_layernorm.bias"], table)[:len(served)]
    return logits, np.asarray(served, np.int32)

"""Plain reference for the Qwen2 decoder: forward, loss, gradients, AdamW.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
written from the published description (Qwen2 technical report; the
``Qwen2ForCausalLM`` of the public ``config.json``): token embedding,
pre-norm decoder layers of RMSNorm -> grouped-query attention with bias
on q/k/v and rotary embedding over half-split head dimensions (theta
from the config) -> residual -> RMSNorm -> SwiGLU -> residual, a final
RMSNorm and a linear head (the embedding transposed where tied). No
kernels, no cache, no batching tricks. It imports nothing of
``paddle_tpu`` and takes its weights from the benchmark's seeded
generator (``benchmark/lib/weights.py``), never from the program.

Departures from the published model, each because the configuration
states the same: weights are bf16 values (upcast here to f32); there is
no dropout and no attention mask besides causality. Training keeps a
float32 master copy of every parameter (AdamW ``multi_precision``) and
runs the forward on its bf16 cast.

``lowp`` is the control of the comparison that decides ``correct``: the
same mathematics with every matmul operand rounded to float8 (e4m3, one
scale per tensor), the nearest precision below the bf16 that the
configurations state; gradients pass the rounding straight through.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST


def _q(x, lowp):
    """Matmul operand as the stated precision sees it (f32), or rounded
    to float8 e4m3 with one scale per tensor for the control."""
    x = x.astype(F32)
    if not lowp:
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), F32(1e-30)) / F32(448.0)
    rounded = (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s
    # straight through: the backward matmuls see the rounded operands,
    # the cotangent itself stays float32 (a float8 cotangent underflows
    # to nought, and a step that moves nothing is no precision to tempt)
    return x + jax.lax.stop_gradient(rounded - x)


def _bf16(x):
    """float32 values rounded to bfloat16's. Not ``astype`` there and
    back: XLA on the TPU drops such a pair (excess precision allowed)."""
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _mm(x, w, lowp):
    return jnp.matmul(_q(x, lowp), _q(w, lowp), precision=HI)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + F32(eps)) * w.astype(F32)


def rope_tables(positions, head_dim, theta):
    inv = F32(theta) ** (-jnp.arange(0, head_dim, 2, dtype=F32)
                         / F32(head_dim))
    ang = positions.astype(F32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def rope(x, cos, sin):
    """x [B, T, H, D]; rotation over the two halves of D."""
    d = x.shape[-1] // 2
    x1, x2 = x[..., :d], x[..., d:]
    c, s = cos[None, :, None, :], sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def attention(q, k, v):
    """Causal grouped-query attention. q [B,T,H,D], k/v [B,T,Hkv,D]."""
    b, t, h, d = q.shape
    rep = h // k.shape[2]
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k, precision=HI) / F32(np.sqrt(d))
    mask = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(mask[None, None], s, F32(-1e30))
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision=HI)


def layer_forward(h, w, cfg, lowp=False):
    """One decoder layer. ``w``: this layer's leaves by short name."""
    b, t, _ = h.shape
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // nh
    eps = cfg["rms_norm_eps"]
    x = rms_norm(h, w["input_layernorm.weight"], eps)
    q = _mm(x, w["self_attn.q_proj.weight"], lowp) \
        + w["self_attn.q_proj.bias"].astype(F32)
    k = _mm(x, w["self_attn.k_proj.weight"], lowp) \
        + w["self_attn.k_proj.bias"].astype(F32)
    v = _mm(x, w["self_attn.v_proj.weight"], lowp) \
        + w["self_attn.v_proj.bias"].astype(F32)
    cos, sin = rope_tables(jnp.arange(t), hd, cfg["rope_theta"])
    q = rope(q.reshape(b, t, nh, hd), cos, sin)
    k = rope(k.reshape(b, t, nkv, hd), cos, sin)
    a = attention(q, k, v.reshape(b, t, nkv, hd)).reshape(b, t, nh * hd)
    h = h + _mm(a, w["self_attn.o_proj.weight"], lowp)
    x = rms_norm(h, w["post_attention_layernorm.weight"], eps)
    g = _mm(x, w["mlp.gate_proj.weight"], lowp)
    u = _mm(x, w["mlp.up_proj.weight"], lowp)
    return h + _mm(jax.nn.silu(g) * u, w["mlp.down_proj.weight"], lowp)


def embed(ids, table):
    return jnp.take(table, ids, axis=0).astype(F32)


def head(h, w_norm, w_head, cfg, lowp=False):
    """Final norm and vocabulary projection; ``w_head`` is [hidden, V]."""
    return _mm(rms_norm(h, w_norm, cfg["rms_norm_eps"]), w_head, lowp)


LAYER_LEAVES = (
    "input_layernorm.weight", "self_attn.q_proj.weight",
    "self_attn.q_proj.bias", "self_attn.k_proj.weight",
    "self_attn.k_proj.bias", "self_attn.v_proj.weight",
    "self_attn.v_proj.bias", "self_attn.o_proj.weight",
    "post_attention_layernorm.weight", "mlp.gate_proj.weight",
    "mlp.up_proj.weight", "mlp.down_proj.weight")


def layer_weights(weights, i):
    pre = f"llama.layers.{i}."
    return {k: weights[pre + k] for k in LAYER_LEAVES}


def head_weight(weights, cfg):
    if cfg["tie_word_embeddings"]:
        return weights["llama.embed_tokens.weight"].T
    return weights["lm_head.weight"]


def forward(weights, ids, cfg, lowp=False):
    """Whole forward from a full weight dict: logits [B, T, V] (f32)."""
    h = embed(ids, weights["llama.embed_tokens.weight"])
    for i in range(cfg["num_hidden_layers"]):
        h = layer_forward(h, layer_weights(weights, i), cfg, lowp)
    return head(h, weights["llama.norm.weight"], head_weight(weights, cfg),
                cfg, lowp)


def loss_fn(weights, ids, labels, cfg, lowp=False):
    """Mean next-token cross entropy; labels are already shifted."""
    h = embed(ids, weights["llama.embed_tokens.weight"])
    layer = jax.checkpoint(functools.partial(layer_forward, cfg=cfg,
                                             lowp=lowp))
    for i in range(cfg["num_hidden_layers"]):
        h = layer(h, layer_weights(weights, i))
    logits = head(h, weights["llama.norm.weight"],
                  head_weight(weights, cfg), cfg, lowp)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def adamw_update(p, g, m1, m2, t, opt):
    """Decoupled-decay Adam on one leaf, step ``t`` (1-based). ``p`` is
    the float32 master copy (AdamW ``multi_precision``); moments are f32.
    The forward sees it cast to the served dtype (``_train_step``)."""
    lr, b1, b2 = F32(opt["lr"]), F32(opt["beta1"]), F32(opt["beta2"])
    g = g.astype(F32)
    m1 = b1 * m1 + (1 - b1) * g
    m2 = b2 * m2 + (1 - b2) * g * g
    m1_hat = m1 / (1 - b1 ** t)
    m2_hat = m2 / (1 - b2 ** t)
    new = p * (1 - lr * F32(opt["weight_decay"])) \
        - lr * m1_hat / (jnp.sqrt(m2_hat) + F32(opt["epsilon"]))
    return new, m1, m2


@functools.partial(jax.jit, static_argnames=("cfg_items", "opt_items",
                                             "lowp", "half_batch",
                                             "no_master"))
def _train_step(master, m1, m2, t, ids, labels, cfg_items, opt_items,
                lowp, half_batch, no_master):
    cfg, opt = dict(cfg_items), dict(opt_items)
    if half_batch:      # the planted fault: half of the rows left out
        ids, labels = ids[: ids.shape[0] // 2], labels[: ids.shape[0] // 2]
    weights = {k: _bf16(v) for k, v in master.items()}
    loss, grads = jax.value_and_grad(loss_fn)(weights, ids, labels, cfg, lowp)
    new, new_m1, new_m2 = {}, {}, {}
    for k in master:
        new[k], new_m1[k], new_m2[k] = adamw_update(
            master[k], grads[k], m1[k], m2[k], t, opt)
        if no_master:   # the planted fault: the update is rounded to the
            # served dtype each step, as with no float32 master copy
            new[k] = _bf16(new[k])
    gnorm = {k: jnp.sqrt(jnp.sum(jnp.square(g.astype(F32))))
             for k, g in grads.items()}
    return loss, new, new_m1, new_m2, gnorm


def train(weights, batches, cfg, opt, lowp=False, half_batch=False,
          no_master=False):
    """Follow ``len(batches)`` AdamW steps from bf16 ``weights`` with a
    float32 master copy. Returns the losses, the per-leaf norm of the
    first gradient and the per-leaf norm of the change, after the last
    step, of the parameters as they are served (the master cast back)."""
    w0 = weights
    w = {k: v.astype(F32) for k, v in weights.items()}
    m1 = {k: jnp.zeros(v.shape, F32) for k, v in w.items()}
    m2 = {k: jnp.zeros(v.shape, F32) for k, v in w.items()}
    cfg_items = tuple(sorted((k, v) for k, v in cfg.items()
                             if isinstance(v, (int, float, bool))))
    opt_items = tuple(sorted(opt.items()))
    losses, gnorm1 = [], None
    for t, (ids, labels) in enumerate(batches, start=1):
        loss, w, m1, m2, gnorm = _train_step(
            w, m1, m2, F32(t), jnp.asarray(ids, jnp.int32),
            jnp.asarray(labels, jnp.int32), cfg_items, opt_items, lowp,
            half_batch, no_master)
        losses.append(float(loss))
        if t == 1:
            gnorm1 = {k: float(v) for k, v in gnorm.items()}
    change = {k: float(jnp.sqrt(jnp.sum(jnp.square(
        w[k].astype(w0[k].dtype).astype(F32) - w0[k].astype(F32)))))
        for k in w}
    return losses, gnorm1, change

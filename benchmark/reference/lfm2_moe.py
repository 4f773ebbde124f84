"""Plain reference for the LFM2-MoE decoder (``model_type: lfm2_moe``:
LFM2-8B-A1B, LFM2-24B-A2B): forward only.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, written from the equations of the published architecture
(the ``Lfm2MoeForCausalLM`` of ``transformers`` that the public
``config.json`` names). With ``x`` the residual stream and ``rms`` an
RMSNorm with a learned weight, every layer is ``x = x + mixer(rms(x,
operator_norm))`` then ``x = x + ffn(rms(x, ffn_norm))``; after the
last layer ``rms(x, embedding_norm)`` and the head, which is the
embedding (tied).

- ``conv`` mixer (``layer_types[i] == "conv"``): ``B, C, z = split3(u
  W_in)``; ``g = B * z``; ``c[t] = sum_{j < L} w[:, j] * g[t - (L - 1)
  + j]`` with ``L = conv_L_cache`` — depthwise, causal, one filter a
  channel, ``g`` before the sequence's start 0 — written as the sum of
  ``L`` shifted copies; ``out = (C * c) W_out``.
- ``full_attention`` mixer: grouped-query attention, head size ``hidden
  / heads``; q and k are RMS-normed per head over the head's lanes
  (``q_layernorm`` / ``k_layernorm``) BEFORE rotate-half RoPE (lane
  ``i`` pairs with ``i + D / 2``), scale ``D^-0.5``, causal softmax,
  ``out_proj``.
- feed-forward: the first ``num_dense_layers`` layers dense SwiGLU
  (``w2(silu(w1 u) * w3 u)``); the others ``sum_e weight_e
  Expert_e(u)`` over the top ``num_experts_per_tok`` of ``s + expert_bias``
  with ``s = sigmoid(u W_g)``; the weights are the chosen ``s`` (without
  the bias) over ``(their sum + 1e-6)`` times ``routed_scaling_factor``.

No kernels, no cache, no batching: one sequence at a time, the experts
a dense loop. It imports nothing of ``paddle_tpu`` and takes its
weights from the benchmark's seeded generator, never from the program.
It routes from its own hidden state.

Departures from the published code, each because the configuration
states the same: weights are bf16 values (upcast here to float32); the
gate runs in float32 (``transformers`` runs it in the model's dtype);
ties in the top-k go to the lower index; the checkpoint layout of the
experts is the program's stacked one (``feed_forward.experts.
gate_up_proj [E, hidden, 2 f]``, gate columns then up columns, and
``down_proj [E, f, hidden]``), and the depthwise filter is
``conv.conv.weight [hidden, L]``.

``lowp`` is the control of the comparison that decides ``correct``: the
same mathematics with every matmul operand (the gate's too) rounded to
float8 (e4m3, one scale per tensor), the nearest precision below the
bf16 that the configuration states.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256
ROUTER_EPS = 1e-6


def _q(x, lowp):
    x = x.astype(F32)
    if not lowp:
        return x
    s = jnp.maximum(jnp.max(jnp.abs(x)), F32(1e-30)) / F32(448.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def _mm(x, w, lowp):
    return jnp.matmul(_q(x, lowp), _q(w, lowp), precision=HI)


def rms_norm(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + F32(eps)) * w.astype(F32)


def swiglu(x, gate, up, down, lowp):
    return _mm(jax.nn.silu(_mm(x, gate, lowp)) * _mm(x, up, lowp), down,
               lowp)


# -- the two mixers ------------------------------------------------------------

def short_conv(x, w, cfg, lowp):
    """One sequence ``x [T, hidden]`` (already normed) -> ``[T,
    hidden]``."""
    taps = cfg["conv_L_cache"]
    t = x.shape[0]
    bcz = _mm(x, w["conv.in_proj.weight"], lowp)
    hidden = bcz.shape[1] // 3
    b, c, z = bcz[:, :hidden], bcz[:, hidden:2 * hidden], bcz[:, 2 * hidden:]
    g = b * z
    filt = w["conv.conv.weight"].astype(F32)            # [hidden, L]
    conv = jnp.zeros_like(g)
    for j in range(taps):
        back = taps - 1 - j         # tap j reads g[t - back]
        shifted = jnp.concatenate(
            [jnp.zeros((back, hidden), F32), g[:t - back]]) if back else g
        conv = conv + filt[:, j][None, :] * shifted
    return _mm(c * conv, w["conv.out_proj.weight"], lowp)


def rope_halves(x, positions, theta):
    """``x [T, heads, D]``: lane ``i`` pairs with lane ``i + D / 2``,
    both rotated by ``positions * theta^(-2i / D)``."""
    d = x.shape[-1]
    inv = F32(theta) ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = positions.astype(F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    a, b = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def attention(x, w, cfg, lowp):
    """One sequence ``x [T, hidden]`` (already normed) -> ``[T,
    hidden]``."""
    t = x.shape[0]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    eps, theta = cfg["norm_eps"], cfg["rope_parameters"]["rope_theta"]
    pos = jnp.arange(t)
    q = _mm(x, w["self_attn.q_proj.weight"], lowp).reshape(t, h, d)
    k = _mm(x, w["self_attn.k_proj.weight"], lowp).reshape(t, hkv, d)
    v = _mm(x, w["self_attn.v_proj.weight"], lowp).reshape(t, hkv, d)
    q = rope_halves(rms_norm(q, w["self_attn.q_layernorm.weight"], eps),
                    pos, theta)
    k = rope_halves(rms_norm(k, w["self_attn.k_layernorm.weight"], eps),
                    pos, theta)
    k = jnp.repeat(k, h // hkv, axis=1)         # kv head g serves heads
    v = jnp.repeat(v, h // hkv, axis=1)         # g * rep .. (g + 1) * rep
    scale = F32(d ** -0.5)
    qb = max(n for n in range(1, min(Q_BLOCK, t) + 1) if t % n == 0)

    def block(i):
        rows = i * qb + jnp.arange(qb)
        s = jnp.einsum("qhd,khd->hqk", _q(q[rows], lowp), _q(k, lowp),
                       precision=HI) * scale
        s = jnp.where(pos[None, None, :] <= rows[None, :, None], s,
                      F32(-1e30))
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _q(p, lowp), _q(v, lowp),
                          precision=HI)

    o = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, h * d)
    return _mm(o, w["self_attn.out_proj.weight"], lowp)


# -- the expert layer -----------------------------------------------------------

def route(x, w, cfg, lowp):
    """``(idx [T, k], weight [T, k])`` over all the experts."""
    e, k = cfg["num_experts"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm(x, w["feed_forward.gate.weight"], lowp))
    choice = s
    if cfg["use_expert_bias"]:
        choice = s + w["feed_forward.expert_bias"].astype(F32)[None]
    idx = []
    for _ in range(k):          # the k largest, one argmax at a time
        top = jnp.argmax(choice, axis=-1)
        idx.append(top)
        choice = jnp.where(jax.nn.one_hot(top, e, dtype=bool),
                           F32(-jnp.inf), choice)
    idx = jnp.stack(idx, axis=-1)
    weight = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True)
                           + F32(ROUTER_EPS))
    return idx, weight * F32(cfg["routed_scaling_factor"])


def experts(x, w, cfg, lowp):
    """A dense loop: each expert on all rows, gated by the weight the
    router gave it (0 where it was not chosen)."""
    idx, weight = route(x, w, cfg, lowp)
    f = cfg["moe_intermediate_size"]
    gate_up = w["feed_forward.experts.gate_up_proj"]
    down = w["feed_forward.experts.down_proj"]

    def one(y, e):
        gate = jnp.sum(jnp.where(idx == e, weight, F32(0.0)), axis=-1)
        out = swiglu(x, gate_up[e][:, :f], gate_up[e][:, f:], down[e],
                     lowp)
        return y + gate[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(gate_up.shape[0]))
    return y


def layer_forward(h, w, cfg, kind, dense, lowp=False):
    """One decoder layer over ``h [B, T, hidden]``, a sequence at a
    time. ``w``: this layer's leaves by short name; ``kind``: its
    ``layer_types`` entry; ``dense``: whether it is one of the leading
    dense layers."""
    eps = cfg["norm_eps"]
    mixer = attention if kind == "full_attention" else short_conv

    def one(hs):
        hs = hs + mixer(rms_norm(hs, w["operator_norm.weight"], eps), w,
                        cfg, lowp)
        x = rms_norm(hs, w["ffn_norm.weight"], eps)
        if dense:
            return hs + swiglu(x, w["feed_forward.w1.weight"],
                               w["feed_forward.w3.weight"],
                               w["feed_forward.w2.weight"], lowp)
        return hs + experts(x, w, cfg, lowp)

    return jax.lax.map(one, h)


def embed(ids, table):
    return jnp.take(table, ids, axis=0).astype(F32)


def head(h, w_norm, table, cfg, lowp=False):
    """The tied head: ``rms(h) E^T``."""
    return _mm(rms_norm(h, w_norm, cfg["norm_eps"]),
               jnp.swapaxes(table, 0, 1), lowp)


def forward(weights, ids, cfg, lowp=False):
    """Whole forward from a full weight dict: logits ``[B, T, V]``."""
    table = weights["model.embed_tokens.weight"]
    h = embed(ids, table)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        w = {k[len(pre):]: v for k, v in weights.items()
             if k.startswith(pre)}
        h = layer_forward(h, w, cfg, cfg["layer_types"][i],
                          i < cfg["num_dense_layers"], lowp)
    return head(h, weights["model.embedding_norm.weight"], table, cfg,
                lowp)

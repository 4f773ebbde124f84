"""Plain reference for the Solar-Open2 decoder (``model_type:
solar_open2``: Solar-Open2-250B): forward only.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, written from the equations the public ``config.json`` and
the cited papers fix. With ``x`` the residual stream and ``rms`` an
RMSNorm with a learned weight, every layer is ``x = x + mixer(rms(x,
input_layernorm))`` then ``x = x + moe(rms(x,
post_attention_layernorm))``; after the last layer ``rms(x, norm)`` and
the untied head. Layer ``i`` is a ``gqa`` layer where ``i`` is in
``gqa_layers``, else a ``kda`` layer; every layer has the expert block.

- ``kda`` mixer (Kimi Delta Attention, arXiv:2510.26692: the gated
  delta rule of arXiv:2412.06464 with a decay per channel). With ``u``
  the normed input, per head of ``linear_attn_config``: ``q =
  l2norm(silu(conv(u W_q))) d^-0.5``, ``k = l2norm(silu(conv(u W_k)))``,
  ``v = silu(conv(u W_v))``; ``conv`` depthwise, causal, ``L =
  short_conv_kernel_size`` taps a channel, zeros before the sequence's
  start, written as the sum of ``L`` shifted copies (tap ``j``
  multiplies the input ``L - 1 - j`` rows back); ``l2norm(x) = x /
  sqrt(sum x^2 + 1e-6)`` over the head's lanes; ``g = -exp(A_log[h])
  softplus(u W_f1 W_f2 + dt_bias)`` per channel; ``beta = 2 sigmoid(u
  W_b)`` per head (``kda_allow_neg_eigval``); the state ``S [d, d]`` a
  head, token by token in a ``lax.scan`` (no chunking): ``S = Diag(exp(
  g_t)) S``, ``S = S + beta_t k_t (v_t - S^T k_t)^T``, ``o_t = S^T
  q_t``; ``out = (rms(o, o_norm) * sigmoid(u W_g1 W_g2)) W_o``, the
  RMSNorm over the head's lanes.
- ``gqa`` mixer: grouped-query softmax attention without any positional
  encoding, scale ``head_dim^-0.5``, causal; ``out = (attn * sigmoid(u
  W_gate)) W_o``, one gate a query-head lane (arXiv:2505.06708).
- expert block: ``s = sigmoid(u W_r)`` over the gate's whole width; the
  choice is the top ``num_experts_per_tok`` of ``s +
  e_score_correction_bias``, the weights the chosen ``s`` (without the
  bias) over ``(their sum + 1e-20)`` times ``routed_scaling_factor``;
  the routed output is ``sum_e weight_e Expert_e(u)`` over the experts
  HELD here (``expert_first .. expert_first + held``: what the absent
  experts would have added is left out, as in the program); one shared
  expert is added for every row; each expert ``W_2 (silu(W_1 u) * W_3
  u)``.

No kernels, no cache, no batching: one sequence at a time, the experts
a dense loop. It imports nothing of ``paddle_tpu`` and takes its
weights from the benchmark's seeded generator, never from the program.
It routes from its own hidden state. Ties in the top-k go to the lower
index.

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
ROUTER_EPS = 1e-20
L2_EPS = 1e-6


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


def l2norm(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                        + F32(L2_EPS))


# -- the two mixers ------------------------------------------------------------

def short_conv(x, filt):
    """``x [T, C]`` through a causal depthwise filter ``filt [C, L]``:
    the sum of ``L`` shifted copies, zeros before the start."""
    t, c = x.shape
    taps = filt.shape[1]
    out = jnp.zeros_like(x)
    for j in range(taps):
        back = taps - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros((back, c), F32), x[:t - back]]) if back else x
        out = out + filt[:, j].astype(F32)[None, :] * shifted
    return out


def delta_rule(q, k, v, g, beta):
    """The recurrence a token at a time: ``q, k, v, g [T, H, d]``,
    ``beta [T, H]`` -> ``o [T, H, d]``, from a zero state."""
    def token(s, row):
        q_t, k_t, v_t, g_t, b_t = row
        s = jnp.exp(g_t)[:, :, None] * s                # decay the rows
        seen = jnp.einsum("hkv,hk->hv", s, k_t, precision=HI)
        s = s + jnp.einsum("hk,hv->hkv", k_t,
                           b_t[:, None] * (v_t - seen), precision=HI)
        return s, jnp.einsum("hkv,hk->hv", s, q_t, precision=HI)

    h, d = q.shape[1], q.shape[2]
    _, o = jax.lax.scan(token, jnp.zeros((h, d, v.shape[2]), F32),
                        (q, k, v, g, beta))
    return o


def kda(x, w, cfg, lowp):
    """One sequence ``x [T, hidden]`` (already normed) -> ``[T,
    hidden]``."""
    la = cfg["linear_attn_config"]
    heads, d = la["num_heads"], la["head_dim"]
    t = x.shape[0]
    p = "linear_attn."

    def branch(name):
        y = _mm(x, w[p + name + "_proj.weight"], lowp)
        y = jax.nn.silu(short_conv(y, w[p + name + "_conv1d.weight"]))
        return y.reshape(t, heads, d)

    q = l2norm(branch("q")) * F32(d ** -0.5)
    k = l2norm(branch("k"))
    v = branch("v")
    dt = _mm(_mm(x, w[p + "f_a_proj.weight"], lowp),
             w[p + "f_b_proj.weight"], lowp) + w[p + "dt_bias"].astype(F32)
    g = -jnp.exp(w[p + "A_log"].astype(F32))[None, :, None] \
        * jax.nn.softplus(dt).reshape(t, heads, d)
    beta = jax.nn.sigmoid(_mm(x, w[p + "b_proj.weight"], lowp))
    if cfg["kda_allow_neg_eigval"]:
        beta = F32(2.0) * beta
    o = rms_norm(delta_rule(q, k, v, g, beta), w[p + "o_norm.weight"],
                 cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(_mm(_mm(x, w[p + "g_a_proj.weight"], lowp),
                              w[p + "g_b_proj.weight"], lowp))
    return _mm(o.reshape(t, heads * d) * gate, w[p + "o_proj.weight"],
               lowp)


def gqa(x, w, cfg, lowp):
    """One sequence ``x [T, hidden]`` (already normed) -> ``[T,
    hidden]``: no positional encoding."""
    t = x.shape[0]
    h, hkv, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    pos = jnp.arange(t)
    q = _mm(x, w["self_attn.q_proj.weight"], lowp).reshape(t, h, d)
    k = _mm(x, w["self_attn.k_proj.weight"], lowp).reshape(t, hkv, d)
    v = _mm(x, w["self_attn.v_proj.weight"], lowp).reshape(t, hkv, d)
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
    if cfg["use_gqa_gate"]:
        o = o * jax.nn.sigmoid(_mm(x, w["self_attn.g_proj.weight"], lowp))
    return _mm(o, w["self_attn.o_proj.weight"], lowp)


# -- the expert block -----------------------------------------------------------

def route(x, w, cfg, lowp):
    """``(idx [T, k], weight [T, k])`` over the gate's whole width."""
    e, k = cfg["gate_width"], cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm(x, w["mlp.gate.weight"], lowp))
    choice = s + w["mlp.gate.e_score_correction_bias"].astype(F32)[None]
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
    """The held experts' part of the routed output (a dense loop: each
    held expert on all rows, gated by the weight the router gave it, 0
    where it was not chosen) plus the shared expert."""
    idx, weight = route(x, w, cfg, lowp)
    f = cfg["moe_intermediate_size"]
    gate_up, down = w["mlp.experts.gate_up_proj"], w["mlp.experts.down_proj"]

    def one(y, e):
        gate = jnp.sum(jnp.where(idx == cfg["expert_first"] + e, weight,
                                 F32(0.0)), axis=-1)
        out = swiglu(x, gate_up[e][:, :f], gate_up[e][:, f:], down[e],
                     lowp)
        return y + gate[:, None] * out, None

    y, _ = jax.lax.scan(one, jnp.zeros_like(x),
                        jnp.arange(gate_up.shape[0]))
    return y + swiglu(x, w["mlp.shared_experts.gate_proj.weight"],
                      w["mlp.shared_experts.up_proj.weight"],
                      w["mlp.shared_experts.down_proj.weight"], lowp)


def layer_forward(h, w, cfg, is_gqa, lowp=False):
    """One decoder layer over ``h [B, T, hidden]``, a sequence at a
    time. ``w``: this layer's leaves by short name; ``is_gqa``: whether
    the layer's index is in ``gqa_layers``."""
    eps = cfg["rms_norm_eps"]
    mixer = gqa if is_gqa else kda

    def one(hs):
        hs = hs + mixer(rms_norm(hs, w["input_layernorm.weight"], eps), w,
                        cfg, lowp)
        return hs + experts(
            rms_norm(hs, w["post_attention_layernorm.weight"], eps), w,
            cfg, lowp)

    return jax.lax.map(one, h)


def embed(ids, table):
    return jnp.take(table, ids, axis=0).astype(F32)


def head(h, w_norm, w_head, cfg, lowp=False):
    return _mm(rms_norm(h, w_norm, cfg["rms_norm_eps"]), w_head, lowp)


def forward(weights, ids, cfg, lowp=False):
    """Whole forward from a full weight dict: logits ``[B, T, V]``."""
    h = embed(ids, weights["model.embed_tokens.weight"])
    for i in range(cfg["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        w = {k[len(pre):]: v for k, v in weights.items()
             if k.startswith(pre)}
        h = layer_forward(h, w, cfg, i in cfg["gqa_layers"], lowp)
    return head(h, weights["model.norm.weight"], weights["lm_head.weight"],
                cfg, lowp)

"""Plain reference for the DeepSeek-V3 decoder (``model_type:
deepseek_v3``: DeepSeek-V3 / R1, GigaChat3): forward only.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, written from the published description (DeepSeek-V3
technical report; the ``DeepseekV3ForCausalLM`` of ``transformers``
that the public ``config.json`` names). For one token ``x`` after the
input RMSNorm:

- latent attention, NON-absorbed: ``c_q = RMSNorm(x W_qa)``, ``q = c_q
  W_qb`` split per head into ``q_nope | q_pe``; ``[c_kv | k_pe] = x
  W_kva``, ``c_kv = RMSNorm(c_kv)``; per-head ``[k_nope_h | v_h] = c_kv
  W_kvb``; the one ``k_pe`` is shared by all heads; RoPE (YaRN inverse
  frequencies) on ``q_pe`` and ``k_pe``; scores ``(q_nope . k_nope +
  q_pe . k_pe) * (nope + rope)^-0.5 * m^2``, ``m = 0.1 mscale_all_dim
  ln(factor) + 1``; causal softmax; ``o_proj``;
- feed-forward: the first ``first_k_dense_replace`` layers dense SwiGLU;
  the others ``Shared(x) + sum_e w_e Expert_e(x)`` with ``s = sigmoid(x
  W_g)`` in float32, the choice on ``s + e_score_correction_bias``
  limited to the best ``topk_group`` of ``n_group`` groups (a group's
  score: the sum of its two largest), the ``num_experts_per_tok``
  largest inside them, the weights the chosen ``s`` over their sum
  times ``routed_scaling_factor``.

No kernels, no cache, no absorbed products; the experts are a dense
loop over the experts held. It imports nothing of ``paddle_tpu`` and
takes its weights from the benchmark's seeded generator, never from the
program. It routes from its own hidden state.

Departures from the published code, each because the configuration
states the same:

- weights are bf16 values (upcast here to float32); no dropout;
- **the chip's share**: the gate keeps its published width, but only
  experts ``first .. first + held`` exist here; what the absent experts
  would have added is left out (their pairs are chosen, weighted and
  normalised as published, then dropped), and that partial result goes
  on to the next layer. The vocabulary is the configuration's slice;
- RoPE is applied on the published interleaved lane pairs ``(2i, 2i +
  1)`` directly, where ``transformers`` first permutes the lanes to two
  halves: the same rotation, and q . k is unchanged by a permutation
  applied to both;
- a group outside the kept groups has its choice scores set to 0, as
  published (``masked_fill(~mask, 0.0)``); ties go to the lower index
  (``torch.topk`` leaves them open);
- the multi-token-prediction module (``num_nextn_predict_layers``) is
  not run: ``transformers`` drops its weights at load;
- the checkpoint layout of the held experts is the program's stacked
  one: ``mlp.experts.gate_up_proj [held, hidden, 2 f]`` (gate columns,
  then up columns) and ``mlp.experts.down_proj [held, f, hidden]``;
- every sequence is run on its own (``lax.map``), attention in blocks
  of query rows: the mathematics of the whole, sized to fit.

``lowp`` is the control of the comparison that decides ``correct``: the
same mathematics with every matmul operand (the gate's too) rounded to
float8 (e4m3, one scale per tensor), the nearest precision below the
bf16 that the configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HI = jax.lax.Precision.HIGHEST
Q_BLOCK = 256


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


# -- YaRN rotary embedding ------------------------------------------------------

def mscale(factor, m):
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def inv_frequencies(cfg):
    """The rope lanes' inverse frequencies ``[rope / 2]`` and the
    multiplier of cos and sin (``m(mscale) / m(mscale_all_dim)``)."""
    dim, base = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    sc = cfg.get("rope_scaling")
    extra = base ** (-np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not sc:
        return extra.astype(np.float32), 1.0
    factor, orig = float(sc["factor"]), \
        float(sc["original_max_position_embeddings"])
    inter = extra / factor

    def pair_of(turns):     # the lane pair that makes `turns` turns
        return dim * math.log(orig / (turns * 2 * math.pi)) \
            / (2 * math.log(base))

    low = max(math.floor(pair_of(sc["beta_fast"])), 0)
    high = min(math.ceil(pair_of(sc["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    inv = inter * ramp + extra * (1 - ramp)
    return inv.astype(np.float32), \
        mscale(factor, sc["mscale"]) / mscale(factor, sc["mscale_all_dim"])


def softmax_scale(cfg):
    scale = (cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]) ** -0.5
    sc = cfg.get("rope_scaling")
    if sc and sc.get("mscale_all_dim"):
        scale *= mscale(float(sc["factor"]), sc["mscale_all_dim"]) ** 2
    return scale


def rope_interleaved(x, positions, cfg):
    """x ``[T, ..., rope]``: lane pair ``(2i, 2i + 1)`` is rotated by
    ``positions * inv_freq[i]``."""
    inv, mult = inv_frequencies(cfg)
    ang = positions.astype(F32)[:, None] * jnp.asarray(inv)[None, :]
    shape = (x.shape[0],) + (1,) * (x.ndim - 2) + (inv.shape[0],)
    cos = (jnp.cos(ang) * F32(mult)).reshape(shape)
    sin = (jnp.sin(ang) * F32(mult)).reshape(shape)
    a, b = x[..., 0::2], x[..., 1::2]
    out = jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1)
    return out.reshape(x.shape)


# -- attention ---------------------------------------------------------------

def attention(x, w, cfg, lowp):
    """One sequence ``x [T, hidden]`` (already normed) -> ``[T,
    hidden]``: per-head keys and values expanded from the latent."""
    t = x.shape[0]
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    rank, eps = cfg["kv_lora_rank"], cfg["rms_norm_eps"]
    pos = jnp.arange(t)
    c_q = rms_norm(_mm(x, w["self_attn.q_a_proj.weight"], lowp),
                   w["self_attn.q_a_layernorm.weight"], eps)
    q = _mm(c_q, w["self_attn.q_b_proj.weight"], lowp) \
        .reshape(t, h, dn + dr)
    ckv = _mm(x, w["self_attn.kv_a_proj_with_mqa.weight"], lowp)
    c_kv = rms_norm(ckv[:, :rank], w["self_attn.kv_a_layernorm.weight"],
                    eps)
    k_pe = rope_interleaved(ckv[:, rank:], pos, cfg)          # [T, dr]
    q_pe = rope_interleaved(q[..., dn:], pos, cfg)            # [T, H, dr]
    kv = _mm(c_kv, w["self_attn.kv_b_proj.weight"], lowp) \
        .reshape(t, h, dn + dv)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = F32(softmax_scale(cfg))
    qb = max(d for d in range(1, min(Q_BLOCK, t) + 1) if t % d == 0)

    def block(i):
        rows = i * qb + jnp.arange(qb)
        s = (jnp.einsum("qhd,khd->hqk", _q(q[rows, :, :dn], lowp),
                        _q(k_nope, lowp), precision=HI)
             + jnp.einsum("qhd,kd->hqk", _q(q_pe[rows], lowp),
                          _q(k_pe, lowp), precision=HI)) * scale
        s = jnp.where(pos[None, None, :] <= rows[None, :, None], s,
                      F32(-1e30))
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("hqk,khd->qhd", _q(p, lowp), _q(v, lowp),
                          precision=HI)

    o = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, h * dv)
    return _mm(o, w["self_attn.o_proj.weight"], lowp)


# -- the expert layer -----------------------------------------------------------

def route(x, w, cfg, lowp):
    """``(idx [T, k], weight [T, k])`` over the gate's full width."""
    e = cfg["gate_width"]
    groups, kept, k = cfg["n_group"], cfg["topk_group"], \
        cfg["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm(x, w["mlp.gate.weight"], lowp))
    choice = s + w["mlp.gate.e_score_correction_bias"].astype(F32)[None]
    per = e // groups
    grouped = choice.reshape(-1, groups, per)
    group_score = jnp.sort(grouped, axis=-1)[..., -2:].sum(-1)
    # a group is kept if fewer than `kept` groups beat it (a tie goes
    # to the lower index)
    g = jnp.arange(groups)
    beats = (group_score[:, None, :] > group_score[:, :, None]) | (
        (group_score[:, None, :] == group_score[:, :, None])
        & (g[None, None, :] < g[None, :, None]))
    keep = beats.sum(-1) < kept                               # [T, G]
    choice = jnp.where(jnp.repeat(keep, per, axis=1), choice, F32(0.0))
    idx = []
    for _ in range(k):          # the k largest, one argmax at a time
        top = jnp.argmax(choice, axis=-1)
        idx.append(top)
        choice = jnp.where(jax.nn.one_hot(top, e, dtype=bool),
                           F32(-jnp.inf), choice)
    idx = jnp.stack(idx, axis=-1)
    weight = jnp.take_along_axis(s, idx, axis=-1)
    if cfg["norm_topk_prob"]:
        weight = weight / (weight.sum(-1, keepdims=True) + F32(1e-20))
    return idx, weight * F32(cfg["routed_scaling_factor"])


def experts(x, w, cfg, lowp):
    """Shared expert on every token, plus the held experts' part of the
    routed output: a dense loop, each held expert on all rows, gated by
    the weight the router gave it (0 where it was not chosen)."""
    idx, weight = route(x, w, cfg, lowp)
    f = cfg["moe_intermediate_size"]
    gate_up, down = w["mlp.experts.gate_up_proj"], \
        w["mlp.experts.down_proj"]
    y = swiglu(x, w["mlp.shared_experts.gate_proj.weight"],
               w["mlp.shared_experts.up_proj.weight"],
               w["mlp.shared_experts.down_proj.weight"], lowp)

    def one(y, e):
        gate = jnp.sum(jnp.where(idx == cfg["expert_first"] + e, weight,
                                 F32(0.0)), axis=-1)
        out = swiglu(x, gate_up[e][:, :f], gate_up[e][:, f:], down[e],
                     lowp)
        return y + gate[:, None] * out, None

    y, _ = jax.lax.scan(one, y, jnp.arange(gate_up.shape[0]))
    return y


def layer_forward(h, w, cfg, dense, lowp=False):
    """One decoder layer over ``h [B, T, hidden]``, a sequence at a
    time. ``w``: this layer's leaves by short name; ``dense``: whether
    it is one of the leading dense layers."""
    eps = cfg["rms_norm_eps"]

    def one(hs):
        hs = hs + attention(rms_norm(hs, w["input_layernorm.weight"], eps),
                            w, cfg, lowp)
        x = rms_norm(hs, w["post_attention_layernorm.weight"], eps)
        if dense:
            return hs + swiglu(x, w["mlp.gate_proj.weight"],
                               w["mlp.up_proj.weight"],
                               w["mlp.down_proj.weight"], lowp)
        return hs + experts(x, w, cfg, lowp)

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
        h = layer_forward(h, w, cfg, i < cfg["first_k_dense_replace"],
                          lowp)
    return head(h, weights["model.norm.weight"], weights["lm_head.weight"],
                cfg, lowp)

"""Plain reference for Phi-4-mini-flash-reasoning (``model_type:
phi4flash``: the SambaY decoder-hybrid-decoder, arXiv:2507.06607):
forward only.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision, one layer at a time, written from the equations the public
``config.json`` and the cited papers fix. With ``N`` layers and ``i`` a
layer's index: layers ``0 .. N/2 - 1`` are ``mamba`` at even ``i`` and
``swa`` at odd; layer ``N/2`` is ``mamba`` and its gated inner output
is the memory ``m``; layer ``N/2 + 1`` is ``full``; after it ``gmu`` at
even ``i`` and ``cross`` at odd. Every layer is ``h = h + mixer(ln(h,
input_layernorm))``, ``h = h + fc2(up * silu(gate))`` over ``[gate |
up] = fc1(ln(h, post_attention_layernorm))``; after the last, ``ln(h,
final_layernorm)`` and the head tied to the embedding.

- ``mamba`` (Mamba-1): ``[x, z] = u W_in``; ``x = silu(conv(x) +
  b_conv)``, a causal depthwise filter of ``mamba_d_conv`` taps (tap
  ``j`` multiplies the input ``3 - j`` rows back, zeros before the
  start); ``[d, B, C] = x W_x``; ``dt = softplus(d W_dt + b_dt)``; from
  a zero state ``s [E, S]``, a token at a time in a ``lax.scan``: ``s =
  exp(dt A) s + (dt x) B^T``, ``y = s C + D x``, ``A = -exp(A_log)``;
  ``out = (y silu(z)) W_out``.
- differential attention (``swa``, ``full``, ``cross``; DIFF
  Transformer, arXiv:2410.05258): head ``j`` of ``H/2`` takes ``q1 =
  q_{2j}``, ``q2 = q_{2j+1}``; kv pair ``p = j // ((H/2) / (H_kv/2))``
  takes ``k1 = k_{2p}``, ``k2 = k_{2p+1}``, ``V = [v_{2p} | v_{2p+1}]``;
  ``o = softmax(q1 k1^T / sqrt(D)) V - lambda softmax(q2 k2^T / sqrt(D))
  V``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 i)``; ``(1 - lambda_init) rms(o,
  subln)`` over the head's ``2D`` lanes (eps ``layer_norm_eps``); ``out
  = concat(o) W_o``. Causal; ``swa`` also drops keys at or before ``t -
  sliding_window`` (a plain mask); ``cross`` projects q alone and reads
  the ``full`` layer's k and v. No positional encoding.
- ``gmu``: ``out = (m silu(u W_1)) W_2``, ``m`` the memory at the same
  position.

Every row goes through every layer (the cross-decoder too). No kernel,
no cache, no batching: one sequence at a time. It imports nothing of
``paddle_tpu`` and takes its weights from the benchmark's seeded
generator, never from the program.

Assumed (the configuration file's ``assumed``, which the catalog row
does not fix): the Mamba sizes ``d_state`` 16, ``d_conv`` 4, ``expand``
2, ``dt_rank`` ceil(hidden / 16), a conv bias and no projection biases;
the stripe pairing of heads above; ``m`` taken after the ``silu(z)``
gate; no positional encoding; no bias on ``W_qkv`` / ``W_o``, a bias on
every LayerNorm; the window counts ``sliding_window`` keys including the
query's own.

``lowp`` is the control of the comparison that decides ``correct``: the
same mathematics with every matmul operand rounded to float8 (e4m3, one
scale per tensor), the nearest precision below the bf16 that the
configuration states.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

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


def layer_kind(cfg, i):
    n = cfg["num_hidden_layers"]
    if i < n // 2:
        return "mamba" if i % cfg["mb_per_layer"] == 0 else "swa"
    if i == n // 2:
        return "mamba"
    if i == n // 2 + 1:
        return "full"
    return "gmu" if i % 2 == 0 else "cross"


def mamba_dims(cfg):
    """``(E, S, dt rank, conv taps)``."""
    c = cfg["hidden_size"]
    return (cfg["mamba_expand"] * c, cfg["mamba_d_state"],
            cfg.get("mamba_dt_rank") or math.ceil(c / 16),
            cfg["mamba_d_conv"])


def layer_norm(x, w, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + F32(eps)) * w.astype(F32) \
        + b.astype(F32)


def causal_conv(x, filt):
    """``x [T, E]`` through ``filt [E, L]``: the sum of ``L`` shifted
    copies, zeros before the start."""
    t, e = x.shape
    out = jnp.zeros_like(x)
    for j in range(filt.shape[1]):
        back = filt.shape[1] - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros((back, e), F32), x[:t - back]]) if back else x
        out = out + filt[:, j].astype(F32)[None, :] * shifted
    return out


# -- mixers -------------------------------------------------------------------

def mamba(x, w, cfg, lowp):
    """One sequence ``x [T, C]`` (normed) -> ``(out [T, C], m [T, E])``."""
    e, s_dim, rank, _taps = mamba_dims(cfg)
    xx, z = jnp.split(_mm(x, w["mixer.in_proj.weight"], lowp), 2, axis=-1)
    xx = jax.nn.silu(causal_conv(xx, w["mixer.conv1d.weight"])
                     + w["mixer.conv1d.bias"].astype(F32))
    dbc = _mm(xx, w["mixer.x_proj.weight"], lowp)
    d, b, c = dbc[:, :rank], dbc[:, rank:rank + s_dim], dbc[:, rank + s_dim:]
    dt = jax.nn.softplus(_mm(d, w["mixer.dt_proj.weight"], lowp)
                         + w["mixer.dt_proj.bias"].astype(F32))
    a = -jnp.exp(w["mixer.A_log"].astype(F32))              # [E, S]

    def token(s, row):
        dt_t, x_t, b_t, c_t = row
        s = jnp.exp(dt_t[:, None] * a) * s + (dt_t * x_t)[:, None] * b_t[None]
        return s, jnp.sum(s * c_t[None], axis=-1)

    _, y = jax.lax.scan(token, jnp.zeros((e, s_dim), F32), (dt, xx, b, c))
    m = (y + w["mixer.D"].astype(F32)[None] * xx) * jax.nn.silu(z)
    return _mm(m, w["mixer.out_proj.weight"], lowp), m


def lambda_init(i):
    return 0.8 - 0.6 * math.exp(-0.3 * i)


def diff_attention(x, w, cfg, kind, init, kv, lowp):
    """One sequence ``x [T, C]`` (normed) -> ``(out [T, C], (k, v))``
    of a ``kind`` layer whose ``lambda_init`` is ``init``; ``kv`` is the
    ``full`` layer's for a ``cross`` layer."""
    t = x.shape[0]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // h
    if kind == "cross":
        q = _mm(x, w["attn.Wq.weight"], lowp).reshape(t, h, d)
        k, v = kv
    else:
        qkv = _mm(x, w["attn.Wqkv.weight"], lowp)
        q = qkv[:, :h * d].reshape(t, h, d)
        k = qkv[:, h * d:(h + hkv) * d].reshape(t, hkv, d)
        v = qkv[:, (h + hkv) * d:].reshape(t, hkv, d)
    g = (h // 2) // (hkv // 2)
    pair = jnp.arange(h // 2) // g
    q1, q2 = q[:, 0::2], q[:, 1::2]                          # [T, H/2, D]
    k1, k2 = k[:, 0::2][:, pair], k[:, 1::2][:, pair]
    vv = jnp.concatenate([v[:, 0::2], v[:, 1::2]], axis=-1)[:, pair]
    init = jnp.asarray(init, F32)
    lam = jnp.exp(jnp.sum(w["attn.lambda_q1"].astype(F32)
                          * w["attn.lambda_k1"].astype(F32))) \
        - jnp.exp(jnp.sum(w["attn.lambda_q2"].astype(F32)
                          * w["attn.lambda_k2"].astype(F32))) + init
    pos = jnp.arange(t)
    window = cfg["sliding_window"] if kind == "swa" else None
    scale = F32(d ** -0.5)
    qb = max(n for n in range(1, min(Q_BLOCK, t) + 1) if t % n == 0)

    def block(bi):
        rows = bi * qb + jnp.arange(qb)
        keep = pos[None, :] <= rows[:, None]
        if window is not None:
            keep = keep & (pos[None, :] > rows[:, None] - window)

        def attend(qq, kk):
            s = jnp.einsum("qjd,kjd->jqk", _q(qq[rows], lowp), _q(kk, lowp),
                           precision=HI) * scale
            p = jax.nn.softmax(jnp.where(keep[None], s, F32(-1e30)), axis=-1)
            return jnp.einsum("jqk,kjd->qjd", _q(p, lowp), _q(vv, lowp),
                              precision=HI)

        o = attend(q1, k1) - lam * attend(q2, k2)            # [qb, H/2, 2D]
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True)
                              + F32(cfg["layer_norm_eps"]))
        return o * w["attn.subln.weight"].astype(F32) * (F32(1.0) - init)

    o = jax.lax.map(block, jnp.arange(t // qb)).reshape(t, h * d)
    return _mm(o, w["attn.out_proj.weight"], lowp), (k, v)


def gmu(x, m, w, lowp):
    return _mm(m * jax.nn.silu(_mm(x, w["mixer.in_proj.weight"], lowp)),
               w["mixer.out_proj.weight"], lowp)


def layer_forward(h, m, k, v, w, cfg, kind, init, memory=False,
                  lowp=False):
    """One layer of ``kind`` over ``h [B, T, C]``, a sequence at a time,
    carrying the memory ``m [B, T, E]`` (``memory``: this layer makes
    it) and the ``full`` layer's ``k, v [B, T, H_kv, D]`` (zeros until
    those layers have run); ``init`` is the layer's ``lambda_init``.
    ``w``: the layer's leaves by short name. Returns ``(h, m, k, v)``."""
    eps = cfg["layer_norm_eps"]

    def one(args):
        hs, ms, ks, vs = args
        u = layer_norm(hs, w["input_layernorm.weight"],
                       w["input_layernorm.bias"], eps)
        if kind == "mamba":
            a, mem = mamba(u, w, cfg, lowp)
            ms = mem if memory else ms
        elif kind == "gmu":
            a = gmu(u, ms, w, lowp)
        else:
            a, (kk, vv) = diff_attention(u, w, cfg, kind, init, (ks, vs),
                                         lowp)
            if kind == "full":
                ks, vs = kk, vv
        hs = hs + a
        u = layer_norm(hs, w["post_attention_layernorm.weight"],
                       w["post_attention_layernorm.bias"], eps)
        gate, up = jnp.split(_mm(u, w["mlp.fc1.weight"], lowp), 2, axis=-1)
        return (hs + _mm(up * jax.nn.silu(gate), w["mlp.fc2.weight"], lowp),
                ms, ks, vs)

    return jax.lax.map(one, (h, m, k, v))


def carry(cfg, b, t):
    """The zeros ``(m, k, v)`` a batch of ``b`` sequences of ``t`` rows
    starts the layers with."""
    e = mamba_dims(cfg)[0]
    hkv = cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (jnp.zeros((b, t, e), F32), jnp.zeros((b, t, hkv, d), F32),
            jnp.zeros((b, t, hkv, d), F32))


def embed(ids, table):
    return jnp.take(table, ids, axis=0).astype(F32)


def head(h, w_norm, b_norm, table, cfg, lowp=False):
    """The final LayerNorm and the head tied to the embedding ``table
    [V, C]``."""
    return _mm(layer_norm(h, w_norm, b_norm, cfg["layer_norm_eps"]),
               table.T, lowp)


def forward(weights, ids, cfg, lowp=False):
    """Whole forward from a full weight dict: logits ``[B, T, V]``."""
    table = weights["model.embed_tokens.weight"]
    h = embed(ids, table)
    m, k, v = carry(cfg, *ids.shape)
    for i in range(cfg["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        w = {key[len(pre):]: val for key, val in weights.items()
             if key.startswith(pre)}
        h, m, k, v = layer_forward(
            h, m, k, v, w, cfg, layer_kind(cfg, i), lambda_init(i),
            i == cfg["num_hidden_layers"] // 2, lowp)
    return head(h, weights["model.final_layernorm.weight"],
                weights["model.final_layernorm.bias"], table, cfg, lowp)

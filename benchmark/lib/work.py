"""Operations and bytes that the algorithms need, from shapes alone.

These count what the mathematics requires, not what an implementation
does: no padded grid steps, no recomputation, no relayouts. A share of a
roofline built on them reads the same whatever later implements the
kernel. bf16 everywhere: 2 bytes an element. ``cfg`` is a configuration
file's dict.
"""
from __future__ import annotations

BYTES = 2


def dims(cfg):
    h = cfg["hidden_size"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = h // nh
    return h, cfg["intermediate_size"], cfg["vocab_size"], nh, nkv, hd


def layer_matmul_params(cfg):
    """Weights of one decoder layer that a token is multiplied with."""
    h, f, _v, _nh, nkv, hd = dims(cfg)
    return h * h + 2 * h * nkv * hd + h * h + 3 * h * f


def head_params(cfg):
    return cfg["hidden_size"] * cfg["vocab_size"]


def matmul_params(cfg):
    """All of them: the layers and the head (the tied embedding counts
    once, as the head; a lookup is not a multiplication)."""
    return cfg["num_hidden_layers"] * layer_matmul_params(cfg) \
        + head_params(cfg)


def attn_flops_token(cfg, ctx):
    """One layer's attention for one query token over ``ctx`` keys:
    QK^T and PV, 2 FLOPs a multiply-add."""
    _h, _f, _v, nh, _nkv, hd = dims(cfg)
    return 4 * ctx * nh * hd


# -- serving ---------------------------------------------------------------

def serve_tokens(requests):
    """``requests``: [(prompt_len, prefilled, first_out, n_out)] — the
    part of each request that was processed: whether its prompt was
    prefilled, and output tokens ``first_out .. first_out + n_out - 1``
    (0-based) were decoded. Yields (rows, ctx_sum, emits) per request:
    token rows pushed through the layers, the summed live context of
    those rows, and rows that went through the head."""
    for p, prefilled, first, n in requests:
        rows = ctx = emits = 0
        if prefilled:
            rows += p
            ctx += p * (p + 1) // 2
            emits += 1                      # the first token comes off it
        # output token j (0-based) is produced by a row at context p + j;
        # token 0 comes off the prefill, so decode rows are j >= 1
        lo, hi = max(first, 1), first + n
        if hi > lo:
            k = hi - lo
            rows += k
            ctx += k * p + (lo + hi - 1) * k // 2
            emits += k
        yield rows, ctx, emits


def tick_flops(cfg, requests):
    """Model FLOPs of the tokens processed: 2 per weight a row touches in
    every layer, the head for rows that emit a token, attention over the
    live context."""
    layers = cfg["num_hidden_layers"]
    total = 0
    for rows, ctx, emits in serve_tokens(requests):
        total += 2 * rows * layers * layer_matmul_params(cfg)
        total += 2 * emits * head_params(cfg)
        total += layers * attn_flops_token(cfg, 1) * ctx
    return total


def ragged_attn_work(cfg, requests, prefill_chunk):
    """(flops, bytes) of paged attention over the LIVE rows and context,
    all layers. Bytes: each decode row reads its whole context's K and V
    once; a prefill chunk reads the context up to its end once, however
    many rows it holds; plus q in and out per row."""
    h, _f, _v, nh, nkv, hd = dims(cfg)
    layers = cfg["num_hidden_layers"]
    kv_tok = 2 * nkv * hd * BYTES
    flops = bytes_ = 0
    for (p, prefilled, first, n), (rows, ctx, _e) in zip(
            requests, serve_tokens(requests)):
        flops += layers * attn_flops_token(cfg, 1) * ctx
        bytes_ += layers * rows * 2 * nh * hd * BYTES
        if prefilled:
            ends = list(range(prefill_chunk, p, prefill_chunk)) + [p]
            bytes_ += layers * kv_tok * sum(ends)
        lo, hi = max(first, 1), first + n
        if hi > lo:
            k = hi - lo
            bytes_ += layers * kv_tok * (k * p + (lo + hi - 1) * k // 2)
    return flops, bytes_


def fused_proj_work(cfg, rows, ticks):
    """(flops, bytes) of the four projections of every layer (q/k/v and
    gate/up behind a norm; o and down into the residual): every tick
    reads each weight once, every row is multiplied with each."""
    h, f, _v, _nh, _nkv, _hd = dims(cfg)
    layers = cfg["num_hidden_layers"]
    w = layer_matmul_params(cfg)
    act = rows * (4 * h + 3 * f + 2 * h) * BYTES     # in/out of the four
    return (2 * rows * layers * w,
            layers * (ticks * w * BYTES + act))


# -- training --------------------------------------------------------------

def flash_attn_work(cfg, batch, seq):
    """(flops, bytes) of causal attention forward and backward in every
    layer of one step. Forward: QK^T and PV over the causal half. The
    backward's mathematics needs four products of that size (dV, dP, dQ,
    dK) where the forward needs two: 3x the forward in all, as the
    step's MFU counts it. A kernel that keeps no score matrix computes
    the scores a fifth time; that is its own cost, not the algorithm's."""
    _h, _f, _v, nh, nkv, hd = dims(cfg)
    layers = cfg["num_hidden_layers"]
    fwd = 4 * batch * nh * seq * seq * hd // 2
    qo = batch * seq * nh * hd * BYTES
    kv = batch * seq * nkv * hd * BYTES
    fwd_bytes = 2 * qo + 2 * kv                # q, k, v in; o out
    bwd_bytes = 4 * qo + 4 * kv                # q,k,v,o,do in; dq,dk,dv out
    return layers * fwd * 3, layers * (fwd_bytes + bwd_bytes)


def train_flops_token(cfg, seq):
    """Forward + backward model FLOPs per token, no recomputation:
    6 per matmul weight, and attention at the causal mean context."""
    layers = cfg["num_hidden_layers"]
    attn = layers * attn_flops_token(cfg, (seq + 1) / 2.0)
    return 6 * matmul_params(cfg) + 3 * attn

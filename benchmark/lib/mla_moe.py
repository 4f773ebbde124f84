"""Work of a latent-attention, sparse-expert decoder, from shapes alone,
and the readers of its metrics.

``lib/work.py`` counts a dense decoder (``h * h`` attention projections,
one FFN width); here the attention has low-rank projections and a latent
cache, the expert layers a shared expert, a gate and routed experts of
which this chip holds a share. As there, the counts are what the
mathematics requires, not what an implementation does; bf16 everywhere.
``cfg`` is a configuration file's dict of the ``deepseek_v3`` family.

What comes from the program's own counters (docs/OPS.md "Tick phases"):
``moe_pairs_local`` in ``engine.stats()`` — the (row, expert) pairs that
fell on experts held here — and ``moe_pairs`` / ``moe_touched`` /
``moe_hot`` on every ``tick`` span. The experts' work is counted from
them, never from an expectation of the routing. A program without them
gives ``None`` and the metric is left out.
"""
from __future__ import annotations

from . import peaks, phases, readers, work

BYTES = 2


def attn_params(cfg):
    """Weights of one layer's attention a row is multiplied with,
    NON-absorbed (q_a, q_b, kv_a, kv_b, o)."""
    h, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    qr, kvr = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (h * qr + qr * nh * (dn + dr) + h * (kvr + dr)
            + kvr * nh * (dn + dv) + nh * dv * h)


def expert_params(cfg):
    """One routed expert (SwiGLU: gate, up, down)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def row_params(cfg):
    """Weights every row touches, all layers, the routed experts left
    out: attention; in an expert layer the shared expert and the gate
    (at its published width); in a dense layer its FFN."""
    h = cfg["hidden_size"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    gate = h * cfg["n_routed_experts"] * cfg["deployment"]["expert_parallel"]
    return (layers * attn_params(cfg)
            + dense * 3 * h * cfg["intermediate_size"]
            + (layers - dense) * (cfg["n_shared_experts"]
                                  * expert_params(cfg) + gate))


def attn_flops_ctx(cfg):
    """Non-absorbed attention of one query row over one context
    position, one layer: QK over nope + rope, PV over the value dim."""
    return 2 * cfg["num_attention_heads"] * (
        cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
        + cfg["v_head_dim"])


def tick_flops(cfg, requests, pairs_local):
    """Model FLOPs of the tokens processed (``requests`` as
    ``work.serve_tokens`` takes them): 2 per weight a row touches, the
    head for rows that emit a token, attention over the live context,
    and 2 per weight of a routed expert for each pair computed here."""
    layers = cfg["num_hidden_layers"]
    total = 2 * pairs_local * expert_params(cfg)
    for rows, ctx, emits in work.serve_tokens(requests):
        total += 2 * rows * row_params(cfg)
        total += 2 * emits * cfg["hidden_size"] * cfg["vocab_size"]
        total += layers * attn_flops_ctx(cfg) * ctx
    return total


def mla_attn_work(cfg, requests, prefill_chunk):
    """(flops, bytes) of latent attention in its ABSORBED form — the
    cheaper mathematics, whatever is implemented — over the live rows
    and context, all layers. FLOPs: per query row and context position
    a score over ``kv_lora_rank + rope`` and a weighted sum over
    ``kv_lora_rank``, every head. Bytes: the latent row (``kv_lora_rank
    + rope`` values) of each context position, read once by a decode row
    and once by a prefill chunk however many rows it holds; the absorbed
    query in and the weighted latent out per row and head."""
    nh, layers = cfg["num_attention_heads"], cfg["num_hidden_layers"]
    key = cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]
    val = cfg["kv_lora_rank"]
    flops = bytes_ = 0
    for (p, prefilled, first, n), (rows, ctx, _e) in zip(
            requests, work.serve_tokens(requests)):
        flops += layers * 2 * nh * (key + val) * ctx
        bytes_ += layers * rows * nh * (key + val) * BYTES
        if prefilled:
            ends = list(range(prefill_chunk, p, prefill_chunk)) + [p]
            bytes_ += layers * key * BYTES * sum(ends)
        lo, hi = max(first, 1), first + n
        if hi > lo:
            k = hi - lo
            bytes_ += layers * key * BYTES * (k * p + (lo + hi - 1) * k // 2)
    return flops, bytes_


def moe_gmm_work(cfg, pairs, touched):
    """(flops, bytes) of the two grouped matmuls over the experts held:
    2 per weight of an expert for each pair; every touched expert's
    weights read once a tick, each pair's row in and out."""
    h = cfg["hidden_size"]
    return (2 * pairs * expert_params(cfg),
            touched * expert_params(cfg) * BYTES + pairs * 2 * h * BYTES)


# -- readers -------------------------------------------------------------------

def _ticks(run, t0, t1):
    """Arguments of the ``tick`` spans that ended in [t0, t1) and carry
    the share's counts; None where the program records none."""
    events = phases.events_of(run)
    if events is None:
        return None
    out = [e["args"] for e in events
           if e["name"] == "tick" and e["tid"] == 0
           and "moe_pairs" in (e["args"] or {})
           and t0 <= e["t0"] + e["dur"] < t1]
    return out or None


def tick_mfu(run):
    if "moe_pairs_local" not in run.counters:
        return None
    reqs = readers.processed(run.records, run.t_open, run.t_close)
    if not reqs:
        return None
    flops = tick_flops(run.cfg, reqs, run.counters["moe_pairs_local"])
    return peaks.share(
        flops / peaks.for_device(run.device_kind)["flops_bf16"],
        run.window_s * run.chips, "tick_mfu")


def work_mla_attn(run, _passes):
    reqs = readers.processed(run.records, *run.interval())
    return mla_attn_work(run.cfg, reqs, run.cell["engine"]["prefill_chunk"])


def work_moe_gmm(run, _passes):
    ticks = _ticks(run, *run.interval())    # moe_gmm_roofline saw some
    return moe_gmm_work(run.cfg, sum(t["moe_pairs"] for t in ticks),
                        sum(t["moe_touched"] for t in ticks))


def moe_gmm_roofline(run, scopes):
    """``readers.kernel_roofline`` of the grouped matmuls, left out
    where the program's ``tick`` spans carry no share counts."""
    if run.trace is None or _ticks(run, *run.interval()) is None:
        return None
    return readers.kernel_roofline(run, scopes, "lib.mla_moe:work_moe_gmm")


def expert_load_max_over_mean(run):
    """Per tick the busiest held expert's pairs (of any expert layer)
    over the mean of all held experts', weighted by the tick's pairs:
    sum of ``moe_hot`` over sum of ``moe_pairs`` / (expert layers x
    held)."""
    ticks = _ticks(run, run.t_open, run.t_close)
    if ticks is None:
        return None
    cfg = run.cfg
    groups = (cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]) \
        * cfg["n_routed_experts"]
    pairs = sum(t["moe_pairs"] for t in ticks)
    return sum(t["moe_hot"] for t in ticks) * groups / pairs \
        if pairs else None

"""Work of a decoder whose mixers are gated delta-rule linear attention
(KDA) or gated grouped-query attention, with an expert block in every
layer of which this chip holds a share, from shapes alone, and the
readers of its metrics.

As in ``lib/work.py`` and ``lib/mla_moe.py`` the counts are what the
mathematics requires, not what an implementation does; activations and
weights bf16 (2 bytes), the recurrent state float32 (4 bytes). ``cfg``
is a configuration file's dict of the ``solar_open2`` family. The
experts' work is counted from the program's own counters
(``moe_pairs_local``; ``moe_pairs`` / ``moe_touched`` / ``moe_hot`` on
every ``tick`` span: the grouped matmuls' roofline and the load ratio
are ``lib/mla_moe.py``'s, which reads the same two widths), the delta
rule's from ``kda_seats`` (seats advanced one row) and
``kda_chunk_rows`` (rows through the chunked form) on every ``tick``
span (docs/OPS.md "Tick phases"). A program without them gives ``None``
and the metric is left out.

**The recurrence, one row of one head** (``d`` = 128 key and value
channels, state ``S [d, d]``): decay ``d^2`` multiplies, ``S^T k`` ``2
d^2``, the rank-one update ``2 d^2``, ``S^T q`` ``2 d^2``: **7 d^2**
FLOPs, and ``S`` read and written once: ``2 x 4 d^2`` bytes, beside the
row's ``q, k, v, g, o`` (``5 d`` values) and ``beta``.

**The chunkwise (WY) form, one sub-chunk of C = 64 rows of one head**,
term by term at 2 FLOPs a multiply-add, the products dense as the form
states them: ``K+ S_0`` ``2 C d^2``; ``Q~ S_0`` ``2 C d^2``; ``K+ K-^T``
``2 C^2 d``; ``Q K^T`` ``2 C^2 d``; ``T (V - K+ S_0)`` ``2 C^2 d``;
``tril(Q K^T) V_new`` ``2 C^2 d``; ``(K to the end)^T V_new`` ``2 C
d^2``: **6 C d^2 + 8 C^2 d** = 6,291,456 + 4,194,304 = 10,485,760 a
sub-chunk a head, i.e. 163,840 a row a head, 10,485,760 a row over 64
heads (the inversion that gives ``T``, the decays and ``Diag(Gamma)
S_0`` are of lower order and left out). Bytes: the state once each way
a chunk, however many rows, and the rows' operands.
"""
from __future__ import annotations

from . import mla_moe, peaks, phases, readers, work

BYTES = 2           # weights, activations, KV
STATE_BYTES = 4     # the recurrent state
SUB = 64            # rows of a sub-chunk of the chunkwise form


def kda_dims(cfg):
    """``(heads, head size, taps, low-rank width)``."""
    la = cfg["linear_attn_config"]
    return (la["num_heads"], la["head_dim"], la["short_conv_kernel_size"],
            cfg.get("kda_low_rank") or la["head_dim"])


def gate_width(cfg):
    return cfg["n_routed_experts"] * cfg["deployment"]["expert_parallel"]


def kda_params(cfg):
    """One ``kda`` mixer: q, k, v, o; the low-rank decay and gate; the
    beta projection; the three filters; ``A_log``, ``dt_bias``,
    ``o_norm``."""
    h = cfg["hidden_size"]
    heads, d, taps, rank = kda_dims(cfg)
    hd = heads * d
    return (4 * h * hd + 2 * (h * rank + rank * hd) + h * heads
            + 3 * hd * taps + heads + hd + d)


def gqa_params(cfg):
    """One ``gqa`` mixer: q, gate and o over the query heads, k and v
    over the kv heads."""
    h, d = cfg["hidden_size"], cfg["head_dim"]
    nh, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return 3 * h * nh * d + 2 * h * nkv * d


def expert_params(cfg):
    """One expert (SwiGLU: gate, up, down)."""
    return mla_moe.expert_params(cfg)


def rest_params(cfg):
    """What a layer holds beside its mixer: the experts held, the shared
    expert, the router and its bias, the two norms."""
    h = cfg["hidden_size"]
    return ((cfg["n_routed_experts"] + cfg["n_shared_experts"])
            * expert_params(cfg) + h * gate_width(cfg) + gate_width(cfg)
            + 2 * h)


def gqa_layers(cfg):
    return len(cfg["gqa_layers"])


def kda_layers(cfg):
    return cfg["num_hidden_layers"] - gqa_layers(cfg)


def total_params(cfg):
    """Every parameter held on this chip."""
    h, v = cfg["hidden_size"], cfg["vocab_size"]
    return (kda_layers(cfg) * kda_params(cfg)
            + gqa_layers(cfg) * gqa_params(cfg)
            + cfg["num_hidden_layers"] * rest_params(cfg) + 2 * h * v + h)


def row_params(cfg):
    """Weights every row is multiplied with, all layers, the routed
    experts left out: each layer's mixer, shared expert and gate."""
    h = cfg["hidden_size"]
    return (kda_layers(cfg) * kda_params(cfg)
            + gqa_layers(cfg) * gqa_params(cfg)
            + cfg["num_hidden_layers"] * (
                cfg["n_shared_experts"] * expert_params(cfg)
                + h * gate_width(cfg)))


def kv_bytes_token(cfg):
    """Paged cache bytes of one position: K and V of the ``gqa`` layers
    alone."""
    return gqa_layers(cfg) * 2 * cfg["num_key_value_heads"] \
        * cfg["head_dim"] * BYTES


def state_bytes_slot(cfg):
    """Slot state of one serving slot: every ``kda`` layer's matrix
    state (float32) and the convolution's last ``taps - 1`` inputs of
    ``q | k | v`` (bf16)."""
    heads, d, taps, _rank = kda_dims(cfg)
    return kda_layers(cfg) * (heads * d * d * STATE_BYTES
                              + (taps - 1) * 3 * heads * d * BYTES)


def recurrent_flops_row(cfg):
    """The recurrence for one row of one layer: 7 d^2 a head."""
    heads, d, _taps, _rank = kda_dims(cfg)
    return 7 * d * d * heads


def chunk_flops_row(cfg):
    """The chunkwise form for one row of one layer: (6 C d^2 + 8 C^2 d)
    / C a head."""
    heads, d, _taps, _rank = kda_dims(cfg)
    return (6 * SUB * d * d + 8 * SUB * SUB * d) // SUB * heads


def row_operand_bytes(cfg):
    """One row's ``q, k, v, g`` in and ``o`` out of the recurrence, and
    its ``beta``, one layer."""
    heads, d, _taps, _rank = kda_dims(cfg)
    return (5 * heads * d + heads) * BYTES


def state_bytes_layer(cfg):
    """One seat's matrix state of one layer."""
    heads, d, _taps, _rank = kda_dims(cfg)
    return heads * d * d * STATE_BYTES


def kda_recurrent_work(cfg, seats):
    """(flops, bytes) of ``seats`` one-row advances summed over the
    ``kda`` layers' calls (a seat of one layer counts once): the state
    read and written once, the row's operands."""
    return (seats * recurrent_flops_row(cfg),
            seats * (2 * state_bytes_layer(cfg) + row_operand_bytes(cfg)))


def kda_chunk_work(cfg, chunks, rows):
    """(flops, bytes) of ``chunks`` chunk calls holding ``rows`` rows in
    all (a chunk of one layer counts once): the state once each way a
    chunk, the rows' operands."""
    return (rows * chunk_flops_row(cfg),
            chunks * 2 * state_bytes_layer(cfg)
            + rows * row_operand_bytes(cfg))


def tick_flops(cfg, requests, pairs_local):
    """Model FLOPs of the tokens processed (``requests`` as
    ``work.serve_tokens`` takes them): 2 per weight a row touches, the
    recurrence in the ``kda`` layers, attention over the live context in
    the ``gqa`` layers, the head for rows that emit a token, and 2 per
    weight of an expert for each (row, expert) pair computed here."""
    per_ctx = gqa_layers(cfg) * 4 * cfg["num_attention_heads"] \
        * cfg["head_dim"]
    per_row = 2 * row_params(cfg) \
        + kda_layers(cfg) * recurrent_flops_row(cfg)
    total = 2 * pairs_local * expert_params(cfg)
    for rows, ctx, emits in work.serve_tokens(requests):
        total += rows * per_row
        total += 2 * emits * cfg["hidden_size"] * cfg["vocab_size"]
        total += per_ctx * ctx
    return total


def ragged_attn_work(cfg, requests, prefill_chunk):
    """``work.ragged_attn_work`` over the ``gqa`` layers alone, at this
    family's head size (``hidden / heads`` is not it)."""
    return work.ragged_attn_work(
        dict(cfg, num_hidden_layers=gqa_layers(cfg),
             hidden_size=cfg["num_attention_heads"] * cfg["head_dim"]),
        requests, prefill_chunk)


# -- readers -------------------------------------------------------------------

def _kda_ticks(run, t0, t1):
    """Arguments of the ``tick`` spans that ended in [t0, t1) and carry
    the delta rule's counts; None where the program records none."""
    events = phases.events_of(run)
    if events is None:
        return None
    out = [e["args"] for e in events
           if e["name"] == "tick" and e["tid"] == 0
           and "kda_seats" in (e["args"] or {})
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


def work_ragged_attn(run, _passes):
    reqs = readers.processed(run.records, *run.interval())
    return ragged_attn_work(run.cfg, reqs,
                            run.cell["engine"]["prefill_chunk"])


def work_kda_recurrent(run, _passes):
    ticks = _kda_ticks(run, *run.interval())
    return kda_recurrent_work(
        run.cfg, kda_layers(run.cfg) * sum(t["kda_seats"] for t in ticks))


def work_kda_chunk(run, _passes):
    ticks = _kda_ticks(run, *run.interval())
    layers = kda_layers(run.cfg)
    return kda_chunk_work(
        run.cfg, layers * sum(1 for t in ticks if t["kda_chunk_rows"]),
        layers * sum(t["kda_chunk_rows"] for t in ticks))


def kda_roofline(run, scopes, work):
    """``readers.kernel_roofline`` of one of the delta rule's kernels,
    left out where the program's ``tick`` spans carry no counts of
    it."""
    if run.trace is None or _kda_ticks(run, *run.interval()) is None:
        return None
    return readers.kernel_roofline(run, scopes, work)

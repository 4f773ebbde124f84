"""Work of a decoder whose layers are gated short convolutions or
grouped-query attention, with experts that are all held on the chip,
from shapes alone, and the readers of its metrics.

As in ``lib/work.py`` and ``lib/mla_moe.py`` the counts are what the
mathematics requires, not what an implementation does; bf16
everywhere. ``cfg`` is a configuration file's dict of the ``lfm2_moe``
family. The experts' work is counted from the program's own counters
(``moe_pairs_local`` in ``engine.stats()``, ``moe_pairs`` /
``moe_touched`` / ``moe_hot`` on every ``tick`` span), never from an
expectation of the routing; the grouped matmuls' roofline is
``lib/mla_moe.py``'s, which reads the same two widths.
"""
from __future__ import annotations

from . import mla_moe, peaks, readers, work

BYTES = 2


def conv_params(cfg):
    """One ``conv`` mixer: ``in_proj [h, 3h]``, the ``L`` taps a
    channel of ``g``, ``out_proj [h, h]``."""
    h = cfg["hidden_size"]
    return h * 3 * h + cfg["conv_L_cache"] * h + h * h


def attn_params(cfg):
    """One attention mixer: q, k, v, out and the two per-head norms."""
    h, nh, nkv = (cfg["hidden_size"], cfg["num_attention_heads"],
                  cfg["num_key_value_heads"])
    d = h // nh
    return h * nh * d + 2 * h * nkv * d + nh * d * h + 2 * d


def gate_params(cfg):
    return cfg["hidden_size"] * cfg["num_experts"] + cfg["num_experts"]


def dense_params(cfg):
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def expert_params(cfg):
    """One expert (SwiGLU: gate, up, down)."""
    return mla_moe.expert_params(cfg)


def attn_layers(cfg):
    return sum(k == "full_attention" for k in cfg["layer_types"])


def row_params(cfg):
    """Weights every row touches, all layers, the experts left out:
    each layer's mixer; a dense layer's FFN; an expert layer's gate."""
    layers, dense = cfg["num_hidden_layers"], cfg["num_dense_layers"]
    n_attn = attn_layers(cfg)
    return ((layers - n_attn) * conv_params(cfg)
            + n_attn * attn_params(cfg) + dense * dense_params(cfg)
            + (layers - dense) * gate_params(cfg))


def kv_bytes_token(cfg):
    """Paged cache bytes of one position: K and V of the attention
    layers alone — a ``conv`` layer keeps no row a position."""
    d = cfg["hidden_size"] // cfg["num_attention_heads"]
    return attn_layers(cfg) * 2 * cfg["num_key_value_heads"] * d * BYTES


def state_bytes_slot(cfg):
    """Convolution state of one serving slot: the last ``L - 1`` rows
    of ``g`` of every ``conv`` layer."""
    n_conv = cfg["num_hidden_layers"] - attn_layers(cfg)
    return n_conv * (cfg["conv_L_cache"] - 1) * cfg["hidden_size"] * BYTES


def tick_flops(cfg, requests, pairs_local):
    """Model FLOPs of the tokens processed (``requests`` as
    ``work.serve_tokens`` takes them): 2 per weight a row touches, the
    head for rows that emit a token, attention over the live context in
    the attention layers, and 2 per weight of an expert for each (row,
    expert) pair computed."""
    per_ctx = attn_layers(cfg) * work.attn_flops_token(cfg, 1)
    total = 2 * pairs_local * expert_params(cfg)
    for rows, ctx, emits in work.serve_tokens(requests):
        total += 2 * rows * row_params(cfg)
        total += 2 * emits * cfg["hidden_size"] * cfg["vocab_size"]
        total += per_ctx * ctx
    return total


def ragged_attn_work(cfg, requests, prefill_chunk):
    """``work.ragged_attn_work`` over the ATTENTION layers alone."""
    return work.ragged_attn_work(
        dict(cfg, num_hidden_layers=attn_layers(cfg)), requests,
        prefill_chunk)


# -- readers -------------------------------------------------------------------
#
# ``host_share.cw``, ``moe_gmm_roofline.cw``, the idle split and the
# grid's live share are read by the readers that exist
# (``lib.phases``, ``lib.mla_moe``, ``lib.grid``): the engine sizes its
# span ring by its slots, so a window's spans are all there.

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


def expert_load_max_over_mean(run):
    """``mla_moe.expert_load_max_over_mean`` by this family's keys: per
    tick the busiest expert's pairs (of any expert layer) over the mean
    of all experts', weighted by the tick's pairs, over the window."""
    ticks = mla_moe._ticks(run, run.t_open, run.t_close)
    if ticks is None:
        return None
    cfg = run.cfg
    groups = (cfg["num_hidden_layers"] - cfg["num_dense_layers"]) \
        * cfg["num_experts"]
    pairs = sum(t["moe_pairs"] for t in ticks)
    return sum(t["moe_hot"] for t in ticks) * groups / pairs \
        if pairs else None

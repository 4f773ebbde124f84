"""Work of the Phi-4-mini-flash family (the SambaY decoder-hybrid-decoder:
Mamba layers, sliding-window and full differential attention, gated
memory units and cross-attention over the full layer's cache), from
shapes alone, and the readers of its metrics.

As in ``lib/work.py`` the counts are what the mathematics requires,
whatever implements it: weights, activations and caches bf16 (2
bytes), the scan's state and operands float32 (4 bytes). ``cfg`` is a
configuration file's dict of the ``phi4flash`` family. Per layer kind
(``reference.phi4flash.layer_kind``), with ``C`` hidden, ``F`` the FFN,
``H`` / ``H_kv`` heads of ``D``, ``E`` Mamba channels, ``S`` state
entries, ``R`` the dt rank, ``W`` the window:

- **attention**, every differential layer: a query row against one
  key position is ``2 H (D + 2D)`` FLOPs (both softmaxes' ``q k`` over
  ``D`` lanes, both weighted sums over the pair's ``2D`` value lanes);
  the keys a row attends and the K/V positions a tick reads come from
  the program's own counts on every ``tick`` span (``window_positions``
  / ``window_reads``, ``shared_kv_positions`` / ``shared_kv_reads``:
  docs/OPS.md "Tick phases"), a slot's K/V read ONCE a layer however
  many rows it has, ``2 H_kv D x 2`` B a position.
- **the scan**, one row of one Mamba layer: ``exp(dt A)``, the decay,
  ``B (dt x)``, the sum, ``C s`` and its sum, ``7 E S`` FLOPs; a seat's
  state ``E S x 4`` B read and written once a one-row advance, once
  each way a chunk; a row's ``dt``, ``x`` in and ``y`` out (``E`` each)
  and ``B``, ``C`` (``S`` each) at 4 B.

A program without the counts gives ``None`` and the metric is left out.
"""
from __future__ import annotations

from benchmark.reference.phi4flash import layer_kind, mamba_dims

from . import peaks, phases, readers

BYTES = 2
STATE_BYTES = 4


def dims(cfg):
    """``(C, F, H, H_kv, D, W, V)``."""
    c, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return (c, cfg["intermediate_size"], h, cfg["num_key_value_heads"],
            c // h, cfg["sliding_window"], cfg["vocab_size"])


def kinds(cfg):
    return [layer_kind(cfg, i) for i in range(cfg["num_hidden_layers"])]


def mixer_params(cfg, kind):
    """One mixer's parameters."""
    c, _f, h, hkv, d, _w, _v = dims(cfg)
    e, s, r, taps = mamba_dims(cfg)
    diff = 4 * d + 2 * d            # the four lambda vectors, subln
    return {"mamba": c * 2 * e + e * taps + e + e * (r + 2 * s) + r * e + e
            + e * s + e + e * c,
            "swa": c * (h + 2 * hkv) * d + h * d * c + diff,
            "full": c * (h + 2 * hkv) * d + h * d * c + diff,
            "cross": 2 * c * h * d + diff,
            "gmu": 2 * c * e}[kind]


def total_params(cfg):
    """Every parameter (the head is the embedding)."""
    c, f, _h, _hkv, _d, _w, v = dims(cfg)
    return (sum(mixer_params(cfg, k) + 3 * c * f + 4 * c for k in kinds(cfg))
            + v * c + 2 * c)


def kv_bytes_token(cfg):
    """The ``full`` layer's K and V of one position: the one
    full-length cache."""
    _c, _f, _h, hkv, d, _w, _v = dims(cfg)
    return 2 * hkv * d * BYTES


def attn_flops_position(cfg):
    """One query row against one key position of a differential layer."""
    _c, _f, h, _hkv, d, _w, _v = dims(cfg)
    return 2 * h * (d + 2 * d)


def row_flops(cfg, kind):
    """One row through one layer of ``kind``, attention's positions
    left out: 2 a weight of its products (the conv's taps too), the
    scan, the FFN."""
    c, f, h, hkv, d, _w, _v = dims(cfg)
    e, s, r, taps = mamba_dims(cfg)
    mixer = {"mamba": 2 * (c * 2 * e + e * (r + 2 * s) + r * e + e * c
                           + e * taps) + 7 * e * s,
             "swa": 2 * (c * (h + 2 * hkv) * d + h * d * c),
             "full": 2 * (c * (h + 2 * hkv) * d + h * d * c),
             "cross": 2 * 2 * c * h * d,
             "gmu": 2 * 2 * c * e}[kind]
    return mixer + 2 * 3 * c * f


def _clipped(n, w):
    """``sum(min(k, w) for k in 1 .. n)``."""
    m = min(n, w)
    return m * (m + 1) // 2 + (n - m) * w


def tick_flops(cfg, requests):
    """Model FLOPs of the tokens processed (``requests`` as
    ``work.serve_tokens`` takes them). Every row goes through the
    self-decoder and the ``full`` layer; a row that EMITS a token (a
    prompt's last, every decode row) also through the cross-decoder and
    the head: a prompt's other rows change nothing the cross-decoder's
    layers would give a later row."""
    c, _f, _h, _hkv, _d, w, v = dims(cfg)
    ks = kinds(cfg)
    cut = ks.index("full") + 1
    self_row = sum(row_flops(cfg, k) for k in ks[:cut])
    cross_row = sum(row_flops(cfg, k) for k in ks[cut:]) + 2 * c * v
    per_pos = attn_flops_position(cfg)
    swa, cross = ks.count("swa"), ks.count("cross")
    total = 0
    for p, prefilled, first, n in requests:
        # (context of a row = its position + 1) of the self-decoder's
        # rows and of the emitting rows
        ctx = list(range(1, p + 1)) if prefilled else []
        emit = [p] if prefilled else []
        lo, hi = max(first, 1), first + n
        dec = list(range(p + lo, p + hi))
        ctx += dec
        emit += dec
        total += len(ctx) * self_row + len(emit) * cross_row
        total += per_pos * (sum(ctx) + swa * sum(min(x, w) for x in ctx)
                            + cross * sum(emit))
    return total


def shared_kv_work(cfg, ticks):
    """(flops, bytes) of the differential calls over the ``full``
    layer's cache in ``ticks`` (the ``full`` layer over every row, each
    ``cross`` layer over one row a live slot): the keys attended, the
    cache read once a slot a layer, a row's q (``H D``) in and its
    ``H x 2D`` output out."""
    _c, _f, h, _hkv, d, _w, _v = dims(cfg)
    cross = kinds(cfg).count("cross")
    rows = sum(t["rows"] + cross * t["cross_rows"] for t in ticks)
    return (attn_flops_position(cfg) * sum(t["shared_kv_positions"]
                                           for t in ticks),
            kv_bytes_token(cfg) * sum(t["shared_kv_reads"] for t in ticks)
            + rows * (h * d + h * 2 * d) * BYTES)


def window_work(cfg, ticks):
    """(flops, bytes) of the ``swa`` layers' attention in ``ticks``:
    the keys attended, each slot's ring and rows read once a layer, a
    row's q in and output out."""
    _c, _f, h, _hkv, d, _w, _v = dims(cfg)
    swa = kinds(cfg).count("swa")
    rows = swa * sum(t["rows"] for t in ticks)
    return (attn_flops_position(cfg) * sum(t["window_positions"]
                                           for t in ticks),
            kv_bytes_token(cfg) * sum(t["window_reads"] for t in ticks)
            + rows * (h * d + h * 2 * d) * BYTES)


def scan_state_bytes(cfg):
    """One seat's scan state of one Mamba layer."""
    e, s, _r, _taps = mamba_dims(cfg)
    return e * s * STATE_BYTES


def scan_row_bytes(cfg):
    """One row's ``dt``, ``x``, ``y``, ``B`` and ``C``."""
    e, s, _r, _taps = mamba_dims(cfg)
    return (3 * e + 2 * s) * STATE_BYTES


def scan_flops_row(cfg):
    e, s, _r, _taps = mamba_dims(cfg)
    return 7 * e * s


def ssm_recurrent_work(cfg, seats):
    """(flops, bytes) of ``seats`` one-row advances summed over the
    Mamba layers' calls (a seat of one layer counts once)."""
    return (seats * scan_flops_row(cfg),
            seats * (2 * scan_state_bytes(cfg) + scan_row_bytes(cfg)))


def ssm_chunk_work(cfg, chunks, rows):
    """(flops, bytes) of ``chunks`` chunk calls holding ``rows`` rows in
    all: the state once each way a chunk, the rows' operands."""
    return (rows * scan_flops_row(cfg),
            chunks * 2 * scan_state_bytes(cfg) + rows * scan_row_bytes(cfg))


# -- readers ------------------------------------------------------------------

def _ticks(run, t0, t1):
    """Arguments of the ``tick`` spans that ended in [t0, t1) and carry
    this family's counts; None where the program records none."""
    events = phases.events_of(run)
    if events is None:
        return None
    out = [e["args"] for e in events
           if e["name"] == "tick" and e["tid"] == 0
           and "shared_kv_reads" in (e["args"] or {})
           and t0 <= e["t0"] + e["dur"] < t1]
    return out or None


def tick_mfu(run):
    reqs = readers.processed(run.records, run.t_open, run.t_close)
    if not reqs:
        return None
    flops = tick_flops(run.cfg, reqs)
    return peaks.share(
        flops / peaks.for_device(run.device_kind)["flops_bf16"],
        run.window_s * run.chips, "tick_mfu")


def cross_row_share(run):
    """Rows through the cross-decoder over rows through the layers
    before it, the window's ticks, in %: the prefill skip's guard."""
    ticks = _ticks(run, run.t_open, run.t_close)
    if ticks is None:
        return None
    rows = sum(t["rows"] for t in ticks)
    return 100.0 * sum(t["cross_rows"] for t in ticks) / rows \
        if rows else None


def work_shared_kv(run, _passes):
    return shared_kv_work(run.cfg, _ticks(run, *run.interval()))


def work_window(run, _passes):
    return window_work(run.cfg, _ticks(run, *run.interval()))


def _mamba_layers(cfg):
    return kinds(cfg).count("mamba")


def work_ssm_recurrent(run, _passes):
    ticks = _ticks(run, *run.interval())
    return ssm_recurrent_work(
        run.cfg, _mamba_layers(run.cfg) * sum(t["ssm_seats"] for t in ticks))


def work_ssm_chunk(run, _passes):
    ticks = _ticks(run, *run.interval())
    layers = _mamba_layers(run.cfg)
    return ssm_chunk_work(
        run.cfg, layers * sum(1 for t in ticks if t["ssm_chunk_rows"]),
        layers * sum(t["ssm_chunk_rows"] for t in ticks))


def roofline(run, scopes, work):
    """``readers.kernel_roofline`` of one of this family's kernels, left
    out where the program's ``tick`` spans carry no counts of it."""
    if run.trace is None or _ticks(run, *run.interval()) is None:
        return None
    return readers.kernel_roofline(run, scopes, work)

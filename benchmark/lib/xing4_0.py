"""Work of the manifold-constrained hyper-connections (mHC) of the
``xing4_0`` family, from shapes alone, and the reader of
``hc_mix_roofline``.

As in ``lib/mla_moe.py`` the counts are what the mathematics of the
connection requires, whatever implements it; activations bf16. With
``n`` streams of width ``C``, per row and connection:

- **FLOPs**: ``x~ Phi`` ``2 nC (2n + n^2)``; the mixing ``sum_j H_res[i,
  j] x[j]``, ``H_post[i] y`` and ``u = sum_i H_pre[i] x[i]`` ``2 (n^2 +
  2n) C``; the Sinkhorn ``iters x 2n^2`` divisions and sums, ``n`` lanes
  of 4 B: ``iters x 2 n^2 x 4`` (the issue's count; the sigmoids, the
  ``exp`` and the rms are of lower order and left out).
- **Bytes**: a boundary between two sublayers moves the streams in and
  out, the sublayer's output in and the next input ``u`` out: ``(2n +
  2) C`` values = ``10 C x 2`` B at ``n = 4``. The two end calls by
  their own shapes: the first reads the embedding and writes the streams
  and ``u`` (``(n + 2) C``), the last reads the streams and the last
  sublayer's output and writes the merged stream (``(n + 2) C``). So
  ``2L`` connections a row are ``2L - 1`` boundaries and the two ends;
  every call but the last reads one connection's ``Phi``, ``b`` and
  ``a`` once.

The rows come from the program's own count, ``hc_rows`` on every
``tick`` span (live rows x connections: docs/OPS.md "Tick phases"); a
program without it gives ``None`` and the metric is left out.
"""
from __future__ import annotations

from . import phases, readers

BYTES = 2
SINKHORN_BYTES = 4      # the Sinkhorn's float32 lanes


def connections(cfg):
    return 2 * cfg["num_hidden_layers"]


def hc_coefs(cfg):
    n = cfg["hc_mult"]
    return 2 * n + n * n


def hc_flops_row(cfg):
    """One row through one connection."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    return (2 * n * c * hc_coefs(cfg) + 2 * (n * n + 2 * n) * c
            + cfg["hc_sinkhorn_iters"] * 2 * n * n * SINKHORN_BYTES)


def hc_param_bytes(cfg):
    """One connection's ``Phi``, ``b`` and ``a``."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    return (hc_coefs(cfg) * (n * c + 1) + 3) * BYTES


def hc_bytes_row(cfg):
    """One row through all of a tick's connection calls."""
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    return ((connections(cfg) - 1) * (2 * n + 2) * c
            + 2 * (n + 2) * c) * BYTES


def hc_mix_work(cfg, hc_rows, ticks):
    """(flops, bytes) of ``hc_rows`` row-connections over ``ticks``
    ticks of ``2L + 1`` calls."""
    rows = hc_rows / connections(cfg)
    return (hc_rows * hc_flops_row(cfg),
            rows * hc_bytes_row(cfg)
            + ticks * connections(cfg) * hc_param_bytes(cfg))


# -- readers -------------------------------------------------------------------

def _hc_ticks(run, t0, t1):
    """Arguments of the ``tick`` spans that ended in [t0, t1) and carry
    ``hc_rows``; None where the program records none."""
    events = phases.events_of(run)
    if events is None:
        return None
    out = [e["args"] for e in events
           if e["name"] == "tick" and e["tid"] == 0
           and "hc_rows" in (e["args"] or {})
           and t0 <= e["t0"] + e["dur"] < t1]
    return out or None


def work_hc_mix(run, _passes):
    ticks = _hc_ticks(run, *run.interval())
    return hc_mix_work(run.cfg, sum(t["hc_rows"] for t in ticks),
                       len(ticks))


def hc_mix_roofline(run, scopes):
    """``readers.kernel_roofline`` of the connection kernel, left out
    where the program's ``tick`` spans carry no ``hc_rows``."""
    if run.trace is None or _hc_ticks(run, *run.interval()) is None:
        return None
    return readers.kernel_roofline(run, scopes, "lib.xing4_0:work_hc_mix")

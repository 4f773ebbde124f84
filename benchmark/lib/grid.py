"""Reader of the ragged attention's own count of its index space.

``ServingEngine`` writes on every ``tick`` span (docs/OPS.md "Tick
phases") what one layer's ragged attention call visits that tick, in
(query tile, kv head, kv tile) units — grid steps or loop iterations —
as ``attn_units``, and how many of them are not predicated off as
``attn_live``; both are counted on the host while the tick is packed.
The share of the two over a window says how much of the kernel's walk
is work: the old ``slot x window_row x kv_head x block`` grid visited
262,144 units a call to do a few hundred.

A program whose ``tick`` spans lack the two arguments (every commit
before the counter), a tracer switched off, or a ring that wrapped past
the window's start gives ``None``: the metric is left out.
"""
from __future__ import annotations

from . import phases


def live_share(run):
    """Sum of ``attn_live`` over sum of ``attn_units`` of the ``tick``
    spans that ended inside the window, in %."""
    events = phases.events_of(run)
    if events is None:
        return None
    units = live = 0
    for e in events:
        args = e["args"] or {}
        if e["name"] != "tick" or e["tid"] != 0 or "attn_units" not in args \
                or not run.t_open <= e["t0"] + e["dur"] < run.t_close:
            continue
        units += args["attn_units"]
        live += args["attn_live"]
    return 100.0 * live / units if units else None

"""Readers of the program's component map: device time by the part of
the model an instruction came from.

``ServingEngine`` writes, as it compiles each executable, which
component of the model every instruction came from
(``paddle_tpu.monitor.accounting.component_map``: ``{name, opcode,
shape, bytes, component, layer, also}`` a row) and keeps the map with
its tracer, outside the ring (``Tracer.annotate("component_map", {exec:
rows})``). A device event is named by its instruction, so the trace's
events join the map by name, and device time sums by component — the
kernels (``kernel:<scope>``), the products against weights
(``WEIGHTS``) and the glue between them (everything else).

The map is found in ``paddle_tpu.monitor.tracing.live_tracers()`` once
the run is over, as the phases are (``lib/phases.py``). A program that
writes no map, a run without a trace, or a trace of which more than
``MAX_UNKNOWN`` joins nothing gives ``None``: the metric is left out,
never a part of its number.

Every event's time is its SELF time: an instant of the traced interval
with several events open on one chip (a ``while`` and the instructions
of its body) is charged to the one that started last, so the
components' times add up to the chip's busy time.
"""
from __future__ import annotations

import re
import statistics

from . import phases, xplane

UNATTRIBUTED = "unattributed"   # an event that joins no row, or rows of
#                                 different components
UNNAMED = "unnamed"             # a row whose path names no component
MAX_UNKNOWN = 0.05              # of busy time, the two together
KERNEL = "kernel:"
# the components that are a product against a weight. The experts'
# products are ``kernel:gmm`` on a chip; what stays under ``moe.experts``
# there is the grouped matmul's group metadata and the activation
# between the two products, which is glue
WEIGHTS = ("mixer.in", "mixer.out", "ffn", "head")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_COMMENT = re.compile(r"/\*.*?\*/")


def find_map(t0, t1, live_tracers=None):
    """``{executable: rows}`` of the one tracer that holds a component
    map and whose tick phases overlap [t0, t1] (monotonic seconds: the
    engine of this run, as ``phases.find_events`` picks it; a process
    keeps the tracers of up to four engines it shut down), or None:
    none does (the program writes no map), or several."""
    if live_tracers is None:
        from paddle_tpu.monitor.tracing import live_tracers
    found = []
    for tracer in live_tracers():
        notes = getattr(tracer, "annotations", None)
        held = notes().get("component_map") if callable(notes) else None
        if held and any(phases.is_phase(e) and e["t0"] < t1
                        and e["t0"] + e["dur"] > t0
                        for e in tracer.events()):
            found.append(held)
    return found[0] if len(found) == 1 else None


def map_of(run):
    """``find_map`` over the run's window, read once a run."""
    if "component_map" not in run.__dict__:
        run.component_map = find_map(run.t_open, run.t_close)
    return run.component_map


def shape_of(event):
    """The result shape in an event's name (the whole HLO line) as the
    map spells it: no layouts, comments or spaces."""
    _head, sep, rest = event.name.partition(" = ")
    if not sep:
        return ""
    if rest.startswith("("):
        depth = 0
        for end, c in enumerate(rest):
            depth += (c == "(") - (c == ")")
            if not depth:
                break
        shape = rest[:end + 1]
    else:
        shape = rest.split(" ", 1)[0]
    return _LAYOUT.sub("", _COMMENT.sub("", shape)).replace(" ", "")


def index(cmap):
    """``{instruction name: [rows of every executable that has it]}``."""
    out = {}
    for rows in cmap.values():
        for row in rows:
            out.setdefault(row["name"], []).append(row)
    return out


def join(event, by_name):
    """The map's row for a device event, or None: the instruction's
    name, with the result shape as the tie-break where executables
    share the name; rows that still differ in component join nothing."""
    rows = by_name.get(xplane.instruction(event).lstrip("%"))
    if not rows:
        return None
    if len(rows) > 1:
        shape = shape_of(event)
        rows = [r for r in rows if r["shape"] == shape] or rows
        if len({(r["component"], r["layer"]) for r in rows}) > 1:
            return None
    return rows[0]


def self_times(ops, t0, t1):
    """``[ns]`` an event of ``ops`` (one chip's, by start), cut to
    [t0, t1]: each instant with events open charged to the one that
    started last. The list sums to the merged busy time."""
    out = [0.0] * len(ops)
    open_, at = [], t0       # [end, index] by start; the clock
    spans = [(max(e.start_ns, t0), min(e.start_ns + e.dur_ns, t1), i)
             for i, e in enumerate(ops)]

    def run_to(upto):
        nonlocal at
        while open_ and at < upto:
            end, i = open_[-1]
            if end <= at:
                open_.pop()
                continue
            stop = min(end, upto)
            out[i] += stop - at
            at = stop
        while open_ and open_[-1][0] <= at:
            open_.pop()

    for a, b, i in sorted(s for s in spans if s[1] > s[0]):
        run_to(a)
        at = max(at, a) if open_ else a
        open_.append([b, i])
    run_to(float("inf"))
    return out


def _kind(row):
    layer = row["layer"]
    return layer.partition(".")[2] if layer else None


def reduce(run):
    """What every reader here reads, once a run: ``{"busy_ns",
    "by_component": {name: ns}, "by_kind": {kind: {name: ns}},
    "ticks"}`` over the traced interval, averaged over the chips; or
    None where there is no trace or no map."""
    if "component_times" in run.__dict__:
        return run.component_times
    run.component_times = None
    cmap = map_of(run) if run.trace is not None else None
    if not cmap:
        return None
    tr = run.trace
    by_name = index(cmap)
    by_comp, by_kind, seen = {}, {}, {}
    chips = len(tr.ops)
    for ops in tr.ops.values():
        for e, ns in zip(ops, self_times(ops, tr.t0_ns, tr.t1_ns)):
            if not ns:
                continue
            row = join(e, by_name)
            name = row["component"] if row else UNATTRIBUTED
            by_comp[name] = by_comp.get(name, 0.0) + ns / chips
            if row is None:
                continue
            seen[row["name"]] = seen.get(row["name"], 0) + 1
            kind = _kind(row)
            if kind:
                acc = by_kind.setdefault(kind, {})
                acc[name] = acc.get(name, 0.0) + ns / chips
    # how many ticks the interval holds: every instruction of the tick
    # executable runs once a tick
    tick = max(cmap.values(), key=len)
    counts = [seen[r["name"]] for r in tick if r["name"] in seen]
    run.component_times = {
        "busy_ns": sum(by_comp.values()), "by_component": by_comp,
        "by_kind": by_kind,
        "ticks": statistics.median(counts) / chips if counts else 0}
    return run.component_times


def _unknown(got):
    by = got["by_component"]
    return (by.get(UNATTRIBUTED, 0.0) + by.get(UNNAMED, 0.0)) \
        / got["busy_ns"]


# -- the readers --------------------------------------------------------------

def glue_share(run):
    """Device time of the traced interval in components that are
    neither a kernel (``kernel:*``) nor a product against a weight
    (``WEIGHTS``) over the interval's busy time, in %. None where the
    events that join no component (and the rows that name none) take
    more than ``MAX_UNKNOWN`` of it."""
    got = reduce(run)
    if not got or not got["busy_ns"] or _unknown(got) > MAX_UNKNOWN:
        return None
    glue = sum(ns for name, ns in got["by_component"].items()
               if not name.startswith(KERNEL) and name not in WEIGHTS)
    return 100.0 * glue / got["busy_ns"]


def unattributed_share(run):
    """Busy time of the traced interval whose events join no row of
    the map, in %."""
    got = reduce(run)
    if not got or not got["busy_ns"]:
        return None
    return 100.0 * got["by_component"].get(UNATTRIBUTED, 0.0) \
        / got["busy_ns"]


def ms_by_component(run):
    """Milliseconds of device time a tick by component, largest first,
    and by layer kind where the family has more than one:
    ``{"ticks", "busy_ms", "all": {component: ms}, "<kind>": {...}}``."""
    got = reduce(run)
    if not got or not got["ticks"]:
        return None

    def per_tick(acc):
        return {k: v / 1e6 / got["ticks"]
                for k, v in sorted(acc.items(), key=lambda kv: -kv[1])}

    out = {"ticks": got["ticks"],
           "busy_ms": got["busy_ns"] / 1e6 / got["ticks"],
           "all": per_tick(got["by_component"])}
    if len(got["by_kind"]) > 1:
        out.update({kind: per_tick(acc)
                    for kind, acc in sorted(got["by_kind"].items())})
    return out

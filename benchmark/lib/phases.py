"""Readers of the program's own tick-phase spans.

``ServingEngine`` records, through its tracer, what its host code does
inside every ``step()``: ``admit``, ``grow``, ``spill`` (nested in the
two before it), ``pack``, ``launch``, ``fetch``, ``commit`` (docs/OPS.md
"Tick phases"), on ``time.monotonic()``. The readers here cut those
spans at the window's edges, and lay them over the device's idle time
in a traced run: every idle nanosecond is charged to the innermost
phase that covers it.

The spans are found in ``paddle_tpu.monitor.tracing.live_tracers()``
once the run is over (an engine hands its tracer over when it shuts
down). A program without such spans, a tracer switched off, or a ring
that wrapped past the start of the interval gives ``None``: the metric
is left out, never a part of its number.
"""
from __future__ import annotations

from . import xplane

PHASES = ("admit", "grow", "spill", "pack", "launch", "fetch", "commit")
QUEUED = " queued"      # "req<rid> queued", the queue row's span


def find_events(t0, t1, live_tracers=None):
    """The events of the one tracer whose phases overlap [t0, t1]
    (monotonic seconds), or None: no such tracer, more than one, or
    its ring overwrote events since t0."""
    if live_tracers is None:
        from paddle_tpu.monitor.tracing import live_tracers
    found = []
    for tracer in live_tracers():
        events = tracer.events()
        if any(is_phase(e) and e["t0"] < t1 and e["t0"] + e["dur"] > t0
               for e in events):
            found.append((tracer, events))
    if len(found) != 1:
        return None
    tracer, events = found[0]
    # the ring holds events in the order they ended, and drops the oldest
    if tracer.dropped and events[0]["t0"] + events[0]["dur"] > t0:
        return None
    return events


def is_phase(event):
    return event["ph"] == "X" and event["tid"] == 0 \
        and event["name"] in PHASES and "tick" in (event["args"] or {})


def events_of(run):
    """``find_events`` over the run's window, read once a run."""
    if "phase_events" not in run.__dict__:
        run.phase_events = find_events(run.t_open, run.t_close)
    return run.phase_events


def flatten(spans):
    """Disjoint ``[(a, b, name)]`` by start from nested ``(a, b, name)``
    spans: each instant under the innermost span that covers it, so a
    span keeps its self time."""
    out, stack, at = [], [], None     # stack of [end, name]

    def close(upto):
        nonlocal at
        while stack and stack[-1][0] <= upto:
            end, name = stack.pop()
            if end > at:
                out.append((at, end, name))
                at = end

    for a, b, name in sorted(spans, key=lambda s: (s[0], -s[1])):
        close(a)
        if stack:
            if a > at:
                out.append((at, a, stack[-1][1]))
            b = min(b, stack[-1][0])
        at = a
        stack.append([b, name])
    close(float("inf"))
    return out


def seconds_by_phase(segments, t0, t1):
    """{name: seconds of [t0, t1] under that name} of disjoint segments."""
    acc = {}
    for a, b, name in segments:
        lo, hi = max(a, t0), min(b, t1)
        if hi > lo:
            acc[name] = acc.get(name, 0.0) + hi - lo
    return acc


def charge_idle(busy, segments, t0, t1):
    """{name: idle time of [t0, t1]}: the complement of the merged busy
    intervals, each part under the segment that covers it, and under
    ``None`` where no segment does. All on one clock."""
    gaps, at = [], t0
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = max(at, b)
    if t1 > at:
        gaps.append((at, t1))
    acc, i = {}, 0
    for a, b in gaps:
        covered = 0.0
        while i < len(segments) and segments[i][1] <= a:
            i += 1
        j = i
        while j < len(segments) and segments[j][0] < b:
            lo, hi = max(a, segments[j][0]), min(b, segments[j][1])
            if hi > lo:
                name = segments[j][2]
                acc[name] = acc.get(name, 0.0) + hi - lo
                covered += hi - lo
            j += 1
        acc[None] = acc.get(None, 0.0) + (b - a) - covered
    return acc


def _segments(events):
    return flatten([(e["t0"], e["t0"] + e["dur"], e["name"])
                    for e in events if is_phase(e)])


# -- the readers --------------------------------------------------------------

def phase_share(run, phases):
    """Window time under the phases' own spans (a ``spill`` inside an
    ``admit`` counts as spill, not as admit) over the window, in %."""
    events = events_of(run)
    if events is None:
        return None
    took = seconds_by_phase(_segments(events), run.t_open, run.t_close)
    return 100.0 * sum(took.get(p, 0.0) for p in phases) / run.window_s


def carry_share(run):
    """Launches from the device-resident carry (the async tick engaged)
    over all launches that began in the window, in %."""
    events = events_of(run)
    if events is None:
        return None
    how = [e["args"].get("dispatch") for e in events
           if is_phase(e) and e["name"] == "launch"
           and run.t_open <= e["t0"] < run.t_close]
    return 100.0 * how.count("carry") / len(how) if how else None


def idle_share_in(run, phases, unphased=False):
    """Device-idle time of the traced interval charged to the phases
    (and, with ``unphased``, to no phase at all: the caller's loop) over
    the traced interval, in %, averaged over the chips. The shares of
    all phases and the unphased rest sum to the device's idle share."""
    events = events_of(run)
    if events is None or run.trace is None:
        return None
    tr = run.trace
    segments = [(tr.t0_ns + (a - tr.t0) * 1e9, tr.t0_ns + (b - tr.t0) * 1e9,
                 name) for a, b, name in _segments(events)]
    names = set(phases) | ({None} if unphased else set())
    total = 0.0
    for ops in tr.ops.values():
        idle = charge_idle(xplane.union(ops, tr.t0_ns, tr.t1_ns), segments,
                           tr.t0_ns, tr.t1_ns)
        total += sum(v for k, v in idle.items() if k in names)
    return 100.0 * total / len(tr.ops) / (tr.t1_ns - tr.t0_ns)


def queue_wait_mean_ms(run):
    """Mean wait of the requests the engine's queue let go as admitted
    inside the window: the part of a first token's time the queue owns."""
    events = events_of(run)
    if events is None:
        return None
    waits = [e["dur"] for e in events
             if e["name"].endswith(QUEUED)
             and (e["args"] or {}).get("outcome") == "admitted"
             and run.t_open <= e["t0"] + e["dur"] < run.t_close]
    return 1e3 * sum(waits) / len(waits) if waits else None

"""From a profiler trace (``.xplane.pb``) to busy time, kernel time, gaps.

``load()`` flattens the file into plain ``Event`` tuples with nothing but
JAX (``jax.profiler.ProfileData``); everything else is arithmetic on
those tuples, so a hand-built list checks it (``benchmark/tests``).

What a TPU trace looks like (read by hand from a v5e trace, PR 23): each
chip is a plane ``/device:TPU:<n>``; its line ``XLA Ops`` holds one
event per executed HLO instruction, named by the whole HLO line; a
Pallas kernel's instruction carries its ``kernel_scope`` name
(``%ragged_paged_attention.10 = bf16[137,4,16,128]{...} custom-call(...)``,
``%jvp_flash_attention_fwd_.4 = ...``), and so do the operands of the
slices and copies that follow it. The scope name is therefore searched
in the instruction's own name (left of `` = ``) and in the event's
string stats, where other JAX versions keep the framework's scope path. Host threads are lines of the plane ``/host:CPU``;
``TraceAnnotation`` spans appear there under their own name, on the
same clock.
"""
from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench:"
_LAYOUT = re.compile(r"\{[^{}]*\}")      # {2,1,0:T(8,128)(2,1)S(1)}


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    text: str          # the event's string stats, joined: scope paths


def find_trace(trace_dir):
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def load(path, keep_line=None):
    """All events of the file. ``keep_line(plane, line) -> bool`` drops
    lines nobody reads (a device plane has several views of the same
    instructions)."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            if keep_line is not None and not keep_line(plane.name, line.name):
                continue
            for ev in line.events:
                text = " ".join(str(v) for _k, v in ev.stats
                                if isinstance(v, str))
                out.append(Event(plane.name, line.name, ev.name,
                                 float(ev.start_ns), float(ev.duration_ns),
                                 text))
    return out


def is_device_line(plane, line):
    """Whether a line holds a chip's executed instructions."""
    return plane.startswith(DEVICE_PREFIX) and line == OPS_LINE


def bench_lines(plane, line):
    """The lines the benchmark's reduction reads."""
    return is_device_line(plane, line) or not plane.startswith("/device:")


def device_ops(events):
    """{device plane: its instruction events, by start}."""
    out = {}
    for e in events:
        if is_device_line(e.plane, e.line):
            out.setdefault(e.plane, []).append(e)
    for ops in out.values():
        ops.sort(key=lambda e: e.start_ns)
    return out


def host_spans(events):
    """The benchmark's own TraceAnnotation spans, by start."""
    return sorted((e for e in events if e.name.startswith(SPAN_PREFIX)),
                  key=lambda e: e.start_ns)


def union(ops, t0=None, t1=None):
    """Merged [start, end) intervals of ``ops`` clipped to [t0, t1]."""
    merged = []
    for e in ops:
        a, b = e.start_ns, e.start_ns + e.dur_ns
        if t0 is not None:
            a = max(a, t0)
        if t1 is not None:
            b = min(b, t1)
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return merged


def busy_seconds(ops, t0=None, t1=None):
    return sum(b - a for a, b in union(ops, t0, t1)) / 1e9


def instruction(event):
    """The instruction's own name: an event is named by its whole HLO
    line (``%fusion.3 = bf16[...] fusion(%operand, ...)``), and a kernel's
    scope name also stands in the lines of the slices and copies that
    take its result as an operand."""
    return event.name.partition(" = ")[0]


def in_scope(event, scopes):
    head = instruction(event)
    return any(s in head or s in event.text for s in scopes)


def scope_seconds(ops, scopes, t0=None, t1=None):
    """Device seconds and count of the instructions under any of the
    ``kernel_scope`` names, within [t0, t1] by start time."""
    total, count = 0.0, 0
    for e in ops:
        if (t0 is not None and e.start_ns < t0) or \
                (t1 is not None and e.start_ns >= t1):
            continue
        if in_scope(e, scopes):
            total += e.dur_ns
            count += 1
    return total / 1e9, count


def op_label(event, scopes):
    """A kernel's scope name where it has one, else the instruction's
    name and the shape it yields (``fusion.12 bf16[2,2048,1536]``): the
    event's own name is the whole HLO line."""
    for s in scopes:
        if in_scope(event, [s]):
            return s
    head, sep, rest = event.name.partition(" = ")
    if not sep:
        return event.name[:80]
    rest = _LAYOUT.sub("", rest)
    shape = rest[:rest.find(")") + 1] if rest.startswith("(") \
        else rest.split(" ", 1)[0]
    return f"{head.lstrip('%')} {shape}"[:80]


def top_ops(ops, scopes, n=10):
    """[[label, seconds]] of the instructions that took most time."""
    acc = {}
    for e in ops:
        k = op_label(e, scopes)
        acc[k] = acc.get(k, 0.0) + e.dur_ns / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(ops, spans, t0, t1, n=10):
    """[[what the host was doing, seconds]]: the device's idle time in
    [t0, t1], each gap charged to the benchmark span that covers most of
    it (``host`` where none does), summed by span name, longest first."""
    busy = union(ops, t0, t1)
    gaps, at = [], t0
    for a, b in busy:
        if a > at:
            gaps.append((at, a))
        at = b
    if t1 > at:
        gaps.append((at, t1))
    acc = {}
    for a, b in gaps:
        best, cover = "host", 0.0
        for s in spans:
            lo, hi = max(a, s.start_ns), min(b, s.start_ns + s.dur_ns)
            if hi - lo > cover:
                best, cover = s.name[len(SPAN_PREFIX):], hi - lo
        acc[best] = acc.get(best, 0.0) + (b - a) / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def describe(path, limit=6, grep=None):
    """What is in a trace file, for reading one by hand: every plane and
    line with its first ``limit`` events, or with ``grep`` the first
    ``limit`` events of each line whose name or stats hold that text."""
    from jax.profiler import ProfileData
    lines = []
    for plane in ProfileData.from_file(path).planes:
        lines.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            lines.append(f"  line {line.name!r}: {len(evs)} events")
            shown = 0
            for ev in evs:
                stats = {k: (v if not isinstance(v, str) else v[:300])
                         for k, v in ev.stats}
                if grep and grep not in ev.name and grep not in str(stats):
                    continue
                lines.append(f"    {ev.name[:200]!r} start={ev.start_ns} "
                             f"dur={ev.duration_ns} {stats}")
                shown += 1
                if shown >= limit:
                    break
    return "\n".join(lines)


if __name__ == "__main__":
    import sys
    print(describe(find_trace(sys.argv[1]) if os.path.isdir(sys.argv[1])
                   else sys.argv[1], grep=(sys.argv[2:] or [None])[0]))

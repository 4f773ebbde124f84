"""Readers: one small function per kind of metric, chosen by name.

A metric's file (``benchmark/metrics/<name>.json``) names its reader as
``<module under benchmark/>:<function>`` with the reader's arguments; a
new kernel's roofline is a new file wherever a reader here fits, and a
reader that does not fit is a new module beside this one. A reader takes
the finished ``Run`` and returns a number, or ``None`` when it finds
nothing to read (the metric is then left out of the result line — never
a 0 for a share of a peak).
"""
from __future__ import annotations

import numpy as np

from . import harness, peaks, spans, work, xplane


# -- what a run hands to its readers ----------------------------------------

class Run:
    """Everything measured in one run. Times are ``time.monotonic()``
    seconds unless a name ends in ``_ns`` (the trace's clock)."""

    def __init__(self, **kw):
        self.kind = None            # "serve" | "train"
        self.cell = self.cfg = self.mix = None
        self.chips = 1
        self.device_kind = None
        self.setup_s = None
        self.t_open = self.t_close = self.t_drained = None
        self.records = []           # serve: loadgen.Record
        self.counters = {}          # program counters, end minus start
        self.host_spans = []        # (name, t0, t1)
        self.steps = 0              # train
        self.tokens_per_step = 0
        self.memory_peak_bytes = None       # the process's peak
        self.memory_window_bytes = None     # in use while the window ran
        self.memory_build_peak_bytes = None     # peak before warm-up
        self.trace = None           # Trace, in a --trace 1 run
        self.__dict__.update(kw)

    @property
    def window_s(self):
        return self.t_close - self.t_open

    def interval(self):
        """Where work is counted for a share of device time: the traced
        part of the window."""
        return (self.trace.t0, self.trace.t1)


class Trace:
    """A reduced profiler trace. ``t0``/``t1`` bound it on the host's
    clock, ``t0_ns``/``t1_ns`` on the trace's own."""

    def __init__(self, events, sink, traced_from):
        self.ops = xplane.device_ops(events)
        self.spans = xplane.host_spans(events)
        if not self.ops or not any(self.ops.values()):
            raise RuntimeError("no operation ran on a device in the trace")
        if not self.spans:
            raise RuntimeError("none of the benchmark's spans is in the trace")
        self.t0_ns = self.spans[0].start_ns
        self.t1_ns = max(s.start_ns + s.dur_ns for s in self.spans)
        # the trace's first span is the first one the host entered after
        # the profiler had started: that pins the two clocks together
        first = next(s for s in sink if s[1] >= traced_from)
        if first[0] != self.spans[0].name[len(xplane.SPAN_PREFIX):]:
            raise RuntimeError(
                f"trace begins with {self.spans[0].name!r}, the host's "
                f"record with {first[0]!r}: clocks cannot be aligned")
        self.t0 = first[1]
        self.t1 = self.t0 + (self.t1_ns - self.t0_ns) / 1e9

    @property
    def window_s(self):
        return (self.t1_ns - self.t0_ns) / 1e9

    def busy_s(self):
        """Seconds with an operation running, averaged over the chips."""
        per = [xplane.busy_seconds(ops, self.t0_ns, self.t1_ns)
               for ops in self.ops.values()]
        return sum(per) / len(per)

    def scope_seconds(self, scopes):
        """Device seconds under the kernel scopes, averaged over chips,
        and their event count on the first chip."""
        per = [xplane.scope_seconds(ops, scopes, self.t0_ns, self.t1_ns)
               for ops in self.ops.values()]
        return sum(s for s, _ in per) / len(per), per[0][1]

    def breakdown(self, scopes):
        ops = next(iter(self.ops.values()))
        inside = [e for e in ops
                  if self.t0_ns <= e.start_ns < self.t1_ns]
        return {"device_ops": xplane.top_ops(inside, scopes),
                "idle_gaps": xplane.idle_gaps(ops, self.spans, self.t0_ns,
                                              self.t1_ns)}


# -- serving: requests seen through an interval ------------------------------

def processed(records, t0, t1):
    """[(prompt_len, prefilled, first_out, n_out)] of the work whose
    tokens reached the client in [t0, t1): the form ``work`` counts."""
    out = []
    for r in records:
        idx = [i for i, t in enumerate(r.token_t) if t0 <= t < t1]
        if not idx:
            continue
        out.append((len(r.prompt), idx[0] == 0, idx[0], len(idx)))
    return out


def _rows(reqs):
    return sum(rows for rows, _c, _e in work.serve_tokens(reqs))


# -- the readers --------------------------------------------------------------

def setup_seconds(run):
    return run.setup_s


def out_tokens_per_s(run):
    n = sum(1 for r in run.records for t in r.token_t
            if run.t_open <= t < run.t_close)
    return n / run.window_s if n else None


def train_tokens_per_s_chip(run):
    if not run.steps:
        return None
    return run.steps * run.tokens_per_step / run.window_s / run.chips


def latency_percentile(run, what, q):
    """ttft: due -> first token, of every request due in the window (one
    that never answered counts its wait until the drain gave up, and is
    counted under ``failed``);
    itl: every gap between consecutive tokens that closed in the window;
    gen_late: submit minus due. ``q`` is a percentile or "mean"."""
    due = [r for r in run.records if run.t_open <= r.due_t < run.t_close]
    if what == "ttft":
        xs = [(r.token_t[0] if r.token_t else run.t_drained) - r.due_t
              for r in due]
    elif what == "itl":
        xs = [b - a for r in run.records
              for a, b in zip(r.token_t, r.token_t[1:])
              if run.t_open <= b < run.t_close]
    elif what == "gen_late":
        xs = [r.submit_t - r.due_t for r in due]
    else:
        raise ValueError(what)
    if not xs:
        return None
    return 1e3 * float(np.mean(xs) if q == "mean" else np.percentile(xs, q))


def batch_occupancy(run):
    ticks = run.counters.get("decode_steps", 0)
    slots = run.cell["engine"].get("num_slots", 8)
    return 100.0 * run.counters["tokens_total"] / (ticks * slots) \
        if ticks else None


def counter_ratio(run, num, den, scale=1.0):
    d = run.counters.get(den, 0)
    return scale * run.counters.get(num, 0) / d if d else None


def span_ms(run, span):
    """Window time inside the span over how many there were."""
    total, count = spans.seconds_in(run.host_spans, span, run.t_open,
                                    run.t_close)
    return 1e3 * total / count if count else None


def span_share(run, span):
    total, count = spans.seconds_in(run.host_spans, span, run.t_open,
                                    run.t_close)
    return 100.0 * total / run.window_s if count else None


def step_ms(run):
    return 1e3 * run.window_s / run.steps if run.steps else None


def _peak(run):
    return peaks.for_device(run.device_kind)


def tick_mfu(run):
    reqs = processed(run.records, run.t_open, run.t_close)
    if not reqs:
        return None
    flops = work.tick_flops(run.cfg, reqs)
    return peaks.share(flops / _peak(run)["flops_bf16"],
                       run.window_s * run.chips, "tick_mfu")


def step_mfu(run):
    if not run.steps:
        return None
    seq = run.mix["seq_len"]
    flops = work.train_flops_token(run.cfg, seq) * run.steps \
        * run.tokens_per_step
    return peaks.share(flops / _peak(run)["flops_bf16"],
                       run.window_s * run.chips, "step_mfu")


def kernel_roofline(run, scopes, work, calls_per_layer=1):
    """Least time the chip could take for the kernel's work in the traced
    interval (the larger of FLOPs over peak FLOP/s and bytes over peak
    bytes/s) over the device time of the kernel's scopes. How many
    ticks or steps that time holds is read off the trace itself: the
    scopes' events over ``calls_per_layer`` times the layers. ``work``
    names the function ``(run, passes) -> (flops, bytes)``."""
    if run.trace is None:
        return None
    taken, count = run.trace.scope_seconds(scopes)
    if not count:
        return None
    passes = count / (calls_per_layer * run.cfg["num_hidden_layers"])
    flops, nbytes = harness.find_function(work)(run, passes)
    p = _peak(run)
    least = max(flops / p["flops_bf16"], nbytes / p["hbm_bytes_per_s"])
    return peaks.share(least, taken * run.chips, "+".join(scopes))


def work_ragged_attn(run, _passes):
    reqs = processed(run.records, *run.interval())
    return work.ragged_attn_work(
        run.cfg, reqs, run.cell["engine"].get("prefill_chunk", 128))


def work_fused_proj(run, passes):
    reqs = processed(run.records, *run.interval())
    return work.fused_proj_work(run.cfg, _rows(reqs), passes)


def work_flash_attn(run, passes):
    flops, nbytes = work.flash_attn_work(
        run.cfg, run.mix["rows_per_step"], run.mix["seq_len"])
    return flops * passes, nbytes * passes


def device_idle_share(run):
    if run.trace is None:
        return None
    return 100.0 * (1.0 - run.trace.busy_s() / run.trace.window_s)


def hbm_gb(run, of):
    """``of``: "window" (bytes in use while the window ran: what the
    cell holds), "process" (the peak since the process began) or
    "build" (the peak before warm-up: the constructor's transient)."""
    got = getattr(run, {"window": "memory_window_bytes",
                        "process": "memory_peak_bytes",
                        "build": "memory_build_peak_bytes"}[of])
    return got / 1e9 if got else None

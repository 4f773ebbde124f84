"""Load generator: drives an engine-shaped target from one thread.

A copy of the arithmetic of ``paddle_tpu/inference/loadgen.py`` (open and
closed loop over ``submit()`` / ``step()`` and the stream callback), kept
here so that a later PR can change the program and not the yardstick.
Two differences: each request is timed from when it was *due* (an open
loop's schedule, a closed loop's moment of hand-over), and how late the
generator submitted it is recorded beside it.

The loop first runs ``lead_s`` seconds of the same traffic: that is the
warm-up (the first tick compiles or loads the one executable) and it
leaves the engine in its steady state, queue and slots as the mix keeps
them, when the window opens. The window closes ``seconds`` later:
nothing is submitted after it. In an open loop the engine is then
stepped on (at most ``drain_s``) until every request that was due has
its first token, so a late answer is counted late, not missing.
Everything still running is then cancelled.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import List

import numpy as np

from . import spans


@dataclass
class Record:
    """Client-side timeline of one request (``time.monotonic()`` seconds)."""
    rid: int
    prompt: np.ndarray
    want: int                      # output tokens asked for
    due_t: float
    submit_t: float
    token_t: List[float] = field(default_factory=list)
    tokens: List[int] = field(default_factory=list)

    @property
    def finished(self):
        return len(self.tokens) >= self.want


class Client:
    """The requests of one run. ``on_token`` is the engine's
    ``stream_callback``."""

    def __init__(self):
        self.records = {}

    def on_token(self, rid, tok):
        rec = self.records.get(rid)
        if rec is not None:
            rec.token_t.append(time.monotonic())
            rec.tokens.append(int(tok))

    def submit(self, engine, prompt, want, due):
        rid = engine.submit(prompt, max_new_tokens=want)
        self.records[rid] = Record(rid, prompt, want, due, time.monotonic())
        return rid

    def drive(self, engine, stream, mix, lead_s, seconds, host_spans,
              on_open=None, on_tick=None, drain_s=60.0):
        """Run lead and window. Returns ``(records, t_open, t_close)``.

        ``on_open()`` is called once, as the window opens (counters are
        read there); ``on_tick(now)`` once per loop turn inside the
        window (the traced run starts and stops the profiler from it)."""
        open_loop = mix["loop"] == "open"
        clients = int(mix.get("clients", 0))
        t_start = time.monotonic()
        t_open = t_start + float(lead_s)
        t_close = t_open + float(seconds)
        opened = False
        nxt = stream.next()
        due = t_start + nxt[2]
        live = set()
        while True:
            now = time.monotonic()
            if now >= t_close:
                break
            if now >= t_open:
                if not opened:
                    opened = True
                    t_open = now
                    t_close = t_open + float(seconds)
                    if on_open is not None:
                        on_open()
                if on_tick is not None:
                    on_tick(now)
            if open_loop:
                while due <= now and due < t_close:
                    self.submit(engine, nxt[0], nxt[1], due)
                    nxt = stream.next()
                    due += nxt[2]
            else:
                live = {r for r in live if not self.records[r].finished}
                while len(live) < clients:
                    live.add(self.submit(engine, nxt[0], nxt[1], now))
                    nxt = stream.next()
            if engine.num_queued or engine.num_active:
                with spans.span(host_spans, "engine.step"):
                    engine.step()
            else:
                with spans.span(host_spans, "generator.wait"):
                    time.sleep(min(max(due - time.monotonic(), 0.0), 0.005))
        t_close = time.monotonic()
        if open_loop:
            t_give_up = t_close + float(drain_s)
            while time.monotonic() < t_give_up and any(
                    not r.token_t for r in self.records.values()):
                engine.step()
        for rec in self.records.values():
            if not rec.finished:
                engine.cancel(rec.rid)
        return list(self.records.values()), t_open, t_close

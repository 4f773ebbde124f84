#!/usr/bin/env python3
"""One ``engine.profile(n, path)`` capture of a serving cell, kept and
read: do the program's tick phases stand in the profiler's trace, on
the device ops' clock?

    python3 benchmark/tools/phase_capture.py \
        --workload chat-sat.qwen2-7b.d10 --seed 5 --ticks 6

The benchmark deletes its own trace once reduced, so this is the tool
for looking at one by hand. The engine is built and warmed as run.py
does it (the pool full before the capture), the cell's closed loop keeps
it busy, and ``profile()`` brackets ``--ticks`` ticks. Printed, one JSON
line each: how many ``paddle_tpu:<phase>`` events the host plane holds,
and for every ``fetch`` how long after the last device op before it the
host came back. The capture stays under ``--out`` for
``python3 benchmark/lib/xplane.py <out> paddle_tpu:``.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench          # noqa: E402

PREFIX = "paddle_tpu:"


def read(trace_dir):
    """Phase counts, and per ``fetch`` the microseconds from the end of
    the last device op that began before the fetch ended to that end."""
    from benchmark.lib import xplane
    events = xplane.load(xplane.find_trace(trace_dir), xplane.bench_lines)
    ops = [e for plane in xplane.device_ops(events).values() for e in plane]
    host = sorted((e for e in events if e.name.startswith(PREFIX)),
                  key=lambda e: e.start_ns)
    lag = []
    for e in host:
        if e.name != PREFIX + "fetch":
            continue
        end = e.start_ns + e.dur_ns
        before = [o.start_ns + o.dur_ns for o in ops if o.start_ns < end]
        if before:
            lag.append((end - max(before)) / 1e3)
    planes = sorted({e.plane for e in host})
    return collections.Counter(e.name for e in host), lag, planes, len(ops)


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--ticks", type=int, default=6)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "phase_capture"))
    args = ap.parse_args()
    cell, cfg, mix = bench.load_cell(args.workload)
    bench.configure_cache()
    import paddle_tpu  # noqa: F401
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from benchmark.lib import traffic
    device = bench.find_device(cell["chips"])
    model = bench.find("models." + cfg["model_type"]).build(
        cfg, args.seed, training=False)
    engine = ServingEngine(model, ServingConfig(**cell["engine"]))
    engine.warm_migration()
    rng = np.random.default_rng([int(args.seed), 4])
    for p_len, o_len in cell["warmup"]["requests"]:
        engine.submit(rng.integers(1, cfg["vocab_size"], p_len),
                      max_new_tokens=o_len)
    engine.run()
    stream = traffic.RequestStream(mix, cfg["vocab_size"], args.seed)
    clients = int(mix.get("clients", 8))

    def step():
        while engine.num_queued + engine.num_active < clients:
            prompt, want, _gap = stream.next()
            engine.submit(prompt, max_new_tokens=want)
        engine.step()

    for _ in range(12):         # the cell's own traffic, in steady state
        step()
    if engine.profile(args.ticks, args.out) is None:
        raise SystemExit("phase_capture: the tracer is switched off "
                         "(PADDLE_TPU_TRACE=0): nothing to capture")
    while engine.stats()["profile_captures"] < 1:
        step()
    engine.shutdown(check_leaks=False)
    counts, lag, planes, n_ops = read(args.out)
    print(json.dumps({"device": device["kind"], "ticks": args.ticks,
                      "device_ops": n_ops, "planes": planes,
                      "phase_events": dict(sorted(counts.items()))}))
    print(json.dumps({"fetch_end_minus_last_device_op_us": lag}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

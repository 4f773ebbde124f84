"""A serving cell (``"kind": "serve"``): ``ServingEngine`` driven through
``submit()`` / ``step()`` by the benchmark's load generator.

A kind's file is found by the ``kind`` of a cell's file and exposes
``run(ctx)``; it fills ``ctx.run`` with what the readers read and
returns ``(numbers compared, attempted, failed)``. The model comes from
the configuration's family (``ctx.family``), never from a name here.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import check, harness, loadgen, traffic
from benchmark.lib.harness import log


def run(ctx):
    from paddle_tpu.inference import ServingConfig, ServingEngine
    args, cell, cfg, mix, run = ctx.args, ctx.cell, ctx.cfg, ctx.mix, ctx.run
    model = ctx.family.build(cfg, args.seed, training=False)
    log(phase="model built", t=harness.since_start())
    # what the constructor's float32 build took (ROADMAP D5b): a set-up
    # figure; the window's own memory is read when it opens and closes
    run.memory_build_peak_bytes = harness.memory("peak_bytes_in_use")
    client = loadgen.Client()
    engine = ServingEngine(model, ServingConfig(**cell["engine"]),
                           stream_callback=client.on_token)
    ctx.hooks.get("engine", lambda e: None)(engine)
    log(phase="engine built", t=harness.since_start())
    # the export/import pair that a prefix-cache eviction spills through:
    # built here, or the first eviction compiles it inside the window
    engine.warm_migration()
    # warm-up: a prompt of several chunks beside a decoding slot compiles
    # (or loads) the one tick executable, before the lead's clock starts
    rng = np.random.default_rng([int(args.seed), 4])
    for p_len, o_len in cell["warmup"]["requests"]:
        engine.submit(rng.integers(1, cfg["vocab_size"], p_len),
                      max_new_tokens=o_len)
    engine.run()
    log(phase="warmed", t=harness.since_start())
    stats0, n0, held = {}, [0], []

    def on_open():
        stats0.update(engine.stats())
        n0[0] = ctx.compiles.n
        held.append(harness.memory("bytes_in_use"))
        run.setup_s = harness.since_start()

    tracer = harness.Tracer(cell["trace"], args.trace_dir) \
        if args.trace else None
    stream = traffic.RequestStream(mix, cfg["vocab_size"], args.seed)
    records, run.t_open, run.t_close = client.drive(
        engine, stream, mix, cell["warmup"]["lead_s"], args.seconds,
        run.host_spans, on_open=on_open,
        on_tick=tracer.on_tick if tracer else None)
    run.t_drained = time.monotonic()
    if tracer:
        tracer.finish()
    stats1 = engine.stats()
    held.append(harness.memory("bytes_in_use"))
    run.records = records
    run.counters = {k: stats1[k] - stats0[k] for k in cell["counters"]}
    run.memory_window_bytes = max(held)
    run.memory_peak_bytes = harness.memory("peak_bytes_in_use")
    due = [r for r in records if run.t_open <= r.due_t < run.t_close]
    compiled = ctx.compiles.n - n0[0]
    log(window="closed", compiles_in_window=compiled,
        counters=run.counters, requests_due=len(due),
        finished=sum(r.finished for r in records),
        memory_build_peak_bytes=run.memory_build_peak_bytes,
        memory_window_bytes=run.memory_window_bytes)
    if compiled or run.counters["executables_compiled"]:
        raise RuntimeError("something was compiled inside the window: "
                           "warm-up does not cover the cell's shapes")
    if tracer:
        run.trace = tracer.reduce(run.host_spans)

    engine.shutdown()
    del engine, model, stream
    gc.collect()

    # the comparison: a seeded sample of finished requests, the longest
    # in it, against the plain reference
    lim = cell["check"]
    picked = check.sample_requests(records, args.seed, lim["requests"],
                                   lim["min_tokens"])
    numbers = {"wrong_answers": check.token_faults(records,
                                                   cfg["vocab_size"])}
    if picked:
        samples = [(r.prompt, r.tokens) for r in picked]
        shape = (lim["requests"], mix["max_total"], lim["rows_cap"])
        logits, served = ctx.family.served_logits(cfg, args.seed, samples,
                                                  *shape)
        numbers["logit_gap_max"] = float(
            check.gaps_below_best(logits, served).max())
        numbers["unchecked"] = 0.0
        if ctx.hooks.get("control"):
            # the reference in the program's place one precision down:
            # the gap of the token that it puts first (tools/control.py)
            low, _ = ctx.family.served_logits(cfg, args.seed, samples,
                                              *shape, lowp=True)
            ctx.controls["control_lowp"] = {
                "wrong_answers": 0.0, "unchecked": 0.0,
                "logit_gap_max": float(check.gaps_below_best(
                    logits, np.asarray(low.argmax(-1))).max())}
            log(checked_tokens=int(len(served)), checked_requests=len(picked))
    else:
        numbers["unchecked"] = 1.0      # nothing finished: nothing proven
    # a closed loop's last requests are simply in flight at the close;
    # in an open loop one that was due and never answered has failed
    failed = sum(1 for r in due if not r.token_t) \
        if mix["loop"] == "open" else 0
    return numbers, len(due), failed

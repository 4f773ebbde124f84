#!/usr/bin/env python3
"""The rate sweep of an open-loop serving cell: made once, on the chip.

    python3 benchmark/tools/sweep.py --workload chat-rate.qwen2-7b.d10 \
        --seed 1 --seconds 51 --rates 0.3,0.4,0.5,0.6,0.7,0.8 \
        --spread-at 0.8,0.6 --spread-seeds 4

One process, one engine (the cell's own, built and warmed as run.py
does it: the pool is full before the first window). Each rate replays
the mix's own schedule of sizes and gaps, scaled to that rate, through
the cell's lead and one window of ``--seconds``; the engine is drained
between windows. A rate is *sustained* when the requests waiting
for a slot did not grow from the window's first half to its second and
nine tenths of the arrivals had their first token by the close. Then,
at ``--spread-at`` shares of the highest sustained rate, several windows
with other seeds (other token ids, the same schedule) show how far the
means and tails swing from run to run.
Every window is one JSON line; the benchmark itself never searches for a
rate — the cell's traffic file carries the number chosen from this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench          # noqa: E402


def window(engine, holder, cfg, cell, mix, rate, seed, seconds):
    from benchmark.lib import loadgen, readers, traffic
    mix = dict(mix, rate_per_s=rate)
    client = holder["client"] = loadgen.Client()
    depth = []
    stats0 = {}
    records, t_open, t_close = client.drive(
        engine, traffic.RequestStream(mix, cfg["vocab_size"], seed), mix,
        cell["warmup"]["lead_s"], seconds, [],
        on_open=lambda: stats0.update(engine.stats()),
        on_tick=lambda now: depth.append((now, engine.num_queued,
                                          engine.num_active)))
    t_end = time.monotonic()
    stats1 = engine.stats()
    engine.run()                                # drain before the next
    run = readers.Run(records=records, t_open=t_open, t_close=t_close,
                      t_drained=t_end)
    lat = lambda what, q: readers.latency_percentile(run, what, q)  # noqa
    due = [r for r in records if t_open <= r.due_t < t_close]
    mid = (t_open + t_close) / 2
    q1 = [q for t, q, _a in depth if t < mid]
    q2 = [q for t, q, _a in depth if t >= mid]
    answered = sum(1 for r in due if r.token_t and r.token_t[0] < t_close)
    out = {
        "rate": rate, "seed": seed, "pool": mix["pool"], "due": len(due),
        "answered_by_close": answered,
        "finished": sum(r.finished for r in due),
        "queued_mean_1st_half": float(np.mean(q1)) if q1 else None,
        "queued_mean_2nd_half": float(np.mean(q2)) if q2 else None,
        "queued_at_close": depth[-1][1] if depth else None,
        "active_mean": float(np.mean([a for _t, _q, a in depth])),
        "ttft_ms": {k: lat("ttft", q) for k, q in (
            ("mean", "mean"), ("p50", 50), ("p90", 90), ("max", 100))},
        "itl_ms": {k: lat("itl", q) for k, q in (
            ("mean", "mean"), ("p50", 50), ("p95", 95), ("p99", 99))},
        "gen_late_p95_ms": lat("gen_late", 95),
        "out_tok_per_s": readers.out_tokens_per_s(run),
        "ticks": stats1["decode_steps"] - stats0["decode_steps"],
        "compiled_in_window": stats1["executables_compiled"]
        - stats0["executables_compiled"],
    }
    out["sustained"] = bool(
        answered >= 0.9 * len(due)
        and out["queued_mean_2nd_half"] <= out["queued_mean_1st_half"] + 1.0)
    return out


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--spread-at", default="")
    ap.add_argument("--spread-seeds", type=int, default=4)
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out",
                                                  "sweep.jsonl"))
    args = ap.parse_args()
    cell, cfg, mix = bench.load_cell(args.workload)
    bench.configure_cache()
    import paddle_tpu  # noqa: F401
    from paddle_tpu.inference import ServingConfig, ServingEngine
    device = bench.find_device(cell["chips"])
    model = bench.find("models." + cfg["model_type"]).build(
        cfg, args.seed, training=False)
    holder = {}
    engine = ServingEngine(
        model, ServingConfig(**cell["engine"]),
        stream_callback=lambda rid, tok: holder["client"].on_token(rid, tok))
    engine.warm_migration()
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    sink = open(args.out, "a")

    def emit(row):
        line = json.dumps(dict(row, device=device["kind"]))
        print(line, flush=True)
        sink.write(line + "\n")
        sink.flush()

    from benchmark.lib import loadgen
    holder["client"] = loadgen.Client()
    rng = np.random.default_rng([int(args.seed), 4])
    for p_len, o_len in cell["warmup"]["requests"]:    # as run.py: no two
        engine.submit(rng.integers(1, cfg["vocab_size"], p_len),   # alike
                      max_new_tokens=o_len)
    engine.run()
    knee = None
    for rate in [float(r) for r in args.rates.split(",")]:
        row = window(engine, holder, cfg, cell, mix, rate, args.seed,
                     args.seconds)
        emit(row)
        if row["sustained"]:
            knee = max(knee or 0.0, rate)
    emit({"highest_sustained_rate": knee})
    for share in [float(s) for s in args.spread_at.split(",") if s]:
        if knee is None:
            break
        rate = round(share * knee, 3)
        for k in range(args.spread_seeds):
            emit(dict(window(engine, holder, cfg, cell, mix, rate,
                             args.seed + 1000 * (k + 1), args.seconds),
                      share_of_knee=share))
    engine.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())

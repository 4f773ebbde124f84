#!/usr/bin/env python3
"""The benchmark's one command.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, traffic mix or metric
is a data file found by name::

    benchmark/workloads/<cell>.json     config, traffic, chips, kind, settings, limits
    benchmark/configs/<config>.json     the model configuration as it is run
    benchmark/traffic/<traffic>.json    the mix: lengths, loop, rate (lib/traffic.py)
    benchmark/metrics/<metric>.json     tier (end_to_end | per_layer | observed), reader + arguments
    benchmark/kinds/<kind>.py           how a cell of that kind is driven
    benchmark/models/<model_type>.py    the family: builder, leaves, reference

and a metric's reader (or a roofline's work function) is named in its
file as ``<module under benchmark/>:<function>``. A later PR adds a
cell, a configuration, a mix, a metric, a kind of job, a model family,
a reader or a work function as new files, and edits none that is there.

One process, one TPU host. Set-up (weights from the seed on the device,
the cell's own shapes warmed) is timed as ``setup_s``; then the window of
``--seconds``; then, with the program's state freed, the plain reference
decides ``correct``. The last line of standard output is the result.
Without a TPU (or on a device with no published peaks) it exits non-zero
and prints no result: a number from a CPU never stands under a device
metric's name.
"""
from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark.lib import harness       # noqa: E402  (starts the clock)
from benchmark.lib.harness import log   # noqa: E402

import argparse             # noqa: E402
import json                 # noqa: E402
import types                # noqa: E402

find, read_json = harness.find, harness.read_json


def load_cell(name):
    cell = read_json("workloads", name + ".json")
    cfg = read_json("configs", cell["config"] + ".json")
    mix = read_json("traffic", cell["traffic"] + ".json")
    return cell, cfg, mix


def load_metrics(cell_name, listed, tier):
    """Every metric file of this tier that the cell's file lists, or
    that lists the cell itself: either side can be the new file."""
    out = []
    folder = os.path.join(harness.TREE, "metrics")
    for fn in sorted(os.listdir(folder)):
        if not fn.endswith(".json"):
            continue
        m = read_json("metrics", fn)
        if m["tier"] == tier and (cell_name in m["workloads"]
                                  or m["name"] in listed):
            out.append(m)
    return out


def read_metric(metric, run):
    """A metric's number by its file's reader, or None where the reader
    found nothing to read."""
    reader = harness.find_function(metric["reader"])
    return reader(run, **metric.get("args", {}))


def configure_cache():
    """JAX's persistent compile cache at a fixed path inside the
    checkout (the path is part of the key), or where the environment
    says. Everything is cached, however quick to compile, so that only
    a checkout's first run of a cell compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def find_device(chips):
    """The device as JAX reports it; no TPU, too few chips or a kind with
    no published peaks end the run with no result."""
    import jax
    from benchmark.lib import peaks
    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        raise SystemExit(f"benchmark: platform is {dev.platform!r}, not "
                         "'tpu'; this benchmark measures only on the chip")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: cell needs {chips} chips, JAX sees "
                         f"{len(devs)}")
    peaks.for_device(dev.device_kind)
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(devs)}


def main(argv=None, hooks=None, device=None):
    """``hooks`` and ``device`` are for the tests and tools under
    benchmark/: they break the timed path underneath, ask for the
    control's readings, or stand in for the look for a chip. The command
    line sets neither."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    args.trace_dir = os.path.join(ROOT, "benchmark_out", "trace",
                                  args.workload)
    cell, cfg, mix = load_cell(args.workload)

    cache_dir = configure_cache()
    import paddle_tpu  # noqa: F401  (before JAX's backend is touched)
    from benchmark.lib import check, readers
    if device is None:
        device = find_device(cell["chips"])
    log(workload=args.workload, seed=args.seed, seconds=args.seconds,
        trace=args.trace, device=device, compile_cache_dir=cache_dir)

    run = readers.Run(kind=cell["kind"], cell=cell, cfg=cfg, mix=mix,
                      chips=cell["chips"], device_kind=device["kind"])
    ctx = types.SimpleNamespace(
        args=args, cell=cell, cfg=cfg, mix=mix, run=run, hooks=hooks or {},
        compiles=harness.CompileCount(), controls={},
        family=find("models." + cfg["model_type"]))
    numbers, attempted, failed = find("kinds." + cell["kind"]).run(ctx)

    tier = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in load_metrics(args.workload, cell["metrics"], tier):
        value = read_metric(m, run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    # what is measured in every run but can carry no bound and moves no
    # bounded metric (a metric file's tier "observed"): a key of its own,
    # which the driver ignores, for the writer of the next issue
    observed = {m["name"]: {"value": read_metric(m, run), "unit": m["unit"]}
                for m in load_metrics(args.workload, cell["metrics"],
                                      "observed")}
    device = dict(device, memory_peak_bytes=run.memory_peak_bytes,
                  memory_window_bytes=run.memory_window_bytes)
    result = {"correct": None, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s()
        device["window_s"] = run.trace.window_s
        result["breakdown"] = run.trace.breakdown(cell["trace"]["scopes"])

    limits = cell["check"]["limits"]
    # the control and the planted faults, where a tool asked for them:
    # each through the same verdict as the run's own numbers
    for name, other in ctx.controls.items():
        rows, ok = check.verdict(other, limits)
        log(reading=name, correct=ok, over=[r[0] for r in rows if not r[3]],
            numbers=other)
    rows, ok = check.verdict(numbers, limits)
    result["correct"] = ok
    # where this run's own time went (the driver ignores the key): every
    # run of every later check pays the comparison after the window too
    result["observed"] = observed
    result["seconds"] = {"setup": run.setup_s, "window": run.window_s,
                         "whole_run": harness.since_start()}
    result["compared"] = {name: {"value": v, "limit": lim}
                          for name, v, lim, _ok in rows}
    sys.stdout.flush()
    for name, v, lim, good in rows:
        print(f"compared {name} = {v:.6g} (limit {lim:.6g})"
              f"{'' if good else '  <-- over'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

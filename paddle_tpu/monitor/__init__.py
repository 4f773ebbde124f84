"""``paddle_tpu.monitor`` — runtime telemetry for the whole framework.

The three layers (see ISSUE 2 / docs/OPS.md "Telemetry"):

1. **Metrics registry** (``registry.py``): labeled Counter / Gauge /
   Histogram / Info, thread-safe, env-gated JSONL export
   (``PADDLE_TPU_METRICS_DIR``) plus an atexit text-table dump
   (``PADDLE_TPU_METRICS_DUMP=stdout|stderr``). Generalizes the old
   ``MOE_STATS`` dict — which is now a thin alias over this registry.
2. **Compiled-step accounting** (``accounting.py``): every
   ``TrainStep`` compile records ``cost_analysis()`` FLOPs/bytes,
   ``memory_analysis()`` peak HBM, and a jaxpr-walk collective census
   (op counts + payload bytes per mesh axis) — the analytic side of
   the MFU the bench measures.
3. **Hot-path instrumentation**: jit/SOT cache hit/miss/recompile
   counters with guard-failure and graph-break reason strings,
   ``RecordEvent`` span histograms (MoE dispatch stages, 1F1B, PS
   push/pull), and HBM watermark gauges at step boundaries.

Usage::

    from paddle_tpu import monitor
    monitor.counter("my_events", "what happened", labels=("kind",)) \
        .labels(kind="x").inc()
    print(monitor.report())          # text table
    monitor.export_jsonl("/tmp/m")   # or via PADDLE_TPU_METRICS_DIR
"""
from __future__ import annotations

import atexit
import os
import sys

from .registry import (Counter, Gauge, Histogram, Info, Registry,
                       get_registry, metrics_dir, metrics_enabled,
                       prometheus_path)
from .accounting import (COMPONENTS, DEVICE_PEAKS, analytic_mfu,
                         collective_census, component_map, device_peaks,
                         executable_cost, kernel_census,
                         record_compiled_step, sample_device_memory,
                         step_report, step_reports)
from .digest import LatencyDigest, P2Quantile
from .tracing import (ProfilerWindow, Tracer, next_flow_id,
                      tracing_enabled)
from .health import (ALERT_SEVERITY, BurnRateMonitor, CollapseDetector,
                     EwmaSpikeDetector, HealthMonitor, IncidentCapture,
                     RatioDetector, StormDetector, TrendDetector)

__all__ = [
    "Counter", "Gauge", "Histogram", "Info", "Registry",
    "get_registry", "metrics_dir", "metrics_enabled",
    "counter", "gauge", "histogram", "info",
    "export_jsonl", "report", "reset",
    "prometheus_dump", "prometheus_path",
    "LatencyDigest", "P2Quantile", "Tracer", "tracing_enabled",
    "ProfilerWindow", "next_flow_id",
    "record_compiled_step", "collective_census", "kernel_census",
    "component_map", "COMPONENTS",
    "step_report", "step_reports", "sample_device_memory",
    "analytic_mfu", "DEVICE_PEAKS", "device_peaks", "executable_cost",
    "ALERT_SEVERITY", "BurnRateMonitor", "CollapseDetector",
    "EwmaSpikeDetector", "HealthMonitor", "IncidentCapture",
    "RatioDetector", "StormDetector", "TrendDetector",
]


def counter(name, help="", labels=()) -> Counter:
    return get_registry().counter(name, help, labels)


def gauge(name, help="", labels=()) -> Gauge:
    return get_registry().gauge(name, help, labels)


def histogram(name, help="", labels=(), buckets=None) -> Histogram:
    if buckets is not None:
        return get_registry().histogram(name, help, labels,
                                        buckets=buckets)
    return get_registry().histogram(name, help, labels)


def info(name, help="", labels=()) -> Info:
    return get_registry().info(name, help, labels)


def export_jsonl(path=None):
    """Dump every metric as JSONL; ``path`` defaults to
    ``$PADDLE_TPU_METRICS_DIR``. Returns the file written or None."""
    return get_registry().dump_jsonl(path)


def prometheus_dump(path=None):
    """Render the registry in the Prometheus text exposition format to
    ``path`` (default ``$PADDLE_TPU_METRICS_PROM``; a directory gets
    ``metrics-<pid>.prom``). Returns the file written or None. The
    atexit hook writes this automatically when the env var is set —
    the JSONL export's scrape-side twin."""
    return get_registry().dump_prometheus(path)


def report() -> str:
    """Human text table of every metric sample."""
    return get_registry().table()


def reset():
    """Clear all samples (test/bench hygiene; metric handles survive)."""
    get_registry().reset()


def _atexit_dump():
    try:
        if metrics_dir():
            get_registry().dump_jsonl()
        if prometheus_path():
            get_registry().dump_prometheus()
        dump = os.environ.get("PADDLE_TPU_METRICS_DUMP")
        if dump:
            stream = sys.stdout if dump == "stdout" else sys.stderr
            print(get_registry().table(), file=stream)
    except Exception:
        pass          # never let telemetry break interpreter shutdown


atexit.register(_atexit_dump)

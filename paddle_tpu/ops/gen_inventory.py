"""Op-inventory generator (reference: the yaml op registry
``paddle/phi/ops/yaml/ops.yaml`` fans out via codegen to four consumers
— SURVEY §1 'key architectural fact').

TPU-first: the single source of truth here is the live ``OPS`` registry
(every public op behind the one ``apply_jax`` dispatch point). Its
consumers are (1) the ``paddle.*`` namespace, (2) Tensor methods,
(3) the static-graph recorder, and — produced by this module — (4) the
generated inventory document ``docs/OPS.md``, which is the greppable
parity ledger a yaml registry gives the reference.

Run: ``python -m paddle_tpu.ops.gen_inventory``
"""
from __future__ import annotations

import inspect
import os


# Hand-maintained kernel notes appended to the generated ledger (kept
# here so regeneration never drops them).
_KERNEL_NOTES = [
    "",
    "## MoE grouped-matmul kernels (`distributed/moe.py`)",
    "",
    "The MoE dispatch paths (`moe_dispatch_combine_dropless`,",
    "`moe_dispatch_combine_grouped`) run the expert MLP as two grouped",
    "matmuls over expert-sorted rows — the megablox Pallas kernel on",
    "real TPU, `lax.ragged_dot` elsewhere. Under an expert-sharded mesh",
    "the dropless pipeline runs INSIDE `shard_map` over the `ep` axis",
    "(`_dropless_ep`): sort-based grouping, explicit `all_to_all`",
    "placement before/after the expert matmuls, grouped kernels on",
    "static per-shard shapes, and a hand-written custom VJP that runs",
    "the backward grouped kernels too.",
    "",
    "Tuning knobs:",
    "",
    "- `moe._GMM_TILING` — forward (m, k, n) tile, default",
    "  `(512, 1024, 512)` (v5e-tuned at [32768, 1024→1408]; last two",
    "  block dims must stay 8/128-aligned).",
    "- `moe._GMM_TILING_BWD` — backward tile for the transpose-rhs gmm",
    "  and tgmm, default `(512, 512, 512)` (tgmm measured 2.32 ms vs",
    "  3.30 with the forward tiling at the bench shapes).",
    "- `ep_buffer_factor` (model config / dispatch kwarg) — per-",
    "  (src, dst) EP exchange-slot bound in multiples of the balanced",
    "  per-shard load; `>= ep degree` is exactly dropless, smaller",
    "  values bound memory and report overflow in `drop_rate`.",
    "- `MOE_STATS` / `moe_stats()` — trace-time path counters",
    "  (grouped_mm_calls, grouped_mm_kernel, ep_shard_map_calls,",
    "  padded_einsum_calls) for asserting kernel selection. Served by",
    "  the `paddle_tpu.monitor` registry (`moe_path_calls{path=...}`)",
    "  — the dict is a thin alias.",
    "",
    "## Telemetry (`paddle_tpu.monitor`)",
    "",
    "Framework-wide runtime telemetry: a labeled metrics registry",
    "(Counter/Gauge/Histogram/Info), compiled-step cost/memory",
    "accounting, and hot-path instrumentation.",
    "",
    "Environment variables:",
    "",
    "- `PADDLE_TPU_METRICS_DIR=<dir>` — export every metric as JSONL to",
    "  `<dir>/metrics-<pid>.jsonl` at interpreter exit (and on demand",
    "  via `monitor.export_jsonl()`). One JSON record per",
    "  (metric, labelset): `{name, kind, labels, value, ts}`.",
    "- `PADDLE_TPU_METRICS_DUMP=stdout|stderr` — print the text table",
    "  (`monitor.report()`) at exit.",
    "- `PADDLE_TPU_METRICS=1` — enable the heavier opt-in accounting",
    "  (per-specialization `to_static` cost records) without exporting.",
    "- `GLOG_v=<n>` — verbose runtime logging (framework/log.py), the",
    "  reference's glog knob; orthogonal to metrics but usually read",
    "  together when debugging a step.",
    "",
    "Reading the step report: every `TrainStep` AOT-compiles on its",
    "first call and records `cost_analysis()` FLOPs/bytes,",
    "`memory_analysis()` peak HBM, and a jaxpr-walk collective census",
    "(op counts + per-shard payload bytes per mesh axis) under",
    "`monitor.step_report(step.telemetry_name)`. Key metrics:",
    "",
    "- `step_flops{step=}` / `step_bytes_accessed{step=}` /",
    "  `step_peak_hbm_bytes{step=}` — the XLA cost model's view of one",
    "  compiled step.",
    "- `step_collectives{step=,op=,axis=}` (+ `step_collective_bytes`)",
    "  — all_reduce / all_to_all / all_gather / ppermute /",
    "  reduce_scatter counts per mesh axis. GSPMD-inferred collectives",
    "  only exist post-partitioning; their jaxpr proxy is the",
    "  `sharding_constraint` row.",
    "- `jit_cache_events{fn=,event=hit|miss|recompile}`,",
    "  `jit_guard_invalidations{fn=,reason=}`, `sot_events{fn=,event=}`,",
    "  `sot_graph_breaks{reason=}` — compile-cache behavior with reason",
    "  strings (a recompile-per-step loop shows up here first).",
    "- `device_peak_bytes_in_use{device=}` — HBM watermark sampled at",
    "  step boundaries.",
    "- `record_event_ms{name=}` — RecordEvent span histograms (MoE",
    "  dispatch/expert_mm/combine, pipeline 1F1B, PS push/pull).",
    "",
    "Analytic vs bench MFU: `monitor.analytic_mfu(name, step_time_s)`",
    "= recorded FLOPs/step ÷ measured step time ÷ chip peak. The bench",
    "MFU uses the 6N+attention FLOPs/token closed form; the analytic",
    "number uses XLA's per-op cost model on the exact compiled program,",
    "so it additionally counts remat recompute, optimizer/elementwise",
    "FLOPs, and non-matmul work — expect it to sit ABOVE the bench MFU",
    "at equal throughput, and read their RATIO as the compiled",
    "program's overhead factor rather than comparing either to 1.0.",
]


def generate(out_path=None) -> str:
    from . import OPS
    from ..framework.core import Tensor

    rows = []
    for name in sorted(OPS):
        fn = OPS[name]
        mod = getattr(fn, "__module__", "") or ""
        category = mod.rsplit(".", 1)[-1]
        try:
            sig = str(inspect.signature(fn))
        except (TypeError, ValueError):
            sig = "(...)"
        tensor_method = "yes" if name in Tensor.__dict__ or \
            hasattr(Tensor, name) else ""
        inplace = "yes" if hasattr(Tensor, name + "_") else ""
        rows.append((name, category, sig, tensor_method, inplace))

    lines = [
        "# Op inventory (generated — do not edit)",
        "",
        "Regenerate with `python -m paddle_tpu.ops.gen_inventory`.",
        "Single source of truth: the `OPS` registry behind `apply_jax`",
        "(`framework/core.py`); consumers: `paddle.*` namespace, Tensor",
        "methods, static-graph recording, and this ledger.",
        "",
        f"**{len(rows)} registered ops**",
        "",
        "| op | module | signature | Tensor method | in-place |",
        "|---|---|---|---|---|",
    ]
    for name, cat, sig, tm, ip in rows:
        sig = sig.replace("|", "\\|")
        lines.append(f"| `{name}` | {cat} | `{sig}` | {tm} | {ip} |")

    # namespace ops: public callables living under paddle.<ns>.* rather
    # than the flat tensor-op registry (the reference's ops.yaml count
    # spans these too — fft, sparse, geometric, nn.functional, ...)
    import importlib
    ns_rows = []
    for ns in ("fft", "signal", "sparse", "geometric", "linalg",
               "nn.functional", "nn.quant", "incubate.nn.functional",
               "vision.ops"):
        try:
            mod = importlib.import_module("paddle_tpu." + ns)
        except Exception:
            continue
        names = [n for n in getattr(mod, "__all__", [])
                 if callable(getattr(mod, n, None))]
        for n in sorted(names):
            ns_rows.append((ns, n))
    lines += [
        "",
        f"**{len(ns_rows)} namespace ops** "
        f"(total {len(rows) + len(ns_rows)})",
        "",
        "| namespace | op |",
        "|---|---|",
    ]
    for ns, n in ns_rows:
        lines.append(f"| {ns} | `{n}` |")
    lines += _KERNEL_NOTES
    text = "\n".join(lines) + "\n"

    if out_path is None:
        root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        out_path = os.path.join(root, "docs", "OPS.md")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        f.write(text)
    return out_path


if __name__ == "__main__":
    print(generate())

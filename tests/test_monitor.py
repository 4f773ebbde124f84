"""Telemetry-layer tests: the metrics registry (labels, JSONL
round-trip, atexit dump), compiled-step cost/memory accounting on a
jitted toy TrainStep, and the collective census on a shard_map program
over the test mesh (ISSUE 2 tentpole)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.monitor.registry import Registry


# ---------------------------------------------------------------- registry

def test_counter_gauge_histogram_labels():
    reg = Registry()
    c = reg.counter("requests", "total requests", labels=("path",))
    c.labels(path="a").inc()
    c.labels(path="a").inc(4)
    c.labels(path="b").inc()
    assert c.labels(path="a").value() == 5
    assert c.labels(path="b").value() == 1

    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.dec()
    assert g.value() == 6

    h = reg.histogram("lat_ms", "latency", labels=("op",))
    h.labels(op="x").observe(0.2)
    h.labels(op="x").observe(800.0)
    st = h.labels(op="x").value()
    assert st["count"] == 2
    assert abs(st["sum"] - 800.2) < 1e-6

    i = reg.info("kernel", "last kernel")
    i.set("megablox")
    assert i.get() == "megablox"

    # unknown label names are rejected
    with pytest.raises(ValueError):
        c.labels(nope="x")
    # re-registering with different labels is rejected
    with pytest.raises(ValueError):
        reg.counter("requests", labels=("other",))


def test_registry_reset_keeps_handles():
    reg = Registry()
    c = reg.counter("n", "")
    c.inc(3)
    reg.reset()
    assert c.value() == 0         # same handle, cleared sample
    c.inc()
    assert c.value() == 1


def test_jsonl_round_trip(tmp_path):
    reg = Registry()
    reg.counter("hits", "", labels=("fn",)).labels(fn="f").inc(2)
    reg.gauge("hbm", "").set(1234)
    reg.histogram("ms", "").observe(3.0)
    reg.info("report", "").set({"flops": 10, "census": []})
    path = reg.dump_jsonl(str(tmp_path))
    assert path and os.path.exists(path)
    recs = [json.loads(line) for line in open(path)]
    by_name = {r["name"]: r for r in recs}
    assert by_name["hits"]["value"] == 2
    assert by_name["hits"]["labels"] == {"fn": "f"}
    assert by_name["hbm"]["value"] == 1234
    assert by_name["ms"]["value"]["count"] == 1
    assert by_name["report"]["value"]["flops"] == 10
    assert all("ts" in r and "kind" in r for r in recs)


def test_atexit_dump_writes_jsonl(tmp_path):
    """A fresh interpreter that only touches the registry must leave a
    parseable JSONL behind via the atexit hook."""
    env = dict(os.environ,
               PADDLE_TPU_METRICS_DIR=str(tmp_path),
               JAX_PLATFORMS="cpu")
    code = ("from paddle_tpu import monitor; "
            "monitor.counter('exit_probe', 'x', labels=('k',))"
            ".labels(k='v').inc(3)")
    subprocess.run([sys.executable, "-c", code], check=True, env=env,
                   cwd=os.path.dirname(os.path.dirname(
                       os.path.abspath(__file__))), timeout=240)
    files = [f for f in os.listdir(tmp_path) if f.endswith(".jsonl")]
    assert files, "atexit hook wrote no metrics file"
    recs = [json.loads(line)
            for line in open(os.path.join(tmp_path, files[0]))]
    probe = [r for r in recs if r["name"] == "exit_probe"]
    assert probe and probe[0]["value"] == 3
    assert probe[0]["labels"] == {"k": "v"}


def test_report_table_mentions_metrics():
    reg = Registry()
    reg.counter("tbl_metric", "", labels=("a",)).labels(a="1").inc()
    text = reg.table()
    assert "tbl_metric" in text and "a=1" in text


# ------------------------------------------------- compiled-step accounting

def test_trainstep_cost_memory_accounting():
    """A jitted toy TrainStep records cost_analysis FLOPs, a peak-HBM
    figure, and cache counters: 1 compile however many calls run."""
    paddle.seed(0)
    m = paddle.nn.Sequential(paddle.nn.Linear(8, 16),
                             paddle.nn.ReLU(),
                             paddle.nn.Linear(16, 4))
    opt = paddle.optimizer.AdamW(1e-3, parameters=m.parameters())
    from paddle_tpu.jit import TrainStep
    step = TrainStep(m, lambda out, a, k: (out * out).mean(), opt)
    x = paddle.to_tensor(
        np.random.RandomState(0).randn(4, 8).astype(np.float32))
    l0 = float(step(x).numpy())
    l1 = float(step(x).numpy())
    assert np.isfinite(l0) and np.isfinite(l1) and l1 < l0  # it trains

    rep = monitor.step_report(step.telemetry_name)
    assert rep is not None
    assert rep.get("flops", 0) > 0
    assert rep["memory"].get("peak_hbm_bytes", 0) > 0
    assert rep["collective_census"] == []     # single-device program

    def c(name):
        return monitor.counter(name, labels=("step",)) \
            .labels(step=step.telemetry_name).value()

    assert c("train_step_compiles") == 1
    assert c("train_step_calls") == 2
    assert c("train_step_fallback_recompiles") == 0

    # analytic MFU is defined and positive once FLOPs are recorded —
    # against a device peak; the CPU backend has none
    assert monitor.analytic_mfu(step.telemetry_name, 1e-3) is None
    amfu = monitor.analytic_mfu(step.telemetry_name, 1e-3,
                                peak_flops=197e12)
    assert amfu is not None and amfu > 0


def test_trainstep_signature_change_counts_fallback():
    """A new batch shape must still run (through the caching jit path)
    and be counted as a fallback recompile, not crash the AOT path."""
    paddle.seed(0)
    m = paddle.nn.Linear(6, 3)
    opt = paddle.optimizer.SGD(1e-2, parameters=m.parameters())
    from paddle_tpu.jit import TrainStep
    step = TrainStep(m, lambda out, a, k: (out * out).mean(), opt)
    rng = np.random.RandomState(0)
    step(paddle.to_tensor(rng.randn(4, 6).astype(np.float32)))
    step(paddle.to_tensor(rng.randn(2, 6).astype(np.float32)))  # new sig
    val = monitor.counter(
        "train_step_fallback_recompiles", labels=("step",)) \
        .labels(step=step.telemetry_name).value()
    assert val == 1


# ------------------------------------------------------- collective census

def test_collective_census_counts_shard_map_ops():
    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices")
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(devs[:2]), ("x",))

    def body(a):
        s = jax.lax.psum(a, "x")
        t = jax.lax.all_to_all(a.reshape(2, -1), "x", 0, 0)
        return s.sum() + t.sum()

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=P("x"),
        out_specs=P(), check_vma=False)
    traced = jax.jit(f).trace(jnp.ones((16,), jnp.float32))
    census = monitor.collective_census(traced.jaxpr)
    by_op = {r["op"]: r for r in census}
    assert by_op["all_reduce"]["count"] == 1
    assert by_op["all_reduce"]["axis"] == "x"
    assert by_op["all_to_all"]["count"] == 1
    # per-shard payload: 8 f32 rows = 32 bytes each
    assert by_op["all_reduce"]["bytes"] == 32
    assert by_op["all_to_all"]["bytes"] == 32


def test_census_recurses_into_scan():
    def step(c, x):
        return c + x.sum(), jax.lax.psum(x, "x")

    devs = jax.devices()
    if len(devs) < 2:
        pytest.skip("needs >= 2 devices")
    from jax.sharding import Mesh, PartitionSpec as P
    mesh = Mesh(np.array(devs[:2]), ("x",))

    def body(xs):
        c, ys = jax.lax.scan(step, jnp.float32(0), xs)
        return ys + c

    f = jax.shard_map(
        body, mesh=mesh,
        in_specs=P(None, "x"),
        out_specs=P(None, "x"), check_vma=False)
    traced = jax.jit(f).trace(jnp.ones((3, 8), jnp.float32))
    census = monitor.collective_census(traced.jaxpr)
    ar = [r for r in census if r["op"] == "all_reduce"]
    assert ar and ar[0]["count"] >= 1     # found inside the scan body


# ----------------------------------------------------- span instrumentation

def test_record_event_feeds_registry_histogram():
    from paddle_tpu.profiler import RecordEvent
    h = monitor.histogram("record_event_ms", labels=("name",))
    before = h.labels(name="unit_test_span").value()["count"]
    with RecordEvent("unit_test_span"):
        pass
    after = h.labels(name="unit_test_span").value()["count"]
    assert after == before + 1


def test_moe_stats_served_by_registry():
    from paddle_tpu.distributed import moe as moe_mod
    moe_mod.reset_moe_stats()
    moe_mod.MOE_STATS["grouped_mm_calls"] += 1
    moe_mod.MOE_STATS["grouped_mm_kernel"] = "ragged_dot"
    st = moe_mod.moe_stats()
    assert st["grouped_mm_calls"] == 1
    assert st["grouped_mm_kernel"] == "ragged_dot"
    # the registry serves the same numbers
    g = monitor.gauge("moe_path_calls", labels=("path",))
    assert g.labels(path="grouped_mm_calls").value() == 1
    assert monitor.info("moe_grouped_mm_kernel").get() == "ragged_dot"
    moe_mod.reset_moe_stats()
    assert moe_mod.moe_stats()["grouped_mm_calls"] == 0

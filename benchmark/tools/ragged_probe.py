#!/usr/bin/env python3
"""The ragged attention kernel alone, at the cells' shapes, under a
profiler trace: its device time a call for each kv-tile width of the
narrow walk (the walk of a one-token query tile), beside another
checkout's kernel.

    python3 benchmark/tools/ragged_probe.py [--other DIR] \
        [--spans 128,256,512,1024] [--calls 20] [--seed N]

Shapes (bf16, block 16, contexts drawn from the seed):

- ``phi4_cross``: ``reason-long-sat``'s cross layers' call — 96 slots
  of one row, 40 paired query heads over a flat pool of 10 lane tiles
  (1,280 lanes), contexts lognormal around 1,700 positions;
  its ``narrow_<N>_row`` variants take the whole pool row as one head
  group.
- ``solar_gqa``: ``reason-wide-sat``'s call — 95 decode rows at
  contexts lognormal around 1,500 and a 256-row chunk at 512, 64
  query heads over 8 kv heads of 128 (4-D pool).
- ``lfm2_pairs``: ``chat-wide-sat``'s — 127 decode rows around 600
  and a 256-row chunk, 32 heads over 8 kv heads of 64 in pairs (flat).

``--other DIR`` times ``DIR/paddle_tpu/ops/pallas/paged_attention.py``
(another checkout's kernel, loaded beside this one) at each shape too,
and every variant's output is compared with the first one timed. The
kernel's time is the sum of its ``tpu_custom_call`` events in the
trace over ``--calls`` calls. One JSON line a (shape, variant); a run
of the benchmark never calls this.
"""
import argparse
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                      # noqa: E402
import jax.numpy as jnp                         # noqa: E402
import numpy as np                              # noqa: E402

from benchmark.lib import xplane                # noqa: E402
from paddle_tpu.ops.pallas import paged_attention as pa  # noqa: E402

BS = 16
SHAPES = {
    # slots, rows, w_max, query heads, lane tiles, head dim, table
    # positions, flat pool, (mean context, sigma), chunk rows
    "phi4_cross": (96, 96, 1, 40, 10, 128, 8192, True, (1700, 0.6), 0),
    "solar_gqa": (96, 352, 256, 64, 8, 128, 6144, False, (1500, 0.6), 256),
    "lfm2_pairs": (128, 384, 256, 32, 4, 64, 4096, True, (600, 0.7), 256),
}


def other_module(checkout):
    path = os.path.join(checkout, "paddle_tpu", "ops", "pallas",
                        "paged_attention.py")
    spec = importlib.util.spec_from_file_location(
        "paddle_tpu.ops.pallas._other_paged_attention", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def inputs(name, seed):
    s, r, w, h, tiles, d, reach, flat, (mean, sigma), chunk = SHAPES[name]
    rng = np.random.default_rng(seed)
    mb = reach // BS
    hkv = tiles * 128 // d
    ctx = np.clip(np.exp(rng.normal(np.log(mean), sigma, s)), 64,
                  reach - 512).astype(np.int32)
    q_lens = np.ones(s, np.int32)
    if chunk:
        q_lens[0], ctx[0] = chunk, 512
    starts = np.concatenate([[0], np.cumsum(q_lens)[:-1]]).astype(np.int32)
    nb = 1 + s * mb
    tables = (1 + rng.permutation(nb - 1)).reshape(s, mb).astype(np.int32)
    key = jax.random.PRNGKey(seed % (1 << 31))
    k1, k2, k3 = jax.random.split(key, 3)
    shape = (nb, BS, hkv * d) if flat else (nb, BS, hkv, d)
    kp = jax.random.normal(k1, shape, jnp.bfloat16)
    vp = jax.random.normal(k2, shape, jnp.bfloat16)
    q = jax.random.normal(k3, (r, h, d), jnp.bfloat16)
    return (q, kp, vp, jnp.asarray(tables), jnp.asarray(ctx),
            jnp.asarray(q_lens), jnp.asarray(starts)), w, ctx, q_lens


def kernel_ms(fn, args, calls, where):
    """Device milliseconds a call of the kernel, from a trace."""
    out = fn(*args)
    out.block_until_ready()
    shutil.rmtree(where, ignore_errors=True)
    jax.profiler.start_trace(where)
    for _ in range(calls):
        out = fn(*args)
    out.block_until_ready()
    jax.profiler.stop_trace()
    events = xplane.load(xplane.find_trace(where), xplane.is_device_line)
    kern = [e for e in events if "custom-call" in e.name
            and "ragged_paged_attention" in e.name + e.text]
    if len(kern) != calls:
        raise RuntimeError(f"{len(kern)} kernel events for {calls} calls")
    return sum(e.dur_ns for e in kern) / calls / 1e6, out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--other")
    ap.add_argument("--spans", default="128,256,512,1024")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--seed", type=int, default=2147539001)
    a = ap.parse_args()
    print(json.dumps({"device": jax.devices()[0].device_kind,
                      "platform": jax.devices()[0].platform}), flush=True)
    other = other_module(a.other) if a.other else None
    where = os.path.join(ROOT, "benchmark_out", "ragged_probe")
    spans = [int(x) for x in a.spans.split(",")]
    for name in a.shapes.split(","):
        args, w, ctx, q_lens = inputs(name, a.seed)
        variants = []
        if other is not None:
            variants.append(("other", other, None, None))
        variants += [(f"narrow_{n}", pa, n, None) for n in spans]
        if name == "phi4_cross":
            variants += [(f"narrow_{n}_row", pa, n, 1280) for n in spans]
        first = None
        for label, mod, span, lanes in variants:
            keep = (pa._NARROW_POSITIONS, pa._GROUP_LANES)
            if span is not None:
                pa._NARROW_POSITIONS = span
            if lanes is not None:
                pa._GROUP_LANES = lanes
            fn = jax.jit(lambda q, kp, vp, t, c, ql, rs, mod=mod:
                         mod.pallas_ragged_paged_attention(
                             q, kp, vp, t, c, ql, rs, w_max=w))
            t0 = time.monotonic()
            try:
                ms, out = kernel_ms(fn, args, a.calls, where)
            finally:
                pa._NARROW_POSITIONS, pa._GROUP_LANES = keep
            rec = {"shape": name, "variant": label, "kernel_ms": ms,
                   "wall_s": round(time.monotonic() - t0, 1),
                   "mean_ctx": float(ctx.mean()),
                   "rows": int(q_lens.sum())}
            out = np.asarray(out, np.float32)
            if first is None:
                first = out
            else:
                rec["max_abs_diff"] = float(np.abs(out - first).max())
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()

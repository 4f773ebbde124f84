#!/usr/bin/env python3
"""What a device trace keeps of a ``jax.named_scope`` around XLA's own
fusions, and what one short-convolution mixer's parts cost.

    python3 benchmark/tools/scope_probe.py

One ``conv`` mixer's shapes at the published widths (384 rows x 2048,
a state of 129 seats): ``in_proj``, then under the scope ``short_conv``
the gating, the taps, the state's gather and scatter, then
``out_proj``, jitted and traced for three calls. Printed: the trace's
device events whose name holds ``fusion`` (with their times), then
those whose name or statistics hold ``short_conv``. On the v5e the
second list is empty: the profiler keeps a scope's path on a kernel's
custom call alone, so no reader can time the scope and
``state_share.cw`` is not in the benchmark (PERF.md section 7); the
first list is where the mixer's ~8 us of state work against its ~88 us
was read. A run of the benchmark never calls this.
"""
import os
import shutil
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                      # noqa: E402
import jax.numpy as jnp                         # noqa: E402

from benchmark.lib import xplane                # noqa: E402

ROWS, HIDDEN, SEATS = 384, 2048, 129


def mixer(x, w_in, w_out, state, seat):
    y = x @ w_in
    with jax.named_scope("short_conv"):
        b, c, z = jnp.split(y, 3, axis=-1)
        g = b * z
        before = jnp.pad(g, ((1, 0), (0, 0)))[:-1]
        kept = state[seat]
        taps = (g.astype(jnp.float32) * 0.5
                + jnp.where((seat > 3)[:, None], before,
                            kept).astype(jnp.float32) * 0.25)
        state = state.at[:8].set(g[:8])
        out = c * taps.astype(x.dtype)
    return out @ w_out, state


if __name__ == "__main__":
    x = jnp.ones((ROWS, HIDDEN), jnp.bfloat16)
    w_in = jnp.full((HIDDEN, 3 * HIDDEN), 0.01, jnp.bfloat16)
    w_out = jnp.full((HIDDEN, HIDDEN), 0.01, jnp.bfloat16)
    state = jnp.zeros((SEATS, HIDDEN), jnp.bfloat16)
    seat = jnp.arange(ROWS) % SEATS
    step = jax.jit(mixer)
    step(x, w_in, w_out, state, seat)[0].block_until_ready()
    where = os.path.join(ROOT, "benchmark_out", "scope_probe")
    shutil.rmtree(where, ignore_errors=True)
    jax.profiler.start_trace(where)
    for _ in range(3):
        step(x, w_in, w_out, state, seat)[0].block_until_ready()
    jax.profiler.stop_trace()
    path = xplane.find_trace(where)
    print(xplane.describe(path, limit=12, grep="fusion")[:6000])
    print("---- short_conv")
    print(xplane.describe(path, limit=4, grep="short_conv")[:3000])

#!/usr/bin/env python3
"""A run of a cell of a model with slot state, with a fault planted in
the state manager.

    python3 benchmark/tools/state_fault.py --workload <cell> --seed <n> \
        --seconds <s> --trace 0

The same run as ``run.py`` makes, but a seat's convolution state is NOT
zeroed when a new request takes it: the served path's short convolution
(``paddle_tpu.models.lfm2_moe.Lfm2ShortConv.forward_paged``) is handed
row positions in which no slot's first row is position 0 (a 0 reads as
a 1 there, and only there: attention and the cache writes see the true
positions), so the first rows of every request after a seat's first
read the last ``L - 1`` gated inputs of the seat's previous occupant
where the reference reads zeros. The result line has to read
``"correct": false`` at the cell's limits, or ``PERF.md`` section 2
says that it is not caught (``tools/control.py`` holds the float8
control and the altered token). The benchmark's own runs plant nothing.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench          # noqa: E402


def stale_state(_engine=None):
    from paddle_tpu.models import lfm2_moe
    sound = lfm2_moe.Lfm2ShortConv.forward_paged

    def forward_paged(self, x, cache, ragged_meta):
        ql, rs, sl, pos = ragged_meta[:4]
        pos = lfm2_moe.apply_jax(
            "stale_state", lambda p: p + (p == 0).astype(p.dtype), pos)
        return sound(self, x, cache, (ql, rs, sl, pos))

    lfm2_moe.Lfm2ShortConv.forward_paged = forward_paged


if __name__ == "__main__":
    stale_state()
    sys.exit(bench.main(sys.argv[1:]))

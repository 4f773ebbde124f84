#!/usr/bin/env python3
"""A run of an expert cell with a fault planted in the router.

    python3 benchmark/tools/gate_fault.py --workload <cell> --seed <n> \
        --seconds <s> --trace 0

The same run as ``run.py`` makes, but the served path's gate
(``paddle_tpu.distributed.moe.group_limited_gate``) takes its logits and
its choice bias rounded to bfloat16 — what a router run in the served
precision, not in the float32 its publishers state, would choose from.
Some rows then pick a different eighth expert than the float32
reference does; where that expert is held here the layer's output
moves. The result line has to read ``"correct": false`` at the cell's
limits (``tools/control.py`` holds the float8 control and the altered
token). The benchmark's own runs plant nothing.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench          # noqa: E402


def gate_in_bf16(_engine=None):
    import jax.numpy as jnp
    from paddle_tpu.distributed import moe
    sound = moe.group_limited_gate

    def rounded(x):
        return x.astype(jnp.bfloat16).astype(jnp.float32)

    def gate(logits, bias, **kw):
        return sound(rounded(logits), rounded(bias), **kw)
    moe.group_limited_gate = gate


if __name__ == "__main__":
    gate_in_bf16()
    sys.exit(bench.main(sys.argv[1:]))

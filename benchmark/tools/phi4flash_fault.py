#!/usr/bin/env python3
"""A run of a cell of the ``phi4flash`` family with a fault planted in
the program.

    python3 benchmark/tools/phi4flash_fault.py \
        --fault window_half|lambda_zero|scan_state_zero|memory_ones \
        --workload <cell> --seed <n> --seconds <s> --trace 0

The same run as ``run.py`` makes, but

- ``window_half``: the program's window layers attend the last 256
  positions where the configuration states 512 (their rings hold 256);
- ``lambda_zero``: every differential attention layer's lambda is 0,
  so the second softmax is never subtracted;
- ``scan_state_zero``: the Mamba layers' scan state is zeroed before
  every tick, so no state is carried from one tick to the next (the
  conv's taps still are);
- ``memory_ones``: the gated memory units read ones where the memory
  ``m`` of layer ``N/2`` stands.

The result line has to read ``"correct": false`` at the cell's limits,
or ``PERF.md`` section 2 says that the fault is not caught
(``tools/control.py`` holds the float8 control and the altered token).
The benchmark's own runs plant nothing.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench          # noqa: E402


def window_half():
    from benchmark.models import phi4flash as fam
    sound = fam.build

    def build(cfg, seed, training):
        model = sound(cfg, seed, training)
        model.config.sliding_window = cfg["sliding_window"] // 2
        return model

    fam.build = build


def lambda_zero():
    import jax.numpy as jnp
    from paddle_tpu.models import phi4flash
    phi4flash.diff_lambda = lambda *a: jnp.float32(0.0)


def scan_state_zero():
    import jax.numpy as jnp
    from paddle_tpu.models import phi4flash
    sound = phi4flash.ssm_step

    def ssm_step(dt, x, b, c, a, state, meta):
        return sound(dt, x, b, c, a, jnp.zeros_like(state), meta)

    phi4flash.ssm_step = ssm_step


def memory_ones():
    import jax.numpy as jnp
    from paddle_tpu.framework.core import as_jax
    from paddle_tpu.models import phi4flash
    sound = phi4flash.Phi4FlashGMU.forward

    def forward(self, u, m):
        return sound(self, u, jnp.ones_like(as_jax(m)))

    phi4flash.Phi4FlashGMU.forward = forward


FAULTS = {"window_half": window_half, "lambda_zero": lambda_zero,
          "scan_state_zero": scan_state_zero, "memory_ones": memory_ones}

if __name__ == "__main__":
    argv = sys.argv[1:]
    i = argv.index("--fault")
    FAULTS[argv[i + 1]]()
    del argv[i:i + 2]
    sys.exit(bench.main(argv))

#!/usr/bin/env python3
"""A run of a cell of the ``solar_open2`` family with a fault planted in
the delta rule's recurrent state.

    python3 benchmark/tools/kda_fault.py --fault stale_state|bf16_state \
        --workload <cell> --seed <n> --seconds <s> --trace 0

The same run as ``run.py`` makes, but

- ``stale_state``: a seat's recurrent (matrix) state is NOT zeroed when
  a new request takes it: ``ops/pallas/delta_rule.kda_step`` is handed
  row positions in which no slot's first row is position 0 (a 0 reads
  as a 1 there, and only there: the short convolution, attention and
  the cache writes see the true positions), so every request after a
  seat's first starts from the matrix state its seat's previous
  occupant left where the reference starts from zeros;
- ``bf16_state``: the recurrent state is rounded to bfloat16 every time
  a tick has advanced it (the table stays float32, its values are
  bfloat16's): what holding the state one precision down would give.

The result line has to read ``"correct": false`` at the cell's limits,
or ``PERF.md`` section 2 says that the fault is not caught
(``tools/control.py`` holds the float8 control and the altered token,
``tools/state_fault.py`` the stale convolution state of the
``lfm2_moe`` family). The benchmark's own runs plant nothing.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench          # noqa: E402


def stale_state():
    from paddle_tpu.models import solar_open2
    sound = solar_open2.kda_step

    def kda_step(q, k, v, g, beta, state, meta):
        ql, rs, sl, pos = meta[:4]
        pos = pos + (pos == 0).astype(pos.dtype)
        return sound(q, k, v, g, beta, state, (ql, rs, sl, pos) + meta[4:])

    solar_open2.kda_step = kda_step


def bf16_state():
    import jax.numpy as jnp
    from paddle_tpu.models import solar_open2
    sound = solar_open2.kda_step

    def kda_step(q, k, v, g, beta, state, meta):
        o, state = sound(q, k, v, g, beta, state, meta)
        return o, state.astype(jnp.bfloat16).astype(state.dtype)

    solar_open2.kda_step = kda_step


FAULTS = {"stale_state": stale_state, "bf16_state": bf16_state}

if __name__ == "__main__":
    argv = sys.argv[1:]
    i = argv.index("--fault")
    FAULTS[argv[i + 1]]()
    del argv[i:i + 2]
    sys.exit(bench.main(argv))

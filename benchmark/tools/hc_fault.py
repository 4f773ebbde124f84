#!/usr/bin/env python3
"""A run of a cell of the ``xing4_0`` family with a fault planted in the
hyper-connections of its residual path.

    python3 benchmark/tools/hc_fault.py --fault res_identity|sinkhorn_once \
        --workload <cell> --seed <n> --seconds <s> --trace 0

The same run as ``run.py`` makes, but every connection boundary
(``paddle_tpu.models.xing4_0.hc_mix``, the kernel on the chip) is
wrapped so that

- ``res_identity``: the coefficients it hands to the next boundary carry
  ``H_res = I``: the streams are not mixed, each only gains its
  ``H_post`` share of the sublayer's output;
- ``sinkhorn_once``: ``H_res`` is projected by ONE Sinkhorn step (rows,
  then columns) instead of the configuration's 20: still doubly
  stochastic by its columns, not by its rows.

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


def _wrap(make):
    from paddle_tpu.models import xing4_0
    xing4_0.hc_mix = make(xing4_0.hc_mix)


def res_identity():
    import jax.numpy as jnp

    def make(sound):
        def hc_mix(*a, n, last=False, **kw):
            out = sound(*a, n=n, last=last, **kw)
            if last:
                return out
            x, u, coef = out
            eye = jnp.eye(n, dtype=coef.dtype).reshape(1, n * n)
            return x, u, coef.at[:, n:].set(
                jnp.broadcast_to(eye, (coef.shape[0], n * n)))
        return hc_mix
    _wrap(make)


def sinkhorn_once():
    def make(sound):
        return lambda *a, **kw: sound(*a, **dict(kw, iters=1))
    _wrap(make)


FAULTS = {"res_identity": res_identity, "sinkhorn_once": sinkhorn_once}

if __name__ == "__main__":
    argv = sys.argv[1:]
    i = argv.index("--fault")
    FAULTS[argv[i + 1]]()
    del argv[i:i + 2]
    sys.exit(bench.main(argv))

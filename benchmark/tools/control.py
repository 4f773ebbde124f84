#!/usr/bin/env python3
"""A run of a cell that also reads the comparison's control, or runs
with a fault planted under the timed path.

    python3 benchmark/tools/control.py --workload <cell> --seed <n> \
        --seconds <s> --trace 0 [--fault altered_token]

The same run as ``run.py`` makes, with one thing more after the window:
the plain reference is computed again one precision below the one the
configuration states (float8 operands for bfloat16), put in the
program's place, and its numbers go through the same verdict under the
cell's limits as the run's own: a line ``{"reading": "control_lowp",
"correct": false, "over": [...]}`` before the result (a training cell
adds the planted faults "half of the batch left out" and "no master
copy"). Those are the upper readings that ``PERF.md`` sets each limit
under. ``--fault altered_token`` instead alters every fifth token where
the engine hands it out, at the cell's own size: the result line then
has to read ``"correct": false``. The benchmark's own runs do neither.
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import run as bench          # noqa: E402


def altered_token(engine):
    inner, seen = engine._stream, [0]

    def stream(rid, tok):
        seen[0] += 1
        inner(rid, tok + 1 if seen[0] % 5 == 0 and tok > 1 else tok)
    engine._stream = stream


if __name__ == "__main__":
    argv = sys.argv[1:]
    hooks = {"control": True}
    if "--fault" in argv:
        i = argv.index("--fault")
        hooks = {"engine": {"altered_token": altered_token}[argv[i + 1]]}
        del argv[i:i + 2]
    sys.exit(bench.main(argv, hooks=hooks))

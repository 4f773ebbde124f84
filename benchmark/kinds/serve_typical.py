"""A serving cell (``"kind": "serve_typical"``) whose comparison also
bounds the TYPICAL served token, not the worst one alone.

Driven as ``kinds/serve.py`` drives a cell, by that file. What differs
is the comparison. ``logit_gap_max`` is a maximum over a few hundred to
two thousand rows. Where every expert of a layer is held, a row in
which the bf16 program's chosen experts differ from the float32
reference's (which routes from its own hidden state) moves by a
fraction of a logit, more with every layer that flips; three rows in
ten have such a layer in a sound run and the worst of them reaches
what the float8 control reads, whose every row has one
(``tools/route_probe.py`` counts them). The two overlap by their maxima
and lie far apart by their quantiles: in a sound run nine rows in ten
carry the reference's own first token, in the control half of them do.
So the cell's file names quantiles of the same gaps
(``check.quantiles``: ``{"logit_gap_p75": 0.75, ...}``), each with a
limit of its own beside ``logit_gap_max``'s, and every run logs the
gaps' whole summary (``gap_summary``) for the next limit to be set
from.

The gaps are those of ``serve.run``'s own reference pass, kept as they
go by; the control's (``tools/control.py``) likewise.
"""
from __future__ import annotations

import types

import numpy as np

from benchmark.kinds import serve
from benchmark.lib import check
from benchmark.lib.harness import log

SUMMARY = (0.5, 0.75, 0.9, 0.95, 0.99)


def summary(gaps):
    """What a run logs of its gaps: count, mean, quantiles, maximum."""
    out = {"n": int(gaps.size), "mean": float(gaps.mean()),
           "max": float(gaps.max())}
    out.update({f"p{round(100 * q)}": float(np.quantile(gaps, q))
                for q in SUMMARY})
    return out


def run(ctx):
    kept = {}

    def served_logits(*args, lowp=False, **kw):
        out = ctx.family.served_logits(*args, lowp=lowp, **kw)
        kept[lowp] = out
        return out

    inner = types.SimpleNamespace(**vars(ctx))
    inner.family = types.SimpleNamespace(
        build=ctx.family.build, served_logits=served_logits)
    numbers, attempted, failed = serve.run(inner)
    if False not in kept:
        return numbers, attempted, failed       # nothing finished
    logits, served = kept[False]
    quantiles = ctx.cell["check"]["quantiles"]
    readings = {"run": check.gaps_below_best(logits, served)}
    if True in kept:
        # the control: the gap of the token the float8 reference puts
        # first, row by row (``serve.run`` took their maximum)
        readings["control_lowp"] = check.gaps_below_best(
            logits, np.asarray(kept[True][0].argmax(-1)))
    for name, gaps in readings.items():
        into = numbers if name == "run" else ctx.controls[name]
        into.update({k: float(np.quantile(gaps, q))
                     for k, q in quantiles.items()})
        log(gap_summary=name, **summary(gaps))
    return numbers, attempted, failed

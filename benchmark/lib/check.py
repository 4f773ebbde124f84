"""The comparisons that decide ``correct``, and their limits' arithmetic.

Serving: the widest gap by which a served (greedy) token's logit lies
below the reference's best logit at that position, over a seeded sample
of finished requests with the longest in it.

Training: the first two steps' losses; per leaf the norm of the first gradient (as
the optimizer got it: Adam's first moment after one step over 1 - beta1)
and the norm of the parameters' change after three steps. Norm gaps are
taken by the worst leaf: |program's norm - reference's norm| over the
larger of the reference's norm of that leaf and of the median leaf.
"""
from __future__ import annotations

import numpy as np


# The third step's loss is followed and logged but not compared: on the
# chip neither the float8 control nor a planted fault reads far enough
# above sound runs for a limit to stand between them (PERF.md, section 2).
LOSSES_COMPARED = 2


def sample_requests(records, seed, max_requests, min_tokens):
    """Finished requests to check: the longest, then seeded draws until
    ``min_tokens`` served tokens or ``max_requests``."""
    done = [r for r in records if r.finished]
    if not done:
        return []
    done.sort(key=lambda r: r.rid)
    longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([int(seed), 3]).permutation(len(rest))
    picked, n_tok = [longest], len(longest.tokens)
    for i in order:
        if len(picked) >= max_requests or n_tok >= min_tokens:
            break
        picked.append(rest[i])
        n_tok += len(rest[i].tokens)
    return picked


def token_faults(records, vocab):
    """Requests whose answer is wrong in itself: more tokens than asked,
    a finished one with fewer, or an id outside the vocabulary."""
    bad = 0
    for r in records:
        toks = np.asarray(r.tokens, np.int64)
        if len(toks) > r.want or (toks.size and (toks.min() < 0
                                                 or toks.max() >= vocab)):
            bad += 1
    return bad


def gaps_below_best(logits, tokens):
    """For each row: how far the token's logit lies below the row's best."""
    logits = np.asarray(logits, np.float32)
    got = np.take_along_axis(logits, np.asarray(tokens)[:, None], axis=-1)
    return (logits.max(axis=-1) - got[:, 0]).astype(np.float64)


def worst_leaf_gap(prog, ref, skip=()):
    """Worst over leaves of |prog - ref| / max(ref, median ref)."""
    names = [k for k in ref if k not in skip]
    med = float(np.median([ref[k] for k in names]))
    worst, at = 0.0, None
    for k in names:
        gap = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if gap > worst:
            worst, at = gap, k
    return worst, at


def flat_gradient_leaves(ref_gnorm):
    """Leaves whose reference gradient is nought to rounding (a key's
    bias under softmax): under a thousandth of the median leaf's. Adam
    moves them by round-off alone, so their change is not compared."""
    med = float(np.median(list(ref_gnorm.values())))
    return {k for k, v in ref_gnorm.items() if v < 1e-3 * med}


def train_numbers(prog, ref):
    """``prog``/``ref``: {"losses": [..3], "gnorm": {leaf: norm},
    "change": {leaf: norm}}. Returns {name: value} and where the worst
    leaves were."""
    out, where = {}, {}
    for i, (a, b) in enumerate(zip(prog["losses"][:LOSSES_COMPARED],
                                   ref["losses"]), 1):
        out[f"loss_gap_{i}"] = abs(a - b) / abs(b)
    out["grad_norm_gap"], where["grad_norm_gap"] = worst_leaf_gap(
        prog["gnorm"], ref["gnorm"])
    out["change_norm_gap"], where["change_norm_gap"] = worst_leaf_gap(
        prog["change"], ref["change"],
        skip=flat_gradient_leaves(ref["gnorm"]))
    return out, where


def verdict(numbers, limits):
    """[(name, value, limit, ok)] and whether all hold. A number that is
    not finite fails; one with no limit in the cell's file is an error."""
    rows, ok = [], True
    for name, value in numbers.items():
        limit = limits[name]
        good = bool(np.isfinite(value)) and value <= limit
        rows.append((name, float(value), float(limit), good))
        ok = ok and good
    return rows, ok

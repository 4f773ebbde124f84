"""One general traffic generator, driven by a mix's data file.

A mix (``benchmark/traffic/<name>.json``) fixes the request sizes, the
arrival gaps and the order of both: a replayed trace. ``--seed`` draws
the token ids (and the weights), so every seed offers the same work at
the same moments: a window holds so few requests that their order
decides the tails, and two seeds must differ no more than two runs of one.

Serving mix keys::

    pool            how many (prompt, output) pairs make one epoch
    prompt_len      {"median", "sigma", "min", "max"}  lognormal, clipped
    output_len      the same
    max_total       prompt + output never exceeds this (output is cut)
    loop            "closed" (clients) or "open" (rate_per_s, Poisson)
    clients         closed loop: requests kept in flight
    rate_per_s      open loop: mean arrivals per second

Lengths are the lognormal's quantiles at (i + 0.5) / pool, not draws, so
the pool is the distribution itself; prompts and outputs are paired by a
fixed shuffle. Inter-arrival gaps are the exponential's quantiles the
same way. Each epoch is a fresh permutation of both from a fixed
generator, the same for every seed.

Training job keys: ``seq_len``, ``rows_per_step``; rows are uniform token
ids from the seed, all different, labels are the next token.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

_PAIRING_SEED = 20260930      # fixed: pairs and orders sizes, never --seed


def lognormal_quantiles(spec, n):
    """The n mid-quantiles of a clipped lognormal, as whole numbers."""
    nd = NormalDist()
    mu = math.log(float(spec["median"]))
    q = [math.exp(mu + float(spec["sigma"]) * nd.inv_cdf((i + 0.5) / n))
         for i in range(n)]
    return np.clip(np.rint(q), int(spec["min"]), int(spec["max"])).astype(int)


def exponential_quantiles(rate, n):
    """The n mid-quantiles of Exp(rate): gaps whose mean is ~1/rate."""
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / float(rate)
    return gaps * (1.0 / float(rate)) / gaps.mean()   # exact mean 1/rate


def size_pool(mix):
    """The epoch's (prompt_len, output_len) pairs: the same for every seed."""
    n = int(mix["pool"])
    prompts = lognormal_quantiles(mix["prompt_len"], n)
    outputs = lognormal_quantiles(mix["output_len"], n)
    outputs = outputs[np.random.default_rng(_PAIRING_SEED).permutation(n)]
    cap = int(mix["max_total"])
    outputs = np.minimum(outputs, cap - prompts)
    if outputs.min() < 1:
        raise ValueError("a prompt leaves no room for output under max_total")
    return [(int(p), int(o)) for p, o in zip(prompts, outputs)]


class RequestStream:
    """Endless sequence of requests: ``next()`` gives
    ``(prompt_ids, output_len, gap_s)``; ``gap_s`` is the time to wait
    after the previous arrival (0.0 in a closed loop)."""

    def __init__(self, mix, vocab_size, seed):
        self.mix = mix
        self.vocab = int(vocab_size)
        self.rng = np.random.default_rng([int(seed), 1])
        self.order = np.random.default_rng([_PAIRING_SEED, 5])
        self.pool = size_pool(mix)
        self.gaps = exponential_quantiles(mix["rate_per_s"], len(self.pool)) \
            if mix["loop"] == "open" else np.zeros(len(self.pool))
        self._epoch = []

    def next(self):
        if not self._epoch:
            order = self.order.permutation(len(self.pool))
            gaps = self.gaps[self.order.permutation(len(self.pool))]
            self._epoch = [(self.pool[i], float(g))
                           for i, g in zip(order, gaps)][::-1]
        (p_len, o_len), gap = self._epoch.pop()
        prompt = self.rng.integers(1, self.vocab, p_len, dtype=np.int64)
        return prompt, o_len, gap


class TokenRows:
    """Map-style dataset of seeded token rows for a training job. Row i
    is a function of (seed, i), so every row differs and DataLoader
    workers (numpy only, no JAX) reproduce it without shared state."""

    def __init__(self, seq_len, vocab_size, seed, length=1 << 24):
        self.seq_len, self.vocab = int(seq_len), int(vocab_size)
        self.seed, self.length = int(seed), int(length)

    def __len__(self):
        return self.length

    def __getitem__(self, i):
        row = np.random.default_rng([self.seed, 2, int(i)]).integers(
            0, self.vocab, self.seq_len + 1, dtype=np.int64)
        return row[:-1], row[1:]

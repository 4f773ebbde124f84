"""Weights from ``--seed``, made on the device in one jitted call.

Every leaf is a function of (seed, leaf name) alone, so the program's
copy (all leaves, one call) and the reference's (one layer at a time,
after the program's state is freed) are the same numbers without either
side handing the other an array. Leaf names are the program's
``state_dict`` names — the one thing taken from it, as a checkpoint
format would be; a family's file (``benchmark/models/``) lists them.

Initialisation: N(0, 0.02) for matrices, embedding and biases (the
published ``initializer_range``; biases are non-zero so the q/k/v bias
path is exercised), ones for the RMSNorm weights.
"""
from __future__ import annotations

import functools
import zlib

import jax
import jax.numpy as jnp

STD = 0.02


def split_seed(seed):
    """Any whole number (past 2**31 too) as two 31-bit words."""
    seed = int(seed)
    return seed & 0x7FFFFFFF, (seed >> 31) & 0x7FFFFFFF


def _is_norm(name):
    return name.endswith("norm.weight")


@functools.partial(jax.jit, static_argnames=("shapes", "norms", "dtype"))
def _make(lo, hi, crcs, shapes, norms, dtype):
    """Leaf names enter as numbers (``crcs``), so every layer is the same
    program and compiles once."""
    key = jax.random.fold_in(jax.random.PRNGKey(lo), hi)
    out = []
    for i, (shape, norm) in enumerate(zip(shapes, norms)):
        if norm:
            out.append(jnp.ones(shape, dtype))
            continue
        k = jax.random.fold_in(key, crcs[i])
        out.append((jax.random.normal(k, shape, jnp.float32)
                    * jnp.float32(STD)).astype(dtype))
    return out


def make(shapes, seed, dtype=jnp.bfloat16):
    """All leaves of ``shapes`` ({name: shape}) in one device call."""
    lo, hi = split_seed(seed)
    names = sorted(shapes)
    crcs = jnp.asarray([zlib.crc32(n.encode()) & 0x7FFFFFFF for n in names],
                       jnp.uint32)
    leaves = _make(jnp.uint32(lo), jnp.uint32(hi), crcs,
                   tuple(tuple(shapes[n]) for n in names),
                   tuple(_is_norm(n) for n in names), jnp.dtype(dtype).name)
    return dict(zip(names, leaves))

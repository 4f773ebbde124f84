"""Global RNG state (``paddle.seed`` parity) over jax PRNG keys.

Paddle has stateful global generators (``paddle/phi/core/generator.h``);
jax is functional. We keep a process-global key that is split on every
draw in eager mode. Inside a jitted step, callers should thread keys
explicitly (``paddle_tpu.jit`` handles this for dropout by folding in a
step counter); eager draws that happen during tracing bake the key as a
constant for that trace, which matches "fixed seed per compiled program".
"""
from __future__ import annotations

import threading

import jax
import numpy as np


class _RNGState(threading.local):
    """The key is built on first use, not at import: building a PRNG
    key initialises the backend, and a process that merely imports the
    package (a launcher, a DataLoader worker) must not take the chip."""

    def __init__(self):
        self._key = None
        self.seed_value = 0

    @property
    def key(self):
        if self._key is None:
            self._key = jax.random.PRNGKey(self.seed_value)
        return self._key

    @key.setter
    def key(self, value):
        self._key = value


_state = _RNGState()


def seed(value: int):
    _state.seed_value = int(value)
    _state.key = None
    np.random.seed(int(value) % (2 ** 32))
    return _state


def get_rng_state():
    return [_state.key]


def set_rng_state(state):
    _state.key = state[0] if isinstance(state, (list, tuple)) else state


def next_key():
    # under the traced/functional path (paddle_tpu.jit), draw from the
    # per-step traced key so dropout masks differ across jitted steps
    from .core import _grad_state
    fk = getattr(_grad_state, "functional_key", None)
    if fk is not None:
        _grad_state.functional_key, sub = jax.random.split(fk)
        return sub
    _state.key, sub = jax.random.split(_state.key)
    return sub


def set_functional_key(key):
    from .core import _grad_state
    _grad_state.functional_key = key


def get_key():
    """The active PRNG key (the per-step functional key when tracing)."""
    from .core import _grad_state
    fk = getattr(_grad_state, "functional_key", None)
    return fk if fk is not None else _state.key


def swap_key(key):
    """Install ``key`` as the active PRNG key; returns the previous one.
    Used by the mp RNG tracker to scope named dropout streams."""
    from .core import _grad_state
    fk = getattr(_grad_state, "functional_key", None)
    if fk is not None:
        _grad_state.functional_key = key
        return fk
    prev = _state.key
    _state.key = key
    return prev


def get_cuda_rng_state():
    return get_rng_state()


def set_cuda_rng_state(state):
    set_rng_state(state)

"""Tensor facade over ``jax.Array`` with Paddle eager semantics.

Reference parity (upstream paths, see SURVEY.md §0 for the line-number caveat):
  - ``phi::DenseTensor`` + eager ``autograd_meta`` (``paddle/phi/core/``,
    ``paddle/fluid/eager/``): here one Python ``Tensor`` class holding a
    ``jax.Array`` plus autograd metadata.
  - The eager GradNode engine (``paddle/fluid/eager/backward.cc``): here
    ``GradNode`` records a ``jax.vjp`` closure per executed op and
    ``run_backward`` does the queue-based topological walk with gradient
    accumulation and hook firing.

TPU-first design notes:
  - A Tensor is a registered pytree node, so user code written against this
    API can be traced by ``jax.jit``/``jax.grad`` directly — the jitted train
    step (``paddle_tpu.jit.to_static``) bypasses the tape entirely and lets
    XLA see one fused program. The tape exists for eager/debug parity only.
  - Mutation (``add_``, ``__setitem__``) is rebind-on-mutate: jax arrays are
    immutable, so in-place ops compute a new array and swap it in, preserving
    aliasing semantics at the Python-object level.
"""
from __future__ import annotations

import contextlib
import threading
import weakref
from typing import Any, Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import dtype as dtypes
from .place import Place, _get_default_place

__all__ = [
    "Tensor", "Parameter", "GradNode", "to_tensor", "as_jax", "apply_jax",
    "component", "executable_scopes",
    "no_grad", "enable_grad", "is_grad_enabled", "set_grad_enabled",
    "run_backward", "calc_gradients",
]


# --------------------------------------------------------------------------
# grad mode
# --------------------------------------------------------------------------

class _GradState(threading.local):
    def __init__(self):
        self.enabled = True
        # functional (traced) execution: mutation of module buffers is
        # allowed to carry tracers; paddle_tpu.jit collects them as outputs
        self.functional = False
        # when set (a list), functional buffer writes are journaled so a
        # trace context that does NOT thread buffers (binderless
        # to_static) can roll them back instead of leaking tracers
        self.buffer_capture = None


_grad_state = _GradState()
_warned_to_device = False


def in_functional_mode() -> bool:
    return _grad_state.functional


@contextlib.contextmanager
def functional_mode():
    prev = _grad_state.functional
    _grad_state.functional = True
    try:
        yield
    finally:
        _grad_state.functional = prev


def functional_buffer_write(t: "Tensor", new_arr) -> None:
    """Single entry point for module-buffer updates (BN running stats,
    QAT moving averages): journals the write when a rollback capture is
    active, so traces that cannot collect buffer outputs restore the
    pre-trace values instead of persisting tracers."""
    cap = _grad_state.buffer_capture
    if cap is not None and _grad_state.functional:
        cap.append((t, t._data))
    t._data = new_arr


@contextlib.contextmanager
def capture_buffer_writes():
    """Roll back functional buffer writes on exit (binderless
    ``to_static``: there is no binder to thread the new values, so
    keeping them would leak trace-time tracers into persistent state).
    Yields the journal so callers can inspect what was (speculatively)
    written — dy2static uses a non-empty journal to graph-break."""
    prev = _grad_state.buffer_capture
    _grad_state.buffer_capture = journal = []
    try:
        yield journal
    finally:
        for t, old in reversed(journal):
            t._data = old
        _grad_state.buffer_capture = prev


# Parameter-version clock: a monotonically increasing counter bumped
# whenever trainable state may have changed — optimizer steps (eager
# ``step()`` and the compiled ``TrainStep`` write-back) and Layer
# ``train()``/``eval()`` flips. Compiled-program caches that bake
# parameter VALUES or mode flags in as constants (the SOT segment
# cache) key on it so a stale program is never replayed.
_param_version = [0]


def bump_param_version() -> int:
    _param_version[0] += 1
    return _param_version[0]


def param_version() -> int:
    return _param_version[0]


def is_grad_enabled() -> bool:
    return _grad_state.enabled


def set_grad_enabled(mode: bool):
    _grad_state.enabled = bool(mode)


class _NoGradContext(contextlib.ContextDecorator):
    """``paddle.no_grad`` — usable as context manager and decorator."""

    def __init__(self, enabled=False):
        self._target = enabled
        self._prev = []

    def __enter__(self):
        self._prev.append(_grad_state.enabled)
        _grad_state.enabled = self._target
        return self

    def __exit__(self, *exc):
        _grad_state.enabled = self._prev.pop()
        return False

    def __call__(self, func=None):
        if func is None:
            return _NoGradContext(self._target)
        return super().__call__(func)


def no_grad(func=None):
    ctx = _NoGradContext(False)
    if func is not None:
        return ctx(func)
    return ctx


def enable_grad(func=None):
    ctx = _NoGradContext(True)
    if func is not None:
        return ctx(func)
    return ctx


# --------------------------------------------------------------------------
# GradNode
# --------------------------------------------------------------------------

class GradNode:
    """One executed op on the eager tape.

    Holds the ``jax.vjp`` pullback plus edges to the differentiable input
    tensors. Output tensors are held weakly (their grads are looked up by
    position during the backward walk); inputs strongly (they keep the
    upstream graph alive, mirroring GradNodeBase edge ownership).
    """

    __slots__ = ("op_name", "vjp_fn", "inputs", "out_refs", "out_shapes",
                 "out_dtypes", "released", "fwd_fn")

    def __init__(self, op_name: str, vjp_fn, inputs: List["Tensor"],
                 outputs: List["Tensor"], fwd_fn=None):
        self.op_name = op_name
        self.vjp_fn = vjp_fn
        self.inputs = inputs
        self.out_refs = [weakref.ref(t) for t in outputs]
        self.out_shapes = [tuple(t._data.shape) for t in outputs]
        self.out_dtypes = [t._data.dtype for t in outputs]
        self.released = False
        # pure fn over the diff-input arrays; kept so create_graph=True
        # can re-linearize (jax.vjp) AS A RECORDED OP — the saved
        # vjp_fn's residuals are constants and cannot express f''(x)
        self.fwd_fn = fwd_fn

    def release(self):
        self.vjp_fn = None
        self.inputs = []
        self.fwd_fn = None
        self.released = True


# --------------------------------------------------------------------------
# Tensor
# --------------------------------------------------------------------------

def _coerce_to_array(value, dtype=None):
    if isinstance(value, Tensor):
        arr = value._data
        if dtype is not None:
            arr = arr.astype(dtypes.to_np(dtype))
        return arr
    if isinstance(value, (jax.Array, jnp.ndarray)) or hasattr(value, "aval"):
        # jax arrays and tracers
        return value if dtype is None else value.astype(dtypes.to_np(dtype))
    np_val = np.asarray(value)
    if dtype is not None:
        np_val = np_val.astype(dtypes.to_np(dtype))
    elif np_val.dtype == np.float64:
        np_val = np_val.astype(np.float32)  # Paddle default float is fp32
    elif np_val.dtype == np.int64 and not isinstance(value, np.ndarray):
        pass  # python ints stay int64, matching Paddle
    return jnp.asarray(np_val)


class Tensor:
    __slots__ = ("_data", "stop_gradient", "grad_node", "_grad", "name",
                 "persistable", "_hooks", "is_leaf_override", "__weakref__",
                 "__dict__")

    def __init__(self, data, dtype=None, place=None, stop_gradient=True,
                 name=None):
        self._data = _coerce_to_array(data, dtype)
        self.stop_gradient = stop_gradient
        self.grad_node: Optional[GradNode] = None
        self._grad: Optional[Tensor] = None
        self.name = name
        self.persistable = False
        self._hooks = None
        self.is_leaf_override = None
        if place is not None and isinstance(place, Place):
            if not _is_tracer(self._data):
                self._data = jax.device_put(self._data, place.jax_device())

    # -- basic properties ---------------------------------------------------
    @property
    def shape(self) -> List[int]:
        return list(self._data.shape)

    @property
    def ndim(self) -> int:
        return self._data.ndim

    @property
    def dtype(self) -> dtypes.DType:
        return dtypes.convert_dtype(self._data.dtype)

    @property
    def size(self) -> int:
        return int(np.prod(self._data.shape)) if self._data.shape else 1

    def numel(self) -> int:
        return self.size

    def dim(self) -> int:
        return self._data.ndim

    @property
    def place(self) -> Place:
        if _is_tracer(self._data):
            return _get_default_place()
        try:
            dev = self._data.devices().pop()
            kind = "cpu" if dev.platform == "cpu" else "tpu"
            return Place(kind, dev.id)
        except Exception:
            return _get_default_place()

    @property
    def is_leaf(self) -> bool:
        if self.is_leaf_override is not None:
            return self.is_leaf_override
        return self.grad_node is None

    @property
    def grad(self) -> Optional["Tensor"]:
        return self._grad

    @grad.setter
    def grad(self, value):
        if value is not None and not isinstance(value, Tensor):
            value = Tensor(value)
        self._grad = value

    # -- conversions --------------------------------------------------------
    def numpy(self) -> np.ndarray:
        return np.asarray(self._data)

    def item(self, *args):
        if args:
            return self.numpy().item(*args)
        return self.numpy().item()

    def tolist(self):
        return self.numpy().tolist()

    def __float__(self):
        return float(self.numpy().item())

    def __int__(self):
        return int(self.numpy().item())

    def __bool__(self):
        return bool(self.numpy())

    def __index__(self):
        # lets size-1 integer tensors drive range()/slicing in eager,
        # matching the reference Tensor's __index__
        v = self.numpy().item()
        if not isinstance(v, (int, np.integer, bool, np.bool_)):
            raise TypeError(
                f"only integer tensors can be used as an index, got "
                f"dtype {self.dtype}")
        return int(v)

    def __len__(self):
        if self._data.ndim == 0:
            raise TypeError("len() of a 0-D tensor")
        return self._data.shape[0]

    def __repr__(self):
        if _is_tracer(self._data):
            return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                    f"traced)")
        return (f"Tensor(shape={self.shape}, dtype={self.dtype.name}, "
                f"place={self.place}, stop_gradient={self.stop_gradient},\n"
                f"       {np.asarray(self._data)!r})")

    # -- autograd -----------------------------------------------------------
    def backward(self, grad_tensor=None, retain_graph=False):
        run_backward([self], [grad_tensor], retain_graph=retain_graph)

    def clear_grad(self):
        self._grad = None

    def clear_gradient(self, set_to_zero=False):
        if set_to_zero and self._grad is not None:
            self._grad = Tensor(jnp.zeros_like(self._grad._data))
        else:
            self._grad = None

    def register_hook(self, hook: Callable):
        if self._hooks is None:
            self._hooks = []
        self._hooks.append(hook)
        return _RemovableHandle(self._hooks, hook)

    def detach(self) -> "Tensor":
        t = Tensor.__new__(Tensor)
        t._data = self._data
        t.stop_gradient = True
        t.grad_node = None
        t._grad = None
        t.name = self.name
        t.persistable = False
        t._hooks = None
        t.is_leaf_override = None
        return t

    def detach_(self):
        self.grad_node = None
        self.stop_gradient = True
        return self

    def clone(self) -> "Tensor":
        return apply_jax("clone", lambda x: x, self)

    # -- mutation (rebind) --------------------------------------------------
    def _rebind(self, other: "Tensor"):
        """In-place ops: adopt ``other``'s array + autograd state."""
        self._data = other._data
        self.grad_node = other.grad_node
        if other.grad_node is not None:
            # the node's weakref must point at *this* object now
            for i, ref in enumerate(other.grad_node.out_refs):
                if ref() is other:
                    other.grad_node.out_refs[i] = weakref.ref(self)
        self.stop_gradient = self.stop_gradient and other.stop_gradient
        return self

    def set_value(self, value):
        arr = _coerce_to_array(value)
        if tuple(arr.shape) != tuple(self._data.shape):
            raise ValueError(
                f"set_value shape mismatch: {arr.shape} vs {self._data.shape}")
        self._data = arr.astype(self._data.dtype)
        return self

    def copy_(self, other, *args):
        return self.set_value(other)

    def get_tensor(self):  # LoDTensor access parity
        return self

    # -- misc Paddle API ----------------------------------------------------
    def astype(self, dtype) -> "Tensor":
        np_dt = dtypes.to_np(dtype)
        return apply_jax("cast", lambda x: x.astype(np_dt), self)

    def cast(self, dtype) -> "Tensor":
        return self.astype(dtype)

    def cpu(self):
        t = self.detach()
        t.stop_gradient = self.stop_gradient
        if not _is_tracer(t._data):
            t._data = jax.device_put(t._data, Place("cpu").jax_device())
        return t

    def cuda(self, *a, **k):
        t = self.detach()
        t.stop_gradient = self.stop_gradient
        if not _is_tracer(t._data):
            t._data = jax.device_put(t._data, Place("tpu").jax_device())
        return t

    def to(self, *args, **kwargs):
        t = self
        for a in list(args) + list(kwargs.values()):
            if isinstance(a, str) and a.replace("paddle.", "") in dtypes._BY_NAME:
                t = t.astype(a)
            elif isinstance(a, dtypes.DType):
                t = t.astype(a)
            elif isinstance(a, (Place, str)):
                # single-process device moves are no-ops on TPU (XLA owns
                # placement); say so once instead of silently ignoring
                global _warned_to_device
                if not _warned_to_device:
                    _warned_to_device = True
                    import warnings
                    warnings.warn(
                        f"Tensor.to({a!r}): device moves are ignored in "
                        "single-process TPU execution (XLA owns "
                        "placement); use dist.shard_tensor / "
                        "paddle.device.set_device for placement control. "
                        "(warned once)")
        return t

    def pin_memory(self):
        return self

    def contiguous(self):
        return self

    def is_contiguous(self):
        return True

    @property
    def T(self):
        return apply_jax("t", lambda x: x.T, self)

    @property
    def mT(self):
        return apply_jax("mT", lambda x: jnp.swapaxes(x, -1, -2), self)

    def _to_jax(self):
        return self._data

    # NOTE: arithmetic/indexing dunders and ~200 methods (reshape, sum, ...)
    # are installed by ``paddle_tpu.ops`` at import time — single source of
    # truth for op definitions (the ops.yaml equivalent).


class Parameter(Tensor):
    """Trainable tensor (``EagerParamBase`` parity)."""

    def __init__(self, data, dtype=None, trainable=True, name=None):
        super().__init__(data, dtype=dtype, stop_gradient=not trainable,
                         name=name)
        self.persistable = True

    @property
    def trainable(self):
        return not self.stop_gradient

    @trainable.setter
    def trainable(self, value):
        self.stop_gradient = not value

    def __repr__(self):
        return "Parameter containing:\n" + super().__repr__()


class _RemovableHandle:
    def __init__(self, hooks_list, hook):
        self._hooks = hooks_list
        self._hook = hook

    def remove(self):
        try:
            self._hooks.remove(self._hook)
        except ValueError:
            pass


def _is_tracer(x) -> bool:
    return isinstance(x, jax.core.Tracer)


# pytree registration: lets jax.jit / jax.grad trace straight through Tensors
def _tensor_flatten(t: Tensor):
    return (t._data,), (t.stop_gradient,)


def _tensor_unflatten(aux, children):
    t = Tensor.__new__(Tensor)
    t._data = children[0]
    t.stop_gradient = aux[0]
    t.grad_node = None
    t._grad = None
    t.name = None
    t.persistable = False
    t._hooks = None
    t.is_leaf_override = None
    return t


jax.tree_util.register_pytree_node(Tensor, _tensor_flatten, _tensor_unflatten)
jax.tree_util.register_pytree_node(Parameter, _tensor_flatten,
                                   _tensor_unflatten)


# --------------------------------------------------------------------------
# dispatch: the single entry point every op goes through
# --------------------------------------------------------------------------

# AMP O1 interposition (set by paddle_tpu.amp; mirrors the eager AMP cast
# in paddle/fluid/eager/amp_utils.h)
_amp_hook = None


def set_amp_hook(hook):
    global _amp_hook
    _amp_hook = hook


# static-graph dispatch gate: False until paddle.static.data() creates
# the first placeholder in this process
_static_graph_seen = False


def _mark_static_graph_used():
    global _static_graph_seen
    _static_graph_seen = True


def _is_symbolic(x) -> bool:
    return isinstance(x, Tensor) and (
        getattr(x, "_feed_name", None) is not None
        or getattr(x, "_node", None) is not None)


def _any_symbolic(inputs) -> bool:
    return any(_is_symbolic(x) for x in inputs)


def tree_to_arrays(tree):
    """Pytree of Tensors -> raw arrays (shared by jit and static.nn)."""
    return jax.tree_util.tree_map(
        lambda x: as_jax(x) if isinstance(x, Tensor) else x, tree,
        is_leaf=lambda x: isinstance(x, Tensor))


def tree_to_tensors(tree):
    """Raw arrays/tracers in a pytree -> Tensors."""
    return jax.tree_util.tree_map(
        lambda x: _wrap_out(x) if isinstance(x, (jax.Array, jnp.ndarray))
        or hasattr(x, "aval") else x, tree)


def as_jax(x):
    """Tensor | array-like → jax array (no copy for Tensors)."""
    if isinstance(x, Tensor):
        return x._data
    if isinstance(x, (jax.Array, jnp.ndarray)) or hasattr(x, "aval"):
        return x
    if getattr(x, "_is_kv_quant_pool", False) \
            or getattr(x, "_is_slot_state", False):
        # a quantized KV block pool (ops.paged_cache.QuantKV) or a
        # layer's slot state (ops.paged_cache.SlotState) is a jax
        # pytree of arrays — pass it through, never coerce
        return x
    return _coerce_to_array(x)


def _wrap_out(arr, stop_gradient=True) -> Tensor:
    t = Tensor.__new__(Tensor)
    t._data = arr
    t.stop_gradient = stop_gradient
    t.grad_node = None
    t._grad = None
    t.name = None
    t.persistable = False
    t._hooks = None
    t.is_leaf_override = None
    return t


# FLAGS_check_nan_inf consumer (reference: nan_inf_utils_detail.* hooks
# every kernel output — SURVEY §5.2). Cached against the flag-registry
# version so the off-path costs one int compare per op.
_nan_check_cache = (-1, False)


def _nan_check_enabled() -> bool:
    global _nan_check_cache
    from .. import base_flags as bf
    if _nan_check_cache[0] != bf._version:
        _nan_check_cache = (bf._version,
                            bool(bf.get_flag("FLAGS_check_nan_inf")))
    return _nan_check_cache[1]


def _check_nan_inf(op_name: str, outputs):
    for o in outputs:
        if hasattr(o, "dtype") and jnp.issubdtype(o.dtype, jnp.inexact) \
                and not _is_tracer(o):
            bad = int(jnp.sum(~jnp.isfinite(o)))
            if bad:
                raise RuntimeError(
                    f"FLAGS_check_nan_inf: op {op_name!r} produced "
                    f"{bad} non-finite value(s) in output shape "
                    f"{tuple(o.shape)} dtype {o.dtype}")


# How many serving engines are tracing an executable right now
# (``executable_scopes``). While one is, ``component(name)`` enters its
# scope, so every instruction of the compiled program carries the path
# of the model code it came from (``monitor.accounting.component_map``
# reads it back out of ``compiled.as_text()``). A scope changes an
# instruction's metadata and nothing else; everywhere else it is not
# entered at all. (``apply_jax``'s op names are NOT entered: in the
# paged paths they are one name a module, ``kda_paged``, which says
# what the layer's kind and the component already say.)
_executable_scopes = 0
_scopes_lock = threading.Lock()


@contextlib.contextmanager
def executable_scopes():
    """Armed by ``ServingEngine._trace_ctx`` around every trace."""
    global _executable_scopes
    with _scopes_lock:
        _executable_scopes += 1
    try:
        yield
    finally:
        with _scopes_lock:
            _executable_scopes -= 1


def component(name: str):
    """``jax.named_scope(name)`` while an engine traces an executable,
    else a context that does nothing. ``name`` is one of
    ``monitor.accounting.COMPONENTS``, or the outer segment of one
    decoder layer, ``L<index>.<kind>`` (``L3.kda``)."""
    if _executable_scopes:
        return jax.named_scope(name)
    return contextlib.nullcontext()


def apply_jax(op_name: str, fn: Callable, *inputs, n_outputs: int = 1,
              **ignored):
    """Execute ``fn(*arrays)`` over the inputs' arrays, recording autograd.

    ``fn`` must be a pure jax function of exactly ``len(inputs)`` arrays
    (close over any static config). Non-Tensor inputs are coerced. If any
    input requires grad and grad mode is on, a ``jax.vjp`` pullback is
    recorded as a GradNode.
    """
    # static-graph mode: any symbolic input turns this op into a lazy
    # Program node instead of executing (``paddle.static`` DAG build).
    # _static_graph_seen is flipped once by static.data(), so eager-only
    # workloads never pay the per-input scan.
    if _static_graph_seen and _any_symbolic(inputs):
        from ..static.program import record_static_op
        return record_static_op(op_name, fn, inputs, n_outputs)

    # python scalars stay raw: jax weak typing then matches Paddle's
    # promotion (float32 tensor + 2 -> float32)
    arrays = [x if isinstance(x, (int, float, bool, complex))
              and not isinstance(x, Tensor) else as_jax(x) for x in inputs]
    if _amp_hook is not None:
        arrays = _amp_hook(op_name, arrays)
    tape = is_grad_enabled()
    diff_idx = []
    if tape:
        for i, x in enumerate(inputs):
            if (isinstance(x, Tensor) and not x.stop_gradient
                    and jnp.issubdtype(arrays[i].dtype, jnp.inexact)):
                diff_idx.append(i)
    if not diff_idx:
        out = fn(*arrays)
        if _nan_check_enabled():
            _check_nan_inf(op_name,
                           out if isinstance(out, (tuple, list)) else
                           (out,))
        if n_outputs == 1 and not isinstance(out, (tuple, list)):
            return _wrap_out(out)
        return tuple(_wrap_out(o) for o in out)

    diff_arrays = [arrays[i] for i in diff_idx]

    def g(*diffs):
        full = list(arrays)
        for j, i in enumerate(diff_idx):
            full[i] = diffs[j]
        res = fn(*full)
        return res if isinstance(res, tuple) else (res,)

    outs, vjp_fn = jax.vjp(g, *diff_arrays)
    if _nan_check_enabled():
        _check_nan_inf(op_name, outs)
    out_tensors = [_wrap_out(o, stop_gradient=False) for o in outs]
    node = GradNode(op_name, vjp_fn, [inputs[i] for i in diff_idx],
                    out_tensors, fwd_fn=g)
    for t in out_tensors:
        t.grad_node = node
    if n_outputs == 1 and len(out_tensors) == 1:
        return out_tensors[0]
    return tuple(out_tensors)


# --------------------------------------------------------------------------
# backward engine
# --------------------------------------------------------------------------

def _toposort_nodes(roots: Sequence[GradNode]):
    """Reachable nodes + per-node pending-consumer counts."""
    pending = {}  # node -> number of consuming edges from reachable nodes
    visited = set()
    stack = list(roots)
    nodes = []
    while stack:
        node = stack.pop()
        if id(node) in visited or node.released:
            continue
        visited.add(id(node))
        nodes.append(node)
        for inp in node.inputs:
            parent = inp.grad_node
            if parent is not None and not parent.released:
                pending[id(parent)] = pending.get(id(parent), 0) + 1
                stack.append(parent)
    return nodes, pending


def run_backward(tensors: Sequence[Tensor], grad_tensors=None,
                 retain_graph=False, capture=None, write_leaf_grad=True):
    """``loss.backward()`` — queue-based walk mirroring egr::RunBackward.

    ``capture``: optional dict; if given, grads for tensors whose id() is a
    key are stored there (used by ``paddle.grad`` for non-leaf inputs) and
    ``.grad`` is still written for leaves.
    """
    _backward_walk(tensors, grad_tensors, retain_graph=retain_graph,
                   capture=capture, write_leaf_grad=write_leaf_grad,
                   create_graph=False)


def _run_backward_create_graph(tensors, grad_tensors=None, capture=None,
                               write_leaf_grad=True):
    """create_graph=True backward: the same queue walk, but every grad is
    a RECORDED Tensor. Each node's pullback is re-expressed as
    ``jax.vjp(node.fwd_fn, *inputs)`` applied through ``apply_jax`` — a
    tape op differentiable in (inputs, upstream grads), which is what
    grad-of-grad needs (reference: ``egr::RunBackward`` with
    ``create_graph`` + generated double-grad nodes)."""
    _backward_walk(tensors, grad_tensors, retain_graph=True,
                   capture=capture, write_leaf_grad=write_leaf_grad,
                   create_graph=True)


def _apply_node_grads(node, out_grads, create_graph):
    """One node's pullback in the chosen grad representation."""
    if not create_graph:
        return node.vjp_fn(tuple(out_grads))
    nx = len(node.inputs)
    if node.fwd_fn is not None:
        fwd = node.fwd_fn

        def grad_fn(*args, _fwd=fwd, _nx=nx):
            xs, gs = args[:_nx], args[_nx:]
            _, vjp = jax.vjp(_fwd, *xs)
            return vjp(tuple(gs))
        res = apply_jax(node.op_name + "_grad", grad_fn,
                        *node.inputs, *out_grads, n_outputs=nx)
        return res if isinstance(res, tuple) else (res,)
    # custom node (PyLayer) without a re-linearizable forward: grads
    # are correct but constant w.r.t. further differentiation
    raw = node.vjp_fn(tuple(as_jax(g) for g in out_grads))
    return tuple(None if g is None else _wrap_out(g) for g in raw)


def _backward_walk(tensors, grad_tensors, *, retain_graph, capture,
                   write_leaf_grad, create_graph):
    """The ONE queue-based backward walk. ``create_graph`` switches the
    grad representation: raw arrays + saved vjp closures (fast path) vs
    recorded Tensors + re-linearized pullbacks (differentiable grads).
    Everything else — seeding, toposort, hook firing, dtype casts, leaf
    writes — is shared so the two modes cannot drift."""
    grad_tensors = grad_tensors or [None] * len(tensors)
    grads: dict = {}
    keepalive: dict = {}

    if create_graph:
        to_grad = lambda g: g if isinstance(g, Tensor) \
            else _wrap_out(as_jax(g))
        ones = lambda t: _wrap_out(jnp.ones_like(t._data))
        zeros = lambda shape, dt: _wrap_out(jnp.zeros(shape, dt))
        dtype_of = lambda g: as_jax(g).dtype
        fire = lambda t, g: _wrap_out(_fire_hooks(t, as_jax(g)))
        leaf_write = _accumulate_leaf_tensor
    else:
        to_grad = as_jax
        ones = lambda t: jnp.ones_like(t._data)
        zeros = jnp.zeros
        dtype_of = lambda g: g.dtype
        fire = _fire_hooks
        leaf_write = _accumulate_leaf

    roots = []
    for t, g in zip(tensors, grad_tensors):
        if t.stop_gradient:
            raise RuntimeError(
                "backward() on a tensor with stop_gradient=True")
        if g is None:
            if t.size != 1:
                raise RuntimeError(
                    "grad must be provided for non-scalar backward()")
            g_v = ones(t)
        else:
            g_v = to_grad(g)
        prev = grads.get(id(t))
        grads[id(t)] = g_v if prev is None else prev + g_v
        keepalive[id(t)] = t
        if t.grad_node is None:
            pass    # leaf root: written once by the final loop below
        elif t.grad_node.released:
            raise RuntimeError(
                "Trying to backward through the graph a second time, but "
                "the saved intermediate results have been freed. Specify "
                "retain_graph=True the first time.")
        else:
            roots.append(t.grad_node)

    nodes, pending = _toposort_nodes(roots) if roots else ([], {})
    ready = [n for n in nodes if pending.get(id(n), 0) == 0]
    processed = set()

    while ready:
        node = ready.pop()
        if id(node) in processed:
            continue
        processed.add(id(node))
        out_grads = []
        for ref, shape, dt in zip(node.out_refs, node.out_shapes,
                                  node.out_dtypes):
            t = ref()
            g = grads.get(id(t)) if t is not None else None
            if g is None:
                g = zeros(shape, dt)
            elif t is not None and t._hooks:
                # hooks fire once on the fully-accumulated grad (all
                # consumers of this node's outputs have been processed)
                g = fire(t, g)
                grads[id(t)] = g
            if dtype_of(g) != dt:
                # mixed-precision consumers (AMP O1) accumulate f32
                # grads against bf16 outputs; the vjp wants the
                # output's dtype (under create_graph the cast is a
                # recorded op, staying differentiable)
                g = g.astype(dt)
            out_grads.append(g)
        in_grads = _apply_node_grads(node, out_grads, create_graph)
        for t, g in zip(node.inputs, in_grads):
            if g is None:
                continue
            prev = grads.get(id(t))
            grads[id(t)] = g if prev is None else prev + g
            keepalive[id(t)] = t
            parent = t.grad_node
            if parent is None:
                pass
            elif parent.released:
                raise RuntimeError(
                    "Trying to backward through the graph a second time, "
                    "but the saved intermediate results have been freed. "
                    "Specify retain_graph=True the first time.")
            else:
                pending[id(parent)] -= 1
                if pending[id(parent)] == 0:
                    ready.append(parent)
        if not retain_graph and not create_graph:
            node.release()

    # write .grad on leaves; fill capture dict for requested tensors
    for tid, t in keepalive.items():
        if t.grad_node is None and t._hooks and tid in grads:
            grads[tid] = fire(t, grads[tid])
        if capture is not None and tid in capture:
            capture[tid] = grads[tid]
        if (write_leaf_grad and t.grad_node is None
                and not t.stop_gradient):
            leaf_write(t, grads[tid])


def _fire_hooks(t: "Tensor", g_arr):
    gt = _wrap_out(g_arr)
    for hook in list(t._hooks):
        res = hook(gt)
        if res is not None:
            gt = res if isinstance(res, Tensor) else _wrap_out(as_jax(res))
    return gt._data


def _accumulate_leaf_tensor(t: "Tensor", g: "Tensor"):
    t._grad = g if t._grad is None else t._grad + g


def _accumulate_leaf(t: Tensor, g_arr):
    if t._grad is None:
        t._grad = _wrap_out(g_arr)
    else:
        t._grad = _wrap_out(t._grad._data + g_arr)




def calc_gradients(outputs, inputs, grad_outputs=None, retain_graph=None,
                   create_graph=False, allow_unused=False):
    """``paddle.grad`` — like run_backward but returns grads, doesn't
    write ``.grad``. With ``create_graph=True`` the returned grads carry
    their own tape (each pullback re-linearized through ``apply_jax``),
    so grad-of-grad / gradient penalties work (reference:
    ``python/paddle/autograd/``)."""
    outputs = outputs if isinstance(outputs, (list, tuple)) else [outputs]
    inputs = inputs if isinstance(inputs, (list, tuple)) else [inputs]
    if grad_outputs is not None and not isinstance(grad_outputs, (list, tuple)):
        grad_outputs = [grad_outputs]

    capture = {id(t): None for t in inputs}
    if create_graph:
        _run_backward_create_graph(outputs, grad_tensors=grad_outputs,
                                   capture=capture, write_leaf_grad=False)
    else:
        retain = True if retain_graph is None else retain_graph
        run_backward(outputs, grad_tensors=grad_outputs,
                     retain_graph=retain, capture=capture,
                     write_leaf_grad=False)
    results = []
    for t in inputs:
        g = capture[id(t)]
        if g is None:
            if not allow_unused:
                raise RuntimeError(
                    "One of the differentiated tensors appears unused; pass "
                    "allow_unused=True to return None for it")
            results.append(None)
        else:
            results.append(g if isinstance(g, Tensor) else _wrap_out(g))
    return results


def to_tensor(data, dtype=None, place=None, stop_gradient=True) -> Tensor:
    """``paddle.to_tensor`` parity."""
    if isinstance(data, Tensor):
        t = data.detach()
        if dtype is not None and t.dtype != dtypes.convert_dtype(dtype):
            t = t.astype(dtype)
        t.stop_gradient = stop_gradient
        return t
    return Tensor(data, dtype=dtype, place=place, stop_gradient=stop_gradient)

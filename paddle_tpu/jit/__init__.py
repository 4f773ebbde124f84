"""``paddle.jit`` — the compile path.

Reference parity: ``paddle.jit.to_static`` (SOT bytecode capture +
PIR/CINN compile — ``python/paddle/jit/``, ``paddle/cinn/``). TPU-first
replacement: the user function runs once under ``jax.jit`` tracing (Tensors
are pytree nodes, so no bytecode interception is needed) and XLA performs
the fusion CINN did. ``TrainStep`` jits the whole train step — forward,
backward, optimizer — into one XLA program with buffer donation, which is
the performance path for every benchmark config.
"""
from __future__ import annotations

import functools
import os
from typing import Any, Callable, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import (Tensor, as_jax, bump_param_version,
                              _wrap_out, functional_mode, no_grad)
from ..static import InputSpec
from .. import monitor as _monitor

# jit-tier cache observability (monitor registry): every compile-cache
# decision and every graph break is countable, with reason strings
_jit_cache_events = _monitor.counter(
    "jit_cache_events", "to_static compile-cache decisions",
    labels=("fn", "event"))
_jit_guard_invalidations = _monitor.counter(
    "jit_guard_invalidations",
    "guard snapshot changes forcing a retrace", labels=("fn", "reason"))
_jit_graph_breaks = _monitor.counter(
    "jit_graph_breaks", "to_static eager fallbacks",
    labels=("fn", "kind"))

__all__ = ["to_static", "not_to_static", "enable_to_static", "save", "load",
           "TrainStep", "ignore_module", "TranslatedLayer", "dy2static"]

_to_static_enabled = True
_JIT_CACHE_SIZE = 64    # LRU bound on per-function compiled specializations
_JIT_CACHE_WARN = 32    # warn once past this many live specializations
_GUARD_MISS = object()  # sentinel: name absent (vs a None value)


def _guarded_name_sets(code):
    """(global_names, self_attr_names) actually loaded by ``code`` —
    LOAD_GLOBAL targets, and LOAD_ATTR names whose receiver is the
    frame's ``self``. Falls back to co_names for both when dis fails."""
    import dis
    g_names, a_names = set(), set()
    try:
        prev = None
        for ins in dis.get_instructions(code):
            if ins.opname == "LOAD_GLOBAL":
                g_names.add(ins.argval)
            elif ins.opname == "LOAD_ATTR" and prev is not None \
                    and prev.opname == "LOAD_FAST" \
                    and prev.argval == "self":
                a_names.add(ins.argval)
            prev = ins
    except Exception:
        g_names = a_names = set(code.co_names)
    return g_names, a_names


def enable_to_static(flag: bool):
    global _to_static_enabled
    _to_static_enabled = bool(flag)


def ignore_module(modules):
    pass


def not_to_static(fn):
    fn._paddle_jit_ignore = True
    return fn


class _LayerBinder:
    """Swap traced arrays into a Layer's parameters/buffers for the duration
    of a traced call, and collect (possibly traced) buffer values after."""

    def __init__(self, layer):
        self.layer = layer
        self.param_items = list(layer.named_parameters())
        self.buffer_items = list(layer.named_buffers())

    def param_arrays(self):
        return [as_jax(p) for _, p in self.param_items]

    def buffer_arrays(self):
        return [as_jax(b) for _, b in self.buffer_items]

    def call(self, param_arrays, buffer_arrays, args, kwargs, fn=None):
        saved_p = [p._data for _, p in self.param_items]
        saved_b = [b._data for _, b in self.buffer_items]
        try:
            for (_, p), arr in zip(self.param_items, param_arrays):
                p._data = arr
            for (_, b), arr in zip(self.buffer_items, buffer_arrays):
                b._data = arr
            with functional_mode(), no_grad():
                out = (fn or self.layer)(*args, **kwargs)
            new_buffers = [b._data for _, b in self.buffer_items]
            return out, new_buffers
        finally:
            for (_, p), arr in zip(self.param_items, saved_p):
                p._data = arr
            for (_, b), arr in zip(self.buffer_items, saved_b):
                b._data = arr


from ..framework.core import tree_to_arrays as _tree_to_arrays
from ..framework.core import tree_to_tensors as _tree_to_tensors


class StaticFunction:
    """Result of ``to_static`` on a function or Layer method."""

    def __init__(self, fn, layer=None, input_spec=None, build_strategy=None,
                 backend=None, full_graph=True):
        self._fn = fn
        self._layer = layer
        self._input_spec = input_spec
        self._binder = _LayerBinder(layer) if layer is not None else None
        self._jitted = None
        functools.update_wrapper(self, fn)

    def _traced_fn(self):
        """Control-flow-converted callable (dy2static AST transform) or
        the original when conversion is impossible."""
        if not hasattr(self, "_conv_fn"):
            try:
                from .dy2static import convert_to_static
                self._conv_fn = convert_to_static(self._fn)
            except Exception:
                self._conv_fn = None
        return self._conv_fn or self._fn

    _GUARDABLE = (int, float, bool, str, bytes, type(None))

    def _guard_snapshot(self):
        """SOT-style guards (reference ``python/paddle/jit/sot/``
        guard-cache semantics): python-level values the trace closes
        over — closure cells, module globals the code names, and scalar
        Layer attributes — are baked into the compiled program as
        constants. Snapshotting them into the cache key makes a change
        re-trace instead of silently replaying stale constants. Only
        hashable scalars are guarded; container/object state follows the
        reference's behavior (guard on identity is out of scope — the
        dy2static graph-break report covers those)."""
        fn = self._fn
        plan = getattr(self, "_guard_plan", None)
        if plan is None:
            # one-time plan: which (kind, name) sites held a guardable
            # scalar at first call — steady-state calls re-read only
            # those (a site that only LATER becomes a scalar is not
            # guarded; that matches SOT, which guards what the traced
            # frame actually saw)
            plan = []
            code = getattr(fn, "__code__", None)
            if code is not None:
                if getattr(fn, "__closure__", None):
                    for i, name in enumerate(code.co_freevars):
                        try:
                            v = fn.__closure__[i].cell_contents
                        except ValueError:
                            continue
                        if isinstance(v, self._GUARDABLE):
                            plan.append(("c", i, name))
                # bytecode-accurate name sets: co_names also contains
                # pure attribute names of OTHER objects; guarding on
                # those would add spurious cache-key entries and
                # avoidable retraces. Scan the actual LOAD_GLOBAL ops
                # and LOAD_ATTRs whose receiver is `self`.
                g_names, a_names = _guarded_name_sets(code)
                g = getattr(fn, "__globals__", {})
                for name in sorted(g_names):
                    if isinstance(g.get(name, _GUARD_MISS),
                                  self._GUARDABLE):
                        plan.append(("g", 0, name))
                if self._layer is not None:
                    for name in sorted(a_names):
                        try:
                            v = getattr(self._layer, name, _GUARD_MISS)
                        except Exception:
                            continue   # state-dependent property
                        if isinstance(v, self._GUARDABLE):
                            plan.append(("a", 0, name))
            self._guard_plan = plan
        out = []
        for kind, idx, name in plan:
            if kind == "c":
                try:
                    v = fn.__closure__[idx].cell_contents
                except (ValueError, IndexError):
                    continue
            elif kind == "g":
                v = fn.__globals__.get(name, _GUARD_MISS)
            else:
                try:
                    v = getattr(self._layer, name, _GUARD_MISS)
                except Exception:
                    continue
            if v is not _GUARD_MISS and isinstance(v, self._GUARDABLE):
                out.append((kind + ":" + name, v))
        return tuple(out)

    def _build(self, treedef, dyn_idx, statics):
        """jit specialized on the (treedef, static-leaf) signature —
        python scalars/strings/None stay python values during the trace
        (the reference specializes the same way), only tensors are
        traced."""
        binder = self._binder
        traced = self._traced_fn()

        def rebuild(dyn_arrays):
            flat = list(statics)
            for pos, arr in zip(dyn_idx, dyn_arrays):
                flat[pos] = _wrap_out(arr)
            return jax.tree_util.tree_unflatten(treedef, flat)

        if binder is not None:
            def pure(param_arrays, buffer_arrays, dyn_arrays):
                args, kwargs = rebuild(dyn_arrays)
                out, new_buffers = binder.call(param_arrays, buffer_arrays,
                                               args, kwargs, fn=traced)
                return _tree_to_arrays(out), new_buffers
        else:
            def pure(param_arrays, buffer_arrays, dyn_arrays):
                args, kwargs = rebuild(dyn_arrays)
                from ..framework.core import capture_buffer_writes
                # no binder to thread buffer updates: roll back any
                # functional buffer writes (BN stats, QAT averages) so
                # tracers never leak into persistent state
                with functional_mode(), no_grad(), \
                        capture_buffer_writes():
                    out = traced(*args, **kwargs)
                return _tree_to_arrays(out), []
        return jax.jit(pure)

    @staticmethod
    def _partition(args, kwargs):
        """Flatten (args, kwargs) stopping at Tensors; split leaves into
        traced arrays (tensors) and static python values."""
        flat, treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
        dyn_idx, dyn_arrays, statics = [], [], []
        for i, leaf in enumerate(flat):
            if isinstance(leaf, (Tensor, jax.Array, np.ndarray)):
                dyn_idx.append(i)
                dyn_arrays.append(as_jax(leaf))
                statics.append(None)        # placeholder
            else:
                statics.append(leaf)
        return treedef, tuple(dyn_idx), statics, dyn_arrays

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled or getattr(self, "_fallback", False):
            return self._fn(*args, **kwargs)
        treedef, dyn_idx, statics, dyn_arrays = self._partition(args,
                                                                kwargs)
        guards = self._guard_snapshot()
        if getattr(self, "_last_guards", None) != guards:
            # a guarded python value changed: the dy2static-converted
            # callable baked the OLD cell contents into its rebuilt
            # globals — drop it so conversion re-runs against the
            # current values (the compile-cache key below changes too)
            prev = getattr(self, "_last_guards", None)
            if prev is not None:
                prev_d, cur_d = dict(prev), dict(guards)
                changed = sorted(
                    k for k in set(prev_d) | set(cur_d)
                    if prev_d.get(k, _GUARD_MISS)
                    != cur_d.get(k, _GUARD_MISS))
                _jit_guard_invalidations.labels(
                    fn=getattr(self._fn, "__name__", "?"),
                    reason=",".join(changed[:4]) or "?").inc()
            self._last_guards = guards
            self.__dict__.pop("_conv_fn", None)
        try:
            key = (treedef, dyn_idx,
                   tuple((i, s) for i, s in enumerate(statics)
                         if i not in dyn_idx),
                   guards)
            hash(key)
        except TypeError:
            # an unhashable non-tensor arg cannot key the compile cache;
            # re-jitting every call would silently pay full compilation
            # per invocation — run eagerly instead (with a warning)
            import warnings
            _jit_graph_breaks.labels(
                fn=getattr(self._fn, "__name__", "?"),
                kind="unhashable_arg").inc()
            if not getattr(self, "_unhashable_warned", False):
                warnings.warn(
                    f"to_static: {getattr(self._fn, '__name__', '?')} "
                    "received an unhashable non-tensor argument; running "
                    "eagerly (cannot cache a compiled program for it)")
                self._unhashable_warned = True
            return self._fn(*args, **kwargs)
        if self._jitted is None:
            from collections import OrderedDict
            self._jitted = OrderedDict()
        jitted = self._jitted.get(key)
        fn_label = getattr(self._fn, "__name__", "?")
        if jitted is None:
            _jit_cache_events.labels(fn=fn_label, event="miss").inc()
            if self._jitted:
                # a prior specialization exists: this miss is a
                # RE-specialization (guard change / new arg signature),
                # the event worth alerting on vs a cold first compile
                _jit_cache_events.labels(fn=fn_label,
                                         event="recompile").inc()
            jitted = self._build(treedef, dyn_idx, statics)
            self._jitted[key] = jitted
            if len(self._jitted) > _JIT_CACHE_SIZE:
                self._jitted.popitem(last=False)   # LRU-bounded
            if (len(self._jitted) > _JIT_CACHE_WARN
                    and not getattr(self, "_cache_growth_warned", False)):
                self._cache_growth_warned = True
                import warnings
                warnings.warn(
                    f"to_static: {getattr(self._fn, '__name__', '?')} has "
                    f"compiled {len(self._jitted)} specializations — a "
                    "python scalar/string argument is varying per call; "
                    "each distinct value costs a full recompile. Pass it "
                    "as a Tensor to trace it instead.")
        else:
            self._jitted.move_to_end(key)
            _jit_cache_events.labels(fn=fn_label, event="hit").inc()
        if self._binder is not None:
            p = self._binder.param_arrays()
            b = self._binder.buffer_arrays()
        else:
            p, b = [], []
        if key not in getattr(self, "_accounted", ()) \
                and _monitor.metrics_enabled():
            # per-specialization cost accounting (opt-in: it pays one
            # extra trace). The jaxpr census is exact; FLOPs come from
            # the pre-compile lowering's cost model when available.
            self._accounted = getattr(self, "_accounted", set())
            self._accounted.add(key)
            try:
                traced = jitted.trace(p, b, dyn_arrays)
                lowered = traced.lower()
                _monitor.record_compiled_step(
                    f"jit:{fn_label}", jaxpr=traced.jaxpr,
                    compiled=lowered
                    if hasattr(lowered, "cost_analysis") else None)
            except Exception:
                pass
        try:
            out, new_buffers = jitted(p, b, dyn_arrays)
        except (jax.errors.TracerBoolConversionError,
                jax.errors.TracerArrayConversionError,
                jax.errors.TracerIntegerConversionError,
                jax.errors.ConcretizationTypeError) as exc:
            return self._graph_break(exc, type(exc).__name__, args, kwargs)
        except Exception as exc:
            from .dy2static import Dy2StUnsupported
            if isinstance(exc, Dy2StUnsupported) or isinstance(
                    getattr(exc, "__cause__", None), Dy2StUnsupported):
                reason = exc if isinstance(exc, Dy2StUnsupported) \
                    else exc.__cause__
                return self._graph_break(reason, "Dy2StUnsupported",
                                         args, kwargs)
            raise
        if self._binder is not None:
            for (_, buf), arr in zip(self._binder.buffer_items, new_buffers):
                buf._data = arr
        return _tree_to_tensors(out)

    def _graph_break(self, exc, kind, args, kwargs):
        # graph break (reference: jit/sot graph-break fallback): part of
        # the function is genuinely untraceable even after the dy2static
        # conversion — record a per-break report entry and run eagerly
        # from now on instead of crashing.
        import warnings
        from . import dy2static as _d2s
        name = getattr(self._fn, "__name__", str(self._fn))
        _jit_graph_breaks.labels(fn=name, kind=kind).inc()
        _d2s.record_break(name, 0, f"{kind}: {exc}")
        breaks = [b for b in _d2s.graph_break_report()
                  if b["function"].split(".")[-1] == name.split(".")[-1]]
        detail = "; ".join(f"line {b['lineno']}: {b['reason']}"
                           for b in breaks[-3:])
        warnings.warn(
            f"to_static: {name} is not fully traceable; falling back "
            f"to eager execution. Graph breaks: {detail or kind}. "
            "See paddle.jit.dy2static.graph_break_report() for details.")
        self._fallback = True
        return self._fn(*args, **kwargs)

    # paddle API surface
    @property
    def forward(self):
        return self

    def concrete_program_specify_input_spec(self, *a, **k):
        return None


def to_static(function=None, input_spec=None, build_strategy=None,
              backend=None, full_graph=True, **kwargs):
    """``paddle.jit.to_static`` — wrap a Layer or function for XLA
    compile. ``full_graph=True`` (default) uses the AST/dy2static tier
    (whole-function jit with converted control flow);
    ``full_graph=False`` uses the SOT bytecode-capture tier
    (``jit/sot/``): sub-graph compilation with graph-break fallback
    mid-function, matching the reference's default mode."""

    def decorate(obj):
        from ..nn.layer.layers import Layer
        if not full_graph:
            from .sot import symbolic_translate
            if input_spec is not None:
                import warnings
                warnings.warn(
                    "to_static(full_graph=False): input_spec is an "
                    "AOT-export concept and is ignored by the SOT "
                    "bytecode tier (shapes are guarded per call)")
            if isinstance(obj, Layer):
                obj.forward = symbolic_translate(obj.forward)
                return obj
            return symbolic_translate(obj)
        if isinstance(obj, Layer):
            static_fwd = StaticFunction(obj.forward, layer=obj,
                                        input_spec=input_spec)
            obj.forward = static_fwd
            return obj
        return StaticFunction(obj, layer=None, input_spec=input_spec)

    if function is not None:
        return decorate(function)
    return decorate


_TRAIN_STEP_SEQ = [0]


class TrainStep:
    """Whole-train-step compilation: loss, grads, clip, optimizer update in
    one donated XLA program. This is the structural replacement for the
    reference's fused optimizer + CINN path and the entry point used by
    ``paddle.Model.fit`` and ``chip_smoke.py``'s train phase.

    The first call compiles through the AOT path (trace → lower →
    compile) and the executable is REUSED for every later call with the
    same input signature, so the compiled-step accounting —
    ``cost_analysis()`` FLOPs/bytes, ``memory_analysis()`` peak HBM,
    and the jaxpr collective census — costs no extra compilation.
    ``paddle_tpu.monitor.step_report(step.telemetry_name)`` serves the
    report; a signature change (new batch shape) drops back to the
    caching ``jax.jit`` path, counted as a fallback recompile."""

    def __init__(self, layer, loss_fn, optimizer, donate=None):
        self.layer = layer
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.binder = _LayerBinder(layer)
        self._jitted = None
        self._compiled = None
        self._state_keys: List[List[str]] = []
        if donate is None:
            from ..base_flags import get_flag
            donate = bool(get_flag("FLAGS_paddle_tpu_donate_buffers"))
        self._donate = donate
        _TRAIN_STEP_SEQ[0] += 1
        self.telemetry_name = (
            f"train_step:{type(layer).__name__}:{_TRAIN_STEP_SEQ[0]}")

    def _layer_caller(self):
        """Callable for the traced forward: the layer through its hooks,
        with a dy2static-converted forward when one is available (so
        data-dependent python control flow compiles inside the whole-step
        jit instead of erroring)."""
        layer = self.layer
        fwd = layer.__dict__.get("forward", None)
        base = getattr(fwd, "_fn", fwd)       # unwrap StaticFunction
        if base is None:
            base = type(layer).forward.__get__(layer, type(layer))
        conv = None
        try:
            from .dy2static import convert_to_static
            conv = convert_to_static(base)
        except Exception:
            conv = None
        if conv is None and fwd is None:
            return None                       # plain path: call the layer
        from .dy2static.convert_operators import _patched_layer_call
        return _patched_layer_call(layer, conv or base)

    # -- optimizer state as a pytree -----------------------------------
    def _init_opt_state(self):
        states = []
        self._state_keys = []
        for _, p in self.binder.param_items:
            s = self.optimizer._state_for(p)
            keys = sorted(s.keys())
            self._state_keys.append(keys)
            states.append([s[k] for k in keys])
        return states

    def _write_back_state(self, states):
        for (_, p), keys, vals in zip(self.binder.param_items,
                                      self._state_keys, states):
            self.optimizer._write_state_dict(p, dict(zip(keys, vals)))

    def _build(self, out_shardings=None):
        binder = self.binder
        loss_fn = self.loss_fn
        opt = self.optimizer
        fwd_fn = self._layer_caller()
        # a param is updated only if it requires grad AND the optimizer
        # was given it — paddle semantics: AdamW(parameters=[subset])
        # freezes everything outside the subset
        opt_ids = set()
        for entry in getattr(opt, "_parameter_list", []):
            if isinstance(entry, dict):       # param-group style
                opt_ids.update(id(p) for p in entry.get("params", []))
            else:
                opt_ids.add(id(entry))
        trainable = [not p.stop_gradient and (not opt_ids or id(p) in opt_ids)
                     for _, p in binder.param_items]

        def step(param_arrays, opt_states, buffer_arrays, lr, base_key,
                 step_idx, batch):
            from ..framework.random import set_functional_key
            # fold the step counter in HERE (inside the compiled step):
            # a host-side jax.random.fold_in is a separate tiny device
            # program dispatched every step; inside the jit it fuses to
            # nothing
            rng_key = jax.random.fold_in(base_key, step_idx)

            def loss_of(train_params):
                set_functional_key(rng_key)
                full = []
                ti = 0
                for i, is_t in enumerate(trainable):
                    if is_t:
                        full.append(train_params[ti])
                        ti += 1
                    else:
                        full.append(param_arrays[i])
                args, kwargs = batch
                kwargs = dict(kwargs)
                labels = kwargs.pop("_labels", ())
                try:
                    out, new_buffers = binder.call(full, buffer_arrays,
                                                   args, kwargs, fn=fwd_fn)
                    loss = loss_fn(out, args, {"_labels": labels, **kwargs})
                finally:
                    set_functional_key(None)
                loss_arr = as_jax(loss) if isinstance(loss, Tensor) \
                    else loss
                return loss_arr, new_buffers

            train_params = [a for a, t in zip(param_arrays, trainable) if t]
            (loss, new_buffers), grads = jax.value_and_grad(
                loss_of, has_aux=True)(train_params)

            # ZeRO stage-2: constrain grads to the sharding axis so XLA
            # reduce-scatters them and updates on local shards
            if getattr(opt, "_shard_grads", False):
                from ..distributed.sharding import constrain_grad_shards
                t_objs = [p for (_, p), t in zip(binder.param_items,
                                                 trainable) if t]
                grads = constrain_grad_shards(grads, params=t_objs)

            # grad clip (operates on Tensor pairs — pure jnp inside)
            if opt._grad_clip is not None:
                pairs = [( _wrap_out(p), _wrap_out(g))
                         for p, g in zip(train_params, grads)]
                pairs = opt._grad_clip(pairs)
                grads = [as_jax(g) for _, g in pairs]

            new_params = []
            new_states = []
            ti = 0
            for i, (keys, st) in enumerate(zip(self._state_keys,
                                               opt_states)):
                p_arr = param_arrays[i]
                if not trainable[i]:
                    new_params.append(p_arr)
                    new_states.append(st)
                    continue
                g = opt._apply_decay(_wrap_out(p_arr), grads[ti])
                ti += 1
                state = dict(zip(keys, st))
                opt._current_param = binder.param_items[i][1] \
                    if hasattr(opt, "_current_param") else None
                p_new, s_new = opt._update_rule(p_arr, g, state, lr)
                new_params.append(p_new)
                new_states.append([s_new.get(k, state[k]) for k in keys])
            return loss, new_params, new_states, new_buffers

        donate = (0, 1, 2) if self._donate else ()
        pinned = {} if out_shardings is None \
            else {"out_shardings": out_shardings}
        return jax.jit(step, donate_argnums=donate, **pinned)

    def _aot_compile(self, call_args):
        """AOT-compile the step for this input signature and record the
        cost/memory accounting + collective census. The executable is
        kept for reuse, so accounting costs no second compile. A trace
        or compile error propagates: retrying through ``jax.jit`` would
        only compile the same program again to raise the same error."""
        traced = self._jitted.trace(*call_args)
        compiled = traced.lower().compile()
        self._compiled = compiled
        _monitor.counter(
            "train_step_compiles", "TrainStep AOT compilations",
            labels=("step",)).labels(step=self.telemetry_name).inc()
        try:
            _monitor.record_compiled_step(
                self.telemetry_name, jaxpr=traced.jaxpr,
                compiled=compiled)
        except Exception:
            pass          # accounting must never sink the train step

    def _commit_state_to_mesh(self):
        """Under a fleet mesh, put every piece of the step's carried
        state that is not already laid out on that mesh — parameters
        without a ``dist_spec`` (norm weights), scalar optimizer
        accumulators, buffers, the RNG key — onto it, replicated, and
        return the ``out_shardings`` that hand the state back in the
        layout it came in (``None`` without a mesh). Left as
        single-device arrays, that state reaches the partitioned
        program with no sharding at all: its placement is then the
        partitioner's guess, and XLA:TPU's scheduler trips over it on a
        sharding x mp mesh (RET_CHECK hlo_schedule.cc "not scheduled
        after its control predecessor" — the first hybrid step on four
        chips, PR 21; the same program with its state committed
        compiles). Pinning the outputs makes the state a fixed point
        of the step, so donated buffers alias and step 2 sees exactly
        the shardings step 1 compiled for."""
        from jax.sharding import NamedSharding, PartitionSpec
        from ..distributed import env as _denv
        mesh = _denv.get_mesh()
        if mesh is None or mesh.size == 1:
            return None
        replicated = NamedSharding(mesh, PartitionSpec())

        def place(a):
            on_mesh = isinstance(a, jax.Array) and isinstance(
                a.sharding, NamedSharding) and a.sharding.mesh == mesh
            return a if on_mesh or not isinstance(a, jax.Array) \
                else jax.device_put(a, replicated)

        for _, t in self.binder.param_items + self.binder.buffer_items:
            t._data = place(t._data)
        self._opt_states = jax.tree_util.tree_map(place, self._opt_states)
        self._write_back_state(self._opt_states)
        self._base_key = place(self._base_key)

        def sharding_of(a):
            return a.sharding if isinstance(a, jax.Array) else None
        return (None,       # the loss: wherever XLA leaves a scalar
                [sharding_of(a) for a in self.binder.param_arrays()],
                jax.tree_util.tree_map(sharding_of, self._opt_states),
                [sharding_of(a) for a in self.binder.buffer_arrays()])

    def __call__(self, *args, **kwargs):
        first = self._jitted is None
        if first:
            self._opt_states = self._init_opt_state()
            self._base_key = jax.random.PRNGKey(
                np.random.randint(0, 2 ** 31 - 1))
            self._step_idx = 0
            self._jitted = self._build(self._commit_state_to_mesh())
        params = self.binder.param_arrays()
        buffers = self.binder.buffer_arrays()
        lr = self.optimizer.get_lr()
        step_idx = np.uint32(self._step_idx)
        self._step_idx += 1
        batch = (_tree_to_arrays(args), _tree_to_arrays(kwargs))
        call_args = (params, self._opt_states, buffers, lr,
                     self._base_key, step_idx, batch)
        if first:
            self._aot_compile(call_args)
        out = None
        if self._compiled is not None:
            try:
                out = self._compiled(*call_args)
            except TypeError:
                # input signature changed (e.g. a new batch shape — jax
                # rejects mismatched avals as TypeError BEFORE running,
                # so donated buffers are untouched): fall back to the
                # caching jit path, which recompiles per signature —
                # counted so cache churn is visible. Runtime failures
                # (OOM, XlaRuntimeError) propagate: the step may have
                # consumed its donated inputs, so re-running would mask
                # the real error with 'Array has been deleted'.
                self._compiled = None
                _monitor.counter(
                    "train_step_fallback_recompiles",
                    "signature misses off the AOT executable",
                    labels=("step",)) \
                    .labels(step=self.telemetry_name).inc()
        if out is None:
            out = self._jitted(*call_args)
        loss, new_params, new_states, new_buffers = out
        for (_, p), arr in zip(self.binder.param_items, new_params):
            p._data = arr
        for (_, b), arr in zip(self.binder.buffer_items, new_buffers):
            b._data = arr
        self._opt_states = new_states
        # keep the optimizer's own accumulator store aliased to the live
        # state (its inputs were donated), so state_dict()/save stay valid
        self._write_back_state(new_states)
        self.optimizer._step_count += 1
        bump_param_version()   # compiled caches baking params go stale
        if hasattr(self.optimizer._learning_rate, "step"):
            pass  # scheduler stepping stays caller-controlled (Paddle parity)
        _monitor.counter("train_step_calls", "TrainStep invocations",
                         labels=("step",)) \
            .labels(step=self.telemetry_name).inc()
        # HBM watermark gauges at the step boundary (no-op on backends
        # without allocator stats)
        _monitor.sample_device_memory(step=self._step_idx - 1)
        from ..framework.core import _nan_check_enabled
        if _nan_check_enabled():
            val = float(np.asarray(loss))
            if not np.isfinite(val):
                raise RuntimeError(
                    f"FLAGS_check_nan_inf: non-finite loss {val} at "
                    f"train step {self._step_idx - 1}")
        return _wrap_out(loss)


# ---------------------------------------------------------------------------
# jit.save / jit.load
# ---------------------------------------------------------------------------

def _specs_to_sds(specs):
    """InputSpecs -> ShapeDtypeStructs. None/-1 dims become jax.export
    symbolic dimensions (shared scope), so the exported StableHLO module
    accepts any size there — matching InputSpec([None, ...]) dynamic-
    batch semantics instead of silently baking batch=1."""
    import numpy as _np
    from jax import export as jexport
    scope = None
    out = []
    for si, s in enumerate(specs):
        dim_strs = []
        dynamic = False
        for di, d in enumerate(s.shape):
            if isinstance(d, str):
                # explicit symbol name: dims sharing a name unify, so
                # users control cross-input equality precisely
                dim_strs.append(d)
                dynamic = True
            elif d is None or (isinstance(d, int) and d < 0):
                # Paddle convention: dim 0 is the batch — share ONE
                # symbol across all inputs (ids [None, L] + mask
                # [None, 1, L, L] must trace together); other dynamic
                # dims stay per-(input, dim). Use string dims in the
                # InputSpec shape to override.
                dim_strs.append("_dyn_batch" if di == 0
                                else f"_dyn_{si}_{di}")
                dynamic = True
            else:
                dim_strs.append(str(int(d)))
        if dynamic:
            if scope is None:
                scope = jexport.SymbolicScope()
            shape = jexport.symbolic_shape(",".join(dim_strs),
                                           scope=scope)
        else:
            shape = tuple(int(d) for d in s.shape)
        out.append(jax.ShapeDtypeStruct(shape, _np.dtype(s.dtype)))
    return out


def save(layer, path, input_spec=None, **configs):
    """``paddle.jit.save`` parity (``python/paddle/jit/api.py``): the
    ``*.pdmodel`` graph artifact becomes a serialized jax.export
    StableHLO module — the TPU-native deployable program — alongside the
    ``*.pdparams`` state dict. The exported callable has signature
    ``(flat_params, *inputs)``."""
    from ..framework.io import save as fsave
    state = layer.state_dict() if hasattr(layer, "state_dict") else {}
    fsave(state, path + ".pdparams")
    specs = [s for s in (input_spec or []) if isinstance(s, InputSpec)]
    meta = {
        "class": type(layer).__name__,
        "input_spec": [
            {"shape": list(s.shape), "dtype": str(np.dtype(s.dtype)),
             "name": s.name}
            for s in specs
        ],
    }
    import json
    if specs and hasattr(layer, "parameters"):
        was_training = getattr(layer, "training", False)
        if hasattr(layer, "eval"):
            layer.eval()
        binder = _LayerBinder(layer)
        params = binder.param_arrays()
        buffers = binder.buffer_arrays()

        def fwd(param_arrays, *inputs):
            args = tuple(_wrap_out(x) for x in inputs)
            out, _ = binder.call(param_arrays, buffers, args, {})
            return _tree_to_arrays(out)

        from jax import export as jexport
        exported = jexport.export(jax.jit(fwd))(
            [jax.ShapeDtypeStruct(p.shape, p.dtype) for p in params],
            *_specs_to_sds(specs))
        with open(path + ".pdmodel", "wb") as f:
            f.write(exported.serialize())
        meta["param_names"] = [n for n, _ in binder.param_items]
        meta["exported"] = True
        if was_training and hasattr(layer, "train"):
            layer.train()
    with open(path + ".pdmodel.json", "w") as f:
        json.dump(meta, f)


class TranslatedLayer:
    """Loaded inference artifact (``TranslatedLayer`` parity): params +
    the deserialized AOT module; callable when the artifact was exported
    with an input_spec."""

    def __init__(self, state_dict, meta, exported=None):
        self._state_dict = state_dict
        self._meta = meta
        self._exported = exported
        names = meta.get("param_names")
        if names:
            self._flat_params = [as_jax(state_dict[n]) for n in names]
        else:
            self._flat_params = [as_jax(v) for v in state_dict.values()]

    def state_dict(self):
        return self._state_dict

    @property
    def input_spec(self):
        return self._meta.get("input_spec", [])

    def __call__(self, *args):
        if self._exported is None:
            raise RuntimeError(
                "artifact was saved without input_spec; only state_dict "
                "is available")
        arrays = [as_jax(a) if isinstance(a, Tensor)
                  else jnp.asarray(np.asarray(a)) for a in args]
        out = self._exported.call(self._flat_params, *arrays)
        return _tree_to_tensors(out)


def load(path, **configs):
    from ..framework.io import load as fload
    import json
    state = fload(path + ".pdparams")
    meta = {}
    meta_path = path + ".pdmodel.json"
    if os.path.exists(meta_path):
        with open(meta_path) as f:
            meta = json.load(f)
    exported = None
    model_path = path + ".pdmodel"
    if meta.get("exported") and os.path.exists(model_path):
        from jax import export as jexport
        with open(model_path, "rb") as f:
            exported = jexport.deserialize(f.read())
    return TranslatedLayer(state, meta, exported)


from . import dy2static  # noqa: E402  (graph-break report API)

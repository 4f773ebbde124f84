"""What every kind of run shares: the log, the clock since process
start, the device's memory, the count of compilations, the profiler's
window. Nothing here knows a model, a kind of cell or a metric."""
from __future__ import annotations

import importlib
import importlib.util
import json
import os
import shutil
import sys
import time

T_START = time.monotonic()      # run.py imports this before anything heavy

PKG = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TREE = PKG      # where data files are looked up; the tests point it at
#                 a tree of their own


def read_json(*parts):
    with open(os.path.join(TREE, *parts)) as f:
        return json.load(f)


def find(dotted):
    """``benchmark/<dotted>.py`` as a module, found by its name in a data
    file: ``kinds.serve``, ``models.qwen2``, ``lib.readers``."""
    path = os.path.join(TREE, *dotted.split(".")) + ".py"
    if TREE == PKG or not os.path.exists(path):
        return importlib.import_module("benchmark." + dotted)
    name = "benchmark_tree." + dotted       # a file of the tests' own tree
    mod = sys.modules.get(name)
    if mod is None or mod.__file__ != path:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = sys.modules[name] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
    return mod


def find_function(ref):
    """``"lib.readers:span_ms"`` -> the function."""
    dotted, _, fn = ref.partition(":")
    return getattr(find(dotted), fn)


def since_start():
    return time.monotonic() - T_START


def log(**fields):
    """One JSON line per observation on standard output, before the
    result line (the driver reads only the last)."""
    print(json.dumps(fields, default=str), flush=True)


def memory(key):
    """``peak_bytes_in_use`` or ``bytes_in_use`` on the fullest chip."""
    import jax
    return max(int((d.memory_stats() or {}).get(key, 0))
               for d in jax.local_devices())


class CompileCount:
    """Programs JAX built or fetched from its cache, from its own
    events; the difference over the window says how many fell inside."""

    def __init__(self):
        import jax.monitoring
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, _duration, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


class Tracer:
    """Starts the profiler ``start_s`` into the window and stops it
    ``seconds`` later, from the loop's own turn."""

    def __init__(self, spec, trace_dir):
        self.start_s, self.seconds = spec["start_s"], spec["seconds"]
        self.dir = trace_dir
        self.t_origin = None
        self.traced_from = None
        self.done = False

    def on_tick(self, now):
        if self.done:
            return
        if self.t_origin is None:
            self.t_origin = now
        if self.traced_from is None:
            if now - self.t_origin >= self.start_s:
                import jax
                shutil.rmtree(self.dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 2
                jax.profiler.start_trace(self.dir, profiler_options=opts)
                self.traced_from = time.monotonic()
        elif now - self.traced_from >= self.seconds:
            self.finish()

    def finish(self):
        if self.traced_from is not None and not self.done:
            import jax
            jax.profiler.stop_trace()
        self.done = True

    def reduce(self, sink):
        from . import readers, xplane
        if self.traced_from is None:
            raise RuntimeError("the window ended before the trace began")
        events = xplane.load(xplane.find_trace(self.dir), xplane.bench_lines)
        shutil.rmtree(self.dir, ignore_errors=True)
        return readers.Trace(events, sink, self.traced_from)

"""Compiled-step cost/memory accounting and collective census.

GSPMD (PAPERS.md) partitioned programs live or die by communication
placement, and XLA's cost model is how compiled-step time is attributed
to compute vs bytes — this module surfaces both from INSIDE the
framework at compile time instead of from offline trace parses:

- ``record_compiled_step``: for every ``TrainStep``/jit compile, pull
  ``compiled.cost_analysis()`` FLOPs/bytes and ``memory_analysis()``
  peak HBM into registry gauges, and walk the jaxpr for a census of
  collective ops (all_reduce/all_to_all/all_gather/... counts + payload
  bytes per mesh axis).
- ``collective_census``: the jaxpr walk itself — recurses through
  pjit/shard_map/scan/cond sub-jaxprs, so shard_map-placed collectives
  (MoE EP all-to-alls, 1F1B ppermutes, ring attention) are counted
  with their per-shard payloads. GSPMD-inferred collectives only
  materialize in HLO post-partitioning; their jaxpr-level proxy here is
  the ``sharding_constraint`` count.
- ``sample_device_memory``: HBM watermark gauges at step boundaries.
- ``analytic_mfu``: the cost-model MFU — recorded FLOPs/step over
  measured step time over the chip's peak.
"""
from __future__ import annotations

import re as _re
from typing import Any, Dict, List, Optional

import numpy as np

from .registry import get_registry

__all__ = ["record_compiled_step", "collective_census",
           "kernel_census", "component_map", "COMPONENTS",
           "step_report", "step_reports",
           "sample_device_memory", "analytic_mfu",
           "DEVICE_PEAKS", "device_peaks", "executable_cost"]

# jaxpr primitive -> census op family
_COLLECTIVE_PRIMS = {
    "psum": "all_reduce",
    "pmax": "all_reduce",
    "pmin": "all_reduce",
    "all_to_all": "all_to_all",
    "all_gather": "all_gather",
    "ppermute": "ppermute",
    "pshuffle": "ppermute",
    "psum_scatter": "reduce_scatter",
    "reduce_scatter": "reduce_scatter",
}

_STEP_REPORTS: Dict[str, dict] = {}


def _walk_jaxpr(jaxpr, visit):
    """Depth-first over every eqn including sub-jaxprs hidden in params
    (pjit ``jaxpr``, shard_map ``jaxpr``, scan/while bodies, cond
    ``branches``, custom_vjp ``call_jaxpr``...)."""
    core = getattr(jaxpr, "jaxpr", jaxpr)     # ClosedJaxpr -> Jaxpr
    for eqn in getattr(core, "eqns", ()):
        visit(eqn)
        for v in eqn.params.values():
            vs = v if isinstance(v, (list, tuple)) else (v,)
            for e in vs:
                inner = getattr(e, "jaxpr", e)
                if hasattr(inner, "eqns"):
                    _walk_jaxpr(e, visit)


def _payload_bytes(eqn) -> int:
    total = 0
    for var in eqn.invars:
        aval = getattr(var, "aval", None)
        shape = getattr(aval, "shape", None)
        dtype = getattr(aval, "dtype", None)
        if shape is None or dtype is None:
            continue
        try:
            total += int(np.prod(shape)) * np.dtype(dtype).itemsize
        except Exception:
            pass
    return total


def _axis_label(eqn) -> str:
    ax = eqn.params.get("axis_name", eqn.params.get("axes", ()))
    if not isinstance(ax, (list, tuple)):
        ax = (ax,)
    names = [str(a) for a in ax if isinstance(a, (str,))]
    return ",".join(names) or "?"


def collective_census(jaxpr) -> List[dict]:
    """[{op, axis, count, bytes}] over the whole (closed) jaxpr,
    including sub-jaxprs, plus one ``sharding_constraint`` row when
    GSPMD annotations are present (their collectives are inserted by
    the SPMD partitioner and only visible in HLO)."""
    agg: Dict[tuple, List[int]] = {}
    n_constraint = [0]

    def visit(eqn):
        name = eqn.primitive.name
        fam = _COLLECTIVE_PRIMS.get(name)
        if fam is not None:
            key = (fam, _axis_label(eqn))
            cnt_b = agg.setdefault(key, [0, 0])
            cnt_b[0] += 1
            cnt_b[1] += _payload_bytes(eqn)
        elif name == "sharding_constraint":
            n_constraint[0] += 1

    _walk_jaxpr(jaxpr, visit)
    out = [{"op": op, "axis": axis, "count": c, "bytes": b}
           for (op, axis), (c, b) in sorted(agg.items())]
    if n_constraint[0]:
        out.append({"op": "sharding_constraint", "axis": "",
                    "count": n_constraint[0], "bytes": 0})
    return out


# HLO entry-computation instructions that are bookkeeping, not kernel
# thunks — everything else in the optimized entry is (approximately)
# one launch on the target backend
_HLO_SKIP_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                 "bitcast", "after-all", "add-dependency", "domain",
                 "partition-id", "replica-id"}

# jaxpr primitives that root a kernel launch regardless of backend:
# matmuls/convs (MXU), Pallas custom calls, and the data-movement /
# reduction ops XLA cannot fuse into a neighbor. Elementwise chains
# count 0 — XLA fuses them into these roots — so this is a LOWER-bound
# launch proxy that is stable across backends (the interpret-mode
# pallas_call stays ONE equation here even though its CPU emulation
# expands in HLO, which is exactly what makes the fused-decode
# collapse measurable on a CPU census).
_LAUNCH_PRIMS = {"dot_general", "conv_general_dilated", "pallas_call",
                 "sort", "gather", "scatter", "scatter-add",
                 "scatter-mul", "scatter-min", "scatter-max",
                 "argmax", "argmin", "top_k", "while", "fori"}

_HLO_ENTRY_RE = _re.compile(r"^ENTRY [^\n]*\{\n(.*?)^\}",
                            _re.S | _re.M)
_HLO_INSTR_RE = _re.compile(
    r"\s+(?:ROOT\s+)?[%\w\.\-]+ = (?:\([^=]*?\)|\S+) "
    r"([a-zA-Z][\w\-]*)\(")
_HLO_OP_NAME_RE = _re.compile(r'op_name="([^"]+)"')
_HLO_TRANSFORM_RE = _re.compile(r"\w+\((.*)\)")   # jvp(...), transpose(...)


def _kernel_scope(op_name: str) -> str:
    """The ``kernel_scope`` a Mosaic call was invoked under: the path
    segment before its ``pallas_call``, with the transformations it was
    traced under taken off (``transpose(jvp(<scope>))`` for a backward
    kernel); ``?`` where there is none."""
    parts = op_name.split("/")
    calls = [i for i, p in enumerate(parts) if p.startswith("pallas_call")]
    name = parts[calls[-1] - 1] if calls and calls[-1] > 0 else ""
    while (w := _HLO_TRANSFORM_RE.fullmatch(name)) is not None:
        name = w.group(1)
    return name or "?"


def _mosaic_kernels(hlo_text: str) -> Dict[str, int]:
    """The Mosaic (Pallas) kernels a COMPILED program holds, in every
    computation (loop and shard_map bodies included): ``{name: count}``
    over its ``tpu_custom_call`` instructions. XLA keeps no kernel name
    on the instruction; what survives is the op metadata, whose
    ``op_name`` ends ``.../<scope>/pallas_call`` — ``<scope>`` being the
    ``jax.named_scope`` every kernel of ``ops/pallas`` is invoked under
    (``kernel_scope``), wrapped by the transformations it was traced
    under (``transpose(jvp(<scope>))`` for a backward kernel). A call
    with no scope of its own counts under the enclosing component."""
    names: Dict[str, int] = {}
    for line in hlo_text.splitlines():
        if _MOSAIC_TARGET not in line:
            continue
        m = _HLO_OP_NAME_RE.search(line)
        name = _kernel_scope(m.group(1) if m else "")
        names[name] = names.get(name, 0) + 1
    return dict(sorted(names.items()))


# -- which part of the model an instruction came from -------------------------
#
# The taxonomy of components, defined here ONCE (tabled in docs/OPS.md
# "Components of the tick executable"). A model's paged path enters them
# as ``framework.core.component(name)`` scopes while an engine traces an
# executable; ``component_map`` reads them back out of the compiled
# program's op metadata, so a device event (named by its instruction)
# joins the part of the model it belongs to.
COMPONENTS = (
    "embed",         # token ids -> rows of the embedding table
    "norm",          # the layer norms on the residual stream, final norm
    "mixer.in",      # q | k | v, in_proj, low-rank decay and gate products
    "mixer.glue",    # rope, QK-norm, gating, silu and split, chunk slabs,
    #                  the query tiles around an attention kernel
    "mixer.out",     # o_proj / out_proj (and what XLA fuses into it)
    "ffn",           # dense FFN and shared expert
    "moe.gate",      # router logits, sort, top-k, pair ranks
    "moe.dispatch",  # rows gathered into the expert-major pair buffer
    "moe.experts",   # the grouped matmuls where no kernel takes them,
    #                  and the activation between the two
    "moe.combine",   # pairs gathered back, weighted and summed
    "cache",         # pool and slot-state writes, relayouts, table gathers
    "head",          # the LM head
    "sample",        # last-row gather, filtering, token choice, health probe
    "tick.io",       # carry, packed operands, outputs, routing counters
)
_LAYER_RE = _re.compile(r"L(\d+)\.(\w+)")
_MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_HLO_COMMENT_RE = _re.compile(r"/\*.*?\*/")
_HLO_LAYOUT_RE = _re.compile(r"\{[^{}]*\}")
_HLO_NAME_RE = _re.compile(r"\s+(ROOT\s+)?%?([\w\.\-]+) = ")
_HLO_OPCODE_RE = _re.compile(r" ?([a-zA-Z][\w\-]*)\(")
_HLO_CALLEE_RE = _re.compile(
    r"(?:calls|to_apply|body|condition)=%?([\w\.\-]+)"
    r"|(?:branch_computations|called_computations)=\{([^}]*)\}")
_HLO_ARRAY_RE = _re.compile(r"([a-z]+\d*)\[([\d,]*)\]")
# data movement the compiler inserts with no metadata of its own: named
# ``copy`` where it carries no scope, never ``unnamed``
_HLO_MOVE_OPS = {"copy", "bitcast-convert", "slice", "dynamic-slice",
                 "transpose", "reshape", "concatenate", "pad", "broadcast",
                 "iota"}
# ... and the custom calls that only re-assemble such movement (async
# slices of one array joined again)
_HLO_MOVE_TARGETS = ('custom_call_target="ConcatBitcast"',)
_HLO_CONTROL_OPS = {"while", "conditional", "call"}
_ASYNC_SUFFIXES = ("-start", "-done", "-update")


def _parse_instruction(line: str):
    """``(name, is_root, result shape, opcode, rest of the line)`` of one
    HLO instruction line, or None. The result shape is the type as
    printed without layouts, comments or spaces; a tuple type may hold
    ``/*index=5*/`` comments and nested parentheses."""
    m = _HLO_NAME_RE.match(line)
    if m is None:
        return None
    at = m.end()
    if line.startswith("(", at):
        depth, end = 0, at
        for end in range(at, len(line)):
            c = line[end]
            if c == "(":
                depth += 1
            elif c == ")":
                depth -= 1
                if not depth:
                    break
        end += 1
    else:
        end = line.find(" ", at)
        if end < 0:
            return None
    om = _HLO_OPCODE_RE.match(line, end)
    if om is None:
        return None
    shape = _HLO_LAYOUT_RE.sub("", _HLO_COMMENT_RE.sub("", line[at:end]))
    return (m.group(2), m.group(1) is not None, shape.replace(" ", ""),
            om.group(1), line[om.end():])


def _shape_bytes(shape: str) -> int:
    """Bytes of every array in a result shape (``bf16[352,4096]``)."""
    total = 0
    for dtype, dims in _HLO_ARRAY_RE.findall(shape):
        bits = "".join(c for c in dtype if c.isdigit())
        n = 1
        for d in dims.split(","):
            n *= int(d) if d else 1
        total += n * (max(int(bits), 8) if bits else 8) // 8
    return total


def _path_component(op_name: str):
    """``(component, layer)`` of one op-metadata path: its INNERMOST
    taxonomy segment and its innermost ``L<n>.<kind>`` segment, each
    None where the path holds none."""
    comp = layer = None
    for seg in op_name.split("/"):
        while (w := _HLO_TRANSFORM_RE.fullmatch(seg)) is not None:
            seg = w.group(1)
        if seg in COMPONENTS:
            comp = seg
        elif _LAYER_RE.fullmatch(seg):
            layer = seg
    return comp, layer


def _base_opcode(op: str) -> str:
    for suffix in _ASYNC_SUFFIXES:
        if op.endswith(suffix):
            return op[:-len(suffix)]
    return op


def component_map(hlo_text: str) -> List[dict]:
    """Which component of the model each instruction of a compiled
    program's ENTRY computation came from: one ``{name, opcode, shape,
    bytes, component, layer, also}`` for every instruction that is not
    bookkeeping (``_HLO_SKIP_OPS``), in program order; after them the
    instructions of the computations ENTRY runs through control flow
    (``while``, ``conditional``, ``call``), which a device executes as
    events of their own.

    - ``name``: the instruction's name as a device trace spells it (no
      ``%``); ``shape``: its result type without layouts; ``bytes``: of
      that result; ``opcode``: an async pair (``copy-start`` /
      ``copy-done``) under its one base name.
    - ``component``: from the scope path (op metadata ``op_name``) of
      the instruction's HEAVIEST member — the ``convolution`` / ``dot``
      of a fused (or otherwise called) computation where it holds one,
      else the called computation's root, else the instruction itself,
      else the ONE component its members carry where they all agree; a
      Mosaic call is ``kernel:<kernel_scope>``; a ``-done`` takes its
      ``-start``'s; bare data movement with no scope is ``copy``; and
      an instruction whose path holds no taxonomy segment, or whose
      members disagree, is ``unnamed`` — nothing is chosen among
      candidates (``also`` still says what its body holds).
    - ``layer``: the innermost ``L<n>.<kind>`` segment of the same path
      (``L3.kda``), or None.
    - ``also``: the OTHER components found in the bodies of the
      computations the instruction calls (what else XLA fused in)."""
    comps: Dict[str, list] = {}     # computation -> parsed instructions
    entry = current = None
    for line in hlo_text.splitlines():
        if not line:
            continue
        if not line[0].isspace():
            if line.endswith("{"):
                head = line.split(None, 2)
                is_entry = head[0] == "ENTRY"
                current = head[1 if is_entry else 0].lstrip("%")
                comps[current] = []
                if is_entry:
                    entry = current
            continue
        if current is None:
            continue
        ins = _parse_instruction(line)
        if ins is not None:
            comps[current].append(ins)
    if entry is None:
        return []

    def op_name(rest):
        m = _HLO_OP_NAME_RE.search(rest)
        return m.group(1) if m else ""

    def callees(rest):
        out = []
        for one, many in _HLO_CALLEE_RE.findall(rest):
            out += [one] if one else \
                [c.strip().lstrip("%") for c in many.split(",")]
        return [c for c in out if c in comps]

    members_of: Dict[str, list] = {}

    def members(comp):
        """Every instruction of ``comp`` and of what it calls."""
        if comp not in members_of:
            members_of[comp] = out = []
            for ins in comps[comp]:
                out.append(ins)
                for c in callees(ins[4]):
                    out += members(c)
        return members_of[comp]

    rows, by_name = [], {}
    # the ENTRY computation, then what it runs through control flow (a
    # ``while``'s body and condition, a ``conditional``'s branches, a
    # ``call``): their instructions are device events of their own
    todo, seen = [entry], {entry}
    program = []
    while todo:
        comp_name = todo.pop(0)
        for ins in comps[comp_name]:
            program.append(ins)
            if ins[3] in _HLO_CONTROL_OPS:
                for c in callees(ins[4]):
                    if c not in seen:
                        seen.add(c)
                        todo.append(c)
    for name, _root, shape, op, rest in program:
        if op in _HLO_SKIP_OPS:
            continue
        called = [] if op in _HLO_CONTROL_OPS else callees(rest)
        inner = [m for c in called for m in members(c)]
        heavy = next((m for m in inner
                      if m[3] in ("convolution", "dot")), None)
        if heavy is None and called and op == "fusion":
            heavy = next((m for m in comps[called[0]] if m[1]), None)
        comp = layer = None
        for path in ((op_name(heavy[4]),) if heavy else ()) \
                + (op_name(rest),):
            c, l = _path_component(path)
            comp, layer = comp or c, layer or l
        if _MOSAIC_TARGET in rest:
            comp = "kernel:" + _kernel_scope(op_name(rest))
        base = _base_opcode(op)
        if comp is None and base != op and op.endswith("-done"):
            first = rest.split(")", 1)[0].split(",")[0].strip().lstrip("%")
            start = by_name.get(first)
            if start is not None:
                comp, layer = start["component"], layer or start["layer"]
        paths = {_path_component(op_name(m[4])) for m in inner}
        also = {c for c, _l in paths if c is not None}
        if comp is None and len(also) == 1:
            # its root and its own path name nothing (a multi-output
            # fusion's tuple; a rewrite that kept no metadata), and the
            # members that carry a component all carry this one
            (comp,) = also
            inner_layers = {l for c, l in paths if c is not None}
            if layer is None and len(inner_layers) == 1:
                (layer,) = inner_layers
        if comp is None:
            # (a fusion or an ``async-start`` whose body only moves data
            # is movement too)
            moves = base in _HLO_MOVE_OPS \
                or any(t in rest for t in _HLO_MOVE_TARGETS) \
                or (inner and all(m[3] in _HLO_MOVE_OPS
                                  or m[3] in _HLO_SKIP_OPS for m in inner))
            comp = "copy" if moves else "unnamed"
        row = {"name": name, "opcode": base, "shape": shape,
               "bytes": _shape_bytes(shape), "component": comp,
               "layer": layer, "also": sorted(also - {comp})}
        rows.append(row)
        by_name[name] = row
    return rows


def kernel_census(compiled=None, jaxpr=None) -> dict:
    """Kernel-count census of one executable (ISSUE 13 — the
    machinery behind ``ServingEngine.stats()['kernels_per_tick']`` and
    the ``serving_kernels_per_tick`` gauge, so "kernel count per
    decode layer down" is measured, not asserted). Three views, the
    first two from ONE read of ``compiled.as_text()``:

    - ``hlo_kernels`` (+ ``hlo_fusions``/``hlo_custom_calls``/
      ``hlo_by_op``): instructions of the optimized HLO ENTRY
      computation, excluding pure bookkeeping — each is approximately
      one kernel thunk on the compiling backend.
      ``hlo_mosaic_kernels`` counts the Mosaic custom calls of EVERY
      computation by kernel scope name — on a TPU, the Pallas kernels
      that actually compiled in (empty for an interpreted or
      XLA-fallback graph).
    - ``hlo_components`` (``component_map``): for each of those ENTRY
      instructions, the component of the model it came from
      (``COMPONENTS``), its layer, and what else XLA fused into it —
      the join from a device trace's events to the model.
    - ``launch_proxy`` (+ ``launch_by_op``): a jaxpr walk (the PR 2
      collective-census machinery, same recursion through
      pjit/scan/while/shard_map bodies) counting launch-rooted
      primitives. Backend-independent: a ``pallas_call`` is ONE entry
      whether it will run as a real TPU kernel or under the
      interpreter, so a CPU census of the fused decode tick shows the
      same collapse the TPU compile gets.

    A TPU run should trust the first two: they describe the program
    the chip executes, instruction for instruction, and
    ``hlo_components`` names every event of its trace. On the CPU
    backend they describe the CPU's program (other fusions, no Mosaic
    call): good for counts and for the taxonomy's coverage, not for
    what a chip will run. Either input may be omitted; its view is
    then absent."""
    out = {}
    if jaxpr is not None:
        n = [0]
        by: Dict[str, int] = {}

        def walk(jx):
            core = getattr(jx, "jaxpr", jx)     # ClosedJaxpr -> Jaxpr
            for eqn in getattr(core, "eqns", ()):
                name = eqn.primitive.name
                if name in _LAUNCH_PRIMS or name.startswith("reduce_") \
                        or name.startswith("cum"):
                    n[0] += 1
                    by[name] = by.get(name, 0) + 1
                if name == "pallas_call":
                    # ONE launch — its body's ops run INSIDE the
                    # kernel, never as separate thunks (recursing
                    # there would double-count the very boundaries
                    # the fusion removed)
                    continue
                for v in eqn.params.values():
                    vs = v if isinstance(v, (list, tuple)) else (v,)
                    for e in vs:
                        inner = getattr(e, "jaxpr", e)
                        if hasattr(inner, "eqns"):
                            walk(e)

        walk(jaxpr)
        out["launch_proxy"] = n[0]
        out["launch_by_op"] = dict(sorted(by.items()))
    if compiled is not None:
        txt = compiled.as_text()
        m = _HLO_ENTRY_RE.search(txt)
        body = m.group(1) if m else ""
        by = {}
        for line in body.splitlines():
            im = _HLO_INSTR_RE.match(line)
            if im is None:
                continue
            op = im.group(1)
            if op in _HLO_SKIP_OPS:
                continue
            by[op] = by.get(op, 0) + 1
        out["hlo_kernels"] = sum(by.values())
        out["hlo_fusions"] = by.get("fusion", 0)
        out["hlo_custom_calls"] = by.get("custom-call", 0)
        out["hlo_by_op"] = dict(sorted(by.items()))
        out["hlo_mosaic_kernels"] = _mosaic_kernels(txt)
        out["hlo_components"] = component_map(txt)
    return out


def _cost_dict(compiled) -> dict:
    """Normalized ``cost_analysis()``: {'flops': f, 'bytes_accessed': b}
    across jax versions (dict vs list-of-dict per program)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out = {}
    if "flops" in ca:
        out["flops"] = float(ca["flops"])
    if "bytes accessed" in ca:
        out["bytes_accessed"] = float(ca["bytes accessed"])
    return out


def _memory_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    if ma is None:
        return {}
    fields = ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes")
    out = {}
    for f in fields:
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = int(v)
    if out:
        # peak HBM the executable pins: live arguments + temporaries +
        # the program itself (outputs alias into temp space)
        out["peak_hbm_bytes"] = (out.get("argument_size_in_bytes", 0)
                                 + out.get("temp_size_in_bytes", 0)
                                 + out.get(
                                     "generated_code_size_in_bytes", 0))
    return out


def record_compiled_step(name: str, jaxpr=None, compiled=None) -> dict:
    """Account one compiled step program under ``name``. Fills the
    step gauges + census counters and returns (and stores) the report
    dict that ``step_report(name)`` serves."""
    reg = get_registry()
    report: dict = {"step": name}
    if compiled is not None:
        cost = _cost_dict(compiled)
        mem = _memory_dict(compiled)
        report.update(cost)
        report["memory"] = mem
        if "flops" in cost:
            reg.gauge("step_flops",
                      "cost_analysis FLOPs of the compiled step",
                      labels=("step",)).labels(step=name) \
                .set(cost["flops"])
        if "bytes_accessed" in cost:
            reg.gauge("step_bytes_accessed",
                      "cost_analysis bytes accessed per step",
                      labels=("step",)).labels(step=name) \
                .set(cost["bytes_accessed"])
        if "peak_hbm_bytes" in mem:
            reg.gauge("step_peak_hbm_bytes",
                      "memory_analysis peak HBM of the compiled step",
                      labels=("step",)).labels(step=name) \
                .set(mem["peak_hbm_bytes"])
    census = collective_census(jaxpr) if jaxpr is not None else []
    report["collective_census"] = census
    cc = reg.counter("step_collectives",
                     "collective ops in the step jaxpr",
                     labels=("step", "op", "axis"))
    cb = reg.counter("step_collective_bytes",
                     "per-shard payload bytes of step collectives",
                     labels=("step", "op", "axis"))
    for row in census:
        cc.labels(step=name, op=row["op"], axis=row["axis"]) \
            .inc(row["count"])
        cb.labels(step=name, op=row["op"], axis=row["axis"]) \
            .inc(row["bytes"])
    # always-present summary keys (a zero is information: no explicit
    # collectives in this program's jaxpr)
    reg.gauge("step_collective_ops",
              "total collective-op count in the step jaxpr",
              labels=("step",)).labels(step=name).set(
        sum(r["count"] for r in census
            if r["op"] != "sharding_constraint"))
    reg.info("step_report", "full per-step accounting report",
             labels=("step",)).labels(step=name).set(report)
    _STEP_REPORTS[name] = report
    return report


def step_report(name: str) -> Optional[dict]:
    return _STEP_REPORTS.get(name)


def step_reports() -> Dict[str, dict]:
    return dict(_STEP_REPORTS)


# Published per-chip peaks, keyed by ``jax.devices()[0].device_kind``:
# kind -> (bf16 FLOP/s, HBM bytes/s, source). The engine's table
# (``benchmark/lib/peaks.py`` keeps the benchmark's own copy). A
# device that is not here is an error, not a default — a utilization
# against a guessed peak is not a measurement.
DEVICE_PEAKS = {
    "TPU v5 lite": (197e12, 819e9,
                    'Google Cloud documentation, "TPU v5e": 197 TFLOP/s '
                    "bf16, 819 GB/s HBM per chip"),
}


def device_peaks():
    """``(peak bf16 FLOP/s, peak HBM bytes/s)`` of the local chip from
    ``DEVICE_PEAKS``, or ``None`` on the CPU backend (a CPU run has no
    device peak: callers report no utilization there). An accelerator
    whose ``device_kind`` is not in the table raises ``KeyError``."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    if dev.device_kind not in DEVICE_PEAKS:
        raise KeyError(
            f"no published peak for device_kind {dev.device_kind!r} "
            f"(platform {dev.platform!r}); add it to "
            "monitor.accounting.DEVICE_PEAKS with its source")
    flops, bw, _source = DEVICE_PEAKS[dev.device_kind]
    return flops, bw


def executable_cost(compiled) -> dict:
    """XLA cost-model inputs of ONE compiled executable, merged:
    ``cost_analysis()`` FLOPs + bytes accessed plus the
    ``memory_analysis()`` fields (under ``"memory"``, incl.
    ``peak_hbm_bytes``). The static half of the per-tick roofline
    attribution — divide by a measured step time for live MFU /
    HBM-bandwidth utilization. {} when the backend exposes neither
    analysis (the caller then simply has no roofline row)."""
    out = dict(_cost_dict(compiled))
    mem = _memory_dict(compiled)
    if mem:
        out["memory"] = mem
    return out


def analytic_mfu(name: str, step_time_s: float,
                 peak_flops: Optional[float] = None) -> Optional[float]:
    """Cost-model MFU: recorded FLOPs/step over measured step time over
    chip peak. None when the step has no recorded FLOPs, or on the CPU
    backend (no device peak) unless ``peak_flops`` is given."""
    rep = _STEP_REPORTS.get(name) or {}
    flops = rep.get("flops")
    if not flops or step_time_s <= 0:
        return None
    if peak_flops is None:
        peaks = device_peaks()
        if peaks is None:
            return None
        peak_flops = peaks[0]
    return float(flops) / step_time_s / peak_flops


def sample_device_memory(step: Optional[int] = None) -> dict:
    """HBM watermark gauges from the device allocator, sampled at step
    boundaries. Returns the raw stats dict ({} where the backend has no
    allocator stats, e.g. CPU)."""
    import jax
    try:
        stats = jax.local_devices()[0].memory_stats() or {}
    except Exception:
        stats = {}
    if not stats:
        return {}
    reg = get_registry()
    keep = {"bytes_in_use": "device_bytes_in_use",
            "peak_bytes_in_use": "device_peak_bytes_in_use",
            "bytes_limit": "device_bytes_limit",
            "largest_alloc_size": "device_largest_alloc_bytes"}
    for src, gname in keep.items():
        if src in stats:
            reg.gauge(gname, "device allocator watermark",
                      labels=("device",)) \
                .labels(device="0").set(int(stats[src]))
    return stats

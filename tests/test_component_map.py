"""The component map: which part of the model each instruction of an
engine's executable came from (``monitor.accounting.component_map``).

Three layers, all on the CPU:

- the parser on hand-written HLO text (the rules of ``component``,
  ``layer`` and ``also``);
- a tiny engine of each of the four serving families: the map covers
  the executable, every taxonomy name the family enters occurs, little
  is ``unnamed``, the map outlives the engine with its tracer and
  stands in ``dump_trace()``'s file, and the scopes change nothing but
  metadata (the same executables, the same census, the same tokens as
  with no scope entered at all — the parent's program);
- ``component`` enters no scope outside an engine's trace, and
  ``apply_jax`` none at all.
"""
import collections
import contextlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.framework import core
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.monitor import accounting, tracing

META = 'metadata={op_name="jit(ragged_tick)/%s"}'


def _hlo(entry, *others):
    return "HloModule m\n\n" + "\n\n".join(others) \
        + "\n\nENTRY %main.1 (p0: f32[8,16]) -> f32[8,16] {\n" \
        + "  %p0 = f32[8,16]{1,0} parameter(0)\n" + entry + "\n}\n"


FUSED = """%fused_computation.3 (a: bf16[8,16], b: bf16[16,16]) -> (f32[8], bf16[8,16]) {
  %a = bf16[8,16]{1,0} parameter(0)
  %b = bf16[16,16]{1,0} parameter(1)
  %convolution.1 = bf16[8,16]{1,0} convolution(%a, %b), dim_labels=bf_io->bf, """ \
    + META % "L3.kda/kda_paged/mixer.out/dot_general" + """
  %mul.1 = f32[8,16]{1,0} multiply(%convolution.1, %convolution.1), """ \
    + META % "L3.kda/norm/rms_norm/mul" + """
  %reduce.1 = f32[8]{0} reduce(%mul.1, %c), dimensions={1}, to_apply=%region_1.2, """ \
    + META % "L3.kda/norm/rms_norm/reduce_sum" + """
  ROOT %tuple.9 = (f32[8]{0}, bf16[8,16]{1,0}) tuple(%reduce.1, %convolution.1)
}

%region_1.2 (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.3 = f32[] add(%x, %y)
}"""

AGREED = """%fused_computation.8 (a: f32[8,16]) -> (f32[8,16], f32[8,16]) {
  %a = f32[8,16]{1,0} parameter(0)
  %sub.1 = f32[8,16]{1,0} subtract(%a, %a), """ \
    + META % "L0.mla/mla_attention_paged/mixer.glue/sub" + """
  %add.1 = f32[8,16]{1,0} add(%a, %a), """ \
    + META % "L0.mla/mla_attention_paged/mixer.glue/add" + """
  ROOT %tuple.8 = (f32[8,16]{1,0}, f32[8,16]{1,0}) tuple(%sub.1, %add.1)
}"""

CASES = {
    # a fused convolution under mixer.out with the next norm's reduce
    "fused": (
        "  %fusion.469 = (f32[8]{0:T(128)}, /*index=1*/bf16[8,16]{1,0:T(8,128)(2,1)}) "
        "fusion(%p0, %p0), kind=kOutput, calls=%fused_computation.3",
        dict(name="fusion.469", opcode="fusion",
             shape="(f32[8],bf16[8,16])", bytes=8 * 4 + 8 * 16 * 2,
             component="mixer.out", layer="L3.kda", also=["norm"])),
    # a Mosaic call is the kernel's, whatever encloses it
    "mosaic": (
        "  %ragged_paged_attention.10 = bf16[137,4,16,128]{3,2,1,0} "
        'custom-call(%p0), custom_call_target="tpu_custom_call", '
        + META % ("L0.gqa/solar_gated_attention_paged/mixer.glue/"
                  "ragged_paged_attention/pallas_call"),
        dict(name="ragged_paged_attention.10", opcode="custom-call",
             component="kernel:ragged_paged_attention", layer="L0.gqa",
             also=[])),
    # a path with no taxonomy segment is unnamed, never guessed
    "unnamed": (
        "  %fusion.7 = f32[8,16]{1,0} fusion(%p0), kind=kLoop, "
        "calls=%fused_computation.9, " + META % "L1.kda/kda_paged/mul",
        dict(name="fusion.7", component="unnamed", layer="L1.kda")),
    # bare data movement with no scope is `copy`; with one, the scope's
    "copy": (
        "  %copy.5 = f32[8,16]{0,1} copy(%p0)",
        dict(name="copy.5", opcode="copy", component="copy", layer=None)),
    "copy_scoped": (
        "  %copy.6 = f32[8,16]{0,1} copy(%p0), "
        + META % "L2.conv/short_conv_paged/mixer.glue/cache/transpose",
        dict(name="copy.6", component="cache", layer="L2.conv")),
    # root and own path name nothing, the members agree: theirs
    "agreed": (
        "  %fusion.8 = (f32[8,16]{1,0}, f32[8,16]{1,0}) fusion(%p0), "
        "kind=kLoop, calls=%fused_computation.8",
        dict(name="fusion.8", component="mixer.glue", layer="L0.mla",
             also=[])),
    # bookkeeping has no row
    "bookkeeping": (
        "  %bitcast.1 = f32[128]{0} bitcast(%p0)\n"
        "  %tuple.4 = (f32[8,16]{1,0}) tuple(%p0)\n"
        "  %get-tuple-element.2 = f32[8,16]{1,0} get-tuple-element(%tuple.4), index=0",
        None),
    # the innermost taxonomy segment wins
    "innermost": (
        "  %gather.3 = bf16[8,16]{1,0} gather(%p0, %p0), "
        + META % "L0.mla/mla_attention_paged/mixer.glue/cache/gather",
        dict(name="gather.3", opcode="gather", component="cache",
             layer="L0.mla")),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_parser_names_one_instruction(case):
    line, want = CASES[case]
    other = FUSED if case == "fused" else AGREED if case == "agreed" else \
        "%fused_computation.9 (a: f32[8,16]) -> f32[8,16] {\n" \
        "  %a = f32[8,16]{1,0} parameter(0)\n" \
        "  ROOT %mul.2 = f32[8,16]{1,0} multiply(%a, %a), " \
        + META % "L1.kda/kda_paged/mul" + "\n}"
    rows = accounting.component_map(_hlo(line, other))
    if want is None:
        assert rows == []
        return
    (row,) = rows
    assert set(row) == {"name", "opcode", "shape", "bytes", "component",
                        "layer", "also"}
    assert {k: row[k] for k in want} == want


def test_parser_counts_an_async_pair_under_one_name():
    rows = accounting.component_map(_hlo(
        "  %copy-start.2 = (bf16[8,16]{1,0}, bf16[8,16]{1,0}, u32[]) "
        "copy-start(%p0), " + META % "L4.gqa/x/mixer.in/dot_general" + "\n"
        "  %copy-done.2 = bf16[8,16]{1,0} copy-done(%copy-start.2)\n"
        "  %slice-start.1 = ((f32[8,16]{1,0}), f32[2,16]{1,0}, s32[]) "
        "slice-start(%p0), slice={[0:2], [0:16]}\n"
        "  %slice-done.1 = f32[2,16]{1,0} slice-done(%slice-start.1)\n"
        # the generic wrapper, as the chip's compiler prints LFM2's
        "  %slice-start.7 = ((f32[8,16]{1,0}), f32[2,16]{1,0}, s32[]) "
        "async-start(%p0), calls=%async_slice.7\n"
        "  %slice-done.7 = f32[2,16]{1,0} async-done(%slice-start.7)",
        "%async_slice.7 (a: f32[8,16]) -> f32[2,16] {\n"
        "  %a = f32[8,16]{1,0} parameter(0)\n"
        "  ROOT %slice.9 = f32[2,16]{1,0} slice(%a), slice={[0:2], [0:16]}\n}"))
    assert [(r["name"], r["opcode"], r["component"], r["layer"])
            for r in rows] == [
        ("copy-start.2", "copy", "mixer.in", "L4.gqa"),
        ("copy-done.2", "copy", "mixer.in", "L4.gqa"),
        ("slice-start.1", "slice", "copy", None),
        ("slice-done.1", "slice", "copy", None),
        ("slice-start.7", "async", "copy", None),
        ("slice-done.7", "async", "copy", None)]


def test_parser_follows_control_flow_and_chooses_among_no_candidates():
    body = """%body.1 (s: (f32[8,16])) -> (f32[8,16]) {
  %s = (f32[8,16]{1,0}) parameter(0)
  %g = f32[8,16]{1,0} get-tuple-element(%s), index=0
  %fusion.12 = f32[8,16]{1,0} fusion(%g), kind=kLoop, calls=%fused_computation.12
  ROOT %t = (f32[8,16]{1,0}) tuple(%fusion.12)
}

%cond.1 (s: (f32[8,16])) -> pred[] {
  %s = (f32[8,16]{1,0}) parameter(0)
  ROOT %lt.1 = pred[] compare(%s, %s), direction=LT, """ \
        + META % "L0.gqa/moe.experts/gmm/lt" + """
}

%fused_computation.12 (a: f32[8,16]) -> f32[8,16] {
  %a = f32[8,16]{1,0} parameter(0)
  %small.1 = f32[8]{0} reduce(%a, %a), dimensions={1}, """ \
        + META % "L0.gqa/norm/reduce_sum" + """
  %wide.1 = f32[8,16]{1,0} exponential(%a), """ \
        + META % "L0.gqa/x/mixer.glue/exp" + """
  ROOT %copy.1 = f32[8,16]{1,0} copy(%wide.1)
}"""
    rows = accounting.component_map(_hlo(
        "  %while.3 = (f32[8,16]{1,0}) while(%p0), condition=%cond.1, "
        "body=%body.1, " + META % "L0.gqa/moe.experts/gmm/while", body))
    got = {r["name"]: (r["component"], r["also"]) for r in rows}
    assert got == {"while.3": ("moe.experts", []),
                   # its root carries no path and neither does it:
                   # the body's two scopes are listed, none is chosen
                   "fusion.12": ("unnamed", ["mixer.glue", "norm"]),
                   "lt.1": ("moe.experts", [])}


def test_census_reads_the_compiled_text_once():
    """``kernel_census`` builds the map from the one ``as_text()`` read:
    a row for every ENTRY instruction it counts (it counts none whose
    tuple type holds an ``/*index=5*/`` comment, and goes on counting
    so: ``kernels_per_tick`` is the parent's)."""
    class Compiled:
        reads = 0

        def as_text(self):
            self.reads += 1
            return _hlo("\n".join(CASES[c][0] for c in
                                  ("fused", "copy", "innermost")), FUSED)

    compiled = Compiled()
    census = accounting.kernel_census(compiled=compiled)
    assert compiled.reads == 1
    assert census["hlo_by_op"] == {"copy": 1, "gather": 1}
    assert [r["opcode"] for r in census["hlo_components"]] \
        == ["fusion", "copy", "gather"]


def test_every_scope_in_the_source_is_in_the_taxonomy():
    root = os.path.dirname(paddle.__file__)
    used = set()
    for folder, _dirs, files in os.walk(root):
        for fn in files:
            if fn.endswith(".py"):
                with open(os.path.join(folder, fn)) as f:
                    used |= set(re.findall(
                        r'\bcomponent\(\s*"([^"]+)"', f.read()))
    assert used and used <= set(accounting.COMPONENTS)
    # every name of the taxonomy is entered somewhere
    assert used == set(accounting.COMPONENTS)


# -- tiny engines of the four families ----------------------------------------

def _model(family):
    paddle.seed(11)
    if family == "llama":
        from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
        return LlamaForCausalLM(LlamaConfig.tiny())
    if family == "deepseek_v3":
        from paddle_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                                   DeepseekV3ForCausalLM)
        return DeepseekV3ForCausalLM(DeepseekV3Config.tiny())
    if family == "lfm2_moe":
        from paddle_tpu.models.lfm2_moe import (Lfm2MoeConfig,
                                                Lfm2MoeForCausalLM)
        return Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny())
    from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                               SolarOpen2ForCausalLM)
    return SolarOpen2ForCausalLM(SolarOpen2Config.tiny(layers=4))


MOE = {"moe.gate", "moe.dispatch", "moe.experts", "moe.combine"}
COMMON = {"embed", "norm", "mixer.in", "mixer.glue", "mixer.out", "cache",
          "head", "sample", "tick.io"}
USES = {"llama": COMMON | {"ffn"},
        "deepseek_v3": COMMON | MOE | {"ffn"},
        "lfm2_moe": COMMON | MOE | {"ffn"},
        "solar_open2": COMMON | MOE | {"ffn"}}
KINDS = {"llama": {"attn"}, "deepseek_v3": {"mla"},
         "lfm2_moe": {"conv", "full_attention"},
         "solar_open2": {"gqa", "kda"}}
FAMILIES = sorted(USES)


def _serve(family, model=None):
    engine = ServingEngine(
        model or _model(family),
        ServingConfig(num_slots=4, max_model_len=128, block_size=16,
                      prefill_chunk=32, host_kv_tier_bytes=0))
    engine.submit(np.arange(1, 50), max_new_tokens=4)
    engine.submit(np.arange(3, 12), max_new_tokens=3)
    tokens = {rid: [int(t) for t in toks]
              for rid, toks in engine.run().items()}
    return engine, tokens


@pytest.fixture(scope="module", params=FAMILIES)
def served(request):
    family = request.param
    model = _model(family)
    engine, tokens = _serve(family, model)
    yield family, model, engine, tokens
    engine.shutdown(check_leaks=False)


def test_map_covers_the_executable_and_names_it(served):
    family, _model_, engine, _tokens = served
    cmap, census = engine.component_map(), engine.kernel_census()
    assert set(cmap) == set(census) and "decode" in cmap
    assert all("hlo_components" not in c for c in census.values())
    for name, rows in cmap.items():
        # an entry for every instruction the census counts, by opcode
        by_op = collections.Counter(r["opcode"] for r in rows)
        for op, n in census[name]["hlo_by_op"].items():
            assert by_op[op] >= n, (name, op)
        assert len(rows) >= census[name]["hlo_kernels"]
    rows = cmap["decode"]
    named = {r["component"] for r in rows} \
        | {c for r in rows for c in r["also"]}
    assert USES[family] <= named
    assert named <= set(accounting.COMPONENTS) | {"copy", "unnamed"}
    unnamed = sum(r["component"] == "unnamed" for r in rows)
    assert unnamed < 0.05 * len(rows), (unnamed, len(rows))
    kinds = {r["layer"].partition(".")[2] for r in rows if r["layer"]}
    assert kinds == KINDS[family]
    layers = {int(r["layer"].partition(".")[0][1:])
              for r in rows if r["layer"]}
    assert layers == set(range(len(layers)))
    # the other executables move cache and nothing else
    for name, other in cmap.items():
        if name != "decode":
            assert {r["component"] for r in other} <= {"cache", "copy"}


def test_map_outlives_the_engine_and_stands_in_the_dump(tmp_path):
    engine, _tokens = _serve("lfm2_moe")
    cmap = engine.component_map()
    tracer = engine.tracer
    path = engine.dump_trace(str(tmp_path / "trace.json"))
    engine.shutdown(check_leaks=False)
    del engine
    assert tracer in tracing.live_tracers()
    assert tracer.annotations()["component_map"] == cmap
    # outside the ring: no event of it, and clear() leaves it
    assert all(e["name"] != "component_map" for e in tracer.events())
    with open(path) as f:
        records = json.load(f)["traceEvents"]
    (meta,) = [r for r in records if r["name"] == "component_map"]
    assert meta["ph"] == "M" and meta["args"] == json.loads(
        json.dumps(cmap))


def _counts(engine):
    stats = engine.stats()
    return (stats["executables_compiled"], stats["kernels_per_tick"],
            {k: (v["hlo_kernels"], v["hlo_by_op"], v["launch_proxy"])
             for k, v in engine.kernel_census().items()})


def test_scopes_and_tracing_change_nothing_but_metadata(served, monkeypatch):
    """The same model served (a) as the parent served it, with no scope
    entered at all, and (b) with ``PADDLE_TPU_TRACE=0``: the same
    tokens, executables and census as the default; without a tracer the
    map still answers."""
    family, model, engine, tokens = served
    with monkeypatch.context() as m:
        m.setattr("paddle_tpu.inference.serving.executable_scopes",
                  contextlib.nullcontext)
        bare, bare_tokens = _serve(family, model)
    # (the tick alone: jax keeps the trace of ``jit(export_slot_state)``
    # and its like from one engine to the next, scopes included)
    assert all(r["component"] in ("unnamed", "copy")
               for r in bare.component_map()["decode"])
    monkeypatch.setenv("PADDLE_TPU_TRACE", "0")
    off, off_tokens = _serve(family, model)
    assert off.tracer is None and off.dump_trace("/nowhere") is None
    assert off.component_map().keys() == engine.component_map().keys()
    assert [len(v) for v in off.component_map().values()] \
        == [len(v) for v in engine.component_map().values()]
    assert tokens == bare_tokens == off_tokens
    assert _counts(engine) == _counts(bare) == _counts(off)
    for other in (bare, off):
        other.shutdown(check_leaks=False)


def test_no_scope_is_entered_outside_an_engines_trace():
    def op(x):
        return core.apply_jax("my_named_op", lambda a: a * 2 + 1, x)

    def text():
        def traced(a):      # a new function: jax keeps no trace of it
            with core.component("mixer.glue"), core.component("L3.kda"):
                return core.as_jax(op(a))

        return jax.jit(traced).lower(jnp.ones((4,))).as_text(
            debug_info=True)

    assert core._executable_scopes == 0
    assert isinstance(core.component("norm"), contextlib.nullcontext)
    plain = text()
    assert "my_named_op" not in plain and "mixer.glue" not in plain
    with core.executable_scopes():
        assert core._executable_scopes == 1
        scoped = text()
    assert core._executable_scopes == 0
    # the component scopes, and no scope of ``apply_jax``'s own
    assert "mixer.glue/L3.kda/" in scoped and "my_named_op" not in scoped
    # eager: one global read, the result unchanged
    np.testing.assert_array_equal(
        np.asarray(core.as_jax(op(paddle.to_tensor([1.0, 2.0])))),
        [3.0, 5.0])


_CACHE_SCRIPT = """
import contextlib, sys
import jax
jax.config.update("jax_compilation_cache_dir", sys.argv[1])
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
sys.path[:0] = sys.argv[2:4]
import test_component_map as t
import paddle_tpu.inference.serving as serving
model = t._model("llama")
scopes = serving.executable_scopes
serving.executable_scopes = contextlib.nullcontext     # the parent's program
bare, _ = t._serve("llama", model)
serving.executable_scopes = scopes
engine, _ = t._serve("llama", model)
print(sorted({r["component"] for r in bare.component_map()["decode"]}))
print(sorted({r["component"] for r in engine.component_map()["decode"]}))
"""


def test_a_cache_filled_without_scopes_is_not_read_back(tmp_path):
    """JAX's persistent compile cache leaves metadata out of its key:
    an executable cached by a program without the scopes must not come
    back as this program's (its text would name nothing)."""
    import subprocess
    import sys
    here = os.path.dirname(os.path.abspath(__file__))
    out = subprocess.run(
        [sys.executable, "-c", _CACHE_SCRIPT, str(tmp_path / "cache"),
         here, os.path.dirname(here)],
        capture_output=True, text=True, timeout=600,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-2000:]
    bare, scoped = (eval(line) for line in out.stdout.splitlines()[-2:])
    assert set(bare) <= {"copy", "unnamed"}
    assert {"mixer.in", "mixer.out", "ffn", "head", "cache"} <= set(scoped)

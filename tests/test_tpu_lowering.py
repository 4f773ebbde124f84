"""Compile every Pallas entry point for a TPU v5e — on the CPU.

Two strengths of the same check, strongest available first:

- **AOT compile.** ``jax.experimental.topologies`` describes a v5e slice
  from the installed libtpu with no chip attached, and ``jit(f).lower(
  abstract args placed on it).compile()`` then runs the WHOLE TPU
  compiler — XLA and Mosaic — here. It reproduces the chip's verdicts to
  the letter (the moe_gmm kernels fail with the same "Slice shape along
  dimension 0 must be aligned to tiling" the first chip run of PR 21
  printed), so a kernel Mosaic refuses fails in tier-1, not on the chip.
- **Cross-lowering**, where no topology can be built:
  ``jit(f).trace(*args).lower(lowering_platforms=("tpu",))`` still runs
  the Pallas -> Mosaic lowering and its block-shape checks.

Two sweeps:

- every kernel case of ``chip_smoke.py`` at its production shape (the
  shapes the smoke then runs on the chip and compares with XLA mirrors);
- every shape the eligibility predicates (``paged_attention.
  _kernel_eligible``, ``decode_fused._eligible``) admit across pool
  dtypes, KV-head counts, head dims and biased projections must come out
  as a ``tpu_custom_call`` — the predicates may not admit what the
  compiler rejects.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import chip_smoke
from paddle_tpu.ops import paged_cache as pc
from paddle_tpu.ops.pallas import decode_fused as df
from paddle_tpu.ops.pallas import flash_attention_kernel as fak
from paddle_tpu.ops.pallas import flashmask_kernel as fmk
from paddle_tpu.ops.pallas import paged_attention as pa


@functools.cache
def _v5e_placement():
    """A replicated sharding on one device of a described (not attached)
    v5e slice, or None where libtpu cannot describe one."""
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception:       # no libtpu here: cross-lowering still runs
        return None
    return NamedSharding(Mesh(np.array(topo.devices[:1]), ("x",)),
                         PartitionSpec())


def _tpu_text(fn, *args):
    """The program ``fn(*args)`` becomes for a TPU: compiled HLO where a
    v5e can be described, lowered StableHLO otherwise."""
    placement = _v5e_placement()
    if placement is None:
        return jax.jit(fn).trace(*args).lower(
            lowering_platforms=("tpu",)).as_text()
    abstract = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                       sharding=placement), args)
    return jax.jit(fn).lower(*abstract).compile().as_text()


def _lowers_to_mosaic(fn, *args):
    return "tpu_custom_call" in _tpu_text(fn, *args)


class _CheapRng:
    """``chip_smoke`` builds its operands from a numpy Generator; only
    shapes and dtypes matter to a lowering, so normals come back as a
    zero-stride view (no 136 M-sample draws for the FFN weights)."""

    def __init__(self):
        self._rng = np.random.default_rng(0)

    def standard_normal(self, shape):
        return np.broadcast_to(np.float32(0.5), shape)

    def __getattr__(self, name):
        return getattr(self._rng, name)


@pytest.fixture
def compiled_kernels(monkeypatch):
    """The flash kernels pick interpret mode from the backend; a
    cross-lowering must take the compiled path."""
    monkeypatch.setattr(fak, "_interpret", lambda: False)
    monkeypatch.setattr(fmk, "_interpret", lambda: False)


_CASES = chip_smoke.kernel_cases(True, interpret=False)
_MOSAIC = [n for n, _ in _CASES if chip_smoke.EXPECT[n][0] == "mosaic"]


@pytest.mark.parametrize("name", _MOSAIC)
def test_smoke_kernel_case_compiles_for_tpu(name, compiled_kernels):
    kern, _mirror, args = dict(_CASES)[name](_CheapRng())
    assert _lowers_to_mosaic(kern, *args), name


def test_every_expected_kernel_entry_has_a_case():
    want = {k for k in chip_smoke.EXPECT if k.startswith("kernel.")}
    assert want == {n for n, _ in _CASES}


_REFUSED = sorted(n for n, _ in _CASES if chip_smoke.EXPECT[n][0] == "xla")


@pytest.mark.parametrize("name", _REFUSED)
def test_mosaic_still_refuses_the_row_dma_kernels(name, compiled_kernels):
    """The written reason these entries are ``xla`` in the smoke's table,
    kept executable: Mosaic rejects their per-row DMA slices. The day a
    toolchain accepts them this fails, and the gates can open."""
    if _v5e_placement() is None:
        pytest.skip("needs the TPU compiler (libtpu topology)")
    kern, _mirror, args = dict(_CASES)[name](_CheapRng())
    # (a MosaicError, a private jax class, chained on a JaxRuntimeError)
    with pytest.raises(Exception, match="must be aligned to tiling"):
        _tpu_text(kern, *args)


def test_gates_route_nothing_to_kernels_mosaic_refuses(monkeypatch):
    """The moe_gmm kernels lower, then fail Mosaic's compile (per-row
    DMA slices of a tiled array, ``chip_smoke.EXPECT``): their gates must
    route nothing to them on a TPU backend, whatever the shape."""
    from paddle_tpu.distributed import moe
    from paddle_tpu.ops import lora
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for env in ("1", None):
        for var in ("PADDLE_TPU_MOE_FUSED_GMM", "PADDLE_TPU_LORA_GMM"):
            monkeypatch.delenv(var, raising=False)
            if env is not None:
                monkeypatch.setenv(var, env)
        assert moe._use_fused_gmm(8192, 2048, 1408) is False
        assert moe._use_fused_gmm(8192, 2048, 1408, fused=True) is False
        assert lora._use_lora_gmm(136, 3584, 128, 3584) is False
    assert _REFUSED == ["kernel.gather_gmm", "kernel.gather_gmm_swiglu",
                        "kernel.lora_gmm", "kernel.scatter_gmm"]


def _pools(hkv, d, bs, quant):
    data = jnp.zeros((9, bs, hkv, d), jnp.int8 if quant else jnp.bfloat16)
    if not quant:
        return data
    return pc.QuantKV(data, jnp.zeros((9, bs, hkv), jnp.float32))


@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("hkv,d", [(1, 128), (4, 128), (8, 128),
                                   (4, 64), (1, 64), (2, 256)])
def test_paged_eligibility_is_what_compiles(hkv, d, quant):
    """For every (pool dtype, Hkv, D) at the pool dtype's sublane-tile
    block size: a shape ``_kernel_eligible`` admits compiles to Mosaic
    in all three entry points (linear and tree-masked); head dim 64 is
    the shape it refuses."""
    rep, s, mb, t = 7, 2, 4, 3
    h, bs = hkv * rep, 32 if quant else 16
    pool = _pools(hkv, d, bs, quant)
    tables = jnp.zeros((s, mb), jnp.int32)
    lens = jnp.ones((s,), jnp.int32)
    ok = pa._kernel_eligible(h, d, jnp.bfloat16, pool, window=t)
    assert ok == (d % 128 == 0)
    if not ok:
        return
    q = jnp.zeros((s, t, h, d), jnp.bfloat16)
    assert _lowers_to_mosaic(
        lambda q, k, v: pa.pallas_paged_attention(
            q[:, 0], k, v, tables, lens, interpret=False), q, pool, pool)
    assert _lowers_to_mosaic(
        lambda q, k, v: pa.pallas_paged_verify_attention(
            q, k, v, tables, lens, interpret=False, tree_anc=(0, 0)),
        q, pool, pool)
    rows = jnp.zeros((s * t + 8, h, d), jnp.bfloat16)
    assert _lowers_to_mosaic(
        lambda q, k, v: pa.pallas_ragged_paged_attention(
            q, k, v, tables, lens, lens, lens, w_max=8, interpret=False,
            tree_anc=(0, 0)), rows, pool, pool)


# the default ServingConfig at the Qwen2-7B widths: 8 slots, 8 decode
# rows + one 128-row chunk, 7 query heads a kv head, a 1024-token table
_SERVING = dict(s=8, r=136, w=128, rep=7, d=128, reach=1024)
# chat-wide-sat's (LFM2-24B-A2B): 128 slots, 128 decode rows + one
# 256-row chunk, 32 query heads over 8 kv heads of 64 lanes side by
# side in a FLAT pool row (4 paired lane tiles), a 4096-token table
_WIDE = dict(s=128, r=384, w=256, rep=4, d=64, reach=4096)


def _serving_ragged_call(hkv, quant, tree, c=_SERVING, flat=False):
    """``(fn, args)``: one ragged attention call at the serving shape
    ``c`` (``flat``: over a flat ``[NB, BS, H_kv * D]`` pool)."""
    bs = 32 if quant else 16
    pool = _pools(hkv, c["d"], bs, quant)
    if flat:
        pool = pool.reshape(*pool.shape[:2], -1)
    tables = jnp.zeros((c["s"], c["reach"] // bs), jnp.int32)
    lens = jnp.ones((c["s"],), jnp.int32)
    q = jnp.zeros((c["r"], hkv * c["rep"], c["d"]), jnp.bfloat16)
    return (lambda q, k, v, tables, lens: pa.pallas_ragged_paged_attention(
        q, k, v, tables, lens, lens, lens, w_max=c["w"], interpret=False,
        tree_anc=(0, 0) if tree else None)), (q, pool, pool, tables, lens)


@pytest.mark.parametrize("tree", [False, True], ids=["linear", "tree"])
@pytest.mark.parametrize("quant", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("hkv", [4, 1, 8],
                         ids=["hkv4", "tp4_shard", "hkv8_two_groups"])
def test_ragged_serving_shape_compiles(hkv, quant, tree):
    """The shape the Qwen benchmark cells run in every layer of every
    tick (and its int8-pool, tree-verify, one-kv-head TP=4 shard and
    wider-than-``_GROUP_LANES`` variants: 8 kv heads walk two head
    groups a query tile) compiles to Mosaic: the async copies out of
    the HBM pools, the dynamic walk and the tile metadata included."""
    fn, args = _serving_ragged_call(hkv, quant, tree)
    assert _lowers_to_mosaic(fn, *args)


@pytest.mark.parametrize("tree", [False, True], ids=["linear", "tree"])
def test_ragged_wide_cell_shape_compiles(tree):
    """chat-wide-sat's call: head size 64 in pairs over a flat pool
    ``[NB, 16, 512]``, 128 slots, 384 rows — one head group of 4 lane
    tiles, a pool block one contiguous copy."""
    fn, args = _serving_ragged_call(8, False, tree, _WIDE, flat=True)
    assert _lowers_to_mosaic(fn, *args)


def _launched_grid(fn, args):
    def pallas_calls(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                yield eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from pallas_calls(sub)

    (call,) = pallas_calls(jax.make_jaxpr(fn)(*args).jaxpr)
    return tuple(call.params["grid_mapping"].grid)


@pytest.mark.parametrize("hkv,groups", [(4, 1), (1, 1), (8, 2), (32, 8)])
def test_ragged_launch_stays_far_below_the_old_grid(hkv, groups):
    """One call at the default config launches ``(query tile, head
    group)`` grid steps — a step takes every kv head of its slot's
    tile while they fit ``_GROUP_LANES``, so ``hkv`` heads are
    ``groups`` steps a tile — of at most ``n_kv`` loop iterations
    each: under 1/64 of the ``slot x window_row x kv_head x block``
    walk (262,144 steps at 4 kv heads) this kernel replaced, so that
    walk cannot grow back unnoticed."""
    c = _SERVING
    bs = 16
    grid = _launched_grid(*_serving_ragged_call(hkv, False, False))
    mb = c["reach"] // bs
    _, tq, kb, n_tiles, n_kv = pa._ragged_geometry(
        c["r"], c["s"], c["rep"], jnp.bfloat16, bs, mb)
    assert pa._head_group(hkv, c["d"]) == hkv // groups
    assert grid == (n_tiles, groups) == (c["s"] + -(-c["r"] // tq),
                                         groups)
    assert (tq, kb * bs, n_kv) == (8, 128, 8)
    assert int(np.prod(grid)) * n_kv < c["s"] * c["w"] * hkv * mb // 64


def test_ragged_wide_cell_launches_one_step_a_query_tile():
    """chat-wide-sat's call is 176 grid steps (128 slots + 384 / 8
    query tiles, one head group of the 4 paired lane tiles), where a
    step a kv head made 704."""
    c = _WIDE
    grid = _launched_grid(*_serving_ragged_call(8, False, False, c,
                                                flat=True))
    assert grid == (c["s"] + c["r"] // 8, 1) == (176, 1)


def test_paged_eligibility_wants_whole_sublane_tiles():
    """Block size per pool dtype: 16 rows of bf16, 32 of int8."""
    assert pa._kernel_eligible(28, 128, jnp.bfloat16,
                               _pools(4, 128, 16, False))
    assert not pa._kernel_eligible(28, 128, jnp.bfloat16,
                                   _pools(4, 128, 8, False))
    assert pa._kernel_eligible(28, 128, jnp.bfloat16,
                               _pools(4, 128, 32, True))
    assert not pa._kernel_eligible(28, 128, jnp.bfloat16,
                                   _pools(4, 128, 16, True))


def test_paged_eligibility_bounds_the_window():
    pool = _pools(4, 128, 16, False)
    # 64 window tokens x 16 padded rows per group fit; 128 do not
    assert pa._kernel_eligible(28, 128, jnp.bfloat16, pool, window=64)
    assert not pa._kernel_eligible(28, 128, jnp.bfloat16, pool, window=128)
    assert not pa._kernel_eligible(30, 128, jnp.bfloat16, pool)  # 30 % 4


@pytest.mark.parametrize("rows", [8, 136])
@pytest.mark.parametrize("d,widths,kdim", [
    (3584, (3584, 512, 512), 18944),      # Qwen2-7B QKV / down-proj
    (1536, (1536, 256, 256), 8960),       # Qwen2-1.5B
    (256, (384, 128), 640),
])
def test_fused_decode_eligibility_is_what_compiles(rows, d, widths, kdim):
    """Biased norm->projections and the (K-tiled) projection->residual
    at every width ``_eligible`` admits on TPU, Qwen2-7B's FFN
    included."""
    bf = jnp.bfloat16
    isz = 2
    assert df._eligible([d, *widths], rows, True,
                        df._norm_mm_vmem(rows, d, list(widths), isz))
    x, g = jnp.zeros((rows, d), bf), jnp.zeros((d,), bf)
    ws = [jnp.zeros((d, n), bf) for n in widths]
    bs = [jnp.zeros((n,), bf) for n in widths]
    assert _lowers_to_mosaic(
        lambda x, g, ws, bs: df.pallas_norm_matmul(
            x, g, g, ws, bs, eps=1e-6, kind="ln", interpret=False),
        x, g, ws, bs)
    assert df._eligible([kdim, d], rows, True,
                        df._mm_res_vmem(rows, kdim, d, 2, isz))
    xs = [jnp.zeros((rows, kdim), bf)] * 2
    assert _lowers_to_mosaic(
        lambda xs, w, b, r: df.pallas_matmul_residual(
            xs, w, b, r, act="swiglu", interpret=False),
        xs, jnp.zeros((kdim, d), bf), g, jnp.zeros((rows, d), bf))


def test_fused_decode_eligibility_refuses_what_cannot_fit():
    isz = 2
    # unaligned dims and row counts take the XLA path on TPU
    assert not df._eligible([3584, 100], 8, True, 0)
    assert not df._eligible([3584, 128], 7, True, 0)
    # a whole-row block past the VMEM budget
    assert not df._eligible(
        [16384, 128], 1024, True,
        df._norm_mm_vmem(1024, 16384, [128], isz))


# -- the component map of a whole tick, compiled for the v5e -------------------

def _tick_components(model, engine_kw, prompt_len):
    """The ``component_map`` rows of the ragged tick of an engine over
    ``model``, compiled for the described v5e (XLA:TPU and Mosaic)
    instead of this host: what ``ServingEngine._aot_compile`` would
    read on the chip; and the compiled text they were read from."""
    from unittest import mock
    from paddle_tpu.inference import ServingConfig, ServingEngine
    from paddle_tpu.monitor import accounting
    placement = _v5e_placement()
    engine = ServingEngine(model, ServingConfig(**engine_kw))
    got = {}

    class Compiled(Exception):
        pass

    def aot(name, jitted, args):
        abstract = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype,
                                           sharding=placement), args)
        with engine._trace_ctx(), \
                mock.patch.object(jax, "default_backend", lambda: "tpu"):
            text = jitted.lower(*abstract).compile().as_text()
        got[name] = accounting.component_map(text), text
        raise Compiled

    engine._aot_compile = aot
    engine.submit(np.arange(prompt_len) % 1000 + 1, max_new_tokens=2)
    with pytest.raises(Compiled):
        engine.run()
    return got["decode"]


def _cell_config(name):
    import json
    import os
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", name)) as f:
        return json.load(f)


def _reasoning_tick():
    """``reason-wide-sat``'s widths and engine at depth 2 (a gqa and a
    kda layer), two held experts of the 320-wide gate, 2,048 rows of
    vocabulary; zeros for weights (nothing is run)."""
    from benchmark.models import solar_open2 as fam
    from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                               SolarOpen2ForCausalLM)
    cfg = _cell_config("configs/solar-open2-250b.ep16.d8.json")
    keys = dict({k: cfg[k] for k in fam.CFG_KEYS}, num_hidden_layers=2,
                vocab_size=2048)
    model = SolarOpen2ForCausalLM(SolarOpen2Config(
        dtype="bfloat16", initializer_range=0.0, n_routed_experts=320,
        expert_first=0, expert_count=2, gqa_layers=(0,),
        kda_low_rank=fam.low_rank(cfg), **keys))
    cell = _cell_config(
        "workloads/reason-wide-sat.solar-open2-250b.ep16.d8.json")
    return model, dict(cell["engine"], num_blocks=512), {
        "kernel:ragged_paged_attention", "kernel:gmm",
        "kernel:short_conv_taps", "kernel:kda_chunk",
        "kernel:kda_recurrent"}, {"gqa", "kda"}, {}


def _latent_tick():
    """``longprompt-sat``'s widths and engine at depth 2 (the dense and
    an expert layer), two held experts of the 256-wide gate, 2,048 rows
    of vocabulary."""
    from benchmark.models import deepseek_v3 as fam
    from paddle_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                               DeepseekV3ForCausalLM)
    cfg = _cell_config("configs/gigachat3.1-702b.ep16.d5.json")
    keys = dict({k: cfg[k] for k in fam.CFG_KEYS}, num_hidden_layers=2,
                vocab_size=2048)
    model = DeepseekV3ForCausalLM(DeepseekV3Config(
        dtype="bfloat16", initializer_range=0.0, n_routed_experts=256,
        expert_first=0, expert_count=2, **keys))
    cell = _cell_config(
        "workloads/longprompt-sat.gigachat3.1-702b.ep16.d5.json")
    # the latent kernel's operands: six scalar-prefetched arrays (a
    # tile's slot, first row, kv reach and LIVE tokens, which choose
    # the step's rung; the tables; the lengths), the query tiles, the pool
    return model, dict(cell["engine"], num_blocks=512), {
        "kernel:ragged_latent_attention", "kernel:gmm"}, {"mla"}, {
        "ragged_latent_attention": 8}


@pytest.mark.parametrize("tick", [_reasoning_tick, _latent_tick])
def test_tick_compiled_for_tpu_is_named_by_component(tick):
    """The cell's tick at a cut-down depth and the cell's widths,
    through the TPU compiler: the Mosaic kernels are ``kernel:<scope>``
    rows, the weight products and the glue carry their components, and
    ``unnamed`` covers under 5% of the instructions' result bytes (and
    of the instructions); a kernel the tick names with an operand count
    takes that many (the latent kernel's live-token list reached it)."""
    if _v5e_placement() is None:
        pytest.skip("needs the TPU compiler (libtpu topology)")
    from paddle_tpu.monitor import accounting
    import paddle_tpu as paddle
    paddle.seed(0)
    model, engine_kw, kernels, kinds, operands = tick()
    model.eval()
    rows, text = _tick_components(model, engine_kw, 600)
    names = {r["component"] for r in rows}
    assert kernels <= names
    for scope, count in operands.items():
        calls = [line for line in text.splitlines()
                 if "custom-call(" in line and scope in line]
        assert calls and all(
            line.split("custom-call(", 1)[1]
            .split("custom_call_target", 1)[0].count("%") == count
            for line in calls), scope
    assert {"embed", "norm", "mixer.in", "mixer.glue", "mixer.out", "ffn",
            "moe.gate", "moe.dispatch", "moe.combine", "cache", "head",
            "sample", "tick.io"} <= names
    assert names <= set(accounting.COMPONENTS) | kernels \
        | {"copy", "unnamed"}
    assert {r["layer"].partition(".")[2] for r in rows if r["layer"]} \
        == kinds
    unnamed = [r for r in rows if r["component"] == "unnamed"]
    assert sum(r["bytes"] for r in unnamed) \
        < 0.05 * sum(r["bytes"] for r in rows)
    assert len(unnamed) < 0.05 * len(rows)
    # the output projection's fusion says what else XLA fused into it
    out = [r for r in rows if r["component"] == "mixer.out"
           and r["shape"].startswith("(f32[")]
    assert out and all("norm" in r["also"] for r in out)

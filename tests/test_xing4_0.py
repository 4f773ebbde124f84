"""Xing4.0 family at a small size on the CPU: the manifold-constrained
hyper-connections (four streams, Sinkhorn-projected mixing) over
DeepSeek-V3's latent attention and sigmoid experts.

The plain reference is the benchmark's (``benchmark/reference/
xing4_0.py``: float32, sequential Sinkhorn, no cache); weights are the
benchmark's seeded ones with its symmetry-breaking connections
(``benchmark/models/xing4_0.py::leaves``: ``base`` N(0, 1), ``scale``
ones). The program runs in float32 here, so program and reference agree
to float32 rounding, and each planted fault of the residual path is
caught by the same tolerance.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.lib import check, mla_moe
from benchmark.lib import xing4_0 as hcw
from benchmark.models import xing4_0 as fam
from benchmark.reference import xing4_0 as ref
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.models import xing4_0 as xing
from paddle_tpu.ops.pallas import hyper_connection as hc

SEED = 2**31 + 7
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
YARN = dict(beta_fast=32, beta_slow=1, factor=64, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=64, type="yarn")
# program (float32) against reference: float32 rounding through 3 layers
# of 4 streams; the faults below move the logits by 1e-2 and more
LOGIT_TOL = 1e-4


def tiny_cfg(layers=3):
    """A configuration file's dict: hidden 128, n = 4, 8 experts, all
    held."""
    return dict(
        model_type="xing4_0", vocab_size=512, hidden_size=128,
        intermediate_size=96, moe_intermediate_size=32,
        num_hidden_layers=layers, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=24, n_shared_experts=1, n_routed_experts=8,
        num_experts_per_tok=4, n_group=1, topk_group=1, norm_topk_prob=True,
        routed_scaling_factor=2.0, first_k_dense_replace=1,
        max_position_embeddings=8192, rms_norm_eps=1e-6, rope_theta=10000,
        rope_scaling=dict(YARN), tie_word_embeddings=False, hc_mult=4,
        hc_sinkhorn_iters=20, hc_eps=1e-6, mhc_h_res_clamp_min=-30,
        mhc_h_res_clamp_max=30, deployment=dict(expert_parallel=1, rank=0))


@pytest.fixture(scope="module")
def built():
    cfg = tiny_cfg()
    model = fam.build(cfg, SEED, False).to(dtype="float32")
    model.config.dtype = "float32"
    return cfg, model, fam.leaves(fam.leaf_shapes(cfg), SEED)


def _ref_logits(cfg, w, seq, pad_to=128):
    """The reference's logits ``[len(seq), V]`` for one sequence, a
    jitted layer at a time at one padded length."""
    small = fam._small(cfg)
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        h = ref.embed(jnp.asarray(ids), w["model.embed_tokens.weight"],
                      small)
        for i in range(cfg["num_hidden_layers"]):
            pre = f"model.layers.{i}."
            wi = {k[len(pre):]: v for k, v in w.items()
                  if k.startswith(pre)}
            h = fam._layer(h, wi, fam.ds._static(small),
                           i < cfg["first_k_dense_replace"], False)
        logits = ref.head(h, w["model.norm.weight"], w["lm_head.weight"],
                          small)
    return np.asarray(logits)[0, :len(seq)]


def _program_logits(model, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]


IDS = np.random.default_rng(0).integers(1, 512, 100)


def test_full_forward_matches_reference(built):
    cfg, model, w = built
    got = _program_logits(model, IDS)
    np.testing.assert_allclose(got, _ref_logits(cfg, w, IDS),
                               atol=LOGIT_TOL)


PROMPTS = (40, 7, 61, 23, 16)


@pytest.fixture(scope="module")
def served(built):
    """One engine run: prompts of several chunks beside decoding slots,
    through ``ServingEngine``'s ragged tick and the latent pool."""
    _cfg, model, _w = built
    rng = np.random.default_rng(1)
    engine = ServingEngine(model, ServingConfig(
        num_slots=4, max_model_len=128, block_size=16, prefill_chunk=16,
        host_kv_tier_bytes=0))
    prompts = [rng.integers(1, 512, n) for n in PROMPTS]
    rids = [engine.submit(p, max_new_tokens=6) for p in prompts]
    out = engine.run()
    stats = engine.stats()
    ticks = [e["args"] for e in engine._trace.events()
             if e["name"] == "tick" and e["tid"] == 0]
    engine.shutdown()
    return prompts, [out[r] for r in rids], stats, ticks


def test_prefill_then_decode_through_latent_pool_matches_reference(
        built, served):
    """Every served (greedy) token is the reference's best at its
    position of the reference's full forward, within float32 rounding
    of it."""
    cfg, _model, w = built
    prompts, outs, st, _ticks = served
    assert st["executables_compiled"] == 1 and st["kernel_fallbacks"] == 0
    for p, toks in zip(prompts, outs):
        assert len(toks) == 6
        logits = _ref_logits(cfg, w, np.concatenate([p, toks]))
        rows = logits[len(p) - 1:len(p) - 1 + len(toks)]
        assert check.gaps_below_best(rows, toks).max() < LOGIT_TOL


def test_tick_span_counts_hc_rows(built, served):
    """``hc_rows`` on every ``tick`` span: its live rows x 2 connections
    a layer, so the window's sum is every row the engine ran."""
    _cfg, model, _w = built
    prompts, _outs, _st, ticks = served
    assert model.hc_connections == 6
    assert all(t["hc_rows"] % 6 == 0 and t["hc_rows"] > 0 for t in ticks)
    assert sum(t["hc_rows"] for t in ticks) == 6 * (
        sum(PROMPTS) + 5 * len(PROMPTS))


# -- the kernel against its mirror ----------------------------------------------

def _operands(rows, c=256, n=4, seed=3):
    rng = np.random.default_rng(seed)
    k = hc.n_coef(n)
    bf = jnp.bfloat16
    fn = jnp.asarray(rng.standard_normal((k, n * c)) * 0.05, bf)
    base = jnp.asarray(rng.standard_normal(k), bf)
    scale = jnp.asarray(rng.uniform(0.5, 1.5, 3), bf)
    e = jnp.asarray(rng.standard_normal((rows, c)), bf)
    y = jnp.asarray(rng.standard_normal((rows, c)), bf)
    kw = dict(n=n, iters=20, eps=1e-6, clamp=(-30.0, 30.0), norm_eps=1e-6)
    x, _u, coef = hc._xla_hc_mix(None, e, None, fn, base, scale,
                                 first=True, last=False, **kw)
    return dict(first=(None, e, None, fn, base, scale),
                middle=(x, y, coef, fn, base, scale),
                last=(x, y, coef, None, None, None)), kw


@pytest.mark.parametrize("rows", [24, 128, 200])
@pytest.mark.parametrize("call", ["first", "middle", "last"])
def test_hc_kernel_matches_mirror(rows, call):
    """The Mosaic kernel under the interpreter gives its mirror's
    streams and ``u`` to a bf16 rounding and its coefficients to float32
    rounding, on a whole tile, a partial last tile and a call smaller
    than a tile, at both end calls."""
    ops, kw = _operands(rows)
    flags = dict(first=call == "first", last=call == "last")
    want = hc._xla_hc_mix(*ops[call], **flags, **kw)
    got = hc.pallas_hc_mix(*ops[call], **flags, **kw, interpret=True)
    want, got = ((w,) if call == "last" else w for w in (want, got))
    for a, b in zip(got, want):
        assert a.shape == b.shape and a.dtype == b.dtype
        coef = b.dtype == jnp.float32
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        # streams, u, the merge: two bf16 steps at their scale (the two
        # sum in other orders); the coefficients move with a stream
        # element a bf16 step apart, through fn's 0.05
        tol = 5e-3 if coef else 2 ** -6 * max(1.0, np.abs(b).max())
        np.testing.assert_allclose(a, b, atol=tol, rtol=0)


def test_sinkhorn_output_is_doubly_stochastic(built):
    """``H_res`` of every connection of the tiny model, at the seed's
    weights: rows and columns sum to 1 within 1e-3 after 20 steps."""
    cfg, model, _w = built
    seen = []
    sound = xing.hc_mix

    def spy(*a, **kw):
        out = sound(*a, **kw)
        if not kw["last"]:
            jax.debug.callback(lambda c: seen.append(
                np.asarray(c)[:, 4:].reshape(-1, 4, 4)), out[2])
        return out

    xing.hc_mix = spy
    try:
        _program_logits(model, IDS[:40])
    finally:
        xing.hc_mix = sound
    assert len(seen) == 2 * cfg["num_hidden_layers"]
    res = np.concatenate(seen)
    assert res.min() > 0
    np.testing.assert_allclose(res.sum(-1), 1.0, atol=1e-3)
    np.testing.assert_allclose(res.sum(-2), 1.0, atol=1e-3)
    # and far from the identity: the streams are mixed
    assert np.abs(res - np.eye(4)).max() > 0.5


# -- planted faults: each must fail the tolerance --------------------------------

def _res_identity(sound):
    def f(x, y, coef, *a, **kw):
        out = sound(x, y, coef, *a, **kw)
        if kw["last"]:
            return out
        eye = jnp.broadcast_to(jnp.eye(4, dtype=out[2].dtype).reshape(16),
                               (out[2].shape[0], 16))
        return out[0], out[1], out[2].at[:, 4:].set(eye)
    return f


def _sinkhorn_once(sound):
    return lambda *a, **kw: sound(*a, **dict(kw, iters=1))


def _pre_uniform(sound):
    def f(*a, **kw):
        out = sound(*a, **kw)
        if kw["last"]:
            return out
        xs = out[0].reshape(out[0].shape[0], 4, -1)
        return out[0], jnp.mean(xs, axis=1).astype(out[1].dtype), out[2]
    return f


@pytest.mark.parametrize("fault", [_res_identity, _sinkhorn_once,
                                   _pre_uniform])
def test_planted_fault_fails_the_tolerance(built, fault):
    cfg, model, w = built
    sound = xing.hc_mix
    xing.hc_mix = fault(sound)
    try:
        got = _program_logits(model, IDS)
    finally:
        xing.hc_mix = sound
    gap = np.abs(got - _ref_logits(cfg, w, IDS)).max()
    assert gap > 100 * LOGIT_TOL, gap


# -- the configuration as it is run, and its arithmetic --------------------------

def _config_file():
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "xing4.0-29b-a4b.d6.json")) as f:
        return json.load(f)


def test_published_configuration_sizes_as_the_issue_counts():
    """The cut's arithmetic from the configuration file's own leaves:
    688,182 mHC parameters a layer, 744.99 M an expert layer, 128.20 M
    the dense one, 939.52 M embedding and head: 9.59 GB in bf16."""
    cfg = _config_file()
    assert (cfg["hidden_size"], cfg["hc_mult"], cfg["n_routed_experts"],
            cfg["num_hidden_layers"]) == (3584, 4, 64, 6)
    size = {k: int(np.prod(v)) for k, v in fam.leaf_shapes(cfg).items()}
    hc_layer = sum(v for k, v in size.items()
                   if k.startswith("model.layers.1.hc_"))
    assert hc_layer == 688_182
    layer = [sum(v for k, v in size.items()
                 if k.startswith(f"model.layers.{i}."))
             for i in range(6)]
    assert round(layer[0] / 1e6, 2) == 128.20
    assert {round(x / 1e6, 2) for x in layer[1:]} == {744.99}
    total = sum(size.values())
    assert round(total / 1e6, 2) == 4792.67
    assert round(2 * total / 1e9, 2) == 9.59


def test_hc_work_counts_the_connection_whatever_implements_it():
    """Per row and connection 2 nC (2n + n^2) + 2 (n^2 + 2n) C + 20 x
    2n^2 x 4 FLOPs; ``10 C x 2`` B a boundary and ``6 C x 2`` at each
    end; one connection's leaves a call but the last."""
    cfg = _config_file()
    c = 3584
    assert hcw.hc_flops_row(cfg) == 2 * 4 * c * 24 + 2 * 24 * c + 2560
    flops, nbytes = hcw.hc_mix_work(cfg, hc_rows=12 * 1056, ticks=1)
    assert flops == 12 * 1056 * hcw.hc_flops_row(cfg)
    assert nbytes == 1056 * (11 * 10 * c + 2 * 6 * c) * 2 \
        + 12 * (24 * (4 * c + 1) + 3) * 2
    # a full tick's stream traffic: ~0.93 GB, ~1.1 ms at 819 GB/s
    assert 0.9e9 < nbytes < 0.95e9
    # the mHC's FLOPs that tick_mfu leaves out: under 1% of a row's
    # weights and its four experts (the head and the context besides)
    row = 2 * mla_moe.row_params(cfg) + 2 * 4 * 5 * mla_moe.expert_params(cfg)
    assert 12 * hcw.hc_flops_row(cfg) / row < 0.01

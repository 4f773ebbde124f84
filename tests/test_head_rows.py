"""The ragged tick's LM head runs over the rows the tick samples from.

``ServingEngine._compile_ragged_step`` hands the model step the packed
rows it samples (``logit_rows``: a slot's continuation row, and a
verifying slot's window after it), and each family's
``*ForCausalLM.forward`` gathers them from the hidden state before the
head. The path this replaced ran the head over every packed row and
took the rows from its output; the tests below rebuild that path and
hold the new one to it: the same logits to float32 rounding, the same
served tokens, and a vocabulary dot of ``S`` rows where it had ``R``.
A verify tick gathers its ``S x (gamma + 2)`` rows even where they
reach ``R``.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.ops import paged_cache as pc

FAMILIES = ("qwen2", "deepseek_v3", "xing4_0", "lfm2_moe", "solar_open2")
SLOTS = 4


def _model(family):
    paddle.seed(11)
    if family == "qwen2":
        from paddle_tpu.models.qwen2 import Qwen2Config, Qwen2ForCausalLM
        return Qwen2ForCausalLM(Qwen2Config.tiny(vocab=384))
    if family == "deepseek_v3":
        from paddle_tpu.models.deepseek_v3 import (DeepseekV3Config,
                                                   DeepseekV3ForCausalLM)
        return DeepseekV3ForCausalLM(DeepseekV3Config.tiny())
    if family == "xing4_0":
        from benchmark.models import xing4_0 as fam
        from test_xing4_0 import tiny_cfg
        model = fam.build(tiny_cfg(layers=2), 2**31 + 7, False)
        model.to(dtype="float32")
        model.config.dtype = "float32"
        return model
    if family == "lfm2_moe":
        from paddle_tpu.models.lfm2_moe import (Lfm2MoeConfig,
                                                Lfm2MoeForCausalLM)
        return Lfm2MoeForCausalLM(Lfm2MoeConfig.tiny())
    from paddle_tpu.models.solar_open2 import (SolarOpen2Config,
                                               SolarOpen2ForCausalLM)
    return SolarOpen2ForCausalLM(SolarOpen2Config.tiny(layers=4))


def _engine(model, **kw):
    cfg = dict(num_slots=SLOTS, max_model_len=128, block_size=16,
               prefill_chunk=32, host_kv_tier_bytes=0)
    cfg.update(kw)
    return ServingEngine(model, ServingConfig(**cfg))


def _head_then_take(engine):
    """Arm ``engine`` with the path the gather replaced: the head over
    every packed row, then the sampled rows taken from its output."""
    step = engine._model_step

    def whole(*a, logit_rows=None, **kw):
        logits, pools = step(*a, **kw)
        if logit_rows is not None:
            logits = jnp.take(logits, logit_rows, axis=1)
        return logits, pools

    engine._model_step = whole


def _serve(engine):
    rng = np.random.default_rng(5)
    rids = [engine.submit(rng.integers(1, 300, n), max_new_tokens=5)
            for n in (45, 6, 19, 33, 2)]
    out = engine.run()
    ticks = [e["args"] for e in engine._trace.events()
             if e["name"] == "tick" and e["tid"] == 0]
    return [[int(t) for t in out[r]] for r in rids], ticks


def _vocab_dot_rows(engine):
    """The row counts of the tick executable's LM-head dots, read from
    its compiled text."""
    vocab = engine.model.config.vocab_size
    rows = set()
    for line in engine._ragged_exec.as_text().splitlines():
        m = re.search(r"= \w+\[([\d,]+)\]\S* dot\(", line)
        if m and "/head/" in line:
            dims = [int(d) for d in m.group(1).split(",")]
            assert dims[-1] == vocab, line
            rows.add(int(np.prod(dims[:-1])))
    return rows


@pytest.fixture(scope="module", params=FAMILIES)
def family_model(request):
    return request.param, _model(request.param)


def test_head_over_taken_rows_matches_head_then_take(family_model):
    """One packed tick (a chunk, a one-row slot, an idle slot, a second
    chunk) through the model step: the logits of the gathered rows are
    the full head's logits at those rows, to float32 rounding."""
    _family, model = family_model
    engine = _engine(model)
    try:
        q_lens = np.array([7, 1, 0, 5], np.int32)
        base = np.zeros(SLOTS, np.int32)
        row_slot, row_pos, row_starts, last_rows = pc.ragged_row_meta(
            q_lens, base, engine._rows, engine._overflow)
        ids = np.random.default_rng(2).integers(
            1, 300, engine._rows).astype(np.int32)
        meta = tuple(jnp.asarray(x, jnp.int32) for x in (
            q_lens, row_starts, row_slot, row_pos, np.arange(1),
            np.arange(engine._wmax)))
        tables = jnp.asarray(engine._tables)

        @jax.jit
        def logits(params, pools, rows):
            return engine._model_step(
                params, jnp.asarray(ids)[None], pools, None,
                block_tables=tables, cache_lens=jnp.asarray(base),
                ragged_meta=meta, logit_rows=rows)[0][0]

        rows = jnp.asarray(last_rows, jnp.int32)
        full = logits(engine._params, engine._pools, None)
        got = logits(engine._params, engine._pools, rows)
        assert full.shape[0] == engine._rows
        assert got.shape == (SLOTS, full.shape[1])
        np.testing.assert_allclose(np.asarray(got),
                                   np.asarray(full)[last_rows],
                                   rtol=1e-5, atol=1e-5)
    finally:
        engine.shutdown(check_leaks=False)


def test_served_tokens_and_head_rows_against_head_then_take(family_model):
    """A run of chunked prompts beside decoding slots serves the tokens
    the head-then-take path serves; its head dot has ``S`` rows where
    that path's has ``R``, and every ``tick`` span says ``S``."""
    _family, model = family_model
    engine, parent = _engine(model), _engine(model)
    _head_then_take(parent)
    try:
        tokens, ticks = _serve(engine)
        parent_tokens, parent_ticks = _serve(parent)
        assert tokens == parent_tokens
        assert engine._head_rows == SLOTS < engine._rows
        assert ticks and all(t["head_rows"] == SLOTS for t in ticks)
        assert _vocab_dot_rows(engine) == {SLOTS}
        assert _vocab_dot_rows(parent) == {engine._rows}
    finally:
        engine.shutdown(check_leaks=False)
        parent.shutdown(check_leaks=False)


@pytest.fixture(scope="module")
def greedy():
    """Plain greedy tokens of the tiny Qwen2, no speculation."""
    engine = _engine(_model("qwen2"))
    tokens, _ticks = _serve(engine)
    engine.shutdown(check_leaks=False)
    return tokens


@pytest.mark.parametrize("prefill_rows", [4, 32])
def test_verify_tick_gathers_its_rows_before_the_head(greedy, prefill_rows):
    """gamma 2 over 4 slots samples 4 x (2 + 2) = 16 rows: with a
    prefill budget of 4 rows that is all of ``R`` = 16, with 32 it is
    16 of ``R`` = 44. Either way the head runs over the 16 gathered
    rows, the verify tick accepts what the head-then-take path accepts,
    and greedy speculation serves plain greedy's tokens."""
    model = _model("qwen2")
    kw = dict(num_speculative_tokens=2, ragged_prefill_rows=prefill_rows)
    engine, parent = _engine(model, **kw), _engine(model, **kw)
    _head_then_take(parent)
    try:
        tokens, ticks = _serve(engine)
        parent_tokens, _ = _serve(parent)
        r = engine._rows
        assert r == SLOTS * 3 + prefill_rows
        assert engine._head_rows == SLOTS * 4
        assert ticks and all(t["head_rows"] == SLOTS * 4 for t in ticks)
        assert _vocab_dot_rows(engine) == {SLOTS * 4}
        assert _vocab_dot_rows(parent) == {r}
        assert tokens == parent_tokens == greedy
        assert engine._n_spec_accepted == parent._n_spec_accepted > 0
    finally:
        engine.shutdown(check_leaks=False)
        parent.shutdown(check_leaks=False)

"""Phi-4-mini-flash family at a small size on the CPU: Mamba layers
whose scan and conv state are slot state, sliding-window differential
attention over a bounded ring a seat, one full-attention layer over the
paged pool, and a cross-decoder (gated memory units and cross-attention
over that one pool) that the engine runs on the rows it samples alone.

Size: hidden 128, 8 layers ``mamba, swa, mamba, swa, mamba (memory),
full, gmu, cross``, 8 / 4 heads of 16, window 16, E 256, S 16,
vocabulary 512, the benchmark's seeded weights and Mamba initialiser
(``benchmark/models/phi4flash.py``; the Mamba projections scaled to
the published widths' magnitudes, ``WIDTH_SCALE``), contexts of ~64 so
the window binds. The plain reference is the benchmark's
(``benchmark/reference/phi4flash.py``: float32, no cache, the scan a
token at a time, the window a plain mask, the cross-decoder on every
row).

Tolerances: everything here runs in float32, where the program and the
reference differ by summation order alone: logits agree to ``LOGIT_TOL``
= 2e-5 (the readings are ~5e-7). Each of the four planted faults —
the window ignored, lambda = 0, the Mamba state not carried from one
tick to the next, ``m`` replaced by ones — misses it by more than two
orders of magnitude. The kernels in interpret mode agree with their
mirrors to 1e-5 (the scan, float32) and 2e-3 (attention: bfloat16
probabilities into the value product, against float32 ones).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.lib import check
from benchmark.models import phi4flash as fam
from benchmark.reference import phi4flash as ref
from paddle_tpu.framework.core import as_jax
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.models import phi4flash as p4
from paddle_tpu.ops import paged_cache as pc
from paddle_tpu.ops.pallas import paged_attention as pa
from paddle_tpu.ops.pallas import selective_scan as ss
from paddle_tpu.ops.pallas import window_attention as wa

SEED = 2**31 + 38
VOCAB = 512
WINDOW = 16
LOGIT_TOL = 2e-5
PAD = 128


def tiny_cfg(**kw):
    """``Phi4FlashConfig.tiny()`` as a configuration file's dict."""
    return dict(dict(
        model_type="phi4flash", vocab_size=VOCAB, hidden_size=128,
        intermediate_size=256, num_hidden_layers=8, num_attention_heads=8,
        num_key_value_heads=4, sliding_window=WINDOW, mb_per_layer=2,
        layer_norm_eps=1e-5, tie_word_embeddings=True, mamba_d_state=16,
        mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
        max_position_embeddings=4096), **kw)


# the Mamba layers' ``in_proj`` and ``x_proj`` scaled by the ratio of
# the published widths to these (sqrt(2560 / 128) ~ 4): N(0, 0.02) over
# a 20th of the published fan-in shrinks ``x`` and ``B, C`` four-fold
# each, and with them the scan's part of a Mamba layer's output to a
# ten-thousandth, where the published widths give it ~7%
WIDTH_SCALE = ("mixer.in_proj.weight", "mixer.x_proj.weight")


@pytest.fixture(scope="module")
def built():
    """The program's model in float32 with the seed's weights (the
    Mamba leaves of ``WIDTH_SCALE`` times 4), and the same weights as
    the reference takes them."""
    cfg = tiny_cfg()
    model = fam.build(cfg, SEED, False).to(dtype="float32")
    model.config.dtype = "float32"
    w = fam.make_leaves(fam.leaf_shapes(cfg), SEED)
    params = dict(model.named_parameters())
    for name in w:
        w[name] = w[name].astype(jnp.float32)
        if name.endswith(WIDTH_SCALE) and ref.layer_kind(
                cfg, int(name.split(".")[2])) == "mamba":
            w[name] = w[name] * 4
            params[name]._data = w[name]
    return cfg, model, w


def _ref_logits(cfg, w, seq):
    """The reference's logits ``[len(seq), V]`` of one sequence, padded
    to ``PAD`` rows (causal: the padding is inert)."""
    ids = np.zeros((1, PAD), np.int32)
    ids[0, :len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        return np.asarray(ref.forward(w, jnp.asarray(ids), cfg))[0,
                                                                 :len(seq)]


def _forward(model, ids):
    with jax.default_matmul_precision("highest"):
        return np.asarray(as_jax(model(jnp.asarray(ids))))


def _engine(model, **kw):
    """An engine whose tick also hands the test what its head computed:
    ``(q_lens, base, logits [slots, V])`` a tick (``engine.rows``)."""
    base = dict(num_slots=3, max_model_len=PAD, block_size=16,
                prefill_chunk=16, host_kv_tier_bytes=0)
    base.update(kw)
    engine = ServingEngine(model, ServingConfig(**base))
    engine.rows = []
    inner = engine._model_step

    def step(params, ids, *a, logit_rows=None, **kw):
        out, pools = inner(params, ids, *a, logit_rows=logit_rows, **kw)
        jax.debug.callback(
            lambda *x: engine.rows.append(tuple(map(np.asarray, x))),
            kw["ragged_meta"][0], kw["cache_lens"], out[0])
        return out, pools

    engine._model_step = step
    return engine


def _one_at_a_time(cfg, w, engine, prompts, new=12):
    """Serve the prompts one after another (each finds the seats its
    predecessor left) and return the largest gap between a logit the
    head computed (every tick's last row of the slot: chunk ends and
    decode rows) and the reference's at that position."""
    worst = 0.0
    for prompt in prompts:
        engine.rows.clear()
        rid = engine.submit(prompt, max_new_tokens=new)
        out = np.asarray(engine.run()[rid])
        rl = _ref_logits(cfg, w, np.concatenate([prompt, out]))
        for ql, base, logits in engine.rows:
            for s in np.flatnonzero(ql > 0):
                pos = base[s] + ql[s] - 1
                worst = max(worst, float(np.abs(logits[s] - rl[pos]).max()))
    return worst


PROMPTS = [np.random.RandomState(n).randint(0, VOCAB, size).astype(np.int32)
           for n, size in ((1, 45), (2, 61), (3, 9))]


# -- the program against the reference ----------------------------------------

def test_forward_matches_the_reference(built):
    cfg, model, w = built
    ids = np.random.RandomState(0).randint(0, VOCAB, (1, 64)).astype(np.int32)
    got = _forward(model, ids)[0]
    want = _ref_logits(cfg, w, ids[0])
    assert np.abs(got - want).max() < LOGIT_TOL


def test_engine_prefill_in_chunks_then_decode_matches_the_reference(built):
    """Three requests one after another on a 3-seat engine: prompts of
    45 and 61 rows in chunks of 16 (the window of 16 binds in every
    chunk after the first), 12 decode rows each; a seat is re-used from
    zeros by each later request."""
    cfg, model, w = built
    engine = _engine(model)
    assert _one_at_a_time(cfg, w, engine, PROMPTS) < LOGIT_TOL


def test_engine_concurrent_requests_and_a_prefix_hit(built):
    """Five requests on three seats at once (seats re-used mid-run,
    chunks beside decoding rows), then a request that shares 32 tokens
    with one already served: its prefix is a hit restored with the scan
    and ring state snapshotted at that block boundary. Every served
    token is the reference's best."""
    cfg, model, w = built
    engine = _engine(model)
    # room for every snapshot (at this size a seat's state is 90 KB
    # beside a 400 KB pool: the engine's own budget would hold none)
    engine._state_snap_budget = 1 << 30
    rng = np.random.RandomState(5)
    shared = rng.randint(0, VOCAB, 32)
    prompts = [np.concatenate([shared, rng.randint(0, VOCAB, 9)]),
               rng.randint(0, VOCAB, 50), rng.randint(0, VOCAB, 7),
               rng.randint(0, VOCAB, 33), rng.randint(0, VOCAB, 70)]
    prompts = [p.astype(np.int32) for p in prompts]
    rids = [engine.submit(p, max_new_tokens=14) for p in prompts]
    res = engine.run()
    late = np.concatenate([shared, rng.randint(0, VOCAB, 20)]).astype(
        np.int32)
    rid = engine.submit(late, max_new_tokens=14)
    res.update(engine.run())
    assert engine.stats()["prefix_tokens_reused"] == 32
    assert engine.stats()["state_snapshot_hits"] == 1
    for prompt, r in zip(prompts + [late], rids + [rid]):
        toks = np.asarray(res[r])
        rl = _ref_logits(cfg, w, np.concatenate([prompt, toks]))
        gaps = check.gaps_below_best(rl[len(prompt) - 1 + np.arange(
            len(toks))], toks)
        assert float(np.max(gaps)) < LOGIT_TOL


def test_engine_with_every_kernel_interpreted_serves_the_reference(
        monkeypatch):
    """Heads of 64 (kv pairs of 128 lanes, a flat pool) and E 512, so
    that the tick takes every kernel — the scan's two, the window's two,
    the ragged kernel in the ``full`` and ``cross`` layers — under the
    interpreter: 12 Mosaic calls a tick, no fallback, and every served
    token the reference's best."""
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "interpret")
    cfg = tiny_cfg(hidden_size=256, num_attention_heads=4,
                   num_key_value_heads=2, mamba_dt_rank=16)
    seed = SEED + 1
    model = fam.build(cfg, seed, False).to(dtype="float32")
    model.config.dtype = "float32"
    w = {k: v.astype(jnp.float32) for k, v in fam.make_leaves(
        fam.leaf_shapes(cfg), seed).items()}
    engine = ServingEngine(model, ServingConfig(
        num_slots=3, max_model_len=PAD, block_size=16, prefill_chunk=16,
        host_kv_tier_bytes=0))
    rng = np.random.RandomState(3)
    prompts = [rng.randint(0, VOCAB, n).astype(np.int32) for n in (45, 20, 7)]
    rids = [engine.submit(p, max_new_tokens=10) for p in prompts]
    res = engine.run()
    assert engine.stats()["kernel_fallbacks"] == 0
    launched = engine.kernel_census()["decode"]["launch_by_op"]
    assert launched["pallas_call"] == 12
    for prompt, rid in zip(prompts, rids):
        toks = np.asarray(res[rid])
        rl = _ref_logits(cfg, w, np.concatenate([prompt, toks]))
        gaps = check.gaps_below_best(rl[len(prompt) - 1 + np.arange(
            len(toks))], toks)
        assert float(np.max(gaps)) < LOGIT_TOL
    engine.shutdown()


def _tick(model, slots, q_lens, base, caches=None, mb=8, seed=0):
    """One ragged tick through ``Phi4FlashModel`` by hand: ``(hidden at
    the sampled rows, hidden at every row through the cross-decoder,
    caches after the tick)``. Slot ``s`` owns blocks ``s * mb ..``."""
    c = model.config
    r = 40
    q_lens = np.asarray(q_lens)
    row_slot, row_pos, row_starts, last = pc.ragged_row_meta(
        q_lens, base, r, mb * 16 * slots)
    ids = np.random.RandomState(seed).randint(0, VOCAB, (1, r))
    tables = np.arange(slots * mb, dtype=np.int32).reshape(slots, mb)
    if caches is None:
        caches = model.init_paged_caches(slots * mb + 1, 16,
                                         num_slots=slots)
    meta = tuple(jnp.asarray(x, jnp.int32) for x in (
        q_lens, row_starts, row_slot, row_pos)) + (
        jnp.arange(1, dtype=jnp.int32), jnp.arange(24, dtype=jnp.int32))
    args = dict(block_tables=jnp.asarray(tables),
                cache_lens=jnp.asarray(base, jnp.int32), ragged_meta=meta)
    with jax.default_matmul_precision("highest"):
        h, new = model.model(jnp.asarray(ids), caches=caches,
                             logit_rows=jnp.asarray(last), **args)
        h_all, _ = model.model(jnp.asarray(ids), caches=caches,
                               cross_rows=jnp.arange(r, dtype=jnp.int32),
                               **args)
    return (np.asarray(as_jax(h))[0],
            np.asarray(as_jax(h_all))[0][last], new)


def test_cross_decoder_on_gathered_rows_equals_it_on_every_row(built):
    """Two ticks: a 24-row chunk beside a decode row, then a 12-row
    chunk beside two decode rows (one of them a fresh seat). The
    cross-decoder run on the sampled rows alone gives, at those rows,
    what it gives run on every packed row."""
    _cfg, model, _w = built
    h, h_all, caches = _tick(model, 3, [24, 1, 0], [0, 0, 0])
    np.testing.assert_allclose(h[:2], h_all[:2], atol=1e-6)
    caches = [tuple(map(as_jax, layer)) for layer in caches]
    h, h_all, _ = _tick(model, 3, [1, 12, 1], [24, 1, 0], caches, seed=1)
    np.testing.assert_allclose(h, h_all, atol=1e-6)


def test_a_window_layer_holds_w_positions_a_seat_whatever_the_context(
        built):
    """The ring a seat of a ``swa`` layer is ``W`` positions at every
    context the engine allows — at most ``W + prefill_chunk`` — and the
    cross-decoder holds no cache of its own."""
    cfg, model, _w = built
    for max_len in (PAD, 4096):
        engine = ServingEngine(model, ServingConfig(
            num_slots=3, max_model_len=max_len, block_size=16,
            prefill_chunk=16, host_kv_tier_bytes=0))
        pools = engine._pools
        assert len(pools) == cfg["num_hidden_layers"] // 2 + 2
        for i, layer in enumerate(pools):
            if ref.layer_kind(cfg, i) == "swa":
                assert [t.shape for t in layer] == [(4, WINDOW, 64)] * 2
                assert WINDOW <= WINDOW + 16
        engine.shutdown()


def test_tick_counts_are_the_work_of_the_tick(built):
    """The ``tick`` span's counts against a row-by-row count: 2 ``swa``
    layers, the ``full`` layer over every row and 1 ``cross`` layer over
    one row a live slot."""
    _cfg, model, _w = built
    q_lens, base = np.array([1, 30, 0, 1]), np.array([40, 5, 0, 3])
    rows = [(b, t) for q, b in zip(q_lens, base) for t in range(q)]
    live = q_lens > 0
    assert model.tick_counts(q_lens, base) == {
        "window_positions": 2 * sum(min(b + t + 1, WINDOW)
                                    for b, t in rows),
        "window_reads": 2 * (1 + 30 + 1 + min(40, WINDOW) + 5 + 3),
        "shared_kv_positions": sum(b + t + 1 for b, t in rows)
        + int((base + q_lens)[live].sum()),
        "shared_kv_reads": 2 * int((base + q_lens)[live].sum()),
        "cross_rows": 3}


def test_speculation_is_refused(built):
    _cfg, model, _w = built
    with pytest.raises(NotImplementedError):
        ServingEngine(model, ServingConfig(
            num_slots=2, max_model_len=PAD, block_size=16,
            num_speculative_tokens=2, host_kv_tier_bytes=0))


# -- the kernels against their mirrors (interpret mode) -----------------------

def _layout(q_lens, base, rows):
    row_slot, row_pos, row_starts, _last = pc.ragged_row_meta(
        q_lens, base, rows, 10_000)
    return tuple(jnp.asarray(x, jnp.int32) for x in (
        q_lens, row_starts, row_slot, row_pos)) + (
        jnp.arange(1, dtype=jnp.int32), jnp.arange(20, dtype=jnp.int32))


# a wide slot of 20 rows, one-row seats at several bases (one fresh),
# a slot with no rows
Q_LENS, BASE = np.array([1, 20, 0, 1, 1]), np.array([7, 40, 0, 0, 100])


def test_ssm_kernels_match_their_mirrors():
    rng = np.random.RandomState(0)
    r, e, n = 32, 256, 16
    meta = _layout(Q_LENS, BASE, r)
    dt = jnp.asarray(rng.uniform(1e-3, 0.1, (r, e)), jnp.float32)
    x, b, c = (jnp.asarray(rng.randn(*s), jnp.float32)
               for s in ((r, e), (r, n), (r, n)))
    a = -jnp.asarray(rng.uniform(1, 16, (n, e)), jnp.float32)
    state = jnp.asarray(rng.randn(6, n, e), jnp.float32)
    for kernel, mirror in ((ss.pallas_ssm_recurrent, ss._xla_recurrent),
                           (ss.pallas_ssm_chunk, ss._xla_chunk)):
        y_k, s_k = kernel(dt, x, b, c, a, state, meta, interpret=True)
        y_m, s_m = mirror(dt, x, b, c, a, state, meta)
        np.testing.assert_allclose(y_k, y_m, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(s_k, s_m, atol=1e-5, rtol=1e-5)


def test_window_kernels_match_the_mirror():
    """Heads of 64 paired over 4 kv pairs (head size 128, 2 query slots
    a pair), a window of 32: seats past the window, inside it, fresh;
    the wide slot's chunk crosses its ring and its own rows."""
    rng = np.random.RandomState(1)
    r, h, d, lanes, w = 32, 8, 128, 512, 32
    meta = _layout(Q_LENS, BASE, r)
    q = jnp.asarray(rng.randn(r, h, d), jnp.bfloat16)
    k, v = (jnp.asarray(rng.randn(r, lanes), jnp.bfloat16) for _ in "kv")
    ring_k, ring_v = (jnp.asarray(rng.randn(6, w, lanes), jnp.bfloat16)
                      for _ in "kv")
    base = jnp.asarray(BASE, jnp.int32)
    got = (wa.pallas_window_decode(q, k, v, ring_k, ring_v, base, meta, w,
                                   0.125, interpret=True)
           + wa.pallas_window_chunk(q, k, v, ring_k, ring_v, base, meta, w,
                                    0.125, interpret=True))
    want = wa._xla_window(q, k, v, ring_k, ring_v, base, meta, w, 0.125)
    np.testing.assert_allclose(got, want, atol=2e-3, rtol=2e-3)


def test_ring_write_keeps_each_slot_s_last_w_positions():
    w = 8
    meta = _layout(Q_LENS, BASE, 32)
    rows = jnp.arange(32, dtype=jnp.float32)[:, None] * jnp.ones((1, 4))
    ring = wa.ring_write(jnp.full((6, w, 4), -1.0), rows,
                         jnp.asarray(BASE), meta, w)
    pos = wa.ring_positions(jnp.asarray(BASE + Q_LENS), w)
    # slot 1's 20 rows from position 40 are packed rows 1..20: its ring
    # holds positions 52..59 = rows 13..20
    held = {int(p): float(ring[1, j, 0]) for j, p in enumerate(pos[1])}
    assert held == {40 + i: 1.0 + i for i in range(12, 20)}
    assert float(ring[0, 7 % w, 0]) == 0.0      # slot 0's row at position 7
    assert float(ring[2].max()) == -1.0         # no rows: untouched


_N = pa._NARROW_POSITIONS


@pytest.mark.parametrize("q_lens,base,w_max,hkv", [
    ([1, 20, 1], [30, 7, 0], 20, 4),
    # the cross layers' call: one row a slot, every tile walked narrow,
    # the token seeing 1, 16, 17, N, N + 1 and 3N + 5 positions
    ([1, 1, 1, 1, 1, 1, 0], [0, 15, 16, _N - 1, _N, 3 * _N + 4, 0], 1, 4),
    # the full layer's call at its heads (20 kv heads of 64: 10 pairs)
    # and its w_max: one-token tiles, walked as the whole row, beside a
    # chunk tile, walked in five groups of two pairs
    ([1, 20, 1, 0, 1], [30, 7, 0, 0, _N], 256, 20),
], ids=["chunk", "cross_narrow", "full_layer"])
def test_differential_mode_of_the_ragged_kernel_is_two_softmaxes(
        q_lens, base, w_max, hkv):
    """Paired queries over a flat pool of head-64 kv heads (two pairs,
    or the full layer's ten): the ragged kernel, all 128 output lanes
    kept, gives each query its own kv head's softmax against the pair's
    two value heads — beside a chunk, in the cross layers'
    one-row-a-slot call, and in the full layer's call."""
    rng = np.random.RandomState(2)
    q_lens, base = np.array(q_lens), np.array(base)
    slots, bs, h, d = len(q_lens), 16, 2 * hkv, 64
    mb = -(-int((base + q_lens).max()) // bs)
    r = -(-int(q_lens.sum()) // 8) * 8 + 8
    row_slot, row_pos, row_starts, _ = pc.ragged_row_meta(q_lens, base, r,
                                                          10_000)
    kp, vp = (jnp.asarray(rng.randn(slots * mb + 1, bs, hkv * d),
                          jnp.bfloat16) for _ in "kv")
    tables = jnp.arange(slots * mb, dtype=jnp.int32).reshape(slots, mb)
    q = jnp.asarray(rng.randn(r, h, d), jnp.bfloat16)
    lens = jnp.asarray(base + 1, jnp.int32)
    got = pa.pallas_ragged_paged_attention(
        p4.diff_pairs(q), kp, vp, tables, lens, jnp.asarray(q_lens),
        jnp.asarray(row_starts), w_max=w_max, sm_scale=d ** -0.5,
        interpret=True)
    # plain: per packed row of its slot, every query head over its kv
    # head's keys, both value heads of its pair side by side
    dense_k = kp[tables].reshape(slots, mb * bs, hkv, d).astype(jnp.float32)
    dense_v = vp[tables].reshape(slots, mb * bs, hkv // 2, 2 * d).astype(
        jnp.float32)
    qp = np.asarray(p4.diff_pairs(q).astype(jnp.float32))
    for row in range(int(q_lens.sum())):
        s = row_slot[row]
        pos = row_pos[row]
        for i in range(h):
            pair, half = i // 4, (i % 4) // 2
            kk = dense_k[s, :pos + 1, 2 * pair + half]
            qq = qp[row, i, half * d:(half + 1) * d]
            p = jax.nn.softmax(kk @ qq * d ** -0.5)
            want = p @ dense_v[s, :pos + 1, pair]
            np.testing.assert_allclose(got[row, i], want, atol=2e-2,
                                       rtol=2e-2)


# -- planted faults: each must fail the tolerance -----------------------------

def test_window_ignored_fails_the_tolerance(built):
    cfg, model, w = built
    ids = np.random.RandomState(0).randint(0, VOCAB, (1, 64)).astype(np.int32)
    model.config.sliding_window = 10 ** 6
    try:
        got = _forward(model, ids)[0]
    finally:
        model.config.sliding_window = WINDOW
    assert np.abs(got - _ref_logits(cfg, w, ids[0])).max() > 100 * LOGIT_TOL


def test_lambda_zero_fails_the_tolerance(built, monkeypatch):
    cfg, model, w = built
    monkeypatch.setattr(p4, "diff_lambda", lambda *a: jnp.float32(0.0))
    ids = np.random.RandomState(0).randint(0, VOCAB, (1, 64)).astype(np.int32)
    got = _forward(model, ids)[0]
    assert np.abs(got - _ref_logits(cfg, w, ids[0])).max() > 100 * LOGIT_TOL


def test_scan_state_not_carried_between_ticks_fails_the_tolerance(
        built, monkeypatch):
    cfg, model, w = built
    sound = p4.ssm_step
    monkeypatch.setattr(p4, "ssm_step", lambda dt, x, b, c, a, state, meta:
                        sound(dt, x, b, c, a, jnp.zeros_like(state), meta))
    engine = _engine(model)
    assert _one_at_a_time(cfg, w, engine, PROMPTS[:1]) > 100 * LOGIT_TOL


def test_memory_replaced_by_ones_fails_the_tolerance(built, monkeypatch):
    cfg, model, w = built
    sound = p4.Phi4FlashGMU.forward
    monkeypatch.setattr(p4.Phi4FlashGMU, "forward", lambda self, u, m:
                        sound(self, u, jnp.ones_like(as_jax(m))))
    ids = np.random.RandomState(0).randint(0, VOCAB, (1, 64)).astype(np.int32)
    got = _forward(model, ids)[0]
    assert np.abs(got - _ref_logits(cfg, w, ids[0])).max() > 100 * LOGIT_TOL

"""Solar-Open2 family at a small size on the CPU: gated delta-rule
linear attention (KDA) whose matrix state a head is slot state of two
kinds beside the paged KV, gated NoPE grouped-query attention one layer
in four, the sigmoid top-k router with a share of the experts held and
a shared expert, and the engine's handling of a LARGE slot state (seat
re-use, chunked prefill through the chunkwise form, prefix hits cut to
a snapshot, snapshots budgeted in bytes, preemption, an EOS inside the
async pipeline).

The plain reference is the benchmark's (``benchmark/reference/
solar_open2.py``: float32, no cache, the delta rule a scan over tokens,
the convolution a sum of shifted copies, a dense loop over the
experts); weights are the benchmark's seeded ones, the decays drawn
over the published range (``benchmark/models/solar_open2.py``).
Tolerances: everything here runs in float32, where the program and the
reference differ by summation order alone — the chunkwise form
re-associates 64 rows of the recurrence, so logits agree to 5e-5 and a
served token's logit lies within 2e-4 of the reference's best; the two
kernels agree with the token scan to 2e-5 of a state whose entries
reach ~4 (bfloat16 in place of float32 for the recurrent state misses
every one of these by two orders of magnitude:
``test_bf16_state_fails_the_tolerance``).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.lib import check
from benchmark.models import solar_open2 as fam
from benchmark.reference import solar_open2 as ref
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig, Lfm2ShortConv
from paddle_tpu.models.deepseek_v3 import DeepseekV3MoE
from paddle_tpu.models.solar_open2 import SolarOpen2Config
from paddle_tpu.ops import paged_cache as pc
from paddle_tpu.ops import short_conv
from paddle_tpu.ops.pallas import delta_rule as dr

SEED = 2**31 + 17
VOCAB = 512
GQA = (0, 4)
LOGIT_TOL = 5e-5
BEST_TOL = 2e-4


def tiny_cfg(**kw):
    """``SolarOpen2Config.tiny()`` as a configuration file's dict: 8
    layers ``gqa, kda, kda, kda`` twice, every expert held."""
    return dict(dict(
        model_type="solar_open2", vocab_size=VOCAB, hidden_size=64,
        intermediate_size=96, moe_intermediate_size=32,
        num_hidden_layers=8, num_attention_heads=4, num_key_value_heads=2,
        head_dim=16, gqa_interval=3, gqa_layers=list(GQA),
        linear_attn_config=dict(short_conv_kernel_size=4, head_dim=16,
                                num_heads=4, num_kv_heads=None),
        kda_use_full_proj=False, kda_allow_neg_eigval=True, use_rope=False,
        use_gqa_gate=True, n_routed_experts=16, n_shared_experts=1,
        num_experts_per_tok=2, norm_topk_prob=True,
        routed_scaling_factor=1, first_k_dense_replace=0,
        max_position_embeddings=8192, rms_norm_eps=1e-5,
        tie_word_embeddings=False,
        deployment=dict(expert_parallel=1, rank=0)), **kw)


@pytest.fixture(scope="module")
def built():
    """The program's model in float32 with the seed's weights, and the
    same weights as the reference takes them."""
    cfg = tiny_cfg()
    model = fam.build(cfg, SEED, False).to(dtype="float32")
    model.config.dtype = "float32"
    return cfg, model, fam.make_leaves(fam.leaf_shapes(cfg), SEED)


def _ref_logits(cfg, w, seq, pad_to=256):
    """The reference's logits ``[len(seq), V]`` for one sequence, a
    jitted layer at a time at one padded length (causal: the padding is
    inert), so every call shares the compiled layers."""
    small = fam._small(cfg)
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :len(seq)] = seq
    with jax.default_matmul_precision("highest"):
        h = ref.embed(jnp.asarray(ids), w["model.embed_tokens.weight"])
        for i in range(cfg["num_hidden_layers"]):
            pre = f"model.layers.{i}."
            wi = {k[len(pre):]: v for k, v in w.items()
                  if k.startswith(pre)}
            h = fam._layer(h, wi, fam._static(small),
                           i in cfg["gqa_layers"], False)
        logits = ref.head(h, w["model.norm.weight"], w["lm_head.weight"],
                          small)
    return np.asarray(logits)[0, :len(seq)]


def _engine(model, **kw):
    """An engine whose tick also hands every row's logits to the test
    (``engine.rows``), as ``tests/test_lfm2_moe.py`` builds one."""
    base = dict(num_slots=4, max_model_len=256, block_size=16,
                prefill_chunk=80, host_kv_tier_bytes=0)
    base.update(kw)
    engine = ServingEngine(model, ServingConfig(**base))
    engine.rows = []
    inner = engine._model_step

    def step(params, ids, *a, **kw):
        logits, pools = inner(params, ids, *a, **kw)
        _ql, _rs, slot, pos = kw["ragged_meta"][:4]
        jax.debug.callback(
            lambda *x: engine.rows.append(tuple(map(np.asarray, x))),
            ids[0], slot, pos, logits[0])
        return logits, pools

    engine._model_step = step
    return engine


def _assert_served_exact(cfg, w, engine, prompt, toks, n=None, start=0):
    """Every logit the engine computed for this request — the prompt's
    rows from ``start`` and a row for each served token but the last —
    equals the reference's full forward over prompt + served tokens;
    and every served (greedy) token is the reference's best."""
    assert len(toks) == (n or len(toks)) and len(toks)
    seq = np.concatenate([prompt, toks]).astype(np.int64)
    want = _ref_logits(cfg, w, seq)
    rows = want[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    assert check.gaps_below_best(rows, toks).max() < BEST_TOL
    overflow = engine.config.max_model_len
    seen = set()
    for s in range(engine.config.num_slots):
        run = []
        for ids, slot, pos, logits in engine.rows:
            for r in np.flatnonzero((slot == s) & (pos < overflow)):
                if run and pos[r] != run[-1][0] + 1:
                    seen |= _check_run(run, seq, want)
                    run = []
                run.append((int(pos[r]), int(ids[r]), logits[r]))
        seen |= _check_run(run, seq, want)
    assert seen >= set(range(start, len(seq) - 1))


def _check_run(run, seq, want):
    if not run:
        return set()
    pos = np.asarray([p for p, _i, _l in run])
    if pos[-1] >= len(seq) or any(seq[pos] != [i for _p, i, _l in run]):
        return set()
    np.testing.assert_allclose(np.stack([l for _p, _i, l in run]),
                               want[pos], atol=LOGIT_TOL)
    return set(pos.tolist())


def _ticks(engine):
    return [e["args"] for e in engine._trace.events()
            if e["name"] == "tick" and e["tid"] == 0]


# -- (a) the whole-sequence form ---------------------------------------------

def test_tiny_config_is_the_file_form(built):
    _cfg, model, w = built
    want = SolarOpen2Config.tiny(dtype="float32", initializer_range=0.0,
                                 expert_count=16, kda_low_rank=16)
    assert model.config == want
    assert want.gqa_layers == GQA and want.kda_heads == 4
    # the decays cover the published range: A in 1..16, dt in 1e-3..1e-1
    a = np.exp(np.asarray(w["model.layers.1.linear_attn.A_log"], np.float64))
    dt = np.log1p(np.exp(np.asarray(
        w["model.layers.1.linear_attn.dt_bias"], np.float64)))
    assert 1.0 <= a.min() < 6 and 11 < a.max() <= 16.1
    assert 1e-3 <= dt.min() < 3e-3 and 0.03 < dt.max() <= 0.101


def test_full_forward_matches_reference(built):
    cfg, model, w = built
    ids = np.random.default_rng(0).integers(1, VOCAB, 150)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
    np.testing.assert_allclose(got, _ref_logits(cfg, w, ids),
                               atol=LOGIT_TOL)


# -- (b) (d) the engine: chunked prefill, decoding, re-used seats -------------

# five prompts on four seats admitted at different ticks (150 takes two
# chunks of 80 rows = a whole sub-chunk and a part of one; 97 two), then
# two short ones that take seats others left: their first rows must not
# read the seat's state
PROMPTS = (97, 7, 150, 23, 64, 2, 3)


@pytest.fixture(scope="module")
def served(built):
    _cfg, model, _w = built
    rng = np.random.default_rng(1)
    engine = _engine(model)
    prompts = [rng.integers(1, VOCAB, n) for n in PROMPTS]
    rids = [engine.submit(p, max_new_tokens=6) for p in prompts[:3]]
    engine.step()
    engine.step()
    rids += [engine.submit(p, max_new_tokens=6) for p in prompts[3:]]
    out = engine.run()
    stats, ticks = engine.stats(), _ticks(engine)
    pools = engine._pools
    engine.shutdown()
    return prompts, [out[r] for r in rids], stats, ticks, pools, engine


@pytest.mark.parametrize("which", range(len(PROMPTS)))
def test_engine_prefill_then_decode_matches_reference(built, served, which):
    cfg, _model, w = built
    prompts, outs, _st, _ticks_, _pools, engine = served
    _assert_served_exact(cfg, w, engine, prompts[which], outs[which], n=6)


def test_engine_keeps_state_of_two_kinds(built, served):
    _cfg, _model, _w = built
    _prompts, _outs, st, ticks, pools, _engine_ = served
    assert st["executables_compiled"] == 2 and st["kernel_fallbacks"] == 0
    assert [pc.is_slot_state(layer) for layer in pools] \
        == [i not in GQA for i in range(8)]
    # a kda layer: the convolution's last 3 inputs [S + 1, 3, 3 H d] and
    # the matrix state [S + 1, H, d, d] in float32; the null seat (the
    # last row of each) is never written
    for layer in pools:
        if pc.is_slot_state(layer):
            assert [t.shape for t in layer] == [(5, 3, 192), (5, 4, 16, 16)]
            assert layer[1].dtype == jnp.float32
            assert not any(np.asarray(t.data[4]).any() for t in layer)
    per_slot = 6 * (3 * 192 + 4 * 16 * 16) * 4
    assert st["state_bytes"] == 5 * per_slot == pc.state_bytes(pools)
    assert pc.snapshot_nbytes(pools) == per_slot
    assert st["kv_pool_bytes"] == pc.pool_bytes(pools) \
        == sum(int(a.nbytes) for i in GQA for a in pools[i])
    assert st["state_seats_started"] == len(PROMPTS)
    # every tick says how many seats it advanced one row and how many
    # rows went through the chunked form
    assert all(t["kda_seats"] + (t["kda_chunk_rows"] > 0)
               == t["state_seats"] for t in ticks)
    assert max(t["kda_chunk_rows"] for t in ticks) == 80
    # every prompt row but a prompt's one-row tail went through a chunk
    assert sum(t["kda_chunk_rows"] for t in ticks) + sum(
        t["kda_seats"] for t in ticks) == sum(PROMPTS) + 5 * len(PROMPTS)
    assert st["moe_pairs_local"] == 2 * st["moe_rows"] > 0


def test_slot_state_pytree_round_trips(built):
    _cfg, model, _w = built
    pools = model.init_paged_caches(9, 16, num_slots=3)
    pools = [tuple(pc.SlotState(t.data.at[1].set(7.0)) for t in layer)
             if pc.is_slot_state(layer) else layer for layer in pools]
    snap = pc.export_slot_state(pools, jnp.int32(1))
    assert len(snap) == 6 and all(len(rows) == 2 for rows in snap)
    assert snap[0][0].shape == (3, 192) and snap[0][1].shape == (4, 16, 16)
    seated = pc.import_slot_state(
        pools, jnp.int32(2), jax.tree_util.tree_map(lambda x: x * 0 + 3, snap))
    assert float(seated[1][1].data[2].max()) == 3.0
    assert float(seated[1][0].data[1].min()) == 7.0
    assert seated[0] is pools[0]
    # the block walkers pass both tables over
    copied = pc.copy_blocks(pools, jnp.int32(2), jnp.int32(5))
    assert copied[1] is pools[1] and pc.export_blocks(
        copied, jnp.asarray([2], jnp.int32))[1] == ()


# -- (c) the two kernels against the token scan ---------------------------------

def _token_scan(q, k, v, g, beta, s0):
    def step(s, x):
        q_t, k_t, v_t, g_t, b_t = x
        s = s * jnp.exp(g_t)[..., None]
        pred = jnp.sum(s * k_t[..., None], -2)
        s = s + k_t[..., None] * (b_t[..., None] * (v_t - pred))[..., None, :]
        return s, jnp.sum(s * q_t[..., None], -2)
    s, o = jax.lax.scan(step, s0, (q, k, v, g, beta))
    return o, s


def _kda_case(rng, rows, h, d, strong):
    """Operands over the published range, or at its strongest: ``A`` 16
    and ``dt`` 0.1 on every channel (a 64-row sub-chunk sums to -102)
    with ``beta`` near 2."""
    def unit(x):
        return x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.standard_normal((rows, h, d))) * d ** -0.5
    k = unit(rng.standard_normal((rows, h, d)))
    v = rng.standard_normal((rows, h, d))
    if strong:
        g = np.full((rows, h, d), -1.6)
        beta = np.full((rows, h), 1.98)
    else:
        g = -rng.uniform(1, 16, (1, h, 1)) \
            * 10 ** rng.uniform(-3, -1, (rows, h, d))
        beta = rng.uniform(0, 2, (rows, h))
    return [jnp.asarray(x, jnp.float32) for x in (q, k, v, g, beta)]


def _meta(q_lens, base, rows, w_max):
    sl, pos, rs, _ = pc.ragged_row_meta(q_lens, base, rows, 10 ** 6)
    return (jnp.asarray(q_lens, jnp.int32), jnp.asarray(rs),
            jnp.asarray(sl), jnp.asarray(pos),
            jnp.arange(1, dtype=jnp.int32),
            jnp.arange(w_max, dtype=jnp.int32))


def _xla_step(*a):
    return dr.kda_step(*a)


def _pallas_step(q, k, v, g, beta, state, meta):
    o_c, state = dr.pallas_kda_chunk(q, k, v, g, beta, state, meta,
                                     interpret=True)
    o_r, state = dr.pallas_kda_recurrent(q, k, v, g, beta, state, meta,
                                         interpret=True)
    return o_c + o_r, state


KDA_CASES = {
    # one-row seats beside a chunk of a sub-chunk and a part of one that
    # continues from a held state; a rowless seat; a fresh one-row seat
    "mixed": ([1, 0, 100, 1, 1], [5, 0, 64, 0, 9], 128, False),
    # the strongest published decay over a full sub-chunk, beta near 2
    "strongest": ([1, 64, 0, 1], [3, 7, 0, 0], 64, True),
    "decode_only": ([1, 1, 0, 1], [3, 7, 0, 0], 64, False),
}


@pytest.mark.parametrize("form", ["xla", "pallas_interpret"])
@pytest.mark.parametrize("case", sorted(KDA_CASES))
def test_kda_kernels_equal_the_token_scan(case, form):
    q_lens, base, w_max, strong = KDA_CASES[case]
    h, d = 2, 128
    n, rows = len(q_lens), len(q_lens) + w_max
    rng = np.random.default_rng(7)
    ops = _kda_case(rng, rows, h, d, strong)
    state = jnp.asarray(rng.standard_normal((n + 1, h, d, d)),
                        jnp.float32).at[n].set(0)
    want_o = np.zeros((rows, h, d), np.float32)
    want_s = np.array(state)
    r0 = 0
    for s, n_rows in enumerate(q_lens):
        if n_rows:
            # (the table is value-major: S^T a head)
            s0 = jnp.swapaxes(state[s], -1, -2) if base[s] \
                else jnp.zeros_like(state[s])
            o, s1 = _token_scan(*(x[r0:r0 + n_rows] for x in ops), s0)
            want_o[r0:r0 + n_rows] = o
            want_s[s] = np.swapaxes(np.asarray(s1), -1, -2)
        r0 += n_rows
    step = _xla_step if form == "xla" else _pallas_step
    o, new = jax.jit(step)(*ops, state, _meta(q_lens, base, rows, w_max))
    np.testing.assert_allclose(np.asarray(o), want_o, atol=2e-6)
    np.testing.assert_allclose(np.asarray(new), want_s, atol=2e-5)
    assert not np.asarray(new[n]).any()         # the null seat


def test_bf16_state_fails_the_tolerance():
    """The tolerances above are tight enough to tell float32 from
    bfloat16 in the recurrent state."""
    q_lens, base, w_max, strong = KDA_CASES["mixed"]
    rng = np.random.default_rng(7)
    rows = len(q_lens) + w_max
    ops = _kda_case(rng, rows, 2, 128, strong)
    s0 = jnp.asarray(rng.standard_normal((2, 128, 128)), jnp.float32)
    o32, s32 = _token_scan(*(x[1:101] for x in ops), s0)

    def step16(s, x):
        (o, s2) = _token_scan(*(a[None] for a in x), s.astype(jnp.float32))
        return s2.astype(jnp.bfloat16), o[0]
    s16, o16 = jax.lax.scan(step16, s0.astype(jnp.bfloat16),
                            tuple(x[1:101] for x in ops))
    assert np.abs(np.asarray(o16) - np.asarray(o32)).max() > 100 * 2e-6
    assert np.abs(np.asarray(s16, np.float32)
                  - np.asarray(s32)).max() > 100 * 2e-5


# -- (e) prefix hits are cut to a boundary whose state is held ----------------

def test_prefix_hit_is_seated_only_where_the_state_is_held(built):
    """A (96-token prompt, chunk 32) leaves snapshots at 32, 64 and 96;
    with 20 served tokens one more block (112) is published with no
    snapshot. B shares A's first 80 tokens: 5 blocks hit, the deepest
    boundary with state is 64, one block is cut. C repeats A's prompt
    and its continuation: 7 blocks hit, seated at 96, one cut. D is A's
    prompt itself: the full-prompt hit stops a chunk short, at 64."""
    cfg, model, w = built
    rng = np.random.default_rng(2)
    engine = _engine(model, prefill_chunk=32)
    # room for every snapshot (at this size a snapshot is 38 KB beside a
    # 532 KB pool: the engine's own budget would hold one)
    engine._state_snap_budget = 1 << 30
    a = rng.integers(1, VOCAB, 96)
    out_a = engine.serve([a], max_new_tokens=20)[0]
    st0 = engine.stats()
    assert st0["state_snapshots"] == 3 and st0["prefix_tokens_reused"] == 0
    assert st0["state_snapshot_bytes"] == 3 * engine._snap_nbytes
    b = np.concatenate([a[:80], rng.integers(1, VOCAB, 9)])
    c = np.concatenate([a, out_a[:18], rng.integers(1, VOCAB, 5)])
    _assert_served_exact(cfg, w, engine, a, out_a, n=20)
    for prompt, reused, cut in ((b, 64, 16), (c, 96, 16), (a, 64, 31)):
        before = engine.stats()
        engine.rows.clear()
        toks = engine.serve([prompt], max_new_tokens=5)[0]
        after = engine.stats()
        _assert_served_exact(cfg, w, engine, prompt, toks, n=5,
                             start=reused)
        assert after["prefix_tokens_reused"] \
            - before["prefix_tokens_reused"] == reused
        assert after["prefix_tokens_cut_for_state"] \
            - before["prefix_tokens_cut_for_state"] == cut
        assert after["state_snapshot_hits"] \
            - before["state_snapshot_hits"] == 1
    assert engine._state_snaps
    engine.purge_published()
    assert not engine._state_snaps
    assert engine.stats()["state_snapshot_bytes"] == 0
    engine.shutdown()


# -- (f) the snapshot byte budget ------------------------------------------------

def test_snapshots_are_budgeted_in_bytes(built):
    """With a budget of two snapshots a third boundary drops the oldest:
    the slot keeps its deepest, the table's oldest goes first, the bytes
    held never pass the budget, and a hit past a dropped boundary is
    cut. A budget smaller than one snapshot takes none and cuts a hit
    to 0 tokens."""
    cfg, model, w = built
    rng = np.random.default_rng(8)
    engine = _engine(model, prefill_chunk=32)
    # the rule in the engine: an eighth of the pool's bytes
    assert engine._state_snap_budget == pc.pool_bytes(engine._pools) // 8
    snap = engine._snap_nbytes
    engine._state_snap_budget = 2 * snap + 5
    a = rng.integers(1, VOCAB, 96)
    held = []
    rid = engine.submit(a, max_new_tokens=4)
    while engine.num_active or engine.num_queued:
        engine.step()
        held.append(engine.stats()["state_snapshot_bytes"])
    assert len(engine.run()[rid]) == 4
    st = engine.stats()
    assert max(held) == 2 * snap and st["state_snapshot_bytes"] == 2 * snap
    # boundaries 32, 64, 96 were reached; 32 was dropped for 96
    assert st["state_snapshots"] == 3 and st["state_snapshots_dropped"] == 1
    # a prompt sharing 80 tokens is seated at 64, which is held (its own
    # last chunk ends off a block boundary and leaves no snapshot)
    c = np.concatenate([a[:80], rng.integers(1, VOCAB, 9)])
    before = engine.stats()
    engine.rows.clear()
    toks = engine.serve([c], max_new_tokens=3)[0]
    after = engine.stats()
    assert after["prefix_tokens_reused"] \
        - before["prefix_tokens_reused"] == 64
    _assert_served_exact(cfg, w, engine, c, toks, n=3, start=64)
    # one sharing 48 could be seated at 32, whose state is gone: the hit
    # is cut to nothing
    b = np.concatenate([a[:48], rng.integers(1, VOCAB, 9)])
    engine.rows.clear()
    toks = engine.serve([b], max_new_tokens=3)[0]
    last = engine.stats()
    assert last["prefix_tokens_reused"] == after["prefix_tokens_reused"]
    assert last["prefix_tokens_cut_for_state"] \
        - after["prefix_tokens_cut_for_state"] == 48
    _assert_served_exact(cfg, w, engine, b, toks, n=3)
    assert last["state_snapshot_bytes"] <= 2 * snap
    engine.shutdown()
    small = _engine(model, prefill_chunk=32)
    small._state_snap_budget = snap - 1
    small.serve([a], max_new_tokens=2)
    st = small.stats()
    assert st["state_snapshots"] == 0 and st["state_snapshots_dropped"] == 3
    assert st["state_snapshot_bytes"] == 0
    small.rows.clear()
    toks = small.serve([a], max_new_tokens=2)[0]
    assert small.stats()["prefix_tokens_reused"] == 0
    _assert_served_exact(cfg, w, small, a, toks, n=2)
    small.shutdown()


# -- (g) preemption; an EOS inside the async pipeline ------------------------------

def test_preempted_request_resumes_token_exact(built):
    cfg, model, w = built
    rng = np.random.default_rng(3)
    lo, h1, h2 = (rng.integers(1, VOCAB, n) for n in (37, 9, 7))
    engine = _engine(model, num_slots=2, host_kv_tier_bytes=1 << 20)
    rids = [engine.submit(lo, 12, priority=0)]
    for _ in range(6):
        engine.step()
    rids += [engine.submit(h1, 12, priority=2),
             engine.submit(h2, 12, priority=2)]
    out = engine.run()
    st = engine.stats()
    engine.shutdown()
    assert st["preemptions"] >= 1
    assert st["kv_blocks_spilled"] == 0 and st["preempt_swap_resumes"] == 0
    assert st["preempt_recompute_resumes"] >= 1
    for p, r in zip((lo, h1, h2), rids):
        _assert_served_exact(cfg, w, engine, p, out[r], n=12)


def test_eos_inside_the_pipeline_leaves_the_seat_clean(built):
    cfg, model, w = built
    rng = np.random.default_rng(4)
    first, second = rng.integers(1, VOCAB, 21), rng.integers(1, VOCAB, 2)
    plain = _engine(model, num_slots=1)
    stream = plain.serve([first], max_new_tokens=8)[0]
    plain.shutdown()
    eos = int(stream[3])
    stop = list(stream).index(eos) + 1
    engine = _engine(model, num_slots=1, eos_token_id=eos)
    rids = [engine.submit(first, 8), engine.submit(second, 6)]
    out = engine.run()
    engine.shutdown()
    np.testing.assert_array_equal(out[rids[0]], stream[:stop])
    toks = np.asarray(out[rids[1]])
    keep = len(toks) if eos not in toks else list(toks).index(eos) + 1
    _assert_served_exact(cfg, w, engine, first, stream[:stop])
    _assert_served_exact(cfg, w, engine, second, toks[:keep])


def test_refusals_are_the_slot_state_predicate_s(built):
    _cfg, model, _w = built
    with pytest.raises(NotImplementedError, match="slot state"):
        _engine(model, num_speculative_tokens=2)
    with pytest.raises(ValueError, match="num_slots"):
        model.init_paged_caches(9, 16)
    src = _engine(model, num_slots=1)
    rid = src.submit(np.arange(1, 20), 9)
    while len(src._results.get(rid, ())) < 3:
        src.step()
    src._flush_pipe()
    assert src.export_session(0).payload is None
    src.shutdown()


# -- (h) the share test -------------------------------------------------------------

def test_shares_add_up_to_the_uncut_expert_block():
    """16 experts as 4 shares of 4: the routed parts that all the
    shares give, with the shared expert counted once, add up to the
    uncut reference's expert block."""
    whole_cfg = tiny_cfg()
    pre = "model.layers.1."
    w = {k[len(pre):]: v for k, v in fam.make_leaves(
        fam.layer_shapes(whole_cfg, 1), SEED).items()}
    x = jnp.asarray(np.random.default_rng(9).standard_normal((40, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.experts(x, w, fam._small(whole_cfg), False))
        shared = np.asarray(ref.swiglu(
            x, w["mlp.shared_experts.gate_proj.weight"],
            w["mlp.shared_experts.up_proj.weight"],
            w["mlp.shared_experts.down_proj.weight"], False))
        total = np.zeros_like(want)
        for rank in range(4):
            # every rank routes with the whole gate and holds its four
            held = slice(4 * rank, 4 * rank + 4)
            mlp = DeepseekV3MoE(SolarOpen2Config.tiny(
                expert_first=4 * rank, expert_count=4))
            assert mlp.experts.gate_up_proj.shape[0] == 4
            assert mlp.gate.weight.shape == [64, 16]
            for leaf, name in (
                    (mlp.gate.weight, "mlp.gate.weight"),
                    (mlp.gate.e_score_correction_bias,
                     "mlp.gate.e_score_correction_bias"),
                    (mlp.shared_experts.gate_proj.weight,
                     "mlp.shared_experts.gate_proj.weight"),
                    (mlp.shared_experts.up_proj.weight,
                     "mlp.shared_experts.up_proj.weight"),
                    (mlp.shared_experts.down_proj.weight,
                     "mlp.shared_experts.down_proj.weight")):
                leaf._data = w[name].astype(jnp.float32)
            mlp.experts.gate_up_proj._data = \
                w["mlp.experts.gate_up_proj"][held].astype(jnp.float32)
            mlp.experts.down_proj._data = \
                w["mlp.experts.down_proj"][held].astype(jnp.float32)
            total += np.asarray(mlp(paddle.to_tensor(x))._data) - shared
    np.testing.assert_allclose(total + shared, want, atol=2e-6)


# -- (i) the tap reader ----------------------------------------------------------------

@pytest.mark.parametrize("taps", [3, 4])
def test_tap_reader_equals_the_whole_sequence_forms(taps):
    """Packed rows of three slots (one continuing from a held state,
    one fresh over a seat with stale state, one decoding) through
    ``ragged_causal_taps``: at 3 taps inside ``Lfm2ShortConv``, against
    its own whole-sequence form; at 4 taps with silu against the
    reference's ``short_conv``."""
    rng = np.random.default_rng(10 + taps)
    c, keep = 32, taps - 1
    seqs = [rng.standard_normal((n, c)).astype(np.float32)
            for n in (9, 5, 7)]
    if taps == 3:
        paddle.seed(5)
        conv = Lfm2ShortConv(Lfm2MoeConfig.tiny(hidden=c, heads=2,
                                                kv_heads=2))
        w_in = np.asarray(conv.in_proj.weight._data)

        def taps_in(x):
            b, _c, z = np.split(x @ w_in, 3, axis=-1)
            return b * z

        def whole(x):
            return np.asarray(conv(paddle.to_tensor(x[None]))._data)[0]

        def paged(rows, state, meta):
            out, (new,) = conv.forward_paged(
                paddle.to_tensor(rows[None]),
                (pc.SlotState(jnp.asarray(state)),), meta)
            return np.asarray(out._data)[0], np.asarray(new.data)
    else:
        w = rng.standard_normal((c, taps)).astype(np.float32)

        def taps_in(x):
            return x

        def whole(x):
            return np.asarray(jax.nn.silu(ref.short_conv(
                jnp.asarray(x), jnp.asarray(w))))

        def paged(rows, state, meta):
            got, new = short_conv.ragged_causal_taps(
                jnp.asarray(rows), jnp.asarray(state), jnp.asarray(w), meta)
            return np.asarray(jax.nn.silu(got)), np.asarray(new)
    # slot 0: rows 6..8 of its sequence, the state holds what came
    # before; slot 1: all 5 rows from position 0 over a seat with stale
    # state; slot 2: its seventh row alone; the null seat last
    state = np.full((4, keep, c), 9.0, np.float32)
    state[3] = 0
    state[0] = taps_in(seqs[0])[6 - keep:6]
    state[2] = taps_in(seqs[2])[6 - keep:6]
    q_lens, base = [3, 5, 1], [6, 0, 6]
    rows = np.concatenate([seqs[0][6:], seqs[1], seqs[2][6:7],
                           np.zeros((3, c), np.float32)])
    sl, pos, rs, _ = pc.ragged_row_meta(q_lens, base, len(rows), 10 ** 6)
    got, new = paged(rows, state, (
        jnp.asarray(q_lens), jnp.asarray(rs), jnp.asarray(sl),
        jnp.asarray(pos)))
    want = np.concatenate([whole(seqs[0])[6:], whole(seqs[1]),
                           whole(seqs[2])[6:7]])
    np.testing.assert_allclose(got[:9], want, atol=1e-5)
    np.testing.assert_allclose(new[0], taps_in(seqs[0])[9 - keep:], atol=1e-6)
    np.testing.assert_allclose(new[1], taps_in(seqs[1])[5 - keep:], atol=1e-6)
    np.testing.assert_allclose(new[2], taps_in(seqs[2])[7 - keep:7],
                               atol=1e-6)
    assert not new[3].any()

"""LFM2-MoE family at a small size on the CPU: gated short-convolution
layers whose state is a row a serving SLOT beside the paged KV,
attention at a head size below a lane tile with QK-norm, the sigmoid
top-k router with every expert held, and the engine's handling of the
slot state (seat re-use, chunked prefill, prefix hits cut to a
snapshot, preemption, an EOS inside the async pipeline).

The plain reference is the benchmark's (``benchmark/reference/
lfm2_moe.py``: float32, no cache, the convolution as a sum of shifted
copies, a dense loop over the experts); weights are the benchmark's
seeded ones. Tolerances: everything here runs in float32, where the
program and the reference differ by summation order alone — logits
agree to 2e-5 and a served token's logit lies within 1e-4 of the
reference's best (bfloat16 in either place misses both by two orders
of magnitude).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.lib import check, weights
from benchmark.models import lfm2_moe as fam
from benchmark.reference import lfm2_moe as ref
from paddle_tpu.distributed import moe
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig
from paddle_tpu.ops import paged_cache as pc
from paddle_tpu.ops.pallas import paged_attention as pa

SEED = 2**31 + 11
VOCAB = 512
LAYERS = ["conv", "full_attention", "conv", "conv", "conv"]


def tiny_cfg():
    """``Lfm2MoeConfig.tiny()`` as a configuration file's dict."""
    return dict(
        model_type="lfm2_moe", vocab_size=VOCAB, hidden_size=256,
        intermediate_size=96, moe_intermediate_size=32,
        num_hidden_layers=5, num_attention_heads=4, num_key_value_heads=2,
        num_experts=8, num_experts_per_tok=2, num_dense_layers=1,
        layer_types=list(LAYERS), conv_L_cache=3, conv_bias=False,
        norm_eps=1e-5, norm_topk_prob=True, use_expert_bias=True,
        routed_scaling_factor=1, max_position_embeddings=8192,
        rope_parameters=dict(rope_theta=1000000, rope_type="default"),
        tie_word_embeddings=True, deployment=dict(expert_parallel=1))


@pytest.fixture(scope="module")
def built():
    """The program's model in float32 with the seed's weights, and the
    same weights as the reference takes them."""
    cfg = tiny_cfg()
    model = fam.build(cfg, SEED, False).to(dtype="float32")
    model.config.dtype = "float32"
    return cfg, model, weights.make(fam.leaf_shapes(cfg), SEED)


def _ref_logits(cfg, w, seq, pad_to=128):
    """The reference's logits ``[len(seq), V]`` for one sequence, a
    jitted layer at a time at one padded length (causal: the padding is
    inert), so every call shares the compiled layers."""
    small = fam._small(cfg)
    ids = np.zeros((1, pad_to), np.int32)
    ids[0, :len(seq)] = seq
    table = w["model.embed_tokens.weight"]
    with jax.default_matmul_precision("highest"):
        h = ref.embed(jnp.asarray(ids), table)
        for i, kind in enumerate(cfg["layer_types"]):
            pre = f"model.layers.{i}."
            wi = {k[len(pre):]: v for k, v in w.items()
                  if k.startswith(pre)}
            h = fam._layer(h, wi, fam._static(small), kind,
                           i < cfg["num_dense_layers"], False)
        logits = ref.head(h, w["model.embedding_norm.weight"], table,
                          small)
    return np.asarray(logits)[0, :len(seq)]


def _engine(model, **kw):
    """An engine whose tick also hands every row's logits to the test
    (``engine.rows``: per tick the rows' input ids, slots, positions
    and logits), so that what is compared is every row's logits, not
    only which token came first. Those rows come from a second pass of
    the step with the head over every row; the tick serves from its
    own pass, the head over the rows it samples (``logit_rows``)."""
    base = dict(num_slots=4, max_model_len=128, block_size=16,
                prefill_chunk=16, host_kv_tier_bytes=0)
    base.update(kw)
    engine = ServingEngine(model, ServingConfig(**base))
    engine.rows = []
    inner = engine._model_step

    def step(params, ids, *a, logit_rows=None, **kw):
        every, _pools = inner(params, ids, *a, **kw)
        _ql, _rs, slot, pos = kw["ragged_meta"][:4]
        jax.debug.callback(
            lambda *x: engine.rows.append(tuple(map(np.asarray, x))),
            ids[0], slot, pos, every[0])
        return inner(params, ids, *a, logit_rows=logit_rows, **kw)

    engine._model_step = step
    return engine


def _assert_served_exact(cfg, w, engine, prompt, toks, n=None,
                         start=0):
    """Every logit the engine computed for this request — the prompt's
    rows from ``start`` (what a prefix hit skipped has no row) and a
    row for each served token but the last — equals the reference's
    full forward over prompt + served tokens; and every served (greedy)
    token is the reference's best at its position."""
    assert len(toks) == (n or len(toks)) and len(toks)
    seq = np.concatenate([prompt, toks]).astype(np.int64)
    want = _ref_logits(cfg, w, seq)
    rows = want[len(prompt) - 1:len(prompt) - 1 + len(toks)]
    assert check.gaps_below_best(rows, toks).max() < 1e-4
    # the request's rows, by the slot they rode in: runs of consecutive
    # positions whose input ids are this sequence's
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
    """Positions of ``run`` (consecutive rows of one slot) if its ids
    are ``seq``'s at those positions — then its logits must be the
    reference's — else nothing: another request's rows."""
    if not run:
        return set()
    pos = np.asarray([p for p, _i, _l in run])
    if pos[-1] >= len(seq) or any(seq[pos] != [i for _p, i, _l in run]):
        return set()
    np.testing.assert_allclose(np.stack([l for _p, _i, l in run]),
                               want[pos], atol=2e-5)
    return set(pos.tolist())


def _ticks(engine):
    return [e["args"] for e in engine._trace.events()
            if e["name"] == "tick" and e["tid"] == 0]


# -- (a) the whole-sequence form ---------------------------------------------

def test_tiny_config_is_the_file_form(built):
    cfg, model, _w = built
    want = Lfm2MoeConfig.tiny(dtype="float32", initializer_range=0.0)
    assert model.config == want
    assert want.head_dim == 64 and want.layer_types == tuple(LAYERS)


def test_full_forward_matches_reference(built):
    cfg, model, w = built
    ids = np.random.default_rng(0).integers(1, VOCAB, 100)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
    np.testing.assert_allclose(got, _ref_logits(cfg, w, ids), atol=2e-5)


# -- (b) (c) the engine: chunked prefill, decoding, re-used seats -------------

# five prompts on four seats admitted at different ticks (61 and 40
# take several chunks), then two short ones that take seats others
# left: their first rows read the seat's state
PROMPTS = (40, 7, 61, 23, 16, 2, 3)


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


def test_engine_keeps_two_kinds_of_state(built, served):
    cfg, model, _w = built
    _prompts, _outs, st, ticks, pools, _engine_ = served
    # the tick, and the gather that keeps a seat's state where a chunk
    # ended on a block boundary
    assert st["executables_compiled"] == 2 and st["kernel_fallbacks"] == 0
    kinds = [pc.is_slot_state(layer) for layer in pools]
    assert kinds == [k == "conv" for k in LAYERS]
    # [num_slots + 1, L - 1, hidden] a conv layer; the null seat (the
    # last row) is never written
    for layer in pools:
        if pc.is_slot_state(layer):
            assert layer[0].shape == (5, 2, 256)
            assert not np.asarray(layer[0].data[4]).any()
    assert st["state_bytes"] == 4 * 5 * 2 * 256 * 4
    assert st["state_bytes"] == pc.state_bytes(pools)
    assert pc.first_paged(pools) is pools[1]
    # the attention layer's pool is FLAT, the layout the cell runs: two
    # kv heads of 64 lanes side by side in one 128-lane row
    assert all(a.ndim == 3 and a.shape[1:] == (16, 128) for a in pools[1])
    # the pool's bytes and the bytes a position costs are the paged
    # layer's alone: 2 (k, v) x 2 heads x 64 x 4 B
    assert st["kv_pool_bytes"] == pc.pool_bytes(pools) \
        == sum(int(a.nbytes) for a in pools[1])
    # every request began its seat from zeros; every tick says how many
    # seats' state it wrote, and the expert share reports all pairs
    assert st["state_seats_started"] == len(PROMPTS)
    assert all(0 < t["state_seats"] <= 4 for t in ticks)
    assert st["moe_pairs_local"] == 2 * st["moe_rows"] > 0


def test_walkers_pass_slot_state_layers_over(built):
    _cfg, model, _w = built
    pools = model.init_paged_caches(9, 16, num_slots=3)
    pools = [tuple(pc.SlotState(jnp.full(a.shape, 7.0, a.dtype))
                   if isinstance(a, pc.SlotState)
                   else jnp.arange(a.size, dtype=a.dtype).reshape(a.shape)
                   for a in layer) for layer in pools]
    copied = pc.copy_blocks(pools, jnp.int32(2), jnp.int32(5))
    ids = jnp.asarray([2, 5], jnp.int32)
    payload = pc.export_blocks(copied, ids)
    back = pc.import_blocks(pools, jnp.asarray([3, 4], jnp.int32), payload)
    stacked = pc.export_stacked(copied, ids)
    layout = pc.stacked_layout(copied)
    for i, kind in enumerate(LAYERS):
        if kind == "conv":
            assert copied[i] is pools[i] and back[i] is pools[i]
            assert payload[i] == () and layout[i] == ()
        else:
            np.testing.assert_array_equal(copied[i][0][5], pools[i][0][2])
            np.testing.assert_array_equal(back[i][1][4], pools[i][1][2])
    assert len(stacked) == 1 and stacked[0].shape[:2] == (2, 2)
    assert pc.payload_nbytes(pc.payload_to_host(payload)) \
        == 2 * 2 * 16 * 2 * 64 * 4
    snap = pc.export_slot_state(pools, jnp.int32(1))
    assert snap.shape == (4, 2, 256)
    seated = pc.import_slot_state(pools, jnp.int32(2), snap * 0 + 3.0)
    assert float(seated[0][0].data[2].max()) == 3.0
    assert float(seated[0][0].data[1].min()) == 7.0
    assert seated[1] is pools[1]


# -- (d) prefix hits are cut to a boundary whose state is held ----------------

def test_prefix_hit_is_seated_only_where_the_state_is_held(built):
    """A (48-token prompt, chunk 16) leaves snapshots at 16, 32 and 48
    and publishes 3 prompt blocks and, with 20 served tokens, one more
    whose boundary (64) has no snapshot. B shares A's first 40 tokens:
    2 blocks hit, both with state. C repeats A's prompt and its
    continuation: 4 blocks hit, the deepest boundary with a snapshot is
    48, one block is cut. D is A's prompt itself: the full-prompt hit
    stops a block short, at 32, since the state at 48 is of no use to
    the row that recomputes position 47."""
    cfg, model, w = built
    rng = np.random.default_rng(2)
    engine = _engine(model)
    a = rng.integers(1, VOCAB, 48)
    out_a = engine.serve([a], max_new_tokens=20)[0]
    st0 = engine.stats()
    assert st0["state_snapshots"] == 3 and st0["prefix_tokens_reused"] == 0
    b = np.concatenate([a[:40], rng.integers(1, VOCAB, 9)])
    c = np.concatenate([a, out_a[:18], rng.integers(1, VOCAB, 5)])
    _assert_served_exact(cfg, w, engine, a, out_a, n=20)
    for prompt, reused, cut in ((b, 32, 0), (c, 48, 16), (a, 32, 15)):
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
    # what is evicted or purged takes its snapshot along
    assert engine._state_snaps
    engine.purge_published()
    assert not engine._state_snaps
    before = engine.stats()
    engine.serve([a], max_new_tokens=2)
    assert engine.stats()["prefix_tokens_reused"] \
        == before["prefix_tokens_reused"]
    engine.shutdown()


# -- (e) preemption resumes by recompute ---------------------------------------

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
    # the host tier was never offered a block, the victim recomputed
    assert st["kv_blocks_spilled"] == 0 and st["preempt_swap_resumes"] == 0
    assert st["preempt_recompute_resumes"] >= 1
    for p, r in zip((lo, h1, h2), rids):
        _assert_served_exact(cfg, w, engine, p, out[r], n=12)


# -- (f) an EOS inside the async pipeline ---------------------------------------

def test_eos_inside_the_pipeline_leaves_the_seat_clean(built):
    """One seat; the first request ends on an EOS the host learns of a
    tick late, so the tick already launched carries its ``done`` row,
    which must not touch the state the next occupant starts from."""
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


def test_speculation_over_slot_state_is_refused(built):
    _cfg, model, _w = built
    with pytest.raises(NotImplementedError, match="slot state"):
        _engine(model, num_speculative_tokens=2)
    with pytest.raises(ValueError, match="num_slots"):
        model.init_paged_caches(9, 16)


def test_sessions_and_handoffs_leave_without_a_payload(built):
    """A migrated session and a disaggregated handoff carry no payload
    for a model with slot state, and the importing engine recomputes."""
    cfg, model, w = built
    rng = np.random.default_rng(5)
    p = rng.integers(1, VOCAB, 19)
    src, dst = _engine(model, num_slots=1), _engine(model, num_slots=1)
    rid = src.submit(p, 9)
    while len(src._results.get(rid, ())) < 3:
        src.step()
    src._flush_pipe()
    head = list(src._results[rid])
    rec = src.export_session(0)
    assert rec.payload is None
    rid2 = dst.admit_migrated(rec)
    tail = dst.run()[rid2]
    whole = np.asarray(head + list(tail))
    _assert_served_exact(cfg, w, src, p, whole[:len(head)])
    _assert_served_exact(cfg, w, dst, p, whole, n=9)
    src.shutdown()
    dst.shutdown()
    pre = _engine(model, num_slots=1, role="prefill")
    dec = _engine(model, num_slots=1)
    pre.submit(p, 9)
    while not pre._handoff_ready:
        pre.step()
    (handed,) = pre.pop_prefilled()
    assert handed.payload is None
    rid3 = dec.admit_prefilled(handed)
    tail = dec.run()[rid3]
    _assert_served_exact(
        cfg, w, dec, p, np.asarray([handed.first_token] + list(tail)), n=9)
    pre.shutdown()
    dec.shutdown()


# -- (g) the router --------------------------------------------------------------

def test_gate_with_one_group_is_a_plain_biased_topk():
    rng = np.random.default_rng(6)
    logits = rng.normal(size=(33, 8)).astype(np.float32)
    bias = (0.3 * rng.normal(size=8)).astype(np.float32)
    idx, wgt = moe.group_limited_gate(
        jnp.asarray(logits), jnp.asarray(bias), n_group=1, topk_group=1,
        top_k=2, norm_topk_prob=True, routed_scaling_factor=1.0, eps=1e-6)
    s = 1.0 / (1.0 + np.exp(-logits.astype(np.float64)))
    for row in range(33):
        order = sorted(range(8), key=lambda e: (-(s[row, e] + bias[e]), e))
        top = order[:2]
        assert list(np.asarray(idx[row])) == top
        want = s[row, top] / (s[row, top].sum() + 1e-6)
        np.testing.assert_allclose(np.asarray(wgt[row]), want, rtol=1e-6)
    # the default epsilon is the DeepSeek-V3 lineage's, bit for bit
    kw = dict(n_group=4, topk_group=2, top_k=3)
    _i, w_default = moe.group_limited_gate(jnp.asarray(logits),
                                           jnp.asarray(bias), **kw)
    _i, w_named = moe.group_limited_gate(jnp.asarray(logits),
                                         jnp.asarray(bias), eps=1e-20, **kw)
    np.testing.assert_array_equal(np.asarray(w_default),
                                  np.asarray(w_named))


# -- (h) the ragged kernel at head size 64 ---------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_ragged_kernel_reads_two_heads_a_tile_at_head_size_64(dtype, tol):
    """Interpreted Pallas against the XLA mirror: 8 query heads over 4
    kv heads of 64 lanes run as 8 over 2 tiles of 128, on a FLAT pool
    (a position's heads side by side in one row); the step's write
    lands each head's row in its lanes."""
    rng = np.random.default_rng(7)
    h, hkv, d, bs, nb, mb, s = 8, 4, 64, 16, 24, 5, 3
    q_lens = np.asarray([1, 9, 0], np.int32)
    base = np.asarray([37, 20, 0], np.int32)
    r = 12
    _slot, _pos, starts, _last = pc.ragged_row_meta(q_lens, base, r, mb * bs)
    row_slot, row_pos = jnp.asarray(_slot), jnp.asarray(_pos)
    q = jnp.asarray(rng.normal(size=(r, h, d)), dtype)
    plain = [jnp.asarray(rng.normal(size=(nb, bs, hkv, d)), dtype)
             for _ in range(2)]
    flat = [p.reshape(nb, bs, hkv * d) for p in plain]
    assert [p.shape for p in pc.init_flat_pool(nb, bs, hkv, d, dtype)] \
        == [p.shape for p in flat]
    with pytest.raises(ValueError, match="128-lane"):
        pc.init_flat_pool(nb, bs, 3, d, dtype)
    new = [jnp.asarray(rng.normal(size=(r, hkv, d)), dtype)
           for _ in range(2)]
    tables = jnp.asarray(rng.permutation(np.arange(1, nb))[:s * mb]
                         .reshape(s, mb), jnp.int32)
    assert pa.flat_pool_tile(d) == 128 == pa.flat_pool_tile(128)
    assert pa._kernel_eligible(h, d, dtype, flat[0], flat_ok=True)
    assert not pa._kernel_eligible(h, d, dtype, flat[0])
    assert not pa._kernel_eligible(h, d, dtype, plain[0], flat_ok=True)
    meta = (jnp.asarray(q_lens), jnp.asarray(starts), row_slot, row_pos,
            jnp.arange(1), jnp.arange(16))
    # the step on the CPU: the mirror over the flat pool equals the
    # step over the plain one, and so do the rows it wrote
    want, kp, vp = pa.ragged_attention_step(
        q, *new, *plain, tables, jnp.asarray(base), *meta)
    got, kp2, vp2 = pa.ragged_attention_step(
        q, *new, *flat, tables, jnp.asarray(base), *meta)
    np.testing.assert_array_equal(np.asarray(kp2, np.float32),
                                  np.asarray(kp.reshape(kp2.shape),
                                             np.float32))
    np.testing.assert_array_equal(np.asarray(vp2, np.float32),
                                  np.asarray(vp.reshape(vp2.shape),
                                             np.float32))
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))
    kern = pa.pallas_ragged_paged_attention(
        q, kp2, vp2, tables, jnp.asarray(base + 1), jnp.asarray(q_lens),
        jnp.asarray(starts), w_max=16, interpret=True)
    live = np.asarray(_pos) < mb * bs
    assert kern.shape == (r, h, d) and live.sum() == 10
    np.testing.assert_allclose(
        np.asarray(kern, np.float32)[live],
        np.asarray(want, np.float32)[live], atol=tol, rtol=tol)

"""DeepSeek-V3 family at a small size on the CPU: latent (MLA) attention
and its paged cache, YaRN, the sigmoid group-limited gate, one chip's
expert-parallel share, the arity-free pool walkers.

The plain reference is the benchmark's (``benchmark/reference/
deepseek_v3.py``: float32, non-absorbed attention, dense loop over the
experts held, no cache); weights are the benchmark's seeded ones.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from benchmark.lib import check, weights
from benchmark.models import deepseek_v3 as fam
from benchmark.reference import deepseek_v3 as ref
from paddle_tpu.distributed import moe
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.models import deepseek_v3 as dsv3
from paddle_tpu.ops import paged_cache as pc
from paddle_tpu.ops.pallas import paged_attention as pa

SEED = 2**31 + 5
YARN = dict(beta_fast=32, beta_slow=1, factor=64, mscale=1, mscale_all_dim=1,
            original_max_position_embeddings=64, rope_type="yarn")


def tiny_cfg(held=4, ranks=4, rank=1, layers=3):
    """A configuration file's dict: 16 experts over ``ranks`` chips."""
    return dict(
        model_type="deepseek_v3", vocab_size=512, hidden_size=64,
        intermediate_size=96, moe_intermediate_size=32,
        num_hidden_layers=layers, num_attention_heads=4, q_lora_rank=32,
        kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
        v_head_dim=24, n_shared_experts=1, n_routed_experts=held,
        num_experts_per_tok=4, n_group=4, topk_group=2, norm_topk_prob=True,
        routed_scaling_factor=2.5, first_k_dense_replace=1,
        max_position_embeddings=8192, rms_norm_eps=1e-6, rope_theta=100000,
        rope_scaling=dict(YARN), tie_word_embeddings=False,
        deployment=dict(expert_parallel=ranks, rank=rank))


@pytest.fixture(scope="module")
def built():
    """The program's model in float32 with the seed's weights, and the
    same weights as the reference takes them."""
    cfg = tiny_cfg()
    model = fam.build(cfg, SEED, False).to(dtype="float32")
    model.config.dtype = "float32"
    return cfg, model, weights.make(fam.leaf_shapes(cfg), SEED)


def _ref_logits(cfg, w, seq, pad_to=128):
    """The reference's logits ``[len(seq), V]`` for one sequence, run a
    jitted layer at a time at one padded length (causal: the padding is
    inert), so every call shares two compiled layers."""
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
                           i < cfg["first_k_dense_replace"], False)
        logits = ref.head(h, w["model.norm.weight"], w["lm_head.weight"],
                          small)
    return np.asarray(logits)[0, :len(seq)]


def test_full_forward_matches_reference(built):
    cfg, model, w = built
    ids = np.random.default_rng(0).integers(1, 512, 100)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model(paddle.to_tensor(ids[None]))._data)[0]
    np.testing.assert_allclose(got, _ref_logits(cfg, w, ids), atol=2e-5)


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


def test_chunked_prefill_then_decode_through_latent_cache_matches_reference(
        built, served):
    """Every served (greedy) token is the reference's best at its
    position of the reference's full forward, and the reference's logit
    of it lies within rounding of the best."""
    cfg, model, w = built
    prompts, outs, st, _ticks = served
    assert st["executables_compiled"] == 1 and st["kernel_fallbacks"] == 0
    assert st["latent_pool_bytes"] == pc.pool_bytes(
        model.init_paged_caches(1 + 4 * 8, 16))
    for p, toks in zip(prompts, outs):
        assert len(toks) == 6
        logits = _ref_logits(cfg, w, np.concatenate([p, toks]))
        rows = logits[len(p) - 1:len(p) - 1 + len(toks)]
        assert check.gaps_below_best(rows, toks).max() < 1e-4


def test_engine_counts_the_share_it_holds(served):
    """``moe_rows`` / ``moe_pairs_local`` in ``stats()`` and
    ``moe_pairs`` / ``moe_touched`` on the ``tick`` span: live rows
    only, summed over the two expert layers."""
    _prompts, _outs, st, ticks = served
    # every prompt row and 5 decode rows a request, two expert layers
    assert st["moe_rows"] == 2 * (sum(PROMPTS) + 5 * len(PROMPTS))
    assert 0 < st["moe_pairs_local"] < st["moe_rows"] * 4
    assert st["moe_grouped_mm_kernel"] == "ragged_dot"     # the CPU's
    assert sum(t["moe_pairs"] for t in ticks) == st["moe_pairs_local"]
    assert all(0 <= t["moe_touched"] <= 2 * 4 for t in ticks)
    assert all(t["moe_hot"] <= t["moe_pairs"] for t in ticks)
    assert max(t["moe_touched"] for t in ticks) == 8
    assert all(t["attn_units"] >= t["attn_live"] > 0 for t in ticks)


def test_absorbed_attention_equals_non_absorbed(built):
    """The layer's cache path (absorbed: ``q W_UK`` against the cached
    latent, ``W_UV`` after the softmax) gives what its no-cache path
    (per-head K and V expanded from the latent) gives."""
    _cfg, model, _w = built
    attn = model.model.layers[1].self_attn
    t, bs = 48, 16
    x = paddle.to_tensor(np.random.default_rng(2).standard_normal(
        (1, t, 64)).astype(np.float32))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(attn(x)._data)
        cache = pc.init_latent_pool(1 + t // bs, bs, 32 + 8, jnp.float32)
        tables = jnp.arange(1, 1 + t // bs, dtype=jnp.int32)[None]
        got, cache = attn.forward_paged(
            x, cache, paddle.to_tensor(tables),
            paddle.to_tensor(jnp.zeros((1,), jnp.int32)), None)
    np.testing.assert_allclose(np.asarray(got._data), want, atol=2e-5)
    # the cache holds (c_kv, k_pe) and zero pad lanes, nothing per head
    pool = np.asarray(cache[0]._data)
    assert pool.shape == (1 + t // bs, bs, 128)
    assert np.abs(pool[1:, :, :40]).min() > 0 and not pool[..., 40:].any()


def _latent_tq(heads, dtype):
    """Window tokens a latent query tile holds at this head count."""
    return pa._ragged_geometry(1, 1, heads, dtype, 16, 1,
                               pa.LATENT_TILE)[1]


# (q_lens, context of each slot's first row) as functions of ``tq``,
# the window tokens a tile holds (8 at 64 bf16 heads, 64 at 8 float32)
_LATENT_TICKS = {
    # a decode row, a chunk, an idle slot, a long context
    "mixed": lambda tq: ([1, 37, 0, 1], [100, 40, 0, 333]),
    # one-token slots (the narrow rung) whose context ends inside the
    # first block, a position short of a kv tile, on its edge, a
    # position past it and three tiles on inside a block; an idle slot
    # between them
    "one_token": lambda tq: ([1, 1, 0, 1, 1, 1],
                             [1, 511, 0, 512, 513, 1500]),
    # a chunk of k * tq + 1 rows (the mirror takes ONE wide slot a
    # tick, as the engine schedules): its LAST tile holds one token at
    # ``row0 > 0``, so the narrow rung's causal bound is the tile's
    # first row's; an idle slot and a decode row past a kv tile beside
    "chunk_tail": lambda tq: ([1, 2 * tq + 1, 0, 1], [513, 509, 0, 77]),
    # a chunk of a few rows: a partial tile, on the wide rung
    "chunk_partial": lambda tq: ([1, 0, min(5, tq - 1), 1],
                                 [1500, 0, 700, 512]),
}


@pytest.mark.parametrize("tick", sorted(_LATENT_TICKS))
@pytest.mark.parametrize("dtype,heads,tol", [(jnp.float32, 8, 1e-5),
                                             (jnp.bfloat16, 64, 2e-2)])
def test_latent_kernel_matches_mirror_at_key_width_576(dtype, heads, tol,
                                                       tick):
    """The Pallas kernel under the interpreter against its XLA mirror:
    key width 576 (640 lanes in the pool), value = the first 512,
    ragged lengths; tiles that hold one token take the kernel's narrow
    rung, the others its full tile."""
    rng = np.random.default_rng(3)
    tq = _latent_tq(heads, dtype)
    q_lens, ctx = (np.asarray(a) for a in _LATENT_TICKS[tick](tq))
    s, bs, width, vdim = len(q_lens), 16, 576, 512
    w = int(max(q_lens.max(), 40))
    mb = -(-int((ctx + q_lens).max()) // bs) + 1
    r = -(-int(q_lens.sum() + 8) // 8) * 8
    nb = 1 + s * mb
    (pool,) = pc.init_latent_pool(nb, bs, width, dtype)
    lanes = pool.shape[2]
    assert lanes == 640
    pool = jnp.asarray(rng.standard_normal((nb, bs, lanes)) * 0.5,
                       dtype).at[..., width:].set(0)
    tables = jnp.asarray(rng.permutation(np.arange(1, nb))
                         .reshape(s, mb), jnp.int32)
    starts = np.concatenate([[0], np.cumsum(q_lens)[:-1]])
    row_slot = np.zeros(r, np.int32)
    live = np.zeros(r, bool)
    for i, (a, n) in enumerate(zip(starts, q_lens)):
        row_slot[a:a + n], live[a:a + n] = i, True
    q = jnp.asarray(rng.standard_normal((r, heads, lanes)), dtype) \
        .at[..., width:].set(0)
    args = (tables, jnp.asarray(ctx, jnp.int32),
            jnp.asarray(q_lens, jnp.int32), jnp.asarray(starts, jnp.int32))
    got = pa.pallas_ragged_latent_attention(q, pool, *args, w, vdim, 0.1,
                                            interpret=True)
    want = pa.ragged_latent_attention(
        q, pool, *args, jnp.asarray(row_slot), jnp.arange(1),
        jnp.arange(w), vdim, 0.1)
    assert got.shape == (r, heads, vdim)
    np.testing.assert_allclose(np.asarray(got, np.float32)[live],
                               np.asarray(want, np.float32)[live], atol=tol)
    assert not np.asarray(got, np.float32)[~live].any()


def test_latent_narrow_count_is_the_kernels_own_choice():
    """``ragged_grid_units(..., narrow=True)``'s fourth value, tile by
    tile against what the wrapper's own ``_ragged_tiles`` call hands
    the kernel (a tile whose live tokens fit the first rung walks its
    kv tiles narrow); ``units`` / ``live`` / ``copies`` are the same
    with and without it, and a non-latent geometry's are untouched."""
    geo = dict(rows=544, w_max=512, num_heads=64, num_kv_heads=1,
               q_dtype=jnp.bfloat16, block_size=16, max_blocks=512,
               latent=True, streams=1)
    q_lens = np.zeros(32, np.int64)
    ctx = np.zeros(32, np.int64)
    q_lens[:6] = (1, 17, 1, 0, 3, 1)
    ctx[:6] = (1000, 257, 4096, 0, 600, 512)
    three = pa.ragged_grid_units(q_lens, ctx, **geo)
    units, live, copies, narrow = pa.ragged_grid_units(
        q_lens, ctx, narrow=True, **geo)
    assert (units, live, copies) == three
    _, tq, kb, n_tiles, n_kv = pa._ragged_geometry(
        544, 32, 64, jnp.bfloat16, 16, 512, pa.LATENT_TILE)
    rungs = pa._tile_rungs(tq)
    assert rungs == (1, 8)
    _, _, held, kv = (np.asarray(a) for a in pa._ragged_tiles(
        jnp, jnp.asarray(q_lens, jnp.int32), jnp.asarray(ctx, jnp.int32),
        tq, kb * 16, n_tiles, n_kv))
    assert narrow == sum(int(k) for h, k in zip(held, kv) if h <= rungs[0])
    # slots 0, 2, 5 and the 17-row chunk's last tile (row 16: one token)
    assert narrow == 2 + 8 + 1 + -(-(257 + 16) // 512)
    assert live == narrow + 2 * 1 + -(-(600 + 2) // 512)
    # the other kernel's geometry: units and live are the values the
    # parent of this count gave (296, 280, computed there); its copies
    # (1120 there) lose what the five decode rows' narrow walks no
    # longer read past their reach: 1 + 0 + 7 + 3 + 2 blocks, K and V
    gqa = dict(rows=136, w_max=128, num_heads=28, num_kv_heads=4,
               q_dtype=jnp.bfloat16, block_size=16, max_blocks=64)
    q8, c8 = np.array([1, 128, 0, 1, 1, 0, 1, 1]), \
        np.array([100, 300, 0, 1024, 5, 0, 77, 600])
    assert pa.ragged_grid_units(q8, c8, **gqa) == (296, 280,
                                                   1120 - 13 * 2)


def _narrow_walk_copies(table, seen, bs, nkb):
    """The pool blocks ``_ragged_kernel``'s narrow walk starts copies of
    for one tile a stream, kv tile by kv tile as its loops start them,
    for a token that sees ``seen`` positions through ``table``."""
    blocks = min(-(-seen // bs), len(table))
    return [table[j * nkb + i] for j in range(-(-blocks // nkb))
            for i in range(min(blocks - j * nkb, nkb))]


@pytest.mark.parametrize("geo,q_lens,ctx", [
    # the Qwen cells' call: decode rows (one at the table's last
    # entry), a 40-row chunk whose last tile holds one token, dead slots
    (dict(rows=136, w_max=128, num_heads=28, num_kv_heads=4),
     [1, 1, 0, 40, 1, 0, 3, 1], [6, 512, 0, 25, 1024, 78, 500, 1000]),
    # the cross layers' call: one row a slot, 10 paired lane tiles in
    # one head group, every tile walked narrow
    (dict(rows=96, w_max=1, num_heads=40, num_kv_heads=10),
     [1] * 95 + [0], [1, 15, 16, 17, 511, 512, 513, 1541] * 11 + [900] * 7
     + [0]),
    # the full layer's call: the same pool and heads, one-row slots at
    # the narrow walk's edges beside a 256-row chunk and an empty slot;
    # a one-token tile walks the whole row in one group, a chunk tile in
    # five groups of two lane tiles
    (dict(rows=352, w_max=256, num_heads=40, num_kv_heads=10),
     [256] + [1] * 94 + [0], [300] + [1, 15, 16, 17, 1023, 1024, 1025,
                                     1000] * 11 + [900] * 6 + [0]),
], ids=["qwen2", "phi4_cross", "phi4_full"])
def test_ragged_narrow_count_is_the_kernels_own_choice(geo, q_lens, ctx):
    """The paged geometry's ``ragged_grid_units(..., narrow=True)``:
    ``attn_narrow`` is the live units of the tiles the wrapper's own
    ``_ragged_tiles`` call hands the kernel with one live token (the
    rung ``_tile_rungs`` chooses), and ``attn_copies`` the block copies
    the narrow walk starts — the blocks of the token's reach, none past
    it, once for the whole pool row — beside whole kv tiles for every
    other tile once a ``_GROUP_LANES`` head group, a stream each."""
    geo = dict(geo, q_dtype=jnp.bfloat16, block_size=16, max_blocks=64)
    q_lens, ctx = np.asarray(q_lens), np.asarray(ctx)
    units, live, copies, narrow = pa.ragged_grid_units(
        q_lens, ctx, narrow=True, **geo)
    assert (units, live, copies) == pa.ragged_grid_units(q_lens, ctx,
                                                         **geo)
    hkv, bs, mb = geo["num_kv_heads"], 16, 64
    _, tq, kb, n_tiles, n_kv = pa._ragged_geometry(
        geo["rows"], len(q_lens), geo["num_heads"] // hkv, jnp.bfloat16,
        bs, mb)
    slot, row0, held, kv = (np.asarray(a) for a in pa._ragged_tiles(
        jnp, jnp.asarray(np.minimum(q_lens, geo["w_max"]), jnp.int32),
        jnp.asarray(ctx, jnp.int32), tq, kb * bs, n_tiles, n_kv))
    one = held <= pa._tile_rungs(tq)[0]
    assert narrow == int(kv[one].sum()) * hkv
    table = list(range(mb))
    nkb = pa._narrow_blocks(bs, mb)
    narrow_blocks = whole_blocks = 0
    for s_, r0, h_, k_ in zip(slot, row0, held, kv):
        if not h_:
            continue
        if h_ == 1:
            seen = int(ctx[s_] + r0)
            walked = _narrow_walk_copies(table, seen, bs, nkb)
            assert max(walked) == min(-(-seen // bs), mb) - 1  # its reach
            narrow_blocks += len(walked)
        else:
            whole_blocks += int(k_) * kb
    rungs = pa._tile_rungs(tq) if geo["w_max"] > 1 else (1,)
    heads = pa._rung_heads(hkv, 128, rungs)
    groups = (hkv // heads[0], hkv // heads[-1])
    assert copies == (narrow_blocks * groups[0]
                      + whole_blocks * groups[1]) * 2
    # a one-token tile walks the whole pool row as one group; a tile of
    # more takes 2 lane tiles a group of the 10-tile row (5 groups)
    assert groups[0] == 1
    if geo["w_max"] == 1:
        assert live == narrow and not whole_blocks
    else:
        assert groups[1] == {4: 1, 10: 5}[hkv] and whole_blocks


def test_full_layer_count_keeps_its_units_and_cuts_the_narrow_copies():
    """The ``tick`` span's counts for the full layer's call (the
    ``phi4_full`` mix above): ``attn_units``, ``attn_live`` and
    ``attn_narrow`` — the inputs of ``ragged_grid_live_share`` — are
    the values the call gave while its group was chosen once a call,
    and ``attn_copies`` loses four fifths of the one-token tiles' part:
    their 3,202 blocks a stream leave HBM once as the whole row where
    five 256-lane groups took them five times (41,940 copies then)."""
    geo = dict(rows=352, w_max=256, num_heads=40, num_kv_heads=10,
               q_dtype=jnp.bfloat16, block_size=16, max_blocks=64)
    q_lens = [256] + [1] * 94 + [0]
    ctx = [300] + [1, 15, 16, 17, 1023, 1024, 1025, 1000] * 11 + [900] * 6
    units, live, copies, narrow = pa.ragged_grid_units(
        np.asarray(q_lens), np.asarray(ctx + [0]), narrow=True, **geo)
    assert (units, live, narrow) == (5820, 5680, 4440)
    assert copies == 41940 - 2 * (5 - 1) * 3202 == 16324


def test_latent_grid_units_follow_the_kernels_tiles():
    """``attn_units`` / ``attn_live`` for a latent pool: one "kv head",
    a tile of ``LATENT_TILE[0] / 64`` window tokens, kv tiles of
    ``LATENT_TILE[1]`` positions that end at the slot's length."""
    geo = dict(rows=544, w_max=512, num_heads=64, num_kv_heads=1,
               q_dtype=jnp.bfloat16, block_size=16, max_blocks=512,
               latent=True)
    q_lens = np.zeros(32, np.int64)
    ctx = np.zeros(32, np.int64)
    q_lens[:3], ctx[:3] = (1, 512, 1), (1000, 257, 4096)
    units, live, copies = pa.ragged_grid_units(q_lens, ctx, streams=1,
                                               **geo)
    tq, span = pa.LATENT_TILE[0] // 64, pa.LATENT_TILE[1]
    assert copies == live * (span // 16)    # a whole block, one array
    chunk = sum(-(-(257 + r0 + tq - 1) // span)
                for r0 in range(0, 512, tq))
    assert live == -(-1000 // span) + chunk + 4096 // span
    n_tiles = 32 + -(-544 // tq)
    assert units == live + n_tiles - (2 + 512 // tq)


def test_yarn_frequencies_and_softmax_scale_are_the_published_ones():
    """Hand-worked at the published numbers (rope 64 lanes, theta 1e5,
    factor 64, original context 4096, beta 32 / 1): pairs 0-8 keep
    their frequency, pairs 19-31 are interpolated, between them a
    linear ramp; ``m^2 = (0.1 ln 64 + 1)^2`` on the softmax scale."""
    sc = dict(YARN, original_max_position_embeddings=4096)
    inv, factor = dsv3.yarn_inv_freq(64, 100000.0, sc)
    low = 64 * math.log(4096 / (32 * 2 * math.pi)) / (2 * math.log(1e5))
    high = 64 * math.log(4096 / (1 * 2 * math.pi)) / (2 * math.log(1e5))
    assert (math.floor(low), math.ceil(high)) == (8, 19)
    base = [1e5 ** (-2 * i / 64) for i in range(32)]
    want = [base[i] if i <= 8 else base[i] / 64 if i >= 19 else
            base[i] / 64 * (i - 8) / 11 + base[i] * (1 - (i - 8) / 11)
            for i in range(32)]
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert factor == 1.0            # m(mscale) / m(mscale_all_dim)
    cfg = dsv3.DeepseekV3Config(rope_scaling=sc, qk_nope_head_dim=128,
                                qk_rope_head_dim=64)
    m = 0.1 * math.log(64) + 1
    assert dsv3.yarn_softmax_scale(cfg) == pytest.approx(
        m * m / math.sqrt(192), rel=1e-12)
    assert dsv3.yarn_softmax_scale(cfg) == pytest.approx(0.1446796, rel=1e-6)
    # a rotation past the original context: lane pair (2i, 2i + 1) of
    # the published interleaved layout turns by 5000 * inv[i]
    x = np.zeros((1, 64), np.float32)
    x[0, 2 * 13] = 1.0
    got = np.asarray(dsv3._rope_lanes(jnp.asarray(x), jnp.asarray([5000]),
                                      inv, factor))[0]
    ang = np.float32(5000.0) * np.float32(want[13])
    assert got[13] == pytest.approx(math.cos(ang), abs=1e-6)       # halves:
    assert got[32 + 13] == pytest.approx(math.sin(ang), abs=1e-6)  # i, 32 + i
    np.testing.assert_allclose(
        ref.inv_frequencies(dict(qk_rope_head_dim=64, rope_theta=100000,
                                 rope_scaling=sc))[0], inv, rtol=1e-7)


def _loop_gate(scores, bias, n_group, topk_group, top_k, scaling):
    """Group-limited top-k, one token at a time in plain Python."""
    idx, wts = [], []
    for s in np.asarray(scores, np.float64):
        choice = s + np.asarray(bias, np.float64)
        per = len(s) // n_group
        group = [sum(sorted(choice[g * per:(g + 1) * per])[-2:])
                 for g in range(n_group)]
        kept = sorted(range(n_group), key=lambda g: (-group[g], g))
        kept = set(kept[:topk_group])
        cand = [(choice[e] if e // per in kept else 0.0, e)
                for e in range(len(s))]
        chosen = [e for _c, e in sorted(cand, key=lambda c: (-c[0], c[1]))
                  ][:top_k]
        total = sum(s[e] for e in chosen) + 1e-20
        idx.append(chosen)
        wts.append([s[e] / total * scaling for e in chosen])
    return np.asarray(idx), np.asarray(wts)


def test_gate_matches_a_loop_written_group_limited_topk():
    rng = np.random.default_rng(4)
    logits = rng.standard_normal((64, 32)).astype(np.float32)
    logits[3, 5] = logits[3, 6]             # a tie inside a kept group
    logits[4] = 0.0                         # every score equal
    bias = (rng.standard_normal(32) * 0.3).astype(np.float32)
    idx, w = moe.group_limited_gate(
        jnp.asarray(logits), jnp.asarray(bias), n_group=8, topk_group=4,
        top_k=8, routed_scaling_factor=2.5)
    scores = 1 / (1 + np.exp(-logits.astype(np.float64)))
    want_idx, want_w = _loop_gate(scores, bias, 8, 4, 8, 2.5)
    np.testing.assert_array_equal(np.asarray(idx), want_idx)
    np.testing.assert_allclose(np.asarray(w), want_w, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    # the bias moves the choice and never the weight: the weights are
    # the chosen experts' scores themselves
    idx0, _ = moe.group_limited_gate(
        jnp.asarray(logits), jnp.zeros(32), n_group=8, topk_group=4,
        top_k=8, routed_scaling_factor=2.5)
    assert (np.sort(np.asarray(idx0)) != np.sort(np.asarray(idx))).any()
    picked = np.take_along_axis(scores, np.asarray(idx), axis=1)
    np.testing.assert_allclose(
        np.asarray(w), picked / picked.sum(-1, keepdims=True) * 2.5,
        rtol=1e-5)
    # ... and the reference's own router agrees with both
    cfg = dict(gate_width=32, n_group=8, topk_group=4, num_experts_per_tok=8,
               norm_topk_prob=True, routed_scaling_factor=2.5)
    eye = {"mlp.gate.weight": jnp.eye(32),
           "mlp.gate.e_score_correction_bias": jnp.asarray(bias)}
    with jax.default_matmul_precision("highest"):
        r_idx, r_w = ref.route(jnp.asarray(logits), eye, cfg, False)
    np.testing.assert_array_equal(np.asarray(r_idx), want_idx)
    np.testing.assert_allclose(np.asarray(r_w), want_w, rtol=1e-5)


def test_shares_of_all_ranks_add_up_to_the_uncut_layer():
    """4 ranks x 4 experts: the routed part each rank computes for the
    experts it holds, summed over the ranks, plus the shared expert
    counted once, is the uncut reference layer (all 16 experts held)."""
    cfg = tiny_cfg(held=16, ranks=1, rank=0, layers=2)
    w = weights.make(fam.layer_shapes(cfg, 1), SEED)
    w = {k.split("layers.1.")[1]: v.astype(jnp.float32) for k, v in w.items()}
    x = jnp.asarray(np.random.default_rng(5).standard_normal((40, 64)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        whole = np.asarray(ref.experts(x, w, fam._small(cfg), False))
        shared = np.asarray(ref.swiglu(
            x, w["mlp.shared_experts.gate_proj.weight"],
            w["mlp.shared_experts.up_proj.weight"],
            w["mlp.shared_experts.down_proj.weight"], False))
        routed = np.zeros_like(whole)
        for rank in range(4):
            layer = dsv3.DeepseekV3MoE(dsv3.DeepseekV3Config.tiny(
                expert_first=4 * rank, expert_count=4))
            held = slice(4 * rank, 4 * rank + 4)
            for p, leaf in (
                    (layer.gate.weight, w["mlp.gate.weight"]),
                    (layer.gate.e_score_correction_bias,
                     w["mlp.gate.e_score_correction_bias"]),
                    (layer.experts.gate_up_proj,
                     w["mlp.experts.gate_up_proj"][held]),
                    (layer.experts.down_proj,
                     w["mlp.experts.down_proj"][held]),
                    (layer.shared_experts.gate_proj.weight,
                     w["mlp.shared_experts.gate_proj.weight"]),
                    (layer.shared_experts.up_proj.weight,
                     w["mlp.shared_experts.up_proj.weight"]),
                    (layer.shared_experts.down_proj.weight,
                     w["mlp.shared_experts.down_proj.weight"])):
                p._data = leaf
            part = np.asarray(layer(paddle.to_tensor(x))._data) - shared
            assert np.abs(part).max() > 1e-3        # every rank adds
            routed += part
    np.testing.assert_allclose(routed + shared, whole, atol=2e-5)


def test_share_leaves_absent_and_pad_rows_uncomputed():
    """Pairs of absent experts and (under ``serving_rows_mask``) pad
    rows belong to no group: counted out, gated to zero."""
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((8, 16)), jnp.float32)
    gate_up = jnp.asarray(rng.standard_normal((2, 16, 8)), jnp.float32)
    down = jnp.asarray(rng.standard_normal((2, 4, 16)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 8, (8, 2)), jnp.int32) \
        .at[0].set(jnp.asarray([2, 3], jnp.int32))  # row 0: both held
    wts = jnp.ones((8, 2), jnp.float32)
    sink = []
    live = jnp.arange(8) < 5
    with moe.serving_rows_mask(live), moe.serving_share_counts(sink):
        y = moe.moe_share_dispatch_combine(x, idx, wts, gate_up, down,
                                           first=2, num_expert=8)
    local = np.asarray(idx) - 2
    held = (local >= 0) & (local < 2) & np.asarray(live)[:, None]
    counts = np.asarray(sink[0])
    assert counts[:2].tolist() == [int((held & (local == e)).sum())
                                   for e in range(2)]
    assert counts[2] == 5
    want = np.zeros((8, 16), np.float32)
    for r in range(8):
        for j in range(2):
            if held[r, j]:
                g, u = np.split(np.asarray(x[r] @ gate_up[local[r, j]]), 2)
                want[r] += (g / (1 + np.exp(-g)) * u) \
                    @ np.asarray(down[local[r, j]])
    np.testing.assert_allclose(np.asarray(y), want, atol=1e-4)
    assert not np.asarray(y)[5:].any() and np.asarray(y)[0].any()


def _filled(layer, seed):
    rng = np.random.default_rng(seed)

    def fill(p):
        if isinstance(p, pc.QuantKV):
            return pc.QuantKV(fill(p.data), fill(p.scale))
        vals = rng.integers(-100, 100, p.shape) \
            if jnp.issubdtype(p.dtype, jnp.integer) \
            else rng.standard_normal(p.shape)
        return jnp.asarray(vals, p.dtype)

    return tuple(fill(p) for p in layer)


def _leaves(pools):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(pools)]


@pytest.mark.parametrize("make", [
    lambda: pc.init_latent_pool(6, 4, 40, jnp.float32),
    lambda: pc.init_pool(6, 4, 2, 8, jnp.float32),
    lambda: pc.init_pool(6, 4, 2, 8, "int8"),
], ids=["latent-1-tuple", "kv-pair", "int8-pair"])
def test_pool_walkers_take_any_arity(make):
    """copy, export / import round trip, host payload and byte counts on
    a latent cache's 1-tuple and on the (k, v) pairs, the int8 pair with
    its scales."""
    pools = [_filled(make(), 10 + i) for i in range(2)]
    arity = len(pools[0])
    assert pc.pool_bytes(pools) == sum(
        x.nbytes for x in jax.tree_util.tree_leaves(pools))
    copied = pc.copy_blocks(pools, jnp.int32(2), jnp.int32(5))
    for before, after in zip(_leaves(pools), _leaves(copied)):
        np.testing.assert_array_equal(after[5], before[2])
        np.testing.assert_array_equal(np.delete(after, 5, 0),
                                      np.delete(before, 5, 0))
    ids = jnp.asarray([3, 1, 0, 0], jnp.int32)
    payload = pc.export_blocks(pools, ids)
    assert all(len(rows) == arity for rows in payload)
    host = pc.payload_rows(pc.payload_to_host(payload), 2)
    assert pc.payload_nbytes(host) * 3 == pc.pool_bytes(pools)
    empty = [make() for _ in range(2)]
    back = pc.import_blocks(
        empty, jnp.asarray([4, 2, 0, 0], jnp.int32),
        jax.tree_util.tree_map(jnp.asarray, pc.payload_pad(host, 4)))
    for src, dst in zip(_leaves(pools), _leaves(back)):
        np.testing.assert_array_equal(dst[4], src[3])
        np.testing.assert_array_equal(dst[2], src[1])
        assert not dst[[1, 3, 5]].any()
    with pytest.raises(ValueError, match="arity"):
        pc.import_blocks(empty, ids, [rows + rows for rows in payload])


def test_scatter_rows_writes_a_latent_cache_like_a_pair():
    """``scatter_rows`` on a 1-tuple lands each row where ``write_rows``
    lands a pair's, pad rows in the null block."""
    (pool,) = pc.init_latent_pool(5, 4, 40, jnp.float32)
    kp, vp = pc.init_pool(5, 4, 1, 128, jnp.float32)
    tables = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    slot = jnp.asarray([0, 0, 1, 0], jnp.int32)
    pos = jnp.asarray([3, 4, 0, 8], jnp.int32)      # the last: overflow
    rows = jnp.asarray(np.random.default_rng(7).standard_normal((4, 128)),
                       jnp.float32)
    (got,) = pc.scatter_rows((pool,), tables, slot, pos, (rows,))
    want, _ = pc.write_rows(kp, vp, tables, slot, pos, rows[:, None],
                            rows[:, None])
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want)[:, :, 0])
    np.testing.assert_array_equal(np.asarray(got)[1, 3], np.asarray(rows[0]))
    assert pc.gather_dense(got, tables).shape == (2, 8, 128)


def test_model_is_built_in_its_dtype_and_refuses_what_is_not_built():
    cfg = dsv3.DeepseekV3Config.tiny(dtype="bfloat16", expert_count=4)
    model = dsv3.DeepseekV3ForCausalLM(cfg)
    assert {str(p._data.dtype) for p in model.parameters()} == {"bfloat16"}
    assert model.model.layers[1].mlp.experts.gate_up_proj.shape[0] == 4
    assert model.model.layers[1].mlp.gate.weight.shape == [64, 16]
    caches = model.init_paged_caches(9, 16)
    assert len(caches) == 3 and all(
        len(c) == 1 and c[0].shape == (9, 16, 128) for c in caches)
    with pytest.raises(NotImplementedError, match="dense cache"):
        model.init_caches(1, 8)
    with pytest.raises(NotImplementedError, match="quantized latent"):
        model.init_paged_caches(9, 16, kv_cache_dtype="int8")

"""Ragged mixed-batch serving (ISSUE 7): ONE executable per engine
consumes decode rows + speculative verify windows + prefill chunk rows
as a single packed ragged batch. Covered here: the ragged row-layout
helper and per-row pool scatter, interpret-mode parity of the ragged
Pallas grid vs the XLA fallback on mixed batches (slots at block
boundaries, zero-row/retired slots), bitwise equality of the fallback
vs each sequential per-width mirror (T=1 decode, gamma+1 verify, chunk
prefill), engine-level greedy token-exactness against
``generate(cache_impl="dense")`` across Llama / GPT / int8 /
speculative (ngram + draft model) / prefix-cache engines and under
TP=2, the 1-executable (2 with draft) steady-state pin with zero
recompiles under concurrent admissions, and the
``serving_kernel_fallback`` telemetry satellite.

Tier-1 guard: every test here must run in the standard
``-m 'not slow'`` sweep — ``test_tier1_no_slow_marker`` pins that.
"""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import monitor
from paddle_tpu.inference import ServingConfig, ServingEngine
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


@pytest.fixture
def llama_tiny():
    paddle.seed(7)
    cfg = LlamaConfig.tiny(vocab=128, hidden=64, layers=2, heads=4,
                           kv_heads=2, ffn=128)
    m = LlamaForCausalLM(cfg)
    m.eval()
    return m


def _serve_waves(model, prompts, max_new=6, waves=2, draft=None,
                 **kw):
    """Serve ``waves`` rounds of the same prompts; returns (outputs,
    stats)."""
    base = dict(num_slots=2, block_size=8, max_model_len=96,
                prefill_chunk=8)
    base.update(kw)
    eng = ServingEngine(model, ServingConfig(**base), draft_model=draft)
    outs = []
    for _ in range(waves):
        outs += eng.serve(list(prompts), max_new_tokens=max_new)
    st = eng.stats()
    eng.shutdown()
    return outs, st


def _dense_refs(model, prompts, max_new):
    """The independent reference: greedy ``generate`` over the dense
    cache, one prompt at a time — no block pool, no packed rows, no
    engine."""
    outs = []
    for p in prompts:
        out, _ = model.generate(
            paddle.to_tensor(np.asarray(p)[None].astype(np.int64)),
            max_new_tokens=max_new, cache_impl="dense",
            decode_strategy="greedy_search")
        outs.append(np.asarray(out.numpy())[0])
    return outs


def _assert_equal_streams(a, b, tag):
    assert len(a) == len(b)
    for i, (x, y) in enumerate(zip(a, b)):
        np.testing.assert_array_equal(
            x, y, err_msg=f"{tag}: request {i} diverged")


# ------------------------------------------------------------ row layout
# + per-row scatter primitives


def test_ragged_row_meta_layout():
    from paddle_tpu.ops.paged_cache import ragged_row_meta
    q_lens = [1, 3, 0, 5]
    base = [10, 4, 0, 0]
    row_slot, row_pos, starts, last = ragged_row_meta(q_lens, base, 12,
                                                      999)
    assert starts.tolist() == [0, 1, 4, 4]
    assert last.tolist() == [0, 3, 0, 8]
    assert row_slot.tolist() == [0, 1, 1, 1, 3, 3, 3, 3, 3, 0, 0, 0]
    assert row_pos.tolist() == [10, 4, 5, 6, 0, 1, 2, 3, 4, 999, 999,
                                999]
    with pytest.raises(ValueError, match="row budget"):
        ragged_row_meta([7, 7], [0, 0], 12, 999)


def test_write_rows_matches_write_tokens_and_null_routes():
    """The per-row scatter must land each row exactly where the
    multi-token append would, and overflow rows (pad sentinel) must hit
    the null block, never a slot's live blocks."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    rng = np.random.RandomState(3)
    S, T, H, D, BS, MB = 2, 4, 2, 4, 4, 3
    kp0, vp0 = pc.init_pool(1 + S * MB, BS, H, D, jnp.float32)
    tables = jnp.asarray(
        (1 + np.arange(S * MB, dtype=np.int32)).reshape(S, MB))
    lens = np.asarray([3, 6], np.int64)
    k = jnp.asarray(rng.randn(S, T, H, D), jnp.float32)
    v = jnp.asarray(rng.randn(S, T, H, D), jnp.float32)
    want_k, want_v = pc.write_tokens(kp0, vp0, tables,
                                     jnp.asarray(lens), k, v)
    # same writes expressed as one packed ragged batch + 2 pad rows
    row_slot, row_pos, _, _ = pc.ragged_row_meta(
        [T, T], lens, 2 * T + 2, MB * BS)
    kr = jnp.concatenate([k.reshape(S * T, H, D),
                          jnp.asarray(rng.randn(2, H, D), jnp.float32)])
    vr = jnp.concatenate([v.reshape(S * T, H, D),
                          jnp.asarray(rng.randn(2, H, D), jnp.float32)])
    got_k, got_v = pc.write_rows(kp0, vp0, tables,
                                 jnp.asarray(row_slot),
                                 jnp.asarray(row_pos), kr, vr)
    # live blocks identical; pad rows only touched the null block
    np.testing.assert_array_equal(np.asarray(got_k[1:]),
                                  np.asarray(want_k[1:]))
    np.testing.assert_array_equal(np.asarray(got_v[1:]),
                                  np.asarray(want_v[1:]))
    assert np.asarray(got_k)[0].any()


def test_write_decode_overflow_routes_to_null():
    """The ragged draft scan parks must-not-write slots at an overflow
    position: write_decode routes it to the null block instead of
    clamping onto the slot's last live block."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    rng = np.random.RandomState(5)
    kp, vp = pc.init_pool(4, 4, 2, 4, jnp.float32)
    tables = jnp.asarray([[1, 2]], jnp.int32)
    k1 = jnp.asarray(rng.randn(1, 2, 4), jnp.float32)
    kp2, _ = pc.write_decode(kp, vp, tables,
                             jnp.asarray([8], jnp.int32), k1, k1)
    assert not np.asarray(kp2)[1:].any()      # live blocks untouched
    assert np.asarray(kp2)[0].any()           # null block absorbed it


# ------------------------------------------------------- kernel parity


def _mixed_batch(rng, S=4, H=8, Hkv=4, D=64, BS=8, MB=6):
    """A ragged batch exercising every width: decode row, verify
    window, chunk at a block boundary, and a zero-row (retired) slot."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    NB = 1 + S * MB
    kp = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
    vp = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
    tables = np.zeros((S, MB), np.int32)
    base = np.asarray([5, 15, 0, 24], np.int64)   # 15+3, 24 block-edge
    q_lens = np.asarray([1, 3, 0, 8], np.int64)
    alloc = pc.BlockAllocator(NB)
    for s in range(S):
        n = pc.blocks_for(int(base[s]) + int(q_lens[s]), BS)
        if n:
            tables[s, :n] = alloc.alloc(n)
    R, W = 16, 8
    row_slot, row_pos, row_starts, _ = pc.ragged_row_meta(
        q_lens, base, R, MB * BS)
    q = jnp.asarray(rng.randn(R, H, D), jnp.float32)
    return (q, kp, vp, jnp.asarray(tables), jnp.asarray(base + 1),
            jnp.asarray(q_lens), jnp.asarray(row_starts),
            jnp.asarray(row_slot), W, q_lens, row_starts)


def test_ragged_fallback_bitwise_equals_per_width_paths():
    """The issue's CPU-parity bar: every live row of the ragged XLA
    fallback is BITWISE the sequential per-width fallback's output —
    T=1 decode (``_xla_paged_attention``), gamma+1 verify and chunk
    prefill (``_xla_paged_verify``)."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    rng = np.random.RandomState(0)
    (q, kp, vp, tables, ctx, ql, rs, sl, W,
     q_lens, row_starts) = _mixed_batch(rng)
    # narrow width 3 (the verify window); the chunk slot is the ONE
    # wide slot — the two-lane fallback contract
    out = pa._xla_ragged_paged(q, kp, vp, tables, ctx, ql, rs, sl, 3,
                               W)
    # decode slot (1 row)
    ref = pa._xla_paged_attention(q[0:1], kp, vp, tables[0:1], ctx[0:1])
    np.testing.assert_array_equal(np.asarray(out[0]), np.asarray(ref[0]))
    # verify window (3 rows) + chunk (8 rows, block-boundary start)
    for s, (s0, n) in ((1, (1, 3)), (3, (4, 8))):
        ref = pa._xla_paged_verify(q[s0:s0 + n][None], kp, vp,
                                   tables[s:s + 1], ctx[s:s + 1])
        np.testing.assert_array_equal(np.asarray(out[s0:s0 + n]),
                                      np.asarray(ref[0]))


# The mixes the kernel's index space can get wrong — (q_lens, base
# lengths before the tick, w_narrow, tree-flagged slots, int8 pools) on
# 8 slots, block 8, a 24-block table (reach 192: two 128-position kv
# tiles, the second one short), w_max 128. f32 query heads pad to 8
# rows, so a query tile holds 16 window rows of a slot.
_KERNEL_MIXES = {
    "decode_only": ([1] * 8, [5, 15, 0, 24, 63, 100, 150, 191], 1),
    "chunk_128_beside_7_decodes":
        ([1, 1, 1, 128, 1, 1, 1, 1], [5, 15, 0, 40, 63, 100, 150, 191],
         1),
    "chunk_not_a_tile_multiple":
        ([1, 0, 77, 1, 1, 0, 1, 1], [9, 0, 30, 24, 63, 0, 150, 17], 1),
    "contexts_127_128_129_and_the_last_block":
        ([1, 1, 1, 1, 3, 17, 1, 1],
         [126, 127, 128, 191, 125, 112, 184, 190], 3),
    "empty_slot_between_live_ones":
        ([1, 0, 1, 0, 0, 40, 0, 1], [5, 77, 0, 24, 63, 100, 150, 129],
         1),
    "three_rows_a_slot": ([3] * 8, [5, 15, 0, 24, 63, 125, 126, 189], 3),
    "tree_flagged_beside_linear":
        ([3, 3, 20, 3, 0, 3, 2, 3], [5, 15, 0, 24, 63, 125, 126, 189], 3,
         [1, 1, 0, 1, 0, 1, 0, 1]),
    "int8_pools":
        ([1, 3, 0, 50, 1, 3, 1, 1], [5, 15, 0, 24, 63, 125, 126, 189], 3,
         None, True),
}


def _block_tables(q_lens, base, MB, NB, BS):
    """``[S, MB]`` tables with each slot's blocks allocated up to its
    length after the tick, null past the allocation."""
    from paddle_tpu.ops import paged_cache as pc
    tables = np.zeros((len(q_lens), MB), np.int32)
    alloc = pc.BlockAllocator(NB)
    for s in range(len(q_lens)):
        n = pc.blocks_for(int(base[s] + q_lens[s]), BS)
        if n:
            tables[s, :n] = alloc.alloc(n)
    return tables


def _assert_live_rows_match(out, ref, q_lens, row_starts, tol):
    """Every live row of ``out`` agrees with ``ref``; every packed row
    no slot owns is zero."""
    owned = np.zeros(len(out), bool)
    for s, n in enumerate(map(int, q_lens)):
        s0 = int(row_starts[s])
        owned[s0:s0 + n] = True
        np.testing.assert_allclose(
            out[s0:s0 + n], ref[s0:s0 + n], rtol=tol, atol=tol,
            err_msg=f"slot {s} rows diverged")
    assert np.isfinite(out).all()
    assert not out[~owned].any(), "a row past a slot's q_lens is written"


@pytest.mark.parametrize("mix", list(_KERNEL_MIXES))
def test_ragged_kernel_matches_fallback_interpret(mix):
    """The ragged Pallas kernel (interpret mode on CPU) agrees with the
    gather fallback on every live row of each mix, and returns zeros in
    every packed row no slot owns."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    from paddle_tpu.ops.pallas import paged_attention as pa
    q_lens, base, wn, tree, quant = (
        _KERNEL_MIXES[mix] + (None, False))[:5]
    q_lens, base = np.asarray(q_lens, np.int64), np.asarray(base, np.int64)
    S, H, Hkv, D, BS, MB, W = 8, 8, 4, 64, 8, 24, 128
    R = S * wn + W
    NB = 1 + S * MB
    rng = np.random.RandomState(1)

    def pool():
        x = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
        return pc.QuantKV(*pc.kv_quantize(x)) if quant else x

    kp, vp = pool(), pool()
    tables = _block_tables(q_lens, base, MB, NB, BS)
    row_slot, _, row_starts, _ = pc.ragged_row_meta(q_lens, base, R,
                                                    MB * BS)
    q = jnp.asarray(rng.randn(R, H, D), jnp.float32)
    args = (q, kp, vp, jnp.asarray(tables), jnp.asarray(base + 1),
            jnp.asarray(q_lens), jnp.asarray(row_starts))
    kw = {}
    if tree is not None:
        kw = dict(tree_anc=(0, 0), tree_slots=jnp.asarray(tree))
    ref = pa._xla_ragged_paged(*args, jnp.asarray(row_slot), wn, W, **kw)
    out = np.asarray(pa.pallas_ragged_paged_attention(
        *args, w_max=W, interpret=True, **kw))
    _assert_live_rows_match(out, np.asarray(ref), q_lens, row_starts,
                            1e-5)


def test_ragged_kernel_mixed_batch_interpret():
    """The seed's mixed batch (4 slots, a 6-block table shorter than
    one kv tile, a zero-row slot, a chunk at a block boundary)."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    rng = np.random.RandomState(1)
    (q, kp, vp, tables, ctx, ql, rs, sl, W,
     q_lens, row_starts) = _mixed_batch(rng)
    ref = pa._xla_ragged_paged(q, kp, vp, tables, ctx, ql, rs, sl, 3,
                               W)
    out = pa.pallas_ragged_paged_attention(q, kp, vp, tables, ctx, ql,
                                           rs, w_max=W, interpret=True)
    for s, n in enumerate(map(int, np.asarray(q_lens))):
        s0 = int(row_starts[s])
        np.testing.assert_allclose(
            np.asarray(out[s0:s0 + n]), np.asarray(ref[s0:s0 + n]),
            rtol=1e-5, atol=1e-5,
            err_msg=f"slot {s} rows diverged")


# One mix for the head-group cases: a decode row, a 3-row verify window
# (tree-flagged where the case masks by tree), a dead slot, a 20-row
# chunk, a decode row whose context ends on a kv tile's edge, a dead slot
_GROUP_MIX = ([1, 3, 0, 20, 1, 0], [5, 130, 0, 24, 127, 77],
              [0, 1, 0, 0, 0, 0])


@pytest.mark.parametrize("pool", ["bf16_4d", "flat_d64", "int8", "tree"])
@pytest.mark.parametrize("tiles,hg", [(1, 1), (2, 2), (4, 4), (8, 4)],
                         ids=["hg1", "hg2", "hg4", "hg4_two_groups"])
def test_ragged_kernel_head_groups_match_fallback_interpret(pool, tiles,
                                                            hg):
    """A grid step takes ``hg`` kv heads (128-lane tiles of the pool
    row) — one (the TP shard's kernel), all of a narrow row, or a
    capped group of a row wider than ``_GROUP_LANES`` — and every live
    row agrees with the gather fallback whatever the pool: 4-D bf16,
    flat with head size 64 in pairs, int8 with its scale pools, f32
    under the tree mask."""
    import jax.numpy as jnp
    from paddle_tpu.ops import paged_cache as pc
    from paddle_tpu.ops.pallas import paged_attention as pa
    assert pa._head_group(tiles, 128) == hg
    q_lens, base, tree = (np.asarray(a, np.int64) for a in _GROUP_MIX)
    flat, quant = pool == "flat_d64", pool == "int8"
    dt = jnp.float32 if pool in ("int8", "tree") else jnp.bfloat16
    D = 64 if flat else 128
    Hkv = tiles * 128 // D
    S, H, BS, W, wn = len(q_lens), 2 * Hkv, 32 if quant else 16, 24, 3
    MB = 192 // BS
    R, NB = S * wn + W, 1 + S * MB
    rng = np.random.RandomState(3)

    def make():
        x = jnp.asarray(rng.randn(NB, BS, Hkv, D), jnp.float32)
        return pc.QuantKV(*pc.kv_quantize(x)) if quant else x.astype(dt)

    kp, vp = make(), make()
    tables = _block_tables(q_lens, base, MB, NB, BS)
    row_slot, _, row_starts, _ = pc.ragged_row_meta(q_lens, base, R,
                                                    MB * BS)
    q = jnp.asarray(rng.randn(R, H, D), dt)
    rest = (jnp.asarray(tables), jnp.asarray(base + 1),
            jnp.asarray(q_lens), jnp.asarray(row_starts))
    kw = {}
    if pool == "tree":
        kw = dict(tree_anc=(0, 0), tree_slots=jnp.asarray(tree))
    ref = np.asarray(pa._xla_ragged_paged(
        q, kp, vp, *rest, jnp.asarray(row_slot), wn, W, **kw), np.float32)
    if flat:
        kp, vp = (x.reshape(NB, BS, Hkv * D) for x in (kp, vp))
    out = np.asarray(pa.pallas_ragged_paged_attention(
        q, kp, vp, *rest, w_max=W, interpret=True, **kw), np.float32)
    _assert_live_rows_match(out, ref, q_lens, row_starts,
                            1e-5 if dt == jnp.float32 else 2e-2)


def _direct_grid_count(q_lens, ctx, tq, span, heads, n_tiles):
    """(units, live) by walking slots, tiles and kv tiles one at a
    time: what ``ragged_grid_units`` must equal."""
    live = tiles = 0
    for n, c in zip(q_lens, ctx):
        for row0 in range(0, int(n), tq):
            last = min(row0 + tq, int(n)) - 1       # last live row
            live += -(-(int(c) + last) // span)     # sees c + last cols
            tiles += 1
    return (live + (n_tiles - tiles)) * heads, live * heads


@pytest.mark.parametrize("hkv,rep,lanes,bs,streams,groups", [
    (4, 7, 128, 16, 2, 1),  # the Qwen cells: a 4-D bf16 pool
    (4, 8, 128, 16, 2, 1),  # the wide cell: 8 heads of 64 in 4 pairs
    (4, 7, 128, 32, 4, 1),  # an int8 pool: the scale pools ride along
    (1, 7, 128, 16, 2, 1),  # a TP shard's one local kv head
    (8, 7, 128, 16, 2, 2),  # a row wider than the lane budget
    (2, 7, 256, 16, 2, 1),  # head size 256: two heads fill the budget
], ids=["bf16_4d", "flat_d64_pairs", "int8", "tp_shard", "two_groups",
        "d256"])
def test_grid_units_keep_their_rule_and_count_the_copies(
        hkv, rep, lanes, bs, streams, groups):
    """``ragged_grid_units`` on a fixed tick (decode rows, a 40-row
    chunk, dead slots): ``(units, live)`` stay the (query tile, kv
    head, kv tile) count the kernel had with one head a grid step — a
    slot-by-slot walk — and ``copies`` is a descriptor a pool block a
    stream a HEAD GROUP of every live (query tile, kv tile)."""
    from paddle_tpu.ops.pallas import paged_attention as pa
    import jax.numpy as jnp
    q_lens = [1, 1, 0, 40, 1, 0, 3, 1]
    ctx = [6, 128, 0, 25, 129, 78, 500, 1024]
    geo = dict(rows=136, w_max=128, num_heads=hkv * rep, num_kv_heads=hkv,
               q_dtype=jnp.bfloat16, block_size=bs, max_blocks=1024 // bs)
    tq, span, n_tiles = 8, 128, 8 + 136 // 8
    units, live, copies = pa.ragged_grid_units(
        q_lens, ctx, head_lanes=lanes, streams=streams, **geo)
    assert (units, live) == _direct_grid_count(q_lens, ctx, tq, span, hkv,
                                               n_tiles)
    kv_tiles, kb = live // hkv, span // bs
    assert copies == kv_tiles * kb * streams * groups
    # a descriptor a kv head, the kernel before: ``hg`` times as many
    assert copies * pa._head_group(hkv, lanes) == live * kb * streams


def test_tick_span_counts_the_attention_grid(llama_tiny):
    """``attn_units`` / ``attn_live`` on the ``tick`` span are the
    (query tile, kv head, kv tile) units one layer's ragged attention
    call visits, counted on the host in ``pack``: a hand-made tick (a
    23-token prompt prefilled 8 rows a tick beside one decoding slot)
    against a direct count."""
    rng = np.random.RandomState(2)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=3, block_size=8, max_model_len=192, prefill_chunk=8))
    eng.submit(rng.randint(1, 128, (4,)), 30)
    eng.step()                      # slot 0: 4 prompt rows, then decodes
    eng.submit(rng.randint(1, 128, (150,)), 4)
    for _ in range(22):
        eng.step()
    spans = [e["args"] for e in eng.tracer.events() if e["name"] == "tick"]
    eng.shutdown()
    # f32, 2 query heads a kv head: 8 padded rows, 16 window rows a
    # tile; block 8: 16 blocks = 128 positions a kv tile; 3 slots +
    # ceil((3 + 8) / 16) tiles launched
    tq, span, heads, n_tiles = 16, 128, 2, 4
    # tick 0: the 4-row prompt alone; ticks 1-19: one decode row at
    # context 5, 6, ... beside an 8-row chunk at 0, 8, ...
    want = [_direct_grid_count([4], [1], tq, span, heads, n_tiles)]
    for k in range(19):
        chunk = min(8, 150 - 8 * k)
        want.append(_direct_grid_count(
            [1, chunk], [5 + k, 8 * k + 1], tq, span, heads, n_tiles))
    got = [(a["attn_units"], a["attn_live"]) for a in spans]
    assert got[:20] == want
    # 2 kv heads of 16 lanes are ONE head group: a copy a block (16 a
    # kv tile) a stream (K, V) of every live (query tile, kv tile)
    assert [a["attn_copies"] for a in spans[:20]] == [
        live // heads * 16 * 2 for _, live in want]
    # the chunk's walk grows past one kv tile at position 128
    assert want[15] == (8, 4) and want[17] == (10, 6)


# ----------------------------------------------- engine-level exactness
# the engine == an independent reference


def _llama(seed, **kw):
    paddle.seed(seed)
    cfg = dict(vocab=128, hidden=64, layers=2, heads=4, kv_heads=2,
               ffn=128)
    cfg.update(kw)
    m = LlamaForCausalLM(LlamaConfig.tiny(**cfg))
    m.eval()
    return m


def _case_llama_with_prefix_cache():
    rng = np.random.RandomState(0)
    sysp = rng.randint(1, 128, (24,))
    prompts = [np.concatenate([sysp, rng.randint(1, 128, (t,))])
               for t in (5, 9, 3)]
    return _llama(7), prompts, dict(), dict(
        executables_compiled=1, prefix_blocks_reused=True)


def _case_gpt():
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM
    paddle.seed(3)
    m = GPTForCausalLM(GPTConfig.tiny(vocab=96, hidden=64, layers=2,
                                      heads=4))
    m.eval()
    rng = np.random.RandomState(5)
    prompts = [rng.randint(1, 96, (n,)).astype(np.int64)
               for n in (5, 11, 8)]
    return m, prompts, dict(max_new=4, waves=1, max_model_len=64), \
        dict(executables_compiled=1)


def _case_int8():
    from paddle_tpu.nn.quant import quantize_for_inference
    m = _llama(11)
    assert quantize_for_inference(m) > 0
    rng = np.random.RandomState(9)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int64)
               for n in (6, 10)]
    return m, prompts, dict(max_new=4, waves=1, max_model_len=64), \
        dict(executables_compiled=1)


def _case_speculative_ngram():
    rng = np.random.RandomState(4)
    sysp = np.tile(rng.randint(1, 128, (8,)), 3)
    prompts = [np.concatenate([sysp, rng.randint(1, 128, (t,))])
               for t in (4, 7)]
    return _llama(7), prompts, \
        dict(max_new=8, num_speculative_tokens=3), \
        dict(executables_compiled=1, spec_tokens_proposed=True)


def _case_speculative_draft_model():
    draft = _llama(13, hidden=32, layers=1, heads=2, ffn=64)
    rng = np.random.RandomState(3)
    sysp = rng.randint(1, 128, (16,))
    prompts = [np.concatenate([sysp, rng.randint(1, 128, (t,))])
               for t in (5, 11)]
    # target ragged step + fused draft (prime + scan): exactly two
    return _llama(7), prompts, \
        dict(draft=draft, num_speculative_tokens=2, drafter="model"), \
        dict(executables_compiled=2, spec_tokens_proposed=True)


_ENGINE_CASES = {
    "llama_with_prefix_cache": _case_llama_with_prefix_cache,
    "gpt": _case_gpt,
    "int8": _case_int8,
    "speculative_ngram": _case_speculative_ngram,
    "speculative_draft_model": _case_speculative_draft_model,
}


@pytest.mark.parametrize("case", list(_ENGINE_CASES))
def test_engine_streams_match_dense_generate(case):
    """Every stream the engine emits — packed rows, the paged pool,
    prefix reuse, speculation under greedy — is token for token what
    greedy ``generate(cache_impl="dense")`` of the same model emits
    for that prompt alone; each case keeps its own pins (the
    one-executable count, blocks reused, drafts proposed)."""
    model, prompts, kw, pins = _ENGINE_CASES[case]()
    kw = dict(kw)
    max_new, waves = kw.pop("max_new", 6), kw.pop("waves", 2)
    got, st = _serve_waves(model, prompts, max_new=max_new,
                           waves=waves, **kw)
    want = _dense_refs(model, prompts, max_new) * waves
    _assert_equal_streams(got, want, f"{case}: engine vs dense")
    assert st["executables_compiled"] == pins["executables_compiled"]
    assert st["prefill_compiles"] == 0
    if pins.get("prefix_blocks_reused"):
        assert st["prefix_blocks_reused"] > 0   # cache composes
    if pins.get("spec_tokens_proposed"):
        assert st["spec_tokens_proposed"] > 0


@pytest.mark.skipif(
    __import__("jax").device_count() < 2,
    reason="needs a multi-device mesh")
def test_ragged_exact_tp2(llama_tiny):
    """TP composes unchanged: the ragged step under tp_degree=2 is
    token-exact vs the single-device ragged engine and still shows
    EXACTLY ONE explicit collective (the logits all_gather)."""
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, 128, (n,)).astype(np.int64)
               for n in (5, 9, 13)]

    def serve(tp):
        eng = ServingEngine(llama_tiny, ServingConfig(
            num_slots=2, block_size=8, max_model_len=64, tp_degree=tp,
            prefill_chunk=8))
        outs = eng.serve(list(prompts), max_new_tokens=5)
        st = eng.stats()
        census = eng.collective_census()
        eng.shutdown()
        return outs, st, census

    ref, st1, _ = serve(1)
    got, st2, census = serve(2)
    _assert_equal_streams(got, ref, "ragged tp=2")
    assert st2["tp_degree"] == 2
    assert st2["executables_compiled"] == 1
    rows = [r for r in census["decode"]
            if r["op"] != "sharding_constraint"]
    assert len(rows) == 1 and rows[0]["op"] == "all_gather"
    assert rows[0]["axis"] == "mp" and rows[0]["count"] == 1


# ------------------------------------------- one-executable steady state
# + telemetry


def test_ragged_one_executable_with_concurrent_admissions(llama_tiny):
    """The tentpole pin: with admissions landing WHILE other slots
    decode (the mixed regime that used to interleave chunk executables
    between decode launches), the engine still compiles exactly ONE
    executable and never recompiles across waves."""
    rng = np.random.RandomState(2)
    eng = ServingEngine(llama_tiny, ServingConfig(
        num_slots=3, block_size=8, max_model_len=64, prefill_chunk=8))
    rids = [eng.submit(rng.randint(1, 128, (n,)), 6) for n in (4, 9)]
    for _ in range(3):
        eng.step()
    # admissions mid-flight: prefill rows must ride the SAME executable
    rids += [eng.submit(rng.randint(1, 128, (n,)), 5)
             for n in (23, 2, 17)]
    while eng.num_queued or eng.num_active:
        eng.step()
    st = eng.stats()
    done = eng.run()
    eng.shutdown()
    assert st["executables_compiled"] == 1, \
        f"ragged engine must stay at ONE executable, got {st}"
    assert st["decode_compiles"] == 1
    assert st["prefill_compiles"] == 0
    assert sorted(done) == sorted(rids)
    assert st["prefill_chunks"] >= sum(
        -(-n // 8) for n in (4, 9, 23, 2, 17))


def test_ragged_stats_keys_and_fallback_counter(llama_tiny, tmp_path):
    """Satellites: stats() always exposes executables_compiled /
    kernel_fallbacks and the compile counts, and _warn_fallback
    bumps the serving_kernel_fallback monitor counter per occurrence
    (not once per process) + it lands in the JSONL export."""
    import json
    from paddle_tpu.ops.pallas import paged_attention as pa
    rng = np.random.RandomState(1)
    _, st = _serve_waves(llama_tiny, [rng.randint(1, 128, (5,))],
                         max_new=2, waves=1)
    for k in ("executables_compiled", "kernel_fallbacks",
              "prefill_compiles", "decode_compiles"):
        assert k in st, f"{k} missing"
    c = monitor.counter("serving_kernel_fallback", labels=("path",))
    before = c.labels(path="test_path").value()
    n0 = pa.kernel_fallback_counts().get("test_path", 0)
    pa._warn_fallback("test_path", (1, 4, 64), (8, 8, 2, 64))
    pa._warn_fallback("test_path", (1, 4, 64), (8, 8, 2, 64))
    assert pa.kernel_fallback_counts()["test_path"] == n0 + 2
    assert c.labels(path="test_path").value() == before + 2
    path = monitor.export_jsonl(str(tmp_path / "metrics.jsonl"))
    names = {json.loads(line)["name"] for line in open(path)}
    assert "serving_kernel_fallback" in names


def test_tier1_no_slow_marker():
    """CI guard (the PR-4/5 pattern): every ragged-batch test runs in
    the tier-1 ``-m 'not slow'`` sweep and the kernel parity test is
    present."""
    import tests.conftest as c
    here = open(__file__).read()
    assert "pytest.mark.slow" not in here.replace(
        '"pytest.mark.slow"', "")
    names = [ln.split("(")[0][4:] for ln in here.splitlines()
             if ln.startswith("def test_")]
    overlap = set(names) & set(c._SLOW_TESTS)
    assert not overlap, f"tier-1 ragged tests marked slow: {overlap}"
    assert "test_ragged_kernel_matches_fallback_interpret" in names
    # every engine is torn down through _serve_waves (or explicitly):
    # the allocator leak sweep guards each engine test
    assert here.count(".shutdown()") >= 4, \
        "engine shutdown (check_leaks) must guard these tests"

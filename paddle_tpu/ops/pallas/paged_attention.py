"""Ragged paged decode attention for TPU.

Reads the block-pool KV layout of ``ops/paged_cache.py`` for ONE query
token per serving slot (the continuous-batching decode step). Reference
pattern: *Ragged Paged Attention* (arxiv 2604.15464) — per-slot
length-bounded iteration over the slot's block table, so compute and
HBM traffic scale with each sequence's ACTUAL length while every array
shape stays static.

TPU path: a Pallas kernel gridded ``(slot, kv_head, block)`` with the
block dimension innermost and sequential. The block tables and context
lengths ride in as scalar-prefetch operands, so the K/V BlockSpec index
maps chase the table — each grid step DMAs exactly the pooled block the
slot owns (out-of-range steps fetch the null block and are predicated
off with ``pl.when``, paying one dead DMA but no FLOPs). Online softmax
state accumulates in VMEM scratch across block steps, flash-attention
style. GQA is native: the kernel routes the ``rep = H / H_kv`` query
heads of one kv group together and reads each K/V block once.

Off TPU (or for kernel-ineligible shapes) the jnp fallback gathers the
slot's blocks into a dense view and runs the same masked softmax — the
numerics twin of ``models.llama.cached_attention``, so paged-vs-dense
parity holds token-for-token on CPU.

Speculative decoding adds the MULTI-QUERY variant
(``paged_verify_attention``): each slot carries ``T = gamma + 1``
query tokens (the draft window plus the committed token), causal
WITHIN the window — query ``t`` sits at cache position
``context_lens[s] - 1 + t`` and may attend to every position before or
at its own. Same grid, same scalar-prefetch block-table chasing; the
only kernel delta is ``t_q * rep`` softmax rows with a per-row length
bound instead of ``rep`` rows with one shared bound (the single-token
decode kernel is the ``t_q = 1`` instantiation of the same body).

CHUNKED PREFILL is the same multi-query arithmetic at ``T = chunk``:
a chunk of the prompt enters as T query rows at ``cache_lens + t``,
attending to every previously cached block (possibly mapped from the
content-addressed prefix cache) plus its own in-chunk causal prefix.
The serving engine runs it, with decode and verify, through the
ragged variant below; the per-width kernels serve
``generate(cache_impl="paged")`` and ``SpecGenerator``.

The RAGGED MIXED-BATCH variant (``ragged_paged_attention``) goes the
rest of the way per *Ragged Paged Attention*: ONE invocation consumes
a packed row buffer ``[R, H, D]`` holding every live query row of a
serving tick — decoding slots (1 row), speculative verify windows
(gamma+1 rows) and prefill chunks (up to ``chunk`` rows) — partitioned
by per-slot ``q_lens``/``row_starts``. Its index space is what the
tick holds, not what it could hold:

- **query tiles.** The jitted wrapper cuts each slot's rows into tiles
  of ``tq`` consecutive window rows (``tq * rp`` = 128 operand rows,
  ``rp`` the padded heads of a kv group: ``_ragged_geometry``) and
  builds the tile list from ``q_lens`` (``_ragged_tiles``: a cumsum
  over the slots): tile -> slot, first window row, live rows, kv tiles
  to walk. The list's static length is ``S + ceil(R / tq)``, which
  every split of ``R`` rows over ``S`` slots fits. The rows are
  gathered into a tile-aligned ``[tiles, H_kv, tq * rp, D]`` buffer in
  XLA and the outputs gathered back per packed row, so a tile's dead
  rows never reach a live packed row; rows no slot owns come back
  zero. The gathers stand OUTSIDE ``kernel_scope``: the scope (and so
  the kernel's roofline share) holds the ``pallas_call`` alone.
- **the grid** is ``(query tile, head group)``, and the
  scalar-prefetched tile list rides in SMEM. A step holds one tile for
  EVERY kv head of its slot while the pool row's lanes fit
  ``_GROUP_LANES`` (``_head_group``: 4 heads of 128 lanes, or 8 of 64
  in pairs, are one group; a wider row walks ``H_kv / group`` steps a
  tile; a TP shard's one local head is a group of one) — per head the
  ``[tq * rp, D]`` layout of the verify kernel, row ``r`` being window
  token ``row0 + (r >> row_shift)`` with the causal bound ``lens +
  that token`` — against kv tiles of ``kb`` pool blocks = 128 cache
  positions (8 blocks of 16, 4 of int8's 32).
- **the walk ends where the data ends.** The K/V pools stay in HBM; a
  loop of exactly ``ceil((lens + last live row of the tile) / 128)``
  iterations chases ``block_tables[slot]`` with double-buffered async
  copies (tile ``j + 1`` in flight while tile ``j`` is multiplied),
  each iteration one online-softmax update a head. A pool block leaves
  HBM ONCE a stream an iteration, all the group's heads side by side —
  where the group is the whole row, one contiguous ``[BS, H_kv * D]``
  copy (16 KB at both benchmark families), not a strided 128-lane
  slice a head — and one wait a stream covers the iteration's ``kb``
  copies; the heads then run as a static loop over the copied
  buffer's lane tiles, each with its own f32 softmax state, under one
  mask. A tile past the live count loops zero times and stores zeros.
  At the default serving shape (8 slots, 136 rows, 4 kv heads, a
  64-block table) that is 25 grid steps of at most 8 iterations (176
  at the wide cell's 128 slots and 384 rows), where the ``(slot,
  window_row, kv_head, block)`` grid this replaced walked 262,144
  steps whatever was live.

``ragged_grid_units`` counts the same index space on the host from the
same rule, for the engine's ``tick`` span (``attn_units`` /
``attn_live``, in (query tile, kv head, kv tile) units whatever the
group, and ``attn_copies``, the copy descriptors issued). The XLA
fallback scatters the packed rows into the
per-slot padded ``[S, W, H, D]`` layout and calls the SAME
``_xla_paged_verify`` einsum, so every row is bitwise the per-width
fallback's output (test-pinned).

QUANTIZED POOLS (``paged_cache.QuantKV`` — int8 data + per-(block,
position, head) f32 absmax scales): all three kernel variants take
the scale pools as two extra block-chased operands (the ragged
kernel's copies fetch them beside the data, a block's scales once for
all the heads of its group, their head axis padded to a lane tile)
and dequantize each K/V tile in VMEM right after its DMA
(int8 -> f32 * scale, kept f32 through the dots — accuracy over MXU
rate on a bandwidth-bound op), so the HBM stream per decode step
halves while the softmax math is unchanged. The gather fallbacks read the SAME stored
bytes through ``paged_cache.gather_dense`` (which applies the
identical dequant recipe), so fallback-vs-interpret-kernel parity
holds for int8 pools exactly as for fp pools. Kernel eligibility
follows the pool dtype's sublane tile: int8 pools need
``block_size % 32 == 0`` on TPU (use ``block_size=32``).
"""
from __future__ import annotations

import contextlib
import functools
import math
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention_kernel import kernel_scope

__all__ = ["paged_decode_attention", "pallas_paged_attention",
           "paged_verify_attention", "pallas_paged_verify_attention",
           "ragged_paged_attention", "pallas_ragged_paged_attention",
           "paged_attention_step", "ragged_attention_step",
           "sharded_ragged_attention_step", "kernel_fallback_counts",
           "tp_shard_degree", "serving_tp_scope",
           "serving_tp_active", "tree_ancestor_bits",
           "spec_tree_scope", "ragged_latent_attention",
           "pallas_ragged_latent_attention",
           "ragged_latent_attention_step", "LATENT_TILE"]

NEG_INF = np.float32(-1e30)


def tree_ancestor_bits(parents) -> tuple:
    """Per-node inclusive ancestor bitmasks for a speculative token
    tree. ``parents`` names node ``k + 1``'s parent (``parents[k] in
    [0, k]`` — nodes are numbered in topological order, node 0 the
    committed root); the chain topology is ``tuple(range(gamma))``.
    Bit ``j - 1`` of ``bits[t]`` is set iff window node ``j >= 1`` is
    an ancestor of node ``t`` OR ``t`` itself — exactly the columns
    window row ``t`` may attend to beyond the committed prefix (the
    prefix plus root ride the ``rel < 0`` term of the mask). A chain
    instantiates ``bits[t] = (1 << t) - 1``, which makes the tree mask
    boolean-identical to the linear causal bound ``cols < lens + t``
    at every mask site — the bitwise-parity pin."""
    parents = tuple(int(p) for p in parents)
    if len(parents) > 31:
        raise ValueError(
            f"spec tree supports at most 31 draft nodes (int32 "
            f"ancestor bitmask), got {len(parents)}")
    bits = [0]
    for k, p in enumerate(parents):
        if not 0 <= p <= k:
            raise ValueError(
                f"spec_tree[{k}] = {p}: node {k + 1}'s parent must be "
                f"an earlier node (0..{k})")
        bits.append(bits[p] | (1 << k))
    return tuple(bits)


_SPEC_TREE = threading.local()    # thread-scoped like serving_tp_scope
_AMBIENT = object()               # "read the ambient scope" sentinel


@contextlib.contextmanager
def spec_tree_scope(tree_anc, tree_slots=None):
    """Arm the token-tree verify mask for the duration of one trace.
    The serving engine / ``SpecGenerator`` enter this while tracing a
    tree-speculative executable; the attention step wrappers below
    read it at dispatch time, so MODEL forwards stay untouched (their
    ``ragged_meta`` tuple keeps its fixed 6-slot shape). ``tree_anc``
    is the static parent tuple (``tree_ancestor_bits`` validates it);
    ``tree_slots`` an optional traced [S] int32 flag vector naming
    which slots carry a tree window this tick (``None`` = all). The
    flag is thread-local so a tree compile on one thread never arms a
    concurrent trace on another. NOTE: the tensor-parallel wrapper
    reads the scope OUTSIDE ``shard_map`` and forwards ``tree_slots``
    as an explicit replicated operand — a traced array must never be
    closed over inside a manual region."""
    prev = getattr(_SPEC_TREE, "ctx", None)
    _SPEC_TREE.ctx = (tuple(int(p) for p in tree_anc)
                      if tree_anc is not None else None, tree_slots)
    try:
        yield
    finally:
        _SPEC_TREE.ctx = prev


def _tree_ctx():
    """(tree_anc, tree_slots) of the innermost ``spec_tree_scope``,
    or ``(None, None)`` outside one."""
    ctx = getattr(_SPEC_TREE, "ctx", None)
    return (None, None) if ctx is None else ctx

_FORCE_INTERPRET = False  # tests flip this to run the kernel on CPU


def _interpret() -> bool:
    """Interpreted only when asked for or on the CPU backend; a backend
    query that raises propagates (never a silent interpreter run on a
    machine whose accelerator failed to come up)."""
    return _FORCE_INTERPRET or jax.default_backend() == "cpu"


def _force_kernel_routing() -> bool:
    """``PADDLE_TPU_PAGED_KERNEL=interpret``: route eligible shapes to
    the Pallas kernels even OFF TPU (they run under the interpreter —
    ``_interpret()`` already flips there). Lets CPU tests and the
    decode-tick fusion bench compile the REAL kernelized graph, so the
    kernel census measures what TPU hardware would launch (the
    ``PADDLE_TPU_MOE_FUSED_GMM=interpret`` precedent)."""
    return os.environ.get("PADDLE_TPU_PAGED_KERNEL", "") == "interpret"


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _row_pad(rep, q_dtype) -> int:
    """Rows one kv group's ``rep = H / H_kv`` query heads occupy in the
    kernels: the next power of two >= ``rep`` that also fills a whole
    sublane tile of the query dtype (8 rows of f32, 16 of bf16). Mosaic
    then sees only tile-aligned matmul operands (Qwen2-7B's ``rep = 7``
    would otherwise be a ragged 7-row MXU operand), and the verify
    kernel recovers a row's window token with a shift instead of an
    integer division by ``rep``. The wrappers zero-pad the extra rows
    and drop them from the output."""
    rp = 32 // jnp.dtype(q_dtype).itemsize
    while rp < rep:
        rp *= 2
    return rp


def _pad_rep(q5, rp):
    """Zero-pad the ``rep`` axis (second to last) of ``[..., rep, D]``
    to ``rp`` rows."""
    pad = [(0, 0)] * q5.ndim
    pad[-2] = (0, rp - q5.shape[-2])
    return jnp.pad(q5, pad)


def _pool_view(pool):
    """``[NB, BS, H_kv, D]`` pool as the contiguous ``[NB, BS,
    H_kv * D]`` view the kernels index: a ``(1, BS, D)`` block at lane
    offset ``g * D`` is kv head ``g``'s ``[BS, D]`` tile, and its last
    two dims are (whole dim, 128-multiple) — the only K/V block shape
    Mosaic's block rule admits for ``H_kv > 1`` without moving the
    pool to another layout."""
    nb, bs, hkv, d = pool.shape
    return pool.reshape(nb, bs, hkv * d)


def _dequant_rows(data, sc, g):
    """In-VMEM dequant of pooled K/V rows after their DMA: int8
    ``[rows, D]`` x kv head ``g``'s per-position f32 scale. The scale
    rows carry every kv head (``[rows, H_kv]`` — a single-head column
    is not a legal Mosaic block); head ``g``'s column is picked by an
    iota mask + lane sum, exact because every other term is 0. The
    result STAYS f32 through the dots (accuracy over MXU rate on a
    bandwidth-bound op: re-rounding to bf16 would stack a second
    ~0.2% grid error on the int8 step and measurably cost greedy
    token-match) — the same recipe ``paged_cache.kv_dequantize`` runs
    in the gather fallback, so kernel and fallback read identical
    values from identical stored bytes."""
    head = jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
    sc_g = jnp.sum(jnp.where(head == g, sc, np.float32(0.0)),
                   axis=1, keepdims=True)             # [rows, 1]
    return data.astype(jnp.float32) * sc_g


def _dequant_tile(k_ref, sc_ref, g):
    """``_dequant_rows`` of one block-chased ``(1, BS, D)`` K/V block
    and its ``(1, BS, H_kv)`` scale block."""
    return _dequant_rows(k_ref[0], sc_ref[0], g)


def _decode_kernel(tables_ref, lens_ref, q_ref, k_ref, v_ref, *rest,
                   scale, block_size, n_blocks, t_q=1, row_shift=0,
                   quantized=False, tree_bits=None):
    """Shared body for single-token decode (``t_q=1``) and the
    speculative multi-query verify window (``t_q=gamma+1``): the
    ``t_q * rp`` softmax rows (``rp = 1 << row_shift``, see
    ``_row_pad``) carry a per-row causal bound — row ``r`` belongs to
    window token ``t = r >> row_shift`` and may see cache positions
    ``< lens_ref[s] + t`` (``lens_ref`` counts positions visible to
    window token 0, that token itself included).
    ``tree_bits`` (static per-node ancestor bitmasks,
    ``tree_ancestor_bits``) swaps that linear bound for the token-tree
    mask: window row ``t`` sees the committed prefix + root
    (``rel < 0``) plus exactly its own ancestor chain inside the
    window. A chain tree's bits reproduce the linear bound
    boolean-for-boolean, so this is the SAME kernel body either way.
    ``quantized`` pools ride two extra per-(position, head) scale
    operands; each K/V block tile dequantizes in VMEM right after its
    DMA — the HBM stream stays int8."""
    if quantized:
        ks_ref, vs_ref, o_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, m_scr, l_scr, acc_scr = rest
    s = pl.program_id(0)
    g = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    ctx = lens_ref[s]
    # ragged bound: blocks at/after the slot's LAST window token's
    # reach hold no live tokens — predicate off their FLOPs entirely
    @pl.when(j * block_size < ctx + (t_q - 1))
    def _compute():
        q = q_ref[0, 0]                       # [t_q * rp, D]
        if quantized:
            q = q.astype(jnp.float32)         # match the f32 dequant
            k = _dequant_tile(k_ref, ks_ref, g)
            v = _dequant_tile(v_ref, vs_ref, g)
        else:
            k = k_ref[0]                      # [BS, D]
            v = v_ref[0]
        sc = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        cols = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, sc.shape, 1)
        if t_q == 1:
            bound = ctx
            sc = jnp.where(cols < bound, sc, NEG_INF)
        elif tree_bits is None:
            # causal within the window: row r is window token
            # r >> row_shift
            bound = ctx + (jax.lax.broadcasted_iota(
                jnp.int32, sc.shape, 0) >> row_shift)
            sc = jnp.where(cols < bound, sc, NEG_INF)
        else:
            # token-tree verify window: window node j sits at cache
            # position lens-1+j, so rel = cols - ctx names the window
            # node (rel < 0 = committed prefix + root); row t keeps a
            # column iff that node is on its own ancestor path
            node = jax.lax.broadcasted_iota(
                jnp.int32, sc.shape, 0) >> row_shift
            bits = jnp.zeros(sc.shape, jnp.int32)
            for i, b in enumerate(tree_bits):
                bits = jnp.where(node == i, np.int32(b), bits)
            rel = cols - ctx
            ok = (rel < 0) | (
                ((bits >> jnp.clip(rel, 0, 31)) & 1) > 0)
            sc = jnp.where(ok, sc, NEG_INF)
        m_prev = m_scr[:, :1]
        l_prev = l_scr[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        p = jnp.exp(sc - m_cur)
        alpha = jnp.exp(m_prev - m_cur)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scr[:] = alpha * acc_scr[:] + pv
        m_scr[:] = jnp.broadcast_to(m_cur, m_scr.shape)
        l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(j == n_blocks - 1)
    def _finish():
        l = l_scr[:, :1]
        safe_l = jnp.where(l == 0.0, np.float32(1.0), l)
        o_ref[0, 0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


# rows (window tokens x ``_row_pad``) and cache positions one ragged
# grid step brings to the MXU: one 128 x 128 tile each way
_TILE_ROWS = 128
_TILE_POSITIONS = 128

# lanes of a pool row one ragged grid step copies and holds: the kv
# heads of one head group (``_head_group``). 512 lanes (4 heads of 128,
# or 8 of 64 in pairs) keep the step's K/V halves, q/out blocks and
# softmax state near 2 MB of VMEM and its body at four unrolled heads
_GROUP_LANES = 512


# the latent (MLA) kernel's tile: every head reads the one latent, so a
# window token brings all its heads (64 rows at the published widths);
# eight tokens' rows against 512 positions keep the latent's re-reads
# and the online softmax's rescaling well under a tile's two products.
# On one v5e, a 512-row chunk at context 2k beside 20 decode rows, 64
# heads: 2.32 ms a call against 2.96 at (256, 256), 3.66 at (128, 256),
# 3.20 at (1024, 256) (chip run, PR 26). What the tall tile costs a
# slot that brings ONE token (a decode row, a prompt trickling a row a
# tick) is no longer its dead rows: such a tile walks at the token's
# own rows (``_latent_rungs``), a kv tile in ~1.5 us where the full
# tile takes 4.5 — about its 655 KB copy and the products' stationary
# operands, which are pushed whatever the row count — and what is left
# of the tile's height there is the ``[512, W]`` query block in and
# the ``[512, value_dim]`` block out a grid step (1.2 MB, a live tile
# or not). At the long-prompt cell's shapes (32 slots, 544 rows, 64
# heads): that chunk beside 20 decode rows 2.09 -> 1.66 ms a call, a
# decode-only call of 32 slots at 800-7,000 positions 1.12 -> 0.43, of
# 20 slots 0.70 -> 0.29 (chip run, PR 35; the kernel alone, from a
# profiler trace)
LATENT_TILE = (512, 512)


def _ragged_geometry(rows, slots, rep, q_dtype, block_size, max_blocks,
                     tile=(_TILE_ROWS, _TILE_POSITIONS)):
    """Static index space of one ragged call, from what the operands
    show: ``(rp, tq, kb, n_tiles, n_kv)`` — ``rp`` rows a kv group's
    heads pad to (``_row_pad``), ``tq`` window rows of one slot a query
    tile holds (``tq * rp`` = ``tile[0]``, within
    ``_MAX_GROUP_ROWS``), ``kb`` pool blocks a kv tile chases
    (``kb * block_size`` = ``tile[1]`` where the block is
    smaller), ``n_tiles = slots + ceil(rows / tq)`` query tiles launched
    (every split of ``rows`` over ``slots`` fits: each slot wastes less
    than one tile) and ``n_kv`` kv tiles a full table holds."""
    rp = _row_pad(rep, q_dtype)
    tq = max(1, tile[0] // rp)
    kb = max(1, min(tile[1] // block_size, max_blocks))
    return (rp, tq, kb, slots + -(-rows // tq), -(-max_blocks // kb))


def _ragged_tiles(xp, ql, context_lens, tq, kv_span, n_tiles, n_kv):
    """The query tiles of one ragged call, ``[n_tiles]`` each: ``slot``
    the tile belongs to, ``row0`` its first window row, ``rows`` live
    in it (0 = a tile past the live count) and ``kv`` tiles of
    ``kv_span`` cache positions its walk visits — it ends where the
    tile's LAST live row's causal bound ``context_lens + row`` ends.
    ``ql`` is ``q_lens`` held to ``w_max``. ``xp`` is ``jnp`` inside
    the jitted wrapper and ``numpy`` on the host
    (``ragged_grid_units``): one rule, so the engine's counter cannot
    drift from what the kernel walks."""
    n_slots = ql.shape[0]
    per_slot = (ql + (tq - 1)) // tq
    ends = xp.cumsum(per_slot)
    t = xp.arange(n_tiles, dtype=ends.dtype)
    slot = xp.sum((t[:, None] >= ends[None, :]).astype(ends.dtype),
                  axis=1)
    live = slot < n_slots
    slot = xp.minimum(slot, n_slots - 1)
    row0 = (t - (ends - per_slot)[slot]) * tq
    rows = xp.where(live, xp.clip(ql[slot] - row0, 0, tq), 0)
    reach = context_lens[slot] + row0 + rows - 1
    kv = xp.where(rows > 0,
                  xp.clip((reach + (kv_span - 1)) // kv_span, 0, n_kv),
                  0)
    return slot, row0, rows, kv


def _head_group(num_kv_heads, head_lanes) -> int:
    """kv heads (lane tiles of ``head_lanes``) one ragged grid step
    takes: every one of the pool row while the group stays within
    ``_GROUP_LANES`` — a pool block then leaves HBM as ONE contiguous
    copy — else the largest divisor of ``num_kv_heads`` that does, and
    the step's query tile is walked once a group."""
    hg = max(1, min(num_kv_heads, _GROUP_LANES // head_lanes))
    while num_kv_heads % hg:
        hg -= 1
    return hg


def ragged_grid_units(q_lens, context_lens, *, rows, w_max, num_heads,
                      num_kv_heads, q_dtype, block_size, max_blocks,
                      tile=(_TILE_ROWS, _TILE_POSITIONS), head_lanes=128,
                      streams=2, narrow=False):
    """Host-side count of what ONE ``pallas_ragged_paged_attention``
    call (or, with ``num_kv_heads=1``, ``streams=1`` and
    ``tile=LATENT_TILE``, one ``pallas_ragged_latent_attention`` call)
    visits for this tick's ``q_lens`` / ``context_lens`` (numpy, no
    device read): ``(units, live, copies)``. ``units`` and ``live`` are
    in (query tile, kv head, kv tile) units: a live tile walks one loop
    iteration per kv tile of its reach for each of its kv heads, all
    live; a tile past the live count is still launched, predicated off
    — so ``units - live`` is the dead steps and ``live / units`` the
    share of the walk that is work. ``copies`` is the async-copy
    descriptors the call issues: per live (query tile, kv tile) one a
    pool block (``kb``) a stream (K and V; an int8 pool's two scale
    pools make ``streams`` 4) a head GROUP (``_head_group`` of
    ``head_lanes``-wide kv heads) — not one a kv head. ``narrow=True``
    (the latent call's geometry) appends a fourth value: the live
    units that the latent kernel walks at its narrow rung (a tile
    that holds one window token, ``_latent_rungs``: rows of one
    token's heads, not of ``tq`` tokens')."""
    ql = np.minimum(np.asarray(q_lens, np.int64), w_max)
    _, tq, kb, n_tiles, n_kv = _ragged_geometry(
        rows, ql.shape[0], num_heads // num_kv_heads, q_dtype,
        block_size, max_blocks, tile)
    _, _, held, kv = _ragged_tiles(
        np, ql, np.asarray(context_lens, np.int64), tq,
        kb * block_size, n_tiles, n_kv)
    walked = int(kv.sum())
    live = walked * num_kv_heads
    groups = num_kv_heads // _head_group(num_kv_heads, head_lanes)
    counts = (live + int((kv == 0).sum()) * num_kv_heads, live,
              walked * kb * streams * groups)
    if not narrow:
        return counts
    return counts + (
        int(kv[held <= _latent_rungs(tq)[0]].sum()) * num_kv_heads,)


def _ragged_kernel(tslot_ref, trow_ref, tkv_ref, tables_ref, lens_ref,
                   *args, scale, block_size, kv_blocks, max_blocks,
                   head_dim, heads, row_shift, quantized=False,
                   tree_bits=None):
    """Ragged mixed-batch body: grid ``(query tile, head group)``. A
    step holds ``tq`` consecutive window rows of ONE slot for the
    ``heads`` kv heads of its group (``[heads, tq * rp, D]``, row ``r``
    = window token ``trow_ref[t] + (r >> row_shift)``, causal bound
    ``lens + that token``) and walks the slot's cache in tiles of
    ``kv_blocks`` pool blocks, chased through ``tables_ref[slot]`` by
    double-buffered async copies out of the HBM pools, for exactly
    ``tkv_ref[t]`` iterations — the tile's own live reach, 0 for a tile
    past the live count (which stores zeros). A pool block is copied
    ONCE a stream a kv tile, all the group's heads side by side (where
    the group is the pool's whole row, one contiguous block); the
    heads then run as a static loop over the lane tiles of the copied
    buffer — ``heads`` independent online-softmax chains an iteration,
    each with its own f32 state, one mask. ``tree_bits`` (static ancestor bitmasks) adds a SIXTH
    scalar-prefetch operand ``tree_ref`` [S]: slots flagged ``> 0``
    mask their first ``len(tree_bits)`` window rows by ancestor path
    instead of the linear bound — unflagged slots (prefill chunks and
    their narrow trickle rows) keep the linear mask untouched.
    ``quantized``: the scale pools ride the same copies (a block's
    scales once for all its heads) and each K/V tile dequantizes in
    VMEM as in ``_decode_kernel``."""
    if tree_bits is not None:
        tree_ref, *args = args
    if quantized:
        (q_ref, k_hbm, v_hbm, ks_hbm, vs_hbm, o_ref, k_buf, v_buf,
         ks_buf, vs_buf, sems, m_scr, l_scr, acc_scr) = args
        streams = ((k_hbm, k_buf, True), (v_hbm, v_buf, True),
                   (ks_hbm, ks_buf, False), (vs_hbm, vs_buf, False))
    else:
        (q_ref, k_hbm, v_hbm, o_ref, k_buf, v_buf, sems,
         m_scr, l_scr, acc_scr) = args
        streams = ((k_hbm, k_buf, True), (v_hbm, v_buf, True))
    t = pl.program_id(0)
    g0 = pl.program_id(1) * heads             # the group's first kv head
    slot = tslot_ref[t]
    row0 = trow_ref[t]
    n_kv = tkv_ref[t]
    lens = lens_ref[slot]
    bs, kb, d = block_size, kv_blocks, head_dim
    whole_rows = k_hbm.shape[2] == heads * d

    def start(j, buf):
        """Start the async copies that bring kv tile ``j`` into buffer
        half ``buf``: per pool block the ``[BS, heads * D]`` lanes of
        the group (and, quantized, its ``[BS, H_kv]`` scale block).
        Blocks past the table's end re-read its last entry; their
        columns lie past every row's bound."""
        for i in range(kb):
            blk = tables_ref[slot, jnp.minimum(j * kb + i,
                                               max_blocks - 1)]
            for n, (hbm, vmem, per_head) in enumerate(streams):
                src = (hbm.at[blk, :, pl.ds(g0 * d, heads * d)]
                       if per_head and not whole_rows else hbm.at[blk])
                pltpu.make_async_copy(
                    src, vmem.at[buf, pl.ds(i * bs, bs), :],
                    sems.at[n, buf]).start()

    def wait(buf):
        """Wait for buffer half ``buf``'s copies: a wait takes the
        semaphore and the destination's size alone, so one descriptor
        a stream the size of the whole half stands for its ``kb``
        block copies, and no table entry is read again."""
        for n, (_, vmem, _) in enumerate(streams):
            pltpu.make_async_copy(vmem.at[buf], vmem.at[buf],
                                  sems.at[n, buf]).wait()

    m_scr[:] = jnp.full_like(m_scr, NEG_INF)
    l_scr[:] = jnp.zeros_like(l_scr)
    acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when(n_kv > 0)
    def _first():
        start(0, 0)

    def walk(j, carry):
        buf = jax.lax.rem(j, 2)

        @pl.when(j + 1 < n_kv)
        def _next():
            start(j + 1, 1 - buf)

        wait(buf)
        shape = (q_ref.shape[2], kb * bs)
        cols = j * (kb * bs) + jax.lax.broadcasted_iota(
            jnp.int32, shape, 1)
        # row r is window token row0 + (r >> row_shift)
        node = row0 + (jax.lax.broadcasted_iota(
            jnp.int32, shape, 0) >> row_shift)
        linear = cols < lens + node
        if tree_bits is None:
            keep = linear
        else:
            # tree slots: rel = cols - lens names the window node this
            # column holds (rel < 0 = committed prefix + root); row t
            # keeps it iff it is on t's ancestor path. Every tree
            # column satisfies the linear bound, so the walk's end
            # (the last live row's linear reach) stays a superset.
            bits = jnp.zeros(shape, jnp.int32)
            for i, b in enumerate(tree_bits):
                bits = jnp.where(node == i, np.int32(b), bits)
            rel = cols - lens
            ok_tree = (rel < 0) | (
                ((bits >> jnp.clip(rel, 0, 31)) & 1) > 0)
            # (boolean algebra, not a select between masks: Mosaic
            # cannot legalize arith.select on i1 vectors)
            is_tree = (jnp.full(shape, tree_ref[slot]) > 0) & (
                node < len(tree_bits))
            keep = (is_tree & ok_tree) | (
                jnp.logical_not(is_tree) & linear)
        for h in range(heads):
            lanes = pl.ds(h * d, d)
            q = q_ref[0, h]                   # [tq * rp, D]
            if quantized:
                q = q.astype(jnp.float32)     # match the f32 dequant
                k = _dequant_rows(k_buf[buf, :, lanes], ks_buf[buf],
                                  g0 + h)
                v = _dequant_rows(v_buf[buf, :, lanes], vs_buf[buf],
                                  g0 + h)
            else:
                k = k_buf[buf, :, lanes]      # [kb * BS, D]
                v = v_buf[buf, :, lanes]
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            sc = jnp.where(keep, sc, NEG_INF)
            m_prev = m_scr[h, :, :1]
            l_prev = l_scr[h, :, :1]
            m_cur = jnp.maximum(m_prev,
                                jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_cur)
            alpha = jnp.exp(m_prev - m_cur)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_scr[h] = alpha * acc_scr[h] + pv
            m_scr[h] = jnp.broadcast_to(m_cur, m_scr.shape[1:])
            l_scr[h] = jnp.broadcast_to(l_new, l_scr.shape[1:])
        return carry

    jax.lax.fori_loop(0, n_kv, walk, 0)
    l = l_scr[:, :, :1]
    safe_l = jnp.where(l == 0.0, np.float32(1.0), l)
    o_ref[0] = (acc_scr[:] / safe_l).astype(o_ref.dtype)


def flat_pool_tile(head_dim) -> int:
    """Lanes of one K/V tile the ragged kernel copies out of a FLAT
    pool (``paged_cache.init_flat_pool``: ``[NB, BS, H_kv * D]``) for
    queries of this head size: the head itself where it is whole lane
    tiles, else — head size 64 — the 128 lanes that hold two
    neighbouring kv heads (``_pair_queries``)."""
    return head_dim if head_dim % 128 == 0 else 2 * head_dim


def _pair_queries(q, pairs):
    """Head size 64 as head size 128 over half the kv heads, with no
    change to the kernel. Over a flat pool pair ``p``'s 128-lane tile
    holds kv heads ``2p`` (lanes 0-63) and ``2p + 1`` (lanes 64-127): a
    ``(BS, 64)`` block at a 64-lane offset is what Mosaic refuses. A
    query head of kv head ``2p`` takes its 64 lanes in the tile's first
    half and zeros in the second (``2p + 1``: the other way round), so
    its scores against the 128-lane key rows are exactly its own head's
    — the other head's lanes meet zeros — and of its output ``[..,
    128]`` over the paired value rows the half that is its own head's
    values is kept (``unpair``). The pair's ``2 * rep`` query heads
    share one K/V tile, so every K/V byte is still read once a query
    tile; the MXU multiplies twice the lanes, on a walk that waits for
    HBM. Returns ``(q [R, H, 128], unpair)``."""
    r, h, d = q.shape
    second = ((jnp.arange(h) // (h // (2 * pairs))) % 2 == 1)[None, :,
                                                                None]
    q2 = jnp.concatenate([jnp.where(second, 0, q),
                          jnp.where(second, q, 0)], -1)
    return q2, lambda out: jnp.where(second, out[..., d:], out[..., :d])


def _unflat(pool, head_dim):
    """A flat pool as the ``[NB, BS, H_kv, head_dim]`` the XLA mirror
    reads (the CPU path: a free reshape there)."""
    nb, bs, lanes = pool.shape
    return pool.reshape(nb, bs, lanes // head_dim, head_dim)


def _unpack_pools(k_pool, v_pool):
    """(k_view, v_view, [k_scale, v_scale] or [], quantized): each
    pool as its ``_pool_view``; quantized pools split into the int8
    data views plus the scale operands the kernels dequantize with."""
    from ..paged_cache import QuantKV
    if isinstance(k_pool, QuantKV):
        return (_pool_view(k_pool.data), _pool_view(v_pool.data),
                [k_pool.scale, v_pool.scale], True)
    return _pool_view(k_pool), _pool_view(v_pool), [], False


def _softmax_scratch(rows, d, lead=()):
    """Online-softmax state (running max, denominator, weighted
    values) for ``rows`` query rows (``lead``: a leading head axis)."""
    return [pltpu.VMEM((*lead, rows, 128), jnp.float32),
            pltpu.VMEM((*lead, rows, 128), jnp.float32),
            pltpu.VMEM((*lead, rows, d), jnp.float32)]


def pallas_paged_attention(q, k_pool, v_pool, block_tables,
                           context_lens, sm_scale=None,
                           interpret=None):
    """q: [S, H, D]; pools: [NB, BS, H_kv, D] (or ``QuantKV`` int8
    pools — dequantized per block tile in VMEM); block_tables:
    [S, MB] int32; context_lens: [S] int32 (valid positions per
    slot, current token included). Returns [S, H, D]."""
    return pallas_paged_verify_attention(
        q[:, None], k_pool, v_pool, block_tables, context_lens,
        sm_scale=sm_scale, interpret=interpret)[:, 0]


def pallas_paged_verify_attention(q, k_pool, v_pool, block_tables,
                                  context_lens, sm_scale=None,
                                  interpret=None, tree_anc=None):
    """Multi-query (speculative verify) variant. q: [S, T, H, D]
    (T = gamma + 1 window tokens per slot, already written to the
    pool; T = 1 is the decode step); context_lens: [S] int32 —
    positions visible to window token 0, itself included (token ``t``
    sees ``context_lens + t`` positions). ``tree_anc`` (static parent
    tuple, ``len = T-1``) masks every slot's window by ancestor path
    instead of the linear in-window bound (``tree_ancestor_bits``).
    Returns [S, T, H, D]."""
    s, t, h, d = q.shape
    nb, bs, hkv, _ = k_pool.shape
    kd, vd, scales, quant = _unpack_pools(k_pool, v_pool)
    mb = block_tables.shape[1]
    rep = h // hkv
    rp = _row_pad(rep, q.dtype)
    scale = np.float32(sm_scale if sm_scale is not None
                       else 1.0 / math.sqrt(d))
    tree_bits = None
    if tree_anc is not None:
        tree_bits = tree_ancestor_bits(tree_anc)
        if len(tree_bits) != t:
            raise ValueError(
                f"spec tree has {len(tree_bits)} nodes but the "
                f"verify window carries {t} rows")
    # rows grouped kv-head-major: [S, hkv, T*rp, D] so one K/V
    # block DMA feeds every window token of the kv group
    rows = t * rp
    q4 = _pad_rep(q.reshape(s, t, hkv, rep, d), rp) \
        .transpose(0, 2, 1, 3, 4).reshape(s, hkv, rows, d)
    kernel = functools.partial(
        _decode_kernel, scale=scale, block_size=bs, n_blocks=mb,
        t_q=t, row_shift=rp.bit_length() - 1, quantized=quant,
        tree_bits=tree_bits)

    def q_block(si, g, j, tables, lens):
        return (si, g, 0, 0)

    def kv_block(si, g, j, tables, lens):
        # chase the slot's block table; out-of-range grid steps read
        # the null block (tables are null-filled past the slot's
        # allocation) and are predicated off in the kernel
        return (tables[si, j], 0, g)

    def sc_block(si, g, j, tables, lens):
        return (tables[si, j], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(s, hkv, mb),
        in_specs=[
            pl.BlockSpec((1, 1, rows, d), q_block),
            pl.BlockSpec((1, bs, d), kv_block),
            pl.BlockSpec((1, bs, d), kv_block),
        ] + [pl.BlockSpec((1, bs, hkv), sc_block)] * len(scales),
        out_specs=pl.BlockSpec((1, 1, rows, d), q_block),
        scratch_shapes=_softmax_scratch(rows, d),
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((s, hkv, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel",
                                 "arbitrary")),
        interpret=_interpret() if interpret is None else interpret,
    )
    with kernel_scope("paged_decode_attention" if t == 1
                      else "paged_verify_attention"):
        out = call(block_tables.astype(jnp.int32),
                   context_lens.astype(jnp.int32), q4, kd, vd, *scales)
    return out.reshape(s, hkv, t, rp, d)[:, :, :, :rep] \
        .transpose(0, 2, 1, 3, 4).reshape(s, t, h, d)


def pallas_ragged_paged_attention(q, k_pool, v_pool, block_tables,
                                  context_lens, q_lens, row_starts,
                                  row_slot=None, w_max=None,
                                  sm_scale=None, interpret=None,
                                  tree_anc=None, tree_slots=None):
    """Ragged mixed-batch variant. q: [R, H, D] — ONE packed row
    buffer holding every live query row of a serving tick, slot
    ``s`` owning rows ``row_starts[s] .. row_starts[s] +
    q_lens[s]``; ``context_lens[s]`` = positions visible to the
    slot's first row, itself included (row ``t`` sees
    ``context_lens[s] + t``). ``w_max`` is the static per-slot
    row-count ceiling (rows of a slot past it are not attended).
    ``row_slot`` is accepted for fallback-signature parity and unused
    here. ``tree_anc`` (static parent tuple) + ``tree_slots`` ([S]
    int32 flags, ``None`` = every slot) mask the flagged slots' verify
    windows by ancestor path — unflagged slots (prefill chunks and
    their trickle rows) keep the linear bound.

    The packed rows are cut into query tiles (``_ragged_tiles``) here,
    in XLA: gathered into a tile-aligned ``[tiles, H_kv, tq * rp, D]``
    buffer going in and gathered back per packed row coming out, so a
    tile's dead rows never reach a live packed row. Returns [R, H, D];
    rows no slot owns come back zero."""
    r, h, d = q.shape
    scale = np.float32(sm_scale if sm_scale is not None
                       else 1.0 / math.sqrt(d))
    unpair = None
    if k_pool.ndim == 3:
        # a flat pool IS the view the kernel indexes: no reshape (which
        # on the chip is a copy of the whole pool, a tick, a layer)
        nb, bs, lanes = k_pool.shape
        tile = flat_pool_tile(d)
        hkv = lanes // tile
        kd, vd, scales, quant = k_pool, v_pool, [], False
        if tile != d:
            q, unpair = _pair_queries(q, hkv)
            d = tile
    else:
        nb, bs, hkv, _ = k_pool.shape
        kd, vd, scales, quant = _unpack_pools(k_pool, v_pool)
    s, mb = block_tables.shape
    w = int(w_max)
    rep = h // hkv
    rp, tq, kb, n_tiles, n_kv = _ragged_geometry(r, s, rep, q.dtype, bs,
                                                 mb)
    tree_bits = None
    tree_args = []
    if tree_anc is not None:
        tree_bits = tree_ancestor_bits(tree_anc)
        if tree_slots is None:
            tree_slots = jnp.ones((s,), jnp.int32)
        tree_args = [tree_slots.astype(jnp.int32)]
    ql = jnp.minimum(q_lens.astype(jnp.int32), w)
    starts = row_starts.astype(jnp.int32)
    lens = context_lens.astype(jnp.int32)
    tslot, trow, _, tkv = (a.astype(jnp.int32) for a in _ragged_tiles(
        jnp, ql, lens, tq, kb * bs, n_tiles, n_kv))
    # tile t's rows in the packed buffer (clipped: a dead row reads
    # some live row's q and its output is never gathered back)
    src = jnp.clip((starts[tslot] + trow)[:, None]
                   + jnp.arange(tq, dtype=jnp.int32)[None, :], 0, r - 1)
    # rows grouped kv-head-major: [tiles, hkv, tq * rp, D] so one K/V
    # tile feeds every window row of the kv group
    rows = tq * rp
    q4 = _pad_rep(q[src].reshape(n_tiles, tq, hkv, rep, d), rp) \
        .transpose(0, 2, 1, 3, 4).reshape(n_tiles, hkv, rows, d)
    # a scale block leaves HBM by an async copy of whole lane tiles:
    # the head axis is padded out to one (the layout HBM holds it in
    # anyway — a minor dim under 128 lanes is stored padded to them)
    scales = [jnp.pad(sc, ((0, 0), (0, 0), (0, -hkv % 128)))
              for sc in scales]
    hg = _head_group(hkv, d)
    kernel = functools.partial(
        _ragged_kernel, scale=scale, block_size=bs, kv_blocks=kb,
        max_blocks=mb, head_dim=d, heads=hg,
        row_shift=rp.bit_length() - 1, quantized=quant,
        tree_bits=tree_bits)

    def q_block(t, g, *prefetch):
        return (t, g, 0, 0)

    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    n_streams = 2 + len(scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5 + len(tree_args),
        grid=(n_tiles, hkv // hg),
        in_specs=[pl.BlockSpec((1, hg, rows, d), q_block)]
        + [hbm] * n_streams,
        out_specs=pl.BlockSpec((1, hg, rows, d), q_block),
        scratch_shapes=[pltpu.VMEM((2, kb * bs, hg * d), kd.dtype)] * 2
        + [pltpu.VMEM((2, kb * bs, sc.shape[2]), jnp.float32)
           for sc in scales]
        + [pltpu.SemaphoreType.DMA((n_streams, 2))]
        + _softmax_scratch(rows, d, (hg,)),
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, hkv, rows, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            # no state crosses a step: every (tile, head group) starts
            # and waits its own copies and writes its own output block
            dimension_semantics=("parallel", "parallel")),
        interpret=_interpret() if interpret is None else interpret,
    )
    with kernel_scope("ragged_paged_attention"):
        out = call(tslot, trow, tkv, block_tables.astype(jnp.int32),
                   lens, *tree_args, q4, kd, vd, *scales)
    out = out.reshape(n_tiles, hkv, tq, rp, d)[:, :, :, :rep] \
        .transpose(0, 2, 1, 3, 4).reshape(n_tiles * tq, h, d)
    out = _untile(out, starts, ql, tq, r)
    return out if unpair is None else unpair(out)


def _untile(out, starts, ql, tq, r):
    """Tile-ordered rows ``[n_tiles * tq, ...]`` back to the packed
    buffer's ``[R, ...]``: packed row -> (its slot's tile, row in the
    tile); rows no slot owns come back zero."""
    row = jnp.arange(r, dtype=jnp.int32)[:, None]
    owned = (row >= starts[None, :]) & (row < (starts + ql)[None, :])
    owner = jnp.argmax(owned, axis=1).astype(jnp.int32)
    per_slot = (ql + (tq - 1)) // tq
    first_tile = (jnp.cumsum(per_slot) - per_slot).astype(jnp.int32)
    local = row[:, 0] - starts[owner]
    back = (first_tile[owner] + local // tq) * tq + local % tq
    has = jnp.any(owned, axis=1)
    return jnp.where(has[:, None, None],
                     out[jnp.where(has, back, 0)], 0)


def _latent_kernel(tslot_ref, trow_ref, tkv_ref, tlive_ref, tables_ref,
                   lens_ref, q_ref, c_hbm, o_ref, c_buf, sems, m_scr,
                   l_scr, acc_scr, *, scale, block_size, kv_blocks,
                   max_blocks, value_dim, row_shift, rungs):
    """Latent (MLA) ragged body: grid ``(query tile,)``. Every head of
    a window token reads the SAME cached row — the compressed
    ``c_kv`` and the shared rotary key ``k_pe`` side by side, ``W``
    lanes — so a step holds ``tq`` consecutive window rows of ONE slot
    with all their heads (``[tq * rp, W]``, row ``r`` = window token
    ``trow_ref[t] + (r >> row_shift)``, the absorbed query: ``q_nope
    W_UK`` beside ``q_pe``) and walks the slot's cache as
    ``_ragged_kernel`` does: ``tkv_ref[t]`` tiles of ``kv_blocks``
    pool blocks, chased through ``tables_ref[slot]`` by double-buffered
    async copies out of the HBM pool, one wait a kv tile. One copied
    tile serves both products: the scores contract all ``W`` lanes, the
    values are the tile's first ``value_dim`` lanes.

    A step computes over the rows its tile HOLDS: ``tlive_ref[t]`` is
    the tile's live window tokens and ``rungs`` the static token
    counts a walk is compiled at, ascending (``_latent_rungs``); the
    step takes the smallest rung that covers its tile — a decoding
    slot's and a trickling prompt's one token walk ``rp`` rows of
    query, scores and softmax state a kv tile, a chunk's tiles all
    ``tq * rp`` — and stores zeros in the rows of its output block
    above the rung. Every rung is the one body at another static row
    count: a live row's scores, softmax and weighted sum do not depend
    on the other rows of its tile."""
    t = pl.program_id(0)
    slot = tslot_ref[t]
    row0 = trow_ref[t]
    n_kv = tkv_ref[t]
    live = tlive_ref[t]
    lens = lens_ref[slot]
    bs, kb = block_size, kv_blocks

    def start(j, buf):
        """Start the ``kb`` block copies that bring kv tile ``j`` into
        buffer half ``buf``. Blocks past the table's end re-read its
        last entry; their columns lie past every row's bound."""
        for i in range(kb):
            blk = tables_ref[slot, jnp.minimum(j * kb + i,
                                               max_blocks - 1)]
            pltpu.make_async_copy(
                c_hbm.at[blk], c_buf.at[buf, pl.ds(i * bs, bs), :],
                sems.at[buf]).start()

    def wait(buf):
        """One descriptor the size of the buffer half stands for its
        ``kb`` block copies (``_ragged_kernel.wait``): no table entry
        is read a second time."""
        pltpu.make_async_copy(c_buf.at[buf], c_buf.at[buf],
                              sems.at[buf]).wait()

    def walk_rows(n):
        """The whole step over the tile's first ``n`` rows (static)."""
        m_scr[:n] = jnp.full((n, m_scr.shape[1]), NEG_INF, m_scr.dtype)
        l_scr[:n] = jnp.zeros((n, l_scr.shape[1]), l_scr.dtype)
        acc_scr[:n] = jnp.zeros((n, value_dim), acc_scr.dtype)

        @pl.when(n_kv > 0)
        def _first():
            start(0, 0)

        def walk(j, carry):
            buf = jax.lax.rem(j, 2)

            @pl.when(j + 1 < n_kv)
            def _next():
                start(j + 1, 1 - buf)

            wait(buf)
            q = q_ref[0, :n]                      # [n, W]
            c_tile = c_buf[buf]                   # [kb * BS, W]
            sc = jax.lax.dot_general(
                q, c_tile, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            cols = j * (kb * bs) + jax.lax.broadcasted_iota(
                jnp.int32, sc.shape, 1)
            node = row0 + (jax.lax.broadcasted_iota(
                jnp.int32, sc.shape, 0) >> row_shift)
            sc = jnp.where(cols < lens + node, sc, NEG_INF)
            m_prev = m_scr[:n, :1]
            l_prev = l_scr[:n, :1]
            m_cur = jnp.maximum(m_prev,
                                jnp.max(sc, axis=-1, keepdims=True))
            p = jnp.exp(sc - m_cur)
            alpha = jnp.exp(m_prev - m_cur)
            l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(c_tile.dtype), c_tile[:, :value_dim],
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            acc_scr[:n] = alpha * acc_scr[:n] + pv
            m_scr[:n] = jnp.broadcast_to(m_cur, (n, m_scr.shape[1]))
            l_scr[:n] = jnp.broadcast_to(l_new, (n, l_scr.shape[1]))
            return carry

        jax.lax.fori_loop(0, n_kv, walk, 0)
        l = l_scr[:n, :1]
        safe_l = jnp.where(l == 0.0, np.float32(1.0), l)
        o_ref[0, :n] = (acc_scr[:n] / safe_l).astype(o_ref.dtype)
        dead = o_ref.shape[1] - n
        if dead:
            o_ref[0, n:] = jnp.zeros((dead, value_dim), o_ref.dtype)

    # the smallest rung that covers the tile's live tokens; a tile past
    # the live count (0 tokens, 0 kv tiles) takes the first
    rung = sum((live > tokens).astype(jnp.int32) for tokens in rungs[:-1])
    for i, tokens in enumerate(rungs):
        pl.when(rung == i)(
            functools.partial(walk_rows, tokens << row_shift))


def _latent_rungs(tq):
    """Window tokens of a tile the latent kernel's walk is compiled
    at, ascending; a grid step takes the smallest that covers its
    tile's live tokens. Two rungs: ONE token — a decoding slot, a
    pending prompt trickling a row a tick: a third of the long-prompt
    cell's walk, at an eighth of the full tile's rows — and the whole
    tile."""
    return (1, tq) if tq > 1 else (tq,)


def pallas_ragged_latent_attention(q, pool, block_tables, context_lens,
                                   q_lens, row_starts, w_max, value_dim,
                                   sm_scale, interpret=None):
    """Ragged mixed-batch attention over a latent (MLA) pool. q:
    ``[R, H, W]`` — the absorbed queries of every live row of a tick,
    laid out as the pool's rows are (``q_nope W_UK`` on the
    ``c_kv`` lanes, ``q_pe`` on the ``k_pe`` lanes, zeros on the pad
    lanes); pool: ``[NB, BS, W]``, ``W`` a whole number of 128-lane
    tiles; the slot partition (``q_lens`` / ``row_starts`` /
    ``context_lens`` / ``w_max``) as in
    ``pallas_ragged_paged_attention``, whose tiling (``_ragged_tiles``)
    this shares, with all ``H`` heads of a token in one tile. Each
    tile's live token count rides beside its slot, first row and kv
    reach, and a grid step computes over that many rows' rung
    (``_latent_rungs``; ``ragged_grid_units(..., narrow=True)`` counts
    the same choice on the host). Returns
    ``[R, H, value_dim]``: per head the softmax-weighted sum of the
    cached rows' first ``value_dim`` lanes (``u_h``, still to be
    expanded by ``W_UV``); rows no slot owns come back zero."""
    r, h, wd = q.shape
    nb, bs, _ = pool.shape
    s, mb = block_tables.shape
    rp, tq, kb, n_tiles, n_kv = _ragged_geometry(
        r, s, h, q.dtype, bs, mb, LATENT_TILE)
    ql = jnp.minimum(q_lens.astype(jnp.int32), int(w_max))
    starts = row_starts.astype(jnp.int32)
    lens = context_lens.astype(jnp.int32)
    tslot, trow, tlive, tkv = (
        a.astype(jnp.int32) for a in _ragged_tiles(
            jnp, ql, lens, tq, kb * bs, n_tiles, n_kv))
    src = jnp.clip((starts[tslot] + trow)[:, None]
                   + jnp.arange(tq, dtype=jnp.int32)[None, :], 0, r - 1)
    rows = tq * rp
    q3 = _pad_rep(q[src], rp).reshape(n_tiles, rows, wd)
    kernel = functools.partial(
        _latent_kernel, scale=np.float32(sm_scale), block_size=bs,
        kv_blocks=kb, max_blocks=mb, value_dim=value_dim,
        row_shift=rp.bit_length() - 1, rungs=_latent_rungs(tq))

    def q_block(t, *prefetch):
        return (t, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(n_tiles,),
        in_specs=[pl.BlockSpec((1, rows, wd), q_block),
                  pl.BlockSpec(memory_space=pltpu.HBM)],
        out_specs=pl.BlockSpec((1, rows, value_dim), q_block),
        scratch_shapes=[pltpu.VMEM((2, kb * bs, wd), pool.dtype),
                        pltpu.SemaphoreType.DMA((2,))]
        + _softmax_scratch(rows, value_dim),
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((n_tiles, rows, value_dim),
                                       q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=_interpret() if interpret is None else interpret,
    )
    with kernel_scope("ragged_latent_attention"):
        out = call(tslot, trow, tkv, tlive,
                   block_tables.astype(jnp.int32), lens, q3, pool)
    out = out.reshape(n_tiles, tq, rp, value_dim)[:, :, :h] \
        .reshape(n_tiles * tq, h, value_dim)
    return _untile(out, starts, ql, tq, r)


# ---------------------------------------------------------------------------
# jnp fallback + dispatcher
# ---------------------------------------------------------------------------

def _xla_paged_attention(q, k_pool, v_pool, block_tables, context_lens,
                         sm_scale=None):
    """Gather-based fallback of the single-token decode step: the
    ``T = 1`` window of ``_xla_paged_verify``, so a decode row is
    bitwise the same row of a verify window or of the ragged mirror
    (one body, one order of operations)."""
    return _xla_paged_verify(q[:, None], k_pool, v_pool, block_tables,
                             context_lens, sm_scale=sm_scale)[:, 0]


def _xla_paged_verify(q, k_pool, v_pool, block_tables, context_lens,
                      sm_scale=None, tree_anc=None, tree_rows=None):
    """Multi-query gather fallback (speculative verify window): a
    dense per-slot view of the pooled blocks, masked by a
    per-window-token causal bound. Mirrors ``cached_attention``'s
    dtype recipe (f32 score accumulation, input-dtype PV contraction)
    so greedy decode matches the dense path token-for-token, and the
    verify forward is the numerics twin of T sequential single-token
    decode steps — greedy acceptance stays token-exact on CPU.
    ``tree_anc`` (static parent tuple) swaps the linear bound for the
    ancestor-path tree mask, op-for-op the
    kernels' recipe; ``tree_rows`` ([S] flags, ``None`` = all) selects
    which slots carry a tree window (the others keep the linear
    bound — a chain tree's mask IS the linear bound, so parity pins
    hold either way)."""
    s, t, h, d = q.shape
    hkv = k_pool.shape[2]
    rep = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    from ..paged_cache import QuantKV, gather_dense
    # quantized pools: gather_dense dequantizes to f32 and the math
    # STAYS f32 (no re-round to the activation dtype) — the kernel's
    # in-VMEM dequant recipe, value for value
    ad = jnp.float32 if isinstance(k_pool, QuantKV) else q.dtype
    k = gather_dense(k_pool, block_tables)      # [S, L, Hkv, D]
    v = gather_dense(v_pool, block_tables)
    lens = context_lens.astype(jnp.int32)
    q6 = q.reshape(s, t, hkv, rep, d)
    scores = jnp.einsum(
        "stgrd,slgd->sgtrl", q6, k.astype(ad),
        preferred_element_type=jnp.float32) * scale
    pos = jnp.arange(k.shape[1], dtype=jnp.int32)
    bound = lens[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    allow = pos[None, None, :] < bound[:, :, None]   # [S, T, L]
    if tree_anc is not None:
        bits = tree_ancestor_bits(tree_anc)
        if len(bits) != t:
            raise ValueError(
                f"spec tree has {len(bits)} nodes but the verify "
                f"window carries {t} rows")
        bits_a = jnp.asarray(bits, jnp.int32)        # [T]
        rel = pos[None, None, :] - lens[:, None, None]
        bit = (bits_a[None, :, None] >> jnp.clip(rel, 0, 31)) & 1
        allow_tree = (rel < 0) | (bit > 0)
        if tree_rows is None:
            allow = allow_tree
        else:
            tr = tree_rows.astype(jnp.int32) > 0
            allow = jnp.where(tr[:, None, None], allow_tree, allow)
    bias = jnp.where(allow, 0.0, -1e9)               # [S, T, L]
    scores = scores + bias[:, None, :, None, :]
    w = jax.nn.softmax(scores, axis=-1).astype(ad)
    out = jnp.einsum("sgtrl,slgd->stgrd", w, v.astype(ad))
    return out.astype(q.dtype).reshape(s, t, h, d)


def _xla_ragged_paged(q, k_pool, v_pool, block_tables, context_lens,
                      q_lens, row_starts, row_slot, w_narrow, w_max,
                      sm_scale=None, tree_anc=None, tree_slots=None):
    """Ragged gather fallback in TWO lanes (``_xla_ragged_lanes``),
    both pure ``_xla_paged_verify`` calls so every live row stays
    BITWISE the sequential per-width fallback's output (softmax rows
    are independent — the batched window width never changes a value;
    test-pinned in f32 AND bf16).

    ``tree_anc`` + ``tree_slots`` route the flagged slots' narrow-lane
    windows through the ancestor-path tree mask (``w_narrow`` must
    equal the tree's node count); the wide lane — always a prefill
    chunk, never a verify window — stays linear."""
    tree_rows = None
    if tree_anc is not None and tree_slots is not None:
        tree_rows = tree_slots

    def verify(q4, tables, lens, narrow):
        if not narrow:
            return _xla_paged_verify(q4, k_pool, v_pool, tables, lens,
                                     sm_scale=sm_scale)
        return _xla_paged_verify(q4, k_pool, v_pool, tables, lens,
                                 sm_scale=sm_scale, tree_anc=tree_anc,
                                 tree_rows=tree_rows)

    return _xla_ragged_lanes(q, verify, block_tables, context_lens,
                             q_lens, row_starts, row_slot, w_narrow,
                             w_max)


def _xla_ragged_lanes(q, verify, block_tables, context_lens, q_lens,
                      row_starts, row_slot, w_narrow, w_max):
    """The two lanes of a ragged gather fallback over
    ``verify(q [S', T, H, D], tables [S', MB], lens [S'], narrow)``:

    - **narrow lane**: every slot's first ``w_narrow`` rows (the
      decode / speculative-verify width, ``gamma + 1``) as one padded
      ``[S, w_narrow]`` verify — exactly the per-width decode/verify
      fallback's compute.
    - **wide lane**: THE single slot carrying more than ``w_narrow``
      rows (a prefill chunk; the serving engine schedules at most ONE
      wide slot per tick — the op contract) as one ``[1, w_max]``
      verify against its dynamically gathered table row.

    Attention FLOPs therefore scale with ``S * w_narrow + w_max`` —
    the live row count — instead of the ``S * w_max`` a naively padded
    layout would pay on every decode-only tick. Pad/dead rows produce
    garbage the caller discards."""
    r, h, d = q.shape
    s = block_tables.shape[0]
    wn = int(w_narrow)
    w = int(w_max)
    lens32 = q_lens.astype(jnp.int32)
    starts = row_starts.astype(jnp.int32)
    slot = row_slot.astype(jnp.int32)
    local = jnp.arange(r, dtype=jnp.int32) - starts[slot]      # [R]
    live = (local >= 0) & (local < lens32[slot]) & (local < w)
    # narrow lane: dead/pad rows scatter into (and gather from) a
    # garbage slot S; the K/V stays per-SLOT dense views, exactly the
    # per-width fallbacks' traffic
    nar = live & (local < wn)
    q_pad = jnp.zeros((s + 1, wn, h, d), q.dtype)
    q_pad = q_pad.at[jnp.where(nar, slot, s),
                     jnp.where(nar, jnp.minimum(local, wn - 1),
                               0)].set(q)
    out_n = verify(q_pad[:s], block_tables, context_lens, True)
    out = out_n[jnp.clip(slot, 0, s - 1),
                jnp.clip(local, 0, wn - 1)]                    # [R,H,D]
    if w <= wn:
        return out

    def _with_wide(o):
        # wide lane: the unique slot with q_lens > w_narrow
        wide = jnp.argmax(lens32).astype(jnp.int32)
        ws = starts[wide]
        rows_idx = jnp.clip(ws + jnp.arange(w, dtype=jnp.int32),
                            0, r - 1)
        out_w = verify(q[rows_idx][None], block_tables[wide][None],
                       context_lens[wide][None], False)[0]     # [W,H,D]
        use_w = (slot == wide) & (lens32[wide] > wn) & live
        return jnp.where(use_w[:, None, None],
                         out_w[jnp.clip(local, 0, w - 1)], o)

    # a decode/verify-only tick carries no wide slot: skip the whole
    # wide-lane gather + einsum at runtime (when a wide slot exists
    # the branch output is bitwise the unconditional merge — the
    # merge mask was all-false without one), so steady-state ticks
    # cost the per-width verify, not verify + a dead chunk pass
    return jax.lax.cond(jnp.max(lens32) > wn, _with_wide,
                        lambda o: o, out)


def _xla_latent_verify(q, pool, block_tables, context_lens, value_dim,
                       sm_scale):
    """The latent kernel's XLA mirror for one ``[S, T]`` window: the
    dense per-slot view of the pooled rows, scores over all ``W``
    lanes in f32, causal per window token, values = the rows' first
    ``value_dim`` lanes. q ``[S, T, H, W]`` -> ``[S, T, H, value_dim]``."""
    from ..paged_cache import gather_dense
    t = q.shape[1]
    c = gather_dense(pool, block_tables).astype(q.dtype)    # [S, L, W]
    scores = jnp.einsum("sthw,slw->shtl", q, c,
                        preferred_element_type=jnp.float32) \
        * np.float32(sm_scale)
    pos = jnp.arange(c.shape[1], dtype=jnp.int32)
    bound = context_lens.astype(jnp.int32)[:, None] \
        + jnp.arange(t, dtype=jnp.int32)[None, :]
    allow = pos[None, None, :] < bound[:, :, None]          # [S, T, L]
    scores = scores + jnp.where(allow, 0.0, -1e9)[:, None]
    w = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("shtl,slv->sthv", w, c[..., :value_dim])


# query rows one kv group may bring to a grid step (window tokens x
# ``_row_pad``): online-softmax state, the q/out blocks and the score
# tile all scale with it, and 1024 rows keep them under ~6 MB of the
# 16 MB Mosaic grants a kernel by default
_MAX_GROUP_ROWS = 1024


def _kernel_eligible(num_heads, head_dim, q_dtype, k_pool, window=1,
                     flat_ok=False):
    """Exactly the shapes the kernels above lower and compile for
    (cross-lowered for TPU in ``tests/test_tpu_lowering.py``, compiled
    by Mosaic in ``chip_smoke.py``): the ``_pool_view`` K/V block is
    ``(BS, D)`` at a lane offset, so ``D`` must be a whole number of
    128-lane tiles (64 is not) and ``BS`` a whole number of sublane
    tiles of the pool dtype (8 f32, 16 bf16/f16, 32 int8/fp8);
    ``window`` query tokens per slot must fit ``_MAX_GROUP_ROWS``.
    Over a FLAT pool (3-D, ``flat_ok``: the ragged kernel alone) the
    block is ``flat_pool_tile`` lanes wide, which serves head size 64
    as well: two kv heads a tile."""
    sublanes = 32 // jnp.dtype(k_pool.dtype).itemsize
    if k_pool.ndim == 3:
        d = flat_pool_tile(head_dim)
        if not flat_ok or k_pool.shape[2] % d:
            return False
        hkv = k_pool.shape[2] // d
    else:
        hkv, d = k_pool.shape[2], head_dim
    if num_heads % hkv:
        return False
    rows = window * _row_pad(num_heads // hkv, q_dtype)
    return (d % 128 == 0
            and k_pool.shape[1] % sublanes == 0
            and rows <= _MAX_GROUP_ROWS)


def _use_kernel(kind, q_shape, num_heads, head_dim, q_dtype, k_pool,
                window=1, flat_ok=False):
    """The ONE routing decision of the three entry points: the Pallas
    kernel on a TPU backend (or under ``PADDLE_TPU_PAGED_KERNEL=
    interpret``) for eligible shapes, the gather fallback otherwise.
    An ineligible shape on TPU is counted (``kernel_fallback_counts``);
    nothing here is caught — a kernel the gate chose that then fails
    to trace or compile is an error."""
    on_tpu = jax.default_backend() == "tpu"
    use = (on_tpu or _force_kernel_routing()) and _kernel_eligible(
        num_heads, head_dim, q_dtype, k_pool, window, flat_ok)
    if on_tpu and not use:
        _warn_fallback(kind, q_shape, k_pool.shape)
    return use


_fallback_warned = set()    # paths that already logged their fallback
_fallback_counts = {}       # path -> times the kernel was refused


def kernel_fallback_counts() -> dict:
    """Per-entry-point count of Pallas-kernel refusals (TPU backend
    falling back to the XLA gather path). Mirrored into
    ``ServingEngine.stats()["kernel_fallbacks"]`` so a production
    engine silently losing the kernel is visible in telemetry, not
    just a one-shot warning."""
    return dict(_fallback_counts)


def count_fallback(kind) -> bool:
    """Record one refusal of entry point ``kind`` (also the fused
    decode kernels' — one counter for every serving kernel): the
    ``serving_kernel_fallback`` monitor counter (JSONL-exported) and
    the dict ``ServingEngine.stats()`` mirrors. Returns True the first
    time ``kind`` is seen, so callers warn once per entry point."""
    _fallback_counts[kind] = _fallback_counts.get(kind, 0) + 1
    from ... import monitor
    monitor.counter(
        "serving_kernel_fallback",
        "serving kernel entry points routed to their XLA fallback on "
        "a TPU backend (shape not kernel-eligible)",
        labels=("path",)).labels(path=kind).inc()
    first = kind not in _fallback_warned
    _fallback_warned.add(kind)
    return first


def _warn_fallback(kind, q_shape, pool_shape):
    """TPU diagnostic: running the gather fallback in production means
    the decode/verify hot loop lost the kernel — counted every time,
    warned once per entry point."""
    if count_fallback(kind):
        import warnings
        warnings.warn(
            "%s: shape %s / pool %s not kernel-eligible (head_dim "
            "must be a 128-multiple — the ragged kernel also takes 64 "
            "over a flat pool — and block_size a sublane-tile multiple "
            "for the pool dtype); using the gather fallback"
            % (kind, tuple(q_shape), tuple(pool_shape)))


def paged_decode_attention(q, k_pool, v_pool, block_tables, context_lens,
                           sm_scale=None):
    """Ragged paged decode attention; q: [S, H, D] (one token per slot).
    Routes to the Pallas kernel on TPU, the gather fallback elsewhere."""
    if _use_kernel("paged_decode_attention", q.shape, q.shape[1],
                   q.shape[2], q.dtype, k_pool):
        return pallas_paged_attention(q, k_pool, v_pool, block_tables,
                                      context_lens, sm_scale=sm_scale)
    return _xla_paged_attention(q, k_pool, v_pool, block_tables,
                                context_lens, sm_scale=sm_scale)


def paged_attention_step(qh, kh, vh, k_pool, v_pool, block_tables,
                         cache_lens, sm_scale=None):
    """Write this step's K/V into the pool and attend — the shared
    per-width body behind the models' paged forward
    (``generate(cache_impl="paged")``, ``SpecGenerator``): ``T = 1``
    (qh ``[S, 1, H, D]``) is the decode step, ``T > 1`` the
    speculative verify window. Returns
    ``(out [S, T, H, D], k_pool, v_pool)``."""
    from ..paged_cache import write_decode, write_tokens
    lens = cache_lens.astype(jnp.int32)
    if qh.shape[1] == 1:
        kp2, vp2 = write_decode(k_pool, v_pool, block_tables, lens,
                                kh[:, 0], vh[:, 0])
        out = paged_decode_attention(qh[:, 0], kp2, vp2, block_tables,
                                     lens + 1, sm_scale=sm_scale)
        return out[:, None], kp2, vp2
    kp2, vp2 = write_tokens(k_pool, v_pool, block_tables, lens, kh, vh)
    out = paged_verify_attention(qh, kp2, vp2, block_tables, lens + 1,
                                 sm_scale=sm_scale)
    return out, kp2, vp2


def ragged_paged_attention(q, k_pool, v_pool, block_tables,
                           context_lens, q_lens, row_starts, row_slot,
                           narrow_iota, win_iota, sm_scale=None,
                           tree_anc=None, tree_slots=None):
    """Ragged mixed-batch paged attention over ONE packed row buffer;
    q: [R, H, D] (every live query row of a serving tick, partitioned
    by per-slot ``q_lens``/``row_starts``; ``row_slot[r]`` names row
    ``r``'s slot). ``context_lens[s]`` = positions visible to slot
    ``s``'s FIRST row, itself included. ``narrow_iota``/``win_iota``
    are iotas whose SHAPES carry the static widths through the traced
    call: ``w_narrow`` (= gamma+1, the decode/verify width every slot
    may use) and ``w_max`` (the chunk ceiling — AT MOST ONE slot per
    call may carry more than ``w_narrow`` rows; the serving scheduler
    guarantees it). ``tree_anc``/``tree_slots`` (see
    ``spec_tree_scope``) mask the flagged slots' windows by ancestor
    path. Routes to the ragged Pallas kernel on TPU, the two-lane
    verify fallback elsewhere."""
    wn = int(narrow_iota.shape[0])
    w = int(win_iota.shape[0])
    if _use_kernel("ragged_paged_attention", q.shape, q.shape[1],
                   q.shape[2], q.dtype, k_pool, flat_ok=True):
        return pallas_ragged_paged_attention(
            q, k_pool, v_pool, block_tables, context_lens, q_lens,
            row_starts, row_slot=row_slot, w_max=w, sm_scale=sm_scale,
            tree_anc=tree_anc, tree_slots=tree_slots)
    if k_pool.ndim == 3:
        k_pool = _unflat(k_pool, q.shape[2])
        v_pool = _unflat(v_pool, q.shape[2])
    return _xla_ragged_paged(q, k_pool, v_pool, block_tables,
                             context_lens, q_lens, row_starts,
                             row_slot, wn, w, sm_scale=sm_scale,
                             tree_anc=tree_anc, tree_slots=tree_slots)


def ragged_attention_step(qh, kh, vh, k_pool, v_pool, block_tables,
                          cache_lens, q_lens, row_starts, row_slot,
                          row_pos, narrow_iota, win_iota,
                          sm_scale=None, tree_anc=_AMBIENT,
                          tree_slots=_AMBIENT):
    """Write + attend for the ragged mixed-batch serving step: scatter
    this tick's per-row K/V ([R, H_kv, D]) into the pool at
    ``(row_slot, row_pos)`` (pad rows null-route) and attend each
    packed query row against its slot's length-bounded block list —
    decode, speculative verify and chunked prefill in ONE launch.
    ``cache_lens[s]`` is the slot's valid length BEFORE this tick's
    first row. ``tree_anc``/``tree_slots`` default to the ambient
    ``spec_tree_scope`` (how the tree reaches here THROUGH an
    untouched model forward); pass explicit values (``None`` = force
    linear) to override — the TP wrapper below does, because the
    traced flag vector must enter its manual region as an operand.
    Also the per-shard body of that wrapper. Returns
    ``(out [R, H, D], k_pool, v_pool)``."""
    if tree_anc is _AMBIENT or tree_slots is _AMBIENT:
        amb_anc, amb_slots = _tree_ctx()
        if tree_anc is _AMBIENT:
            tree_anc = amb_anc
        if tree_slots is _AMBIENT:
            tree_slots = amb_slots if tree_anc is not None else None
    from ..paged_cache import write_rows
    lens = cache_lens.astype(jnp.int32)
    if k_pool.ndim == 3:
        # a flat pool: a position's kv heads side by side in one row
        kh = kh.reshape(kh.shape[0], -1)
        vh = vh.reshape(vh.shape[0], -1)
    kp2, vp2 = write_rows(k_pool, v_pool, block_tables, row_slot,
                          row_pos, kh, vh)
    out = ragged_paged_attention(qh, kp2, vp2, block_tables, lens + 1,
                                 q_lens, row_starts, row_slot,
                                 narrow_iota, win_iota,
                                 sm_scale=sm_scale, tree_anc=tree_anc,
                                 tree_slots=tree_slots)
    return out, kp2, vp2


def ragged_latent_attention(q, pool, block_tables, context_lens,
                            q_lens, row_starts, row_slot, narrow_iota,
                            win_iota, value_dim, sm_scale):
    """Ragged mixed-batch attention over a latent (MLA) pool — the
    one-array counterpart of ``ragged_paged_attention``, same slot
    partition and iotas. Routes to ``pallas_ragged_latent_attention``
    on TPU (or under ``PADDLE_TPU_PAGED_KERNEL=interpret``), to the
    two-lane XLA mirror elsewhere. On a TPU backend a pool the kernel
    cannot copy from (``W`` not whole lane tiles, ``BS`` not whole
    sublane tiles) is counted as a fallback like the others."""
    wn = int(narrow_iota.shape[0])
    w = int(win_iota.shape[0])
    on_tpu = jax.default_backend() == "tpu"
    sublanes = 32 // jnp.dtype(pool.dtype).itemsize
    ok = (pool.shape[2] % 128 == 0 and pool.shape[1] % sublanes == 0
          and value_dim % 128 == 0
          and LATENT_TILE[0] >= _row_pad(q.shape[1], q.dtype))
    if (on_tpu or _force_kernel_routing()) and ok:
        return pallas_ragged_latent_attention(
            q, pool, block_tables, context_lens, q_lens, row_starts, w,
            value_dim, sm_scale)
    if on_tpu:
        _warn_fallback("ragged_latent_attention", q.shape, pool.shape)

    def verify(q4, tables, lens, _narrow):
        return _xla_latent_verify(q4, pool, tables, lens, value_dim,
                                  sm_scale)

    return _xla_ragged_lanes(q, verify, block_tables, context_lens,
                             q_lens, row_starts, row_slot, wn, w)


def ragged_latent_attention_step(q, c_new, layer, block_tables,
                                 cache_lens, q_lens, row_starts,
                                 row_slot, row_pos, narrow_iota,
                                 win_iota, value_dim, sm_scale):
    """Write + attend of the ragged tick over a latent cache: scatter
    this tick's rows ``c_new`` ``[R, W]`` into the layer's one pool at
    ``(row_slot, row_pos)`` (pad rows null-route) and attend the
    absorbed queries ``q`` ``[R, H, W]`` against each slot's
    length-bounded block list. ``layer`` is the layer's cache, a
    1-tuple. Returns ``(out [R, H, value_dim], (pool,))``. Tree
    speculation masks are not built for the latent kernel."""
    if _tree_ctx()[0] is not None:
        raise NotImplementedError(
            "tree-speculative verify over a latent (MLA) pool")
    from ..paged_cache import scatter_rows
    layer = scatter_rows(layer, block_tables, row_slot, row_pos,
                         (c_new,))
    out = ragged_latent_attention(
        q, layer[0], block_tables, cache_lens.astype(jnp.int32) + 1,
        q_lens, row_starts, row_slot, narrow_iota, win_iota, value_dim,
        sm_scale)
    return out, layer


def _pool_pspec(pool):
    """shard_map PartitionSpec tree for one pool half: the kv_head cut
    on the data (``[NB, BS, H_kv, D]``); a quantized pool's scale half
    (``[NB, BS, H_kv]``) rides the SAME cut — the spec mirrors the
    ``QuantKV`` pytree structure so shard_map matches it leaf-wise."""
    import jax.sharding as _js
    from ..paged_cache import QuantKV
    P = _js.PartitionSpec
    if isinstance(pool, QuantKV):
        return QuantKV(P(None, None, "mp", None), P(None, None, "mp"))
    return P(None, None, "mp", None)


def sharded_ragged_attention_step(qh, kh, vh, k_pool, v_pool,
                                  block_tables, cache_lens, q_lens,
                                  row_starts, row_slot, row_pos,
                                  narrow_iota, win_iota,
                                  sm_scale=None):
    """Tensor-parallel ``ragged_attention_step``: the same write+attend
    body inside ``shard_map`` over the mesh's ``mp`` axis — q/k/v
    ``[R, H, D]`` and the pools ``[NB, BS, H_kv, D]`` split on their
    head dim: each shard owns a contiguous kv_head GROUP slice, so GQA
    routing, the Pallas grid and the XLA mirror all run unmodified on
    local shapes (``rep = H/H_kv`` is shard-invariant; int8 pools'
    scale halves ride the same cut). Block tables, lengths and ALL row
    metadata are REPLICATED: block ids are global, one host allocator
    serves every shard, and each shard's pool slice is indexed by the
    same tables — which is why prefix caching, COW, speculative
    rollback and chunked prefill compose with TP for free. No
    collective inside; the step's only cross-shard traffic stays the
    engine's logits gather."""
    import jax.sharding as _js
    from ...distributed.shard_utils import current_mesh
    P = _js.PartitionSpec
    mesh = current_mesh()
    heads = P(None, "mp", None)           # [R, H, D] head split
    kspec, vspec = _pool_pspec(k_pool), _pool_pspec(v_pool)
    rows = P(None)
    # the ambient spec-tree scope resolves OUT HERE: tree_slots is a
    # traced array and must enter the manual region as a replicated
    # operand, never a closure; the static parent tuple closes over
    tree_anc, tree_slots = _tree_ctx()
    if tree_anc is not None and tree_slots is None:
        tree_slots = jnp.ones((block_tables.shape[0],), jnp.int32)

    if tree_anc is not None:
        def local(q, k, v, kp, vp, tables, lens, ql, rs, sl, pos,
                  nwin, win, ts):
            return ragged_attention_step(q, k, v, kp, vp, tables,
                                         lens, ql, rs, sl, pos, nwin,
                                         win, sm_scale=sm_scale,
                                         tree_anc=tree_anc,
                                         tree_slots=ts)

        f = jax.shard_map(
            local, mesh=mesh,
            in_specs=(heads, heads, heads, kspec, vspec,
                      P(None, None), rows, rows, rows, rows, rows,
                      rows, rows, rows),
            out_specs=(heads, kspec, vspec), check_vma=False)
        return f(qh, kh, vh, k_pool, v_pool, block_tables, cache_lens,
                 q_lens, row_starts, row_slot, row_pos, narrow_iota,
                 win_iota, tree_slots)

    def local(q, k, v, kp, vp, tables, lens, ql, rs, sl, pos, nwin,
              win):
        return ragged_attention_step(q, k, v, kp, vp, tables, lens,
                                     ql, rs, sl, pos, nwin, win,
                                     sm_scale=sm_scale, tree_anc=None,
                                     tree_slots=None)

    f = jax.shard_map(
        local, mesh=mesh,
        in_specs=(heads, heads, heads, kspec, vspec, P(None, None),
                  rows, rows, rows, rows, rows, rows, rows),
        out_specs=(heads, kspec, vspec), check_vma=False)
    return f(qh, kh, vh, k_pool, v_pool, block_tables, cache_lens,
             q_lens, row_starts, row_slot, row_pos, narrow_iota,
             win_iota)


_SERVING_TP = threading.local()   # thread-scoped like in_manual_region


@contextlib.contextmanager
def serving_tp_scope():
    """Arm the TP routing gate below for the duration of one trace.
    ``ServingEngine._trace_ctx`` enters this while tracing a
    tensor-parallel executable; everywhere else ``tp_shard_degree``
    reports 1, so an ambient training/fleet mesh with a live ``mp``
    axis can never reroute a single-device engine (tp_degree=1, the
    ``PADDLE_TPU_SERVE_TP=0`` kill switch) or ``generate``'s paged
    loop through ``shard_map``. The flag is thread-local so a TP
    compile on one thread never arms a concurrent trace on another."""
    prev = getattr(_SERVING_TP, "on", False)
    _SERVING_TP.on = True
    try:
        yield
    finally:
        _SERVING_TP.on = prev


def serving_tp_active() -> bool:
    """True while tracing inside a TP engine's ``serving_tp_scope``
    with a live ``mp`` mesh (and not already inside a manual region) —
    the condition under which GSPMD owns the partitioning of any op in
    the trace. Non-attention callers (the MoE grouped matmuls) use
    this to keep opaque Pallas kernels OFF such traces: an opaque
    pallas_call cannot be partitioned, so they must take their XLA
    lowering there (the same reasoning as the r5 ragged_dot gate)."""
    if not getattr(_SERVING_TP, "on", False):
        return False
    from ...distributed.shard_utils import current_mesh, in_manual_region
    mesh = current_mesh()
    return (mesh is not None and int(mesh.shape.get("mp", 1)) > 1
            and not in_manual_region())


def tp_shard_degree(num_heads, num_kv_heads) -> int:
    """``mp`` degree the TP paged-attention path can use right now:
    > 1 only inside a ``serving_tp_scope`` (a TP engine's trace) whose
    mesh has a live ``mp`` axis, when tracing is not already inside a
    manual (shard_map) region, and BOTH head counts divide — otherwise
    the caller must stay on the single-program path (GSPMD partitions
    it if it can)."""
    if not getattr(_SERVING_TP, "on", False):
        return 1
    from ...distributed.shard_utils import current_mesh, in_manual_region
    mesh = current_mesh()
    if mesh is None or in_manual_region():
        return 1
    tp = int(mesh.shape.get("mp", 1))
    if tp <= 1 or num_heads % tp or num_kv_heads % tp:
        return 1
    return tp


def paged_verify_attention(q, k_pool, v_pool, block_tables,
                           context_lens, sm_scale=None,
                           tree_anc=_AMBIENT):
    """Multi-query ragged paged attention for the speculative verify
    window; q: [S, T, H, D] (T = gamma + 1 tokens per slot, causal
    within the window). ``context_lens[s]`` = positions visible to the
    slot's FIRST window token, itself included. ``tree_anc`` defaults
    to the ambient ``spec_tree_scope`` (every slot's window becomes a
    token tree — ``SpecGenerator``'s tree verify arms this through
    the untouched model forward); the tree never applies to chunked
    prefill because the scope is only entered around verify traces.
    Routes to the Pallas kernel on TPU, the gather fallback
    elsewhere."""
    if tree_anc is _AMBIENT:
        tree_anc = _tree_ctx()[0]
    # a T-row window can only carry a (T-1)-draft tree; the ambient
    # scope may legitimately cover other widths' traces (prefill
    # chunks ride the ragged exec, not this one) — mismatches mean
    # "not a verify window", so the linear bound stands
    if tree_anc is not None and len(tree_anc) + 1 != q.shape[1]:
        tree_anc = None
    if _use_kernel("paged_verify_attention", q.shape, q.shape[2],
                   q.shape[3], q.dtype, k_pool, window=q.shape[1]):
        return pallas_paged_verify_attention(
            q, k_pool, v_pool, block_tables, context_lens,
            sm_scale=sm_scale, tree_anc=tree_anc)
    return _xla_paged_verify(q, k_pool, v_pool, block_tables,
                             context_lens, sm_scale=sm_scale,
                             tree_anc=tree_anc)

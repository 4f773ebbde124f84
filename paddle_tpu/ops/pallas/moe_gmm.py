"""Fused-dispatch grouped matmul for MoE (TPU Pallas).

The sorted grouped-matmul MoE path (``distributed/moe.py``) pays for
dispatch twice: the stable argsort's row permutation is materialized as
a packed ``[s*k, d]`` buffer in HBM before the first expert matmul
(``_expand_sort``), and the combine gathers the expert outputs back to
token order as a second full-size HBM round-trip (``_perm_rows``).
Profiling (bench ``moe_profile``) attributes most of the MoE-vs-dense
MFU gap to exactly these fusion boundaries ("Operator Fusion in XLA";
the mega-kernelization direction in MPK — PAPERS.md).

This module folds both boundaries into the grouped matmuls themselves:

- **gather-on-read** (``gather_gmm`` / ``gather_gmm_swiglu``): the
  scalar-prefetched row-permutation ``src_rows`` drives the lhs load —
  each ``[tm, tk]`` lhs tile is assembled in VMEM by per-row async
  copies straight out of the UNSORTED activations in HBM, so the
  expert-sorted packed buffer never exists as an HBM array. The swiglu
  variant additionally keeps the ``[m, 2f]`` gate/up projection in
  VMEM: two accumulators (gate and up column tiles of the same rhs)
  feed ``silu(g) * u`` in the epilogue, and only the ``[m, f]`` hidden
  ever reaches HBM.
- **scatter-on-write** (``scatter_gmm``): the second expert matmul's
  epilogue routes each output row through ``dst_rows`` (the inverse
  permutation) with per-row async copies, so the combine's unsort is
  the matmul's own store — the gate-weighted reduction over the
  ``top_k`` slots then runs on a token-major ``[s, k, d]`` view that
  XLA fuses with the residual add.

Group handling follows the megablox formulation: group boundaries that
split a row tile re-visit the tile once per group (CSR-style metadata
from ``make_group_metadata``; grid size is the data-dependent
``num_tiles`` — Pallas supports a dynamic leading grid bound), stores
are masked to the visiting group's rows, and the scatter epilogue
writes only rows the current group owns, so every output row is
written exactly once.

All kernels take ``interpret=`` so the CPU test suite can run them
bit-for-bit under the Pallas interpreter — today the ONLY way they run:
they lower for TPU (``tests/test_tpu_lowering.py``) but Mosaic refuses
to compile the per-row async copies below ("Slice shape along dimension
0 must be aligned to tiling (8), but is 1": a one-row slice of a tiled
array is not a legal DMA operand, in HBM or VMEM — ``chip_smoke.py`` on
a v5e, PR 21), so the production gates
(``distributed.moe._use_fused_gmm``, ``ops.lora._use_lora_gmm``) route
no shape here on a TPU backend. ROADMAP S1 holds the verdict to reach:
a row gather Mosaic accepts, or deletion.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["make_group_metadata", "gather_gmm", "gather_gmm_swiglu",
           "scatter_gmm", "pick_tiling"]


def make_group_metadata(group_sizes, m: int, tm: int):
    """CSR-style grid metadata for a grouped matmul over ``m`` sorted
    rows tiled at ``tm``: which group each grid step works on and which
    row tile it visits. A group whose start is not tile-aligned
    re-visits its first tile (the tile's owner already visited it), so
    the static grid bound is ``m//tm + e - 1``; the returned
    ``num_tiles`` is the data-dependent number of steps actually
    executed (a dynamic grid dimension skips the padding).

    Returns ``(group_offsets [e+1], group_ids [T], m_tile_ids [T]),
    num_tiles`` — all int32; ``group_offsets[i]`` is the first row of
    group ``i``.
    """
    e = group_sizes.shape[0]
    if m % tm:
        raise ValueError(f"m ({m}) must be divisible by tile ({tm})")
    tiles_m = m // tm
    ends = jnp.cumsum(group_sizes).astype(jnp.int32)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    starts = offsets[:-1]
    # tiles each group touches, after rounding its span out to tiles
    r_ends = ((ends + tm - 1) // tm).astype(jnp.int32)
    r_starts = starts // tm
    g_tiles = jnp.where(group_sizes == 0, 0, r_ends - r_starts)
    group_ids = jnp.repeat(
        jnp.arange(e, dtype=jnp.int32), g_tiles,
        total_repeat_length=tiles_m + e - 1)
    # visits per row tile: its owner plus one per group that starts
    # mid-tile (non-aligned, non-empty, not the tile-owning group)
    mid_start = jnp.logical_and(starts % tm != 0, group_sizes != 0)
    start_tile = jnp.where(mid_start, starts // tm, tiles_m)
    extra = jnp.zeros(tiles_m, jnp.int32).at[start_tile].add(
        1, mode="drop")
    m_tile_ids = jnp.repeat(
        jnp.arange(tiles_m, dtype=jnp.int32), extra + 1,
        total_repeat_length=tiles_m + e - 1)
    num_tiles = g_tiles.sum()
    return (offsets, group_ids, m_tile_ids), num_tiles


def pick_tiling(m: int, k: int, n: int, prefer=(512, 512, 512)):
    """Largest power-of-two tile sizes (<= ``prefer``) that divide each
    problem dim — the fused kernels require exact tiling; the caller's
    eligibility gate guarantees dims large enough for the MXU."""
    def best(dim, cap):
        t = 8
        while t * 2 <= min(dim, cap) and dim % (t * 2) == 0:
            t *= 2
        return t if dim % t == 0 else 1
    return best(m, prefer[0]), best(k, prefer[1]), best(n, prefer[2])


def _validate(m, k, n, tm, tk, tn, e):
    if m % tm or k % tk or n % tn:
        raise ValueError(
            f"fused gmm needs exact tiling: (m, k, n)=({m}, {k}, {n}) "
            f"vs tiles ({tm}, {tk}, {tn})")


def _gather_tile(x_hbm, src_ref, lhs_vmem, row0, col0, tm, tk, sem):
    """Assemble the ``[tm, tk]`` lhs tile in VMEM by per-row copies
    from the unsorted HBM activations: row ``i`` of the tile is
    ``x[src_rows[row0 + i], col0:col0+tk]`` — the dispatch gather,
    executed as the matmul's own load."""
    def body(i, _):
        r = src_ref[row0 + i]
        cp = pltpu.make_async_copy(
            x_hbm.at[pl.ds(r, 1), pl.ds(col0, tk)],
            lhs_vmem.at[pl.ds(i, 1)], sem)
        cp.start()
        cp.wait()
        return 0
    lax.fori_loop(0, tm, body, 0, unroll=False)


def _call_grouped(x, rhs, group_sizes, *, src_rows, dst_rows, swiglu,
                  transpose_rhs, tiling, interpret, out_dtype):
    """Shared pallas_call builder behind the three public entry
    points. ``x``: activations — ``[m, k]`` sorted rows when
    ``src_rows is None``, else the unsorted gather source (any row
    count; ``src_rows [m]`` selects). ``rhs``: ``[e, k, n]`` stacked
    expert weights (``[e, n, k]`` under ``transpose_rhs``; with
    ``swiglu`` the n dim is ``2f`` and the output is ``[m, f]``).
    ``dst_rows [m]``: scatter permutation for the output rows (must be
    a permutation — every output row is written exactly once).
    Metadata AND the kernel trace run in 32-bit mode under
    ``kernel_scope`` (the ``_gmm32`` lesson)."""
    from .flash_attention_kernel import kernel_scope
    with kernel_scope("moe_gmm"):
        return _call_grouped_32(
            x, rhs, group_sizes, src_rows=src_rows, dst_rows=dst_rows,
            swiglu=swiglu, transpose_rhs=transpose_rhs, tiling=tiling,
            interpret=interpret, out_dtype=out_dtype)


def _call_grouped_32(x, rhs, group_sizes, *, src_rows, dst_rows,
                     swiglu, transpose_rhs, tiling, interpret,
                     out_dtype):
    m = x.shape[0] if src_rows is None else src_rows.shape[0]
    k = rhs.shape[2] if transpose_rhs else rhs.shape[1]
    n_full = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    n = n_full // 2 if swiglu else n_full
    e = rhs.shape[0]
    out_dtype = out_dtype or x.dtype
    tm, tk, tn = tiling
    _validate(m, k, n, tm, tk, tn, e)
    tiles_n, tiles_k = n // tn, k // tk
    gather = src_rows is not None
    scatter = dst_rows is not None
    if swiglu and (transpose_rhs or scatter):
        raise ValueError("swiglu epilogue is forward-only (plain rhs, "
                         "blocked store)")

    meta, num_tiles = make_group_metadata(group_sizes, m, tm)
    offsets, group_ids, m_tile_ids = meta
    i32 = functools.partial(jnp.asarray, dtype=jnp.int32)
    scalars = [offsets, group_ids, m_tile_ids,
               i32(src_rows) if gather else jnp.zeros(1, jnp.int32),
               i32(dst_rows) if scatter else jnp.zeros(1, jnp.int32)]

    def rhs_index(n_i, g_i, k_i, *pref, up=False):
        gid = pref[1][g_i]
        col = n_i + (tiles_n if up else 0)
        if transpose_rhs:
            return gid, col, k_i
        return gid, k_i, col

    rhs_block = (None, tn, tk) if transpose_rhs else (None, tk, tn)
    in_specs = []
    args = []
    if gather:
        in_specs.append(pl.BlockSpec(memory_space=pl.ANY))
        args.append(x)
    else:
        in_specs.append(pl.BlockSpec(
            (tm, tk),
            lambda n_i, g_i, k_i, *pref: (pref[2][g_i], k_i)))
        args.append(x)
    in_specs.append(pl.BlockSpec(rhs_block, rhs_index))
    args.append(rhs)
    if swiglu:
        in_specs.append(pl.BlockSpec(
            rhs_block, functools.partial(rhs_index, up=True)))
        args.append(rhs)

    if scatter:
        out_specs = pl.BlockSpec(memory_space=pl.ANY)
    else:
        out_specs = pl.BlockSpec(
            (tm, tn), lambda n_i, g_i, k_i, *pref: (pref[2][g_i], n_i))

    scratch = [pltpu.VMEM((tm, tn), jnp.float32)]
    if swiglu:
        scratch.append(pltpu.VMEM((tm, tn), jnp.float32))
    if gather:
        scratch.append(pltpu.VMEM((tm, tk), x.dtype))
        scratch.append(pltpu.SemaphoreType.DMA)
    if scatter:
        scratch.append(pltpu.VMEM((tm, tn), out_dtype))
        scratch.append(pltpu.SemaphoreType.DMA)

    def kernel(offs_ref, gids_ref, tids_ref, src_ref, dst_ref,
               *refs):
        refs = list(refs)
        lhs_ref = refs.pop(0)
        rhs_ref = refs.pop(0)
        rhs_up_ref = refs.pop(0) if swiglu else None
        out_ref = refs.pop(0)
        acc = refs.pop(0)
        acc_up = refs.pop(0) if swiglu else None
        lhs_vmem = refs.pop(0) if gather else None
        gsem = refs.pop(0) if gather else None
        store_vmem = refs.pop(0) if scatter else None
        ssem = refs.pop(0) if scatter else None

        n_i = pl.program_id(0)
        g_i = pl.program_id(1)
        k_i = pl.program_id(2)
        gid = gids_ref[g_i]
        tid = tids_ref[g_i]

        @pl.when(k_i == 0)
        def _zero():
            acc[...] = jnp.zeros_like(acc)
            if swiglu:
                acc_up[...] = jnp.zeros_like(acc_up)

        if gather:
            _gather_tile(lhs_ref, src_ref, lhs_vmem, tid * tm,
                         k_i * tk, tm, tk, gsem)
            lhs = lhs_vmem[...]
        else:
            lhs = lhs_ref[...]

        dims = (((1,), (1,)), ((), ())) if transpose_rhs \
            else (((1,), (0,)), ((), ()))
        acc[...] += lax.dot_general(
            lhs, rhs_ref[...], dimension_numbers=dims,
            preferred_element_type=jnp.float32)
        if swiglu:
            acc_up[...] += lax.dot_general(
                lhs, rhs_up_ref[...], dimension_numbers=dims,
                preferred_element_type=jnp.float32)

        @pl.when(k_i == tiles_k - 1)
        def _store():
            g_start = offs_ref[gid]
            g_end = offs_ref[gid + 1]
            if swiglu:
                # silu(gate) * up in fp32, cast once at the store — the
                # [m, 2f] projection never leaves VMEM
                val = (jax.nn.silu(acc[...]) * acc_up[...]) \
                    .astype(out_dtype)
            else:
                val = acc[...].astype(out_dtype)
            if scatter:
                # the combine's unsort IS the store: row i of the tile
                # lands at dst_rows[row] of the token-major output.
                # Only rows the visiting group owns are written, so a
                # tile re-visited across a group boundary never
                # double-writes.
                store_vmem[...] = val

                def srow(i, _):
                    row = tid * tm + i

                    @pl.when(jnp.logical_and(row >= g_start,
                                             row < g_end))
                    def _():
                        d = dst_ref[row]
                        cp = pltpu.make_async_copy(
                            store_vmem.at[pl.ds(i, 1)],
                            out_ref.at[pl.ds(d, 1),
                                       pl.ds(n_i * tn, tn)],
                            ssem)
                        cp.start()
                        cp.wait()
                    return 0
                lax.fori_loop(0, tm, srow, 0, unroll=False)
            else:
                rows = lax.broadcasted_iota(
                    jnp.int32, (tm, tn), 0) + tid * tm
                mask = jnp.logical_and(rows >= g_start, rows < g_end)
                out_ref[...] = lax.select(
                    mask, val, out_ref[...].astype(out_dtype))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(scalars),
        grid=(tiles_n, num_tiles, tiles_k),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=scratch,
    )
    flops = 2 * m * k * n_full
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=flops, transcendentals=m * n if swiglu else 0,
            bytes_accessed=(m * k + k * n_full * e + m * n)
            * x.dtype.itemsize),
        interpret=interpret,
    )
    return call(*scalars, *args)


def gather_gmm(x, src_rows, rhs, group_sizes, *, tiling=None,
               transpose_rhs=False, interpret=False, out_dtype=None):
    """Grouped matmul with the dispatch gather fused into the lhs
    load: ``out[r] = x[src_rows[r]] @ rhs[group(r)]`` for the sorted
    row partition ``group_sizes`` (must sum to ``out`` rows). With
    ``src_rows=None`` the lhs is taken as already sorted (plain
    blocked load). ``transpose_rhs`` contracts the LAST dim of rhs
    (``[e, n, k]``) — the backward's d(lhs) shape."""
    m = x.shape[0] if src_rows is None else src_rows.shape[0]
    k = rhs.shape[2] if transpose_rhs else rhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiling = tiling or pick_tiling(m, k, n)
    return _call_grouped(
        x, rhs, group_sizes, src_rows=src_rows, dst_rows=None,
        swiglu=False, transpose_rhs=transpose_rhs, tiling=tiling,
        interpret=interpret, out_dtype=out_dtype)


def gather_gmm_swiglu(x, src_rows, gate_up, group_sizes, *, tiling=None,
                      interpret=False, out_dtype=None):
    """First expert matmul with BOTH dispatch fusions: gather-on-read
    lhs (``src_rows``) and the swiglu nonlinearity in the epilogue —
    ``out[r] = silu(xs @ W_gate) * (xs @ W_up)`` with ``gate_up``
    ``[e, k, 2f]`` split column-wise. Neither the sorted ``[m, k]``
    input nor the ``[m, 2f]`` projection ever reaches HBM."""
    m = x.shape[0] if src_rows is None else src_rows.shape[0]
    k = gate_up.shape[1]
    f = gate_up.shape[2] // 2
    tiling = tiling or pick_tiling(m, k, f)
    return _call_grouped(
        x, gate_up, group_sizes, src_rows=src_rows, dst_rows=None,
        swiglu=True, transpose_rhs=False, tiling=tiling,
        interpret=interpret, out_dtype=out_dtype)


def scatter_gmm(x, rhs, group_sizes, dst_rows, *, tiling=None,
                transpose_rhs=False, interpret=False, out_dtype=None):
    """Second expert matmul with the combine's unsort fused into the
    epilogue: row ``r`` of the grouped product is stored at
    ``out[dst_rows[r]]`` (``dst_rows`` a permutation of ``[0, m)`` —
    for MoE, the sorted→token-major ``order``, so the output is the
    token-major pair buffer the gate-weighted reduction consumes
    without any further gather)."""
    m = x.shape[0]
    k = rhs.shape[2] if transpose_rhs else rhs.shape[1]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tiling = tiling or pick_tiling(m, k, n)
    return _call_grouped(
        x, rhs, group_sizes, src_rows=None, dst_rows=dst_rows,
        swiglu=False, transpose_rhs=transpose_rhs, tiling=tiling,
        interpret=interpret, out_dtype=out_dtype)

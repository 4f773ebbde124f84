"""Batched multi-LoRA serving: ragged per-slot adapter grouped matmuls.

Multi-tenant serving wants N adapters live on ONE engine: every decode
tick is a mixed-adapter ragged batch where row ``r`` of the packed
``[1, R, d]`` hidden carries the tenant adapter of the slot that owns
it. The delta math is the classic low-rank update ``y += (x @ A_g) @
B_g * alpha/r`` with ``g`` varying PER ROW — exactly the shape of the
MoE dispatch problem PR 8 solved, so the TPU path reuses the
``moe_gmm`` gather-on-read / scatter-on-write grouped-matmul kernels
(rows argsorted by adapter, gathered straight out of the unsorted
activations by scalar-prefetch; cf. Ragged Paged Attention, PAPERS.md)
while the CPU/XLA fallback is a per-row gather + einsum that computes
the SAME per-row contraction, so batched-vs-solo token-exactness never
depends on which backend ran.

Pieces:

- :class:`AdapterPool` — stacked A/B delta weights ``[n_res+1, d, r]``
  / ``[n_res+1, r, out]`` per target module, slot 0 all-zero (the null
  adapter: base-model rows gather an exact-zero delta, mirroring the
  paged cache's null block 0). The host-DRAM registry is authoritative
  (write-through, never dropped); the device-resident image is an LRU
  window over it in the ``HostKVTier`` mold, refcounted so an adapter
  serving an in-flight request can never be evicted from under it.
  ``quant="int8"`` stores the resident stacks as int8 + per-matrix
  absmax scales (the PR 10 KV-pool recipe), dequantized in-trace.
- :func:`tag_modules` — stamps ``_lora_slot`` on the model's target
  projections (construction-order walk of ``named_sublayers()``, so
  two engines over the same architecture agree on stack order).
- :func:`serving_lora_scope` — thread-local trace scope (the
  ``spec_tree_scope`` idiom): the serving engine enters it while
  tracing the ONE ragged tick executable, handing the traced stack
  operands + per-row adapter vector to the projection hook in
  ``mp_layers``; model forwards stay untouched everywhere else.
- :func:`apply` — the hook body: no-op outside a scope, on untagged
  modules, or on shapes that are not the ragged row pack (draft /
  dense prefill traces), else adds the per-row delta.

The adapter stacks ride the tick as RUNTIME OPERANDS (never closure
constants): swapping an adapter in or out rewrites stack VALUES at a
fixed ``[n_res+1, ...]`` shape, so adapter churn is a host->device
copy, not a recompile — the zero-recompile claim the bench pins.

Kill switches: ``PADDLE_TPU_LORA=0`` disables the whole feature (the
engine then builds the bit-identical base tick — no extra operand, no
hook arming); ``PADDLE_TPU_LORA_GMM=0`` forces the einsum fallback,
``=interpret`` routes eligible shapes through the Pallas kernels under
the interpreter so CPU tests cover the real kernel graph (the
``PADDLE_TPU_MOE_FUSED_GMM=interpret`` precedent).
"""
from __future__ import annotations

import contextlib
import os
import threading
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["lora_enabled", "ATTN_TARGETS", "MLP_TARGETS", "tag_modules",
           "AdapterPool", "serving_lora_scope", "armed", "apply"]

# leaf module names the serving integration targets: attention
# projections always; MLP projections under targets="all". Llama/Qwen2
# use {q,k,v,o}_proj + {gate,up,down}_proj; GPT fuses qkv and names its
# MLP linear1/linear2 — every one is a Column/RowParallelLinear, so the
# single mp_layers hook covers all architectures.
ATTN_TARGETS = ("q_proj", "k_proj", "v_proj", "o_proj",
                "qkv_proj", "out_proj")
MLP_TARGETS = ("gate_proj", "up_proj", "down_proj", "linear1", "linear2")


def lora_enabled() -> bool:
    """Kill switch: ``PADDLE_TPU_LORA=0`` restores the base engine
    bit-for-bit (the gate is resolved ONCE at engine construction, like
    ``PADDLE_TPU_PREEMPT``)."""
    return os.environ.get("PADDLE_TPU_LORA", "1") != "0"


def tag_modules(model, targets: str = "attn"):
    """Stamp ``_lora_slot`` (the module's index into the adapter
    stacks) on every target projection of ``model`` and return the
    ordered spec list ``[(qualified_name, leaf, d_in, d_out), ...]``.
    The walk is ``named_sublayers()`` construction order, so two
    engines over the same architecture build identically-ordered
    stacks — what keeps disaggregated prefill/decode handoffs and solo
    comparison runs gather-compatible."""
    names = set(ATTN_TARGETS)
    if targets == "all":
        names |= set(MLP_TARGETS)
    elif targets != "attn":
        raise ValueError(
            f"lora_targets must be 'attn' or 'all', got {targets!r}")
    all_names = set(ATTN_TARGETS) | set(MLP_TARGETS)
    specs = []
    for qual, layer in model.named_sublayers():
        leaf = qual.rsplit(".", 1)[-1]
        w = getattr(layer, "weight", None)
        if leaf not in all_names or w is None or len(w.shape) != 2:
            continue
        if leaf in names:
            layer._lora_slot = len(specs)
            specs.append((qual, leaf, int(w.shape[0]), int(w.shape[1])))
        else:
            # clear a stale stamp from a previous engine over the SAME
            # model with a wider target set — a leftover _lora_slot
            # would arm this module with an out-of-range stack index
            layer._lora_slot = None
    return specs


class AdapterPool:
    """Host-authoritative multi-adapter store with an LRU device-
    resident window.

    The HOST registry (``register``) holds every adapter's float32 A/B
    pairs and is never dropped — it is the authoritative tier, so an
    eviction is a pure bookkeeping step (unlike ``HostKVTier``, whose
    entries are reconstructible and may be dropped). The RESIDENT image
    is one stacked pair of arrays per target module with
    ``max_resident + 1`` rows: row 0 is the all-zero null adapter
    (base-model rows), rows 1.. are an LRU-managed window assigned by
    ``acquire``. Refcounts pin a resident adapter while any slot serves
    it — ``acquire`` never victimizes a pinned row and ``evict``
    refuses one, so a request's gather index stays valid for its whole
    life (the mid-request-eviction lifecycle edge).
    """

    def __init__(self, specs, rank: int, alpha=None, max_resident: int = 8,
                 quant: bool = False):
        if rank <= 0:
            raise ValueError(f"lora rank must be positive, got {rank}")
        if max_resident < 1:
            raise ValueError(
                f"max_adapters (resident budget) must be >= 1, got "
                f"{max_resident}")
        self.specs = list(specs)
        self.rank = int(rank)
        self.alpha = float(rank if alpha is None else alpha)
        self.scaling = self.alpha / self.rank
        self.max_resident = int(max_resident)
        self.quant = bool(quant)
        self.version = 0          # bumped on every stack write -> the
        self.swaps = 0            # engine re-uploads the operand image
        self._host = {}           # aid -> [per-module (A, B) | None]
        self._resident = OrderedDict()   # aid -> row (LRU order)
        self._refs = {}                  # aid -> pin count
        n = self.max_resident + 1
        self._stacks = []
        for (_, _, d, out) in self.specs:
            if self.quant:
                self._stacks.append((
                    np.zeros((n, d, self.rank), np.int8),
                    np.ones((n, 1, 1), np.float32),
                    np.zeros((n, self.rank, out), np.int8),
                    np.ones((n, 1, 1), np.float32)))
            else:
                self._stacks.append((
                    np.zeros((n, d, self.rank), np.float32),
                    np.zeros((n, self.rank, out), np.float32)))

    # -- host registry ---------------------------------------------------
    def register(self, adapter_id, weights) -> int:
        """Install (or overwrite) adapter ``adapter_id`` in the host
        registry. ``weights`` maps target-module names — qualified
        (``model.layers.0.self_attn.q_proj``) or leaf (``q_proj``,
        broadcast to every matching layer) — to ``(A [d, rank],
        B [rank, out])`` pairs; modules the adapter does not target get
        an exact-zero delta. If the adapter is currently resident, its
        stack rows are rewritten in place (live hot-reload, no
        recompile)."""
        aid = int(adapter_id)
        mats, used = [], set()
        for (qual, leaf, d, out) in self.specs:
            key = qual if qual in weights else (
                leaf if leaf in weights else None)
            if key is None:
                mats.append(None)
                continue
            used.add(key)
            A = np.asarray(weights[key][0], np.float32)
            B = np.asarray(weights[key][1], np.float32)
            if A.shape != (d, self.rank) or B.shape != (self.rank, out):
                raise ValueError(
                    f"adapter {aid}: {qual} expects A {(d, self.rank)} "
                    f"/ B {(self.rank, out)}, got {A.shape} / {B.shape}")
            mats.append((A, B))
        unknown = set(weights) - used
        if unknown:
            raise ValueError(
                f"adapter {aid}: no target module matches "
                f"{sorted(unknown)}")
        self._host[aid] = mats
        if aid in self._resident:
            self._write_row(self._resident[aid], mats)
        return aid

    def known(self, adapter_id) -> bool:
        return int(adapter_id) in self._host

    def refcount(self, adapter_id) -> int:
        return self._refs.get(int(adapter_id), 0)

    def resident(self, adapter_id) -> bool:
        return int(adapter_id) in self._resident

    # -- residency -------------------------------------------------------
    def acquire(self, adapter_id):
        """Pin ``adapter_id`` resident and return its stack row (the
        value the per-slot adapter vector carries — the trace gathers
        by ROW, so the row must stay fixed while pinned; refcounts
        guarantee it). Loads from host into a free or LRU-victimized
        unpinned row on miss; returns ``None`` when every row is
        pinned (admission defers — the request stays queued)."""
        aid = int(adapter_id)
        if aid not in self._host:
            raise KeyError(f"unknown adapter_id {aid}")
        if aid in self._resident:
            self._resident.move_to_end(aid)
            self._refs[aid] = self._refs.get(aid, 0) + 1
            return self._resident[aid]
        row = self._free_row()
        if row is None:
            return None
        self._write_row(row, self._host[aid])
        self._resident[aid] = row
        self._refs[aid] = 1
        return row

    def release(self, adapter_id):
        """Unpin one reference; the adapter STAYS resident (warm for
        the next request of the same tenant) but becomes an eviction
        candidate at refcount 0."""
        aid = int(adapter_id)
        n = self._refs.get(aid, 0)
        if n > 0:
            self._refs[aid] = n - 1

    def evict(self, adapter_id):
        """Explicitly drop ``adapter_id`` from the resident window.
        Refuses while any in-flight request pins it — eviction
        mid-request would re-point the slot's gather row at another
        tenant's weights."""
        aid = int(adapter_id)
        if aid not in self._resident:
            return
        n = self._refs.get(aid, 0)
        if n > 0:
            raise ValueError(
                f"adapter {aid} is pinned by {n} in-flight request(s); "
                "eviction mid-request is blocked")
        del self._resident[aid]
        self._refs.pop(aid, None)
        self.swaps += 1

    def _free_row(self):
        used = set(self._resident.values())
        for row in range(1, self.max_resident + 1):
            if row not in used:
                return row
        victim = next((a for a in self._resident     # LRU order
                       if self._refs.get(a, 0) == 0), None)
        if victim is None:
            return None
        row = self._resident.pop(victim)
        self._refs.pop(victim, None)
        self.swaps += 1
        return row

    def _write_row(self, row, mats):
        for stacks, mat in zip(self._stacks, mats):
            if self.quant:
                ad, asc, bd, bsc = stacks
                if mat is None:
                    ad[row] = 0
                    asc[row] = 1.0
                    bd[row] = 0
                    bsc[row] = 1.0
                else:
                    A, B = mat
                    sa = float(np.max(np.abs(A))) / 127.0 or 1.0
                    sb = float(np.max(np.abs(B))) / 127.0 or 1.0
                    ad[row] = np.clip(np.round(A / sa),
                                      -127, 127).astype(np.int8)
                    asc[row] = sa
                    bd[row] = np.clip(np.round(B / sb),
                                      -127, 127).astype(np.int8)
                    bsc[row] = sb
            else:
                a_stack, b_stack = stacks
                if mat is None:
                    a_stack[row] = 0.0
                    b_stack[row] = 0.0
                else:
                    a_stack[row] = mat[0]
                    b_stack[row] = mat[1]
        self.version += 1

    # -- operand + accounting --------------------------------------------
    def operand(self):
        """The device operand pytree for the tick executable: one
        tuple per target module — ``(A, B)`` float32 stacks, or
        ``(A_q, A_scale, B_q, B_scale)`` under int8 quant. Fixed
        shapes; the caller re-``device_put``s when ``version`` moves
        (value swap, never a recompile)."""
        return tuple(tuple(s for s in stacks) for stacks in self._stacks)

    @property
    def n_resident(self) -> int:
        return len(self._resident)

    @property
    def host_tier_bytes(self) -> int:
        """Bytes of registered adapters currently NOT resident — the
        host-DRAM spill tier the `lora_host_tier_bytes` stat reports."""
        total = 0
        for aid, mats in self._host.items():
            if aid in self._resident:
                continue
            for mat in mats:
                if mat is not None:
                    total += mat[0].nbytes + mat[1].nbytes
        return total


# ---------------------------------------------------------------------------
# trace scope + projection hook
# ---------------------------------------------------------------------------

_SCOPE = threading.local()    # thread-scoped like spec_tree_scope


@contextlib.contextmanager
def serving_lora_scope(operands, row_adapter, scaling, gmm_ok=True):
    """Arm the per-row LoRA delta for the duration of one trace.
    ``operands`` is :meth:`AdapterPool.operand` passed as TRACED tick
    operands (never closed-over constants — swapped values must not
    bake in); ``row_adapter`` a traced ``[R]`` int32 vector naming each
    packed row's resident stack row (0 = null adapter); ``scaling`` the
    static ``alpha / rank``; ``gmm_ok=False`` pins the einsum fallback
    (the engine clears it under tensor parallelism — the Pallas path
    is single-device, exactly like the MoE gate). Thread-local so a
    LoRA trace on one engine never arms a concurrent draft/prefill
    trace on another thread."""
    prev = getattr(_SCOPE, "ctx", None)
    _SCOPE.ctx = (operands, row_adapter, float(scaling), bool(gmm_ok))
    try:
        yield
    finally:
        _SCOPE.ctx = prev


def armed(module) -> bool:
    """Static trace-time predicate: is ``module`` a tagged target
    inside an active serving scope? The fused decode paths branch on
    this to compose the delta with their fallback ordering."""
    return (getattr(_SCOPE, "ctx", None) is not None
            and getattr(module, "_lora_slot", None) is not None)


def _use_lora_gmm(n_rows: int, d_in: int, rank: int, d_out: int):
    """Route one projection's delta to the fused grouped-matmul
    kernels? Mirrors ``distributed.moe._use_fused_gmm``: only under
    ``PADDLE_TPU_LORA_GMM=interpret`` (the kernels under the Pallas
    interpreter, for CPU coverage of the kernel graph), at aligned
    shapes: activations/outputs on 128 lanes, rows and rank on the
    8-sublane f32 tile. NEVER compiled: Mosaic refuses the moe_gmm
    kernels' single-row DMA slices of a tiled array (see
    ``_use_fused_gmm``), so on a TPU backend every delta takes the
    einsum, which XLA fuses well at rank 8..64."""
    if os.environ.get("PADDLE_TPU_LORA_GMM", "1") != "interpret":
        return False
    aligned = (d_in % 128 == 0 and d_out % 128 == 0
               and n_rows % 8 == 0 and rank % 8 == 0)
    return "interpret" if aligned else False


def _ragged_delta(rows, row_adapter, A, B, mode):
    """Per-row low-rank delta ``out[i] = (rows[i] @ A[g_i]) @ B[g_i]``
    with ``g_i = row_adapter[i]`` — float32, unscaled. ``mode`` truthy
    routes through the moe_gmm kernels: rows argsorted by adapter form
    the sorted group partition, ``gather_gmm`` pulls each row straight
    out of the UNSORTED activations (gather-on-read), ``scatter_gmm``
    stores row ``r`` back at its token-order position (scatter-on-
    write) — dispatch and combine never exist as HBM arrays. The
    einsum fallback computes the same per-row contraction via a
    stacked gather."""
    if mode:
        from .pallas.moe_gmm import gather_gmm, scatter_gmm
        interpret = (mode == "interpret")
        n_groups = int(A.shape[0])
        m, d = int(rows.shape[0]), int(rows.shape[1])
        r, out = int(B.shape[1]), int(B.shape[2])
        order = jnp.argsort(row_adapter)
        gs = jnp.bincount(row_adapter, length=n_groups)
        tm = 8 if m % 8 == 0 else 1
        # full-K single tile: one dot per row tile, matching the
        # einsum's per-row reduction grouping
        ax = gather_gmm(rows, order, A, gs, tiling=(tm, d, r),
                        interpret=interpret, out_dtype=jnp.float32)
        return scatter_gmm(ax, B, gs, order, tiling=(tm, r, out),
                           interpret=interpret, out_dtype=jnp.float32)
    ax = jnp.einsum("rd,rdk->rk", rows, A[row_adapter])
    return jnp.einsum("rk,rko->ro", ax, B[row_adapter])


def apply(module, x, y):
    """The projection hook: ``y + per_row_delta(x)`` when ``module``
    is a tagged target inside an active :func:`serving_lora_scope`,
    else ``y`` untouched. Shape-guarded to the ragged row pack — a
    draft-model or dense-prefill trace whose leading dims don't
    multiply out to the scope's row count no-ops, so only the ONE
    ragged tick executable carries deltas. Called at the END of the
    Column/RowParallelLinear forwards (after sharding constraints and
    bias), so the fused decode paths can reproduce the exact same
    ordering."""
    ctx = getattr(_SCOPE, "ctx", None)
    idx = getattr(module, "_lora_slot", None)
    if ctx is None or idx is None:
        return y
    operands, row_adapter, scaling, gmm_ok = ctx
    mod = operands[idx]
    quant = len(mod) == 4
    d = int(mod[0].shape[1])
    rank = int(mod[0].shape[2])
    out = int(mod[2].shape[2]) if quant else int(mod[1].shape[2])
    n_rows = int(row_adapter.shape[0])
    lead = 1
    for s in x.shape[:-1]:
        lead *= int(s)
    if lead != n_rows or int(x.shape[-1]) != d \
            or int(y.shape[-1]) != out:
        return y
    mode = _use_lora_gmm(n_rows, d, rank, out) if gmm_ok else False
    # the raw jnp dtype (Tensor.dtype is the paddle enum)
    out_dtype = getattr(y, "_data", y).dtype

    def fn(xv, rav, *ws):
        if quant:
            ad, asc, bd, bsc = ws
            A = ad.astype(jnp.float32) * asc
            B = bd.astype(jnp.float32) * bsc
        else:
            A, B = ws
        rows = xv.reshape(n_rows, d).astype(jnp.float32)
        delta = _ragged_delta(rows, rav, A, B, mode)
        delta = delta * jnp.float32(scaling)
        return delta.reshape(xv.shape[:-1] + (out,)).astype(out_dtype)

    from ..framework.core import apply_jax
    delta = apply_jax("lora_apply", fn, x, row_adapter, *mod)
    return y + delta

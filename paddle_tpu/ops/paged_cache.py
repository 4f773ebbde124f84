"""Paged KV cache: block pool + per-slot block tables.

The serving-side cache layout (reference: *Ragged Paged Attention*,
arxiv 2604.15464, and vLLM's PagedAttention block tables): instead of
one dense ``[B, S, H, D]`` cache per sequence, all sequences share one
pool of fixed-size blocks ``[num_blocks, block_size, H_kv, D]`` and each
serving slot owns an int32 row of block ids (its *block table*). A
sequence of length ``n`` holds ``ceil(n / block_size)`` blocks; token
position ``p`` lives at ``(table[p // block_size], p % block_size)``.

Why this layout on TPU (arxiv 2603.09555: design the cache for the
compiler's static-shape world): every array here is FIXED shape — the
pool, the tables, the per-slot lengths — so one compiled decode step
serves every mix of sequence lengths with zero recompiles; raggedness
lives in the *values* of the tables/lengths, never in shapes. Block 0
is reserved as the null block: retired/inactive slots point at it, so
their (masked, discarded) reads and writes stay in-bounds without any
dynamic shape or host-side branch.

Device ops (pure jax, jit-safe) live here next to a host-side
``BlockAllocator`` that the serving scheduler uses to admit/retire
slots. The allocator is **content-addressed** (vLLM-style automatic
prefix caching on the block granularity): every block carries a
refcount, a retired sequence's FULL blocks are published under a
rolling content hash (``chain_hashes`` — a hash chain over token ids
seeded by a model/config fingerprint, so block ``i``'s hash commits to
the entire prefix through it), and freed-but-published blocks park in
an LRU side-list where they stay reusable until memory pressure
evicts them. A later request whose prompt prefix hashes to cached
blocks maps them straight into its block table (refcount++) and only
prefills the suffix; a shared block that must be appended into is
copy-on-write duplicated (``copy_blocks`` — one device block copy).
The ragged decode attention that READS this layout is
``ops/pallas/paged_attention.py``.

**Quantized pools** (``kv_cache_dtype="int8"`` /
``PADDLE_TPU_KV_INT8=1``): steady-state decode is HBM-bandwidth-bound
on KV reads, and the fp pool is the hard ceiling on concurrent slots.
Each pool half becomes a :class:`QuantKV` — an int8 data pool
``[NB, BS, H_kv, D]`` plus a per-(block, position, head) f32 absmax
scale pool ``[NB, BS, H_kv]`` — halving the bytes every
paged-attention step streams and roughly doubling block capacity at a
fixed byte budget. Every write path quantizes on store through ONE
shared scatter helper (``_store``), so the stored bytes are a pure
function of the written rows: prefix-cached blocks hold bitwise the
int8 the cold path would recompute, COW copies data+scales together,
and the Pallas kernels / XLA fallbacks dequantize with identical math
(block load -> f32 * scale -> activation dtype). Scale granularity is
per TOKEN per head — not per block — because the write paths are
position scatters: a block-wide absmax would need a read-modify-write
requantization of the whole block on every appended token.
"""
from __future__ import annotations

import functools
import hashlib
import os
from collections import OrderedDict

import jax
import jax.numpy as jnp
import numpy as np

from ..framework.core import component

__all__ = ["NULL_BLOCK", "BlockAllocator", "blocks_for", "init_pool",
           "write_prefill", "write_decode", "write_tokens",
           "write_rows", "scatter_rows", "init_latent_pool",
           "gather_dense", "chain_hashes",
           "iter_chain_hashes", "copy_blocks", "pool_sharding",
           "pool_head_slice", "ragged_row_meta", "QuantKV",
           "kv_quantize", "kv_dequantize", "resolve_kv_cache_dtype",
           "pool_bytes", "scale_sharding", "model_fingerprint",
           "prompt_block_hashes", "export_blocks", "import_blocks",
           "HostKVTier", "payload_to_host", "payload_nbytes",
           "payload_rows", "payload_pad", "export_stacked",
           "stacked_layout", "stacked_payload", "SlotState",
           "is_slot_state", "init_slot_state", "first_paged",
           "state_bytes", "export_slot_state", "import_slot_state",
           "snapshot_nbytes",
           "init_flat_pool"]

# block id 0 is never allocated: inactive slots' tables point here, so
# their scatter/gather indices stay valid while their data is garbage
NULL_BLOCK = 0


def _cache_component(fn):
    """Run ``fn`` under the component scope ``cache``
    (``monitor.accounting.COMPONENTS``): what moves pool blocks and
    slot state inside an engine's executables is named so in their
    component maps."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with component("cache"):
            return fn(*args, **kwargs)
    return scoped


def blocks_for(n_tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``n_tokens`` cache positions."""
    return -(-int(n_tokens) // int(block_size))


class QuantKV:
    """One half (K or V) of an int8-quantized block pool: ``data`` int8
    ``[NB, BS, H_kv, D]`` + ``scale`` f32 ``[NB, BS, H_kv]`` (symmetric
    per-(block, position, head) absmax / 127). Registered as a jax
    pytree, so it rides everywhere a plain pool array rides — jit
    arguments, donation, shard_map specs, the models' cache tuples —
    and every op in this module (and the paged-attention kernels)
    branches on it explicitly. ``shape``/``dtype``/``nbytes`` mirror
    the data pool so host-side shape logic and byte accounting keep
    working unchanged."""

    _is_kv_quant_pool = True          # duck-typed marker (framework)
    __slots__ = ("data", "scale")

    def __init__(self, data, scale):
        self.data = data
        self.scale = scale

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def ndim(self):
        return self.data.ndim

    @property
    def nbytes(self):
        return int(self.data.nbytes) + int(self.scale.nbytes)

    def __repr__(self):             # pragma: no cover - debugging aid
        return (f"QuantKV(data={self.data.shape} int8, "
                f"scale={self.scale.shape})")


jax.tree_util.register_pytree_node(
    QuantKV,
    lambda p: ((p.data, p.scale), None),
    lambda _, children: QuantKV(*children))


def kv_quantize(x):
    """Symmetric per-(row, head) absmax int8 quantization of K/V rows:
    ``x [..., D]`` -> ``(int8 [..., D], f32 scale [...])`` with
    ``scale = absmax / 127`` over the head_dim. All-zero rows store
    scale 0 (dequant gives exact zeros — the null block and untouched
    pool positions stay zero)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = amax * np.float32(1.0 / 127.0)
    safe = jnp.where(scale > 0, scale, np.float32(1.0))
    q = jnp.clip(jnp.round(xf / safe[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), scale


def kv_dequantize(data, scale, dtype=jnp.float32):
    """Inverse of ``kv_quantize``: ``int8 [..., D] * f32 scale [...]``
    -> ``dtype [..., D]``. The kernels and the gather fallback use the
    SAME recipe (int8 -> f32 multiply -> cast), so both read identical
    values from identical stored bytes."""
    return (data.astype(jnp.float32) * scale[..., None]).astype(dtype)


class SlotState:
    """A layer's cache that is NOT paged: ``data [num_slots + 1, ...]``
    holds one row of recurrent state a serving SLOT (a gated short
    convolution's last ``L - 1`` inputs, ``[S + 1, L - 1, hidden]``; a
    delta-rule mixer's matrix a head, ``[S + 1, heads, d_v, d_k]``),
    whatever the sequence's length; the last row is the null seat that
    rows no slot owns read, and nothing writes. The layer's cache entry
    is a tuple of NOTHING BUT such tables — one (``(SlotState,)``) or,
    where a mixer keeps state of two kinds, several of different shape
    and dtype — and :func:`is_slot_state` is the ONE predicate that
    tells it from a block-paged entry: every walker that moves BLOCKS
    (``copy_blocks``, ``export_blocks`` / ``import_blocks``,
    ``export_stacked``, ``pool_bytes``) passes such a layer through
    untouched, because it holds no blocks. Registered as a jax pytree
    like :class:`QuantKV`, so it rides jit arguments and donation
    unchanged."""

    _is_slot_state = True             # duck-typed marker (framework)
    __slots__ = ("data",)

    def __init__(self, data):
        self.data = data

    @property
    def shape(self):
        return self.data.shape

    @property
    def dtype(self):
        return self.data.dtype

    @property
    def nbytes(self):
        return int(self.data.nbytes)

    def __repr__(self):             # pragma: no cover - debugging aid
        return f"SlotState({self.data.shape} {self.data.dtype})"


jax.tree_util.register_pytree_node(
    SlotState,
    lambda p: ((p.data,), None),
    lambda _, children: SlotState(*children))


def is_slot_state(layer) -> bool:
    """Whether a layer's cache entry is slot state (a tuple of
    ``SlotState`` tables) and not block-paged arrays."""
    return len(layer) > 0 and all(isinstance(t, SlotState) for t in layer)


def init_slot_state(num_slots: int, shape, dtype) -> tuple:
    """Zeroed ``(SlotState,)`` of ``[num_slots + 1, *shape]``; a layer
    with tables of two kinds adds two such tuples."""
    return (SlotState(jnp.zeros(
        (int(num_slots) + 1, *(int(n) for n in shape)), dtype)),)


def first_paged(pools):
    """The first block-paged layer's cache entry (layer 0 of a model
    with slot state may hold no blocks)."""
    for layer in pools:
        if not is_slot_state(layer):
            return layer
    raise ValueError("no block-paged layer among the caches")


def state_bytes(pools) -> int:
    """Total bytes of the slot-state tables among ``pools``."""
    return sum(t.nbytes for layer in pools if is_slot_state(layer)
               for t in layer)


@_cache_component
def export_slot_state(pools, slot):
    """One slot's row of every slot-state table: the snapshot the
    engine keeps beside a published block, a PYTREE — a list over the
    slot-state layers of a tuple over the layer's tables — or, where
    every such layer holds ONE table and the tables share a shape and a
    dtype, those rows stacked in layer order ``[n, ...]`` (one leaf).
    ``slot`` is a traced int32 scalar, so one executable serves every
    seat."""
    rows = [tuple(t.data[slot] for t in layer) for layer in pools
            if is_slot_state(layer)]
    if all(len(r) == 1 for r in rows) and len(
            {(r[0].shape, r[0].dtype) for r in rows}) == 1:
        return jnp.stack([r[0] for r in rows])
    return rows


@_cache_component
def import_slot_state(pools, slot, snap):
    """Write an :func:`export_slot_state` snapshot back at ``slot``
    (donate ``pools``); block-paged layers pass through."""
    out, k = [], 0
    for layer in pools:
        if is_slot_state(layer):
            rows = snap[k] if isinstance(snap[k], tuple) else (snap[k],)
            layer = tuple(
                SlotState(t.data.at[slot].set(row.astype(t.dtype)))
                for t, row in zip(layer, rows))
            k += 1
        out.append(layer)
    return out


def snapshot_nbytes(pools) -> int:
    """Bytes of one :func:`export_slot_state` snapshot: one seat's row
    of every slot-state table."""
    return sum(t.nbytes // t.shape[0] for layer in pools
               if is_slot_state(layer) for t in layer)


def resolve_kv_cache_dtype(requested=None):
    """Resolve the KV-pool quantization request to ``"int8"`` or
    ``None`` (pool in the model dtype — the pre-quantization layout,
    bit-for-bit). ``requested`` is the config value
    (``ServingConfig.kv_cache_dtype`` / ``generate(kv_cache_dtype=)``);
    the env twin ``PADDLE_TPU_KV_INT8`` composes the repo's usual way:
    ``0`` is the kill switch (beats an explicit ``"int8"`` — rollback
    is one env var, test-pinned bit parity), ``1`` turns int8 on when
    the config leaves the choice open (``None``/``"auto"``)."""
    env = os.environ.get("PADDLE_TPU_KV_INT8")
    if env == "0":
        return None
    if requested is None or requested == "auto":
        return "int8" if env == "1" else None
    r = str(requested).lower()
    if r == "int8":
        return "int8"
    raise ValueError(
        f"kv_cache_dtype {requested!r}; supported: None/'auto' (pool "
        "in the model dtype) or 'int8' (quantized pool; env twin "
        "PADDLE_TPU_KV_INT8=1/0)")


def pool_bytes(pools) -> int:
    """Total bytes of a per-layer pool list — ``[(k, v), ...]`` pairs
    or a latent cache's ``[(c,), ...]`` — int8 pools count data AND
    scales (telemetry/bench accounting). A slot-state layer holds no
    block and counts nothing here (``state_bytes``)."""
    return sum(int(p.nbytes) for layer in pools
               if not is_slot_state(layer) for p in layer)


def _each(fn, *layers):
    """``fn`` over every array of a layer's cache, whatever its arity:
    a layer's cache is a tuple of arrays (or ``QuantKV``) whose leading
    dims are ``[NB, BS]`` — the ``(k_pool, v_pool)`` pair of per-head
    attention, the one ``(latent_pool,)`` of latent (MLA) attention.
    With a payload beside the pools the two must agree in arity."""
    if len({len(layer) for layer in layers}) != 1:
        raise ValueError(
            "layer cache arity mismatch: "
            f"{[len(layer) for layer in layers]} arrays a layer")
    return tuple(fn(*ps) for ps in zip(*layers))


class BlockAllocator:
    """Host-side refcounted, content-addressed allocator over block ids
    ``1..num_blocks-1`` (block 0 is the reserved null block). The
    serving scheduler allocates at admission/growth and frees at
    retirement; the device never sees this object — only the int32
    tables it fills in.

    Block lifecycle: ``alloc`` hands out blocks at refcount 1; ``free``
    decrements, and a block hitting refcount 0 either returns to the
    plain free-list (unpublished) or parks in the **LRU cache list**
    (published via ``publish`` — it keeps its content hash and stays
    discoverable through ``lookup`` until ``alloc`` evicts it under
    memory pressure, oldest first). ``lookup`` + ``ref`` map a cached
    or live block into another sequence's table (prefix reuse);
    ``is_shared`` tells the caller a block must be copy-on-write
    duplicated before any in-place append (refcount > 1, or published
    — the cache itself holds an interest in published content)."""

    def __init__(self, num_blocks: int):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (1 null + 1 usable), got {num_blocks}")
        self.num_blocks = int(num_blocks)
        # LIFO reuse keeps hot blocks hot in HBM-side caches
        self._free = list(range(self.num_blocks - 1, NULL_BLOCK, -1))
        self._refs = [0] * self.num_blocks
        self._hash_of = {}          # published block id -> content hash
        self._by_hash = {}          # content hash -> block id (bijective)
        self._lru = OrderedDict()   # refcount-0 published blocks, LRU->MRU
        self.evictions = 0          # cached blocks reclaimed by alloc()
        # eviction hook (host-DRAM KV tier): called as
        # ``on_evict(block_id, content_hash)`` the moment ``alloc``
        # reclaims an LRU-cached block — BEFORE the id is handed back
        # out, so the owner of the pool bytes can still spill them to
        # host DRAM (launches issue in host order, so a spill gather
        # submitted here reads the block before any new write lands)
        self.on_evict = None

    @property
    def free_blocks(self) -> int:
        """Allocatable blocks: truly free + evictable cached (admission
        reservations treat the LRU cache as free — eviction is
        transparent)."""
        return len(self._free) + len(self._lru)

    @property
    def cached_blocks(self) -> int:
        """Refcount-0 published blocks parked in the LRU list."""
        return len(self._lru)

    def alloc(self, n: int = 1):
        """Pop ``n`` block ids, evicting LRU cached blocks when the
        plain free-list runs short; raises when even the cache cannot
        cover it (the scheduler's admission reservation should make
        this unreachable in steady state)."""
        if n > self.free_blocks:
            raise RuntimeError(
                f"paged KV pool exhausted: want {n} blocks, "
                f"{self.free_blocks} free of {self.num_blocks - 1}")
        while len(self._free) < n:
            b, _ = self._lru.popitem(last=False)     # oldest first
            h = self._hash_of.pop(b)
            self._by_hash.pop(h, None)
            self.evictions += 1
            if self.on_evict is not None:
                self.on_evict(b, h)
            self._free.append(b)
        out = self._free[-n:][::-1]
        del self._free[-n:]
        for b in out:
            self._refs[b] = 1
        return out

    def free(self, block_ids):
        """Drop one reference per block; refcount 0 parks published
        blocks in the LRU cache and returns the rest to the free-list."""
        for b in block_ids:
            b = int(b)
            if not (NULL_BLOCK < b < self.num_blocks):
                raise ValueError(f"freeing invalid block id {b}")
            if self._refs[b] <= 0:
                raise ValueError(f"double free of block {b}")
            self._refs[b] -= 1
            if self._refs[b] == 0:
                if b in self._hash_of:
                    self._lru[b] = None
                    self._lru.move_to_end(b)         # MRU end
                else:
                    self._free.append(b)

    def ref(self, block_id: int) -> int:
        """Take one more reference on a live or cached block (prefix
        reuse: map it into another slot's table). A cached block leaves
        the LRU list — it is live again."""
        b = int(block_id)
        if not (NULL_BLOCK < b < self.num_blocks):
            raise ValueError(f"ref of invalid block id {b}")
        if self._refs[b] == 0:
            if b not in self._lru:
                raise ValueError(f"ref of free block {b}")
            del self._lru[b]
        self._refs[b] += 1
        return b

    def refcount(self, block_id: int) -> int:
        return self._refs[int(block_id)]

    def is_shared(self, block_id: int) -> bool:
        """True when an in-place append into the block would be visible
        beyond the caller: more than one reference, or published (the
        hash index may hand it to a future request) — the caller must
        copy-on-write first."""
        b = int(block_id)
        return self._refs[b] > 1 or b in self._hash_of

    def lookup(self, content_hash):
        """Block id published under ``content_hash``, or None. The
        block may be cached (refcount 0) or live inside other slots;
        either way ``ref`` it before mapping."""
        return self._by_hash.get(content_hash)

    def publish(self, block_id: int, content_hash) -> bool:
        """Register a live block's content hash so future prompts can
        reuse it (call before ``free`` at retirement). First writer
        wins: when the hash already maps to another block (identical
        concurrent sequences), or the block is already published, the
        call is a no-op returning whether THIS block backs the hash."""
        b = int(block_id)
        if self._refs[b] <= 0:
            raise ValueError(f"publishing dead block {b}")
        if content_hash in self._by_hash:
            return self._by_hash[content_hash] == b
        if b in self._hash_of:
            return False
        self._by_hash[content_hash] = b
        self._hash_of[b] = content_hash
        return True

    def unpublish_all(self) -> int:
        """Wipe the content index (replica drain / failure: the
        cluster router must stop scoring prefix affinity onto this
        pool — a published hash on a replica that no longer serves is
        a route to nowhere). LRU-cached blocks (refcount 0, reachable
        only through the index) return to the free list; live blocks
        keep their references and merely lose their published hashes.
        Returns the number of index entries dropped."""
        n = len(self._hash_of)
        for b in self._lru:
            self._free.append(b)
        self._lru.clear()
        self._hash_of.clear()
        self._by_hash.clear()
        return n

    def check_leaks(self, live_blocks=()):
        """Debug invariant sweep (engine shutdown in tests): every
        block is exactly one of {free, LRU-cached, referenced}, the
        referenced set equals ``live_blocks``, and the hash index is
        bijective. Raises RuntimeError on any violation."""
        live = {int(b) for b in live_blocks}
        free = set(self._free)
        cached = set(self._lru)
        if free & cached:
            raise RuntimeError(
                f"blocks both free and cached: {sorted(free & cached)}")
        refd = {b for b in range(1, self.num_blocks) if self._refs[b] > 0}
        if refd & (free | cached):
            raise RuntimeError(
                "referenced blocks on a free/cache list: "
                f"{sorted(refd & (free | cached))}")
        lost = set(range(1, self.num_blocks)) - free - cached - refd
        if lost:
            raise RuntimeError(f"leaked blocks (unreachable): "
                               f"{sorted(lost)}")
        if refd != live:
            raise RuntimeError(
                f"live-block mismatch: allocator holds {sorted(refd)}, "
                f"caller expects {sorted(live)}")
        for b, h in self._hash_of.items():
            if self._by_hash.get(h) != b:
                raise RuntimeError(f"hash index not bijective at "
                                   f"block {b}")
        for b in cached:
            if b not in self._hash_of:
                raise RuntimeError(f"cached block {b} has no hash")
        return True


def iter_chain_hashes(seed: bytes, tokens, block_size: int):
    """Lazy ``chain_hashes``: yields the per-full-block hashes one at a
    time, so a consumer that stops at the first cache miss (the
    admission prefix walk) never pays for hashing the whole prompt."""
    toks = np.ascontiguousarray(np.asarray(tokens, np.int32))
    bs = int(block_size)
    h = bytes(seed)
    for i in range(len(toks) // bs):
        m = hashlib.blake2b(h, digest_size=16)
        m.update(toks[i * bs:(i + 1) * bs].tobytes())
        h = m.digest()
        yield h


def chain_hashes(seed: bytes, tokens, block_size: int):
    """Rolling per-FULL-block content hashes: ``h_i = H(h_{i-1} ||
    tokens[i*bs:(i+1)*bs])`` with ``h_{-1} = seed`` (the model/config
    fingerprint). Because each hash chains over everything before it,
    equal hashes mean equal *prefixes through that block* — the
    soundness condition for block-granular prefix sharing. Partial
    trailing blocks are never hashed (they are never shared)."""
    return list(iter_chain_hashes(seed, tokens, block_size))


def model_fingerprint(model) -> bytes:
    """Seed for the content-hash chains: two caches may share blocks
    only when the model architecture + config (and thus the K/V a
    token sequence produces) agree. Per-engine pools make cross-model
    collisions impossible today; the fingerprint keeps the hash space
    partitioned if the index is ever externalized — and it is what
    lets a CLUSTER router hash a prompt once and probe every replica's
    index with the same keys (every replica of one model computes the
    identical fingerprint)."""
    import dataclasses
    desc = [type(model).__name__]
    cfg = getattr(model, "config", None)
    if cfg is not None:
        try:
            fields = dataclasses.asdict(cfg)
        except TypeError:
            fields = dict(vars(cfg))
        desc.append(repr(sorted(fields.items())))
    return hashlib.blake2b("\x1f".join(desc).encode(),
                           digest_size=16).digest()


def prompt_block_hashes(fingerprint: bytes, prompt, block_size: int):
    """THE prompt -> full-block hash walk that serving admission AND
    the cluster router share (lazy — a consumer stopping at its first
    index miss never hashes the whole prompt). Factored here so the
    two can NEVER drift: if the router hashed even one byte
    differently from ``ServingEngine._map_prefix``, every affinity
    probe would silently miss and session-affine routing would
    degrade to load balancing without any error. Yields the chain
    hash of each FULL block of ``prompt`` in order."""
    return iter_chain_hashes(fingerprint, prompt, block_size)


def init_pool(num_blocks: int, block_size: int, num_kv_heads: int,
              head_dim: int, dtype, sharding=None) -> tuple:
    """Zeroed (k_pool, v_pool), each [num_blocks, block_size, H_kv, D].

    ``dtype="int8"`` (or ``jnp.int8``) builds QUANTIZED halves: each is
    a :class:`QuantKV` of an int8 data pool plus the f32 scale pool
    ``[NB, BS, H_kv]`` — ~0.53x the bytes of the bf16 pool at D=64
    (0.5x data + 4/D scale overhead), the serving capacity/bandwidth
    win. Zero-filled scales dequantize to exact zeros.

    ``sharding`` (tensor-parallel serving): a ``jax.sharding.Sharding``
    — normally ``pool_sharding(mesh)``, the kv_heads split — the pool
    is created under, so each shard materializes only its contiguous
    kv_head slice and no resharding transfer ever happens. A quantized
    pool's scale half shards on the SAME kv_head cut
    (``scale_sharding``)."""
    shape = (num_blocks, block_size, num_kv_heads, head_dim)
    quant = dtype == "int8" or jnp.dtype(dtype) == jnp.int8
    if quant:
        sshape = shape[:3]
        if sharding is not None:
            mk = _sharded_zeros(shape, jnp.dtype(jnp.int8), sharding)
            mks = _sharded_zeros(sshape, jnp.dtype(jnp.float32),
                                 scale_sharding(sharding))
            return (QuantKV(mk(), mks()), QuantKV(mk(), mks()))
        return (QuantKV(jnp.zeros(shape, jnp.int8),
                        jnp.zeros(sshape, jnp.float32)),
                QuantKV(jnp.zeros(shape, jnp.int8),
                        jnp.zeros(sshape, jnp.float32)))
    if sharding is not None:
        # compile the zeros INTO the sharding: each device writes only
        # its own slice, so a pool sized near per-chip HBM x tp never
        # materializes unsharded on device 0 first
        mk = _sharded_zeros(shape, jnp.dtype(dtype), sharding)
        return mk(), mk()
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


def init_flat_pool(num_blocks: int, block_size: int, num_kv_heads: int,
                   head_dim: int, dtype) -> tuple:
    """Zeroed ``(k_pool, v_pool)``, each FLAT: ``[num_blocks,
    block_size, H_kv * D]``, a position's kv heads side by side in one
    row. That is the view the ragged kernel copies its K/V tiles out of
    (``ops/pallas/paged_attention._pool_view``), held as the array
    itself: on the chip the view of a 4-D pool is a relayout — a copy
    of the whole pool in every layer of every tick, which a pool of a
    few hundred blocks hides and one of tens of thousands does not —
    and a head of 64 lanes is half a lane tile, which the kernel cannot
    copy at all but reads here two heads a tile. The ragged step
    (``ragged_attention_step``) writes and reads such a pool; the
    walkers take it as they take a latent pool. Float pools only."""
    lanes = int(num_kv_heads) * int(head_dim)
    if lanes % 128:
        raise ValueError(
            f"flat pool: {num_kv_heads} kv heads x {head_dim} is not a "
            "whole number of 128-lane tiles")
    shape = (num_blocks, block_size, lanes)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


@functools.lru_cache(maxsize=32)
def _sharded_zeros(shape, dtype, sharding):
    """One compiled sharded-zeros program per (shape, dtype, sharding)
    — every layer of a model (and its draft) reuses it instead of
    paying a fresh XLA compile per ``init_pool`` call."""
    import jax
    return jax.jit(lambda: jnp.zeros(shape, dtype),
                   out_shardings=sharding)


def init_latent_pool(num_blocks: int, block_size: int, width: int,
                     dtype) -> tuple:
    """Zeroed ``(latent_pool,)`` of latent (MLA) attention: ONE array
    ``[num_blocks, block_size, W]`` a layer holding each position's
    compressed key/value ``c_kv`` and its shared rotary key ``k_pe``
    side by side, ``W`` = ``width`` rounded up to whole 128-lane tiles
    (the pad lanes stay zero). That is the layout HBM gives a minor
    dim anyway and the one the latent attention kernel copies from
    (``ops/pallas/paged_attention.py``), so no op of a serving tick
    relays the pool out. Every head reads the same latent, so there is
    no head axis to shard: under tensor parallelism the pool is
    replicated."""
    lanes = -(-int(width) // 128) * 128
    return (jnp.zeros((num_blocks, block_size, lanes), dtype),)


def pool_sharding(mesh):
    """The tensor-parallel pool placement: ``[NB, BS, H_kv, D]`` split
    on the kv_heads dim over the mesh's ``mp`` axis. Every shard holds
    ALL blocks (block ids stay global — one host allocator, one set of
    block tables serves every shard) but only a contiguous kv_head
    slice of each, which is exactly the slice the per-shard paged
    attention grid iterates."""
    from jax.sharding import NamedSharding, PartitionSpec
    return NamedSharding(mesh, PartitionSpec(None, None, "mp", None))


def scale_sharding(data_sharding):
    """Scale-pool twin of ``pool_sharding``: the ``[NB, BS, H_kv]``
    scale pool splits on the SAME kv_head cut as its int8 data pool
    (drop the trailing head_dim entry of the data spec)."""
    from jax.sharding import NamedSharding, PartitionSpec
    spec = tuple(data_sharding.spec) + (None,) * 3
    return NamedSharding(data_sharding.mesh, PartitionSpec(*spec[:3]))


def pool_head_slice(pool, shard: int, tp: int):
    """The contiguous kv_head slice shard ``shard`` of ``tp`` owns —
    the per-shard view the TP attention computes on (tests/debugging;
    the device never materializes this outside its own shard)."""
    hkv = pool.shape[2]
    if hkv % tp:
        raise ValueError(f"kv_heads={hkv} not divisible by tp={tp}")
    per = hkv // tp
    if isinstance(pool, QuantKV):
        return QuantKV(
            pool.data[:, :, shard * per:(shard + 1) * per, :],
            pool.scale[:, :, shard * per:(shard + 1) * per])
    return pool[:, :, shard * per:(shard + 1) * per, :]


def _store(pool, bi, off, rows):
    """THE scatter-on-store every write path funnels through
    (``write_prefill`` / ``write_decode`` / ``write_tokens`` /
    ``write_rows``, K and V sides): fp pools store the rows cast to the
    pool dtype; int8 pools quantize on store, landing data and
    per-(position, head) scales at the SAME ``[bi, off]`` indices — so
    null-routing/masking decided upstream covers both halves, and the
    int8 path is written exactly once."""
    if isinstance(pool, QuantKV):
        q, s = kv_quantize(rows)
        return QuantKV(pool.data.at[bi, off].set(q),
                       pool.scale.at[bi, off].set(s))
    return pool.at[bi, off].set(rows.astype(pool.dtype))


def write_prefill(k_pool, v_pool, block_tables, k_new, v_new,
                  n_real=None):
    """Scatter a dense prefill's K/V into the pool.

    k_new/v_new: [B, P, H_kv, D] (the dense cached-prefill output for B
    slots); block_tables: [B, MB] int32. Rows with position >= n_real
    ([B] or scalar; default all P) are routed to the null block so a
    right-padded prompt's garbage tail never lands in live blocks."""
    b, p = k_new.shape[0], k_new.shape[1]
    bs = k_pool.shape[1]
    pos = jnp.arange(p, dtype=jnp.int32)                     # [P]
    bi = jnp.take_along_axis(
        block_tables.astype(jnp.int32),
        jnp.broadcast_to(pos // bs, (b, p)), axis=1)         # [B, P]
    if n_real is not None:
        valid = pos[None, :] < jnp.reshape(
            jnp.asarray(n_real, jnp.int32), (-1, 1))
        bi = jnp.where(valid, bi, NULL_BLOCK)
    off = jnp.broadcast_to(pos % bs, (b, p))                 # [B, P]
    return _store(k_pool, bi, off, k_new), _store(v_pool, bi, off, v_new)


def write_decode(k_pool, v_pool, block_tables, cache_lens, k_new, v_new):
    """Write ONE token per slot at position ``cache_lens[s]``.

    k_new/v_new: [S, H_kv, D]; block_tables: [S, MB]; cache_lens: [S]
    (valid length BEFORE this token — i.e. the write position).
    Inactive slots' tables hold the null block, so their writes are
    harmless by construction. Positions past the table's reach are
    routed to the null block (the ragged serving step parks slots it
    must NOT write — e.g. mid-prefill slots inside the draft loop's
    scan — at an overflow position rather than clamping onto their
    last live block)."""
    bs = k_pool.shape[1]
    mb = block_tables.shape[1]
    lens = cache_lens.astype(jnp.int32)
    blk = lens // bs
    bi = jnp.take_along_axis(block_tables.astype(jnp.int32),
                             jnp.minimum(blk, mb - 1)[:, None],
                             axis=1)[:, 0]                         # [S]
    bi = jnp.where(blk < mb, bi, NULL_BLOCK)
    off = lens % bs
    return _store(k_pool, bi, off, k_new), _store(v_pool, bi, off, v_new)


def write_tokens(k_pool, v_pool, block_tables, cache_lens, k_new, v_new):
    """Append T tokens per slot: token ``t`` of slot ``s`` lands at
    position ``cache_lens[s] + t`` (the speculative-verify window
    write — the multi-token generalization of ``write_decode``).

    k_new/v_new: [S, T, H_kv, D]; block_tables: [S, MB]; cache_lens:
    [S] (valid length BEFORE this window, i.e. the first write
    position). Rollback of rejected speculated tokens is O(1) and
    needs NO cache edit: the caller simply decrements its length
    bookkeeping — positions at/after ``cache_lens`` are masked out of
    every attention read and are overwritten by the next append at the
    same positions. Inactive slots' tables hold the null block, so
    their writes are harmless by construction. Positions past the
    table's reach (chunked prefill right-pads the final chunk, so its
    pad tokens can overrun ``MB * block_size``) are routed to the null
    block instead of letting the gather clamp silently target the
    slot's LAST block."""
    t = k_new.shape[1]
    bs = k_pool.shape[1]
    mb = block_tables.shape[1]
    lens = cache_lens.astype(jnp.int32)
    pos = lens[:, None] + jnp.arange(t, dtype=jnp.int32)[None, :]
    blk = pos // bs
    bi = jnp.take_along_axis(block_tables.astype(jnp.int32),
                             jnp.minimum(blk, mb - 1), axis=1)  # [S, T]
    bi = jnp.where(blk < mb, bi, NULL_BLOCK)
    off = pos % bs
    return _store(k_pool, bi, off, k_new), _store(v_pool, bi, off, v_new)


@_cache_component
def scatter_rows(layer, block_tables, row_slot, row_pos, news):
    """Append a RAGGED mixed batch to one layer's cache, whatever its
    arity: row ``r`` of each array of ``news`` (``[R, ...]``, the
    trailing dims of its pool) lands at cache position ``row_pos[r]``
    of slot ``row_slot[r]`` — the per-row generalization of
    ``write_decode`` (every row its own slot) and ``write_tokens`` (a
    slot may own any number of consecutive rows). One scatter serves
    decode rows (1/slot), speculative verify windows (gamma+1/slot)
    and prefill chunk rows in a single launch. Pad rows carry an
    overflow ``row_pos`` (past the table's reach) and are routed to
    the null block, so the packed buffer's static width never writes
    anything live. Returns the layer's updated tuple."""
    bs = layer[0].shape[1]
    mb = block_tables.shape[1]
    pos = row_pos.astype(jnp.int32)
    slot = row_slot.astype(jnp.int32)
    blk = pos // bs
    bi = block_tables.astype(jnp.int32)[slot, jnp.minimum(blk, mb - 1)]
    bi = jnp.where((pos >= 0) & (blk < mb), bi, NULL_BLOCK)   # [R]
    off = pos % bs
    return _each(lambda pool, rows: _store(pool, bi, off, rows),
                 tuple(layer), tuple(news))


@_cache_component
def write_rows(k_pool, v_pool, block_tables, row_slot, row_pos,
               k_new, v_new):
    """``scatter_rows`` on a ``(k_pool, v_pool)`` pair: ``k_new/v_new``
    are ``[R, H_kv, D]``."""
    return scatter_rows((k_pool, v_pool), block_tables, row_slot,
                        row_pos, (k_new, v_new))


@_cache_component
def permute_window(k_pool, v_pool, block_tables, cache_lens, perm,
                   n_keep):
    """Tree-acceptance K/V compaction: after a tree-speculative verify
    tick, slot ``s``'s accepted root path lives at SCATTERED window
    positions ``cache_lens[s] + perm[s, j]`` — move each onto the
    linear tail position ``cache_lens[s] + j`` (``j < n_keep[s]``) so
    the cache looks exactly as if the accepted tokens had been decoded
    sequentially (the invariant every later read, rollback and prefix
    reuse depends on).

    ``perm``: [S, T] int32 window-node indices, a root path in tree
    node order so ``perm[s, j] >= j``; ``n_keep``: [S] int32 positions
    to keep (0 skips the slot entirely). Pure gather-then-scatter —
    the gather reads the ORIGINAL pool, so overlapping moves can't
    clobber each other; positions past ``n_keep`` (and slots with
    ``n_keep == 0``) scatter into the null block, and their gathers
    read whatever block the clamp lands on (discarded by
    construction). Quantized pools move data AND scales — a moved row
    must dequantize to the identical values its source held. Returns
    the updated ``(k_pool, v_pool)``."""
    bs = k_pool.shape[1]
    mb = block_tables.shape[1]
    lens = cache_lens.astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    t = perm.shape[1]
    j = jnp.arange(t, dtype=jnp.int32)[None, :]                # [1, T]
    keep = j < jnp.asarray(n_keep, jnp.int32).reshape(-1, 1)   # [S, T]
    src = lens[:, None] + perm.astype(jnp.int32)               # [S, T]
    dst = lens[:, None] + j

    def addr(pos, valid):
        blk = pos // bs
        bi = jnp.take_along_axis(tables, jnp.minimum(blk, mb - 1),
                                 axis=1)
        bi = jnp.where(valid & (pos >= 0) & (blk < mb), bi,
                       NULL_BLOCK)
        return bi, pos % bs

    sbi, soff = addr(src, keep)
    dbi, doff = addr(dst, keep)

    def mv(pool):
        if isinstance(pool, QuantKV):
            return QuantKV(
                pool.data.at[dbi, doff].set(pool.data[sbi, soff]),
                pool.scale.at[dbi, doff].set(pool.scale[sbi, soff]))
        return pool.at[dbi, doff].set(pool[sbi, soff])

    return mv(k_pool), mv(v_pool)


def ragged_row_meta(q_lens, base_lens, total_rows, overflow_pos):
    """Host-side row layout of ONE ragged mixed-batch step: slot ``s``
    contributes ``q_lens[s]`` consecutive rows (0 = inactive this tick)
    whose cache positions start at ``base_lens[s]``; rows are packed in
    slot order into a fixed ``total_rows`` buffer.

    Returns ``(row_slot [R], row_pos [R], row_starts [S],
    last_rows [S])`` int32 — pad rows (past the packed total) carry
    slot 0 and ``overflow_pos`` so device writes null-route and reads
    are discarded; ``last_rows[s]`` is the row whose logits continue
    slot ``s`` (its only row for decode, the window head for verify,
    the final prompt row for a completing prefill; 0 for rowless
    slots — the caller discards those)."""
    q = np.asarray(q_lens, np.int64).reshape(-1)
    base = np.asarray(base_lens, np.int64).reshape(-1)
    if int(q.sum()) > int(total_rows):
        raise ValueError(
            f"ragged batch of {int(q.sum())} rows exceeds the "
            f"executable's row budget ({int(total_rows)})")
    row_slot = np.zeros(int(total_rows), np.int32)
    row_pos = np.full(int(total_rows), int(overflow_pos), np.int32)
    row_starts = np.zeros(len(q), np.int32)
    last_rows = np.zeros(len(q), np.int32)
    r = 0
    for s, n in enumerate(map(int, q)):
        row_starts[s] = r
        if n:
            row_slot[r:r + n] = s
            row_pos[r:r + n] = base[s] + np.arange(n)
            last_rows[s] = r + n - 1
        r += n
    return row_slot, row_pos, row_starts, last_rows


@_cache_component
def copy_blocks(pools, src, dst):
    """Copy-on-write device op: duplicate block ``src`` into ``dst``
    across every array of every layer's cache (a pair, or a latent
    cache's one array). ``src``/``dst`` are
    traced int32 scalars, so ONE jitted executable (donate the pools)
    serves every COW — the cost is a single block's K/V bytes per
    layer, no host roundtrip. The caller then swaps ``dst`` into the
    slot's block table and drops its reference on ``src``. Quantized
    pools copy data AND scales (a COW'd block must dequantize to the
    identical values its source holds)."""
    def cp(pool):
        if isinstance(pool, QuantKV):
            return QuantKV(pool.data.at[dst].set(pool.data[src]),
                           pool.scale.at[dst].set(pool.scale[src]))
        return pool.at[dst].set(pool[src])

    return [layer if is_slot_state(layer) else _each(cp, layer)
            for layer in pools]


@_cache_component
def export_blocks(pools, block_ids):
    """Disaggregated prefill->decode transfer, read side: gather the
    SELF-CONTAINED bytes of ``block_ids`` ([M] int32, padded with the
    null block) out of every array of every layer's cache — fp pools as
    ``[M, BS, ...]`` rows in the pool dtype (``[M, BS, H_kv, D]`` of a
    k or v pool, ``[M, BS, W]`` of a latent pool), int8 pools as a
    :class:`QuantKV` of data ``[M, BS, H_kv, D]`` + scales
    ``[M, BS, H_kv]`` (a quantized block's bytes are self-contained
    thanks to the per-row scales, so data + scales IS the block). A
    fixed ``M`` (the engine's max blocks per request) makes this ONE
    compiled executable per engine: pad entries gather the null
    block's garbage, which the importer routes right back to ITS null
    block. The caller copies the result between engines (pools are
    NOT donated — the source pool stays live)."""
    ids = block_ids.astype(jnp.int32)

    def gx(pool):
        if isinstance(pool, QuantKV):
            return QuantKV(pool.data[ids], pool.scale[ids])
        return pool[ids]

    # a slot-state layer holds no blocks: its payload entry is empty
    return [() if is_slot_state(layer) else _each(gx, layer)
            for layer in pools]


@_cache_component
def import_blocks(pools, block_ids, payload):
    """Disaggregated prefill->decode transfer, write side: scatter an
    :func:`export_blocks` payload into THIS pool at ``block_ids``
    ([M] int32, padded with the null block — pad rows land in the
    null block, harmless by construction, so one fixed-width
    executable serves every request size). Layer count / dtypes must
    match the exporter's (same model, same ``kv_cache_dtype``); int8
    payloads scatter data AND scales, so an imported block
    dequantizes to bitwise the values the prefill engine computed.
    Donate ``pools`` — the decode pool is updated in place."""
    ids = block_ids.astype(jnp.int32)

    def sx(pool, rows):
        if isinstance(pool, QuantKV):
            if not isinstance(rows, QuantKV):
                raise TypeError(
                    "import_blocks: int8 pool fed a non-quantized "
                    "payload (exporter and importer must share "
                    "kv_cache_dtype)")
            return QuantKV(pool.data.at[ids].set(rows.data),
                           pool.scale.at[ids].set(rows.scale))
        if isinstance(rows, QuantKV):
            raise TypeError(
                "import_blocks: fp pool fed a quantized payload "
                "(exporter and importer must share kv_cache_dtype)")
        return pool.at[ids].set(rows.astype(pool.dtype))

    if len(payload) != len(pools):
        raise ValueError(
            f"import_blocks: payload has {len(payload)} layers, pool "
            f"has {len(pools)}")
    return [layer if is_slot_state(layer) else _each(sx, layer, rows)
            for layer, rows in zip(pools, payload)]


def _stacked_plan(pools):
    """``(groups, layout)`` of :func:`export_stacked`: every array of
    every layer (an int8 pool's data and scales apart) filed under its
    ``(dtype, block shape)``, in pool order, and for each layer a tuple
    shaped like its cache whose entries say where the array went —
    ``(group, row)``, or a :class:`QuantKV` of two such."""
    index, groups, layout = {}, [], []

    def place(leaf):
        g = index.setdefault(
            (jnp.dtype(leaf.dtype), tuple(leaf.shape[1:])), len(groups))
        if g == len(groups):
            groups.append([])
        groups[g].append(leaf)
        return g, len(groups[g]) - 1

    def file(pool):
        if isinstance(pool, QuantKV):
            return QuantKV(place(pool.data), place(pool.scale))
        return place(pool)

    for layer in pools:
        layout.append(() if is_slot_state(layer) else _each(file, layer))
    return groups, layout


@_cache_component
def export_stacked(pools, block_ids):
    """Eviction spill, read side: the bytes of ``block_ids`` ([n]
    int32 — the evicted blocks, no padding) as ONE array per dtype, so
    that the copy to the host is one transfer and the buffer it lands
    in IS the block: every layer's k and v rows stacked ``[2L, n, BS,
    H_kv, D]``, an int8 pool's data and scales as two stacks, a latent
    pool's one array a layer as ``[L, n, BS, W]``. Never crosses
    engines (:func:`export_blocks` does: its width is a contract), so
    its width is its caller's own. :func:`stacked_layout` +
    :func:`stacked_payload` turn the host copy back into the per-layer
    payload ``import_blocks`` takes."""
    ids = block_ids.astype(jnp.int32)
    groups, _ = _stacked_plan(pools)
    return tuple(jnp.stack([leaf[ids] for leaf in g]) for g in groups)


def stacked_layout(pools):
    """Where :func:`export_stacked` puts each array of ``pools`` (shapes
    and dtypes only: nothing is read)."""
    return _stacked_plan(pools)[1]


def stacked_payload(layout, stacked):
    """The per-layer payload (``[(k, v), ...]``, ``QuantKV`` halves, a
    latent cache's ``[(c,), ...]``) as VIEWS into the host copies
    ``stacked`` of an :func:`export_stacked` result: the payload owns
    exactly the bytes of its blocks."""
    def at(where):
        if isinstance(where, QuantKV):
            return QuantKV(at(where.data), at(where.scale))
        g, row = where
        return stacked[g][row]

    return [_each(at, layer) for layer in layout]


def payload_to_host(payload):
    """Materialize an :func:`export_blocks` payload into host DRAM:
    every jax array becomes a numpy copy (int8 pools keep their
    :class:`QuantKV` shell around numpy data + scale halves, so the
    bytes stay self-contained). This is the spill half of the
    host-DRAM KV tier — the ``np.asarray`` also blocks on the export
    gather, so callers timing the transfer measure real bytes/s."""
    def h(x):
        if isinstance(x, QuantKV):
            return QuantKV(np.asarray(x.data), np.asarray(x.scale))
        return np.asarray(x)

    return [_each(h, rows) for rows in payload]


def payload_nbytes(payload) -> int:
    """Total bytes of an export/spill payload (int8: data + scales) —
    the host-tier accounting unit and the swap half of the
    recompute-vs-swap cost model."""
    return pool_bytes(payload)


def payload_rows(payload, n: int):
    """First ``n`` block rows of a payload — the export executable is
    fixed-width, so a preemption swap or a migration of fewer blocks
    slices the gather down. The rows are numpy VIEWS: the tier books
    ``payload_nbytes`` of them, but the padded buffers stay alive
    behind them for as long as the entry does (a victim's swap lives
    until its resume; the eviction spill, whose entries live long,
    goes through :func:`export_stacked` and owns only its block)."""
    def s(x):
        if isinstance(x, QuantKV):
            return QuantKV(x.data[:n], x.scale[:n])
        return x[:n]

    return [_each(s, rows) for rows in payload]


def payload_pad(payload, m: int):
    """Zero-pad a host payload back to the fixed import width ``m``
    (inverse of :func:`payload_rows`): pad rows ride id slots holding
    the null block, so the import scatter discards them by
    construction."""
    def p(x):
        if isinstance(x, QuantKV):
            return QuantKV(p(x.data), p(x.scale))
        n = x.shape[0]
        if n == m:
            return x
        pad = np.zeros((m - n,) + tuple(x.shape[1:]),
                       np.asarray(x).dtype)
        return np.concatenate([np.asarray(x), pad], axis=0)

    return [_each(p, rows) for rows in payload]


class HostKVTier:
    """Host-DRAM block tier: an LRU byte-capacity cache of spilled KV
    payloads (``payload_to_host`` / ``stacked_payload`` output). Two
    kinds of entries share it — LRU-EVICTED published blocks (keyed
    ``("pub", content_hash)``, one block each: a prefix-cache hit that
    misses the device index can restore the block instead of
    re-prefilling it) and PREEMPTED victim
    payloads (keyed ``("victim", rid)``, the whole slot's live blocks:
    the swap half of preemptive scheduling — a resumed request imports
    the bytes back instead of recomputing them). The tier is pure host
    memory (numpy buffers) and pure bookkeeping: device transfers
    happen in the engine — a victim's swap and every restore through
    the fixed-width ``export_blocks``/``import_blocks`` pair, an
    evicted block through its own one-block ``export_stacked`` gather,
    which is booked here at launch and filled (:meth:`fill`) once its
    bytes are on the host.

    ``capacity_bytes`` bounds resident bytes; inserting past it drops
    oldest entries first (a dropped victim payload forces that
    request's resume onto the recompute path — correctness never
    depends on the tier holding anything). Counters: ``spills`` /
    ``restores`` / ``drops`` and the ``bytes_used`` gauge feed the
    ``serving_host_tier_bytes`` telemetry."""

    def __init__(self, capacity_bytes: int):
        self.capacity = int(capacity_bytes)
        if self.capacity <= 0:
            raise ValueError(
                f"HostKVTier needs a positive byte capacity, got "
                f"{capacity_bytes!r} (0 disables the tier — pass None "
                "to the engine instead)")
        self._items = OrderedDict()     # key -> (payload, nbytes, meta)
        self.bytes_used = 0
        self.spills = 0                 # payloads accepted
        self.restores = 0               # payloads consumed via pop()
        self.drops = 0                  # payloads evicted / refused

    def __len__(self):
        return len(self._items)

    def __contains__(self, key):
        return key in self._items

    def put(self, key, payload, nbytes: int, meta=None) -> bool:
        """Insert (or refresh) ``key``; evicts oldest entries to fit.
        Returns False (counted as a drop) when the payload alone
        exceeds capacity — the caller falls back to recompute."""
        nbytes = int(nbytes)
        if nbytes > self.capacity:
            self.drops += 1
            return False
        old = self._items.pop(key, None)
        if old is not None:
            self.bytes_used -= old[1]
        while self.bytes_used + nbytes > self.capacity and self._items:
            _, (_, nb, _) = self._items.popitem(last=False)
            self.bytes_used -= nb
            self.drops += 1
        self._items[key] = (payload, nbytes, meta)
        self.bytes_used += nbytes
        self.spills += 1
        return True

    def fill(self, key, launched, payload) -> bool:
        """Swap a resident entry's placeholder ``launched`` (what
        ``put`` was given while the bytes were still on their way) for
        the host ``payload``: same bytes booked, same place in the LRU
        order, no counter moves. False where the entry has gone
        meanwhile (dropped for room, purged, or put again)."""
        it = self._items.get(key)
        if it is None or it[0] is not launched:
            return False
        self._items[key] = (payload,) + it[1:]
        return True

    def get(self, key):
        """Peek (MRU-touch) — payload or None; the entry stays
        resident (cost-model probing must not consume it)."""
        it = self._items.get(key)
        if it is None:
            return None
        self._items.move_to_end(key)
        return it[0]

    def meta(self, key):
        it = self._items.get(key)
        return None if it is None else it[2]

    def nbytes_of(self, key) -> int:
        it = self._items.get(key)
        return 0 if it is None else it[1]

    def pop(self, key, restore: bool = True):
        """Remove and return ``key``'s payload (None when absent).
        ``restore=False`` discards without counting a restore (a
        resumed-by-recompute request's stale victim payload, a
        cancelled request's spill)."""
        it = self._items.pop(key, None)
        if it is None:
            return None
        self.bytes_used -= it[1]
        if restore:
            self.restores += 1
        return it[0]

    def purge_published(self) -> int:
        """Drop every LRU-evicted published-block entry (keys shaped
        ``("pub", hash)``) — the host-side half of a replica-drain
        index purge (``BlockAllocator.unpublish_all``): a drained or
        dead replica must stop answering the router's affinity probe
        from its spill tier too. Victim payloads (in-flight resume
        state) are untouched. Returns the number of entries dropped."""
        keys = [k for k in self._items
                if isinstance(k, tuple) and k and k[0] == "pub"]
        for k in keys:
            _, nb, _ = self._items.pop(k)
            self.bytes_used -= nb
            self.drops += 1
        return len(keys)


def gather_dense(pool, block_tables):
    """[S, MB*BS, H_kv, D] dense view of each slot's cache (a latent
    pool ``[NB, BS, W]`` gives ``[S, MB*BS, W]``; positions
    beyond the slot's length read whatever the pooled blocks hold — the
    caller masks by length). The jnp fallback attention and tests use
    this; the TPU kernel never materializes it. Quantized pools come
    back DEQUANTIZED to f32, and the fallbacks keep that f32 through
    their dots — the kernels' in-VMEM dequant recipe,
    value-for-value."""
    s, mb = block_tables.shape
    tables = block_tables.astype(jnp.int32)
    if isinstance(pool, QuantKV):
        g = kv_dequantize(pool.data[tables], pool.scale[tables])
    else:
        g = pool[tables]                        # [S, MB, BS, H, D]
    return g.reshape((s, mb * pool.shape[1]) + tuple(pool.shape[2:]))

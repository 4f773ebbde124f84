"""Continuous-batching LLM serving engine over the paged KV cache.

The serving role PaddleNLP's ``llm/predict/predictor.py`` + a request
scheduler play over AnalysisPredictor, rebuilt TPU-first for the
compiler's static-shape world (arxiv 2603.09555) with the block-table
paged KV layout of *Ragged Paged Attention* (arxiv 2604.15464):

- **One tick, ONE executable.** The engine owns ``num_slots`` serving
  slots. Every tick runs ONE AOT-compiled ragged step
  (``_compile_ragged_step``) that consumes ALL active work as a single
  packed row buffer: decoding slots contribute 1 query row,
  speculative verify windows ``gamma + 1`` rows, and pending prompts
  up to ``prefill_chunk`` rows each inside the tick's
  ``ragged_prefill_rows`` budget — partitioned by per-slot ``q_lens``
  and cumulative ``row_starts``, with the surrounding write/sample
  fused into the same launch. The packed width is static, so
  raggedness lives in VALUES and steady state runs ZERO recompiles:
  executables per engine is 1 (2 with a draft model — its proposal
  scan + prefill priming fuse into one draft ragged step; assert via
  ``stats()["executables_compiled"]``), every tick is one dispatch
  round-trip, and admission prefill overlaps running decodes (a
  pending slot simply contributes prompt rows instead of a decode
  row). See docs/OPS.md "Ragged mixed-batch serving".
- **Paged KV.** All slots share one block pool per layer
  (``ops/paged_cache.py``); the host-side ``BlockAllocator`` hands
  blocks to admitted requests and reclaims them at retirement, so HBM
  scales with live tokens, not ``slots x max_len``. The tick's
  attention reads the pool through the ragged Pallas kernel on TPU
  (``ops/pallas/paged_attention.py``) and its XLA mirror on CPU.
- **Continuous batching.** ``step()`` admits queued requests into freed
  slots, runs the tick, streams tokens out, and retires slots on
  EOS/max-len — freed blocks and slots are reused by the next
  admission without ever draining the batch.
- **Ticks dispatched ahead.** Without speculation every tick is
  launched BEFORE the last one's tokens are fetched: the host packs it
  from committed state plus what the tick in flight does to it
  (``_ahead``), a decoding slot's id is read from that tick's output
  on the device (``src``), and the commit of tick N — fetch, emit,
  retire, publish, spans — runs while tick N+1 executes. Admission,
  chunked prefill, growth and eviction spills ride along; what reads
  slot state from outside the tick (cancel, preemption, migration, a
  handoff, shutdown) drains the pipeline first. ``async_depth=0`` /
  ``PADDLE_TPU_ASYNC_TICK=0`` keep the blocking loop as the
  reference. See docs/OPS.md "Async tick pipeline".
- **Prefix caching (content-addressed blocks).** The ``BlockAllocator``
  keeps per-block refcounts and a content-hash index (rolling hash
  chains over token ids, seeded by a model/config fingerprint —
  ``ops/paged_cache.chain_hashes``). Retirement publishes the retired
  sequence's FULL blocks into the index instead of dropping them; they
  park in an LRU list until memory pressure evicts them. Admission
  hashes the prompt's full blocks, maps the longest cached prefix
  straight into the slot's block table (refcount++) and prefills
  only the suffix — shared system prompts, few-shot headers and
  multi-turn history prefill once per cache lifetime, not per request.
  A shared block the suffix must write into (full-prompt hit) is
  copy-on-write duplicated first (one device block copy). Greedy
  outputs are token-exact vs the cold path. Kill switch:
  ``PADDLE_TPU_PREFIX_CACHE=0``. See docs/OPS.md "Prefix caching &
  chunked prefill".
- **Speculative decoding** (``num_speculative_tokens = gamma > 0``): a
  drafter (model-free n-gram prompt lookup, a smaller draft model
  sharing the block tables, or draft heads filling a token tree)
  proposes gamma tokens per slot and the tick's verify rows accept
  1..gamma+1 of them — accept/reject lives in the LENGTH values:
  rejected tokens roll back by not advancing ``cache_lens`` and
  returning overhang blocks to the allocator (no data movement). The
  scheduler reserves ``prompt + max_new + gamma`` blocks worst-case
  (the speculated window may overhang the final token), retires EOS
  found anywhere inside the window, and streams every accepted token
  through the ordinary callback. Kill switch:
  ``PADDLE_TPU_SPECULATIVE=0``; capacity-routed MoE is excluded (the
  window tokens would compete for expert capacity). See docs/OPS.md
  "Speculative decoding".

- **Mega-kernelized decode tick** (``ServingConfig(fused_decode=
  True)``, the default): inside every serving executable the decoder
  layers' norm -> QKV, attention-epilogue -> O-projection (+
  residual), norm -> gate/up and swiglu -> down (+ residual)
  boundaries run as fused Pallas kernels
  (``ops/pallas/decode_fused.py``) — per-layer activations stay in
  VMEM across the old kernel boundaries on TPU. Off TPU the fallback
  is bitwise the unfused graph, so fused ON==OFF is token-exact by
  construction; GSPMD TP traces keep the unfused projections. The
  sampling head's temperature/top-k/top-p ride as a per-SLOT device
  tensor (``submit()`` accepts per-request overrides), so a new
  sampling config never recompiles anything. The per-executable
  kernel census (``monitor.kernel_census`` —
  ``stats()["kernels_per_tick"]``, ``serving_kernels_per_tick``
  gauge) measures the collapse. Kill switch
  ``PADDLE_TPU_FUSED_DECODE=0``; ``=interpret`` runs the kernels
  under the Pallas interpreter on any backend. See docs/OPS.md
  "Decode-tick fusion & the in-executable sampling head".

- **Quantized KV cache** (``ServingConfig(kv_cache_dtype="int8")`` /
  env twin ``PADDLE_TPU_KV_INT8``): the block pool stores int8 K/V
  plus per-(block, position, head) absmax scales
  (``ops/paged_cache.QuantKV``) — every write path quantizes on store
  through one shared scatter, the Pallas kernels dequantize tiles in
  VMEM after the block load, and the XLA fallbacks mirror the same
  math through ``gather_dense``. Steady-state decode is HBM-bound on
  KV reads, so bytes/step halve (~0.53x pool bytes vs bf16) and
  ~2x the slots fit a fixed pool budget. Prefix caching, COW,
  speculative rollback, chunked prefill and TP all compose (stored
  bytes are a pure function of the tokens; the scale pool shards on
  the same kv_head cut). Default (None) keeps the fp pool bit-for-bit;
  ``PADDLE_TPU_KV_INT8=0`` is the kill switch. See docs/OPS.md "KV
  cache quantization".

- **Tensor-parallel serving** (``ServingConfig(tp_degree=N)``): every
  serving executable — the tick, the draft step and the
  ``copy_blocks`` COW — is sharded over a ``Mesh(devices[:N],
  ("mp",))`` axis (GSPMD, arxiv 2105.04663). The KV block pool splits
  on its kv_heads dim (each shard owns a contiguous kv_head slice of
  EVERY block); model params shard column/row-wise through the
  models' existing ``mp`` PartitionSpecs; block tables,
  ``cache_lens``, token ids and the sampling PRNG key are replicated.
  The only EXPLICIT cross-shard collective is one logits
  ``all_gather`` before sampling (``_gather_logits`` —
  census-asserted; the per-layer reduces of the row-parallel linears
  are GSPMD-inserted and proxied by the ``sharding_constraint``
  census row), so sampling consumes the same replicated logits/key on
  every shard. Host state is untouched: ONE ``BlockAllocator``, one
  scheduler, one prefix-cache index — block ids are global and every
  shard's pool slice is indexed by the same tables, so prefix
  caching, COW, speculative rollback and chunked prefill all compose
  with TP for free. Kill switch ``PADDLE_TPU_SERVE_TP=0`` restores
  the single-device path bit-for-bit. See docs/OPS.md
  "Tensor-parallel serving".

- **Disaggregated prefill -> decode** (``ServingConfig(role=
  "prefill" | "decode" | "both")``): a role="prefill" engine runs
  admission + chunked prefill only — each completed prompt streams its
  first token, then parks for ``pop_prefilled()``, which exports the
  slot's KV blocks as a self-contained payload (ONE fixed-width
  ``ops/paged_cache.export_blocks`` executable; int8 blocks carry
  data + per-row scales) and publishes the prompt's blocks into the
  prefix index before freeing them. ``admit_prefilled()`` on any
  engine of the same model/layout imports the payload (ONE fixed-width
  scatter) and seats a decoding slot at exactly the colocated
  post-prefill state, so greedy continuation is token-exact. The
  ``EngineCluster`` (``inference/cluster.py``) orchestrates N replicas
  behind a session-affine router on top of this. See docs/OPS.md
  "Engine replication & disaggregated prefill".

Admission is worst-case reserved: a request is admitted when the pool
can cover ``prompt + max_new`` blocks for it PLUS the outstanding
reservations of every active slot; the preemptive scheduler
(``enable_preemption``, docs/OPS.md "Preemption & hierarchical KV
offload") may overcommit past that and reclaims by preemption. A
role="prefill" engine reserves only the prompt's blocks — its decode
horizon lives on the importing replica.

Telemetry (monitor registry, exported in the JSONL dump):
``serving_slot_occupancy`` gauge, ``serving_batch_utilization`` /
``serving_queue_wait_ms`` histograms (the latter labeled by terminal
outcome: admitted | cancelled | rejected | shutdown, so pre-admission
exits leave a record too), ``serving_tokens_total`` /
``serving_decode_steps`` / ``serving_decode_compiles`` /
``serving_requests_completed`` /
``serving_prefix_blocks_reused`` / ``serving_prefix_tokens_reused`` /
``serving_cow_copies`` / ``serving_cache_evictions`` counters and the
``serving_prefix_hit_rate`` gauge.

Request-lifecycle tracing + SLO digests (docs/OPS.md "Request tracing
& SLO goodput"): every engine owns a span tracer
(``monitor/tracing.py`` — one trace-viewer pid per engine, tid 0 the
engine tick timeline, tid 1+i slot i, last tid the admission queue)
recording ``submit -> queued -> admit (prefix-hit annotated) ->
prefill chunk -> decode/verify tick (rows, accepted_len, exec id)
-> retired`` plus per-tick engine spans (occupancy, kernel-fallback
count); ``engine.dump_trace(path)`` writes
Perfetto-loadable Chrome trace JSON. Kill switch ``PADDLE_TPU_TRACE=0``
(bit-for-bit inert: tracing is host-side only). Independent of that
switch, four always-on P² latency digests power ``stats()``'s
``ttft_ms`` / ``itl_ms`` / ``queue_wait_ms`` / ``e2e_ms`` summaries and
the ``serving_ttft_ms`` / ``serving_itl_ms`` /
``serving_queue_wait_quantile_ms`` / ``serving_e2e_ms`` p50/p95/p99
gauges.
"""
from __future__ import annotations

import contextlib
import itertools
import os
import time
import warnings
from collections import OrderedDict, deque, namedtuple
from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import monitor
from ..distributed import moe as _moe
from ..framework.core import component, executable_scopes
from ..monitor import health as _health
from ..monitor import tracing as _tracing
from ..monitor.digest import LatencyDigest
from ..ops import lora as _lora
from ..ops import paged_cache as _pc
from ..ops.pallas import paged_attention as _pa

__all__ = ["ServingConfig", "ServingRequest", "ServingEngine",
           "PrefilledRequest", "MigratedSession", "QueueShedError"]


class QueueShedError(RuntimeError):
    """Raised by ``submit()`` when queue-depth load shedding is armed
    (``ServingConfig.shed_queue_depth``) and the admission queue is
    already at the threshold: the request is REFUSED at the front
    door (a ``serving_queue_wait_ms{outcome="shed"}`` observation is
    the only trace it leaves) so queued work keeps its latency budget
    instead of everyone timing out together under overload."""

# trace-viewer pid per engine (and the stats() engine_id)
_ENGINE_IDS = itertools.count()


@contextlib.contextmanager
def _quiet_donation():
    """Pool donation is a TPU-side optimization (decode/prefill reuse
    the pool's HBM in place); CPU ignores donation with a warning that
    would fire every engine tick. Scoped here so other code's genuinely
    broken donations still surface."""
    with warnings.catch_warnings():
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable")
        yield


@dataclass
class ServingConfig:
    num_slots: int = 8                  # fixed decode batch width
    block_size: int = 16                # tokens per KV block
    max_model_len: int = 1024           # prompt + generated cap per seq
    # pool size; default covers every slot at max_model_len (admission
    # then never queues on blocks, only on slots) — shrink to trade HBM
    # for queueing
    num_blocks: Optional[int] = None
    max_new_tokens: int = 128           # per-request default
    eos_token_id: Optional[int] = None
    pad_token_id: int = 0
    decode_strategy: str = "greedy_search"   # or "sampling"
    temperature: float = 1.0
    top_k: int = 0
    top_p: float = 1.0
    seed: int = 0
    # speculative decoding: draft gamma tokens per slot per step and
    # verify them in one multi-token forward (0 = off)
    num_speculative_tokens: int = 0
    drafter: str = "ngram"              # ngram | model (pass draft_model)
    #                                     | heads (tree draft heads)
    spec_ngram_max: int = 3             # longest prompt-lookup n-gram
    # tree-structured speculation (docs/OPS.md "Tree speculation"):
    # the speculated window becomes a token TREE instead of a linear
    # chain. The tuple gives each non-root node's parent index —
    # node k+1's parent is spec_tree[k], 0 = the root (this tick's
    # committed token); len(spec_tree) must equal
    # num_speculative_tokens, so the verify node budget t_q = gamma+1
    # is unchanged and the ONE ragged executable keeps zero
    # steady-state recompiles. Topology is static per engine; a chain
    # tree (0, 1, 2, ...) is bit-for-bit the linear path. Drafting:
    # drafter="ngram" fills the tree's root-to-leaf chains with the
    # top-k prompt-lookup continuations (zero extra weights);
    # drafter="heads" adds Medusa-style draft-head projections over
    # the target's final hidden state (weights ride WITH the target
    # params, so head-drafted trees serve on disaggregated clusters
    # where separate draft models cannot). Kill switch
    # PADDLE_TPU_SPEC_TREE=0 restores the linear speculative engine
    # bit-for-bit (heads engines fall back to the linear ngram
    # drafter). None = linear speculation, exactly as before.
    spec_tree: Optional[tuple] = None
    # most prompt rows ONE slot rides into a tick: a prompt suffix
    # prefills in ceil(n / prefill_chunk) ticks or more (the tick's
    # row budget, ragged_prefill_rows, is shared by pending slots)
    prefill_chunk: int = 128
    # content-addressed prefix reuse over the block pool. False (or
    # PADDLE_TPU_PREFIX_CACHE=0) disables hashing/publishing — blocks
    # free eagerly.
    enable_prefix_cache: bool = True
    # False: retirement drops each request's token buffer instead of
    # holding it for run() — REQUIRED for long-lived streaming
    # deployments that consume tokens via stream_callback and drive
    # step() themselves (otherwise finished results accumulate
    # unboundedly; run() then returns {}).
    retain_results: bool = True
    # per-tick prefill row budget of the tick (the executable's
    # packed width is num_slots * (gamma+1) + this). None = one
    # prefill_chunk's worth; shrink to trade time-to-first-token for
    # smaller per-tick padding when slots mostly decode.
    ragged_prefill_rows: Optional[int] = None
    # tensor-parallel degree: shard every serving executable over a
    # Mesh(devices[:tp_degree], ("mp",)) axis — the KV pool splits on
    # kv_heads, params column/row-wise, tables/lengths/keys replicate,
    # one explicit logits all_gather per step. Must divide the model's
    # num_kv_heads / num_attention_heads / vocab_size (validated at
    # engine construction). Kill switch: PADDLE_TPU_SERVE_TP=0.
    tp_degree: int = 1
    # KV-pool quantization: None/'auto' = pool in the model dtype
    # (bit-for-bit the pre-quantization layout); 'int8' = quantized
    # block pool (int8 data + per-(block, position, head) f32 absmax
    # scales — ~0.53x the bf16 pool bytes, half the KV HBM stream per
    # decode step, ~2x admissible slots at a fixed pool byte budget).
    # Composes with prefix caching/COW (quantize-on-store makes cached
    # bytes a pure function of the tokens), speculative verify/
    # rollback, chunked prefill and TP (the scale
    # pool shards on the same kv_head cut). Env twin
    # PADDLE_TPU_KV_INT8: 0 = kill switch (fp pool, bit-for-bit), 1 =
    # int8 when this field is left None. On TPU use block_size=32 (the
    # int8 sublane tile) to keep the Pallas kernel eligible.
    kv_cache_dtype: Optional[str] = None
    # MoE routing telemetry (serving_moe_expert_load /
    # serving_moe_routing_entropy): each sparse layer's dispatch
    # embeds one tiny host callback per executed tick. False (or
    # PADDLE_TPU_MOE_TELEMETRY=0) traces the executables without the
    # tap — zero callback cost, stats() moe_routing_entropy stays 0.0.
    moe_telemetry: bool = True
    # disaggregated-cluster role: "both" (default) serves requests end
    # to end; "prefill" runs admission + chunked prefill ONLY — a slot
    # whose prompt completes parks for ``pop_prefilled()`` handoff
    # (its first token is still streamed; its KV blocks export via
    # ``ops/paged_cache.export_blocks``) and the engine reserves only
    # the PROMPT's blocks per request (the decode horizon lives on the
    # importing replica); "decode" marks a replica that additionally
    # receives ``admit_prefilled()`` imports (any role accepts them —
    # the flag documents cluster intent and shows up in stats()).
    role: str = "both"
    # -- SLO-aware preemptive scheduling + host-DRAM KV tier ----------
    # (docs/OPS.md "Preemption & hierarchical KV offload"). True (the
    # default) arms: priority classes on submit(priority=) — highest
    # class admits first, FIFO within a class; a WATERMARK admission
    # policy that may overcommit the block pool (admit on
    # immediately-needed blocks + headroom instead of the worst-case
    # prompt+max_new reservation — the 1.88x int8 slot win becomes
    # usable); and preemption under slot/block pressure: the
    # lowest-priority victim slot is spilled (full blocks published
    # into the prefix index, live bytes exported to the host-DRAM
    # tier), freed, and re-enqueued at the front of its class — on
    # re-admission it either swap-restores the spilled bytes or
    # re-prefills from the published blocks (recompute-vs-swap cost
    # model), continuing token-exact vs never-preempted. False (or the
    # PADDLE_TPU_PREEMPT=0 kill switch, which beats an explicit True)
    # restores the worst-case-reservation FIFO scheduler bit-for-bit:
    # priorities are ignored, nothing spills, no host tier exists.
    # Preemption never runs on a role="prefill" engine (its slots
    # only park for handoff).
    enable_preemption: bool = True
    # watermark admission headroom in blocks: a request is admitted
    # when the worst-case reservation fits (the old policy, unchanged
    # when the pool is ample) OR when free blocks cover its immediate
    # allocation plus this headroom (overcommit — growth past it is
    # reclaimed by preemption). None = num_slots (one growth block per
    # slot of headroom).
    admission_watermark_blocks: Optional[int] = None
    # host-DRAM KV tier capacity (bytes) for spilled blocks: preempted
    # victims' live bytes and LRU-evicted published blocks park here
    # (ops/paged_cache.HostKVTier) and restore through the fixed-width
    # import executable. 0 disables the tier — victims always resume
    # by recompute, evicted cached blocks just die (pre-tier
    # behavior).
    host_kv_tier_bytes: int = 64 << 20
    # resume path for preempted victims: "auto" picks per victim from
    # the measured recompute-vs-swap cost model (chunk-prefill tok/s
    # vs host-transfer bytes/s), "swap"/"recompute" force one path
    # (tests, tuning).
    preempt_resume: str = "auto"
    # queue-depth load shedding: submit() raises QueueShedError (and
    # lands a serving_queue_wait_ms{outcome="shed"} observation) when
    # the admission queue already holds this many requests. None = off.
    shed_queue_depth: Optional[int] = None
    # default per-request queue-wait budget: a request still queued
    # after this many ms exits with outcome="timeout" (empty result,
    # stream never starts). None = unbounded; submit(max_queue_wait_ms=)
    # overrides per request.
    max_queue_wait_ms: Optional[float] = None
    # mega-kernelized decode tick (ops/pallas/decode_fused.py): fuse
    # RMSNorm/LayerNorm into the QKV projection prologue, the
    # attention epilogue into the O-projection + residual add, and the
    # MLP's norm/swiglu boundaries, inside every serving executable —
    # per-layer activations stay in VMEM across the kernel boundaries
    # on TPU. Off-TPU the fallback is bitwise the unfused graph, so
    # this flag is numerics-free on CPU. Kill switch
    # PADDLE_TPU_FUSED_DECODE=0 (beats an explicit True);
    # PADDLE_TPU_FUSED_DECODE=interpret runs the fused kernels under
    # the Pallas interpreter on any backend (tests/bench). GSPMD TP
    # engines keep the unfused projections (an opaque pallas_call
    # cannot be partitioned).
    fused_decode: bool = True
    # fleet health engine (monitor/health.py): SLO burn-rate monitors,
    # anomaly detectors, a stuck-tick watchdog, and incident
    # auto-capture over signals the engine already produces. Pure host
    # code: under PADDLE_TPU_HEALTH=0 (beats an explicit True) the
    # monitor is never constructed and tokens + executables_compiled
    # stay bit-for-bit identical.
    health: bool = True
    # per-request SLO for burn-rate attainment (ms); a request misses
    # its SLO when TTFT exceeds health_slo_ttft_ms or any inter-token
    # latency exceeds health_slo_itl_ms.
    health_slo_ttft_ms: float = 2000.0
    health_slo_itl_ms: float = 500.0
    # SLO target (error budget = 1 - target) and SRE fast/slow burn
    # windows: the fast alert pages only when BOTH windows burn faster
    # than health_burn_threshold x budget and the fast window holds at
    # least health_burn_min_requests retirements.
    health_slo_target: float = 0.99
    health_burn_fast_s: float = 5.0
    health_burn_slow_s: float = 60.0
    health_burn_threshold: float = 2.0
    health_burn_min_requests: int = 8
    # stuck-tick watchdog deadline: max(floor, mult x step-time EMA).
    health_watchdog_mult: float = 50.0
    health_watchdog_floor_s: float = 5.0
    # arm a ProfilerWindow for this many ticks when an alert fires
    # (0 = off; needs PADDLE_TPU_PROFILE_DIR or an explicit path to
    # land anywhere).
    health_profile_ticks: int = 0
    # -- batched multi-LoRA serving (docs/OPS.md "Multi-LoRA
    # serving"): lora_rank > 0 arms the adapter machinery —
    # engine.load_adapter() registers per-tenant A/B delta weights in
    # a host-DRAM registry, submit(adapter_id=) tags requests, and
    # every decode tick applies the per-slot deltas as ONE
    # mixed-adapter ragged grouped matmul inside the single existing
    # tick executable (adapter churn swaps stack VALUES at a fixed
    # shape — zero steady-state recompiles). Kill switch
    # PADDLE_TPU_LORA=0 restores the base engine
    # bit-for-bit (no extra operand, no tagged module, no extra
    # per-slot row).
    lora_rank: int = 0
    # device-resident adapter budget: this many adapters stay loaded
    # in the stacked device image at once (plus the always-present
    # null adapter); the rest of the registry spills to host DRAM and
    # LRU-swaps in on demand (refcounts pin adapters serving in-flight
    # requests, so eviction mid-request is impossible).
    max_adapters: int = 8
    # LoRA scale numerator: delta = (x @ A @ B) * lora_alpha /
    # lora_rank. None = lora_rank (scale 1.0).
    lora_alpha: Optional[float] = None
    # which projections carry deltas: "attn" = q/k/v/o (qkv/out on
    # GPT), "all" adds the MLP projections (gate/up/down, linear1/2)
    lora_targets: str = "attn"
    # int8-quantize the resident adapter stacks (per-matrix absmax
    # scales, dequantized in-trace — the PR 10 KV-pool recipe applied
    # to the delta weights; ~4x adapters per resident byte)
    lora_quant: bool = False
    # -- async tick pipeline (docs/OPS.md "Async tick pipeline") ------
    # None / 1 (the default): every tick without speculation is
    # launched BEFORE the last one's tokens are fetched — the host
    # packs it from committed state plus what the tick in flight does
    # to it, the decode ids it does not have yet are read from that
    # tick's output on the device — and the commit of tick N (fetch,
    # emit, retire, publish, spans: stream callbacks, stats() and
    # spans lag one tick) runs while tick N+1 executes. Admission,
    # growth, eviction spills and chunked prefill ride along;
    # cancel, preemption, migration, a handoff and shutdown drain it
    # first (stats()["pipeline_flushes"]). Served tokens are the
    # blocking loop's. 0 = that blocking loop, the reference the
    # parity tests compare against; env twin PADDLE_TPU_ASYNC_TICK=0
    # beats an explicit depth. A speculating engine (gamma > 0)
    # blocks whatever is set here. Only depth 1 is implemented.
    async_depth: Optional[int] = None

    def __post_init__(self):
        # reject broken degrees HERE, with a message, instead of as a
        # shape crash deep inside shard_map tracing
        tp = self.tp_degree
        if not isinstance(tp, int) or isinstance(tp, bool) or tp < 1:
            raise ValueError(
                f"tp_degree must be a positive int, got {tp!r}")
        if self.role not in ("both", "prefill", "decode"):
            raise ValueError(
                f"role must be both|prefill|decode, got {self.role!r}")
        if self.preempt_resume not in ("auto", "swap", "recompute"):
            raise ValueError(
                f"preempt_resume must be auto|swap|recompute, got "
                f"{self.preempt_resume!r}")
        if self.host_kv_tier_bytes < 0:
            raise ValueError(
                f"host_kv_tier_bytes must be >= 0, got "
                f"{self.host_kv_tier_bytes!r}")
        if self.shed_queue_depth is not None \
                and int(self.shed_queue_depth) < 1:
            raise ValueError(
                f"shed_queue_depth must be >= 1 (or None), got "
                f"{self.shed_queue_depth!r}")
        if not 0.0 < self.health_slo_target < 1.0:
            raise ValueError(
                f"health_slo_target must be in (0, 1), got "
                f"{self.health_slo_target!r}")
        if not 0.0 < self.health_burn_fast_s < self.health_burn_slow_s:
            raise ValueError(
                f"need 0 < health_burn_fast_s < health_burn_slow_s, got "
                f"{self.health_burn_fast_s!r}, {self.health_burn_slow_s!r}")
        if self.health_watchdog_floor_s <= 0:
            raise ValueError(
                f"health_watchdog_floor_s must be > 0, got "
                f"{self.health_watchdog_floor_s!r}")
        if self.health_watchdog_mult < 1.0:
            raise ValueError(
                f"health_watchdog_mult must be >= 1, got "
                f"{self.health_watchdog_mult!r}")
        ad = self.async_depth
        if ad is not None and (not isinstance(ad, int)
                               or isinstance(ad, bool)
                               or ad < 0 or ad > 1):
            raise ValueError(
                f"async_depth must be 0, 1 or None, got {ad!r}")
        lr = self.lora_rank
        if not isinstance(lr, int) or isinstance(lr, bool) or lr < 0:
            raise ValueError(
                f"lora_rank must be an int >= 0, got {lr!r}")
        if lr > 0:
            if int(self.max_adapters) < 1:
                raise ValueError(
                    f"max_adapters must be >= 1, got "
                    f"{self.max_adapters!r}")
            if self.lora_targets not in ("attn", "all"):
                raise ValueError(
                    f"lora_targets must be 'attn' or 'all', got "
                    f"{self.lora_targets!r}")
            if self.lora_alpha is not None \
                    and float(self.lora_alpha) <= 0.0:
                raise ValueError(
                    f"lora_alpha must be > 0 (or None), got "
                    f"{self.lora_alpha!r}")


def _num_experts(cfg) -> int:
    """Routed-expert count of a model config (0 = dense): the ONE
    probe behind the MoE admission gate, the engine's ``_moe`` flag
    and the TP divisibility check — a third MoE config field name
    lands in exactly one place."""
    return int(getattr(cfg, "num_experts", 0)
               or getattr(cfg, "n_routed_experts", 0) or 0)


@dataclass
class ServingRequest:
    request_id: int
    prompt: np.ndarray                  # [L] int32
    max_new_tokens: int
    submit_time: float = field(default_factory=time.monotonic)
    # per-request sampling overrides (None = the engine's
    # ServingConfig values); land in the engine's per-SLOT sampling
    # tensors at admission — device DATA, so a request with its own
    # knobs never recompiles anything
    temperature: Optional[float] = None
    top_k: Optional[int] = None
    top_p: Optional[float] = None
    # scheduling class: higher admits first under the preemptive
    # scheduler and may preempt strictly-lower-priority victims; FIFO
    # within a class. Ignored (pure FIFO) when preemption is off.
    priority: int = 0
    # queue-wait budget (ms): still queued past it -> outcome="timeout"
    max_queue_wait_ms: Optional[float] = None
    # multi-LoRA tenant: which registered adapter's delta weights this
    # request decodes under (None = base model). Validated at submit;
    # pinned (refcounted) in the AdapterPool while the request holds a
    # slot, carried across preemption spill/resume and disaggregated
    # handoffs.
    adapter_id: Optional[int] = None
    # preemption carry-over (None for fresh requests): the victim's
    # continuation state — {"cache_len", "last_token", "n_emitted",
    # "history", "worst_blocks", "n_blocks", "nbytes", "key"} — plus
    # the resolved per-slot sampling row, so re-admission seats the
    # slot EXACTLY where the preempted one stopped
    resume: Optional[dict] = None


@dataclass
class PrefilledRequest:
    """One finished prefill, packaged for a decode replica: the prompt
    whose KV the payload holds, the first token the prefill engine
    sampled (already streamed to the client), and the exported block
    bytes (``ops/paged_cache.export_blocks`` output — a fixed-width
    ``[mb]`` gather per layer, padded entries carrying null-block
    garbage the importer routes back to its own null block). Produced
    by ``ServingEngine.pop_prefilled()`` on a role="prefill" engine,
    consumed by ``admit_prefilled()`` on any engine of the SAME model
    and serving layout (block_size / max_model_len / kv_cache_dtype)."""
    request_id: int                     # PREFILL-engine-local rid
    prompt: np.ndarray                  # [L] int32
    first_token: int
    max_new_tokens: int
    n_blocks: int                       # real (non-pad) blocks
    payload: Optional[list]             # per-layer (k_rows, v_rows);
    #                                     None (a model with slot
    #                                     state) -> recompute on import
    # the request's per-slot sampling knobs travel with the handoff
    # (the decode replica seats the slot with the SAME values the
    # prefill engine sampled the first token under)
    temperature: Optional[float] = None
    top_k: Optional[float] = None
    top_p: Optional[float] = None
    # the request's scheduling class rides the handoff so the decode
    # replica's preemptive scheduler sees the same priority the
    # prefill tier admitted under
    priority: int = 0
    # trace flow-link id (monitor/tracing.next_flow_id): the export
    # side records the flow start, the import side the finish, so a
    # merged trace draws the handoff as an arrow between the two
    # replicas' request spans. None when tracing is disabled.
    flow_id: Optional[int] = None
    # multi-LoRA tenant id: the prefill tier computed this payload's
    # KV UNDER the adapter's deltas, so the decode replica MUST seat
    # the slot under the same adapter (load_adapter() is broadcast
    # cluster-wide, so the id resolves on both sides)
    adapter_id: Optional[int] = None


@dataclass
class MigratedSession:
    """One LIVE session packaged for another replica (scale-down
    drain / cluster rebalancing): the full continuation state a
    preemption resume carries — cache position, last sampled token,
    emit count, token history, sampling row, scheduling class,
    adapter pin — PLUS the exported live KV bytes, so the importing
    engine seats a decoding slot exactly where this one stopped and
    the client's stream continues token-exact, never re-submitted.
    ``payload=None`` degrades to the recompute path: the target
    re-prefills ``history[:cache_len]`` through the ordinary chunk
    machinery and restores the continuation (token-exact either way —
    recompute IS the preemption recompute resume). Produced by
    ``ServingEngine.export_session`` / ``drain_sessions``, consumed
    by ``admit_migrated`` on any decode-capable engine of the SAME
    model and serving layout (block_size / max_model_len /
    kv_cache_dtype)."""
    request_id: int                     # SOURCE-engine-local rid
    prompt: np.ndarray                  # [L] int32 original prompt
    history: list                       # prompt + emitted tokens
    cache_len: int                      # valid cache positions
    last_token: int                     # sampled, not yet in cache
    n_emitted: int                      # tokens already streamed
    max_new_tokens: int
    worst_blocks: int                   # admission reserve (carried —
    #                                     replicas share the config,
    #                                     so the target's accounting
    #                                     matches the source's)
    n_blocks: int                       # real (non-pad) payload blocks
    payload: Optional[list] = None      # per-layer host (k, v) rows;
    #                                     None -> recompute on import
    # the resolved per-slot sampling row travels verbatim (the target
    # decodes under the SAME knobs the source sampled with)
    temperature: Optional[float] = None
    top_k: Optional[float] = None
    top_p: Optional[float] = None
    priority: int = 0
    adapter_id: Optional[int] = None
    # trace flow-link id: export records the start, import the finish
    # — the merged fleet trace draws the migration as an arrow
    flow_id: Optional[int] = None
    # export timestamp: the cluster's migration_ms digest observes
    # export -> seated wall time (queueing while pending included —
    # that IS the drain latency a client could feel as a stall)
    export_t: float = field(default_factory=time.monotonic)


class _Slot:
    __slots__ = ("rid", "blocks", "worst_blocks", "cache_len",
                 "last_token", "n_emitted", "max_new", "history",
                 "prompt", "pend_pos", "admit_t",
                 "handoff", "priority", "resume", "adapter_id",
                 "state_snaps")

    def __init__(self, rid, blocks, worst_blocks, cache_len, last_token,
                 max_new, history=None, prompt=None, pend_pos=None):
        self.admit_t = time.monotonic()   # request-span start (trace)
        self.handoff = False    # prefill-role slot parked for export
        self.priority = 0       # scheduling class (preemptive sched)
        self.resume = None      # (last_token, n_emitted) to restore
        #                         when a recompute re-prefill completes
        self.adapter_id = None  # pinned LoRA adapter (None = base)
        self.state_snaps = {}   # block boundary (tokens) -> slot-state
        #                         snapshot taken where a chunk ended on it
        self.rid = rid
        self.blocks = blocks            # allocated block ids (ordered)
        self.worst_blocks = worst_blocks
        self.cache_len = cache_len      # valid cache positions
        self.last_token = last_token
        self.n_emitted = 1              # prefill emitted the first token
        self.max_new = max_new
        # prompt + emitted tokens: position p of the cache holds
        # history[p] for p < cache_len — the n-gram drafter's lookup
        # corpus AND the token stream retirement hashes full blocks of
        self.history = history
        self.prompt = prompt            # int32 prompt (pending chunks)
        self.pend_pos = pend_pos        # next chunk start; None = done


# a slot as the tick about to be packed must see it (``_ahead``)
_Ahead = namedtuple("_Ahead", "pend_pos cache_len rowless tok")


class _Pipe:
    """One dispatched-but-uncommitted ragged tick: the executable's
    output futures plus the host-side row layout the commit half
    needs. ``left`` holds the slots that gave their seat back between
    this tick's dispatch and its commit (``_release_spent``): the
    commit still owes each its last token."""
    __slots__ = ("outs", "active", "given", "n_pending", "q_lens",
                 "rid_of", "pend_pos0", "t_tick", "t_l0", "left",
                 "tick", "dispatch", "span_args", "moe")

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw.get(k))


class ServingEngine:
    """Continuous-batching serving over a causal-LM with the paged-KV
    protocol (``init_paged_caches`` + ``block_tables``/``cache_lens``
    forward kwargs — Llama/Qwen2/GPT families; a layer's cache is a
    tuple of pool arrays, the ``(k, v)`` pair or a latent (MLA)
    cache's one array, and the engine reaches them only through
    ``ops/paged_cache``'s arity-free walkers).

    Usage::

        engine = ServingEngine(model, ServingConfig(num_slots=8))
        rid = engine.submit([1, 2, 3], max_new_tokens=32)
        results = engine.run()          # {rid: np.ndarray of tokens}

    or stream: pass ``stream_callback=lambda rid, tok: ...`` and drive
    ``step()`` yourself.
    """

    def __init__(self, model, config: Optional[ServingConfig] = None,
                 stream_callback: Optional[Callable] = None,
                 draft_model=None, spec_heads=None):
        from ..generation import GenerationMixin, _select_token
        from ..generation import speculative as _spec
        if not isinstance(model, GenerationMixin):
            raise TypeError(
                f"{type(model).__name__} does not support generation "
                "(needs the KV-cache protocol)")
        if not hasattr(model, "init_paged_caches"):
            raise TypeError(
                f"{type(model).__name__} does not implement "
                "init_paged_caches (paged-KV serving)")
        cfg = config or ServingConfig()
        if cfg.decode_strategy not in ("greedy_search", "sampling"):
            raise NotImplementedError(
                f"serving decode_strategy {cfg.decode_strategy!r}; "
                "supported: greedy_search, sampling")
        gamma = int(cfg.num_speculative_tokens or 0)
        if gamma < 0:
            raise ValueError(
                f"num_speculative_tokens must be >= 0, got {gamma}")
        # a model whose layers keep slot state (ops/paged_cache
        # ``SlotState``: a recurrence's state a SLOT, beside the paged
        # KV) says so (and is handed the engine's slot count when its
        # caches are built: ``_init_caches``)
        if gamma and getattr(model, "paged_slot_state", False):
            raise NotImplementedError(
                "speculative serving over slot state: a rejected draft "
                "would have to roll a layer's recurrent state back, and "
                "only the block tables can be trimmed "
                f"({type(model).__name__} declares paged_slot_state)")
        if draft_model is not None and \
                (gamma == 0 or cfg.drafter != "model"):
            # silently drafting via n-gram while the caller handed over
            # a draft model would measure the wrong configuration
            raise ValueError(
                "draft_model requires num_speculative_tokens > 0 and "
                "drafter='model' "
                f"(got gamma={gamma}, drafter={cfg.drafter!r})")
        # -- tree-structured speculation: validate BEFORE the kill
        # switches (misconfiguration must raise regardless of env),
        # resolve AFTER them (a killed tree is exactly the linear
        # engine, heads downgrading to the ngram drafter)
        spec_tree = getattr(cfg, "spec_tree", None)
        if spec_tree is not None:
            spec_tree = tuple(int(p) for p in spec_tree)
            if len(spec_tree) != gamma or gamma == 0:
                raise ValueError(
                    f"spec_tree has {len(spec_tree)} non-root nodes; "
                    "must equal num_speculative_tokens="
                    f"{gamma} (> 0)")
            _pa.tree_ancestor_bits(spec_tree)   # topology/depth check
            if cfg.drafter == "model":
                raise ValueError(
                    "spec_tree drafts via drafter='ngram' (top-k "
                    "prompt lookup) or drafter='heads' (draft-head "
                    "projections); a separate draft_model proposes "
                    "one chain, not a tree")
        if cfg.drafter == "heads" and spec_tree is None:
            raise ValueError(
                "drafter='heads' requires spec_tree (the draft heads "
                "fill a token tree)")
        if not _spec.speculative_enabled():  # PADDLE_TPU_SPECULATIVE=0
            gamma = 0
            draft_model = None
            spec_tree = None
        if spec_tree is not None and not _spec.spec_tree_enabled():
            spec_tree = None                 # PADDLE_TPU_SPEC_TREE=0
        drafter = str(cfg.drafter)
        if drafter == "heads" and spec_tree is None:
            drafter = "ngram"   # killed tree -> the linear ngram path
        self._spec_tree = spec_tree
        self._drafter = drafter
        if spec_tree is not None:
            # static per-engine layout: node depths, the leaf (chain)
            # each node feeds, chain count, max depth (= head count)
            (self._tree_depth, self._tree_leaf_of, self._tree_chains,
             self._tree_max_depth) = _spec.tree_chain_layout(spec_tree)
        self._role = str(getattr(cfg, "role", "both") or "both")
        if self._role == "prefill" and gamma:
            raise NotImplementedError(
                "a prefill-role engine never decodes, so speculative "
                "decoding (num_speculative_tokens > 0) has nothing to "
                "verify there — put the draft on the decode replicas")
        if gamma:
            if cfg.drafter not in ("ngram", "model", "heads"):
                raise ValueError(f"drafter {cfg.drafter!r}; "
                                 "supported: ngram, model, heads")
            if cfg.drafter == "model" and draft_model is None:
                raise ValueError(
                    "drafter='model' requires a draft_model")
            reason = _spec.spec_exclusion_reason(model)
            if reason is not None:
                raise NotImplementedError(
                    f"speculative serving unavailable: {reason}")
            if cfg.drafter == "model":
                reason = _spec.draft_exclusion_reason(model, draft_model)
                if reason is not None:
                    raise NotImplementedError(
                        f"draft model unusable: {reason}")
        # -- MoE admission gate ---------------------------------------
        # Dropless MoE serves: decode-time routing is tiny-batch and
        # per-row, so packed serving rows (other slots' tokens, verify
        # windows, prefill chunks) cannot perturb a row's expert
        # outputs. Capacity routing stays excluded — the batched rows
        # WOULD compete for each expert's capacity slots, making
        # logits depend on batch composition (the bucketing/spec
        # exclusion reasoning of PRs 3-4, applied to the engine
        # itself).
        for mdl, who in ((model, "model"), (draft_model, "draft model")):
            c = getattr(mdl, "config", None) if mdl is not None else None
            if _num_experts(c) and not getattr(c, "dropless", False):
                raise NotImplementedError(
                    f"capacity-routed MoE {who} cannot serve: batched "
                    "slots' tokens would compete for expert capacity, "
                    "so logits would depend on batch composition. Set "
                    "config.dropless=True (grouped dropless routing) "
                    "to serve this model.")
        cfgm = getattr(model, "config", None)
        self._moe = bool(_num_experts(cfgm))
        # stats()['moe_fused_gmm'] reports whether the fused kernel
        # ACTUALLY traced into one of this engine's executables
        # (captured in _aot_compile from the MOE_STATS kernel stamp) —
        # env kill switch, config twin, backend and shape gates all
        # fold in by construction
        self._moe_fused_traced = False
        self._moe_tap_on = bool(getattr(cfg, "moe_telemetry", True)) \
            and os.environ.get("PADDLE_TPU_MOE_TELEMETRY", "1") != "0"
        max_pos = getattr(getattr(model, "config", None),
                          "max_position_embeddings", None)
        if max_pos is not None and cfg.max_model_len + gamma > max_pos:
            raise ValueError(
                f"max_model_len ({cfg.max_model_len})"
                + (f" + speculative window ({gamma})" if gamma else "")
                + f" exceeds the model's max_position_embeddings "
                f"({max_pos})")
        self.model = model
        self.config = cfg
        self._stream = stream_callback
        model.eval()

        # -- tensor parallelism -----------------------------------------
        tp = int(getattr(cfg, "tp_degree", 1) or 1)
        if tp > 1 and os.environ.get("PADDLE_TPU_SERVE_TP", "1") == "0":
            tp = 1          # kill switch: single-device path, bit-for-bit
        self._tp = tp
        self._mesh = self._build_tp_mesh(model, draft_model, tp) \
            if tp > 1 else None
        self._pool_sharding = _pc.pool_sharding(self._mesh) \
            if self._mesh is not None else None
        self._census = {}           # exec name -> jaxpr collective rows
        self._tp_step_bytes = 0     # explicit mp payload of one decode
        self._n_tp_bytes = 0

        from ..jit import _LayerBinder
        binder = _LayerBinder(model)
        self._params = self._shard_params(binder) \
            if self._mesh is not None else binder.param_arrays()
        self._model_step = model._build_model_step(
            binder, binder.buffer_arrays())
        # -- Medusa-style draft heads (drafter="heads") ---------------
        # one [hidden, vocab] projection per tree depth over the
        # target's final hidden state; node k+1 (depth d, sibling rank
        # j under its parent) takes the (j+1)-th top token of head
        # d-1's logits. The head weights ride WITH the target params —
        # never a separate model — which is what lifts the disagg
        # draft-spec exclusion for head-drafted trees.
        self._heads = None
        self._model_step_h = None
        self._slot_props = {}    # slot -> cached next-tick proposal [g]
        if self._spec_tree is not None and self._drafter == "heads":
            import inspect
            if "return_hidden" not in inspect.signature(
                    type(model).forward).parameters:
                raise NotImplementedError(
                    f"{type(model).__name__} does not expose "
                    "forward(return_hidden=...) — draft heads need "
                    "the target's final hidden state")
            hdim = int(cfgm.hidden_size)
            vocab = int(cfgm.vocab_size)
            n_heads = self._tree_max_depth
            sib, cnt = [], {}
            for p in self._spec_tree:
                r = cnt.get(p, 0)
                cnt[p] = r + 1
                sib.append(r)
            self._tree_sib = tuple(sib)
            self._tree_kmax = max(sib) + 1
            if spec_heads is not None:
                ws = [np.asarray(w, np.float32) for w in spec_heads]
                if len(ws) != n_heads or any(
                        w.shape != (hdim, vocab) for w in ws):
                    raise ValueError(
                        f"spec_heads must be {n_heads} arrays of "
                        f"shape ({hdim}, {vocab}) (one per tree "
                        "depth)")
            else:
                # deterministic random calibration: every engine (and
                # every cluster replica) derives the SAME weights from
                # the fixed seed, so head-drafted trees stay
                # token-exact across colocated and disaggregated
                # deployments with zero weight shipping
                ws = [np.random.default_rng(0x5EED + d)
                      .standard_normal((hdim, vocab))
                      .astype(np.float32) * 0.02
                      for d in range(n_heads)]
            self._heads = self._dev(np.stack(ws))
            self._model_step_h = model._build_model_step(
                binder, binder.buffer_arrays(), want_hidden=True)
        elif spec_heads is not None and cfg.drafter != "heads":
            raise ValueError(
                "spec_heads requires drafter='heads' (and spec_tree)")
        do_sample = cfg.decode_strategy == "sampling"
        self._do_sample = do_sample
        self._select_token = _select_token
        # -- in-executable sampling head with per-SLOT knobs ----------
        # (temperature, top_k, top_p) ride as a [num_slots, 3] device
        # tensor every tick instead of Python floats baked into the
        # trace: a new sampling config (engine-wide OR per-request via
        # submit()) is DATA — same executable, zero recompiles. Greedy
        # engines carry the operand untouched (argmax never reads it).
        self._samp_default = np.asarray(
            [float(cfg.temperature), float(cfg.top_k),
             float(cfg.top_p)], np.float32)
        self._slot_samp = np.tile(self._samp_default,
                                  (cfg.num_slots, 1))
        self._samp_dev = None           # device mirror of _slot_samp
        # -- mega-kernelized decode tick ------------------------------
        # resolved ONCE at construction (config flag + the
        # PADDLE_TPU_FUSED_DECODE env twin); GSPMD TP traces keep the
        # unfused projections — an opaque pallas_call cannot be
        # partitioned, the moe_gmm gate applied here
        from ..ops.pallas import decode_fused as _df
        self._df = _df
        self._fused_mode = _df.resolve_fused_mode(
            getattr(cfg, "fused_decode", True))
        if self._mesh is not None:
            # GSPMD TP traces keep the unfused projections (an opaque
            # pallas_call cannot be partitioned — fused_decode_mode
            # would report "off" inside serving_tp_scope anyway);
            # resolving to None HERE keeps stats()['fused_decode']
            # honest on TP engines
            self._fused_mode = None
        self._kcensus = {}          # exec name -> kernel census rows
        self._cmap = {}             # exec name -> component map rows
        # JAX's persistent compile cache leaves op metadata out of its
        # key, so a cache another version filled (other scope names, the
        # same program: a shared JAX_COMPILATION_CACHE_DIR) would hand
        # back ITS text and the map would name scopes this program never
        # entered. Counted into the key, set once and left on; the
        # compiled program is the same either way
        jax.config.update(
            "jax_compilation_cache_include_metadata_in_key", True)

        self._bs = int(cfg.block_size)
        # +gamma: the speculative verify window may overhang the last
        # emitted token by up to gamma written-then-rolled-back slots
        self._gamma = gamma
        self._ngram_max = int(cfg.spec_ngram_max)
        self._prefix_on = bool(cfg.enable_prefix_cache) \
            and os.environ.get("PADDLE_TPU_PREFIX_CACHE", "1") != "0"
        self._chunk = max(1, min(int(cfg.prefill_chunk),
                                 int(cfg.max_model_len)))
        # KV-pool quantization: resolved ONCE at construction (config
        # + PADDLE_TPU_KV_INT8 env twin) — "int8" or None; raises on
        # an unsupported request before any pool is built
        self._kv_dtype = _pc.resolve_kv_cache_dtype(
            getattr(cfg, "kv_cache_dtype", None))
        # content-hash chain seed: hashes are only comparable within
        # one (model architecture, config, cache layout) world
        self._fp = self._model_fingerprint(model)
        self._mb = _pc.blocks_for(cfg.max_model_len + gamma, self._bs)
        nb = (1 + cfg.num_slots * self._mb) if cfg.num_blocks is None \
            else int(cfg.num_blocks)
        self._alloc = _pc.BlockAllocator(nb)
        # -- async tick pipeline (docs/OPS.md "Async tick pipeline") --
        # resolved ONCE at construction: every tick without
        # speculation is launched before the last one's tokens are
        # fetched, unless ``async_depth=0`` or the
        # PADDLE_TPU_ASYNC_TICK=0 kill switch (which beats an explicit
        # depth) keeps the blocking loop as the reference. A
        # speculating engine's proposals need the committed history:
        # it blocks whatever the depth says.
        _ad = getattr(cfg, "async_depth", None)
        if os.environ.get("PADDLE_TPU_ASYNC_TICK", "") == "0":
            _ad = 0
        self._ahead_on = (_ad is None or int(_ad) >= 1) and not gamma
        self._async_depth = 1 if self._ahead_on else 0
        self._pipe = None               # in-flight (uncommitted) tick
        self._commit_due = None         # commit half of a split tick
        self._flushed = []              # tokens a drain committed
        self._n_pipe_flushes = 0
        self._last_dispatch_t = None    # host-gap digest anchor
        self._split_t0 = 0.0            # cluster phase-split health
        self._split_c0 = 0              # bracket (tick_dispatch)
        # -- the tick's packed row layout -----------------------------
        want = cfg.ragged_prefill_rows
        self._prefill_rows = max(1, min(
            int(self._chunk if want is None else want),
            int(cfg.max_model_len)))
        # static packed width: every active slot's decode/verify rows
        # plus one tick's prefill row budget always fit
        self._rows = cfg.num_slots * (gamma + 1) + self._prefill_rows
        # the rows the LM head runs over: the ones the tick samples
        # from (a slot's continuation row, and speculating its verify
        # window), gathered from the hidden state before the head
        self._head_rows = cfg.num_slots * (gamma + 2 if gamma else 1)
        # static per-slot row ceiling (the rows of a slot the ragged
        # kernel attends)
        self._wmax = max(gamma + 1,
                         min(self._chunk, self._prefill_rows))
        # pad rows park at a position past every table's reach — the
        # write null-routes and the rope/position gathers clamp
        self._overflow = self._mb * self._bs
        self._ragged_exec = None
        self._ragged_draft_exec = None
        self._pools = self._init_caches(model, nb)
        # -- slot state beside the paged KV (docs/OPS.md "Slot state")
        self._stateful = any(map(_pc.is_slot_state, self._pools))
        self._state_bytes = _pc.state_bytes(self._pools)
        # snapshots of a slot's state where a prefill chunk ended on a
        # block boundary: with the slot until its blocks are published,
        # then here under the boundary block's chain hash, evicted with
        # the block. The table and the slots' unpublished snapshots
        # together are budgeted in BYTES, an eighth of the KV pool's: a
        # snapshot may be a few KB (a short convolution's rows) or tens
        # of MB (a matrix state a head). Short of budget a slot keeps
        # its deepest boundary only and the table's oldest entry goes
        # first; a snapshot larger than the budget is never taken
        self._state_snaps = OrderedDict()
        self._snap_nbytes = _pc.snapshot_nbytes(self._pools)
        self._state_snap_budget = _pc.pool_bytes(self._pools) // 8
        self._n_state_snaps_dropped = 0
        # what the tick span calls a scanned state's one-row seats and
        # chunked rows (the model's ``paged_scan_state``), if it has one
        self._scan_state = getattr(model, "paged_scan_state", None)
        self._snap_exec = None          # export_slot_state
        self._snap_import_exec = None   # import_slot_state
        self._n_state_started = 0       # seats a tick began from zeros
        self._n_state_snaps = 0
        self._n_state_snap_hits = 0
        self._n_prefix_cut = 0          # hit tokens cut for want of state
        self._attn_geometry = self._ragged_attn_geometry(model)
        # connections a row passes a tick where the residual is several
        # streams (the model's ``hc_connections``): the tick span's
        # ``hc_rows`` is that many a live row
        self._hc_connections = int(getattr(model, "hc_connections", 0))
        # what else the model counts on the tick span, from the slots'
        # ``q_lens`` and base lengths (the model's ``tick_counts``)
        self._tick_counts = getattr(model, "tick_counts", None)
        self._draft_model = draft_model \
            if gamma and cfg.drafter == "model" else None
        if self._draft_model is not None:
            self._draft_model.eval()
            dbinder = _LayerBinder(self._draft_model)
            self._dparams = self._shard_params(dbinder) \
                if self._mesh is not None else dbinder.param_arrays()
            self._draft_step = self._draft_model._build_model_step(
                dbinder, dbinder.buffer_arrays())
            self._dpools = self._init_caches(self._draft_model, nb)
        self._tables = np.zeros((cfg.num_slots, self._mb), np.int32)
        self._slots: List[Optional[_Slot]] = [None] * cfg.num_slots
        self._reserved = 0              # blocks promised to active slots
        self._queue: deque = deque()
        self._results: Dict[int, list] = {}
        self._done: Dict[int, np.ndarray] = {}
        self._next_rid = 0
        self._eos = -1 if cfg.eos_token_id is None \
            else int(cfg.eos_token_id)
        self._pad = int(cfg.pad_token_id)
        # the sampling key is EXPLICITLY replicated across shards: every
        # shard consumes the identical key against the identical
        # gathered logits, so TP sampling is the same draw as
        # single-device (never split per-shard — that would silently
        # sample a different token on every shard)
        self._key = self._dev(jax.random.PRNGKey(int(cfg.seed)))
        self._tables_dev = None         # device mirror of _tables
        # the last tick's per-slot tokens as it left them on the
        # device: the next tick's operand (zeros until one has run —
        # read only where a tick is in flight)
        self._prev_tok = self._dev(np.zeros(cfg.num_slots, np.int32))
        self._cow_exec = None           # copy-on-write block duplicate
        self._draft_cow_exec = None
        # disaggregated prefill -> decode handoff (role="prefill"
        # parks completed prompts here; export/import are each ONE
        # fixed-width [mb] executable, so steady state stays
        # recompile-free on both sides of the transfer)
        self._handoff_ready: List[int] = []     # slot indices parked
        # transfer width: the payload only ever carries PROMPT blocks,
        # so it is sized by max_model_len alone — NOT _mb, whose +gamma
        # headroom differs between a (spec-free) prefill engine and a
        # speculating decode replica and would shape-mismatch the
        # import executable
        self._mb_xfer = _pc.blocks_for(cfg.max_model_len, self._bs)
        self._export_exec = None
        self._import_exec = None
        self._n_handoffs = 0            # prefills exported (this engine)
        self._n_blocks_exported = 0
        self._n_blocks_imported = 0
        # -- SLO-aware preemptive scheduling + host-DRAM KV tier ------
        # resolved ONCE at construction: config flag AND the
        # PADDLE_TPU_PREEMPT env twin (0 = kill switch beating an
        # explicit True — the worst-case FIFO scheduler returns
        # bit-for-bit); a prefill-role engine never decodes so it has
        # nothing to preempt
        self._preempt_on = bool(getattr(cfg, "enable_preemption",
                                        True)) \
            and os.environ.get("PADDLE_TPU_PREEMPT", "1") != "0" \
            and self._role != "prefill"
        wm = getattr(cfg, "admission_watermark_blocks", None)
        self._watermark = int(cfg.num_slots if wm is None else wm)
        self._resume_policy = str(getattr(cfg, "preempt_resume",
                                          "auto"))
        self._shed_depth = getattr(cfg, "shed_queue_depth", None)
        self._default_qwait = getattr(cfg, "max_queue_wait_ms", None)
        tier_bytes = int(getattr(cfg, "host_kv_tier_bytes", 0) or 0)
        self._host_tier = _pc.HostKVTier(tier_bytes) \
            if self._preempt_on and tier_bytes > 0 else None
        if self._host_tier is not None and self._prefix_on:
            # LRU-evicted published blocks spill their bytes to host
            # instead of dying — a later prefix hit restores them
            # through the fixed-width import scatter
            self._alloc.on_evict = self._spill_evicted
        if self._stateful:
            # a block of a stateful model cannot be restored without
            # the state at its boundary: the tier is never offered one,
            # and the block's snapshot dies with it
            self._alloc.on_evict = self._evict_stateful
        # the eviction spill's own one-block gather (never crosses
        # engines, so not _mb_xfer wide); built by warm_migration() or
        # the first eviction
        self._spill_exec = None
        self._spill_layout = None
        self._spill_nbytes = 0          # one block, every layer
        self._spill_pending = []        # (tier key, device arrays):
        #                                 launched, not yet on the host
        self._n_spill_bytes = 0         # bytes the spills brought over
        self._n_preempt = 0             # victim slots preempted
        self._n_spilled = 0             # KV blocks spilled to host
        self._n_restored = 0            # KV blocks restored from host
        self._n_swap_resumes = 0
        self._n_recompute_resumes = 0
        self._n_shed = 0
        self._n_timeout = 0
        self._n_cancelled = 0           # in-flight cancels
        # live-session migration (elastic fleet: scale-down drain /
        # cluster rebalancing — ISSUE 19)
        self._n_migrated_out = 0        # live sessions exported
        self._n_migrated_in = 0         # live sessions imported
        # recompute-vs-swap cost model, measured online: EMA of chunk-
        # prefill row throughput (rows/s — what a recompute resume
        # pays per cached token) and of host-transfer bandwidth
        # (bytes/s over the export/import executables — what a swap
        # pays per payload byte)
        self._prefill_rows_s = 0.0
        self._xfer_bytes_s = 0.0
        # per-engine counts (the monitor counters below are process-
        # global telemetry shared by every engine; stats() must report
        # THIS engine)
        self._n_decode_compiles = 0
        self._n_exec_compiled = 0       # EVERY executable this engine
        #                                 built (tick, cow, export,
        #                                 import, spill; target AND draft)
        # snapshot of the op-layer's process-wide fallback counter:
        # stats() reports the DELTA, i.e. fallback events observed
        # since this engine was created, not another engine's history
        self._fallbacks0 = sum(_pa.kernel_fallback_counts().values())
        self._n_decode_steps = 0
        self._n_tokens = 0
        self._n_completed = 0
        self._n_prefill_chunks = 0
        self._n_prefix_blocks = 0       # cached blocks mapped into slots
        self._n_prefix_tokens = 0       # prompt tokens NOT re-prefilled
        self._n_prompt_tokens = 0       # prompt tokens admitted
        self._n_cow = 0
        self._n_evictions_seen = 0
        self._n_spec_proposed = 0
        self._n_spec_accepted = 0
        self._n_spec_verifies = 0       # per-slot verify windows
        self._n_spec_emitted = 0
        # -- batched multi-LoRA serving -------------------------------
        # resolved ONCE at construction: config (lora_rank > 0) AND
        # the PADDLE_TPU_LORA env kill switch (0 beating an explicit
        # rank — the base engine returns bit-for-bit: no module is
        # tagged, the tick executable takes no extra operand and the
        # slots pack carries no extra row, so the jaxpr is identical)
        lora_rank = int(getattr(cfg, "lora_rank", 0) or 0)
        self._lora_on = lora_rank > 0 and _lora.lora_enabled()
        self._lora_pool: Optional[_lora.AdapterPool] = None
        self._lora_dev = None           # device image of the stacks
        self._lora_dev_version = -1     # pool.version the image holds
        self._lora_swaps_seen = 0       # counter-delta bookkeeping
        # per-slot RESIDENT STACK ROW (not adapter id; 0 = the null
        # all-zero adapter) — rides the slots pack as one more int32
        # row next to the sampling tensor, so adapter churn is a VALUE
        # change at a fixed shape: zero steady-state recompiles
        self._slot_adapter = np.zeros(cfg.num_slots, np.int64)
        if self._lora_on:
            specs = _lora.tag_modules(model, str(getattr(
                cfg, "lora_targets", "attn")))
            if not specs:
                raise NotImplementedError(
                    "no LoRA-taggable projection layers found on this "
                    "model (expected q/k/v/o | qkv/out projections "
                    "named per Llama/GPT idiom)")
            self._lora_pool = _lora.AdapterPool(
                specs, lora_rank,
                alpha=getattr(cfg, "lora_alpha", None),
                max_resident=int(getattr(cfg, "max_adapters", 8)),
                quant=bool(getattr(cfg, "lora_quant", False)))

        # -- telemetry ------------------------------------------------
        self._m_occupancy = monitor.gauge(
            "serving_slot_occupancy", "active serving slots")
        self._m_util = monitor.histogram(
            "serving_batch_utilization",
            "active slots / num_slots per decode step",
            buckets=(0.125, 0.25, 0.375, 0.5, 0.625, 0.75, 0.875, 1.0))
        self._m_queue_wait = monitor.histogram(
            "serving_queue_wait_ms",
            "submit -> queue-exit wait, labeled by outcome (admitted |"
            " cancelled | rejected | shutdown) — EVERY exit path "
            "observes, so the distribution can't survivor-bias toward "
            "admitted requests",
            labels=("outcome",))
        self._m_tokens = monitor.counter(
            "serving_tokens_total", "tokens generated (all requests)")
        self._m_steps = monitor.counter(
            "serving_decode_steps", "batched decode steps executed")
        self._m_decode_compiles = monitor.counter(
            "serving_decode_compiles",
            "decode-step compilations (steady state: stays at 1)")
        self._m_completed = monitor.counter(
            "serving_requests_completed", "requests fully served")
        self._m_prefix_blocks = monitor.counter(
            "serving_prefix_blocks_reused",
            "cached KV blocks mapped into admitted slots")
        self._m_prefix_tokens = monitor.counter(
            "serving_prefix_tokens_reused",
            "prompt tokens served from the prefix cache (not "
            "re-prefilled)")
        self._m_cow = monitor.counter(
            "serving_cow_copies",
            "copy-on-write block duplications (shared block appended "
            "into)")
        self._m_evict = monitor.counter(
            "serving_cache_evictions",
            "cached blocks evicted under memory pressure (LRU)")
        self._m_hit_rate = monitor.gauge(
            "serving_prefix_hit_rate",
            "cumulative reused / admitted prompt tokens")
        self._m_kv_transfer = monitor.counter(
            "serving_kv_blocks_transferred",
            "KV blocks streamed between engine pools (disaggregated "
            "prefill -> decode handoffs; counted at import, data + "
            "scales travel together on int8 pools)")
        # -- preemption + host-tier telemetry (registered
        # unconditionally so stats()/JSONL always carry the keys —
        # FIFO/killed engines report zeros, dashboards never KeyError
        # across a mixed or rolled-back fleet)
        self._m_preempt = monitor.counter(
            "serving_preemptions",
            "victim slots preempted (blocks published + spilled, slot "
            "freed, request re-enqueued at the front of its priority "
            "class)")
        self._m_spill = monitor.counter(
            "serving_kv_blocks_spilled",
            "KV blocks spilled to the host-DRAM tier (preempted "
            "victims' live blocks + LRU-evicted published blocks; "
            "int8 data + scales travel together)")
        self._m_restore = monitor.counter(
            "serving_kv_blocks_restored",
            "KV blocks restored from the host-DRAM tier (swap resumes "
            "+ prefix hits on spilled published blocks)")
        self._m_host_bytes = monitor.gauge(
            "serving_host_tier_bytes",
            "bytes resident in the host-DRAM KV tier (spilled block "
            "payloads awaiting restore or LRU eviction)")
        # -- multi-LoRA telemetry (registered unconditionally so
        # stats()/JSONL always carry the keys — non-LoRA and
        # PADDLE_TPU_LORA=0 engines report zeros, dashboards never
        # KeyError across a mixed or rolled-back fleet)
        self._m_lora_resident = monitor.gauge(
            "serving_lora_adapters_resident",
            "LoRA adapters resident in the device stacks (excludes "
            "the always-present null adapter)")
        self._m_lora_swaps = monitor.counter(
            "serving_lora_adapter_swaps",
            "adapter loads that evicted an unpinned resident adapter "
            "to make room (LRU churn against the max_adapters budget)")
        self._m_lora_host = monitor.gauge(
            "serving_lora_host_tier_bytes",
            "bytes of registered adapters NOT currently resident on "
            "device (host-DRAM registry tier awaiting an LRU swap-in)")
        monitor.info(
            "serving_tp_degree",
            "tensor-parallel degree of the most recent engine").set(
            self._tp)
        self._m_tp_bytes = monitor.counter(
            "serving_tp_collective_bytes",
            "explicit cross-shard collective payload executed per "
            "engine step (per-shard bytes, jaxpr census: decode OR "
            "draft-loop + verify; GSPMD-inserted collectives not "
            "included)")
        self._m_tp_pool = monitor.gauge(
            "serving_tp_pool_bytes_per_shard",
            "KV block-pool bytes each shard holds (kv_head slice)")
        pool_bytes = _pc.pool_bytes(self._pools)
        target_pool_bytes = pool_bytes
        # a latent (MLA) cache is one array a layer
        paged0 = _pc.first_paged(self._pools)
        self._latent_pool_bytes = pool_bytes \
            if len(paged0) == 1 else 0
        if self._draft_model is not None:
            pool_bytes += _pc.pool_bytes(self._dpools)
        self._pool_bytes_per_shard = pool_bytes // self._tp
        self._m_tp_pool.set(self._pool_bytes_per_shard)
        # -- KV-pool telemetry (quantization observability) -----------
        # registered unconditionally, so stats()/JSONL always carry the
        # keys — fp engines report the fp numbers, consumers never
        # KeyError on a mixed or rolled-back fleet
        self._kv_dtype_name = "int8" if self._kv_dtype == "int8" \
            else str(jnp.dtype(paged0[0].dtype))
        self._kv_pool_bytes = pool_bytes            # data + scales
        # bytes ONE cached position costs across all target layers
        # (int8: data + scale rows) — the analytic per-step KV read
        # gauge multiplies this by the tick's attended positions
        self._kv_pos_bytes = target_pool_bytes / float(
            paged0[0].shape[0] * self._bs)
        self._kv_step_bytes_last = 0
        monitor.info(
            "serving_kv_cache_dtype",
            "KV block-pool storage dtype of the most recent engine "
            "(int8 = quantized pool + absmax scales)").set(
            self._kv_dtype_name)
        self._m_kv_pool = monitor.gauge(
            "serving_kv_pool_bytes",
            "total KV block-pool bytes (all layers + scale pools, "
            "target and draft models, every shard)")
        self._m_kv_pool.set(pool_bytes)
        self._m_kv_step = monitor.gauge(
            "serving_kv_bytes_per_step",
            "analytic target-pool KV bytes the last engine tick's "
            "attention streamed from HBM (attended positions x bytes "
            "per cached position; int8 pools count data + scales)")
        # -- decode-tick fusion observability -------------------------
        # the headline "kernel count per decode layer down" metric is
        # MEASURED, not asserted: every _aot_compile runs
        # monitor.kernel_census over the compiled HLO + traced jaxpr,
        # and this gauge tracks the tick executable's kernel count
        self._m_kernels = monitor.gauge(
            "serving_kernels_per_tick",
            "kernel count of the engine's compiled tick executable "
            "(optimized-HLO entry instructions — fusions, dots, "
            "custom calls; the decode-tick fusion headline metric)")
        # -- per-tick roofline attribution (ISSUE 15 layer 2) ---------
        # static half: every _aot_compile captures the executable's
        # cost_analysis FLOPs + bytes accessed (the "Operator Fusion
        # in XLA" accounting, live); measured half: each step path
        # clocks its launch->sync wall time into a per-executable EMA.
        # Fused they give per-executable MFU, HBM-bandwidth
        # utilization and a compute-vs-bandwidth-bound classification
        # (stats()['roofline']). Pure host accounting, independent of
        # the trace kill switch — like the SLO digests.
        self._exec_cost = {}        # exec name -> cost_analysis dict
        self._step_time = {}        # exec name -> wall-seconds EMA
        self._step_ticks = {}       # exec name -> timed launches
        # the chip's published peaks (monitor.DEVICE_PEAKS); None on
        # the CPU backend, where every utilization below stays None —
        # a CPU run has no device peak to be a share of
        self._peaks = monitor.device_peaks()
        self._m_mfu = monitor.gauge(
            "serving_step_mfu",
            "per-tick model FLOPs utilization of the tick executable "
            "(cost_analysis FLOPs / measured launch->sync time / chip "
            "peak FLOPs; unset off the chip)")
        self._m_bw_util = monitor.gauge(
            "serving_hbm_bw_util",
            "per-tick HBM-bandwidth utilization of the tick "
            "executable (cost_analysis bytes accessed / measured "
            "launch->sync time / chip peak HBM bytes/s; unset off "
            "the chip)")
        # -- on-demand profiling windows (ISSUE 15 layer 3) -----------
        # profile(n_ticks) arms a bounded jax.profiler capture around
        # the next N ticks; PADDLE_TPU_TRACE=0 keeps it inert
        self._prof = _tracing.ProfilerWindow()
        # MoE routing telemetry: per-expert load fractions + routing
        # entropy of every dispatch the engine's executables run,
        # observed at DECODE time through the trace-armed tap in
        # distributed/moe.py (one tiny debug callback per sparse layer
        # per tick). Metrics registered unconditionally so stats() and
        # the JSONL export always carry the keys.
        self._m_moe_load = monitor.histogram(
            "serving_moe_expert_load",
            "per-expert share of routed (token, slot) pairs per "
            "dispatch (0 = expert idle this step)",
            buckets=(0.001, 0.01, 0.05, 0.1, 0.2, 0.4, 0.6, 0.8, 1.0))
        self._m_moe_entropy = monitor.gauge(
            "serving_moe_routing_entropy",
            "decode-time routing entropy, normalized to [0, 1] by "
            "log(num_experts) (1 = perfectly balanced routing)")
        self._moe_ent_last = 0.0
        self._moe_load_max_last = 0.0
        self._n_moe_dispatches = 0
        # a chip's expert-parallel share (distributed/moe.
        # moe_share_dispatch_combine): live rows routed and live pairs
        # that fell on experts held here, summed over expert layers
        # and ticks; the grouped-matmul kernel the tick traced
        self._n_moe_rows = 0
        self._n_moe_pairs_local = 0
        self._moe_gmm_kernel = None
        self._moe_share_out = False     # the tick returns share counts
        # -- request-lifecycle tracing + SLO latency digests ----------
        # One Tracer per engine (one trace-viewer pid): tid 0 is the
        # engine tick timeline, tid 1+i slot i's request timeline, the
        # last tid the admission queue. PADDLE_TPU_TRACE=0 leaves
        # self._trace None and every call site skips — the killed hot
        # path runs zero tracer instructions (tracing is host-only
        # code either way, so executables and outputs are identical).
        self._engine_id = next(_ENGINE_IDS)
        self._tid_queue = cfg.num_slots + 1
        self._trace = None
        if _tracing.tracing_enabled():
            # (a span a decoding slot a tick: the ring grows with them)
            tr = _tracing.Tracer(f"ServingEngine[{self._engine_id}]",
                                 rows=cfg.num_slots)
            tr.set_thread(0, "engine")
            for i in range(cfg.num_slots):
                tr.set_thread(1 + i, f"slot {i}")
            tr.set_thread(self._tid_queue, "queue")
            self._trace = tr
        # what the tick-phase spans carry: the ordinal of the tick in
        # progress (a ``spill`` reads it from wherever it is called)
        # and the blocks that growth has allocated so far
        self._tick_ord = 0
        self._n_grown = 0
        # always-on per-engine SLO digests (P², bounded memory) —
        # independent of the trace kill switch; surfaced as stats()
        # keys, the serving_*_ms quantile gauges, and the JSONL/prom
        # exports those gauges ride
        self._d_ttft = LatencyDigest()
        self._d_itl = LatencyDigest()
        self._d_queue = LatencyDigest()
        self._d_e2e = LatencyDigest()
        # tokens emitted per slot verify window, as a P² digest —
        # unconditional (a non-speculative engine just reports a
        # zeroed summary) so stats()['spec_accept_len'] and the
        # serving_spec_accept_len gauge are always present
        self._d_accept = LatencyDigest()
        # dispatch -> dispatch host time, as a P² digest —
        # unconditional (blocking engines observe too: their gap
        # includes the token fetch + commit bookkeeping, which is
        # exactly what dispatching ahead moves off the critical path), so
        # stats()['host_gap_ms'] and the serving_host_gap_ms gauge are
        # always present
        self._d_host_gap = LatencyDigest()
        self._m_host_gap = monitor.gauge(
            "serving_host_gap_ms",
            "host time between consecutive tick dispatches (P2 "
            "digest; under async_depth=1 commit bookkeeping overlaps "
            "device execution, so the gap shrinks toward pure "
            "pack+launch time)", labels=("q",))
        self._m_accept = monitor.gauge(
            "serving_spec_accept_len",
            "accepted-length quantiles per slot verify window (P2 "
            "digest; tokens emitted = accepted drafts + bonus — tree "
            "and linear speculation both observe; empty on "
            "non-speculative engines)", labels=("q",))
        self._submit_t = {}     # rid -> submit monotonic (live reqs)
        self._last_emit = {}    # rid -> last token-emit monotonic
        self._m_lat = {
            "ttft": monitor.gauge(
                "serving_ttft_ms",
                "time-to-first-token quantiles (P2 digest; submit -> "
                "first streamed token)", labels=("q",)),
            "itl": monitor.gauge(
                "serving_itl_ms",
                "inter-token latency quantiles (P2 digest; gap "
                "between consecutive streamed tokens of one request)",
                labels=("q",)),
            "queue_wait": monitor.gauge(
                "serving_queue_wait_quantile_ms",
                "queue-wait quantiles (P2 digest; terminal "
                "cancelled/rejected/shutdown outcomes included)",
                labels=("q",)),
            "e2e": monitor.gauge(
                "serving_e2e_ms",
                "submit -> retirement latency quantiles (P2 digest)",
                labels=("q",)),
        }
        if gamma:
            self._m_spec_len = monitor.histogram(
                "serving_spec_accepted_len",
                "tokens emitted per slot verify window "
                "(accepted drafts + the correction/bonus token)",
                buckets=(1, 2, 3, 4, 5, 6, 7, 8, 9))
            self._m_spec_proposed = monitor.counter(
                "spec_tokens_proposed", "draft tokens proposed")
            self._m_spec_accepted = monitor.counter(
                "spec_tokens_accepted", "draft tokens accepted")
            self._m_spec_rate = monitor.gauge(
                "serving_spec_acceptance_rate",
                "accepted / proposed draft tokens (cumulative)")

        # -- fleet health engine (monitor/health.py) ------------------
        # Gauges register UNCONDITIONALLY (the always-present metrics
        # contract); the monitor itself only exists when the kill
        # switch is up. Under PADDLE_TPU_HEALTH=0 every health hook is
        # a no-op and the compiled executables are bit-identical (the
        # nonfinite probe output is always computed; only the HOST
        # fetch is gated).
        self._health_on = (bool(getattr(cfg, "health", True))
                           and os.environ.get("PADDLE_TPU_HEALTH", "1")
                           != "0")
        self._m_health = monitor.gauge(
            "serving_health_score",
            "engine health in [0,1]: 1 - severity penalties of firing "
            "alerts (page 0.5, warn 0.15)")
        self._m_burn = monitor.gauge(
            "serving_slo_burn_rate",
            "fast-window SLO burn rate (violation fraction / error "
            "budget; 1.0 = budget consumed exactly on schedule)")
        self._m_alerts = monitor.gauge(
            "serving_alerts_firing", "number of currently-firing alerts")
        self._m_health.set(1.0)
        self._nonfinite_ticks = 0
        self._nf_last = False
        self._slo_ok: Dict[int, bool] = {}
        self._h_slo_ttft = float(cfg.health_slo_ttft_ms)
        self._h_slo_itl = float(cfg.health_slo_itl_ms)
        if self._health_on:
            profile_cb = None
            if int(cfg.health_profile_ticks) > 0:
                n_prof = int(cfg.health_profile_ticks)

                def profile_cb(n=n_prof):
                    try:
                        self.profile(n)
                    except Exception:
                        pass
            self._health = _health.HealthMonitor(
                slo_target=cfg.health_slo_target,
                burn_fast_s=cfg.health_burn_fast_s,
                burn_slow_s=cfg.health_burn_slow_s,
                burn_threshold=cfg.health_burn_threshold,
                burn_min_requests=cfg.health_burn_min_requests,
                watchdog_mult=cfg.health_watchdog_mult,
                watchdog_floor_s=cfg.health_watchdog_floor_s,
                stats_cb=self.stats,
                trace_cb=self._health_trace,
                profile_cb=profile_cb,
                incident=_health.IncidentCapture())
        else:
            self._health = None

    def _health_trace(self):
        """Chrome-trace dict for incident bundles (None w/o a tracer)."""
        if self._trace is None:
            return None
        return {"traceEvents": list(self._trace.chrome_events()),
                "displayTimeUnit": "ms"}

    # -- public API ---------------------------------------------------

    def load_adapter(self, adapter_id, weights) -> int:
        """Register (or hot-reload) LoRA adapter ``adapter_id`` from a
        ``{module_name: (A, B)}`` dict — names either fully qualified
        (``model.layers.0.self_attn.q_proj``) or bare leaf names
        (``q_proj``, broadcast to every matching layer); ``A`` is
        ``[d_in, rank]``, ``B`` ``[rank, d_out]``. The weights land in
        the host-DRAM registry immediately and are device-loaded
        lazily on first acquire (LRU within the ``max_adapters``
        resident budget). Safe mid-serving: re-registering a RESIDENT
        id rewrites its stack row in place (requests already pinned to
        it pick up the new weights next tick — stack VALUES change,
        never shapes, so nothing recompiles)."""
        if self._lora_pool is None:
            raise ValueError(
                "load_adapter requires a LoRA-serving engine "
                "(ServingConfig(lora_rank=...) and PADDLE_TPU_LORA "
                "not 0)")
        aid = self._lora_pool.register(adapter_id, weights)
        self._sync_lora_metrics()
        return aid

    def adapter_resident(self, adapter_id) -> bool:
        """True when the adapter currently occupies a device stack row
        (the router's adapter-affinity probe — residency means a
        submit against it seats without an LRU swap)."""
        return self._lora_pool is not None \
            and self._lora_pool.resident(adapter_id)

    def submit(self, prompt, max_new_tokens=None, temperature=None,
               top_k=None, top_p=None, priority=0,
               max_queue_wait_ms=None, adapter_id=None) -> int:
        """Queue one request; returns its request id. Tokens stream to
        ``stream_callback`` as ``step()``/``run()`` produce them.
        ``temperature``/``top_k``/``top_p`` override the engine's
        ``ServingConfig`` values FOR THIS REQUEST ONLY (sampling
        engines; they land in the per-slot sampling tensors at
        admission — device data, never a recompile). ``priority`` is
        the request's scheduling class under the preemptive scheduler
        (higher admits first and may preempt strictly-lower victims;
        FIFO within a class; ignored when preemption is off).
        ``max_queue_wait_ms`` bounds the queue wait — a request still
        queued past it exits with outcome="timeout" and an empty
        result (default: ``ServingConfig.max_queue_wait_ms``). A
        validation rejection still leaves a terminal queue-wait
        observation (outcome="rejected") so the latency digest sees
        every request that touched the front door, not only the
        admitted survivors; queue-depth shedding
        (``ServingConfig.shed_queue_depth``) refuses with
        :class:`QueueShedError` and an outcome="shed" observation.
        ``adapter_id`` decodes the request under a LoRA adapter
        previously registered via :meth:`load_adapter` (None = base
        model); unknown ids are rejected at this front door, never
        mid-flight."""
        t0 = time.monotonic()
        if self._shed_depth is not None \
                and len(self._queue) >= int(self._shed_depth):
            self._n_shed += 1
            self._m_queue_wait.labels(outcome="shed").observe(0.0)
            self._d_queue.observe(0.0)
            if self._trace is not None:
                self._trace.instant("shed", tid=self._tid_queue,
                                    args={"queued": len(self._queue)})
            raise QueueShedError(
                f"admission queue at shed threshold "
                f"({len(self._queue)} >= {int(self._shed_depth)}): "
                "request refused (load shedding)")
        try:
            ids = np.asarray(prompt, np.int32).reshape(-1)
            if ids.size == 0:
                raise ValueError("empty prompt")
            max_new = int(self.config.max_new_tokens
                          if max_new_tokens is None
                          else max_new_tokens)
            if max_new < 1:
                raise ValueError(f"max_new_tokens must be >= 1, "
                                 f"got {max_new}")
            if ids.size + max_new > self.config.max_model_len:
                raise ValueError(
                    f"prompt ({ids.size}) + max_new_tokens "
                    f"({max_new}) exceeds max_model_len "
                    f"({self.config.max_model_len})")
            worst = self._worst_for(ids.size, max_new)
            if worst > self._alloc.num_blocks - 1:
                raise ValueError(
                    f"request needs {worst} blocks; pool has only "
                    f"{self._alloc.num_blocks - 1}")
            has_samp = any(v is not None
                           for v in (temperature, top_k, top_p))
            if has_samp and not self._do_sample:
                # greedy argmax never reads the knobs — honoring the
                # unknown-option policy, fail instead of silently
                # producing tokens that ignore the request
                raise ValueError(
                    "per-request temperature/top_k/top_p require "
                    "decode_strategy='sampling' (this engine decodes "
                    f"{self.config.decode_strategy!r})")
            if temperature is not None and float(temperature) < 0.0:
                raise ValueError(
                    f"temperature must be >= 0, got {temperature}")
            if top_k is not None and int(top_k) < 0:
                raise ValueError(f"top_k must be >= 0, got {top_k}")
            if top_p is not None and not 0.0 < float(top_p) <= 1.0:
                raise ValueError(
                    f"top_p must be in (0, 1], got {top_p}")
            if isinstance(priority, bool) or not isinstance(
                    priority, (int, np.integer)):
                raise ValueError(
                    f"priority must be an int, got {priority!r}")
            if max_queue_wait_ms is None:
                max_queue_wait_ms = self._default_qwait
            if max_queue_wait_ms is not None \
                    and float(max_queue_wait_ms) <= 0.0:
                raise ValueError(
                    f"max_queue_wait_ms must be > 0 (or None), got "
                    f"{max_queue_wait_ms}")
            if adapter_id is not None:
                if self._lora_pool is None:
                    raise ValueError(
                        "adapter_id requires a LoRA-serving engine "
                        "(ServingConfig(lora_rank=...) and "
                        "PADDLE_TPU_LORA not 0); this engine serves "
                        "the base model only")
                adapter_id = int(adapter_id)
                if not self._lora_pool.known(adapter_id):
                    raise ValueError(
                        f"unknown adapter_id {adapter_id}: register "
                        "it with load_adapter() before submitting "
                        "against it")
        except ValueError:
            wait = 1000.0 * (time.monotonic() - t0)
            self._m_queue_wait.labels(outcome="rejected").observe(wait)
            self._d_queue.observe(wait)
            if self._trace is not None:
                self._trace.instant("rejected", tid=self._tid_queue)
            raise
        rid = self._next_rid
        self._next_rid += 1
        req = ServingRequest(
            rid, ids, max_new,
            temperature=None if temperature is None
            else float(temperature),
            top_k=None if top_k is None else int(top_k),
            top_p=None if top_p is None else float(top_p),
            priority=int(priority),
            max_queue_wait_ms=None if max_queue_wait_ms is None
            else float(max_queue_wait_ms),
            adapter_id=adapter_id)
        self._queue.append(req)
        self._submit_t[rid] = req.submit_time
        if self._trace is not None:
            self._trace.instant(
                "submit", tid=self._tid_queue,
                args={"rid": rid, "prompt_tokens": int(ids.size),
                      "max_new": max_new, "priority": int(priority)})
        return rid

    def cancel(self, request_id: int) -> bool:
        """Cancel a request ANYWHERE in its lifetime. Queued: removed
        with a terminal queue-wait observation (outcome="cancelled");
        a queued PREEMPTED request additionally lands its e2e
        observation and surfaces the tokens already streamed. In
        flight (mid-prefill or mid-decode): the slot is retired
        immediately — its blocks are freed WITHOUT publishing (a
        cancelled stream's continuation must not seed the prefix
        cache), its spilled payload (if any) is dropped from the host
        tier, the partial tokens land in ``run()``'s results, and the
        e2e digest observes submit -> cancel. Returns False only when
        the id is unknown (never submitted, already finished, or
        already cancelled)."""
        self._flush_pipe()      # commit in-flight ticks before mutating
        for k, req in enumerate(self._queue):
            if req.request_id == request_id:
                del self._queue[k]
                self._queue_exit(req, "cancelled")
                self._finish_unserved(req)
                return True
        for i, s in enumerate(self._slots):
            if s is not None and s.rid == request_id:
                self._cancel_slot(i)
                return True
        return False

    def _finish_unserved(self, req, record_empty=False):
        """Terminal bookkeeping for a request leaving the QUEUE without
        service (cancel / timeout): surface what already streamed (the
        partial tokens of a preempted request; ``record_empty`` lands
        an empty result for never-admitted timeouts so ``run()``
        consumers never KeyError), drop any spilled payload, and land
        the e2e observation for requests that DID stream (their
        clients saw tokens; the digest must see the request end)."""
        rid = req.request_id
        if req.resume is not None:
            if self._host_tier is not None:
                self._host_tier.pop(("victim", rid), restore=False)
                self._m_host_bytes.set(self._host_tier.bytes_used)
            # anchor on the request's own submit time — _queue_exit
            # already popped _submit_t for terminal outcomes, and a
            # preempted request DID stream, so its end must land in
            # the e2e digest
            self._submit_t.pop(rid, None)
            self._d_e2e.observe(
                1000.0 * (time.monotonic() - req.submit_time))
        self._slo_ok.pop(rid, None)
        self._last_emit.pop(rid, None)
        toks = self._results.pop(rid, None)
        if self.config.retain_results and (
                toks is not None or record_empty):
            self._done[rid] = np.asarray(toks or [], np.int64)

    def _cancel_slot(self, i):
        """Retire slot ``i`` mid-flight on behalf of ``cancel()``: no
        completion accounting, no publishing (the cancelled stream
        must not seed the prefix index with its continuation), blocks
        freed, stream terminated with the tokens already emitted."""
        slot = self._slots[i]
        now = time.monotonic()
        t0 = self._submit_t.pop(slot.rid, None)
        if t0 is not None:
            self._d_e2e.observe(1000.0 * (now - t0))
        self._slo_ok.pop(slot.rid, None)    # cancels don't burn budget
        self._last_emit.pop(slot.rid, None)
        if self._trace is not None:
            self._trace.emit(
                f"req{slot.rid}", tid=1 + i, t0=slot.admit_t, t1=now,
                args={"tokens": slot.n_emitted,
                      "cache_len": slot.cache_len, "cancelled": True})
            self._trace.instant("cancelled", tid=1 + i,
                                args={"rid": slot.rid})
        if slot.handoff and i in self._handoff_ready:
            self._handoff_ready.remove(i)
        self._alloc.free(slot.blocks)
        self._reserved -= slot.worst_blocks - len(slot.blocks)
        self._tables[i, :] = 0
        self._tables_dev = None
        self._slots[i] = None
        self._set_slot_samp(i)
        self._lora_release_slot(i, slot)
        toks = self._results.pop(slot.rid, [])
        if self.config.retain_results:
            self._done[slot.rid] = np.asarray(toks, np.int64)
        self._n_cancelled += 1
        self._m_occupancy.set(self.num_active)

    def _ragged_attn_geometry(self, model):
        """What ``paged_attention.ragged_grid_units`` needs beside a
        tick's ``q_lens`` and lengths, from the model's config and the
        pools as built; ``None`` (the ``tick`` span then carries no
        ``attn_units`` / ``attn_live``) for a model that does not
        state its head count."""
        heads = getattr(getattr(model, "config", None),
                        "num_attention_heads", None)
        if not heads:
            return None
        paged0 = _pc.first_paged(self._pools)
        pool = paged0[0]
        pool = getattr(pool, "data", pool)      # QuantKV: the int8 half
        dtype = pool.dtype
        if not jnp.issubdtype(dtype, jnp.floating):
            dtype = jnp.dtype(getattr(model.config, "dtype", "float32"))
        geo = dict(rows=self._rows, w_max=self._wmax,
                   num_heads=int(heads), num_kv_heads=int(pool.shape[2]),
                   q_dtype=dtype, block_size=self._bs,
                   max_blocks=self._mb, head_lanes=int(pool.shape[-1]),
                   # K and V, and an int8 pool's two scale pools
                   streams=4 if isinstance(paged0[0], _pc.QuantKV) else 2,
                   narrow=True)
        if len(paged0) == 2 and pool.ndim == 3:
            # a flat pool: its rows hold every kv head; the kernel walks
            # them a lane tile (one head, or two of 64 lanes) at a time
            d = int(getattr(model.config, "head_dim", 0)
                    or model.config.hidden_size // int(heads))
            lanes = _pa.flat_pool_tile(d)
            geo.update(num_kv_heads=int(pool.shape[2]) // lanes,
                       head_lanes=lanes)
        if len(paged0) == 1:
            # a latent (MLA) cache: every head reads the layer's one
            # array, in the latent kernel's own tile
            geo.update(num_kv_heads=1, latent=True, streams=1)
        return geo

    def _attn_grid(self, q_lens, context_lens):
        """The ``tick`` span's ``attn_units`` / ``attn_live`` /
        ``attn_copies`` / ``attn_narrow`` of one layer's ragged
        attention call this tick (docs/OPS.md "Tick phases"), or
        nothing where no span would carry them: numpy on ``num_slots``
        entries."""
        if self._trace is None or self._attn_geometry is None:
            return {}
        return dict(zip(
            ("attn_units", "attn_live", "attn_copies", "attn_narrow"),
            _pa.ragged_grid_units(q_lens, context_lens,
                                  **self._attn_geometry)))

    def _launch_ragged(self, args):
        """Run THE tick executable: ``(outs, share counts or None)``.
        Where the model holds an expert-parallel share the executable
        returns the share's counts as one more output, LAST; it is
        taken off for this tick's commit, so every other reader of the
        outputs sees the same tuple for every model."""
        outs = self._ragged_exec(*args)
        if self._moe_share_out:
            return outs[:-1], outs[-1]
        return outs, None

    def _commit_moe_share(self, counts):
        """The ``tick`` span's ``moe_pairs`` / ``moe_touched`` /
        ``moe_hot`` from one tick's share counts (``[expert layers,
        held + 1]``, fetched once the tick's tokens are), and the
        running ``stats()`` counters."""
        if counts is None:
            return {}
        counts = np.asarray(counts)
        pairs = counts[:, :-1]
        self._n_moe_rows += int(counts[:, -1].sum())
        self._n_moe_pairs_local += int(pairs.sum())
        return {"moe_pairs": int(pairs.sum()),
                "moe_touched": int((pairs > 0).sum()),
                "moe_hot": int(pairs.max())}

    def _trace_tick(self, t_tick, exec_name: str, path: str, **extra):
        """One engine-tick span (tid 0): the tick-span schema
        (exec/path/queued/kernel-fallback delta + extras) in one
        place. Caller guards on ``self._trace``."""
        args = {"exec": exec_name, "path": path,
                "queued": len(self._queue),
                "kernel_fallbacks": int(sum(
                    _pa.kernel_fallback_counts().values())
                    - self._fallbacks0)}
        args.update(extra)
        self._trace.emit("tick", tid=0, t0=t_tick, args=args)

    def _queue_exit(self, req, outcome: str) -> float:
        """Terminal queue-wait observation — EVERY path a request
        leaves the admission queue by (admitted / cancelled /
        shutdown; submit rejections observe outcome="rejected"
        directly) funnels through here, so neither the histogram nor
        the digest can survivor-bias toward admitted requests."""
        now = time.monotonic()
        wait = 1000.0 * (now - req.submit_time)
        self._m_queue_wait.labels(outcome=outcome).observe(wait)
        self._d_queue.observe(wait)
        if outcome not in ("admitted", "resumed"):
            # request will never emit/retire (a "resumed" one keeps
            # its original submit anchor for the e2e digest)
            self._submit_t.pop(req.request_id, None)
        if self._trace is not None:
            self._trace.emit(
                f"req{req.request_id} queued", tid=self._tid_queue,
                t0=req.submit_time, t1=now,
                args={"rid": req.request_id, "outcome": outcome})
        return wait

    @property
    def num_active(self) -> int:
        return sum(s is not None for s in self._slots)

    @property
    def num_queued(self) -> int:
        return len(self._queue)

    def step(self) -> List[tuple]:
        """One engine tick: admit what fits, decode one token (or
        verify a speculative window) for every active slot, retire
        finished sequences. Returns this tick's
        ``[(request_id, token), ...]`` (admission prefills included).
        One tick is ONE executable launch covering decode + verify +
        prefill rows together. An armed profiling window
        (``profile(n_ticks)``) brackets the tick — the capture starts
        before the first armed tick and stops after the last, bounding
        the profile to exactly N ticks."""
        t0 = time.monotonic()
        c0 = self._n_exec_compiled
        with self._prof.tick():
            out = self._tick_dispatch()
            out.extend(self._tick_commit())
        if self._health is not None:
            self._health_tick(t0, time.monotonic(), c0)
        return out

    def _health_tick(self, t0: float, t1: float, c0: int) -> None:
        """Feed one tick's signals to the health monitor (host only)."""
        h = self._health
        nf = self._nf_last
        self._nf_last = False
        if nf:
            self._nonfinite_ticks += 1
        ema = self._step_time.get(
            "verify" if self._gamma else "decode", 0.0)
        try:
            fallbacks = sum(_pa.kernel_fallback_counts().values())
        except Exception:
            fallbacks = 0
        h.on_tick(
            tick_s=t1 - t0,
            queued=len(self._queue),
            step_ema_s=ema,
            fallbacks=fallbacks,
            compiles=self._n_exec_compiled,
            spec_emitted=self._n_spec_emitted,
            spec_verifies=self._n_spec_verifies,
            preemptions=self._n_preempt,
            completed=self._n_completed,
            nonfinite=nf,
            compiled=self._n_exec_compiled > c0)
        self._m_health.set(h.score())
        self._m_burn.set(h._last_burn.get("fast", 0.0))
        self._m_alerts.set(float(len(h.firing())))

    def _tree_draft(self, i) -> np.ndarray:
        """One slot's gamma-node tree proposal for this tick, in node
        order. drafter='heads': the verify executable computed it LAST
        tick from the accepted path's final hidden state (cached per
        slot); a slot with no cached proposal (fresh prefill, disagg
        import, post-preemption resume) falls back to the ngram-topk
        chains — the SAME rule on every engine, which keeps colocated
        and disaggregated head drafting token-exact. drafter='ngram':
        always the top-k prompt-lookup chains."""
        from ..generation import speculative as _spec
        if self._heads is not None:
            props = self._slot_props.get(i)
            if props is not None:
                return props
        chains = _spec.ngram_propose_topk(
            self._slots[i].history, self._tree_max_depth,
            self._tree_chains, self._ngram_max)
        return np.asarray(_spec.tree_fill_from_chains(
            self._spec_tree, chains), np.int32)

    def _commit_verify_window(self, i, out_row, accept_row, emitted):
        """Commit one slot's verified speculative window — the
        host-side half of acceptance: emit the kept prefix, account
        acceptance, retire on EOS/max_new, else advance
        ``cache_len`` over the accepted prefix (rollback of
        the rejected tail = NOT advancing over it) and trim overhang
        blocks. Returns the number of tokens emitted (the per-slot
        ``accepted_len`` the trace annotates verify-tick spans
        with)."""
        from ..generation import speculative as _spec
        g = self._gamma
        slot = self._slots[i]
        # EOS inside the window and max_new room both truncate
        kept, n_acc = _spec.commit_window(
            out_row, accept_row, slot.max_new - slot.n_emitted,
            self._eos)
        slot.n_emitted += len(kept)
        slot.history.extend(kept)
        for tok in kept:
            self._emit(slot.rid, tok)
            emitted.append((slot.rid, tok))
        # accepted drafts that were actually USED: EOS-inside-window
        # or max_new room can truncate the emission below n_acc+1,
        # and the metrics must agree with what clients received
        n_used = min(n_acc, len(kept))
        self._n_spec_proposed += g
        self._n_spec_accepted += n_used
        self._n_spec_verifies += 1
        self._n_spec_emitted += len(kept)
        self._d_accept.observe(float(len(kept)))
        self._m_spec_len.observe(len(kept))
        self._m_spec_proposed.inc(g)
        self._m_spec_accepted.inc(n_used)
        if kept[-1] == self._eos or slot.n_emitted >= slot.max_new:
            self._retire(i)
        else:
            # commit the window prefix [cur, accepted drafts]; the
            # rejected tail rolls back by NOT advancing over it
            slot.cache_len += n_acc + 1
            slot.last_token = kept[-1]
            self._trim_blocks(i)
        return len(kept)

    # -- the tick: a dispatch half and a commit half -------------------

    def _ahead(self, i):
        """Slot ``i`` as the tick about to be packed must see it: its
        committed state with what the one uncommitted tick
        (``self._pipe``, docs/OPS.md "Async tick pipeline") does to it
        applied. That tick's row counts are the host's own; only the
        token it samples is not here yet. Returns ``(pend_pos,
        cache_len, rowless, tok)``: ``rowless`` where the uncommitted
        tick ends the slot's work here whatever it samples (budget
        used up, or a prefill-role slot about to park), ``tok`` the id
        of the slot's next decode row — ``None`` where that id is the
        uncommitted tick's own output and stays on the device."""
        s = self._slots[i]
        pipe = self._pipe
        if pipe is None or pipe.rid_of.get(i) != s.rid:
            return _Ahead(s.pend_pos, s.cache_len, False, s.last_token)
        k = pipe.given.get(i)
        if k is None:                   # a decode row in flight
            return _Ahead(None, s.cache_len + 1,
                          s.n_emitted + 1 >= s.max_new, None)
        pend = s.pend_pos + k
        if pend < int(s.prompt.size):
            return _Ahead(pend, pend, False, None)
        if s.resume is not None:
            # a recompute resume completes in flight: the continuation
            # it restores is the host's own
            return _Ahead(None, pend, False, int(s.resume[0]))
        return _Ahead(None, pend,
                      s.max_new <= 1 or self._role == "prefill", None)

    def _release_spent(self, pipe):
        """A decode row in flight that uses up its request's budget
        ends the request whatever it samples, so the seat and the
        blocks go back NOW, ahead of this dispatch's admission — where
        the blocking loop's commit would have returned them — and the
        schedule stays the blocking loop's, tick for tick. The commit
        still to come delivers the last token (``pipe.left``). What
        the published blocks' hashes cover is on the host already
        (position ``cache_len`` holds the row's input token), and
        launches issue in host order, so whoever maps or spills such a
        block reads it after that row's write."""
        for i in pipe.active:
            s = self._slots[i]
            if s is not None and self._ahead(i).rowless:
                s.cache_len += 1
                self._release_seat(i)
                pipe.left[i] = s

    def _settle(self) -> bool:
        """Preemption reads and rewrites slot state, so it is decided
        on committed state only: commit what is in flight first. True
        if there was something — the caller then looks again, since
        the commit may have freed what it was about to take."""
        if self._pipe is None and self._commit_due is None:
            return False
        self._flush_pipe()
        return True

    def _ragged_dispatch(self):
        """Dispatch half of one ragged tick: admit, pack every live
        query row — 1 per decoding slot, ``gamma + 1`` per verifying
        slot, up to the prefill row budget for pending prompts — and
        launch the engine's ONE compiled executable. The packed width
        is static (``num_slots * (gamma+1) + prefill_rows``); slots
        with no work contribute zero rows, so raggedness lives
        entirely in the ``q_lens``/``row_starts`` VALUES and steady
        state runs zero recompiles.

        The rows are packed from committed state plus what the
        uncommitted tick does to it (``_ahead``); a decoding slot
        whose last token that tick is still sampling names it by
        ``src`` and the executable reads it on the device. Returns
        ``(pipe, emitted)`` — ``pipe`` holds everything the commit
        half needs (``None`` on an idle tick), ``emitted`` what
        admission emitted."""
        from ..generation import speculative as _spec
        t_tick = time.monotonic()
        # tick-phase spans (docs/OPS.md "Tick phases"): each carries
        # the ordinal of the tick it belongs to
        tr = self._trace
        tick = self._tick_ord = self._n_decode_steps
        if self._pipe is not None:
            self._release_spent(self._pipe)
        ph = tr.phase("admit", tick=tick).begin() \
            if tr is not None else None
        emitted = self._admit()
        if ph is not None:
            ph.end(admitted=sum(s is not None and s.admit_t >= ph.t0
                                for s in self._slots),
                   queued=len(self._queue))
        cfg = self.config
        g = self._gamma
        n_slots = cfg.num_slots
        while True:
            # the tick still uncommitted; None once anything drained it
            prev = self._pipe
            view = {i: self._ahead(i)
                    for i, s in enumerate(self._slots)
                    if s is not None and not s.handoff}
            active = [i for i, v in view.items()
                      if v.pend_pos is None and not v.rowless]
            pending = [i for i, v in view.items()
                       if v.pend_pos is not None]
            if not active and not pending:
                return None, emitted
            if active:
                # room for this tick's write positions (the verify
                # window overhangs by up to gamma speculated slots);
                # growth under an overcommitted pool may preempt —
                # survivors only
                n0 = self._n_grown
                ph = tr.phase("grow", tick=tick).begin() \
                    if tr is not None else None
                active = self._ensure_blocks(active, horizon=g + 1)
                if ph is not None:
                    ph.end(blocks=self._n_grown - n0)
            if self._pipe is prev:
                break
            # growth found the pool dry and preempted, which drained
            # the pipeline: every view above is of a state that has
            # moved on — pack again, from what is committed now
        if not active and not pending:
            return None, emitted

        # -- pack the tick's work into per-slot row counts -------------
        ph = tr.phase("pack", tick=tick).begin() \
            if tr is not None else None
        q_lens = np.zeros(n_slots, np.int64)
        base = np.zeros(n_slots, np.int64)
        given = {}              # slot -> prefill rows granted this tick
        cap = min(self._chunk, self._prefill_rows)
        budget = self._prefill_rows
        for i in active:
            q_lens[i] = g + 1
            base[i] = view[i].cache_len
        # a growth preemption above may have victimized a pending slot
        pending = [i for i in pending if self._slots[i] is not None]
        if self._preempt_on and len(pending) > 1:
            # the per-tick prefill row budget is a scheduled resource
            # too: the highest class prefills first (its TTFT is the
            # SLO), FIFO within a class — under the kill switch the
            # slot-index order is untouched, bit-for-bit
            pending.sort(key=lambda i: (-self._slots[i].priority,
                                        self._slots[i].admit_t, i))
        for i in pending:
            if budget <= 0:
                break
            slot = self._slots[i]
            # ONE wide (chunk-width) slot per tick — the fallback's
            # two-lane contract; later pending slots still trickle at
            # the narrow (gamma+1) width, so nothing starves
            cap_i = cap if not given else (g + 1)
            k = min(int(slot.prompt.size) - view[i].pend_pos, cap_i,
                    budget)
            if k <= 0:
                continue
            q_lens[i] = k
            base[i] = view[i].pend_pos
            given[i] = k
            budget -= k
        if not int(q_lens.sum()):
            if ph is not None:
                ph.end(rows=0)
            return None, emitted    # budget exhausted by earlier slots
        row_slot, row_pos, row_starts, last_rows = _pc.ragged_row_meta(
            q_lens, base, self._rows, self._overflow)
        if self._tables_dev is None:
            # a copy: the CPU backend aliases a numpy operand, and the
            # host rewrites its tables while the tick that reads this
            # upload is still in flight (likewise the sampling tensor
            # and the adapter stacks, where they are uploaded)
            self._tables_dev = self._dev(self._tables.copy())

        # -- draft proposals (speculative mode) ------------------------
        toks = None
        dq = None
        if g:
            toks = np.full((n_slots, g + 1), self._pad, np.int32)
            for i in active:
                toks[i, 0] = self._slots[i].last_token
        if g and self._draft_model is not None:
            # ONE fused draft executable: prime its cache over this
            # tick's prefill rows, then run the gamma+1 proposal scan.
            # Verify rows are parked at the overflow position for the
            # prime (their K/V comes from the scan itself), and
            # non-verifying slots' scan writes null-route past the
            # table's reach — a pending slot's real blocks are never
            # touched by the draft.
            prime_ids = np.full(self._rows, self._pad, np.int32)
            prime_pos = row_pos.copy()
            prime_q = q_lens.copy()
            for i in active:
                s0, n = int(row_starts[i]), int(q_lens[i])
                prime_pos[s0:s0 + n] = self._overflow
                prime_q[i] = 0
            for i, k in given.items():
                s0 = int(row_starts[i])
                slot = self._slots[i]
                prime_ids[s0:s0 + k] = \
                    slot.prompt[slot.pend_pos:slot.pend_pos + k]
            scan_lens = np.full(n_slots, self._overflow, np.int64)
            for i in active:
                scan_lens[i] = self._slots[i].cache_len
            sub = self._next_key()
            # TWO packed uploads carry the whole tick's draft metadata
            drows = np.stack([prime_ids, row_slot, prime_pos]) \
                .astype(np.int32)
            dslots = np.stack([base, prime_q, row_starts, scan_lens,
                               toks[:, 0]]).astype(np.int32)
            dargs = (self._dparams, self._dpools, self._tables_dev,
                     self._dev(drows), self._dev(dslots),
                     self._samp_operand(), sub)
            if self._ragged_draft_exec is None:
                self._ragged_draft_exec = self._compile_ragged_draft(
                    dargs)
            with _quiet_donation():
                outs = self._ragged_draft_exec(*dargs)
            if self._do_sample:
                props, dq, self._dpools = outs
            else:
                props, self._dpools = outs
            toks[:, 1:] = np.asarray(props)
        elif g and self._spec_tree is not None:
            for i in active:
                toks[i, 1:] = self._tree_draft(i)
        elif g:
            for i in active:
                toks[i, 1:] = _spec.ngram_propose(
                    self._slots[i].history, g, self._ngram_max)

        # -- the ONE mixed-batch launch --------------------------------
        ids = np.full(self._rows, self._pad, np.int32)
        # row -> the slot whose token the uncommitted tick is sampling
        # (the executable reads it from that tick's output), -1 where
        # the host packed the id
        src = np.full(self._rows, -1, np.int32)
        for i in active:
            s0 = int(row_starts[i])
            if g:
                ids[s0:s0 + g + 1] = toks[i]
            elif view[i].tok is None:
                src[s0] = i
            else:
                ids[s0] = view[i].tok
        for i, k in given.items():
            s0 = int(row_starts[i])
            p0 = view[i].pend_pos
            ids[s0:s0 + k] = self._slots[i].prompt[p0:p0 + k]
        sub = self._next_key()
        # TWO packed uploads carry the whole tick's row layout: the
        # per-row rows (ids, slot, position; without speculation also
        # ``src``) and the per-slot quad (base length, q_lens,
        # row_starts, last_rows)
        rrows = [ids, row_slot, row_pos]
        if not g:
            rrows.append(src)
        rows_pack = np.stack(rrows).astype(np.int32)
        srows = [base, q_lens, row_starts, last_rows]
        if self._spec_tree is not None:
            # 5th per-slot row: which slots verify a TREE window this
            # tick (prefill rows keep the linear causal mask)
            tree_flags = np.zeros(n_slots, np.int64)
            for i in active:
                tree_flags[i] = 1
            srows.append(tree_flags)
        if self._lora_on:
            # per-slot adapter row (RESIDENT stack rows, 0 = the null
            # adapter) rides the slots pack next to the sampling
            # tensor — churn changes VALUES at a fixed shape, so no
            # adapter mix ever recompiles the tick
            srows.append(self._slot_adapter)
        slots_pack = np.stack(srows).astype(np.int32)
        args = [self._params, self._pools, self._tables_dev,
                self._dev(rows_pack), self._dev(slots_pack)]
        if not g:
            # the tick before's tokens, as that tick left them on the
            # device: read where ``src`` points, and read again by that
            # tick's commit, so never donated
            args.append(self._prev_tok)
        if self._lora_on:
            # the stacked A/B weights are a runtime OPERAND (cached on
            # device until the pool version moves), same reasoning
            args.append(self._lora_operand())
        if g:
            args.append(self._dev(toks))
            if self._heads is not None:
                args.append(self._heads)
            if self._do_sample and dq is not None:
                args.append(dq)
        args.append(self._samp_operand())
        args.append(sub)
        if self._ragged_exec is None:
            self._ragged_exec = self._compile_ragged_step(tuple(args))
        # names/positions BEFORE any commit retires slots (the commit's
        # guard keys on these: a slot that retired on an EOS the host
        # could not foresee, and may be seated with ANOTHER request by
        # the time this tick commits, must drop this tick's token)
        rid_of = {i: self._slots[i].rid
                  for i in active + list(given)}
        pend_pos0 = {i: int(view[i].pend_pos) for i in given}
        # row t of slot s sees base[s] + t + 1 positions
        attn_grid = self._attn_grid(q_lens, base + 1)
        attn_grid["head_rows"] = self._head_rows
        if self._hc_connections:
            attn_grid["hc_rows"] = int(q_lens.sum()) * self._hc_connections
        if self._tick_counts is not None:
            attn_grid.update(self._tick_counts(q_lens, base))
        dispatch = "packed" if prev is None else "carry"
        t_l0 = time.monotonic()
        if ph is not None:
            ph.end(rows=int(q_lens.sum()))
        if self._last_dispatch_t is not None:
            self._d_host_gap.observe(
                1000.0 * (t_l0 - self._last_dispatch_t))
        self._last_dispatch_t = t_l0
        with _quiet_donation():
            if tr is None:
                outs, moe = self._launch_ragged(args)
            else:
                with tr.phase("launch", tick=tick, dispatch=dispatch):
                    outs, moe = self._launch_ragged(args)
        # the pools advance at DISPATCH (device futures): the next
        # launch, a spill's gather or a COW consumes them before this
        # tick's commit runs
        self._pools = outs[-1]
        state_args = {}
        if self._stateful:
            # a seat whose first row is position 0 began from zeros in
            # the executable; a chunk that ended on a block boundary
            # leaves a state worth keeping beside that block
            started = sum(1 for p0 in pend_pos0.values() if p0 == 0)
            self._n_state_started += started
            state_args = {"state_seats": len(active) + len(given)}
            if self._scan_state:
                state_args[self._scan_state + "_seats"] = int(
                    (q_lens == 1).sum())
                state_args[self._scan_state + "_chunk_rows"] = int(
                    q_lens[q_lens > 1].sum())
            if self._prefix_on:
                for i, k in given.items():
                    if (pend_pos0[i] + k) % self._bs == 0:
                        self._snapshot_state(i, pend_pos0[i] + k)
        if not g:
            self._prev_tok = outs[0]
            # the commit wants the tokens on the host as soon as the
            # tick ends, not a round trip after it asks
            outs[0].copy_to_host_async()

        self._m_steps.inc()
        self._n_decode_steps += 1
        if self._mesh is not None:
            self._m_tp_bytes.inc(self._tp_step_bytes)
            self._n_tp_bytes += self._tp_step_bytes
        self._m_util.observe(len(active) / n_slots)
        # packed row t of slot s attends base[s] + t + 1 positions
        self._note_kv_read(int((q_lens * base).sum())
                           + int((q_lens * (q_lens + 1) // 2).sum()))
        pipe = _Pipe(
            outs=outs, active=list(active), given=given,
            n_pending=len(pending), q_lens=q_lens, rid_of=rid_of,
            pend_pos0=pend_pos0, t_tick=t_tick, t_l0=t_l0, left={},
            tick=tick, dispatch=dispatch,
            span_args=dict(attn_grid, **state_args), moe=moe)
        return pipe, emitted

    def _ragged_commit(self, pipe, flush=False) -> List[tuple]:
        """Commit half of one ragged tick: fetch tokens, advance
        slots, retire, commit prefill progress, emit trace spans.
        Behind a tick dispatched ahead this runs while the NEXT tick
        executes. A decode row whose slot is gone by now — retired by
        the commit before this one on an EOS the host could not
        foresee — is skipped, dropping its token exactly (the
        executable parked that row at the overflow position, so there
        is nothing to trim). ``flush`` only labels the ``commit``
        phase: the pipeline was drained."""
        outs = pipe.outs
        g = self._gamma
        n_slots = self.config.num_slots
        active, given, q_lens = pipe.active, pipe.given, pipe.q_lens
        rid_of, pend_pos0 = pipe.rid_of, pipe.pend_pos0
        t_tick, t_l0 = pipe.t_tick, pipe.t_l0
        tr = self._trace
        emitted: List[tuple] = []
        committed = [i for i in active
                     if i in pipe.left
                     or (self._slots[i] is not None
                         and self._slots[i].rid == rid_of[i])]

        # -- fetch: the host blocks on the device here -----------------
        self._tick_ord = pipe.tick
        ph = tr.phase("fetch", tick=pipe.tick).begin() \
            if tr is not None else None
        tok_arr = np.asarray(outs[0])   # decode tokens | prefill firsts
        k = 1
        if g:
            out = np.asarray(outs[1])
            accept = np.asarray(outs[2])
            k = 4 if self._heads is not None else 3
            if self._heads is not None:
                props_next = np.asarray(outs[3])
        if self._health is not None:            # host fetch gated on
            self._nf_last = bool(outs[k])       # the kill switch only
        moe_args = self._commit_moe_share(pipe.moe)
        t_sync = time.monotonic()
        if ph is not None:
            ph.end()
            ph = tr.phase("commit", tick=pipe.tick, flush=flush).begin()
        # the tick has completed, so the spill gathers launched ahead
        # of it — and of the tick dispatched since — have too: their
        # bytes are taken in without a wait
        self._drain_spills()

        # -- commit decode / verify rows -------------------------------
        acc_lens = {}
        if not g:
            for i in committed:
                # a slot whose budget this row used up left its seat
                # at the dispatch after this one (``_release_spent``)
                slot = pipe.left.get(i)
                left = slot is not None
                if not left:
                    slot = self._slots[i]
                    slot.cache_len += 1
                tok = int(tok_arr[i])
                slot.last_token = tok
                slot.n_emitted += 1
                slot.history.append(tok)
                self._emit(slot.rid, tok)
                emitted.append((slot.rid, tok))
                if left:
                    self._finish_request(i, slot)
                elif tok == self._eos or slot.n_emitted >= slot.max_new:
                    self._retire(i)
        else:
            for i in committed:
                acc_lens[i] = self._commit_verify_window(
                    i, out[i], accept[i], emitted)
            if self._heads is not None:
                # cache the heads' next-tick tree proposal for every
                # slot that survived the commit (retired/preempted
                # slots dropped theirs); fresh slots without a cached
                # proposal draft via the ngram-topk fallback next tick
                for i in committed:
                    if self._slots[i] is not None:
                        self._slot_props[i] = props_next[i]
            if self._n_spec_proposed:
                self._m_spec_rate.set(
                    self._n_spec_accepted / self._n_spec_proposed)

        # -- commit prefill progress -----------------------------------
        self._note_step_time("verify" if g else "decode",
                             t_sync - t_l0)
        if given:
            # cost-model input: rows prefilled this launch / wall time
            self._note_prefill_rate(sum(given.values()),
                                    t_sync - t_l0)
        for i, k in given.items():
            slot = self._slots[i]
            slot.pend_pos += k
            slot.cache_len = slot.pend_pos
            self._n_prefill_chunks += 1
            if slot.pend_pos >= int(slot.prompt.size):
                # the chunk's last row IS the final prompt row: its
                # sampled logits are the request's first token
                self._finish_prefill(i, int(tok_arr[i]), emitted)
        if tr is not None:
            # behind a tick dispatched ahead the span's [t_l0, t_sync]
            # brackets dispatch -> commit, i.e. it INCLUDES the
            # one-tick overlap window (commit-lag semantics, docs/OPS.md
            # "Async tick pipeline"); dropped (stale-slot) rows emit no
            # span
            for i in committed:
                args_i = {"rid": rid_of[i], "rows": int(q_lens[i])}
                if g:
                    args_i["accepted_len"] = acc_lens[i]
                tr.emit("verify tick" if g else "decode tick",
                        tid=1 + i, t0=t_l0, t1=t_sync, args=args_i)
            for i, k in given.items():
                tr.emit("prefill chunk", tid=1 + i, t0=t_l0,
                        t1=t_sync,
                        args={"rid": rid_of[i], "rows": int(k),
                              "pos": pend_pos0[i]})
            ph.end(tokens=len(emitted))
            self._trace_tick(
                t_tick, "verify" if g else "decode", "ragged",
                rows=int(q_lens.sum()), active=len(active),
                pending=pipe.n_pending,
                occupancy=round(
                    (len(active) + pipe.n_pending) / n_slots, 3),
                dispatch=pipe.dispatch, **pipe.span_args, **moe_args)
        return emitted

    # -- async tick pipeline (docs/OPS.md "Async tick pipeline") ------

    def _tick_dispatch(self) -> List[tuple]:
        """First half of one engine tick: launch the next tick. Where
        the engine dispatches ahead (``_ahead_on``: no speculation,
        ``async_depth`` not 0) the tick launched before stays
        uncommitted until ``_tick_commit``, which then runs while this
        one executes; otherwise this tick is its own commit's."""
        pipe, emitted = self._ragged_dispatch()
        out, self._flushed = self._flushed, []
        out.extend(emitted)
        if self._ahead_on:
            # (None where a preemption inside the dispatch drained it)
            self._commit_due, self._pipe = self._pipe, pipe
        else:
            self._commit_due = pipe
        return out

    def _tick_commit(self) -> List[tuple]:
        """Second half: commit the tick that was in flight when
        ``_tick_dispatch`` launched (the one it launched, on a
        blocking engine)."""
        due, self._commit_due = self._commit_due, None
        out = self._ragged_commit(due) if due is not None else []
        pipe = self._pipe
        if pipe is not None and self._eos >= 0 and not any(
                s is not None and s.rid == pipe.rid_of.get(i)
                for i, s in enumerate(self._slots)):
            # every row of the tick in flight belongs to a slot this
            # commit retired on an EOS: nothing waits for it and the
            # engine may go idle, so take it in now
            self._pipe = None
            out.extend(self._ragged_commit(pipe))
        if self._pipe is None:
            # no tick in flight whose ``commit`` would take in what
            # was evicted since the last one
            self._drain_spills()
        return out

    def tick_dispatch(self) -> List[tuple]:
        """Dispatch phase of an overlapped CLUSTER tick: launch this
        engine's next tick and leave the commit to ``tick_commit()``,
        so N replicas' executables run concurrently instead of
        serially. ``step()`` is the two back to back."""
        self._split_t0 = time.monotonic()
        self._split_c0 = self._n_exec_compiled
        with self._prof.tick():
            return self._tick_dispatch()

    def tick_commit(self) -> List[tuple]:
        """Commit phase of an overlapped cluster tick."""
        out = self._tick_commit()
        if self._health is not None:
            self._health_tick(self._split_t0, time.monotonic(),
                              self._split_c0)
        return out

    def _flush_pipe(self) -> None:
        """Commit whatever is in flight NOW. Everything that reads or
        rewrites slot state from outside the tick (cancel, preempt,
        handoff pop, prefilled/migrated admits, session export/drain,
        shutdown) calls this first: a host view that lags the device
        by a tick would be wrong there. The tokens it commits reach
        their callbacks here and the caller of the next
        ``tick_dispatch()`` / ``step()`` in its return. No-op on an
        idle pipeline; each drain of a tick dispatched ahead counts in
        ``stats()["pipeline_flushes"]``."""
        tick_ord = self._tick_ord
        due, self._commit_due = self._commit_due, None
        if due is not None:
            self._flushed.extend(self._ragged_commit(due))
        pipe, self._pipe = self._pipe, None
        if pipe is not None:
            self._n_pipe_flushes += 1
            self._flushed.extend(self._ragged_commit(pipe, flush=True))
        self._tick_ord = tick_ord

    def run(self) -> Dict[int, np.ndarray]:
        """Drive ``step()`` until queue and slots drain; returns (and
        drains) the tokens of every request completed since the last
        ``run()``, keyed by request id — a long-lived engine therefore
        never accumulates finished results."""
        if self._role == "prefill":
            # parked handoff slots only free via pop_prefilled() —
            # run() would spin forever waiting on them
            raise RuntimeError(
                "a role='prefill' engine cannot run() to completion: "
                "drive step() and collect pop_prefilled() handoffs "
                "(EngineCluster does this)")
        while self._queue or self.num_active:
            self.step()
        done, self._done = self._done, {}
        return done

    def serve(self, prompts, max_new_tokens=None) -> List[np.ndarray]:
        """Batch convenience: submit all, run to completion, return
        token arrays in submission order."""
        rids = [self.submit(p, max_new_tokens) for p in prompts]
        done = self.run()
        return [done[r] for r in rids]

    def stats(self) -> dict:
        """Scheduler/counter snapshot (tests + ops dashboards). In
        speculative mode ``decode_steps``/``decode_compiles`` count the
        verify executable (the spec-mode decode step)."""
        self._sync_cache_metrics()
        out = {
            "active": self.num_active,
            "queued": self.num_queued,
            "free_blocks": self._alloc.free_blocks,
            "reserved_blocks": self._reserved,
            "decode_steps": self._n_decode_steps,
            "decode_compiles": self._n_decode_compiles,
            "tokens_total": self._n_tokens,
            "requests_completed": self._n_completed,
            # no executable is ever built for prefill alone: prompt
            # rows ride the tick
            "prefill_compiles": 0,
            "prefill_chunks": self._n_prefill_chunks,
            # EVERY executable this engine built (the tick, cow,
            # export/import, spill; target AND draft): 1 in steady
            # state (2 with a draft model)
            "executables_compiled": self._n_exec_compiled,
            # paged-attention entry points that lost the Pallas kernel
            # on a TPU backend since THIS engine was created (0 on
            # CPU; the op-layer counter is process-wide, so the
            # engine-lifetime delta is what "my engine silently fell
            # off the kernel" means — a concurrent engine's events
            # still land in the window, but never another's history)
            "kernel_fallbacks": sum(
                _pa.kernel_fallback_counts().values())
            - self._fallbacks0,
            # decode-tick fusion: mode (False | "kernel" |
            # "interpret") + the MEASURED kernel census of the tick
            # executable (0 before first compile). kernels_per_tick is
            # the optimized-HLO entry instruction count (≈ kernel
            # launches on this backend); the launch proxy counts
            # jaxpr-level launch-rooted ops (dot/pallas/gather/...) —
            # backend-independent, what the fused collapse shows on a
            # CPU census with interpret-routed kernels
            "fused_decode": self._fused_mode is not None,
            "fused_decode_mode": self._fused_mode or "off",
            "kernels_per_tick": self._kcensus.get(
                "verify" if self._gamma else "decode", {}).get(
                "hlo_kernels", 0),
            "kernel_launch_proxy_per_tick": self._kcensus.get(
                "verify" if self._gamma else "decode", {}).get(
                "launch_proxy", 0),
            "prefix_cache_enabled": self._prefix_on,
            "prefix_blocks_reused": self._n_prefix_blocks,
            "prefix_tokens_reused": self._n_prefix_tokens,
            # slot state beside the paged KV (docs/OPS.md "Slot
            # state"): all 0 for a model without it
            "prefix_tokens_cut_for_state": self._n_prefix_cut,
            "state_bytes": self._state_bytes,
            "state_seats_started": self._n_state_started,
            "state_snapshots": self._n_state_snaps,
            "state_snapshot_hits": self._n_state_snap_hits,
            "state_snapshot_bytes":
                self._snaps_held() * self._snap_nbytes,
            "state_snapshots_dropped": self._n_state_snaps_dropped,
            "prefix_hit_rate":
                self._n_prefix_tokens / self._n_prompt_tokens
                if self._n_prompt_tokens else 0.0,
            "cow_copies": self._n_cow,
            "cache_evictions": self._alloc.evictions,
            "cached_blocks": self._alloc.cached_blocks,
            # KV-pool quantization keys are ALWAYS present (fp engines
            # report the fp dtype/bytes), so dashboards never KeyError
            # across a mixed fleet or a PADDLE_TPU_KV_INT8=0 rollback
            "kv_cache_dtype": self._kv_dtype_name,
            "kv_pool_bytes": self._kv_pool_bytes,
            "kv_bytes_per_step": self._kv_step_bytes_last,
            # disaggregated-cluster keys: ALWAYS present (0 /
            # role="both" on a standalone engine) so fleet dashboards
            # never KeyError on a mixed colocated/disaggregated fleet
            "role": self._role,
            "prefills_exported": self._n_handoffs,
            "kv_blocks_exported": self._n_blocks_exported,
            "kv_blocks_imported": self._n_blocks_imported,
            # preemptive-scheduler + host-tier keys: ALWAYS present
            # (zeros under the PADDLE_TPU_PREEMPT=0 kill switch or
            # enable_preemption=False), so dashboards never KeyError
            # across a mixed or rolled-back fleet
            "preemption_enabled": self._preempt_on,
            "preemptions": self._n_preempt,
            "kv_blocks_spilled": self._n_spilled,
            "kv_spill_bytes_copied": self._n_spill_bytes,
            "kv_blocks_restored": self._n_restored,
            "host_tier_bytes": self._host_tier.bytes_used
            if self._host_tier is not None else 0,
            "host_tier_capacity_bytes": self._host_tier.capacity
            if self._host_tier is not None else 0,
            "preempt_swap_resumes": self._n_swap_resumes,
            "preempt_recompute_resumes": self._n_recompute_resumes,
            "prefill_rows_per_s_est": round(self._prefill_rows_s, 3),
            "host_xfer_bytes_per_s_est": round(self._xfer_bytes_s, 1),
            "requests_shed": self._n_shed,
            "requests_timed_out": self._n_timeout,
            "requests_cancelled": self._n_cancelled,
            # live-session migration (ISSUE 19): ALWAYS present (0 on
            # engines that never joined an elastic cluster) so
            # dashboards never KeyError across a mixed fleet
            "sessions_migrated_out": self._n_migrated_out,
            "sessions_migrated_in": self._n_migrated_in,
            # multi-LoRA keys: ALWAYS present (False/0 on base-model
            # or PADDLE_TPU_LORA=0 engines) so dashboards never
            # KeyError across a mixed or rolled-back fleet
            "lora_enabled": self._lora_on,
            "lora_adapters_resident": self._lora_pool.n_resident
            if self._lora_pool is not None else 0,
            "lora_adapter_swaps": self._lora_pool.swaps
            if self._lora_pool is not None else 0,
            "lora_host_tier_bytes": self._lora_pool.host_tier_bytes
            if self._lora_pool is not None else 0,
            "tp_degree": self._tp,
            # always present (0 / full pool when single-device), so a
            # tp_degree>1 request downgraded by the PADDLE_TPU_SERVE_TP=0
            # kill switch never KeyErrors stats() consumers mid-rollback
            "tp_collective_bytes_per_step": self._tp_step_bytes,
            "tp_collective_bytes_total": self._n_tp_bytes,
            "tp_pool_bytes_per_shard": self._pool_bytes_per_shard,
            # MoE keys are ALWAYS present (False/0.0 for dense models)
            # so dashboards and rollbacks never KeyError on a mixed
            # fleet
            "moe": self._moe,
            "moe_fused_gmm": self._moe_fused_traced,
            "moe_routing_entropy": self._moe_ent_last,
            "moe_expert_load_max": self._moe_load_max_last,
            "moe_dispatches": self._n_moe_dispatches,
            # a chip's expert-parallel share: live rows routed and the
            # pairs that fell on experts held here (both summed over
            # expert layers); the grouped-matmul kernel the tick
            # traced ("megablox" | "ragged_dot" | None); the bytes of
            # a latent (MLA) cache's pools (0 for k/v pairs)
            "moe_rows": self._n_moe_rows,
            "moe_pairs_local": self._n_moe_pairs_local,
            "moe_grouped_mm_kernel": self._moe_gmm_kernel,
            "latent_pool_bytes": self._latent_pool_bytes,
            # request-lifecycle tracing + SLO latency digests: ALWAYS
            # present (zeroed summaries on an idle engine; the digests
            # run regardless of the PADDLE_TPU_TRACE kill switch) —
            # each *_ms value is a P² digest summary {count, mean,
            # min, max, p50, p95, p99}
            "engine_id": self._engine_id,
            "tracing": self._trace is not None,
            "trace_events": len(self._trace)
            if self._trace is not None else 0,
            # ring-wrap loss accounting (ISSUE 15 satellite): events
            # the bounded PADDLE_TPU_TRACE_EVENTS ring overwrote —
            # the observer is no longer unobservable (0 when killed)
            "trace_events_dropped": self._trace.dropped
            if self._trace is not None else 0,
            # on-demand profiling windows: completed captures +
            # ticks left in an armed window (both 0 when idle/killed)
            "profile_captures": self._prof.captures,
            "profile_ticks_remaining": self._prof.pending,
            # per-tick roofline attribution (always present — an
            # un-ticked engine reports zeros; off the chip the
            # peak-derived fields are None)
            "roofline": self._roofline(),
            "ttft_ms": self._d_ttft.summary(),
            "itl_ms": self._d_itl.summary(),
            "queue_wait_ms": self._d_queue.summary(),
            "e2e_ms": self._d_e2e.summary(),
            # tree-speculation keys: ALWAYS present (zeroed digest /
            # 0 nodes on linear-spec and non-speculative engines) so
            # dashboards never KeyError across a mixed or
            # PADDLE_TPU_SPEC_TREE=0 rolled-back fleet.
            # spec_accept_len is the P² digest of tokens emitted per
            # slot verify window (accepted + bonus)
            "spec_accept_len": self._d_accept.summary(),
            "spec_tree_nodes": (len(self._spec_tree) + 1)
            if self._spec_tree is not None else 0,
            # fleet-health keys: ALWAYS present (1.0 score / zeros
            # under the PADDLE_TPU_HEALTH=0 kill switch) so
            # dashboards never KeyError across a mixed or rolled-back
            # fleet. alerts_firing is a COUNT here; the named set
            # lives in engine.health()["alerts_firing"].
            "health_score": self._health.score()
            if self._health is not None else 1.0,
            "alerts_firing": len(self._health.firing())
            if self._health is not None else 0,
            "alerts_fired_total": self._health.fired_total
            if self._health is not None else 0,
            "incidents_captured": self._health._incident.captured
            if self._health is not None
            and self._health._incident is not None else 0,
            "nonfinite_logits_ticks": self._nonfinite_ticks,
            # async-tick-pipeline keys: ALWAYS present. The depth is
            # the one the engine runs: 1, or 0 / 0 flushes on a
            # blocking engine (async_depth=0, the
            # PADDLE_TPU_ASYNC_TICK=0 kill switch, speculation);
            # host_gap_ms observes there too — its gap includes the
            # blocking fetch the pipeline removes
            "async_depth": self._async_depth,
            "pipeline_flushes": self._n_pipe_flushes,
            "host_gap_ms": self._d_host_gap.summary(),
        }
        if self._gamma:
            out.update({
                "spec_tokens_proposed": self._n_spec_proposed,
                "spec_tokens_accepted": self._n_spec_accepted,
                "spec_acceptance_rate":
                    self._n_spec_accepted / self._n_spec_proposed
                    if self._n_spec_proposed else 0.0,
                "spec_mean_accepted_len":
                    self._n_spec_emitted / self._n_spec_verifies
                    if self._n_spec_verifies else 0.0,
            })
        return out

    def health(self) -> Optional[dict]:
        """Health snapshot: score, firing alerts, burn rates, per-alert
        state, and the recent transition journal. None when the health
        engine is off (``health=False`` or ``PADDLE_TPU_HEALTH=0``)."""
        if self._health is None:
            return None
        return self._health.snapshot()

    def watchdog_stuck(self) -> bool:
        """Stuck-tick watchdog probe (the cluster sweep calls this
        between ticks): True when this engine's last completed
        non-compile tick blew the deadline ``max(floor, mult x
        step-EMA)``. Always False when the health engine is off."""
        if self._health is None:
            return False
        ema = self._step_time.get(
            "verify" if self._gamma else "decode", 0.0)
        return self._health.watchdog_check(ema)

    def shutdown(self, check_leaks: bool = True) -> bool:
        """Engine teardown hook (tests / graceful ops restarts):
        sweeps the allocator's invariants — every block must be exactly
        one of free, LRU-cached, or owned by a live slot, with a
        bijective hash index — raising RuntimeError on any leak or
        double-accounting. Call after draining (or at any quiescent
        point; live slots' blocks are passed as the expected live
        set). Requests still waiting in the admission queue are
        drained with a terminal queue-wait observation
        (outcome="shutdown") — they would otherwise leave no latency
        record at all."""
        self._flush_pipe()      # surface in-flight tokens first
        self._drain_spills()
        while self._queue:
            self._queue_exit(self._queue.popleft(), "shutdown")
        self._sync_cache_metrics()
        if self._trace is not None:
            # the spans outlive the engine: a post-mortem dump still
            # finds them in ``tracing.live_tracers()``
            _tracing.retire(self._trace)
        if check_leaks:
            live = [b for s in self._slots if s is not None
                    for b in s.blocks]
            self._alloc.check_leaks(live)
        return True

    # -- disaggregated prefill -> decode ------------------------------

    def published_overlap(self, hashes) -> int:
        """Leading run of ``hashes`` (``ops/paged_cache.
        prompt_block_hashes`` output, materialized once by the caller)
        present in this engine's content index — the cluster router's
        affinity probe: the replica with the longest run already holds
        that many of the prompt's KV blocks and will prefill only the
        suffix. 0 when the prefix cache is off (nothing to hit)."""
        if not self._prefix_on:
            return 0
        n = 0
        for h in hashes:
            if self._alloc.lookup(h) is None and not (
                    self._host_tier is not None
                    and ("pub", h) in self._host_tier):
                # host-tier entries count: a spilled published block
                # restores on admission, so the replica still serves
                # the prefix without re-prefilling it
                break
            n += 1
        return n

    def pop_prefilled(self) -> List[PrefilledRequest]:
        """Collect every prefill this role="prefill" engine finished
        since the last call: each parked slot's blocks are exported
        through the ONE fixed-width export executable into a
        self-contained :class:`PrefilledRequest` payload, the prompt's
        full blocks are published into the prefix index (the next turn
        of the same session prefills only its suffix HERE — what the
        router's affinity probe keys on), and the slot is freed for
        the next admission. The caller (``EngineCluster``) imports the
        payload into a decode replica via ``admit_prefilled()``."""
        if not self._handoff_ready:
            # slots park at a commit: nothing to hand over, nothing to
            # drain the pipeline for
            return []
        self._flush_pipe()      # commit in-flight ticks before mutating
        out = []
        for i in self._handoff_ready:
            slot = self._slots[i]
            payload = None
            if not self._stateful:
                ids = np.zeros(self._mb_xfer, np.int32)
                ids[:len(slot.blocks)] = slot.blocks
                ids_dev = self._dev(ids)
                if self._export_exec is None:
                    # pools are NOT donated: the blocks stay live until
                    # _release_handoff publishes + frees them
                    self._export_exec = self._aot_compile(
                        "export", jax.jit(_pc.export_blocks),
                        (self._pools, ids_dev))
                payload = self._export_exec(self._pools, ids_dev)
            self._n_handoffs += 1
            self._n_blocks_exported += len(slot.blocks)
            samp = self._slot_samp[i]
            fid = None
            if self._trace is not None:
                # flow START on the exporting slot: the matching
                # finish lands wherever admit_prefilled seats the
                # payload, so the merged trace draws the handoff as
                # an arrow across the two replicas' lanes
                fid = _tracing.next_flow_id()
                self._trace.flow(
                    "kv handoff", tid=1 + i, flow_id=fid, phase="s",
                    args={"rid": slot.rid,
                          "blocks": len(slot.blocks)})
            out.append(PrefilledRequest(
                request_id=slot.rid, prompt=slot.prompt,
                first_token=int(slot.last_token),
                max_new_tokens=slot.max_new,
                n_blocks=len(slot.blocks), payload=payload,
                temperature=float(samp[0]), top_k=float(samp[1]),
                top_p=float(samp[2]), priority=int(slot.priority),
                flow_id=fid, adapter_id=slot.adapter_id))
            self._release_handoff(i)
        self._handoff_ready = []
        return out

    def admit_prefilled(self, prefilled: PrefilledRequest):
        """Admit a prefill ANOTHER engine completed (the disaggregated
        decode side): allocate this pool's blocks, import the payload
        bytes at those ids through the ONE fixed-width import
        executable, and seat a decoding slot at ``cache_len ==
        len(prompt)`` with the prefill's first token as its last token
        — exactly the state a colocated engine holds after its own
        prefill, so greedy continuation is token-exact by construction
        (int8 payloads carry data + scales, so imported blocks
        dequantize bitwise). Returns the engine-local request id, or
        None when no slot / block capacity is available right now (the
        cluster keeps the handoff pending and retries next tick). No
        TTFT is observed here — the first token already streamed from
        the prefill engine; this request's later emits feed the ITL
        digest only."""
        self._flush_pipe()      # commit in-flight ticks before mutating
        prompt = np.asarray(prefilled.prompt, np.int32).reshape(-1)
        n_real = int(prompt.size)
        max_new = int(prefilled.max_new_tokens)
        if n_real + max_new > self.config.max_model_len:
            raise ValueError(
                f"prefilled prompt ({n_real}) + max_new_tokens "
                f"({max_new}) exceeds max_model_len "
                f"({self.config.max_model_len})")
        init = _pc.blocks_for(n_real, self._bs)
        if prefilled.n_blocks != init:
            raise ValueError(
                f"prefilled payload holds {prefilled.n_blocks} blocks "
                f"but a {n_real}-token prompt needs {init} at "
                f"block_size={self._bs} — exporter and importer must "
                "share the serving layout")
        free = [i for i, s in enumerate(self._slots) if s is None]
        if not free:
            return None
        worst = self._worst_for(n_real, max_new)
        if self._alloc.free_blocks - self._reserved < worst:
            return None
        aid = getattr(prefilled, "adapter_id", None)
        lrow = 0
        if aid is not None:
            # the payload's KV was computed under this adapter — the
            # decode replica must seat it under the SAME deltas
            if self._lora_pool is None:
                raise ValueError(
                    "prefilled handoff carries adapter_id "
                    f"{int(aid)} but this engine serves the base "
                    "model only (lora_rank=0 / PADDLE_TPU_LORA=0)")
            if not self._lora_pool.known(int(aid)):
                raise ValueError(
                    f"prefilled handoff carries unknown adapter_id "
                    f"{int(aid)}: load_adapter() it on the decode "
                    "replica (the cluster broadcasts registrations)")
            lrow = self._lora_pool.acquire(int(aid))
            if lrow is None:
                return None     # every row pinned; cluster retries
            self._sync_lora_metrics()
        i = free[0]
        self._slot_adapter[i] = lrow
        if prefilled.payload is None:
            return self._seat_prefilled_recompute(i, prefilled, prompt,
                                                  worst, aid)
        blocks = self._alloc.alloc(init)
        self._reserved += worst - len(blocks)
        ids = np.zeros(self._mb_xfer, np.int32)
        ids[:init] = blocks
        ids_dev = self._dev(ids)
        if self._import_exec is None:
            self._import_exec = self._aot_compile(
                "import",
                jax.jit(_pc.import_blocks, donate_argnums=(0,)),
                (self._pools, ids_dev, prefilled.payload))
        with _quiet_donation():
            self._pools = self._import_exec(self._pools, ids_dev,
                                            prefilled.payload)
        self._n_blocks_imported += init
        self._m_kv_transfer.inc(init)
        rid = self._next_rid
        self._next_rid += 1
        self._results[rid] = []
        self._tables[i, :] = 0
        self._tables[i, :init] = blocks
        self._tables_dev = None
        tok = int(prefilled.first_token)
        self._slots[i] = _Slot(
            rid, blocks, worst, n_real, tok, max_new,
            history=list(map(int, prompt)) + [tok],
            prompt=prompt, pend_pos=None)
        self._slots[i].priority = int(getattr(prefilled, "priority",
                                              0) or 0)
        self._slots[i].adapter_id = None if aid is None else int(aid)
        self._set_slot_samp(i, prefilled)
        self._m_occupancy.set(self.num_active)
        if self._trace is not None:
            self._trace.instant(
                "admit_prefilled", tid=1 + i,
                args={"rid": rid, "blocks": init,
                      "prompt_tokens": n_real})
            fid = getattr(prefilled, "flow_id", None)
            if fid:
                self._trace.flow("kv handoff", tid=1 + i,
                                 flow_id=int(fid), phase="f",
                                 args={"rid": rid})
        return rid

    def _seat_prefilled_recompute(self, i, prefilled, prompt, worst,
                                  aid):
        """A handoff with ``payload=None`` (a model with slot state:
        its blocks are no use without the state, which no payload
        carries): the prompt re-prefills here through the ordinary
        chunk machinery and ``_finish_prefill`` restores the
        continuation — the first token already streamed — as a
        preemption's recompute resume does."""
        n_real = int(prompt.size)
        tok = int(prefilled.first_token)
        blocks, cached = self._map_prefix(prompt, n_real, i)
        self._reserved += worst - len(blocks)
        rid = self._next_rid
        self._next_rid += 1
        self._results[rid] = []
        self._tables[i, :] = 0
        self._tables[i, :len(blocks)] = blocks
        self._tables_dev = None
        slot = _Slot(rid, blocks, worst, cached, None,
                     int(prefilled.max_new_tokens),
                     history=list(map(int, prompt)) + [tok],
                     prompt=prompt, pend_pos=cached)
        slot.resume = (tok, 1)
        slot.priority = int(getattr(prefilled, "priority", 0) or 0)
        slot.adapter_id = None if aid is None else int(aid)
        self._slots[i] = slot
        self._set_slot_samp(i, prefilled)
        self._m_occupancy.set(self.num_active)
        if self._trace is not None:
            self._trace.instant(
                "admit_prefilled", tid=1 + i,
                args={"rid": rid, "blocks": 0, "prompt_tokens": n_real})
        if self._alloc.is_shared(blocks[cached // self._bs]):
            self._cow(i, cached // self._bs)
        return rid

    def _release_handoff(self, i):
        """Free a handed-off slot WITHOUT completion accounting — the
        request is still live, on another engine. The prompt's full
        blocks are published first (multi-turn affinity: the session's
        next turn hits this engine's prefix cache), mirroring
        ``_retire``'s publish; e2e latency belongs to the cluster's
        client-side rollup, not this engine's digest."""
        slot = self._slots[i]
        now = time.monotonic()
        self._submit_t.pop(slot.rid, None)
        self._last_emit.pop(slot.rid, None)
        if self._trace is not None:
            self._trace.emit(
                f"req{slot.rid}", tid=1 + i, t0=slot.admit_t, t1=now,
                args={"tokens": slot.n_emitted,
                      "cache_len": slot.cache_len, "handoff": True})
            self._trace.instant("handoff", tid=1 + i,
                                args={"rid": slot.rid,
                                      "blocks": len(slot.blocks)})
        # cache position p holds history[p] for p < cache_len (the
        # sampled first token is NOT in the cache), so the publish
        # walk is _retire's
        self._publish_full(slot)
        self._alloc.free(slot.blocks)
        self._reserved -= slot.worst_blocks - len(slot.blocks)
        self._tables[i, :] = 0
        self._tables_dev = None
        self._slots[i] = None
        self._set_slot_samp(i)
        self._lora_release_slot(i, slot)
        self._results.pop(slot.rid, None)
        self._m_occupancy.set(self.num_active)

    def _worst_for(self, n_real, max_new) -> int:
        """Worst-case block reservation for one request. A
        role="prefill" engine reserves only the PROMPT's blocks — the
        first token's K/V is never written there (chunked prefill
        writes prompt positions only; decode happens on the importing
        replica), so the decode horizon (max_new + gamma) would only
        inflate admission pressure on the prefill tier."""
        if self._role == "prefill":
            return _pc.blocks_for(int(n_real), self._bs)
        return _pc.blocks_for(int(n_real) + int(max_new) + self._gamma,
                              self._bs)

    # -- live session migration (elastic fleet, ISSUE 19) --------------

    def export_session(self, i) -> MigratedSession:
        """Package slot ``i``'s LIVE session for another replica
        (scale-down drain / cluster rebalancing) and free the slot
        with NO terminal accounting — the request stays live; its
        stream continues wherever ``admit_migrated`` seats the record.
        A decoding slot ships its trimmed live bytes through THE
        fixed-width export executable (shared with the disaggregated
        handoff and the preemption spill — still zero extra
        executables); a mid-re-prefill slot (partial cache) ships
        ``payload=None`` and resumes by recompute on the target.
        Nothing is published locally: the session's prefix affinity
        must FOLLOW the KV to the target (``admit_migrated``
        republishes there), not linger on a replica that is going
        away."""
        self._flush_pipe()      # commit in-flight ticks before mutating
        self._drain_spills()
        slot = self._slots[i]
        self._slot_props.pop(i, None)
        samp_row = self._slot_samp[i].copy()
        if slot.handoff and i in self._handoff_ready:
            self._handoff_ready.remove(i)
        # trim the verify-window overhang: blocks past cache_len hold
        # rolled-back/garbage positions — same walk as _preempt, so
        # the payload is exactly the live bytes
        keep = max(_pc.blocks_for(slot.cache_len, self._bs), 1)
        while len(slot.blocks) > keep:
            blk = slot.blocks.pop()
            self._alloc.free([blk])
            self._tables[i, len(slot.blocks)] = 0
            self._reserved += 1
            self._tables_dev = None
        if slot.resume is not None:
            # mid-re-prefill: the ORIGINAL continuation carries over;
            # its partial KV cannot back a payload
            last_token, n_emitted = slot.resume
        else:
            last_token, n_emitted = slot.last_token, slot.n_emitted
        n_ctx = len(slot.history) - 1   # == cache_len for a decoding
        #                                 slot (the pending last_token
        #                                 is not in the cache)
        payload = None      # a stateful model's blocks are no use
        #                     without its slot state: recompute
        if slot.pend_pos is None and slot.blocks \
                and not self._stateful \
                and len(slot.blocks) <= self._mb_xfer:
            payload = _pc.payload_rows(
                self._export_payload(slot.blocks), len(slot.blocks))
        fid = None
        now = time.monotonic()
        if self._trace is not None:
            fid = _tracing.next_flow_id()
            self._trace.flow(
                "kv migrate", tid=1 + i, flow_id=fid, phase="s",
                args={"rid": slot.rid, "blocks": len(slot.blocks)})
            self._trace.emit(
                f"req{slot.rid}", tid=1 + i, t0=slot.admit_t, t1=now,
                args={"tokens": slot.n_emitted,
                      "cache_len": slot.cache_len, "migrated": True})
        rec = MigratedSession(
            request_id=slot.rid,
            prompt=np.asarray(slot.prompt, np.int32),
            history=list(map(int, slot.history)),
            cache_len=int(n_ctx), last_token=int(last_token),
            n_emitted=int(n_emitted),
            max_new_tokens=int(slot.max_new),
            worst_blocks=int(slot.worst_blocks),
            n_blocks=_pc.blocks_for(n_ctx, self._bs), payload=payload,
            temperature=float(samp_row[0]), top_k=float(samp_row[1]),
            top_p=float(samp_row[2]), priority=int(slot.priority),
            adapter_id=slot.adapter_id, flow_id=fid)
        self._alloc.free(slot.blocks)
        self._reserved -= slot.worst_blocks - len(slot.blocks)
        self._tables[i, :] = 0
        self._tables_dev = None
        self._slots[i] = None
        self._set_slot_samp(i)
        self._lora_release_slot(i, slot)
        self._submit_t.pop(slot.rid, None)
        self._last_emit.pop(slot.rid, None)
        self._slo_ok.pop(slot.rid, None)
        self._results.pop(slot.rid, None)
        self._n_migrated_out += 1
        self._m_occupancy.set(self.num_active)
        return rec

    def admit_migrated(self, rec: MigratedSession):
        """Seat a LIVE session ANOTHER replica exported: allocate
        blocks, import the payload bytes through THE fixed-width
        import executable, and seat a DECODING slot at the exact
        continuation point — cache_len, last token, emit count,
        history, sampling row, priority, adapter pin — so the resumed
        stream is token-exact vs never-migrated by construction (int8
        payloads carry data + per-row scales, bitwise like the
        handoff). ``payload=None`` seats the recompute path instead:
        the context re-prefills through the ordinary chunk machinery
        and ``_finish_prefill`` restores the continuation — still
        token-exact (it IS the preemption recompute resume). The
        session's full blocks are PUBLISHED here at import, so the
        router's prefix-affinity probe follows the KV to this replica
        (the source unpublished at export). Returns the engine-local
        rid, or None when no slot / block / adapter-row capacity is
        available right now (the cluster retries or tries another
        replica). No TTFT is observed — the session already
        streamed; later emits feed the ITL digest only."""
        if self._role == "prefill":
            raise ValueError(
                "a role='prefill' engine cannot seat a migrated "
                "session: migration targets must decode")
        self._flush_pipe()      # commit in-flight ticks before mutating
        n_ctx = int(rec.cache_len)
        history = list(map(int, rec.history))
        if len(history) > self.config.max_model_len:
            raise ValueError(
                f"migrated session history ({len(history)} tokens) "
                f"exceeds max_model_len ({self.config.max_model_len})"
                " — exporter and importer must share the serving "
                "layout")
        payload = rec.payload
        need = _pc.blocks_for(n_ctx, self._bs)
        if payload is not None and int(rec.n_blocks) != need:
            raise ValueError(
                f"migrated payload holds {rec.n_blocks} blocks but a "
                f"{n_ctx}-token cache needs {need} at block_size="
                f"{self._bs} — exporter and importer must share the "
                "serving layout")
        ctx = np.asarray(history[:n_ctx], np.int32)
        free = [i for i, s in enumerate(self._slots) if s is None]
        if not free:
            return None
        worst = int(rec.worst_blocks)
        if self._alloc.free_blocks - self._reserved < worst:
            return None
        aid = rec.adapter_id
        lrow = 0
        if aid is not None:
            if self._lora_pool is None:
                raise ValueError(
                    f"migrated session carries adapter_id {int(aid)} "
                    "but this engine serves the base model only "
                    "(lora_rank=0 / PADDLE_TPU_LORA=0)")
            if not self._lora_pool.known(int(aid)):
                raise ValueError(
                    "migrated session carries unknown adapter_id "
                    f"{int(aid)}: load_adapter() it on the target "
                    "(the cluster broadcasts registrations)")
            lrow = self._lora_pool.acquire(int(aid))
            if lrow is None:
                return None     # every row pinned; caller retries
            self._sync_lora_metrics()
        i = free[0]
        self._slot_adapter[i] = lrow
        rid = self._next_rid
        self._next_rid += 1
        self._results[rid] = []
        if payload is not None:
            n_blocks = int(rec.n_blocks)
            blocks = self._alloc.alloc(n_blocks)
            self._import_payload(blocks, payload)
            self._n_blocks_imported += n_blocks
            self._m_kv_transfer.inc(n_blocks)
            self._reserved += worst - n_blocks
            self._tables[i, :] = 0
            self._tables[i, :n_blocks] = blocks
            self._tables_dev = None
            slot = _Slot(rid, blocks, worst, n_ctx,
                         int(rec.last_token),
                         int(rec.max_new_tokens),
                         history=list(history), prompt=ctx,
                         pend_pos=None)
            slot.n_emitted = int(rec.n_emitted)
            # publish the session's full blocks NOW: the prefix
            # affinity that pointed at the source must resolve HERE
            # from the next router probe on (positions < cache_len
            # are committed — decode appends never write a published
            # block, same invariant as _retire's publish-then-free)
            self._publish_full(slot)
            mode = "swap"
        else:
            blocks, cached = self._map_prefix(ctx, n_ctx, i)
            self._reserved += worst - len(blocks)
            self._tables[i, :] = 0
            self._tables[i, :len(blocks)] = blocks
            self._tables_dev = None
            slot = _Slot(rid, blocks, worst, cached, None,
                         int(rec.max_new_tokens),
                         history=list(history), prompt=ctx,
                         pend_pos=cached)
            slot.resume = (int(rec.last_token), int(rec.n_emitted))
            mode = "recompute"
        slot.priority = int(rec.priority)
        slot.adapter_id = None if aid is None else int(aid)
        self._slots[i] = slot
        self._set_slot_samp(i, rec)
        self._n_migrated_in += 1
        self._m_occupancy.set(self.num_active)
        if self._trace is not None:
            self._trace.instant(
                "admit_migrated", tid=1 + i,
                args={"rid": rid, "cache_len": n_ctx, "mode": mode})
            if rec.flow_id:
                self._trace.flow("kv migrate", tid=1 + i,
                                 flow_id=int(rec.flow_id), phase="f",
                                 args={"rid": rid})
        if mode != "swap":
            # shared suffix-boundary block: COW before the recomputed
            # tail writes into it (same as _seat_resume's path)
            bidx = cached // self._bs
            if self._alloc.is_shared(blocks[bidx]):
                self._cow(i, bidx)
        return rid

    def drain_sessions(self):
        """Drain this engine for a scale-down: every RESIDENT session
        leaves as a :class:`MigratedSession` (live-migrated — the
        client's stream continues on the target, token-exact), every
        queued-but-unserved request comes back as its ServingRequest
        for plain re-routing, and the engine ends empty. Preempted
        queue residents (resume-carrying) migrate too, shipping their
        host-tier spill payload when one survives (a missing payload
        degrades to the recompute path on the target — correctness
        never depends on the tier). Mid-prefill slots that have
        streamed nothing are preempted back to the queue first (there
        is nothing to move) and leave as fresh requests. Parked
        handoff slots are NOT drained here — collect them with
        ``pop_prefilled()`` first; their payloads are self-contained.
        Queue exits observe outcome="migrated". Returns
        ``(migrations, fresh_requests)``."""
        self._flush_pipe()      # commit in-flight ticks before mutating
        for i, slot in enumerate(self._slots):
            if slot is None or slot.handoff:
                continue
            if slot.pend_pos is not None and slot.resume is None:
                # streamed nothing yet: cheaper to re-prefill on the
                # target than to move a partial cache (counts as a
                # preemption; the published blocks are purged by the
                # caller, so the warm-start publish is moot here)
                self._preempt(i)
        migrations, fresh = [], []
        while self._queue:
            req = self._queue.popleft()
            self._queue_exit(req, "migrated")
            if req.resume is not None:
                migrations.append(self._migrate_queued(req))
            else:
                fresh.append(req)
        for i, slot in enumerate(self._slots):
            if slot is not None and not slot.handoff:
                migrations.append(self.export_session(i))
        return migrations, fresh

    def _migrate_queued(self, req) -> MigratedSession:
        """A PREEMPTED request still waiting to resume leaves the
        queue as a migration record: its continuation state rides the
        resume dict, its KV rides the host-tier spill payload (when
        one survives — otherwise the target recomputes from
        history)."""
        r = req.resume
        rid = req.request_id
        payload = None
        if self._host_tier is not None and r.get("key") is not None:
            payload = self._host_tier.get(r["key"])
            self._host_tier.pop(r["key"], restore=False)
            self._m_host_bytes.set(self._host_tier.bytes_used)
        self._last_emit.pop(rid, None)
        self._slo_ok.pop(rid, None)
        self._results.pop(rid, None)
        self._n_migrated_out += 1
        return MigratedSession(
            request_id=rid, prompt=np.asarray(req.prompt, np.int32),
            history=list(map(int, r["history"])),
            cache_len=int(r["cache_len"]),
            last_token=int(r["last_token"]),
            n_emitted=int(r["n_emitted"]),
            max_new_tokens=int(req.max_new_tokens),
            worst_blocks=int(r["worst_blocks"]),
            n_blocks=int(r["n_blocks"]), payload=payload,
            temperature=req.temperature, top_k=req.top_k,
            top_p=req.top_p, priority=int(req.priority),
            adapter_id=req.adapter_id)

    def shed_queued(self, n: int) -> list:
        """Pop up to ``n`` queued-but-unserved FRESH requests (newest
        first — the oldest waiters keep their place) for the cluster
        to re-route after a scale-up: without this, new capacity only
        absorbs future arrivals while the burst that triggered the
        scale keeps queueing here. Preempted resume-carrying waiters
        are skipped — their KV lives on this replica. Queue exits
        observe outcome="migrated", same as a scale-down drain."""
        out, keep = [], []
        while self._queue and len(out) < int(n):
            req = self._queue.pop()
            if req.resume is not None:
                keep.append(req)
                continue
            self._queue_exit(req, "migrated")
            out.append(req)
        while keep:
            self._queue.append(keep.pop())
        return out

    def purge_published(self) -> int:
        """Wipe this engine's prefix-affinity surface — the
        allocator's content index AND the host tier's published-block
        spill entries — so ``published_overlap()`` scores 0 from now
        on. Called when a replica drains (scale-down) or fails: the
        router must never again steer a multi-turn session at KV this
        replica no longer serves. Returns the number of index entries
        dropped."""
        n = self._alloc.unpublish_all()
        self._state_snaps.clear()
        if self._host_tier is not None:
            self._drain_spills()
            n += self._host_tier.purge_published()
            self._m_host_bytes.set(self._host_tier.bytes_used)
        self._sync_cache_metrics()
        return n

    def warm_migration(self):
        """Pre-build the export/import executable pair off the hot
        path (scale-up warm): one null-block round trip, so the first
        real migration or handoff on this replica compiles nothing —
        the zero-steady-state-recompile pin holds across scale
        cycles. Where evictions spill, their one-block gather is built
        here too."""
        if self._stateful:
            # no payload ever leaves or enters (recompute); what a
            # prefix hit needs instead is the snapshot pair: seat 0's
            # state read and written back, which changes nothing
            if self._prefix_on:
                self._flush_pipe()
                hits = self._n_state_snap_hits
                self._seat_state(0, self._read_state(0))
                self._n_state_snap_hits = hits
            return
        payload = _pc.payload_rows(self._export_payload([]), 0)
        if self._role != "prefill":
            self._import_payload([], payload)
        if self._alloc.on_evict is not None and self._spill_exec is None:
            self._compile_spill()

    # -- tracing ------------------------------------------------------

    @property
    def tracer(self):
        """This engine's span tracer, or None when tracing is disabled
        (``PADDLE_TPU_TRACE=0``)."""
        return self._trace

    def dump_trace(self, path: str):
        """Write this engine's request-lifecycle trace as Chrome
        trace-event JSON (load it at https://ui.perfetto.dev or
        chrome://tracing). Returns the path written, or None when
        tracing is disabled."""
        if self._trace is None:
            return None
        return self._trace.dump_chrome_trace(path)

    # thin alias: the fingerprint (and the prompt -> block-hash walk
    # seeded by it) lives in ops/paged_cache so the cluster router and
    # engine admission hash IDENTICALLY — see model_fingerprint /
    # prompt_block_hashes there
    _model_fingerprint = staticmethod(_pc.model_fingerprint)

    # -- tensor parallelism -------------------------------------------

    def _init_caches(self, mdl, nb):
        """Per-layer paged pools. The ``sharding``/``kv_cache_dtype``
        kwargs are passed only when needed (TP / int8), and
        ``num_slots`` only to a model that declares slot state
        (``paged_slot_state``), so duck-typed
        models implementing the pre-TP two-argument
        ``init_paged_caches(num_blocks, block_size)`` protocol keep
        working on the default path."""
        kw = {}
        if self._pool_sharding is not None:
            kw["sharding"] = self._pool_sharding
        if self._kv_dtype is not None:
            kw["kv_cache_dtype"] = self._kv_dtype
        if getattr(mdl, "paged_slot_state", False):
            kw["num_slots"] = self.config.num_slots
        return mdl.init_paged_caches(nb, self._bs, **kw)

    @staticmethod
    def _build_tp_mesh(model, draft_model, tp: int) -> Mesh:
        """Validate ``tp_degree`` against the device count and BOTH
        models' head/vocab divisibility — a clear error here instead of
        a shape crash inside shard_map tracing — then build the serving
        mesh: the first ``tp`` devices on one ``mp`` axis."""
        devs = jax.devices()
        if tp > len(devs):
            raise ValueError(
                f"tp_degree={tp} needs {tp} devices, but only "
                f"{len(devs)} are visible")
        for mdl, who in ((model, "model"), (draft_model, "draft model")):
            if mdl is None:
                continue
            c = getattr(mdl, "config", None)
            h = getattr(c, "num_attention_heads", None)
            hkv = getattr(c, "num_key_value_heads", None) or h
            v = getattr(c, "vocab_size", None)
            if hkv is not None and hkv % tp:
                ok = [d for d in range(1, hkv + 1) if hkv % d == 0]
                raise ValueError(
                    f"tp_degree={tp} does not divide the {who}'s "
                    f"num_kv_heads={hkv}: the KV block pool is sharded "
                    f"on the kv_heads dim, so tp_degree must divide it "
                    f"(valid degrees for this model: {ok})")
            if h is not None and h % tp:
                raise ValueError(
                    f"tp_degree={tp} does not divide the {who}'s "
                    f"num_attention_heads={h}")
            if v is not None and v % tp:
                raise ValueError(
                    f"tp_degree={tp} does not divide the {who}'s "
                    f"vocab_size={v} (the logits all_gather needs an "
                    f"even vocab split)")
            # MoE: the stacked expert weights shard their ffn dim over
            # mp (gate_up [e, d, 2f] / down [e, f, d] PartitionSpecs),
            # so the per-expert width must split evenly — reject here,
            # before any compile, instead of silently replicating the
            # largest parameter group in the model
            f = getattr(c, "moe_intermediate_size", None)
            if _num_experts(c) and f is not None and f % tp:
                ok = [d_ for d_ in range(1, 17) if f % d_ == 0]
                raise ValueError(
                    f"tp_degree={tp} does not divide the {who}'s "
                    f"moe_intermediate_size={f}: the stacked expert "
                    f"gate_up/down projections shard their ffn dim "
                    f"over mp (valid degrees for this model: {ok})")
        return Mesh(np.array(devs[:tp]), ("mp",))

    def _shard_params(self, binder):
        """Place every parameter under the engine mesh: params carrying
        an ``mp`` PartitionSpec (the models' Column/Row-parallel linears
        and vocab-parallel embeddings annotate these at construction)
        shard along it; everything else — norms, biases without specs,
        int8 weights/scales from ``quantize_for_inference`` — is
        replicated. The serving mesh has ONLY the ``mp`` axis, so spec
        dims naming foreign fleet axes (``dp``/``sharding``/expert
        axes, e.g. a model previously placed by stage-3 sharding)
        replicate on that dim instead of crashing NamedSharding; a
        ``mp`` dim that does not divide ``tp`` falls back to fully
        replicated (correct, just not memory-split)."""
        out = []
        from ..framework.core import as_jax
        for _, p in binder.param_items:
            arr = as_jax(p)
            spec = getattr(p, "dist_spec", None)
            pspec = None
            if spec is not None:
                dims = []
                for dim, names in enumerate(spec):
                    axes = names if isinstance(names, tuple) \
                        else (names,)
                    if "mp" in axes:
                        if arr.shape[dim] % self._tp:
                            dims = None
                            break
                        dims.append("mp")
                    else:
                        dims.append(None)
                if dims is not None:
                    pspec = P(*dims)
            if pspec is None:
                pspec = P()
            out.append(jax.device_put(
                arr, NamedSharding(self._mesh, pspec)))
        return out

    def _dev(self, x):
        """Committed device operand: under TP every scheduler-produced
        array (tables, lengths, token ids, PRNG keys, COW indices) must
        be explicitly replicated across the mesh — compiled executables
        are strict about input shardings; single-device engines keep the
        plain ``asarray``. ``device_put`` takes host arrays directly, so
        the per-token hot path pays ONE transfer, not asarray + reshard."""
        if self._mesh is None:
            return jnp.asarray(x)
        return jax.device_put(
            x, NamedSharding(self._mesh, P(*([None] * np.ndim(x)))))

    def _gather_logits(self, logits):
        """THE step's explicit cross-shard collective: all_gather the
        vocab-sharded logits over ``mp`` so sampling sees the full
        replicated row on every shard (bitwise the same concatenation
        of per-shard columns the single-device matmul produces).
        Identity when TP is off — the single-device path traces
        unchanged."""
        if self._mesh is None:
            return logits
        nd = logits.ndim
        spec = P(*([None] * (nd - 1) + ["mp"]))
        logits = jax.lax.with_sharding_constraint(
            logits, NamedSharding(self._mesh, spec))
        gather = jax.shard_map(
            lambda x: jax.lax.all_gather(x, "mp", axis=nd - 1,
                                         tiled=True),
            mesh=self._mesh, in_specs=(spec,),
            out_specs=P(*([None] * nd)), check_vma=False)
        return gather(logits)

    @contextlib.contextmanager
    def _trace_ctx(self):
        """Tracing context for every ``_compile_*``: arm the fused
        decode-tick scope (``ops/pallas/decode_fused`` — thread-local
        like ``serving_tp_scope``, so only THIS engine's traces route
        through the fused kernels), and under TP activate the engine's
        mesh (the TP layers' sharding constraints and the shard_map
        attention wrapper read the global mesh at trace time) and
        un-gather the lm_head so logits leave the model vocab-sharded
        — ``_gather_logits`` is then the step's ONE explicit logits
        collective instead of a gather/re-shard pair. Everything is
        restored on exit, so nothing leaks into other code. Every
        trace also runs under ``executable_scopes()``: the model's
        ``component`` scopes are entered only here, and
        ``component_map()`` reads them back."""
        if self._mesh is None:
            with self._df.fused_decode_scope(self._fused_mode), \
                    executable_scopes():
                yield
            return
        from ..distributed import env as _denv
        prev = _denv.get_mesh()
        heads = []
        for mdl in (self.model, self._draft_model):
            head = getattr(mdl, "lm_head", None) \
                if mdl is not None else None
            if head is not None and getattr(head, "gather_output",
                                            False):
                heads.append(head)
                head.gather_output = False
        from ..ops.pallas.paged_attention import serving_tp_scope
        _denv.set_mesh(self._mesh)
        try:
            # the fused scope is armed even under TP: serving_tp_active
            # folds into fused_decode_mode(), which reports "off" there
            # (an opaque pallas_call cannot be GSPMD-partitioned)
            with serving_tp_scope(), \
                    self._df.fused_decode_scope(self._fused_mode), \
                    executable_scopes():
                yield
        finally:
            _denv.set_mesh(prev)
            for head in heads:
                head.gather_output = True

    def _aot_compile(self, name, jitted, args):
        """Lower + AOT-compile one serving executable. Under TP the
        traced jaxpr is also walked for the collective census (PR 2's
        ``monitor.collective_census``): explicit shard_map collectives
        appear as op rows with per-shard payload bytes; GSPMD-inserted
        ones only materialize post-partitioning and are proxied by the
        ``sharding_constraint`` row. The decode/verify census feeds the
        per-step collective-bytes counter. Every executable the engine
        ever builds flows through here, so ``executables_compiled`` in
        ``stats()`` is exact."""
        self._n_exec_compiled += 1
        tap = _moe.serving_stats_tap(self._observe_moe_routing) \
            if self._moe_tap_on else contextlib.nullcontext()
        try:
            with self._trace_ctx(), _quiet_donation(), tap:
                traced = jitted.trace(*args)
                exec_ = traced.lower().compile()
                if self._mesh is not None:
                    self._census[name] = monitor.collective_census(
                        traced.jaxpr)
                kc = monitor.kernel_census(compiled=exec_,
                                           jaxpr=traced.jaxpr)
                # which component of the model each instruction came
                # from: kept beside the census, and with the tracer
                # (outside its ring), so it outlives the engine with
                # the spans and stands in dump_trace()'s file
                self._cmap[name] = kc.pop("hlo_components", [])
                if self._trace is not None:
                    self._trace.annotate("component_map",
                                         {name: self._cmap[name]})
                self._kcensus[name] = kc
                # roofline static half: the executable's cost-model
                # FLOPs + HBM bytes (per-tick MFU / bandwidth
                # utilization divide these by the measured step time)
                cost = monitor.executable_cost(exec_)
                if cost:
                    self._exec_cost[name] = cost
                if name in ("decode", "verify"):
                    # THE tick executable: the headline fusion metric
                    self._m_kernels.set(kc.get("hlo_kernels", 0))
                return exec_
        finally:
            # which grouped kernel the trace just stamped: the honest
            # source for stats()['moe_fused_gmm'] (env/config/backend/
            # shape gates all folded in by construction)
            if self._moe and \
                    _moe.MOE_STATS["grouped_mm_kernel"] == "fused_gmm":
                self._moe_fused_traced = True

    def _observe_moe_routing(self, load, entropy):
        """Run-time sink of the MoE routing tap (armed around every
        executable trace): fed the per-expert load fractions and raw
        routing entropy of each dispatch the compiled step executes.
        Mirrors into the monitor registry AND the per-engine fields
        ``stats()`` reports."""
        load = np.asarray(load)
        e = max(int(load.size), 2)
        self._m_moe_load.observe_many(load)
        ent = float(entropy) / float(np.log(e))
        self._m_moe_entropy.set(ent)
        self._moe_ent_last = ent
        self._moe_load_max_last = float(load.max())
        self._n_moe_dispatches += 1

    def collective_census(self) -> dict:
        """Per-executable jaxpr collective census (TP engines only):
        ``{exec_name: [{op, axis, count, bytes}, ...]}`` — the ops
        dashboard / test hook behind the "exactly one logits gather
        per step" assertion."""
        return dict(self._census)

    def kernel_census(self) -> dict:
        """Per-executable kernel census
        (``monitor.kernel_census`` — optimized-HLO entry instruction
        counts + the jaxpr-level launch proxy): ``{exec_name:
        {hlo_kernels, hlo_fusions, hlo_custom_calls, launch_proxy,
        ...}}``. The decode-tick fusion headline ("kernel count per
        decode layer down") is read off the ``decode``/``verify``
        row — measured on every engine, every compile."""
        return dict(self._kcensus)

    def component_map(self) -> dict:
        """Per-executable component map (``monitor.component_map``,
        from the same read of the compiled text as the census):
        ``{exec_name: [{name, opcode, shape, bytes, component, layer,
        also}, ...]}`` — which part of the model each instruction of
        the executable came from, so a device trace's events (named by
        instruction) can be summed by component. Built at compile
        time, with tracing on or off."""
        return dict(self._cmap)

    def _tp_census_bytes(self, name) -> int:
        """Explicit per-shard ``mp`` collective payload of one
        execution of ``name`` (the census-derived per-step cost)."""
        return sum(
            r["bytes"] for r in self._census.get(name, ())
            if r["op"] != "sharding_constraint"
            and "mp" in r["axis"].split(","))

    # -- scheduler internals ------------------------------------------

    def _emit(self, rid, tok):
        """Single exit point for generated tokens (prefill's first token
        AND every decode token) — the token counters and the TTFT /
        inter-token digests live here so they agree exactly with what
        clients receive."""
        now = time.monotonic()
        prev = self._last_emit.get(rid)
        if prev is None:                # this request's FIRST token
            t0 = self._submit_t.get(rid)
            if t0 is not None:
                ttft_ms = 1000.0 * (now - t0)
                self._d_ttft.observe(ttft_ms)
                if self._health is not None:
                    self._slo_ok[rid] = ttft_ms <= self._h_slo_ttft
        else:
            itl_ms = 1000.0 * (now - prev)
            self._d_itl.observe(itl_ms)
            if self._health is not None and itl_ms > self._h_slo_itl:
                self._slo_ok[rid] = False
        self._last_emit[rid] = now
        self._results[rid].append(tok)
        self._m_tokens.inc()
        self._n_tokens += 1
        if self._stream is not None:
            self._stream(rid, tok)

    def _set_slot_samp(self, i, req=None):
        """Seat slot ``i``'s row of the per-slot sampling tensor:
        the engine defaults overlaid with the request's overrides
        (``req`` may be a ServingRequest or a PrefilledRequest — both
        carry the three optional fields). The device mirror is
        invalidated only when the row actually changes, so steady
        uniform traffic re-uploads nothing."""
        row = self._samp_default.copy()
        if req is not None:
            if getattr(req, "temperature", None) is not None:
                row[0] = float(req.temperature)
            if getattr(req, "top_k", None) is not None:
                row[1] = float(req.top_k)
            if getattr(req, "top_p", None) is not None:
                row[2] = float(req.top_p)
        if not np.array_equal(self._slot_samp[i], row):
            self._slot_samp[i] = row
            self._samp_dev = None

    def _samp_operand(self):
        """The [num_slots, 3] per-slot sampling tensor, uploaded only
        after a change (the ``_tables_dev`` pattern)."""
        if self._samp_dev is None:
            self._samp_dev = self._dev(self._slot_samp.copy())
        return self._samp_dev

    def _lora_operand(self):
        """Device image of the stacked adapter weights, re-uploaded
        only when the pool version moved (register/LRU load rewrote a
        stack row — the ``_samp_dev`` invalidation pattern). Runtime
        OPERAND, never a closure capture: baking the stacks into the
        trace would turn every adapter churn into a recompile."""
        pool = self._lora_pool
        if self._lora_dev is None \
                or self._lora_dev_version != pool.version:
            self._lora_dev = jax.tree_util.tree_map(
                lambda a: self._dev(np.array(a)), pool.operand())
            self._lora_dev_version = pool.version
        return self._lora_dev

    def _lora_release_slot(self, i, slot):
        """Unpin slot ``i``'s adapter when the slot empties (retire /
        cancel / preempt / handoff-release). The adapter STAYS
        resident — release only drops the refcount that was blocking
        LRU eviction."""
        self._slot_adapter[i] = 0
        if self._lora_pool is not None \
                and getattr(slot, "adapter_id", None) is not None:
            self._lora_pool.release(slot.adapter_id)
            self._sync_lora_metrics()

    def _sync_lora_metrics(self):
        pool = self._lora_pool
        if pool is None:
            return
        self._m_lora_resident.set(pool.n_resident)
        self._m_lora_host.set(pool.host_tier_bytes)
        d = pool.swaps - self._lora_swaps_seen
        if d > 0:
            self._m_lora_swaps.inc(d)
            self._lora_swaps_seen = pool.swaps

    def _select_rows(self, lg, key, samp):
        """Per-slot token selection: ``samp``'s trailing axis is
        (temperature, top_k, top_p) — traced DATA through the shared
        ``_filter_logits`` pipeline, so every sampling config rides
        one executable. ``lg``: [S, V] (or any leading shape samp
        broadcasts over); greedy engines argmax and never read
        ``samp``."""
        return self._select_token(
            lg, key, do_sample=self._do_sample,
            temperature=samp[..., 0], top_k=samp[..., 1],
            top_p=samp[..., 2])

    def _next_key(self):
        """Greedy decode never consumes randomness — skip the per-step
        split (one device dispatch per token saved). Under TP the key
        (and every split of it) stays replicated across shards: all
        shards draw the same sample from the same gathered logits."""
        if not self._do_sample:
            return self._key
        self._key, sub = jax.random.split(self._key)
        if self._mesh is not None:
            self._key = self._dev(self._key)
            sub = self._dev(sub)
        return sub

    def _admit(self) -> List[tuple]:
        emitted = []
        self._expire_queue()
        while self._queue:
            k = self._pick_next_idx()
            req = self._queue[k]
            free = [i for i, s in enumerate(self._slots) if s is None]
            if not free:
                # slot-pressure preemption: a strictly-lower-priority
                # victim yields its slot to the waiting request
                # (blocks published + spilled, victim requeued at the
                # front of ITS class)
                if not self._preempt_on:
                    break
                v = self._pick_victim(below=req.priority)
                if v is None:
                    break
                if self._settle():
                    continue
                self._preempt(v)
                free = [v]
            if not self._admission_fits(req):
                break
            lrow = 0
            if self._lora_pool is not None \
                    and req.adapter_id is not None:
                # pin the adapter's resident stack row for the life of
                # the slot (refcount blocks LRU eviction mid-request);
                # all rows pinned by OTHER in-flight adapters -> the
                # request waits its turn in the queue
                lrow = self._lora_pool.acquire(req.adapter_id)
                if lrow is None:
                    break
                self._sync_lora_metrics()
            # remove by IDENTITY: a preemption above appendleft'ed the
            # victim's resume request, shifting every index right —
            # ``k`` may no longer point at ``req``
            for k2, r2 in enumerate(self._queue):
                if r2 is req:
                    del self._queue[k2]
                    break
            i = free[0]
            self._slot_adapter[i] = lrow
            if req.resume is not None:
                # a preempted request re-admits through its own seat
                # path (swap-restore or recompute re-prefill)
                self._seat_resume(i, req, emitted)
                self._slots[i].adapter_id = req.adapter_id
                continue
            n_real = int(req.prompt.size)
            worst = self._worst_for(n_real, req.max_new_tokens)
            blocks, cached = self._map_prefix(req.prompt, n_real, i)
            self._reserved += worst - len(blocks)
            self._tables[i, :] = 0
            self._tables[i, :len(blocks)] = blocks
            self._tables_dev = None
            # observe BEFORE prefill so the histogram measures queue
            # wait, not prefill execution/compile time
            self._queue_exit(req, "admitted")
            self._results[req.request_id] = []
            self._slots[i] = _Slot(
                req.request_id, blocks, worst, cached, None,
                req.max_new_tokens,
                history=list(map(int, req.prompt)),
                prompt=np.asarray(req.prompt, np.int32),
                pend_pos=cached)
            self._slots[i].priority = int(req.priority)
            self._slots[i].adapter_id = req.adapter_id
            self._set_slot_samp(i, req)
            self._m_occupancy.set(self.num_active)
            if self._trace is not None:
                self._trace.instant(
                    "admit", tid=1 + i,
                    args={"rid": req.request_id,
                          "prefix_hit": cached > 0,
                          "cached_tokens": int(cached),
                          "prompt_tokens": n_real})
            # a shared suffix-boundary block (full-prompt cache hit)
            # must be copy-on-write duplicated before the recomputed
            # last token's K/V lands in it; the prompt's rows then
            # ride the next ticks
            bidx = cached // self._bs
            if self._alloc.is_shared(blocks[bidx]):
                self._cow(i, bidx)
        self._sync_cache_metrics()
        return emitted

    # -- preemptive scheduling + host-DRAM KV tier --------------------

    def _pick_next_idx(self) -> int:
        """Queue position to admit next: highest priority class first,
        FIFO within a class (stable max — the leftmost of the winning
        class; a preempted request re-enters via ``appendleft``, so it
        leads its class). Plain FIFO when preemption is off."""
        if not self._preempt_on or len(self._queue) < 2:
            return 0
        best, bp = 0, self._queue[0].priority
        for k in range(1, len(self._queue)):
            p = self._queue[k].priority
            if p > bp:
                best, bp = k, p
        return best

    def _expire_queue(self):
        """Queue-wait timeouts: requests queued past their
        ``max_queue_wait_ms`` exit with outcome="timeout" and an empty
        result (the stream never started). Preempted requests are
        exempt — they already streamed tokens; timing them out
        mid-stream would truncate a live response."""
        if not any(r.max_queue_wait_ms is not None
                   for r in self._queue):
            return
        now = time.monotonic()
        kept = deque()
        for r in self._queue:
            w = r.max_queue_wait_ms
            if w is not None and r.resume is None \
                    and 1000.0 * (now - r.submit_time) > float(w):
                self._n_timeout += 1
                self._queue_exit(r, "timeout")
                self._finish_unserved(r, record_empty=True)
            else:
                kept.append(r)
        self._queue = kept

    def _admission_fits(self, req) -> bool:
        """Admission block policy. The worst-case reservation check
        (prompt + max_new + gamma covered for EVERY active slot) stays
        the first gate — when the pool is ample, behavior is identical
        to the pre-preemption scheduler. When it fails and the
        preemptive scheduler is on, the WATERMARK policy may overcommit:
        admit on the immediately-needed blocks plus
        ``admission_watermark_blocks`` of growth headroom, preempting
        strictly-lower-priority victims to reach it — growth past the
        headroom is reclaimed by preemption against the host tier.
        Resume re-admissions need only their restored block set (their
        reservation was already granted once)."""
        if req.resume is not None:
            need, target = int(req.resume["n_blocks"]), 0
        else:
            n_real = int(req.prompt.size)
            worst = self._worst_for(n_real, req.max_new_tokens)
            if self._alloc.free_blocks - self._reserved >= worst:
                return True
            if not self._preempt_on:
                return False
            need = _pc.blocks_for(n_real, self._bs)
            target = self._watermark
        while self._alloc.free_blocks - need < target:
            v = self._pick_victim(below=req.priority)
            if v is None:
                break
            if self._settle():
                continue
            self._preempt(v)
        return self._alloc.free_blocks - need >= target

    def _pick_victim(self, below=None, exclude=()):
        """Victim policy: the lowest priority class loses first;
        within a class MID-PREFILL slots lose before decoding ones
        (they have streamed nothing yet — preempting them costs no
        client-visible stall, and their full blocks publish into the
        prefix index so the re-prefill is mostly a cache hit), then
        the most recently admitted slot (LIFO — the oldest resident
        keeps its progress, which is what bounds thrash).
        Parked-handoff slots are never victims. ``below`` restricts to
        strictly-lower classes (slot/admission preemption);
        ``exclude`` keeps a growing slot from victimizing itself."""
        cands = [i for i, s in enumerate(self._slots)
                 if s is not None and not s.handoff
                 and i not in exclude
                 and (below is None or s.priority < below)]
        if not cands:
            return None
        return min(cands, key=lambda i: (
            self._slots[i].priority,
            0 if self._slots[i].pend_pos is not None else 1,
            -self._slots[i].admit_t))

    def _alloc_with_preempt(self, n, exclude=(), below=None):
        """Allocate ``n`` blocks, preempting victims under pool
        pressure (preemptive scheduler only; lowest class first,
        optionally bounded by ``below``). Raises like ``alloc`` when
        even preemption cannot cover the demand."""
        if self._preempt_on:
            while self._alloc.free_blocks < n:
                v = self._pick_victim(below=below, exclude=exclude)
                if v is None:
                    break
                if self._settle():
                    continue
                self._preempt(v)
        return self._alloc.alloc(n)

    def _preempt(self, i):
        """Preempt slot ``i``: trim the verify-window overhang, publish
        the full blocks into the prefix index (the recompute path's
        warm start), spill the live bytes to the host-DRAM tier (the
        swap path), free everything, and re-enqueue the request at the
        FRONT of its priority class carrying the exact continuation
        state (cache_len / last_token / n_emitted / history / sampling
        row) — resume is token-exact by construction on either
        path."""
        self._flush_pipe()      # no-op from inside a tick: its callers
        #                         ``_settle()`` before they pick a victim
        slot = self._slots[i]
        self._slot_props.pop(i, None)
        samp_row = self._slot_samp[i].copy()
        # a mid-prefill slot is "pending" ONLY when it carries no
        # continuation: a previously-preempted request re-prefilling
        # its context (slot.resume set) must keep that continuation —
        # treating it as fresh would reset n_emitted and overrun the
        # client's stream past max_new
        pending = slot.pend_pos is not None and slot.resume is None
        # 1) blocks past cache_len hold rolled-back/garbage positions
        # (or not-yet-prefilled prompt room, for a mid-prefill victim)
        # — return them first so the spill payload is exactly live
        # bytes
        keep = max(_pc.blocks_for(slot.cache_len, self._bs), 1)
        while len(slot.blocks) > keep:
            blk = slot.blocks.pop()
            self._alloc.free([blk])
            self._tables[i, len(slot.blocks)] = 0
            self._reserved += 1
            self._tables_dev = None
        # 2) publish full blocks (same walk as _retire)
        self._publish_full(slot)
        # 3) spill live bytes to the host tier (swap-resume payload).
        # A MID-PREFILL victim skips the spill: it has streamed
        # nothing, so it requeues as a FRESH request — its published
        # full blocks (step 2) make the re-prefill mostly a prefix-
        # cache hit, no continuation state needed.
        key = None
        nbytes = 0
        if slot.pend_pos is None and self._host_tier is not None \
                and slot.blocks and not self._stateful \
                and len(slot.blocks) <= self._mb_xfer:
            # spill only a fully-valid cache (a decoding victim); a
            # mid-re-prefill victim keeps its continuation but its
            # partial KV cannot back a swap — it resumes by recompute
            payload = _pc.payload_rows(
                self._export_payload(slot.blocks), len(slot.blocks))
            nbytes = _pc.payload_nbytes(payload)
            key = ("victim", slot.rid)
            if self._host_tier.put(key, payload, nbytes):
                self._n_spilled += len(slot.blocks)
                self._m_spill.inc(len(slot.blocks))
            else:
                key = None      # refused (too big): recompute resume
            self._m_host_bytes.set(self._host_tier.bytes_used)
        n_spilled_blocks = len(slot.blocks) if key is not None else 0
        # 4) free the blocks (published ones park in the LRU cache)
        self._alloc.free(slot.blocks)
        self._reserved -= slot.worst_blocks - len(slot.blocks)
        self._tables[i, :] = 0
        self._tables_dev = None
        self._slots[i] = None
        self._set_slot_samp(i)
        # the adapter pin drops with the slot (LRU may now evict it);
        # re-admission re-acquires, reloading from the host registry
        # if churn swapped it out meanwhile — the request carries the
        # ID, never a stack-row index
        self._lora_release_slot(i, slot)
        self._m_occupancy.set(self.num_active)
        # 5) re-enqueue at the front of its class; a DECODING victim
        # carries the exact continuation state, a mid-prefill victim
        # goes back as a fresh request (nothing streamed yet). The
        # ORIGINAL submit time anchors queue-wait/e2e observations
        # either way.
        resume = None
        if not pending:
            if slot.resume is not None:
                # twice-preempted mid-re-prefill: the ORIGINAL
                # continuation carries over; its full context is the
                # stored history minus the pending last_token
                last_token, n_emitted = slot.resume
            else:
                last_token, n_emitted = slot.last_token, slot.n_emitted
            n_ctx = len(slot.history) - 1   # == cache_len for a
            #                                 decoding victim
            resume = {"cache_len": int(n_ctx),
                      "last_token": int(last_token),
                      "n_emitted": int(n_emitted),
                      "history": list(slot.history),
                      "worst_blocks": int(slot.worst_blocks),
                      "n_blocks": _pc.blocks_for(n_ctx, self._bs),
                      "nbytes": int(nbytes), "key": key}
        req = ServingRequest(
            slot.rid, np.asarray(slot.prompt, np.int32), slot.max_new,
            temperature=float(samp_row[0]) if self._do_sample
            else None,
            top_k=int(samp_row[1]) if self._do_sample else None,
            top_p=float(samp_row[2]) if self._do_sample else None,
            priority=int(slot.priority), resume=resume,
            adapter_id=slot.adapter_id)
        req.submit_time = self._submit_t.get(slot.rid,
                                             req.submit_time)
        self._queue.appendleft(req)
        self._n_preempt += 1
        self._m_preempt.inc()
        if self._trace is not None:
            self._trace.instant(
                "preempt", tid=1 + i,
                args={"rid": slot.rid, "priority": int(slot.priority),
                      "cache_len": int(slot.cache_len),
                      "blocks_spilled": n_spilled_blocks})

    def _seat_resume(self, i, req, emitted):
        """Re-admit a preempted request into slot ``i`` exactly where
        it stopped. Swap: import the spilled bytes at freshly
        allocated blocks (bitwise the preempted pool state) and seat
        the slot ACTIVE. Recompute: map the published prefix blocks
        (the prefix cache IS the recompute fast path) and re-prefill
        only what eviction lost, through the ordinary chunk machinery;
        ``_finish_prefill`` then restores the continuation instead of
        emitting. Either way last_token / n_emitted / history / the
        sampling row carry over, so the resumed stream is token-exact
        vs never-preempted."""
        r = req.resume
        rid = req.request_id
        n_ctx = int(r["cache_len"])
        ctx = np.asarray(r["history"][:n_ctx], np.int32)
        payload = None
        if self._host_tier is not None and r["key"] is not None:
            payload = self._host_tier.get(r["key"])
        mode = self._resume_mode(r, payload)
        self._queue_exit(req, "resumed")
        if rid not in self._results:        # kept across preemption
            self._results[rid] = []
        if mode == "swap":
            n_blocks = int(r["n_blocks"])
            blocks = self._alloc.alloc(n_blocks)
            self._import_payload(blocks, payload)
            self._host_tier.pop(r["key"])
            self._n_restored += n_blocks
            self._m_restore.inc(n_blocks)
            self._m_host_bytes.set(self._host_tier.bytes_used)
            self._n_swap_resumes += 1
            self._reserved += int(r["worst_blocks"]) - n_blocks
            self._tables[i, :] = 0
            self._tables[i, :n_blocks] = blocks
            self._tables_dev = None
            slot = _Slot(rid, blocks, int(r["worst_blocks"]), n_ctx,
                         int(r["last_token"]), int(req.max_new_tokens),
                         history=list(r["history"]), prompt=ctx,
                         pend_pos=None)
            slot.n_emitted = int(r["n_emitted"])
        else:
            if self._host_tier is not None and r["key"] is not None:
                # the stale payload (if any) will never be imported
                self._host_tier.pop(r["key"], restore=False)
                self._m_host_bytes.set(self._host_tier.bytes_used)
            self._n_recompute_resumes += 1
            blocks, cached = self._map_prefix(ctx, n_ctx, i)
            self._reserved += int(r["worst_blocks"]) - len(blocks)
            self._tables[i, :] = 0
            self._tables[i, :len(blocks)] = blocks
            self._tables_dev = None
            slot = _Slot(rid, blocks, int(r["worst_blocks"]), cached,
                         None, int(req.max_new_tokens),
                         history=list(r["history"]), prompt=ctx,
                         pend_pos=cached)
            slot.resume = (int(r["last_token"]), int(r["n_emitted"]))
        slot.priority = int(req.priority)
        self._slots[i] = slot
        self._set_slot_samp(i, req)
        self._m_occupancy.set(self.num_active)
        if self._trace is not None:
            self._trace.instant(
                "resume", tid=1 + i,
                args={"rid": rid, "mode": mode, "cache_len": n_ctx})
        if mode != "swap":
            # a shared suffix-boundary block is COW'd before the
            # recomputed tail writes into it — same as a fresh
            # admission's full-prompt-hit path
            bidx = cached // self._bs
            if self._alloc.is_shared(blocks[bidx]):
                self._cow(i, bidx)

    def _resume_mode(self, r, payload) -> str:
        """Recompute-vs-swap, per victim: restore time ~= payload
        bytes / measured host-transfer bandwidth; recompute time ~=
        cached tokens / measured chunk-prefill row throughput. A
        missing payload (tier off, dropped under pressure, or refused)
        forces recompute; un-measured rates default to swap (bytes
        beat re-running the model until the prefill rate proves
        otherwise). ``ServingConfig.preempt_resume`` pins one path."""
        if payload is None:
            return "recompute"
        if self._resume_policy in ("swap", "recompute"):
            return self._resume_policy
        if self._prefill_rows_s > 0 and self._xfer_bytes_s > 0:
            t_swap = float(r["nbytes"]) / self._xfer_bytes_s
            t_rec = float(r["cache_len"]) / self._prefill_rows_s
            return "swap" if t_swap <= t_rec else "recompute"
        return "swap"

    def _export_payload(self, blocks):
        """Gather ``blocks``' self-contained bytes to host DRAM through
        THE fixed-width export executable (shared with the
        disaggregated handoff — compiled once per engine; the eviction
        spill has its own, ``_spill_evicted``). The
        ``payload_to_host`` materialization blocks on the gather, so
        the timing feeds the cost model's transfer-bandwidth EMA."""
        ids = np.zeros(self._mb_xfer, np.int32)
        ids[:len(blocks)] = blocks
        ids_dev = self._dev(ids)
        if self._export_exec is None:
            self._export_exec = self._aot_compile(
                "export", jax.jit(_pc.export_blocks),
                (self._pools, ids_dev))
        t0 = time.monotonic()
        host = _pc.payload_to_host(
            self._export_exec(self._pools, ids_dev))
        self._note_xfer(_pc.payload_nbytes(host),
                        time.monotonic() - t0)
        return host

    def _import_payload(self, blocks, payload):
        """Scatter a host payload back into this engine's pools at
        ``blocks`` through THE fixed-width import executable (shared
        with ``admit_prefilled`` — compiled once). Short payloads are
        zero-padded back to the fixed width; pad rows scatter into the
        null block."""
        ids = np.zeros(self._mb_xfer, np.int32)
        ids[:len(blocks)] = blocks
        ids_dev = self._dev(ids)
        dev = self._payload_dev(
            _pc.payload_pad(payload, self._mb_xfer))
        if self._import_exec is None:
            self._import_exec = self._aot_compile(
                "import",
                jax.jit(_pc.import_blocks, donate_argnums=(0,)),
                (self._pools, ids_dev, dev))
        with _quiet_donation():
            self._pools = self._import_exec(self._pools, ids_dev, dev)

    def _payload_dev(self, payload):
        """Host payload -> device operands for the import executable;
        under TP each array is placed with the pool's kv_head sharding
        (the compiled executable is strict about input shardings)."""
        if self._mesh is None:
            def d(x):
                if isinstance(x, _pc.QuantKV):
                    return _pc.QuantKV(jnp.asarray(x.data),
                                       jnp.asarray(x.scale))
                return jnp.asarray(x)
        else:
            dsh = self._pool_sharding
            ssh = _pc.scale_sharding(dsh)

            def d(x):
                if isinstance(x, _pc.QuantKV):
                    return _pc.QuantKV(jax.device_put(x.data, dsh),
                                       jax.device_put(x.scale, ssh))
                return jax.device_put(x, dsh)
        return [tuple(d(x) for x in rows) for rows in payload]

    def _compile_spill(self):
        """The eviction spill's gather: ONE block wide, its result one
        array per dtype (``ops/paged_cache.export_stacked``). The
        fixed-width export is another executable on purpose: its shape
        must match across engines, this one never leaves its own."""
        self._spill_exec = self._aot_compile(
            "spill", jax.jit(_pc.export_stacked),
            (self._pools, self._dev(np.zeros(1, np.int32))))
        self._spill_layout = _pc.stacked_layout(self._pools)
        self._spill_nbytes = \
            _pc.pool_bytes(self._pools) // self._alloc.num_blocks

    def _spill_evicted(self, b, h):
        """Allocator eviction hook (``BlockAllocator.on_evict``): an
        LRU-cached published block is being reclaimed — gather its
        bytes for the host tier first, keyed by content hash, so a
        later prefix hit restores it instead of re-prefilling. Only
        the LAUNCH happens here: the gather of that one block is
        issued before the evicting caller's next write (launches issue
        in host order, so the bytes read are the published ones), its
        copy to the host is started, and the tier books the entry;
        ``_drain_spills`` takes the bytes in once the device has
        finished. Whether the tier has room is known from shapes: a
        refused block launches nothing."""
        tr = self._trace
        ph = tr.phase("spill", tick=self._tick_ord,
                      block=int(b)).begin() if tr is not None else None
        if self._spill_exec is None:
            self._compile_spill()
        tier, nbytes = self._host_tier, self._spill_nbytes
        key, launched, copied = ("pub", h), None, 0
        if nbytes <= tier.capacity:
            # the id rides the call as numpy: one dispatch, not an
            # upload and then a dispatch
            launched = self._spill_exec(
                self._pools, np.full(1, b, np.int32))
            for a in launched:
                a.copy_to_host_async()
            copied = sum(int(a.nbytes) for a in launched)
        stored = tier.put(key, launched, nbytes)
        if stored:
            self._spill_pending.append((key, launched))
            self._n_spilled += 1
            self._m_spill.inc()
        self._m_host_bytes.set(tier.bytes_used)
        if ph is not None:
            ph.end(bytes=int(nbytes), stored=bool(stored),
                   copied=copied)

    def _drain_spills(self):
        """Take the launched eviction spills in: each block's device
        arrays become the numpy buffers its tier entry keeps (views of
        one buffer per dtype, ``ops/paged_cache.stacked_payload``).
        Called where the device has already finished — the tick's
        ``commit`` after ``fetch`` — and before anything reads the
        tier (a restore, a purge, a session export, shutdown), so no
        more is ever in flight than one tick evicts. The host time it
        takes is a ``spill`` span of its own, with ``drained``."""
        pending = self._spill_pending
        if not pending:
            return
        self._spill_pending = []
        tr = self._trace
        ph = tr.phase("spill", tick=self._tick_ord).begin() \
            if tr is not None else None
        for key, launched in pending:
            host = [np.asarray(a) for a in launched]
            self._n_spill_bytes += sum(int(a.nbytes) for a in host)
            self._host_tier.fill(
                key, launched,
                _pc.stacked_payload(self._spill_layout, host))
        if ph is not None:
            ph.end(drained=len(pending))

    def _restore_published(self, h):
        """Host-tier prefix restore: a prompt hash that misses the
        device index but hits the host tier re-materializes the block
        — alloc (opportunistic: never preempts for a cache hit),
        import, re-publish — and the admission walk continues as if
        the block had never been evicted. Returns the block id (one
        reference, owned by the caller's slot) or None."""
        if self._host_tier is None or ("pub", h) not in self._host_tier:
            return None
        # h may have been evicted this very admit: its bytes must be in
        # before they are read. (Only then: taking spills in waits for
        # the tick in flight.)
        self._drain_spills()
        payload = self._host_tier.get(("pub", h))
        if payload is None:
            return None
        if self._alloc.free_blocks < 1:
            return None
        (b,) = self._alloc.alloc(1)
        self._import_payload([b], payload)
        self._host_tier.pop(("pub", h))
        self._alloc.publish(b, h)
        self._n_restored += 1
        self._m_restore.inc()
        self._m_host_bytes.set(self._host_tier.bytes_used)
        return b

    def _note_xfer(self, nbytes, dt):
        """Host-transfer bandwidth EMA (the swap side of the
        recompute-vs-swap cost model)."""
        if dt <= 0.0 or nbytes <= 0:
            return
        bps = nbytes / dt
        self._xfer_bytes_s = bps if not self._xfer_bytes_s \
            else 0.7 * self._xfer_bytes_s + 0.3 * bps

    def _note_prefill_rate(self, rows, dt):
        """Chunk-prefill throughput EMA (the recompute side of the
        cost model). Fed by ticks that carried prefill rows — the
        whole launch is attributed to them, so the estimate is
        conservative (recompute looks slower than it is, biasing
        toward swap; the transfer EMA is measured the same
        wall-clock way)."""
        if dt <= 0.0 or rows <= 0:
            return
        rps = rows / dt
        self._prefill_rows_s = rps if not self._prefill_rows_s \
            else 0.7 * self._prefill_rows_s + 0.3 * rps

    def queue_depth(self, priority=None):
        """Queued + active work. With ``priority`` given (and the
        preemptive scheduler on) lower-priority work is DISCOUNTED to
        0.25 — it can be preempted or bypassed by an arrival of that
        class, so it blocks the arrival far less than peer work does.
        The cluster router's priority-weighted tiebreak reads this."""
        if priority is None or not self._preempt_on:
            return self.num_queued + self.num_active
        w = 0.0
        for r in self._queue:
            w += 1.0 if r.priority >= priority else 0.25
        for s in self._slots:
            if s is not None:
                w += 1.0 if s.priority >= priority else 0.25
        return w

    def _map_prefix(self, prompt, n_real, seat):
        """Map the longest cached prefix of ``prompt`` — leading FULL
        blocks whose rolling content hashes hit the allocator's index
        get refcount++'d straight into the slot's block list — then
        allocate fresh blocks for the remainder. Returns ``(blocks,
        cached_tokens)``. ``cached_tokens`` is block-aligned except on
        a full-prompt hit, where the last prompt token is recomputed
        anyway (admission must produce first-token logits) and its
        shared block is COW-duplicated by the caller before the
        write. A model with slot state is seated only at a boundary
        whose state the snapshot table holds: the hit is cut back to
        the deepest such block short of the prompt's end (to nothing if
        there is none), ``prefix_tokens_cut_for_state`` counts what was
        cut, and the snapshot is written into ``seat``."""
        init = _pc.blocks_for(n_real, self._bs)
        matched = []
        if self._prefix_on:
            # lazy hashing: a cache-cold prompt stops at block 0. THE
            # shared prompt->hash walk (ops/paged_cache) — the cluster
            # router probes replicas with exactly these keys, so a
            # router hit here IS an admission hit
            for h in _pc.prompt_block_hashes(self._fp, prompt,
                                             self._bs):
                b = self._alloc.lookup(h)
                if b is not None:
                    matched.append(self._alloc.ref(b))
                    continue
                # device-index miss: the block may have been LRU-
                # evicted INTO the host tier — restore it (one
                # fixed-width import) and keep walking
                rb = self._restore_published(h)
                if rb is None:
                    break
                matched.append(rb)
        cached = len(matched) * self._bs
        if matched and self._stateful:
            # the state after position n_real - 1 is no use either: the
            # last prompt row is recomputed, from the state before it
            keep, snap = 0, None
            hashes = _pc.chain_hashes(self._fp, prompt[:cached],
                                      self._bs)
            for j in range(min(len(matched),
                               (n_real - 1) // self._bs), 0, -1):
                snap = self._state_snaps.get(hashes[j - 1])
                if snap is not None:
                    keep = j
                    break
            self._alloc.free(matched[keep:])
            del matched[keep:]
            self._n_prefix_cut += min(cached, n_real - 1) \
                - keep * self._bs
            cached = keep * self._bs
            if snap is not None:
                self._seat_state(seat, snap)
        if cached >= n_real:                     # full-prompt hit
            cached = n_real - 1
        if matched:
            self._n_prefix_blocks += len(matched)
            self._n_prefix_tokens += cached
            self._m_prefix_blocks.inc(len(matched))
            self._m_prefix_tokens.inc(cached)
        self._n_prompt_tokens += n_real
        if self._prefix_on:
            self._m_hit_rate.set(
                self._n_prefix_tokens / self._n_prompt_tokens)
        fresh = self._alloc.alloc(init - len(matched)) \
            if init > len(matched) else []
        return matched + fresh, cached

    def _cow(self, i, bidx):
        """Copy-on-write: duplicate the shared block at table position
        ``bidx`` of slot ``i`` into a fresh block (ONE device block
        copy per pool — target and draft pools share block ids), swap
        it into the table, and drop this slot's reference on the
        original (which stays intact for the cache / its other
        holders)."""
        slot = self._slots[i]
        old = slot.blocks[bidx]
        (new,) = self._alloc_with_preempt(1, exclude=(i,),
                                          below=slot.priority + 1)
        if self._cow_exec is None:
            self._cow_exec = self._compile_cow(self._pools)
        with _quiet_donation():
            self._pools = self._cow_exec(
                self._pools, self._dev(np.int32(old)),
                self._dev(np.int32(new)))
        if self._draft_model is not None:
            if self._draft_cow_exec is None:
                self._draft_cow_exec = self._compile_cow(self._dpools)
            with _quiet_donation():
                self._dpools = self._draft_cow_exec(
                    self._dpools, self._dev(np.int32(old)),
                    self._dev(np.int32(new)))
        self._alloc.free([old])
        slot.blocks[bidx] = new
        self._tables[i, bidx] = new
        self._tables_dev = None
        self._n_cow += 1
        self._m_cow.inc()

    def _finish_prefill(self, i, tok, emitted):
        """Admission epilogue, once a prompt's last row has ridden a
        tick: record and emit the first token, retire immediately
        on EOS / max_new_tokens == 1. On a role="prefill" engine a
        surviving slot parks for ``pop_prefilled()`` instead of
        entering decode — the request's remaining tokens belong to the
        decode replica the blocks stream to."""
        slot = self._slots[i]
        slot.cache_len = int(slot.prompt.size)
        slot.pend_pos = None
        if slot.resume is not None:
            # recompute resume completing: the re-prefilled cache now
            # holds EXACTLY the preempted state — restore the
            # continuation instead of emitting (the client already
            # holds these tokens; the stream resumes next decode tick)
            last_token, n_emitted = slot.resume
            slot.resume = None
            slot.last_token = int(last_token)
            slot.n_emitted = int(n_emitted)
            if self._trace is not None:
                self._trace.instant("resumed", tid=1 + i,
                                    args={"rid": slot.rid})
            return
        slot.last_token = tok
        slot.history.append(tok)
        self._emit(slot.rid, tok)
        emitted.append((slot.rid, tok))
        if tok == self._eos or slot.max_new <= 1:
            self._retire(i)
        elif self._role == "prefill":
            slot.handoff = True
            self._handoff_ready.append(i)

    def _note_step_time(self, name, dt):
        """Measured half of the roofline: one launch->sync wall-time
        sample for executable ``name``, folded into a per-executable
        EMA (so the estimate tracks the live batch mix, like the
        preemption cost model's rates). The tick executable's sample
        also refreshes the ``serving_step_mfu`` /
        ``serving_hbm_bw_util`` gauges."""
        if dt <= 0.0:
            return
        ema = self._step_time.get(name)
        self._step_time[name] = dt if ema is None \
            else 0.7 * ema + 0.3 * dt
        self._step_ticks[name] = self._step_ticks.get(name, 0) + 1
        if name == ("verify" if self._gamma else "decode") \
                and self._peaks is not None:
            cost = self._exec_cost.get(name)
            if cost:
                if cost.get("flops"):
                    self._m_mfu.set(
                        cost["flops"] / dt / self._peaks[0])
                if cost.get("bytes_accessed"):
                    self._m_bw_util.set(
                        cost["bytes_accessed"] / dt / self._peaks[1])

    def _roofline(self) -> dict:
        """Live per-executable roofline attribution (the
        ``stats()['roofline']`` block): the XLA cost model's FLOPs /
        HBM bytes of every executable this engine compiled, fused
        with the measured per-tick step-time EMA into MFU and
        HBM-bandwidth utilization. ``bound`` classifies each
        executable against the chip's ridge point (peak FLOPs / peak
        HBM bytes/s — arithmetic intensity below it means the
        executable saturates bandwidth before compute). On the CPU
        backend there is no chip peak: ``device`` says so and every
        peak-derived field (``mfu``, ``hbm_bw_util``, ``bound``, the
        peaks, the ridge) is ``None`` — the counts (FLOPs, bytes,
        ticks) and the host step time remain."""
        peaks = self._peaks
        ridge = peaks[0] / peaks[1] if peaks else None
        per = {}
        for name, cost in self._exec_cost.items():
            f = float(cost.get("flops", 0.0) or 0.0)
            b = float(cost.get("bytes_accessed", 0.0) or 0.0)
            ai = (f / b) if b else 0.0
            dt = self._step_time.get(name)
            row = {
                "flops": f, "bytes_accessed": b,
                "arithmetic_intensity": round(ai, 4),
                "ticks": self._step_ticks.get(name, 0),
                "step_time_ms": round(1000.0 * dt, 4)
                if dt is not None else None,
                "bound": None, "mfu": None, "hbm_bw_util": None,
            }
            if peaks:
                row["bound"] = "compute" if ai >= ridge else "bandwidth"
                row["mfu"] = round(f / dt / peaks[0], 6) \
                    if dt and f else 0.0
                row["hbm_bw_util"] = round(b / dt / peaks[1], 6) \
                    if dt and b else 0.0
            per[name] = row
        tick = "verify" if self._gamma else "decode"
        t = per.get(tick, {})
        # speculative token credit: the verify window's FLOPs/bytes
        # are charged ONCE per tick (the executable cost above) but
        # the tick emits accepted+1 tokens — the mean accepted length
        # is the divisor that turns per-tick roofline numbers into
        # per-TOKEN cost (tree speculation raises it at the same
        # verify node budget)
        acc = (self._n_spec_emitted / self._n_spec_verifies
               if self._n_spec_verifies else 0.0)
        idle = 0.0 if peaks else None
        return {"device": jax.devices()[0].device_kind,
                "tick_executable": tick,
                "step_mfu": t.get("mfu", idle),
                "step_hbm_bw_util": t.get("hbm_bw_util", idle),
                "verify_tokens_credited_per_tick": round(acc, 4),
                "verify_node_budget": (self._gamma + 1)
                if self._gamma else 1,
                "peak_flops_per_s": peaks[0] if peaks else None,
                "peak_hbm_bytes_per_s": peaks[1] if peaks else None,
                "ridge_flops_per_byte": round(ridge, 4)
                if peaks else None,
                "per_executable": per}

    def profile(self, n_ticks: int, path: Optional[str] = None):
        """Arm a BOUNDED ``jax.profiler`` capture around the next
        ``n_ticks`` engine ticks (ISSUE 15 layer 3): the capture
        starts before the next tick and stops after the Nth, so an
        operator can grab a device-level profile of a live engine
        without an always-on tracer. ``path`` defaults to
        ``$PADDLE_TPU_PROFILE_DIR``. Returns the capture dir, or
        None under the ``PADDLE_TPU_TRACE=0`` kill switch (the whole
        flight recorder is inert there). Raises while a window is
        already armed (jax allows one live capture per process)."""
        return self._prof.arm(n_ticks, path)

    def _note_kv_read(self, positions):
        """Analytic KV HBM traffic of one tick: ``positions`` cache
        positions attended x bytes per position (the quantization win
        shows up here directly — int8 halves the multiplier)."""
        b = int(positions * self._kv_pos_bytes)
        self._kv_step_bytes_last = b
        self._m_kv_step.set(b)

    def _sync_cache_metrics(self):
        """Mirror allocator-side eviction counts into the monitor
        registry (the allocator stays monitor-free), and refresh the
        SLO latency quantile gauges from the per-engine digests."""
        d = self._alloc.evictions - self._n_evictions_seen
        if d:
            self._m_evict.inc(d)
            self._n_evictions_seen = self._alloc.evictions
        for key, dig in (("ttft", self._d_ttft), ("itl", self._d_itl),
                         ("queue_wait", self._d_queue),
                         ("e2e", self._d_e2e)):
            g = self._m_lat[key]
            for q, v in dig.quantiles().items():
                g.labels(q=q).set(round(v, 3))
        for q, v in self._d_accept.quantiles().items():
            self._m_accept.labels(q=q).set(round(v, 3))
        for q, v in self._d_host_gap.quantiles().items():
            self._m_host_gap.labels(q=q).set(round(v, 3))

    def _ensure_blocks(self, active, horizon=1):
        """Grow any slot whose next ``horizon`` write positions cross
        into unallocated blocks (covered by the admission reservation;
        speculative mode needs ``gamma + 1`` positions of headroom for
        the verify window). Returns the SURVIVING active list: under
        the watermark policy the pool may be overcommitted, so a
        growth that finds it dry preempts the lowest
        same-or-lower-priority victim — or, when no other candidate
        exists, the growing slot itself (spilled + requeued; it skips
        this tick and resumes token-exact later)."""
        out = []
        for i in active:
            slot = self._slots[i]
            if slot is None:        # preempted as an earlier victim
                continue
            grown = True
            # the length the tick about to be packed writes from: the
            # committed one plus what the uncommitted tick adds. Read
            # afresh each turn: a preemption below commits that tick
            # (and may retire this very slot)
            while self._slots[i] is slot and len(slot.blocks) < \
                    _pc.blocks_for(self._ahead(i).cache_len + horizon,
                                   self._bs):
                try:
                    (blk,) = self._alloc_with_preempt(
                        1, exclude=(i,), below=slot.priority + 1)
                except RuntimeError:
                    if not self._preempt_on:
                        raise
                    if self._settle():
                        continue
                    self._preempt(i)    # self-preempt: out of options
                    grown = False
                    break
                if self._slots[i] is not slot:
                    self._alloc.free([blk])
                    break
                self._tables[i, len(slot.blocks)] = blk
                slot.blocks.append(blk)
                self._n_grown += 1
                self._tables_dev = None
                self._reserved -= 1
            if grown:
                out.append(i)
        # a LATER slot's growth may have victimized an EARLIER
        # survivor — keep only slots still seated
        return [i for i in out if self._slots[i] is not None]

    def _trim_blocks(self, i):
        """Speculative rollback, block side: return blocks only the
        rejected window tail reached to the allocator (back under the
        slot's admission reservation; no cache data moves). Blocks
        within the NEXT window's reach (``cache_len + gamma + 1``
        positions) are kept: freeing them would be reservation-neutral
        (``free - reserved`` is invariant under trim, so admission
        capacity cannot improve) yet the very next `_ensure_blocks`
        would re-allocate them and re-upload the device block table —
        pure hot-loop churn. With a fixed gamma that makes mid-flight
        trims rare; retirement frees everything regardless."""
        slot = self._slots[i]
        need = _pc.blocks_for(slot.cache_len + self._gamma + 1,
                              self._bs)
        while len(slot.blocks) > need:
            blk = slot.blocks.pop()
            self._alloc.free([blk])
            self._tables[i, len(slot.blocks)] = 0
            self._reserved += 1
            self._tables_dev = None

    def _retire(self, i):
        slot = self._slots[i]
        self._release_seat(i)
        self._finish_request(i, slot)

    def _publish_full(self, slot):
        """Publish a sequence's FULL blocks into the content index
        instead of just dropping them: the hash chain runs over the
        tokens the cache actually holds (prompt + committed
        continuation — position p holds history[p] for p <
        cache_len), so a future prompt sharing the prefix maps these
        blocks instead of re-prefilling. Blocks go to the LRU cached
        list when their refcount hits 0 and survive until memory
        pressure evicts them. The slot-state snapshots taken at these
        blocks' boundaries go into the snapshot table under the same
        hashes: a later hit is seated only where one exists
        (``_map_prefix``)."""
        if not self._prefix_on or slot.cache_len < self._bs:
            return
        n_full = min(len(slot.blocks), slot.cache_len // self._bs)
        hashes = _pc.chain_hashes(
            self._fp, slot.history[:n_full * self._bs], self._bs)
        for b, h in zip(slot.blocks[:n_full], hashes):
            self._alloc.publish(b, h)
        for end, snap in slot.state_snaps.items():
            h = hashes[end // self._bs - 1] \
                if end <= n_full * self._bs else None
            if h is None or self._alloc.lookup(h) is None:
                continue
            self._state_snaps[h] = snap
            self._state_snaps.move_to_end(h)
        slot.state_snaps = {}

    def _snaps_held(self):
        """Snapshots held now: the table's and the live slots'
        unpublished ones."""
        return len(self._state_snaps) + sum(
            len(s.state_snaps) for s in self._slots if s is not None)

    def _room_for_snapshot(self, slot):
        """Make room in the byte budget for one more snapshot of
        ``slot``: its own shallower boundaries go first, then the
        table's oldest entries. False where the budget holds not even
        one, or other slots' unpublished snapshots fill it."""
        cap = self._state_snap_budget // max(1, self._snap_nbytes)
        while self._snaps_held() >= cap:
            if slot.state_snaps:
                del slot.state_snaps[min(slot.state_snaps)]
            elif self._state_snaps:
                self._state_snaps.popitem(last=False)
            else:
                return False
            self._n_state_snaps_dropped += 1
        return True

    def _snapshot_state(self, i, end):
        """Keep slot ``i``'s state as the tick just launched leaves it
        — its prefill chunk ended on the block boundary ``end`` — until
        the slot's blocks are published, if the byte budget has room
        for it. One gather of the seat's rows on the device, launched
        behind the tick; nothing comes to the host."""
        slot = self._slots[i]
        if not self._room_for_snapshot(slot):
            self._n_state_snaps_dropped += 1
            return
        slot.state_snaps[int(end)] = self._read_state(i)
        self._n_state_snaps += 1

    def _read_state(self, i):
        slot_dev = self._dev(np.int32(i))
        if self._snap_exec is None:
            self._snap_exec = self._aot_compile(
                "state_snapshot", jax.jit(_pc.export_slot_state),
                (self._pools, slot_dev))
        return self._snap_exec(self._pools, slot_dev)

    def _seat_state(self, i, snap):
        """Write a snapshot into seat ``i`` before the request mapped
        past its boundary rides a tick."""
        slot_dev = self._dev(np.int32(i))
        if self._snap_import_exec is None:
            self._snap_import_exec = self._aot_compile(
                "state_seat",
                jax.jit(_pc.import_slot_state, donate_argnums=(0,)),
                (self._pools, slot_dev, snap))
        with _quiet_donation():
            self._pools = self._snap_import_exec(self._pools, slot_dev,
                                                 snap)
        self._n_state_snap_hits += 1

    def _evict_stateful(self, b, h):
        """Allocator eviction hook of a model with slot state: the
        block's snapshot goes with it, and the host tier is not offered
        a block it could not give back whole."""
        self._state_snaps.pop(h, None)
        if self._host_tier is not None and self._trace is not None:
            self._trace.phase("spill", tick=self._tick_ord,
                              block=int(b)).begin().end(
                bytes=0, stored=False, copied=0)

    def _release_seat(self, i):
        """The seat's half of a retirement: publish the sequence's
        full blocks, free them, and empty slot ``i`` for the next
        admission."""
        slot = self._slots[i]
        self._slot_props.pop(i, None)
        self._publish_full(slot)
        self._alloc.free(slot.blocks)
        self._reserved -= slot.worst_blocks - len(slot.blocks)
        self._tables[i, :] = 0
        self._tables_dev = None
        self._slots[i] = None
        self._set_slot_samp(i)
        self._lora_release_slot(i, slot)
        self._m_occupancy.set(self.num_active)

    def _finish_request(self, i, slot):
        """The request's half of a retirement, once its last token is
        out: latency records, the residency span, the result."""
        now = time.monotonic()
        t0 = self._submit_t.pop(slot.rid, None)
        if t0 is not None:
            self._d_e2e.observe(1000.0 * (now - t0))
        if self._health is not None:
            # burn-rate intake: a retirement that never hit a latency
            # violation counts as SLO-met (requests retired before the
            # first token never entered _slo_ok)
            self._health.on_request(self._slo_ok.pop(slot.rid, True))
        else:
            self._slo_ok.pop(slot.rid, None)
        self._last_emit.pop(slot.rid, None)
        if self._trace is not None:
            # the request's whole residency on this slot, admission to
            # retirement — per-tick decode/verify/prefill spans nest
            # inside it on the same tid
            self._trace.emit(
                f"req{slot.rid}", tid=1 + i, t0=slot.admit_t, t1=now,
                args={"tokens": slot.n_emitted,
                      "cache_len": slot.cache_len})
            self._trace.instant("retired", tid=1 + i,
                                args={"rid": slot.rid})
        toks = self._results.pop(slot.rid)
        if self.config.retain_results:
            self._done[slot.rid] = np.asarray(toks, np.int64)
        self._m_completed.inc()
        self._n_completed += 1

    # -- compiled steps -----------------------------------------------

    def _compile_cow(self, pools):
        """AOT-compile the copy-on-write block duplicate (src/dst ride
        as traced scalars — one executable serves every COW)."""
        jitted = jax.jit(_pc.copy_blocks, donate_argnums=(0,))
        return self._aot_compile(
            "cow", jitted, (pools, self._dev(np.int32(0)),
                            self._dev(np.int32(0))))

    def _compile_ragged_step(self, args):
        """AOT-compile THE ragged mixed-batch executable ONCE (decode
        + verify + chunk prefill in one): a packed ``[R]`` token
        buffer runs the model over every live row (``ragged_meta``
        partitions it by slot), K/V scatter per row, and the sampling
        head takes each slot's
        continuation row from ``last_rows`` — decode rows sample their
        only row, completing prefills their final prompt row, verify
        windows run the shared acceptance core on their gamma+1 rows.
        Those rows are taken from the hidden state BEFORE the LM head,
        which runs over them alone (``logit_rows``: ``_head_rows``).
        ONE logits gather serves all of it (under TP: still exactly
        one explicit all_gather per step). Its census name is
        ``verify`` on a speculating engine, else ``decode``."""
        from ..generation import _filter_logits
        from ..generation import speculative as _spec
        g = self._gamma
        r = self._rows
        do_sample = self._do_sample
        tree = self._spec_tree
        heads_on = self._heads is not None
        lora_on = self._lora_on
        # adapter row index in the slots pack: appended AFTER the tree
        # flags (when present) by _ragged_dispatch
        lora_row = 5 if tree is not None else 4
        lora_scaling = self._lora_pool.scaling if lora_on else 1.0
        # grouped-matmul path only off-mesh: under TP the delta einsum
        # shards on the existing GSPMD cut instead (the gmm kernel's
        # scalar-prefetch gather is a single-device layout)
        lora_gmm_ok = self._mesh is None
        eos = self._eos
        n_slots = self.config.num_slots
        overflow = self._overflow
        share = []      # what expert-parallel shares report, per layer

        def unpack(rows_pack, slots_pack, prev_tok):
            """The packs' rows as the model's operands: ``(ids,
            row_slot, row_pos, base, q_lens, row_starts, last_rows)``,
            a decode row's id taken from the tick before's output
            where the host packed none."""
            ids, row_slot, row_pos = (rows_pack[0], rows_pack[1],
                                      rows_pack[2])
            base, q_lens, row_starts, last_rows = (
                slots_pack[0], slots_pack[1], slots_pack[2],
                slots_pack[3])
            if not g:
                # a decode row whose token the tick before this one
                # sampled takes it from that tick's own output, still
                # on the device: ``src`` names the slot, -1 a row whose
                # id the host packed (docs/OPS.md "Async tick
                # pipeline"). A slot that tick ended on EOS retires at
                # its commit, which the host has not run yet: its row
                # becomes a pad row here (parked at the overflow
                # position, so the KV write null-routes) and the commit
                # drops what it samples.
                src = rows_pack[3]
                fed = src >= 0
                fed_tok = jnp.take(prev_tok, jnp.maximum(src, 0))
                if eos >= 0:
                    done = fed & (fed_tok == eos)
                    fed = fed & ~done
                    row_pos = jnp.where(done, overflow, row_pos)
                    # a slot's first row names the slot itself exactly
                    # where the slot has a fed row
                    sl = jnp.arange(n_slots, dtype=jnp.int32)
                    q_lens = jnp.where(
                        (jnp.take(src, row_starts) == sl)
                        & (prev_tok == eos), 0, q_lens)
                ids = jnp.where(fed, fed_tok, ids)
            return (ids, row_slot, row_pos, base, q_lens, row_starts,
                    last_rows)

        def ragged(params, pools, tables, rows_pack, slots_pack, *rest):
            prev_tok = None
            if not g:
                # the tick before's tokens ride right after the packs
                prev_tok, rest = rest[0], rest[1:]
            if lora_on:
                # the stacked adapter weights ride at a FIXED operand
                # position (right after the packs) — strip them before
                # the g/heads/dq parsing below, which indexes rest
                # from both ends
                lora_ops, rest = rest[0], rest[1:]
            # the component scopes (monitor.COMPONENTS): what the tick
            # does around the model's forward is ``tick.io`` (carry,
            # packs, outputs) and ``sample``; the model names its own
            with component("tick.io"):
                (ids, row_slot, row_pos, base, q_lens, row_starts,
                 last_rows) = unpack(rows_pack, slots_pack, prev_tok)
                tree_rows = slots_pack[4] if tree is not None else None
                nwin = jnp.arange(g + 1, dtype=jnp.int32)
                win = jnp.arange(self._wmax, dtype=jnp.int32)
                meta = (q_lens, row_starts, row_slot, row_pos, nwin, win)
            with component("sample"):
                # the rows the tick samples from: each slot's
                # continuation row, then (verifying) its window — ONE
                # index set, gathered before the head
                take = last_rows.astype(jnp.int32)
                if g:
                    idx = row_starts.astype(jnp.int32)[:, None] \
                        + jnp.arange(g + 1, dtype=jnp.int32)[None, :]
                    take = jnp.clip(jnp.concatenate(
                        [take[:, None], idx], axis=1), 0, r - 1).reshape(-1)
            # pad rows park at the overflow position — exclude them
            # from the MoE routing telemetry (they'd read as
            # hot-expert skew on lightly loaded ticks)
            step = self._model_step_h if heads_on else self._model_step
            with contextlib.ExitStack() as ctx:
                if tree is not None:
                    # the ancestor mask rides the ambient scope — the
                    # kernels read the static topology at trace time
                    # and tree_rows as a per-slot operand; prefill
                    # rows (tree_rows == 0) keep the linear mask
                    ctx.enter_context(
                        _pa.spec_tree_scope(tree, tree_rows))
                if lora_on:
                    # per-ROW adapter assignment: each packed query
                    # row applies its slot's adapter (decode, verify
                    # AND prefill rows — the prompt's KV must carry
                    # the deltas too); pad rows gather slot 0's value
                    # and contribute nothing downstream. The scope
                    # arms the tagged q/k/v/o projections' ragged
                    # grouped-matmul delta inside the SAME executable.
                    with component("tick.io"):
                        row_adapter = jnp.take(slots_pack[lora_row],
                                               row_slot)
                    ctx.enter_context(_lora.serving_lora_scope(
                        lora_ops, row_adapter, lora_scaling,
                        gmm_ok=lora_gmm_ok))
                with component("tick.io"):
                    live_rows = row_pos < self._overflow
                ctx.enter_context(_moe.serving_rows_mask(live_rows))
                ctx.enter_context(_moe.serving_share_counts(share))
                logits, pools = step(
                    params, ids[None, :], pools, None,
                    block_tables=tables, cache_lens=base,
                    ragged_meta=meta, logit_rows=take)
            if heads_on:
                logits, hid = logits
            with component("sample"):
                lg = logits[0]              # [len(take), V(/tp)]
                if not g:
                    return sample_rows(lg, q_lens, rest, pools)
                return verify_rows(lg, row_starts, tables, base,
                                   tree_rows,
                                   hid if heads_on else None, rest,
                                   pools)

        def sample_rows(lg, q_lens, rest, pools):
            samp, key = rest
            rows = self._gather_logits(lg)      # the ONE collective
            # health probe: one any(~isfinite) reduction over the
            # rows already gathered for sampling — a scalar OUTPUT
            # of the same executable, never a new one. Always
            # computed (executable stays bit-identical under
            # PADDLE_TPU_HEALTH=0); only the host fetch is gated.
            # Live slots only: a rowless slot gathers row 0, and a
            # pad row's fully-masked attention output is not
            # meaningful.
            live = q_lens > 0
            nf = jnp.any(~jnp.isfinite(rows) & live[:, None])
            _, sel = jax.random.split(key)
            tok, _ = self._select_rows(rows, sel, samp)
            tok = tok.astype(jnp.int32)
            if self._mesh is not None:
                # the tokens feed straight back as the next tick's
                # operand, and compiled executables are strict
                # about INPUT shardings: pin them replicated (what
                # _dev commits the host's packs as)
                tok = jax.lax.with_sharding_constraint(
                    tok, NamedSharding(self._mesh, P(None)))
            return tok, nf, pools

        def verify_rows(lg, row_starts, tables, base, tree_rows, hid,
                        rest, pools):
            toks = rest[0]
            if tree is not None:
                heads = rest[1] if heads_on else None
                dq = None
            else:
                dq = rest[1] if len(rest) == 4 else None
            samp = rest[-2]
            key = rest[-1]
            # ONE gather covers the per-slot continuation rows AND the
            # verify windows
            rows = self._gather_logits(lg)
            nf = jnp.any(~jnp.isfinite(rows))   # health probe (see g=0)
            rows = rows.reshape(toks.shape[0], g + 2, -1)
            sel_key, acc_key = jax.random.split(key)
            first_tok, _ = self._select_rows(rows[:, 0, :], sel_key,
                                             samp)
            # per-slot knobs over the verify windows: [S] broadcasts
            # across each slot's gamma+1 rows inside _filter_logits
            f = _filter_logits(rows[:, 1:, :], do_sample=do_sample,
                               temperature=samp[:, 0],
                               top_k=samp[:, 1], top_p=samp[:, 2])
            if tree is None:
                out, accept, _logp = _spec.accept_from_filtered(
                    f, toks, dq, acc_key, gamma=g, do_sample=do_sample)
                return first_tok, out, accept, nf, pools
            out, accept, _logp, path, n_acc = \
                _spec.accept_tree_from_filtered(
                    f, toks, tree, acc_key, do_sample=do_sample)
            # compact the accepted root path in place: position
            # base+j must hold node path[j]'s K/V before the next
            # tick appends at base + n_acc + 1. Non-verifying slots
            # (prefill rows, idle) keep n_keep = 0 — their moves all
            # null-route, so a mid-prefill cache is never touched.
            n_keep = jnp.where(tree_rows > 0, n_acc + 1, 0)
            pools = [
                _pc.permute_window(kp, vp, tables, base, path, n_keep)
                for (kp, vp) in pools]
            if not heads_on:
                return first_tok, out, accept, nf, pools
            # next tick's tree proposal from the draft heads, drafted
            # off the accepted path's FINAL hidden row (the row whose
            # LM-head logits produced the bonus token): head d-1
            # predicts the token at depth d, node k+1 taking its
            # sibling-rank-th top entry
            fin = jnp.take_along_axis(path, n_acc[:, None],
                                      axis=1)[:, 0]
            hrow = row_starts.astype(jnp.int32) + fin
            h_fin = jnp.take(hid[0], jnp.clip(hrow, 0, r - 1),
                             axis=0).astype(jnp.float32)
            head_lg = jnp.einsum("sh,dhv->dsv", h_fin, heads)
            _, tidx = jax.lax.top_k(head_lg, self._tree_kmax)
            props = jnp.stack(
                [tidx[self._tree_depth[k + 1] - 1][:,
                      self._tree_sib[k]] for k in range(g)],
                axis=1).astype(jnp.int32)
            return first_tok, out, accept, props, nf, pools

        def ragged_tick(*a):
            outs = ragged(*a)
            if not share:
                return outs
            # one more output, LAST: [expert layers, held + 1] live
            # pairs an expert held here, then live rows; _launch_ragged
            # takes it off again
            self._moe_share_out = True
            with component("tick.io"):
                return tuple(outs) + (jnp.stack(share),)

        jitted = jax.jit(ragged_tick, donate_argnums=(1,))
        name = "verify" if g else "decode"
        exec_ = self._aot_compile(name, jitted, args)
        if self._moe:
            self._moe_gmm_kernel = _moe.MOE_STATS["grouped_mm_kernel"]
        if self._mesh is not None:
            self._tp_step_bytes = self._tp_census_bytes(name)
            if g and self._draft_model is not None:
                # the fused draft step's gather sits inside its scan
                # body (census walks it once; gamma+1 iterations move
                # bytes per step)
                self._tp_step_bytes += \
                    (g + 1) * self._tp_census_bytes("draft")
        self._m_decode_compiles.inc()
        self._n_decode_compiles += 1
        return exec_

    def _compile_ragged_draft(self, args):
        """AOT-compile the draft model's HALF of a ragged spec tick
        ONCE — one fused executable: (1) prime the draft cache over
        this tick's prefill rows (the ragged write, logits
        discarded), then (2) run the gamma+1-step proposal scan. With
        a draft model the engine's steady state is therefore exactly
        TWO executables."""
        from ..generation import speculative as _spec
        g = self._gamma
        loop = _spec.build_draft_loop(
            self._draft_step, gamma=g, do_sample=self._do_sample,
            want_probs=self._do_sample,
            gather_logits=self._gather_logits
            if self._mesh is not None else None, slot_params=True)

        def dstep(dparams, dpools, tables, drows, dslots, samp, key):
            ids, row_slot, prime_pos = drows[0], drows[1], drows[2]
            base, prime_q, row_starts, scan_lens, cur = (
                dslots[0], dslots[1], dslots[2], dslots[3], dslots[4])
            nwin = jnp.arange(g + 1, dtype=jnp.int32)
            win = jnp.arange(self._wmax, dtype=jnp.int32)
            meta = (prime_q, row_starts, row_slot, prime_pos,
                    nwin, win)

            def _prime(dp):
                with _moe.serving_rows_mask(
                        prime_pos < self._overflow):
                    _, dp = self._draft_step(
                        dparams, ids[None, :], dp, None,
                        block_tables=tables, cache_lens=base,
                        ragged_meta=meta)
                return dp

            # no pending prefill rows this tick -> the prime forward
            # would only null-route pad writes; skip the whole pass at
            # runtime (same executable, zero steady-state recompiles)
            dpools = jax.lax.cond(jnp.max(prime_q) > 0, _prime,
                                  lambda dp: dp, dpools)
            # non-verifying slots scan at the overflow length — pad
            # rows, excluded from the draft's routing telemetry
            with _moe.serving_rows_mask(scan_lens < self._overflow):
                props, qp, dpools = loop(dparams, dpools, tables,
                                         scan_lens, cur, samp, key)
            if qp is None:
                return props, dpools
            return props, qp, dpools

        jitted = jax.jit(dstep, donate_argnums=(1,))
        return self._aot_compile("draft", jitted, args)

"""Data-parallel engine replication: N ``ServingEngine`` replicas
behind a session-affine router, with optional disaggregated
prefill -> decode KV streaming.

TP (``ServingConfig(tp_degree=N)``) scales ONE engine across an ``mp``
mesh group; this is the layer above it — the ROADMAP's "millions of
users" unlock: aggregate capacity past a single replica, TTFT isolated
from decode ticks, and a fault domain smaller than the fleet. Per
GSPMD (arxiv 2105.04663) the mesh side is solved; the router/affinity/
role-split layer here is host-side scheduling over engines that never
talk to each other's devices except through the block-transfer ops.

- **Session-affine routing.** ``submit()`` hashes the prompt's FULL
  blocks with the same chain-hash walk engine admission uses
  (``ops/paged_cache.prompt_block_hashes`` — factored out so router
  and engine can NEVER hash differently) and scores every live
  candidate replica by published-prefix overlap: the longest cached
  run wins, because that replica already holds the session's KV blocks
  and will prefill only the suffix. Ties (cold prompts included) break
  on queue depth (queued + active, the PR 2/11 telemetry), then on
  replica index — so multi-turn conversations stick to "their" replica
  while cold traffic load-balances. An overlap > 0 route counts as a
  ``serving_router_affinity_hits`` event; per-candidate depths land in
  the ``serving_router_queue_depth{replica=}`` gauge each route.
- **Disaggregated prefill -> decode** (``ClusterConfig(
  prefill_replicas=K)``): K role="prefill" engines run admission +
  chunked prefill ONLY (reserving only the prompt's blocks, so the
  prefill tier admits aggressively), then stream each finished
  prompt's KV blocks into a decode replica's pool —
  ``pop_prefilled()`` exports the blocks (one fixed-width gather
  executable; int8 pools travel as data + per-row scales, so a
  block's bytes are self-contained) and ``admit_prefilled()`` imports
  them (one fixed-width scatter, null-block padding, zero steady-state
  recompiles on either side). The decode replica seats the request at
  exactly the state a colocated engine holds after its own prefill,
  so greedy output is token-exact vs colocated by construction. The
  win is ISOLATION: decode ticks never share a launch with prefill
  rows (long prompts stop inflating every running request's ITL), and
  prefill chunks never wait behind decode batches (TTFT under
  concurrent long-prefill load). Routing in this mode targets the
  prefill tier (that is where the prefix caches fill — a handoff
  publishes the prompt's blocks before freeing them, so the session's
  next turn hits the same prefill engine's index).
- **Failure domain.** A replica whose ``step()`` raises (or an
  administrative ``fail_replica(i)``) drains its admission queue back
  through the router onto the surviving replicas — global request ids
  are preserved, the re-routed requests just prefill again elsewhere.
  In-flight slots on the failed replica terminate with the tokens
  already streamed (partial results, surfaced through ``run()``
  normally). A fully-failed prefill tier falls back to the decode
  replicas serving end-to-end (they are full engines); a fully-failed
  DECODE tier is fatal for new work (prefill engines cannot decode:
  new submits raise, in-flight requests terminate with what
  streamed). The cluster raises on submit only when no replica that
  could serve the request survives.
- **Kill switch** ``PADDLE_TPU_CLUSTER=0``: the cluster collapses to
  ONE colocated replica (``num_replicas=1, prefill_replicas=0``)
  regardless of config — the single engine underneath is bit-for-bit
  a plain ``ServingEngine`` (same executables, same outputs), the
  router degenerates to the identity, and no transfer executable is
  ever built. Rollback is one env var, like every switch in this
  repo.

Every replica is a full ``ServingEngine`` — prefix cache, COW,
speculative n-gram decoding, ragged batching, int8 pools and TP all
compose per replica unchanged (host state stays per-engine: one
allocator, one scheduler, one prefix index each). Greedy cluster
output is token-exact vs a single engine for every request (replicas
never interact mid-request), which is what makes N replicas a pure
capacity knob.

Telemetry: ``serving_router_affinity_hits`` /
``serving_router_queue_depth{replica=}`` here,
``serving_kv_blocks_transferred`` at the engine import site;
``stats()`` returns per-replica dicts plus rolled-up client-side
``ttft_ms`` / ``itl_ms`` / ``e2e_ms`` digests (P², observed at the
cluster's own stream callback — the view a client of the WHOLE
cluster sees, handoff gaps included) and the goodput-harness keys
(``tokens_total``, ``requests_completed``, queue/active depths).
See docs/OPS.md "Engine replication & disaggregated prefill".
"""
from __future__ import annotations

import json
import os
import re
import time
import warnings
from dataclasses import dataclass, replace as _dc_replace
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from .. import monitor
from ..monitor import health as _health
from ..monitor import tracing as _tracing
from ..monitor.digest import LatencyDigest
from ..ops import paged_cache as _pc
from .autoscale import (AutoscaleConfig, AutoscalePolicy,
                        autoscale_enabled)
from .serving import (MigratedSession, PrefilledRequest,
                      QueueShedError, ServingConfig, ServingEngine)

__all__ = ["ClusterConfig", "Router", "EngineCluster"]


def cluster_enabled() -> bool:
    """False under the ``PADDLE_TPU_CLUSTER=0`` kill switch — the
    cluster then runs ONE colocated replica (a plain engine behind the
    cluster API), never N, never disaggregated."""
    return os.environ.get("PADDLE_TPU_CLUSTER", "1") != "0"


@dataclass
class ClusterConfig:
    # decode-capable replicas (role="both" colocated, role="decode"
    # when a prefill tier exists). Aggregate slot capacity is
    # num_replicas * ServingConfig.num_slots.
    num_replicas: int = 2
    # > 0: disaggregated mode — this many role="prefill" engines run
    # admission + chunked prefill only and stream finished KV blocks
    # into the decode replicas' pools (export_blocks/import_blocks).
    prefill_replicas: int = 0
    # elastic fleet (ISSUE 19): an AutoscaleConfig arms the control
    # loop — each cluster tick the policy reads queue depth /
    # occupancy / SLO burn / roofline busy-ness and drives scale_up()
    # / scale_down() (live-migrating drains) within its replica
    # bounds. None (default) = fixed-N fleet; the
    # PADDLE_TPU_AUTOSCALE=0 kill switch beats an explicit config.
    autoscale: Optional[AutoscaleConfig] = None

    def __post_init__(self):
        n = self.num_replicas
        if not isinstance(n, int) or isinstance(n, bool) or n < 1:
            raise ValueError(
                f"num_replicas must be a positive int, got {n!r}")
        k = self.prefill_replicas
        if not isinstance(k, int) or isinstance(k, bool) or k < 0:
            raise ValueError(
                f"prefill_replicas must be an int >= 0, got {k!r}")


class _MemoHashes:
    """Re-iterable memoizing view over the lazy ``prompt_block_hashes``
    walk: each replica probe re-iterates from the start, but a hash is
    computed at most once — so N cold replicas cost ONE block hash
    total (every probe stops at hash[0]), and the whole route costs
    ``max(overlap) + 1`` hashes, never O(prompt)."""

    __slots__ = ("_it", "_memo", "_done")

    def __init__(self, it):
        self._it = it
        self._memo = []
        self._done = False

    def __iter__(self):
        i = 0
        while True:
            if i == len(self._memo) and not self._done:
                try:
                    self._memo.append(next(self._it))
                except StopIteration:
                    self._done = True
            if i >= len(self._memo):
                return
            yield self._memo[i]
            i += 1


class Router:
    """Session-affine replica scoring. ``route()`` hashes the prompt
    with ``prompt_block_hashes`` — the exact walk engine admission
    runs, so a router hit IS an admission hit — lazily and memoized
    across the per-replica probes (a cache-cold fleet hashes ONE
    block, not the prompt), and asks every candidate engine for its
    published-prefix overlap; the longest cached run wins, ties break
    on queue depth then index. Pure scoring — metrics/bookkeeping
    live on the cluster."""

    def __init__(self, fingerprint: bytes, block_size: int):
        self._fp = bytes(fingerprint)
        self._bs = int(block_size)

    def route(self, prompt,
              engines: Dict[int, ServingEngine],
              priority: int = 0, adapter_id: Optional[int] = None
              ) -> Tuple[int, int, Dict[int, int]]:
        """Pick a replica for ``prompt`` among ``engines`` (index ->
        engine). Returns ``(index, overlap_blocks, depths)`` where
        ``depths`` is every candidate's queue depth at scoring time —
        PRIORITY-WEIGHTED when the replicas run the preemptive
        scheduler: work below ``priority`` is discounted (it can be
        preempted or bypassed, so it barely delays this arrival),
        which steers high-priority traffic toward replicas whose load
        is preemptible rather than merely toward short queues.
        ``adapter_id`` adds ADAPTER affinity below prefix affinity:
        among equal prefix overlaps, a replica whose device stacks
        already hold the adapter wins (seating there skips an LRU
        swap) — prefix overlap still dominates, because re-prefilling
        a lost prefix costs more than one adapter row upload."""
        if not engines:
            raise ValueError("route() needs at least one candidate")
        ids = np.asarray(prompt, np.int32).reshape(-1)
        hashes = _MemoHashes(
            _pc.prompt_block_hashes(self._fp, ids, self._bs))
        best = None
        depths = {}
        for idx, eng in engines.items():
            ov = eng.published_overlap(hashes)
            res = 0
            if adapter_id is not None:
                res = int(eng.adapter_resident(adapter_id))
            depth = eng.queue_depth(priority)
            depths[idx] = depth
            # longest run, then adapter-resident, then least loaded,
            # then lowest index
            key = (ov, res, -depth, -idx)
            if best is None or key > best[0]:
                best = (key, idx, ov)
        return best[1], best[2], depths


class EngineCluster:
    """N serving-engine replicas behind a session-affine router (+
    optional disaggregated prefill tier). The public surface mirrors
    ``ServingEngine`` — ``submit`` / ``step`` / ``run`` / ``serve`` /
    ``cancel`` / ``stats`` / ``shutdown`` / ``num_active`` /
    ``num_queued`` — so the loadgen harness, benches and applications
    drive either interchangeably. Request ids are CLUSTER-global;
    tokens stream through ``stream_callback(rid, tok)`` exactly like
    the engine's.

    Usage::

        cluster = EngineCluster(model, ClusterConfig(num_replicas=2),
                                ServingConfig(num_slots=8))
        rid = cluster.submit(prompt)
        results = cluster.run()         # {rid: np.ndarray of tokens}
    """

    def __init__(self, model, config: Optional[ClusterConfig] = None,
                 serving_config: Optional[ServingConfig] = None,
                 stream_callback: Optional[Callable] = None,
                 draft_model=None, spec_heads=None):
        ccfg = config or ClusterConfig()
        scfg = serving_config or ServingConfig()
        if not cluster_enabled():       # PADDLE_TPU_CLUSTER=0
            ccfg = ClusterConfig(num_replicas=1, prefill_replicas=0)
        self.config = ccfg
        self.serving_config = scfg
        self._disagg = ccfg.prefill_replicas > 0
        if self._disagg and draft_model is not None:
            # the SEPARATE-model case only: head-drafted tree
            # speculation (drafter="heads" + spec_tree) serves
            # disaggregated fine — the draft heads ride the target
            # params on every replica and re-draft from the imported
            # target pool, so nothing extra travels in the handoff
            raise NotImplementedError(
                "disaggregated mode cannot serve a SEPARATE draft "
                "model: the draft pool's prompt K/V is not part of "
                "the prefill->decode transfer payload (the target "
                "pool is) — use n-gram speculation, draft-head tree "
                "speculation (drafter='heads' + spec_tree), or "
                "colocated replicas")
        self._stream = stream_callback
        self._engines: List[ServingEngine] = []
        self._decode_idx: List[int] = []
        self._prefill_idx: List[int] = []
        # scale_up() spawns replicas from the SAME shared config, so
        # the construction inputs are kept (weights are shared jax
        # arrays — a new replica costs executables + pools, not a
        # second copy of the model)
        self._model = model
        self._draft_model = draft_model
        self._spec_heads = spec_heads
        decode_role = "decode" if self._disagg else "both"
        dkw = {"role": decode_role, "retain_results": True}
        # retain_results forced on: a replica's _done dict is the
        # cluster's completion signal (popped every tick, so a
        # long-lived cluster still never accumulates results)
        if self._disagg and scfg.ragged_prefill_rows is None:
            # a disaggregated decode replica never chunk-prefills (all
            # its admissions arrive via admit_prefilled), so the
            # default one-chunk prefill row budget would ride every
            # ragged launch as DEAD static width — shrink it to the
            # minimum unless the caller pinned a value
            dkw["ragged_prefill_rows"] = 1
        self._dkw = dict(dkw)
        for _ in range(ccfg.num_replicas):
            idx = len(self._engines)
            self._engines.append(ServingEngine(
                model, _dc_replace(scfg, **dkw),
                stream_callback=self._make_cb(idx),
                draft_model=draft_model, spec_heads=spec_heads))
            self._decode_idx.append(idx)
        for _ in range(ccfg.prefill_replicas):
            idx = len(self._engines)
            # speculation is a decode feature: the prefill tier runs
            # gamma=0 (n-gram spec composes on the decode replicas —
            # its history is the prompt + first token, both in the
            # handoff), and the transfer width is gamma-independent
            # (_mb_xfer) so the payloads still shape-match
            # speculation (linear OR tree) is a decode feature, so the
            # prefill tier also drops spec_tree and the heads drafter
            # alongside gamma — a decode replica's head re-draft needs
            # only the imported target pool + handoff history
            self._engines.append(ServingEngine(
                model, _dc_replace(scfg, role="prefill",
                                   retain_results=True,
                                   num_speculative_tokens=0,
                                   spec_tree=None,
                                   drafter="ngram"),
                stream_callback=self._make_cb(idx)))
            self._prefill_idx.append(idx)
        self._router = Router(_pc.model_fingerprint(model),
                              int(scfg.block_size))
        self._next_rid = 0              # cluster-global request ids
        self._l2g: Dict[tuple, int] = {}    # (engine, local) -> global
        self._owner: Dict[int, tuple] = {}  # global -> (engine, local)
        self._tokens: Dict[int, list] = {}
        # per-request sampling overrides, kept so a failure-drain
        # requeue re-submits with the SAME knobs
        self._req_samp: Dict[int, dict] = {}
        self._done: Dict[int, np.ndarray] = {}
        # handoffs exported from a prefill engine, waiting for decode
        # capacity: (src_engine_idx, PrefilledRequest)
        self._pending: List[Tuple[int, PrefilledRequest]] = []
        self._failed = set()
        # -- elastic fleet (ISSUE 19) ---------------------------------
        # replicas retired by scale_down(): drained empty (every
        # session live-migrated out), removed from their tier index so
        # the router/placement never see them, kept in _engines so
        # trace export and index stability survive — and so scale_up()
        # can REVIVE one with its executables already compiled (a
        # scale cycle compiles nothing in steady state)
        self._removed = set()
        # live sessions in transit: (global_rid, MigratedSession) —
        # placed onto the coldest live decode replica each tick
        self._pending_mig: List[Tuple[int, MigratedSession]] = []
        # adapter registry replay for replicas spawned/revived AFTER a
        # load_adapter broadcast (weights are shared refs, not copies)
        self._adapter_reg: Dict[int, object] = {}
        self._n_scale_ups = 0
        self._n_scale_downs = 0
        self._n_migrated = 0            # sessions live-migrated
        self._n_replica_ticks = 0       # sum over ticks of live
        #                                 replicas (the autoscale
        #                                 bench's capacity denominator)
        self._d_migration = LatencyDigest()      # export->seated ms
        self._m_replicas = monitor.gauge(
            "serving_replicas_live",
            "live replicas (decode + prefill tiers) in the cluster "
            "right now — scale_up/scale_down/fail_replica move it")
        self._m_migrated = monitor.counter(
            "serving_sessions_migrated",
            "live sessions moved between replicas with their KV "
            "(scale-down drains + rebalancing), token-exact and "
            "invisible to the client")
        self._m_replicas.set(len(self._decode_idx)
                             + len(self._prefill_idx))
        self._autoscale: Optional[AutoscalePolicy] = None
        if ccfg.autoscale is not None and autoscale_enabled():
            self._autoscale = AutoscalePolicy(ccfg.autoscale)
        # mean prompt length EMA — the prompt-mix signal the policy's
        # prefill:decode retune consumes (and dashboards plot)
        self._prompt_len_ema = 0.0
        self._tick_buf: List[tuple] = []
        self._n_routed = 0
        self._n_affinity = 0
        self._n_completed = 0
        # client-side rolled-up latency digests: observed at THE
        # cluster's own stream boundary, so a disaggregated handoff's
        # gap lands in the ITL digest like a client would see it
        self._submit_t: Dict[int, float] = {}
        self._last_emit: Dict[int, float] = {}
        self._d_ttft = LatencyDigest()
        self._d_itl = LatencyDigest()
        self._d_e2e = LatencyDigest()
        # -- fleet flight recorder (ISSUE 15) -------------------------
        # the cluster's OWN trace lane (router decisions, handoff
        # placements, cluster ticks) plus a (engine, local rid) ->
        # global rid history: the live _l2g map pops entries on
        # completion, but export_trace() must rewrite EVERY buffered
        # span — including retired requests' — to the cluster-global
        # id namespace. The history is populated only while tracing
        # (under PADDLE_TPU_TRACE=0 it would be dead weight) and is
        # FIFO-bounded: each ring holds at most `capacity` events, so
        # rids older than every ring's reach can never need rewriting
        # — one cap'd dict, not unbounded growth on a long-lived
        # fleet.
        self._l2g_hist: Dict[tuple, int] = {}
        self._trace = None
        if _tracing.tracing_enabled():
            tr = _tracing.Tracer("EngineCluster")
            tr.set_thread(0, "router")
            self._trace = tr
        self._hist_cap = (len(self._engines) + 1) \
            * _tracing.trace_buffer_capacity()
        # one bounded jax.profiler window around the next N CLUSTER
        # ticks (each replica's work runs inside the cluster tick, so
        # one process-wide capture covers the fleet)
        self._prof = _tracing.ProfilerWindow()
        self._m_affinity = monitor.counter(
            "serving_router_affinity_hits",
            "requests the cluster router placed on a replica already "
            "holding >= 1 of the prompt's prefix blocks (session "
            "affinity working)")
        self._m_depth = monitor.gauge(
            "serving_router_queue_depth",
            "per-replica queued + active depth at the router's last "
            "scoring pass", labels=("replica",))
        # -- fleet health engine (ISSUE 17) ---------------------------
        # the cluster's own watchdog sweep + incident sink: a replica
        # whose tick blows its deadline feeds the existing
        # fail_replica drain, and the cluster-level incident bundle
        # (merged trace, full fleet stats) captures the scene first.
        # Off exactly when the replicas' health engines are off.
        self._health_on = self._engines[0]._health is not None
        self._incident = (_health.IncidentCapture()
                          if self._health_on else None)

    # -- public API ---------------------------------------------------

    @property
    def engines(self) -> List[ServingEngine]:
        """All replicas, decode tier first (read-only introspection —
        tests, benches, dashboards)."""
        return list(self._engines)

    @property
    def num_active(self) -> int:
        return sum(self._engines[i].num_active
                   for i in self._live()) \
            + len(self._pending) + len(self._pending_mig)

    @property
    def num_queued(self) -> int:
        return sum(self._engines[i].num_queued for i in self._live())

    @property
    def num_slots(self) -> int:
        """Aggregate DECODE slot capacity (the loadgen closed-loop
        concurrency default)."""
        return sum(self._engines[i].config.num_slots
                   for i in self._decode_idx if i not in self._failed)

    def submit(self, prompt, max_new_tokens=None, temperature=None,
               top_k=None, top_p=None, priority=0,
               max_queue_wait_ms=None, adapter_id=None) -> int:
        """Route one request to a replica (prefill tier when
        disaggregated) and queue it there; returns the CLUSTER-global
        request id tokens stream under.
        ``temperature``/``top_k``/``top_p`` are this request's
        sampling overrides, forwarded to the owning replica's per-slot
        sampling tensors (and preserved across a failure-drain
        requeue; in disaggregated mode they travel with the KV handoff
        payload to the decode replica). ``priority`` is the request's
        scheduling class — it weights the router's queue-depth
        tiebreak, orders admission on the owning replica, may preempt
        strictly-lower work there, rides the disaggregated handoff,
        and survives a failure-drain requeue. ``max_queue_wait_ms``
        bounds the replica-side queue wait (outcome="timeout").
        ``adapter_id`` serves the request under a LoRA adapter
        registered via :meth:`load_adapter` — it weights the router's
        tiebreak toward replicas already holding the adapter
        resident, rides the disaggregated KV handoff (the prefill
        tier computes the prompt's KV under the adapter), and
        survives a failure-drain requeue like the sampling knobs."""
        ids = np.asarray(prompt, np.int32).reshape(-1)
        # prompt-length-mix EMA: the autoscaler's prefill:decode
        # retune signal (longer prompts shift pressure prefill-ward)
        n = float(ids.size)
        self._prompt_len_ema = (
            n if self._prompt_len_ema == 0.0
            else 0.9 * self._prompt_len_ema + 0.1 * n)
        if self._disagg:
            # mirror engine.submit()'s pool-fit rejection for the
            # DECODE side: the prefill tier reserves only prompt
            # blocks, so without this check a request whose decode
            # reservation can never fit any decode pool would prefill,
            # export, and then sit as a forever-pending handoff
            # (run() would never drain)
            live = [i for i in self._decode_idx
                    if i not in self._failed]
            if not live:
                raise RuntimeError(
                    "all decode replicas failed: a disaggregated "
                    "cluster's prefill tier cannot decode, so new "
                    "requests cannot be served (in-flight ones "
                    "terminate with the tokens already streamed)")
            de = self._engines[live[0]]
            max_new = int(de.config.max_new_tokens
                          if max_new_tokens is None
                          else max_new_tokens)
            worst = de._worst_for(ids.size, max_new)
            cap = max(self._engines[i]._alloc.num_blocks - 1
                      for i in live)
            if worst > cap:
                raise ValueError(
                    f"request needs {worst} blocks on a decode "
                    f"replica; the largest live decode pool has "
                    f"only {cap}")
        rid = self._next_rid
        samp = {k: v for k, v in (("temperature", temperature),
                                  ("top_k", top_k), ("top_p", top_p),
                                  ("max_queue_wait_ms",
                                   max_queue_wait_ms),
                                  ("adapter_id", adapter_id))
                if v is not None}
        if int(priority):
            samp["priority"] = int(priority)
        self._route_submit(rid, ids, max_new_tokens, samp)
        self._next_rid += 1
        if samp:
            self._req_samp[rid] = samp
        self._tokens[rid] = []
        self._submit_t[rid] = time.monotonic()
        return rid

    def load_adapter(self, adapter_id, weights) -> int:
        """Register LoRA adapter ``adapter_id`` on EVERY live replica
        (prefill tier included — disaggregated prompts must prefill
        under the adapter's deltas). Broadcasting the registry is what
        makes the router's adapter-affinity a soft optimization: any
        replica can serve any tenant, residency just decides who does
        it without an LRU swap."""
        aid = None
        for i in self._live():
            aid = self._engines[i].load_adapter(adapter_id, weights)
        if aid is None:
            raise RuntimeError(
                "no live replicas to register the adapter on")
        # registry replay source: a replica spawned or revived AFTER
        # this broadcast re-registers from here (shared array refs,
        # not copies) so migrated adapter sessions land anywhere
        self._adapter_reg[int(aid)] = weights
        return aid

    def cancel(self, request_id: int) -> bool:
        """Cancel a request anywhere in its cluster lifetime: queued
        or IN FLIGHT on its replica (forwarded to
        ``ServingEngine.cancel``, which retires the slot mid-decode
        and frees its blocks), or parked as a pending disaggregated
        handoff (the payload is dropped — its prefill-engine blocks
        were already freed at export). A request that already
        streamed tokens surfaces them as a partial result through
        ``run()``."""
        owner = self._owner.get(request_id)
        if owner is None:
            # an in-transit migration? (exported, not yet re-seated —
            # owner_of() is None for exactly that window)
            for k, (g, _rec) in enumerate(self._pending_mig):
                if g == request_id:
                    del self._pending_mig[k]
                    # a migrated session has streamed by definition:
                    # surface the partial tokens like an in-flight
                    # cancel would
                    self._finish(g)
                    return True
            return False
        idx, lrid = owner
        streamed = bool(self._tokens.get(request_id))
        if not self._engines[idx].cancel(lrid):
            # not queued / not in a slot there: a pending handoff?
            for k, (src, rec) in enumerate(self._pending):
                if (src, rec.request_id) == (idx, lrid):
                    del self._pending[k]
                    break
            else:
                return False
        # the replica may have parked a partial result under the local
        # rid — drop it; the cluster's own stream records are the
        # client-facing result
        self._engines[idx]._done.pop(lrid, None)
        self._l2g.pop((idx, lrid), None)
        self._owner.pop(request_id, None)
        self._req_samp.pop(request_id, None)
        if streamed:
            self._finish(request_id)        # partial tokens + e2e obs
        else:
            self._tokens.pop(request_id, None)
            self._submit_t.pop(request_id, None)
            self._last_emit.pop(request_id, None)
        return True

    def step(self) -> List[tuple]:
        """One cluster tick: advance every prefill engine and stream
        its finished prompts' KV blocks into decode replicas, then
        advance every decode replica. Returns this tick's
        ``[(request_id, token), ...]`` across the whole cluster. An
        armed profiling window (``profile(n_ticks)``) brackets the
        whole cluster tick."""
        with self._prof.tick():
            return self._step_impl()

    def _step_impl(self) -> List[tuple]:
        t0 = time.monotonic()
        self._tick_buf = []
        # capacity denominator for goodput-per-replica-tick: one unit
        # per LIVE replica per cluster tick (the autoscale bench's
        # "what did this capacity cost" axis)
        self._n_replica_ticks += sum(
            1 for i in self._decode_idx + self._prefill_idx
            if i not in self._failed)
        for i in list(self._prefill_idx):
            if i in self._failed:
                continue
            eng = self._engines[i]
            if eng.num_queued or eng.num_active:
                self._safe_step(i)
            if i not in self._failed:
                for rec in eng.pop_prefilled():
                    self._pending.append((i, rec))
        self._place_handoffs()
        self._place_migrations()
        # decode replicas tick dispatch-all-then-commit-all: every
        # replica's executable is IN FLIGHT before any replica blocks
        # on a token fetch, so N launches run concurrently instead of
        # serially (``tick_dispatch`` / ``tick_commit`` are the
        # engine's own two halves of ``step()``)
        stepped = []
        for i in list(self._decode_idx):
            if i in self._failed:
                continue
            eng = self._engines[i]
            if eng.num_queued or eng.num_active:
                self._safe_phase(i, dispatch=True)
                stepped.append(i)
        for i in stepped:
            if i in self._failed:
                continue
            self._safe_phase(i, dispatch=False)
        self._collect_done()
        if self._health_on:
            self._watchdog_sweep()
        if self._autoscale is not None:
            self._autoscale_tick()
        if self._trace is not None:
            self._trace.emit(
                "cluster tick", tid=0, t0=t0,
                args={"pending_handoffs": len(self._pending),
                      "pending_migrations": len(self._pending_mig),
                      "emitted": len(self._tick_buf),
                      "failed": len(self._failed)})
        return self._tick_buf

    def run(self) -> Dict[int, np.ndarray]:
        """Drive ``step()`` until every replica drains; returns (and
        clears) the tokens of every request completed since the last
        ``run()``, keyed by cluster-global request id."""
        while self.num_queued or self.num_active:
            self.step()
        done, self._done = self._done, {}
        return done

    def serve(self, prompts, max_new_tokens=None) -> List[np.ndarray]:
        """Batch convenience: submit all, run to completion, return
        token arrays in submission order."""
        rids = [self.submit(p, max_new_tokens) for p in prompts]
        done = self.run()
        return [done[r] for r in rids]

    def fail_replica(self, index: int):
        """Administratively fail one replica (also invoked when its
        ``step()`` raises): its admission queue drains back through
        the router onto the surviving replicas — global request ids
        are preserved, the requests simply prefill again elsewhere —
        and its in-flight requests terminate with the tokens already
        streamed (partial results, returned by ``run()`` normally).
        Raises RuntimeError when no replica survives to take the
        drained queue."""
        if index in self._failed:
            return
        self._failed.add(index)
        eng = self._engines[index]
        requeue = []
        for req in list(eng._queue):
            g = self._l2g.pop((index, req.request_id), None)
            eng.cancel(req.request_id)      # terminal queue-wait obs
            if g is None:
                continue
            if req.resume is not None:
                # a PREEMPTED request waiting to resume: its KV lives
                # only on the failed replica (host-tier payload +
                # published blocks), so it cannot continue elsewhere —
                # terminate with the tokens already streamed, like an
                # in-flight slot
                self._finish(g)
                continue
            requeue.append((g, req.prompt, req.max_new_tokens))
        for slot in eng._slots:
            if slot is None:
                continue
            # pop the mapping: the failed engine never emits again
            # (already-exported handoffs are NOT in _slots — their
            # payloads survive and still place into decode replicas)
            g = self._l2g.pop((index, slot.rid), None)
            if g is not None:
                self._finish(g)             # partial result
        for g, prompt, max_new in requeue:
            try:
                self._route_submit(g, prompt, max_new)
            except QueueShedError:
                # a surviving replica shed the drained request: the
                # fault-tolerance path must not crash mid-drain (the
                # remaining requeues' mappings are already popped) —
                # terminate it with whatever streamed, like an
                # in-flight casualty
                warnings.warn(
                    f"request {g} shed during the failure drain; "
                    "terminating with the tokens already streamed")
                self._finish(g)
        # wipe the dead replica's affinity surface: the candidate
        # filter already hides it from the router, but its content
        # index + host-tier published spills would otherwise linger as
        # dead weight for the fleet's lifetime — and any path that
        # ever probes the engine again (diagnostics, a future revival)
        # must see overlap 0, not hashes for KV nobody serves.
        # Best-effort: the replica may be torn down mid-call.
        try:
            eng.purge_published()
        except Exception:       # pragma: no cover - torn down
            pass
        self._set_replica_gauge()

    # -- elastic fleet (ISSUE 19) -------------------------------------

    def scale_up(self, role: str = "decode", warm: bool = True) -> int:
        """Add one replica to ``role``'s tier ("decode" / "prefill")
        from the SAME shared construction inputs (weights are shared
        jax arrays — a replica costs executables + pools, never a
        second model copy) and return its index. A replica previously
        retired by :meth:`scale_down` is REVIVED in preference to
        building a new one: its executables are already compiled, so a
        steady-state scale cycle compiles NOTHING. Fresh or revived,
        the replica replays the cluster's adapter registry (so
        migrated LoRA sessions can land on it) and — with ``warm=True``
        — pre-builds its executables off the request path: the
        migration export/import pair plus one throwaway 1-token
        request driven to completion before the router ever sees the
        replica."""
        if role not in ("decode", "prefill"):
            raise ValueError(f"role must be 'decode' or 'prefill', "
                             f"got {role!r}")
        if role == "prefill" and not self._disagg:
            raise ValueError(
                "cannot scale the prefill tier of a colocated "
                "cluster (prefill_replicas=0)")
        tier = (self._decode_idx if role == "decode"
                else self._prefill_idx)
        want = (("decode" if self._disagg else "both")
                if role == "decode" else "prefill")
        idx = None
        for i in sorted(self._removed):
            if self._engines[i]._role == want:
                idx = i
                break
        revived = idx is not None
        if revived:
            self._removed.discard(idx)
            eng = self._engines[idx]
        else:
            idx = len(self._engines)
            if role == "decode":
                eng = ServingEngine(
                    self._model,
                    _dc_replace(self.serving_config, **self._dkw),
                    stream_callback=self._make_cb(idx),
                    draft_model=self._draft_model,
                    spec_heads=self._spec_heads)
            else:
                # mirror __init__'s prefill-tier construction:
                # speculation is a decode feature
                eng = ServingEngine(
                    self._model,
                    _dc_replace(self.serving_config, role="prefill",
                                retain_results=True,
                                num_speculative_tokens=0,
                                spec_tree=None, drafter="ngram"),
                    stream_callback=self._make_cb(idx))
            self._engines.append(eng)
            self._hist_cap = (len(self._engines) + 1) \
                * _tracing.trace_buffer_capacity()
        for aid, w in self._adapter_reg.items():
            # replay registrations the replica missed (revived
            # replicas keep their registry; known() makes this
            # idempotent either way)
            if eng._lora_pool is not None \
                    and not eng._lora_pool.known(aid):
                eng.load_adapter(aid, w)
        if warm and not revived:
            # a revived replica's executables are already compiled —
            # only a FRESH engine needs the off-path warm pass
            try:
                eng.warm_migration()
            except Exception:   # pragma: no cover - defensive
                warnings.warn(
                    f"replica {idx} failed its migration warm-up; "
                    "the first real transfer will compile inline")
            # one throwaway request end-to-end: prefill + decode (or
            # prefill + export on the prefill tier) executables build
            # NOW, not under the first routed request. A 1-token
            # prompt publishes nothing (cache_len < block_size), so
            # the affinity surface stays clean.
            lrid = eng.submit([1], 1)
            guard = 0
            while (eng.num_queued or eng.num_active) and guard < 64:
                eng.step()
                eng.pop_prefilled()     # prefill role: drop handoff
                guard += 1
            eng._done.pop(lrid, None)
        # joining the tier index LAST: the router and placement loops
        # only ever see a fully-warmed replica
        tier.append(idx)
        self._n_scale_ups += 1
        self._set_replica_gauge()
        if self._trace is not None:
            self._trace.instant(
                "scale up", tid=0,
                args={"replica": idx, "role": role,
                      "revived": revived})
        return idx

    def scale_down(self, index: Optional[int] = None) -> int:
        """Retire one replica with a LIVE-MIGRATING drain: every
        resident session leaves through the compiled export path and
        re-seats on a surviving replica at its exact continuation
        state (cache_len, last token, emit count, sampling row,
        priority, adapter pin) — clients just see their streams
        continue; greedy output is token-exact vs never-migrated.
        Queued-but-unserved work re-routes as fresh submissions.
        ``index`` defaults to the COLDEST live decode replica. The
        replica leaves its tier index immediately (no new routes, no
        placements), its published-prefix surface is purged (affinity
        follows the migrated KV), and the engine object is KEPT for a
        later :meth:`scale_up` revival — executables stay compiled.
        Raises when ``index`` is the last live decode replica (a
        drain needs somewhere to put the sessions)."""
        if index is None:
            cands = [i for i in self._decode_idx
                     if i not in self._failed]
            if len(cands) < 2:
                raise RuntimeError(
                    "scale_down needs >= 2 live decode replicas "
                    "(the drain live-migrates onto the survivors)")
            index = min(cands, key=lambda j:
                        self._engines[j].num_active
                        + self._engines[j].num_queued)
        if index in self._failed or index in self._removed:
            raise ValueError(
                f"replica {index} is already failed/removed")
        if index in self._decode_idx:
            if sum(1 for i in self._decode_idx
                   if i not in self._failed) < 2:
                raise RuntimeError(
                    "cannot drain the last live decode replica")
            self._decode_idx.remove(index)
        elif index in self._prefill_idx:
            self._prefill_idx.remove(index)
        else:
            raise ValueError(f"no replica {index}")
        self._removed.add(index)
        eng = self._engines[index]
        # already-exported handoffs first: their payloads are
        # self-contained, they just place like any pending handoff
        for rec in eng.pop_prefilled():
            self._pending.append((index, rec))
        migrations, fresh = eng.drain_sessions()
        for rec in migrations:
            g = self._l2g.pop((index, rec.request_id), None)
            if g is None:       # cancelled upstream: drop
                continue
            # in transit: owner_of() is None until re-seated
            self._owner.pop(g, None)
            self._pending_mig.append((g, rec))
        for req in fresh:
            g = self._l2g.pop((index, req.request_id), None)
            if g is None:
                continue
            try:
                self._route_submit(g, req.prompt, req.max_new_tokens)
            except QueueShedError:
                warnings.warn(
                    f"request {g} shed during the scale-down drain; "
                    "terminating with the tokens already streamed")
                self._finish(g)
        # affinity follows the KV: the drained replica's index must
        # stop scoring overlaps the survivors now serve
        eng.purge_published()
        self._n_scale_downs += 1
        self._set_replica_gauge()
        if self._trace is not None:
            self._trace.instant(
                "scale down", tid=0,
                args={"replica": index,
                      "migrations": len(migrations),
                      "requeued": len(fresh)})
        self._place_migrations()
        return index

    def rebalance(self, max_moves: int = 1) -> int:
        """Cluster-level load shedding: while the hottest live decode
        replica is >= 2 sessions deeper than the coldest, export its
        best victim (lowest priority class, newest admit — the PR 14
        victim policy, minus the mid-prefill preference since those
        have nothing worth moving) and live-migrate it to the coldest
        replica. Returns the number of sessions moved (bounded by
        ``max_moves``). A no-op below 2 live replicas or under
        balanced load — safe to call every tick."""
        moved = 0
        for _ in range(int(max_moves)):
            live = [i for i in self._decode_idx
                    if i not in self._failed]
            if len(live) < 2:
                break

            def _load(j):
                return (self._engines[j].num_active
                        + self._engines[j].num_queued)

            hot = max(live, key=_load)
            cold = min(live, key=_load)
            if _load(hot) - _load(cold) < 2:
                break
            eng = self._engines[hot]
            # the replica's slots are read here: commit the tick it
            # has in flight first (its tokens stream under the mapping
            # that is still in place, and a slot it retires is no
            # candidate)
            eng._flush_pipe()
            cands = [i for i, s in enumerate(eng._slots)
                     if s is not None and not s.handoff
                     and not (s.pend_pos is not None
                              and s.resume is None)]
            if not cands:
                break
            victim = min(cands, key=lambda i: (
                eng._slots[i].priority, -eng._slots[i].admit_t))
            lrid = eng._slots[victim].rid
            g = self._l2g.pop((hot, lrid), None)
            rec = eng.export_session(victim)
            if g is None:       # pragma: no cover - cancelled race
                continue
            self._owner.pop(g, None)
            self._pending_mig.append((g, rec))
            if self._trace is not None:
                self._trace.instant(
                    "rebalance", tid=0,
                    args={"rid": g, "src": hot, "dst_hint": cold})
            moved += 1
        if moved:
            self._place_migrations()
        return moved

    def _place_migrations(self):
        """Re-seat in-transit migrated sessions, coldest live decode
        replica first. A session that finds no capacity this tick
        stays pending (``num_active`` counts it, so ``run()`` keeps
        ticking); a replica that RAISES during admission is treated
        as failed mid-migration — it drains through ``fail_replica``
        and the session retries the next candidate, degrading to the
        recompute path if its payload import died with the target."""
        if not self._pending_mig:
            return
        still = []
        for g, rec in self._pending_mig:
            placed = False
            while not placed:
                live = [i for i in self._decode_idx
                        if i not in self._failed]
                if not live:
                    warnings.warn(
                        "no live decode replica to seat migrated "
                        f"session {g}; terminating with the tokens "
                        "already streamed")
                    self._finish(g)
                    placed = True       # terminal, not re-queued
                    break
                for i in sorted(live, key=lambda j:
                                self._engines[j].num_active
                                + self._engines[j].num_queued):
                    try:
                        lrid = self._engines[i].admit_migrated(rec)
                    except Exception as exc:    # noqa: BLE001
                        warnings.warn(
                            f"replica {i} failed admitting migrated "
                            f"session {g} ({exc!r}); failing it and "
                            "retrying elsewhere")
                        self.fail_replica(i)
                        break       # re-derive the live set
                    if lrid is None:
                        continue    # no capacity there right now
                    self._l2g[(i, lrid)] = g
                    self._owner[g] = (i, lrid)
                    self._hist_put((i, lrid), g)
                    self._d_migration.observe(
                        1000.0 * (time.monotonic() - rec.export_t))
                    self._n_migrated += 1
                    self._m_migrated.inc()
                    if self._trace is not None:
                        self._trace.instant(
                            "migration placed", tid=0,
                            args={"rid": g, "dst": i,
                                  "blocks": rec.n_blocks,
                                  "recompute": rec.payload is None})
                    placed = True
                    break
                else:
                    # every live candidate said "not right now":
                    # park for the next tick
                    still.append((g, rec))
                    placed = True
        self._pending_mig = still

    def _shed_backlog(self, new_idx):
        """After a scale-up, spread the EXISTING backlog: each
        survivor's queued-but-unserved requests beyond the fleet's
        fair share re-route through the router, which places them on
        the emptiest replica — the one that just joined. Without this
        the new capacity only absorbs future arrivals while the burst
        that triggered it keeps queueing on the old replicas.
        Preempted resume-carrying waiters stay put (their KV lives
        where they queued). Colocated tiers only: a disaggregated
        cluster's router queue lives on the prefill tier."""
        live = [i for i in self._decode_idx if i not in self._failed]
        if len(live) < 2:
            return
        total = sum(self._engines[i].num_queued for i in live)
        fair = -(-total // len(live))               # ceil
        for i in live:
            if i == new_idx:
                continue
            eng = self._engines[i]
            extra = eng.num_queued - fair
            if extra <= 0:
                continue
            for req in eng.shed_queued(extra):
                g = self._l2g.pop((i, req.request_id), None)
                if g is None:
                    continue
                try:
                    self._route_submit(g, req.prompt,
                                       req.max_new_tokens)
                except QueueShedError:
                    warnings.warn(
                        f"request {g} shed during the scale-up "
                        "backlog spread; terminating with the tokens "
                        "already streamed")
                    self._finish(g)

    def _autoscale_tick(self):
        """One control-loop step: gather the tick's signals (queue
        depth per slot, occupancy, worst fast SLO burn rate, busiest
        roofline) and execute the policy's decision. At most ONE
        replica changes per tick — decode tier first; the prefill
        ratio retune only runs on decode-hold ticks."""
        pol = self._autoscale
        dec = [i for i in self._decode_idx if i not in self._failed]
        if not dec:
            return
        burn = 0.0
        busy = 0.0
        for i in dec:
            eng = self._engines[i]
            if eng._health is not None:
                burn = max(burn,
                           eng._health.burn_rates().get("fast", 0.0))
            r = eng._roofline()
            # None off the chip: no device peak, so no busy signal
            busy = max(busy, r["step_mfu"] or 0.0,
                       r["step_hbm_bw_util"] or 0.0)
        sig = {
            "replicas": len(dec),
            "slots": sum(self._engines[i].config.num_slots
                         for i in dec),
            "active": sum(self._engines[i].num_active for i in dec)
            + len(self._pending_mig),
            "queued": sum(self._engines[i].num_queued for i in dec),
            "burn_fast": burn,
            "busy": busy,
            "mean_prompt_len": self._prompt_len_ema,
        }
        d = pol.decide(sig)
        if d == "up":
            try:
                idx = self.scale_up("decode")
            except Exception as exc:    # pragma: no cover - defensive
                warnings.warn(f"autoscale scale_up failed: {exc!r}")
                return
            if not self._disagg:
                self._shed_backlog(idx)
            return
        if d == "down":
            try:
                self.scale_down()
            except RuntimeError:
                pass        # last live decode replica: hold instead
            return
        if not self._disagg:
            return
        pf = [i for i in self._prefill_idx if i not in self._failed]
        sig.update({
            "prefill_replicas": len(pf),
            "prefill_slots": sum(self._engines[i].config.num_slots
                                 for i in pf),
            "prefill_active": sum(self._engines[i].num_active
                                  for i in pf),
            "prefill_queued": sum(self._engines[i].num_queued
                                  for i in pf),
        })
        d = pol.decide_prefill(sig)
        if d == "up":
            try:
                self.scale_up("prefill")
            except Exception as exc:    # pragma: no cover - defensive
                warnings.warn(
                    f"autoscale prefill scale_up failed: {exc!r}")
        elif d == "down" and pf:
            cold = min(pf, key=lambda j:
                       self._engines[j].num_active
                       + self._engines[j].num_queued)
            try:
                self.scale_down(cold)
            except (RuntimeError, ValueError):
                pass

    def _watchdog_sweep(self):
        """Per-tick stuck-replica check: a replica whose watchdog
        trips gets the scene captured (cluster-level incident bundle:
        merged trace + full fleet stats) and is then drained through
        the existing ``fail_replica`` path — a wedged replica degrades
        the fleet instead of freezing it."""
        for i in list(self._live()):
            eng = self._engines[i]
            try:
                stuck = eng.watchdog_stuck()
            except Exception:       # pragma: no cover - defensive
                stuck = True
            if not stuck:
                continue
            warnings.warn(
                f"replica {i} failed its stuck-tick watchdog "
                "deadline; draining it through fail_replica()")
            if self._incident is not None:
                h = eng.health()
                try:
                    self._incident.maybe_capture(
                        "stuck_tick", "page", stats_cb=self.stats,
                        trace_cb=self.export_trace,
                        journal=(h or {}).get("journal", []))
                except Exception:
                    pass            # capture never takes the fleet down
            self.fail_replica(i)

    def health(self) -> Optional[dict]:
        """Fleet health roll-up: the minimum replica score, the union
        of firing alerts, the failed set, and every replica's own
        snapshot. None when the health engine is off."""
        if not self._health_on:
            return None
        reps = []
        for i, eng in enumerate(self._engines):
            if i in self._failed:
                reps.append(None)
                continue
            try:
                reps.append(eng.health())
            except Exception:       # pragma: no cover - torn down
                reps.append(None)
        live = [r for r in reps if r is not None]
        return {
            "health_score": min((r["health_score"] for r in live),
                                default=0.0),
            "alerts_firing": sorted(
                {a for r in live for a in r["alerts_firing"]}),
            "failed_replicas": sorted(self._failed),
            "replicas": reps,
        }

    def owner_of(self, request_id: int) -> Optional[Tuple[int, int]]:
        """Current ``(replica_index, local_rid)`` of a LIVE request,
        or None once it finished — the loadgen record export stamps
        its NDJSON rows with this so offline analysis can join them
        against the merged trace's per-replica pids."""
        return self._owner.get(request_id)

    def profile(self, n_ticks: int, path: Optional[str] = None):
        """Arm ONE bounded ``jax.profiler`` capture around the next
        ``n_ticks`` CLUSTER ticks — every replica's executables run
        inside the cluster tick, so one process-wide capture covers
        the fleet (jax allows a single live profiler session; this is
        the cluster-forwarded form of ``ServingEngine.profile``).
        ``path`` defaults to ``$PADDLE_TPU_PROFILE_DIR``; returns the
        capture dir, or None under ``PADDLE_TPU_TRACE=0``."""
        return self._prof.arm(n_ticks, path)

    def _hist_put(self, key, g):
        """Record one (replica, local rid) -> global rid mapping for
        the trace rewrite. No-op when tracing is disabled (nothing
        will ever be exported); FIFO-pruned past ``_hist_cap`` (an
        rid older than every ring buffer's reach cannot appear in any
        buffered span, so its mapping is dead)."""
        if self._trace is None:
            return
        h = self._l2g_hist
        h[key] = g
        if len(h) > self._hist_cap:
            # dicts iterate in insertion order: drop the oldest (one
            # insert can only overflow by one)
            h.pop(next(iter(h)))

    # request-span names the trace rewrite maps into the global id
    # namespace: "req<rid>" and "req<rid> queued"
    _REQ_NAME = re.compile(r"^req(\d+)(\s.*)?$")

    def export_trace(self, path: Optional[str] = None):
        """Merge the router's and EVERY replica's span ring buffers
        into ONE Chrome/Perfetto trace: each replica keeps its own
        pid lane (process names rewritten to ``replica<i>:<role>``),
        the cluster's router lane rides alongside, and every request
        id — span names like ``req3`` AND ``rid`` args — is rewritten
        to the CLUSTER-global id, so a disaggregated request's route
        decision, prefill chunks, handoff flow arrow, decode ticks
        and preempt/resume marks line up under one rid end-to-end.
        Returns the trace dict when ``path`` is None, else writes the
        JSON and returns ``path``; None when tracing is disabled
        (``PADDLE_TPU_TRACE=0`` — the recorder is inert)."""
        if self._trace is None:
            return None
        events = list(self._trace.chrome_events())
        for idx, eng in enumerate(self._engines):
            tr = eng.tracer
            if tr is None:          # pragma: no cover - mixed switch
                continue
            events.extend(
                self._rewrite_events(idx, eng, tr.chrome_events()))
        doc = {"traceEvents": events, "displayTimeUnit": "ms"}
        if path is None:
            return doc
        os.makedirs(os.path.dirname(os.path.abspath(path)),
                    exist_ok=True)
        with open(path, "w") as f:
            json.dump(doc, f, default=str)
        return path

    def _rewrite_events(self, idx, eng, evs):
        """One replica's Chrome events, mapped into the cluster
        namespace: request ids -> global ids (span names and args),
        process name -> ``replica<i>:<role>``. Events whose local rid
        never passed through this cluster (none, in practice) keep
        their local id rather than guessing."""
        out = []
        for ev in evs:
            if ev.get("ph") == "M":
                if ev.get("name") == "process_name":
                    ev = dict(ev, args={
                        "name": f"replica{idx}:{eng._role}"})
                out.append(ev)
                continue
            name = ev.get("name", "")
            args = ev.get("args")
            new = None
            m = self._REQ_NAME.match(name)
            if m is not None:
                g = self._l2g_hist.get((idx, int(m.group(1))))
                if g is not None:
                    new = dict(ev,
                               name=f"req{g}{m.group(2) or ''}")
            if args and "rid" in args:
                g = self._l2g_hist.get((idx, args["rid"]))
                if g is not None:
                    new = dict(new if new is not None else ev)
                    new["args"] = dict(args, rid=g)
            out.append(new if new is not None else ev)
        return out

    def stats(self) -> dict:
        """Cluster-aggregate snapshot: per-replica ``stats()`` dicts
        under ``replicas`` plus rolled-up routing / transfer /
        throughput / latency keys (the client-side view across the
        whole cluster — the goodput harness's denominators). Failed or
        torn-down replicas are SKIPPED in the roll-ups (annotated in
        ``failed_replicas``, None in ``replicas``) instead of raising
        — the fleet snapshot must survive its own casualties."""
        reps_all: List[Optional[dict]] = []
        skipped = set(self._failed)
        for i, e in enumerate(self._engines):
            if i in self._failed:
                reps_all.append(None)
                continue
            try:
                reps_all.append(e.stats())
            except Exception:       # torn down mid-snapshot
                skipped.add(i)
                reps_all.append(None)
        live_idx = [i for i, r in enumerate(reps_all) if r is not None]
        reps = [reps_all[i] for i in live_idx]
        # headline roofline roll-up: the busiest replica's numbers as
        # a PAIR from that ONE replica — a per-metric max could
        # combine an MFU and a bandwidth figure no single replica
        # exhibits, which is useless for bound classification
        # (off the chip the utilizations are None — no device peak —
        # and the first live replica stands in)
        if reps:
            busy = max(range(len(reps)), key=lambda i: (
                reps[i]["roofline"]["step_mfu"] or 0.0,
                reps[i]["roofline"]["step_hbm_bw_util"] or 0.0))
            roofline = {
                "device": reps[busy]["roofline"]["device"],
                "busiest_replica": live_idx[busy],
                "step_mfu": reps[busy]["roofline"]["step_mfu"],
                "step_hbm_bw_util":
                    reps[busy]["roofline"]["step_hbm_bw_util"],
            }
        else:                       # every replica down
            roofline = {"device": None, "busiest_replica": None,
                        "step_mfu": None, "step_hbm_bw_util": None}
        return {
            "num_replicas": len(self._decode_idx),
            "prefill_replicas": len(self._prefill_idx),
            "disaggregated": self._disagg,
            "cluster_enabled": cluster_enabled(),
            "failed_replicas": sorted(skipped),
            "active": self.num_active,
            "queued": self.num_queued,
            "pending_handoffs": len(self._pending),
            # elastic fleet (ISSUE 19): ALWAYS present — a fixed-N
            # fleet (no policy / kill switch) reports its static size
            # and zeros, so dashboards never KeyError across configs
            "replicas_live": sum(
                1 for i in self._decode_idx + self._prefill_idx
                if i not in self._failed),
            "removed_replicas": sorted(self._removed),
            "scale_ups": self._n_scale_ups,
            "scale_downs": self._n_scale_downs,
            "sessions_migrated": self._n_migrated,
            "pending_migrations": len(self._pending_mig),
            "migration_ms": self._d_migration.summary(),
            "replica_ticks": self._n_replica_ticks,
            "mean_prompt_len": round(self._prompt_len_ema, 2),
            "autoscale": (self._autoscale.state()
                          if self._autoscale is not None else None),
            "router_requests": self._n_routed,
            "router_affinity_hits": self._n_affinity,
            "router_affinity_hit_rate":
                self._n_affinity / self._n_routed
                if self._n_routed else 0.0,
            "kv_blocks_transferred":
                sum(r["kv_blocks_imported"] for r in reps),
            "preemptions": sum(r["preemptions"] for r in reps),
            "kv_blocks_spilled":
                sum(r["kv_blocks_spilled"] for r in reps),
            "kv_blocks_restored":
                sum(r["kv_blocks_restored"] for r in reps),
            "host_tier_bytes":
                sum(r["host_tier_bytes"] for r in reps),
            # multi-LoRA roll-ups: ALWAYS present (False/0 on
            # base-model fleets) — sums over live replicas, matching
            # the host-tier pattern above
            "lora_enabled": any(r["lora_enabled"] for r in reps),
            "lora_adapters_resident":
                sum(r["lora_adapters_resident"] for r in reps),
            "lora_adapter_swaps":
                sum(r["lora_adapter_swaps"] for r in reps),
            "lora_host_tier_bytes":
                sum(r["lora_host_tier_bytes"] for r in reps),
            "prefix_tokens_reused":
                sum(r["prefix_tokens_reused"] for r in reps),
            "tokens_total": sum(r["tokens_total"] for r in reps),
            "requests_completed": self._n_completed,
            "decode_steps": sum(r["decode_steps"] for r in reps),
            "executables_compiled":
                sum(r["executables_compiled"] for r in reps),
            "ttft_ms": self._d_ttft.summary(),
            "itl_ms": self._d_itl.summary(),
            "e2e_ms": self._d_e2e.summary(),
            # fleet flight recorder (ISSUE 15): ALWAYS present —
            # killed/idle clusters report False/0 so dashboards never
            # KeyError across a rolled-back fleet
            "tracing": self._trace is not None,
            "trace_events_dropped":
                (self._trace.dropped
                 if self._trace is not None else 0)
                + sum(r["trace_events_dropped"] for r in reps),
            "profile_captures": self._prof.captures,
            # fleet health (ISSUE 17): ALWAYS present — min score over
            # live replicas, sums for the counters; a killed fleet
            # reports 1.0 / zeros
            "health_score": min((r["health_score"] for r in reps),
                                default=0.0 if skipped else 1.0),
            "alerts_firing": sum(r["alerts_firing"] for r in reps),
            "alerts_fired_total":
                sum(r["alerts_fired_total"] for r in reps),
            "incidents_captured":
                sum(r["incidents_captured"] for r in reps)
                + (self._incident.captured
                   if self._incident is not None else 0),
            "nonfinite_logits_ticks":
                sum(r["nonfinite_logits_ticks"] for r in reps),
            # async tick pipeline (ISSUE 20): ALWAYS present — max
            # depth across live replicas (the fleet's commit lag is
            # the deepest replica's) and a flush-count sum; a sync or
            # killed fleet reports 0/0
            "async_depth": max((r["async_depth"] for r in reps),
                               default=0),
            "pipeline_flushes":
                sum(r["pipeline_flushes"] for r in reps),
            "roofline": roofline,
            "replicas": reps_all,
        }

    def shutdown(self, check_leaks: bool = True) -> bool:
        """Drain every replica's queue (terminal queue-wait
        observations) and sweep every allocator's free/cached/
        referenced partition — the per-replica leak check, fleet-wide.
        Failed replicas are swept too (their blocks were never freed
        by the drain, so live-slot blocks are passed as expected)."""
        for eng in self._engines:
            eng.shutdown(check_leaks=check_leaks)
        return True

    # -- internals ----------------------------------------------------

    def _live(self):
        return [i for i in range(len(self._engines))
                if i not in self._failed and i not in self._removed]

    def _set_replica_gauge(self):
        self._m_replicas.set(sum(
            1 for i in self._decode_idx + self._prefill_idx
            if i not in self._failed))

    def _make_cb(self, idx):
        def cb(lrid, tok):
            g = self._l2g.get((idx, lrid))
            if g is not None:
                self._on_token(g, tok)
        return cb

    def _on_token(self, g, tok):
        now = time.monotonic()
        prev = self._last_emit.get(g)
        if prev is None:
            t0 = self._submit_t.get(g)
            if t0 is not None:
                self._d_ttft.observe(1000.0 * (now - t0))
        else:
            self._d_itl.observe(1000.0 * (now - prev))
        self._last_emit[g] = now
        rec = self._tokens.get(g)
        if rec is not None:
            rec.append(int(tok))
        self._tick_buf.append((g, int(tok)))
        if self._stream is not None:
            self._stream(g, int(tok))

    def _route_submit(self, g, prompt, max_new_tokens, samp=None):
        """Score candidates, submit to the winner, and map its local
        rid to the global one — shared by ``submit()`` and the
        failure-drain requeue (which must preserve ``g`` AND the
        request's per-slot sampling overrides)."""
        if samp is None:
            samp = self._req_samp.get(g, {})
        tier = self._prefill_idx if self._disagg else self._decode_idx
        cands = {i: self._engines[i] for i in tier
                 if i not in self._failed}
        if not cands and self._disagg:
            # the whole prefill tier failed: decode replicas are full
            # engines (they prefill their own admissions), so a
            # healthy decode tier keeps serving end-to-end — the
            # cluster only dies when NO replica survives
            cands = {i: self._engines[i] for i in self._decode_idx
                     if i not in self._failed}
        if not cands:
            raise RuntimeError(
                "no live replicas to route to "
                f"({len(self._failed)} of {len(self._engines)} "
                "failed)")
        if len(cands) == 1:
            # identity route (kill switch / N=1 / last survivor):
            # skip the per-block prompt hashing — there is nothing to
            # choose between, so affinity is meaningless here
            idx, overlap, depths = next(iter(cands)), 0, {}
        else:
            idx, overlap, depths = self._router.route(
                prompt, cands, priority=int(samp.get("priority", 0)),
                adapter_id=samp.get("adapter_id"))
        # submit FIRST: a validation rejection must not skew the
        # router counters (the hit rate is an acceptance metric)
        lrid = self._engines[idx].submit(prompt, max_new_tokens,
                                         **samp)
        for i, d in depths.items():
            self._m_depth.labels(replica=str(i)).set(d)
        self._n_routed += 1
        if overlap > 0:
            self._n_affinity += 1
            self._m_affinity.inc()
        self._l2g[(idx, lrid)] = g
        self._owner[g] = (idx, lrid)
        self._hist_put((idx, lrid), g)
        if self._trace is not None:
            # router-decision span: which replica won, on how much
            # published-prefix overlap, against which queue depths
            self._trace.instant(
                "route", tid=0,
                args={"rid": g, "replica": idx,
                      "overlap": int(overlap),
                      "depths": {str(i): float(d)
                                 for i, d in depths.items()}})

    def _place_handoffs(self):
        """Import pending prefilled requests into decode replicas,
        least-loaded first; a handoff that finds no capacity stays
        pending for the next tick (its blocks are already freed on the
        prefill engine — the payload carries the bytes)."""
        still = []
        for src, rec in self._pending:
            live = [i for i in self._decode_idx
                    if i not in self._failed]
            if not live:
                # the whole decode tier failed: a prefill engine
                # cannot decode, so nothing can continue this request
                # — terminate it with the tokens already streamed
                # (the first token) instead of stranding run() or
                # raising past a healthy prefill tier; submit()
                # rejects new disaggregated requests in this state
                warnings.warn(
                    "all decode replicas failed; terminating "
                    f"prefilled request {rec.request_id} with the "
                    "tokens already streamed")
                g = self._l2g.pop((src, rec.request_id), None)
                if g is not None:
                    self._finish(g)
                continue
            g = self._l2g.get((src, rec.request_id))
            if g is None:       # cancelled/failed upstream: drop
                continue
            placed = False
            for i in sorted(live, key=lambda j:
                            self._engines[j].num_active
                            + self._engines[j].num_queued):
                drid = self._engines[i].admit_prefilled(rec)
                if drid is not None:
                    self._l2g.pop((src, rec.request_id), None)
                    self._l2g[(i, drid)] = g
                    self._owner[g] = (i, drid)
                    self._hist_put((i, drid), g)
                    if self._trace is not None:
                        self._trace.instant(
                            "handoff placed", tid=0,
                            args={"rid": g, "src": src, "dst": i,
                                  "blocks": rec.n_blocks})
                    placed = True
                    break
            if not placed:
                still.append((src, rec))
        self._pending = still

    def _safe_step(self, idx):
        try:
            self._engines[idx].step()
        except Exception as exc:        # noqa: BLE001 — fault domain
            warnings.warn(
                f"cluster replica {idx} failed mid-step ({exc!r}); "
                "draining its queue back to the router")
            self.fail_replica(idx)
            if not self._live():
                raise

    def _safe_phase(self, idx, dispatch: bool):
        """One phase of an overlapped decode tick (same fault domain
        as ``_safe_step``): dispatch launches the replica's next tick,
        commit takes in the tick that was in flight before it (a
        blocking replica: the tick it just launched)."""
        try:
            eng = self._engines[idx]
            if dispatch:
                eng.tick_dispatch()
            else:
                eng.tick_commit()
        except Exception as exc:        # noqa: BLE001 — fault domain
            warnings.warn(
                f"cluster replica {idx} failed mid-step ({exc!r}); "
                "draining its queue back to the router")
            self.fail_replica(idx)
            if not self._live():
                raise

    def _collect_done(self):
        """Completion signal: a request is done when the replica that
        owns its tail retires it (``_done`` populated under
        ``retain_results``). Token content comes from the CLUSTER's
        own stream records, so a disaggregated request's first token
        (prefill engine) and continuation (decode replica) splice into
        one result."""
        for idx, eng in enumerate(self._engines):
            if not eng._done:
                continue
            for lrid in list(eng._done):
                eng._done.pop(lrid)
                g = self._l2g.pop((idx, lrid), None)
                if g is not None:
                    self._finish(g)

    def _finish(self, g):
        now = time.monotonic()
        t0 = self._submit_t.pop(g, None)
        if t0 is not None:
            self._d_e2e.observe(1000.0 * (now - t0))
        self._last_emit.pop(g, None)
        self._owner.pop(g, None)
        self._req_samp.pop(g, None)
        self._done[g] = np.asarray(self._tokens.pop(g, []), np.int64)
        self._n_completed += 1

"""Speculative decoding over the paged KV cache.

Breaks decode's 1:1 target-forward-per-token ratio: a cheap drafter
proposes ``gamma`` tokens and the target model verifies the whole
window in ONE batched multi-query forward (the ragged-paged-attention
verify kernel, ``ops/pallas/paged_attention.py``), emitting 1 to
``gamma + 1`` tokens per step. Two drafters:

- **n-gram / prompt-lookup** (``ngram_propose``, the zero-extra-weights
  default): propose the continuation of the most recent earlier
  occurrence of the current suffix n-gram — free on repetitive text
  (code, retrieval, summarization quotes).
- **draft model** (``build_draft_loop``): any smaller paged-KV-capable
  causal LM free-runs ``gamma`` single-token steps inside one compiled
  ``lax.scan``; its cache shares the target's block tables, so
  rollback is the same O(1) length decrement.

Acceptance (``build_verify_step``):

- greedy: accept while the draft token equals the target argmax —
  emitted tokens are BY CONSTRUCTION the target's own greedy chain, so
  speculative greedy is token-exact vs plain ``generate()``.
- sampling: standard speculative rejection sampling (Leviathan et al.;
  Chen et al.) — accept draft ``d_i`` w.p. ``min(1, p(d_i)/q(d_i))``,
  on rejection resample from ``normalize(max(p - q, 0))``. Both ``p``
  and ``q`` run through the SAME ``_filter_logits``
  temperature/top-k/top-p pipeline as non-speculative sampling, which
  is exactly the condition under which the scheme provably preserves
  the (modified) target distribution. The n-gram drafter is the
  degenerate one-hot ``q``.

Everything here is fixed-shape: the verify window is always
``gamma + 1`` tokens, rejected tokens are rolled back by decrementing
length bookkeeping (``ops/paged_cache.write_tokens`` docstring), so
one compiled verify executable serves every accept/reject mix — the
zero-steady-state-recompile bar of the serving engine extends to
speculative mode unchanged. Kill switch: ``PADDLE_TPU_SPECULATIVE=0``.
"""
from __future__ import annotations

import os
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.pallas.paged_attention import tree_ancestor_bits

__all__ = ["speculative_enabled", "ngram_propose", "spec_exclusion_reason",
           "draft_exclusion_reason", "build_verify_step",
           "accept_from_filtered", "build_draft_loop", "SpecGenerator",
           "spec_tree_enabled", "tree_ancestor_bits",
           "tree_chain_layout", "tree_fill_from_chains",
           "ngram_propose_topk", "accept_tree_from_filtered",
           "build_tree_verify_step"]


def speculative_enabled() -> bool:
    """Kill switch: ``PADDLE_TPU_SPECULATIVE=0`` disables speculative
    decoding everywhere (generate() and the serving engine fall back to
    plain single-token decode)."""
    return os.environ.get("PADDLE_TPU_SPECULATIVE", "1") != "0"


def spec_tree_enabled() -> bool:
    """Kill switch: ``PADDLE_TPU_SPEC_TREE=0`` disables TREE-structured
    speculation specifically — ``spec_tree=...`` configs resolve back
    to the linear draft chain (and the ``"heads"`` drafter to
    ``"ngram"``) at construction time, restoring the pre-tree engine
    trace bit-for-bit. The broader ``PADDLE_TPU_SPECULATIVE=0`` switch
    still turns speculation off entirely."""
    return os.environ.get("PADDLE_TPU_SPEC_TREE", "1") != "0"


def spec_exclusion_reason(model) -> Optional[str]:
    """Why speculative decoding cannot run for ``model`` (None = it
    can). Capacity-routed MoE is excluded for the prompt-bucketing
    reason of PR 3: the gamma+1 window tokens would compete with each
    other for expert capacity, so the verify logits would differ from
    sequential decode and acceptance would be unsound."""
    if not hasattr(model, "init_paged_caches"):
        return (f"{type(model).__name__} does not implement "
                "init_paged_caches (paged-KV decode)")
    cfg = getattr(model, "config", None)
    n_experts = getattr(cfg, "num_experts", 0) \
        or getattr(cfg, "n_routed_experts", 0)   # DeepSeek naming
    if n_experts and not getattr(cfg, "dropless", False):
        return ("capacity-routed MoE: window tokens would compete for "
                "expert capacity, changing logits vs sequential decode")
    return None


def draft_exclusion_reason(target, draft) -> Optional[str]:
    """Why ``draft`` cannot draft for ``target`` (None = it can) —
    the shared gate of ``generate(draft_model=...)`` and
    ``ServingEngine(draft_model=...)``."""
    reason = spec_exclusion_reason(draft)
    if reason is not None:
        return reason
    dv = getattr(getattr(draft, "config", None), "vocab_size", None)
    tv = getattr(getattr(target, "config", None), "vocab_size", None)
    if dv is not None and tv is not None and dv != tv:
        return f"draft vocab ({dv}) != target vocab ({tv})"
    return None


# ---------------------------------------------------------------------------
# drafters
# ---------------------------------------------------------------------------

def ngram_propose(history, gamma: int, max_ngram: int = 3) -> List[int]:
    """Model-free prompt-lookup drafter: find the most recent earlier
    occurrence of the longest suffix n-gram (n <= ``max_ngram``) of
    ``history`` (prompt + everything emitted) and propose the ``gamma``
    tokens that followed it; pad short continuations by repeating the
    last proposal, and fall back to repeating the last history token
    when nothing matches. Deterministic, host-side, O(len * max_ngram)."""
    n = len(history)
    g = int(gamma)
    for k in range(min(int(max_ngram), n - 1), 0, -1):
        suf = history[n - k:]
        for start in range(n - k - 1, -1, -1):
            if history[start:start + k] == suf:
                out = list(history[start + k: start + k + g])
                while len(out) < g:
                    out.append(out[-1])
                return out
    return [history[-1]] * g


def ngram_propose_topk(history, gamma: int, n_chains: int,
                       max_ngram: int = 3) -> List[List[int]]:
    """Multi-candidate prompt-lookup drafter: the top-``n_chains``
    DISTINCT continuations of the current suffix, scanning matches in
    the SAME order as :func:`ngram_propose` (longest suffix first,
    most recent occurrence first) — so ``chains[0]`` is exactly
    ``ngram_propose``'s proposal, and a chain-topology tree drafts the
    identical window the linear path would. Later matches (older
    occurrences, then shorter suffixes) supply the sibling candidates
    a branching tree spends its extra nodes on — zero extra weights.
    Chains are deduplicated by their FIRST token: sibling branches
    diverge at their branch point, so two continuations sharing a head
    would collide on the same depth-1 node and the extra chain would
    cover nothing. When fewer than ``n_chains`` head-distinct
    continuations exist, the remainder pads with the repeat-last-token
    fallback chain."""
    n = len(history)
    g = int(gamma)
    chains: List[List[int]] = []
    seen = set()
    for k in range(min(int(max_ngram), n - 1), 0, -1):
        suf = history[n - k:]
        for start in range(n - k - 1, -1, -1):
            if history[start:start + k] == suf:
                out = list(history[start + k: start + k + g])
                while len(out) < g:
                    out.append(out[-1])
                if out[0] in seen:
                    continue
                seen.add(out[0])
                chains.append(out)
                if len(chains) == int(n_chains):
                    return chains
    fb = [history[-1]] * g
    if not chains:
        chains.append(fb)
    while len(chains) < int(n_chains):
        chains.append(list(fb))
    return chains


def tree_chain_layout(parents):
    """Static layout of a speculative token tree given its parent
    tuple (node ``k + 1``'s parent is ``parents[k]``; node 0 is the
    committed root). Returns ``(depth, leaf_of, n_leaves,
    max_depth)``:

    - ``depth[i]``: node ``i``'s depth (root = 0),
    - ``leaf_of[i]``: the chain index (= order among leaves) of node
      ``i``'s first-child-descendant leaf — the chain whose tokens
      fill node ``i`` when drafting from per-chain candidate lists,
    - ``n_leaves``: how many root-to-leaf chains the tree realizes
      (the drafter's candidate count),
    - ``max_depth``: the chains' required length.

    A chain topology (``tuple(range(gamma))``) has one leaf, so every
    node maps to chain 0 — the drafter degenerates to exactly
    :func:`ngram_propose`. NOTE: topologies whose branches share a
    prefix node assume the sibling chains agree on the shared prefix
    tokens (the verify is exact regardless; a disagreeing chain just
    wastes its shared-prefix nodes)."""
    tree_ancestor_bits(parents)          # validates shape/ordering
    parents = tuple(int(p) for p in parents)
    t = len(parents) + 1
    depth = [0] * t
    children: List[List[int]] = [[] for _ in range(t)]
    for k, p in enumerate(parents):
        depth[k + 1] = depth[p] + 1
        children[p].append(k + 1)
    # Chain indices follow depth-first (first-child) traversal so the
    # root's primary spine is always chain 0 — the drafter's best
    # candidate rides the deepest path no matter how nodes are
    # numbered, and a chain topology degenerates to ngram_propose.
    chain_of: dict = {}
    stack = [0]
    while stack:
        i = stack.pop()
        if not children[i] and i > 0:
            chain_of[i] = len(chain_of)
        stack.extend(reversed(children[i]))
    n_leaves = len(chain_of)

    def first_leaf(i):
        while children[i]:
            i = children[i][0]
        return i

    leaf_of = tuple(chain_of[first_leaf(i)] for i in range(t))
    return tuple(depth), leaf_of, n_leaves, max(depth)


def tree_fill_from_chains(parents, chains) -> List[int]:
    """Map per-chain candidate lists onto the tree's draft nodes:
    node ``k + 1`` (depth ``d``, chain ``c`` per
    :func:`tree_chain_layout`) takes ``chains[c][d - 1]``. Returns the
    ``gamma`` draft tokens in node order — the ``toks[:, 1:]`` row a
    tree verify window consumes."""
    depth, leaf_of, n_leaves, max_depth = tree_chain_layout(parents)
    if len(chains) < n_leaves:
        raise ValueError(
            f"tree has {n_leaves} chains but only {len(chains)} "
            "candidate lists were drafted")
    return [int(chains[leaf_of[k + 1]][depth[k + 1] - 1])
            for k in range(len(parents))]


def build_draft_loop(draft_step, *, gamma, do_sample, temperature=1.0,
                     top_k=0, top_p=1.0, want_probs,
                     gather_logits=None, slot_params=False):
    """Compiled draft proposal loop: ``gamma + 1`` single-token decode
    steps of the draft model inside one ``lax.scan`` (the extra step
    emits nothing — it writes the last draft token's K/V so a fully
    accepted window leaves the draft cache gap-free and the next
    proposal starts exactly at the target's new length).

    Returns ``loop(dparams, dpools, tables, lens, cur[, samp], key) ->
    (proposals [S, gamma], q_probs [S, gamma, V] | None, dpools)``.
    ``q_probs`` are the draft distributions AFTER the shared
    temperature/top-k/top-p pipeline (``want_probs`` — sampling mode
    needs them for rejection sampling; greedy verifies by token id
    only). ``gather_logits`` (tensor-parallel serving): applied to the
    per-step logits BEFORE filtering/sampling, so selection always
    sees the full replicated vocab row. ``slot_params`` (the serving
    engine's per-slot sampling tensors): the loop takes a ``samp``
    [S, 3] operand — (temperature, top_k, top_p) per slot, DATA
    instead of trace constants — and the baked keyword knobs are
    ignored; rejection sampling stays sound because the verify step
    filters the target logits with the SAME per-slot values."""
    from . import _filter_logits

    def loop(dparams, dpools, tables, lens, cur, *rest):
        if slot_params:
            samp, key = rest
            t_, k_, p_ = samp[:, 0], samp[:, 1], samp[:, 2]
        else:
            (key,) = rest
            t_, k_, p_ = temperature, top_k, top_p

        def body(carry, _):
            tok, pools, l, k = carry
            logits, pools = draft_step(dparams, tok[:, None], pools,
                                       None, block_tables=tables,
                                       cache_lens=l)
            row = logits[:, -1, :]
            if gather_logits is not None:
                row = gather_logits(row)
            f = _filter_logits(row, do_sample=do_sample,
                               temperature=t_, top_k=k_, top_p=p_)
            k, sub = jax.random.split(k)
            if do_sample:
                nt = jax.random.categorical(sub, f).astype(jnp.int32)
            else:
                nt = jnp.argmax(f, axis=-1).astype(jnp.int32)
            q = jax.nn.softmax(f, axis=-1) if want_probs \
                else jnp.zeros((f.shape[0], 0), jnp.float32)
            return (nt, pools, l + 1, k), (nt, q)

        init = (cur.astype(jnp.int32), dpools,
                lens.astype(jnp.int32), key)
        (_, dpools, _, _), (props, qp) = jax.lax.scan(
            body, init, None, length=gamma + 1)
        props = jnp.swapaxes(props[:gamma], 0, 1)        # [S, gamma]
        qp = jnp.swapaxes(qp[:gamma], 0, 1) if want_probs else None
        return props, qp, dpools

    return loop


# ---------------------------------------------------------------------------
# verify step
# ---------------------------------------------------------------------------

def accept_from_filtered(f, toks, dq, key, *, gamma, do_sample):
    """Window acceptance on ALREADY-FILTERED target logits — the
    shared core of ``build_verify_step`` (``SpecGenerator``'s verify)
    and the serving engine's ragged mixed-batch step (which gathers
    its window logits out of one packed row buffer before calling
    this): given ``f`` [S, gamma+1, V] (the target's window logits
    after the temperature/top-k/top-p pipeline) and the window tokens
    ``toks`` [S, gamma+1] = ``[cur, d_1..d_gamma]``, returns
    ``(out [S, gamma+1], accept [S, gamma], picked_logp [S, gamma+1])``
    with exactly the semantics documented on ``build_verify_step``.
    ``dq`` is the draft's filtered distribution (None = one-hot
    drafter); ``key`` is consumed only when ``do_sample``."""
    if not do_sample:
        logp = jax.nn.log_softmax(f, axis=-1)
        out = jnp.argmax(f, axis=-1).astype(jnp.int32)
        accept = out[:, :-1] == toks[:, 1:]
        picked = jnp.take_along_axis(
            logp, out[..., None], axis=-1)[..., 0]
        return out, accept, picked

    p = jax.nn.softmax(f, axis=-1)                  # [S, G+1, V]
    s, _, v = p.shape
    d = toks[:, 1:].astype(jnp.int32)               # [S, G]
    pd = jnp.take_along_axis(
        p[:, :gamma], d[..., None], axis=-1)[..., 0]
    if dq is None:
        # one-hot draft: q(d_i) = 1, residual = p with d_i removed
        qd = jnp.ones_like(pd)
        hit = jax.lax.broadcasted_iota(
            jnp.int32, (s, gamma, v), 2) == d[..., None]
        res = jnp.where(hit, 0.0, p[:, :gamma])
    else:
        qd = jnp.take_along_axis(dq, d[..., None], axis=-1)[..., 0]
        res = jnp.maximum(p[:, :gamma] - dq, 0.0)
    ku, kr, kb = jax.random.split(key, 3)
    u = jax.random.uniform(ku, (s, gamma))
    accept = u * qd < pd            # u < p/q without dividing by 0
    rs = jnp.sum(res, axis=-1, keepdims=True)
    # degenerate residual (q == p exactly): resample from p
    res = jnp.where(rs > 0.0, res / jnp.maximum(rs, 1e-37),
                    p[:, :gamma])
    rtok = jax.random.categorical(
        kr, jnp.log(jnp.maximum(res, 1e-37))
        + jnp.where(res > 0.0, 0.0, -jnp.inf)).astype(jnp.int32)
    bonus = jax.random.categorical(kb, f[:, gamma]) \
        .astype(jnp.int32)
    out = jnp.concatenate(
        [jnp.where(accept, d, rtok), bonus[:, None]], axis=1)
    logp = jax.nn.log_softmax(f, axis=-1)
    picked = jnp.take_along_axis(
        logp, out[..., None], axis=-1)[..., 0]
    return out, accept, picked


def accept_tree_from_filtered(f, toks, parents, key, *, do_sample):
    """Tree-window acceptance on ALREADY-FILTERED target logits: the
    token-tree generalization of :func:`accept_from_filtered`'s linear
    rollback — longest-accepted-root-path selection. ``f`` [S, T, V]
    holds the target's filtered logits at every window node (node 0 =
    the committed root token), ``toks`` [S, T] the window tokens
    (``toks[:, 0]`` the root), ``parents`` the static topology.

    Walks the tree from the root one depth at a time. Greedy: advance
    to the child whose draft token equals the current node's target
    argmax (at most one, for deduped drafts; ties break to the lowest
    node id). Sampled: SEQUENTIAL SIBLING rejection sampling — visit
    the current node's children in node order, accepting child ``i``
    w.p. ``min(1, p(x_i) / (1 - sum of rejected siblings' p))`` (the
    divide-free test ``u_i * (1 - rej_mass) < p(x_i)``; each node owns
    one pre-drawn uniform, visited at most once), and when every child
    is rejected the bonus token samples from ``p`` with the rejected
    sibling tokens zeroed and renormalized — the multi-candidate
    residual rule that keeps the emitted distribution exactly the
    target's (Leviathan-style; a single-child chain reduces to the
    linear one-hot rule). A slot whose path reaches a leaf (or accepts
    the full depth) gets its bonus from the leaf's full distribution.

    Returns ``(out [S, T], accept [S, T-1], picked_logp [S, T],
    path [S, T], n_acc [S])``. ``out``/``accept`` keep the LINEAR
    layout contract (``accept`` is prefix-true with ``n_acc`` leading
    Trues; the host emits ``out[s, :n_acc + 1]``), so
    ``leading_accepts`` / ``commit_window`` and every engine commit
    path work unchanged. ``path[s, j]`` names the accepted window node
    at depth ``j`` (``path[s, 0] = 0``; ``path[s, j] >= j``), the
    permutation ``ops.paged_cache.permute_window`` compacts the K/V
    window with; ``n_acc`` the accepted draft count."""
    s, t, v = f.shape
    parents = tuple(int(p) for p in parents)
    if len(parents) != t - 1:
        raise ValueError(
            f"spec tree has {len(parents) + 1} nodes but the verify "
            f"window carries {t} rows")
    par = jnp.asarray((-1,) + parents, jnp.int32)           # [T]
    toks = toks.astype(jnp.int32)
    iota_t = jnp.arange(t, dtype=jnp.int32)
    logp = jax.nn.log_softmax(f, axis=-1)

    cur = jnp.zeros((s,), jnp.int32)                # node at depth d-1
    alive = jnp.ones((s,), bool)
    n_acc = jnp.zeros((s,), jnp.int32)
    path = jnp.zeros((s, t), jnp.int32)
    bonus = jnp.zeros((s,), jnp.int32)

    if not do_sample:
        gt = jnp.argmax(f, axis=-1).astype(jnp.int32)       # [S, T]
        for d in range(1, t):
            tgt = jnp.take_along_axis(gt, cur[:, None], axis=1)[:, 0]
            m = (par[None, :] == cur[:, None]) \
                & (toks == tgt[:, None]) & alive[:, None]   # [S, T]
            step = m.any(axis=1)
            nxt = jnp.argmax(m, axis=1).astype(jnp.int32)
            cur = jnp.where(step, nxt, cur)
            path = path.at[:, d].set(cur)
            n_acc = n_acc + step.astype(jnp.int32)
            alive = step
        bonus = jnp.take_along_axis(gt, cur[:, None], axis=1)[:, 0]
    else:
        p = jax.nn.softmax(f, axis=-1)                      # [S, T, V]
        keys = jax.random.split(key, t + 1)
        # one uniform per node: each node is visited at most once (it
        # has exactly one parent), so the draws stay independent
        u = jax.random.uniform(keys[0], (s, t))
        for d in range(1, t):
            p_cur = jnp.take_along_axis(
                p, cur[:, None, None], axis=1)[:, 0]        # [S, V]
            acc_d = jnp.zeros((s,), bool)
            chosen = cur
            rej_mass = jnp.zeros((s,), jnp.float32)
            rej_nodes = jnp.zeros((s, t), bool)
            for i in range(1, t):
                cand = alive & (par[i] == cur) & ~acc_d
                ti = toks[:, i]
                # a duplicate of an already-rejected sibling token has
                # zero residual mass left — force pi to 0 so it can
                # neither re-accept nor re-subtract
                dup = ((toks == ti[:, None]) & rej_nodes).any(axis=1)
                pi = jnp.where(
                    dup, 0.0,
                    jnp.take_along_axis(p_cur, ti[:, None],
                                        axis=1)[:, 0])
                acc_i = cand & (u[:, i] * (1.0 - rej_mass) < pi)
                chosen = jnp.where(acc_i, jnp.int32(i), chosen)
                acc_d = acc_d | acc_i
                newly_rej = cand & ~acc_i
                rej_mass = rej_mass + jnp.where(newly_rej, pi, 0.0)
                rej_nodes = rej_nodes.at[:, i].set(newly_rej)
            # slots stopping at this depth: bonus from the residual
            # (p with the rejected siblings zeroed, renormalized; the
            # degenerate all-mass-rejected residual falls back to p —
            # the linear rule's guard)
            hit = (rej_nodes[:, :, None]
                   & (toks[:, :, None] == jax.lax.broadcasted_iota(
                       jnp.int32, (s, t, v), 2))).any(axis=1)
            res = jnp.where(hit, 0.0, p_cur)
            rs = jnp.sum(res, axis=-1, keepdims=True)
            res = jnp.where(rs > 0.0, res / jnp.maximum(rs, 1e-37),
                            p_cur)
            btok = jax.random.categorical(
                keys[d], jnp.log(jnp.maximum(res, 1e-37))
                + jnp.where(res > 0.0, 0.0, -jnp.inf)).astype(jnp.int32)
            stopping = alive & ~acc_d
            bonus = jnp.where(stopping, btok, bonus)
            cur = jnp.where(acc_d, chosen, cur)
            path = path.at[:, d].set(cur)
            n_acc = n_acc + acc_d.astype(jnp.int32)
            alive = alive & acc_d
        # full-depth paths never stopped: bonus from the final node's
        # complete distribution (no sibling was rejected there)
        p_fin = jnp.take_along_axis(p, cur[:, None, None],
                                    axis=1)[:, 0]
        btok = jax.random.categorical(
            keys[t], jnp.log(jnp.maximum(p_fin, 1e-37))
            + jnp.where(p_fin > 0.0, 0.0, -jnp.inf)).astype(jnp.int32)
        bonus = jnp.where(alive, btok, bonus)

    # assemble the LINEAR-contract outputs: out[s, j] continues the
    # sequence after j accepted drafts — the depth-(j+1) path token
    # while j < n_acc, the bonus token at/after the stop
    child_tok = jnp.take_along_axis(toks, path, axis=1)     # [S, T]
    nxt_tok = jnp.concatenate(
        [child_tok[:, 1:], bonus[:, None]], axis=1)
    out = jnp.where(iota_t[None, :] < n_acc[:, None], nxt_tok,
                    bonus[:, None])
    accept = iota_t[None, :t - 1] < n_acc[:, None]
    # out[s, j] was selected from node path[s, j]'s distribution
    sel = jnp.take_along_axis(logp, path[:, :, None], axis=1)
    picked = jnp.take_along_axis(sel, out[:, :, None],
                                 axis=-1)[..., 0]
    return out, accept, picked, path, n_acc


def build_verify_step(model_step, *, gamma, do_sample, temperature=1.0,
                      top_k=0, top_p=1.0, onehot_draft=True):
    """Build the fixed-gamma multi-token verify step.

    The returned function runs ONE target forward over the window
    ``toks = [cur, d_1..d_gamma]`` (shapes [S, gamma+1], K/V written at
    ``lens + t`` through the paged path) and returns

    ``(out [S, gamma+1], accept [S, gamma], logp [S, gamma+1], pools)``

    where ``accept[s, i]`` says draft ``d_{i+1}`` was accepted and
    ``out[s, t]`` is the token the sequence continues with after ``t``
    accepted drafts — so the host emits exactly
    ``out[s, :n_accepted + 1]`` (the last one is the rejection
    correction, or the free bonus token when everything was accepted)
    and ``logp`` rides along for generate()'s score output.

    Greedy (``do_sample=False``): ``out`` is the target argmax chain —
    signature ``verify(params, pools, tables, lens, toks)`` (no
    randomness). Sampling: rejection sampling against the draft
    distribution — one-hot of ``toks[:, 1:]`` when ``onehot_draft``
    (n-gram drafter), else the explicit ``dq`` operand — signature
    ``verify(params, pools, tables, lens, toks[, dq], key)``."""
    from . import _filter_logits

    def _accept(params, pools, tables, lens, toks, dq, key):
        logits, pools = model_step(params, toks, pools, None,
                                   block_tables=tables,
                                   cache_lens=lens)
        f = _filter_logits(logits, do_sample=do_sample,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p)                 # [S, G+1, V]
        out, accept, picked = accept_from_filtered(
            f, toks, dq, key, gamma=gamma, do_sample=do_sample)
        return out, accept, picked, pools

    if not do_sample:
        def verify(params, pools, tables, lens, toks):
            return _accept(params, pools, tables, lens, toks, None,
                           None)
    elif onehot_draft:
        def verify(params, pools, tables, lens, toks, key):
            return _accept(params, pools, tables, lens, toks, None,
                           key)
    else:
        def verify(params, pools, tables, lens, toks, dq, key):
            return _accept(params, pools, tables, lens, toks, dq, key)
    return verify


def build_tree_verify_step(model_step, *, parents, do_sample,
                           temperature=1.0, top_k=0, top_p=1.0):
    """Tree-topology twin of :func:`build_verify_step`: ONE target
    forward over the window ``toks = [cur, node_1..node_gamma]``
    (tree node order), masked by ancestor path instead of the linear
    in-window bound — the ``spec_tree_scope`` entered around the model
    step arms the paged-attention dispatchers without touching any
    model signature. Acceptance is
    :func:`accept_tree_from_filtered`'s longest-accepted-root-path
    walk, and the accepted nodes' K/V — scattered across the window —
    are compacted onto the linear tail positions in-executable
    (``ops.paged_cache.permute_window``), so the cache the caller's
    ``lens += n_acc + 1`` commit exposes is exactly a sequential
    decode's.

    Drafters here are always one-hot (n-gram top-k chains or Medusa
    heads propose concrete tokens), so there is no ``dq`` operand.
    Signatures mirror ``build_verify_step``'s one-hot forms:
    ``verify(params, pools, tables, lens, toks[, key])`` ->
    ``(out [S, T], accept [S, T-1], logp [S, T], pools)`` — the
    linear-contract shapes, so ``commit_window`` and generate()'s
    score accounting work unchanged. A chain ``parents`` makes the
    greedy form token-exact with ``build_verify_step``'s."""
    from . import _filter_logits
    from ..ops.paged_cache import permute_window
    from ..ops.pallas.paged_attention import spec_tree_scope
    parents = tuple(int(p) for p in parents)
    tree_ancestor_bits(parents)          # validate before tracing

    def _verify(params, pools, tables, lens, toks, key):
        with spec_tree_scope(parents):
            logits, pools = model_step(params, toks, pools, None,
                                       block_tables=tables,
                                       cache_lens=lens)
        f = _filter_logits(logits, do_sample=do_sample,
                           temperature=temperature, top_k=top_k,
                           top_p=top_p)                 # [S, T, V]
        out, accept, picked, path, n_acc = accept_tree_from_filtered(
            f, toks, parents, key, do_sample=do_sample)
        lens32 = lens.astype(jnp.int32)
        pools = [permute_window(kp, vp, tables, lens32, path,
                                n_acc + 1) for kp, vp in pools]
        return out, accept, picked, pools

    if not do_sample:
        def verify(params, pools, tables, lens, toks):
            return _verify(params, pools, tables, lens, toks, None)
        return verify
    return _verify


def leading_accepts(accept_row) -> int:
    """Number of leading True in one slot's accept vector (the
    accepted draft count; the step then emits that many + 1 tokens)."""
    n = 0
    for a in accept_row:
        if not a:
            break
        n += 1
    return n


def commit_window(out_row, accept_row, room: int, eos: int):
    """Shared host-side window commit (``SpecGenerator.run`` AND the
    serving engine's ``_commit_verify_window`` — one implementation so
    the two entry points can never diverge on the same token stream):
    from one slot's verify outputs, the tokens to emit this step and
    the accepted-draft count.

    Emits ``out_row[:n_acc + 1]`` truncated to ``room`` remaining
    tokens and cut after an EOS found anywhere inside the window.
    Returns ``(kept, n_acc)``; ``kept`` is non-empty (``room >= 1`` for
    any live slot/row). The caller commits ``cache_len += n_acc + 1``
    only when the window was NOT truncated (truncation always
    retires/freezes the sequence, so its cache state is moot)."""
    n_acc = leading_accepts(accept_row)
    kept = []
    for tok in out_row[:n_acc + 1][:room]:
        kept.append(int(tok))
        if int(tok) == eos:
            break
    return kept, n_acc


# ---------------------------------------------------------------------------
# generate()-level driver
# ---------------------------------------------------------------------------

class SpecGenerator:
    """Compiled-step bundle + host acceptance loop behind
    ``generate(num_speculative_tokens=gamma)``.

    Same paged layout as ``_build_run_paged`` (generate() owns the
    whole pool, contiguous static block tables, prefill through the
    dense cached path scattered into the blocks) but the decode loop is
    host-driven: every iteration drafts gamma tokens (n-gram host-side,
    or the compiled draft-model scan), verifies the window in one
    fixed-shape compiled forward, and commits 1..gamma+1 tokens by
    advancing per-row lengths — rejection rollback IS the non-advance.
    All device steps are shape-stable, so each compiles exactly once
    and is cached on the model across generate() calls."""

    def __init__(self, model, binder, buffers, b, prompt_len, max_new,
                 gamma, *, do_sample, temperature, top_k, top_p, eos,
                 pad, block_size, draft_model=None, ngram_max=3,
                 kv_cache_dtype=None, spec_tree=None):
        from ..ops import paged_cache as _pc
        from . import _select_token
        # kwarg forwarded only when set — pre-quantization duck-typed
        # models keep working on the default path
        _kv_kw = {"kv_cache_dtype": kv_cache_dtype} \
            if kv_cache_dtype else {}

        self.b, self.max_new, self.gamma = b, int(max_new), int(gamma)
        self.eos, self.pad = int(eos), int(pad)
        self.do_sample = do_sample
        self.ngram_max = int(ngram_max)
        self.prompt_len = prompt_len
        self._draft_model = draft_model
        # tree topology (None = linear chain). The kill switch resolves
        # HERE, so a disabled tree builds the linear executables
        # bit-for-bit (the config value never reaches a trace).
        if spec_tree is not None and not spec_tree_enabled():
            spec_tree = None
        if spec_tree is not None:
            spec_tree = tuple(int(p) for p in spec_tree)
            if len(spec_tree) != int(gamma):
                raise ValueError(
                    f"spec_tree has {len(spec_tree)} draft nodes but "
                    f"num_speculative_tokens={int(gamma)}")
            if draft_model is not None:
                raise ValueError(
                    "spec_tree drafts via n-gram top-k chains (or the "
                    "serving engine's draft heads); a separate "
                    "draft_model only produces linear chains — drop "
                    "one of the two")
            (self._tree_depth, self._tree_leaf_of, self._tree_chains,
             self._tree_max_depth) = tree_chain_layout(spec_tree)
        self.spec_tree = spec_tree

        # +gamma headroom: the last verify window may overhang the
        # final emitted token by up to gamma speculated positions
        mb = _pc.blocks_for(prompt_len + max_new + gamma, block_size)
        self._tables_np = (1 + np.arange(b * mb, dtype=np.int32)) \
            .reshape(b, mb)
        num_blocks = 1 + b * mb

        model_step = model._build_model_step(binder, buffers)
        select = lambda lg, k: _select_token(
            lg, k, do_sample=do_sample, temperature=temperature,
            top_k=top_k, top_p=top_p)

        def prefill(params, ids, key):
            tables = jnp.asarray(self._tables_np)
            pools = model.init_paged_caches(num_blocks, block_size,
                                            **_kv_kw)
            dense = model.init_caches(b, prompt_len)
            logits, dense = model_step(params, ids, dense,
                                       jnp.zeros((), jnp.int32))
            pools = [_pc.write_prefill(kp, vp, tables, dk, dv)
                     for (kp, vp), (dk, dv) in zip(pools, dense)]
            key, sub = jax.random.split(key)
            tok, logp = select(logits[:, -1, :], sub)
            return tok, logp, pools

        self._prefill = jax.jit(prefill)
        if self.spec_tree is not None:
            self._verify = jax.jit(
                build_tree_verify_step(
                    model_step, parents=self.spec_tree,
                    do_sample=do_sample, temperature=temperature,
                    top_k=top_k, top_p=top_p),
                donate_argnums=(1,))
        else:
            self._verify = jax.jit(
                build_verify_step(
                    model_step, gamma=gamma, do_sample=do_sample,
                    temperature=temperature, top_k=top_k, top_p=top_p,
                    onehot_draft=draft_model is None),
                donate_argnums=(1,))

        if draft_model is not None:
            from ..jit import _LayerBinder
            self._dbinder = _LayerBinder(draft_model)
            draft_step = draft_model._build_model_step(
                self._dbinder, self._dbinder.buffer_arrays())

            def dprefill(dparams, ids):
                tables = jnp.asarray(self._tables_np)
                pools = draft_model.init_paged_caches(num_blocks,
                                                      block_size,
                                                      **_kv_kw)
                dense = draft_model.init_caches(b, prompt_len)
                _, dense = draft_step(dparams, ids, dense,
                                      jnp.zeros((), jnp.int32))
                return [_pc.write_prefill(kp, vp, tables, dk, dv)
                        for (kp, vp), (dk, dv) in zip(pools, dense)]

            self._dprefill = jax.jit(dprefill)
            self._dloop = jax.jit(
                build_draft_loop(draft_step, gamma=gamma,
                                 do_sample=do_sample,
                                 temperature=temperature, top_k=top_k,
                                 top_p=top_p, want_probs=do_sample),
                donate_argnums=(1,))

    def run(self, params, ids, seed):
        """(out [B, max_new] int64 pad-filled-after-EOS, scores [B])."""
        b, g, eos = self.b, self.gamma, self.eos
        key = jax.random.PRNGKey(seed)
        key, sub = jax.random.split(key)
        tok0, logp0, pools = self._prefill(params, ids, sub)
        tok0 = np.asarray(tok0)
        ids_np = np.asarray(ids)
        if self._draft_model is not None:
            dparams = self._dbinder.param_arrays()
            dpools = self._dprefill(dparams, ids)
        tables = jnp.asarray(self._tables_np)

        emitted = [[int(t)] for t in tok0]
        scores = [float(v) for v in np.asarray(logp0)]
        hist = [list(map(int, ids_np[r])) + [int(tok0[r])]
                for r in range(b)]
        lens = np.full((b,), self.prompt_len, np.int32)
        cur = tok0.astype(np.int32)
        done = [int(t) == eos or self.max_new <= 1 for t in tok0]

        while not all(done):
            toks = np.empty((b, g + 1), np.int32)
            toks[:, 0] = cur
            dq = None
            if self.spec_tree is not None:
                for r in range(b):
                    if done[r]:
                        toks[r, 1:] = self.pad
                        continue
                    chains = ngram_propose_topk(
                        hist[r], self._tree_max_depth,
                        self._tree_chains, self.ngram_max)
                    toks[r, 1:] = tree_fill_from_chains(
                        self.spec_tree, chains)
            elif self._draft_model is None:
                for r in range(b):
                    toks[r, 1:] = ngram_propose(hist[r], g,
                                                self.ngram_max) \
                        if not done[r] else self.pad
            else:
                key, sub = jax.random.split(key)
                props, dq, dpools = self._dloop(
                    dparams, dpools, tables, jnp.asarray(lens),
                    jnp.asarray(cur), sub)
                toks[:, 1:] = np.asarray(props)
            if self.do_sample:
                key, sub = jax.random.split(key)
                args = (params, pools, tables, jnp.asarray(lens),
                        jnp.asarray(toks))
                args += (dq, sub) if dq is not None else (sub,)
                out, accept, logp, pools = self._verify(*args)
            else:
                out, accept, logp, pools = self._verify(
                    params, pools, tables, jnp.asarray(lens),
                    jnp.asarray(toks))
            out = np.asarray(out)
            accept = np.asarray(accept)
            logp = np.asarray(logp)
            for r in range(b):
                if done[r]:
                    continue
                kept, n_acc = commit_window(
                    out[r], accept[r], self.max_new - len(emitted[r]),
                    eos)
                emitted[r].extend(kept)
                hist[r].extend(kept)
                scores[r] += float(logp[r, :len(kept)].sum())
                if kept[-1] == eos or len(emitted[r]) >= self.max_new:
                    done[r] = True      # rows stay batched but frozen
                else:
                    # commit cur + the accepted drafts; the rejected
                    # tail is rolled back by simply NOT advancing over
                    # it (paged_cache.write_tokens: no data movement)
                    lens[r] += n_acc + 1
                    cur[r] = kept[-1]

        out_np = np.full((b, self.max_new), self.pad, np.int64)
        for r in range(b):
            out_np[r, :len(emitted[r])] = emitted[r]
        return out_np, np.asarray(scores, np.float32)

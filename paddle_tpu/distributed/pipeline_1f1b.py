"""1F1B pipeline schedule with O(pp) activation memory (reference:
``python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py``
1F1B mode — warmup forwards, steady one-forward-one-backward, cooldown
backwards).

TPU-first formulation: the schedule is precomputed in python as static
[pp, T] op/micro tables (SPMD programs cannot branch per rank, but they
can index constant tables by ``axis_index``), and the whole timetable
runs as ONE ``lax.scan`` inside a ``shard_map``. Each slot a device
executes F, B, or idle via ``lax.switch``:

- **F**: consume the ring-received boundary activation (stage 0: run
  ``first_fn`` on the raw feed), save it in a size-``pp`` ring (THE 1F1B
  memory property — at most ``pp`` in-flight microbatches per device),
  run the stage, ``ppermute`` the result forward.
- **B**: recompute the stage from the saved input (activation remat),
  pull the upstream gradient back through ``jax.vjp``, accumulate local
  parameter grads, ``ppermute`` the input-gradient backward. The last
  stage seeds the chain from the per-micro loss; stage 0 additionally
  backprops through ``first_fn``.

Forward and backward interleave in one scan, so peak live boundary
activations are ``pp`` per device — not ``n_micro`` as in fill-drain
GPipe — which is exactly what 1F1B buys the reference on GPUs.
"""
from __future__ import annotations

from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from . import env as _env
from .pipeline import _live_batch_axes

__all__ = ["make_1f1b_schedule", "pipeline_1f1b_grads",
           "make_interleaved_schedule", "pipeline_interleaved_grads"]

_IDLE, _F, _B = 0, 1, 2


def make_1f1b_schedule(pp: int, n_micro: int):
    """Greedy slot assignment of the per-stage 1F1B op sequences under
    the ring's data dependencies. Returns (op[pp, T], mi[pp, T]) numpy
    tables: op in {0 idle, 1 F, 2 B}, mi the micro index."""
    seqs = []
    for s in range(pp):
        warm = min(pp - 1 - s, n_micro)
        seq = [("F", m) for m in range(warm)]
        b = 0
        for f in range(warm, n_micro):
            seq.append(("F", f))
            seq.append(("B", b))
            b += 1
        while b < n_micro:
            seq.append(("B", b))
            b += 1
        seqs.append(seq)

    slot_f, slot_b = {}, {}
    ptr = [0] * pp
    op_rows, mi_rows = [], []
    t = 0
    limit = 8 * (n_micro + pp) + 16
    while any(ptr[s] < len(seqs[s]) for s in range(pp)):
        col_op = [_IDLE] * pp
        col_mi = [0] * pp
        commit = []
        for s in range(pp):
            if ptr[s] >= len(seqs[s]):
                continue
            op, m = seqs[s][ptr[s]]
            if op == "F":
                ok = s == 0 or slot_f.get((s - 1, m), limit) < t
            else:
                ok = slot_f.get((s, m), limit) < t if s == pp - 1 \
                    else slot_b.get((s + 1, m), limit) < t
            if ok:
                col_op[s] = _F if op == "F" else _B
                col_mi[s] = m
                commit.append((s, op, m))
        for s, op, m in commit:
            (slot_f if op == "F" else slot_b)[(s, m)] = t
            ptr[s] += 1
        op_rows.append(col_op)
        mi_rows.append(col_mi)
        t += 1
        if t > limit:
            raise RuntimeError("1F1B schedule did not converge "
                               f"(pp={pp}, n_micro={n_micro})")
    return (np.array(op_rows, np.int32).T,
            np.array(mi_rows, np.int32).T)


def _pipe_env(mesh, axis, batch_axes, feeds, last_feeds, first_fn,
              first_params):
    """Shared prologue for both 1F1B engines: batch-axis partitioning,
    per-device feed/boundary shapes, and in/out spec helpers."""
    batch_spec = _live_batch_axes(mesh, axis, batch_axes, feeds.shape[1])
    _axes = (batch_spec,) if isinstance(batch_spec, str) \
        else (batch_spec or ())
    n_dp = int(np.prod([mesh.shape[a] for a in _axes])) if _axes else 1
    local_mb = feeds.shape[1] // n_dp
    feed_spec = P(None, batch_spec, *([None] * (feeds.ndim - 2)))
    lf_spec = None if last_feeds is None else P(
        None, batch_spec if last_feeds.shape[1] == feeds.shape[1]
        else None, *([None] * (last_feeds.ndim - 2)))
    local_feed = jax.ShapeDtypeStruct((local_mb,) + feeds.shape[2:],
                                      feeds.dtype)
    if first_fn is not None:
        h_struct = jax.eval_shape(first_fn, first_params, local_feed)
    else:
        h_struct = local_feed
    rep = lambda tree: jax.tree_util.tree_map(
        lambda x: P(*([None] * jnp.ndim(x))), tree)
    zeros_like_tree = lambda tree: jax.tree_util.tree_map(
        lambda x: jnp.zeros(jnp.shape(x), jnp.result_type(x)), tree)
    return {"axes": _axes, "n_dp": n_dp, "feed_spec": feed_spec,
            "lf_spec": lf_spec, "h_shape": h_struct.shape,
            "h_dtype": h_struct.dtype, "rep": rep,
            "zeros_like_tree": zeros_like_tree}


def _pipe_outputs(axis, axes, nm, n_dp, loss_acc, gm_acc, gf_acc,
                  gl_acc):
    """Shared epilogue: broadcast the loss, mean-scale and psum grads
    (pp owns its shard of the mid grads; first/last grads live on their
    owner stages)."""
    dp_plus_pp = (axis,) + tuple(axes)
    loss = jax.lax.psum(loss_acc, dp_plus_pp) / (nm * n_dp)
    scale = 1.0 / (nm * n_dp)
    ps = lambda tree: jax.tree_util.tree_map(
        lambda g: jax.lax.psum(g, dp_plus_pp) * scale, tree)
    gm_out = jax.tree_util.tree_map(
        lambda g: (jax.lax.psum(g, tuple(axes)) * scale
                   if axes else g * scale)[None], gm_acc)
    return loss, gm_out, ps(gf_acc), ps(gl_acc)


def pipeline_1f1b_grads(stage_fn: Callable, stacked_params, feeds,
                        last_fn: Callable, *, first_fn=None,
                        first_params=None, last_params=None,
                        last_feeds=None, mesh: Optional[Mesh] = None,
                        axis: str = "pp",
                        batch_axes=("dp", "sharding"),
                        loss_scale=None):
    """Run one full 1F1B train pass; returns
    ``(mean_loss, (g_stacked, g_first, g_last))``.

    stage_fn(params_local, h) -> h           (homogeneous stage body)
    first_fn(first_params, feed_mb) -> h     (stage-0 embed; optional)
    last_fn(last_params, h, last_feed_mb) -> scalar per-micro loss
    feeds: [n_micro, mb, ...] raw stage-0 inputs.
    last_feeds: [n_micro, ...] per-micro labels for last_fn.
    loss_scale: optional traced scalar — seeds the backward chain at the
    last stage (fp16 GradScaler semantics: every grad comes out
    multiplied by it; the reported loss stays unscaled).
    """
    mesh = mesh or _env.get_mesh()
    pp = mesh.shape[axis]
    nm = feeds.shape[0]
    from ..profiler import RecordEvent
    with RecordEvent("pipeline:1f1b_schedule"):
        op_tab, mi_tab = make_1f1b_schedule(pp, nm)
    T = op_tab.shape[1]
    # schedule-shape telemetry: slots per device and bubble fraction
    # (idle slots / total) — the quantity 1F1B exists to minimize
    from .. import monitor as _monitor
    _monitor.gauge("pipeline_schedule_slots",
                   "1F1B timetable length T per device",
                   labels=("pp", "n_micro")).labels(
        pp=str(pp), n_micro=str(nm)).set(int(T))
    _monitor.gauge("pipeline_bubble_fraction",
                   "idle-slot fraction of the 1F1B timetable",
                   labels=("pp", "n_micro")).labels(
        pp=str(pp), n_micro=str(nm)).set(
        round(float((op_tab == _IDLE).mean()), 4))
    env = _pipe_env(mesh, axis, batch_axes, feeds, last_feeds,
                    first_fn, first_params)
    _axes, n_dp = env["axes"], env["n_dp"]
    feed_spec, lf_spec = env["feed_spec"], env["lf_spec"]
    h_shape, h_dtype = env["h_shape"], env["h_dtype"]
    rep, zeros_like_tree = env["rep"], env["zeros_like_tree"]
    in_spec_params = jax.tree_util.tree_map(
        lambda _: P(axis), stacked_params)

    op_arr = jnp.asarray(op_tab)
    mi_arr = jnp.asarray(mi_tab)

    def per_device(params_block, mbs, fparams, lparams, lfeeds, scale_a):
        params_local = jax.tree_util.tree_map(lambda x: x[0],
                                              params_block)
        stage = jax.lax.axis_index(axis)
        perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]
        perm_bwd = [(i, (i - 1) % pp) for i in range(pp)]
        is_first = stage == 0
        is_last = stage == pp - 1
        seed_g = scale_a.astype(jnp.float32)

        zr = lambda: jnp.zeros((pp,) + h_shape, h_dtype)
        g_mid0 = zeros_like_tree(params_local)
        g_first0 = zeros_like_tree(fparams)
        g_last0 = zeros_like_tree(lparams)

        def lf_of(m):
            return None if lfeeds is None else lfeeds[m]

        # ---- slot bodies (uniform signature) --------------------------
        def body_idle(oprnd):
            in_ring, fbuf, gbuf, m = oprnd
            zeros_h = jnp.zeros(h_shape, h_dtype)
            return (in_ring, zeros_h, zeros_h, g_mid0, g_first0,
                    g_last0, jnp.zeros((), jnp.float32))

        def body_F(oprnd):
            in_ring, fbuf, gbuf, m = oprnd
            if first_fn is not None:
                x0 = jax.lax.cond(
                    is_first, lambda: first_fn(fparams, mbs[m]),
                    lambda: jnp.zeros(h_shape, h_dtype))
                x_in = jnp.where(is_first, x0, fbuf[m % pp])
            else:
                x_in = jnp.where(is_first, mbs[m].astype(h_dtype),
                                 fbuf[m % pp])
            in_ring = in_ring.at[m % pp].set(x_in)
            # the last stage's F only banks its input: loss + grads are
            # (re)computed at its B slot
            y = jax.lax.cond(is_last,
                             lambda: jnp.zeros(h_shape, h_dtype),
                             lambda: stage_fn(params_local, x_in))
            return (in_ring, y, jnp.zeros(h_shape, h_dtype), g_mid0,
                    g_first0, g_last0, jnp.zeros((), jnp.float32))

        def body_B(oprnd):
            in_ring, fbuf, gbuf, m = oprnd
            x_saved = in_ring[m % pp]
            g_in = gbuf[m % pp]

            def last_case():
                def loss_of(p_mid, p_last, x):
                    y = stage_fn(p_mid, x)
                    return last_fn(p_last, y, lf_of(m)).astype(
                        jnp.float32)
                loss, pull = jax.vjp(loss_of, params_local, lparams,
                                     x_saved)
                # GradScaler: seed the chain with the loss scale — the
                # grads (incl. the boundary gx riding the ring) come out
                # scaled; the reported loss stays unscaled
                gm, gl, gx = pull(seed_g)
                return gm, g_first0, gl, gx, loss

            def first_case():
                if first_fn is None:
                    return mid_case()

                def fwd(p_first, p_mid, feed):
                    return stage_fn(p_mid, first_fn(p_first, feed))
                _, pull = jax.vjp(fwd, fparams, params_local, mbs[m])
                gf, gm, _ = pull(g_in)
                return gm, gf, g_last0, jnp.zeros(h_shape, h_dtype), \
                    jnp.zeros((), jnp.float32)

            def mid_case():
                _, pull = jax.vjp(
                    lambda p, x: stage_fn(p, x), params_local, x_saved)
                gm, gx = pull(g_in)
                return gm, g_first0, g_last0, gx, \
                    jnp.zeros((), jnp.float32)

            gm, gf, gl, gx, loss = jax.lax.cond(
                is_last, last_case,
                lambda: jax.lax.cond(is_first, first_case, mid_case))
            return (in_ring, jnp.zeros(h_shape, h_dtype), gx, gm, gf,
                    gl, loss)

        def slot(carry, t):
            in_ring, fbuf, gbuf, gm_acc, gf_acc, gl_acc, loss_acc = carry
            op = op_arr[stage, t]
            m = mi_arr[stage, t]
            in_ring, send_f, send_g, gm, gf, gl, loss = jax.lax.switch(
                op, [body_idle, body_F, body_B],
                (in_ring, fbuf, gbuf, m))
            # ---- ring communication (every slot, masked by schedule)
            recv_f = jax.lax.ppermute(send_f, axis, perm_fwd)
            recv_g = jax.lax.ppermute(send_g, axis, perm_bwd)
            prev = (stage - 1) % pp
            nxt = (stage + 1) % pp
            take_f = (op_arr[prev, t] == _F) & (stage > 0)
            take_g = (op_arr[nxt, t] == _B) & (stage < pp - 1)
            fbuf = jnp.where(take_f,
                             fbuf.at[mi_arr[prev, t] % pp].set(recv_f),
                             fbuf)
            gbuf = jnp.where(take_g,
                             gbuf.at[mi_arr[nxt, t] % pp].set(recv_g),
                             gbuf)
            add = jax.tree_util.tree_map
            return (in_ring, fbuf, gbuf,
                    add(jnp.add, gm_acc, gm), add(jnp.add, gf_acc, gf),
                    add(jnp.add, gl_acc, gl),
                    loss_acc + loss), None

        carry0 = (zr(), zr(), zr(), g_mid0, g_first0, g_last0,
                  jnp.zeros((), jnp.float32))
        (in_ring, fbuf, gbuf, gm_acc, gf_acc, gl_acc,
         loss_acc), _ = jax.lax.scan(slot, carry0, jnp.arange(T))

        # loss: only the last stage accumulated; grads for first/last
        # params: only their owner stages. dp shards each saw 1/n_dp of
        # the batch; the loss is the mean over shards.
        return _pipe_outputs(axis, _axes, nm, n_dp, loss_acc,
                             gm_acc, gf_acc, gl_acc)

    from .shard_utils import manual_region
    mapped = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(in_spec_params, feed_spec, rep(first_params),
                  rep(last_params), lf_spec, P()),
        out_specs=(P(), jax.tree_util.tree_map(lambda _: P(axis),
                                               stacked_params),
                   rep(first_params), rep(last_params)),
        check_vma=False)
    scale_a = jnp.float32(1.0) if loss_scale is None \
        else jnp.asarray(loss_scale, jnp.float32)
    with manual_region(), RecordEvent("pipeline:1f1b"):
        loss, g_stacked, g_first, g_last = mapped(
            stacked_params, feeds, first_params, last_params, last_feeds,
            scale_a)
    return loss, (g_stacked, g_first, g_last)


# ---------------------------------------------------------------------------
# interleaved virtual stages (Megatron interleaved 1F1B — reference:
# ``pipeline_parallel.py`` with ``num_virtual_pipeline_stages``: each
# device hosts v model CHUNKS; model part index = chunk * pp + stage, so
# a microbatch crosses every device v times. Cuts the bubble fraction
# by ~v at the cost of v x boundary traffic.)
# ---------------------------------------------------------------------------

def make_interleaved_schedule(pp: int, n_micro: int, v: int):
    """Slot tables for interleaved 1F1B. Returns (op[pp,T], mi[pp,T],
    ci[pp,T]): op in {0 idle, 1 F, 2 B}; mi the micro; ci the chunk.

    Queue order per stage follows the published schedule (warmup
    forwards grouped chunk-major over micro-groups of size pp, then
    one-F-one-B, then drain); slots are assigned by the same greedy
    dependency simulation as the flat schedule."""
    if v <= 1:
        op, mi = make_1f1b_schedule(pp, n_micro)
        return op, mi, np.zeros_like(op)
    if n_micro % pp != 0:
        # the chunk-major micro-grouping is only feasible when micros
        # fill whole groups; other queue orders deadlock (verified)
        raise ValueError(
            f"interleaved schedule needs n_micro % pp == 0 "
            f"(got n_micro={n_micro}, pp={pp}); pad the microbatch "
            "count or use v=1")

    total_f = v * n_micro

    def f_order():
        # i-th forward -> (chunk, micro), chunk-major within
        # micro-groups of pp (same order on every stage)
        out = []
        for i in range(total_f):
            group, rem = divmod(i, pp * v)
            chunk, pos = divmod(rem, pp)
            out.append((chunk, group * pp + pos))
        return out

    def b_order():
        return [(v - 1 - c, m) for c, m in f_order()]

    seqs = []
    for s in range(pp):
        fs = f_order()
        bs = b_order()
        warm = min((pp - s - 1) * 2 + (v - 1) * pp, total_f)
        seq = [("F",) + fs[i] for i in range(warm)]
        bi = 0
        for fi in range(warm, total_f):
            seq.append(("F",) + fs[fi])
            seq.append(("B",) + bs[bi])
            bi += 1
        while bi < total_f:
            seq.append(("B",) + bs[bi])
            bi += 1
        seqs.append(seq)

    # dependency-respecting greedy slot assignment
    slot_f, slot_b = {}, {}
    ptr = [0] * pp
    op_rows, mi_rows, ci_rows = [], [], []
    t = 0
    limit = 16 * (v * n_micro + pp) + 32
    while any(ptr[s] < len(seqs[s]) for s in range(pp)):
        col_op = [_IDLE] * pp
        col_mi = [0] * pp
        col_ci = [0] * pp
        commit = []
        for s in range(pp):
            if ptr[s] >= len(seqs[s]):
                continue
            kind, c, m = seqs[s][ptr[s]]
            if kind == "F":
                if s > 0:
                    ok = slot_f.get((s - 1, c, m), limit) < t
                elif c > 0:
                    ok = slot_f.get((pp - 1, c - 1, m), limit) < t
                else:
                    ok = True
            else:
                if s == pp - 1 and c == v - 1:
                    ok = slot_f.get((s, c, m), limit) < t
                elif s == pp - 1:
                    ok = slot_b.get((0, c + 1, m), limit) < t
                else:
                    ok = slot_b.get((s + 1, c, m), limit) < t
            if ok:
                col_op[s] = _F if kind == "F" else _B
                col_mi[s] = m
                col_ci[s] = c
                commit.append((s, kind, c, m))
        for s, kind, c, m in commit:
            (slot_f if kind == "F" else slot_b)[(s, c, m)] = t
            ptr[s] += 1
        op_rows.append(col_op)
        mi_rows.append(col_mi)
        ci_rows.append(col_ci)
        t += 1
        if t > limit:
            raise RuntimeError(
                f"interleaved schedule did not converge (pp={pp}, "
                f"n_micro={n_micro}, v={v})")
    return (np.array(op_rows, np.int32).T,
            np.array(mi_rows, np.int32).T,
            np.array(ci_rows, np.int32).T)


def _ring_depth(op_tab, mi_tab, ci_tab, pp, v):
    """Minimal ring size such that no two in-flight entries of ANY of the
    three ``m % ring``-slotted buffers collide, computed from the tables
    so correctness never depends on a schedule-shape assumption.

    Occupancy windows per (stage, chunk), keyed by micro m:
    - in_ring (saved stage input): own F slot -> own B slot;
    - fbuf (boundary activation):  prev-stage F slot (ppermute arrival,
      end of slot) -> own F slot (read at slot start, so a same-slot
      rewrite is safe);
    - gbuf (boundary gradient):    next-stage B slot -> own B slot.
    Two windows with m1 % ring == m2 % ring collide iff one's write lands
    strictly inside the other's window."""
    T = op_tab.shape[1]
    f_slot, b_slot = {}, {}
    for s in range(pp):
        for t in range(T):
            k = (s, int(ci_tab[s, t]), int(mi_tab[s, t]))
            if op_tab[s, t] == _F:
                f_slot[k] = t
            elif op_tab[s, t] == _B:
                b_slot[k] = t

    spans = {}   # (buffer, stage, chunk) -> [(t_write, t_read, m)]

    def add(buf, s, c, tw, tr, m):
        spans.setdefault((buf, s, c), []).append((tw, tr, m))

    for (s, c, m), tf in f_slot.items():
        tb = b_slot.get((s, c, m))
        if tb is not None:
            add("in", s, c, tf, tb, m)                    # in_ring
        # fbuf: who wrote this activation? prev stage's F (chunk-routed)
        prev = (s - 1) % pp
        src_c = c - 1 if s == 0 else c
        if not (s == 0 and c == 0):
            tw = f_slot.get((prev, src_c, m))
            if tw is not None:
                add("f", s, c, tw, tf, m)
        # gbuf: written by next stage's B, read at own B
        if tb is not None and not (s == pp - 1 and c == v - 1):
            nxt = (s + 1) % pp
            src_c = c + 1 if s == pp - 1 else c
            tw = b_slot.get((nxt, src_c, m))
            if tw is not None:
                add("g", s, c, tw, tb, m)

    def collides(ring):
        for key, lst in spans.items():
            same_slot_read_ok = key[0] in ("f", "g")   # read-then-write
            for i in range(len(lst)):
                tw1, tr1, m1 = lst[i]
                for j in range(i + 1, len(lst)):
                    tw2, tr2, m2 = lst[j]
                    if m1 % ring != m2 % ring:
                        continue
                    hi1 = tr1 if same_slot_read_ok else tr1 + 1
                    hi2 = tr2 if same_slot_read_ok else tr2 + 1
                    if tw1 < tw2 < hi1 or tw2 < tw1 < hi2:
                        return True
        return False

    ring = 1
    n_micro = int(mi_tab.max()) + 1 if mi_tab.size else 1
    while ring < n_micro and collides(ring):
        ring += 1
    return ring


def pipeline_interleaved_grads(stage_fn: Callable, stacked_params, feeds,
                               last_fn: Callable, v: int, *,
                               first_fn=None, first_params=None,
                               last_params=None, last_feeds=None,
                               mesh: Optional[Mesh] = None,
                               axis: str = "pp",
                               batch_axes=("dp", "sharding"),
                               loss_scale=None):
    """Interleaved-virtual-stage 1F1B train pass. Like
    :func:`pipeline_1f1b_grads`, but each device hosts ``v`` model
    chunks (stacked_params leaves are [pp, v, ...]; model part
    ``c*pp + s`` lives at (stage s, chunk c)) and a microbatch crosses
    the ring ``v`` times. Returns
    ``(mean_loss, (g_stacked [pp, v, ...], g_first, g_last))``."""
    mesh = mesh or _env.get_mesh()
    pp = mesh.shape[axis]
    nm = feeds.shape[0]
    op_tab, mi_tab, ci_tab = make_interleaved_schedule(pp, nm, v)
    T = op_tab.shape[1]
    ring = _ring_depth(op_tab, mi_tab, ci_tab, pp, v)
    env = _pipe_env(mesh, axis, batch_axes, feeds, last_feeds,
                    first_fn, first_params)
    _axes, n_dp = env["axes"], env["n_dp"]
    feed_spec, lf_spec = env["feed_spec"], env["lf_spec"]
    h_shape, h_dtype = env["h_shape"], env["h_dtype"]
    rep, zeros_like_tree = env["rep"], env["zeros_like_tree"]
    in_spec_params = jax.tree_util.tree_map(
        lambda _: P(axis), stacked_params)

    op_arr = jnp.asarray(op_tab)
    mi_arr = jnp.asarray(mi_tab)
    ci_arr = jnp.asarray(ci_tab)

    def per_device(params_block, mbs, fparams, lparams, lfeeds, scale_a):
        # leaves [1, v, ...] -> [v, ...]
        params_local = jax.tree_util.tree_map(lambda x: x[0],
                                              params_block)
        stage = jax.lax.axis_index(axis)
        perm_fwd = [(i, (i + 1) % pp) for i in range(pp)]
        perm_bwd = [(i, (i - 1) % pp) for i in range(pp)]
        is_first = stage == 0
        is_last = stage == pp - 1
        seed_g = scale_a.astype(jnp.float32)

        zr = lambda: jnp.zeros((v, ring) + h_shape, h_dtype)
        g_mid0 = zeros_like_tree(params_local)        # [v, ...]
        g_first0 = zeros_like_tree(fparams)
        g_last0 = zeros_like_tree(lparams)

        def chunk_params(c):
            return jax.tree_util.tree_map(lambda x: x[c], params_local)

        def chunk_zero_like(tree):
            return jax.tree_util.tree_map(
                lambda x: jnp.zeros(x.shape[1:], x.dtype), tree)

        def lf_of(m):
            return None if lfeeds is None else lfeeds[m]

        def body_idle(oprnd):
            in_ring, fbuf, gbuf, m, c = oprnd
            zeros_h = jnp.zeros(h_shape, h_dtype)
            return (in_ring, zeros_h, zeros_h,
                    chunk_zero_like(params_local), g_first0, g_last0,
                    jnp.zeros((), jnp.float32), c)

        def body_F(oprnd):
            in_ring, fbuf, gbuf, m, c = oprnd
            p_c = chunk_params(c)
            first_part = is_first & (c == 0)
            last_part = is_last & (c == v - 1)
            if first_fn is not None:
                x0 = jax.lax.cond(
                    first_part, lambda: first_fn(fparams, mbs[m]),
                    lambda: jnp.zeros(h_shape, h_dtype))
                x_in = jnp.where(first_part, x0, fbuf[c, m % ring])
            else:
                x_in = jnp.where(first_part, mbs[m].astype(h_dtype),
                                 fbuf[c, m % ring])
            in_ring = in_ring.at[c, m % ring].set(x_in)
            y = jax.lax.cond(last_part,
                             lambda: jnp.zeros(h_shape, h_dtype),
                             lambda: stage_fn(p_c, x_in))
            return (in_ring, y, jnp.zeros(h_shape, h_dtype),
                    chunk_zero_like(params_local), g_first0, g_last0,
                    jnp.zeros((), jnp.float32), c)

        def body_B(oprnd):
            in_ring, fbuf, gbuf, m, c = oprnd
            p_c = chunk_params(c)
            x_saved = in_ring[c, m % ring]
            g_in = gbuf[c, m % ring]
            first_part = is_first & (c == 0)
            last_part = is_last & (c == v - 1)

            def last_case():
                def loss_of(p_mid, p_last, x):
                    y = stage_fn(p_mid, x)
                    return last_fn(p_last, y, lf_of(m)).astype(
                        jnp.float32)
                loss, pull = jax.vjp(loss_of, p_c, lparams, x_saved)
                gm, gl, gx = pull(seed_g)    # GradScaler seed
                return gm, g_first0, gl, gx, loss

            def first_case():
                if first_fn is None:
                    return mid_case()

                def fwd(p_first, p_mid, feed):
                    return stage_fn(p_mid, first_fn(p_first, feed))
                _, pull = jax.vjp(fwd, fparams, p_c, mbs[m])
                gf, gm, _ = pull(g_in)
                return gm, gf, g_last0, jnp.zeros(h_shape, h_dtype), \
                    jnp.zeros((), jnp.float32)

            def mid_case():
                _, pull = jax.vjp(
                    lambda p, x: stage_fn(p, x), p_c, x_saved)
                gm, gx = pull(g_in)
                return gm, g_first0, g_last0, gx, \
                    jnp.zeros((), jnp.float32)

            gm, gf, gl, gx, loss = jax.lax.cond(
                last_part, last_case,
                lambda: jax.lax.cond(first_part, first_case, mid_case))
            return (in_ring, jnp.zeros(h_shape, h_dtype), gx, gm, gf,
                    gl, loss, c)

        def slot(carry, t):
            (in_ring, fbuf, gbuf, gm_acc, gf_acc, gl_acc,
             loss_acc) = carry
            op = op_arr[stage, t]
            m = mi_arr[stage, t]
            c = ci_arr[stage, t]
            (in_ring, send_f, send_g, gm, gf, gl, loss,
             c_out) = jax.lax.switch(op, [body_idle, body_F, body_B],
                                     (in_ring, fbuf, gbuf, m, c))
            recv_f = jax.lax.ppermute(send_f, axis, perm_fwd)
            recv_g = jax.lax.ppermute(send_g, axis, perm_bwd)
            prev = (stage - 1) % pp
            nxt = (stage + 1) % pp
            p_op, p_mi, p_ci = op_arr[prev, t], mi_arr[prev, t], \
                ci_arr[prev, t]
            n_op, n_mi, n_ci = op_arr[nxt, t], mi_arr[nxt, t], \
                ci_arr[nxt, t]
            # forward routing: normal hop keeps the chunk; the wrap from
            # the last stage feeds the NEXT chunk at stage 0
            take_f = (p_op == _F) & (
                (stage > 0) | ((stage == 0) & (p_ci < v - 1)))
            fdst = jnp.where(stage == 0, jnp.minimum(p_ci + 1, v - 1),
                             p_ci)
            fbuf = jnp.where(take_f,
                             fbuf.at[fdst, p_mi % ring].set(recv_f),
                             fbuf)
            # backward routing mirrors it: the wrap from stage 0 feeds
            # the PREVIOUS chunk at the last stage
            take_g = (n_op == _B) & (
                (stage < pp - 1) | ((stage == pp - 1) & (n_ci > 0)))
            gdst = jnp.where(stage == pp - 1, jnp.maximum(n_ci - 1, 0),
                             n_ci)
            gbuf = jnp.where(take_g,
                             gbuf.at[gdst, n_mi % ring].set(recv_g),
                             gbuf)
            add = jax.tree_util.tree_map
            gm_acc = add(lambda acc, g: acc.at[c].add(g), gm_acc, gm)
            return (in_ring, fbuf, gbuf, gm_acc,
                    add(jnp.add, gf_acc, gf), add(jnp.add, gl_acc, gl),
                    loss_acc + loss), None

        carry0 = (zr(), zr(), zr(), g_mid0, g_first0, g_last0,
                  jnp.zeros((), jnp.float32))
        (in_ring, fbuf, gbuf, gm_acc, gf_acc, gl_acc,
         loss_acc), _ = jax.lax.scan(slot, carry0, jnp.arange(T))

        return _pipe_outputs(axis, _axes, nm, n_dp, loss_acc,
                             gm_acc, gf_acc, gl_acc)

    from .shard_utils import manual_region
    mapped = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(in_spec_params, feed_spec, rep(first_params),
                  rep(last_params), lf_spec, P()),
        out_specs=(P(), jax.tree_util.tree_map(lambda _: P(axis),
                                               stacked_params),
                   rep(first_params), rep(last_params)),
        check_vma=False)
    scale_a = jnp.float32(1.0) if loss_scale is None \
        else jnp.asarray(loss_scale, jnp.float32)
    from ..profiler import RecordEvent
    with manual_region(), RecordEvent("pipeline:interleaved_1f1b"):
        loss, g_stacked, g_first, g_last = mapped(
            stacked_params, feeds, first_params, last_params, last_feeds,
            scale_a)
    return loss, (g_stacked, g_first, g_last)

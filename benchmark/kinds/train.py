"""A training cell (``"kind": "train"``): ``TrainStep.__call__`` fed by
``io.DataLoader``, on one chip. See ``kinds/serve.py`` for what a kind's
file is."""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.lib import check, harness, spans, traffic, weights
from benchmark.lib.harness import log


def _norm(x):
    import jax.numpy as jnp
    return float(jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))))


def run(ctx):
    import jax
    import paddle_tpu as paddle
    from paddle_tpu import monitor, native
    from paddle_tpu.io import DataLoader
    from paddle_tpu.jit import TrainStep
    args, cell, cfg, mix, run = ctx.args, ctx.cell, ctx.cfg, ctx.mix, ctx.run
    job, opt_cfg = cell["job"], cell["optimizer"]
    model = ctx.family.build(cfg, args.seed, training=True)
    log(phase="model built", t=harness.since_start())
    opt = paddle.optimizer.AdamW(
        opt_cfg["lr"], beta1=opt_cfg["beta1"], beta2=opt_cfg["beta2"],
        epsilon=opt_cfg["epsilon"], weight_decay=opt_cfg["weight_decay"],
        parameters=model.parameters(), multi_precision=True)
    step = TrainStep(model, lambda out, a, k: out, opt)
    step = ctx.hooks.get("step", lambda s: s)(step)
    if job["loader_workers"] and not native.is_available():
        native.ensure_built(verbose=True)   # workers need the shm ring
    loader = DataLoader(
        traffic.TokenRows(mix["seq_len"], cfg["vocab_size"], args.seed),
        batch_size=mix["rows_per_step"], shuffle=False,
        num_workers=job["loader_workers"])
    feed = iter(loader)
    log(phase="loader made", t=harness.since_start())
    names = [n for n, _ in model.named_parameters()]
    params = dict(model.named_parameters())

    # the first three steps, through the window's own call and feed; the
    # same step object and iterator then go on into the window
    prog = {"losses": []}
    fed = []
    for t in (1, 2, 3):
        x, y = next(feed)
        fed.append((np.asarray(x.numpy()), np.asarray(y.numpy())))
        prog["losses"].append(float(step(x, y).numpy()))
        if t == 1:
            m1 = {n: st[keys.index("moment1")] for (n, _p), keys, st in zip(
                step.binder.param_items, step._state_keys, step._opt_states)}
            prog["gnorm"] = {n: _norm(v) / (1.0 - opt_cfg["beta1"])
                             for n, v in m1.items()}
            del m1
    log(phase="three steps", t=harness.since_start())
    start = weights.make(ctx.family.leaf_shapes(cfg), args.seed)
    prog["change"] = {n: _norm(params[n]._data.astype("float32")
                               - start[n].astype("float32")) for n in names}
    del start
    for _ in range(job["warm_steps"]):
        x, y = next(feed)
        loss = step(x, y)
    jax.block_until_ready(loss._data)
    label = step.telemetry_name

    def step_compiles():
        return sum(monitor.counter(m, labels=("step",)).labels(
            step=label).value() for m in (
                "train_step_compiles", "train_step_fallback_recompiles"))
    c0, n0 = step_compiles(), ctx.compiles.n
    run.setup_s = harness.since_start()

    tracer = harness.Tracer(cell["trace"], args.trace_dir) \
        if args.trace else None
    run.tokens_per_step = mix["rows_per_step"] * mix["seq_len"]
    run.t_open = time.monotonic()
    t_end = run.t_open + args.seconds
    while True:
        now = time.monotonic()
        if now >= t_end:
            break
        if tracer:
            tracer.on_tick(now)
        with spans.span(run.host_spans, "loader.next"):
            x, y = next(feed)
        with spans.span(run.host_spans, "train.step"):
            loss = step(x, y)
        run.steps += 1
    with spans.span(run.host_spans, "fetch"):
        last = float(loss.numpy())          # the window ends on the device
    run.t_close = time.monotonic()
    if tracer:
        tracer.finish()
    run.counters = {"steps": run.steps,
                    "executables_compiled": step_compiles() - c0}
    run.memory_peak_bytes = harness.memory("peak_bytes_in_use")
    run.memory_window_bytes = run.memory_peak_bytes     # the step's own
    compiled = ctx.compiles.n - n0
    log(window="closed", compiles_in_window=compiled,
        counters=run.counters, last_loss=last)
    if compiled or run.counters["executables_compiled"]:
        raise RuntimeError("something was compiled inside the window")
    if tracer:
        run.trace = tracer.reduce(run.host_spans)

    feed.close()            # ends the loader's workers and waits for them
    del feed, loader, step, opt, model, params, x, y, loss
    gc.collect()

    rows = traffic.TokenRows(mix["seq_len"], cfg["vocab_size"], args.seed)
    n = mix["rows_per_step"]
    batches = [tuple(np.stack(c) for c in zip(*(rows[t * n + i]
                                                 for i in range(n))))
               for t in range(3)]
    numbers = {"feed_mismatch": float(sum(
        int(not (np.array_equal(a[0], b[0]) and np.array_equal(a[1], b[1])))
        for a, b in zip(fed, batches)))}
    ref = ctx.family.train_reference(cfg, args.seed, batches, opt_cfg)
    got, where = check.train_numbers(prog, ref)
    numbers.update(got)
    numbers["last_loss_not_finite"] = float(not np.isfinite(last))
    log(worst_leaves=where, losses=prog["losses"], ref_losses=ref["losses"])
    if ctx.hooks.get("control"):
        # the reference in the program's place: one precision down, and
        # with a fault planted in it (tools/control.py); and the program
        # against the reference with no master copy, its own fault
        exact = {"feed_mismatch": 0.0, "last_loss_not_finite": 0.0}
        for name, kw in (("control_lowp", {"lowp": True}),
                         ("fault_half_batch", {"half_batch": True}),
                         ("fault_no_master", {"no_master": True})):
            other = ctx.family.train_reference(cfg, args.seed, batches,
                                               opt_cfg, **kw)
            ctx.controls[name] = dict(
                check.train_numbers(other, ref)[0], **exact)
            if name == "fault_no_master":
                ctx.controls["program_vs_no_master"] = dict(
                    check.train_numbers(prog, other)[0], **exact)
            del other
    return numbers, run.steps, 0

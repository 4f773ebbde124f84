#!/usr/bin/env python3
"""Which rows of a sound run lie far from the reference, and why: the
program's expert choices beside the reference's, row by row.

    python3 benchmark/tools/route_probe.py --workload <cell> --seed <n> \
        [--rows 1024]

One seeded sequence of ``--rows`` token ids (drawn as the cell's
traffic draws them) goes through the program's whole-sequence forward
in the configuration's precision (``paddle_tpu.models.lfm2_moe``, the
form tier-1 holds equal to the served path) and through the plain
float32 reference, a layer at a time. Kept on the way: each expert
layer's chosen experts per row, from the program's gate
(``distributed.moe.group_limited_gate``, wrapped) and from the
reference's (``reference.lfm2_moe.route``, called on the reference's
own hidden state, as ``experts`` calls it). Then, as the comparison
that decides ``correct`` does it, each row's gap: how far the token the
PROGRAM puts first lies below the reference's best logit. The same for
the float8 control in the program's place.

Printed (one JSON line, and the rows themselves under
``chiprun_out/route_probe.<seed>.json``): rows by the number of expert
layers whose chosen SET differs from the reference's, with each
class's count, mean, 90th percentile and largest gap; the quantiles of
all rows' gaps; where the largest gaps sit. A run of the benchmark
never calls this; it measures no time.
"""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import numpy as np                                  # noqa: E402

from benchmark import run as bench                  # noqa: E402
from benchmark.lib import check, weights            # noqa: E402


def program_side(fam, cfg, seed, ids):
    """(logits [T, V] float32, chosen [expert layers, T, k]) of the
    program's whole-sequence forward."""
    import paddle_tpu as paddle
    from paddle_tpu.distributed import moe
    model = fam.build(cfg, seed, training=False)
    sound, chosen = moe.group_limited_gate, []

    def gate(*args, **kw):
        idx, w = sound(*args, **kw)
        chosen.append(np.asarray(idx))
        return idx, w

    moe.group_limited_gate = gate
    try:
        with paddle.no_grad():      # eager: the gate's outputs are arrays
            logits = np.asarray(
                model(paddle.to_tensor(ids[None]))._data[0], np.float32)
    finally:
        moe.group_limited_gate = sound
    del model
    gc.collect()
    return logits, np.stack(chosen)


def reference_side(fam, cfg, seed, ids, lowp):
    """The same of the plain reference (``lowp``: the float8 control)."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import lfm2_moe as ref
    small = fam._small(cfg)
    name = "model.embed_tokens.weight"
    table = weights.make({name: fam.leaf_shapes(cfg)[name]}, seed)[name]
    h = ref.embed(jnp.asarray(ids[None].astype(np.int32)), table)
    eps, chosen = cfg["norm_eps"], []

    def routed(hs, w, attends):
        """The layer's choices: its router sees the hidden state after
        the mixer, normed (``ref.layer_forward``)."""
        mixer = ref.attention if attends else ref.short_conv
        hs = hs + mixer(ref.rms_norm(hs, w["operator_norm.weight"], eps),
                        w, small, lowp)
        return ref.route(ref.rms_norm(hs, w["ffn_norm.weight"], eps), w,
                         small, lowp)[0]

    routed = jax.jit(routed, static_argnums=2)

    for i, kind in enumerate(cfg["layer_types"]):
        pre = f"model.layers.{i}."
        w = {k[len(pre):]: v for k, v in
             weights.make(fam.layer_shapes(cfg, i), seed).items()}
        if i >= cfg["num_dense_layers"]:
            chosen.append(np.asarray(
                routed(h[0], w, kind == "full_attention")))
        h = fam._layer(h, w, fam._static(small), kind,
                       i < cfg["num_dense_layers"], lowp)
        del w
    norm = "model.embedding_norm.weight"
    w_norm = weights.make({norm: (cfg["hidden_size"],)}, seed)[norm]
    logits = ref.head(h[0], w_norm, table, small, lowp)
    return np.asarray(logits, np.float32), np.stack(chosen)


def layers_flipped(a, b):
    """Per row, the expert layers whose chosen SET differs."""
    return (np.sort(a, -1) != np.sort(b, -1)).any(-1).sum(0)


def by_flips(gaps, flips):
    out = {}
    for n in sorted(set(flips.tolist())):
        g = gaps[flips == n]
        out[str(n)] = {"rows": int(g.size), "mean": float(g.mean()),
                       "p90": float(np.quantile(g, 0.9)),
                       "max": float(g.max())}
    return out


def quantiles(gaps):
    return {f"p{q}": float(np.quantile(gaps, q / 100))
            for q in (50, 75, 90, 95, 99, 100)}


def probe(fam, cfg, seed, rows):
    ids = np.random.default_rng([int(seed), 7]).integers(
        1, cfg["vocab_size"], rows)
    prog, prog_chosen = program_side(fam, cfg, seed, ids)
    ref_logits, ref_chosen = reference_side(fam, cfg, seed, ids, False)
    low, low_chosen = reference_side(fam, cfg, seed, ids, True)
    out, kept = {"rows": int(rows), "expert_layers": len(ref_chosen)}, {}
    for name, logits, chosen in (("program", prog, prog_chosen),
                                 ("control_lowp", low, low_chosen)):
        gaps = check.gaps_below_best(ref_logits, logits.argmax(-1))
        flips = layers_flipped(chosen, ref_chosen)
        worst = np.argsort(gaps)[::-1][:8]
        out[name] = {
            "rows_with_a_flipped_layer": int((flips > 0).sum()),
            "flipped_layer_rows": int(flips.sum()),
            "gaps": quantiles(gaps), "gap_mean": float(gaps.mean()),
            "by_layers_flipped": by_flips(gaps, flips),
            "worst_rows": [{"row": int(r), "gap": float(gaps[r]),
                            "layers_flipped": int(flips[r])}
                           for r in worst]}
        kept[name] = {"gaps": gaps.tolist(), "flips": flips.tolist()}
    return out, kept


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--rows", type=int, default=1024)
    args = ap.parse_args()
    cell, cfg, _mix = bench.load_cell(args.workload)
    bench.configure_cache()
    import paddle_tpu  # noqa: F401  (before JAX's backend is touched)
    import jax
    out, kept = probe(bench.find("models." + cfg["model_type"]), cfg,
                      args.seed, args.rows)
    out.update(workload=args.workload, seed=args.seed,
               platform=jax.devices()[0].platform)
    os.makedirs(os.path.join(ROOT, "chiprun_out"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out",
                           f"route_probe.{args.seed}.json"), "w") as f:
        json.dump(dict(out, per_row=kept), f)
    print(json.dumps(out), flush=True)

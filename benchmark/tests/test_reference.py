"""The plain reference against the program's model at a tiny size:
forward logits, the loss, and (through a whole tiny training run) the
gradients and the AdamW update. The reference takes nothing from the
program but the names of the leaves."""
import numpy as np
import pytest

from conftest import TINY, known_fault_only, run_cell


@pytest.mark.parametrize("tied", [False, True])
def test_forward_logits_and_loss_match_the_model(tied):
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from benchmark.lib import weights
    from benchmark.models import qwen2 as family
    from benchmark.reference import qwen2 as ref
    cfg = dict(TINY, tie_word_embeddings=tied)
    seed = 2**31 + 11
    model = family.build(cfg, seed, training=False)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], (2, 48))
    got = np.asarray(model(paddle.to_tensor(ids)).numpy(), np.float32)
    w = weights.make(family.leaf_shapes(cfg), seed)
    want = np.asarray(ref.forward(w, jnp.asarray(ids), cfg))
    assert got.shape == want.shape == (2, 48, cfg["vocab_size"])
    # bf16 program against the f32 reference: logits of scale ~0.3
    assert np.abs(got - want).max() < 0.03
    assert (got.argmax(-1) == want.argmax(-1)).mean() > 0.9
    # the loss the train step takes is the reference's cross entropy
    labels = np.roll(ids, -1, axis=1)
    loss = float(model(paddle.to_tensor(ids),
                       paddle.to_tensor(labels)).numpy())
    assert loss == pytest.approx(
        float(ref.loss_fn(w, jnp.asarray(ids), jnp.asarray(labels), cfg)),
        rel=2e-3)


def test_lowp_control_departs_from_the_reference():
    import jax.numpy as jnp
    from benchmark.lib import weights
    from benchmark.models import qwen2 as family
    from benchmark.reference import qwen2 as ref
    w = weights.make(family.leaf_shapes(TINY), 5)
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 512, (1, 32)))
    hi = np.asarray(ref.forward(w, ids, TINY))
    lo = np.asarray(ref.forward(w, ids, TINY, lowp=True))
    bf16_step = 2.0 ** -8
    assert np.abs(hi - lo).max() > 4 * bf16_step * np.abs(hi).max()


def test_gradients_and_update_match_through_a_training_run(tiny_tree,
                                                           capsys):
    res, logs, _err = run_cell(capsys, "tiny-train", seed=123,
                               hooks={"control": True})
    c = {k: v["value"] for k, v in res["compared"].items()}
    known_fault_only(res, logs)
    assert c["feed_mismatch"] == 0
    assert max(c["loss_gap_1"], c["loss_gap_2"]) < 1e-3 and "loss_gap_3" not in c
    assert c["grad_norm_gap"] < 0.02
    worst = next(l["worst_leaves"] for l in logs if "worst_leaves" in l)
    assert set(worst) == {"grad_norm_gap", "change_norm_gap"}
    # against the reference with the master copy taken out, the program's
    # own fault planted in it, every leaf's change agrees
    readings = {l["reading"]: l for l in logs if "reading" in l}
    assert readings["program_vs_no_master"]["numbers"]["change_norm_gap"] \
        < 0.02


def test_eager_adamw_keeps_a_master_copy_as_the_reference_does():
    """The second witness for that fault: the program's eager
    ``opt.step()`` with ``multi_precision`` moves a bf16 norm weight of
    1.0 as the reference's float32 master does; a bf16 weight updated
    in place stays where it was."""
    import jax.numpy as jnp
    import paddle_tpu as paddle
    from benchmark.reference import qwen2 as ref
    opt_cfg = dict(lr=1e-3, beta1=0.9, beta2=0.999, epsilon=1e-8,
                   weight_decay=0.01)
    g = np.linspace(0.5, 1.5, 64).astype(np.float32)
    lin = paddle.nn.Linear(64, 1, bias_attr=False)
    p = lin.weight
    p._data = jnp.ones(p.shape, jnp.bfloat16)
    opt = paddle.optimizer.AdamW(
        opt_cfg["lr"], beta1=0.9, beta2=0.999, epsilon=1e-8,
        weight_decay=0.01, parameters=[p], multi_precision=True)
    master = jnp.ones(p.shape, jnp.float32)
    m1 = m2 = jnp.zeros(p.shape, jnp.float32)
    for t in (1, 2, 3):
        x = paddle.to_tensor(g.reshape(1, 64)).astype("bfloat16")
        lin(x).sum().backward()
        opt.step()
        opt.clear_grad()
        master, m1, m2 = ref.adamw_update(
            master, jnp.asarray(g).reshape(p.shape), m1, m2,
            jnp.float32(t), opt_cfg)
    want = np.asarray(master.astype(jnp.bfloat16), np.float32)
    got = np.asarray(p._data, np.float32)
    assert (want < 1.0).all()       # three steps of 1e-3 cross a bf16 step
    assert np.array_equal(got, want)


def test_flat_gradient_leaves_are_found_by_rule_not_by_name():
    from benchmark.lib import check
    g = {"a": 1.0, "b": 2.0, "c": 3.0, "k_bias": 1e-9}
    assert check.flat_gradient_leaves(g) == {"k_bias"}
    # the worst leaf is measured against the larger of its own norm and
    # the median leaf's
    gap, at = check.worst_leaf_gap({"a": 1.1, "b": 2.0, "c": 3.0,
                                    "k_bias": 2e-9}, g)
    assert at == "a" and gap == pytest.approx(0.1 / 1.5)

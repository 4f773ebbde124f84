"""Pallas flash-attention kernel parity vs jax.nn.dot_product_attention
(the numpy-oracle OpTest pattern, SURVEY.md §4). Runs the real kernel in
pallas interpret mode on CPU; the same code path compiles on TPU."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu.ops.pallas.flash_attention_kernel as fak


@pytest.fixture(autouse=True)
def _interpret():
    prev = fak._FORCE_INTERPRET
    fak._FORCE_INTERPRET = True
    yield
    fak._FORCE_INTERPRET = prev


def _qkv(b=2, l=256, h=4, d=64, dtype=np.float32, seed=0):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, l, h, d).astype(dtype))
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_xla(causal):
    q, k, v = _qkv()
    out = fak.pallas_flash_attention(q, k, v, causal=causal,
                                     block_q=128, block_k=128)
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=causal)
    assert float(jnp.abs(out - ref).max()) < 2e-5


@pytest.mark.parametrize("causal", [False, True])
def test_backward_matches_xla(causal):
    q, k, v = _qkv()

    def loss_pallas(q, k, v):
        o = fak.pallas_flash_attention(q, k, v, causal=causal,
                                       block_q=128, block_k=128)
        return jnp.sum(o ** 2)

    def loss_ref(q, k, v):
        return jnp.sum(jax.nn.dot_product_attention(
            q, k, v, is_causal=causal) ** 2)

    gp = jax.grad(loss_pallas, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gp, gr):
        rel = float(jnp.abs(a - b).max()) / max(1e-6,
                                                float(jnp.abs(b).max()))
        assert rel < 1e-4


def test_bf16_tolerance():
    q, k, v = _qkv(dtype=np.float32)
    qb, kb, vb = (x.astype(jnp.bfloat16) for x in (q, k, v))
    out = fak.pallas_flash_attention(qb, kb, vb, causal=True,
                                     block_q=128, block_k=128)
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=True)
    xla_bf16 = jax.nn.dot_product_attention(qb, kb, vb, is_causal=True)
    kern_err = float(jnp.abs(out.astype(jnp.float32) - ref).max())
    xla_err = float(jnp.abs(xla_bf16.astype(jnp.float32) - ref).max())
    # fp32 accumulators: the kernel must be at least as accurate as the
    # XLA bf16 path, and within bf16 resolution of the fp32 oracle
    assert kern_err <= xla_err + 1e-3
    assert kern_err < 2e-2


def test_uneven_seq_blocks():
    # L=384 -> block sizes must adapt (384 % 256 != 0)
    q, k, v = _qkv(l=384)
    out = fak.pallas_flash_attention(q, k, v, causal=True)
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=True)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_gqa_via_repeat_matches():
    # GQA: caller repeats K/V heads (llama.py:150 pattern)
    q, _, _ = _qkv(h=8)
    _, k, v = _qkv(h=2, seed=1)
    k = jnp.repeat(k, 4, axis=2)
    v = jnp.repeat(v, 4, axis=2)
    out = fak.pallas_flash_attention(q, k, v, causal=True,
                                     block_q=128, block_k=128)
    ref = jax.nn.dot_product_attention(q, k, v, is_causal=True)
    assert float(jnp.abs(out - ref).max()) < 2e-5


def test_core_dispatch_fallback_logs_once(recwarn):
    # bias path must take the XLA fallback (kernel ineligible), silently
    # on CPU (no TPU), and produce correct results
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_core
    q, k, v = _qkv(l=64)
    bias = jnp.zeros((1, 1, 64, 64), jnp.float32)
    out = flash_attention_core(q, k, v, bias=bias)
    ref = jax.nn.dot_product_attention(q, k, v, bias=bias)
    assert float(jnp.abs(out - ref).max()) < 1e-6


def test_kernel_is_shard_mapped_under_a_gspmd_mesh(monkeypatch):
    """Under a fleet mesh the train step is a GSPMD-partitioned program,
    and Mosaic kernels cannot be partitioned automatically (the first
    hybrid step on four chips raised "Please wrap the call in a
    shard_map"). The dispatcher maps the kernel over the mesh by hand —
    batch over the data axes, heads over mp — and it stays exact,
    forward and backward, against XLA attention."""
    import numpy as np
    from jax.sharding import Mesh
    from paddle_tpu.distributed import env as denv
    from paddle_tpu.ops.pallas import flash_attention as fa
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    monkeypatch.setattr(fa, "_pallas_available", lambda: True)
    monkeypatch.setattr(fak, "_FORCE_INTERPRET", True)
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 256, 4, 64), jnp.float32)
    k = jnp.asarray(rng.randn(2, 256, 2, 64), jnp.float32)
    v = jnp.asarray(rng.randn(2, 256, 2, 64), jnp.float32)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) ** 2).sum()

    core = lambda q, k, v: fa.flash_attention_core(q, k, v, is_causal=True)
    ref = lambda q, k, v: fa._xla_attention(q, k, v, None, True, 0.125)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2),
                ("sharding", "mp"))
    prev = denv.get_mesh()
    denv.set_mesh(mesh)
    try:
        spec = fa._gspmd_shard_spec(q, k)
        assert spec[1] == jax.sharding.PartitionSpec(
            ("sharding",), None, "mp", None)
        # one kv head does not split over mp=2: XLA attention instead
        assert fa._gspmd_shard_spec(q, k[:, :, :1]) is False
        traced = jax.jit(core).trace(q, k, v)
        assert "shard_map" in str(traced.jaxpr)
        got = jax.jit(jax.value_and_grad(loss(core), (0, 1, 2)))(q, k, v)
    finally:
        denv.set_mesh(prev)
    assert fa._gspmd_shard_spec(q, k) is None       # no mesh: plain call
    want = jax.value_and_grad(loss(ref), (0, 1, 2))(q, k, v)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)

"""The short convolution's tap reader (``ops/short_conv.py``): the
one-pass Mosaic kernel, run by the Pallas interpreter on the CPU,
against its ``jax.numpy`` mirror. The two must agree EXACTLY — the
kernel moves values (a rotation, 0 / 1 selections on the MXU) and sums
the taps in the mirror's order, so ``np.array_equal`` holds on the sum
and on the whole new table, null seat included. What the mirror itself
computes is held to the whole-sequence forms by
``test_solar_open2.py::test_tap_reader_equals_the_whole_sequence_forms``
and the LFM2 tests.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops import paged_cache as pc
from paddle_tpu.ops import short_conv as sc
from paddle_tpu.ops.pallas import paged_attention as pa

# name: (taps, q_lens a slot, each slot's first position, packed rows,
# channels)
CASES = {
    # every slot one row: each reads all its taps from the table
    "decode_only.4taps": (4, [1, 1, 1, 1], [9, 4, 17, 5], 8, 256),
    "decode_only.3taps": (3, [1, 1, 1, 1, 1], [9, 4, 17, 5, 2], 8, 128),
    # a chunk that continues from a held state, beside decode rows
    "chunk_beside_decode.4taps": (4, [1, 11, 1, 1], [9, 64, 3, 5], 16, 256),
    "chunk_beside_decode.3taps": (3, [1, 1, 11, 1], [9, 3, 64, 5], 16, 256),
    # chunks shorter than, as long as and longer than the L - 1 the
    # table keeps: old taps shift down by 1, by 2, leave altogether
    "chunk_of_1": (4, [1, 1], [6, 0], 8, 128),
    "chunk_of_2": (4, [2, 1], [6, 3], 8, 128),
    "chunk_of_3": (4, [3, 1], [6, 3], 8, 128),
    "chunk_of_2.3taps": (3, [1, 2], [3, 6], 8, 128),
    "chunk_longer": (4, [7, 1], [6, 3], 8, 128),
    # a NEW request's seat over its last occupant's taps: zeros are read
    # and, where its rows are fewer than L - 1, zeros are kept
    "fresh_seat.chunk": (4, [1, 6, 1], [5, 0, 2], 8, 256),
    "fresh_seat.two_rows": (4, [1, 2, 1], [5, 0, 2], 8, 256),
    "fresh_seat.one_row.3taps": (3, [1, 1, 1], [5, 0, 2], 8, 128),
    # a slot with no rows this tick keeps its table row
    "rowless_slot": (4, [1, 0, 3, 0, 1], [5, 8, 2, 0, 7], 8, 128),
    # rows past the packed total (they carry slot 0) and a tick of
    # nothing but them: the null seat is read, nothing is written
    "rows_past_the_total": (4, [2, 1], [4, 9], 16, 128),
    "no_live_row": (3, [0, 0, 0], [4, 9, 0], 8, 128),
    # three channel tiles of 128 lanes; one of 384 would not divide
    "three_tiles": (4, [1, 5, 0, 1], [9, 0, 3, 5], 8, 384),
    "two_wide_tiles.3taps": (3, [1, 5, 0, 1], [9, 0, 3, 5], 8, 2048),
}


def _operands(taps, q_lens, base, rows, channels, seed=0, null=0.0):
    rng = np.random.default_rng(seed)
    n = len(q_lens)
    g = jnp.asarray(rng.standard_normal((rows, channels)), jnp.bfloat16)
    state = jnp.asarray(rng.standard_normal((n + 1, taps - 1, channels)),
                        jnp.bfloat16).at[n].set(null)
    w = jnp.asarray(rng.standard_normal((channels, taps)), jnp.bfloat16)
    sl, pos, rs, _ = pc.ragged_row_meta(q_lens, base, rows, 10 ** 6)
    meta = tuple(jnp.asarray(x, jnp.int32) for x in (q_lens, rs, sl, pos))
    return g, state, w, meta


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_equals_the_mirror_exactly(case):
    taps, q_lens, base, rows, channels = CASES[case]
    # (the null seat holds zeros in the engine; a marked one shows which
    # rows read it)
    g, state, w, meta = _operands(taps, q_lens, base, rows, channels,
                                  seed=len(case), null=3.0)
    want_conv, want_state = sc._xla_ragged_taps(g, state, w, meta)
    conv, new = sc.pallas_ragged_taps(g, state, w, meta, interpret=True)
    assert conv.dtype == jnp.float32 and new.dtype == state.dtype
    assert np.array_equal(_f32(conv), _f32(want_conv))
    assert np.array_equal(_f32(new), _f32(want_state))
    # what the docstring promises of the table, read off the kernel's
    n = len(q_lens)
    old, new = _f32(state), _f32(new)
    assert np.array_equal(new[n], old[n])
    for s, rows_s in enumerate(q_lens):
        if not rows_s:
            assert np.array_equal(new[s], old[s])
    total = int(np.sum(q_lens))
    if total < rows:
        # a row past the total sits at offset >= L - 1 of slot 0's run:
        # its older taps are the packed rows before it, its seat (where
        # it has to read one) the null seat
        tail = _f32(sc.causal_taps(
            w, [g[rows - taps + j] for j in range(taps)]))
        assert np.array_equal(_f32(conv)[rows - 1], tail)


def test_retired_rows_read_the_null_seat_and_write_nothing():
    """A slot retired inside the executable (``q_lens`` 0 where the
    packed rows still name it): its rows are no slot's."""
    taps, channels = 4, 128
    g, state, w, _meta = _operands(taps, [1, 1, 1], [5, 6, 7], 8, channels,
                                   null=2.0)
    # rows 0..2 name slots 0..2 at row_starts 0..2; slot 1 is retired
    meta = tuple(jnp.asarray(x, jnp.int32) for x in (
        [1, 0, 1], [0, 1, 2], [0, 1, 2, 0, 0, 0, 0, 0], [5, 6, 7] + [9] * 5))
    want_conv, want_state = sc._xla_ragged_taps(g, state, w, meta)
    conv, new = sc.pallas_ragged_taps(g, state, w, meta, interpret=True)
    assert np.array_equal(_f32(conv), _f32(want_conv))
    assert np.array_equal(_f32(new), _f32(want_state))
    assert np.array_equal(_f32(new)[[1, 3]], _f32(state)[[1, 3]])
    null_taps = [jnp.full((channels,), 2.0, jnp.bfloat16)] * (taps - 1)
    assert np.array_equal(_f32(conv)[1],
                          _f32(sc.causal_taps(w, null_taps + [g[1]])))


def test_dispatch_follows_the_kernels_rule(monkeypatch):
    """Off a TPU the mirror runs; under ``PADDLE_TPU_PAGED_KERNEL=
    interpret`` the kernel does; on a TPU a call the kernel refuses
    (channels that no tile of whole lanes divides; a float32 table,
    whose values would not cross the MXU exactly) runs the mirror and
    is COUNTED as a fallback."""
    called = []
    kernel = sc.pallas_ragged_taps
    monkeypatch.setattr(sc, "pallas_ragged_taps",
                        lambda *a, **kw: called.append(1) or kernel(*a, **kw))
    g, state, w, meta = _operands(4, [1, 2], [3, 0], 8, 128)
    want = sc._xla_ragged_taps(g, state, w, meta)
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL", raising=False)
    sc.ragged_causal_taps(g, state, w, meta)
    assert not called
    monkeypatch.setenv("PADDLE_TPU_PAGED_KERNEL", "interpret")
    got = sc.ragged_causal_taps(g, state, w, meta)
    assert called == [1]
    for a, b in zip(got, want):
        assert np.array_equal(_f32(a), _f32(b))
    monkeypatch.delenv("PADDLE_TPU_PAGED_KERNEL")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    n0 = pa.kernel_fallback_counts().get("short_conv_taps", 0)
    for channels, dtype in ((96, jnp.bfloat16), (128, jnp.float32)):
        g, state, w, meta = _operands(4, [1, 2], [3, 0], 8, channels)
        g, state = g.astype(dtype), state.astype(dtype)
        got = sc.ragged_causal_taps(g, state, w, meta)
        for a, b in zip(got, sc._xla_ragged_taps(g, state, w, meta)):
            assert np.array_equal(_f32(a), _f32(b))
    assert called == [1]
    assert pa.kernel_fallback_counts()["short_conv_taps"] == n0 + 2

"""The comparison that decides ``correct`` fails what it has to fail.

Each test drives a whole run (the look for a chip is skipped, here in
the test) with the timed path broken underneath, or puts the control in
the program's place, and sees ``correct`` come out false. The limits of
the tiny cells (``conftest.py``) are set between the tiny size's own
readings, as the real cells' are between the chip's (``PERF.md``).
"""
import pytest

from conftest import known_fault_only, run_cell

SEED = 2**31 + 9


# -- training -------------------------------------------------------------

class _Wrapped:
    """Stands in for the TrainStep; everything but the call is its own."""

    def __init__(self, step):
        self.step = step

    def __getattr__(self, name):
        return getattr(self.step, name)


class _StateUnchanged(_Wrapped):
    """The fault "a step that returns its state unchanged": the loss is
    computed, parameters and optimizer state are put back."""

    def __call__(self, x, y):
        import jax
        import jax.numpy as jnp
        step = self.step
        params = [p for _n, p in step.binder.param_items]
        saved = [jnp.copy(p._data) for p in params]
        saved_opt = jax.tree_util.tree_map(
            jnp.copy, step._init_opt_state() if step._jitted is None
            else step._opt_states)
        loss = step(x, y)
        for p, d in zip(params, saved):
            p._data = d
        step._opt_states = saved_opt
        return loss


class _HalfBatch(_Wrapped):
    """The fault "half of the batch left out, the mean taken over the
    rest"."""

    def __call__(self, x, y):
        n = x.shape[0] // 2
        return self.step(x[:n], y[:n])


@pytest.mark.parametrize("fault,fails", [
    (_StateUnchanged, {"grad_norm_gap", "change_norm_gap"}),
    (_HalfBatch, {"grad_norm_gap"}),
])
def test_train_fault_reads_not_correct(tiny_tree, capsys, fault, fails):
    res, _logs, _err = run_cell(capsys, "tiny-train", seed=SEED,
                                hooks={"step": fault})
    assert res["correct"] is False
    over = {k for k, v in res["compared"].items() if v["value"] > v["limit"]}
    assert fails <= over
    if fault is _StateUnchanged:        # nothing moved: the gap reads 1
        assert res["compared"]["change_norm_gap"]["value"] == \
            pytest.approx(1.0, abs=1e-3)


def _readings(logs):
    return {l["reading"]: l for l in logs if "reading" in l}


def test_train_control_and_faults_fail_what_the_program_passes(tiny_tree,
                                                               capsys):
    """The reference one precision down (float8 operands), put in the
    program's place, fails at least one number that the program passes
    under the same limits; so do the planted faults, and the program's
    own fault (no master copy) reads as the program does."""
    res, logs, _err = run_cell(capsys, "tiny-train", seed=SEED,
                               hooks={"control": True})
    known_fault_only(res, logs)
    passed = {k for k, v in res["compared"].items()
              if v["value"] <= v["limit"]}
    got = _readings(logs)
    for name in ("control_lowp", "fault_half_batch", "fault_no_master"):
        assert got[name]["correct"] is False
    assert set(got["control_lowp"]["over"]) & passed
    assert set(got["fault_half_batch"]["over"]) & passed
    assert got["fault_no_master"]["over"] == ["change_norm_gap"]
    assert got["program_vs_no_master"]["correct"] is True


# -- serving ----------------------------------------------------------------

def test_serve_altered_token_reads_not_correct(tiny_tree, capsys):
    """The fault "a token altered where it is produced": every fifth
    token reaches the client changed."""
    def alter(engine):
        inner, seen = engine._stream, [0]

        def stream(rid, tok):
            seen[0] += 1
            inner(rid, (tok + 1) % 512 if seen[0] % 5 == 0 else tok)
        engine._stream = stream

    res, _logs, _err = run_cell(capsys, "tiny-sat", seed=SEED,
                                hooks={"engine": alter})
    assert res["correct"] is False
    assert res["compared"]["logit_gap_max"]["value"] > 0.1


def test_serve_sound_run_is_correct_and_control_is_not(tiny_tree, capsys):
    res, logs, _err = run_cell(capsys, "tiny-sat", seed=SEED,
                               hooks={"control": True})
    assert res["correct"] is True
    control = _readings(logs)["control_lowp"]
    assert control["correct"] is False
    assert control["over"] == ["logit_gap_max"]


def test_nothing_finished_is_not_correct(tiny_tree, capsys):
    """A run in which no request finished proves nothing."""
    def stall(engine):
        engine._stream = lambda rid, tok: None      # tokens never arrive

    res, _logs, _err = run_cell(capsys, "tiny-sat", seed=SEED, seconds=0.5,
                                hooks={"engine": stall})
    assert res["correct"] is False
    assert res["compared"]["unchecked"]["value"] == 1.0


def test_a_number_with_no_limit_or_not_finite_fails():
    from benchmark.lib import check
    with pytest.raises(KeyError):
        check.verdict({"new_number": 0.0}, {})
    rows, ok = check.verdict({"a": float("nan")}, {"a": 1.0})
    assert not ok and rows[0][3] is False

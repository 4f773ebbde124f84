"""``chip_smoke.py`` off the chip, and the one-process-per-chip rules it
relies on.

The smoke itself only means something on a TPU (it exits non-zero
anywhere else — pinned here); what CAN be checked on the CPU mesh is
that its phase functions run end to end at a tiny size, and that the
process rules hold: ``import paddle_tpu`` takes no device, DataLoader
workers are held to the CPU (the smoke's train phase checks its own
workers), launchers refuse to share a chip, the compile cache sits where
it is told, and an unknown chip has no peak.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

import chip_smoke
import paddle_tpu as paddle
from paddle_tpu import monitor

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code_or_path, env_extra, is_path=False):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", **env_extra)
    cmd = [sys.executable] + ([code_or_path] if is_path
                              else ["-c", code_or_path])
    return subprocess.run(cmd, capture_output=True, text=True, cwd=_ROOT,
                          env=env, timeout=300)


# ------------------------------------------------------- off the chip


def test_smoke_refuses_to_pass_without_a_tpu_and_import_is_device_free(
        monkeypatch):
    """``python chip_smoke.py`` on the CPU backend: the first line names
    the platform and says ``import paddle_tpu`` (plus placing the
    compile cache) initialised no backend; the exit code is non-zero and
    there is no result line. This process resolves the SAME in-checkout
    cache path as that one did."""
    proc = _run(os.path.join(_ROOT, "chip_smoke.py"), {}, is_path=True)
    assert proc.returncode == 4, (proc.stdout, proc.stderr[-2000:])
    lines = proc.stdout.strip().splitlines()
    first = json.loads(lines[0])
    assert first["platform"] == "cpu" and first["jax"] == jax.__version__
    assert first["import_took_a_device"] is False
    assert "platform is 'cpu'" in proc.stderr
    assert not any('"ok"' in ln for ln in lines)

    from paddle_tpu.utils import compile_cache
    placed = []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: placed.append((k, v)))
    want = os.path.join(_ROOT, ".jax_cache")
    assert compile_cache.configure() == want == first["compile_cache_dir"]
    assert placed == [("jax_compilation_cache_dir", want)]


def test_compile_cache_env_is_left_alone(monkeypatch, tmp_path):
    """Where JAX_COMPILATION_CACHE_DIR is set JAX reads it itself and no
    code sets another directory."""
    from paddle_tpu.utils import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(
        jax.config, "update",
        lambda *a: pytest.fail(f"jax.config.update{a} with the env set"))
    assert compile_cache.configure() == str(tmp_path)


# ------------------------------------------------- phases, tiny, CPU mesh


@pytest.fixture(scope="module")
def clock():
    return chip_smoke.CompileClock()


def test_kernel_phase_tiny(clock, capsys, monkeypatch):
    """The phase's loop over a few of its cases, interpreted (every case
    builder runs at production shape in ``test_tpu_lowering.py``, and
    every kernel has its own interpret-mode parity test)."""
    every = chip_smoke.kernel_cases
    keep = ("kernel.ragged_paged.int8", "kernel.norm_matmul.qkv_bias",
            "kernel.matmul_residual.swiglu_down", "kernel.scatter_gmm")
    monkeypatch.setattr(
        chip_smoke, "kernel_cases",
        lambda full: [c for c in every(full) if c[0] in keep])
    chip_smoke.kernel_phase(False, clock, on_chip=False)
    out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert {o["entry"] for o in out if "err" in o} == set(keep)
    assert all(o["err"] <= chip_smoke.KERNEL_TOL for o in out if "err" in o)
    # a mismatch fails the phase, after every case has run
    monkeypatch.setattr(chip_smoke, "KERNEL_TOL", -1.0)
    with pytest.raises(AssertionError, match="kernel phase failed for"):
        chip_smoke.kernel_phase(False, clock, on_chip=False)
    os.remove(os.path.join("chiprun_out", "chip_smoke_failures.log"))


def test_serve_phase_tiny(clock, capsys):
    chip_smoke.serve_phase(chip_smoke.SERVE_TINY, clock, on_chip=False)
    out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    engines = {o["engine"]: o for o in out if "engine" in o}
    assert set(engines) == {"bf16", "int8", "slot_state", "scan_state"}
    for tag, o in engines.items():
        # (slot state: the tick and the snapshot pair of a prefix hit)
        assert o["executables_compiled"] == (
            3 if tag in ("slot_state", "scan_state") else 1)
        assert o["prefix_tokens_reused"] > 0    # the mix hits the cache
        assert o["prefill_chunks"] > o["requests"]      # a multi-chunk one
    gaps = [o for o in out if "logit_gap_max" in o]
    assert set(gaps[0]["logit_gap_max"]) == {"bf16", "int8"}
    assert gaps[1]["model"] == "Lfm2MoeForCausalLM" \
        and set(gaps[1]["logit_gap_max"]) == {"bf16"}
    assert gaps[2]["model"] == "SolarOpen2ForCausalLM" \
        and gaps[2]["gqa_layers"] == [0]


def test_train_phase_tiny_feeds_from_worker_processes(clock, capsys,
                                                       monkeypatch):
    """Five steps fed by two spawned workers. Each worker runs the
    smoke's own ``worker_init_fn``: it fails the loader unless it started
    with JAX_PLATFORMS=cpu (whatever the trainer runs on — here the
    parent pretends to be on the chip) and imported the package without
    initialising a backend."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    chip_smoke.train_phase(chip_smoke.TRAIN_TINY, clock, on_chip=False)
    assert os.environ["JAX_PLATFORMS"] == "tpu,cpu"     # restored
    out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    res = [o for o in out if "losses" in o][0]
    assert len(res["losses"]) == 5 and res["losses"][-1] < res["losses"][0]
    assert res["train_step_compiles"] == 1
    assert res["train_step_fallback_recompiles"] == 0


def test_four_chip_phase_tiny(clock, capsys):
    if len(jax.devices()) < 4:
        pytest.skip("needs >= 4 devices")
    chip_smoke.four_chip_phase(chip_smoke.FOUR_CHIP_TINY, clock,
                               on_chip=False)
    out = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    what = [o.get("what") for o in out if o["phase"] == "four_chip"]
    assert "tp_degree=4 serving" in what
    assert "fleet hybrid train step" in what
    step = [o for o in out if "hybrid" in o][0]
    assert np.isfinite(step["loss"])


def test_expect_table_decides(capsys):
    """A compiled program that disagrees with the table fails, both
    ways; off the chip nothing is claimed."""
    ok = chip_smoke.check_expected(
        "serve.ragged_paged_attention", {"ragged_paged_attention": 8}, True)
    assert ok["checked"] and ok["expected"] == "mosaic"
    with pytest.raises(AssertionError, match="expected Mosaic kernel"):
        chip_smoke.check_expected("serve.fused_norm_matmul", {}, True)
    with pytest.raises(AssertionError, match="expected XLA"):
        chip_smoke.check_expected("tp.fused_decode",
                                  {"fused_norm_matmul": 2}, True)
    assert not chip_smoke.check_expected(
        "serve.fused_norm_matmul", {}, False)["checked"]


# ------------------------------------------------- one process per chip


def test_launchers_refuse_to_share_a_chip(monkeypatch):
    from paddle_tpu.distributed import launch, spawn
    share = launch.children_share_chip
    assert not share(1, {"JAX_PLATFORMS": "tpu"})      # one child: fine
    assert share(2, {"JAX_PLATFORMS": "tpu"})
    assert share(2, {"JAX_PLATFORMS": "tpu,cpu"})
    assert not share(2, {"JAX_PLATFORMS": "cpu"})      # CPU emulation
    # platform unpinned: decided by the host's TPU device nodes
    monkeypatch.setattr(launch.glob, "glob",
                        lambda pat: ["/dev/accel0"]
                        if pat == "/dev/accel*" else [])
    assert share(2, {})
    monkeypatch.setattr(launch.glob, "glob", lambda pat: [])
    assert not share(2, {})
    # the launcher and spawn act on it
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    with pytest.raises(SystemExit, match="one process"):
        launch.launch(["--nproc_per_node", "2", "train.py"])
    with pytest.raises(RuntimeError, match="one process"):
        spawn(print, nprocs=2)


def test_place_and_peak_table_do_not_guess():
    """An out-of-range accelerator id and an unknown chip are errors;
    the CPU backend has no peak at all."""
    with pytest.raises(ValueError, match="out of range"):
        paddle.TPUPlace(len(jax.devices())).jax_device()
    assert paddle.CPUPlace().jax_device().platform == "cpu"
    assert monitor.device_peaks() is None              # tier-1: CPU
    flops, bw, source = monitor.DEVICE_PEAKS["TPU v5 lite"]
    assert (flops, bw) == (197e12, 819e9) and "Google Cloud" in source


def test_unknown_device_kind_raises(monkeypatch):
    class Dev:
        platform, device_kind = "tpu", "TPU v9 imaginary"
    monkeypatch.setattr(jax, "devices", lambda *a: [Dev()])
    with pytest.raises(KeyError, match="TPU v9 imaginary"):
        monitor.device_peaks()
    Dev.device_kind = "TPU v5 lite"
    assert monitor.device_peaks() == (197e12, 819e9)

"""Elastic / failure detection (reference: ``fleet/elastic/manager.py``
watch loop + launch controller relaunch + checkpoint-resume —
SURVEY §5.3; tested with real subprocesses per the reference pattern)."""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                  ElasticStatus,
                                                  latest_checkpoint,
                                                  resume_or_start,
                                                  save_checkpoint)


def test_elastic_manager_heartbeat_and_death():
    mgr = ElasticManager(rank=0, world_size=2, is_master=True,
                         timeout=1.0)
    try:
        # rank 0 registers + beats; rank 1 (same store, simulated)
        mgr.register()
        peer = ElasticManager(rank=1, world_size=2, is_master=False,
                              port=mgr.port, timeout=1.0)
        peer.register()
        assert sorted(mgr.alive_ranks()) == [0, 1]
        assert mgr.watch() == ElasticStatus.COMPLETED
        # rank 1 stops beating -> declared dead after timeout
        time.sleep(1.2)
        mgr.heartbeat()
        assert mgr.alive_ranks() == [0]
        assert mgr.dead_ranks() == [1]
        peer.close()
    finally:
        mgr.close()


def test_elastic_np_range_hold_vs_restart():
    mgr = ElasticManager(rank=0, world_size=3, is_master=True,
                         np_range=(1, 3), timeout=5.0)
    try:
        mgr.register()
        # 1 of 3 alive, others pending (still starting) -> HOLD
        assert mgr.watch() == ElasticStatus.HOLD
        assert mgr.ready()
        # a DEAD rank (registered, stale beat) below np_min -> RESTART
        strict = ElasticManager(rank=2, world_size=3, is_master=False,
                                port=mgr.port, np_range=(3, 3),
                                timeout=0.3)
        time.sleep(0.5)  # rank 0's beat goes stale for `strict`
        polled = strict.poll()
        assert polled["dead"] == [0] and polled["pending"] == [1, 2]
        assert strict.watch() == ElasticStatus.RESTART
        assert not strict.ready()
        strict.close()
    finally:
        mgr.close()


def test_elastic_finished_ranks_not_dead():
    """A deregistered (cleanly exited) rank is 'finished', never
    triggering a restart of a completing job."""
    mgr = ElasticManager(rank=0, world_size=2, is_master=True,
                         timeout=0.5)
    try:
        mgr.register()
        peer = ElasticManager(rank=1, world_size=2, is_master=False,
                              port=mgr.port, timeout=0.5)
        peer.register()
        peer.deregister()   # clean exit
        time.sleep(0.7)     # peer's beat is stale now
        mgr.heartbeat()
        polled = mgr.poll()
        assert polled["alive"] == [0]
        assert polled["finished"] == [1]
        assert polled["dead"] == []
        assert mgr.watch() != ElasticStatus.RESTART
        peer.close()
    finally:
        mgr.close()


def test_checkpoint_resume_roundtrip(tmp_path):
    import paddle_tpu.nn as nn
    paddle.seed(0)
    model = nn.Linear(4, 4)
    state = model.state_dict()
    save_checkpoint(str(tmp_path), 10, state)
    save_checkpoint(str(tmp_path), 20, state)
    assert latest_checkpoint(str(tmp_path)).endswith("checkpoint-20")

    paddle.seed(1)
    model2 = nn.Linear(4, 4)  # different init
    state2 = model2.state_dict()
    step = resume_or_start(str(tmp_path), state2)
    assert step == 20
    np.testing.assert_allclose(model2.weight.numpy(),
                               model.weight.numpy())


def test_checkpoint_pruning(tmp_path):
    import paddle_tpu.nn as nn
    state = nn.Linear(2, 2).state_dict()
    for s in (1, 2, 3, 4, 5):
        save_checkpoint(str(tmp_path), s, state, keep_last=2)
    names = sorted(os.listdir(tmp_path))
    assert names == ["checkpoint-4", "checkpoint-5"]


def test_resume_reshards_to_current_mesh(tmp_path):
    """Save replicated, resume with the param sharded over a 4-way mesh
    (the restart-on-different-mesh story)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    import paddle_tpu.nn as nn
    paddle.seed(3)
    model = nn.Linear(8, 8)
    save_checkpoint(str(tmp_path), 7, model.state_dict())

    paddle.seed(4)
    model2 = nn.Linear(8, 8)
    mesh = Mesh(np.array(jax.devices()[:4]), ("sharding",))
    sharded = NamedSharding(mesh, P("sharding", None))
    model2.weight._data = jax.device_put(
        jnp.asarray(model2.weight.numpy()), sharded)
    step = resume_or_start(str(tmp_path), model2.state_dict())
    assert step == 7
    np.testing.assert_allclose(model2.weight.numpy(),
                               model.weight.numpy())
    assert model2.weight._data.sharding == sharded


def test_launch_elastic_restart(tmp_path):
    """Worker crashes on attempt 0, succeeds on attempt 1; the launch
    controller must relaunch and exit 0 (reference: controller watch
    loop + elastic relaunch)."""
    script = tmp_path / "worker.py"
    marker = tmp_path / "crashed_once"
    script.write_text(
        "import os, sys\n"
        f"m = {str(marker)!r}\n"
        "if not os.path.exists(m):\n"
        "    open(m, 'w').write('x')\n"
        "    sys.exit(3)\n"
        "print('recovered attempt', os.environ['PADDLE_RESTART_ATTEMPT'])\n"
    )
    log_dir = tmp_path / "logs"
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--max_restarts", "1",
         "--log_dir", str(log_dir), str(script)],
        cwd="/root/repo", capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr
    assert "elastic restart 1/1" in r.stderr
    assert (log_dir / "workerlog.1.1").exists()  # attempt-1 log


def test_launch_failure_exhausts_restarts(tmp_path):
    script = tmp_path / "bad.py"
    script.write_text("import sys; sys.exit(5)\n")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--max_restarts", "1",
         "--log_dir", str(tmp_path / "logs"), str(script)],
        cwd="/root/repo", capture_output=True, text=True, timeout=120)
    assert r.returncode == 5


def test_env_elastic_heartbeat_wiring(tmp_path):
    """PADDLE_ELASTIC_ENABLE=1 makes init_parallel_env register a
    heartbeating ElasticManager over the native store (multi-process,
    reference driver/worker pattern)."""
    script = tmp_path / "rank.py"
    script.write_text(
        "import os, time\n"
        "import paddle_tpu.distributed as dist\n"
        "from paddle_tpu.distributed import env as denv\n"
        "e = denv.init_parallel_env()\n"
        "mgr = getattr(e, 'elastic_manager', None)\n"
        "assert mgr is not None\n"
        "time.sleep(1.0)\n"
        "assert 0 in mgr.alive_ranks()\n"
        "print('HEARTBEAT-OK', mgr.alive_ranks())\n"
    )
    env = dict(os.environ)
    env.update({"PADDLE_ELASTIC_ENABLE": "1",
                "PADDLE_TRAINER_ID": "0",
                "PADDLE_TRAINERS_NUM": "2",
                "PADDLE_ELASTIC_PORT": "0",
                "PADDLE_ELASTIC_BEAT_S": "0.2",
                "JAX_PLATFORMS": "cpu",
                "PYTHONPATH": "/root/repo"})
    env.pop("PADDLE_MASTER", None)
    r = subprocess.run([sys.executable, str(script)], env=env,
                       capture_output=True, text=True, timeout=120)
    assert "HEARTBEAT-OK" in r.stdout, r.stderr


def test_launch_hang_detection_restarts(tmp_path):
    """A rank that hangs (stops heartbeating without exiting) must be
    detected by the controller's ElasticManager watch loop and the pod
    restarted (--elastic_level 1)."""
    script = tmp_path / "hang.py"
    marker = tmp_path / "hung_once"
    script.write_text(
        "import os, sys, time\n"
        "from paddle_tpu.distributed import env as denv\n"
        "e = denv.init_parallel_env()\n"
        f"m = {str(marker)!r}\n"
        "rank = int(os.environ['PADDLE_TRAINER_ID'])\n"
        "if rank == 1 and not os.path.exists(m):\n"
        "    open(m, 'w').write('x')\n"
        "    e.elastic_manager._stop_beat = True  # beats stop; hangs\n"
        "    time.sleep(600)\n"
        # healthy ranks outlive the 2s detection window so the
        # heartbeat watcher (not an exit code) fails the pod
        "time.sleep(8.0)\n"
        "print('DONE', rank)\n"
    )
    env = dict(os.environ)
    env.update({"JAX_PLATFORMS": "cpu",
                "PYTHONPATH": "/root/repo",
                "PADDLE_ELASTIC_BEAT_S": "0.2",
                # faulthandler stabilizes child signal handling when
                # spawned from a pytest(+jax) parent; without it the
                # worker's clean exit intermittently SIGSEGVs (exit-time
                # only — the controller still restarts via exit code,
                # but then this test's heartbeat-path assertion races)
                "PYTHONFAULTHANDLER": "1"})
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--max_restarts", "1",
         "--elastic_level", "1", "--elastic_timeout", "2",
         "--log_dir", str(tmp_path / "logs"), str(script)],
        env=env, capture_output=True, text=True, timeout=180)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "heartbeat lost" in r.stderr
    assert "elastic restart 1/1" in r.stderr


@pytest.mark.slow
def test_master_failover_snapshot_resume(tmp_path):
    """Kill rank-0 (the store master) with SIGKILL and relaunch it: the
    persisted store snapshot must restore the elastic state (worker
    registrations survive), and training resumes from the checkpoint
    (r3 verdict #9 — etcd-durability parity without etcd)."""
    snap = str(tmp_path / "store.snapshot")
    ckpt = str(tmp_path / "ckpt")
    script = tmp_path / "master.py"
    script.write_text(f"""
import sys, time
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.distributed.fleet.elastic import (ElasticManager,
                                                  save_checkpoint)
mgr = ElasticManager(rank=0, world_size=2, is_master=True,
                     snapshot_path={snap!r}, timeout=5.0)
mgr.register()
# job metadata a restarted master must recover
mgr._store.set("elastic/job/world_size", "2")
# train a step and checkpoint
paddle.seed(0)
w = paddle.to_tensor(np.full((4,), 7.25, np.float32))
save_checkpoint({ckpt!r}, step=3, state_dict={{"w": w}})
print("PORT", mgr.port, flush=True)
time.sleep(120)   # parent SIGKILLs us here
""")
    env = {k: v for k, v in os.environ.items()}
    env["PYTHONPATH"] = os.getcwd()
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen([sys.executable, str(script)], env=env,
                            stdout=subprocess.PIPE, text=True)
    try:
        line = proc.stdout.readline()
        assert line.startswith("PORT"), line
        # a worker registers against the live master
        worker = ElasticManager(rank=1, world_size=2, is_master=False,
                                port=int(line.split()[1]), timeout=5.0)
        worker.register()
        worker.close()
        time.sleep(0.3)          # let the snapshot land
        proc.kill()              # SIGKILL: no cleanup, no close()
        proc.wait()
    finally:
        if proc.poll() is None:
            proc.kill()

    # ---- relaunched master: same snapshot, fresh process state ----
    mgr2 = ElasticManager(rank=0, world_size=2, is_master=True,
                          snapshot_path=snap, timeout=1e9)
    try:
        polled = mgr2.poll()
        regs = sorted(polled["alive"] + polled["dead"])
        assert regs == [0, 1], (
            f"registrations lost across master restart: {polled}")
        assert mgr2._store.try_get("elastic/job/world_size") == b"2"
    finally:
        mgr2.close()

    # training resumes from the persisted checkpoint
    state = {"w": paddle.to_tensor(np.zeros((4,), np.float32))}
    step = resume_or_start(ckpt, state)
    assert step == 3
    np.testing.assert_allclose(state["w"].numpy(), np.full((4,), 7.25))

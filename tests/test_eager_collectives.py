"""Multi-process EAGER collectives (reference TestDistBase pattern —
``test/legacy_test/test_dist_base.py``: the driver spawns real worker
processes; collectives cross process boundaries, not shard_map axes).
Round-2 verdict item 6: eager facades must stop being identity in a
multi-process world."""
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np
import pytest


def _free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


def _spawn(world, mode, tmpdir):
    port = _free_port()
    endpoints = ",".join(f"127.0.0.1:{6170 + i}" for i in range(world))
    procs = []
    for rank in range(world):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": str(world),
            "PADDLE_TRAINER_ENDPOINTS": endpoints,
            "PADDLE_CURRENT_ENDPOINT": f"127.0.0.1:{6170 + rank}",
            "PADDLE_EAGER_STORE": f"127.0.0.1:{port}",
            "JAX_PLATFORMS": "cpu",
            "PYTHONFAULTHANDLER": "1",
            "PYTHONPATH": os.getcwd(),
        })
        env.pop("PADDLE_MASTER", None)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.join("tests", "dist_worker.py"),
             mode, str(tmpdir)],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT))
    outs = []
    deadline = time.time() + 240
    for p in procs:
        try:
            out, _ = p.communicate(timeout=max(deadline - time.time(), 5))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            raise
        outs.append(out.decode(errors="replace"))
    for p, out in zip(procs, outs):
        assert p.returncode == 0, f"worker failed:\n{out[-3000:]}"
    results = {}
    for rank in range(world):
        with open(os.path.join(str(tmpdir), f"rank{rank}.json")) as f:
            results[rank] = json.load(f)
    return results


@pytest.mark.parametrize("world", [2, 4])
def test_cross_process_collectives(world, tmp_path):
    res = _spawn(world, "collectives", tmp_path)
    expect_sum = [float(sum(range(1, world + 1)))] * 4
    for rank in range(world):
        r = res[rank]
        assert r["allreduce_sum"] == expect_sum
        assert r["allgather"] == [[float(i)] * 2 for i in range(world)]
        assert r["broadcast"] == [15.0]          # src rank 1: 1*10+5
        # reduce_scatter of (arange(world*2) + rank) summed over ranks
        base = np.arange(world * 2, dtype=np.float64)
        full = base * world + sum(range(world))
        chunk = full[rank * 2:(rank + 1) * 2]
        assert r["reduce_scatter"] == chunk.tolist()
        # alltoall: out[d] = chunk destined to me from rank d
        assert r["alltoall"] == [[d * 100.0 + rank]
                                 for d in range(world)]
    assert res[1]["recv"] == [123.0]


def test_dataparallel_loss_parity_vs_single_process(tmp_path):
    world = 2
    res = _spawn(world, "dp", tmp_path)
    # workers all-reduce their shard losses -> identical on every rank
    assert res[0]["losses"] == res[1]["losses"]

    # single-process reference on the FULL batch, same seed/model/lr
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    paddle.seed(42)
    net = nn.Sequential(nn.Linear(4, 8), nn.Tanh(), nn.Linear(8, 1))
    opt = paddle.optimizer.SGD(0.1, parameters=net.parameters())
    rng = np.random.RandomState(0)
    X = rng.randn(8, 4).astype(np.float32)
    Y = rng.randn(8, 1).astype(np.float32)
    ref = []
    for _ in range(4):
        out = net(paddle.to_tensor(X))
        loss = ((out - paddle.to_tensor(Y)) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        ref.append(float(loss.numpy()))
    np.testing.assert_allclose(res[0]["losses"], ref, rtol=1e-5,
                               atol=1e-6)

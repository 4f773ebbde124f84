"""Parameter-server mode (reference ``paddle/fluid/distributed/ps/``
async PS — tested with a real server subprocess + worker subprocesses
per the reference's TestDistBase pattern)."""
import os
import subprocess
import sys

import numpy as np
import pytest


def _free_port():
    import socket
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def test_sparse_dense_tables_local():
    """Server-side table semantics without any transport."""
    from paddle_tpu.distributed.ps import DenseTable, SparseTable
    t = SparseTable(4, lr=0.5)
    rows = t.pull([7, 3, 7])
    assert rows.shape == (3, 4)
    np.testing.assert_array_equal(rows[0], rows[2])   # same id, same row
    g = np.ones((2, 4), np.float32)
    before = t.pull([7, 3]).copy()
    t.push([7, 3], g)
    np.testing.assert_allclose(t.pull([7, 3]), before - 0.5,
                               rtol=1e-6)
    assert t.n_rows() == 2

    d = DenseTable([3, 2], lr=0.1)
    v0 = d.pull()
    d.push(np.ones((3, 2), np.float32))
    np.testing.assert_allclose(d.pull(), v0 - 0.1, rtol=1e-6)

    ada = SparseTable(2, optimizer="adagrad", lr=1.0)
    r0 = ada.pull([1]).copy()
    ada.push([1], np.full((1, 2), 2.0, np.float32))
    # adagrad first step: lr * g / sqrt(g^2) = lr * sign(g)
    np.testing.assert_allclose(ada.pull([1]), r0 - 1.0, rtol=1e-4)


@pytest.mark.slow
def test_ps_async_train_subprocesses(tmp_path):
    """1 PS server + 2 async workers train a toy CTR model (PS-hosted
    embedding + dense layer) — loss drops on both workers and the
    server tables were actually written."""
    repo_root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    port = _free_port()
    script = tmp_path / "node.py"
    script.write_text("""
import os
import numpy as np
rank = int(os.environ['PADDLE_TRAINER_ID'])
import paddle_tpu.distributed.rpc as rpc
from paddle_tpu.distributed.ps import (DistributedEmbedding, PSClient,
                                       run_server, stop_server)

if rank == 0:                        # the PS server
    run_server('ps0')
    rpc.shutdown(timeout=600)        # serves until the world drains
else:                                # async workers
    rpc.init_rpc(f'trainer{rank}')
    import paddle_tpu as paddle
    client = PSClient(['ps0'])
    emb = DistributedEmbedding(client, 'ctr_emb', dim=8, lr=0.5)
    client.create_dense_table('ctr_w', [8, 1], lr=0.5)

    # additive ground truth (representable by embedding-sum + linear):
    # each feature id carries a fixed latent score; the label is the
    # sign of the sum of the batch row's scores
    score = np.random.RandomState(0).randn(64).astype(np.float32)
    rng = np.random.RandomState(100 + rank)
    losses = []
    for step in range(30):
        ids = rng.randint(0, 64, (16, 4))
        labels = (score[ids].sum(1) > 0).astype(np.float32)
        e = emb(paddle.to_tensor(ids.astype(np.int64)))   # [16, 4, 8]
        w = paddle.to_tensor(client.pull_dense('ctr_w'))
        w.stop_gradient = False
        feat = e.sum(axis=1)                              # [16, 8]
        logit = paddle.matmul(feat, w)[:, 0]
        y = paddle.to_tensor(labels)
        loss = paddle.nn.functional.binary_cross_entropy_with_logits(
            logit, y)
        loss.backward()
        emb.push_grads()                                  # async push
        client.push_dense('ctr_w', w.grad.numpy())
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0], losses
    stat = client.stat('ctr_emb')
    assert stat['n_rows'] > 0
    print(f'PS-OK rank={rank} loss {losses[0]:.4f}->{losses[-1]:.4f} '
          f'rows={stat["n_rows"]}')
    rpc.shutdown()
""")
    procs = []
    for rank in range(3):
        env = dict(os.environ)
        env.update({"PADDLE_TRAINER_ID": str(rank),
                    "PADDLE_TRAINERS_NUM": "3",
                    "PADDLE_MASTER": f"127.0.0.1:{port}",
                    "JAX_PLATFORMS": "cpu",
                    "PYTHONPATH": repo_root})
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = [p.communicate(timeout=180)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    assert "PS-OK rank=1" in outs[1], outs[1]
    assert "PS-OK rank=2" in outs[2], outs[2]


@pytest.mark.slow
def test_fleet_ps_role_flow(tmp_path):
    """The reference's fleet PS user flow: PaddleCloudRoleMaker from
    env, fleet.run_server() on PSERVER nodes, fleet.init_worker() on
    trainers, DistributedEmbedding training through the ps_client."""
    repo_root = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    port = _free_port()
    script = tmp_path / "fleet_node.py"
    script.write_text("""
import os
import numpy as np
import paddle_tpu.distributed.fleet as fleet

rm = fleet.PaddleCloudRoleMaker()
fleet.init(role_maker=rm)
if fleet.is_server():
    fleet.run_server()
else:
    client = fleet.init_worker()
    import paddle_tpu as paddle
    from paddle_tpu.distributed.ps import DistributedEmbedding
    emb = DistributedEmbedding(client, 'emb', dim=4, lr=0.5)
    score = np.random.RandomState(0).randn(32).astype(np.float32)
    rng = np.random.RandomState(7)
    losses = []
    for _ in range(20):
        ids = rng.randint(0, 32, (8, 2))
        y = paddle.to_tensor((score[ids].sum(1) > 0)
                             .astype(np.float32))
        e = emb(paddle.to_tensor(ids.astype(np.int64)))
        logit = e.sum(axis=[1, 2])
        loss = paddle.nn.functional \\
            .binary_cross_entropy_with_logits(logit, y)
        loss.backward()
        emb.push_grads()
        losses.append(float(loss.numpy()))
    assert losses[-1] < losses[0], losses
    print(f'FLEET-PS-OK {losses[0]:.4f}->{losses[-1]:.4f}')
    fleet.stop_worker()
""")
    specs = [("PSERVER", {"PADDLE_PSERVER_ID": "0"}),
             ("TRAINER", {"PADDLE_TRAINER_ID": "0"})]
    procs = []
    for role, extra in specs:
        env = dict(os.environ)
        env.update({"TRAINING_ROLE": role,
                    "PADDLE_PSERVERS_IP_PORT_LIST": "127.0.0.1:0",
                    "PADDLE_TRAINERS_NUM": "1",
                    "PADDLE_MASTER": f"127.0.0.1:{port}",
                    "JAX_PLATFORMS": "cpu",
                    "PYTHONPATH": repo_root, **extra})
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True))
    outs = [p.communicate(timeout=180)[0] for p in procs]
    assert all(p.returncode == 0 for p in procs), outs
    assert "FLEET-PS-OK" in outs[1], outs[1]

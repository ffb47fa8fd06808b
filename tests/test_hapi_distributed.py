"""hapi.Model distributed wiring (VERDICT r3 weak #5): Model.prepare in
a launched 2-proc run auto-wraps with DataParallel + shards batches via
DistributedBatchSampler, and training matches the single-process run on
the same global data (reference: hapi/model.py:1054 DynamicGraphAdapter
init_parallel_env + paddle.DataParallel wiring)."""
import os
import socket
import subprocess
import sys
import textwrap

import numpy as np

WORKER = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    import paddle_tpu.distributed as dist
    from paddle_tpu.io import Dataset

    dist.init_parallel_env()
    rank = dist.get_rank()

    class Reg(Dataset):
        def __init__(self):
            rng = np.random.RandomState(0)
            self.x = rng.randn(32, 4).astype("float32")
            w = rng.randn(4, 1).astype("float32")
            self.y = self.x @ w

        def __getitem__(self, i):
            return self.x[i], self.y[i]

        def __len__(self):
            return 32

    paddle.seed(0)
    net = nn.Linear(4, 1)
    model = paddle.Model(net)
    model.prepare(optimizer=paddle.optimizer.SGD(
        0.1, parameters=net.parameters()), loss=F.mse_loss)
    # prepare must have auto-wrapped (world=2, env initialized)
    assert isinstance(model.network, dist.DataParallel), type(model.network)

    ds = Reg()
    model.fit(ds, batch_size=8, epochs=3, shuffle=False, verbose=0)

    w = np.asarray(net.weight._data).ravel()
    # ranks must agree bit-for-bit after synced training
    outs = []
    t = paddle.to_tensor(w.astype(np.float32))
    dist.all_gather(outs, t)
    np.testing.assert_allclose(outs[0].numpy(), outs[1].numpy(),
                               rtol=0, atol=0)
    np.save(os.environ["HAPI_OUT"] + f".{rank}.npy", w)
    print(f"RANK{rank}_OK")
""")

SINGLE = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.nn.functional as F
    from paddle_tpu.io import Dataset

    class Reg(Dataset):
        def __init__(self):
            rng = np.random.RandomState(0)
            self.x = rng.randn(32, 4).astype("float32")
            w = rng.randn(4, 1).astype("float32")
            self.y = self.x @ w

        def __getitem__(self, i):
            return self.x[i], self.y[i]

        def __len__(self):
            return 32

    paddle.seed(0)
    net = nn.Linear(4, 1)
    model = paddle.Model(net)
    model.prepare(optimizer=paddle.optimizer.SGD(
        0.1, parameters=net.parameters()), loss=F.mse_loss)
    # replicate the 2-rank global batches: DistributedBatchSampler
    # splits contiguously (rank0: samples 0-15, rank1: 16-31), so DP
    # global step k averages over rows [8k:8k+8] U [16+8k:16+8k+8]
    ds = Reg()
    batches = []
    for k in range(2):
        idx = list(range(8 * k, 8 * k + 8)) + \
            list(range(16 + 8 * k, 16 + 8 * k + 8))
        batches.append((ds.x[idx], ds.y[idx]))
    model.fit(batches * 3, epochs=1, verbose=0)  # 3 epochs of 2 steps
    np.save(os.environ["HAPI_OUT"] + ".single.npy",
            np.asarray(net.weight._data).ravel())
""")


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def test_hapi_fit_two_proc_parity(tmp_path):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    out_base = str(tmp_path / "w")

    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    port = _free_port()
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": "2",
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
            "HAPI_OUT": out_base,
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env, cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    for rank, p in enumerate(procs):
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"rank {rank} failed:\n{err[-3000:]}"
        assert f"RANK{rank}_OK" in out

    single = tmp_path / "single.py"
    single.write_text(SINGLE)
    env = dict(os.environ)
    env.update({"HAPI_OUT": out_base,
                "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", "")})
    r = subprocess.run([sys.executable, str(single)], env=env, cwd=repo,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr[-3000:]

    w_dp = np.load(out_base + ".0.npy")
    w_single = np.load(out_base + ".single.npy")
    # 2-rank DP with local batch 8 averages grads over the same global
    # 16-sample batch as the single run -> same trajectory (fp tolerance)
    np.testing.assert_allclose(w_dp, w_single, rtol=1e-4, atol=1e-5)

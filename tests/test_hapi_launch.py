"""hapi.Model.fit + launcher CLI (reference: hapi/model.py:1054,
distributed/launch/main.py:20)."""
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
import paddle_tpu.nn.functional as F
from paddle_tpu.io import Dataset


class XorDataset(Dataset):
    def __init__(self, n=128):
        w = np.random.RandomState(1).randn(8, 1).astype("float32")
        rng = np.random.RandomState(0)
        self.x = rng.randn(n, 8).astype("float32")
        self.y = (self.x @ w > 0).astype("int64")[:, 0]

    def __getitem__(self, i):
        return self.x[i], self.y[i]

    def __len__(self):
        return len(self.x)


class TestHapiModel:
    def _model(self):
        paddle.seed(0)
        net = nn.Sequential(nn.Linear(8, 32), nn.ReLU(), nn.Linear(32, 2))
        model = paddle.Model(net)
        model.prepare(
            optimizer=paddle.optimizer.Adam(
                0.01, parameters=net.parameters()),
            loss=F.cross_entropy,
            metrics=paddle.metric.Accuracy())
        return model

    def test_fit_trains_and_history(self):
        model = self._model()
        ds = XorDataset()
        hist = model.fit(ds, epochs=3, batch_size=32, verbose=0)
        assert "loss" in hist and len(hist["loss"]) == 3
        assert hist["loss"][-1] < hist["loss"][0]

    def test_fit_with_eval_and_metrics(self):
        model = self._model()
        ds = XorDataset()
        hist = model.fit(ds, eval_data=XorDataset(64), epochs=6,
                         batch_size=32, verbose=0)
        assert any(k.startswith("eval_") for k in hist)
        logs = model.evaluate(XorDataset(64), batch_size=32, verbose=0)
        assert "acc" in logs and logs["acc"] > 0.5

    def test_predict(self):
        model = self._model()
        out = model.predict(XorDataset(32), batch_size=16,
                            stack_outputs=True)
        assert out[0].shape == (32, 2)

    def test_save_load_roundtrip(self, tmp_path):
        model = self._model()
        ds = XorDataset(64)
        model.fit(ds, epochs=1, batch_size=32, verbose=0)
        path = str(tmp_path / "ckpt" / "model")
        model.save(path)
        assert os.path.exists(path + ".pdparams")
        assert os.path.exists(path + ".pdopt")

        model2 = self._model()
        model2.load(path)
        for p1, p2 in zip(model.parameters(), model2.parameters()):
            np.testing.assert_array_equal(np.asarray(p1._data),
                                          np.asarray(p2._data))

    def test_early_stopping_and_checkpoint(self, tmp_path):
        from paddle_tpu.hapi.callbacks import EarlyStopping

        model = self._model()
        ds = XorDataset()
        es = EarlyStopping(monitor="loss", patience=0, verbose=0,
                           save_best_model=False)
        hist = model.fit(ds, eval_data=XorDataset(64), epochs=20,
                         batch_size=32, verbose=0,
                         save_dir=str(tmp_path / "ck"), callbacks=[es])
        # checkpointing wrote epoch dirs + final
        assert os.path.exists(str(tmp_path / "ck" / "final.pdparams"))

    def test_summary(self, capsys):
        model = self._model()
        info = model.summary()
        assert info["total_params"] == 8 * 32 + 32 + 32 * 2 + 2

    def test_mnist_lenet_via_fit(self):
        """The BASELINE config-anchor #1 through the high-level API."""
        from paddle_tpu.vision.models import LeNet

        paddle.seed(1)
        net = LeNet(num_classes=10)
        model = paddle.Model(net)
        model.prepare(
            optimizer=paddle.optimizer.Adam(
                1e-3, parameters=net.parameters()),
            loss=F.cross_entropy, metrics=paddle.metric.Accuracy())

        class FakeMnist(Dataset):
            def __init__(self, n=64):
                rng = np.random.RandomState(0)
                self.x = rng.randn(n, 1, 28, 28).astype("float32")
                self.y = rng.randint(0, 10, (n,)).astype("int64")

            def __getitem__(self, i):
                return self.x[i], self.y[i]

            def __len__(self):
                return len(self.x)

        hist = model.fit(FakeMnist(), epochs=2, batch_size=16, verbose=0)
        assert hist["loss"][-1] < hist["loss"][0]


class TestLaunchCLI:
    def test_two_process_launch_smoke(self, tmp_path):
        """2-process CPU launch: PADDLE_* env contract + both ranks run
        (reference: launch/main.py:20 + collective.py:22)."""
        script = tmp_path / "worker.py"
        script.write_text(textwrap.dedent("""
            import os
            rank = int(os.environ["PADDLE_TRAINER_ID"])
            world = int(os.environ["PADDLE_TRAINERS_NUM"])
            eps = os.environ["PADDLE_TRAINER_ENDPOINTS"].split(",")
            assert world == 2 and len(eps) == 2
            assert os.environ["MASTER_ADDR"]
            print(f"worker {rank}/{world} ok", flush=True)
        """))
        logdir = str(tmp_path / "logs")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", "--log_dir", logdir, str(script)],
            cwd="/root/repo", env=env, capture_output=True, text=True,
            timeout=120)
        assert proc.returncode == 0, proc.stderr[-800:]
        logs = sorted(os.listdir(logdir))
        assert logs == ["workerlog.0", "workerlog.1"]
        body = "".join(open(os.path.join(logdir, f)).read() for f in logs)
        assert "worker 0/2 ok" in body and "worker 1/2 ok" in body

    def test_failure_propagates(self, tmp_path):
        script = tmp_path / "bad.py"
        script.write_text("import sys; sys.exit(3)")
        env = dict(os.environ)
        env["JAX_PLATFORMS"] = "cpu"
        proc = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "2", str(script)],
            cwd="/root/repo", env=env, capture_output=True, text=True,
            timeout=120)
        assert proc.returncode == 3


class TestElasticExitCode:
    def test_exit_101_triggers_relaunch_without_elastic_level(self, tmp_path):
        """Exit code 101 is the elastic-restart REQUEST (manager.py:32):
        the launcher relaunches even without --elastic_level."""
        script = tmp_path / "flaky.py"
        marker = tmp_path / "ran_once"
        script.write_text(
            "import os, sys\n"
            f"m = {str(marker)!r}\n"
            "if not os.path.exists(m):\n"
            "    open(m, 'w').write('1')\n"
            "    sys.exit(101)\n"  # first run requests elastic restart
            "print('SECOND_RUN_OK')\n")
        import subprocess
        import sys as _sys

        env = dict(os.environ)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
        proc = subprocess.run(
            [_sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc_per_node", "1", "--max_restarts", "2", str(script)],
            env=env, capture_output=True, text=True, timeout=120)
        assert proc.returncode == 0, proc.stderr[-1500:]
        assert "elastic restart requested" in proc.stderr

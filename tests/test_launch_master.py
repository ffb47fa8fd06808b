"""Built-in launch master (VERDICT r3 missing #4): two launcher
processes on localhost rendezvous through the KV master with NO
hand-wired per-node config beyond a shared --master address, heartbeat
each other, and survive one node restart via generation-scoped
re-rendezvous (reference: launch/controllers/master.py HTTPMaster/
ETCDMaster; utils/kv_server.py)."""
import os
import signal
import socket
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAIN = textwrap.dedent("""
    import os, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist

    gen = int(os.environ.get("PADDLE_RESTART_GENERATION", "0"))
    rank = int(os.environ["PADDLE_TRAINER_ID"])
    out = os.environ["LAUNCH_OUT"]

    dist.init_parallel_env()
    # prove the data plane works this generation
    t = paddle.to_tensor(np.full(2, rank + 1.0, np.float32))
    dist.all_reduce(t)
    assert t.numpy()[0] == 3.0, t.numpy()
    open(f"{out}/g{gen}.rank{rank}.start", "w").write("ok")

    if gen == 0:
        time.sleep(60)   # generation 0 lingers so the test can kill a node
    open(f"{out}/g{gen}.rank{rank}.done", "w").write("ok")
""")


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _wait_listening(master, proc, timeout):
    host, port = master.rsplit(":", 1)
    deadline = time.time() + timeout
    while time.time() < deadline:
        assert proc.poll() is None, proc.communicate()[1][-2000:]
        try:
            socket.create_connection((host, int(port)), 1.0).close()
            return
        except OSError:
            time.sleep(0.2)
    raise AssertionError(f"launcher never listened on {master}")


def _launcher(master, script, out_dir, extra=()):
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    env["LAUNCH_OUT"] = out_dir
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--nnodes", "2", "--nproc_per_node", "1",
           "--master", master, "--elastic_level", "1",
           "--max_restarts", "2", *extra, script]
    return subprocess.Popen(cmd, env=env, cwd=REPO,
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True,
                            start_new_session=True)


@pytest.mark.timeout(300)
def test_two_node_rendezvous_and_failover(tmp_path):
    script = tmp_path / "train.py"
    script.write_text(TRAIN)
    out = str(tmp_path)
    master = f"127.0.0.1:{_free_port()}"

    la = _launcher(master, str(script), out)
    # deterministic: A hosts the KV server (B gets killed). Wait until
    # A's server answers instead of a fixed 1.5 s — under a loaded
    # 6-worker run A's interpreter can take longer than that to start,
    # B would bind first, and killing B would take the store with it
    _wait_listening(master, la, timeout=90)
    lb = _launcher(master, str(script), out)

    # generation 0 rendezvoused: both ranks ran a real collective
    deadline = time.time() + 120
    want0 = [f"{out}/g0.rank0.start", f"{out}/g0.rank1.start"]
    while time.time() < deadline and not all(
            os.path.exists(p) for p in want0):
        assert la.poll() is None and lb.poll() is None, (
            la.communicate()[1][-2000:] if la.poll() is not None
            else lb.communicate()[1][-2000:])
        time.sleep(0.5)
    assert all(os.path.exists(p) for p in want0), \
        "generation-0 rendezvous did not complete"

    # kill node B's whole process group mid-run (launcher + worker)
    os.killpg(os.getpgid(lb.pid), signal.SIGKILL)
    lb.wait(timeout=30)

    # restart node B after the heartbeat TTL so the survivor has
    # already torn down and bumped the generation (room for a loaded
    # host: the TTL check itself runs on the survivor's clock)
    time.sleep(15)
    lb2 = _launcher(master, str(script), out)

    # both launchers must finish generation 1 cleanly
    rc_a = la.wait(timeout=150)
    rc_b = lb2.wait(timeout=150)
    err_a = la.communicate()[1]
    err_b = lb2.communicate()[1]
    assert rc_a == 0, err_a[-3000:]
    assert rc_b == 0, err_b[-3000:]
    for r in (0, 1):
        assert os.path.exists(f"{out}/g1.rank{r}.start"), \
            f"rank {r} never rendezvoused at generation 1\n{err_a[-1500:]}"
        assert os.path.exists(f"{out}/g1.rank{r}.done")
    # the survivor reported the failover
    assert "re-rendezvous at generation 1" in err_a


def test_single_node_unchanged(tmp_path):
    """nnodes=1 keeps the no-master fast path."""
    script = tmp_path / "ok.py"
    script.write_text("print('hi')\n")
    env = dict(os.environ)
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", str(script)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]

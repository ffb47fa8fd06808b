"""paddle.distributed.rpc over the coordination KV (reference:
python/paddle/distributed/rpc/rpc.py; C++ paddle/fluid/distributed/rpc).
Two localhost processes: sync/async calls both directions, remote
exception propagation, worker-info registry, shutdown."""
import os
import socket
import subprocess
import sys
import textwrap

WORKER = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed import rpc

    rank = int(os.environ["PADDLE_TRAINER_ID"])
    rpc.init_rpc(f"worker{rank}", rank=rank, world_size=2)

    infos = rpc.get_all_worker_infos()
    assert sorted(i.name for i in infos) == ["worker0", "worker1"], infos
    assert rpc.get_worker_info("worker1").rank == 1

    def add(a, b):
        return a + b

    def boom():
        raise ValueError("kaboom")

    peer = f"worker{1 - rank}"
    # sync both directions
    assert rpc.rpc_sync(peer, add, args=(2, 3)) == 5
    # async + numpy payload
    fut = rpc.rpc_async(peer, np.arange, args=(4,))
    np.testing.assert_array_equal(fut.wait(), np.arange(4))
    # remote exception propagates
    try:
        rpc.rpc_sync(peer, boom)
    except RuntimeError as e:
        assert "kaboom" in str(e)
    else:
        raise AssertionError("expected remote exception")
    # barrier before shutdown: a fast rank must not tear down its inbox
    # while the peer's last request is still in flight
    from jax._src import distributed as _dist
    _dist.global_state.client.wait_at_barrier("rpc_done_1", 60000)
    rpc.shutdown()
    print(f"RPC_RANK{rank}_OK")
""")


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def _run_cluster(script, port, repo):
    procs = []
    for rank in range(2):
        # strip stale distributed env from earlier tests in the session
        # (e.g. launch tests export PADDLE_TRAINER_ENDPOINTS)
        env = {k: v for k, v in os.environ.items()
               if not k.startswith(("PADDLE_", "MASTER_", "FLAGS_"))}
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": "2",
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env, cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    results = [p.communicate(timeout=600) for p in procs]
    return procs, results


def test_two_process_rpc(tmp_path):
    script = tmp_path / "rpc_worker.py"
    script.write_text(WORKER)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    last_err = ""
    for attempt in range(2):  # retry once: free-port races happen
        procs, results = _run_cluster(script, _free_port(), repo)
        if all(p.returncode == 0 for p in procs) and all(
                f"RPC_RANK{r}_OK" in out
                for r, (out, _) in enumerate(results)):
            return
        last_err = "\n".join(err[-1500:] for _, err in results)
    raise AssertionError(f"rpc cluster failed twice:\n{last_err}")


REINIT_WORKER = WORKER.replace(
    'print(f"RPC_RANK{rank}_OK")',
    '''# re-init after shutdown: the persisted inbox counter must not
# strand the fresh inbox thread (round-3 review fix)
rpc.init_rpc(f"worker{rank}", rank=rank, world_size=2)
assert rpc.rpc_sync(peer, add, args=(10, 20)) == 30
# rpc_async timeout is honored on the Future
fut = rpc.rpc_async(peer, add, args=(1, 1), timeout=30)
assert fut.wait() == 2
_dist.global_state.client.wait_at_barrier("rpc_done_2", 60000)
rpc.shutdown()
print(f"RPC_RANK{rank}_OK")''')


def test_rpc_reinit_after_shutdown(tmp_path):
    script = tmp_path / "rpc_reinit_worker.py"
    script.write_text(REINIT_WORKER)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    last_err = ""
    for attempt in range(2):
        procs, results = _run_cluster(script, _free_port(), repo)
        if all(p.returncode == 0 for p in procs) and all(
                f"RPC_RANK{r}_OK" in out
                for r, (out, _) in enumerate(results)):
            return
        last_err = "\n".join(err[-1500:] for _, err in results)
    raise AssertionError(f"rpc reinit cluster failed twice:\n{last_err}")

"""Subset-group collectives across 3 processes (member + non-member).

Exercises the store-brokered members-only paths: rank 2 is NOT in the
group and must no-op without corrupting the barrier (reference
semantics: non-members return untouched). Mirrors
test_collective_api_base.py with a sub-world group.
"""
import os
import socket
import subprocess
import sys
import textwrap

WORKER = textwrap.dedent("""
    import os
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=1").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.communication.collectives import (
        all_reduce, all_gather, broadcast, reduce_scatter)
    from paddle_tpu.distributed.communication.group import new_group

    dist.init_parallel_env()
    rank = jax.process_index()
    g = new_group([0, 1])  # rank 2 is NOT a member

    # all_reduce on the subset: members see the member sum; the
    # non-member's tensor is untouched
    t = paddle.to_tensor(np.full(3, rank + 1.0, np.float32))
    all_reduce(t, group=g)
    if rank in (0, 1):
        np.testing.assert_allclose(t.numpy(), np.full(3, 3.0))
    else:
        np.testing.assert_allclose(t.numpy(), np.full(3, rank + 1.0))

    # all_gather: members collect exactly the 2 member rows
    outs = []
    all_gather(outs, paddle.to_tensor(np.full(2, float(rank),
                                              np.float32)), group=g)
    if rank in (0, 1):
        got = np.stack([o.numpy() for o in outs])
        np.testing.assert_allclose(got, [[0, 0], [1, 1]])
    else:
        assert outs == []

    # broadcast with GLOBAL src rank 1 (permuted/subset convention)
    t = paddle.to_tensor(np.full(2, float(rank * 5), np.float32))
    broadcast(t, src=1, group=g)
    if rank in (0, 1):
        np.testing.assert_allclose(t.numpy(), [5.0, 5.0])
    else:
        np.testing.assert_allclose(t.numpy(), [10.0, 10.0])  # untouched

    # reduce_scatter on the subset: member r keeps member-sum of chunk r
    if rank in (0, 1):
        chunks = [paddle.to_tensor(np.full(2, rank * 10 + i, np.float32))
                  for i in range(2)]
        out = paddle.to_tensor(np.zeros(2, np.float32))
        reduce_scatter(out, chunks, group=g)
        gr = g.get_group_rank(rank)
        want = np.full(2, (0 * 10 + gr) + (1 * 10 + gr), np.float32)
        np.testing.assert_allclose(out.numpy(), want)
    print(f"RANK{rank}_OK")
""")


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


def test_three_process_subset_group(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    port = _free_port()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for rank in range(3):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": "3",
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    for rank, p in enumerate(procs):
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"rank {rank} failed:\n{err[-3000:]}"
        assert f"RANK{rank}_OK" in out

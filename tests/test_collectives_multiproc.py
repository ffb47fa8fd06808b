"""True multi-process collective tests on localhost.

Port of the reference's collective test harness (reference:
test/legacy_test/test_collective_api_base.py:113 — spawn per-rank
subprocesses with crafted PADDLE_* envs, compare collective results
against numpy semantics). Two CPU processes rendezvous through the JAX
coordinator (the TCPStore equivalent) and run the eager collective API;
the compiled data plane is exercised because both processes participate
in each jitted collective program.
"""
import os
import socket
import subprocess
import sys
import textwrap

import pytest

WORKER = textwrap.dedent("""
    import os, sys
    # force CPU before any jax import
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=1").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.communication.collectives import (
        all_reduce, all_gather, broadcast, reduce, reduce_scatter,
        all_to_all, send, recv, ReduceOp)

    dist.init_parallel_env()
    import jax
    rank = jax.process_index()
    world = jax.process_count()
    assert world == 2, world

    # all_reduce(SUM): ranks contribute [rank+1]*4
    t = paddle.to_tensor(np.full(4, rank + 1.0, np.float32))
    all_reduce(t)
    np.testing.assert_allclose(t.numpy(), np.full(4, 3.0))

    # all_reduce(MAX)
    t = paddle.to_tensor(np.full(3, float(rank), np.float32))
    all_reduce(t, op=ReduceOp.MAX)
    np.testing.assert_allclose(t.numpy(), np.full(3, 1.0))

    # all_gather
    outs = []
    t = paddle.to_tensor(np.full(2, float(rank), np.float32))
    all_gather(outs, t)
    got = np.stack([o.numpy() for o in outs])
    np.testing.assert_allclose(got, [[0, 0], [1, 1]])

    # broadcast from rank 1
    t = paddle.to_tensor(np.full(2, float(rank * 7), np.float32))
    broadcast(t, src=1)
    np.testing.assert_allclose(t.numpy(), [7.0, 7.0])

    # reduce to dst=0: only rank 0 sees the sum
    t = paddle.to_tensor(np.full(2, rank + 1.0, np.float32))
    reduce(t, dst=0)
    want = [3.0, 3.0] if rank == 0 else [rank + 1.0] * 2
    np.testing.assert_allclose(t.numpy(), want)

    # reduce_scatter: rank r keeps sum of everyone's r-th chunk
    chunks = [paddle.to_tensor(np.full(2, rank * 10 + i, np.float32))
              for i in range(2)]
    out = paddle.to_tensor(np.zeros(2, np.float32))
    reduce_scatter(out, chunks)
    # rank0 chunk0 + rank1 chunk0 = 0 + 10 ; rank: r -> 2r+10... compute:
    want = np.full(2, (0 * 10 + rank) + (1 * 10 + rank), np.float32)
    np.testing.assert_allclose(out.numpy(), want)

    # all_to_all
    ins = [paddle.to_tensor(np.full(2, rank * 2 + j, np.float32))
           for j in range(2)]
    outs = []
    all_to_all(outs, ins)
    got = np.stack([o.numpy() for o in outs])
    want = np.stack([np.full(2, p * 2 + rank, np.float32)
                     for p in range(2)])
    np.testing.assert_allclose(got, want)

    # flag-gated cross-rank dynamic check (nccl_dynamic_check parity):
    # matching metadata passes and the collective still reduces right
    paddle.set_flags({"check_collective": True})
    t = paddle.to_tensor(np.full(2, rank + 1.0, np.float32))
    all_reduce(t)
    np.testing.assert_allclose(t.numpy(), np.full(2, 3.0))
    paddle.set_flags({"check_collective": False})

    # uneven all_to_all_single (reference: communication/all_to_all.py
    # alltoall_single with in/out_split_sizes): rank0 sends [1,3] rows,
    # rank1 sends [2,2] rows -> rank0 receives [1,2], rank1 [3,2]
    from paddle_tpu.distributed.communication.collectives import (
        all_to_all_single, gather)
    in_sp = [[1, 3], [2, 2]][rank]
    out_sp = [[1, 2], [3, 2]][rank]
    data = np.arange(sum(in_sp) * 2, dtype=np.float32).reshape(-1, 2) \
        + 100 * rank
    out = paddle.to_tensor(np.zeros((sum(out_sp), 2), np.float32))
    all_to_all_single(out, paddle.to_tensor(data),
                      out_split_sizes=out_sp, in_split_sizes=in_sp)
    # expected: my inbox = [rank0's piece for me; rank1's piece for me]
    r0 = np.arange(8, dtype=np.float32).reshape(4, 2)
    r1 = np.arange(8, dtype=np.float32).reshape(4, 2) + 100
    if rank == 0:
        want = np.concatenate([r0[:1], r1[:2]])
    else:
        want = np.concatenate([r0[1:], r1[2:]])
    np.testing.assert_allclose(out.numpy(), want)

    # bad split sizes must raise, not silently even-split
    try:
        all_to_all_single(out, paddle.to_tensor(data),
                          in_split_sizes=[1, 1, 1])
    except ValueError:
        pass
    else:
        raise AssertionError("expected ValueError for bad split count")

    # gather honors dst: only rank 1 receives
    gl = []
    gather(paddle.to_tensor(np.full(2, rank + 5.0, np.float32)),
           gl, dst=1)
    if rank == 1:
        got = np.stack([t.numpy() for t in gl])
        np.testing.assert_allclose(got, [[5, 5], [6, 6]])
    else:
        assert gl == [], "gather filled gather_list on a non-dst rank"

    # cross-process send/recv through the coordination-service store
    if rank == 0:
        send(paddle.to_tensor(np.arange(6, dtype=np.float32)), dst=1)
        send(paddle.to_tensor(np.full(3, 9.0, np.float32)), dst=1)
    else:
        buf = paddle.to_tensor(np.zeros(6, np.float32))
        recv(buf, src=0)
        np.testing.assert_allclose(buf.numpy(), np.arange(6))
        buf2 = paddle.to_tensor(np.zeros(3, np.float32))
        recv(buf2, src=0)
        np.testing.assert_allclose(buf2.numpy(), np.full(3, 9.0))

    # fleet observability: every rank runs one profiled collective and
    # dumps its trace + stats snapshot into the SHARED run dir; the
    # parent test merges them with tools/trace_merge.py
    from paddle_tpu.profiler import Profiler, dump_rank
    with Profiler(on_trace_ready=lambda p: None) as prof:
        t = paddle.to_tensor(np.full(4, rank + 1.0, np.float32))
        all_reduce(t)
        prof.step()
    written = dump_rank(os.environ["PADDLE_RUN_DIR"], profiler=prof)
    assert written["stats"].endswith(f"stats_rank{rank}.json")

    print(f"RANK{rank}_OK")
""")


def _free_port():
    with socket.socket() as s:
        s.bind(("", 0))
        return s.getsockname()[1]


# Fleet-observability worker: initializes the 2-process distributed
# context (the coordinator rendezvous works on CPU; only COMPILED
# cross-process collectives don't — see the note in the main worker),
# runs rank-local profiled work, and dumps this rank's trace + stats
# snapshot into the shared run dir for tools/trace_merge.py.
FLEET_WORKER = textwrap.dedent("""
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=1").strip()
    import jax
    jax.config.update("jax_platforms", "cpu")

    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu.profiler import Profiler, dump_rank, stats

    dist.init_parallel_env()
    rank = jax.process_index()
    assert jax.process_count() == 2

    with Profiler(on_trace_ready=lambda p: None) as prof:
        a = paddle.to_tensor(np.ones((8, 8), np.float32))
        for _ in range(rank + 1):    # rank1 does MORE matmuls than rank0
            _ = a @ a
        prof.step()
    written = dump_rank(os.environ["PADDLE_RUN_DIR"], profiler=prof)
    assert written["stats"].endswith(f"stats_rank{rank}.json")
    assert written["trace"].endswith(f"trace_rank{rank}.json")
    print(f"RANK{rank}_OK")
""")


def test_two_process_fleet_dump_and_merge(tmp_path):
    """≥2-rank multiproc run → per-rank dumps → ONE merged chrome trace
    (pid = rank) + ONE fleet stats snapshot (counters summed, gauges
    maxed). Rank-local work only: compiled cross-process collectives
    are unimplemented on the CPU backend, but the coordinator
    rendezvous — and therefore real distinct process_index stamps —
    works, which is exactly what the aggregation layer needs."""
    import json

    script = tmp_path / "fleet_worker.py"
    script.write_text(FLEET_WORKER)
    run_dir = tmp_path / "run"
    port = _free_port()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    procs = []
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": "2",
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
            "PADDLE_RUN_DIR": str(run_dir),
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env, cwd=repo,
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    for rank, p in enumerate(procs):
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"rank {rank} failed:\n{err[-3000:]}"
        assert f"RANK{rank}_OK" in out

    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        import trace_merge
    finally:
        sys.path.pop(0)
    assert trace_merge.main([str(run_dir)]) == 0

    merged = json.load(open(run_dir / "merged_trace.json"))
    assert merged["metadata"]["ranks"] == [0, 1]
    # one timeline: every event re-pid'd to its rank, both ranks named
    assert {e["pid"] for e in merged["traceEvents"]} == {0, 1}
    spans = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    assert {e["pid"] for e in spans} == {0, 1}
    names = {e["args"]["name"] for e in merged["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert any("rank 0" in n for n in names)
    assert any("rank 1" in n for n in names)

    fleet = json.load(open(run_dir / "fleet_stats.json"))
    per_rank = [json.load(open(run_dir / f"stats_rank{r}.json"))
                for r in (0, 1)]
    # rank stamps are REAL process indices, not env echoes
    assert sorted(s["meta"]["process_index"] for s in per_rank) == [0, 1]
    # counters summed: rank0 ran 1 matmul, rank1 ran 2 -> fleet 3
    assert fleet["counters"]["op.matmul"] == sum(
        s["counters"]["op.matmul"] for s in per_rank) == 3
    # gauges maxed: the fleet view keeps the high-water rank coords
    assert fleet["gauges"]["dist.process_index"] == 1
    assert fleet["gauges"]["dist.process_count"] == 2


def _cpu_jaxlib() -> bool:
    import jax

    try:
        return jax.default_backend() == "cpu"
    except Exception:
        return True


@pytest.mark.skipif(
    _cpu_jaxlib(),
    reason="compiled cross-process collectives are unimplemented on CPU "
           "jaxlib (the multi-process CPU runtime has no data-plane "
           "transport for jitted psum/all_gather programs — workers die "
           "in the first compiled collective); run on a real multi-host "
           "TPU slice. The eager/store-based collective paths are "
           "covered by test_subset_group_multiproc and "
           "test_two_process_fleet_dump_and_merge.")
def test_two_process_collectives(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER)
    run_dir = tmp_path / "run"
    port = _free_port()
    procs = []
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for rank in range(2):
        env = dict(os.environ)
        env.update({
            "PADDLE_TRAINER_ID": str(rank),
            "PADDLE_TRAINERS_NUM": "2",
            "MASTER_ADDR": "127.0.0.1",
            "MASTER_PORT": str(port),
            "PADDLE_RUN_DIR": str(run_dir),
            "PYTHONPATH": repo + os.pathsep + env.get("PYTHONPATH", ""),
        })
        procs.append(subprocess.Popen(
            [sys.executable, str(script)], env=env,
            cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True))
    for rank, p in enumerate(procs):
        out, err = p.communicate(timeout=300)
        assert p.returncode == 0, f"rank {rank} failed:\n{err[-3000:]}"
        assert f"RANK{rank}_OK" in out

    # ---- fleet aggregation over the real 2-rank artifacts ----
    import json

    sys.path.insert(0, os.path.join(repo, "tools"))
    try:
        import trace_merge
    finally:
        sys.path.pop(0)
    rc = trace_merge.main([str(run_dir)])
    assert rc == 0
    merged = json.load(open(run_dir / "merged_trace.json"))
    # one timeline, pid = rank, both ranks present and named
    pids = {e["pid"] for e in merged["traceEvents"]}
    assert pids == {0, 1}
    names = {e["args"]["name"] for e in merged["traceEvents"]
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert any("rank 0" in n for n in names)
    assert any("rank 1" in n for n in names)
    fleet = json.load(open(run_dir / "fleet_stats.json"))
    assert sorted(fleet["meta"]["ranks"]) == [0, 1]
    # counters summed across ranks: the fleet total is the SUM of the
    # per-rank counts (each rank ran the same >= 4 all_reduces), not
    # either rank's own count
    per_rank = [json.load(open(run_dir / f"stats_rank{r}.json"))
                for r in (0, 1)]
    want = sum(s["counters"]["dist.all_reduce.calls"] for s in per_rank)
    assert want >= 8
    assert fleet["counters"]["dist.all_reduce.calls"] == want
    # gauges maxed: the fleet view shows the highest rank index/world
    assert fleet["gauges"]["dist.process_index"] == 1
    assert fleet["gauges"]["dist.process_count"] == 2

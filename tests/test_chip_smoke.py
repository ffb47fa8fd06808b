"""chip_smoke.py / bench.py off the chip: they refuse, and the smoke's
control flow can be rehearsed at toy widths without ever claiming a
result (only a run that saw a TPU prints ``"ok": true``)."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script, *argv, devices=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    if devices:
        env["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={devices}")
    else:
        env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(REPO, script), *argv],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=900)


@pytest.mark.parametrize("script,argv", [
    ("chip_smoke.py", ()),
    ("chip_smoke.py", ("--chips", "4")),
    ("bench.py", ("--no-lint",)),
    ("bench.py", ("--serve",)),
])
def test_refuses_without_a_chip(script, argv):
    proc = _run(script, *argv)
    assert proc.returncode != 0
    assert proc.stdout.strip() == "", proc.stdout   # no result, no metric
    assert "needs" in proc.stderr and "TPU" in proc.stderr


@pytest.mark.parametrize("chips,phases", [
    (1, ["serve", "train"]),
    (4, ["serve_tp4"]),
])
def test_rehearsal_runs_every_phase_and_never_says_ok(chips, phases):
    argv = ("--rehearse",) + (("--chips", "4") if chips == 4 else ())
    proc = _run("chip_smoke.py", *argv, devices=chips)
    assert proc.returncode == 0, proc.stderr[-3000:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    assert [ln["phase"] for ln in lines[:-1]] == phases
    assert all(ln["ok"] is True for ln in lines[:-1])
    last = lines[-1]
    assert last["ok"] is False and last["rehearsal"] == "passed"
    assert last["device"]["platform"] == "cpu"
    assert last["device"]["count"] == chips
    assert '"ok": true' not in proc.stdout.splitlines()[-1]


def test_bench_parent_survives_a_rung_that_times_out(capsys):
    """A rung child that outlives its limit is killed and recorded as
    that rung's error — the parent goes on to the next rung, and never
    imports JAX itself."""
    sys.path.insert(0, REPO)
    try:
        import bench
    finally:
        sys.path.remove(REPO)
    out, err = bench._sub(["--probe"], 0.01)
    assert out is None and err == "timeout after 0.01s"
    assert "--probe FAILED" in capsys.readouterr().err

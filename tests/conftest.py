"""Test harness: run everything on a virtual 8-device CPU mesh.

Port of the reference's "distributed tests without a real cluster" trick
(reference: test/legacy_test/test_dist_base.py:962 — localhost multi-proc;
and the fake-device precedent paddle/phi/backends/custom/fake_cpu_device.h):
here a single process gets 8 virtual XLA host devices, which exercises the
full sharding/collective path without TPU hardware.

Must run before jax initializes a backend (the platform is also set
through jax.config, so an outer JAX_PLATFORMS cannot redirect the tests).

The persistent compile cache (paddle_tpu.device.setup_compile_cache) is
switched off for the tests and, through the environment, for every
child they start: a test run must not depend on what an earlier run
left in ``.jax_cache``, nor fill the checkout with it (and XLA:CPU
reloads its cached executables with a screen of machine-feature errors).

Shared mesh fixtures (session-scoped — the mesh objects are immutable
value types): ``virtual_devices`` (the 8 CPU devices), ``mesh8`` /
``mesh2x4`` (plain ProcessMeshes) and ``fleet_mesh`` (the dp4 x mp2
hybrid mesh via fleet.init — the setup test_distributed/test_moe_ep and
the SPMD-pass tests all need).
"""
import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402


def pytest_configure(config):
    # registered markers (no pytest.ini in this repo): ``slow`` is
    # excluded from tier-1 (`-m 'not slow'`); ``chaos`` tags the
    # deterministic fault-injection serving tests
    # (tests/test_serving_faults.py) — tier-1 RUNS them (they are not
    # slow), the marker exists so a chip run can select them alone
    # (`-m chaos`) before trusting a serving deploy
    config.addinivalue_line(
        "markers", "slow: long-running composition smoke, excluded "
                   "from tier-1 (-m 'not slow')")
    config.addinivalue_line(
        "markers", "chaos: deterministic fault-injection serving "
                   "tests (ISSUE 11) — in tier-1, selectable alone "
                   "via -m chaos")


@pytest.fixture(autouse=True)
def _reseed():
    import paddle_tpu as paddle

    paddle.seed(2024)
    yield


@pytest.fixture(scope="session")
def virtual_devices():
    """The 8 virtual CPU devices (the SPMD-pass mesh substrate)."""
    devs = jax.devices("cpu")
    assert len(devs) >= 8, "xla_force_host_platform_device_count not set"
    return devs[:8]


@pytest.fixture(scope="session")
def mesh8(virtual_devices):
    import paddle_tpu.distributed as dist

    return dist.ProcessMesh(list(range(8)), dim_names=["x"])


@pytest.fixture(scope="session")
def mesh2x4(virtual_devices):
    import paddle_tpu.distributed as dist

    return dist.ProcessMesh([[0, 1, 2, 3], [4, 5, 6, 7]],
                            dim_names=["dp", "mp"])


@pytest.fixture(scope="session")
def fleet_mesh(virtual_devices):
    """The dp4 x mp2 hybrid mesh, fleet-initialized once per session
    (drops the per-test fleet.init boilerplate the distributed/MoE
    tests used to carry)."""
    from paddle_tpu.distributed import fleet

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {
        **strategy.hybrid_configs,
        "dp_degree": 4, "mp_degree": 2, "pp_degree": 1,
        "sharding_degree": 1, "sep_degree": 1,
    }
    fleet.init(is_collective=True, strategy=strategy)
    return fleet.get_hybrid_communicate_group().mesh

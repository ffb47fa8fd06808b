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

The run is held to a wall-clock limit, so two things here only make it
shorter and change no result: CPU programs compile at LLVM's lowest
optimisation level (about a quarter less CPU over the suite), and under
``--dist loadfile`` the longest files are handed out first.

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


def _cpu_compiles_without_llvm_optimisation():
    """Compile for the CPU at LLVM's lowest level: the tests' programs are
    small and run a few times each, so the CPU spends most of the run
    compiling them. XLA's HLO passes run as before; compiles for a
    described TPU (tests/test_chip_compile.py) keep their options."""
    from jax._src import compiler

    compile_and_load = compiler.backend_compile_and_load

    def backend_compile_and_load(backend, module, executable_devices,
                                 options, host_callbacks):
        if backend.platform == "cpu":
            options.executable_build_options.debug_options \
                .xla_backend_optimization_level = 0
        return compile_and_load(backend, module, executable_devices,
                                options, host_callbacks)

    compiler.backend_compile_and_load = backend_compile_and_load


_cpu_compiles_without_llvm_optimisation()


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


#: Test files that take the longest on CPU (each over 60 s of test time
#: with six xdist workers), longest first. ``--dist loadfile`` runs a file
#: on one worker, and xdist queues files by test count, so a long file
#: with few tests would start last and run on alone long after the other
#: workers have finished; handing these out first lets the short files
#: fill in around them.
HEAVY_TEST_FILES = (
    "tests/test_vision_models.py",
    "tests/test_pipeline.py",
    "tests/benchmark/test_granite_reference.py",
    "tests/test_chip_compile.py",
    "tests/test_serving_frontend.py",
    "tests/test_paged_backends.py",
    "tests/benchmark/test_mimo_rehearsal.py",
    "tests/test_hybrid_serving.py",
    "tests/benchmark/test_mimo_reference.py",
    "tests/benchmark/test_harness_rehearsal.py",
    "tests/benchmark/test_train_reference.py",
    "tests/test_serving_continuous.py",
    "tests/benchmark/test_granite_rehearsal.py",
    "tests/test_lora_serving.py",
    "tests/test_window_serving.py",
    "tests/test_tpu_lint.py",
    "tests/test_bert.py",
    "tests/benchmark/test_mistral4_rehearsal.py",
    "tests/test_paged_kv_write.py",
    "tests/test_serving_faults.py",
    "tests/test_flash_varlen.py",
    "tests/test_speculative.py",
    "tests/test_latent_serving.py",
    "tests/benchmark/test_mistral4_reference.py",
    "tests/test_usage_metering.py",
    "tests/test_deferred_prefill.py",
    "tests/test_fleet_router.py",
    "tests/test_jit.py",
    "tests/test_window_attention.py",
    "tests/test_chip_smoke.py",
    "tests/test_nn.py",
    "tests/test_mla_attention.py",
    "tests/test_moe_nodrop.py",
    "tests/benchmark/test_span_readers.py",
    "tests/test_hybrid_pp_mp.py",
    "tests/test_parallelism.py",
)


@pytest.hookimpl(optionalhook=True, tryfirst=True)
def pytest_xdist_make_scheduler(config, log):
    """Under ``--dist loadfile``, hand out ``HEAVY_TEST_FILES`` before the
    rest; every other file keeps xdist's own order."""
    if config.getvalue("dist") != "loadfile":
        return None
    from collections import OrderedDict

    from xdist.scheduler import LoadFileScheduling

    rank = {f: i for i, f in enumerate(HEAVY_TEST_FILES)}

    class HeavyFirst(LoadFileScheduling):
        def _assign_work_unit(self, node):
            if not getattr(self, "_heavy_first", False):
                self._heavy_first = True
                order = sorted(self.workqueue,
                               key=lambda f: rank.get(f, len(rank)))
                self.workqueue = OrderedDict(
                    (f, self.workqueue[f]) for f in order)
            super()._assign_work_unit(node)

    return HeavyFirst(config, log)


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

"""Persistent XLA compilation cache wiring + cold-vs-warm compile
telemetry.

- ``device.setup_compile_cache()``: the cache directory is placed from
  outside. Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses
  it and the repo sets no directory in code; where it is not, one fixed
  path inside the checkout (``.jax_cache``, git-ignored) is used. The
  ``compile.persistent_cache`` gauge records the regime.
- ``TrainStep`` records its first call's wall seconds (trace + XLA
  compile + run) in the ``compile.train_step_first_call_s`` histogram,
  which bench.py embeds in its telemetry block.
"""
import os

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.profiler import stats

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def cache_config():
    """Restore the cache directory the session runs with."""
    prev = jax.config.jax_compilation_cache_dir
    on = jax.config.jax_enable_compilation_cache
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    jax.config.update("jax_enable_compilation_cache", on)


class TestCompileCachePlacement:
    def test_env_dir_is_left_to_jax(self, monkeypatch, tmp_path,
                                    cache_config):
        """With the standard variable set, no directory is set in
        code: whatever JAX's config holds is left as it is."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update("jax_compilation_cache_dir", "sentinel")
        jax.config.update("jax_enable_compilation_cache", True)
        assert paddle.device.setup_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == "sentinel"
        assert stats.gauge("compile.persistent_cache").value == 1

    def test_default_is_one_fixed_path_in_the_checkout(
            self, monkeypatch, cache_config):
        """Nothing given from outside: one fixed path inside the
        checkout, whatever the platform (this run is pinned to the
        CPU) — the path is part of the cache key."""
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        for _ in range(2):
            jax.config.update("jax_compilation_cache_dir", "sentinel")
            assert paddle.device.setup_compile_cache() == want
            assert jax.config.jax_compilation_cache_dir == want
        assert paddle.device.DEFAULT_COMPILE_CACHE_DIR == want

    def test_cpu_runs_switch_the_cache_off_where_they_are_set_up(
            self, cache_config):
        """Not a branch of setup_compile_cache: the tests (and, through
        the environment, every child they start) run with JAX's own
        switch off — tests/conftest.py."""
        assert jax.config.jax_enable_compilation_cache is False
        assert os.environ["JAX_ENABLE_COMPILATION_CACHE"] == "false"
        # ... and the gauge says so: this run's compiles are all cold
        paddle.device.setup_compile_cache()
        assert stats.gauge("compile.persistent_cache").value == 0

    def test_every_program_is_cached(self, monkeypatch, tmp_path,
                                     cache_config):
        """The serving programs compile in seconds each but are many:
        the minimum compile time for a cache entry is zero."""
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs", 1.0)
        paddle.device.setup_compile_cache()
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0

    def test_cache_dir_is_ignored_and_no_private_flag_left(self):
        with open(os.path.join(REPO, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
        with pytest.raises(ValueError):
            paddle.get_flags("compile_cache_dir")


class TestTrainStepCompileSeconds:
    def test_first_call_observed_once(self):
        import paddle_tpu.nn as nn

        paddle.seed(0)
        model = nn.Linear(8, 4)
        opt = paddle.optimizer.SGD(0.1, parameters=model.parameters())
        step = paddle.jit.TrainStep(
            model, lambda o, y: ((o - y) ** 2).mean(), opt)
        h = stats.histogram("compile.train_step_first_call_s")
        before = h.count
        x = paddle.to_tensor(np.ones((2, 8), np.float32))
        y = paddle.to_tensor(np.zeros((2, 4), np.float32))
        step([x], [y])
        assert h.count == before + 1
        assert step.first_call_seconds > 0
        first = step.first_call_seconds
        step([x], [y])  # warm call: no second observation
        assert h.count == before + 1
        assert step.first_call_seconds == first

"""paddle_tpu.device — device control + memory observability.

TPU-native equivalent of the reference's device API (reference:
python/paddle/device — set_device/get_device/synchronize — and the memory
stats surface paddle/fluid/memory/stats.h + paddle.device.cuda.
max_memory_allocated). PJRT owns device memory on TPU; the stats facade
reads the runtime's per-device counters instead of keeping its own
allocator bookkeeping.
"""
from __future__ import annotations

import os

import jax

from ..core.place import (  # noqa: F401
    CPUPlace, Place, TPUPlace, current_place, device_count, get_device,
    is_compiled_with_tpu, set_device,
)
from . import vmem  # noqa: F401  (per-generation VMEM budget table)
from .vmem import (  # noqa: F401
    KERNEL_VMEM_LIMIT_BYTES, VMEM_BUDGET_BYTES, vmem_budget_bytes,
)

__all__ = [
    "set_device", "get_device", "device_count", "current_place",
    "synchronize", "memory_stats", "memory_allocated",
    "max_memory_allocated", "memory_reserved", "max_memory_reserved",
    "reset_peak_memory_stats", "empty_cache", "setup_compile_cache",
    "Place", "CPUPlace", "TPUPlace", "is_compiled_with_tpu",
    "is_compiled_with_cuda", "is_compiled_with_xpu", "cuda", "tpu",
    "vmem", "VMEM_BUDGET_BYTES", "KERNEL_VMEM_LIMIT_BYTES",
    "vmem_budget_bytes",
]


#: where the persistent compile cache lives when nothing outside says
#: otherwise: one fixed path inside the checkout. The path is part of
#: the cache key, so it is never a temp name, a pid or a timestamp.
DEFAULT_COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def setup_compile_cache():
    """Turn on JAX's persistent compilation cache; returns its directory.

    The directory is placed from OUTSIDE: where the standard
    ``JAX_COMPILATION_CACHE_DIR`` variable is set, JAX already uses it
    and nothing is set in code. Where it is not,
    ``DEFAULT_COMPILE_CACHE_DIR`` (``<checkout>/.jax_cache``, listed in
    ``.gitignore``) is used. Compiled executables are written to disk
    and re-loaded by later processes, so a warm run skips the XLA
    compiles the cold run paid. Called once at ``import paddle_tpu``.
    A run that wants no cache (the tests: ``tests/conftest.py``)
    switches JAX's own ``jax_enable_compilation_cache`` off; the
    ``compile.persistent_cache`` gauge records whether the cache is on,
    so a compile-seconds histogram says which regime — cold or
    cache-warm — it was measured under."""
    from ..profiler import stats as _stats

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_COMPILE_CACHE_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # cache even fast-compiling programs: the decode/prefill serving
    # programs are individually cheap but numerous
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _stats.set_gauge("compile.persistent_cache",
                     int(jax.config.jax_enable_compilation_cache))
    return path


def _resolve(device=None) -> jax.Device:
    if device is None:
        return current_place().jax_device()
    if isinstance(device, Place):
        return device.jax_device()
    if isinstance(device, jax.Device):
        return device
    if isinstance(device, int):
        return jax.devices()[device]
    return Place(*_split(str(device))).jax_device()


def _split(spec: str):
    if ":" in spec:
        kind, idx = spec.split(":")
        return kind, int(idx)
    return spec, 0


def synchronize(device=None) -> None:
    """Block until all queued work on the device is complete (reference:
    paddle.device.synchronize / cudaDeviceSynchronize). XLA execution is
    data-dependency-ordered, so the fence is: put a trivial computation on
    the device and block on its result — everything enqueued before it on
    the same device is complete when it returns."""
    import jax.numpy as jnp

    dev = _resolve(device)
    jax.device_put(jnp.zeros(()), dev).block_until_ready()


def memory_stats(device=None) -> dict:
    """Raw PJRT memory counters (reference: memory/stats.h Stat registry).
    Keys follow the PJRT allocator: bytes_in_use, peak_bytes_in_use,
    bytes_limit, ... Empty dict when the backend exposes none (CPU)."""
    dev = _resolve(device)
    try:
        return dict(dev.memory_stats() or {})
    except Exception:
        return {}


def memory_allocated(device=None) -> int:
    """Bytes currently allocated on the device (reference:
    paddle.device.cuda.memory_allocated)."""
    return int(memory_stats(device).get("bytes_in_use", 0))


def max_memory_allocated(device=None) -> int:
    """Peak allocated bytes (reference: memory/stats.h peak tracking,
    paddle.device.cuda.max_memory_allocated)."""
    return int(memory_stats(device).get("peak_bytes_in_use", 0))


def memory_reserved(device=None) -> int:
    """Bytes reserved by the allocator pool; PJRT reports the pool limit
    region in bytes_reserved, falling back to bytes_in_use where the
    backend has no pool concept."""
    stats = memory_stats(device)
    return int(stats.get("bytes_reserved", stats.get("bytes_in_use", 0)))


def max_memory_reserved(device=None) -> int:
    stats = memory_stats(device)
    return int(stats.get("peak_bytes_reserved",
                         stats.get("peak_bytes_in_use", 0)))


def reset_peak_memory_stats(device=None) -> None:
    """PJRT exposes no peak-reset; raise rather than silently no-op
    (the reference resets its own Stat registry — ours is the runtime's)."""
    raise NotImplementedError(
        "PJRT does not expose a peak-counter reset; snapshot "
        "max_memory_allocated() and diff instead")


def empty_cache() -> None:
    """Best-effort release of framework-held caches (reference:
    paddle.device.cuda.empty_cache). XLA's allocator manages its own
    pool; we clear jit caches so dead executables release buffers."""
    jax.clear_caches()


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


class _DeviceNamespace:
    """paddle.device.cuda-compatible namespace (maps onto the TPU/PJRT
    counters so reference code reading .cuda keeps working)."""

    @staticmethod
    def memory_allocated(device=None):
        return memory_allocated(device)

    @staticmethod
    def max_memory_allocated(device=None):
        return max_memory_allocated(device)

    @staticmethod
    def memory_reserved(device=None):
        return memory_reserved(device)

    @staticmethod
    def max_memory_reserved(device=None):
        return max_memory_reserved(device)

    @staticmethod
    def synchronize(device=None):
        return synchronize(device)

    @staticmethod
    def empty_cache():
        return empty_cache()

    @staticmethod
    def device_count():
        return device_count()


cuda = _DeviceNamespace()
tpu = _DeviceNamespace()

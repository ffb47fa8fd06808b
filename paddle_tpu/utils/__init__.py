"""paddle_tpu.utils — misc utilities.

TPU-native equivalent of the reference's utils package (reference:
python/paddle/utils — unique_name.py, deprecated.py, download.py,
dlpack.py, try_import, require_version, cpp_extension/). Zero-egress:
download resolves local paths/caches only; cpp_extension points at the
ctypes/cffi extension path this framework uses for native code.
"""
from __future__ import annotations

import functools
import importlib
import os
import warnings

from . import cpp_extension  # noqa: F401
from . import crypto  # noqa: F401
from . import unique_name  # noqa: F401

__all__ = ["deprecated", "try_import", "require_version", "run_check",
           "unique_name", "download", "dlpack", "cpp_extension",
           "crypto"]


def deprecated(update_to="", since="", reason="", level=0):
    """(reference utils/deprecated.py) decorator emitting a
    DeprecationWarning on call."""

    def deco(fn):
        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            msg = f"API '{fn.__module__}.{fn.__name__}' is deprecated"
            if since:
                msg += f" since {since}"
            if update_to:
                msg += f", use '{update_to}' instead"
            if reason:
                msg += f" ({reason})"
            if level > 1:
                raise RuntimeError(msg)
            warnings.warn(msg, DeprecationWarning, stacklevel=2)
            return fn(*args, **kwargs)

        return wrapper

    return deco


def try_import(module_name, err_msg=None):
    """(reference utils/lazy_import.py try_import)"""
    try:
        return importlib.import_module(module_name)
    except ImportError:
        raise ImportError(
            err_msg or f"{module_name} is required but not installed")


def require_version(min_version, max_version=None):
    """(reference utils/install_check-style version gate) against this
    framework's version string."""
    from .. import __version__

    def _key(v):
        import re

        parts = []
        for x in str(v).split(".")[:3]:
            m = re.match(r"\d+", x)
            parts.append(int(m.group()) if m else 0)
        while len(parts) < 3:  # '0.1' must equal '0.1.0'
            parts.append(0)
        return tuple(parts)

    cur = _key(__version__)
    if _key(min_version) > cur:
        raise RuntimeError(
            f"paddle_tpu>={min_version} required, found {__version__}")
    if max_version is not None and _key(max_version) < cur:
        raise RuntimeError(
            f"paddle_tpu<={max_version} required, found {__version__}")
    return True


def run_check():
    """(reference utils/install_check.py run_check) Sanity-check the
    install: one matmul on the default device."""
    import numpy as np

    import paddle_tpu as paddle

    x = paddle.to_tensor(np.eye(4, dtype=np.float32))
    y = (x @ x).numpy()
    assert np.allclose(y, np.eye(4)), "matmul check failed"
    dev = paddle.device.get_device()
    print(f"paddle_tpu is installed successfully! device: {dev}")


class download:
    """(reference utils/download.py) Zero-egress: resolves local files
    and the local cache dir; remote URLs raise with guidance."""

    @staticmethod
    def get_weights_path_from_url(url, md5sum=None):
        if os.path.exists(url):  # an explicit local path always wins
            return url
        path = os.path.expanduser(
            os.path.join("~", ".cache", "paddle_tpu", "weights",
                         os.path.basename(url)))
        if os.path.exists(path):
            return path
        raise RuntimeError(
            f"zero-egress environment: place the file at {path} "
            f"(requested {url})")


class dlpack:
    """(reference utils/dlpack.py) to/from DLPack via jax's support."""

    @staticmethod
    def to_dlpack(tensor):
        """Returns a DLPack-protocol object (has __dlpack__ /
        __dlpack_device__ — the modern exchange form consumers like
        np/torch/jax from_dlpack expect). Falls back through host
        memory on PJRT transports without external buffer references."""
        from ..core.tensor import Tensor

        arr = tensor._data if isinstance(tensor, Tensor) else tensor
        try:
            arr.__dlpack__()  # probe device support
            return arr
        except Exception:
            import numpy as np

            # writable copy: DLPack cannot export readonly views
            return np.array(arr)

    @staticmethod
    def from_dlpack(ext_array):
        import jax.numpy as jnp

        from ..core.tensor import Tensor

        return Tensor(jnp.from_dlpack(ext_array))

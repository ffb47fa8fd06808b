"""paddle_tpu: a TPU-native deep-learning framework.

Brand-new framework with the capabilities of the PaddlePaddle reference
(surveyed in /root/repo/SURVEY.md), designed TPU-first: eager tensors over
immutable PJRT buffers, tape autograd whose VJPs come from jax.vjp,
whole-step jit compilation to StableHLO/XLA, sharding via jax.sharding
meshes + GSPMD, and Pallas kernels for the hot ops.

Top-level namespace mirrors ``paddle.*`` so reference users can switch.
"""
from __future__ import annotations

import jax as _jax

# dtype parity with the reference: paddle supports float64/int64 defaults
# (python ints create int64 tensors). TPU perf paths use explicit f32/bf16.
_jax.config.update("jax_enable_x64", True)

# f32 matmuls run 3-pass bf16 on the MXU (accuracy ≈ the reference's
# A100 TF32 default, which Paddle enables for cuBLAS); bf16 stays native
# single-pass. Explicit bf16 is the perf path either way.
_jax.config.update("jax_default_matmul_precision", "high")

from .core.dtype import (  # noqa: E402
    bfloat16, bool_, complex128, complex64, dtype, float16, float32, float64,
    get_default_dtype, int8, int16, int32, int64, set_default_dtype, uint8,
    uint16, uint32, uint64,
)
from .core.dtype import bool_ as bool  # noqa: E402,A001
from .core.place import (  # noqa: E402
    CPUPlace, Place, TPUPlace, device_count, get_device, is_compiled_with_tpu,
    set_device,
)

# paddle-compat alias: CUDAPlace maps onto the accelerator place
CUDAPlace = TPUPlace

from .core.flags import get_flags, set_flags  # noqa: E402
from .core.generator import get_rng_state, seed, set_rng_state  # noqa: E402
from .core.tensor import Parameter, Tensor, to_tensor  # noqa: E402
from .core.engine import no_grad, enable_grad, set_grad_enabled, is_grad_enabled  # noqa: E402

from .ops import *  # noqa: E402,F401,F403
from .ops import registry as _op_registry  # noqa: E402

from . import autograd  # noqa: E402
from .autograd import grad  # noqa: E402

from . import nn  # noqa: E402
from .nn.layer_base import ParamAttr  # noqa: E402
from . import regularizer  # noqa: E402
from . import optimizer  # noqa: E402
from . import amp  # noqa: E402
from . import io  # noqa: E402
from . import metric  # noqa: E402
from . import jit  # noqa: E402
from .jit import to_static  # noqa: E402
from . import static  # noqa: E402
from . import distributed  # noqa: E402
from . import vision  # noqa: E402
from . import profiler  # noqa: E402
from . import incubate  # noqa: E402
from . import sparse  # noqa: E402
from . import device  # noqa: E402

# persistent XLA compilation cache (JAX_COMPILATION_CACHE_DIR where set,
# else <checkout>/.jax_cache): applied once at import, before any
# program compiles
device.setup_compile_cache()
from . import framework  # noqa: E402
from .framework.io import load, save  # noqa: E402
from .hapi.model import Model  # noqa: E402
from . import hapi  # noqa: E402
from .hapi.dynamic_flops import flops, summary  # noqa: E402
from . import distribution  # noqa: E402
from . import quantization  # noqa: E402
from . import linalg  # noqa: E402
from . import fft  # noqa: E402
from . import onnx  # noqa: E402
from . import audio  # noqa: E402
from . import signal  # noqa: E402
from . import text  # noqa: E402
from . import geometric  # noqa: E402
from . import utils  # noqa: E402
from .hapi import hub  # noqa: E402
from . import inference  # noqa: E402

def is_compiled_with_cuda():
    """False by design: this build's accelerator backend is TPU/XLA
    (reference framework.py is_compiled_with_cuda)."""
    return False


def is_compiled_with_xpu():
    return False


def is_compiled_with_rocm():
    return False


def is_compiled_with_custom_device(device_type: str = None):
    """TPU is the (PJRT) device backend here."""
    return device_type in (None, "tpu")


# `paddle.disable_static()/enable_static()` parity: we are always dynamic
# with jit-compiled regions, so these are state toggles kept for API compat.
_static_mode = False


def enable_static():
    global _static_mode
    _static_mode = True


def disable_static():
    global _static_mode
    _static_mode = False


def in_dynamic_mode():
    return not _static_mode


def is_grad_enabled_():  # pragma: no cover - compat shim
    return is_grad_enabled()


def version():
    return "0.1.0"


__version__ = "0.1.0"

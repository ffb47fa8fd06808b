"""Dynamic (per-token) activation quantization for A8W8 serving.

TPU-native equivalent of the activation-quant stage of the reference's
full-int8 serving matmuls (reference:
paddle/fluid/operators/fused/fused_multi_transformer_int8_op.cu — the
quantize round feeding its int8 GEMMs, and the dyquant kernels behind
quant_for_infer). Each activation ROW (one token's features) gets a
symmetric absmax scale computed on the fly — no calibration pass, no
stored statistics — so the skinny decode matmuls can run int8 x int8 on
the MXU with int32 accumulation and a single dequant of the accumulator
by ``act_scale (x) per-output-channel weight_scale``.

Error contract (documented for the parity tests): round-to-nearest
symmetric int8 means each quantized element is off by at most
``scale/2`` where ``scale = absmax(row)/127``, so a K-length dot row is
off by at most ``(absmax(row)/254) * sum_k |w_dequant[k, n]|`` — the
bound ``tests/test_stream_linear_a8w8.py`` checks against an fp32
reference.

Consumers: ``nn/functional/stream_linear.py`` (the int8-activation
streamed GEMM), ``incubate/nn/fused_transformer.py`` (prefill A8W8
matmuls), and ``QuantedLinear(a8w8=True)`` (the PTQ deployment target).

Grouped-decode interaction (r6): the GROUPED weight-stream path
(``stream_layer_tail``) accepts the same int8 stacks + scales but runs
its GEMMs via in-kernel weight dequant (weight-only math) — the int8
weight STREAM (the bound resource) is preserved while the act-quant
int8 x int8 MXU form stays exclusive to the ungrouped kernel, which is
why ``FusedMultiTransformer.decode_loop`` keeps A8W8 layerwise.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["dynamic_act_quant", "int8_dot_dequant"]

#: absmax floor so an all-zero token row quantizes to zeros with a
#: finite scale instead of dividing by zero
ACT_SCALE_EPS = 1e-8


def dynamic_act_quant(x, eps: float = ACT_SCALE_EPS):
    """Per-token symmetric absmax int8 quantization of activations.

    x [..., K] (any float dtype) -> (q int8 [..., K], scale f32 [...])
    with ``q = clip(round(x / scale), -127, 127)`` and
    ``scale = max(absmax(row) / 127, eps)``. Pure function (jit-safe);
    callers count ``quant.act_quant_calls`` at the dispatch layer where
    a per-execution count is honest (inside a traced program this body
    runs once per compile, not per step).
    """
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, eps)
    q = jnp.clip(jnp.round(xf / s[..., None]), -127, 127) \
        .astype(jnp.int8)
    return q, s


def int8_dot_dequant(x_q, x_scale, w_q, w_scale, bias=None,
                     out_dtype=None):
    """int8 x int8 matmul with int32 MXU accumulation + one dequant.

    x_q [..., K] int8, x_scale [...] f32 (per-token), w_q [K, N] int8,
    w_scale [N]-broadcastable f32 (per-output-channel). The accumulator
    dequant is the rank-1 outer product ``x_scale (x) w_scale`` applied
    once on the int32 result (the reference's dequant round after its
    int8 GEMMs); bias (full precision) is added post-dequant.
    """
    acc = jax.lax.dot_general(
        x_q, w_q, (((x_q.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32)
    out = acc.astype(jnp.float32) * x_scale[..., None] \
        * w_scale.astype(jnp.float32)
    if bias is not None:
        out = out + bias.astype(jnp.float32)
    return out if out_dtype is None else out.astype(out_dtype)

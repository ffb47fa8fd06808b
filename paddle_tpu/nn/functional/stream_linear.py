"""Weight-streaming linears for skinny (decode-shaped) matmuls.

The serving decode step multiplies tiny activations [batch<=64, K]
against huge weights [K, N]; every step must read the full weight
stack from HBM, so decode throughput is bounded by the weight stream,
not math. What end-to-end measurement (r5, 1.3B b32) actually showed:

- int8 weights WIN through this kernel (3398 vs 3231 tok/s) because
  the int8->bf16 dequant fuses into the streamed block DMA;
- bf16 weights LOST to XLA's loop-sliced dots (2749 vs 2916 tok/s):
  per-call Pallas dispatch fixed cost + stream ramp-up paid ~6x per
  layer ate the DMA gains. (An earlier module docstring blamed "XLA
  only reaching ~27% of HBM bandwidth" on these shapes from a
  microbench — that diagnosis was debunked by the end-to-end numbers;
  the stall is per-call overhead, not XLA's tile pipeline.)

The r6 answer is structural, not a faster dot: FEWER, BIGGER,
double-buffered streams.

- ``stream_linear`` — one streamed GEMM. W streams in multi-MB
  [K, bn] column blocks through a Pallas grid (auto double-buffered
  BlockSpec DMA, the same structure that put the r5 paged-attention
  kernel at ~HBM peak), one [M, K] x [K, bn] MXU dot per block, with
  bias / int8 per-output-channel dequant / activation fused in-kernel.
  Block geometry is dtype-aware: bf16's 2-byte stream gets DOUBLE the
  column-block bytes (the DMA must be big for the 2-byte stream to
  saturate HBM) and M is padded up to the dtype's sublane tile
  (f32: 8, bf16: 16) instead of falling back to XLA on odd batches.

- ``stream_layer_tail`` — the GROUPED serving call: O-projection +
  residual + LN2 + FFN1 + activation + FFN2 + residual of one
  transformer layer as ONE streamed kernel (three weight streams in
  one grid), optionally followed by a CROSS-LAYER PREFETCH phase that
  computes layer l+1's LN1 + QKV projection from the just-finished
  hidden state — so layer l+1's first weight blocks DMA while layer
  l's FFN tail is still on the MXU, and the decode fori_loop issues
  ONE fused streamed call per layer in steady state (~2x fixed cost
  per layer instead of ~6x).

Stacked-layer aware: W may be [L, K, N] with a TRACED layer index —
the block index maps read the layer from scalar prefetch, so the
decode loop never materializes a per-layer weight slice (a
dynamic-slice operand to a custom call would copy the whole layer).

Reference comparator: the fused weight-only GEMV/GEMM serving kernels
(paddle/phi/kernels/fusion/gpu/fused_weight_only_linear_pass &
masked_multihead_attention's surrounding fused_multi_transformer step).

A8W8 mode (``act_quant=True``): activations are dynamically quantized
per token (absmax -> int8 + fp32 scale, quantization/dynamic.py) ahead
of the GEMM, the kernel computes the [M, K] x [K, bn] dot int8 x int8
with **int32 MXU accumulation**, and the accumulator is dequantized
ONCE with ``act_scale (x) per-output-channel weight_scale`` (bias added
post-dequant). This removes the int8->bf16 weight convert from the
streamed read AND keeps the skinny matmul's math on the int8 MXU —
the missing half of the reference's full-int8 serving matmuls
(fused_multi_transformer_int8_op.cu quantize/dequant rounds around its
int8 GEMMs). Off-TPU / ragged shapes fall back to the same math via
``lax.dot_general(..., preferred_element_type=int32)``. The grouped
tail accepts int8/a8w8 weight stacks too, but runs their GEMMs via
in-kernel dequant (weight-only math): the weight STREAM — the bound
resource — stays int8, only the MXU math is bf16, so ``auto`` routing
keeps full A8W8 on the ungrouped act-quant kernel.

Tensor parallelism: under the serving ``mp`` mesh (distributed/tp.py,
shard_map), every call streams a PER-SHARD slice — column-parallel
callers pass [K, N/mp] blocks (bias/scale shard along), row-parallel
callers pass [K/mp, N] with ``reduce_axis="mp"`` so the f32 partial is
psum'd before the replicated bias/activation (the collective stays
fused with the projection call). Per chip the streamed bytes are
exactly 1/mp of the stack, so TP decode keeps its weight-bandwidth
roofline per chip instead of re-streaming replicated full matrices.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...device import chip as _chip
from ...device.vmem import KERNEL_VMEM_LIMIT_BYTES
from .paged_attention import _enable_x64

__all__ = ["stream_linear", "stream_layer_tail"]


#: single-GEMM column-block byte targets per weight itemsize: big DMAs
#: keep the HBM stream saturated, and a 2-byte bf16 stream needs twice
#: the columns of an f32 one to issue the same-size DMA
_TARGET_BLOCK_BYTES = {1: 4 << 20, 2: 8 << 20, 4: 4 << 20}

#: grouped-tail per-stream byte target: the fused kernel double-buffers
#: up to four weight streams at once, so each stream gets a smaller
#: block to stay inside VMEM
_TARGET_GROUPED_BYTES = 2 << 20

#: int8 VMEM tiles are (32, 128) — the quantized-activation block is
#: padded up to this sublane multiple before entering the kernel
_INT8_SUBLANES = 32

#: f32/bf16 sublane tiles: M (the tiny batch dim) is padded up to the
#: compute dtype's tile instead of bouncing odd batches off to XLA
_SUBLANES = {4: 8, 2: 16}


def _pick_bn(K: int, N: int, itemsize: int, target=None) -> int:
    """Largest 128-multiple divisor of N whose [K, bn] block hits the
    dtype's byte target (big DMAs keep the HBM stream saturated)."""
    if target is None:
        target = _TARGET_BLOCK_BYTES.get(itemsize, 4 << 20)
    cap = max(128, target // max(K * itemsize, 1))
    best = 0
    for bn in range(128, min(cap, N) + 1, 128):
        if N % bn == 0:
            best = bn
    return best


def _sublane_pad(x):
    """Pad rows of x [M, K] up to the dtype's sublane tile; returns
    (padded, M)."""
    M = x.shape[0]
    sub = _SUBLANES.get(jnp.dtype(x.dtype).itemsize, 8)
    Mp = -(-M // sub) * sub
    if Mp != M:
        x = jnp.pad(x, ((0, Mp - M), (0, 0)))
    return x, M


def _apply_activation(acc, activation):
    if activation == "gelu":
        return jax.nn.gelu(acc)
    if activation == "relu":
        return jax.nn.relu(acc)
    return acc


def _ln_f32(h, scale, bias, eps):
    """f32 layer norm matching FusedMultiTransformer._ln."""
    mu = jnp.mean(h, axis=-1, keepdims=True)
    var = jnp.var(h, axis=-1, keepdims=True)
    return (h - mu) * jax.lax.rsqrt(var + eps) * scale + bias


def _stream_linear_a8w8(x_q, x_scale, w3, s3, b3, layer, activation,
                        out_dtype, interpret=None):
    """int8-activation streaming kernel: x_q [M, K] int8 (+ per-token
    scales [M] f32) against stacked int8 weights w3 [L, K, N] with
    per-output-channel dequant scales s3 [L, 1, N] (b3 [L, 1, N] bias
    or None). One [M, K] x [K, bn] int8 MXU dot per weight block,
    int32 accumulator dequantized in-kernel by
    ``x_scale[:, None] * s3`` — the weight stream stays int8 end to
    end. Runs in Pallas interpret mode off-TPU so CPU CI pins the
    kernel's numerics (tests/test_stream_linear_a8w8.py)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x_q.shape
    N = w3.shape[-1]
    bn = _pick_bn(K, N, 1)
    if interpret is None:
        interpret = not _chip.on_tpu()
    # pad the (tiny) activation block up to the int8 sublane tile
    Mp = -(-M // _INT8_SUBLANES) * _INT8_SUBLANES
    if Mp != M:
        x_q = jnp.pad(x_q, ((0, Mp - M), (0, 0)))
        x_scale = jnp.pad(x_scale, (0, Mp - M))
    xs2 = x_scale.reshape(Mp, 1).astype(jnp.float32)
    has_bias = b3 is not None
    nb = N // bn
    lidx = jnp.reshape(jnp.asarray(0 if layer is None else layer,
                                   jnp.int32), (1,))

    def kernel(l_ref, x_ref, xs_ref, w_ref, s_ref, *rest):
        del l_ref
        b_ref = rest[0] if has_bias else None
        o_ref = rest[-1]
        acc = jax.lax.dot_general(
            x_ref[...], w_ref[0],
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.int32)          # [Mp, bn] int32
        acc = acc.astype(jnp.float32) * xs_ref[...] \
            * s_ref[0].astype(jnp.float32)
        if b_ref is not None:
            acc = acc + b_ref[0].astype(jnp.float32)
        acc = _apply_activation(acc, activation)
        o_ref[...] = acc.astype(o_ref.dtype)

    in_specs = [
        pl.BlockSpec((Mp, K), lambda j, l: (0, 0)),
        pl.BlockSpec((Mp, 1), lambda j, l: (0, 0)),
        pl.BlockSpec((1, K, bn), lambda j, l: (l[0], 0, j)),
        pl.BlockSpec((1, 1, bn), lambda j, l: (l[0], 0, j)),
    ]
    operands = [x_q, xs2, w3, s3]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, bn),
                                     lambda j, l: (l[0], 0, j)))
        operands.append(b3)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((Mp, bn), lambda j, l: (0, j)),
        scratch_shapes=[])
    with _enable_x64(False), jax.named_scope("pt_stream_linear_a8w8"):
        out = pl.pallas_call(
            kernel,
            name="pt_stream_linear_a8w8",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(lidx, *operands)
    return out[:M] if Mp != M else out


def _stream_linear_act_quant(x, w, layer, bias, scale, activation,
                             out_dtype, *, stacked):
    """A8W8 dispatch: dynamic per-token act quant, then the streaming
    int8 x int8 kernel on TPU (clean geometry) or the XLA
    ``preferred_element_type=int32`` dot everywhere else — identical
    math, so CPU serving tests exercise the same numerics the chip
    runs."""
    from ...quantization.dynamic import dynamic_act_quant

    K = x.shape[1]
    N = w.shape[-1]
    x_q, x_s = dynamic_act_quant(x)
    if _chip.on_tpu() and _pick_bn(K, N, 1) and K % 128 == 0:
        w3 = w if stacked else w[None]
        s3 = (scale if stacked else scale[None]) \
            .reshape(w3.shape[0], 1, N).astype(jnp.float32)
        b3 = None
        if bias is not None:
            b3 = (bias if stacked else bias[None]) \
                .reshape(w3.shape[0], 1, N).astype(jnp.float32)
        return _stream_linear_a8w8(x_q, x_s, w3, s3, b3, layer,
                                   activation, out_dtype)
    from ...quantization.dynamic import int8_dot_dequant

    wl = w[layer] if stacked else w
    out = int8_dot_dequant(
        x_q, x_s, wl, (scale[layer] if stacked else scale),
        bias=(bias[layer] if stacked else bias)
        if bias is not None else None)
    return _apply_activation(out, activation).astype(out_dtype)


def _ring_reduce_pipeline(x, w, layer, scale, act_quant, axis, size):
    """The ring-overlap form of the row-parallel reduction (ISSUE 19):
    the output columns split into ``size`` chunks, each chunk's GEMM
    is a SEPARATE streamed call over its weight-column slice, and
    chunk i's ``size - 1`` ppermute ring steps are emitted after chunk
    i+1's GEMM — the permutes depend only on their own chunk's
    partial, so the reduction of chunk i rides under the weight
    stream of chunk i+1 instead of waiting for the full partial.
    Returns the reduced f32 [M, N] (bias/activation stay with the
    caller, AFTER the reduction, same as the psum form)."""
    import numpy as np

    from ...distributed.tp import ring_chunk_reduce

    N = w.shape[-1]
    bounds = [int(b) for b in np.linspace(0, N, size + 1).astype(int)]
    spans = [(lo, hi) for lo, hi in zip(bounds[:-1], bounds[1:])
             if hi > lo]

    def gemm(lo, hi):
        return stream_linear(
            x, jax.lax.slice_in_dim(w, lo, hi, axis=-1), layer=layer,
            bias=None,
            scale=None if scale is None
            else jax.lax.slice_in_dim(scale, lo, hi, axis=-1),
            activation=None, out_dtype=jnp.float32,
            act_quant=act_quant)

    parts: list = []
    reduced: list = [None] * len(spans)
    for j, (lo, hi) in enumerate(spans):
        parts.append(gemm(lo, hi))
        if j >= 1:
            # ring phase for chunk j-1 under chunk j's GEMM stream
            reduced[j - 1] = ring_chunk_reduce(parts[j - 1], axis, size)
    reduced[-1] = ring_chunk_reduce(parts[-1], axis, size)
    return jnp.concatenate(reduced, axis=-1) if len(reduced) > 1 \
        else reduced[0]


def stream_linear(x, w, layer=None, bias=None, scale=None,
                  activation=None, out_dtype=None, act_quant=False,
                  reduce_axis=None, overlap=None):
    """x [M, K] @ w[(L,) K, N] (+ bias) with streamed weights.

    layer: traced int32 index when w/bias/scale are layer-stacked.
    scale: int8 weight-only per-output-channel dequant scales [(L,) N].
    activation: None | 'gelu' | 'relu', fused on the f32 accumulator.
    act_quant: A8W8 — dynamically quantize x per token (absmax int8 +
    f32 scale) and run the GEMM int8 x int8 with int32 accumulation;
    requires int8 ``w`` with per-output-channel ``scale``.
    reduce_axis: ROW-PARALLEL tensor-parallel form (inside shard_map):
    ``w`` is this shard's [K/mp, N] slice — the f32 partial product is
    reduced over the named mesh axis BEFORE the (replicated) bias add
    and activation, so the collective stays fused with the projection
    call (per-output-channel int8 dequant scales commute with the sum
    and stay per-shard, inside the streamed kernel). An axis of extent
    1 skips the collective at trace time.
    overlap: the reduction schedule when ``reduce_axis`` is set —
    ``"psum"`` (one blocking all-reduce, the bitwise/census reference)
    | ``"ring"`` (mp column chunks, each GEMM'd in its own streamed
    call and ring-reduced via ppermute under the next chunk's weight
    stream) | None (``FLAGS_tp_overlap``).
    Returns [M, N] in out_dtype (default: x.dtype).
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    M, K = x.shape
    stacked = w.ndim == 3
    N = w.shape[-1]
    out_dtype = out_dtype or x.dtype
    if reduce_axis is not None:
        from ...distributed.tp import (axis_extent, resolve_overlap)
        from ...profiler import stats as _rstats

        mode = resolve_overlap(overlap)
        size = axis_extent(reduce_axis)
        if size == 1:
            # single-shard TP view: the collective would be a no-op —
            # skip it at trace time (the census must stay empty)
            out = stream_linear(x, w, layer=layer, bias=None,
                                scale=scale, activation=None,
                                out_dtype=jnp.float32,
                                act_quant=act_quant)
        elif mode == "ring":
            _rstats.counter("dist.overlap_ring_reduces").inc()
            _rstats.gauge("dist.overlap_ring_phases").set(
                float(size * (size - 1)))
            out = _ring_reduce_pipeline(x, w, layer, scale, act_quant,
                                        reduce_axis, size)
        elif mode == "psum":
            part = stream_linear(x, w, layer=layer, bias=None,
                                 scale=scale, activation=None,
                                 out_dtype=jnp.float32,
                                 act_quant=act_quant)
            out = jax.lax.psum(part, reduce_axis)
        else:
            raise ValueError(
                f"stream_linear: overlap={mode!r} is not 'ring'|'psum'")
        if bias is not None:
            b = bias[0 if layer is None else layer] if stacked else bias
            out = out + b.astype(jnp.float32)
        out = _apply_activation(out, activation)
        return out.astype(out_dtype)
    if act_quant:
        if w.dtype != jnp.int8 or scale is None:
            raise ValueError(
                "stream_linear(act_quant=True) needs int8 weights with "
                "per-output-channel scales (quantize_weight_only_int8)")
        return _stream_linear_act_quant(
            x, w, layer, bias, scale, activation, out_dtype,
            stacked=stacked)
    bn = _pick_bn(K, N, w.dtype.itemsize)
    if bn == 0 or K % 128 != 0 or not _chip.on_tpu():
        # fallback: plain XLA dot (CPU tests, odd shapes)
        wl = w[layer] if stacked else w
        out = jax.lax.dot_general(
            x, wl.astype(x.dtype) if wl.dtype == jnp.int8 else wl,
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        if scale is not None:
            out = out * (scale[layer] if stacked else scale)
        if bias is not None:
            out = out + (bias[layer] if stacked else bias)
        out = _apply_activation(out, activation)
        return out.astype(out_dtype)

    # odd batches enter the kernel padded to the compute dtype's
    # sublane tile rather than bouncing the whole call back to XLA
    x, M = _sublane_pad(x)
    Mp = x.shape[0]
    nb = N // bn
    has_bias = bias is not None
    has_scale = scale is not None
    # normalize operands to stacked-3D so one kernel serves both forms
    w3 = w if stacked else w[None]
    b3 = None
    s3 = None
    if has_bias:
        b3 = (bias if stacked else bias[None]).reshape(
            w3.shape[0], 1, N)
    if has_scale:
        s3 = (scale if stacked else scale[None]).reshape(
            w3.shape[0], 1, N)
    lidx = jnp.reshape(
        jnp.asarray(0 if layer is None else layer, jnp.int32), (1,))

    def kernel(l_ref, x_ref, *rest):
        del l_ref
        refs = list(rest)
        w_ref = refs.pop(0)
        b_ref = refs.pop(0) if has_bias else None
        s_ref = refs.pop(0) if has_scale else None
        o_ref = refs.pop(0)
        wb = w_ref[0]                                # [K, bn]
        acc = jax.lax.dot_general(
            x_ref[...], wb.astype(x_ref.dtype),
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)      # [Mp, bn]
        if s_ref is not None:
            acc = acc * s_ref[0].astype(jnp.float32)
        if b_ref is not None:
            acc = acc + b_ref[0].astype(jnp.float32)
        acc = _apply_activation(acc, activation)
        o_ref[...] = acc.astype(o_ref.dtype)

    in_specs = [
        pl.BlockSpec((Mp, K), lambda j, l: (0, 0)),
        pl.BlockSpec((1, K, bn), lambda j, l: (l[0], 0, j)),
    ]
    operands = [x, w3]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, bn), lambda j, l: (l[0], 0, j)))
        operands.append(b3)
    if has_scale:
        in_specs.insert(2 if not has_bias else 3,
                        pl.BlockSpec((1, 1, bn),
                                     lambda j, l: (l[0], 0, j)))
        operands.insert(2 if not has_bias else 3, s3)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((Mp, bn), lambda j, l: (0, j)),
        scratch_shapes=[])
    with _enable_x64(False), jax.named_scope("pt_stream_linear_bf16"):
        out = pl.pallas_call(
            kernel,
            name="pt_stream_linear_bf16",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((Mp, N), out_dtype),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
        )(lidx, *operands)
    return out[:M] if Mp != M else out


# ---------------------------------------------------------------------
# grouped layer tail: O-proj + LN2 + FFN (+ next layer's LN1 + QKV)
# ---------------------------------------------------------------------


def _mm_like(x, w, scale):
    """The exact matmul math of FusedMultiTransformer._mm (plain dot in
    the compute dtype; int8 weights dequant on the OUTPUT via
    per-output-channel scales) — the grouped XLA fallback mirrors the
    ungrouped decode path bitwise so CPU greedy-parity tests stay
    pinned."""
    if w.dtype == jnp.int8:
        return (x @ w.astype(x.dtype)) * scale.astype(x.dtype)
    return x @ w


def _tail_geometry(Ka, d, dff, nq_n, itemsize):
    """Block widths for the fused tail's weight streams, or None when
    the shapes can't tile (the caller then takes the XLA fallback)."""
    if Ka % 128 or d % 128 or dff % 128:
        return None
    bn_o = _pick_bn(Ka, d, itemsize, _TARGET_GROUPED_BYTES)
    bn_f = _pick_bn(d, dff, itemsize, _TARGET_GROUPED_BYTES)
    if not bn_o or not bn_f:
        return None
    bn_q = 0
    if nq_n:
        if nq_n % 128:
            return None
        bn_q = _pick_bn(d, nq_n, itemsize, _TARGET_GROUPED_BYTES)
        if not bn_q:
            return None
    return bn_o, bn_f, bn_q


def _stream_layer_tail_kernel(att, h, wo3, w13, w23, so3, s13, s23,
                              bo3, b13, b23, ln2s, ln2b, lidx, qg,
                              eps, activation, out_dtype, bns,
                              interpret):
    """The fused tail as ONE Pallas grid over three (four with the
    prefetch phase) weight streams. TPU grids run sequentially, so the
    kernel is phased by ``j = program_id(0)``:

      phase O   (j <  nb_o):          h2[:, blk] = h + att @ Wo_blk
      boundary  (j == nb_o):          hn2 = LN2(h2)   (f32 scratch)
      phase FFN (nb_o <= j < +nb_f):  acc += act(hn2 @ W1_blk) @ W2_blk
      finish    (last FFN block):     h_out = h2 + acc; hn1 = LN1'(h_out)
      phase QKV (j >= nb_o + nb_f):   qkv[:, blk] = hn1 @ Wq_blk

    Every weight stream is auto double-buffered by its BlockSpec, so
    the QKV phase overlaps layer l+1's first weight DMAs with layer
    l's FFN tail still in flight — the cross-layer prefetch. Index
    maps CLAMP each stream to its own phase's range; the off-phase
    block a stream re-fetches is the one already resident, so no extra
    HBM traffic is issued for parked streams."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    bn_o, bn_f, bn_q = bns
    Mp, Ka = att.shape
    d = h.shape[1]
    dff = w13.shape[-1]
    nb_o, nb_f = d // bn_o, dff // bn_f
    has_q = qg is not None
    nb_q = (qg["w"].shape[-1] // bn_q) if has_q else 0
    has_s = so3 is not None
    has_sq = has_q and qg.get("s") is not None
    cdtype = att.dtype
    f32 = jnp.float32

    def dot(a, b):
        return jax.lax.dot_general(
            a, b.astype(a.dtype), (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=f32)

    def kernel(l_ref, *rest):
        del l_ref
        refs = list(rest)
        att_r = refs.pop(0)
        h_r = refs.pop(0)
        wo_r = refs.pop(0)
        so_r = refs.pop(0) if has_s else None
        bo_r = refs.pop(0)
        w1_r = refs.pop(0)
        s1_r = refs.pop(0) if has_s else None
        b1_r = refs.pop(0)
        w2_r = refs.pop(0)
        s2_r = refs.pop(0) if has_s else None
        b2_r = refs.pop(0)
        ln2s_r = refs.pop(0)
        ln2b_r = refs.pop(0)
        wq_r = sq_r = bq_r = ln1s_r = ln1b_r = out_q = None
        if has_q:
            wq_r = refs.pop(0)
            sq_r = refs.pop(0) if has_sq else None
            bq_r = refs.pop(0)
            ln1s_r = refs.pop(0)
            ln1b_r = refs.pop(0)
        out_h = refs.pop(0)
        if has_q:
            out_q = refs.pop(0)
        s_h2, s_hn, s_acc = refs
        j = pl.program_id(0)

        @pl.when(j < nb_o)
        def _o_phase():
            blk = dot(att_r[...], wo_r[0])           # [Mp, bn_o] f32
            if so_r is not None:
                blk = blk * so_r[0].astype(f32)
            cols = pl.ds(j * bn_o, bn_o)
            blk = blk + bo_r[0, :, cols].astype(f32)
            s_h2[:, cols] = h_r[:, cols].astype(f32) + blk

        @pl.when(j == nb_o)
        def _ln2_boundary():
            hn = _ln_f32(s_h2[...], ln2s_r[0].astype(f32),
                         ln2b_r[0].astype(f32), eps)
            s_hn[...] = hn.astype(cdtype)
            s_acc[...] = jnp.zeros_like(s_acc)

        @pl.when((j >= nb_o) & (j < nb_o + nb_f))
        def _ffn_phase():
            a = dot(s_hn[...], w1_r[0])              # [Mp, bn_f] f32
            if s1_r is not None:
                a = a * s1_r[0].astype(f32)
            a = _apply_activation(a + b1_r[0].astype(f32), activation)
            s_acc[...] += dot(a.astype(cdtype), w2_r[0])

        @pl.when(j == nb_o + nb_f - 1)
        def _finish():
            acc = s_acc[...]
            if s2_r is not None:
                acc = acc * s2_r[0].astype(f32)
            hout = s_h2[...] + acc + b2_r[0].astype(f32)
            out_h[...] = hout.astype(out_h.dtype)
            if has_q:
                hn1 = _ln_f32(hout, ln1s_r[0].astype(f32),
                              ln1b_r[0].astype(f32), eps)
                s_hn[...] = hn1.astype(cdtype)

        if has_q:
            @pl.when(j >= nb_o + nb_f)
            def _qkv_prefetch_phase():
                qb = dot(s_hn[...], wq_r[0])         # [Mp, bn_q] f32
                if sq_r is not None:
                    qb = qb * sq_r[0].astype(f32)
                out_q[...] = (qb + bq_r[0].astype(f32)) \
                    .astype(out_q.dtype)

    # clamp each stream's block index into its own phase so parked
    # streams keep re-mapping the block already resident in VMEM
    o_idx = lambda j: jnp.minimum(j, nb_o - 1)                # noqa: E731
    f_idx = lambda j: jnp.clip(j - nb_o, 0, nb_f - 1)         # noqa: E731
    q_idx = lambda j: jnp.clip(j - nb_o - nb_f, 0,            # noqa: E731
                               max(nb_q - 1, 0))

    in_specs = [
        pl.BlockSpec((Mp, Ka), lambda j, l: (0, 0)),
        pl.BlockSpec((Mp, d), lambda j, l: (0, 0)),
        pl.BlockSpec((1, Ka, bn_o), lambda j, l: (l[0], 0, o_idx(j))),
    ]
    operands = [att, h, wo3]
    if has_s:
        in_specs.append(pl.BlockSpec((1, 1, bn_o),
                                     lambda j, l: (l[0], 0, o_idx(j))))
        operands.append(so3)
    in_specs.append(pl.BlockSpec((1, 1, d), lambda j, l: (l[0], 0, 0)))
    operands.append(bo3)
    in_specs.append(pl.BlockSpec((1, d, bn_f),
                                 lambda j, l: (l[0], 0, f_idx(j))))
    operands.append(w13)
    if has_s:
        in_specs.append(pl.BlockSpec((1, 1, bn_f),
                                     lambda j, l: (l[0], 0, f_idx(j))))
        operands.append(s13)
    in_specs.append(pl.BlockSpec((1, 1, bn_f),
                                 lambda j, l: (l[0], 0, f_idx(j))))
    operands.append(b13)
    in_specs.append(pl.BlockSpec((1, bn_f, d),
                                 lambda j, l: (l[0], f_idx(j), 0)))
    operands.append(w23)
    if has_s:
        in_specs.append(pl.BlockSpec((1, 1, d),
                                     lambda j, l: (l[0], 0, 0)))
        operands.append(s23)
    in_specs.append(pl.BlockSpec((1, 1, d), lambda j, l: (l[0], 0, 0)))
    operands.append(b23)
    # LN scale/bias ride as [L, 1, d] like the biases: a one-row block
    # of a 2-D [L, d] array is refused by the TPU tiling for L > 1
    # (second-to-last block dim must be a multiple of 8 or the whole dim)
    in_specs.append(pl.BlockSpec((1, 1, d), lambda j, l: (l[0], 0, 0)))
    operands.append(ln2s)
    in_specs.append(pl.BlockSpec((1, 1, d), lambda j, l: (l[0], 0, 0)))
    operands.append(ln2b)
    out_shapes = [jax.ShapeDtypeStruct((Mp, d), out_dtype)]
    out_specs = [pl.BlockSpec((Mp, d), lambda j, l: (0, 0))]
    if has_q:
        nq_n = qg["w"].shape[-1]
        in_specs.append(pl.BlockSpec((1, d, bn_q),
                                     lambda j, l: (l[1], 0, q_idx(j))))
        operands.append(qg["w"])
        if has_sq:
            in_specs.append(pl.BlockSpec(
                (1, 1, bn_q), lambda j, l: (l[1], 0, q_idx(j))))
            operands.append(qg["s"])
        in_specs.append(pl.BlockSpec((1, 1, bn_q),
                                     lambda j, l: (l[1], 0, q_idx(j))))
        operands.append(qg["b"])
        in_specs.append(pl.BlockSpec((1, 1, d),
                                     lambda j, l: (l[1], 0, 0)))
        operands.append(qg["ln_s"])
        in_specs.append(pl.BlockSpec((1, 1, d),
                                     lambda j, l: (l[1], 0, 0)))
        operands.append(qg["ln_b"])
        out_shapes.append(jax.ShapeDtypeStruct((Mp, nq_n), out_dtype))
        out_specs.append(pl.BlockSpec((Mp, bn_q),
                                      lambda j, l: (0, q_idx(j))))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb_o + nb_f + nb_q,),
        in_specs=in_specs,
        out_specs=out_specs,
        scratch_shapes=[
            pltpu.VMEM((Mp, d), f32),      # s_h2: post-attention hidden
            pltpu.VMEM((Mp, d), cdtype),   # s_hn: LN'd matmul input
            pltpu.VMEM((Mp, d), f32),      # s_acc: FFN2 accumulator
        ])
    with _enable_x64(False), jax.named_scope("pt_stream_linear_layer_tail"):
        outs = pl.pallas_call(
            kernel,
            name="pt_stream_linear_layer_tail",
            grid_spec=grid_spec,
            out_shape=out_shapes,
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(lidx, *operands)
    return outs


def _tail_fallback(att, h, wo, w1, w2, layer, so, s1, s2, bo, b1, b2,
                   ln2_scale, ln2_bias, eps, activation, qg, out_dtype,
                   stacked):
    """XLA composition of the identical math (CPU CI, ragged shapes):
    op-for-op the ungrouped decode path (_layer_body + _mm), so the
    grouped CPU engine reproduces the ungrouped greedy tokens."""
    def at(a):
        return a[layer] if (stacked and a is not None) else a

    h2 = (h + _mm_like(att, at(wo), at(so)) + at(bo)).astype(h.dtype)
    hn = _ln_f32(h2, at(ln2_scale), at(ln2_bias), eps).astype(h.dtype)
    ff = _apply_activation(
        (_mm_like(hn, at(w1), at(s1)) + at(b1)).astype(h.dtype),
        activation)
    h_out = (h2 + _mm_like(ff, at(w2), at(s2)) + at(b2)).astype(h.dtype)
    if qg is None:
        return h_out.astype(out_dtype)
    lq = qg.get("layer")

    def atq(a):
        return a[lq] if (stacked and a is not None and lq is not None) \
            else a

    hn1 = _ln_f32(h_out, atq(qg["ln_s"]), atq(qg["ln_b"]), eps) \
        .astype(h.dtype)
    qkv = _mm_like(hn1, atq(qg["w"]), atq(qg.get("s"))) + atq(qg["b"])
    return h_out.astype(out_dtype), qkv.astype(out_dtype)


def _tail_tp_split(att, h, wo, w1, w2, layer, so, s1, s2, bo, b1, b2,
                   ln2_scale, ln2_bias, eps, activation, next_qkv,
                   out_dtype, stacked, reduce_axis, overlap):
    """Tensor-parallel grouped tail (ISSUE 19): the fused Pallas grid
    cannot span a collective, so under a ``reduce_axis`` the tail
    SPLITS at the two reduction points into streamed calls — O-proj
    partial reduced (ring phases riding under the FFN1 weight stream
    that follows), FFN1, FFN2 partial reduced, and the cross-layer
    QKV prefetch emitted AFTER the FFN2 reduction so its weight DMA
    overlaps the trailing ring phases. Op-for-op the ungrouped TP
    decode math (stream_linear reduce_axis= calls), so grouped-TP
    greedy tokens reproduce the four-call form's exactly."""
    l = (0 if layer is None else layer) if stacked else None

    def at(a):
        return a[l] if (stacked and a is not None) else a

    h2 = (h + stream_linear(
        att, wo, layer=layer, bias=bo, scale=so, out_dtype=h.dtype,
        reduce_axis=reduce_axis, overlap=overlap)).astype(h.dtype)
    hn = _ln_f32(h2, at(ln2_scale), at(ln2_bias), eps).astype(h.dtype)
    ff = stream_linear(hn, w1, layer=layer, bias=b1, scale=s1,
                       activation=activation, out_dtype=h.dtype)
    h_out = (h2 + stream_linear(
        ff, w2, layer=layer, bias=b2, scale=s2, out_dtype=h.dtype,
        reduce_axis=reduce_axis, overlap=overlap)).astype(h.dtype)
    if next_qkv is None:
        return h_out.astype(out_dtype)
    lq = next_qkv.get("layer")
    lq = (0 if lq is None else lq) if stacked else None

    def atq(a):
        return a[lq] if (stacked and a is not None) else a

    hn1 = _ln_f32(h_out, atq(next_qkv["ln_s"]), atq(next_qkv["ln_b"]),
                  eps).astype(h.dtype)
    qkv = stream_linear(hn1, next_qkv["w"], layer=next_qkv.get("layer"),
                        bias=next_qkv["b"], scale=next_qkv.get("s"),
                        out_dtype=h.dtype)
    return h_out.astype(out_dtype), qkv.astype(out_dtype)


def stream_layer_tail(att, h, wo, w1, w2, layer=None, *, bo, b1, b2,
                      ln2_scale, ln2_bias, epsilon, activation=None,
                      so=None, s1=None, s2=None, next_qkv=None,
                      out_dtype=None, interpret=None,
                      reduce_axis=None, overlap=None):
    """GROUPED streamed layer tail: everything after attention in one
    call — ``h2 = h + att @ Wo + bo; h_out = h2 + FFN(LN2(h2))`` — and,
    when ``next_qkv`` is given, the CROSS-LAYER PREFETCH phase
    ``qkv' = LN1'(h_out) @ Wq' + bq'`` for the next layer, so the
    decode fori_loop issues ONE streamed call per layer.

    att [M, Ka], h [M, d]. Weights stacked [L, K, N] with a traced
    ``layer`` index, or unstacked 2-D. ``so/s1/s2``: int8
    per-output-channel dequant scales [(L,) N] — the grouped kernel
    streams int8 and dequants in-kernel (weight-only math; full A8W8
    act-quant stays on the ungrouped kernel). ``next_qkv``: dict with
    ``w``, ``b``, ``ln_s``, ``ln_b`` (+ optional ``s`` scale and
    ``layer`` index for the stacked form — pass ``min(l+1, L-1)``).

    Returns ``h_out`` (and ``qkv_next`` when ``next_qkv``), in
    ``out_dtype`` (default: h.dtype). Off-TPU / ragged shapes take an
    XLA fallback with op-for-op ungrouped math; ``interpret=True``
    forces the Pallas kernel in interpret mode (the parity tests).

    ``reduce_axis``/``overlap``: the tensor-parallel grouped tail —
    ``wo``/``w2`` are row-parallel [K/mp, N] shards whose f32 partials
    reduce over the named axis (``overlap="ring"`` pipelines the
    reduction as ppermute chunks under the following weight stream,
    ``"psum"`` is the blocking reference, None reads
    ``FLAGS_tp_overlap``); a collective cannot live inside the fused
    Pallas grid, so this form splits into streamed calls at the two
    reduction points (``_tail_tp_split``).
    """
    out_dtype = out_dtype or h.dtype
    stacked = wo.ndim == 3
    if (w1.ndim != wo.ndim or w2.ndim != wo.ndim
            or (next_qkv is not None
                and next_qkv["w"].ndim != wo.ndim)):
        raise ValueError("stream_layer_tail: wo/w1/w2 (and next_qkv.w) "
                         "must all be stacked [L, K, N] or all 2-D")
    scales = (so, s1, s2)
    if any(s is not None for s in scales) and \
            not all(s is not None for s in scales):
        raise ValueError("stream_layer_tail: pass all of so/s1/s2 or "
                         "none (the engine quantizes all four stacks)")
    if reduce_axis is not None:
        return _tail_tp_split(
            att, h, wo, w1, w2, layer, so, s1, s2, bo, b1, b2,
            ln2_scale, ln2_bias, epsilon, activation, next_qkv,
            out_dtype, stacked, reduce_axis, overlap)
    Ka = att.shape[1]
    d = h.shape[1]
    dff = w1.shape[-1]
    nq_n = next_qkv["w"].shape[-1] if next_qkv is not None else 0
    bns = _tail_geometry(Ka, d, dff, nq_n, wo.dtype.itemsize)
    use_kernel = bns is not None and (interpret is True or _chip.on_tpu())
    if not use_kernel:
        return _tail_fallback(
            att, h, wo, w1, w2,
            (0 if layer is None else layer) if stacked else None,
            so, s1, s2, bo, b1, b2, ln2_scale, ln2_bias, epsilon,
            activation, next_qkv, out_dtype, stacked)

    interpret = bool(interpret) if interpret is not None \
        else not _chip.on_tpu()
    L = wo.shape[0] if stacked else 1

    def norm_w(a):
        return a if stacked else a[None]

    def norm_v(a, n):
        return (a if stacked else a[None]).reshape(L, 1, n)

    def norm_ln(a):
        return a.reshape(-1, 1, d)

    qg = None
    lq = 0
    if next_qkv is not None:
        Lq = next_qkv["w"].shape[0] if stacked else 1
        lq = next_qkv.get("layer")
        lq = 0 if lq is None else lq
        qg = {
            "w": norm_w(next_qkv["w"]),
            "b": (next_qkv["b"] if stacked else next_qkv["b"][None])
            .reshape(Lq, 1, nq_n),
            "ln_s": norm_ln(next_qkv["ln_s"]),
            "ln_b": norm_ln(next_qkv["ln_b"]),
        }
        if next_qkv.get("s") is not None:
            qg["s"] = (next_qkv["s"] if stacked
                       else next_qkv["s"][None]).reshape(Lq, 1, nq_n)
    lidx = jnp.stack([
        jnp.asarray(0 if layer is None else layer, jnp.int32),
        jnp.asarray(lq, jnp.int32)])

    attp, M = _sublane_pad(att)
    hp, _ = _sublane_pad(h)
    outs = _stream_layer_tail_kernel(
        attp, hp, norm_w(wo), norm_w(w1), norm_w(w2),
        norm_v(so, d) if so is not None else None,
        norm_v(s1, dff) if s1 is not None else None,
        norm_v(s2, d) if s2 is not None else None,
        norm_v(bo, d), norm_v(b1, dff), norm_v(b2, d),
        norm_ln(ln2_scale), norm_ln(ln2_bias), lidx, qg,
        epsilon, activation, out_dtype, bns, interpret)
    h_out, qkv = (outs[0], outs[1]) if next_qkv is not None \
        else (outs[0], None)
    if h_out.shape[0] != M:
        h_out = h_out[:M]
        qkv = qkv[:M] if qkv is not None else None
    return h_out if qkv is None else (h_out, qkv)

"""Ragged grouped GEMM + no-drop MoE FFN (ROADMAP item 4 tentpole).

The MoE expert bank is really E independent GEMMs over CONTIGUOUS row
segments of a token matrix sorted by expert id — the capacity-factor
GShard einsum the repo carried until now materialized dense
``[T, E, capacity]`` dispatch/combine one-hots instead (O(T·E·C) memory
and FLOPs for what is a ragged gather) and silently shed work at the
capacity bound (``moe.dropped_tokens``). This module is the
megablocks-style replacement (reference comparator: the fork's cutlass
grouped GEMM ``phi/kernels/fusion/cutlass/moe_kernel.cu``; the
FlashAttention-2/CUTLASS case study in PAPERS.md is the Pallas
tiling/pipelining exemplar, and "LLM Inference Acceleration via
Efficient Operation Fusion" grounds fusing the bias/activation tail
into the GEMM):

- :func:`grouped_work_map` — per-expert row intervals come in as a
  TRACED ``offsets`` vector (computed from the gate output with a
  handful of O(T) integer ops) and are compiled OUTSIDE the kernel into
  a static-shape work-unit schedule ``(gids, tids, lo, hi)`` that rides
  into the kernel as scalar-prefetch operands — the same pattern as the
  varlen flash kernel's ``varlen_block_map`` (PR 13). A work unit is
  one (expert, row-tile) visit; row tiles shared by two experts get one
  unit per expert, tiles past the last real row get a phantom unit that
  zero-fills them. The schedule's SHAPE is static (``t_pad/bm + 2E +
  1`` units whatever the offsets are), so the grid visits every unit;
  the ones that own no row (``hi <= lo``: phantom tiles, empty experts,
  the trailing inactive units) cost a grid step, not a matmul.
- :func:`grouped_gemm` — the Pallas kernel: grid ``(nb, nwu)`` with the
  unit axis fastest, per-expert ``[K, bn]`` weight blocks streamed
  double-buffered through their BlockSpec (the whole matrix as one
  block while two fit VMEM, else the per-dtype block geometry of
  ``stream_linear``: ``_geometry``), bias add + activation fused on the
  fp32 accumulator in-kernel, and the output tile accumulated across
  the consecutive units that share it (expert-boundary tiles).
- ``custom_vjp`` backward: dx walks the forward map with the per-expert
  weights transposed (the SAME kernel over ``swapaxes(w, 1, 2)``); dw
  accumulates each expert's ``x_rows^T @ dz_rows`` over that expert's
  CONSECUTIVE work units (units are expert-sorted, so the dw output
  block stays resident across them); db is a plain segment-sum.
- Off-TPU the default backend is a math-identical tiled XLA walk that
  visits the same units in the same order with the same fp32
  accumulation — pinned BITWISE-equal to the interpreter-run kernel
  (tests/test_grouped_gemm.py), so CPU CI exercises the exact serving
  numerics.

On top of the kernel, :func:`moe_ffn_nodrop` is the complete no-drop
MoE FFN (fp32 router → stable sort by expert → ragged FFN1/act/FFN2 →
scatter-combine: ZERO capacity padding, ZERO dropped tokens, no
``[T, E, C]`` intermediate anywhere in the trace), and
:func:`moe_ffn_ep` is its expert-parallel twin for the serving mesh —
per-shard token slices exchanged with the expert owners through the
two ``lax.all_to_all`` of the classic EP dispatch/combine (worst-case
per-shard capacity, so EP serving drops nothing either), experts
sharded 1/ep per chip.
"""
from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from ...device import chip as _chip
from ...device.vmem import KERNEL_VMEM_LIMIT_BYTES
from .paged_attention import _enable_x64
from .stream_linear import _apply_activation, _pick_bn

__all__ = [
    "grouped_work_map", "grouped_gemm", "moe_route", "moe_ffn_nodrop",
    "moe_ffn_ep", "DEFAULT_BLOCK_ROWS",
]

#: row-tile height: one MXU-friendly sublane-aligned token block
DEFAULT_BLOCK_ROWS = 128


#: numpy (not jnp) on purpose: this module is imported lazily
#: from inside traced functions, and a module-level jnp constant
#: created under an active trace would leak that tracer
_I0 = np.int32(0)


def _i32(v):
    return jnp.asarray(v, jnp.int32)


def _cdiv(a, b):
    return -(-a // b)


def _resolve_backend(backend: str, geometry_ok: bool) -> str:
    if backend == "auto":
        backend = "pallas" if _chip.on_tpu() else "xla"
    if backend not in ("pallas", "interpret", "xla"):
        raise ValueError(
            f"grouped_gemm backend={backend!r}: expected 'auto', "
            "'pallas', 'interpret' or 'xla'")
    if backend != "xla" and not geometry_ok:
        # ragged shapes (N not a multiple of 128) can't tile — the XLA
        # walk is math-identical, so this is a silent-safe fallback
        backend = "xla"
    return backend


# ---------------------------------------------------------------------
# Work-unit map (traced offsets -> static-shape schedule)
# ---------------------------------------------------------------------

def grouped_work_map(offsets, t_pad: int, bm: int):
    """Compile traced per-expert row offsets into the kernel's
    work-unit schedule.

    ``offsets``: int32 ``[E+1]`` cumulative row offsets of the
    expert-sorted token matrix (``offsets[E]`` = real rows, traced).
    ``t_pad``: static padded row count (multiple of ``bm``).

    Returns ``(gids, tids, lo, hi)``, each int32 ``[nwu]`` with
    ``nwu = t_pad//bm + 2*E + 1`` (static): unit ``u`` computes row
    tile ``tids[u]`` against expert ``gids[u]``'s weights, masked to
    global rows ``[lo[u], hi[u])``. Invariants the kernel relies on:
    ``tids`` is non-decreasing (an output tile's visits are
    consecutive), units are expert-sorted (a dw block's visits are
    consecutive), every real expert has >= 1 unit (its dw block is
    always initialized), every tile has >= 1 unit (pad tiles get a
    phantom unit with an empty mask that zero-fills them), and trailing
    inactive units alias the last tile/expert with empty masks.
    """
    offsets = jnp.asarray(offsets, jnp.int32)
    E = offsets.shape[0] - 1
    nm = t_pad // bm
    nwu = nm + 2 * E + 1
    # E real intervals + 1 phantom interval [offsets[E], t_pad)
    ext = jnp.concatenate(
        [offsets, jnp.asarray([t_pad], jnp.int32)])        # [E+2]
    t_lo = ext[:-1] // bm                                  # [E+1]
    t_hi = _cdiv(ext[1:], bm)
    counts = jnp.maximum(t_hi - t_lo, 0)
    # every REAL expert gets >= 1 (possibly empty-masked) unit so its
    # dw output block is zero-initialized even when it owns no rows
    counts = jnp.where(jnp.arange(E + 1) < E,
                       jnp.maximum(counts, 1), counts)
    starts = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts).astype(jnp.int32)])            # [E+2]
    u = jnp.arange(nwu, dtype=jnp.int32)
    seg = jnp.searchsorted(starts[1:], u, side="right") \
        .astype(jnp.int32)                                 # 0..E+1
    segc = jnp.minimum(seg, E)
    tid = t_lo[segc] + (u - starts[segc])
    active = u < starts[E + 1]
    tid = jnp.clip(jnp.where(active, tid, nm - 1), 0, nm - 1)
    gid = jnp.minimum(segc, E - 1)       # weight index (phantom -> E-1)
    is_real = jnp.logical_and(active, seg < E)
    lo = jnp.where(is_real, ext[segc], 0)
    hi = jnp.where(is_real, ext[segc + 1], 0)
    return (gid.astype(jnp.int32), tid.astype(jnp.int32),
            lo.astype(jnp.int32), hi.astype(jnp.int32))


# ---------------------------------------------------------------------
# Kernels (Pallas; interpret=True is the off-TPU debug path)
# ---------------------------------------------------------------------

def _grouped_fwd_pallas(x_pad, w3, b3, gids, tids, lo, hi, bm, bn,
                        activation, interpret):
    """x_pad [t_pad, K] (rows sorted by expert, zero pad tail),
    w3 [E, K, N], b3 [E, 1, N] f32. Returns [t_pad, N] f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_pad, K = x_pad.shape
    N = w3.shape[-1]
    nb = N // bn
    nwu = gids.shape[0]

    def kernel(gids_r, tids_r, lo_r, hi_r, xt_r, x_ref, w_ref, b_ref,
               o_ref):
        u = pl.program_id(1)
        first = jnp.logical_or(
            u == 0, tids_r[jnp.maximum(u - 1, 0)] != tids_r[u])

        # every tile's first visit zero-fills it, live or not: rows past
        # offsets[E] must read exact zeros
        @pl.when(first)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        # a unit that owns no row added an exact +0.0 to every row:
        # skipping it leaves every output bit where it was
        @pl.when(hi_r[u] > lo_r[u])
        def _():
            rows = tids_r[u] * bm \
                + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
            acc = jax.lax.dot_general(
                x_ref[...], w_ref[0].astype(x_ref.dtype),
                (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)        # [bm, bn]
            acc = acc + b_ref[0].astype(jnp.float32)
            acc = _apply_activation(acc, activation)
            mask = jnp.logical_and(rows >= lo_r[u], rows < hi_r[u])
            o_ref[...] += jnp.where(mask, acc, jnp.float32(0.0))

    # x tiles past the last live row are never read, so never fetched:
    # their units' x block index repeats the last live tile's (their
    # weight block index already repeats the last expert's)
    xt = jnp.minimum(tids, jnp.maximum(jnp.max(hi) - 1, 0) // bm)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(nb, nwu),
        in_specs=[
            pl.BlockSpec((bm, K), lambda j, u, g, t, lo_, hi_, xt_:
                         (xt_[u], 0)),
            pl.BlockSpec((1, K, bn), lambda j, u, g, t, lo_, hi_, xt_:
                         (g[u], 0, j)),
            pl.BlockSpec((1, 1, bn), lambda j, u, g, t, lo_, hi_, xt_:
                         (g[u], 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda j, u, g, t, lo_, hi_, xt_:
                               (t[u], j)),
        scratch_shapes=[])
    with _enable_x64(False), jax.named_scope("pt_grouped_gemm_fwd"):
        out = pl.pallas_call(
            kernel,
            name="pt_grouped_gemm_fwd",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((t_pad, N), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(gids, tids, lo, hi, xt, x_pad, w3, b3)
    return out


def _grouped_fwd_xla(x_pad, w3, b3, gids, tids, lo, hi, bm, bn,
                     activation):
    """Math-identical tiled XLA walk: the SAME (bm, K) x (K, bn) dots
    over the SAME units in the same order, fp32 accumulation from a
    zero output — bitwise-equal to the interpreter-run kernel (a unit
    that owns no row is skipped here as there: it would add an exact
    +0.0 to every row)."""
    t_pad, K = x_pad.shape
    E, _, N = w3.shape
    nb = N // bn
    nwu = gids.shape[0]
    rows_in_tile = jnp.arange(bm, dtype=jnp.int32)[:, None]

    def live_unit(u, out):
        tid = tids[u]
        gid = gids[u]
        xt = jax.lax.dynamic_slice(x_pad, (_i32(tid * bm), _I0), (bm, K))
        rows = tid * bm + rows_in_tile
        mask = jnp.logical_and(rows >= lo[u], rows < hi[u])

        def col(j, out):
            wb = jax.lax.dynamic_slice(
                w3, (gid, _I0, _i32(j * bn)), (1, K, bn))[0]
            acc = jax.lax.dot_general(
                xt, wb.astype(xt.dtype), (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)
            acc = acc + jax.lax.dynamic_slice(
                b3, (gid, _I0, _i32(j * bn)), (1, 1, bn))[0].astype(jnp.float32)
            acc = _apply_activation(acc, activation)
            contrib = jnp.where(mask, acc, jnp.float32(0.0))
            cur = jax.lax.dynamic_slice(
                out, (_i32(tid * bm), _i32(j * bn)), (bm, bn))
            return jax.lax.dynamic_update_slice(
                out, cur + contrib, (_i32(tid * bm), _i32(j * bn)))

        return jax.lax.fori_loop(0, nb, col, out)

    def unit(u, out):
        return jax.lax.cond(hi[u] > lo[u], live_unit,
                            lambda u, out: out, u, out)

    out0 = jnp.zeros((t_pad, N), jnp.float32)
    return jax.lax.fori_loop(0, nwu, unit, out0)


def _grouped_dw_pallas(x_pad, dz_pad, gids, tids, lo, hi, bm, bn,
                       interpret):
    """dw[e] = sum over e's rows of x_r^T dz_r. Units are expert-sorted,
    so each expert's [K, bn] output block stays resident across its
    consecutive units; the first unit of each expert zero-initializes
    it (grouped_work_map guarantees every expert has one)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_pad, K = x_pad.shape
    N = dz_pad.shape[-1]
    nb = N // bn
    nwu = gids.shape[0]

    def kernel(gids_r, tids_r, lo_r, hi_r, x_ref, dz_ref, o_ref):
        u = pl.program_id(1)
        first = jnp.logical_or(
            u == 0, gids_r[jnp.maximum(u - 1, 0)] != gids_r[u])

        @pl.when(first)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        # as in the forward kernel: a unit without rows adds nothing
        @pl.when(hi_r[u] > lo_r[u])
        def _():
            rows = tids_r[u] * bm \
                + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
            mask = jnp.logical_and(rows >= lo_r[u], rows < hi_r[u])
            xm = jnp.where(mask, x_ref[...], jnp.zeros_like(x_ref))
            contrib = jax.lax.dot_general(
                xm, dz_ref[...], (((0,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)        # [K, bn]
            o_ref[...] += contrib[None]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nb, nwu),
        in_specs=[
            pl.BlockSpec((bm, K), lambda j, u, g, t, lo_, hi_: (t[u], 0)),
            pl.BlockSpec((bm, bn), lambda j, u, g, t, lo_, hi_: (t[u], j)),
        ],
        out_specs=pl.BlockSpec((1, K, bn),
                               lambda j, u, g, t, lo_, hi_: (g[u], 0, j)),
        scratch_shapes=[])
    return grid_spec, kernel


def _grouped_dw(x_pad, dz_pad, E, gids, tids, lo, hi, bm, bn, backend):
    """Dispatch the dw accumulation (kernel or the identical XLA walk);
    returns [E, K, N] f32."""
    t_pad, K = x_pad.shape
    N = dz_pad.shape[-1]
    if backend == "xla":
        nb = N // bn
        nwu = gids.shape[0]
        rows_in_tile = jnp.arange(bm, dtype=jnp.int32)[:, None]

        def live_unit(u, dw):
            tid = tids[u]
            gid = gids[u]
            xt = jax.lax.dynamic_slice(x_pad, (_i32(tid * bm), _I0), (bm, K))
            rows = tid * bm + rows_in_tile
            mask = jnp.logical_and(rows >= lo[u], rows < hi[u])
            xm = jnp.where(mask, xt, jnp.zeros_like(xt))

            def col(j, dw):
                dzb = jax.lax.dynamic_slice(
                    dz_pad, (_i32(tid * bm), _i32(j * bn)), (bm, bn))
                contrib = jax.lax.dot_general(
                    xm, dzb, (((0,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)
                cur = jax.lax.dynamic_slice(
                    dw, (gid, _I0, _i32(j * bn)), (1, K, bn))
                return jax.lax.dynamic_update_slice(
                    dw, cur + contrib[None], (gid, _I0, _i32(j * bn)))

            return jax.lax.fori_loop(0, nb, col, dw)

        def unit(u, dw):
            return jax.lax.cond(hi[u] > lo[u], live_unit,
                                lambda u, dw: dw, u, dw)

        dw0 = jnp.zeros((E, K, N), jnp.float32)
        return jax.lax.fori_loop(0, nwu, unit, dw0)

    from jax.experimental import pallas as pl

    grid_spec, kernel = _grouped_dw_pallas(
        x_pad, dz_pad, gids, tids, lo, hi, bm, bn,
        interpret=(backend == "interpret"))
    from jax.experimental.pallas import tpu as pltpu

    with _enable_x64(False), jax.named_scope("pt_grouped_gemm_dw"):
        return pl.pallas_call(
            kernel,
            name="pt_grouped_gemm_dw",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((E, K, N), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=(backend == "interpret" or not _chip.on_tpu()),
        )(gids, tids, lo, hi, x_pad, dz_pad)


# ---------------------------------------------------------------------
# Public entry (custom_vjp)
# ---------------------------------------------------------------------

#: an expert matrix up to this size is ONE column block. Every column
#: block walks the whole static schedule again (a grid step a unit, the
#: dead ones too) and fetches every x tile again, so fewer blocks win
#: while two of them sit in VMEM beside the tiles (kernel alone on a
#: v5e, PR 34: ``[4096, 1536]`` bf16 as one block 0.75 ms, as two of 768
#: 0.78, as four 0.79; the row tile moved nothing: 64, 128, 256 within 1%)
_WHOLE_MATRIX_BYTES = 16 << 20


def _geometry(K: int, N: int, itemsize: int):
    """(bm, bn) for the kernel path, or None when N can't tile."""
    if N % 128 == 0 and K * N * itemsize <= _WHOLE_MATRIX_BYTES:
        return DEFAULT_BLOCK_ROWS, N
    bn = _pick_bn(K, N, itemsize)
    return (DEFAULT_BLOCK_ROWS, bn) if bn else None


def _pad_rows(x, t_pad):
    t = x.shape[0]
    if t == t_pad:
        return x
    return jnp.pad(x, ((0, t_pad - t), (0, 0)))


def _raw_grouped(x, w, b, offsets, activation, backend, first_group=0):
    """One ragged grouped GEMM (no autodiff): the f32 output [T, N] and
    int32 ``[2]``, the (unit, column block) grid steps its schedule
    walks and those of them that own a row. The ``len(offsets) - 1``
    groups are rows ``first_group ..`` of the bank ``w`` (0 and the
    whole bank, except through ``grouped_gemm_banked``)."""
    T, K = x.shape
    E, _, N = w.shape
    geo = _geometry(K, N, w.dtype.itemsize)
    backend = _resolve_backend(backend, geo is not None)
    if backend == "xla" and geo is None:
        # un-tileable shapes: same unit walk with bn = N (one column
        # block); bm stays the row tile so the unit schedule is shared
        geo = (DEFAULT_BLOCK_ROWS, N)
    bm, bn = geo
    t_pad = _cdiv(T, bm) * bm
    x_pad = _pad_rows(x, t_pad)
    b3 = b.reshape(E, 1, N).astype(jnp.float32)
    gids, tids, lo, hi = grouped_work_map(offsets, t_pad, bm)
    if first_group:
        gids = gids + jnp.int32(first_group)
    if backend == "xla":
        out = _grouped_fwd_xla(x_pad, w, b3, gids, tids, lo, hi,
                               bm, bn, activation)
    else:
        out = _grouped_fwd_pallas(
            x_pad, w, b3, gids, tids, lo, hi, bm, bn, activation,
            interpret=(backend == "interpret" or not _chip.on_tpu()))
    units = (N // bn) * jnp.stack(
        [jnp.int32(gids.shape[0]), jnp.sum(hi > lo, dtype=jnp.int32)])
    return out[:T], units


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _grouped_core(x, w, b, offsets, activation, backend, out_dtype):
    y, _ = _grouped_core_fwd(x, w, b, offsets, activation, backend,
                             out_dtype)
    return y


def _grouped_core_fwd(x, w, b, offsets, activation, backend, out_dtype):
    y, _ = _raw_grouped(x, w, b, offsets, activation, backend)
    return y.astype(out_dtype), (x, w, b, offsets)


def _act_fn(activation):
    if activation == "gelu":
        return jax.nn.gelu
    if activation == "relu":
        return jax.nn.relu
    return lambda z: z


def _grouped_core_bwd(activation, backend, out_dtype, res, g):
    x, w, b, offsets = res
    T, K = x.shape
    E, _, N = w.shape
    # tpu-lint: ok(X-PROMOTE) -- fp32 grad accumulation by design
    g32 = g.astype(jnp.float32)
    if activation:
        # recompute the pre-activation with one more grouped GEMM
        # (cheaper than carrying the [T, N] residual through fwd)
        z, _ = _raw_grouped(x, w, b, offsets, None, backend)
        _, act_vjp = jax.vjp(_act_fn(activation), z)
        (dz,) = act_vjp(g32)
    else:
        dz = g32
    # dx walks the forward map against the per-expert transposed bank
    zero_bk = jnp.zeros((E, K), jnp.float32)
    dx, _ = _raw_grouped(dz, jnp.swapaxes(w, 1, 2), zero_bk, offsets,
                         None, backend)
    # dw accumulates per expert segment (expert-sorted units)
    geo = _geometry(K, N, w.dtype.itemsize)
    dwb = _resolve_backend(backend, geo is not None)
    bm, bn = geo if geo is not None else (DEFAULT_BLOCK_ROWS, N)
    t_pad = _cdiv(T, bm) * bm
    gids, tids, lo, hi = grouped_work_map(offsets, t_pad, bm)
    dw = _grouped_dw(_pad_rows(x, t_pad), _pad_rows(dz, t_pad), E,
                     gids, tids, lo, hi, bm, bn, dwb)
    # db: plain per-expert segment sum of dz (rows are expert-sorted)
    row_e = jnp.clip(
        jnp.searchsorted(offsets[1:], jnp.arange(T, dtype=jnp.int32),
                         side="right"), 0, E - 1)
    live = (jnp.arange(T, dtype=jnp.int32)
            < offsets[-1])[:, None].astype(jnp.float32)
    db = jax.ops.segment_sum(dz * live, row_e, num_segments=E)
    return (dx.astype(x.dtype), dw.astype(w.dtype), db.astype(b.dtype),
            None)


def _grouped_core_fwd_rule(x, w, b, offsets, activation, backend,
                           out_dtype):
    return _grouped_core_fwd(x, w, b, offsets, activation, backend,
                             out_dtype)


_grouped_core.defvjp(_grouped_core_fwd_rule, _grouped_core_bwd)


def grouped_gemm(x, w, offsets, *, bias=None, activation=None,
                 out_dtype=None, backend="auto"):
    """Ragged grouped GEMM: ``y[r] = act(x[r] @ w[e(r)] + bias[e(r)])``
    where row ``r``'s expert ``e(r)`` is defined by the sorted-segment
    ``offsets``.

    ``x``: ``[T, K]`` rows SORTED by expert (expert e owns rows
    ``offsets[e]:offsets[e+1]``); ``w``: ``[E, K, N]`` expert bank;
    ``offsets``: int32 ``[E+1]`` TRACED cumulative offsets
    (``offsets[E] <= T``; rows past ``offsets[E]`` produce zeros);
    ``bias``: optional ``[E, N]``. Differentiable in x/w/bias via a
    custom_vjp whose backward walks the same work map. ``backend``:
    ``auto`` (Pallas on TPU, XLA tile walk elsewhere), ``pallas``,
    ``interpret``, ``xla``.
    """
    E, _, N = w.shape
    if offsets.shape[0] != E + 1:
        raise ValueError(
            f"grouped_gemm: offsets has {offsets.shape[0]} entries for "
            f"{E} experts (need E+1)")
    b = bias if bias is not None else jnp.zeros((E, N), jnp.float32)
    if b.ndim == 3:
        b = b.reshape(E, N)
    out_dtype = out_dtype or x.dtype
    return _grouped_core(x, w, b, jnp.asarray(offsets, jnp.int32),
                         activation, backend, out_dtype)


def grouped_gemm_banked(x, bank, offsets, first_group: int, *,
                        backend="auto"):
    """The serving form over a LAYER-STACKED bank read in place:
    ``bank [G, K, N]`` holds every layer's experts back to back
    (``stack.reshape(L * E, K, N)``: no copy) and this call's ``E =
    len(offsets) - 1`` groups are its rows ``first_group ..
    first_group + E - 1`` — the kernel's weight block index is shifted,
    so no per-layer slice of the bank is ever materialised (a slice
    handed to a Pallas call is copied: 432 MB a layer at granite's
    widths). No bias, no activation, no autodiff. Returns float32 ``[T,
    N]`` and int32 ``[2]``: the (unit, column block) grid steps the
    schedule walked and those that owned a row."""
    G, _, N = bank.shape
    if offsets.shape[0] - 1 + int(first_group) > G:
        raise ValueError(
            f"grouped_gemm_banked: groups {first_group}.."
            f"{first_group + offsets.shape[0] - 2} exceed the bank's {G}")
    return _raw_grouped(x, bank, jnp.zeros((G, N), jnp.float32),
                        jnp.asarray(offsets, jnp.int32), None, backend,
                        first_group=int(first_group))


# ---------------------------------------------------------------------
# No-drop MoE FFN (sort -> ragged FFN1/act/FFN2 -> scatter-combine)
# ---------------------------------------------------------------------

def moe_route(x, gate_w, top_k: int):
    """fp32 gate routing: softmax, top-k and the top-k renormalization
    all run in fp32 REGARDLESS of the compute dtype — under AMP a bf16
    router rounds away top-k margins (ties flip expert choice) and a
    bf16 renormalization drifts the combine weights; the router is
    O(T·E), so fp32 here is free next to the expert GEMMs.

    Returns ``(probs [T, E] f32, topk_val [T, K] f32 normalized,
    topk_idx [T, K] int32)``.
    """
    # top-k tie stability under AMP; see the bf16-vs-fp32 parity test
    # tpu-lint: ok(X-PROMOTE) -- fp32 gate routing by design
    logits = jax.lax.dot_general(
        x.astype(jnp.float32), gate_w.astype(jnp.float32),
        (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, -1)
    topk_val, topk_idx = jax.lax.top_k(probs, top_k)
    topk_val = topk_val / jnp.sum(topk_val, -1, keepdims=True)
    return probs, topk_val, topk_idx.astype(jnp.int32)


def _sort_by_expert(topk_idx, E: int):
    """(order [T*K], offsets [E+1], counts [E]) for the expert-sorted
    row layout; ``order`` is a STABLE argsort so same-expert tokens
    keep their batch order (deterministic accumulation)."""
    flat_e = topk_idx.reshape(-1)
    order = jnp.argsort(flat_e, stable=True).astype(jnp.int32)
    counts = jnp.bincount(flat_e, length=E).astype(jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts).astype(jnp.int32)])
    return order, offsets, counts


def moe_ffn_nodrop(x, gate_w, w1, b1, w2, b2, *, top_k: int,
                   activation="gelu", backend="auto"):
    """No-drop MoE FFN over flat tokens ``x [T, d]``.

    fp32 router -> tokens stable-sorted by expert id -> TWO ragged
    grouped GEMMs (FFN1 with the activation fused, FFN2) -> unsort +
    gate-weighted combine. Zero capacity padding, zero dropped tokens,
    and no ``[T, E, capacity]`` intermediate exists in the traced
    program (the trace-pin test walks the jaxpr).

    ``w1 [E, d, dff]``, ``b1 [E, dff]`` (or ``[E, 1, dff]``),
    ``w2 [E, dff, d]``, ``b2`` likewise. Returns
    ``(y [T, d] in x.dtype, probs f32, topk_idx, counts [E] int32)`` —
    the extras feed the aux loss and the ``moe.*`` telemetry.
    """
    T, d = x.shape
    E = w1.shape[0]
    probs, topk_val, topk_idx = moe_route(x, gate_w, top_k)
    order, offsets, counts = _sort_by_expert(topk_idx, E)
    # row r of the sorted matrix is token order[r] // K
    x_rows = jnp.take(x, order // top_k, axis=0)           # [T*K, d]
    h = grouped_gemm(x_rows, w1, offsets, bias=b1,
                     activation=activation, backend=backend,
                     out_dtype=x.dtype)
    y_rows = grouped_gemm(h, w2, offsets, bias=b2, backend=backend,
                          out_dtype=jnp.float32)
    # combine: unsort the expert outputs, weight by the normalized
    # gate values, sum the K contributions per token
    y_flat = jnp.zeros((T * top_k, d), jnp.float32) \
        .at[order].set(y_rows)
    y = jnp.sum(y_flat.reshape(T, top_k, d)
                * topk_val[..., None], axis=1)
    return y.astype(x.dtype), probs, topk_idx, counts


# ---------------------------------------------------------------------
# Expert-parallel MoE FFN (inside shard_map over the ep mesh axis)
# ---------------------------------------------------------------------

def moe_ffn_ep(x, gate_w, w1, b1, w2, b2, *, top_k: int, axis: str,
               ep: int, activation="gelu", overlap=None):
    """Expert-parallel MoE FFN for the serving mesh — call INSIDE a
    ``shard_map`` body whose mesh carries the ``axis`` (ep) axis.

    ``x [T, d]`` enters REPLICATED (the serving hidden state); each
    shard slices its ``T/ep`` token block, routes it in fp32, scatters
    the rows into per-expert slot buffers with WORST-CASE per-shard
    capacity ``(T/ep)*K`` (so nothing can ever drop), and exchanges
    with the expert owners through the classic EP pair:

      ``[E, c, d] --all_to_all--> [E/ep, ep*c, d]`` (dispatch)
      local expert FFN (this shard's 1/ep expert slice — the only
      expert weights this chip ever streams)
      ``[E/ep, ep*c, d] --all_to_all--> [E, c, d]`` (combine)

    followed by one ``all_gather`` that restores the replicated hidden
    state for the next layer. The traced collective census of one MoE
    layer is therefore EXACTLY (all_to_all, all_to_all, all_gather) —
    pinned by the EP decode tests and the dryrun_multichip phase.

    ``w1 [E/ep, d, dff]`` etc. are this shard's expert slice (sharded
    by ``TPContext.shard_stack``). Returns ``y [T, d]`` replicated.

    ``overlap`` (default: ``FLAGS_ep_overlap``): double-buffer the
    exchange — the capacity dim splits into two half buffers, BOTH
    dispatch all_to_alls issue before the first expert FFN so buffer
    1's exchange rides under buffer 0's compute, and buffer 0's
    combine issues before buffer 1's FFN. Math-exact (per-slot-row
    GEMMs are independent, halves concatenate back along capacity);
    the census becomes EXACTLY (all_to_all x4, all_gather). Falls
    back to the single-buffer form when the capacity is odd.
    """
    T, d = x.shape
    e_loc = w1.shape[0]
    E = e_loc * ep
    if T % ep:
        raise ValueError(
            f"moe_ffn_ep: {T} tokens not divisible by ep={ep}")
    tl = T // ep
    r = jax.lax.axis_index(axis)
    x_loc = jax.lax.dynamic_slice_in_dim(x, r * tl, tl, 0)
    _, topk_val, topk_idx = moe_route(x_loc, gate_w, top_k)
    order, offsets, _counts = _sort_by_expert(topk_idx, E)
    c = tl * top_k                       # worst case: zero drops
    flat_sorted = jnp.take(topk_idx.reshape(-1), order)
    pos = jnp.arange(tl * top_k, dtype=jnp.int32) \
        - offsets[flat_sorted]
    slot = flat_sorted * c + pos
    x_rows = jnp.take(x_loc, order // top_k, axis=0)
    buf = jnp.zeros((E * c, d), x.dtype).at[slot].set(x_rows) \
        .reshape(E, c, d)
    if overlap is None:
        from ...core.flags import flag
        overlap = bool(flag("ep_overlap"))

    def dispatch(bh):
        # rows for MY experts from every shard, capacities
        # concatenated (the exchange is an all-to-all, not a reduce)
        return jax.lax.all_to_all(bh, axis, split_axis=0,
                                  concat_axis=1, tiled=True)

    def expert_ffn(recv):
        # tpu-lint: ok(X-PROMOTE) -- fp32 expert-GEMM accumulation
        # matches the grouped kernel's accumulator
        h1 = jax.lax.dot_general(
            recv, w1.astype(recv.dtype), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        h1 = _apply_activation(h1 + b1.reshape(e_loc, 1, -1)
                               .astype(jnp.float32), activation) \
            .astype(x.dtype)
        out = jax.lax.dot_general(
            h1, w2.astype(h1.dtype), (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        return out + b2.reshape(e_loc, 1, -1).astype(jnp.float32)

    def combine(out):
        # reverse exchange back to the token owners
        return jax.lax.all_to_all(out.astype(jnp.float32), axis,
                                  split_axis=1, concat_axis=0,
                                  tiled=True)

    if overlap and c % 2 == 0 and c >= 2:
        from ...profiler import stats as _ep_stats
        _ep_stats.counter("dist.overlap_ep_double_buffer").inc()
        half = c // 2
        # BOTH dispatches issue before the first FFN (buffer 1's
        # exchange rides under buffer 0's compute), and buffer 0's
        # combine issues before buffer 1's FFN — XLA's async collective
        # scheduler overlaps the dataflow-independent pairs
        r0 = dispatch(buf[:, :half])
        r1 = dispatch(buf[:, half:])
        back0 = combine(expert_ffn(r0))
        back1 = combine(expert_ffn(r1))
        back = jnp.concatenate([back0, back1], axis=1)
    else:
        back = combine(expert_ffn(dispatch(buf)))
    y_rows = jnp.take(back.reshape(E * c, d), slot, axis=0)
    y_flat = jnp.zeros((tl * top_k, d), jnp.float32) \
        .at[order].set(y_rows)
    y_loc = jnp.sum(y_flat.reshape(tl, top_k, d)
                    * topk_val[..., None], axis=1)
    # restore the replicated hidden state for the next layer
    y = jax.lax.all_gather(y_loc.astype(x.dtype), axis, axis=0,
                           tiled=True)                   # [T, d]
    return y

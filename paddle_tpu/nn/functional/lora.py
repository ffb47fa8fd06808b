"""Ragged batched-LoRA delta GEMM (ISSUE 18 tentpole kernel).

Batched multi-LoRA serving is the MoE grouped-GEMM problem with a
different bank: K tenants share one base weight stream, and each
token's low-rank delta ``x @ A[s] @ B[s]`` is a ragged grouped matmul
over tokens SORTED BY ADAPTER SLOT — exactly how ``grouped_gemm``
groups tokens by expert (S-LoRA's batched-adapter insight, folded onto
this repo's PR 15 kernel family). This module reuses that machinery
wholesale:

- :func:`sort_by_adapter` mirrors the MoE ``_sort_by_expert``: a
  STABLE argsort of the chunk's per-token adapter-slot ids, except
  BASE-MODEL tokens (slot < 0) sort past every adapter and land after
  ``offsets[-1]`` — the work map already zero-fills rows past the last
  real offset, so base tokens are skipped by construction, not by a
  branch (mixed base+adapter batches cost nothing extra).
- :func:`lora_delta` is ONE ragged launch computing every adapter's
  ``x·A·B`` for all tokens in the chunk: the traced ``offsets`` vector
  compiles into the same static-shape scalar-prefetched work-unit
  schedule (``grouped_work_map``), the grid visits only row tiles with
  live rows, and each unit chains TWO dots — ``[bm, K] x [K, R]`` down
  to the rank, ``[bm, R] x [R, bn]`` back up — with fp32 accumulation
  throughout. Per-adapter dispatch never exists in the trace: adapter
  membership rides the work map, so the compiled-program count is
  independent of which adapters are loaded.
- Ranks are padded to the weight dtype's SUBLANE TILE
  (:func:`pad_rank` — int8: 32, bf16: 16, f32: 8) when the bank is
  built (serving/adapters.py), so the ``[K, R]`` / ``[R, bn]`` blocks
  tile cleanly; padded rank columns are zero and contribute exact
  +0.0.

Off-TPU the default backend is a math-identical tiled XLA walk over
the same units in the same order (the ``grouped_gemm`` discipline), so
CPU CI pins the serving numerics bitwise against the interpreter-run
kernel (tests/test_lora_adapters.py). Inference-only: no custom_vjp —
adapters are served, not trained, here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...device import chip as _chip
from ...device.vmem import KERNEL_VMEM_LIMIT_BYTES
from .grouped_gemm import (_I0, _cdiv, _geometry, _i32,
                           _pad_rows, _resolve_backend,
                           DEFAULT_BLOCK_ROWS, grouped_work_map)
from .paged_attention import _enable_x64
from .stream_linear import _INT8_SUBLANES, _SUBLANES

__all__ = ["lora_delta", "sort_by_adapter", "inverse_order",
           "pad_rank"]


def pad_rank(rank: int, dtype) -> int:
    """LoRA rank padded up to ``dtype``'s sublane tile (int8: 32,
    bf16: 16, f32: 8) — the bank stores ``[K, R_pad]`` / ``[R_pad, N]``
    so the delta kernel's rank axis tiles cleanly; the padded columns
    are zero and contribute exact +0.0 to the delta."""
    it = jnp.dtype(dtype).itemsize
    sub = _INT8_SUBLANES if it == 1 else _SUBLANES.get(it, 8)
    return _cdiv(int(rank), sub) * sub


def sort_by_adapter(slot_ids, n_slots: int):
    """(order [T], offsets [S+1], counts [S]) for the adapter-sorted
    row layout of one chunk.

    ``slot_ids``: int32 ``[T]`` per-token adapter SLOT index into the
    bank (traced); ``< 0`` (or out of range) marks a BASE-MODEL token.
    ``order`` is a STABLE argsort so same-adapter tokens keep their
    batch order; base tokens sort to the TAIL, past ``offsets[-1]``,
    where :func:`lora_delta`'s work map zero-fills — base tokens are
    skipped without a branch in the trace.
    """
    flat = jnp.asarray(slot_ids, jnp.int32).reshape(-1)
    key = jnp.where(jnp.logical_or(flat < 0, flat >= n_slots),
                    _i32(n_slots), flat)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    counts = jnp.bincount(key, length=n_slots + 1)[:n_slots] \
        .astype(jnp.int32)
    offsets = jnp.concatenate(
        [jnp.zeros((1,), jnp.int32),
         jnp.cumsum(counts).astype(jnp.int32)])
    return order, offsets, counts


def inverse_order(order):
    """Inverse permutation: ``inv[order[r]] = r`` — unsorts the delta
    rows back to batch order with one gather."""
    T = order.shape[0]
    return jnp.zeros((T,), jnp.int32).at[order].set(
        jnp.arange(T, dtype=jnp.int32))


# ---------------------------------------------------------------------
# Kernels (Pallas; interpret=True is the off-TPU debug path)
# ---------------------------------------------------------------------

def _lora_fwd_pallas(x_pad, a3, b3, gids, tids, lo, hi, bm, bn,
                     interpret):
    """x_pad [t_pad, K] (rows sorted by adapter, base/pad tail),
    a3 [S, K, R], b3 [S, R, N]. Returns [t_pad, N] f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    t_pad, K = x_pad.shape
    S, _, R = a3.shape
    N = b3.shape[-1]
    nb = N // bn
    nwu = gids.shape[0]

    def kernel(gids_r, tids_r, lo_r, hi_r, x_ref, a_ref, b_ref, o_ref):
        u = pl.program_id(1)
        rows = tids_r[u] * bm \
            + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)
        # down to the rank, back up — both dots accumulate fp32
        h = jax.lax.dot_general(
            x_ref[...], a_ref[0].astype(x_ref.dtype),
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)            # [bm, R]
        acc = jax.lax.dot_general(
            h, b_ref[0].astype(jnp.float32),
            (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)            # [bm, bn]
        mask = jnp.logical_and(rows >= lo_r[u], rows < hi_r[u])
        contrib = jnp.where(mask, acc, jnp.float32(0.0))
        first = jnp.logical_or(
            u == 0, tids_r[jnp.maximum(u - 1, 0)] != tids_r[u])

        @pl.when(first)
        def _():
            o_ref[...] = jnp.zeros_like(o_ref)

        o_ref[...] += contrib

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nb, nwu),
        in_specs=[
            pl.BlockSpec((bm, K), lambda j, u, g, t, lo_, hi_: (t[u], 0)),
            pl.BlockSpec((1, K, R),
                         lambda j, u, g, t, lo_, hi_: (g[u], 0, 0)),
            pl.BlockSpec((1, R, bn),
                         lambda j, u, g, t, lo_, hi_: (g[u], 0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn),
                               lambda j, u, g, t, lo_, hi_: (t[u], j)),
        scratch_shapes=[])
    with _enable_x64(False), jax.named_scope("pt_lora_delta"):
        out = pl.pallas_call(
            kernel,
            name="pt_lora_delta",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((t_pad, N), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(gids, tids, lo, hi, x_pad, a3, b3)
    return out


def _lora_fwd_xla(x_pad, a3, b3, gids, tids, lo, hi, bm, bn):
    """Math-identical tiled XLA walk: the SAME chained
    (bm, K) x (K, R), (bm, R) x (R, bn) dots over the SAME units in
    the same order, fp32 accumulation from a zero output — bitwise-
    equal to the interpreter-run kernel."""
    t_pad, K = x_pad.shape
    S, _, R = a3.shape
    N = b3.shape[-1]
    nb = N // bn
    nwu = gids.shape[0]
    rows_in_tile = jnp.arange(bm, dtype=jnp.int32)[:, None]

    def unit(u, out):
        tid = tids[u]
        gid = gids[u]
        xt = jax.lax.dynamic_slice(x_pad, (_i32(tid * bm), _I0), (bm, K))
        ag = jax.lax.dynamic_slice(a3, (gid, _I0, _I0), (1, K, R))[0]
        h = jax.lax.dot_general(
            xt, ag.astype(xt.dtype), (((1,), (0,)), ((), ())),
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)
        rows = tid * bm + rows_in_tile
        mask = jnp.logical_and(rows >= lo[u], rows < hi[u])

        def col(j, out):
            bb = jax.lax.dynamic_slice(
                b3, (gid, _I0, _i32(j * bn)), (1, R, bn))[0]
            # fp32 rank-space delta: h is the fp32 down-projection and
            # B rides up at fp32 so the delta adds exactly onto the base
            # projection's fp32 accumulator.
            # tpu-lint: ok(X-PROMOTE) -- rank-thin [bm,R]x[R,bn] dot: upcast traffic is R/K-th of a base-weight stream
            acc = jax.lax.dot_general(
                h, bb.astype(jnp.float32), (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)
            contrib = jnp.where(mask, acc, jnp.float32(0.0))
            cur = jax.lax.dynamic_slice(
                out, (_i32(tid * bm), _i32(j * bn)), (bm, bn))
            return jax.lax.dynamic_update_slice(
                out, cur + contrib, (_i32(tid * bm), _i32(j * bn)))

        return jax.lax.fori_loop(0, nb, col, out)

    out0 = jnp.zeros((t_pad, N), jnp.float32)
    return jax.lax.fori_loop(0, nwu, unit, out0)


# ---------------------------------------------------------------------
# Public entry
# ---------------------------------------------------------------------

def lora_delta(x, a, b, offsets, *, out_dtype=None, backend="auto"):
    """ONE ragged grouped launch: ``delta[r] = x[r] @ a[s(r)] @ b[s(r)]``
    for every adapter in the bank, where row ``r``'s adapter ``s(r)``
    is defined by the sorted-segment ``offsets``.

    ``x``: ``[T, K]`` rows SORTED by adapter slot
    (:func:`sort_by_adapter`; slot s owns rows
    ``offsets[s]:offsets[s+1]``); ``a``: ``[S, K, R]`` down-projection
    bank; ``b``: ``[S, R, N]`` up-projection bank (adapter scaling
    ``alpha/r`` folded into ``b`` at load); ``offsets``: int32
    ``[S+1]`` TRACED cumulative offsets — rows past ``offsets[S]``
    (base-model tokens, pad) produce ZERO delta. Returns ``[T, N]`` in
    ``out_dtype`` (default fp32, for adding onto the base projection's
    fp32 accumulator). ``backend``: ``auto`` (Pallas on TPU, XLA tile
    walk elsewhere), ``pallas``, ``interpret``, ``xla``.
    """
    T, K = x.shape
    S, _, R = a.shape
    N = b.shape[-1]
    if offsets.shape[0] != S + 1:
        raise ValueError(
            f"lora_delta: offsets has {offsets.shape[0]} entries for "
            f"{S} adapter slots (need S+1)")
    if b.shape[0] != S or b.shape[1] != R:
        raise ValueError(
            f"lora_delta: bank mismatch a={a.shape} vs b={b.shape} "
            "(need a [S, K, R], b [S, R, N])")
    geo = _geometry(K, N, b.dtype.itemsize)
    backend = _resolve_backend(backend, geo is not None)
    if backend == "xla" and geo is None:
        geo = (DEFAULT_BLOCK_ROWS, N)
    bm, bn = geo
    t_pad = _cdiv(T, bm) * bm
    x_pad = _pad_rows(x, t_pad)
    gids, tids, lo, hi = grouped_work_map(
        jnp.asarray(offsets, jnp.int32), t_pad, bm)
    if backend == "xla":
        out = _lora_fwd_xla(x_pad, a, b, gids, tids, lo, hi, bm, bn)
    else:
        out = _lora_fwd_pallas(
            x_pad, a, b, gids, tids, lo, hi, bm, bn,
            interpret=(backend == "interpret" or not _chip.on_tpu()))
    out = out[:T]
    return out if out_dtype is None else out.astype(out_dtype)

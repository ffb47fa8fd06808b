"""Segment-aware block-skipping varlen flash attention (ROADMAP item 3).

One kernel family serves the repo's three variable-length attention
customers:

- **Packed training** (``flash_attn_unpadded``): a packed batch
  ``[total_tokens, heads, d]`` whose segment boundaries are
  ``cu_seqlens`` offsets. The old path materialized a dense
  ``[h, total_q, total_k]`` mask+logits tensor — O(T²) memory, unusable
  at real packed batch sizes.
- **Chunked prefill** (``FusedMultiTransformer.prefill_chunk_raw``) and
  the speculative-verify window (``serve.verify``): a chunk of queries
  attending to the paged KV pool. The old path round-tripped a dense
  token-major ``gather_kv_pages`` copy of every cached page per chunk —
  O(S) extra HBM writes+reads per chunk per layer.

Design (the FlashAttention-2/CUTLASS case study in PAPERS.md is the
tiling/online-softmax exemplar; "LLM Inference Acceleration via
Efficient Operation Fusion" grounds fusing the segment/causal mask into
the attention kernel instead of materializing it):

- **Block map** (:func:`varlen_block_map`): packed segments are
  CONTIGUOUS in both q and k, so the k tiles a q tile must visit form
  one interval ``[kstart, kstart+klen)``. The map is computed OUTSIDE
  the kernel (a handful of O(T) integer ops) from the traced
  ``cu_seqlens`` and rides into the kernel as scalar-prefetch operands;
  the kernel's inner loop runs ``klen`` iterations — tiles where
  ``seg_q ∩ seg_k = ∅`` are never visited, so work is proportional to
  the sum of per-segment tile areas, not ``T²``.
- **Boundary-only masking**: per-tile segment aggregates (first/last
  segment id, positions) let the kernel prove a tile is INTERIOR (one
  segment, fully causal-valid) and skip the in-tile mask entirely;
  only boundary tiles compute the ``[bq, bk]`` seg/pos compare.
- **Online softmax**, fp32 running (m, l, acc) — memory is O(T·d).
- **custom_vjp backward** built the same way: a dq kernel walks the
  forward map; a dk/dv kernel walks the TRANSPOSED map (for k tile j,
  the attending q tiles are again one interval).
- **Paged variant** (:func:`paged_prefill_attention`): K/V are read IN
  PLACE from the page-major pool via block-table-indexed DMAs (the
  scalar-prefetched table drives per-page copies), so chunked prefill
  and speculative verify stop materializing the gathered pool.
- **Off-TPU**: ``backend="interpret"`` runs the SAME Pallas kernels
  through the interpreter; ``backend="xla"`` is a tiled XLA
  implementation that visits tiles in the same order with the same
  fp32 accumulation — math-identical by construction, and the default
  off-chip (serving engines jit it on CPU CI).

Layouts: packed q/k/v are ``[total, heads, head_dim]`` (paddle
``flash_attn_unpadded`` convention); the paged pool is the repo's
page-major ``[pages, n_kv, page_size, d]``.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ...device import chip as _chip
from ...device.vmem import KERNEL_VMEM_LIMIT_BYTES
from .paged_attention import _enable_x64

__all__ = [
    "varlen_block_map", "flash_varlen_packed", "paged_prefill_attention",
    "DEFAULT_BLOCK_Q", "DEFAULT_BLOCK_K",
]

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
#: every in-kernel dot pins its precision: the package-wide default
#: (jax_default_matmul_precision="high") is one Mosaic refuses
#: ("Unsupported dot precision: HIGH")
_PREC = jax.lax.Precision.DEFAULT
_NEG = -1e30          # python literal: jnp scalars would be captured consts
_NEG_SAFE = -5e29     # lse clamp floor: exp(_NEG - _NEG_SAFE) underflows to 0


def _resolve_backend(backend: str) -> str:
    if backend == "auto":
        return "pallas" if _chip.on_tpu() else "xla"
    if backend not in ("pallas", "interpret", "xla"):
        raise ValueError(
            f"flash_varlen backend={backend!r}: expected 'auto', "
            "'pallas', 'interpret' or 'xla'")
    return backend


def _cdiv(a, b):
    return -(-a // b)


# ---------------------------------------------------------------------
# Block map
# ---------------------------------------------------------------------

@dataclasses.dataclass
class BlockMap:
    """Per-tile visit intervals + segment aggregates (all int32 jnp
    arrays, computed from traced cu_seqlens — one trace serves every
    packing of the same shape).

    Forward map: q tile ``i`` visits k tiles ``kstart[i] ..
    kstart[i]+klen[i]-1``. Transposed map (the dk/dv walk): k tile
    ``j`` is visited by q tiles ``qstart2[j] .. qstart2[j]+qlen2[j]-1``.
    ``n_active = sum(klen)`` is the exact number of computed tiles —
    the skip-count tests pin it against the per-segment closed form.
    """
    kstart: jnp.ndarray   # [nq]
    klen: jnp.ndarray     # [nq]
    qslo: jnp.ndarray     # [nq] segment id of tile's first row
    qshi: jnp.ndarray     # [nq] segment id of tile's LAST row — pad
    #                       tails land in the phantom segment, so a
    #                       partially-padded tile never tests interior
    qpos0: jnp.ndarray    # [nq] in-segment position of tile's first row
    kslo: jnp.ndarray     # [nk]
    kshi: jnp.ndarray     # [nk] (same phantom-segment convention)
    kmax: jnp.ndarray     # [nk] in-segment position of tile's last row
    qstart2: jnp.ndarray  # [nk]
    qlen2: jnp.ndarray    # [nk]
    qmeta: jnp.ndarray    # [2, tq_pad] rows: (segment id, in-seg pos)
    kmeta: jnp.ndarray    # [2, tk_pad]
    n_active: jnp.ndarray  # scalar: tiles actually computed


def _seg_pos(cu, total_pad):
    """Per-token (segment id, in-segment position) for a padded packed
    axis. Tokens past ``cu[-1]`` land in the phantom segment ``nseg``
    (matched by nothing real — boundary masks kill them)."""
    pos = jnp.arange(total_pad, dtype=jnp.int32)
    seg = jnp.searchsorted(cu[1:], pos, side="right").astype(jnp.int32)
    start = cu[jnp.minimum(seg, cu.shape[0] - 1)]
    return seg, pos - start


def varlen_block_map(cu_q, cu_k, total_q_pad: int, total_k_pad: int,
                     block_q: int, block_k: int, causal: bool) -> BlockMap:
    """Build the block-skipping visit map from cu_seqlens.

    ``cu_q``/``cu_k``: int32 ``[nseg+1]`` cumulative offsets (traced or
    concrete). ``total_*_pad``: the padded (tile-aligned) axis lengths.
    """
    cu_q = jnp.asarray(cu_q, jnp.int32)
    cu_k = jnp.asarray(cu_k, jnp.int32)
    nseg = cu_q.shape[0] - 1
    nq = total_q_pad // block_q
    nk = total_k_pad // block_k
    tqr = cu_q[nseg]                      # real token counts (traced)
    tkr = cu_k[nseg]
    cu_k_ext = jnp.concatenate([cu_k, tkr[None]])   # segment nseg empty
    cu_q_ext = jnp.concatenate([cu_q, tqr[None]])

    seg_q, off_q = _seg_pos(cu_q, total_q_pad)
    seg_k, off_k = _seg_pos(cu_k, total_k_pad)

    # ---- forward map: per q tile, the contiguous k-tile interval ----
    row_lo = jnp.arange(nq, dtype=jnp.int32) * block_q
    # clamped last REAL row: drives the visit-interval arithmetic
    row_hi = jnp.clip(row_lo + block_q - 1, 0, jnp.maximum(tqr - 1, 0))
    row_hi = jnp.maximum(row_hi, row_lo)  # all-pad tiles: degenerate
    qslo = seg_q[jnp.minimum(row_lo, total_q_pad - 1)]
    qshi_c = seg_q[row_hi]
    # UNclamped last row: drives the interior test — a tile whose tail
    # is padding lands in the phantom segment and stays a boundary
    # tile (the kernel must mask its pad rows)
    qshi = seg_q[jnp.minimum(row_lo + block_q - 1, total_q_pad - 1)]
    qpos0 = off_q[jnp.minimum(row_lo, total_q_pad - 1)]
    kstart_tok = cu_k[jnp.minimum(qslo, nseg)]
    kend_tok = cu_k_ext[jnp.minimum(qshi_c, nseg) + 1]
    if causal:
        lim = cu_k[jnp.minimum(qshi_c, nseg)] \
            + (row_hi - cu_q[jnp.minimum(qshi_c, nseg)]) + 1
        kend_tok = jnp.minimum(kend_tok, jnp.maximum(lim, kstart_tok))
    kstart_tile = kstart_tok // block_k
    kend_tile = _cdiv(kend_tok, block_k)
    klen = jnp.maximum(kend_tile - kstart_tile, 0)
    klen = jnp.where(row_lo < tqr, klen, 0)
    kstart_tile = jnp.minimum(kstart_tile, jnp.maximum(nk - 1, 0))

    # ---- per-k-tile aggregates ----
    col_lo = jnp.arange(nk, dtype=jnp.int32) * block_k
    col_hi = jnp.clip(col_lo + block_k - 1, 0, jnp.maximum(tkr - 1, 0))
    col_hi = jnp.maximum(col_hi, col_lo)
    col_hi_raw = jnp.minimum(col_lo + block_k - 1, total_k_pad - 1)
    kslo = seg_k[jnp.minimum(col_lo, total_k_pad - 1)]
    kshi_c = seg_k[col_hi]
    kshi = seg_k[col_hi_raw]        # unclamped: pad tail => boundary
    kmax = off_k[col_hi_raw]

    # ---- transposed map: per k tile, the attending q-tile interval ----
    qstart_tok = cu_q[jnp.minimum(kslo, nseg)]
    if causal:
        # the earliest attending row of the tile's FIRST segment is at
        # the tile's first in-segment k position (rows before it are
        # strictly causal-masked); clamp inside the segment
        qstart_tok = jnp.minimum(
            qstart_tok + off_k[jnp.minimum(col_lo, total_k_pad - 1)],
            cu_q_ext[jnp.minimum(kslo, nseg) + 1])
    qend_tok = cu_q_ext[jnp.minimum(kshi_c, nseg) + 1]
    qstart2 = qstart_tok // block_q
    qend2 = _cdiv(qend_tok, block_q)
    qlen2 = jnp.maximum(qend2 - qstart2, 0)
    qlen2 = jnp.where(col_lo < tkr, qlen2, 0)
    qstart2 = jnp.minimum(qstart2, jnp.maximum(nq - 1, 0))

    return BlockMap(
        kstart=kstart_tile.astype(jnp.int32),
        klen=klen.astype(jnp.int32),
        qslo=qslo, qshi=qshi, qpos0=qpos0,
        kslo=kslo, kshi=kshi, kmax=kmax,
        qstart2=qstart2.astype(jnp.int32),
        qlen2=qlen2.astype(jnp.int32),
        qmeta=jnp.stack([seg_q, off_q]),
        kmeta=jnp.stack([seg_k, off_k]),
        n_active=jnp.sum(klen).astype(jnp.int32),
    )


# ---------------------------------------------------------------------
# Packed kernels (Pallas; interpret=True is the off-TPU debug path)
# ---------------------------------------------------------------------

def _boundary_mask(sq, pq, sk, pk, causal: bool):
    """[bq, bk] validity for a boundary tile from per-token metadata."""
    msk = sq[:, None] == sk[None, :]
    if causal:
        msk = jnp.logical_and(msk, pq[:, None] >= pk[None, :])
    return msk


def _packed_fwd_pallas(qt, kt, vt, bm: BlockMap, scale: float,
                       causal: bool, block_q: int, block_k: int,
                       interpret: bool):
    """Forward kernel. qt/kt/vt: [h, T_pad, d]. Returns
    (out [h, tq_pad, d] f32, lse [h, tq_pad] f32)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, tq, d = qt.shape
    tk = kt.shape[1]
    bq, bk = block_q, block_k
    nq = tq // bq

    def kernel(kstart, klen, qslo, qshi, qpos0, kslo, kshi, kmax,
               qmeta_ref, q_ref, kmeta_hbm, k_hbm, v_hbm,
               o_ref, lse_ref, kbuf, vbuf, kmbuf, ksem, vsem, msem):
        i = pl.program_id(0)
        ks = kstart[i]
        kl = klen[i]

        def dmas(j, slot):
            return (
                pltpu.make_async_copy(
                    k_hbm.at[:, pl.ds(j * bk, bk), :], kbuf.at[slot],
                    ksem.at[slot]),
                pltpu.make_async_copy(
                    v_hbm.at[:, pl.ds(j * bk, bk), :], vbuf.at[slot],
                    vsem.at[slot]),
                pltpu.make_async_copy(
                    kmeta_hbm.at[:, pl.ds(j * bk, bk)], kmbuf.at[slot],
                    msem.at[slot]))

        @pl.when(kl > 0)
        def _():
            for c in dmas(ks, jnp.int32(0)):
                c.start()

        # fold the softmax scale into q once per tile
        # tpu-lint: ok(X-PROMOTE) -- fp32 softmax accumulator by design
        qf = q_ref[...].astype(jnp.float32) * jnp.float32(scale)
        sq = qmeta_ref[0]
        pq = qmeta_ref[1]
        uniform_q = qslo[i] == qshi[i]

        m0 = jnp.full((h, bq), _NEG, jnp.float32)
        l0 = jnp.zeros((h, bq), jnp.float32)
        a0 = jnp.zeros((h, bq, d), jnp.float32)

        def body(s, carry):
            m, l, acc = carry
            j = ks + s
            slot = jax.lax.rem(s, jnp.int32(2))

            @pl.when(s + 1 < kl)
            def _():
                for c in dmas(j + 1, jax.lax.rem(s + 1, jnp.int32(2))):
                    c.start()

            for c in dmas(j, slot):
                c.wait()
            kf = kbuf[slot].astype(jnp.float32)
            vf = vbuf[slot].astype(jnp.float32)
            lg = jax.lax.dot_general(
                qf, kf, (((2,), (2,)), ((0,), (0,))),
                precision=_PREC,
                preferred_element_type=jnp.float32)      # [h, bq, bk]
            interior = jnp.logical_and(
                jnp.logical_and(uniform_q, kslo[j] == kshi[j]),
                qslo[i] == kslo[j])
            if causal:
                interior = jnp.logical_and(interior,
                                           kmax[j] <= qpos0[i])

            def _masked(z):
                msk = _boundary_mask(sq, pq, kmbuf[slot, 0],
                                     kmbuf[slot, 1], causal)
                return (jnp.where(msk[None], z, jnp.float32(_NEG)),
                        msk.astype(jnp.float32))

            def _plain(z):
                return z, jnp.ones((bq, bk), jnp.float32)

            lg, mskf = jax.lax.cond(interior, _plain, _masked, lg)
            pm = jnp.maximum(m, lg.max(-1))
            alpha = jnp.exp(m - pm)
            p = jnp.exp(lg - pm[..., None]) * mskf[None]
            l = l * alpha + p.sum(-1)
            pv = jax.lax.dot_general(
                p, vf, (((2,), (1,)), ((0,), (0,))),
                precision=_PREC,
                preferred_element_type=jnp.float32)      # [h, bq, d]
            acc = acc * alpha[..., None] + pv
            return pm, l, acc

        m, l, acc = jax.lax.fori_loop(jnp.int32(0), kl, body,
                                      (m0, l0, a0))
        o_ref[...] = acc / jnp.maximum(l, jnp.float32(1e-30))[..., None]
        lse_ref[...] = jnp.where(
            l > 0, m + jnp.log(jnp.maximum(l, jnp.float32(1e-30))),
            jnp.float32(_NEG))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(nq,),
        in_specs=[
            pl.BlockSpec((2, bq), lambda i, *_: (0, i)),
            pl.BlockSpec((h, bq, d), lambda i, *_: (0, i, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=[
            pl.BlockSpec((h, bq, d), lambda i, *_: (0, i, 0)),
            pl.BlockSpec((h, bq), lambda i, *_: (0, i)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, h, bk, d), kt.dtype),
            pltpu.VMEM((2, h, bk, d), vt.dtype),
            pltpu.VMEM((2, 2, bk), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    with _enable_x64(False), jax.named_scope("pt_flash_varlen_packed_fwd"):
        out, lse = pl.pallas_call(
            kernel,
            name="pt_flash_varlen_packed_fwd",
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((h, tq, d), jnp.float32),
                jax.ShapeDtypeStruct((h, tq), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(bm.kstart, bm.klen, bm.qslo, bm.qshi, bm.qpos0,
          bm.kslo, bm.kshi, bm.kmax,
          bm.qmeta, qt, bm.kmeta, kt, vt)
    return out, lse


def _packed_dq_pallas(qt, kt, vt, dot_, lse, delta, bm: BlockMap,
                      scale: float, causal: bool, block_q: int,
                      block_k: int, interpret: bool):
    """dq kernel: walks the forward map again; P is recomputed from
    lse. Returns dq [h, tq_pad, d] f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, tq, d = qt.shape
    bq, bk = block_q, block_k
    nq = tq // bq

    def kernel(kstart, klen, qslo, qshi, qpos0, kslo, kshi, kmax,
               qmeta_ref, q_ref, do_ref, ld_ref, kmeta_hbm, k_hbm,
               v_hbm, dq_ref, kbuf, vbuf, kmbuf, ksem, vsem, msem):
        i = pl.program_id(0)
        ks = kstart[i]
        kl = klen[i]

        def dmas(j, slot):
            return (
                pltpu.make_async_copy(
                    k_hbm.at[:, pl.ds(j * bk, bk), :], kbuf.at[slot],
                    ksem.at[slot]),
                pltpu.make_async_copy(
                    v_hbm.at[:, pl.ds(j * bk, bk), :], vbuf.at[slot],
                    vsem.at[slot]),
                pltpu.make_async_copy(
                    kmeta_hbm.at[:, pl.ds(j * bk, bk)], kmbuf.at[slot],
                    msem.at[slot]))

        @pl.when(kl > 0)
        def _():
            for c in dmas(ks, jnp.int32(0)):
                c.start()

        # tpu-lint: ok(X-PROMOTE) -- fp32 softmax accumulator by design
        qf = q_ref[...].astype(jnp.float32) * jnp.float32(scale)
        dof = do_ref[...].astype(jnp.float32)
        lse_t = jnp.maximum(ld_ref[0], jnp.float32(_NEG_SAFE))
        delta_t = ld_ref[1]
        sq = qmeta_ref[0]
        pq = qmeta_ref[1]
        uniform_q = qslo[i] == qshi[i]

        def body(s, dq):
            j = ks + s
            slot = jax.lax.rem(s, jnp.int32(2))

            @pl.when(s + 1 < kl)
            def _():
                for c in dmas(j + 1, jax.lax.rem(s + 1, jnp.int32(2))):
                    c.start()

            for c in dmas(j, slot):
                c.wait()
            kf = kbuf[slot].astype(jnp.float32)
            vf = vbuf[slot].astype(jnp.float32)
            lg = jax.lax.dot_general(
                qf, kf, (((2,), (2,)), ((0,), (0,))),
                precision=_PREC,
                preferred_element_type=jnp.float32)
            interior = jnp.logical_and(
                jnp.logical_and(uniform_q, kslo[j] == kshi[j]),
                qslo[i] == kslo[j])
            if causal:
                interior = jnp.logical_and(interior,
                                           kmax[j] <= qpos0[i])

            def _masked(z):
                msk = _boundary_mask(sq, pq, kmbuf[slot, 0],
                                     kmbuf[slot, 1], causal)
                return (jnp.where(msk[None], z, jnp.float32(_NEG)),
                        msk.astype(jnp.float32))

            def _plain(z):
                return z, jnp.ones((bq, bk), jnp.float32)

            lg, mskf = jax.lax.cond(interior, _plain, _masked, lg)
            p = jnp.exp(lg - lse_t[..., None]) * mskf[None]
            dp = jax.lax.dot_general(
                dof, vf, (((2,), (2,)), ((0,), (0,))),
                precision=_PREC,
                preferred_element_type=jnp.float32)      # [h, bq, bk]
            ds = p * (dp - delta_t[..., None])
            return dq + jax.lax.dot_general(
                ds, kf, (((2,), (1,)), ((0,), (0,))),
                precision=_PREC,
                preferred_element_type=jnp.float32)

        dq = jax.lax.fori_loop(jnp.int32(0), kl, body,
                               jnp.zeros((h, bq, d), jnp.float32))
        dq_ref[...] = dq * jnp.float32(scale)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(nq,),
        in_specs=[
            pl.BlockSpec((2, bq), lambda i, *_: (0, i)),
            pl.BlockSpec((h, bq, d), lambda i, *_: (0, i, 0)),
            pl.BlockSpec((h, bq, d), lambda i, *_: (0, i, 0)),
            pl.BlockSpec((2, h, bq), lambda i, *_: (0, 0, i)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((h, bq, d), lambda i, *_: (0, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, h, bk, d), kt.dtype),
            pltpu.VMEM((2, h, bk, d), vt.dtype),
            pltpu.VMEM((2, 2, bk), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    ld = jnp.stack([lse, delta])                         # [2, h, tq]
    with _enable_x64(False), jax.named_scope("pt_flash_varlen_packed_dq"):
        dq = pl.pallas_call(
            kernel,
            name="pt_flash_varlen_packed_dq",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((h, tq, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(bm.kstart, bm.klen, bm.qslo, bm.qshi, bm.qpos0,
          bm.kslo, bm.kshi, bm.kmax,
          bm.qmeta, qt, dot_, ld, bm.kmeta, kt, vt)
    return dq


def _packed_dkv_pallas(qt, kt, vt, dot_, lse, delta, bm: BlockMap,
                       scale: float, causal: bool, block_q: int,
                       block_k: int, interpret: bool):
    """dk/dv kernel: walks the TRANSPOSED map — for k tile j the
    attending q tiles are the interval [qstart2[j], +qlen2[j])."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    h, tq, d = qt.shape
    tk = kt.shape[1]
    bq, bk = block_q, block_k
    nk = tk // bk

    def kernel(qstart2, qlen2, qslo, qshi, qpos0, kslo, kshi, kmax,
               kmeta_ref, k_ref, v_ref, qmeta_hbm, q_hbm, do_hbm,
               ld_hbm, dk_ref, dv_ref, qbuf, dobuf, ldbuf, qmbuf,
               qsem, dosem, ldsem, qmsem):
        j = pl.program_id(0)
        qs = qstart2[j]
        ql = qlen2[j]

        def dmas(t, slot):
            return (
                pltpu.make_async_copy(
                    q_hbm.at[:, pl.ds(t * bq, bq), :], qbuf.at[slot],
                    qsem.at[slot]),
                pltpu.make_async_copy(
                    do_hbm.at[:, pl.ds(t * bq, bq), :], dobuf.at[slot],
                    dosem.at[slot]),
                pltpu.make_async_copy(
                    ld_hbm.at[:, :, pl.ds(t * bq, bq)], ldbuf.at[slot],
                    ldsem.at[slot]),
                pltpu.make_async_copy(
                    qmeta_hbm.at[:, pl.ds(t * bq, bq)], qmbuf.at[slot],
                    qmsem.at[slot]))

        @pl.when(ql > 0)
        def _():
            for c in dmas(qs, jnp.int32(0)):
                c.start()

        # tpu-lint: ok(X-PROMOTE) -- fp32 softmax accumulator by design
        kf = k_ref[...].astype(jnp.float32)
        vf = v_ref[...].astype(jnp.float32)
        sk = kmeta_ref[0]
        pk = kmeta_ref[1]
        uniform_k = kslo[j] == kshi[j]

        def body(s, carry):
            dk, dv = carry
            t = qs + s
            slot = jax.lax.rem(s, jnp.int32(2))

            @pl.when(s + 1 < ql)
            def _():
                for c in dmas(t + 1, jax.lax.rem(s + 1, jnp.int32(2))):
                    c.start()

            for c in dmas(t, slot):
                c.wait()
            qf = qbuf[slot].astype(jnp.float32) * jnp.float32(scale)
            dof = dobuf[slot].astype(jnp.float32)
            lse_t = jnp.maximum(ldbuf[slot, 0], jnp.float32(_NEG_SAFE))
            delta_t = ldbuf[slot, 1]
            lg = jax.lax.dot_general(
                qf, kf, (((2,), (2,)), ((0,), (0,))),
                precision=_PREC,
                preferred_element_type=jnp.float32)      # [h, bq, bk]
            interior = jnp.logical_and(
                jnp.logical_and(uniform_k, qslo[t] == qshi[t]),
                qslo[t] == kslo[j])
            if causal:
                interior = jnp.logical_and(interior,
                                           kmax[j] <= qpos0[t])

            def _masked(z):
                msk = _boundary_mask(qmbuf[slot, 0], qmbuf[slot, 1],
                                     sk, pk, causal)
                return (jnp.where(msk[None], z, jnp.float32(_NEG)),
                        msk.astype(jnp.float32))

            def _plain(z):
                return z, jnp.ones((bq, bk), jnp.float32)

            lg, mskf = jax.lax.cond(interior, _plain, _masked, lg)
            p = jnp.exp(lg - lse_t[..., None]) * mskf[None]
            dv = dv + jax.lax.dot_general(
                p, dof, (((1,), (1,)), ((0,), (0,))),
                precision=_PREC,
                preferred_element_type=jnp.float32)      # [h, bk, d]
            dp = jax.lax.dot_general(
                dof, vf, (((2,), (2,)), ((0,), (0,))),
                precision=_PREC,
                preferred_element_type=jnp.float32)      # [h, bq, bk]
            ds = p * (dp - delta_t[..., None])
            dk = dk + jax.lax.dot_general(
                ds, qf, (((1,), (1,)), ((0,), (0,))),
                precision=_PREC,
                preferred_element_type=jnp.float32)      # [h, bk, d]
            return dk, dv

        dk, dv = jax.lax.fori_loop(
            jnp.int32(0), ql, body,
            (jnp.zeros((h, bk, d), jnp.float32),
             jnp.zeros((h, bk, d), jnp.float32)))
        dk_ref[...] = dk        # scale already folded into qf
        dv_ref[...] = dv

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=8,
        grid=(nk,),
        in_specs=[
            pl.BlockSpec((2, bk), lambda j, *_: (0, j)),
            pl.BlockSpec((h, bk, d), lambda j, *_: (0, j, 0)),
            pl.BlockSpec((h, bk, d), lambda j, *_: (0, j, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=[
            pl.BlockSpec((h, bk, d), lambda j, *_: (0, j, 0)),
            pl.BlockSpec((h, bk, d), lambda j, *_: (0, j, 0)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, h, bq, d), qt.dtype),
            pltpu.VMEM((2, h, bq, d), dot_.dtype),
            pltpu.VMEM((2, 2, h, bq), jnp.float32),
            pltpu.VMEM((2, 2, bq), jnp.int32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    ld = jnp.stack([lse, delta])                         # [2, h, tq]
    with _enable_x64(False), jax.named_scope("pt_flash_varlen_packed_dkv"):
        dk, dv = pl.pallas_call(
            kernel,
            name="pt_flash_varlen_packed_dkv",
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((h, tk, d), jnp.float32),
                jax.ShapeDtypeStruct((h, tk, d), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(bm.qstart2, bm.qlen2, bm.qslo, bm.qshi, bm.qpos0,
          bm.kslo, bm.kshi, bm.kmax,
          bm.kmeta, kt, vt, bm.qmeta, qt, dot_, ld)
    return dk, dv


# ---------------------------------------------------------------------
# Packed XLA fallback (math-identical tile walk, pure jax ops)
# ---------------------------------------------------------------------

def _packed_fwd_xla(qt, kt, vt, bm: BlockMap, scale: float,
                    causal: bool, block_q: int, block_k: int):
    """Same tile visit order and fp32 accumulation as the kernel, as a
    fori_loop over visit slots (slot s of q tile i is k tile
    ``kstart[i]+s``). Work is bounded by the LONGEST per-tile interval,
    memory by O(T·d) — no [T, T] intermediate ever exists."""
    h, tq, d = qt.shape
    tk = kt.shape[1]
    bq, bk = block_q, block_k
    nq, nk = tq // bq, tk // bk

    # tpu-lint: ok(X-PROMOTE) -- fp32 softmax accumulator by design
    q4 = (qt.astype(jnp.float32) * jnp.float32(scale)) \
        .reshape(h, nq, bq, d)
    k4 = kt.astype(jnp.float32).reshape(h, nk, bk, d)
    v4 = vt.astype(jnp.float32).reshape(h, nk, bk, d)
    sq4 = bm.qmeta[0].reshape(nq, bq)
    pq4 = bm.qmeta[1].reshape(nq, bq)
    sk4 = bm.kmeta[0].reshape(nk, bk)
    pk4 = bm.kmeta[1].reshape(nk, bk)
    maxlen = jnp.max(bm.klen).astype(jnp.int32)

    def body(s, carry):
        m, l, acc = carry
        j = jnp.clip(bm.kstart + s, 0, nk - 1)           # [nq]
        active = s < bm.klen                             # [nq]
        ktile = jnp.take(k4, j, axis=1)                  # [h, nq, bk, d]
        vtile = jnp.take(v4, j, axis=1)
        sk = jnp.take(sk4, j, axis=0)                    # [nq, bk]
        pk = jnp.take(pk4, j, axis=0)
        # tpu-lint: ok(X-PROMOTE) -- attention scores fp32 by design
        lg = jnp.einsum("hnqd,hnkd->hnqk", q4, ktile)    # [h,nq,bq,bk]
        msk = sq4[:, :, None] == sk[:, None, :]          # [nq, bq, bk]
        if causal:
            msk = jnp.logical_and(msk,
                                  pq4[:, :, None] >= pk[:, None, :])
        msk = jnp.logical_and(msk, active[:, None, None])
        lg = jnp.where(msk[None], lg, jnp.float32(_NEG))
        pm = jnp.maximum(m, lg.max(-1))
        alpha = jnp.exp(m - pm)
        p = jnp.exp(lg - pm[..., None]) * msk[None].astype(jnp.float32)
        l = l * alpha + p.sum(-1)
        # tpu-lint: ok(X-PROMOTE) -- fp32 PV accumulation pairs with scores
        pv = jnp.einsum("hnqk,hnkd->hnqd", p, vtile)
        acc = acc * alpha[..., None] + pv
        return pm, l, acc

    m0 = jnp.full((h, nq, bq), _NEG, jnp.float32)
    l0 = jnp.zeros((h, nq, bq), jnp.float32)
    a0 = jnp.zeros((h, nq, bq, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(jnp.int32(0), maxlen, body,
                                  (m0, l0, a0))
    out = (acc / jnp.maximum(l, 1e-30)[..., None]).reshape(h, tq, d)
    lse = jnp.where(l > 0, m + jnp.log(jnp.maximum(l, 1e-30)),
                    jnp.float32(_NEG)).reshape(h, tq)
    return out, lse


def _packed_bwd_xla(qt, kt, vt, dot_, lse, delta, bm: BlockMap,
                    scale: float, causal: bool, block_q: int,
                    block_k: int):
    """XLA backward: dq over the forward map, dk/dv over the
    transposed map — the same walks as the Pallas backward kernels."""
    h, tq, d = qt.shape
    tk = kt.shape[1]
    bq, bk = block_q, block_k
    nq, nk = tq // bq, tk // bk

    qf4 = (qt.astype(jnp.float32) * jnp.float32(scale)) \
        .reshape(h, nq, bq, d)
    do4 = dot_.astype(jnp.float32).reshape(h, nq, bq, d)
    k4 = kt.astype(jnp.float32).reshape(h, nk, bk, d)
    v4 = vt.astype(jnp.float32).reshape(h, nk, bk, d)
    lse4 = jnp.maximum(lse, jnp.float32(_NEG_SAFE)).reshape(h, nq, bq)
    dl4 = delta.reshape(h, nq, bq)
    sq4 = bm.qmeta[0].reshape(nq, bq)
    pq4 = bm.qmeta[1].reshape(nq, bq)
    sk4 = bm.kmeta[0].reshape(nk, bk)
    pk4 = bm.kmeta[1].reshape(nk, bk)

    def tile_mask(sq, pq, sk, pk, active):
        msk = sq[:, :, None] == sk[:, None, :]
        if causal:
            msk = jnp.logical_and(msk, pq[:, :, None] >= pk[:, None, :])
        return jnp.logical_and(msk, active[:, None, None])

    def dq_body(s, dq):
        j = jnp.clip(bm.kstart + s, 0, nk - 1)
        active = s < bm.klen
        ktile = jnp.take(k4, j, axis=1)
        vtile = jnp.take(v4, j, axis=1)
        msk = tile_mask(sq4, pq4, jnp.take(sk4, j, axis=0),
                        jnp.take(pk4, j, axis=0), active)
        lg = jnp.einsum("hnqd,hnkd->hnqk", qf4, ktile)
        lg = jnp.where(msk[None], lg, jnp.float32(_NEG))
        p = jnp.exp(lg - lse4[..., None]) \
            * msk[None].astype(jnp.float32)
        dp = jnp.einsum("hnqd,hnkd->hnqk", do4, vtile)
        ds = p * (dp - dl4[..., None])
        return dq + jnp.einsum("hnqk,hnkd->hnqd", ds, ktile)

    maxlen = jnp.max(bm.klen).astype(jnp.int32)
    dq = jax.lax.fori_loop(
        jnp.int32(0), maxlen, dq_body,
        jnp.zeros((h, nq, bq, d), jnp.float32))
    dq = (dq * jnp.float32(scale)).reshape(h, tq, d)

    def dkv_body(s, carry):
        dk, dv = carry
        t = jnp.clip(bm.qstart2 + s, 0, nq - 1)          # [nk]
        active = s < bm.qlen2
        qtile = jnp.take(qf4, t, axis=1)                 # [h, nk, bq, d]
        dtile = jnp.take(do4, t, axis=1)
        ltile = jnp.take(lse4, t, axis=1)                # [h, nk, bq]
        dltile = jnp.take(dl4, t, axis=1)
        sq = jnp.take(sq4, t, axis=0)                    # [nk, bq]
        pq = jnp.take(pq4, t, axis=0)
        msk = tile_mask(sq, pq, sk4, pk4, active)        # [nk, bq, bk]
        lg = jnp.einsum("hnqd,hnkd->hnqk", qtile, k4)
        lg = jnp.where(msk[None], lg, jnp.float32(_NEG))
        p = jnp.exp(lg - ltile[..., None]) \
            * msk[None].astype(jnp.float32)
        dv = dv + jnp.einsum("hnqk,hnqd->hnkd", p, dtile)
        dp = jnp.einsum("hnqd,hnkd->hnqk", dtile, v4)
        ds = p * (dp - dltile[..., None])
        dk = dk + jnp.einsum("hnqk,hnqd->hnkd", ds, qtile)
        return dk, dv

    maxlen2 = jnp.max(bm.qlen2).astype(jnp.int32)
    dk, dv = jax.lax.fori_loop(
        jnp.int32(0), maxlen2, dkv_body,
        (jnp.zeros((h, nk, bk, d), jnp.float32),
         jnp.zeros((h, nk, bk, d), jnp.float32)))
    return dq, dk.reshape(h, tk, d), dv.reshape(h, tk, d)


# ---------------------------------------------------------------------
# Packed public entry (custom_vjp)
# ---------------------------------------------------------------------

def _pad_axis(x, axis, target):
    n = x.shape[axis]
    if n == target:
        return x
    pads = [(0, 0)] * x.ndim
    pads[axis] = (0, target - n)
    return jnp.pad(x, pads)


def _packed_prepare(q, k, v, cu_q, cu_k, causal, scale, bq, bk):
    tq, h, d = q.shape
    tk = k.shape[0]
    tqp = _cdiv(tq, bq) * bq
    tkp = _cdiv(tk, bk) * bk
    qt = _pad_axis(jnp.swapaxes(q, 0, 1), 1, tqp)        # [h, tqp, d]
    kt = _pad_axis(jnp.swapaxes(k, 0, 1), 1, tkp)
    vt = _pad_axis(jnp.swapaxes(v, 0, 1), 1, tkp)
    bm = varlen_block_map(cu_q, cu_k, tqp, tkp, bq, bk, causal)
    return qt, kt, vt, bm


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9))
def _packed_core(q, k, v, cu_q, cu_k, causal, scale, bq, bk, backend):
    out, _res = _packed_core_fwd(q, k, v, cu_q, cu_k, causal, scale,
                                 bq, bk, backend)
    return out


def _packed_core_fwd(q, k, v, cu_q, cu_k, causal, scale, bq, bk,
                     backend):
    tq = q.shape[0]
    qt, kt, vt, bm = _packed_prepare(q, k, v, cu_q, cu_k, causal,
                                     scale, bq, bk)
    if backend == "xla":
        outp, lse = _packed_fwd_xla(qt, kt, vt, bm, scale, causal,
                                    bq, bk)
    else:
        outp, lse = _packed_fwd_pallas(qt, kt, vt, bm, scale, causal,
                                       bq, bk,
                                       interpret=(backend == "interpret"
                                                  or not _chip.on_tpu()))
    out = jnp.swapaxes(outp[:, :tq], 0, 1).astype(q.dtype)
    return out, (q, k, v, cu_q, cu_k, out, lse)


def _packed_core_bwd(causal, scale, bq, bk, backend, res, g):
    q, k, v, cu_q, cu_k, out, lse = res
    tq, h, d = q.shape
    tk = k.shape[0]
    qt, kt, vt, bm = _packed_prepare(q, k, v, cu_q, cu_k, causal,
                                     scale, bq, bk)
    dot_ = _pad_axis(jnp.swapaxes(g, 0, 1), 1, qt.shape[1])
    outp = _pad_axis(jnp.swapaxes(out, 0, 1), 1, qt.shape[1])
    # tpu-lint: ok(X-PROMOTE) -- fp32 softmax accumulator by design
    delta = jnp.sum(dot_.astype(jnp.float32)
                    * outp.astype(jnp.float32), axis=-1)  # [h, tqp]
    if backend == "xla":
        dq, dk, dv = _packed_bwd_xla(qt, kt, vt, dot_, lse, delta, bm,
                                     scale, causal, bq, bk)
    else:
        interp = backend == "interpret" or not _chip.on_tpu()
        dq = _packed_dq_pallas(qt, kt, vt, dot_, lse, delta, bm, scale,
                               causal, bq, bk, interp)
        dk, dv = _packed_dkv_pallas(qt, kt, vt, dot_, lse, delta, bm,
                                    scale, causal, bq, bk, interp)
    dq = jnp.swapaxes(dq[:, :tq], 0, 1).astype(q.dtype)
    dk = jnp.swapaxes(dk[:, :tk], 0, 1).astype(k.dtype)
    dv = jnp.swapaxes(dv[:, :tk], 0, 1).astype(v.dtype)
    return dq, dk, dv, None, None


def _packed_core_fwd_rule(q, k, v, cu_q, cu_k, causal, scale, bq, bk,
                          backend):
    out, res = _packed_core_fwd(q, k, v, cu_q, cu_k, causal, scale,
                                bq, bk, backend)
    return out, res


_packed_core.defvjp(_packed_core_fwd_rule, _packed_core_bwd)


def flash_varlen_packed(q, k, v, cu_seqlens_q, cu_seqlens_k, *,
                        scale=None, causal=False, block_q=None,
                        block_k=None, backend="auto"):
    """Segment-aware block-skipping flash attention over a packed batch.

    q/k/v: ``[total, heads, head_dim]`` raw arrays; ``cu_seqlens_*``:
    int ``[nseg+1]`` cumulative offsets (TRACED operands — one compiled
    program serves every packing of the same shape). Returns
    ``[total_q, heads, head_dim]`` in q's dtype. Differentiable via a
    custom_vjp whose backward kernels walk the same block map.
    """
    bq = int(block_q or DEFAULT_BLOCK_Q)
    bk = int(block_k or DEFAULT_BLOCK_K)
    scale = float(scale if scale is not None else q.shape[-1] ** -0.5)
    backend = _resolve_backend(backend)
    cu_q = jnp.asarray(cu_seqlens_q, jnp.int32)
    cu_k = jnp.asarray(cu_seqlens_k, jnp.int32)
    return _packed_core(q, k, v, cu_q, cu_k, bool(causal), scale, bq,
                        bk, backend)


# ---------------------------------------------------------------------
# Paged variant: chunked prefill / speculative verify attention that
# reads K/V in place from the page-major pool
# ---------------------------------------------------------------------

def _paged_block_k(page_size: int, pages_per_seq: int) -> int:
    """k-tile width for the paged walk: whole pages, ~128 tokens,
    never more pages than the table holds."""
    npp = max(1, min(128 // max(page_size, 1), pages_per_seq))
    return npp * page_size


def _paged_fwd_pallas(qt, key_cache, value_cache, tables, start, klen,
                      scale: float, n_kv: int, bk: int,
                      interpret: bool):
    """qt: [b, n_q, c, d] (kv-major head order); pool
    [P, n_kv, ps, d]; tables [b, pp] ABSOLUTE page ids; start [b]
    chunk position offsets; klen [b] k-tile visit counts.
    Returns [b, n_q, c, d] f32."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n_q, c, d = qt.shape
    _, _, ps, _ = key_cache.shape
    pp = tables.shape[1]
    g = n_q // n_kv
    npp = bk // ps

    def kernel(tables_ref, start_ref, klen_ref, q_ref, k_hbm, v_hbm,
               o_ref, kbuf, vbuf, ksem, vsem):
        i = pl.program_id(0)
        kl = klen_ref[i]
        st = start_ref[i]

        def dmas(j, slot):
            cps = []
            for p in range(npp):
                pidx = jnp.minimum(j * npp + p, jnp.int32(pp - 1))
                pid = tables_ref[i * pp + pidx]
                cps.append(pltpu.make_async_copy(
                    k_hbm.at[pid], kbuf.at[slot, p], ksem.at[slot, p]))
                cps.append(pltpu.make_async_copy(
                    v_hbm.at[pid], vbuf.at[slot, p], vsem.at[slot, p]))
            return cps

        @pl.when(kl > 0)
        def _():
            for cp in dmas(jnp.int32(0), jnp.int32(0)):
                cp.start()

        # tpu-lint: ok(X-PROMOTE) -- fp32 softmax accumulator by design
        qf = q_ref[0].astype(jnp.float32) * jnp.float32(scale)
        q3 = qf.reshape(n_kv, g * c, d)
        pos_q = jax.lax.broadcasted_iota(jnp.int32, (c, bk), 0) + st

        m0 = jnp.full((n_kv, g * c), _NEG, jnp.float32)
        l0 = jnp.zeros((n_kv, g * c), jnp.float32)
        a0 = jnp.zeros((n_kv, g * c, d), jnp.float32)

        def body(j, carry):
            m, l, acc = carry
            slot = jax.lax.rem(j, jnp.int32(2))

            @pl.when(j + 1 < kl)
            def _():
                for cp in dmas(j + 1, jax.lax.rem(j + 1, jnp.int32(2))):
                    cp.start()

            for cp in dmas(j, slot):
                cp.wait()
            # [npp, n_kv, ps, d] pages -> per-head contiguous [bk, d]
            kt = jnp.swapaxes(kbuf[slot], 0, 1).reshape(n_kv, bk, d) \
                .astype(jnp.float32)
            vt = jnp.swapaxes(vbuf[slot], 0, 1).reshape(n_kv, bk, d) \
                .astype(jnp.float32)
            lg = jax.lax.dot_general(
                q3, kt, (((2,), (2,)), ((0,), (0,))),
                precision=_PREC,
                preferred_element_type=jnp.float32)   # [n_kv, g*c, bk]
            interior = (j + 1) * bk - 1 <= st

            def _masked(z):
                pos_k = jax.lax.broadcasted_iota(
                    jnp.int32, (c, bk), 1) + j * bk
                msk = pos_k <= pos_q                  # [c, bk]
                z4 = z.reshape(n_kv * g, c, bk)
                z4 = jnp.where(msk[None], z4, jnp.float32(_NEG))
                return (z4.reshape(n_kv, g * c, bk),
                        jnp.broadcast_to(
                            msk.astype(jnp.float32)[None],
                            (n_kv * g, c, bk))
                        .reshape(n_kv, g * c, bk))

            def _plain(z):
                return z, jnp.ones((n_kv, g * c, bk), jnp.float32)

            lg, mskf = jax.lax.cond(interior, _plain, _masked, lg)
            pm = jnp.maximum(m, lg.max(-1))
            alpha = jnp.exp(m - pm)
            p = jnp.exp(lg - pm[..., None]) * mskf
            l = l * alpha + p.sum(-1)
            pv = jax.lax.dot_general(
                p, vt, (((2,), (1,)), ((0,), (0,))),
                precision=_PREC,
                preferred_element_type=jnp.float32)   # [n_kv, g*c, d]
            acc = acc * alpha[..., None] + pv
            return pm, l, acc

        m, l, acc = jax.lax.fori_loop(jnp.int32(0), kl, body,
                                      (m0, l0, a0))
        out = acc / jnp.maximum(l, jnp.float32(1e-30))[..., None]
        o_ref[0] = out.reshape(n_q, c, d)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((1, n_q, c, d), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=pl.BlockSpec((1, n_q, c, d),
                               lambda i, *_: (i, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, npp, n_kv, ps, d), key_cache.dtype),
            pltpu.VMEM((2, npp, n_kv, ps, d), value_cache.dtype),
            pltpu.SemaphoreType.DMA((2, npp)),
            pltpu.SemaphoreType.DMA((2, npp)),
        ])
    with _enable_x64(False), jax.named_scope("pt_flash_varlen_paged"):
        out = pl.pallas_call(
            kernel,
            name="pt_flash_varlen_paged",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((b, n_q, c, d), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=interpret,
        )(tables.reshape(-1).astype(jnp.int32),
          start.astype(jnp.int32), klen.astype(jnp.int32),
          qt, key_cache, value_cache)
    return out


def _paged_fwd_xla(qt, key_cache, value_cache, tables, start, klen,
                   scale: float, n_kv: int, bk: int):
    """Tiled XLA walk over the pool — one k tile (a few whole pages)
    gathered per step, online softmax. Never materializes the dense
    [b, S, n_kv, d] gather (memory per step is O(b·bk·d))."""
    b, n_q, c, d = qt.shape
    _, _, ps, _ = key_cache.shape
    pp = tables.shape[1]
    g = n_q // n_kv
    npp = bk // ps

    # tpu-lint: ok(X-PROMOTE) -- fp32 softmax accumulator by design
    q5 = (qt.astype(jnp.float32) * jnp.float32(scale)) \
        .reshape(b, n_kv, g, c, d)
    pos_q = start.astype(jnp.int32)[:, None, None] \
        + jax.lax.broadcasted_iota(jnp.int32, (1, c, bk), 1)  # [b,c,bk]
    jmax = jnp.max(klen).astype(jnp.int32)

    def body(j, carry):
        m, l, acc = carry
        # per-page clamp (NOT a clamped slice start — that would shift
        # the whole window and misalign pages with positions on a
        # partial last tile); clamped tail pages sit at positions >= S,
        # which the pos_k mask kills
        page_idx = jnp.clip(j * npp + jnp.arange(npp, dtype=jnp.int32),
                            0, pp - 1)
        pids = jnp.take(tables, page_idx, axis=1)
        kt = key_cache[pids]                  # [b, npp, n_kv, ps, d]
        vt = value_cache[pids]
        kt = jnp.swapaxes(kt, 1, 2).reshape(b, n_kv, bk, d) \
            .astype(jnp.float32)
        vt = jnp.swapaxes(vt, 1, 2).reshape(b, n_kv, bk, d) \
            .astype(jnp.float32)
        # tpu-lint: ok(X-PROMOTE) -- attention scores fp32 by design
        lg = jnp.einsum("bngcd,bnkd->bngck", q5, kt)
        pos_k = jax.lax.broadcasted_iota(jnp.int32, (1, c, bk), 2) \
            + j * bk
        msk = jnp.logical_and(pos_k <= pos_q,
                              (j < klen)[:, None, None])  # [b, c, bk]
        lg = jnp.where(msk[:, None, None], lg, jnp.float32(_NEG))
        pm = jnp.maximum(m, lg.max(-1))
        alpha = jnp.exp(m - pm)
        p = jnp.exp(lg - pm[..., None]) \
            * msk[:, None, None].astype(jnp.float32)
        l = l * alpha + p.sum(-1)
        # tpu-lint: ok(X-PROMOTE) -- fp32 PV accumulation pairs with scores
        pv = jnp.einsum("bngck,bnkd->bngcd", p, vt)
        acc = acc * alpha[..., None] + pv
        return pm, l, acc

    m0 = jnp.full((b, n_kv, g, c), _NEG, jnp.float32)
    l0 = jnp.zeros((b, n_kv, g, c), jnp.float32)
    a0 = jnp.zeros((b, n_kv, g, c, d), jnp.float32)
    nk_static = _cdiv(pp * ps, bk)
    if nk_static <= 4:
        # tiny pools (the CI serving geometry): python-unroll — a
        # per-layer while loop costs more in compile+dispatch than the
        # walk saves when the whole span is a handful of tiles
        carry = (m0, l0, a0)
        for j in range(nk_static):
            carry = body(jnp.int32(j), carry)
        m, l, acc = carry
    else:
        m, l, acc = jax.lax.fori_loop(jnp.int32(0), jmax, body,
                                      (m0, l0, a0))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.reshape(b, n_q, c, d)


def paged_prefill_attention(q, key_cache, value_cache, block_tables,
                            start, *, n_kv: int, scale=None,
                            backend="auto"):
    """Chunk-over-paged-pool attention, reading K/V IN PLACE.

    q: ``[b, c, n_q_heads, d]`` chunk queries at positions
    ``start[b] .. start[b]+c-1``; ``block_tables`` ``[b, pp]`` hold
    ABSOLUTE (layer-offset) page ids; the chunk's own K/V must already
    be written to the pool — by ``write_prefill_kv_inplace`` where this
    call sits in a layer loop on the chip: an XLA scatter on the
    loop-carried pool beside this Pallas call costs two whole-pool
    copies a layer (the note on ``paged_decode_attention_inplace``).
    Queries attend causally: key position <= query position — the
    cached prefix plus the in-chunk triangle, exactly the dense-gather
    path's mask. Returns ``[b, c, n_q_heads, d]`` in q's dtype.
    """
    b, c, n_q, d = q.shape
    _, _, ps, _ = key_cache.shape
    pp = block_tables.shape[1]
    g = n_q // n_kv
    scale = float(scale if scale is not None else d ** -0.5)
    backend = _resolve_backend(backend)
    bk = _paged_block_k(ps, pp)
    S = pp * ps
    # per-row visit count: tiles covering positions <= start + c - 1
    kend_tok = jnp.minimum(start.astype(jnp.int32) + c, S)
    klen = _cdiv(kend_tok, bk).astype(jnp.int32)
    # heads are kv-major (head = kv*g + g_idx, the repo's GQA layout),
    # so [b, n_q, c, d] reshapes to [n_kv, g*c, d] blocks in-kernel
    qt = jnp.swapaxes(q, 1, 2)                          # [b, n_q, c, d]
    if backend == "xla":
        out = _paged_fwd_xla(qt, key_cache, value_cache, block_tables,
                             start, klen, scale, n_kv, bk)
    else:
        out = _paged_fwd_pallas(
            qt, key_cache, value_cache, block_tables, start, klen,
            scale, n_kv, bk,
            interpret=(backend == "interpret" or not _chip.on_tpu()))
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)     # [b, c, n_q, d]

"""Multi-head latent attention over a paged LATENT pool — the serving
attention of ``"latent_attention"`` layers (``incubate/nn/layer_pattern``).

The pool holds ONE row a token and layer, ``[c | k_r | 0]``: the normed
latent (``kv_lora_rank``), the rotated rope key (one head, shared by every
query head), zero lanes up to a whole 128-lane tile (``LatentAttentionSpec
.row_width``). Both kernels attend in the ABSORBED form: a query head is
``[q_nope W_uk^T | q_rope | 0]`` over the same lanes, its score against a
token is ONE dot with the token's row, its value the row's first
``kv_lora_rank`` lanes; the caller contracts the result with ``W_uv``. So
every head of a sequence reads the same rows — multi-query attention with a
``row_width`` key and a ``kv_lora_rank`` value that is a prefix of the key —
and nothing is expanded to K / V per head, in HBM or in VMEM.

``pt_mla_paged_prefill``  one sequence's chunk ``[c, H, W]`` at positions
    ``start ..``: the chunk's rows are written into their pages (whole-page
    read-modify-write, the pool aliased, as ``pt_paged_kv_write``), then a
    tile of ``tq`` query tokens (``tq x H`` MXU rows) at a time walks the
    pages the block table names up to its own positions, online softmax.
``pt_mla_paged_decode``   one token for every slot ``[S, H, W]``: a grid
    step a sequence appends the token's row to its page and walks the
    sequence's own pages, each row read once for all H heads.

Neither hands XLA a slice of the loop-carried pool. Off the chip both take
an XLA scatter + gather that computes the same thing and is the reference
the kernels are tested against (``backend="interpret"`` runs the kernels
through the interpreter).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ...device import chip as _chip
from ...device.vmem import KERNEL_VMEM_LIMIT_BYTES
from .paged_attention import _enable_x64, stream_chunk_pages

__all__ = ["LatentKV", "yarn_inv_freq", "yarn_rope_table",
           "rope_interleaved", "query_temperature", "mla_prefill_attend",
           "mla_decode_attend"]

_NEG = -1e30
#: query tokens a prefill tile holds (x H heads = the MXU rows of a tile)
PREFILL_TILE_TOKENS = 128
#: pool tokens a prefill tile scores at a time
PREFILL_CHUNK_TOKENS = 256


class LatentKV(NamedTuple):
    """Layer-folded paged latent pool (the decode-loop carry): layer
    ``l``'s logical page ``p`` is physical page ``l * num_pages + p``, a
    page ``[page_size, row_width]`` one contiguous block."""
    rows: jax.Array        # [num_layers * num_pages, page_size, row_width]


# --------------------------------------------------------- rotary

def yarn_inv_freq(dim: int, theta: float, yarn=None):
    """Per-pair frequencies ``[dim / 2]`` float32 (``yarn`` a
    ``layer_pattern.YarnSpec`` or None for the plain table)."""
    extra = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    if yarn is None or yarn.factor <= 1:
        return extra

    def correction(rot):
        return dim * math.log(yarn.original_max_position
                              / (rot * 2 * math.pi)) \
            / (2 * math.log(theta))

    low = max(math.floor(correction(yarn.beta_fast)), 0)
    high = min(math.ceil(correction(yarn.beta_slow)), dim - 1)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return extra / yarn.factor * ramp + extra * (1.0 - ramp)


def yarn_rope_table(max_position: int, dim: int, theta: float, yarn=None):
    """``(cos, sin)`` float32 ``[max_position, dim / 2]``, times the
    table's own YaRN factor (1 where mscale = mscale_all_dim)."""
    ang = jnp.arange(max_position, dtype=jnp.float32)[:, None] \
        * yarn_inv_freq(dim, theta, yarn)[None, :]
    f = yarn.table_factor if yarn is not None else 1.0
    return jnp.cos(ang) * f, jnp.sin(ang) * f


def rope_interleaved(x, cos, sin):
    """Rotate ADJACENT pairs ``(x[2i], x[2i+1])`` of the last axis;
    ``cos`` / ``sin`` ``[..., dim / 2]`` broadcast over x's leading axes.
    float32 in, float32 out."""
    xe, xo = x[..., 0::2], x[..., 1::2]
    return jnp.stack([xe * cos - xo * sin, xo * cos + xe * sin],
                     -1).reshape(x.shape)


def query_temperature(positions, beta, period: int):
    """``1 + beta ln(1 + floor(pos / period))`` float32 (1 where ``beta``
    is None)."""
    if beta is None:
        return jnp.ones(positions.shape, jnp.float32)
    return 1.0 + beta * jnp.log1p(
        jnp.floor(positions.astype(jnp.float32) / period))


# ------------------------------------------------------ XLA fallbacks

def _prefill_xla(q, rows, pool, tables, start, vlen, v_width):
    c, H, W = q.shape
    _, ps, _ = pool.shape
    pp = tables.shape[1]
    pos = start[0] + jnp.arange(c, dtype=jnp.int32)
    live = (jnp.arange(c) < vlen[0]) & (pos < pp * ps)
    page = jnp.where(live, tables[0, jnp.minimum(pos // ps, pp - 1)], 0)
    pool = pool.at[page, jnp.where(live, pos % ps, 0)].set(
        jnp.where(live[:, None], rows.astype(pool.dtype),
                  pool[0, 0][None]))
    keys = pool[tables[0]].reshape(pp * ps, W)
    sc = jnp.einsum("chw,kw->chk", q.astype(jnp.float32),
                    keys.astype(jnp.float32))
    kpos = jnp.arange(pp * ps, dtype=jnp.int32)
    msk = kpos[None, :] <= pos[:, None]
    sc = jnp.where(msk[:, None, :], sc, _NEG)
    p = jax.nn.softmax(sc, -1)
    out = jnp.einsum("chk,kr->chr", p,
                     keys[:, :v_width].astype(jnp.float32))
    return out, pool


def _decode_xla(q, new_rows, pool, tables, lens, base, v_width):
    S, H, W = q.shape
    _, ps, _ = pool.shape
    pp = tables.shape[1]
    ok = lens < pp * ps
    page = jnp.take_along_axis(
        tables, jnp.minimum(lens // ps, pp - 1)[:, None], 1)[:, 0] + base
    cur = pool[page, lens % ps]
    pool = pool.at[page, lens % ps].set(
        jnp.where(ok[:, None], new_rows.astype(pool.dtype), cur))
    # the cached tokens from the pool, the current one from the operand
    # (an overfull row has no slot for it and still attends to it)
    nr = new_rows.astype(pool.dtype).astype(jnp.float32)
    keys = jnp.concatenate(
        [pool[tables + base].reshape(S, pp * ps, W).astype(jnp.float32),
         nr[:, None, :]], 1)
    sc = jnp.einsum("shw,skw->shk", q.astype(jnp.float32), keys)
    kpos = jnp.arange(pp * ps + 1, dtype=jnp.int32)
    msk = (kpos[None, :] < lens[:, None]) | (kpos[None, :] == pp * ps)
    sc = jnp.where(msk[:, None, :], sc, _NEG)
    p = jax.nn.softmax(sc, -1)
    return jnp.einsum("shk,skr->shr", p, keys[..., :v_width]), pool


# ---------------------------------------------------------- prefill

def mla_prefill_attend(q, rows, pool, block_tables, start, valid_lens,
                       *, v_width: int, backend: str = "auto"):
    """Write a chunk's latent rows and attend the chunk over its prefix.

    q ``[c, H, W]`` the ABSORBED queries of ONE sequence (scale and
    temperature folded in, lanes as the pool's rows); ``rows [c, W]`` the
    chunk's own cache rows; ``pool [P, page, W]`` (donated by the caller:
    it comes back aliased); ``block_tables [1, pp]`` ABSOLUTE page ids;
    ``start [1]`` the chunk's first position, ``valid_lens [1]`` its real
    rows (rows past them are not written: a bucketed chunk's padding never
    lands in the pool). Query ``i`` attends positions ``<= start + i``.
    Returns ``(out [c, H, v_width] float32, pool')``: the softmax-weighted
    sum of the rows' first ``v_width`` lanes.
    """
    if backend not in ("auto", "interpret"):
        raise ValueError(f"mla_prefill_attend backend={backend!r}: "
                         "expected 'auto' or 'interpret'")
    start = start.astype(jnp.int32)
    vlen = jnp.minimum(valid_lens.astype(jnp.int32), q.shape[0])
    tables = block_tables.astype(jnp.int32)
    if backend == "auto" and not _chip.on_tpu():
        return _prefill_xla(q, rows, pool, tables, start, vlen, v_width)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    c, H, W = q.shape
    _, ps, _ = pool.shape
    pp = tables.shape[1]
    R = int(v_width)
    tq = math.gcd(c, PREFILL_TILE_TOKENS)
    M = tq * H
    nq = c // tq
    cpk = max(1, min(PREFILL_CHUNK_TOKENS // ps, pp))
    CK = cpk * ps
    # pages a chunk of c rows can touch at any offset inside a page
    npg = (c + ps - 2) // ps + 1
    span = npg * ps
    off = start[0] % ps
    first = start[0] // ps
    # the chunk shifted by ``off`` into whole page-shaped blocks
    shifted = jax.lax.dynamic_slice_in_dim(
        jnp.pad(rows.astype(pool.dtype), ((ps, span - c), (0, 0))),
        ps - off, span, axis=0).reshape(npg, ps, W)
    r = jnp.arange(span, dtype=jnp.int32)
    live = ((r >= off) & (r < off + vlen[0])
            & (first * ps + r < pp * ps)).reshape(npg, ps)
    counts = live.sum(-1).astype(jnp.int32)
    rowmask = live.astype(jnp.float32)[:, :, None]
    pidx = jnp.minimum(first + jnp.arange(npg, dtype=jnp.int32), pp - 1)
    pids = tables[0, pidx]
    qf = q.reshape(c * H, W).astype(pool.dtype)
    hshift = H.bit_length() - 1 if H & (H - 1) == 0 else None

    def kernel(meta_ref, pid_ref, cnt_ref, tbl_ref, q_ref, new_ref, msk_ref,
               pool_in, o_ref, pool_hbm, pg, kb, m_ref, l_ref, acc_ref,
               wsem, rsem):
        del pool_in                         # aliased: pool_hbm
        i = pl.program_id(0)
        st = meta_ref[0]

        def page_in(j):
            return pltpu.make_async_copy(pool_hbm.at[pid_ref[j]], pg.at[j],
                                         wsem.at[0])

        def page_out(j):
            return pltpu.make_async_copy(pg.at[j], pool_hbm.at[pid_ref[j]],
                                         wsem.at[1])

        def each_touched(copy, act):
            def one(j, carry):
                @pl.when(cnt_ref[j] > 0)
                def _():
                    act(copy(j))
                return carry
            jax.lax.fori_loop(0, npg, one, 0)

        @pl.when(i == 0)
        def _():
            # the chunk's rows into their pages: whole-page read, select,
            # whole-page write (single-slot DMA slices break the sublane
            # tiling); landed before any tile reads the pool
            each_touched(page_in, lambda cp: cp.start())
            each_touched(page_in, lambda cp: cp.wait())
            sel = jnp.broadcast_to(msk_ref[...], pg.shape) > jnp.float32(0.5)
            pg[...] = jnp.where(sel, new_ref[...].astype(jnp.float32),
                                pg[...].astype(jnp.float32)).astype(pg.dtype)
            each_touched(page_out, lambda cp: cp.start())
            each_touched(page_out, lambda cp: cp.wait())

        q_lo = st + i * tq                  # the tile's first position
        nk = (jnp.minimum(q_lo + tq, jnp.int32(pp * ps))
              + jnp.int32(CK - 1)) // jnp.int32(CK)

        def copies(j, slot):
            out = []
            for p in range(cpk):
                pidx = jnp.minimum(j * cpk + p, jnp.int32(pp - 1))
                out.append(pltpu.make_async_copy(
                    pool_hbm.at[tbl_ref[pidx]], kb.at[slot, p],
                    rsem.at[slot]))
            return out

        for cp in copies(jnp.int32(0), jnp.int32(0)):
            cp.start()

        m_ref[...] = jnp.full((M, 1), _NEG, jnp.float32)
        l_ref[...] = jnp.zeros((M, 1), jnp.float32)
        acc_ref[...] = jnp.zeros((M, R), jnp.float32)
        qv = q_ref[...]                                     # [M, W]

        def body(j, carry):
            slot = jax.lax.rem(j, jnp.int32(2))

            @pl.when(j + 1 < nk)
            def _():
                for cp in copies(j + 1, jnp.int32(1) - slot):
                    cp.start()

            for cp in copies(j, slot):
                cp.wait()
            kt = kb[slot].reshape(CK, W)
            s = jax.lax.dot_general(
                qv, kt, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)         # [M, CK]

            def masked(z):
                row = jax.lax.broadcasted_iota(jnp.int32, (M, CK), 0)
                tok = jax.lax.shift_right_logical(row, jnp.int32(hshift)) \
                    if hshift is not None else row // H
                kpos = jax.lax.broadcasted_iota(jnp.int32, (M, CK), 1) \
                    + j * CK
                return jnp.where(kpos <= q_lo + tok, z, jnp.float32(_NEG))

            s = jax.lax.cond((j + 1) * CK - 1 <= q_lo, lambda z: z, masked,
                             s)
            m = m_ref[...]
            pm = jnp.maximum(m, s.max(-1, keepdims=True))
            alpha = jnp.exp(m - pm)
            p = jnp.exp(s - pm)
            l_ref[...] = l_ref[...] * alpha + p.sum(-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(kt.dtype), kt[:, :R], (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)         # [M, R]
            acc_ref[...] = acc_ref[...] * alpha + pv
            m_ref[...] = pm
            return carry

        jax.lax.fori_loop(jnp.int32(0), nk, body, 0)
        o_ref[...] = acc_ref[...] / jnp.maximum(l_ref[...],
                                                jnp.float32(1e-30))

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(nq,),
        in_specs=[
            pl.BlockSpec((M, W), lambda i, *_: (i, 0)),
            pl.BlockSpec((npg, ps, W), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec((npg, ps, 1), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=[
            pl.BlockSpec((M, R), lambda i, *_: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((npg, ps, W), pool.dtype),
            pltpu.VMEM((2, cpk, ps, W), pool.dtype),
            pltpu.VMEM((M, 1), jnp.float32),
            pltpu.VMEM((M, 1), jnp.float32),
            pltpu.VMEM((M, R), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    with _enable_x64(False), jax.named_scope("pt_mla_paged_prefill"):
        out, pool = pl.pallas_call(
            kernel,
            name="pt_mla_paged_prefill",
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((c * H, R), jnp.float32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            # inputs are numbered with the four scalar-prefetch operands
            # first: the pool is arg 7
            input_output_aliases={7: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=not _chip.on_tpu(),
        )(start, pids, counts, tables.reshape(-1), qf, shifted, rowmask,
          pool)
    return out.reshape(c, H, R), pool


# ----------------------------------------------------------- decode

def mla_decode_attend(q, new_rows, pool, block_tables, seq_lens,
                      pool_base=None, *, v_width: int,
                      backend: str = "auto"):
    """Append one token's row a sequence and attend over the sequence.

    q ``[S, H, W]`` the ABSORBED queries (scale and temperature folded
    in); ``new_rows [S, W]`` the current token's cache rows; ``pool`` the
    layer-folded latent pool (comes back aliased); ``block_tables [S, pp]``
    LAYER-LOCAL page ids, ``pool_base`` the first physical page of the
    layer's region; ``seq_lens [S]`` the tokens already cached, the current
    one excluded (its write position). A row whose table is full gets a
    no-op write (it still attends to its own token from the operand).
    Returns ``(out [S, H, v_width] float32, pool')``.
    """
    if backend not in ("auto", "interpret"):
        raise ValueError(f"mla_decode_attend backend={backend!r}: "
                         "expected 'auto' or 'interpret'")
    lens = seq_lens.astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    base = jnp.asarray(0 if pool_base is None else pool_base, jnp.int32)
    if backend == "auto" and not _chip.on_tpu():
        return _decode_xla(q, new_rows, pool, tables, lens, base, v_width)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    S, H, W = q.shape
    _, ps, _ = pool.shape
    pp = tables.shape[1]
    R = int(v_width)
    cpk = max(1, min(stream_chunk_pages(ps), pp))
    CK = cpk * ps
    unroll = math.gcd(cpk, 8)
    overfull = lens >= jnp.int32(pp * ps)
    wpages = jnp.take_along_axis(
        tables, jnp.minimum(lens // ps, pp - 1)[:, None], 1)[:, 0] + base
    slotmask = ((jnp.arange(ps, dtype=jnp.int32)[None, :]
                 == (lens % ps)[:, None]) & ~overfull[:, None]) \
        .astype(jnp.float32)[:, :, None]                   # [S, ps, 1]
    nr = new_rows.astype(pool.dtype).astype(jnp.float32)[:, None, :]
    meta = jnp.concatenate([jnp.reshape(base, (1,)), lens, wpages])

    def kernel(meta_ref, tbl_ref, q_ref, nr_ref, sm_ref, pool_in, o_ref,
               pool_hbm, pg, kb, wsem, rsem):
        del pool_in                         # aliased: pool_hbm
        s = pl.program_id(0)
        base_p = meta_ref[0]
        n = meta_ref[1 + s]
        wp = meta_ref[1 + S + s]
        nch = (n + jnp.int32(CK - 1)) // jnp.int32(CK)

        def gather(c, slot, act):
            def some(j8, carry):
                for j in range(unroll):
                    pidx = jnp.minimum(c * cpk + j8 * unroll + j,
                                       jnp.int32(pp - 1))
                    pid = base_p + tbl_ref[s * pp + pidx]
                    act(pltpu.make_async_copy(
                        pool_hbm.at[pid], kb.at[slot, j8 * unroll + j],
                        rsem.at[slot]))
                return carry
            jax.lax.fori_loop(0, cpk // unroll, some, 0)

        @pl.when(nch > 0)
        def _():
            gather(jnp.int32(0), jnp.int32(0), lambda cp: cp.start())

        # the current token's row into its page: whole-page read, select,
        # whole-page write; the write overlaps the walk (a raced read sees
        # the same bytes except the current row, which the walk masks)
        page_in = pltpu.make_async_copy(pool_hbm.at[wp], pg, wsem.at[0])
        page_out = pltpu.make_async_copy(pg, pool_hbm.at[wp], wsem.at[1])
        page_in.start()
        page_in.wait()
        nrv = nr_ref[0]                                     # [1, W] f32
        sel = jnp.broadcast_to(sm_ref[0], (ps, W)) > jnp.float32(0.5)
        pg[...] = jnp.where(sel, jnp.broadcast_to(nrv, (ps, W)),
                            pg[...].astype(jnp.float32)).astype(pg.dtype)
        page_out.start()

        qv = q_ref[0]                                       # [H, W]

        def chunk(c, carry):
            m, l, acc = carry
            slot = jax.lax.rem(c, jnp.int32(2))

            @pl.when(c + 1 < nch)
            def _():
                gather(c + 1, jnp.int32(1) - slot, lambda cp: cp.start())

            gather(c, slot, lambda cp: cp.wait())
            kt = kb[slot].reshape(CK, W)
            sc = jax.lax.dot_general(
                qv, kt, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)         # [H, CK]
            kpos = jax.lax.broadcasted_iota(jnp.int32, (H, CK), 1) + c * CK
            valid = kpos < n
            sc = jnp.where(valid, sc, jnp.float32(_NEG))
            pm = jnp.maximum(m, sc.max(-1, keepdims=True))
            alpha = jnp.exp(m - pm)
            p = jnp.where(valid, jnp.exp(sc - pm), jnp.float32(0.0))
            l = l * alpha + p.sum(-1, keepdims=True)
            pv = jax.lax.dot_general(
                p.astype(kt.dtype), kt[:, :R], (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)         # [H, R]
            return pm, l, acc * alpha + pv

        m, l, acc = jax.lax.fori_loop(
            jnp.int32(0), nch, chunk,
            (jnp.full((H, 1), _NEG, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, R), jnp.float32)))
        # the current token, from the operand
        lc = jnp.sum(qv.astype(jnp.float32) * nrv, axis=-1, keepdims=True)
        pm = jnp.maximum(m, lc)
        alpha = jnp.exp(m - pm)
        wc = jnp.exp(lc - pm)
        l = l * alpha + wc
        acc = acc * alpha + wc * nrv[:, :R]
        o_ref[0] = acc / jnp.maximum(l, jnp.float32(1e-30))
        page_out.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, H, W), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec((1, 1, W), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec((1, ps, 1), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=[
            pl.BlockSpec((1, H, R), lambda s, *_: (s, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((ps, W), pool.dtype),
            pltpu.VMEM((2, cpk, ps, W), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    with _enable_x64(False), jax.named_scope("pt_mla_paged_decode"):
        out, pool = pl.pallas_call(
            kernel,
            name="pt_mla_paged_decode",
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((S, H, R), jnp.float32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            # inputs are numbered with the two scalar-prefetch operands
            # first: the pool is arg 5
            input_output_aliases={5: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=not _chip.on_tpu(),
        )(meta, tables.reshape(-1), q.astype(pool.dtype), nr, slotmask,
          pool)
    return out, pool

"""Multi-head latent attention over a paged LATENT pool — the serving
attention of ``"latent_attention"`` layers (``incubate/nn/layer_pattern``).

The pool holds ONE row a token and layer, ``[c | k_r | 0]``: the normed
latent (``kv_lora_rank``), the rotated rope key (one head, shared by every
query head), zero lanes up to a whole 128-lane tile (``LatentAttentionSpec
.row_width``). The two kernels attend in the form that suits their phase:

``pt_mla_paged_prefill``  EXPANDED: a query head is ``[q_rope | q_nope]``,
    its key ``[k_r | c W_uk^T]``, its value ``c W_uv``. One sequence's chunk
    ``[c, H, qk_head_dim]`` at positions ``start ..``: the chunk's rows are
    written into their pages (whole-page read-modify-write, the pool
    aliased, as ``pt_paged_kv_write``), then a grid step a group of heads
    walks the pool tiles the block table names up to the chunk's last real
    row; each tile is expanded ONCE in VMEM to the group's K / V and scored
    by ALL c query rows, online softmax. A key row costs ``kv_lora_rank x
    (qk_nope + v_head)`` MACs a head once a chunk, against ``qk_head +
    v_head`` a query it meets.
``pt_mla_paged_decode``   ABSORBED: a query head is ``[q_nope W_uk^T |
    q_rope | 0]`` over a row's lanes, its score ONE dot with the row, its
    value the row's first ``kv_lora_rank`` lanes (the caller contracts the
    result with ``W_uv``). One token for every slot ``[S, H, W]``: a grid
    step a sequence appends the token's row to its page and walks the
    sequence's own pages, each row read once for all H heads — one query
    a row makes expansion dearer than absorption.

Neither hands XLA a slice of the loop-carried pool. Off the chip both take
an XLA scatter + gather that computes the same thing and is the reference
the kernels are tested against (``backend="interpret"`` runs the kernels
through the interpreter).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ...device import chip as _chip
from ...device.vmem import KERNEL_VMEM_LIMIT_BYTES
from .paged_attention import _enable_x64, stream_chunk_pages

__all__ = ["LatentKV", "yarn_inv_freq", "yarn_rope_table",
           "rope_interleaved", "query_temperature", "mla_prefill_attend",
           "prefill_tile_tokens", "prefill_keys_expanded",
           "mla_decode_attend", "decode_rows"]

_NEG = -1e30
#: pool tokens a prefill tile expands and scores at a time (whole pages)
PREFILL_TILE_TOKENS = 256
#: heads a prefill grid step attends: their K / V of a tile live together
PREFILL_HEAD_GROUP = 8


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

def _prefill_xla(q, rows, pool, tables, start, vlen, w_uk, w_uv):
    c, H, D = q.shape
    _, ps, W = pool.shape
    pp = tables.shape[1]
    _, n, R = w_uk.shape
    pos = start[0] + jnp.arange(c, dtype=jnp.int32)
    live = (jnp.arange(c) < vlen[0]) & (pos < pp * ps)
    page = jnp.where(live, tables[0, jnp.minimum(pos // ps, pp - 1)], 0)
    pool = pool.at[page, jnp.where(live, pos % ps, 0)].set(
        jnp.where(live[:, None], rows.astype(pool.dtype),
                  pool[0, 0][None]))
    keys = pool[tables[0]].reshape(pp * ps, W).astype(jnp.float32)
    def rounded(a):                     # as the kernel's VMEM holds it
        return a.astype(pool.dtype).astype(jnp.float32)

    # every head's K = [k_r | c W_uk^T] and V = c W_uv
    lat, kr = keys[:, :R], keys[:, R: R + D - n]
    k = rounded(jnp.concatenate(
        [jnp.broadcast_to(kr[:, None], (pp * ps, H, D - n)),
         jnp.einsum("kr,hnr->khn", lat, rounded(w_uk))], -1))
    v = rounded(jnp.einsum("kr,hrv->khv", lat, rounded(w_uv)))
    sc = jnp.einsum("chd,khd->chk", q.astype(jnp.float32), k)
    kpos = jnp.arange(pp * ps, dtype=jnp.int32)
    msk = kpos[None, :] <= pos[:, None]
    sc = jnp.where(msk[:, None, :], sc, _NEG)
    p = jax.nn.softmax(sc, -1)
    return jnp.einsum("chk,khv->chv", p, v).astype(q.dtype), pool


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

def prefill_tile_tokens(page_size: int, table_pages: int) -> int:
    """Pool tokens of a prefill tile: whole pages, at most
    ``PREFILL_TILE_TOKENS`` and at most the table."""
    return max(1, min(PREFILL_TILE_TOKENS // page_size, table_pages)) \
        * page_size


def prefill_keys_expanded(start: int, n: int, page_size: int,
                          table_pages: int) -> int:
    """Key rows ``pt_mla_paged_prefill`` expands a head for a chunk of
    ``n`` real rows at ``start``: whole tiles up to the last real row's
    position (the table's end at most)."""
    ck = prefill_tile_tokens(page_size, table_pages)
    reach = min(start + n, page_size * table_pages)
    return -(-reach // ck) * ck


def mla_prefill_attend(q, rows, pool, block_tables, start, valid_lens,
                       w_uk, w_uv, *, backend: str = "auto"):
    """Write a chunk's latent rows and attend the chunk over its prefix.

    q ``[c, H, D]`` the EXPANDED queries of ONE sequence, ``[q_rope |
    q_nope]`` a head (scale and temperature folded in); ``rows [c, W]``
    the chunk's own cache rows; ``pool [P, page, W]`` (donated by the
    caller: it comes back aliased); ``block_tables [1, pp]`` ABSOLUTE page
    ids; ``start [1]`` the chunk's first position, ``valid_lens [1]`` its
    real rows (rows past them are not written: a bucketed chunk's padding
    never lands in the pool); ``w_uk [H, nope, R]`` / ``w_uv [H, R, v]``
    the layer's up-projections of the latent. A key head is ``[k_r | c
    W_uk^T]`` (``k_r`` the row's ``D - nope`` lanes after the latent), a
    value ``c W_uv``. Query ``i`` attends positions ``<= start + i``.
    Returns ``(out [c, H, v]`` in q's dtype, ``pool')``; rows past
    ``valid_lens`` hold no answer.
    """
    if backend not in ("auto", "interpret"):
        raise ValueError(f"mla_prefill_attend backend={backend!r}: "
                         "expected 'auto' or 'interpret'")
    c, H, D = q.shape
    _, ps, W = pool.shape
    _, n, R = w_uk.shape
    V = w_uv.shape[2]
    if R + D > W:
        raise ValueError(f"mla_prefill_attend: a row of {W} lanes has no "
                         f"room for a {D}-lane key after the {R}-lane "
                         "latent")
    start = start.astype(jnp.int32)
    vlen = jnp.minimum(valid_lens.astype(jnp.int32), c)
    tables = block_tables.astype(jnp.int32)
    if backend == "auto" and not _chip.on_tpu():
        return _prefill_xla(q, rows, pool, tables, start, vlen, w_uk, w_uv)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pp = tables.shape[1]
    r = D - n
    g = math.gcd(H, PREFILL_HEAD_GROUP)
    CK = prefill_tile_tokens(ps, pp)
    cpk = CK // ps
    # pages a chunk of c rows can touch at any offset inside a page
    npg = (c + ps - 2) // ps + 1
    span = npg * ps
    off = start[0] % ps
    first = start[0] // ps
    # the chunk shifted by ``off`` into whole page-shaped blocks
    shifted = jax.lax.dynamic_slice_in_dim(
        jnp.pad(rows.astype(pool.dtype), ((ps, span - c), (0, 0))),
        ps - off, span, axis=0).reshape(npg, ps, W)
    rr = jnp.arange(span, dtype=jnp.int32)
    live = ((rr >= off) & (rr < off + vlen[0])
            & (first * ps + rr < pp * ps)).reshape(npg, ps)
    counts = live.sum(-1).astype(jnp.int32)
    rowmask = live.astype(jnp.float32)[:, :, None]
    pidx = jnp.minimum(first + jnp.arange(npg, dtype=jnp.int32), pp - 1)
    pids = tables[0, pidx]
    meta = jnp.concatenate([start, vlen])
    qf = q.reshape(c, H * D).astype(pool.dtype)
    # a head's K / V weight over the latent lanes: [0_r | W_uk^T | W_uv]
    # — the tile's rope key fills the zero lanes
    wkv = jnp.concatenate(
        [jnp.zeros((H, R, r), pool.dtype),
         jnp.swapaxes(w_uk, 1, 2).astype(pool.dtype),
         w_uv.astype(pool.dtype)], -1)                     # [H, R, D + V]

    def kernel(meta_ref, pid_ref, cnt_ref, tbl_ref, q_ref, w_ref, new_ref,
               msk_ref, pool_in, o_ref, pool_hbm, pg, kb, m_ref, l_ref,
               acc_ref, wsem, rsem):
        del pool_in                         # aliased: pool_hbm
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

        @pl.when(pl.program_id(0) == 0)
        def _():
            # the chunk's rows into their pages: whole-page read, select,
            # whole-page write (single-slot DMA slices break the sublane
            # tiling); landed before any group reads the pool
            each_touched(page_in, lambda cp: cp.start())
            each_touched(page_in, lambda cp: cp.wait())
            sel = jnp.broadcast_to(msk_ref[...], pg.shape) > jnp.float32(0.5)
            pg[...] = jnp.where(sel, new_ref[...].astype(jnp.float32),
                                pg[...].astype(jnp.float32)).astype(pg.dtype)
            each_touched(page_out, lambda cp: cp.start())
            each_touched(page_out, lambda cp: cp.wait())

        # the tiles up to the last real row's position
        nk = (jnp.minimum(st + meta_ref[1], jnp.int32(pp * ps))
              + jnp.int32(CK - 1)) // jnp.int32(CK)

        def copies(j, slot):
            out = []
            for p in range(cpk):
                pidx = jnp.minimum(j * cpk + p, jnp.int32(pp - 1))
                out.append(pltpu.make_async_copy(
                    pool_hbm.at[tbl_ref[pidx]], kb.at[slot, p],
                    rsem.at[slot]))
            return out

        @pl.when(nk > 0)
        def _():
            for cp in copies(jnp.int32(0), jnp.int32(0)):
                cp.start()

        m_ref[...] = jnp.full(m_ref.shape, _NEG, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        def masked(j, s):
            qpos = st + jax.lax.broadcasted_iota(jnp.int32, (c, CK), 0)
            kpos = jax.lax.broadcasted_iota(jnp.int32, (c, CK), 1) + j * CK
            keep = (kpos <= qpos) & (kpos < jnp.int32(pp * ps))
            return jnp.where(keep, s, jnp.float32(_NEG))

        def body(j, carry):
            slot = jax.lax.rem(j, jnp.int32(2))

            @pl.when(j + 1 < nk)
            def _():
                for cp in copies(j + 1, jnp.int32(1) - slot):
                    cp.start()

            for cp in copies(j, slot):
                cp.wait()
            tile = kb[slot].reshape(CK, W)
            lat = tile[:, :R]
            lane = jax.lax.broadcasted_iota(jnp.int32, (CK, D), 1)
            kr = jnp.where(lane < r, tile[:, R: R + D].astype(jnp.float32),
                           jnp.float32(0.0))        # [k_r | 0]
            # keys past the chunk's first position or the table's end (a
            # last tile the table does not fill repeats its last page)
            diag = ((j + 1) * CK - 1 > st) | ((j + 1) * CK > pp * ps)
            for h in range(g):
                kv = jax.lax.dot_general(
                    lat, w_ref[h], (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)     # [CK, D + V]
                k = (kv[:, :D] + kr).astype(tile.dtype)
                v = kv[:, D:].astype(tile.dtype)
                s = jax.lax.dot_general(
                    q_ref[:, h * D: (h + 1) * D], k,
                    (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)     # [c, CK]
                s = jax.lax.cond(diag, functools.partial(masked, j),
                                 lambda z: z, s)
                m = m_ref[h]
                pm = jnp.maximum(m, s.max(-1, keepdims=True))
                alpha = jnp.exp(m - pm)
                p = jnp.exp(s - pm)
                l_ref[h] = l_ref[h] * alpha + p.sum(-1, keepdims=True)
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)     # [c, V]
                acc_ref[h] = acc_ref[h] * alpha + pv
                m_ref[h] = pm
            return carry

        jax.lax.fori_loop(jnp.int32(0), nk, body, 0)
        for h in range(g):
            o_ref[:, h * V: (h + 1) * V] = (
                acc_ref[h] / jnp.maximum(l_ref[h], jnp.float32(1e-30))
            ).astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(H // g,),
        in_specs=[
            pl.BlockSpec((c, g * D), lambda i, *_: (0, i)),
            pl.BlockSpec((g, R, D + V), lambda i, *_: (i, 0, 0)),
            pl.BlockSpec((npg, ps, W), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec((npg, ps, 1), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=[
            pl.BlockSpec((c, g * V), lambda i, *_: (0, i)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((npg, ps, W), pool.dtype),
            pltpu.VMEM((2, cpk, ps, W), pool.dtype),
            pltpu.VMEM((g, c, 1), jnp.float32),
            pltpu.VMEM((g, c, 1), jnp.float32),
            pltpu.VMEM((g, c, V), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    with _enable_x64(False), jax.named_scope("pt_mla_paged_prefill"):
        out, pool = pl.pallas_call(
            kernel,
            name="pt_mla_paged_prefill",
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((c, H * V), q.dtype),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            # inputs are numbered with the four scalar-prefetch operands
            # first: the pool is arg 8
            input_output_aliases={8: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=not _chip.on_tpu(),
        )(meta, pids, counts, tables.reshape(-1), qf, wkv, shifted, rowmask,
          pool)
    return out.reshape(c, H, V), pool


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
    def launch(kernel, spec, operands):
        from jax.experimental import pallas as pl
        with _enable_x64(False), jax.named_scope("pt_mla_paged_decode"):
            return pl.pallas_call(kernel, name="pt_mla_paged_decode",
                                  **spec)(*operands)

    return decode_rows(q, new_rows, pool, block_tables, seq_lens,
                       pool_base, v_width=v_width, backend=backend,
                       launch=launch, who="mla_decode_attend")


def decode_rows(q, new_rows, pool, block_tables, seq_lens, pool_base, *,
                v_width: int, backend: str, launch, who: str):
    """The decode of a layer-folded pool whose query scores a row with ONE
    dot (arguments as ``mla_decode_attend``'s). Off the chip (``backend``
    "auto") the XLA path; else the kernel, handed to ``launch(kernel,
    spec, operands)``, which makes the ``pallas_call`` under its caller's
    own name and returns ``(out, pool')``; ``who`` names the caller in an
    error."""
    if backend not in ("auto", "interpret"):
        raise ValueError(f"{who} backend={backend!r}: "
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
    return launch(
        kernel,
        dict(grid_spec=grid_spec,
             out_shape=[jax.ShapeDtypeStruct((S, H, R), jnp.float32),
                        jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
             # inputs are numbered with the two scalar-prefetch operands
             # first: the pool is arg 5
             input_output_aliases={5: 1},
             compiler_params=pltpu.CompilerParams(
                 dimension_semantics=("arbitrary",),
                 vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
             interpret=not _chip.on_tpu()),
        (meta, tables.reshape(-1), q.astype(pool.dtype), nr, slotmask, pool))

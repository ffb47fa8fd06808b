"""Paged-KV block attention — the serving attention path.

TPU-native equivalent of the reference's paged-KV serving kernel
(reference: paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu
and the decode kernel family masked_multihead_attention_kernel.cu). The KV
cache lives in fixed-size pages addressed through per-sequence block
tables, so sequences grow without reallocation/copy and memory is shared
across a continuous batch.

One decode step picks its attention ONCE, here, from what it can see
(``plan_decode_attention``; every layer then calls ``decode_attend``):
an int8 pool takes ``paged_decode_attention_inplace_q``; on the chip a
bf16/f32 pool whose head_dim fills the lanes takes
``paged_decode_attention_inplace`` (append + attend in one Pallas call
over the pages the block tables name); everything else — off the chip,
narrow heads — takes ``write_kv_pages`` + ``paged_attention``, an XLA
gather + masked dense attention that computes the same thing and is the
reference the kernels are tested against.

Layouts (PAGE-MAJOR, head-major pages — r5 redesign):
  q            [batch, num_q_heads, head_dim]        one decode token/seq
  key_cache    [num_pages, num_kv_heads, page_size, head_dim]
  value_cache  [num_pages, num_kv_heads, page_size, head_dim]
  seq_lens     [batch] int32   tokens already in cache (incl. current)
  block_tables [batch, pages_per_seq] int32          page ids per sequence

Why page-major: one page is a CONTIGUOUS [n_kv, page_size, d] block in
the default XLA layout, so (a) the decode scatter writes token rows
in-place with no layout transition, (b) the Pallas decode kernels DMA
whole pages HBM→VMEM, and (c) the XLA gather fallback gathers on the
leading dim. Heads-major WITHIN the page (r5, vs r4's [ps, n_kv, d]):
the streaming decode kernel consumes one kv head at a time, and with
heads outer each per-head slice of a page is a contiguous
[page_size, d] block — the r4 token-major page made that a 256-byte
strided gather that cost ~40% of kernel time (decode ablation r5).
"""
from __future__ import annotations

import contextlib
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ...device import chip as _chip
from ...device.vmem import KERNEL_VMEM_LIMIT_BYTES

__all__ = ["paged_attention", "plan_decode_attention", "decode_attend",
           "write_kv_pages", "write_prefill_kv_pages",
           "write_prefill_kv_inplace"]


def _enable_x64(flag: bool):
    """x64 off around a kernel trace: f64/i64 leaking into a kernel does
    not legalize in Mosaic. Off-TPU the kernel runs interpreted as
    ordinary jax ops under the ambient setting (the package turns x64
    on), so the context is a no-op there, as it is when the config
    already matches."""
    if bool(jax.config.jax_enable_x64) == bool(flag) or not _chip.on_tpu():
        return contextlib.nullcontext()
    return jax.enable_x64(flag)


def paged_attention(q, key_cache, value_cache, seq_lens, block_tables,
                    pool_base=None):
    """Single-token decode attention over a paged KV cache as an XLA
    gather + masked dense attention: the reference every decode kernel
    is checked against, and the path ``decode_attend`` takes off the
    chip and for head sizes the kernels do not tile.

    Raw-array functional op. ``seq_lens`` counts the tokens in the cache
    INCLUDING the current one. ``pool_base``: first physical page of
    this layer's region in a layer-folded pool (``block_tables`` then
    hold LAYER-LOCAL page ids; it may be a traced loop index).
    """
    if pool_base is not None:
        block_tables = block_tables + pool_base
    b, n_q, d = q.shape
    _, n_kv, page_size, _ = key_cache.shape
    pages_per_seq = block_tables.shape[1]
    max_len = pages_per_seq * page_size

    # gather pages on the leading dim: [b, pages, n_kv, page, d];
    # the einsums consume the head-major page layout directly
    k = key_cache[block_tables]
    v = value_cache[block_tables]

    group = n_q // n_kv  # GQA: q heads per kv head
    qh = q.reshape(b, n_kv, group, d)
    # fp32 scores by design (softmax stability; QK reads are KV-bound)
    # tpu-lint: ok(X-PROMOTE) -- attention scores fp32 by design
    logits = jnp.einsum("bngd,bpnsd->bngps", qh.astype(jnp.float32),
                        k.astype(jnp.float32)) * (d ** -0.5)
    logits = logits.reshape(b, n_kv, group, max_len)
    pos = jnp.arange(max_len)
    mask = pos[None, :] < seq_lens[:, None]           # [b, max_len]
    logits = jnp.where(mask[:, None, None, :], logits,
                       jnp.finfo(jnp.float32).min)
    w = jax.nn.softmax(logits, axis=-1) \
        .reshape(b, n_kv, group, pages_per_seq, page_size)
    # tpu-lint: ok(X-PROMOTE) -- fp32 PV accumulation pairs with scores
    out = jnp.einsum("bngps,bpnsd->bngd", w, v.astype(jnp.float32))
    return out.reshape(b, n_q, d).astype(q.dtype)


def build_pool_ownership(block_tables, seq_lens, pool_pages, page_size):
    """Token-level inverse of the block tables: for each token slot of
    one layer's page pool, which batch row owns it and at what position.

    Returns (owner_tok [P*ps] int32 — owning row or -1, pos_tok [P*ps]
    int32 — the token's position in its owner's sequence). Page entries
    whose page-start position is already >= the row's seq_len are
    treated as unallocated padding (block tables are padded with page 0;
    the reserved scratch page must not inherit an owner). Layer-
    independent for the layer-folded pool — compute ONCE per decode
    step and share across layers (the int8 kernel's mask operands).
    """
    b, pp = block_tables.shape
    ps = page_size
    jstart = jnp.arange(pp, dtype=jnp.int32)[None, :] * ps    # [1, pp]
    validj = jstart < seq_lens.astype(jnp.int32)[:, None]     # [b, pp]
    # invalid entries are redirected out of range and dropped
    idx = jnp.where(validj, block_tables.astype(jnp.int32),
                    jnp.int32(pool_pages)).ravel()
    rows = jnp.broadcast_to(
        jnp.arange(b, dtype=jnp.int32)[:, None], (b, pp)).ravel()
    pidx = jnp.broadcast_to(
        jnp.arange(pp, dtype=jnp.int32)[None, :], (b, pp)).ravel()
    owner_page = jnp.full((pool_pages,), -1, jnp.int32) \
        .at[idx].set(rows, mode="drop")
    page_index = jnp.zeros((pool_pages,), jnp.int32) \
        .at[idx].set(pidx, mode="drop")
    owner_tok = jnp.repeat(owner_page, ps)
    pos_tok = (jnp.repeat(page_index, ps) * ps
               + jnp.tile(jnp.arange(ps, dtype=jnp.int32), (pool_pages,)))
    return owner_tok, pos_tok


# target token count per stream chunk; the engine rounds its pool
# allocation to a multiple of the resulting page count (see
# inference/engine.py _round_pool_pages, which imports this) so the
# kernels get full-size chunks
STREAM_CHUNK_TOKENS = 1024


def stream_chunk_pages(page_size: int) -> int:
    """Full-target pages-per-chunk for a page size: the pool-size
    rounding quantum, and the entries the in-place decode kernel gathers
    and scores at a time (on the chip 64 pages of 16 beat 16, 32 and 128
    at gpt3-1.3b's widths; 128 is 3-5% better at granite's: PERF.md,
    PR 32)."""
    return max(1, STREAM_CHUNK_TOKENS // max(page_size, 1))


def _pick_chunk_pages(pool_pages: int, page_size: int) -> int:
    """Pages per stream chunk: the largest divisor of the pool size
    whose token count stays near STREAM_CHUNK_TOKENS (DMA blocks of a
    few MB keep the HBM stream saturated; a divisor keeps every block
    in bounds)."""
    for cp in range(min(stream_chunk_pages(page_size), pool_pages),
                    0, -1):
        if pool_pages % cp == 0:
            return cp
    return 1


class PageWalk(NamedTuple):
    """The pages one decode step has to read, compacted: entry ``j`` of
    row ``r`` is live while ``j * page_size < seq_lens[r]``; live entries
    stand first, row by row, in table order. ``E`` is ``batch *
    pages_per_seq`` rounded up to whole chunks of ``stream_chunk_pages``.

    index  [E] int32  where the entry stands in the block tables, ``r *
                      pages_per_seq + j`` (the kernel reads the page id
                      there; past ``length``: entry 0's index again, so
                      a short last chunk gathers bytes that are live
                      and masked)
    length [1] int32  number of live entries
    owner  [E] int32  the entry's row, -1 past ``length``
    pos    [E] int32  sequence position of the page's first slot
    tok_owner [E // cp, cp * page_size] int32: per token slot of the
                      list the row that may attend to it (its position
                      is under the row's length), else -1 -- the mask
                      the kernel compares against its row ids
    """
    index: jax.Array
    length: jax.Array
    owner: jax.Array
    pos: jax.Array
    tok_owner: jax.Array


def build_page_walk(block_tables, seq_lens, page_size) -> PageWalk:
    """``PageWalk`` of ``block_tables [b, pp]`` at ``seq_lens [b]``
    (tokens in the pool, the current one not among them). A row takes
    its pages from ITS table, so a physical page that two tables name
    (a shared prefix) appears once a row. Layer-independent: build it
    once a decode step and hand it to every layer's
    ``paged_decode_attention_inplace``. A row's live entries are a
    prefix of its table, so the list follows from the rows' page counts
    alone: compares and row sums over ``[E, b]``, no gather and no
    scatter (either costs 5 ns an entry on the chip, 0.1-0.3 ms a step:
    my chip run, PR 32); the table itself is read by the kernel."""
    b, pp = block_tables.shape
    ps = int(page_size)
    cp = stream_chunk_pages(ps)
    E = -(-(b * pp) // cp) * cp
    lens = seq_lens.astype(jnp.int32)
    count = jnp.minimum((lens + (ps - 1)) // ps, pp)       # [b]
    ends = jnp.cumsum(count, dtype=jnp.int32)              # [b]
    length = ends[-1]
    e = jnp.arange(E, dtype=jnp.int32)
    # rows that end at or before entry e: their number is e's row, the
    # largest of their ends is where that row starts
    before = e[:, None] >= ends[None, :]                   # [E, b]
    row = jnp.minimum(jnp.sum(before, axis=1, dtype=jnp.int32), b - 1)
    start = jnp.max(jnp.where(before, ends[None, :], 0), axis=1)
    row_len = jnp.sum(
        jnp.where(row[:, None] == jnp.arange(b, dtype=jnp.int32)[None, :],
                  lens[None, :], 0), axis=1, dtype=jnp.int32)
    live = e < length
    j = jnp.clip(e - start, 0, pp - 1)
    index = row * pp + j
    index = jnp.where(live, index, index[0])
    owner = jnp.where(live, row, -1)
    pos = jnp.where(live, j * ps, 0)
    tok_pos = pos[:, None] + jnp.arange(ps, dtype=jnp.int32)[None, :]
    tok_owner = jnp.where(tok_pos < row_len[:, None], owner[:, None], -1)
    return PageWalk(index, length.reshape(1), owner, pos,
                    tok_owner.reshape(E // cp, cp * ps))


def paged_decode_attention_inplace(q, new_k, new_v, key_cache,
                                   value_cache, seq_lens, block_tables,
                                   pool_base=None, walk=None):
    """Fused KV-append + decode attention over the pages the block
    tables name, IN PLACE.

    One Pallas kernel per layer does what the reference's
    masked_multihead_attention_kernel.cu does on GPU: append the current
    token's K/V to the paged cache AND attend over it. Returns
    (out [b, n_q, d], key_cache', value_cache') with the pools aliased
    in place (``input_output_aliases``).

    Why fusion is load-bearing on TPU (r5 HLO diagnosis): with a
    separate XLA scatter in the decode loop, layout assignment pins the
    loop-carried pool to the scatter's preferred token-major physical
    layout while the Pallas custom call constrains the default
    head-major layout — XLA inserts two FULL-POOL copies per layer per
    token (measured 2502 -> 281 tok/s end-to-end). Fused, the pool is
    touched only by this kernel, so it stays in the default layout and
    is never copied.

    What it walks: the ``PageWalk`` of (block_tables, seq_lens) — the
    compacted list of the pages the rows' tables name for their current
    lengths — in chunks of ``stream_chunk_pages`` entries. A loop with a
    dynamic trip count (the list's length over the chunk, rounded up)
    gathers chunk c+1 page by page into one half of a double buffer
    (one DMA a page and side: a page is a contiguous [n_kv, ps, d]
    block) while chunk c is scored: one batched-per-head MXU matmul for
    ALL rows against the chunk's token slots, masked to each slot's own
    row, online softmax in VMEM. Pages no table names are never read,
    whatever the pool holds; the kernel's time follows the tokens in
    the batch, not the size of the layer's region (until PR 32 it
    streamed and scored the whole region, 1,664 pages a call whether 30
    or 1,200 were live). ``pool_base`` is the first physical page of
    the layer's region (tables and walk hold LAYER-LOCAL ids; it may be
    a traced loop index). ``walk``: the step's ``build_page_walk``
    result, shared by all layers (built here when absent).

    The current token's K/V arrive as OPERANDS: they join the softmax
    as a virtual chunk (diagonal mask), while 2b whole-page DMAs patch
    them into their page slots concurrently — the gathered reads of
    those slots are masked out (where-before-max also kills any NaN
    garbage), so the write/read race is benign and the writes land
    before the kernel returns.

    seq_lens = tokens already cached EXCLUDING the current token (the
    current token's write position, and its softmax entry comes from
    the operand, not the pool).

    Precondition: every row needs a free slot, i.e.
    ``seq_lens[i] < block_tables.shape[1] * page_size``. An exactly-full
    sequence has nowhere to append; rather than let the clamped
    ``lens // page_size`` index silently overwrite slot
    ``lens % page_size`` of the row's LAST allocated page (HBM cache
    corruption), overfull rows get a MASKED NO-OP write: the page
    read-modify-write runs with an all-zero slot selector, writing back
    identical bytes. The attention output for such a row still folds in
    the operand K/V (the current token attends to itself) but the pool
    is untouched — the caller must grow the table before retrying.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n_q, d = q.shape
    _, n_kv, ps, _ = key_cache.shape
    g = n_q // n_kv
    bg = b * g
    scale = d ** -0.5
    NEG = -1e30

    if walk is None:
        walk = build_page_walk(block_tables, seq_lens, ps)
    cp = stream_chunk_pages(ps)
    C = cp * ps
    nch_max = walk.tok_owner.shape[0]
    unroll = math.gcd(cp, 8)

    qt = jnp.transpose(q.reshape(b, n_kv, g, d), (1, 0, 2, 3)) \
        .reshape(n_kv, bg, d).astype(key_cache.dtype)
    # two views of the current K/V: [n_kv, b, d] for the compute slices,
    # [b, n_kv, d] for the page patch (broadcast over slots)
    nk_t = jnp.swapaxes(new_k, 0, 1).astype(key_cache.dtype)
    nv_t = jnp.swapaxes(new_v, 0, 1).astype(value_cache.dtype)
    # the page patch's view, rounded to the pool's dtype: float32 with
    # a unit slot dim, which the kernel broadcasts over the page's slots
    # (Mosaic broadcasts only 32-bit values along the sub-minor dim and
    # cannot insert one on 16-bit values)
    nk_w = new_k.astype(key_cache.dtype).astype(jnp.float32)[:, :, None, :]
    nv_w = new_v.astype(value_cache.dtype).astype(jnp.float32)[:, :, None, :]

    base = jnp.asarray(0 if pool_base is None else pool_base, jnp.int32)
    lens_i = seq_lens.astype(jnp.int32)
    tables = block_tables.astype(jnp.int32)
    # seq_lens < pages_per_seq*page_size guard (see docstring): overfull
    # rows clamp their write-page index in range and zero their slot
    # selector, turning the page RMW into a no-op write-back
    pp = block_tables.shape[1]
    overfull = lens_i >= jnp.int32(pp * ps)                # [b]
    wpages = (jnp.take_along_axis(
        tables,
        jnp.minimum(lens_i // ps, pp - 1)[:, None],
        axis=1)[:, 0] + base)                              # [b] abs page
    # slot selector as a 4-D f32 operand (single-slot DMA slices violate
    # Mosaic's sublane tiling — the kernel read-modify-writes WHOLE
    # pages and blends the slot row arithmetically; f32 because Mosaic
    # supports only 32-bit sub-minor broadcasts, and pre-shaped 4-D
    # because i1/bf16 dim insertion doesn't lower)
    slotmask = ((jnp.arange(ps, dtype=jnp.int32)[None, :]
                 == (lens_i % ps)[:, None])
                & ~overfull[:, None]) \
        .astype(jnp.float32)[:, None, :, None]           # [b,1,ps,1]
    # scalars: [region base, chunks to walk, each row's write page]
    nch = (walk.length + jnp.int32(cp - 1)) // jnp.int32(cp)
    scalars = jnp.concatenate([jnp.reshape(base, (1,)), nch, wpages])

    def kernel(s_ref, walk_ref, tbl_ref, q_ref, own_ref, nk_ref, nv_ref,
               nkw_ref, nvw_ref, sm_ref, k_in, v_in, o_ref, k_hbm, v_hbm,
               kb, vb, pgk, pgv, m_ref, l_ref, acc_ref, rsem, pin_sem,
               pout_sem):
        del k_in, v_in                      # aliased: k_hbm / v_hbm
        base_p = s_ref[0]
        nchunks = s_ref[1]

        def page_copies(idx, slot, j):
            """Entry ``idx * cp + j`` of the walk into page j of half
            ``slot``: (K copy, V copy)."""
            pid = base_p + tbl_ref[walk_ref[idx * cp + j]]
            return (
                pltpu.make_async_copy(k_hbm.at[pid], kb.at[slot, j],
                                      rsem.at[slot, 0]),
                pltpu.make_async_copy(v_hbm.at[pid], vb.at[slot, j],
                                      rsem.at[slot, 1]))

        def gather(idx, slot, act):
            # eight pages a loop step (Mosaic unrolls a loop whole or
            # not at all; cp copies in a row would be cp x 2 call sites)
            def eight(j8, carry):
                for j in range(unroll):
                    for cpy in page_copies(idx, slot, j8 * unroll + j):
                        act(cpy)
                return carry
            jax.lax.fori_loop(0, cp // unroll, eight, 0)

        def page_in(i):
            pid = s_ref[2 + i]
            return (
                pltpu.make_async_copy(k_hbm.at[pid], pgk.at[i],
                                      pin_sem.at[i, 0]),
                pltpu.make_async_copy(v_hbm.at[pid], pgv.at[i],
                                      pin_sem.at[i, 1]))

        def page_out(i):
            pid = s_ref[2 + i]
            return (
                pltpu.make_async_copy(pgk.at[i], k_hbm.at[pid],
                                      pout_sem.at[i, 0]),
                pltpu.make_async_copy(pgv.at[i], v_hbm.at[pid],
                                      pout_sem.at[i, 1]))

        m_ref[...] = jnp.full((n_kv, bg), NEG, jnp.float32)
        l_ref[...] = jnp.zeros((n_kv, bg), jnp.float32)
        acc_ref[...] = jnp.zeros((n_kv, bg, d), jnp.float32)

        @pl.when(nchunks > 0)
        def _():
            gather(jnp.int32(0), jnp.int32(0), lambda cpy: cpy.start())

        # current token's K/V: read-modify-write each row's page
        # (whole-page DMAs; the slot row is patched by vector select).
        # Page-outs overlap the walk — raced reads see identical bytes
        # except the masked current row — and are waited at the end.
        for i in range(b):
            for cpy in page_in(i):
                cpy.start()
        for i in range(b):
            for cpy in page_in(i):
                cpy.wait()
        sel = sm_ref[...]                                # [b,1,ps,1] f32
        inv = jnp.float32(1.0) - sel
        pgk[...] = (pgk[...].astype(jnp.float32) * inv
                    + nkw_ref[...] * sel).astype(pgk.dtype)
        pgv[...] = (pgv[...].astype(jnp.float32) * inv
                    + nvw_ref[...] * sel).astype(pgv.dtype)
        for i in range(b):
            for cpy in page_out(i):
                cpy.start()

        row_id = jax.lax.broadcasted_iota(jnp.int32, (bg, 1), 0) // g

        def chunk(c, carry):
            slot = jax.lax.rem(c, jnp.int32(2))

            @pl.when(c + 1 < nchunks)
            def _():
                gather(c + 1, jnp.int32(1) - slot,
                       lambda cpy: cpy.start())

            gather(c, slot, lambda cpy: cpy.wait())
            valid = own_ref[pl.ds(c, 1), :] == row_id    # [bg, C]

            # head loop (python-unrolled): with heads OUTER in the page
            # layout, each slice is a run of contiguous [ps, d] blocks
            for h in range(n_kv):
                k_h = kb[slot, :, h].reshape(C, d)
                v_h = vb[slot, :, h].reshape(C, d)
                logits = jax.lax.dot_general(
                    q_ref[h], k_h, (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32) \
                    * jnp.float32(scale)
                logits = jnp.where(valid, logits, jnp.float32(NEG))
                m = m_ref[h]
                pm = jnp.maximum(m, logits.max(-1))      # [bg]
                alpha = jnp.exp(m - pm)
                w = jnp.exp(logits - pm[:, None])        # [bg, C]
                w = jnp.where(valid, w, jnp.float32(0.0))
                l_h = l_ref[h] * alpha + w.sum(-1)
                pv = jax.lax.dot_general(
                    w.astype(v_h.dtype), v_h, (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)  # [bg, d]
                acc_h = acc_ref[h] * alpha[:, None] + pv
                m_ref[h] = pm
                l_ref[h] = l_h
                acc_ref[h] = acc_h
            return carry

        jax.lax.fori_loop(jnp.int32(0), nchunks, chunk, 0)

        # fold in the current token from the operands (row i attends to
        # operand column i), normalize
        diag = (jax.lax.broadcasted_iota(jnp.int32, (bg, b), 0) // g
                == jax.lax.broadcasted_iota(jnp.int32, (bg, b), 1))
        for h in range(n_kv):
            lc = jax.lax.dot_general(
                q_ref[h], nk_ref[h], (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32) \
                * jnp.float32(scale)                     # [bg, b]
            lc = jnp.where(diag, lc, jnp.float32(NEG))
            m = m_ref[h]
            pm = jnp.maximum(m, lc.max(-1))
            alpha = jnp.exp(m - pm)
            wc = jnp.exp(lc - pm[:, None])
            wc = jnp.where(diag, wc, jnp.float32(0.0))
            l_h = l_ref[h] * alpha + wc.sum(-1)
            pv = jax.lax.dot_general(
                wc.astype(nv_ref.dtype), nv_ref[h],
                (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)
            acc_h = acc_ref[h] * alpha[:, None] + pv
            o_ref[h] = acc_h / jnp.maximum(
                l_h, jnp.float32(1e-30))[:, None]
        for i in range(b):
            for cpy in page_out(i):
                cpy.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(1,),
        in_specs=[
            pl.BlockSpec((n_kv, bg, d), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec((nch_max, C), lambda i, *_: (0, 0)),
            pl.BlockSpec((n_kv, b, d), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec((n_kv, b, d), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec((b, n_kv, 1, d), lambda i, *_: (0, 0, 0, 0)),
            pl.BlockSpec((b, n_kv, 1, d), lambda i, *_: (0, 0, 0, 0)),
            pl.BlockSpec((b, 1, ps, 1), lambda i, *_: (0, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=[
            pl.BlockSpec((n_kv, bg, d), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, cp, n_kv, ps, d), key_cache.dtype),
            pltpu.VMEM((2, cp, n_kv, ps, d), value_cache.dtype),
            pltpu.VMEM((b, n_kv, ps, d), key_cache.dtype),
            pltpu.VMEM((b, n_kv, ps, d), value_cache.dtype),
            pltpu.VMEM((n_kv, bg), jnp.float32),
            pltpu.VMEM((n_kv, bg), jnp.float32),
            pltpu.VMEM((n_kv, bg, d), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((b, 2)),
            pltpu.SemaphoreType.DMA((b, 2)),
        ])
    with _enable_x64(False), \
            jax.named_scope("pt_paged_attention_decode_inplace"):
        out, ck, cv = pl.pallas_call(
            kernel,
            name="pt_paged_attention_decode_inplace",
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((n_kv, bg, d), jnp.float32),
                jax.ShapeDtypeStruct(key_cache.shape, key_cache.dtype),
                jax.ShapeDtypeStruct(value_cache.shape,
                                     value_cache.dtype),
            ],
            # inputs are numbered with the three scalar-prefetch operands
            # first: key_cache is arg 10, value_cache arg 11
            input_output_aliases={10: 1, 11: 2},
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=not _chip.on_tpu(),
        )(scalars, walk.index, tables.reshape(-1), qt, walk.tok_owner, nk_t,
          nv_t, nk_w, nv_w, slotmask, key_cache, value_cache)
    out = jnp.transpose(out.reshape(n_kv, b, g, d), (1, 0, 2, 3))
    return out.reshape(b, n_q, d).astype(q.dtype), ck, cv


def write_kv_pages(key_cache, value_cache, new_k, new_v, positions,
                   block_tables):
    """Scatter one new token's K/V per sequence into the paged cache.

    new_k/new_v: [batch, num_kv_heads, head_dim]; positions: [batch] slot
    index of the new token (0-based). Returns updated caches. The page-
    major layout keeps this a natural in-place scatter on a loop-carried
    pool: the indexed page dim leads; within the page the token's
    [n_kv, d] rows land at slot stride (head-major pages trade the r4
    contiguous token row for contiguous per-head READS — the decode
    loop reads ~100x more than it writes).
    """
    page_size = key_cache.shape[2]
    b = positions.shape[0]
    page_ids = block_tables[jnp.arange(b), positions // page_size]  # [b]
    slots = positions % page_size                                   # [b]
    key_cache = key_cache.at[page_ids, :, slots].set(
        new_k.astype(key_cache.dtype))
    value_cache = value_cache.at[page_ids, :, slots].set(
        new_v.astype(value_cache.dtype))
    return key_cache, value_cache


def write_prefill_kv_pages(key_cache, value_cache, k, v, block_tables,
                           start=None, valid_lens=None):
    """Write a prompt chunk's K/V ([batch, seq, n_kv, d]) into pages
    with an XLA scatter.

    Used where no Pallas call touches the pool in the same loop: the
    monolithic ``prefill_raw`` (dense attention over the operands), the
    int8-quantized pool (its attend is an XLA gather), and off the chip
    as the fallback of ``write_prefill_kv_inplace``. On the chip the
    chunked-prefill loop must NOT use it for a bf16/f32 pool: beside a
    Pallas call the scatter's preferred layout costs two whole-pool
    copies a layer (the note on ``paged_decode_attention_inplace``).

    ``start`` (optional [batch] int32): per-sequence position offset —
    the chunked-prefill path writes chunk c's tokens at positions
    ``start .. start+seq-1`` (default: position 0, fresh sequences).
    ``valid_lens`` (optional [batch] int32): rows ``>= valid_lens[b]``
    are PADDING — their writes are routed to page 0 (the reserved
    scratch page) so a right-padded final chunk never clobbers live
    pages past the table's real coverage.
    ``key_cache``/``value_cache`` may be quantized (int8 rows, f32
    scale plane) tuples — rows are then int8-quantized per (token,
    head) on the way in (the cache-KV int8 serving mode).
    """
    b, s, n_kv, d = k.shape
    quant = isinstance(key_cache, tuple)
    page_size = (key_cache[0] if quant else key_cache).shape[2]
    if start is None:
        pos = jnp.arange(s)
        page_ids = block_tables[:, pos // page_size]      # [b, s]
        slots = jnp.broadcast_to(pos % page_size, (b, s))  # [b, s]
    else:
        pos2 = start.astype(jnp.int32)[:, None] \
            + jnp.arange(s, dtype=jnp.int32)[None, :]      # [b, s]
        # clamp the page INDEX into the table width (pad rows may point
        # past it); the scratch reroute below keeps clamped rows dead
        pidx = jnp.minimum(pos2 // page_size,
                           block_tables.shape[1] - 1)
        page_ids = jnp.take_along_axis(block_tables, pidx, axis=1)
        slots = pos2 % page_size
    if valid_lens is not None:
        valid = jnp.arange(s, dtype=jnp.int32)[None, :] \
            < valid_lens.astype(jnp.int32)[:, None]        # [b, s]
        page_ids = jnp.where(valid, page_ids, 0)
        slots = jnp.where(valid, slots, 0)
    if quant:
        kq_pool, ks_plane = key_cache
        vq_pool, vs_plane = value_cache
        cols = (page_ids * page_size + slots).reshape(-1)   # [b*s]
        qk, sk = quantize_kv_rows(k)
        qv, sv = quantize_kv_rows(v)
        kq_pool = kq_pool.at[page_ids, :, slots].set(qk)
        vq_pool = vq_pool.at[page_ids, :, slots].set(qv)
        ks_plane = ks_plane.at[:, cols].set(
            jnp.moveaxis(sk.reshape(b * s, n_kv), 0, 1))
        vs_plane = vs_plane.at[:, cols].set(
            jnp.moveaxis(sv.reshape(b * s, n_kv), 0, 1))
        return (kq_pool, ks_plane), (vq_pool, vs_plane)
    key_cache = key_cache.at[page_ids, :, slots].set(
        k.astype(key_cache.dtype))
    value_cache = value_cache.at[page_ids, :, slots].set(
        v.astype(value_cache.dtype))
    return key_cache, value_cache


def write_prefill_kv_inplace(key_cache, value_cache, k, v, block_tables,
                             start, valid_lens=None, backend="auto"):
    """``write_prefill_kv_pages`` for a bf16/f32 pool as ONE Pallas call
    that aliases both pool sides (``pt_paged_kv_write``): the write the
    chunked-prefill layer loop uses, because an XLA scatter on a
    loop-carried pool beside a Pallas call costs two whole-pool copies a
    layer (the layout note on ``paged_decode_attention_inplace``).

    k/v ``[b, c, n_kv, d]`` land at positions ``start[b] ..
    start[b]+c-1`` of the pages ``block_tables`` ``[b, pp]`` names
    (ABSOLUTE ids), for ANY traced ``start``. Rows ``>= valid_lens[b]``
    and rows at positions past the table are DROPPED (the scatter sends
    the former to scratch page 0 and clamps the latter into the table's
    last page); every other byte of the pool matches the scatter's.

    Mechanics: XLA shifts the small chunk by ``start % page_size`` into
    whole page-shaped blocks and builds a per-row f32 mask; the kernel
    (one grid step a sequence) reads each touched page, selects the
    chunk's rows into it and writes the whole page back — whole-page
    DMAs only, as in the decode kernel (single-slot DMA slices break
    Mosaic's sublane tiling). Pages no valid row touches are skipped,
    so a page index clamped into the table never writes stale bytes.

    ``backend``: auto (the kernel on TPU, the scatter elsewhere) |
    interpret (the kernel through the interpreter — tests).
    """
    if backend not in ("auto", "interpret"):
        raise ValueError(f"write_prefill_kv_inplace backend={backend!r}: "
                         "expected 'auto' or 'interpret'")
    if backend == "auto" and not _chip.on_tpu():
        return write_prefill_kv_pages(key_cache, value_cache, k, v,
                                      block_tables, start=start,
                                      valid_lens=valid_lens)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, c, n_kv, d = k.shape
    _, _, ps, _ = key_cache.shape
    pp = block_tables.shape[1]
    # pages a chunk of c rows can touch at any offset inside a page
    npg = (c + ps - 2) // ps + 1
    span = npg * ps
    start = start.astype(jnp.int32)
    off = start % ps
    first = start // ps                                    # [b] page idx
    vlen = jnp.full((b,), c, jnp.int32) if valid_lens is None \
        else jnp.minimum(valid_lens.astype(jnp.int32), c)

    def shifted(x, dtype):
        # row r of the page blocks = chunk row r - off: a window of the
        # chunk padded by one page in front (kilobytes, not the pool)
        x = jnp.pad(x.astype(dtype),
                    ((0, 0), (ps, span - c), (0, 0), (0, 0)))
        x = jax.vmap(lambda xi, s: jax.lax.dynamic_slice_in_dim(
            xi, s, span, axis=0))(x, ps - off)
        return jnp.swapaxes(x.reshape(b, npg, ps, n_kv, d), 2, 3) \
            .reshape(b * npg, n_kv, ps, d)

    r = jnp.arange(span, dtype=jnp.int32)[None, :]
    live = ((r >= off[:, None]) & (r < (off + vlen)[:, None])
            & (first[:, None] * ps + r < pp * ps))         # [b, span]
    live = live.reshape(b * npg, ps)
    counts = live.sum(-1).astype(jnp.int32)                # [b*npg]
    # f32 and pre-shaped 4-D: Mosaic broadcasts only 32-bit values
    # along the sub-minor dim and cannot insert dims on i1/bf16
    rowmask = live.astype(jnp.float32)[:, None, :, None]
    pidx = jnp.minimum(
        first[:, None] + jnp.arange(npg, dtype=jnp.int32)[None, :],
        pp - 1)
    pids = jnp.take_along_axis(block_tables.astype(jnp.int32), pidx,
                               axis=1).reshape(-1)         # [b*npg]

    def kernel(pid_ref, cnt_ref, nk_ref, nv_ref, m_ref, k_in, v_in,
               k_hbm, v_hbm, pgk, pgv, in_sem, out_sem):
        del k_in, v_in                      # aliased: k_hbm/v_hbm
        i = pl.program_id(0)

        def page_in(j):
            pid = pid_ref[i * npg + j]
            return (pltpu.make_async_copy(k_hbm.at[pid], pgk.at[j],
                                          in_sem.at[j, 0]),
                    pltpu.make_async_copy(v_hbm.at[pid], pgv.at[j],
                                          in_sem.at[j, 1]))

        def page_out(j):
            pid = pid_ref[i * npg + j]
            return (pltpu.make_async_copy(pgk.at[j], k_hbm.at[pid],
                                          out_sem.at[j, 0]),
                    pltpu.make_async_copy(pgv.at[j], v_hbm.at[pid],
                                          out_sem.at[j, 1]))

        def each_touched(copies, act):
            for j in range(npg):
                @pl.when(cnt_ref[i * npg + j] > 0)
                def _():
                    for cpy in copies(j):
                        act(cpy)

        each_touched(page_in, lambda cpy: cpy.start())
        each_touched(page_in, lambda cpy: cpy.wait())
        # select in f32 (exact both ways for bf16; v5e has no bf16 VPU);
        # a select, not a blend: garbage beside a 0 weight stays out
        sel = jnp.broadcast_to(m_ref[...], pgk.shape) > jnp.float32(0.5)
        pgk[...] = jnp.where(sel, nk_ref[...].astype(jnp.float32),
                             pgk[...].astype(jnp.float32)) \
            .astype(pgk.dtype)
        pgv[...] = jnp.where(sel, nv_ref[...].astype(jnp.float32),
                             pgv[...].astype(jnp.float32)) \
            .astype(pgv.dtype)
        each_touched(page_out, lambda cpy: cpy.start())
        each_touched(page_out, lambda cpy: cpy.wait())

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b,),
        in_specs=[
            pl.BlockSpec((npg, n_kv, ps, d), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((npg, n_kv, ps, d), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec((npg, 1, ps, 1), lambda i, *_: (i, 0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((npg, n_kv, ps, d), key_cache.dtype),
            pltpu.VMEM((npg, n_kv, ps, d), value_cache.dtype),
            pltpu.SemaphoreType.DMA((npg, 2)),
            pltpu.SemaphoreType.DMA((npg, 2)),
        ])
    with _enable_x64(False), jax.named_scope("pt_paged_kv_write"):
        ck, cv = pl.pallas_call(
            kernel,
            name="pt_paged_kv_write",
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct(key_cache.shape, key_cache.dtype),
                jax.ShapeDtypeStruct(value_cache.shape,
                                     value_cache.dtype),
            ],
            # inputs are numbered with the scalar-prefetch operands
            # first: key_cache is arg 5, value_cache arg 6
            input_output_aliases={5: 0, 6: 1},
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=not _chip.on_tpu(),
        )(pids, counts, shifted(k, key_cache.dtype),
          shifted(v, value_cache.dtype), rowmask, key_cache, value_cache)
    return ck, cv


def gather_kv_pages(cache_side, block_tables, out_dtype=None):
    """Gather one cache side's pages into token-major [b, S, n_kv, d]
    (S = table_width * page_size, token t = page t//ps, slot t%ps).
    LEGACY chunked-prefill K/V view: since ISSUE 13 the default prefill
    attend reads the pool IN PLACE through
    ``flash_varlen.paged_prefill_attention`` (this dense copy cost an
    extra O(S) HBM write+read per chunk per layer); this gather remains
    the int8-quantized-pool path (it dequantizes on the way out) and
    the ``FLAGS_prefill_attention_backend=gather`` reference. Callers
    mask dead positions by seq_lens/causality, so garbage rows are
    harmless. ``block_tables`` must hold ABSOLUTE (layer-offset) page
    ids."""
    quant = isinstance(cache_side, tuple)
    pool = cache_side[0] if quant else cache_side
    b, P = block_tables.shape
    _, n_kv, ps, d = pool.shape
    g = pool[block_tables]                       # [b, P, n_kv, ps, d]
    g = jnp.moveaxis(g, 2, 3).reshape(b, P * ps, n_kv, d)
    if quant:
        plane = cache_side[1]                    # [n_kv, pool_tokens]
        cols = (block_tables[:, :, None] * ps
                + jnp.arange(ps, dtype=jnp.int32)[None, None, :]) \
            .reshape(b, P * ps)                  # [b, S]
        scales = jnp.moveaxis(plane[:, cols], 0, -1)   # [b, S, n_kv]
        g = g.astype(jnp.float32) * scales[..., None]
    return g if out_dtype is None else g.astype(out_dtype)


def quantize_kv_rows(x):
    """Per-(row..., head) symmetric int8 quantization of K/V token rows
    x [..., n_kv, d] -> (q int8 [..., n_kv, d], scale f32 [..., n_kv]).
    The serving cache-KV quantizer (reference comparator: the
    cache_k/v_quant_scales operands of block_multi_head_attention,
    paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu)."""
    xf = x.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / 127.0, 1e-8)
    qv = jnp.clip(jnp.round(xf / s[..., None]), -127, 127) \
        .astype(jnp.int8)
    return qv, s


def paged_decode_attention_inplace_q(q, new_k, new_v, kq_pool, ks_plane,
                                     vq_pool, vs_plane, seq_lens,
                                     block_tables, pool_base=None,
                                     pool_pages=None, ownership=None):
    """int8-KV variant of ``paged_decode_attention_inplace``.

    The KV cache holds int8 token rows (same head-major page layout)
    plus per-token-per-head f32 scales kept as LANE-MAJOR planes
    [n_kv, total_tokens] so the kernel can apply them as logits-COLUMN
    multiplies — the only layout in which dequant costs O(b*C) VPU ops
    instead of O(C*d) per chunk (a per-element dequant of the streamed
    data measured ~2.7ms/step of pure VPU, erasing the DMA saving).
    All matmuls run on the int8 MXU path (2x bf16 rate):
      logits = (qq @ kq^T) * q_scale[row] * k_scale[col]
      pv     = (wq @ vq)   * w_scale[row],  w' = softmax_w * v_scale[col]
    with q and the softmax weights quantized per-row on the fly. The
    current token joins unquantized from operands (exact); its K/V rows
    are RMW-patched into the int8 pages and its scales blended into the
    scale planes (which ride through the kernel as blocked aliased
    outputs — they never touch a non-Pallas op in the decode loop).

    Halves attention HBM traffic vs bf16 KV. Opt-in via the engine's
    ``kv_dtype="int8"``. Reference comparator: cache-KV int8 serving
    (block_multi_head_attention cache_*_quant_scales).

    Same ``seq_lens < pages_per_seq*page_size`` precondition as
    ``paged_decode_attention_inplace``: overfull rows take a masked
    no-op write (zeroed page-slot selector, scale-plane patch dropped)
    instead of corrupting their last allocated page.
    """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    b, n_q, d = q.shape
    _, n_kv, ps, _ = kq_pool.shape
    P = int(pool_pages) if pool_pages is not None else kq_pool.shape[0]
    g = n_q // n_kv
    bg = b * g
    scale = d ** -0.5
    NEG = -1e30

    cp = _pick_chunk_pages(P, ps)
    C = cp * ps
    nchunks = P // cp
    T = P * ps           # tokens per layer region
    rows_pp = n_kv * ps  # pool rows per page (flattened int8 view)

    if ownership is None:
        ownership = build_pool_ownership(block_tables, seq_lens, P, ps)
    owner_tok, pos_tok = ownership
    rows = jnp.arange(b, dtype=jnp.int32)[:, None]
    valid_full = ((owner_tok[None, :] == rows)
                  & (pos_tok[None, :]
                     < seq_lens.astype(jnp.int32)[:, None]))
    mask3 = jnp.transpose(
        valid_full.astype(jnp.int32).reshape(b, nchunks, C), (1, 0, 2))

    # q -> int8 rows + scales in the kernel's [n_kv, bg, ...] layout
    qt = jnp.transpose(q.reshape(b, n_kv, g, d), (1, 0, 2, 3)) \
        .reshape(n_kv, bg, d)
    qq, qs = quantize_kv_rows(
        jnp.swapaxes(qt, 0, 1).reshape(bg, n_kv, d))   # [bg,n_kv,..]
    qq = jnp.swapaxes(qq, 0, 1)                        # [n_kv, bg, d]
    qs = jnp.swapaxes(qs, 0, 1)                        # [n_kv, bg]
    nk_t = jnp.swapaxes(new_k, 0, 1).astype(jnp.bfloat16)
    nv_t = jnp.swapaxes(new_v, 0, 1).astype(jnp.bfloat16)

    # quantized current rows for the page patch + plane blend values
    nkq, nks = quantize_kv_rows(new_k)                 # [b,n_kv,d],[b,n_kv]
    nvq, nvs = quantize_kv_rows(new_v)
    nkq_w = jnp.broadcast_to(nkq[:, :, None, :], (b, n_kv, ps, d)) \
        .reshape(b, rows_pp, d)
    nvq_w = jnp.broadcast_to(nvq[:, :, None, :], (b, n_kv, ps, d)) \
        .reshape(b, rows_pp, d)

    base = jnp.asarray(0 if pool_base is None else pool_base, jnp.int32)
    lens_i = seq_lens.astype(jnp.int32)
    # overfull-row guard (see docstring): clamp the page index, zero the
    # slot selector, and push the scale-plane patch token out of range
    # so its scatter drops — masked no-op write all the way down
    pp = block_tables.shape[1]
    overfull = lens_i >= jnp.int32(pp * ps)                # [b]
    wpage_local = jnp.take_along_axis(
        block_tables.astype(jnp.int32),
        jnp.minimum(lens_i // ps, pp - 1)[:, None], axis=1)[:, 0]
    wpages = wpage_local + base                            # [b] abs page
    # flat row selector for the int8 page patch: [b, n_kv*ps, 1] f32
    slot_sel = ((jnp.arange(ps, dtype=jnp.int32)[None, :]
                 == (lens_i % ps)[:, None])
                & ~overfull[:, None]).astype(jnp.float32)
    sel_flat = jnp.broadcast_to(slot_sel[:, None, :], (b, n_kv, ps)) \
        .reshape(b, rows_pp)[..., None]                    # [b,rp,1]

    # scale-plane patch operands (LAYER-LOCAL token space [T]):
    # one-hot columns at each row's write position + the new values
    wtok = jnp.where(overfull, jnp.int32(T),
                     wpage_local * ps + lens_i % ps)       # [b] 0..T
    sel_col = jnp.zeros((1, T), jnp.float32).at[0, wtok].set(
        1.0, mode="drop")
    kval = jnp.zeros((n_kv, T), jnp.float32).at[:, wtok].set(
        jnp.swapaxes(nks, 0, 1), mode="drop")
    vval = jnp.zeros((n_kv, T), jnp.float32).at[:, wtok].set(
        jnp.swapaxes(nvs, 0, 1), mode="drop")

    scalars = jnp.concatenate(
        [jnp.reshape(base // jnp.int32(cp), (1,)),
         jnp.reshape((base * ps) // jnp.int32(C), (1,)), wpages])

    kq_flat = kq_pool.reshape(kq_pool.shape[0], rows_pp, d)
    vq_flat = vq_pool.reshape(vq_pool.shape[0], rows_pp, d)

    def kernel(s_ref, qq_ref, qs_ref, mask_ref, nk_ref, nv_ref,
               nkq_ref, nvq_ref, self_ref, selc_ref, kval_ref, vval_ref,
               ks_ref, vs_ref, kq_hbm_in, vq_hbm_in,
               o_ref, kq_hbm, vq_hbm, kso_ref, vso_ref,
               kb, vb, pgq, pgv, m_ref, l_ref, acc_ref,
               rsem, pin_sem, pout_sem):
        c = pl.program_id(0)
        base_c = s_ref[0]

        def chunk_copy(idx, slot):
            return (
                pltpu.make_async_copy(
                    kq_hbm.at[pl.ds((base_c + idx) * cp, cp)],
                    kb.at[slot], rsem.at[slot, 0]),
                pltpu.make_async_copy(
                    vq_hbm.at[pl.ds((base_c + idx) * cp, cp)],
                    vb.at[slot], rsem.at[slot, 1]))

        def page_in(i):
            pid = s_ref[2 + i]
            return (
                pltpu.make_async_copy(kq_hbm.at[pid], pgq.at[i],
                                      pin_sem.at[i, 0]),
                pltpu.make_async_copy(vq_hbm.at[pid], pgv.at[i],
                                      pin_sem.at[i, 1]))

        def page_out(i):
            pid = s_ref[2 + i]
            return (
                pltpu.make_async_copy(pgq.at[i], kq_hbm.at[pid],
                                      pout_sem.at[i, 0]),
                pltpu.make_async_copy(pgv.at[i], vq_hbm.at[pid],
                                      pout_sem.at[i, 1]))

        @pl.when(c == 0)
        def _():
            m_ref[...] = jnp.full((n_kv, bg), NEG, jnp.float32)
            l_ref[...] = jnp.zeros((n_kv, bg), jnp.float32)
            acc_ref[...] = jnp.zeros((n_kv, bg, d), jnp.float32)
            for cpy in chunk_copy(jnp.int32(0), jnp.int32(0)):
                cpy.start()
            for i in range(b):
                for cpy in page_in(i):
                    cpy.start()
            for i in range(b):
                for cpy in page_in(i):
                    cpy.wait()
            sel = self_ref[...]                      # [b, rp, 1] f32
            inv = jnp.float32(1.0) - sel
            pgq[...] = (pgq[...].astype(jnp.float32) * inv
                        + nkq_ref[...].astype(jnp.float32) * sel) \
                .astype(pgq.dtype)
            pgv[...] = (pgv[...].astype(jnp.float32) * inv
                        + nvq_ref[...].astype(jnp.float32) * sel) \
                .astype(pgv.dtype)
            for i in range(b):
                for cpy in page_out(i):
                    cpy.start()

        @pl.when(c + 1 < nchunks)
        def _():
            for cpy in chunk_copy(c + 1, jax.lax.rem(c + 1,
                                                     jnp.int32(2))):
                cpy.start()

        slot = jax.lax.rem(c, jnp.int32(2))
        for cpy in chunk_copy(c, slot):
            cpy.wait()

        # scale planes: blend in the current tokens' scales, expose the
        # blended block for this chunk, write it back (aliased output)
        selc = selc_ref[...]                         # [1, C]
        ks_blend = ks_ref[...] * (jnp.float32(1.0) - selc) \
            + kval_ref[...] * selc                   # [n_kv, C]
        vs_blend = vs_ref[...] * (jnp.float32(1.0) - selc) \
            + vval_ref[...] * selc
        kso_ref[...] = ks_blend
        vso_ref[...] = vs_blend

        valid = mask_ref[0] != 0                     # [b, C]
        if g > 1:
            valid = jnp.repeat(valid, g, axis=0)     # [bg, C]
        diag = (jax.lax.broadcasted_iota(jnp.int32, (bg, b), 0) // g
                == jax.lax.broadcasted_iota(jnp.int32, (bg, b), 1))

        for h in range(n_kv):
            k_h = kb[slot][:, h * ps:(h + 1) * ps].reshape(C, d)
            v_h = vb[slot][:, h * ps:(h + 1) * ps].reshape(C, d)
            li = jax.lax.dot_general(
                qq_ref[h], k_h, (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.int32)     # [bg, C] int32
            logits = (li.astype(jnp.float32)
                      * (qs_ref[h] * jnp.float32(scale))[:, None]
                      * ks_blend[h][None, :])
            logits = jnp.where(valid, logits, jnp.float32(NEG))
            m = m_ref[h]
            pm = jnp.maximum(m, logits.max(-1))
            alpha = jnp.exp(m - pm)
            w = jnp.exp(logits - pm[:, None])
            w = jnp.where(valid, w, jnp.float32(0.0))
            l_h = l_ref[h] * alpha + w.sum(-1)
            # fold the V column scales into w, re-quantize per row
            wv = w * vs_blend[h][None, :]
            ws = jnp.maximum(wv.max(-1), jnp.float32(1e-20)) \
                / jnp.float32(127.0)                  # [bg]
            wq = jnp.clip(jnp.round(wv / ws[:, None]),
                          -127, 127).astype(jnp.int8)
            pvi = jax.lax.dot_general(
                wq, v_h, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.int32)     # [bg, d]
            pv = pvi.astype(jnp.float32) * ws[:, None]
            acc_ref[h] = acc_ref[h] * alpha[:, None] + pv
            m_ref[h] = pm
            l_ref[h] = l_h

        @pl.when(c == nchunks - 1)
        def _():
            # current token, exact bf16 operands
            for h in range(n_kv):
                qf = (qq_ref[h].astype(jnp.float32)
                      * qs_ref[h][:, None]).astype(jnp.bfloat16)
                lc = jax.lax.dot_general(
                    qf, nk_ref[h], (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32) \
                    * jnp.float32(scale)
                lc = jnp.where(diag, lc, jnp.float32(NEG))
                m = m_ref[h]
                pm = jnp.maximum(m, lc.max(-1))
                alpha = jnp.exp(m - pm)
                wc = jnp.exp(lc - pm[:, None])
                wc = jnp.where(diag, wc, jnp.float32(0.0))
                l_h = l_ref[h] * alpha + wc.sum(-1)
                pv = jax.lax.dot_general(
                    wc.astype(jnp.bfloat16), nv_ref[h],
                    (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)
                acc_h = acc_ref[h] * alpha[:, None] + pv
                o_ref[h] = acc_h / jnp.maximum(
                    l_h, jnp.float32(1e-30))[:, None]
            for i in range(b):
                for cpy in page_out(i):
                    cpy.wait()

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nchunks,),
        in_specs=[
            pl.BlockSpec((n_kv, bg, d), lambda c, s: (0, 0, 0)),
            pl.BlockSpec((n_kv, bg), lambda c, s: (0, 0)),
            pl.BlockSpec((1, b, C), lambda c, s: (c, 0, 0)),
            pl.BlockSpec((n_kv, b, d), lambda c, s: (0, 0, 0)),
            pl.BlockSpec((n_kv, b, d), lambda c, s: (0, 0, 0)),
            pl.BlockSpec((b, rows_pp, d), lambda c, s: (0, 0, 0)),
            pl.BlockSpec((b, rows_pp, d), lambda c, s: (0, 0, 0)),
            pl.BlockSpec((b, rows_pp, 1), lambda c, s: (0, 0, 0)),
            # sel/val patch operands are LAYER-LOCAL [.., T] -> block c;
            # the scale PLANES span all layers -> block s[1] + c
            pl.BlockSpec((1, C), lambda c, s: (0, c)),
            pl.BlockSpec((n_kv, C), lambda c, s: (0, c)),
            pl.BlockSpec((n_kv, C), lambda c, s: (0, c)),
            pl.BlockSpec((n_kv, C), lambda c, s: (0, s[1] + c)),
            pl.BlockSpec((n_kv, C), lambda c, s: (0, s[1] + c)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=[
            pl.BlockSpec((n_kv, bg, d), lambda c, s: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
            pl.BlockSpec((n_kv, C), lambda c, s: (0, s[1] + c)),
            pl.BlockSpec((n_kv, C), lambda c, s: (0, s[1] + c)),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, cp, rows_pp, d), jnp.int8),
            pltpu.VMEM((2, cp, rows_pp, d), jnp.int8),
            pltpu.VMEM((b, rows_pp, d), jnp.int8),
            pltpu.VMEM((b, rows_pp, d), jnp.int8),
            pltpu.VMEM((n_kv, bg), jnp.float32),
            pltpu.VMEM((n_kv, bg), jnp.float32),
            pltpu.VMEM((n_kv, bg, d), jnp.float32),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SemaphoreType.DMA((b, 2)),
            pltpu.SemaphoreType.DMA((b, 2)),
        ])
    with _enable_x64(False), \
            jax.named_scope("pt_paged_attention_decode_inplace_q"):
        out, kq2, vq2, ks2, vs2 = pl.pallas_call(
            kernel,
            name="pt_paged_attention_decode_inplace_q",
            grid_spec=grid_spec,
            out_shape=[
                jax.ShapeDtypeStruct((n_kv, bg, d), jnp.float32),
                jax.ShapeDtypeStruct(kq_flat.shape, jnp.int8),
                jax.ShapeDtypeStruct(vq_flat.shape, jnp.int8),
                jax.ShapeDtypeStruct(ks_plane.shape, jnp.float32),
                jax.ShapeDtypeStruct(vs_plane.shape, jnp.float32),
            ],
            # inputs numbered with the scalar operand as 0: kq=14,
            # vq=15, ks=13? -> see in_specs order: [qq1, qs2, mask3,
            # nk4, nv5, nkq6, nvq7, self8, selc9, kval10, vval11,
            # ks12, vs13, kq14, vq15]
            input_output_aliases={14: 1, 15: 2, 12: 3, 13: 4},
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=not _chip.on_tpu(),
        )(scalars, qq, qs, mask3, nk_t, nv_t, nkq_w, nvq_w, sel_flat,
          sel_col, kval, vval, ks_plane, vs_plane, kq_flat, vq_flat)
    out = jnp.transpose(out.reshape(n_kv, b, g, d), (1, 0, 2, 3))
    return (out.reshape(b, n_q, d).astype(q.dtype),
            kq2.reshape(kq_pool.shape), ks2,
            vq2.reshape(vq_pool.shape), vs2)


class DecodeAttention(NamedTuple):
    """What the layers of one decode step share: which of the three
    paths runs (``kind``, static) and the layer-independent operands it
    masks with. Built by ``plan_decode_attention``."""
    kind: str                  # "inplace_q" | "inplace" | "xla"
    seq_lens: jax.Array        # [b] tokens cached, the current one excluded
    block_tables: jax.Array    # [b, pp] LAYER-LOCAL page ids
    pages_per_layer: int
    walk: PageWalk | None      # "inplace": the pages the tables name
    ownership: tuple | None    # "inplace_q": build_pool_ownership's pair


def plan_decode_attention(key_cache, block_tables, seq_lens,
                          pages_per_layer) -> DecodeAttention:
    """Choose the decode attention of one step — THE place that does —
    and build what its layers share. Call once a step, outside the
    layer loop; hand the result to every layer's ``decode_attend``.

    The choice follows from what can be observed: a quantized pool (the
    ``(int8 rows, f32 scale plane)`` pair) takes the int8 kernel and its
    whole-region ownership mask; on the chip a pool whose head_dim is a
    lane multiple takes the fused append + attend kernel and the step's
    page walk; anything else the XLA scatter + gather."""
    if isinstance(key_cache, tuple):
        ownership = build_pool_ownership(
            block_tables, seq_lens.astype(jnp.int32), pages_per_layer,
            key_cache[0].shape[2])
        return DecodeAttention("inplace_q", seq_lens, block_tables,
                               pages_per_layer, None, ownership)
    _, _, page_size, head_dim = key_cache.shape
    if _chip.on_tpu() and head_dim % 128 == 0:
        walk = build_page_walk(block_tables, seq_lens, page_size)
        return DecodeAttention("inplace", seq_lens, block_tables,
                               pages_per_layer, walk, None)
    return DecodeAttention("xla", seq_lens, block_tables, pages_per_layer,
                           None, None)


def decode_attend(plan: DecodeAttention, q, k, v, key_cache, value_cache,
                  layer):
    """Layer ``layer``'s decode attention under ``plan``: append the
    current token's ``k``/``v`` ``[b, n_kv, d]`` to the layer's region of
    the folded pool (``layer`` may be a traced loop index) and attend
    ``q [b, n_q, d]`` over it, scaled by ``d ** -0.5``. Returns
    ``(att [b, n_q, d], key_cache', value_cache')``."""
    lens, tables = plan.seq_lens, plan.block_tables
    base = layer * plan.pages_per_layer
    if plan.kind == "inplace_q":
        att, kq, ks, vq, vs = paged_decode_attention_inplace_q(
            q, k, v, *key_cache, *value_cache, lens, tables,
            pool_base=base, pool_pages=plan.pages_per_layer,
            ownership=plan.ownership)
        return att, (kq, ks), (vq, vs)
    if plan.kind == "inplace":
        return paged_decode_attention_inplace(
            q, k, v, key_cache, value_cache, lens, tables,
            pool_base=base, walk=plan.walk)
    key_cache, value_cache = write_kv_pages(
        key_cache, value_cache, k, v, lens, tables + base)
    att = paged_attention(q, key_cache, value_cache, lens + 1, tables,
                          pool_base=base)
    return att, key_cache, value_cache

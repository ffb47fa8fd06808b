"""Attention of a pattern that mixes sliding-window layers with full
layers whose V heads are narrower than their q / k heads
(``"window_attention"`` and packed ``"attention"`` in
``incubate/nn/layer_pattern``).

The full layers keep ``PackedKV``: ONE row a token and layer, every kv
head's V then every kv head's K, ``[v_0 .. v_{G-1} | k_0 .. k_{G-1} | 0]``
(at MiMo-V2-Flash's widths 4 x 128 + 4 x 192 = 1,280 lanes and no pad,
where a 192-wide K head stored alone takes 256 lanes). The window layers
keep ``WindowRing``, slot-indexed beside the pool: ``k [layers, slots, G,
window, k_lanes]`` (K heads padded to whole lane tiles) and ``v [layers,
slots, G, window, dv]``; position ``p`` of a slot's sequence is row ``p %
window``, so a slot holds ``window`` rows whatever its length. A row's
position is known from the sequence's own length: rows older than the
sequence (its slot's last occupant) are masked, never cleared.

``pt_gqa_paged_prefill``  one sequence's chunk over its paged prefix: the
    chunk's rows are written into their pages (whole-page read, select,
    write, the pool aliased), then a grid step a group of query heads of
    ONE kv head walks the pool tiles the block table names up to the
    chunk's last real row; a tile's K and V of that head are picked out
    of the packed rows ONCE by a 0/1 matmul (exact) and scored by all c
    query rows, online softmax.
``pt_gqa_paged_decode``   the latent pool's decode kernel body
    (``mla_attention.decode_rows``) in a launch of its own: a query head
    is zero outside its kv head's K lanes, so its score is ONE dot with
    the whole row, and the value is the row's V lanes (the caller keeps
    its own head's).
``pt_swa_prefill``        a chunk against the ``window`` positions before
    it (from the ring) and its own rows: a block of query rows against the
    keys that can reach it, the sink logit in every denominator.
``pt_swa_decode``         one token a slot: its K / V row written into the
    slot's ring (aliased), then attended with the ring's other rows.

Off the chip each takes an XLA path that computes the same thing and is
the reference the kernels are tested against (``backend="interpret"``
runs the kernels through the interpreter).
"""
from __future__ import annotations

import math
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ...device import chip as _chip
from ...device.vmem import KERNEL_VMEM_LIMIT_BYTES
from .mla_attention import decode_rows, prefill_tile_tokens
from .paged_attention import _enable_x64

__all__ = ["PackedKV", "WindowRing", "pack_rows", "gqa_prefill_attend",
           "gqa_decode_attend", "swa_prefill_attend", "swa_decode_attend",
           "window_pairs"]

_NEG = -1e30
#: query heads of one kv head a prefill grid step attends
PREFILL_HEAD_GROUP = 8
#: query rows of a window prefill block (at most; a divisor of the chunk)
WINDOW_BLOCK = 128


class PackedKV(NamedTuple):
    """Layer-folded paged pool of packed rows: layer ``l``'s logical page
    ``p`` is physical page ``l * num_pages + p``, a page ``[page_size,
    row_width]``, a row ``[v_0 .. v_{G-1} | k_0 .. k_{G-1} | 0]``."""
    rows: jax.Array        # [num_layers * num_pages, page_size, row_width]


class WindowRing(NamedTuple):
    """The window layers' slot-indexed ring (the decode-loop carry)."""
    k: jax.Array           # [layers, slots, G, window, k_lanes]
    v: jax.Array           # [layers, slots, G, window, dv]


def pack_rows(k, v, width: int):
    """``k [T, G, dk]``, ``v [T, G, dv]`` -> packed rows ``[T, width]``."""
    T = k.shape[0]
    body = jnp.concatenate([v.reshape(T, -1), k.reshape(T, -1)], -1)
    return jnp.pad(body, ((0, 0), (0, width - body.shape[1])))


def window_pairs(start: int, n: int, window: int) -> int:
    """Query-key pairs of ``n`` rows at ``start``: row ``i`` sees
    ``min(start + i + 1, window)`` positions."""
    lo, hi = start + 1, start + n
    m = min(hi, window)
    below = (m * (m + 1) - (lo - 1) * lo) // 2 if m >= lo else 0
    return below + window * (hi - max(m, lo - 1))


def _lanes(n: int) -> int:
    return -(-n // 128) * 128


# --------------------------------------------------- full: prefill

def _gqa_prefill_xla(q, rows, pool, tables, start, vlen, G, dv):
    c, H, dk = q.shape
    _, ps, RW = pool.shape
    pp = tables.shape[1]
    pos = start[0] + jnp.arange(c, dtype=jnp.int32)
    live = (jnp.arange(c) < vlen[0]) & (pos < pp * ps)
    page = jnp.where(live, tables[0, jnp.minimum(pos // ps, pp - 1)], 0)
    pool = pool.at[page, jnp.where(live, pos % ps, 0)].set(
        jnp.where(live[:, None], rows.astype(pool.dtype), pool[0, 0][None]))
    keys = pool[tables[0]].reshape(pp * ps, RW).astype(jnp.float32)
    v = keys[:, :G * dv].reshape(-1, G, dv)
    k = keys[:, G * dv: G * (dv + dk)].reshape(-1, G, dk)
    qg = q.astype(jnp.float32).reshape(c, G, H // G, dk)
    sc = jnp.einsum("cgjd,kgd->cgjk", qg, k)
    kpos = jnp.arange(pp * ps, dtype=jnp.int32)
    msk = kpos[None, :] <= pos[:, None]
    sc = jnp.where(msk[:, None, None, :], sc, _NEG)
    p = jax.nn.softmax(sc, -1)
    out = jnp.einsum("cgjk,kgv->cgjv", p, v).reshape(c, H, dv)
    return out, pool


def gqa_prefill_attend(q, rows, pool, block_tables, start, valid_lens, *,
                       n_kv: int, v_dim: int, backend: str = "auto"):
    """Write a chunk's packed rows and attend the chunk over its prefix.

    q ``[c, H, dk]`` ONE sequence's queries (rotated, scale folded in),
    head ``j`` of kv head ``j // (H / n_kv)``; ``rows [c, RW]`` the chunk's
    packed rows (``pack_rows``); ``pool [P, page, RW]`` (donated by the
    caller: it comes back aliased); ``block_tables [1, pp]`` ABSOLUTE page
    ids; ``start [1]`` the chunk's first position, ``valid_lens [1]`` its
    real rows (rows past them are not written). Query ``i`` attends
    positions ``<= start + i``. Returns ``(out [c, H, v_dim]`` float32,
    ``pool')``; rows past ``valid_lens`` hold no answer.
    """
    if backend not in ("auto", "interpret"):
        raise ValueError(f"gqa_prefill_attend backend={backend!r}: "
                         "expected 'auto' or 'interpret'")
    c, H, dk = q.shape
    _, ps, RW = pool.shape
    G, dv = int(n_kv), int(v_dim)
    if G * (dk + dv) > RW or H % G:
        raise ValueError(f"gqa_prefill_attend: {H} heads over {G} kv "
                         f"heads of {dk} + {dv} lanes in a {RW}-lane row")
    start = start.astype(jnp.int32)
    vlen = jnp.minimum(valid_lens.astype(jnp.int32), c)
    tables = block_tables.astype(jnp.int32)
    if backend == "auto" and not _chip.on_tpu():
        return _gqa_prefill_xla(q, rows, pool, tables, start, vlen, G, dv)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    pp = tables.shape[1]
    hpg = H // G
    g = math.gcd(hpg, PREFILL_HEAD_GROUP)
    kl = _lanes(dk)
    CK = prefill_tile_tokens(ps, pp)
    cpk = CK // ps
    npg = (c + ps - 2) // ps + 1
    span = npg * ps
    off = start[0] % ps
    first = start[0] // ps
    shifted = jax.lax.dynamic_slice_in_dim(
        jnp.pad(rows.astype(pool.dtype), ((ps, span - c), (0, 0))),
        ps - off, span, axis=0).reshape(npg, ps, RW)
    rr = jnp.arange(span, dtype=jnp.int32)
    live = ((rr >= off) & (rr < off + vlen[0])
            & (first * ps + rr < pp * ps)).reshape(npg, ps)
    counts = live.sum(-1).astype(jnp.int32)
    rowmask = live.astype(jnp.float32)[:, :, None]
    pidx = jnp.minimum(first + jnp.arange(npg, dtype=jnp.int32), pp - 1)
    pids = tables[0, pidx]
    meta = jnp.concatenate([start, vlen])
    qf = jnp.pad(q.astype(pool.dtype), ((0, 0), (0, 0), (0, kl - dk))) \
        .reshape(c, H * kl)
    # a kv head's 0/1 picker: its K lanes to [0, dk), its V lanes to
    # [kl, kl + dv) of a [kl + dv]-lane tile row
    lane = jnp.arange(RW)[None, :, None]
    col = jnp.arange(kl + dv)[None, None, :]
    head = jnp.arange(G)[:, None, None]
    sel = (((col < dk) & (lane == G * dv + head * dk + col))
           | ((col >= kl) & (lane == head * dv + col - kl))
           ).astype(pool.dtype)                             # [G, RW, kl+dv]

    def kernel(meta_ref, pid_ref, cnt_ref, tbl_ref, q_ref, s_ref, new_ref,
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
            # the chunk's rows into their pages, landed before any group
            # reads the pool
            each_touched(page_in, lambda cp: cp.start())
            each_touched(page_in, lambda cp: cp.wait())
            pick = jnp.broadcast_to(msk_ref[...], pg.shape) > 0.5
            pg[...] = jnp.where(pick, new_ref[...].astype(jnp.float32),
                                pg[...].astype(jnp.float32)).astype(pg.dtype)
            each_touched(page_out, lambda cp: cp.start())
            each_touched(page_out, lambda cp: cp.wait())

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
            tile = kb[slot].reshape(CK, RW)
            kv = jax.lax.dot_general(
                tile, s_ref[0], (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)         # [CK, kl + dv]
            k = kv[:, :kl].astype(tile.dtype)
            v = kv[:, kl:].astype(tile.dtype)
            diag = ((j + 1) * CK - 1 > st) | ((j + 1) * CK > pp * ps)
            for h in range(g):
                s = jax.lax.dot_general(
                    q_ref[:, h * kl: (h + 1) * kl], k,
                    (((1,), (1,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)     # [c, CK]
                s = jax.lax.cond(diag, lambda z: masked(j, z),
                                 lambda z: z, s)
                m = m_ref[h]
                pm = jnp.maximum(m, s.max(-1, keepdims=True))
                alpha = jnp.exp(m - pm)
                p = jnp.exp(s - pm)
                l_ref[h] = l_ref[h] * alpha + p.sum(-1, keepdims=True)
                pv = jax.lax.dot_general(
                    p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                    precision=jax.lax.Precision.DEFAULT,
                    preferred_element_type=jnp.float32)     # [c, dv]
                acc_ref[h] = acc_ref[h] * alpha + pv
                m_ref[h] = pm
            return carry

        jax.lax.fori_loop(jnp.int32(0), nk, body, 0)
        for h in range(g):
            o_ref[:, h * dv: (h + 1) * dv] = (
                acc_ref[h] / jnp.maximum(l_ref[h], jnp.float32(1e-30))
            ).astype(o_ref.dtype)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(H // g,),
        in_specs=[
            pl.BlockSpec((c, g * kl), lambda i, *_: (0, i)),
            pl.BlockSpec((1, RW, kl + dv),
                         lambda i, *_: (i * g // hpg, 0, 0)),
            pl.BlockSpec((npg, ps, RW), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec((npg, ps, 1), lambda i, *_: (0, 0, 0)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        out_specs=[
            pl.BlockSpec((c, g * dv), lambda i, *_: (0, i)),
            pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((npg, ps, RW), pool.dtype),
            pltpu.VMEM((2, cpk, ps, RW), pool.dtype),
            pltpu.VMEM((g, c, 1), jnp.float32),
            pltpu.VMEM((g, c, 1), jnp.float32),
            pltpu.VMEM((g, c, dv), jnp.float32),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.SemaphoreType.DMA((2,)),
        ])
    with _enable_x64(False), jax.named_scope("pt_gqa_paged_prefill"):
        out, pool = pl.pallas_call(
            kernel,
            name="pt_gqa_paged_prefill",
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((c, H * dv), jnp.float32),
                       jax.ShapeDtypeStruct(pool.shape, pool.dtype)],
            # inputs are numbered with the four scalar-prefetch operands
            # first: the pool is arg 8
            input_output_aliases={8: 1},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=not _chip.on_tpu(),
        )(meta, pids, counts, tables.reshape(-1), qf, sel, shifted, rowmask,
          pool)
    return out.reshape(c, H, dv), pool


# ---------------------------------------------------- full: decode

def gqa_decode_attend(q, k, v, pool, block_tables, seq_lens, pool_base, *,
                      backend: str = "auto"):
    """Append one token's packed row a sequence and attend over the
    sequence: q ``[S, H, dk]`` (rotated, scale folded in), ``k [S, G,
    dk]`` / ``v [S, G, dv]`` the current token's; ``pool`` the packed pool
    (comes back aliased), ``block_tables [S, pp]`` LAYER-LOCAL page ids,
    ``pool_base`` the layer region's first page, ``seq_lens [S]`` the
    tokens already cached. Returns ``(out [S, H, dv] float32, pool')``."""
    S, H, dk = q.shape
    G, dv = v.shape[1], v.shape[2]
    RW = pool.shape[2]
    hpg = H // G
    # head j's query over the row's lanes: zero but for its kv head's K
    qg = q.astype(pool.dtype).reshape(S, G, hpg, dk)
    qpad = jnp.concatenate(
        [jnp.pad(qg[:, g], ((0, 0), (0, 0),
                            (G * dv + g * dk, RW - G * dv - (g + 1) * dk)))
         for g in range(G)], 1)                             # [S, H, RW]
    rows = pack_rows(k, v, RW)

    def launch(kernel, spec, operands):
        from jax.experimental import pallas as pl
        with _enable_x64(False), jax.named_scope("pt_gqa_paged_decode"):
            return pl.pallas_call(kernel, name="pt_gqa_paged_decode",
                                  **spec)(*operands)

    out, pool = decode_rows(qpad, rows, pool, block_tables, seq_lens,
                            pool_base, v_width=G * dv, backend=backend,
                            launch=launch, who="gqa_decode_attend")
    out = out.reshape(S, G, hpg, G, dv)
    own = jnp.stack([out[:, g, :, g] for g in range(G)], 1)
    return own.reshape(S, H, dv), pool


# -------------------------------------------------- window: prefill

def _window_block(c: int) -> int:
    return math.gcd(c, WINDOW_BLOCK)


def _swa_prefill_xla(q, k_all, v_all, start, sink, Wr):
    c, H, kl = q.shape
    G = k_all.shape[0]
    qg = q.astype(jnp.float32).reshape(c, G, H // G, kl)
    sc = jnp.einsum("cgjd,gkd->cgjk", qg, k_all.astype(jnp.float32))
    qpos = start[0] + jnp.arange(c, dtype=jnp.int32)
    kpos = start[0] - Wr + jnp.arange(Wr + c, dtype=jnp.int32)
    keep = (kpos[None, :] <= qpos[:, None]) \
        & (kpos[None, :] > qpos[:, None] - Wr) & (kpos[None, :] >= 0)
    sc = jnp.where(keep[:, None, None, :], sc, _NEG)
    m = sc.max(-1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink.reshape(1, G, H // G, 1))
    p = jnp.where(keep[:, None, None, :], jnp.exp(sc - m), 0.0)
    den = p.sum(-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sink.reshape(1, G, H // G, 1) - m)
    out = jnp.einsum("cgjk,gkv->cgjv", p, v_all.astype(jnp.float32)) / den
    return out.reshape(c, H, -1)


def swa_prefill_attend(q, k, v, ring, layer: int, slot, start, valid_lens,
                       sink=None, *, backend: str = "auto"):
    """Attend a chunk over the window and write its rows into the ring.

    q ``[c, H, dk]`` ONE sequence's queries (rotated, scale folded in);
    ``k [c, G, dk]`` / ``v [c, G, dv]`` the chunk's own keys and values;
    ``ring`` the whole ``WindowRing`` (donated by the caller); ``layer`` the
    window layer (a Python int); ``slot [1]`` the sequence's slot;
    ``start [1]`` the chunk's first position, ``valid_lens [1]`` its real
    rows; ``sink [H]`` float32 or None. Query ``i`` sees positions ``start
    + i - window < p <= start + i``: the ``window`` positions before the
    chunk come from the ring. Returns ``(out [c, H, dv]`` float32,
    ``ring')``: the slot's ring holds the last ``window`` positions up to
    the chunk's last real row."""
    if backend not in ("auto", "interpret"):
        raise ValueError(f"swa_prefill_attend backend={backend!r}: "
                         "expected 'auto' or 'interpret'")
    c, H, dk = q.shape
    _, _, G, Wr, kl = ring.k.shape
    dv = ring.v.shape[-1]
    start = start.astype(jnp.int32)
    vlen = jnp.minimum(valid_lens.astype(jnp.int32), c)
    s = slot.astype(jnp.int32)[0]
    at = (jnp.int32(layer), s, jnp.int32(0), jnp.int32(0), jnp.int32(0))
    old_k = jax.lax.dynamic_slice(ring.k, at, (1, 1, G, Wr, kl))[0, 0]
    old_v = jax.lax.dynamic_slice(ring.v, at, (1, 1, G, Wr, dv))[0, 0]
    # positions start - Wr .. start - 1 in order, then the chunk's
    prior = (start[0] + jnp.arange(Wr, dtype=jnp.int32)) % Wr
    kp = jnp.pad(k.astype(ring.k.dtype), ((0, 0), (0, 0), (0, kl - dk)))
    k_all = jnp.concatenate([jnp.take(old_k, prior, axis=1),
                             jnp.swapaxes(kp, 0, 1)], 1)    # [G, Wr+c, kl]
    v_all = jnp.concatenate([jnp.take(old_v, prior, axis=1),
                             jnp.swapaxes(v.astype(ring.v.dtype), 0, 1)], 1)
    qp = jnp.pad(q.astype(ring.k.dtype), ((0, 0), (0, 0), (0, kl - dk)))
    # ring row r: the newest position = r (mod Wr) up to the last real row
    last = start[0] + vlen[0] - 1
    r = jnp.arange(Wr, dtype=jnp.int32)
    src = last - (last - r) % Wr - start[0] + Wr
    ring = WindowRing(
        jax.lax.dynamic_update_slice(
            ring.k, jnp.take(k_all, src, axis=1)[None, None], at),
        jax.lax.dynamic_update_slice(
            ring.v, jnp.take(v_all, src, axis=1)[None, None], at))
    if backend == "auto" and not _chip.on_tpu():
        return _swa_prefill_xla(qp, k_all, v_all, start, sink, Wr), ring
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hpg = H // G
    B = _window_block(c)
    has_sink = sink is not None
    sk = (sink if has_sink else jnp.zeros((H,), jnp.float32)) \
        .astype(jnp.float32).reshape(G, hpg, 1)

    def kernel(meta_ref, q_ref, k_ref, v_ref, s_ref, o_ref):
        i = pl.program_id(1)
        st = meta_ref[0]
        base = pl.multiple_of(i * B, B)
        kk = k_ref[0, pl.ds(base, B + Wr), :]               # [B + Wr, kl]
        vv = v_ref[0, pl.ds(base, B + Wr), :]
        # key j of the slab is position st - Wr + i B + j, query r is
        # position st + i B + r: visible iff r < j <= r + Wr and kpos >= 0
        rq = jax.lax.broadcasted_iota(jnp.int32, (B, B + Wr), 0)
        jk = jax.lax.broadcasted_iota(jnp.int32, (B, B + Wr), 1)
        keep = (jk > rq) & (jk <= rq + Wr) & (st - Wr + base + jk >= 0)
        for h in range(hpg):
            sc = jax.lax.dot_general(
                q_ref[:, h * kl: (h + 1) * kl], kk,
                (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)         # [B, B + Wr]
            sc = jnp.where(keep, sc, jnp.float32(_NEG))
            m = sc.max(-1, keepdims=True)
            if has_sink:
                sh = s_ref[0, h: h + 1]                     # [1, 1]
                m = jnp.maximum(m, sh)
            p = jnp.where(keep, jnp.exp(sc - m), jnp.float32(0.0))
            den = p.sum(-1, keepdims=True)
            if has_sink:
                den = den + jnp.exp(sh - m)
            pv = jax.lax.dot_general(
                p.astype(vv.dtype), vv, (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)         # [B, dv]
            o_ref[:, h * dv: (h + 1) * dv] = pv / den

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(G, c // B),
        in_specs=[
            pl.BlockSpec((B, hpg * kl), lambda g, i, *_: (i, g)),
            pl.BlockSpec((1, Wr + c, kl), lambda g, i, *_: (g, 0, 0)),
            pl.BlockSpec((1, Wr + c, dv), lambda g, i, *_: (g, 0, 0)),
            pl.BlockSpec((1, hpg, 1), lambda g, i, *_: (g, 0, 0)),
        ],
        out_specs=pl.BlockSpec((B, hpg * dv), lambda g, i, *_: (i, g)))
    with _enable_x64(False), jax.named_scope("pt_swa_prefill"):
        out = pl.pallas_call(
            kernel,
            name="pt_swa_prefill",
            grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((c, H * dv), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary", "arbitrary"),
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=not _chip.on_tpu(),
        )(start, qp.reshape(c, H * kl), k_all, v_all, sk)
    return out.reshape(c, H, dv), ring


# --------------------------------------------------- window: decode

def _swa_decode_xla(q, k, v, ring, layer, lens, active, sink):
    S, H, kl = q.shape
    _, _, G, Wr, _ = ring.k.shape
    row = lens % Wr
    sl = jnp.arange(S)
    keep_new = active[:, None, None]
    rk = ring.k.at[layer, sl, :, row].set(
        jnp.where(keep_new, k.astype(ring.k.dtype), ring.k[layer, sl, :, row]))
    rv = ring.v.at[layer, sl, :, row].set(
        jnp.where(keep_new, v.astype(ring.v.dtype), ring.v[layer, sl, :, row]))
    kk, vv = rk[layer].astype(jnp.float32), rv[layer].astype(jnp.float32)
    qg = q.astype(rk.dtype).astype(jnp.float32).reshape(S, G, H // G, kl)
    sc = jnp.einsum("sgjd,sgkd->sgjk", qg, kk)
    rr = jnp.arange(Wr, dtype=jnp.int32)
    keep = (rr[None, :] <= lens[:, None]) | (lens[:, None] >= Wr - 1)
    sc = jnp.where(keep[:, None, None, :], sc, _NEG)
    m = sc.max(-1, keepdims=True)
    if sink is not None:
        m = jnp.maximum(m, sink.reshape(1, G, H // G, 1))
    p = jnp.where(keep[:, None, None, :], jnp.exp(sc - m), 0.0)
    den = p.sum(-1, keepdims=True)
    if sink is not None:
        den = den + jnp.exp(sink.reshape(1, G, H // G, 1) - m)
    out = jnp.einsum("sgjk,sgkv->sgjv", p, vv) / den
    return out.reshape(S, H, -1), WindowRing(rk, rv)


def swa_decode_attend(q, k, v, ring, layer: int, seq_lens, active,
                      sink=None, *, backend: str = "auto"):
    """One token a slot through a window layer: q ``[S, H, dk]`` (rotated,
    scale folded in), ``k [S, G, dk]`` / ``v [S, G, dv]`` the current
    token's; ``ring`` the whole ``WindowRing`` (comes back aliased);
    ``layer`` a Python int; ``seq_lens [S]`` the current token's position;
    ``active [S]`` bool (other slots keep their ring). The token's row
    lands in ring row ``pos % window`` and the query sees the ring's rows
    of positions ``pos - window < p <= pos``. Returns ``(out [S, H, dv]
    float32, ring')``."""
    if backend not in ("auto", "interpret"):
        raise ValueError(f"swa_decode_attend backend={backend!r}: "
                         "expected 'auto' or 'interpret'")
    S, H, dk = q.shape
    _, _, G, Wr, kl = ring.k.shape
    dv = ring.v.shape[-1]
    lens = seq_lens.astype(jnp.int32)
    dt = ring.k.dtype
    qp = jnp.pad(q.astype(dt), ((0, 0), (0, 0), (0, kl - dk)))
    kp = jnp.pad(k.astype(dt), ((0, 0), (0, 0), (0, kl - dk)))
    if backend == "auto" and not _chip.on_tpu():
        return _swa_decode_xla(qp, kp, v, ring, layer, lens, active, sink)
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hpg = H // G
    has_sink = sink is not None
    sk = (sink if has_sink else jnp.zeros((H,), jnp.float32)) \
        .astype(jnp.float32).reshape(G, hpg, 1)
    meta = jnp.concatenate([lens, active.astype(jnp.int32)])

    def kernel(meta_ref, q_ref, kn_ref, vn_ref, s_ref, k_in, v_in, o_ref,
               k_out, v_out):
        s = pl.program_id(0)
        n = meta_ref[s]
        on = meta_ref[S + s] > 0
        row = jax.lax.rem(n, jnp.int32(Wr))
        hit_k = (jax.lax.broadcasted_iota(jnp.int32, (G, Wr, kl), 1) == row) \
            & on
        hit_v = (jax.lax.broadcasted_iota(jnp.int32, (G, Wr, dv), 1) == row) \
            & on
        kb = jnp.where(hit_k, jnp.broadcast_to(kn_ref[0], (G, Wr, kl)),
                       k_in[0, 0])
        vb = jnp.where(hit_v, jnp.broadcast_to(vn_ref[0], (G, Wr, dv)),
                       v_in[0, 0])
        k_out[0, 0] = kb
        v_out[0, 0] = vb
        keep = (jax.lax.broadcasted_iota(jnp.int32, (hpg, Wr), 1) <= n) \
            | (n >= Wr - 1)
        for g in range(G):
            sc = jax.lax.dot_general(
                q_ref[0, g], kb[g], (((1,), (1,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)         # [hpg, Wr]
            sc = jnp.where(keep, sc, jnp.float32(_NEG))
            m = sc.max(-1, keepdims=True)
            if has_sink:
                m = jnp.maximum(m, s_ref[g])
            p = jnp.where(keep, jnp.exp(sc - m), jnp.float32(0.0))
            den = p.sum(-1, keepdims=True)
            if has_sink:
                den = den + jnp.exp(s_ref[g] - m)
            pv = jax.lax.dot_general(
                p.astype(vb.dtype), vb[g], (((1,), (0,)), ((), ())),
                precision=jax.lax.Precision.DEFAULT,
                preferred_element_type=jnp.float32)         # [hpg, dv]
            o_ref[0, g] = pv / den

    ring_k = pl.BlockSpec((1, 1, G, Wr, kl),
                          lambda s, *_: (layer, s, 0, 0, 0))
    ring_v = pl.BlockSpec((1, 1, G, Wr, dv),
                          lambda s, *_: (layer, s, 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(S,),
        in_specs=[
            pl.BlockSpec((1, G, hpg, kl), lambda s, *_: (s, 0, 0, 0)),
            pl.BlockSpec((1, G, 1, kl), lambda s, *_: (s, 0, 0, 0)),
            pl.BlockSpec((1, G, 1, dv), lambda s, *_: (s, 0, 0, 0)),
            pl.BlockSpec((G, hpg, 1), lambda s, *_: (0, 0, 0)),
            ring_k, ring_v,
        ],
        out_specs=[
            pl.BlockSpec((1, G, hpg, dv), lambda s, *_: (s, 0, 0, 0)),
            ring_k, ring_v,
        ])
    with _enable_x64(False), jax.named_scope("pt_swa_decode"):
        out, rk, rv = pl.pallas_call(
            kernel,
            name="pt_swa_decode",
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((S, G, hpg, dv), jnp.float32),
                       jax.ShapeDtypeStruct(ring.k.shape, dt),
                       jax.ShapeDtypeStruct(ring.v.shape, ring.v.dtype)],
            # the scalar-prefetch operand is arg 0: the ring is args 5, 6
            input_output_aliases={5: 1, 6: 2},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=KERNEL_VMEM_LIMIT_BYTES),
            interpret=not _chip.on_tpu(),
        )(meta, qp.reshape(S, G, hpg, kl), kp.reshape(S, G, 1, kl),
          v.astype(ring.v.dtype).reshape(S, G, 1, dv), sk, ring.k, ring.v)
    return out.reshape(S, H, dv), WindowRing(rk, rv)

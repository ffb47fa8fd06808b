"""Paged KV-cache block manager for continuous-batching serving.

TPU-native equivalent of the block-table machinery behind the reference's
block_multi_head_attention serving kernel (reference:
paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu — its
``block_tables`` input; allocation policy lives in serving frontends).
Pages are rows of a preallocated PAGE-MAJOR pool
[num_layers * num_pages, n_kv_heads, page_size, head_dim] (each page one
contiguous head-major block — see nn/functional/paged_attention.py
layout notes);
the manager hands out LOGICAL page ids from a free list so sequences of
different lengths share one pool with no copies.

``latent=True`` is the pool of latent-attention layers: ONE array
``[num_layers * num_pages, page_size, head_dim]`` (``LatentKV``; a row
is the normed latent, the rope key and the pad to whole lane tiles),
never K and V side by side. Allocation, growth, refcounts and block
tables do not know the difference; the paths that move page CONTENTS
(``phys_rows`` users) are the engines' and refuse a latent pool by type.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..incubate.nn.fused_transformer import PagedKV
from ..nn.functional.mla_attention import LatentKV

__all__ = ["BlockKVCacheManager", "restore_scatter",
           "restore_scatter_jit", "gather_rows"]


def restore_scatter(pool, rows, vals):
    """The host→HBM KV restore as one program: scatter a spilled page
    blob (``vals``, layer-major rows — see ``phys_rows``) back into the
    pool. The pool argument is DONATED at the jit boundary
    (``restore_scatter_jit``) so a restore never holds two copies of
    the pool in HBM; registered as the ``serve.kv_restore`` program
    site for the lint passes."""
    return pool.at[rows].set(vals.astype(pool.dtype))


#: the jitted restore — what the serving restore/import paths call.
#: One executable per (pool, rows, vals) shape bucket (row vectors are
#: power-of-two padded, see ``ContinuousBatchingEngine._pad_pow2``);
#: the eager op-by-op form costs several ms of dispatch overhead PER
#: CALL, which a prefill replica's stepping thread pays mid-drive.
restore_scatter_jit = jax.jit(restore_scatter, donate_argnums=(0,))


@jax.jit
def gather_rows(pool, rows):
    """The export half (spill/migration): pool rows to one contiguous
    blob as a single compiled gather — same bucketed-shape contract
    (and the same dispatch-overhead rationale) as the restore."""
    return pool[rows]


class BlockKVCacheManager:
    """Owns the page pool + free list; builds per-batch block tables.

    Pages are REFCOUNTED: ``allocate``/``grow`` hand out pages at
    refcount 1, ``share`` maps existing pages into another sequence at
    +1 (the prefix/KV-reuse path — requests sharing a system prompt map
    the prefix's pages instead of re-prefilling them), and ``free``
    only returns a page to the free list once its last reference drops.
    Shared pages are copy-on-write in the page-table sense: only FULL,
    immutable prefix pages are ever shared (serving/prefix_cache.py),
    and a sharer's decode writes land in its privately owned tail
    pages, so no data copy is ever needed.
    """

    def __init__(self, num_layers: int, num_kv_heads: int, head_dim: int,
                 page_size: int = 16, num_pages: int = 512,
                 dtype=jnp.float32, reserve_scratch: bool = False,
                 mp_degree: int = 1, mesh=None, mp_axis: str = "mp",
                 recurrent=None, slots: int = 0, latent: bool = False):
        self.num_layers = num_layers
        # one array family a token row (``LatentKV``) instead of K and V
        self.latent = bool(latent)
        # recurrent layers (a ``LayerPattern.recurrent`` spec): their
        # state is indexed by decode SLOT, not by page — allocated with
        # the slot, zeroed at admission, carried from prefill chunk to
        # prefill chunk and through every decode step, released with the
        # slot. ``_fresh`` holds the slots admitted since their last
        # prefill chunk landed: that chunk starts from zeros.
        self.recurrent = recurrent
        self.slots = int(slots)
        self._fresh: set = set()
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.page_size = page_size
        self.num_pages = num_pages
        # dtype: pool element type ("bfloat16"/"float32" strings are
        # normalized; "int8"/jnp.int8 selects the QUANTIZED cache-KV
        # mode below). Orthogonal to the engines' weight quantization —
        # quant="int8"/"a8w8" changes the matmul path, not the pool, so
        # any (quant, kv_dtype) pair composes (the bench's best rung is
        # int8 weights + int8 KV at b64).
        if isinstance(dtype, str) and dtype != "int8":
            dtype = jnp.dtype(dtype)
        self.dtype = dtype
        # tensor parallelism (mp_degree > 1): the pool's kv-head axis
        # shards over the mesh's mp axis — each shard stores only
        # num_kv_heads // mp heads (or ONE replicated head per shard in
        # the GQA small-kv fallback, mp % num_kv_heads == 0; any other
        # combination raises here with the exact divisibility
        # constraint instead of shape-crashing in the pool scatter).
        # Page tables are host-side ints and stay replicated, so every
        # page-level mechanism (prefix sharing, refcounts, preemption)
        # is TP-oblivious.
        self.mp_degree = max(int(mp_degree or 1), 1)
        self.mp_axis = mp_axis
        self._mesh = mesh
        if self.mp_degree > 1:
            from ..distributed.tp import split_kv_heads

            self.kv_heads_per_shard, self.kv_replication = \
                split_kv_heads(num_kv_heads, self.mp_degree)
        else:
            self.kv_heads_per_shard = num_kv_heads
            self.kv_replication = 1
        self._pool_heads = self.kv_heads_per_shard * self.mp_degree
        if self.latent and (self._mesh is not None or self.dtype == "int8"
                            or self.dtype == jnp.int8):
            raise NotImplementedError(
                "a latent pool is served unsharded in bf16 / f32: its row "
                "has no kv-head axis to shard and no int8 kernel")
        if self._mesh is not None and \
                (self.dtype == "int8" or self.dtype == jnp.int8):
            raise NotImplementedError(
                "int8 cache-KV is not supported under tensor "
                "parallelism yet — serve TP with a bf16/f32 pool")
        # reserve_scratch: page 0 is never handed out, so block-table
        # padding entries (0) and idle continuous-batching slots can
        # write/read it without clobbering a live sequence
        self._free: List[int] = list(
            range(1 if reserve_scratch else 0, num_pages))
        self._owned: dict = {}
        self._refs: Dict[int, int] = {}
        # fault-injection registry (serving/faults.py) or None — the
        # ``kv.alloc`` / ``kv.grow`` sites fire BEFORE any free-list
        # mutation, so an injected raise leaves the pool consistent
        # and a retry is clean (one attribute test when disabled)
        self._faults = None

    def fresh_cache(self):
        if self.latent:
            return LatentKV(jnp.zeros(
                (self.num_layers * self.num_pages, self.page_size,
                 self.head_dim), self.dtype))
        # layer-FOLDED page-major pool (see PagedKV): layer l's logical
        # page p is physical page l * num_pages + p — decode updates it
        # in place; each page is one contiguous DMA block.
        # dtype "int8" = quantized cache-KV mode: int8 token rows plus
        # per-token-per-head f32 scale PLANES [n_kv, pages*page_size]
        # (lane-major so the decode kernel applies them as logits-column
        # multiplies; see paged_decode_attention_inplace_q)
        shape = (self.num_layers * self.num_pages, self._pool_heads,
                 self.page_size, self.head_dim)
        if self.dtype == "int8" or self.dtype == jnp.int8:
            plane = (self._pool_heads,
                     self.num_layers * self.num_pages * self.page_size)
            return PagedKV(
                (jnp.zeros(shape, jnp.int8),
                 jnp.zeros(plane, jnp.float32)),
                (jnp.zeros(shape, jnp.int8),
                 jnp.zeros(plane, jnp.float32)))
        if self._mesh is not None:
            # kv-head-sharded pool: allocated directly under its
            # NamedSharding so no chip ever holds the full pool. On an
            # ep-only mesh (mp_degree == 1, expert parallelism — ISSUE
            # 15) the pool is REPLICATED over the mesh instead: EP
            # shards the expert bank, and the pool must still be
            # mesh-committed so the shard_mapped decode programs never
            # mix single-device arrays with mesh-sharded weights.
            import jax
            from jax.sharding import NamedSharding, PartitionSpec as P

            spec = P(None, self.mp_axis, None, None) \
                if self.mp_degree > 1 else P()
            sh = NamedSharding(self._mesh, spec)
            zero = jax.jit(lambda: jnp.zeros(shape, self.dtype),
                           out_shardings=sh)
            return PagedKV(zero(), zero())
        return PagedKV(jnp.zeros(shape, self.dtype),
                       jnp.zeros(shape, self.dtype))

    def fresh_recurrent_state(self, conv_dtype=None):
        """The slot-indexed state of the recurrent layers, zeros, or None
        for a pattern without any (``RecurrentState``: ssm float32
        ``[layers, slots, d_state, d_inner]``, conv tail in the compute
        dtype)."""
        r = self.recurrent
        if r is None:
            return None
        from ..incubate.nn.hybrid_stack import RecurrentState

        return RecurrentState(
            jnp.zeros((r.layers, self.slots, r.d_state, r.d_inner),
                      jnp.float32),
            jnp.zeros((r.layers, self.slots, r.conv_rows, r.conv_dim),
                      conv_dtype or self.dtype))

    def recurrent_admit(self, slot: int) -> None:
        """A sequence takes ``slot``: whatever state the slot holds is
        dead, the sequence's first prefill chunk starts from zeros (a
        preempted request re-admitted for recompute comes through here
        too). No device work: the reset rides in that chunk's program."""
        if self.recurrent is None:
            return
        self._fresh.add(slot)

    def recurrent_is_fresh(self, slot: int) -> bool:
        return slot in self._fresh

    def recurrent_landed(self, slot: int) -> None:
        """A prefill chunk of ``slot`` landed: the state it wrote is the
        sequence's own from here on."""
        self._fresh.discard(slot)

    def recurrent_free(self, slot: int) -> None:
        self._fresh.discard(slot)

    def pages_needed(self, length: int) -> int:
        return -(-length // self.page_size)

    def page_hbm_bytes(self) -> int:
        """Bytes ONE logical page occupies in HBM across both K and V
        pools (all layers, all kv heads) — the unit of host-tier
        capacity accounting and of the router directory's restore-vs-
        re-prefill cost model. int8 cache-KV counts the quantized rows
        plus their f32 scale-plane columns, so a spilled int8 page
        moves roughly half the bytes of its bf16 equivalent."""
        elems = (self.num_layers * self._pool_heads
                 * self.page_size * self.head_dim)
        if self.latent:
            # one array: the row as STORED, pad lanes included
            return elems * jnp.dtype(self.dtype).itemsize
        if self.dtype == "int8" or self.dtype == jnp.int8:
            scale = (self._pool_heads * self.num_layers
                     * self.page_size * 4)
            return 2 * (elems + scale)
        return 2 * elems * jnp.dtype(self.dtype).itemsize

    def phys_rows(self, pages: Sequence[int]) -> np.ndarray:
        """Physical pool-row indices of logical ``pages`` across the
        layer-folded pool — layer l's copy of page p is row
        ``l * num_pages + p``. LAYER-MAJOR ``[num_layers * len(pages)]``
        so a KV blob gathered with one manager's rows scatters into
        another manager's rows even when their ``num_pages`` differ
        (the fleet page-migration path, serving/router.py)."""
        pages = np.asarray(list(pages), np.int64)
        layers = np.arange(self.num_layers,
                           dtype=np.int64) * self.num_pages
        return (layers[:, None] + pages[None, :]).reshape(-1)

    def allocate(self, seq_id, max_length: int) -> List[int]:
        """Reserve pages covering max_length tokens for one sequence."""
        n = self.pages_needed(max_length)
        f = self._faults
        if f is not None:
            f.fire("kv.alloc")
        if n > len(self._free):
            raise RuntimeError(
                f"KV pool exhausted: need {n} pages, "
                f"{len(self._free)} free (of {self.num_pages})")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        self._owned.setdefault(seq_id, []).extend(pages)
        return pages

    def grow(self, seq_id, n_pages: int) -> List[int]:
        """On-demand paging: extend an existing sequence by n_pages
        (the continuous-batching growth path — the reference's serving
        frontends grow block tables the same way between steps)."""
        f = self._faults
        if f is not None:
            f.fire("kv.grow")
        if n_pages > len(self._free):
            raise RuntimeError(
                f"KV pool exhausted growing seq {seq_id}: need "
                f"{n_pages} pages, {len(self._free)} free")
        pages = [self._free.pop() for _ in range(n_pages)]
        for p in pages:
            self._refs[p] = 1
        self._owned.setdefault(seq_id, []).extend(pages)
        return pages

    def free(self, seq_id) -> None:
        self.release_pages(self._owned.pop(seq_id, []))

    def truncate(self, seq_id, new_len: int) -> List[int]:
        """Page-granular ROLLBACK: shrink ``seq_id``'s page list to
        exactly ``pages_needed(new_len)`` leading pages, releasing the
        tail (the speculative-decoding rejection path — KV written in
        the rejected window is masked-dead, so only the page TABLE
        rolls back; no data moves). Releasing is refcount-aware: a
        tail page also held by the prefix cache or another sequence
        just drops this sequence's reference and stays live — shared
        prefix pages are NEVER freed by a rejection. Returns the pages
        released (possibly still live under other references)."""
        keep = self.pages_needed(max(int(new_len), 0))
        owned = self._owned.get(seq_id)
        if owned is None or keep >= len(owned):
            return []
        tail = owned[keep:]
        del owned[keep:]
        self.release_pages(tail)
        return tail

    # ---------- refcounting (prefix/KV reuse) ----------

    def retain(self, pages: Sequence[int]) -> None:
        """+1 on live pages (prefix-cache registration keeps prompt
        pages alive past their original request's free)."""
        for p in pages:
            if p not in self._refs:
                raise KeyError(f"retain of non-live page {p}")
            self._refs[p] += 1

    def release_pages(self, pages: Sequence[int]) -> None:
        """-1 each; a page returns to the free list when its LAST
        reference drops (shared prefix pages survive a sharer's free)."""
        for p in pages:
            rc = self._refs.get(p, 0)
            if rc <= 0:
                raise KeyError(f"release of non-live page {p}")
            if rc == 1:
                del self._refs[p]
                self._free.append(p)
            else:
                self._refs[p] = rc - 1

    def refcount(self, page: int) -> int:
        return self._refs.get(page, 0)

    def share(self, seq_id, pages: Sequence[int]) -> None:
        """Map already-live pages into ``seq_id``'s page list at +1 ref
        — the prefix-reuse admission path. Call BEFORE allocating the
        sequence's own tail pages: block tables are ordered, and the
        shared pages cover the leading positions."""
        self.retain(pages)
        self._owned.setdefault(seq_id, []).extend(pages)

    def rekey(self, old_seq_id, new_seq_id) -> None:
        """Move a sequence's page list to a new key (the serving
        scheduler parks chunk-prefilling sequences under a side key so
        the decode batch's slot tables never see half-filled pages)."""
        if new_seq_id in self._owned:
            raise KeyError(f"rekey target {new_seq_id!r} already owned")
        if old_seq_id in self._owned:
            self._owned[new_seq_id] = self._owned.pop(old_seq_id)

    def block_tables(self, seq_ids, pages_per_seq: int = None,
                     allow_missing: bool = False):
        """[batch, pages_per_seq] int32 table (padded with page 0 — padded
        entries are masked out by seq_lens in the attention).
        ``allow_missing`` maps unknown seq_ids to all-zero (scratch) rows
        — for continuous-batching idle slots; otherwise a stale/freed
        seq_id is a caller bug and raises KeyError."""
        if allow_missing:
            rows = [self._owned.get(s, []) for s in seq_ids]
        else:
            rows = [self._owned[s] for s in seq_ids]
        width = pages_per_seq or max(len(r) for r in rows)
        table = np.zeros((len(rows), width), np.int32)
        for i, r in enumerate(rows):
            table[i, : len(r)] = r
        return jnp.asarray(table)

    @property
    def free_pages(self) -> int:
        return len(self._free)

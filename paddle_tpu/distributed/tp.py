"""Tensor-parallel serving: the ``mp`` mesh axis for the decode stack.

TPU-native equivalent of the reference's multi-rank fused-transformer
serving (reference: ``fused_multi_transformer_op.cu:220,529`` — one
``ring_id`` allreduce after each row-parallel matmul — driven by the
multi-rank engine ``dist_model.cc:172``). Here the sharding is GSPMD
``shard_map`` over a named ``mp`` axis:

- **column-parallel** QKV and FFN1 (``[K, N/mp]`` shards — attention
  heads partition naturally with the QKV columns),
- **row-parallel** O-proj and FFN2 (``[K/mp, N]`` shards) whose partial
  sums meet in exactly ONE ``psum`` per projection pair — two per
  layer, the same two allreduce points as the reference; the sequential
  pre-LN math admits no fewer without changing the model,
- the **paged KV pool sharded by kv-head** (page tables are host-side
  ints and stay replicated, so the paged-pool bookkeeping — prefix
  cache, refcounts, preemption — is untouched by TP).

GQA small-kv fallback: when ``mp`` does not divide ``num_kv_heads`` but
``num_kv_heads`` divides ``mp``, each kv head is REPLICATED across
``mp // num_kv_heads`` adjacent shards (each shard stores one kv head
and computes that head's K/V redundantly); its query heads still
partition, so weight/KV traffic stays ~1/mp per chip. Any other
combination is a configuration error and raises early with the exact
divisibility constraint.

Weights are sharded AT LOAD: ``TPContext.shard_stack`` rearranges the
stacked host arrays so each shard's block is contiguous (only the QKV
stack needs a column gather — its q/k/v regions interleave per shard)
and ``device_put``s them under a ``NamedSharding`` — no chip ever
materializes the full stack.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

__all__ = ["split_kv_heads", "serving_mesh", "TPContext",
           "axis_extent", "ring_chunk_reduce",
           "ring_reduce", "reduce_over_axis", "ring_census",
           "resolve_overlap"]


def split_kv_heads(num_kv_heads: int, mp: int):
    """Per-shard kv-head layout for an ``mp``-way tensor-parallel pool.

    Returns ``(kv_heads_per_shard, kv_replication)``:

    - ``num_kv_heads % mp == 0`` → each shard owns a contiguous block of
      ``num_kv_heads // mp`` heads (``kv_replication == 1``);
    - ``mp % num_kv_heads == 0`` (GQA small-kv) → each kv head is
      replicated over ``mp // num_kv_heads`` adjacent shards, one head
      per shard (shard ``s`` holds head ``s // kv_replication``);
    - anything else raises with the exact constraint (a silent shape
      crash deep inside the pool scatter would be undebuggable).
    """
    mp = int(mp)
    num_kv_heads = int(num_kv_heads)
    if mp <= 1:
        return num_kv_heads, 1
    if num_kv_heads % mp == 0:
        return num_kv_heads // mp, 1
    if mp % num_kv_heads == 0:
        return 1, mp // num_kv_heads
    raise ValueError(
        f"num_kv_heads={num_kv_heads} is not shardable over "
        f"mp_degree={mp}: tensor-parallel serving needs "
        f"num_kv_heads % mp == 0 (kv-head sharding) or "
        f"mp % num_kv_heads == 0 (kv-head replication, the GQA "
        f"small-kv fallback); pick an mp degree from the divisors/"
        f"multiples of {num_kv_heads}")


def serving_mesh(mp_degree: int, devices=None, axis: str = "mp"):
    """A 1-D jax Mesh over the first ``mp_degree`` devices (or the
    given ones) with the serving ``mp`` axis name."""
    import numpy as np

    import jax
    from jax.sharding import Mesh

    if devices is None:
        devices = jax.devices()
    mp_degree = int(mp_degree)
    if len(devices) < mp_degree:
        raise ValueError(
            f"mp_degree={mp_degree} needs {mp_degree} devices, "
            f"have {len(devices)}")
    return Mesh(np.array(devices[:mp_degree]), (axis,))


#: stacked-weight name -> sharding layout kind. ``col3`` shards the
#: output (last) axis of [L, K, N]; ``row3`` shards the contraction
#: axis; ``col2`` shards per-output vectors [L, N]; ``rep`` replicates
#: (LN params and the row-parallel biases/scales, which apply to the
#: FULL output and are added once, after the psum). ``ep4``/``ep3``
#: shard the EXPERT axis (dim 1) of the MoE bank over the ``ep`` mesh
#: axis — each chip streams only its 1/ep expert slice; the gate stays
#: replicated (every shard routes its own token block).
_STACK_LAYOUT = {
    "qkv_weight": "col3", "qkv_bias": "col2", "qkv_scale": "col2",
    "ffn1_weight": "col3", "ffn1_bias": "col2", "ffn1_scale": "col2",
    "out_weight": "row3", "ffn2_weight": "row3",
    "gate_weight": "rep",
    "moe_w1": "ep4", "moe_b1": "ep3",
    "moe_w2": "ep4", "moe_b2": "ep3",
}

#: LoRA adapter-bank operand -> layout (serving/adapters.py, banks
#: ``{proj}_a [L, S, K, R]`` / ``{proj}_b [L, S, R, N]``). The delta
#: composes with the base shards WITHOUT new collectives: column-
#: parallel projections (qkv, ffn1) replicate A and column-split B
#: (the delta's output columns shard exactly like the base output);
#: row-parallel projections (out, ffn2) row-split A along the base
#: contraction shards and replicate B (``x·A = Σ_s x_s·A_s``, so each
#: shard's delta partial joins the base partial BEFORE the layer's
#: existing psum — still exactly 2 psums/layer).
_ADAPTER_LAYOUT = {
    "qkv_a": "rep", "qkv_b": "col_b",
    "ffn1_a": "rep", "ffn1_b": "col_b",
    "out_a": "row_a", "out_b": "rep",
    "ffn2_a": "row_a", "ffn2_b": "rep",
}


@dataclasses.dataclass(frozen=True)
class TPContext:
    """Resolved tensor/expert-parallel geometry for one serving engine.

    ``heads_per_shard`` / ``kv_heads_per_shard`` are what the per-shard
    transformer view computes with; ``kv_replication`` > 1 marks the
    GQA fallback (shard ``s`` holds kv head ``s // kv_replication``).
    ``ep`` > 1 marks expert parallelism (ISSUE 15): the MoE expert
    bank shards 1/ep per chip over the ``ep_axis`` mesh axis and the
    MoE FFN's dispatch/combine run as the two ``lax.all_to_all`` of
    the EP exchange inside the same shard_map the ``mp`` path uses.
    """

    mesh: Any               # jax.sharding.Mesh with the mp and/or ep axis
    axis: str               # tensor-parallel mesh axis name ("mp")
    mp: int
    num_heads: int          # global query heads
    num_kv_heads: int       # global kv heads
    head_dim: int
    heads_per_shard: int
    kv_heads_per_shard: int
    kv_replication: int
    ep: int = 1             # expert-parallel degree
    ep_axis: str = "ep"     # expert-parallel mesh axis name

    @classmethod
    def create(cls, num_heads: int, num_kv_heads: int, head_dim: int,
               mp_degree: Optional[int] = None, mesh=None,
               axis: str = "mp", ep_degree: Optional[int] = None,
               ep_axis: str = "ep") -> Optional["TPContext"]:
        """Resolve engine kwargs into a context (None = single-chip).

        ``mesh`` may be a jax Mesh or anything with ``.jax_mesh()``
        (e.g. a ProcessMesh); it must carry an ``mp``- and/or
        ``ep``-named axis. With only degrees given, a mesh over the
        first ``ep*mp`` devices is built (``(ep, mp)`` axes when both
        exceed 1).
        """
        mp_req = None if mp_degree is None else int(mp_degree)
        ep_req = None if ep_degree is None else int(ep_degree)
        if mesh is None and (mp_req or 1) <= 1 and (ep_req or 1) <= 1:
            return None
        if mesh is not None and hasattr(mesh, "jax_mesh"):
            mesh = mesh.jax_mesh()
        if mesh is None:
            import numpy as np

            import jax
            from jax.sharding import Mesh

            mp_n, ep_n = mp_req or 1, ep_req or 1
            if ep_n > 1 and mp_n > 1:
                devices = jax.devices()
                if len(devices) < ep_n * mp_n:
                    raise ValueError(
                        f"ep{ep_n} x mp{mp_n} needs {ep_n * mp_n} "
                        f"devices, have {len(devices)}")
                mesh = Mesh(np.array(devices[:ep_n * mp_n])
                            .reshape(ep_n, mp_n), (ep_axis, axis))
            elif ep_n > 1:
                mesh = serving_mesh(ep_n, axis=ep_axis)
            else:
                mesh = serving_mesh(mp_n, axis=axis)
        names = tuple(mesh.axis_names)
        if axis not in names and ep_axis not in names:
            raise ValueError(
                f"tensor/expert-parallel mesh must carry an {axis!r} "
                f"and/or {ep_axis!r} axis, got axes {names}")
        mp = int(mesh.shape[axis]) if axis in names else 1
        ep = int(mesh.shape[ep_axis]) if ep_axis in names else 1
        if mp_req is not None and mp_req != mp:
            raise ValueError(
                f"mp_degree={mp_req} disagrees with the mesh's "
                f"{axis!r} extent {mp}")
        if ep_req is not None and ep_req != ep:
            raise ValueError(
                f"ep_degree={ep_req} disagrees with the mesh's "
                f"{ep_axis!r} extent {ep}")
        if mp <= 1 and ep <= 1:
            return None
        if mp > 1 and num_heads % mp != 0:
            raise ValueError(
                f"num_heads={num_heads} must divide evenly over "
                f"mp_degree={mp} (query heads partition with the QKV "
                f"columns)")
        kvs, repl = split_kv_heads(num_kv_heads, mp)
        return cls(mesh=mesh, axis=axis, mp=mp, num_heads=num_heads,
                   num_kv_heads=num_kv_heads, head_dim=head_dim,
                   heads_per_shard=num_heads // mp,
                   kv_heads_per_shard=kvs, kv_replication=repl,
                   ep=ep, ep_axis=ep_axis)

    # ---------------- specs ----------------

    @property
    def kv_pool_heads(self) -> int:
        """GLOBAL kv-head extent of the sharded pool array: the
        original head count when sharded, ``mp`` (one replicated head
        per shard) in the GQA fallback."""
        return self.kv_heads_per_shard * self.mp

    def pspec(self, *parts):
        from jax.sharding import PartitionSpec

        return PartitionSpec(*parts)

    def sharding(self, *parts):
        from jax.sharding import NamedSharding

        return NamedSharding(self.mesh, self.pspec(*parts))

    def kv_spec(self):
        """PartitionSpec of a pool side [L*P, kv_heads, page, hd]:
        kv-head-sharded over ``mp``; replicated on an ep-only mesh
        (EP shards the EXPERT bank — every shard attends its own token
        block against the same replicated pool)."""
        if self.mp <= 1:
            return self.pspec()
        return self.pspec(None, self.axis, None, None)

    def stack_spec(self, name: str):
        """PartitionSpec for one stacked-weight entry (shard_map
        in_spec / device placement)."""
        kind = _STACK_LAYOUT.get(name, "rep")
        if kind == "col3" and self.mp > 1:
            return self.pspec(None, None, self.axis)
        if kind == "row3" and self.mp > 1:
            return self.pspec(None, self.axis, None)
        if kind == "col2" and self.mp > 1:
            return self.pspec(None, self.axis)
        if kind == "ep4" and self.ep > 1:
            return self.pspec(None, self.ep_axis, None, None)
        if kind == "ep3" and self.ep > 1:
            return self.pspec(None, self.ep_axis, None)
        return self.pspec()

    def adapter_spec(self, name: str):
        """PartitionSpec for one LoRA adapter-bank operand
        (``_ADAPTER_LAYOUT``): B of column-parallel projections splits
        its output columns [L, S, R, N/mp], A of row-parallel ones
        splits its contraction rows [L, S, K/mp, R], everything else
        replicates."""
        kind = _ADAPTER_LAYOUT.get(name, "rep")
        if kind == "col_b" and self.mp > 1:
            return self.pspec(None, None, None, self.axis)
        if kind == "row_a" and self.mp > 1:
            return self.pspec(None, None, self.axis, None)
        return self.pspec()

    def replicate(self, arr):
        """device_put an operand replicated over the mesh (mixing
        single-device-committed arrays with mesh-sharded ones in one
        jit call is an error; replicating once at engine init also
        avoids a per-call host transfer)."""
        import jax

        return jax.device_put(arr, self.sharding())

    # ---------------- weight rearrangement ----------------

    def qkv_col_index(self):
        """Column gather index making each shard's QKV block contiguous.

        The stacked QKV output axis is ``[q0..qH-1, k0..k{nkv}-1,
        v0..]`` (head-major, ``head_dim`` wide each); shard ``s`` needs
        ``[q of its heads, k of its kv heads, v of its kv heads]``
        contiguous so a plain even split of the LAST axis is the shard
        layout. In the GQA fallback the kv columns are DUPLICATED per
        replica shard, so the rearranged width grows to
        ``mp * (heads_per_shard + 2) * head_dim``.
        """
        import numpy as np

        hd = self.head_dim
        H, nkv = self.num_heads, self.num_kv_heads
        Hs, kvs = self.heads_per_shard, self.kv_heads_per_shard
        within = np.arange(hd)
        cols = []
        for s in range(self.mp):
            qh = np.arange(s * Hs, (s + 1) * Hs)
            if self.kv_replication == 1:
                kvh = np.arange(s * kvs, (s + 1) * kvs)
            else:
                kvh = np.array([s // self.kv_replication])
            cols.append((qh[:, None] * hd + within).ravel())
            cols.append((H * hd) + (kvh[:, None] * hd + within).ravel())
            cols.append(((H + nkv) * hd)
                        + (kvh[:, None] * hd + within).ravel())
        return np.concatenate(cols)

    def shard_stack(self, weights: dict) -> dict:
        """Per-shard stacked weights, sharded AT LOAD: rearrange on the
        host (only ``qkv_*`` needs the column gather) and ``device_put``
        each stack under its NamedSharding — every chip receives only
        its ``[K, N/mp]`` / ``[K/mp, N]`` slice, never the full stack.
        """
        import numpy as np

        import jax

        qkv_idx = None
        out = {}
        for name, arr in weights.items():
            a = np.asarray(arr)
            if name.startswith("qkv_") and self.mp > 1:
                if qkv_idx is None:
                    qkv_idx = self.qkv_col_index()
                a = np.take(a, qkv_idx, axis=-1)
            out[name] = jax.device_put(
                a, self.sharding(*self.stack_spec(name)))
        return out


# ---------------- collective overlap: ring reduction (ISSUE 19) ----------------

def axis_extent(axis_name) -> int:
    """Static extent of a named mesh axis at trace time (``psum`` of a
    Python literal folds to the axis size without emitting a
    collective — the jax idiom for a shard_map body that must branch
    on its own parallelism degree)."""
    import jax

    return int(jax.lax.psum(1, axis_name))


def ring_chunk_reduce(chunk, axis_name, size: int):
    """All-reduce ONE column chunk of a row-parallel partial around the
    ring: ``size - 1`` ``ppermute`` steps circulate every shard's
    partial; the shard then re-orders the collected partials into
    GLOBAL rank order and sums them left-to-right, so every shard
    produces the bitwise-identical result (a rank-local accumulation
    order would let replicas drift apart one ulp at a time).

    Each step depends only on THIS chunk's partial, so XLA's async
    collective-permute scheduler is free to run it under the next
    chunk's GEMM — the overlap ``stream_linear(overlap="ring")``
    pipelines for.
    """
    import jax
    import jax.numpy as jnp

    if size == 1:
        return chunk
    perm = [(i, (i + 1) % size) for i in range(size)]
    vals = [chunk]
    recv = chunk
    for _ in range(size - 1):
        recv = jax.lax.ppermute(recv, axis_name, perm)
        vals.append(recv)
    # vals[t] holds shard (rank - t) % size's partial; re-index so
    # position j holds shard j's partial, same on every member
    idx = jax.lax.axis_index(axis_name)
    stacked = jnp.stack(vals)
    order = (idx - jnp.arange(size, dtype=idx.dtype)) % size
    ordered = jnp.take(stacked, order, axis=0)
    acc = ordered[0]
    for j in range(1, size):
        acc = acc + ordered[j]
    return acc


def ring_reduce(part, axis_name, size: Optional[int] = None):
    """Software-pipelined replacement for ``jax.lax.psum(part, axis)``
    on a row-parallel partial: the last dim splits into ``size`` column
    chunks and each chunk all-reduces independently via
    ``ring_chunk_reduce`` — ``size * (size - 1)`` ``ppermute`` steps
    total, none of which blocks the others, where the single psum
    serialized the whole reduction behind the slowest shard."""
    import numpy as np

    import jax
    import jax.numpy as jnp

    if size is None:
        size = axis_extent(axis_name)
    if size == 1:
        return part
    n = part.shape[-1]
    bounds = np.linspace(0, n, size + 1).astype(int)
    chunks = [
        ring_chunk_reduce(
            jax.lax.slice_in_dim(part, int(lo), int(hi), axis=-1),
            axis_name, size)
        for lo, hi in zip(bounds[:-1], bounds[1:]) if hi > lo]
    return jnp.concatenate(chunks, axis=-1) if len(chunks) > 1 \
        else chunks[0]


def reduce_over_axis(part, axis_name, overlap: str = "psum"):
    """The row-parallel reduction seam with the ``overlap`` knob:
    ``"psum"`` is the single blocking all-reduce (the bitwise/census
    reference), ``"ring"`` the chunked ``ppermute`` pipeline. An axis
    of extent 1 (a single-shard TP view) skips the collective entirely
    at trace time — the program census must not carry a no-op psum."""
    import jax

    from ..profiler import stats as _stats

    size = axis_extent(axis_name)
    if size == 1:
        return part
    if overlap == "ring":
        _stats.counter("dist.overlap_ring_reduces").inc()
        _stats.gauge("dist.overlap_ring_phases").set(
            float(size * (size - 1)))
        return ring_reduce(part, axis_name, size)
    if overlap != "psum":
        raise ValueError(
            f"overlap={overlap!r}: expected 'ring' or 'psum'")
    return jax.lax.psum(part, axis_name)


def ring_census(axis_name, size: int, reductions: int = 1):
    """The EXACT collective sequence ``reductions`` ring reductions
    trace to — ``(prim, axes)`` pairs in ``trace_census`` format — for
    census pins: ``size * (size - 1)`` ppermutes per reduction, zero
    psums."""
    step = ("ppermute", str((axis_name,)))
    return [step] * (size * (size - 1)) * reductions


def resolve_overlap(overlap: Optional[str]) -> str:
    """The effective TP overlap mode: an explicit knob wins, else
    ``FLAGS_tp_overlap``."""
    if overlap is not None:
        return overlap
    from ..core.flags import flag

    return flag("tp_overlap")

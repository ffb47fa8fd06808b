"""Ring attention: attention-level sequence/context parallelism.

The reference scales sequence length with Megatron-SP + a `sep` mesh axis
+ FlashAttention (SURVEY.md §5.7) but has NO ring attention; this module
covers that surface the TPU-native way, as §5.7 prescribes: q/k/v sharded
on the sequence dim over a mesh axis, K/V blocks rotated around the ring
with ``lax.ppermute`` (ICI neighbor exchange), online-softmax
rescaling accumulates exact attention — memory per device is O(seq/N),
and the ppermute overlaps with the block matmuls.

Layout: [batch, seqlen, heads, head_dim] (paddle flash_attention layout).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import NamedSharding, PartitionSpec

from ...core.tensor import Tensor
from ...ops.dispatch import as_tensor_args, eager_apply

__all__ = ["ring_attention", "ring_flash_attention"]


def _mark_varying(t, axis_name):
    """Mark a replicated value device-varying over ``axis_name`` so it
    can be a scan carry next to the rotating K/V blocks."""
    return lax.pcast(t, (axis_name,), to="varying")


def _ring_attention_sharded(q, k, v, axis_name: str, causal: bool,
                            scale: float, axis_size: int):
    """Per-device body under shard_map: q,k,v are local seq blocks."""
    b, sq, h, dh = q.shape
    my = lax.axis_index(axis_name)

    def block_attn(q_blk, k_blk, v_blk, q_off, k_off):
        # returns unnormalized (out, row_sum, row_max) with online softmax
        logits = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_blk) * scale
        if causal:
            sq_, sk_ = logits.shape[-2], logits.shape[-1]
            q_pos = q_off + jnp.arange(sq_)
            k_pos = k_off + jnp.arange(sk_)
            mask = q_pos[:, None] >= k_pos[None, :]
            logits = jnp.where(mask[None, None], logits, -jnp.inf)
        m = jnp.max(logits, -1)                       # [b,h,q]
        m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
        p = jnp.exp(logits - m_safe[..., None])
        p = jnp.where(jnp.isfinite(logits), p, 0.0)
        l = jnp.sum(p, -1)                            # [b,h,q]
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v_blk)
        return o, l, m_safe, jnp.isfinite(m)

    sk = k.shape[1]
    q_off = my * sq

    def step(carry, i):
        o_acc, l_acc, m_acc, k_cur, v_cur = carry
        src = (my - i) % axis_size          # which rank's kv block we hold
        k_off = src * sk
        o_b, l_b, m_b, valid = block_attn(q, k_cur, v_cur, q_off, k_off)
        # online softmax merge
        m_new = jnp.maximum(m_acc, jnp.where(valid, m_b, -jnp.inf))
        m_new_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
        alpha = jnp.exp(jnp.where(jnp.isfinite(m_acc), m_acc, -jnp.inf)
                        - m_new_safe)
        alpha = jnp.where(jnp.isfinite(m_acc), alpha, 0.0)
        beta = jnp.exp(jnp.where(valid, m_b, -jnp.inf) - m_new_safe)
        beta = jnp.where(valid, beta, 0.0)
        o_acc = o_acc * alpha.transpose(0, 2, 1)[..., None] \
            + o_b * beta.transpose(0, 2, 1)[..., None]
        l_acc = l_acc * alpha + l_b * beta
        m_acc = m_new
        # rotate kv around the ring (ICI neighbor exchange)
        perm = [(j, (j + 1) % axis_size) for j in range(axis_size)]
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (o_acc, l_acc, m_acc, k_nxt, v_nxt), None

    o0 = jnp.zeros((b, sq, h, dh), q.dtype)
    l0 = jnp.zeros((b, h, sq), q.dtype)
    m0 = jnp.full((b, h, sq), -jnp.inf, q.dtype)
    # carries become device-varying after step 1 (they depend on
    # axis_index); mark the inits as varying over the ring axis
    o0, l0, m0 = (_mark_varying(t, axis_name) for t in (o0, l0, m0))
    (o, l, m, _, _), _ = lax.scan(step, (o0, l0, m0, k, v),
                                  jnp.arange(axis_size))
    l_safe = jnp.maximum(l, 1e-20)
    return o / l_safe.transpose(0, 2, 1)[..., None]


def ring_attention(q, k, v, mesh=None, seq_axis: str = "sep",
                   causal: bool = False, scale: Optional[float] = None,
                   name=None):
    """Exact attention over sequence-sharded q/k/v.

    ``mesh``: a ProcessMesh containing ``seq_axis``; defaults to the fleet
    hybrid mesh. Inputs may be dist tensors sharded on dim 1 over
    ``seq_axis`` (or dense, in which case they're sharded here). Output is
    sharded the same way.
    """

    from ...distributed.auto_parallel.placement import (
        ProcessMesh, Replicate, Shard,
    )

    if mesh is None:
        if isinstance(q, Tensor) and q._dist_attr is not None:
            mesh = q._dist_attr[0]
        else:
            from ...distributed.fleet import fleet

            mesh = fleet.get_hybrid_communicate_group().mesh
    axis_size = mesh.get_dim_size(seq_axis)
    head_dim = (q.shape if isinstance(q, Tensor) else q.shape)[-1]
    scale = scale if scale is not None else head_dim ** -0.5

    spec: list = [None, None, None, None]
    spec[1] = seq_axis
    pspec = PartitionSpec(*spec)
    jmesh = mesh.jax_mesh()

    body = functools.partial(_ring_attention_sharded, axis_name=seq_axis,
                             causal=causal, scale=scale,
                             axis_size=axis_size)
    fn = jax.shard_map(body, mesh=jmesh, in_specs=(pspec, pspec, pspec),
                       out_specs=pspec)
    jit_fn = jax.jit(fn)

    placements = [Replicate()] * mesh.ndim
    placements[mesh.dim_names.index(seq_axis)] = Shard(1)
    sharding = mesh.sharding_for(placements, 4)

    def raw(qa, ka, va):
        qa = lax.with_sharding_constraint(qa, sharding) \
            if qa.shape[1] % axis_size == 0 else qa
        return jit_fn(qa, ka, va)

    tensors = as_tensor_args(q, k, v)
    # place inputs
    for t in tensors:
        if t._dist_attr is None:
            t._data = jax.device_put(t._data, sharding)
            t._dist_attr = (mesh, placements)
    out = eager_apply("ring_attention", raw, tensors)
    out._dist_attr = (mesh, placements)
    return out


def ring_flash_attention(q, k, v, mesh=None, seq_axis="sep", causal=False,
                         dropout=0.0, training=True, name=None):
    """flash_attention-shaped wrapper (returns (out, None))."""
    out = ring_attention(q, k, v, mesh=mesh, seq_axis=seq_axis,
                         causal=causal)
    return out, None

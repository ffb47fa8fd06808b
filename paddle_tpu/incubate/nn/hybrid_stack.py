"""A served stack BUILT from a ``LayerPattern``: a period of layer kinds
(Mamba-2 mixers, NoPE / rotary GQA attention, multi-head latent
attention), each followed by an FFN of routed gated experts plus a
shared gated MLP, RMSNorm, no biases, residual / attention multipliers
from the description.

    h = h + r * Mixer(RMSNorm(h))
    h = h + r * (MoE(x) + SharedMLP(x)),   x = RMSNorm(h)

A weight stack a kind, a cache group a kind:

``"mamba"``             ``m_*``; a slot-indexed ``RecurrentState`` that
    the decode program updates in place.
``"attention"``         ``qkv_weight`` / ``out_weight`` / ``a_norm``; the
    serving engine's paged pool ``PagedKV`` (layer-folded over
    ``n_attention`` layers, touched only by the three paged Pallas
    kernels ``FusedMultiTransformer`` uses).
``"latent_attention"``  ``l_*`` (down / up projections of the queries,
    the latent + rope-key projection padded to the pool's row, the
    per-head ``W_uk`` / ``W_uv`` the absorbed form contracts with); the
    paged latent pool ``LatentKV`` (one row a token and layer, touched
    only by ``pt_mla_paged_prefill`` / ``pt_mla_paged_decode``).

``f_*`` / ``e_*`` / ``s_*`` are the FFN of every layer.

The two raw phases mirror ``FusedMultiTransformer``'s:

``prefill_chunk_raw``  ONE sequence's chunk ``[1, c, d]`` at positions
    ``start ..``; the recurrent state enters as that slot's arrays
    (zeros on a fresh admission) and leaves as the state after the
    chunk's VALID rows — padded rows of a bucketed chunk have ``dt =
    0`` and stay out of the conv tail.
``decode_raw``         one token for every slot ``[slots, d]``; rows not
    ``active`` (idle slots, slots still prefilling) leave pool and state
    as they were.

Both return pick counts of the expert layers (all picks, picks on held
experts, held experts hit, held experts offered; a prefill chunk also
the work units its grouped GEMMs walked and those that owned a row) so
the engine can publish them with the tokens it fetches anyway.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp

from ...nn.layer_base import Layer
from ...nn.functional.mla_attention import LatentKV
from .fused_transformer import PagedKV, _apply_rope
from .layer_pattern import LATENT, MAMBA, LayerPattern

__all__ = ["HybridStack", "RecurrentState"]


@functools.partial(jax.jit, static_argnames=("shape", "dtype", "std"))
def _draw(key, shape, dtype, std):
    """Normal values drawn straight into ``dtype``: under jit no float32
    array of the stack's size is ever alive (an expert bank is
    gigabytes)."""
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


class RecurrentState(NamedTuple):
    """Slot-indexed state of the recurrent layers, beside the paged pool.

    ``ssm [layers, slots, d_state, d_inner]`` float32 (lanes are
    ``(head, p)``: ``nn/functional/ssm.py``), ``conv [layers, slots,
    d_conv - 1, conv_dim]`` the last rows the causal conv still needs."""
    ssm: jax.Array
    conv: jax.Array


class HybridStack(Layer):
    def __init__(self, pattern: LayerPattern, dtype=jnp.float32):
        super().__init__()
        if pattern.moe is None or not pattern.gated \
                or pattern.norm != "rmsnorm" or pattern.bias:
            raise NotImplementedError(
                "HybridStack serves RMSNorm, bias-free, SiLU-gated "
                "routed-expert blocks; the LayerNorm / biased GELU "
                "one-kind pattern is FusedMultiTransformer's (kinds a "
                "period may mix here: mamba, attention, "
                "latent_attention)")
        self.pattern = pattern
        self.embed_dim = pattern.d_model
        self.num_layers = pattern.num_layers
        self.epsilon = pattern.epsilon
        att = pattern.attention

        from ...core.generator import default_generator
        from ...core.tensor import Parameter

        dtype = jnp.dtype(dtype)

        def normal(*s, std=0.02, dt=dtype):
            return _draw(default_generator().next_key(), s, dt, std)

        def mk(name, arr):
            setattr(self, name, Parameter(arr))

        d, L = pattern.d_model, pattern.num_layers
        ones = lambda *s: jnp.ones(s, jnp.float32)   # noqa: E731
        self._names = []

        def add(name, arr):
            mk(name, arr)
            self._names.append(name)

        Lm, La, Ll = (pattern.n_mamba, pattern.n_attention,
                      pattern.n_latent)
        if Ll:
            lt = pattern.latent
            H, R = lt.num_heads, lt.kv_lora_rank
            add("l_norm", ones(Ll, d))
            add("l_dq", normal(Ll, d, lt.q_lora_rank))
            add("l_qnorm", ones(Ll, lt.q_lora_rank))
            add("l_uq", normal(Ll, lt.q_lora_rank,
                               H * (lt.qk_nope_head_dim
                                    + lt.qk_rope_head_dim)))
            # [latent | rope key | 0]: the projection lands on the
            # pool's row, pad lanes included
            add("l_dkv", jnp.pad(
                normal(Ll, d, lt.row_used),
                ((0, 0), (0, 0), (0, lt.row_width - lt.row_used))))
            add("l_kvnorm", ones(Ll, R))
            add("l_uk", normal(Ll, H, lt.qk_nope_head_dim, R))
            add("l_uv", normal(Ll, H, R, lt.v_head_dim))
            add("l_o", normal(Ll, H * lt.v_head_dim, d))
        if Lm:
            m = pattern.mamba
            add("m_norm", ones(Lm, d))
            add("m_in", normal(Lm, d, m.in_proj_dim))
            add("m_conv_w", normal(Lm, m.d_conv, m.conv_dim, std=0.2,
                                   dt=jnp.dtype(jnp.float32)))
            add("m_conv_b", jnp.zeros((Lm, m.conv_dim), jnp.float32))
            add("m_dt_bias", jnp.full((Lm, m.num_heads), -4.0,
                                      jnp.float32))
            add("m_A_log", jnp.zeros((Lm, m.num_heads), jnp.float32))
            add("m_D", ones(Lm, m.num_heads))
            add("m_gnorm", ones(Lm, m.d_inner))
            add("m_out", normal(Lm, m.d_inner, d))
        if La:
            qkv = (att.num_heads + 2 * att.num_kv_heads) * att.head_dim
            add("a_norm", ones(La, d))
            add("qkv_weight", normal(La, d, qkv))
            add("out_weight", normal(La, att.num_heads * att.head_dim, d))
        moe = pattern.moe
        held = moe.held[1]
        add("f_norm", ones(L, d))
        add("f_router", normal(L, d, moe.num_experts,
                               dt=jnp.dtype(jnp.float32)))
        add("e_w1", normal(L, held, d, 2 * moe.expert_dim))
        add("e_w2", normal(L, held, moe.expert_dim, d))
        if moe.shared_dim:
            add("s_w1", normal(L, d, 2 * moe.shared_dim))
            add("s_w2", normal(L, moe.shared_dim, d))

    # ------------------------------------------------ functional core

    def _stack(self):
        return {n: getattr(self, n)._data for n in self._names}

    @staticmethod
    def _rms(x, scale, eps):
        xf = x.astype(jnp.float32)
        var = jnp.mean(jnp.square(xf), -1, keepdims=True)
        return xf * jax.lax.rsqrt(var + eps) * scale

    def _residual(self, h, update):
        """``h + residual_multiplier * update`` in float32, back in h's
        dtype."""
        return (h.astype(jnp.float32)
                + self.pattern.residual_multiplier * update).astype(h.dtype)

    def _rope(self, x, positions, cos_t, sin_t):
        if self.pattern.attention.rope_theta is None:
            return x
        cos = cos_t[positions][..., None, :]
        sin = sin_t[positions][..., None, :]
        return _apply_rope(x, cos, sin)

    def _proj(self, x, w, l, rows_are_decode):
        """x @ w[l] for a layer-stacked matrix: decode rows stream the
        stack in place (``pt_stream_linear_bf16``), prefill rows take the
        XLA dot over the static slice."""
        if rows_are_decode:
            from ...nn.functional.stream_linear import stream_linear

            return stream_linear(x, w, layer=l, out_dtype=jnp.float32)
        return jax.lax.dot_general(
            x, w[l], (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    def _ffn(self, w, h, l, rows, decode, counts):
        """``h + r * (MoE(x) + SharedMLP(x))`` over flat rows ``[T, d]``
        and the layer's pick counts (prefill rows: and the grouped
        GEMMs' unit counts) added to ``counts``."""
        from ...nn.functional.moe_gated import (
            gated_mlp, moe_gated_grouped, moe_gated_stream, pick_counts,
            route_topk_softmax)

        p = self.pattern
        moe = p.moe
        x = self._rms(h, w["f_norm"][l], p.epsilon).astype(h.dtype)
        gates, idx = route_topk_softmax(x, w["f_router"][l], moe.top_k)
        units = []              # the streamed experts have no schedule
        if decode:
            y = moe_gated_stream(x, gates, idx, w["e_w1"], w["e_w2"], l,
                                 moe.held)
        else:
            y, walked_live = moe_gated_grouped(
                x, gates, idx, w["e_w1"], w["e_w2"], l, moe.held)
            units = [walked_live]
        if moe.shared_dim:
            y = y + gated_mlp(x, w["s_w1"][l], w["s_w2"][l])
        c = pick_counts(idx, rows, moe.held)
        counts = counts + jnp.concatenate(
            [c, jnp.full((1,), moe.held[1], jnp.int32)] + units)
        return self._residual(h, y), counts

    def _mamba_split(self, zxbcdt):
        m = self.pattern.mamba
        di, cd = m.d_inner, m.conv_dim
        return (zxbcdt[..., :di], zxbcdt[..., di: di + cd],
                zxbcdt[..., di + cd:])

    def _mamba_finish(self, w, lm, h, y, z, rows_are_decode):
        """Gated RMSNorm over all of d_inner, the out projection and the
        residual. y float32 ``[T, d_inner]``."""
        p = self.pattern
        g = y * jax.nn.silu(z.astype(jnp.float32))
        g = self._rms(g, w["m_gnorm"][lm], p.epsilon).astype(h.dtype)
        return self._residual(
            h, self._proj(g, w["m_out"], lm, rows_are_decode))

    def _latent_inputs(self, w, li, h, positions, cos_t, sin_t, stream):
        """Rows ``h [T, d]`` at ``positions [T]`` of latent layer ``li``
        -> ``(q [T, H, W]`` the absorbed queries, scale and temperature
        folded in, over the lanes of a cache row; ``rows [T, W]`` the
        tokens' cache rows ``[normed latent | rotated rope key | 0]``)."""
        from ...nn.functional.mla_attention import (query_temperature,
                                                    rope_interleaved)

        p = self.pattern
        lt = p.latent
        H, n, r = lt.num_heads, lt.qk_nope_head_dim, lt.qk_rope_head_dim
        R, W = lt.kv_lora_rank, lt.row_width
        T = h.shape[0]
        hn = self._rms(h, w["l_norm"][li], p.epsilon).astype(h.dtype)
        cq = self._rms(self._proj(hn, w["l_dq"], li, stream),
                       w["l_qnorm"][li], p.epsilon).astype(h.dtype)
        qf = self._proj(cq, w["l_uq"], li, stream).reshape(T, H, n + r)
        ckv = self._proj(hn, w["l_dkv"], li, stream)          # [T, W]
        cos, sin = cos_t[positions], sin_t[positions]         # [T, r/2]
        pad = jnp.zeros((T, W - R - r), jnp.float32)
        rows = jnp.concatenate(
            [self._rms(ckv[:, :R], w["l_kvnorm"][li], p.epsilon),
             rope_interleaved(ckv[:, R: R + r], cos, sin), pad], -1)
        q_abs = jnp.einsum("thn,hnr->thr", qf[..., :n].astype(h.dtype),
                           w["l_uk"][li],
                           preferred_element_type=jnp.float32)
        q_rope = rope_interleaved(qf[..., n:], cos[:, None], sin[:, None])
        temp = lt.softmax_scale * query_temperature(
            positions, lt.temperature_beta, lt.temperature_period)
        q = jnp.concatenate(
            [q_abs, q_rope, jnp.broadcast_to(pad[:, None], (T, H, W - R - r))],
            -1) * temp[:, None, None]
        return q.astype(h.dtype), rows.astype(h.dtype)

    def _latent_finish(self, w, li, h, o, stream):
        """``o [T, H, kv_rank]`` float32, the attended latents -> the
        residual stream: per-head ``W_uv``, then ``W_o``."""
        att = jnp.einsum("thr,hrv->thv", o.astype(h.dtype), w["l_uv"][li],
                         preferred_element_type=jnp.float32)
        att = att.reshape(h.shape[0], -1).astype(h.dtype)
        return self._residual(h, self._proj(att, w["l_o"], li, stream))

    @staticmethod
    def _split_cache(cache):
        """``(latent pool | None, K side | None, V side | None)``."""
        if isinstance(cache, LatentKV):
            return cache.rows, None, None
        if cache is None:
            return None, None, None
        return None, cache.k, cache.v

    @staticmethod
    def _join_cache(pool, ck, cv):
        if pool is not None:
            return LatentKV(pool)
        return PagedKV(ck, cv) if ck is not None else None

    # ------------------------------------------------------- prefill

    def prefill_chunk_raw(self, weights, x, cache, state, block_tables,
                          start, chunk_lens, cos_t=None, sin_t=None):
        """x ``[1, c, d]``; ``cache`` the paged pool of the attention
        layers (``PagedKV``) or of the latent-attention layers
        (``LatentKV``); ``state`` this sequence's recurrent arrays
        (``ssm [Lm, N, d_inner]`` float32, ``conv [Lm, k-1, conv_dim]``)
        as they stood before the chunk. Returns ``(hidden [1, c, d],
        cache', state', counts int32 [6])``."""
        from ...nn.functional.flash_varlen import paged_prefill_attention
        from ...nn.functional.mla_attention import mla_prefill_attend
        from ...nn.functional.paged_attention import (
            write_prefill_kv_inplace)
        from ...nn.functional.ssm import (causal_conv1d_chunk,
                                          ssd_chunk_scan)

        p = self.pattern
        b, c, d = x.shape
        if b != 1:
            raise ValueError("HybridStack.prefill_chunk_raw takes one "
                             "sequence's chunk at a time")
        w = weights
        start = start.astype(jnp.int32)
        chunk_lens = chunk_lens.astype(jnp.int32)
        valid = jnp.arange(c, dtype=jnp.int32) < chunk_lens[0]   # [c]
        positions = start[:, None] + jnp.arange(c, dtype=jnp.int32)[None]
        pool, ck, cv = self._split_cache(cache)
        ssm, conv = state if state is not None else (None, None)
        paged = pool if pool is not None else ck
        npages = paged.shape[0] // max(p.n_paged, 1) \
            if paged is not None else 0
        counts = jnp.zeros((6,), jnp.int32)
        h = x[0]
        for l, kind in enumerate(p.kinds()):
            li = p.kind_index(l)
            if kind == MAMBA:
                m = p.mamba
                hn = self._rms(h, w["m_norm"][li], p.epsilon) \
                    .astype(h.dtype)
                z, xbc, dt = self._mamba_split(
                    self._proj(hn, w["m_in"], li, False))
                xbc, tail = causal_conv1d_chunk(
                    xbc.astype(h.dtype)[None], conv[li][None],
                    w["m_conv_w"][li], w["m_conv_b"][li], chunk_lens)
                xbc = xbc[0]
                u = xbc[:, :m.d_inner].reshape(c, m.num_heads, m.head_dim)
                Bm = xbc[:, m.d_inner: m.d_inner + m.d_state]
                Cm = xbc[:, m.d_inner + m.d_state:]
                dt = jax.nn.softplus(dt + w["m_dt_bias"][li][None, :])
                dt = jnp.where(valid[:, None], dt, 0.0)
                y, s_new = ssd_chunk_scan(
                    u, dt, -jnp.exp(w["m_A_log"][li]), Bm, Cm,
                    w["m_D"][li], ssm[li], chunk_size=m.chunk_size)
                ssm = ssm.at[li].set(s_new)
                conv = conv.at[li].set(tail[0])
                h = self._mamba_finish(w, li, h, y, z, False)
            elif kind == LATENT:
                q, rows = self._latent_inputs(w, li, h, positions[0],
                                              cos_t, sin_t, False)
                o, pool = mla_prefill_attend(
                    q, rows, pool, block_tables + li * npages, start,
                    chunk_lens, v_width=p.latent.kv_lora_rank)
                h = self._latent_finish(w, li, h, o, False)
            else:
                att = p.attention
                hn = self._rms(h, w["a_norm"][li], p.epsilon) \
                    .astype(h.dtype)
                proj = self._proj(hn, w["qkv_weight"], li, False) \
                    .astype(h.dtype)
                nq, nkv, hd = att.num_heads, att.num_kv_heads, att.head_dim
                q, k, v = jnp.split(
                    proj.reshape(1, c, nq + 2 * nkv, hd), [nq, nq + nkv],
                    axis=2)
                q = self._rope(q, positions, cos_t, sin_t)
                k = self._rope(k, positions, cos_t, sin_t)
                tbl = block_tables + li * npages
                ck, cv = write_prefill_kv_inplace(ck, cv, k, v, tbl,
                                                  start, chunk_lens)
                o = paged_prefill_attention(
                    q, ck, cv, tbl, start, n_kv=nkv,
                    scale=att.softmax_scale, backend="auto")
                o = o.reshape(c, nq * hd).astype(h.dtype)
                h = self._residual(
                    h, self._proj(o, w["out_weight"], li, False))
            h, counts = self._ffn(w, h, l, valid, False, counts)
        # held experts hit / offered are a reading of the DECODE steps
        # (is the expert stream's time free of the data?): a prefill
        # chunk reports its picks and its grouped GEMMs' units alone
        counts = counts * jnp.asarray([1, 1, 0, 0, 1, 1], jnp.int32)
        state2 = (ssm, conv) if ssm is not None else None
        return h[None], self._join_cache(pool, ck, cv), state2, counts

    # -------------------------------------------------------- decode

    def decode_raw(self, weights, x, cache, state, block_tables,
                   seq_lens, active, cos_t=None, sin_t=None):
        """One decode step for every slot: x ``[slots, d]``, ``seq_lens``
        the tokens already cached, ``active [slots]`` bool. ``state`` is
        the WHOLE ``RecurrentState`` (its ssm array is updated in place
        by ``pt_ssm_decode_update``). Returns ``(hidden, cache',
        state', counts int32 [4])``."""
        from ...device import chip as _chip
        from ...nn.functional.mla_attention import mla_decode_attend
        from ...nn.functional.paged_attention import (
            decode_attend, plan_decode_attention)
        from ...nn.functional.ssm import (causal_conv1d_step,
                                          expand_heads, ssm_decode_update)

        p = self.pattern
        w = weights
        S = x.shape[0]
        stream = _chip.on_tpu() and S % 8 == 0
        seq_lens = seq_lens.astype(jnp.int32)
        pool, ck, cv = self._split_cache(cache)
        ssm, conv = state if state is not None else (None, None)
        paged = pool if pool is not None else ck
        npages = paged.shape[0] // max(p.n_paged, 1) \
            if paged is not None else 0
        # layer-independent: built once a step, shared by the layers
        plan = plan_decode_attention(ck, block_tables, seq_lens, npages) \
            if ck is not None else None
        counts = jnp.zeros((4,), jnp.int32)
        h = x
        for l, kind in enumerate(p.kinds()):
            li = p.kind_index(l)
            if kind == MAMBA:
                m = p.mamba
                hn = self._rms(h, w["m_norm"][li], p.epsilon) \
                    .astype(h.dtype)
                z, xbc, dt = self._mamba_split(
                    self._proj(hn, w["m_in"], li, stream))
                xbc, tail = causal_conv1d_step(
                    xbc.astype(h.dtype), conv[li], w["m_conv_w"][li],
                    w["m_conv_b"][li], active)
                conv = conv.at[li].set(tail)
                u = xbc[:, :m.d_inner].astype(jnp.float32)
                Bm = xbc[:, m.d_inner: m.d_inner + m.d_state]
                Cm = xbc[:, m.d_inner + m.d_state:]
                dt = jax.nn.softplus(dt + w["m_dt_bias"][li][None, :])
                dt = jnp.where(active[:, None], dt, 0.0)        # [S, H]
                A = -jnp.exp(w["m_A_log"][li])
                decay = expand_heads(jnp.exp(dt * A[None, :]), m.head_dim)
                dtx = expand_heads(dt, m.head_dim) * u
                ssm, y = ssm_decode_update(ssm, li, decay, dtx, Bm, Cm)
                y = y + expand_heads(w["m_D"][li], m.head_dim)[None] * u
                h = self._mamba_finish(w, li, h, y, z, stream)
            elif kind == LATENT:
                q, rows = self._latent_inputs(w, li, h, seq_lens, cos_t,
                                              sin_t, stream)
                o, pool = mla_decode_attend(
                    q, rows, pool, block_tables, seq_lens, li * npages,
                    v_width=p.latent.kv_lora_rank)
                h = self._latent_finish(w, li, h, o, stream)
            else:
                att = p.attention
                nq, nkv, hd = att.num_heads, att.num_kv_heads, att.head_dim
                hn = self._rms(h, w["a_norm"][li], p.epsilon) \
                    .astype(h.dtype)
                proj = self._proj(hn, w["qkv_weight"], li, stream)
                q, k, v = jnp.split(proj.reshape(S, nq + 2 * nkv, hd),
                                    [nq, nq + nkv], axis=1)
                # the paged decode kernels scale by 1/sqrt(head_dim):
                # fold the description's own scale into q before the cast
                q = q * (att.softmax_scale * hd ** 0.5)
                q = self._rope(q.astype(h.dtype), seq_lens, cos_t, sin_t)
                k = self._rope(k.astype(h.dtype), seq_lens, cos_t, sin_t)
                v = v.astype(h.dtype)
                o, ck, cv = decode_attend(plan, q, k, v, ck, cv, li)
                o = o.reshape(S, nq * hd).astype(h.dtype)
                h = self._residual(
                    h, self._proj(o, w["out_weight"], li, stream))
            h, counts = self._ffn(w, h, l, active, True, counts)
        state2 = RecurrentState(ssm, conv) if ssm is not None else None
        return h, self._join_cache(pool, ck, cv), state2, counts

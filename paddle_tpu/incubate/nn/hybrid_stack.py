"""A served stack BUILT from a ``LayerPattern``: a period of layer kinds
(Mamba-2 mixers, NoPE / rotary GQA attention, sliding-window GQA
attention, multi-head latent attention), each followed by an FFN of
routed gated experts plus a shared gated MLP (or, in the pattern's
``dense_lead`` layers, a dense gated MLP), RMSNorm, no biases, residual /
attention multipliers from the description.

    h = h + r * Mixer(RMSNorm(h))
    h = h + r * (MoE(x) + SharedMLP(x)),   x = RMSNorm(h)

A weight stack a kind, a cache group a kind:

``"mamba"``             ``m_*``; a slot-indexed ``RecurrentState`` that
    the decode program updates in place.
``"attention"``         ``qkv_weight`` / ``out_weight`` / ``a_norm``; the
    serving engine's paged pool ``PagedKV`` (layer-folded over
    ``n_attention`` layers, touched only by the three paged Pallas
    kernels ``FusedMultiTransformer`` uses), or, where V heads are
    narrower than K heads, ``PackedKV`` (touched only by
    ``pt_gqa_paged_prefill`` / ``pt_gqa_paged_decode``).
``"window_attention"``  ``w_qkv`` / ``w_out`` / ``w_norm`` / ``w_sink``
    (the per-head sink logits); the slot-indexed ``WindowRing`` beside the
    pool (``pt_swa_prefill`` / ``pt_swa_decode``), rotary from the second
    table the phases take (``rope_w``).
``"latent_attention"``  ``l_*`` (down / up projections of the queries,
    the latent + rope-key projection padded to the pool's row, the
    per-head ``W_uk`` / ``W_uv``: prefill expands a pool tile's K / V with
    them, decode absorbs them into its query and output); the
    paged latent pool ``LatentKV`` (one row a token and layer, touched
    only by ``pt_mla_paged_prefill`` / ``pt_mla_paged_decode``).

``f_norm`` is every layer's FFN norm; ``d_*`` the dense gated MLP of the
``dense_lead`` layers; ``f_router`` / ``e_*`` (and ``e_bias``, the
selection bias of sigmoid routing) / ``s_*`` the FFN of every other
layer, stacked over the expert layers alone.

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
from ...nn.functional.mixed_attention import PackedKV
from ...nn.functional.mla_attention import LatentKV
from .fused_transformer import PagedKV, _apply_rope
from .layer_pattern import (LATENT, MAMBA, SLOT_RECURRENT, SLOT_RING,
                            WINDOW, LayerPattern)

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
    def __init__(self, pattern: LayerPattern, dtype=jnp.float32,
                 draw: bool = True):
        """``draw=False``: every stack that would be drawn starts as zeros,
        for a caller that rebinds each parameter to weights of its own (no
        draw, and no draw program to compile, a stack)."""
        super().__init__()
        if pattern.moe is None or not pattern.gated \
                or pattern.norm != "rmsnorm" or pattern.bias:
            raise NotImplementedError(
                "HybridStack serves RMSNorm, bias-free, SiLU-gated "
                "routed-expert blocks; the LayerNorm / biased GELU "
                "one-kind pattern is FusedMultiTransformer's (kinds a "
                "period may mix here: mamba, attention, "
                "window_attention, latent_attention)")
        self.pattern = pattern
        self.embed_dim = pattern.d_model
        self.num_layers = pattern.num_layers
        self.epsilon = pattern.epsilon
        att = pattern.attention

        from ...core.generator import default_generator
        from ...core.tensor import Parameter

        dtype = jnp.dtype(dtype)

        def normal(*s, std=0.02, dt=dtype):
            if not draw:
                return jnp.zeros(s, dt)
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
            qkv = (att.num_heads + att.num_kv_heads) * att.head_dim \
                + att.num_kv_heads * att.v_dim
            add("a_norm", ones(La, d))
            add("qkv_weight", normal(La, d, qkv))
            add("out_weight", normal(La, att.num_heads * att.v_dim, d))
        Lw = pattern.n_window
        if Lw:
            sw = pattern.window_attention
            add("w_norm", ones(Lw, d))
            add("w_qkv", normal(Lw, d, (sw.num_heads + sw.num_kv_heads)
                                * sw.head_dim + sw.num_kv_heads * sw.v_dim))
            add("w_out", normal(Lw, sw.num_heads * sw.v_dim, d))
            add("w_sink", jnp.zeros((Lw, sw.num_heads), jnp.float32))
        moe = pattern.moe
        held = moe.held[1]
        Le, Ld = pattern.n_moe, pattern.dense_lead
        add("f_norm", ones(L, d))
        if Ld:
            add("d_w1", normal(Ld, d, 2 * pattern.d_ff))
            add("d_w2", normal(Ld, pattern.d_ff, d))
        add("f_router", normal(Le, d, moe.num_experts,
                               dt=jnp.dtype(jnp.float32)))
        if moe.routing == "sigmoid_bias":
            add("e_bias", jnp.zeros((Le, moe.num_experts), jnp.float32))
        add("e_w1", normal(Le, held, d, 2 * moe.expert_dim))
        add("e_w2", normal(Le, held, moe.expert_dim, d))
        if moe.shared_dim:
            add("s_w1", normal(Le, d, 2 * moe.shared_dim))
            add("s_w2", normal(Le, moe.shared_dim, d))

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

    def _ffn(self, w, h, l, rows, decode, counts, stream=False):
        """``h + r * (MoE(x) + SharedMLP(x))`` over flat rows ``[T, d]``
        and the layer's pick counts (prefill rows: and the grouped
        GEMMs' unit counts) added to ``counts``; in a ``dense_lead``
        layer ``h + r * DenseMLP(x)`` and nothing counted (``stream``:
        decode rows stream its matrices)."""
        from ...nn.functional.moe_gated import (
            gated_mlp, moe_gated_grouped, moe_gated_stream, pick_counts,
            route_topk_sigmoid, route_topk_softmax)

        p = self.pattern
        moe = p.moe
        x = self._rms(h, w["f_norm"][l], p.epsilon).astype(h.dtype)
        if l < p.dense_lead:
            f = p.d_ff
            ab = self._proj(x, w["d_w1"], l, stream)
            mid = (jax.nn.silu(ab[:, :f]) * ab[:, f:]).astype(h.dtype)
            return self._residual(h, self._proj(mid, w["d_w2"], l,
                                                stream)), counts
        l = p.moe_index(l)
        if moe.routing == "sigmoid_bias":
            gates, idx = route_topk_sigmoid(x, w["f_router"][l],
                                            w["e_bias"][l], moe.top_k,
                                            moe.scaling)
        else:
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

    def _latent_inputs(self, w, li, h, positions, cos_t, sin_t, stream, *,
                       absorbed):
        """Rows ``h [T, d]`` at ``positions [T]`` of latent layer ``li``
        -> ``(q, rows [T, W])``: the tokens' cache rows ``[normed latent |
        rotated rope key | 0]`` and their queries, scale and temperature
        folded in — ``absorbed`` (decode) ``[T, H, W]`` ``[q_nope W_uk^T |
        q_rope | 0]`` over the lanes of a cache row, else (prefill) the
        expanded ``[T, H, rope + nope]`` ``[q_rope | q_nope]``."""
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
        # absorbed: q_nope W_uk^T first, the row's zero lanes last
        lead = [jnp.einsum("thn,hnr->thr", qf[..., :n].astype(h.dtype),
                           w["l_uk"][li],
                           preferred_element_type=jnp.float32)] \
            if absorbed else []
        q_rope = rope_interleaved(qf[..., n:], cos[:, None], sin[:, None])
        temp = lt.softmax_scale * query_temperature(
            positions, lt.temperature_beta, lt.temperature_period)
        tail = jnp.broadcast_to(pad[:, None], (T, H, W - R - r)) \
            if absorbed else qf[..., :n]
        q = jnp.concatenate(lead + [q_rope, tail], -1) * temp[:, None, None]
        return q.astype(h.dtype), rows.astype(h.dtype)

    def _latent_finish(self, w, li, h, o, stream, *, absorbed):
        """``o [T, H, ...]`` float32 -> the residual stream: the attended
        latents (``absorbed``) through per-head ``W_uv`` first, attended
        values (expanded) straight to ``W_o``."""
        if absorbed:
            o = jnp.einsum("thr,hrv->thv", o.astype(h.dtype),
                           w["l_uv"][li], preferred_element_type=jnp.float32)
        att = o.reshape(h.shape[0], -1).astype(h.dtype)
        return self._residual(h, self._proj(att, w["l_o"], li, stream))

    @staticmethod
    def _split_cache(cache):
        """``(pool of rows | None, K side | None, V side | None)``."""
        if isinstance(cache, (LatentKV, PackedKV)):
            return cache.rows, None, None
        if cache is None:
            return None, None, None
        return None, cache.k, cache.v

    @staticmethod
    def _join_cache(pool, ck, cv, rows_cls=LatentKV):
        if pool is not None:
            return rows_cls(pool)
        return PagedKV(ck, cv) if ck is not None else None

    def _gqa_inputs(self, w, names, spec, li, h, positions, table, stream):
        """Rows ``h [T, d]`` at ``positions [T]`` of one layer of a GQA
        kind whose V heads may be narrower -> ``(q [T, H, dk]`` scaled,
        ``k [T, G, dk]``, ``v [T, G, dv])``: rotate-half rotary over each
        head's first ``rot_dim`` lanes from ``table = (cos, sin)``."""
        norm, qkv = names
        nq, nkv, hd, vd = (spec.num_heads, spec.num_kv_heads, spec.head_dim,
                           spec.v_dim)
        T = h.shape[0]
        hn = self._rms(h, w[norm][li], self.pattern.epsilon).astype(h.dtype)
        proj = self._proj(hn, w[qkv], li, stream)
        q = proj[:, :nq * hd].reshape(T, nq, hd)
        k = proj[:, nq * hd: (nq + nkv) * hd].reshape(T, nkv, hd)
        v = proj[:, (nq + nkv) * hd:].reshape(T, nkv, vd)
        if spec.rope_theta is not None:
            r = spec.rot_dim
            cos = table[0][positions][:, None, :]
            sin = table[1][positions][:, None, :]
            q = jnp.concatenate([_apply_rope(q[..., :r], cos, sin),
                                 q[..., r:]], -1)
            k = jnp.concatenate([_apply_rope(k[..., :r], cos, sin),
                                 k[..., r:]], -1)
        q = (q * spec.softmax_scale).astype(h.dtype)
        return q, k.astype(h.dtype), v.astype(h.dtype)

    def _gqa_finish(self, w, out_name, spec, li, h, o, stream):
        """``o [T, H, dv]`` float32 -> the residual stream, times the
        description's value scale (attention is linear in the values)."""
        att = (o * spec.value_scale).reshape(h.shape[0], -1).astype(h.dtype)
        return self._residual(h, self._proj(att, w[out_name], li, stream))

    # ------------------------------------------------------- prefill

    def prefill_chunk_raw(self, weights, x, cache, state, block_tables,
                          start, chunk_lens, cos_t=None, sin_t=None, *,
                          slot=None, rope_w=None):
        """x ``[1, c, d]``; ``cache`` the paged pool of the attention
        layers (``PagedKV`` / ``PackedKV``) or of the latent-attention
        layers (``LatentKV``); ``state`` this sequence's recurrent arrays
        (``ssm [Lm, N, d_inner]`` float32, ``conv [Lm, k-1, conv_dim]``)
        as they stood before the chunk, or the WHOLE ``WindowRing`` of the
        window layers with ``slot [1]`` the sequence's (``rope_w`` their
        rotary ``(cos, sin)``). Returns ``(hidden [1, c, d], cache',
        state', counts int32 [6])``."""
        from ...nn.functional.flash_varlen import paged_prefill_attention
        from ...nn.functional.mixed_attention import (
            gqa_prefill_attend, pack_rows, swa_prefill_attend)
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
        rows_cls = type(cache) if pool is not None else LatentKV
        ring = state if p.slot_state == SLOT_RING else None
        ssm, conv = state if p.slot_state == SLOT_RECURRENT \
            and state is not None \
            else (None, None)
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
                                              cos_t, sin_t, False,
                                              absorbed=False)
                o, pool = mla_prefill_attend(
                    q, rows, pool, block_tables + li * npages, start,
                    chunk_lens, w["l_uk"][li], w["l_uv"][li])
                h = self._latent_finish(w, li, h, o, False, absorbed=False)
            elif kind == WINDOW:
                sw = p.window_attention
                q, k, v = self._gqa_inputs(w, ("w_norm", "w_qkv"), sw, li, h,
                                           positions[0], rope_w, False)
                o, ring = swa_prefill_attend(
                    q, k, v, ring, li, slot, start, chunk_lens,
                    w["w_sink"][li] if sw.sink else None)
                h = self._gqa_finish(w, "w_out", sw, li, h, o, False)
            elif p.attention.packed:
                att = p.attention
                q, k, v = self._gqa_inputs(w, ("a_norm", "qkv_weight"), att,
                                           li, h, positions[0],
                                           (cos_t, sin_t), False)
                o, pool = gqa_prefill_attend(
                    q, pack_rows(k, v, att.row_width), pool,
                    block_tables + li * npages, start, chunk_lens,
                    n_kv=att.num_kv_heads, v_dim=att.v_dim)
                h = self._gqa_finish(w, "out_weight", att, li, h, o, False)
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
        state2 = (ssm, conv) if ssm is not None else ring
        return h[None], self._join_cache(pool, ck, cv, rows_cls), state2, \
            counts

    # -------------------------------------------------------- decode

    def decode_raw(self, weights, x, cache, state, block_tables,
                   seq_lens, active, cos_t=None, sin_t=None, *, rope_w=None):
        """One decode step for every slot: x ``[slots, d]``, ``seq_lens``
        the tokens already cached, ``active [slots]`` bool. ``state`` is
        the WHOLE ``RecurrentState`` (its ssm array is updated in place
        by ``pt_ssm_decode_update``) or ``WindowRing`` (its rows by
        ``pt_swa_decode``). Returns ``(hidden, cache', state', counts
        int32 [4])``."""
        from ...device import chip as _chip
        from ...nn.functional.mixed_attention import (gqa_decode_attend,
                                                      swa_decode_attend)
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
        rows_cls = type(cache) if pool is not None else LatentKV
        ring = state if p.slot_state == SLOT_RING else None
        ssm, conv = state if p.slot_state == SLOT_RECURRENT \
            and state is not None \
            else (None, None)
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
                                              sin_t, stream, absorbed=True)
                o, pool = mla_decode_attend(
                    q, rows, pool, block_tables, seq_lens, li * npages,
                    v_width=p.latent.kv_lora_rank)
                h = self._latent_finish(w, li, h, o, stream, absorbed=True)
            elif kind == WINDOW:
                sw = p.window_attention
                q, k, v = self._gqa_inputs(w, ("w_norm", "w_qkv"), sw, li, h,
                                           seq_lens, rope_w, stream)
                o, ring = swa_decode_attend(
                    q, k, v, ring, li, seq_lens, active,
                    w["w_sink"][li] if sw.sink else None)
                h = self._gqa_finish(w, "w_out", sw, li, h, o, stream)
            elif p.attention.packed:
                att = p.attention
                q, k, v = self._gqa_inputs(w, ("a_norm", "qkv_weight"), att,
                                           li, h, seq_lens, (cos_t, sin_t),
                                           stream)
                o, pool = gqa_decode_attend(q, k, v, pool, block_tables,
                                            seq_lens, li * npages)
                h = self._gqa_finish(w, "out_weight", att, li, h, o, stream)
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
            h, counts = self._ffn(w, h, l, active, True, counts, stream)
        state2 = RecurrentState(ssm, conv) if ssm is not None else ring
        return h, self._join_cache(pool, ck, cv, rows_cls), state2, counts

"""Fused transformer decode stack — the LLM-serving compute path.

TPU-native equivalent of the reference's fused inference ops:
  - paddle/fluid/operators/fused/fused_multi_transformer_op.cu — a whole
    pre-LN transformer stack with KV cache as ONE op;
  - the fork's flagship fused ops qkv_split_rope_fused_op /
    kv_split_fused_op (reference ops.yaml:8-25) — fused QKV projection,
    head split and rotary embedding.

The TPU-first design differs deliberately from the CUDA one: instead of a
hand-scheduled megakernel, layer weights are **stacked along a leading
layer axis and the stack is a single `lax.scan`** — XLA compiles one
layer body, fuses LN + bias + residual + activation into the matmuls
(MXU), and reuses it L times; the paged-KV attention inside is the Pallas
kernel from ``nn.functional.paged_attention``. One compiled program per
(batch, phase) — no per-layer dispatch, no concat-growing cache.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ...core.tensor import Tensor
from ...nn.layer_base import Layer
from ...nn.functional.paged_attention import (
    decode_attend, plan_decode_attention, write_prefill_kv_pages)

__all__ = ["qkv_split_rope_fused", "rope_table", "FusedMultiTransformer"]


def rope_table(max_pos: int, head_dim: int, theta: float = 10000.0):
    """Precomputed rotary cos/sin, [max_pos, head_dim//2] each."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2,
                                      dtype=jnp.float32) / head_dim))
    ang = jnp.arange(max_pos, dtype=jnp.float32)[:, None] * inv[None, :]
    return jnp.cos(ang), jnp.sin(ang)


def _apply_rope(x, cos, sin):
    """x: [..., head_dim]; cos/sin broadcastable [..., head_dim//2].
    Half-rotation (GPT-NeoX) convention."""
    d2 = x.shape[-1] // 2
    x1, x2 = x[..., :d2], x[..., d2:]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _split_rope(proj, positions, num_heads, num_kv_heads, head_dim,
                cos_table, sin_table):
    """Head split + rotary embedding over a computed QKV projection."""
    lead = proj.shape[:-1]
    nq, nkv = num_heads, num_kv_heads
    q, k, v = jnp.split(
        proj.reshape(*lead, (nq + 2 * nkv), head_dim), [nq, nq + nkv],
        axis=-2)
    cos = cos_table[positions][..., None, :]   # [.., 1, hd/2]
    sin = sin_table[positions][..., None, :]
    return _apply_rope(q, cos, sin), _apply_rope(k, cos, sin), v


def qkv_split_rope_fused(x, qkv_w, qkv_b, positions, num_heads,
                         num_kv_heads, head_dim, cos_table, sin_table):
    """Fused QKV projection + head split + rotary embedding.

    Raw-array op equivalent of the fork's qkv_split_rope_fused_op
    (reference ops.yaml:8; CUDA kernel
    phi/kernels/gpu/qkv_split_rope_fused_op_kernel.cu). x may be
    [b, d_model] (decode) or [b, s, d_model] (prefill); positions
    matches x's token dims. Returns q [.., n_q, hd], k/v [.., n_kv, hd].
    """
    proj = x @ qkv_w
    if qkv_b is not None:
        proj = proj + qkv_b
    return _split_rope(proj, positions, num_heads, num_kv_heads,
                       head_dim, cos_table, sin_table)


class PagedKV(NamedTuple):
    """Layer-folded PAGE-MAJOR paged KV pool (the decode-loop carry).

    Layers are FOLDED into the page dimension — layer ``l``'s logical
    page ``p`` lives at physical page ``l * num_pages + p`` — so one
    decode step updates the pool **in place** (XLA aliases loop-carry
    buffers; the scatter writes only the new token's rows). The round-3
    layout ([L, n_kv, pages, ...] shuttled through scan xs→ys) copied
    the whole pool every token: 10.8ms/step of pure copy on the 1.3B
    config vs 0.7ms for this carry design (r4 chip record, before PR 1;
    not measured on today's code). Page-major ([P, n_kv, ps, d], heads
    outer within the page — r5) makes each page one contiguous block
    whose per-head slices are contiguous too: the scatter's indexed page
    dim leads and the decode kernel consumes whole [C, d] head
    runs with zero relayout.
    """
    k: jax.Array   # [num_layers * num_pages, n_kv, page_size, head_dim]
    v: jax.Array


class FusedMultiTransformer(Layer):
    """Pre-LN GPT-style transformer stack with paged-KV incremental decode.

    API parity target: paddle.incubate.nn.FusedMultiTransformer
    (reference python/paddle/incubate/nn/layer/fused_transformer.py,
    backed by fused_multi_transformer_op.cu). Weights are stacked
    [num_layers, ...] Parameters, executed as one lax.scan.
    """

    def __init__(self, embed_dim, num_heads, dim_feedforward, num_layers,
                 num_kv_heads=None, activation="gelu", epsilon=1e-5,
                 rope_theta=10000.0, max_position=32768, dtype=None,
                 moe_num_experts=None, moe_top_k=2):
        super().__init__()
        self.embed_dim = embed_dim
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads or num_heads
        self.head_dim = embed_dim // num_heads
        self.dim_feedforward = dim_feedforward
        self.num_layers = num_layers
        self.activation = activation
        self.epsilon = epsilon
        self.rope_theta = rope_theta
        self.max_position = max_position
        # MoE serving stack (ISSUE 15): moe_num_experts replaces the
        # dense FFN with a per-layer expert bank routed through the
        # no-drop ragged grouped-GEMM FFN (nn/functional/grouped_gemm)
        # — and, under an ep-axis TPContext, the expert-parallel
        # all-to-all exchange with the bank sharded 1/ep per chip.
        self.moe_num_experts = moe_num_experts
        self.moe_top_k = moe_top_k

        L, d, dff = num_layers, embed_dim, dim_feedforward
        qkv_out = (self.num_heads + 2 * self.num_kv_heads) * self.head_dim
        ones = lambda *s: jnp.ones(s, jnp.float32)  # noqa: E731
        zeros = lambda *s: jnp.zeros(s, jnp.float32)  # noqa: E731

        def normal(*s):
            from ...core.generator import default_generator

            return jax.random.normal(default_generator().next_key(), s,
                                     jnp.float32) * 0.02

        self.ln1_scale = self._mk(ones(L, d))
        self.ln1_bias = self._mk(zeros(L, d))
        self.qkv_weight = self._mk(normal(L, d, qkv_out))
        self.qkv_bias = self._mk(zeros(L, qkv_out))
        self.out_weight = self._mk(
            normal(L, self.num_heads * self.head_dim, d))
        self.out_bias = self._mk(zeros(L, d))
        self.ln2_scale = self._mk(ones(L, d))
        self.ln2_bias = self._mk(zeros(L, d))
        if moe_num_experts:
            E = int(moe_num_experts)
            self.gate_weight = self._mk(normal(L, d, E))
            self.moe_w1 = self._mk(normal(L, E, d, dff))
            self.moe_b1 = self._mk(zeros(L, E, dff))
            self.moe_w2 = self._mk(normal(L, E, dff, d))
            self.moe_b2 = self._mk(zeros(L, E, d))
        else:
            self.ffn1_weight = self._mk(normal(L, d, dff))
            self.ffn1_bias = self._mk(zeros(L, dff))
            self.ffn2_weight = self._mk(normal(L, dff, d))
            self.ffn2_bias = self._mk(zeros(L, d))

    def _mk(self, arr):
        from ...core.tensor import Parameter

        return Parameter(arr)

    @property
    def pattern(self):
        """This stack as a ``LayerPattern``: the one-kind pattern (every
        layer rotary GQA attention with pages, a LayerNorm'd biased GELU
        FFN or the 8-expert synthetic MoE) — what the serving engines
        size their cache groups from. ``HybridStack`` is BUILT from a
        pattern; this class only reports one."""
        from .layer_pattern import LayerPattern, MoESpec

        moe = None
        if self.moe_num_experts:
            moe = MoESpec(int(self.moe_num_experts), self.moe_top_k,
                          self.dim_feedforward)
        return LayerPattern.uniform_attention(
            self.embed_dim, self.num_layers, self.num_heads,
            self.num_kv_heads, self.head_dim, self.dim_feedforward,
            rope_theta=self.rope_theta, epsilon=self.epsilon,
            activation=self.activation, moe=moe)

    # ---------- functional core (raw arrays; jit-able) ----------

    def _stack(self):
        names = ["ln1_scale", "ln1_bias", "qkv_weight", "qkv_bias",
                 "out_weight", "out_bias", "ln2_scale", "ln2_bias"]
        if self.moe_num_experts:
            names += ["gate_weight", "moe_w1", "moe_b1", "moe_w2",
                      "moe_b2"]
        else:
            names += ["ffn1_weight", "ffn1_bias", "ffn2_weight",
                      "ffn2_bias"]
        out = {n: getattr(self, n)._data for n in names}
        for n in ("qkv", "out", "ffn1", "ffn2"):
            s = getattr(self, f"{n}_scale_woq", None)
            if s is not None:
                out[f"{n}_scale"] = s._data
        return out

    def quantize_weight_only_int8(self):
        """In-place weight-only int8 quantization of the four matmul
        stacks (serving counterpart of the reference's
        weight_only_linear / weight_quantize ops, ops.yaml): symmetric
        per-output-channel scales; biases/LN stay full precision. The
        decode program applies scales on matmul OUTPUTS so weight HBM
        reads halve (see ``_mm``)."""
        if self.moe_num_experts:
            raise NotImplementedError(
                "int8 weight-only quantization of the MoE expert bank "
                "is not supported yet — serve MoE stacks in bf16/f32")
        from ...core.tensor import Parameter

        for n in ("qkv", "out", "ffn1", "ffn2"):
            p = getattr(self, f"{n}_weight")
            w = p._data.astype(jnp.float32)
            scale = jnp.max(jnp.abs(w), axis=1, keepdims=True) / 127.0
            scale = jnp.maximum(scale, 1e-8)          # [L, 1, out]
            q = jnp.clip(jnp.round(w / scale), -127, 127) \
                .astype(jnp.int8)
            p._rebind(q)
            setattr(self, f"{n}_scale_woq",
                    Parameter(scale[:, 0, :]))        # [L, out]
        return self

    def _act(self, x):
        return (jax.nn.gelu(x) if self.activation == "gelu"
                else jax.nn.relu(x))

    @staticmethod
    def _ln(x, scale, bias, eps):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * scale + bias

    @staticmethod
    def _mm(x, w, scale):
        """x @ w, honoring int8 weight-only quantization: with
        per-OUTPUT-channel scales, dequant commutes with the matmul —
        ``(x @ w_q) * scale`` — so the int8→bf16 convert fuses into the
        dot's weight read and HBM weight traffic halves (the decode
        path is weight-bandwidth bound; reference comparator:
        weight_only_linear, phi/kernels/fusion/gpu/)."""
        if w.dtype == jnp.int8:
            return (x @ w.astype(x.dtype)) * scale.astype(x.dtype)
        return x @ w

    @staticmethod
    def _mm_a8w8(x, w_q, scale):
        """A8W8 matmul: per-token dynamic activation quant into an
        int8 x int8 dot with int32 accumulation, dequantized once by
        ``act_scale (x) weight_scale`` (the reference's
        fused_multi_transformer_int8 quantize/GEMM/dequant round).
        Returns f32 — call sites cast back to the compute dtype."""
        from ...quantization.dynamic import (dynamic_act_quant,
                                             int8_dot_dequant)

        xq, xs = dynamic_act_quant(x)
        return int8_dot_dequant(xq, xs, w_q, scale)

    def _moe_ffn(self, w, hn, ep_axis=None, ep_size=1):
        """The MoE FFN of one layer over normalized hidden ``hn`` (any
        leading dims): flatten to tokens, route through the no-drop
        ragged grouped-GEMM FFN — or, inside an ep shard_map body, the
        expert-parallel all-to-all exchange against this shard's 1/ep
        expert slice (``nn/functional/grouped_gemm.moe_ffn_ep``)."""
        from ...core.flags import flag
        from ...nn.functional.grouped_gemm import (moe_ffn_ep,
                                                   moe_ffn_nodrop)

        lead = hn.shape[:-1]
        x2 = hn.reshape(-1, self.embed_dim)
        if ep_axis is not None:
            y = moe_ffn_ep(
                x2, w["gate_weight"], w["moe_w1"], w["moe_b1"],
                w["moe_w2"], w["moe_b2"], top_k=self.moe_top_k,
                axis=ep_axis, ep=ep_size, activation=self.activation)
        else:
            y, _probs, _idx, _cnt = moe_ffn_nodrop(
                x2, w["gate_weight"], w["moe_w1"], w["moe_b1"],
                w["moe_w2"], w["moe_b2"], top_k=self.moe_top_k,
                activation=self.activation,
                backend=flag("moe_grouped_backend"))
        return y.reshape(*lead, self.embed_dim)

    @staticmethod
    def _lora_delta_fn(adapters):
        """Per-projection LoRA delta closure over ONE layer's adapter
        view (``{proj}_a [S, K, R]`` / ``{proj}_b`` banks plus the
        chunk's shared ``order``/``inv``/``offsets`` from
        ``sort_by_adapter``). Returns f32 ``[.., N]`` or None when the
        projection has no adapter target — base-model tokens sorted
        past ``offsets[-1]`` get exact-zero rows from the work map."""
        from ...core.flags import flag
        from ...nn.functional.lora import lora_delta

        backend = flag("lora_delta_backend")

        def delta(x, kind):
            a = adapters.get(f"{kind}_a")
            if a is None:
                return None
            b = adapters[f"{kind}_b"]
            x2 = x.reshape(-1, x.shape[-1])
            xs = jnp.take(x2, adapters["order"], axis=0)
            d = lora_delta(xs, a, b, adapters["offsets"],
                           backend=backend)
            d = jnp.take(d, adapters["inv"], axis=0)
            return d.reshape(*x.shape[:-1], d.shape[-1])

        return delta

    def _layer_body(self, w, h, positions, kv_write, attend, cos_t,
                    sin_t, linear=None, a8w8=False, psum_axis=None,
                    ep_axis=None, ep_size=1, adapters=None,
                    overlap=None):
        """One pre-LN transformer layer over hidden ``h`` (any leading
        dims). Compute dtype FOLLOWS h (bf16 weights + bf16 h → pure
        bf16 MXU dots; LN statistics promote to fp32 internally and are
        cast back). ``attend`` may return (att, ck, cv) — the fused
        append+attend kernel path, where kv_write is skipped.
        ``linear(x, kind)`` computes x @ W_kind + bias (int8 scales
        applied) — the decode loop overrides it with the weight-
        streaming kernel over UNSLICED stacked weights.

        ``psum_axis``: tensor-parallel shard body (inside shard_map) —
        the row-parallel O-proj and FFN2 partial sums meet in one
        ``psum`` per projection pair BEFORE the (replicated) bias adds,
        the two per-layer allreduce points of the reference
        (fused_multi_transformer_op.cu:220,529). Per-output-channel
        int8 scales commute with the sum, so dequant stays per-shard."""
        eps = self.epsilon
        if adapters is not None and linear is not None:
            raise ValueError(
                "_layer_body: adapters compose with the default linear "
                "only (the decode loop has its own adaptered branch)")
        if linear is None:
            if a8w8:
                def raw(x, kind):
                    return self._mm_a8w8(x, w[f"{kind}_weight"],
                                         w[f"{kind}_scale"])
            else:
                def raw(x, kind):
                    return self._mm(x, w[f"{kind}_weight"],
                                    w.get(f"{kind}_scale"))

            lora = None if adapters is None \
                else self._lora_delta_fn(adapters)

            def linear(x, kind):
                y = raw(x, kind)
                if lora is not None:
                    # the delta joins the per-shard partial BEFORE the
                    # row-parallel psum (x·A = Σ_shards x_s·A_s), so TP
                    # keeps exactly its two collectives per layer
                    d = lora(x, kind)
                    if d is not None:
                        y = y + d
                if psum_axis is not None and kind in ("out", "ffn2"):
                    from ...distributed.tp import reduce_over_axis
                    y = reduce_over_axis(y, psum_axis,
                                         overlap or "psum")
                return y + w[f"{kind}_bias"]
        hn = self._ln(h, w["ln1_scale"], w["ln1_bias"], eps) \
            .astype(h.dtype)
        proj = linear(hn, "qkv")
        q, k, v = _split_rope(proj.astype(h.dtype), positions,
                              self.num_heads, self.num_kv_heads,
                              self.head_dim, cos_t, sin_t)
        if kv_write is None:
            att, ck, cv = attend(q, k, v, None, None)
        else:
            ck, cv = kv_write(k, v)
            att = attend(q, k, v, ck, cv)
        att = att.reshape(*h.shape[:-1],
                          self.num_heads * self.head_dim).astype(h.dtype)
        h = (h + linear(att, "out")).astype(h.dtype)
        hn = self._ln(h, w["ln2_scale"], w["ln2_bias"], eps) \
            .astype(h.dtype)
        if self.moe_num_experts:
            h = (h + self._moe_ffn(w, hn, ep_axis, ep_size)) \
                .astype(h.dtype)
            return h, ck, cv
        ff = self._act(linear(hn, "ffn1").astype(h.dtype))
        h = (h + linear(ff, "ffn2")).astype(h.dtype)
        return h, ck, cv

    @staticmethod
    def _pool_data(side):
        """Raw page array of a cache side (quantized sides are
        (int8_rows, f32_scale_plane) tuples)."""
        return side[0] if isinstance(side, tuple) else side

    def _pages_per_layer(self, cache: PagedKV) -> int:
        return self._pool_data(cache.k).shape[0] // self.num_layers

    # ---------- tensor parallelism (mp mesh axis) ----------

    def _tp_view(self, tp) -> "FusedMultiTransformer":
        """Per-shard view for the shard_map body: the same stack config
        with PER-SHARD head counts (query heads partition with the QKV
        columns; kv heads shard — or replicate one head per shard in
        the GQA fallback). No parameters are attached: the raw methods
        only read config attrs and the weights they are handed."""
        v = object.__new__(FusedMultiTransformer)
        for n in ("embed_dim", "head_dim", "dim_feedforward",
                  "num_layers", "activation", "epsilon", "rope_theta",
                  "max_position", "moe_num_experts", "moe_top_k"):
            object.__setattr__(v, n, getattr(self, n))
        object.__setattr__(v, "num_heads", tp.heads_per_shard)
        object.__setattr__(v, "num_kv_heads", tp.kv_heads_per_shard)
        return v

    def _tp_wrap(self, tp, method: str, weights, x, cache, tables,
                 rep_args, cos_t, sin_t, a8w8, adapters=None,
                 overlap=None):
        """shard_map a raw phase over the ``mp`` and/or ``ep`` mesh
        axes: weights enter pre-sharded (TPContext.shard_stack specs —
        column/row slices over ``mp``, the MoE expert bank 1/ep over
        ``ep``), the KV pool sharded by kv-head (``mp``) or replicated
        (ep-only), everything else — hidden state, block tables,
        seq_lens/positions, rope tables — replicated. The body is the
        SAME raw method on the per-shard view with ``psum_axis`` set
        when mp > 1 (each column→row projection pair contributes
        exactly one psum) and ``ep_axis`` set when ep > 1 (each MoE
        layer contributes exactly the all_to_all dispatch/combine pair
        plus the replicated-hidden all_gather)."""
        from ...distributed.tp import resolve_overlap

        overlap = resolve_overlap(overlap)
        if cache is None:
            raise ValueError(
                "tensor-parallel prefill needs a paged cache (the "
                "dense training/eval path is single-chip)")
        if isinstance(cache.k, tuple):
            raise NotImplementedError(
                "int8 cache-KV is not supported under tensor "
                "parallelism yet — serve TP with a bf16/f32 pool")
        if self.moe_num_experts and tp.mp > 1:
            raise NotImplementedError(
                "MoE serving composes with expert parallelism "
                "(ep_degree) — tensor-parallel (mp) sharding of the "
                "attention stack around an MoE FFN is not wired yet")
        view = self._tp_view(tp)
        rep = tp.pspec()
        wspecs = {n: tp.stack_spec(n) for n in weights}
        kv = tp.kv_spec()
        psum_axis = tp.axis if tp.mp > 1 else None
        ep_axis = tp.ep_axis if tp.ep > 1 else None
        adaptered = adapters is not None
        aspecs = None
        if adaptered:
            # adapter banks shard alongside the base stacks
            # (_ADAPTER_LAYOUT): B column-split for col-parallel
            # projections, A row-split for row-parallel ones, the
            # per-token slot ids replicated
            aspecs = {n: (rep if n == "slots" else tp.adapter_spec(n))
                      for n in adapters}

        def body(w, xb, ck, cv, tbl, cos, sin, *extras):
            kw = dict(a8w8=a8w8, psum_axis=psum_axis, ep_axis=ep_axis,
                      ep_size=tp.ep, overlap=overlap)
            if adaptered:
                kw["adapters"] = extras[-1]
                extras = extras[:-1]
            h, cache2 = getattr(view, method)(
                w, xb, PagedKV(ck, cv), tbl, *extras, cos, sin, **kw)
            return h, cache2.k, cache2.v

        fn = jax.shard_map(
            body, mesh=tp.mesh,
            in_specs=(wspecs, rep, kv, kv, rep, rep, rep)
            + (rep,) * len(rep_args)
            + ((aspecs,) if adaptered else ()),
            out_specs=(rep, kv, kv), check_vma=False)
        h, nk, nv = fn(weights, x, cache.k, cache.v, tables,
                       cos_t, sin_t, *rep_args,
                       *((adapters,) if adaptered else ()))
        return h, PagedKV(nk, nv)

    def prefill_raw(self, weights, x, cache, block_tables, cos_t, sin_t,
                    a8w8=False, tp=None, psum_axis=None,
                    ep_axis=None, ep_size=1, overlap=None):
        """Prompt pass: x [b, s, d] → (hidden [b, s, d], filled cache).

        Causal dense attention (flash-fusable by XLA/Pallas); each
        layer's K/V written into its layer-offset pages of the folded
        pool. ``cache=None`` runs the pure dense forward (training/eval
        parity path) with no KV writes. Ragged batches are NOT masked
        here — pad prompts to a common length (dense attention over
        padding is causal-safe for the suffix tokens actually decoded).
        ``a8w8``: run the four matmuls with per-token dynamic int8
        activations against the int8 weight stack (``_mm_a8w8``).

        ``tp``: a distributed.tp.TPContext — shard the whole pass over
        the ``mp`` mesh axis (weights from TPContext.shard_stack, pool
        kv-head-sharded). ``psum_axis`` is the internal per-shard form
        (set by the shard_map wrapper, not callers).
        """
        if a8w8 and weights["qkv_weight"].dtype != jnp.int8:
            raise ValueError("a8w8 prefill needs an int8 weight stack "
                             "(quantize_weight_only_int8 first)")
        if tp is not None:
            return self._tp_wrap(tp, "prefill_raw", weights, x, cache,
                                 block_tables, (), cos_t, sin_t, a8w8,
                                 overlap=overlap)
        b, s, d = x.shape
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
        group = self.num_heads // self.num_kv_heads

        def attend(q, k, v, ck, cv):
            kq = jnp.repeat(k, group, axis=-2)
            vq = jnp.repeat(v, group, axis=-2)
            return jax.nn.dot_product_attention(
                q, kq, vq, is_causal=True, scale=self.head_dim ** -0.5)

        if cache is None:
            def body(h, w):
                h, _, _ = self._layer_body(
                    w, h, positions, lambda k, v: (None, None), attend,
                    cos_t, sin_t, a8w8=a8w8, psum_axis=psum_axis,
                    ep_axis=ep_axis, ep_size=ep_size, overlap=overlap)
                return h, None

            h, _ = jax.lax.scan(body, x, weights)
            return h, None

        npages = self._pages_per_layer(cache)

        def body(l, carry):
            h, ck, cv = carry
            w = {n: jax.lax.dynamic_index_in_dim(a, l, 0, False)
                 for n, a in weights.items()}
            tbl = block_tables + l * npages
            h, ck, cv = self._layer_body(
                w, h, positions,
                lambda k, v: write_prefill_kv_pages(ck, cv, k, v, tbl),
                attend, cos_t, sin_t, a8w8=a8w8, psum_axis=psum_axis,
                ep_axis=ep_axis, ep_size=ep_size, overlap=overlap)
            return h, ck, cv

        h, nk, nv = jax.lax.fori_loop(
            0, self.num_layers, body, (x, cache.k, cache.v))
        return h, PagedKV(nk, nv)

    def prefill_chunk_raw(self, weights, x, cache, block_tables, start,
                          chunk_lens, cos_t, sin_t, a8w8=False,
                          tp=None, psum_axis=None, ep_axis=None,
                          ep_size=1, adapters=None, overlap=None):
        """CHUNKED prompt pass: x [b, c, d] embeds tokens at positions
        ``start[b] .. start[b]+c-1`` of sequences whose earlier tokens
        (previous chunks, or a shared prefix mapped by the prefix
        cache) are ALREADY in the paged pool. Queries attend to the
        cached pages plus causally within the chunk, so a long prompt
        prefills in fixed-size chunks interleaved with decode steps
        (the serving scheduler's stall bound) instead of one monolithic
        program that blocks the decode batch.

        ``start``/``chunk_lens``: [b] int32 traced arrays — position
        offset (ANY value: the scheduler's chunks start on page
        boundaries, the speculative verify pass at ``seq_len``) and
        VALID row count (rows ``>= chunk_lens[b]`` are right-padding:
        their K/V never reach a live page and their hidden rows are
        garbage the caller discards). Returns (hidden [b, c, d],
        cache').

        Who writes the pool: inside the layer loop a bf16/f32 pool is
        touched ONLY by Pallas calls — ``write_prefill_kv_inplace``
        (``pt_paged_kv_write``, both sides aliased) writes the chunk,
        ``paged_prefill_attention`` reads the pages in place — so the
        loop-carried pool keeps the default layout from entry to exit
        and is never copied. An XLA scatter in this loop would pin it
        to another layout and cost two whole-pool copies a layer (the
        note on ``paged_decode_attention_inplace``; 416 of a chunk's
        424 ms on the 1.3B cell before ISSUE 29). The int8-quantized
        pool keeps the scatter (``write_prefill_kv_pages``): its attend
        is the dequantizing XLA gather, no Pallas call sits beside it.
        """
        if a8w8 and weights["qkv_weight"].dtype != jnp.int8:
            raise ValueError("a8w8 prefill needs an int8 weight stack "
                             "(quantize_weight_only_int8 first)")
        if tp is not None:
            return self._tp_wrap(tp, "prefill_chunk_raw", weights, x,
                                 cache, block_tables,
                                 (start, chunk_lens), cos_t, sin_t,
                                 a8w8, adapters=adapters,
                                 overlap=overlap)
        from ...core.flags import flag
        from ...nn.functional.flash_varlen import paged_prefill_attention
        from ...nn.functional.paged_attention import (
            gather_kv_pages, write_prefill_kv_inplace,
            write_prefill_kv_pages)

        b, c, _d = x.shape
        start = start.astype(jnp.int32)
        chunk_lens = chunk_lens.astype(jnp.int32)
        positions = start[:, None] \
            + jnp.arange(c, dtype=jnp.int32)[None, :]      # [b, c]
        n_kv = self.num_kv_heads
        group = self.num_heads // n_kv
        hd = self.head_dim
        npages = self._pages_per_layer(cache)
        scale = hd ** -0.5
        # int8-quantized pools keep the dequantizing gather path; bf16/
        # f32 pools route through the varlen kernel, which reads the
        # pages IN PLACE (no per-chunk dense gather copy)
        quant_pool = isinstance(cache.k, tuple)
        use_varlen = (flag("prefill_attention_backend") != "gather"
                      and not quant_pool)

        ad_base = None
        if adapters is not None:
            from ...nn.functional.lora import (
                inverse_order, sort_by_adapter)
            # per-row slots broadcast to per-token and sorted ONCE for
            # the whole chunk; the layer loop slices the banks at l.
            # Padding rows inherit their row's slot — their deltas are
            # garbage the caller already discards.
            S_ad = adapters["qkv_a"].shape[1]
            slots_tok = jnp.repeat(
                adapters["slots"].astype(jnp.int32), c)
            order, offsets, _ = sort_by_adapter(slots_tok, S_ad)
            ad_base = {"order": order, "inv": inverse_order(order),
                       "offsets": offsets}

        def body(l, carry):
            h, ck, cv = carry
            w = {n: jax.lax.dynamic_index_in_dim(a, l, 0, False)
                 for n, a in weights.items()}
            tbl = block_tables + l * npages

            def kv_write(k, v):
                if quant_pool:
                    return write_prefill_kv_pages(
                        ck, cv, k, v, tbl, start=start,
                        valid_lens=chunk_lens)
                return write_prefill_kv_inplace(
                    ck, cv, k, v, tbl, start, chunk_lens)

            def attend(q, k, v, nck, ncv):
                # the sequence's whole cached span (the chunk's own KV
                # was just written): key position <= query position
                # covers both the prefix pages and the in-chunk
                # triangle
                if use_varlen:
                    fb = flag("prefill_attention_backend")
                    return paged_prefill_attention(
                        q, nck, ncv, tbl, start, n_kv=n_kv,
                        scale=scale,
                        backend="auto" if fb in ("auto", "varlen")
                        else fb)
                kg = gather_kv_pages(nck, tbl)
                vg = gather_kv_pages(ncv, tbl)
                S = kg.shape[1]
                qh = q.reshape(b, c, n_kv, group, hd)
                # fp32 scores by design (softmax stability; KV-bound)
                # tpu-lint: ok(X-PROMOTE) -- attention scores fp32 by design
                logits = jnp.einsum(
                    "btngd,bsnd->bngts",
                    qh.astype(jnp.float32) * scale,
                    kg.astype(jnp.float32))
                mask = jnp.arange(S, dtype=jnp.int32)[None, None, :] \
                    <= positions[:, :, None]               # [b, t, s]
                logits = jnp.where(mask[:, None, None], logits,
                                   jnp.finfo(jnp.float32).min)
                wts = jax.nn.softmax(logits, axis=-1)
                # tpu-lint: ok(X-PROMOTE) -- fp32 PV accumulation pairs with scores
                out = jnp.einsum("bngts,bsnd->btngd", wts,
                                 vg.astype(jnp.float32))
                return out.reshape(b, c, n_kv * group, hd) \
                    .astype(q.dtype)

            ad = None
            if ad_base is not None:
                ad = dict(ad_base)
                for n, a in adapters.items():
                    if n.endswith("_a") or n.endswith("_b"):
                        ad[n] = jax.lax.dynamic_index_in_dim(
                            a, l, 0, False)
            h, ck, cv = self._layer_body(
                w, h, positions, kv_write, attend, cos_t, sin_t,
                a8w8=a8w8, psum_axis=psum_axis, ep_axis=ep_axis,
                ep_size=ep_size, adapters=ad, overlap=overlap)
            return h, ck, cv

        h, nk, nv = jax.lax.fori_loop(
            0, self.num_layers, body, (x, cache.k, cache.v))
        return h, PagedKV(nk, nv)

    def decode_loop(self, a8w8=False, adapters=None) -> str:
        """Which layer loop ``decode_raw`` runs, decided from the stack
        and the call and nothing else: ``"adaptered"`` with LoRA banks;
        ``"layerwise"`` for MoE stacks (the FFN is the routed expert
        bank, not the fused dense tail) and for A8W8 (the grouped tail
        streams weight-only math and would forgo the int8 x int8 MXU
        dots); ``"grouped"`` for every other dense stack — bf16, f32,
        weight-only int8, one chip or a tensor-parallel shard."""
        if adapters is not None:
            return "adaptered"
        if self.moe_num_experts or a8w8:
            return "layerwise"
        return "grouped"

    def decode_raw(self, weights, x, cache: PagedKV, block_tables,
                   seq_lens, cos_t, sin_t, a8w8=False, tp=None,
                   psum_axis=None, ep_axis=None, ep_size=1,
                   adapters=None, overlap=None):
        """One decode step: x [b, d] token embeddings, seq_lens [b] =
        tokens already cached (the new token's position). Returns
        (hidden [b, d], cache').

        ``weights`` is the stacked dict: the layer loop is a
        ``fori_loop`` whose streamed matmuls read layer l's block of the
        UNSLICED ``[L, K, N]`` stacks through a prefetched index (a
        dynamic-slice operand to a kernel's custom call would copy
        ~100 MB a layer), and the pool is carried through the loop and
        touched only by ``decode_attend`` — never copied. Which loop
        runs is ``decode_loop``'s answer; the attention kernel is
        ``plan_decode_attention``'s, made once here for all layers.

        ``a8w8``: activations dynamically quantized per token into the
        int8 x int8 streamed matmuls (stream_linear act_quant path) —
        requires the int8 weight stack.

        TENSOR PARALLELISM (``tp``, a distributed.tp.TPContext): the
        whole step runs under shard_map over the ``mp`` mesh axis —
        per-shard query/kv heads, a kv-head-sharded pool, and each
        column→row projection pair meeting in exactly one ``psum``
        (two per layer: after the row-parallel O-proj and FFN2, the
        reference's fused_multi_transformer_op.cu:220,529 ring_id
        allreduce points). Every chip streams only its [K, N/mp] /
        [K/mp, N] weight slice; the fused grouped tail is split at the
        psum boundaries (a collective cannot live inside one Pallas
        grid). ``psum_axis`` is the internal per-shard form.
        """
        if not isinstance(weights, dict):
            raise TypeError(
                "decode_raw takes the layer-STACKED weight dict "
                f"(_stack()), not {type(weights).__name__}")
        if a8w8 and weights["qkv_weight"].dtype != jnp.int8:
            raise ValueError("a8w8 decode needs an int8 weight stack "
                             "(quantize_weight_only_int8 first)")
        if tp is not None:
            return self._tp_wrap(tp, "decode_raw", weights, x, cache,
                                 block_tables, (seq_lens,), cos_t,
                                 sin_t, a8w8, adapters=adapters,
                                 overlap=overlap)
        # layer-independent: built ONCE a step, shared by the layer loop
        plan = plan_decode_attention(cache.k, block_tables, seq_lens,
                                     self._pages_per_layer(cache))
        loop = self.decode_loop(a8w8, adapters)
        if loop == "adaptered":
            return self._loop_adaptered(
                weights, x, cache, plan, cos_t, sin_t, adapters,
                a8w8=a8w8, psum_axis=psum_axis, overlap=overlap)
        if loop == "layerwise":
            return self._loop_layerwise(
                weights, x, cache, plan, cos_t, sin_t, a8w8=a8w8,
                psum_axis=psum_axis, overlap=overlap, ep_axis=ep_axis,
                ep_size=ep_size)
        return self._loop_grouped(weights, x, cache, plan, cos_t, sin_t,
                                  psum_axis=psum_axis, overlap=overlap)

    def _attend_qkv(self, plan, qkv, h, ck, cv, l, cos_t, sin_t):
        """Layer l's attention over a computed QKV projection: head
        split + rope, append + attend under the step's ``plan``.
        Returns (att [b, n_q * hd] in h's dtype, ck', cv')."""
        q, k, v = _split_rope(qkv.astype(h.dtype), plan.seq_lens,
                              self.num_heads, self.num_kv_heads,
                              self.head_dim, cos_t, sin_t)
        att, ck, cv = decode_attend(plan, q, k, v, ck, cv, l)
        att = att.reshape(*h.shape[:-1], self.num_heads * self.head_dim)
        return att.astype(h.dtype), ck, cv

    def _loop_grouped(self, weights, x, cache, plan, cos_t, sin_t,
                      psum_axis=None, overlap=None):
        """GROUPED loop: QKV is carried through the ``fori_loop`` and
        each layer issues ONE streamed call — ``stream_layer_tail``: the
        fused O + LN2 + FFN tail whose last grid phase computes layer
        l+1's LN1 + QKV, so that projection's weight DMA overlaps layer
        l's FFN compute (the last layer's prefetched QKV is discarded).
        Under TP the tail splits at its two reduce seams
        (``reduce_axis``) and keeps the carried-QKV structure."""
        from ...nn.functional.stream_linear import (stream_layer_tail,
                                                    stream_linear)

        L = self.num_layers
        w = weights

        def body(l, carry):
            h, qkv, ck, cv = carry
            att, ck, cv = self._attend_qkv(plan, qkv, h, ck, cv, l,
                                           cos_t, sin_t)
            h, qkv = stream_layer_tail(
                att, h, w["out_weight"], w["ffn1_weight"],
                w["ffn2_weight"], layer=l, bo=w["out_bias"],
                b1=w["ffn1_bias"], b2=w["ffn2_bias"],
                ln2_scale=w["ln2_scale"], ln2_bias=w["ln2_bias"],
                epsilon=self.epsilon, activation=self.activation,
                so=w.get("out_scale"), s1=w.get("ffn1_scale"),
                s2=w.get("ffn2_scale"),
                next_qkv=dict(w=w["qkv_weight"], b=w["qkv_bias"],
                              s=w.get("qkv_scale"), ln_s=w["ln1_scale"],
                              ln_b=w["ln1_bias"],
                              layer=jnp.minimum(l + 1, L - 1)),
                out_dtype=h.dtype, reduce_axis=psum_axis,
                overlap=overlap)
            return h, qkv, ck, cv

        ln_s, ln_b = (jax.lax.dynamic_index_in_dim(w[n], 0, 0, False)
                      for n in ("ln1_scale", "ln1_bias"))
        hn = self._ln(x, ln_s, ln_b, self.epsilon).astype(x.dtype)
        qkv0 = stream_linear(hn, w["qkv_weight"], layer=0,
                             bias=w["qkv_bias"], scale=w.get("qkv_scale"),
                             out_dtype=x.dtype)
        h, _q, nk, nv = jax.lax.fori_loop(
            0, L, body, (x, qkv0, cache.k, cache.v))
        return h, PagedKV(nk, nv)

    def _loop_layerwise(self, weights, x, cache, plan, cos_t, sin_t,
                        a8w8=False, psum_axis=None, overlap=None,
                        ep_axis=None, ep_size=1):
        """LAYERWISE loop: ``_layer_body`` a layer, four projections a
        layer. MoE stacks take XLA dots over the loop-sliced weights
        (and the expert bank's own kernels); A8W8 streams each
        projection through the int8 x int8 act-quant kernel, the two
        row-parallel ones reduced over ``psum_axis`` inside
        ``stream_linear`` under TP. Also the reference the grouped loop
        is tested against."""
        from ...nn.functional.stream_linear import stream_linear

        # A8W8 reads its four matmul stacks UNSLICED through the kernel
        streamed = ("qkv_", "out_", "ffn1_", "ffn2_") if a8w8 else ()
        sliced = {n: a for n, a in weights.items()
                  if not n.startswith(streamed)}

        def body(l, carry):
            h, ck, cv = carry
            w = {n: jax.lax.dynamic_index_in_dim(a, l, 0, False)
                 for n, a in sliced.items()}
            linear = None
            if a8w8:
                def linear(xx, kind):
                    row = kind in ("out", "ffn2")
                    return stream_linear(
                        xx, weights[f"{kind}_weight"], layer=l,
                        bias=weights[f"{kind}_bias"],
                        scale=weights[f"{kind}_scale"], act_quant=True,
                        out_dtype=xx.dtype,
                        reduce_axis=psum_axis if row else None,
                        overlap=overlap)

            def attend(q, k, v, _ck, _cv):
                return decode_attend(plan, q, k, v, ck, cv, l)

            return self._layer_body(
                w, h, plan.seq_lens, None, attend, cos_t, sin_t,
                linear=linear, psum_axis=psum_axis, overlap=overlap,
                ep_axis=ep_axis, ep_size=ep_size)

        h, nk, nv = jax.lax.fori_loop(
            0, self.num_layers, body, (x, cache.k, cache.v))
        return h, PagedKV(nk, nv)

    def _loop_adaptered(self, weights, x, cache, plan, cos_t, sin_t,
                        adapters, a8w8=False, psum_axis=None,
                        overlap=None):
        """ADAPTERED loop: per-projection streamed base matmul plus ONE
        ragged grouped delta launch per target projection — tokens
        sorted by adapter slot once per step, membership riding the
        traced work map so the compiled program is independent of which
        adapters are loaded. The fused grouped tail is base-only (a
        delta join point cannot live inside its Pallas grid), so this
        loop runs the four-call per-layer form. Under TP the delta
        partial joins the base partial BEFORE the row-parallel psum
        (x·A = Σ_shards x_s·A_s with B replicated), keeping exactly two
        collectives per layer."""
        if self.moe_num_experts:
            raise NotImplementedError(
                "adaptered decode composes with the dense stack "
                "only (no MoE expert-bank form yet)")
        from ...core.flags import flag
        from ...nn.functional.lora import (
            inverse_order, lora_delta, sort_by_adapter)
        from ...nn.functional.stream_linear import (_apply_activation,
                                                    stream_linear)

        lora_backend = flag("lora_delta_backend")
        S_ad = adapters["qkv_a"].shape[1]
        order, offsets, _ = sort_by_adapter(
            adapters["slots"].astype(jnp.int32), S_ad)
        inv = inverse_order(order)

        def small(name, l):
            return jax.lax.dynamic_index_in_dim(
                weights[name], l, 0, False)

        def delta(xx, kind, l):
            a4 = adapters.get(f"{kind}_a")
            if a4 is None:
                return None
            a3 = jax.lax.dynamic_index_in_dim(a4, l, 0, False)
            b3 = jax.lax.dynamic_index_in_dim(
                adapters[f"{kind}_b"], l, 0, False)
            xs = jnp.take(xx, order, axis=0)
            d = lora_delta(xs, a3, b3, offsets, backend=lora_backend)
            return jnp.take(d, inv, axis=0)

        def proj(xx, kind, l, reduce=False, activation=None):
            # f32 partial with bias/activation deferred past the
            # delta join (and past the psum for row-parallel kinds)
            y = stream_linear(
                xx, weights[f"{kind}_weight"], layer=l,
                scale=weights.get(f"{kind}_scale"),
                act_quant=a8w8, out_dtype=jnp.float32)
            d = delta(xx, kind, l)
            if d is not None:
                y = y + d
            if reduce and psum_axis is not None:
                from ...distributed.tp import reduce_over_axis
                y = reduce_over_axis(y, psum_axis, overlap or "psum")
            y = y + small(f"{kind}_bias", l).astype(jnp.float32)
            if activation is not None:
                y = _apply_activation(y, activation)
            return y

        def body(l, carry):
            h, ck, cv = carry
            hn = self._ln(h, small("ln1_scale", l), small("ln1_bias", l),
                          self.epsilon).astype(h.dtype)
            att, ck, cv = self._attend_qkv(
                plan, proj(hn, "qkv", l), h, ck, cv, l, cos_t, sin_t)
            h = (h + proj(att, "out", l, reduce=True)).astype(h.dtype)
            hn = self._ln(h, small("ln2_scale", l), small("ln2_bias", l),
                          self.epsilon).astype(h.dtype)
            ff = proj(hn, "ffn1", l,
                      activation=self.activation).astype(h.dtype)
            h = (h + proj(ff, "ffn2", l, reduce=True)).astype(h.dtype)
            return h, ck, cv

        h, nk, nv = jax.lax.fori_loop(
            0, self.num_layers, body, (x, cache.k, cache.v))
        return h, PagedKV(nk, nv)

    # ---------- eager Layer API ----------

    def forward(self, x, cache=None, block_tables=None, seq_lens=None):
        """Eager wrapper: prefill when x is [b, s, d] (cache=None → pure
        dense forward, no KV writes), decode step when x is [b, d]."""
        cos_t, sin_t = rope_table(self.max_position, self.head_dim,
                                  self.rope_theta)
        w = self._stack()
        xd = x._data if isinstance(x, Tensor) else jnp.asarray(x)
        if xd.ndim == 3:
            h, cache = self.prefill_raw(
                w, xd, cache,
                None if block_tables is None else jnp.asarray(block_tables),
                cos_t, sin_t)
        else:
            h, cache = self.decode_raw(
                w, xd, cache, jnp.asarray(block_tables),
                jnp.asarray(seq_lens), cos_t, sin_t)
        return Tensor(h), cache

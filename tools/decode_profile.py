"""Decode-bottleneck ablation: time isolated components of the 1.3B
paged-KV decode step on the real chip (VERDICT r3 weak #1 diagnosis).

Run one mode per fresh subprocess (HBM fragmentation):
    python tools/decode_profile.py --mode full|noattn|headonly|xla_attn|...

Each mode prints one JSON line with tokens/sec for a 64-step decode
chunk at batch 16 on the gpt3-1.3b geometry (d2048 L24 h16 hd128).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

D, L, H, HD = 2048, 24, 16, 128
VOCAB = 51200
BATCH = 16
PROMPT = 128
CHUNK = 64
PAGE = 16


def build(bf16_stack=True, bf16_embed=False):
    import jax.numpy as jnp

    import paddle_tpu as paddle
    from paddle_tpu.inference import FusedCausalLM

    paddle.seed(0)
    model = FusedCausalLM(vocab_size=VOCAB, embed_dim=D, num_heads=H,
                         dim_feedforward=4 * D, num_layers=L,
                         max_position=PROMPT + CHUNK + 64)
    if bf16_stack:
        st = model.stack
        for n in ("qkv_weight", "qkv_bias", "out_weight", "out_bias",
                  "ffn1_weight", "ffn1_bias", "ffn2_weight", "ffn2_bias"):
            p = getattr(st, n)
            p._rebind(p._data.astype(jnp.bfloat16))
    if bf16_embed:
        model.embed._rebind(model.embed._data.astype(jnp.bfloat16))
    return model


def time_chunk(fn, args, steps=3):
    """Compile + time a chunk program; returns sec/chunk."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    # re-fetch a scalar: the fetch is what forces execution
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    _ = np.asarray(jax.tree_util.tree_leaves(out)[0]).ravel()[:1]
    return (time.perf_counter() - t0) / steps


def mode_full(cache_dtype="float32", attn="pallas", bf16_embed=False,
              quant=None):
    """Current engine path end-to-end (greedy, chunk=64)."""
    import jax.numpy as jnp

    from paddle_tpu.inference import GenerationEngine

    model = build(bf16_embed=bf16_embed)
    eng = GenerationEngine(model, page_size=PAGE,
                           max_length=PROMPT + CHUNK + 2,
                           decode_chunk=CHUNK, quant=quant)
    if attn == "xla":
        import paddle_tpu as _p

        # flag (not monkeypatch): decode_raw's fused-stream branch
        # checks the flag and would bypass a patched paged_attention
        _p.set_flags({"paged_attention_backend": "xla"})
    if cache_dtype != "float32":
        from paddle_tpu.inference import kv_cache as kvmod
        orig_init = kvmod.BlockKVCacheManager.__init__

        def patched(self, *a, **kw):
            kw["dtype"] = jnp.bfloat16
            orig_init(self, *a, **kw)
        kvmod.BlockKVCacheManager.__init__ = patched

    rng = np.random.RandomState(0)
    ids = rng.randint(0, VOCAB, (BATCH, PROMPT))
    new = 1 + CHUNK
    eng.generate(ids, max_new_tokens=new)  # compile
    t0 = time.perf_counter()
    out = eng.generate(ids, max_new_tokens=new)
    dt = time.perf_counter() - t0
    assert out.shape == (BATCH, PROMPT + new)
    return BATCH * new / dt


def mode_weights_only():
    """Transformer matmuls only (no attention, no cache, no logits):
    the pure weight-streaming floor."""
    import jax
    import jax.numpy as jnp

    model = build()
    st = model.stack
    w = st._stack()

    def chunk(weights, x):
        def tok_step(carry, _):
            h = carry

            def body(h, wl):
                hn = ((h - jnp.mean(h, -1, keepdims=True))
                      * wl["ln1_scale"][:D]).astype(h.dtype)
                qkv = hn @ wl["qkv_weight"]
                att = qkv[:, :D]
                h = (h + att @ wl["out_weight"] + wl["out_bias"]) \
                    .astype(h.dtype)
                ff = jax.nn.gelu(h @ wl["ffn1_weight"] + wl["ffn1_bias"])
                h = (h + ff @ wl["ffn2_weight"] + wl["ffn2_bias"]) \
                    .astype(h.dtype)
                return h, None
            h, _ = jax.lax.scan(body, h, weights)
            return h, h[:, 0]
        h, outs = jax.lax.scan(tok_step, x, jnp.arange(CHUNK))
        return outs

    fn = jax.jit(chunk)
    x = jnp.ones((BATCH, D), jnp.bfloat16)
    sec = time_chunk(fn, (w, x))
    return BATCH * CHUNK / sec


def mode_weights_only_grouped(prefetch=True):
    """GROUPED transformer matmuls only (no attention/cache/logits):
    the r6 fused O+LN2+FFN tail kernel (+ in-tail next-layer QKV when
    ``prefetch``) against mode_weights_only's per-projection floor —
    the delta is the per-call dispatch/ramp-up cost the grouping
    removes. The "attention output" is the QKV projection's leading D
    columns, exactly like mode_weights_only."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.stream_linear import (
        stream_layer_tail, stream_linear)

    model = build()
    st = model.stack
    w = st._stack()
    eps, act = st.epsilon, st.activation

    def qkv_at(weights, l, h):
        ln_s = jax.lax.dynamic_index_in_dim(weights["ln1_scale"], l,
                                            0, False)
        ln_b = jax.lax.dynamic_index_in_dim(weights["ln1_bias"], l,
                                            0, False)
        hn = ((h - jnp.mean(h, -1, keepdims=True)) * ln_s + ln_b) \
            .astype(h.dtype)
        return stream_linear(hn, weights["qkv_weight"], layer=l,
                             bias=weights["qkv_bias"], out_dtype=h.dtype)

    def chunk(weights, x):
        def tok_step(carry, _):
            h = carry

            def body(l, hq):
                h, qkv = hq
                att = qkv[:, :D]
                nq = None
                if prefetch:
                    nq = dict(w=weights["qkv_weight"],
                              b=weights["qkv_bias"],
                              ln_s=weights["ln1_scale"],
                              ln_b=weights["ln1_bias"],
                              layer=jnp.minimum(l + 1, L - 1))
                res = stream_layer_tail(
                    att, h, weights["out_weight"],
                    weights["ffn1_weight"], weights["ffn2_weight"],
                    layer=l, bo=weights["out_bias"],
                    b1=weights["ffn1_bias"], b2=weights["ffn2_bias"],
                    ln2_scale=weights["ln2_scale"],
                    ln2_bias=weights["ln2_bias"], epsilon=eps,
                    activation=act, next_qkv=nq, out_dtype=h.dtype)
                if prefetch:
                    h, qkv = res
                else:
                    h = res
                    qkv = qkv_at(weights, jnp.minimum(l + 1, L - 1), h)
                return h, qkv

            qkv0 = qkv_at(weights, 0, h)
            h, _ = jax.lax.fori_loop(0, L, body, (h, qkv0))
            return h, h[:, 0]
        h, outs = jax.lax.scan(tok_step, x, jnp.arange(CHUNK))
        return outs

    fn = jax.jit(chunk)
    x = jnp.ones((BATCH, D), jnp.bfloat16)
    sec = time_chunk(fn, (w, x))
    return BATCH * CHUNK / sec


def mode_engine_grouped(batch=32, grouped="on", prefetch=True,
                        quant=None):
    """Engine end-to-end with the grouped weight-stream path forced
    on/off (grouped-vs-ungrouped and prefetch on/off ablations)."""
    import paddle_tpu as paddle

    paddle.set_flags({"decode_grouped": grouped,
                      "decode_prefetch": prefetch})
    return mode_engine_full(batch, quant=quant)


def mode_engine_tp(batch=32, mp=2):
    """Engine end-to-end TENSOR-PARALLEL over ``mp`` chips (ISSUE 10):
    per-chip weight streams shrink to 1/mp, two psums per layer ride
    the ICI — compare against engine_grouped_b32 to read the
    collective + split-grouping overhead directly. Needs >= mp
    devices (it is a multi-chip ablation, not an emulation)."""
    import jax

    if len(jax.devices()) < mp:
        raise SystemExit(
            f"engine_tp mp={mp} needs {mp} devices, have "
            f"{len(jax.devices())} — run on a multi-chip host")
    from paddle_tpu.inference import GenerationEngine as _GE

    orig_init = _GE.__init__

    def ginit(self, *a, **kw):
        kw.setdefault("mp_degree", mp)
        orig_init(self, *a, **kw)

    _GE.__init__ = ginit
    try:
        return mode_engine_full(batch)
    finally:
        _GE.__init__ = orig_init


def mode_head_only(bf16=False):
    """Logits head (h @ embed.T) + argmax, 64 steps."""
    import jax
    import jax.numpy as jnp

    model = build(bf16_embed=bf16)
    embed = model.embed._data

    def chunk(embed, h):
        def tok_step(carry, _):
            logits = carry @ embed.T
            tok = jnp.argmax(logits, -1)
            return carry + 1e-6 * tok[:, None].astype(carry.dtype), tok
        _, toks = jax.lax.scan(tok_step, h, jnp.arange(CHUNK))
        return toks

    fn = jax.jit(chunk)
    h = jnp.ones((BATCH, D), embed.dtype)
    sec = time_chunk(fn, (embed, h))
    return BATCH * CHUNK / sec


def mode_cache_copy(dtype="float32"):
    """Cost of shuttling the paged cache through scan xs->ys per token
    (the current decode structure) with NO compute."""
    import jax
    import jax.numpy as jnp

    pages_per_seq = -(-(PROMPT + CHUNK + 2) // PAGE)
    npages = BATCH * pages_per_seq + 1
    shape = (L, H, npages, PAGE, HD)
    dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
    ck, cv = jnp.zeros(shape, dt), jnp.zeros(shape, dt)

    def chunk(ck, cv):
        def tok_step(carry, i):
            ck, cv = carry

            def body(_, per_layer):
                k, v = per_layer
                k = k.at[0, 0, 0, 0].add(1.0)
                return 0.0, (k, v)
            _, (ck, cv) = jax.lax.scan(body, 0.0, (ck, cv))
            return (ck, cv), ck[0, 0, 0, 0, 0]
        (ck, cv), outs = jax.lax.scan(tok_step, (ck, cv),
                                      jnp.arange(CHUNK))
        return outs

    # no donation: time_chunk re-invokes with the same arrays
    fn = jax.jit(chunk)
    sec = time_chunk(fn, (ck, cv))
    return BATCH * CHUNK / sec


def mode_pallas_attn(dtype="float32"):
    """Pallas paged-attention kernel alone, 64 steps x 24 layers."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.paged_attention import paged_attention

    pages_per_seq = -(-(PROMPT + CHUNK + 2) // PAGE)
    npages = BATCH * pages_per_seq + 1
    dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
    # PAGE-MAJOR head-major pool (r5 layout): [P, n_kv, ps, d]
    ck = jnp.zeros((npages, H, PAGE, HD), dt)
    cv = jnp.zeros((npages, H, PAGE, HD), dt)
    tables = jnp.arange(1, 1 + BATCH * pages_per_seq, dtype=jnp.int32) \
        .reshape(BATCH, pages_per_seq)
    lens = jnp.full((BATCH,), PROMPT, jnp.int32)

    def chunk(q, ck, cv):
        def tok_step(q, i):
            def body(q, _):
                o = paged_attention(q, ck, cv, lens, tables)
                return o.astype(q.dtype), None
            q, _ = jax.lax.scan(body, q, jnp.arange(L))
            return q, q[0, 0, 0]
        q, _ = jax.lax.scan(tok_step, q, jnp.arange(CHUNK))
        return q

    q = jnp.ones((BATCH, H, HD), dt)
    fn = jax.jit(chunk)
    sec = time_chunk(fn, (q, ck, cv))
    return BATCH * CHUNK / sec


def mode_carry_cache(dtype="float32"):
    """In-place alternative to the scan xs->ys shuttle: cache pool as
    fori_loop carry, one scatter per layer (layers folded into the page
    dim). If XLA aliases the carry, cost ~= true bytes written (tiny)."""
    import jax
    import jax.numpy as jnp

    pages_per_seq = -(-(PROMPT + CHUNK + 2) // PAGE)
    npages = BATCH * pages_per_seq + 1
    dt = jnp.float32 if dtype == "float32" else jnp.bfloat16
    shape = (L * npages, H, PAGE, HD)  # page-major head-major (r5)
    ck, cv = jnp.zeros(shape, dt), jnp.zeros(shape, dt)
    tables = jnp.arange(1, 1 + BATCH * pages_per_seq, dtype=jnp.int32) \
        .reshape(BATCH, pages_per_seq)

    def chunk(ck, cv):
        def tok_step(carry, i):
            ck, cv = carry
            pos = jnp.full((BATCH,), PROMPT, jnp.int32) + i
            page_ids = tables[jnp.arange(BATCH), pos // PAGE]
            slots = pos % PAGE
            newk = jnp.ones((BATCH, H, HD), dt)

            def body(l, c):
                ck, cv = c
                pid = page_ids + l * npages
                ck = ck.at[pid, :, slots].set(newk)
                cv = cv.at[pid, :, slots].set(newk)
                return (ck, cv)
            ck, cv = jax.lax.fori_loop(0, L, body, (ck, cv))
            return (ck, cv), ck[0, 0, 0, 0]
        (ck, cv), outs = jax.lax.scan(tok_step, (ck, cv),
                                      jnp.arange(CHUNK))
        return outs

    fn = jax.jit(chunk)
    sec = time_chunk(fn, (ck, cv))
    return BATCH * CHUNK / sec


def mode_head_variant(kind):
    """Logits-head alternatives (head_only fp32 = 7.3ms/step is 17x off
    the 420MB/819GB/s roofline; bf16 untransposed is pathological)."""
    import jax
    import jax.numpy as jnp

    model = build()
    embed = model.embed._data  # [V, D] fp32
    # derive the variant from the kind string: transpose iff t_,
    # bf16-cast iff bf16, preferred fp32 accumulate iff prefer
    w = jnp.array(embed.T) if kind.startswith("t_") else embed
    if "bf16" in kind:
        w = w.astype(jnp.bfloat16)
    prefer = "prefer" in kind
    argmax = "noargmax" not in kind

    cdim = 0 if kind.startswith("t_") else 1

    def chunk(w, h):
        def tok_step(carry, _):
            logits = jax.lax.dot_general(
                carry, w, (((1,), (cdim,)), ((), ())),
                preferred_element_type=jnp.float32 if prefer else None)
            tok = (jnp.argmax(logits, -1) if argmax
                   else jnp.max(logits, -1).astype(jnp.int32))
            return carry + (1e-6 * tok[:, None]).astype(carry.dtype), tok
        _, toks = jax.lax.scan(tok_step, h, jnp.arange(CHUNK))
        return toks

    fn = jax.jit(chunk)
    h = jnp.ones((BATCH, D), jnp.bfloat16 if "bf16" in kind
                 else jnp.float32)
    sec = time_chunk(fn, (w, h))
    return BATCH * CHUNK / sec


def mode_argmax_only():
    """Isolate argmax over [b, V] inside a scan (head matmul excluded)."""
    import jax
    import jax.numpy as jnp

    logits = jnp.ones((BATCH, VOCAB), jnp.float32)

    def chunk(logits, h):
        def tok_step(carry, _):
            tok = jnp.argmax(logits + carry[:, :1], -1)
            return carry + (1e-6 * tok[:, None]).astype(carry.dtype), tok
        _, toks = jax.lax.scan(tok_step, h, jnp.arange(CHUNK))
        return toks

    fn = jax.jit(chunk)
    h = jnp.ones((BATCH, VOCAB), jnp.float32)
    sec = time_chunk(fn, (logits, h))
    return BATCH * CHUNK / sec


def mode_weights_unrolled():
    """Weight streaming with UNSTACKED per-layer weights and a Python-
    unrolled layer loop (no scan slice-copies of the stacked arrays)."""
    import jax
    import jax.numpy as jnp

    model = build()
    w = model.stack._stack()
    layers = [{k: v[l] for k, v in w.items()} for l in range(L)]

    def chunk(layers, x):
        def tok_step(h, _):
            for wl in layers:
                hn = ((h - jnp.mean(h, -1, keepdims=True))
                      * wl["ln1_scale"]).astype(h.dtype)
                qkv = hn @ wl["qkv_weight"]
                att = qkv[:, :D]
                h = (h + att @ wl["out_weight"] + wl["out_bias"]) \
                    .astype(h.dtype)
                ff = jax.nn.gelu(h @ wl["ffn1_weight"] + wl["ffn1_bias"])
                h = (h + ff @ wl["ffn2_weight"] + wl["ffn2_bias"]) \
                    .astype(h.dtype)
            return h, h[:, 0]
        h, outs = jax.lax.scan(tok_step, x, jnp.arange(CHUNK))
        return outs

    fn = jax.jit(chunk)
    x = jnp.ones((BATCH, D), jnp.bfloat16)
    sec = time_chunk(fn, (layers, x))
    return BATCH * CHUNK / sec


def mode_loop_overhead():
    """Pure lax.scan iteration cost: 64 steps of h+1 on [b, d]."""
    import jax
    import jax.numpy as jnp

    def chunk(h):
        def tok_step(carry, _):
            return carry + 1.0, carry[0, 0]
        h, outs = jax.lax.scan(tok_step, h, jnp.arange(CHUNK))
        return outs

    fn = jax.jit(chunk)
    h = jnp.ones((BATCH, D), jnp.bfloat16)
    sec = time_chunk(fn, (h,))
    return BATCH * CHUNK / sec


def mode_head_noloop():
    """ONE head matmul+argmax per device program (no scan): per-
    dispatch+compute latency."""
    import jax
    import jax.numpy as jnp

    model = build()
    w = jnp.array(model.embed._data.T).astype(jnp.bfloat16)

    def one(w, h):
        logits = jax.lax.dot_general(
            h, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return jnp.argmax(logits, -1)

    fn = jax.jit(one)
    h = jnp.ones((BATCH, D), jnp.bfloat16)
    out = fn(w, h)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(CHUNK):
        out = fn(w, out.sum() * jnp.zeros((BATCH, D), jnp.bfloat16)
                 + h)
    _ = np.asarray(out)[:1]
    sec = time.perf_counter() - t0
    return BATCH * CHUNK / sec


def mode_head_indep():
    """64-scan of the head matmul with NO loop-carried dependence on the
    matmul input (tests cross-iteration pipelining/prefetch)."""
    import jax
    import jax.numpy as jnp

    model = build()
    w = jnp.array(model.embed._data.T).astype(jnp.bfloat16)

    def chunk(w, h):
        def tok_step(acc, _):
            logits = jax.lax.dot_general(
                h, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return acc + jnp.argmax(logits, -1).sum(), acc
        acc, outs = jax.lax.scan(tok_step, jnp.int32(0),
                                 jnp.arange(CHUNK))
        return acc

    fn = jax.jit(chunk)
    h = jnp.ones((BATCH, D), jnp.bfloat16)
    sec = time_chunk(fn, (w, h))
    return BATCH * CHUNK / sec


def mode_head_unroll():
    """16 sequential head matmul+argmax steps UNROLLED in one jit (no
    while loop): is lax.scan itself the bottleneck?"""
    import jax
    import jax.numpy as jnp

    model = build()
    w = jnp.array(model.embed._data.T).astype(jnp.bfloat16)
    k = 16

    def prog(w, h):
        toks = []
        for _ in range(k):
            logits = jax.lax.dot_general(
                h, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            tok = jnp.argmax(logits, -1)
            toks.append(tok)
            h = h + (1e-6 * tok[:, None]).astype(h.dtype)
        return jnp.stack(toks)

    fn = jax.jit(prog)
    h = jnp.ones((BATCH, D), jnp.bfloat16)
    sec = time_chunk(fn, (w, h))
    return BATCH * k / sec


def mode_weights_int8():
    """Weight streaming with int8 weights dequantized in-body (bytes
    halve vs bf16; if bandwidth-bound, time should halve)."""
    import jax
    import jax.numpy as jnp

    model = build()
    w = model.stack._stack()
    q = {k: (jnp.round(v * 127).astype(jnp.int8) if v.ndim == 3
             else v) for k, v in w.items()}

    def chunk(weights, x):
        def tok_step(carry, _):
            h = carry

            def body(h, wl):
                hn = ((h - jnp.mean(h, -1, keepdims=True))
                      * wl["ln1_scale"]).astype(h.dtype)
                qkv = hn @ (wl["qkv_weight"].astype(jnp.bfloat16)
                            * (1.0 / 127))
                att = qkv[:, :D]
                h = (h + att @ (wl["out_weight"].astype(jnp.bfloat16)
                                * (1.0 / 127)) + wl["out_bias"]) \
                    .astype(h.dtype)
                ff = jax.nn.gelu(
                    h @ (wl["ffn1_weight"].astype(jnp.bfloat16)
                         * (1.0 / 127)) + wl["ffn1_bias"])
                h = (h + ff @ (wl["ffn2_weight"].astype(jnp.bfloat16)
                               * (1.0 / 127)) + wl["ffn2_bias"]) \
                    .astype(h.dtype)
                return h, None
            h, _ = jax.lax.scan(body, h, weights)
            return h, h[:, 0]
        h, outs = jax.lax.scan(tok_step, x, jnp.arange(CHUNK))
        return outs

    fn = jax.jit(chunk)
    x = jnp.ones((BATCH, D), jnp.bfloat16)
    sec = time_chunk(fn, (q, x))
    return BATCH * CHUNK / sec


def mode_xla_paged_attn(batch=32, dtype="bfloat16"):
    """Current XLA gather attention over the FOLDED pool, isolated:
    64-step scan x 24 layers at the given batch."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.paged_attention import _xla_paged

    pages_per_seq = -(-(PROMPT + CHUNK + 2) // PAGE)
    npages = batch * pages_per_seq + 1
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    # PAGE-MAJOR head-major pool (r5 layout): [P, n_kv, ps, d]
    ck = jnp.zeros((L * npages, H, PAGE, HD), dt)
    cv = jnp.zeros((L * npages, H, PAGE, HD), dt)
    tables = jnp.arange(1, 1 + batch * pages_per_seq, dtype=jnp.int32) \
        .reshape(batch, pages_per_seq)
    lens = jnp.full((batch,), PROMPT, jnp.int32)

    def chunk(q, ck, cv):
        def tok_step(q, i):
            def body(l, qq):
                o = _xla_paged(qq, ck, cv, lens, tables + l * npages)
                return o.astype(qq.dtype)
            q = jax.lax.fori_loop(0, L, body, q)
            return q, q[0, 0, 0]
        q, _ = jax.lax.scan(tok_step, q, jnp.arange(CHUNK))
        return q

    q = jnp.ones((batch, H, HD), dt)
    fn = jax.jit(chunk)
    sec = time_chunk(fn, (q, ck, cv))
    return batch * CHUNK / sec


def mode_engine_full(batch=32, backend=None, quant=None, kv=None):
    """Current engine end-to-end at the given batch (bf16 stack; the
    engine derives bf16 compute + bf16 KV from the weight dtype).
    backend forces FLAGS_paged_attention_backend; quant='int8' runs
    weight-only int8 (the bench's int8 rung), quant='a8w8' the full
    dynamic-activation int8 x int8 matmul path; kv='int8' additionally
    quantizes the KV cache (cache-KV int8 mode)."""
    import paddle_tpu as paddle

    if backend:
        paddle.set_flags({"paged_attention_backend": backend})
    if kv == "int8":
        from paddle_tpu.inference import GenerationEngine as _GE
        orig_ginit = _GE.__init__

        def ginit(self, *a, **kw):
            kw.setdefault("kv_dtype", "int8")
            orig_ginit(self, *a, **kw)
        _GE.__init__ = ginit
    global BATCH
    old, BATCH = BATCH, batch
    try:
        return mode_full(quant=quant)
    finally:
        BATCH = old


def mode_stream_attn(batch=32, dtype="bfloat16"):
    """Pool-streaming Pallas attention isolated over the folded pool:
    64-step scan x 24 layers at the given batch (compare
    xla_paged_attn_b32 — same traffic, no gather materialization)."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.nn.functional.paged_attention import (
        _stream_paged, build_pool_ownership)

    pages_per_seq = -(-(PROMPT + CHUNK + 2) // PAGE)
    chunk_pages = max(1, 1024 // PAGE)
    npages = -(-(batch * pages_per_seq + 1) // chunk_pages) * chunk_pages
    dt = jnp.bfloat16 if dtype == "bfloat16" else jnp.float32
    ck = jnp.zeros((L * npages, H, PAGE, HD), dt)
    cv = jnp.zeros((L * npages, H, PAGE, HD), dt)
    tables = jnp.arange(1, 1 + batch * pages_per_seq, dtype=jnp.int32) \
        .reshape(batch, pages_per_seq)
    lens = jnp.full((batch,), PROMPT, jnp.int32)

    def chunk(q, ck, cv):
        own = build_pool_ownership(tables, lens, npages, PAGE)

        def tok_step(q, i):
            def body(l, qq):
                o = _stream_paged(qq, ck, cv, lens, tables,
                                  pool_base=l * npages,
                                  pool_pages=npages, ownership=own)
                return o.astype(qq.dtype)
            q = jax.lax.fori_loop(0, L, body, q)
            return q, q[0, 0, 0]
        q, _ = jax.lax.scan(tok_step, q, jnp.arange(CHUNK))
        return q

    q = jnp.ones((batch, H, HD), dt)
    fn = jax.jit(chunk)
    sec = time_chunk(fn, (q, ck, cv))
    return batch * CHUNK / sec


def mode_engine_knockout(batch=32, knock="attn", quant=None):
    """Engine end-to-end with ONE component knocked out in place —
    in-context component cost = full minus knockout."""
    import jax.numpy as jnp

    import paddle_tpu.incubate.nn.fused_transformer as ft
    from paddle_tpu.inference import GenerationEngine

    if quant == "int8":
        orig_build = globals()["build"]

        def build_q(*a, **kw):
            model = orig_build(*a, **kw)
            model.stack.quantize_weight_only_int8()
            return model
        globals()["build"] = build_q

    if knock == "attn":
        def fake_attn(q, ck, cv, lens, tables, **kw):
            return q  # [b, n_q, d] passthrough, no KV read
        ft.paged_attention = fake_attn

        def fake_fused(q, nk, nv, ck, cv, lens, tables, **kw):
            return q, ck, cv
        ft.paged_decode_attention_inplace = fake_fused
    elif knock == "head":
        def fake_logits(self, h, head_t, lnf_s, lnf_b):
            b = h.shape[0]
            return jnp.broadcast_to(h[:, :1].astype(jnp.float32),
                                    (b, VOCAB))
        GenerationEngine._logits = fake_logits
    elif knock == "argmax":
        @staticmethod
        def fake_pick(logits, key, sample_cfg):
            return jnp.zeros((logits.shape[0],), jnp.int32)
        GenerationEngine._pick_token = fake_pick
    elif knock == "scatter":
        def fake_write(ck, cv, k, v, pos, tables):
            return ck, cv
        ft.write_kv_pages = fake_write
    try:
        return _with_batch(batch, mode_full)
    finally:
        if quant == "int8":
            globals()["build"] = orig_build


def _with_batch(batch, fn):
    global BATCH
    old, BATCH = BATCH, batch
    try:
        return fn()
    finally:
        BATCH = old


def mode_pallas_page(page, dtype="bfloat16"):
    """Pallas paged attention with a different page size (DMA width)."""
    global PAGE
    old, PAGE = PAGE, page
    try:
        return mode_pallas_attn(dtype)
    finally:
        PAGE = old


MODES = {
    "full": lambda: mode_full(),
    "bf16cache": lambda: mode_full(cache_dtype="bfloat16"),
    "bf16embed": lambda: mode_full(bf16_embed=True),
    "bf16both": lambda: mode_full(cache_dtype="bfloat16", bf16_embed=True),
    "xla_attn": lambda: mode_full(attn="xla"),
    "weights_only": mode_weights_only,
    "head_only": lambda: mode_head_only(False),
    "head_only_bf16": lambda: mode_head_only(True),
    "cache_copy": lambda: mode_cache_copy("float32"),
    "cache_copy_bf16": lambda: mode_cache_copy("bfloat16"),
    "pallas_attn": lambda: mode_pallas_attn("float32"),
    "pallas_attn_bf16": lambda: mode_pallas_attn("bfloat16"),
    "carry_cache": lambda: mode_carry_cache("float32"),
    "carry_cache_bf16": lambda: mode_carry_cache("bfloat16"),
    "head_t_bf16": lambda: mode_head_variant("t_bf16"),
    "head_t_bf16_prefer": lambda: mode_head_variant("t_bf16_prefer"),
    "head_bf16_prefer": lambda: mode_head_variant("bf16_prefer"),
    "head_t_f32": lambda: mode_head_variant("t_f32"),
    "pallas_page32": lambda: mode_pallas_page(32),
    "pallas_page64": lambda: mode_pallas_page(64),
    "pallas_page8": lambda: mode_pallas_page(8),
    "head_t_bf16_noargmax": lambda: mode_head_variant("t_bf16_noargmax"),
    "head_bf16_prefer_noargmax":
        lambda: mode_head_variant("bf16_prefer_noargmax"),
    "argmax_only": mode_argmax_only,
    "weights_unrolled": mode_weights_unrolled,
    "loop_overhead": mode_loop_overhead,
    "head_noloop": mode_head_noloop,
    "head_indep": mode_head_indep,
    "head_unroll": mode_head_unroll,
    "weights_int8": mode_weights_int8,
    "xla_paged_attn_b32": lambda: mode_xla_paged_attn(32),
    "xla_paged_attn_b16": lambda: mode_xla_paged_attn(16),
    "stream_attn_b32": lambda: mode_stream_attn(32),
    "stream_attn_b64": lambda: mode_stream_attn(64),
    "weights_only_b32": lambda: _with_batch(32, mode_weights_only),
    "weights_unrolled_b32": lambda: _with_batch(32, mode_weights_unrolled),
    "weights_int8_b32": lambda: _with_batch(32, mode_weights_int8),
    "engine_b32": lambda: mode_engine_full(32),
    "engine_stream_b32": lambda: mode_engine_full(32, backend="stream"),
    "engine_stream_b64": lambda: mode_engine_full(64, backend="stream"),
    "engine_xla_b64": lambda: mode_engine_full(64, backend="xla"),
    "engine_int8_b32": lambda: mode_engine_full(32, quant="int8"),
    "engine_kv8_b32": lambda: mode_engine_full(32, kv="int8"),
    "engine_int8kv8_b32":
        lambda: mode_engine_full(32, quant="int8", kv="int8"),
    "engine_int8kv8_b64":
        lambda: mode_engine_full(64, quant="int8", kv="int8"),
    "engine_int8_stream_b32":
        lambda: mode_engine_full(32, backend="stream", quant="int8"),
    # A8W8 ablation rows: dynamic-act int8 x int8 matmuls vs the
    # weight-only rungs above (same geometry — the delta IS the
    # activation-dequant round the a8w8 kernel removes)
    "engine_a8w8_b32": lambda: mode_engine_full(32, quant="a8w8"),
    "engine_a8w8_b64": lambda: mode_engine_full(64, quant="a8w8"),
    "engine_a8w8kv8_b32":
        lambda: mode_engine_full(32, quant="a8w8", kv="int8"),
    "engine_a8w8kv8_b64":
        lambda: mode_engine_full(64, quant="a8w8", kv="int8"),
    # grouped weight-stream rows (r6): kernel floor, grouped-vs-
    # ungrouped engine delta, and the cross-layer-prefetch knockout
    "weights_only_grouped": mode_weights_only_grouped,
    "weights_only_grouped_b32":
        lambda: _with_batch(32, mode_weights_only_grouped),
    "weights_only_grouped_noprefetch_b32":
        lambda: _with_batch(32,
                            lambda: mode_weights_only_grouped(False)),
    "engine_grouped_b32": lambda: mode_engine_grouped(32),
    "engine_ungrouped_b32":
        lambda: mode_engine_grouped(32, grouped="off"),
    "prefetch_on": lambda: mode_engine_grouped(32, prefetch=True),
    "prefetch_off": lambda: mode_engine_grouped(32, prefetch=False),
    "engine_grouped_int8_b32":
        lambda: mode_engine_grouped(32, quant="int8"),
    # tensor-parallel ablation (ISSUE 10): mp2-sharded engine vs the
    # single-chip grouped row — the delta is the per-layer psum pair
    # plus the tail grouping split at the collective boundaries
    "engine_grouped_mp2_b32": lambda: mode_engine_tp(32, mp=2),
    "engine_int8_noattn_b32":
        lambda: mode_engine_knockout(32, "attn", quant="int8"),
    "engine_int8_nohead_b32":
        lambda: mode_engine_knockout(32, "head", quant="int8"),
    "engine_noattn_b32": lambda: mode_engine_knockout(32, "attn"),
    "engine_nohead_b32": lambda: mode_engine_knockout(32, "head"),
    "engine_noargmax_b32": lambda: mode_engine_knockout(32, "argmax"),
    "engine_noscatter_b32": lambda: mode_engine_knockout(32, "scatter"),
}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mode", required=True, choices=sorted(MODES))
    ap.add_argument("--no-lint", action="store_true",
                    help="skip the tpu_lint preflight gate")
    args = ap.parse_args()
    import os

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    from paddle_tpu.analysis.preflight import preflight

    preflight("decode_profile", no_lint=args.no_lint)
    t0 = time.time()
    tps = MODES[args.mode]()
    out = {"mode": args.mode, "tokens_per_sec": round(tps, 1),
           "wall": round(time.time() - t0, 1)}
    # engine-path modes record each compiled program's XLA cost model
    # and the synced per-chunk wall time (profiler/roofline.py): attach
    # the achieved-rate table so an ablation shows WHERE on the roofline
    # each variant lands, not just tokens/sec
    from paddle_tpu.profiler import roofline

    rl = roofline.report()
    if rl:
        out["roofline"] = rl
    print(json.dumps(out))


if __name__ == "__main__":
    main()

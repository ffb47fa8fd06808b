"""Grouped bf16 weight-stream decode: interpret-mode parity + call
structure (the r6 tentpole, nn/functional/stream_linear.py).

Three contracts pinned on CPU:

1. KERNEL PARITY — ``stream_layer_tail``'s fused Pallas kernel
   (interpret mode) reproduces an independent per-projection numpy
   reference within fp tolerance, for stacked and unstacked weights,
   f32/bf16/int8(weight-only == the a8w8 stack's grouped math), ragged
   N (the XLA fallback), and a TRACED layer index under jit.
2. CALL STRUCTURE — one decode step issues at most TWO streamed weight
   matmul calls per transformer layer (ONE in steady state with
   cross-layer prefetch): counted at trace level, since the fori_loop
   body traces once.
3. ENGINE PARITY — GenerationEngine greedy tokens (the grouped loop)
   equal those of the same engine stepping the layerwise loop for the
   fp32 stack, and the two loops' hidden states agree within quant
   tolerance for int8 stacks. No flag picks between them: the tests
   call ``FusedMultiTransformer._loop_grouped`` /
   ``_loop_layerwise`` directly.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nn.functional.stream_linear import (stream_layer_tail,
                                                    stream_linear)

EPS = 1e-5


def _mk(rng, L, Ka, d, dff, Nq, dtype=np.float32, int8=False):
    """Random stacked tail weights (+ optional int8 quantization)."""
    def w(*s):
        return (rng.randn(*s) * 0.05).astype(np.float32)

    p = dict(wo=w(L, Ka, d), w1=w(L, d, dff), w2=w(L, dff, d),
             wq=w(L, d, Nq), bo=w(L, d), b1=w(L, dff), b2=w(L, d),
             bq=w(L, Nq),
             l2s=(1 + 0.1 * rng.randn(L, d)).astype(np.float32),
             l2b=(0.1 * rng.randn(L, d)).astype(np.float32),
             l1s=(1 + 0.1 * rng.randn(L, d)).astype(np.float32),
             l1b=(0.1 * rng.randn(L, d)).astype(np.float32))
    scales = {}
    if int8:
        for n in ("wo", "w1", "w2", "wq"):
            full = p[n]
            s = np.maximum(np.abs(full).max(axis=-2) / 127.0, 1e-8)
            p[n] = np.clip(np.round(full / s[:, None, :]), -127,
                           127).astype(np.int8)
            scales["s" + n[1:]] = s.astype(np.float32)
    return p, scales


def _ref_tail(att, h, p, scales, layer, activation="gelu",
              with_q=True, lq=None):
    """Independent numpy reference of the grouped tail's math: the
    ungrouped per-projection decode path (fp32)."""
    def deq(n):
        w = p[n].astype(np.float32)
        s = scales.get("s" + n[1:])
        return w * s[:, None, :] if s is not None else w

    def ln(x, s, b):
        mu = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        return (x - mu) / np.sqrt(var + EPS) * s + b

    def act(x):
        if activation == "gelu":
            return np.asarray(jax.nn.gelu(jnp.asarray(x)))
        return np.maximum(x, 0)

    att = np.asarray(att, np.float32)
    h = np.asarray(h, np.float32)
    h2 = h + att @ deq("wo")[layer] + p["bo"][layer]
    hn = ln(h2, p["l2s"][layer], p["l2b"][layer])
    ff = act(hn @ deq("w1")[layer] + p["b1"][layer])
    h_out = h2 + ff @ deq("w2")[layer] + p["b2"][layer]
    if not with_q:
        return h_out
    lq = layer + 1 if lq is None else lq
    hn1 = ln(h_out, p["l1s"][lq], p["l1b"][lq])
    return h_out, hn1 @ deq("wq")[lq] + p["bq"][lq]


def _call_tail(att, h, p, scales, layer, *, stacked=True, with_q=True,
               lq=None, interpret=True, out_dtype=jnp.float32,
               activation="gelu"):
    j = jnp.asarray

    def pick(a, l):
        return j(a) if stacked else j(a[l])

    L = p["wo"].shape[0]
    lq = (layer + 1 if lq is None else lq)
    lq = min(lq, L - 1)
    nq = None
    if with_q:
        nq = dict(w=pick(p["wq"], lq), b=pick(p["bq"], lq),
                  ln_s=pick(p["l1s"], lq), ln_b=pick(p["l1b"], lq))
        if scales:
            nq["s"] = pick(scales["sq"], lq)
        if stacked:
            nq["layer"] = lq
    return stream_layer_tail(
        j(att), j(h), pick(p["wo"], layer), pick(p["w1"], layer),
        pick(p["w2"], layer), layer=layer if stacked else None,
        bo=pick(p["bo"], layer), b1=pick(p["b1"], layer),
        b2=pick(p["b2"], layer), ln2_scale=pick(p["l2s"], layer),
        ln2_bias=pick(p["l2b"], layer), epsilon=EPS,
        activation=activation,
        so=pick(scales["so"], layer) if scales else None,
        s1=pick(scales["s1"], layer) if scales else None,
        s2=pick(scales["s2"], layer) if scales else None,
        next_qkv=nq, out_dtype=out_dtype, interpret=interpret)


class TestGroupedKernelParity:
    """Interpret-mode fused-tail kernel vs the per-projection numpy
    reference (contract 1)."""

    @pytest.mark.parametrize("stacked", [True, False])
    def test_f32_matches_reference_every_layer(self, stacked):
        rng = np.random.RandomState(0)
        L, Ka, d, dff, Nq = 3, 128, 256, 512, 384
        p, _ = _mk(rng, L, Ka, d, dff, Nq)
        att = rng.randn(8, Ka).astype(np.float32)
        h = rng.randn(8, d).astype(np.float32)
        for l in range(L - 1):
            hk, qk = _call_tail(att, h, p, {}, l, stacked=stacked)
            hr, qr = _ref_tail(att, h, p, {}, l)
            np.testing.assert_allclose(np.asarray(hk), hr, rtol=2e-5,
                                       atol=2e-5)
            np.testing.assert_allclose(np.asarray(qk), qr, rtol=2e-5,
                                       atol=2e-5)

    def test_bf16_within_bf16_tolerance(self):
        rng = np.random.RandomState(1)
        L, Ka, d, dff, Nq = 2, 128, 256, 512, 384
        p, _ = _mk(rng, L, Ka, d, dff, Nq)
        pb = {n: (jnp.asarray(a).astype(jnp.bfloat16)
                  if a.ndim == 3 else a) for n, a in p.items()}
        att = jnp.asarray(rng.randn(16, Ka).astype(np.float32)) \
            .astype(jnp.bfloat16)
        h = jnp.asarray(rng.randn(16, d).astype(np.float32)) \
            .astype(jnp.bfloat16)
        hk, qk = _call_tail(np.asarray(att, np.float32),
                            np.asarray(h, np.float32),
                            {n: np.asarray(a, np.float32)
                             for n, a in pb.items()}, {}, 0)
        # run the real bf16 operands through the kernel too
        hkb, qkb = stream_layer_tail(
            att, h, pb["wo"], pb["w1"], pb["w2"], layer=0,
            bo=jnp.asarray(p["bo"]), b1=jnp.asarray(p["b1"]),
            b2=jnp.asarray(p["b2"]), ln2_scale=jnp.asarray(p["l2s"]),
            ln2_bias=jnp.asarray(p["l2b"]), epsilon=EPS,
            activation="gelu",
            next_qkv=dict(w=pb["wq"], b=jnp.asarray(p["bq"]),
                          ln_s=jnp.asarray(p["l1s"]),
                          ln_b=jnp.asarray(p["l1b"]), layer=1),
            out_dtype=jnp.float32, interpret=True)
        # bf16 weights: parity vs the f32 run within bf16 resolution
        np.testing.assert_allclose(np.asarray(hkb), np.asarray(hk),
                                   rtol=0.1, atol=0.2)
        np.testing.assert_allclose(np.asarray(qkb), np.asarray(qk),
                                   rtol=0.1, atol=0.2)

    @pytest.mark.parametrize("stacked", [True, False])
    def test_int8_weight_only_matches_dequant_reference(self, stacked):
        """int8 (and thus the a8w8 stack's grouped form — same
        weights+scales; grouped runs weight-only math by design)."""
        rng = np.random.RandomState(2)
        L, Ka, d, dff, Nq = 2, 128, 256, 256, 384
        p, scales = _mk(rng, L, Ka, d, dff, Nq, int8=True)
        att = rng.randn(8, Ka).astype(np.float32)
        h = rng.randn(8, d).astype(np.float32)
        hk, qk = _call_tail(att, h, p, scales, 0, stacked=stacked)
        hr, qr = _ref_tail(att, h, p, scales, 0)
        np.testing.assert_allclose(np.asarray(hk), hr, rtol=2e-4,
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(qk), qr, rtol=2e-4,
                                   atol=2e-4)

    def test_ragged_n_takes_fallback_and_matches_reference(self):
        """dff/d not 128-multiples -> XLA fallback, same math."""
        rng = np.random.RandomState(3)
        L, Ka, d, dff, Nq = 2, 96, 80, 72, 48
        p, _ = _mk(rng, L, Ka, d, dff, Nq)
        att = rng.randn(5, Ka).astype(np.float32)
        h = rng.randn(5, d).astype(np.float32)
        hk, qk = _call_tail(att, h, p, {}, 0, interpret=None)
        hr, qr = _ref_tail(att, h, p, {}, 0)
        np.testing.assert_allclose(np.asarray(hk), hr, rtol=2e-5,
                                   atol=2e-5)
        np.testing.assert_allclose(np.asarray(qk), qr, rtol=2e-5,
                                   atol=2e-5)

    def test_traced_layer_index_under_jit(self):
        rng = np.random.RandomState(4)
        L, Ka, d, dff, Nq = 3, 128, 128, 256, 128
        p, _ = _mk(rng, L, Ka, d, dff, Nq)
        att = rng.randn(8, Ka).astype(np.float32)
        h = rng.randn(8, d).astype(np.float32)
        j = jnp.asarray

        @jax.jit
        def f(l):
            nq = dict(w=j(p["wq"]), b=j(p["bq"]), ln_s=j(p["l1s"]),
                      ln_b=j(p["l1b"]),
                      layer=jnp.minimum(l + 1, L - 1))
            return stream_layer_tail(
                j(att), j(h), j(p["wo"]), j(p["w1"]), j(p["w2"]),
                layer=l, bo=j(p["bo"]), b1=j(p["b1"]), b2=j(p["b2"]),
                ln2_scale=j(p["l2s"]), ln2_bias=j(p["l2b"]),
                epsilon=EPS, activation="gelu", next_qkv=nq,
                out_dtype=jnp.float32, interpret=True)

        for l in range(L - 1):
            hk, qk = f(jnp.asarray(l, jnp.int32))
            hr, qr = _ref_tail(att, h, p, {}, l)
            np.testing.assert_allclose(np.asarray(hk), hr, rtol=2e-5,
                                       atol=2e-5)
            np.testing.assert_allclose(np.asarray(qk), qr, rtol=2e-5,
                                       atol=2e-5)

    def test_odd_batch_pads_to_sublane(self):
        rng = np.random.RandomState(5)
        p, _ = _mk(rng, 1, 128, 128, 256, 128)
        att = rng.randn(3, 128).astype(np.float32)
        h = rng.randn(3, 128).astype(np.float32)
        hk = _call_tail(att, h, p, {}, 0, with_q=False)
        hr = _ref_tail(att, h, p, {}, 0, with_q=False)
        assert hk.shape == (3, 128)
        np.testing.assert_allclose(np.asarray(hk), hr, rtol=2e-5,
                                   atol=2e-5)

    def test_guards(self):
        rng = np.random.RandomState(6)
        p, scales = _mk(rng, 2, 128, 128, 256, 128, int8=True)
        att = jnp.ones((4, 128))
        h = jnp.ones((4, 128))
        with pytest.raises(ValueError, match="all of so/s1/s2"):
            stream_layer_tail(
                att, h, jnp.asarray(p["wo"]), jnp.asarray(p["w1"]),
                jnp.asarray(p["w2"]), layer=0,
                bo=jnp.asarray(p["bo"]), b1=jnp.asarray(p["b1"]),
                b2=jnp.asarray(p["b2"]),
                ln2_scale=jnp.asarray(p["l2s"]),
                ln2_bias=jnp.asarray(p["l2b"]), epsilon=EPS,
                so=jnp.asarray(scales["so"]))
        with pytest.raises(ValueError, match="stacked"):
            stream_layer_tail(
                att, h, jnp.asarray(p["wo"]), jnp.asarray(p["w1"][0]),
                jnp.asarray(p["w2"]), layer=0,
                bo=jnp.asarray(p["bo"]), b1=jnp.asarray(p["b1"]),
                b2=jnp.asarray(p["b2"]),
                ln2_scale=jnp.asarray(p["l2s"]),
                ln2_bias=jnp.asarray(p["l2b"]), epsilon=EPS)


def _tiny_stack(L=3, d=32, heads=4, dff=64, moe=None):
    from paddle_tpu.incubate.nn.fused_transformer import (
        FusedMultiTransformer, PagedKV, rope_table)

    paddle.seed(11)
    st = FusedMultiTransformer(d, heads, dff, L, max_position=64,
                               moe_num_experts=moe)
    cos, sin = rope_table(64, st.head_dim)
    npages = 4
    cache = PagedKV(
        jnp.zeros((L * npages, heads, 4, st.head_dim)),
        jnp.zeros((L * npages, heads, 4, st.head_dim)))
    tables = jnp.asarray(
        np.arange(2 * 2, dtype=np.int32).reshape(2, 2))
    lens = jnp.asarray(np.array([3, 5], np.int32))
    return st, cache, tables, lens, cos, sin


def _count_streamed(decode):
    """Python-level calls of the two streamed entry points while
    ``decode()`` traces (the fori_loop body traces once, so these ARE
    the per-layer counts plus the loop prologue)."""
    import paddle_tpu.nn.functional.stream_linear as sl

    calls = {"linear": 0, "tail": 0}
    orig_lin, orig_tail = sl.stream_linear, sl.stream_layer_tail

    def lin(*a, **k):
        calls["linear"] += 1
        return orig_lin(*a, **k)

    def tail(*a, **k):
        calls["tail"] += 1
        return orig_tail(*a, **k)

    sl.stream_linear, sl.stream_layer_tail = lin, tail
    try:
        out = decode()
    finally:
        sl.stream_linear, sl.stream_layer_tail = orig_lin, orig_tail
    return calls, out


class TestCallStructure:
    """Contract 2: the grouped loop's TRACE issues ONE streamed weight
    matmul call per transformer layer (the fused tail, which also
    computes the next layer's QKV) plus the one loop-prologue QKV
    call."""

    def test_prefetch_on_one_streamed_call_per_layer(self):
        st, cache, tables, lens, cos, sin = _tiny_stack()
        calls, (h, _) = _count_streamed(lambda: st.decode_raw(
            st._stack(), jnp.ones((2, 32)), cache, tables, lens, cos,
            sin))
        assert np.isfinite(np.asarray(h)).all()
        # fori_loop body: 1 fused tail, 0 standalone QKV (carried);
        # prologue: 1 QKV stream outside the loop
        assert calls == {"tail": 1, "linear": 1}


@pytest.mark.parametrize("case,loop,tails,linears", [
    # grouped: the prologue's QKV stream + ONE fused tail a traced body
    ("dense", "grouped", 1, 1),
    ("int8_weights", "grouped", 1, 1),
    # layerwise: four act-quant streams a layer / XLA dots + the bank
    ("a8w8", "layerwise", 0, 4),
    ("moe", "layerwise", 0, 0)])
def test_decode_loop_choice(case, loop, tails, linears):
    """Which loop ``decode_raw`` runs follows from the stack and the
    call (``decode_loop``), by the streamed calls its trace issues; the
    Python-unrolled list form went with the flags."""
    st, cache, tables, lens, cos, sin = _tiny_stack(
        moe=4 if case == "moe" else None)
    if case in ("int8_weights", "a8w8"):
        st.quantize_weight_only_int8()
    kw = dict(a8w8=case == "a8w8")
    assert st.decode_loop(**kw) == loop
    x = jnp.asarray(np.random.RandomState(0).randn(2, 32), jnp.float32)
    calls, (h, cache2) = _count_streamed(lambda: st.decode_raw(
        st._stack(), x, cache, tables, lens, cos, sin, **kw))
    assert calls == {"tail": tails, "linear": linears}
    assert np.isfinite(np.asarray(h)).all()
    # the step wrote each row's token into every layer's region
    assert np.asarray(cache2.k).any(axis=(1, 2, 3)).sum() == 2 * 3
    per_layer = [{n: a[l] for n, a in st._stack().items()}
                 for l in range(3)]
    with pytest.raises(TypeError, match="STACKED weight dict"):
        st.decode_raw(per_layer, x, cache, tables, lens, cos, sin, **kw)


def _two_loops(weights=None):
    """(hidden, K pool) of one decode step through the grouped and the
    layerwise loop of the same stack, same operands."""
    from paddle_tpu.nn.functional.paged_attention import (
        plan_decode_attention)

    st, cache, tables, lens, cos, sin = _tiny_stack()
    w = weights(st) if weights else st._stack()
    plan = plan_decode_attention(cache.k, tables, lens,
                                 st._pages_per_layer(cache))
    x = jnp.ones((2, 32)) * 0.1
    out = []
    for loop in (st._loop_grouped, st._loop_layerwise):
        h, cache2 = loop(w, x, cache, plan, cos, sin)
        out.append((np.asarray(h), np.asarray(cache2.k)))
    return out


class TestDecodeParity:
    """Contract 3: the grouped and the layerwise loop agree."""

    def test_stacked_grouped_matches_ungrouped_f32(self):
        (h1, k1), (h0, k0) = _two_loops()
        np.testing.assert_allclose(h1, h0, rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(k1, k0, rtol=1e-5, atol=1e-6)

    def test_int8_grouped_matches_ungrouped_stream(self):
        def quant(st):
            st.quantize_weight_only_int8()
            return st._stack()

        (h1, _), (h0, _) = _two_loops(quant)
        np.testing.assert_allclose(h1, h0, rtol=2e-3, atol=2e-3)

    def test_a8w8_stays_layerwise_and_launches_no_tail(self):
        st, cache, tables, lens, cos, sin = _tiny_stack()
        st.quantize_weight_only_int8()
        assert st.decode_loop(a8w8=True) == "layerwise"
        calls, (h, _) = _count_streamed(lambda: st.decode_raw(
            st._stack(), jnp.ones((2, 32)), cache, tables, lens, cos,
            sin, a8w8=True))
        # A8W8 keeps the act-quant kernel: four streamed projections a
        # layer, no fused tail
        assert calls == {"tail": 0, "linear": 4}
        assert np.isfinite(np.asarray(h)).all()


class TestEngineParity:
    """Engine-level greedy-token parity of the two loops (fp32 on CPU —
    the grouped fallback mirrors the layerwise math op-for-op, so the
    token sequences must be identical)."""

    def _gen(self):
        from paddle_tpu.inference import FusedCausalLM

        paddle.seed(7)
        return FusedCausalLM(vocab_size=64, embed_dim=32, num_heads=4,
                             dim_feedforward=64, num_layers=2,
                             max_position=128)

    def test_generate_tokens_identical(self, monkeypatch):
        from paddle_tpu.incubate.nn.fused_transformer import (
            FusedMultiTransformer)
        from paddle_tpu.inference import GenerationEngine

        rng = np.random.RandomState(3)
        ids = rng.randint(0, 64, (2, 6))

        def gen():
            eng = GenerationEngine(self._gen(), page_size=4,
                                   max_length=64)
            return eng.generate(ids, max_new_tokens=8)

        grouped = gen()
        # the same engine with its stack stepping the layerwise loop
        monkeypatch.setattr(
            FusedMultiTransformer, "_loop_grouped",
            lambda self, *a, psum_axis=None, overlap=None:
            self._loop_layerwise(*a))
        np.testing.assert_array_equal(gen(), grouped)

    def test_grouped_engine_reports_grouped_rung(self):
        from paddle_tpu.inference import GenerationEngine

        eng = GenerationEngine(self._gen(), page_size=4, max_length=64)
        assert eng.model.stack.decode_loop() == "grouped"
        assert eng._decode_tag == "decode.f32_grouped"
        assert eng._decode_rung(8) == "decode.f32_grouped[k=8]"


class TestBenchGateRungs:
    def test_grouped_rung_metrics_gated_down(self):
        import tools.bench_gate as bg

        assert bg.DEFAULT_METRICS[
            "decode_bf16_grouped_tokens_per_sec"] == "down"
        assert bg.DEFAULT_METRICS[
            "decode_bf16_grouped_pct_of_hbm_roofline"] == "down"
        prev = {"decode_bf16_grouped_tokens_per_sec": 5000.0,
                "decode_bf16_grouped_pct_of_hbm_roofline": 52.0}
        cur = {"decode_bf16_grouped_tokens_per_sec": 3400.0,
               "decode_bf16_grouped_pct_of_hbm_roofline": 35.0}
        bad, compared = bg.gate(prev, cur)
        assert compared >= 2 and len(bad) == 2
        bad, _ = bg.gate(prev, dict(prev))
        assert not bad

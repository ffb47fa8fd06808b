"""A window / full model (``"window_attention"`` and packed ``"attention"``
layers over a leading dense layer and sigmoid-routed experts, an untied
head) behind the serving engines: the layer-pattern description, the pool of
packed K|V rows and the slot ring in the cache manager, a ring that stays
``window`` rows a slot whatever the sequence's length, preemption by
recompute, the counters by a hand count, and the typed refusals of
everything that moves, shares or re-types K/V pages."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn.layer_pattern import (
    ATTENTION, MAMBA, SLOT_RING, WINDOW, AttentionSpec, LayerPattern,
    MambaSpec, MoESpec)
from paddle_tpu.inference.engine import (ContinuousBatchingEngine,
                                         WindowCacheUnsupported)
from paddle_tpu.inference.hybrid import HybridCausalLM
from paddle_tpu.inference.kv_cache import BlockKVCacheManager
from paddle_tpu.nn.functional.mixed_attention import PackedKV, WindowRing
from paddle_tpu.profiler import stats
from paddle_tpu.serving import ServingEngine, SLOConfig

VOCAB, WIN = 96, 8
A, W = ATTENTION, WINDOW


def specs(**kw):
    full = AttentionSpec(4, 2, 24, None, 5e5, v_head_dim=16, rope_dim=8,
                         value_scale=0.7)
    win = AttentionSpec(4, 2, 24, None, 1e4, v_head_dim=16, rope_dim=8,
                        window=WIN, sink=True, value_scale=0.7)
    return full, win


def pattern(**kw):
    full, win = specs()
    kw.setdefault("lead", (A, W))
    kw.setdefault("period", (A, W, W))
    kw.setdefault("n_periods", 1)
    return LayerPattern(
        d_model=64, attention=full, window_attention=win, d_ff=48,
        dense_lead=1, moe=MoESpec(8, 2, 16, experts_held=(0, 4),
                                  routing="sigmoid_bias"),
        norm="rmsnorm", gated=True, bias=False, activation="silu",
        epsilon=1e-5, tie_embeddings=False, **kw)


def model(seed=3, **kw):
    paddle.seed(seed)
    m = HybridCausalLM(VOCAB, pattern(**kw))
    st = m.stack
    for n in ("qkv_weight", "out_weight", "w_qkv", "w_out", "d_w1", "d_w2",
              "e_w1", "e_w2", "f_router"):
        p = getattr(st, n)
        p._rebind(p._data * 8.0)
    st.w_sink._rebind(jnp.full(st.w_sink._data.shape, 1.5, jnp.float32))
    st.e_bias._rebind(jnp.linspace(-0.2, 0.2, 8, dtype=jnp.float32)[None]
                      .repeat(st.e_bias._data.shape[0], 0))
    m.embed._rebind(m.embed._data * 50.0)
    return m


def engine(m, **kw):
    kw.setdefault("slo", SLOConfig(prefill_chunk=32))
    return ServingEngine(m, max_batch=kw.pop("max_batch", 2), page_size=4,
                         max_length=kw.pop("max_length", 160),
                         decode_chunk=4, prompt_bucket=8, **kw)


def serve(eng, prompts, n=10):
    ids = [eng.submit(list(p), max_new_tokens=n) for p in prompts]
    done = {r.id: r for r in eng.run()}
    assert all(done[i].state == "ok" for i in ids)
    return [list(done[i].generated) for i in ids]


def prompts(*lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n) for n in lens]


# ------------------------------------------------------- description

def test_the_window_kind_in_a_pattern():
    p = pattern()
    assert p.kinds() == (A, W, A, W, W) and p.num_layers == 5
    assert (p.n_attention, p.n_window, p.n_moe, p.dense_lead) == (2, 3, 4, 1)
    assert [p.kind_index(l) for l in range(5)] == [0, 0, 1, 1, 2]
    assert [p.moe_index(l) for l in range(1, 5)] == [0, 1, 2, 3]
    assert p.paged_kind == A and p.n_paged == 2
    assert p.pool_row_width == 128          # 2 x (24 + 16) = 80 -> 128
    ring = p.window_ring
    assert (ring.layers, ring.heads, ring.rows, ring.k_width,
            ring.v_width) == (3, 2, WIN, 128, 16)
    assert p.recurrent is None
    # depth cuts the last period
    assert pattern(depth=4).kinds() == (A, W, A, W)
    # one pattern of the uniform kind is what it was
    u = LayerPattern.uniform_attention(64, 2, 4, 4, 16, 128)
    assert u.kinds() == (A, A) and u.pool_row_width is None \
        and u.window_ring is None


def test_what_no_engine_can_hold_is_refused():
    full, win = specs()
    moe = MoESpec(8, 2, 16)
    with pytest.raises(ValueError, match="window"):
        LayerPattern(d_model=64, period=(A, W), n_periods=1, attention=full,
                     moe=moe)
    with pytest.raises(ValueError, match="two slot"):
        LayerPattern(d_model=64, period=(W, MAMBA), n_periods=1,
                     window_attention=win, moe=moe,
                     mamba=MambaSpec(4, 16, 8))
    with pytest.raises(ValueError, match="depth"):
        pattern(depth=9)
    with pytest.raises(ValueError, match="dense_lead"):
        LayerPattern(d_model=64, period=(A,), n_periods=2, attention=full,
                     moe=moe, dense_lead=1)
    with pytest.raises(ValueError, match="routing"):
        LayerPattern(d_model=64, period=(A,), n_periods=1, attention=full,
                     moe=MoESpec(8, 2, 16, routing="softmax_all"))


def test_the_stack_holds_a_stack_a_kind():
    st = model().stack
    assert st.qkv_weight._data.shape == (2, 64, 4 * 24 + 2 * 24 + 2 * 16)
    assert st.out_weight._data.shape == (2, 4 * 16, 64)
    assert st.w_qkv._data.shape == (3, 64, 4 * 24 + 2 * 24 + 2 * 16)
    assert st.w_sink._data.shape == (3, 4)
    assert st.d_w1._data.shape == (1, 64, 96)
    assert st.e_w1._data.shape[:2] == (4, 4) \
        and st.f_router._data.shape == (4, 64, 8) \
        and st.e_bias._data.shape == (4, 8)
    assert st.f_norm._data.shape == (5, 64)


def test_a_model_built_without_draws_has_the_drawn_shapes():
    """``draw=False``, for a caller that loads its own weights: every drawn
    parameter starts as zeros, of the shape and type a drawn one has; the
    norms start as ones, as always."""
    paddle.seed(3)
    drawn = HybridCausalLM(VOCAB, pattern())
    bare = HybridCausalLM(VOCAB, pattern(), draw=False)
    assert bare.stack._names == drawn.stack._names
    for n in drawn.stack._names:
        a, b = getattr(drawn.stack, n)._data, getattr(bare.stack, n)._data
        assert a.shape == b.shape and a.dtype == b.dtype
    for p in (bare.stack.e_w1, bare.stack.w_qkv, bare.embed, bare.head):
        assert p._data.shape and not np.any(np.asarray(p._data))
    assert np.any(np.asarray(drawn.stack.e_w1._data))
    assert np.all(np.asarray(bare.stack.f_norm._data) == 1.0)


def test_the_managers_packed_pool_and_ring():
    ring = pattern().window_ring
    mgr = BlockKVCacheManager(2, 1, 128, page_size=4, num_pages=32,
                              dtype=jnp.bfloat16, reserve_scratch=True,
                              packed=True, window=ring, slots=3)
    assert mgr.slot_state == pattern().slot_state == SLOT_RING
    cache = mgr.fresh_cache()
    assert isinstance(cache, PackedKV) \
        and cache.rows.shape == (2 * 32, 4, 128)
    assert mgr.page_hbm_bytes() == 2 * 4 * 128 * 2
    r = mgr.fresh_recurrent_state()
    assert isinstance(r, WindowRing)
    assert r.k.shape == (3, 3, 2, WIN, 128) and r.v.shape == (3, 3, 2, WIN, 16)
    assert ring.bytes_per_slot() == 3 * 2 * WIN * (128 + 16) * 2
    with pytest.raises(NotImplementedError, match="packed"):
        BlockKVCacheManager(2, 1, 128, dtype="int8", packed=True)


def test_the_ring_is_window_rows_a_slot_whatever_the_length():
    """The ring's shape follows slots and the window alone: an engine
    for sequences 8 times as long holds the same ring, and serving a
    sequence 15 times the window leaves it as it was."""
    m = model()
    short, long = engine(m, max_length=40), engine(m, max_length=320)
    assert short._rs.k.shape == long._rs.k.shape == (3, 2, 2, WIN, 128)
    assert long._ck.shape[0] == 2 * long._mgr.num_pages
    serve(long, prompts(100, seed=3), n=20)
    assert long._rs.k.shape == (3, 2, 2, WIN, 128)
    gauge = stats.snapshot("serving.window")["gauges"]
    assert gauge["serving.window.ring_bytes"] \
        == pattern().window_ring.bytes_per_slot(itemsize=4) * 2


# ------------------------------------------------------------ serving

def test_tokens_do_not_depend_on_the_company():
    """A request served alone, beside another, and in a slot whose ring a
    longer sequence filled before: the same tokens (a ring row's position
    is the sequence's own; rows of the last occupant are masked)."""
    m = model()
    a, b, c = prompts(37, 21, 50, seed=1)
    alone = serve(engine(m), [a], n=12)[0]
    assert serve(engine(m), [b, a], n=12)[1] == alone
    eng = engine(m, max_batch=1)
    assert serve(eng, [c, a], n=12)[1] == alone


def test_the_sink_the_window_and_the_value_scale_reach_the_tokens():
    p, = prompts(60, seed=2)
    m = model()
    base = serve(engine(m), [p], n=12)[0]
    m.stack.w_sink._rebind(m.stack.w_sink._data - 30.0)
    assert serve(engine(m), [p], n=12)[0] != base


def test_preemption_by_recompute_gives_the_same_tokens():
    p, q = prompts(30, 26, seed=5)
    m = model()
    want = serve(engine(m), [p, q], n=12)
    eng = engine(m)
    ids = [eng.submit(list(x), max_new_tokens=12) for x in (p, q)]
    while not any(r is not None and len(r.generated) >= 5
                  for r in eng._slots):
        eng.step()
    stats.reset()
    victim = next(i for i, r in enumerate(eng._slots) if r is not None)
    eng._preempt_slot(victim)
    done = {r.id: r for r in eng.run()}
    assert [list(done[i].generated) for i in ids] == want
    assert stats.snapshot("serving")["counters"]["serving.preemptions"] == 1


def test_the_counters_by_a_hand_count():
    """One request of 10 prompt tokens and 9 new ones beside an idle slot:
    2 full layers, 3 window layers of 8 rows. The prompt is ONE chunk at
    position 0: 55 causal pairs a full layer, 1 + .. + 8 + 2 x 8 = 52
    windowed pairs a window layer. Two decode chunks of 4 steps at 10..13
    and 14..17 cached tokens read 46 + 62 packed rows a full layer; the
    window kernel reads both slots' 8 rows a step and window layer."""
    eng = engine(model(), slo=SLOConfig(prefill_chunk=32,
                                        prefix_cache=False))
    stats.reset()
    rid = eng.submit(list(prompts(10, seed=1)[0]), max_new_tokens=9)
    done = {r.id: r for r in eng.run()}
    assert done[rid].state == "ok" and len(done[rid].generated) == 9
    snap = stats.snapshot("serving")["counters"]
    assert snap["serving.decode_steps"] == 8
    assert snap["serving.attn.prefill_pairs"] == 55 * 2
    assert snap["serving.window.prefill_pairs"] == 52 * 3
    assert snap["serving.attn.rows_read"] == (46 + 62) * 2
    assert snap["serving.window.rows_read"] == 8 * 2 * WIN * 3
    assert snap["serving.kv.pages_walked"] == (13 + 3 + 17 + 3) * 2
    assert snap["serving.moe.picks"] > 0
    assert "serving.mla.rows_read" not in snap
    # a prompt of two chunks: the second one's rows see 8 positions each
    stats.reset()
    serve(eng, prompts(40, seed=2), n=1)
    snap = stats.snapshot("serving")["counters"]
    assert snap["serving.attn.prefill_pairs"] == 40 * 41 // 2 * 2
    assert snap["serving.window.prefill_pairs"] \
        == (36 + 32 * 8) * 3


# ----------------------------------------------------------- refusals

def _refused(fn):
    n0 = stats.snapshot("serving.window").get("counters", {}).get(
        "serving.window.refusals", 0)
    with pytest.raises(WindowCacheUnsupported):
        fn()
    n1 = stats.snapshot("serving.window")["counters"][
        "serving.window.refusals"]
    assert n1 == n0 + 1


def test_prefix_reuse_is_refused_at_construction():
    m = model()
    _refused(lambda: engine(m, slo=SLOConfig(prefix_cache=True,
                                             prefill_chunk=32)))
    assert engine(m, slo=SLOConfig(prefill_chunk=32)).prefix_cache is None


def test_speculative_verify_and_the_int8_pool_are_refused():
    m = model()
    _refused(lambda: ContinuousBatchingEngine(
        m, max_batch=2, page_size=4, max_length=64, speculative="self"))
    _refused(lambda: engine(m, kv_dtype="int8"))


def test_slot_migration_page_streaming_and_the_host_tier_are_refused():
    eng = engine(model())
    assert not eng.can_migrate() and not eng.can_spill()
    _refused(lambda: eng.export_slot(0))
    _refused(lambda: eng.import_slot(0, {"n_pages": 1}))
    _refused(lambda: eng.export_pages(0, 0, 1))
    _refused(lambda: eng.import_begin(1))
    _refused(lambda: eng.export_kv_pages([1]))
    _refused(lambda: eng.import_kv_pages([1], {"n_pages": 1}))
    with pytest.raises(NotImplementedError, match="slot-indexed ring"):
        eng.export_slot(0)

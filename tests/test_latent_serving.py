"""A latent-attention model (``"latent_attention"`` layers over routed
experts, an untied head) behind the serving engines: the layer-pattern
description, the ONE-array latent pool and its geometry in the cache
manager, growth and preemption by recompute, the two ``serving.mla.*``
counters by a hand count, and the typed refusals of everything that moves,
shares or re-types K/V pages."""
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn.layer_pattern import (
    ATTENTION, LATENT, AttentionSpec, LatentAttentionSpec, LayerPattern,
    MoESpec, YarnSpec)
from paddle_tpu.inference.engine import (ContinuousBatchingEngine,
                                         LatentPoolUnsupported)
from paddle_tpu.inference.hybrid import HybridCausalLM
from paddle_tpu.inference.kv_cache import BlockKVCacheManager
from paddle_tpu.nn.functional.mla_attention import LatentKV
from paddle_tpu.profiler import stats
from paddle_tpu.serving import ServingEngine, SLOConfig

VOCAB = 96


def spec(**kw):
    kw.setdefault("yarn", YarnSpec(8.0, 16, 4.0, 1.0, 1.0, 1.0))
    kw.setdefault("temperature_beta", 0.3)
    kw.setdefault("temperature_period", 16)
    return LatentAttentionSpec(4, 32, 32, 8, 8, 16, **kw)


def pattern(layers=2, **kw):
    return LayerPattern(
        d_model=64, period=(LATENT,), n_periods=layers, latent=spec(**kw),
        moe=MoESpec(8, 2, 32, shared_dim=32, experts_held=(0, 4)),
        norm="rmsnorm", gated=True, bias=False, activation="silu",
        epsilon=1e-6, tie_embeddings=False)


def model(seed=3, **kw):
    paddle.seed(seed)
    m = HybridCausalLM(VOCAB, pattern(**kw))
    st = m.stack
    for n in ("l_dq", "l_uq", "l_dkv", "l_uk", "l_uv", "l_o", "e_w1",
              "e_w2", "s_w1", "s_w2", "f_router"):
        p = getattr(st, n)
        p._rebind(p._data * 8.0)
    m.embed._rebind(m.embed._data * 50.0)
    return m


def engine(m, **kw):
    kw.setdefault("slo", SLOConfig(prefill_chunk=32))
    return ServingEngine(m, max_batch=kw.pop("max_batch", 2), page_size=4,
                         max_length=160, decode_chunk=4, prompt_bucket=8,
                         **kw)


def serve(eng, prompts, n=10):
    ids = [eng.submit(list(p), max_new_tokens=n) for p in prompts]
    done = {r.id: r for r in eng.run()}
    assert all(done[i].state == "ok" for i in ids)
    return [list(done[i].generated) for i in ids]


def prompts(*lens, seed=0):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, VOCAB, n) for n in lens]


# ------------------------------------------------------- description

def test_the_latent_kind_in_a_pattern():
    p = pattern(layers=3)
    assert (p.num_layers, p.n_latent, p.n_attention, p.n_mamba) \
        == (3, 3, 0, 0)
    assert p.paged_kind == LATENT and p.n_paged == 3
    assert p.recurrent is None and not p.tie_embeddings
    assert [p.kind_index(l) for l in range(3)] == [0, 1, 2]
    lt = p.latent
    assert (lt.row_used, lt.row_width) == (40, 128)
    m = 0.1 * np.log(8.0) + 1.0
    assert np.isclose(lt.softmax_scale, 16 ** -0.5 * m * m)
    assert lt.yarn.table_factor == 1.0
    assert spec(yarn=None).softmax_scale == 0.25
    # the published widths: a row stores 384 values, 320 of them content
    real = LatentAttentionSpec(32, 1024, 256, 64, 64, 128)
    assert (real.row_used, real.row_width) == (320, 384)
    with pytest.raises(ValueError, match="LatentAttentionSpec"):
        LayerPattern(d_model=8, period=(LATENT,), n_periods=1)
    with pytest.raises(ValueError, match="two paged pools"):
        LayerPattern(d_model=8, period=(LATENT, ATTENTION), n_periods=1,
                     latent=spec(), attention=AttentionSpec(2, 2, 4))
    with pytest.raises(ValueError, match="latent_attention"):
        LayerPattern(d_model=8, period=("conv",), n_periods=1)
    uniform = LayerPattern.uniform_attention(64, 2, 4, 4, 16, 128)
    assert uniform.paged_kind == ATTENTION and uniform.n_paged == 2


def test_the_stack_holds_the_descriptions_widths():
    m = model()
    st = m.stack
    assert st.l_dkv._data.shape == (2, 64, 128)
    assert not np.asarray(st.l_dkv._data[:, :, 40:]).any()
    assert st.l_uk._data.shape == (2, 4, 8, 32)
    assert st.l_uv._data.shape == (2, 4, 32, 16)
    assert st.l_uq._data.shape == (2, 32, 4 * 16)
    assert m.head._data.shape == m.embed._data.shape == (VOCAB, 64)
    assert not hasattr(st, "qkv_weight") and not hasattr(st, "m_in")


def test_the_managers_latent_geometry():
    mgr = BlockKVCacheManager(3, 1, 128, page_size=4, num_pages=32,
                              dtype=jnp.bfloat16, reserve_scratch=True,
                              latent=True)
    cache = mgr.fresh_cache()
    assert isinstance(cache, LatentKV) and len(cache) == 1
    assert cache.rows.shape == (3 * 32, 4, 128) \
        and cache.rows.dtype == jnp.bfloat16
    # ONE array: a page is layers x page x row x 2 bytes, not twice that
    assert mgr.page_hbm_bytes() == 3 * 4 * 128 * 2
    paged = BlockKVCacheManager(3, 1, 128, page_size=4, num_pages=32,
                                dtype=jnp.bfloat16)
    assert paged.page_hbm_bytes() == 2 * mgr.page_hbm_bytes()
    pages = mgr.allocate("a", 10)
    assert len(pages) == 3 and 0 not in pages
    assert len(mgr.grow("a", 2)) == 2 and mgr.free_pages == 32 - 1 - 5
    assert mgr.truncate("a", 4) and mgr.free_pages == 32 - 1 - 1
    mgr.free("a")
    assert mgr.free_pages == 31
    with pytest.raises(NotImplementedError, match="latent pool"):
        BlockKVCacheManager(3, 1, 128, dtype="int8", latent=True)


def test_the_engine_owns_one_latent_array():
    eng = engine(model())
    assert eng._latent and eng._pattern_built and eng._rs is None
    assert eng._cv is None
    assert eng._ck.shape == (2 * eng._mgr.num_pages, 4, 128)
    assert eng._mgr.latent and eng._mgr.num_layers == 2
    assert eng.prefix_cache is None and eng.host_tier is None


# ------------------------------------------------------------ serving

def test_tokens_do_not_depend_on_the_company():
    """A request served alone, beside another, and in a slot that another
    sequence used before: the same tokens (its rows are its own pages')."""
    m = model()
    a, b, c = prompts(37, 21, 50, seed=1)
    alone = serve(engine(m), [a], n=9)[0]
    assert serve(engine(m), [b, a], n=9)[1] == alone
    eng = engine(m, max_batch=1)
    assert serve(eng, [c, a], n=9)[1] == alone


def test_the_temperature_and_the_blend_reach_the_tokens():
    p, = prompts(60, seed=2)
    base = serve(engine(model()), [p], n=12)[0]
    assert serve(engine(model(temperature_beta=None)), [p], n=12)[0] != base
    assert serve(engine(model(yarn=None)), [p], n=12)[0] != base


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


def test_the_mla_counters_by_a_hand_count():
    """One request of 10 prompt tokens and 9 new ones beside an idle slot,
    two layers. The prompt is ONE chunk at position 0: 10 x 11 / 2 = 55
    causal pairs a layer. Two decode chunks of 4 steps at 10..13 and 14..17
    cached tokens read 46 + 62 rows a layer (the idle row reads none)."""
    eng = engine(model(), slo=SLOConfig(prefill_chunk=32,
                                        prefix_cache=False))
    stats.reset()
    rid = eng.submit(list(prompts(10, seed=1)[0]), max_new_tokens=9)
    done = {r.id: r for r in eng.run()}
    assert done[rid].state == "ok" and len(done[rid].generated) == 9
    snap = stats.snapshot("serving")["counters"]
    assert snap["serving.decode_steps"] == 8
    assert snap["serving.mla.prefill_pairs"] == 55 * 2
    assert snap["serving.mla.rows_read"] == (46 + 62) * 2
    assert snap["serving.kv.pages_walked"] == (13 + 3 + 17 + 3) * 2
    assert snap["serving.moe.picks"] > 0
    # a prompt of two chunks: the second one's rows see the first one's
    stats.reset()
    serve(eng, prompts(40, seed=2), n=1)
    snap = stats.snapshot("serving")["counters"]
    assert snap["serving.mla.prefill_pairs"] \
        == (32 * 33 // 2 + 8 * 32 + 8 * 9 // 2) * 2


# ----------------------------------------------------------- refusals

def _refused(fn):
    n0 = stats.snapshot("serving.latent").get("counters", {}).get(
        "serving.latent.refusals", 0)
    with pytest.raises(LatentPoolUnsupported):
        fn()
    n1 = stats.snapshot("serving.latent")["counters"][
        "serving.latent.refusals"]
    assert n1 == n0 + 1


def test_prefix_reuse_is_refused_at_construction():
    m = model()
    _refused(lambda: engine(m, slo=SLOConfig(prefix_cache=True,
                                             prefill_chunk=32)))
    # the default turns itself off here
    assert engine(m, slo=SLOConfig(prefill_chunk=32)).prefix_cache is None


def test_speculative_verify_and_the_int8_pool_are_refused():
    m = model()
    _refused(lambda: ContinuousBatchingEngine(
        m, max_batch=2, page_size=4, max_length=64, speculative="self"))
    _refused(lambda: engine(m, kv_dtype="int8"))


def test_slot_migration_and_page_streaming_are_refused():
    eng = engine(model())
    assert not eng.can_migrate()
    _refused(lambda: eng.export_slot(0))
    _refused(lambda: eng.import_slot(0, {"n_pages": 1}))
    _refused(lambda: eng.export_pages(0, 0, 1))
    _refused(lambda: eng.import_begin(1))


def test_host_tier_spill_and_restore_are_refused():
    eng = engine(model())
    assert not eng.can_spill()
    _refused(lambda: eng.export_kv_pages([1]))
    _refused(lambda: eng.import_kv_pages([1], {"n_pages": 1}))


def test_a_refusal_is_a_not_implemented_error_that_says_why():
    with pytest.raises(NotImplementedError, match="one latent row a token"):
        engine(model()).export_slot(0)


def test_the_base_engine_points_at_chunked_prefill():
    eng = ContinuousBatchingEngine(model(), max_batch=2, page_size=4,
                                   max_length=64)
    eng.submit([1, 2, 3], max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="latent_attention"):
        eng.step()

"""A pattern-built model (Mamba-2 + NoPE attention layers over routed
experts) behind the serving engines: the layer-pattern description, the
slot-indexed recurrent state beside the paged pool (zeroed at admission
into a used slot, reset after recompute-preemption, untouched by rows that
do not decode), the description's attention scale, and the typed refusals
of what moves pages alone."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.incubate.nn.layer_pattern import (
    ATTENTION, MAMBA, AttentionSpec, LayerPattern, MambaSpec, MoESpec)
from paddle_tpu.inference import FusedCausalLM
from paddle_tpu.inference.engine import (ContinuousBatchingEngine,
                                         RecurrentStateUnsupported)
from paddle_tpu.inference.hybrid import (DECODE_PROGRAM_NAME,
                                         PREFILL_PROGRAM_NAME,
                                         HybridCausalLM)
from paddle_tpu.profiler import stats
from paddle_tpu.serving import ServingEngine, SLOConfig

VOCAB = 96


def pattern(scale=1 / 16.0, held=(0, 4)):
    return LayerPattern(
        d_model=64, period=(MAMBA, MAMBA, ATTENTION, MAMBA), n_periods=1,
        attention=AttentionSpec(4, 2, 16, scale=scale, rope_theta=None),
        mamba=MambaSpec(num_heads=8, head_dim=16, d_state=16,
                        chunk_size=16),
        moe=MoESpec(8, 3, 32, shared_dim=48, experts_held=held),
        norm="rmsnorm", gated=True, bias=False, activation="silu",
        # the embedding must not drown the layers (the head is tied:
        # at the published 12 a toy model echoes its input)
        embedding_multiplier=1.0, residual_multiplier=1.0,
        logits_scaling=16.0)


def model(seed=3, **kw):
    paddle.seed(seed)
    m = HybridCausalLM(VOCAB, pattern(**kw))
    # slow decays (dt * A between 0.002 and 0.05 a token: the state
    # remembers a hundred tokens) and matrices large enough that mixers
    # and experts move the logits
    st = m.stack
    st.m_A_log._rebind(jnp.log(jnp.linspace(0.05, 1.0, 8))[None, :]
                       .repeat(3, 0).astype(jnp.float32))
    st.m_dt_bias._rebind(jnp.full((3, 8), -3.0, jnp.float32))
    for n in ("m_in", "m_out", "qkv_weight", "out_weight", "e_w1", "e_w2",
              "s_w1", "s_w2", "f_router"):
        p = getattr(st, n)
        p._rebind(p._data * 6.0)
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

def test_layer_pattern_counts_and_indices():
    p = pattern()
    assert p.num_layers == 4 and p.n_mamba == 3 and p.n_attention == 1
    assert [p.kind_index(l) for l in range(4)] == [0, 1, 0, 2]
    r = p.recurrent
    assert (r.layers, r.d_state, r.d_inner, r.conv_rows, r.conv_dim) \
        == (3, 16, 128, 3, 160)
    assert r.bytes_per_slot() == 3 * (16 * 128 * 4 + 3 * 160 * 2)
    assert p.mamba.in_proj_dim == 128 + 160 + 8
    with pytest.raises(ValueError, match="unknown layer kinds"):
        LayerPattern(d_model=8, period=("conv",), n_periods=1)


def test_the_uniform_stack_is_the_one_kind_pattern():
    paddle.seed(0)
    m = FusedCausalLM(64, 32, 4, 64, 3, num_kv_heads=2)
    p = m.stack.pattern
    assert p.period == (ATTENTION,) and p.n_periods == 3
    assert p.n_attention == 3 and p.recurrent is None
    assert p.attention.softmax_scale == 8 ** -0.5
    assert p.norm == "layernorm" and p.bias and not p.gated
    eng = ContinuousBatchingEngine(m, max_batch=2, page_size=4,
                                   max_length=32)
    assert eng._rs is None and eng._mgr.num_layers == 3
    assert eng.can_migrate() and eng.can_spill()


def test_cache_groups_follow_the_pattern():
    eng = engine(model())
    assert eng._mgr.num_layers == 1            # pages for 1 layer in 4
    assert eng._ck.shape[0] == eng._mgr.num_pages
    assert eng._rs.ssm.shape == (3, 2, 16, 128)
    assert eng._rs.ssm.dtype == jnp.float32
    assert eng._rs.conv.shape == (3, 2, 3, 160)
    assert eng.prefix_cache is None


def test_programs_have_fixed_module_names():
    eng = engine(model())
    serve(eng, prompts(9), n=6)
    names = {p._jitted.__name__ for p in eng._chunk_jit.values()} \
        | {p._jitted.__name__ for p in eng._gen._decode_k_jit.values()}
    assert names == {PREFILL_PROGRAM_NAME, DECODE_PROGRAM_NAME}


# ------------------------------------------------- the recurrent state

def test_admission_into_a_used_slot_starts_from_zeros():
    """One slot: the second request lands where the first left its state.
    Its tokens equal those of a fresh engine."""
    a, b = prompts(21, 45, seed=1)
    m = model()
    alone = serve(engine(m, max_batch=1), [b])[0]
    eng = engine(m, max_batch=1)
    first, second = serve(eng, [a, b])
    assert second == alone
    fresh = engine(m, max_batch=1)
    serve(fresh, [b])
    np.testing.assert_allclose(eng._rs.ssm, fresh._rs.ssm, atol=1e-6)
    assert float(jnp.abs(eng._rs.ssm).max()) > 1e-3  # a state was left


def test_state_not_reset_is_caught(monkeypatch):
    """The fault: the admission's reset never reaches the program."""
    from paddle_tpu.inference.kv_cache import BlockKVCacheManager

    a, b = prompts(21, 45, seed=1)
    m = model()
    fresh = engine(m, max_batch=1)
    alone = serve(fresh, [b])[0]
    monkeypatch.setattr(BlockKVCacheManager, "recurrent_is_fresh",
                        lambda self, slot: False)
    eng = engine(m, max_batch=1)
    assert serve(eng, [a, b])[1] != alone
    assert float(jnp.abs(eng._rs.ssm - fresh._rs.ssm).max()) > 1e-3


def test_state_is_carried_across_prefill_chunks_and_decode():
    """A prompt of three chunks (the last bucket-padded) and a decode over
    two chunks == the same prompt prefilled in ONE chunk."""
    p, = prompts(70, seed=2)
    m = model()
    stats.reset()
    chunked = serve(engine(m), [p], n=9)[0]
    snap = stats.snapshot("serving.recurrent")["counters"]
    assert snap["serving.recurrent.resets"] == 1
    assert snap["serving.recurrent.resumed_chunks"] == 2
    whole = serve(engine(m, slo=SLOConfig(prefill_chunk=96)), [p], n=9)[0]
    assert chunked == whole


def test_rows_that_do_not_decode_keep_their_state():
    """A slot still prefilling while the other decodes: its carried state
    passes through the decode batch untouched (same tokens as alone)."""
    short, long_ = prompts(6, 90, seed=4)
    m = model()
    alone = serve(engine(m), [long_], n=6)[0]
    both = serve(engine(m), [short, long_], n=6)
    assert both[1] == alone


def test_recompute_preemption_resets_the_state():
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
    assert stats.snapshot("serving.recurrent")["counters"][
        "serving.recurrent.resets"] == 1


def test_attention_multiplier_is_the_descriptions_own():
    """1/16 (the description) and 1/sqrt(16) give different tokens: the
    scale is taken from the pattern, not derived from head_dim."""
    p, = prompts(40, seed=6)
    a = serve(engine(model(scale=1 / 16.0)), [p], n=12)[0]
    b = serve(engine(model(scale=None)), [p], n=12)[0]
    assert a != b
    assert pattern(scale=None).attention.softmax_scale == 0.25


def test_pick_counters_ride_with_the_tokens():
    stats.reset()
    serve(engine(model()), prompts(20, 33, seed=7), n=8)
    c = stats.snapshot("serving.moe")["counters"]
    assert c["serving.moe.picks"] > 0
    assert 0 < c["serving.moe.picks_here"] < c["serving.moe.picks"]
    assert 0 < c["serving.moe.experts_hit"] <= c["serving.moe.experts_held"]
    assert c["serving.moe.experts_held"] % 4 == 0


# ----------------------------------------------------------- refusals

def _refused(fn):
    n0 = stats.snapshot("serving.recurrent").get("counters", {}).get(
        "serving.recurrent.refusals", 0)
    with pytest.raises(RecurrentStateUnsupported):
        fn()
    n1 = stats.snapshot("serving.recurrent")["counters"][
        "serving.recurrent.refusals"]
    assert n1 == n0 + 1


def test_prefix_cache_is_refused_at_construction():
    m = model()
    _refused(lambda: engine(m, slo=SLOConfig(prefix_cache=True,
                                             prefill_chunk=32)))
    assert engine(m, slo=SLOConfig(prefix_cache=False)).prefix_cache is None
    # the default turns itself off here and stays on for a paged model
    assert SLOConfig().prefix_cache is None
    paddle.seed(0)
    paged = ServingEngine(FusedCausalLM(64, 32, 4, 64, 2), max_batch=2,
                          page_size=4, max_length=32)
    assert paged.prefix_cache is not None


def test_speculative_verify_is_refused():
    m = model()
    _refused(lambda: ContinuousBatchingEngine(
        m, max_batch=2, page_size=4, max_length=64, speculative="self"))


def test_slot_migration_is_refused():
    eng = engine(model())
    assert not eng.can_migrate()
    _refused(lambda: eng.export_slot(0))
    _refused(lambda: eng.import_slot(0, {"n_pages": 1}))


def test_host_tier_spill_is_refused():
    eng = engine(model())
    assert not eng.can_spill() and eng.host_tier is None
    _refused(lambda: eng.export_kv_pages([1]))


def test_the_base_engine_points_at_chunked_prefill():
    eng = ContinuousBatchingEngine(model(), max_batch=2, page_size=4,
                                   max_length=64)
    eng.submit([1, 2, 3], max_new_tokens=2)
    with pytest.raises(NotImplementedError, match="ServingEngine"):
        eng.step()


@pytest.mark.parametrize("kind", ["uniform", "hybrid"])
def test_page_walk_counters_count_what_the_tables_name(kind):
    """``serving.kv.pages_walked`` / ``.pages_region``: a decode step and
    attention layer, the pages the block tables name at the step's
    lengths beside the pages of the layer's region. One request of 10
    prompt tokens and 9 new ones on a page of 4, beside an idle slot:
    two decode chunks of 4 steps at 10..13 and 14..17 cached tokens name
    3+3+3+4 and 4+4+4+5 pages; the idle row's length runs 0..3 in each
    chunk and names its table's first entry (the scratch page) from 1."""
    if kind == "uniform":
        paddle.seed(0)
        m, layers = FusedCausalLM(64, 32, 4, 64, 2), 2
    else:
        m, layers = model(), 1          # one attention layer of the four
    eng = ServingEngine(m, max_batch=2, page_size=4, max_length=64,
                        decode_chunk=4, prompt_bucket=8,
                        slo=SLOConfig(prefill_chunk=32, prefix_cache=False))
    stats.reset()
    rid = eng.submit(list(prompts(10, seed=1)[0]), max_new_tokens=9)
    done = {r.id: r for r in eng.run()}
    assert done[rid].state == "ok" and len(done[rid].generated) == 9
    snap = stats.snapshot("serving")["counters"]
    assert snap["serving.decode_steps"] == 8
    assert snap["serving.kv.pages_walked"] == (13 + 3 + 17 + 3) * layers
    assert snap["serving.kv.pages_region"] == \
        eng._mgr.num_pages * layers * 8
    assert eng._mgr.num_layers == layers

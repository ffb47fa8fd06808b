"""Tensor-parallel serving (ISSUE 10): mp-sharded FusedMultiTransformer,
kv-head-sharded paged pool, per-shard weight streaming, engine plumbing.

Everything runs on the conftest virtual 8-device CPU mesh. Parity
targets: the TP path must reproduce the single-chip engine's hidden
states/logits (fp32, allclose) and its greedy token streams (exact —
both runs are deterministic, so equality is stable). Collective
discipline: the traced decode/prefill programs carry exactly ONE psum
per column→row projection pair (two per layer — the reference's
fused_multi_transformer_op.cu:220,529 ring_id allreduce points; the
sequential pre-LN math admits no fewer) and no other collective.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.distributed.tp import (TPContext, serving_mesh,
                                       split_kv_heads)
from paddle_tpu.incubate.nn.fused_transformer import (
    FusedMultiTransformer, PagedKV, rope_table)
from paddle_tpu.inference import FusedCausalLM, GenerationEngine
from paddle_tpu.inference.kv_cache import BlockKVCacheManager
from paddle_tpu.profiler import stats


def _mesh2():
    return serving_mesh(2, devices=jax.devices("cpu")[:2])


def _stack(num_kv_heads=2, d=32, H=4, dff=64, L=2):
    paddle.seed(21)
    return FusedMultiTransformer(d, H, dff, L,
                                 num_kv_heads=num_kv_heads,
                                 max_position=64)


def _pool(st, tp=None, ps=4, npages=16, n_seq=2, tokens=8):
    mgr = BlockKVCacheManager(
        st.num_layers, st.num_kv_heads, st.head_dim, ps,
        num_pages=npages, reserve_scratch=True,
        mp_degree=tp.mp if tp else 1, mesh=tp.mesh if tp else None)
    for i in range(n_seq):
        mgr.allocate(i, tokens)
    tables = mgr.block_tables(range(n_seq), tokens // ps)
    return mgr, mgr.fresh_cache(), tables


class TestSplitKVHeads:
    def test_sharded_branch(self):
        assert split_kv_heads(8, 4) == (2, 1)
        assert split_kv_heads(2, 2) == (1, 1)

    def test_replication_branch(self):
        # GQA small-kv: each kv head replicated over mp//n_kv shards
        assert split_kv_heads(2, 4) == (1, 2)
        assert split_kv_heads(1, 8) == (1, 8)

    def test_mp1_identity(self):
        assert split_kv_heads(5, 1) == (5, 1)

    def test_indivisible_raises_informative(self):
        with pytest.raises(ValueError) as e:
            split_kv_heads(3, 2)
        msg = str(e.value)
        assert "num_kv_heads=3" in msg and "mp_degree=2" in msg
        assert "replication" in msg  # names the GQA fallback

    def test_heads_divisibility_checked(self):
        with pytest.raises(ValueError, match="num_heads"):
            TPContext.create(3, 3, 8, mesh=_mesh2(), mp_degree=None)


class TestKVCacheManagerTP:
    def test_sharded_pool_shape_and_placement(self, virtual_devices):
        tp = TPContext.create(4, 2, 8, mesh=_mesh2())
        mgr = BlockKVCacheManager(2, 2, 8, 4, num_pages=8,
                                  mp_degree=2, mesh=tp.mesh)
        cache = mgr.fresh_cache()
        assert cache.k.shape == (2 * 8, 2, 4, 8)  # heads stay global
        # axis 1 sharded over mp: each device holds one kv head
        assert not cache.k.sharding.is_fully_replicated

    def test_replication_pool_grows_heads(self, virtual_devices):
        # n_kv=1, mp=2 → one replicated head per shard, pool axis1 = 2
        tp = TPContext.create(4, 1, 8, mesh=_mesh2())
        mgr = BlockKVCacheManager(2, 1, 8, 4, num_pages=8,
                                  mp_degree=2, mesh=tp.mesh)
        assert mgr.kv_heads_per_shard == 1 and mgr.kv_replication == 2
        assert mgr.fresh_cache().k.shape[1] == 2

    def test_indivisible_raises_before_any_pool(self):
        with pytest.raises(ValueError, match="num_kv_heads=3"):
            BlockKVCacheManager(2, 3, 8, 4, num_pages=8, mp_degree=2)

    def test_int8_kv_plus_mesh_rejected(self, virtual_devices):
        with pytest.raises(NotImplementedError, match="int8 cache-KV"):
            BlockKVCacheManager(2, 2, 8, 4, num_pages=8, dtype="int8",
                                mp_degree=2, mesh=_mesh2())


class TestShardMapLayerParity:
    """Column/row shard math vs the dense single-chip reference."""

    def _parity(self, num_kv_heads):
        st = _stack(num_kv_heads=num_kv_heads)
        cos, sin = rope_table(64, st.head_dim)
        w = st._stack()
        tp = TPContext.create(st.num_heads, st.num_kv_heads,
                              st.head_dim, mesh=_mesh2())
        w_tp = tp.shard_stack(w)
        rng = np.random.RandomState(3)
        x3 = jnp.asarray(rng.randn(2, 6, st.embed_dim)
                         .astype(np.float32))
        _m1, c1, t1 = _pool(st)
        _m2, c2, t2 = _pool(st, tp)
        h1, c1 = st.prefill_raw(w, x3, c1, t1, cos, sin)
        h2, c2 = st.prefill_raw(w_tp, x3, c2, t2, cos, sin, tp=tp)
        np.testing.assert_allclose(np.asarray(h1), np.asarray(h2),
                                   atol=1e-5)
        if tp.kv_replication == 1:
            # sharded pool: same global head order as the mp1 pool
            np.testing.assert_allclose(np.asarray(c1.k),
                                       np.asarray(c2.k), atol=1e-5)
        x1 = jnp.asarray(rng.randn(2, st.embed_dim).astype(np.float32))
        lens = jnp.array([6, 6], jnp.int32)
        hd1, _ = st.decode_raw(w, x1, c1, t1, lens, cos, sin)
        hd2, _ = st.decode_raw(w_tp, x1, c2, t2, lens, cos, sin, tp=tp)
        np.testing.assert_allclose(np.asarray(hd1), np.asarray(hd2),
                                   atol=1e-5)

    def test_kv_sharded_parity(self, virtual_devices):
        self._parity(num_kv_heads=2)

    def test_gqa_replication_parity(self, virtual_devices):
        # n_kv=1 < mp=2 → the kv-head-replication fallback branch
        self._parity(num_kv_heads=1)

    def test_chunked_prefill_parity(self, virtual_devices):
        st = _stack()
        cos, sin = rope_table(64, st.head_dim)
        w = st._stack()
        tp = TPContext.create(st.num_heads, st.num_kv_heads,
                              st.head_dim, mesh=_mesh2())
        w_tp = tp.shard_stack(w)
        rng = np.random.RandomState(4)
        x = jnp.asarray(rng.randn(2, 4, st.embed_dim)
                        .astype(np.float32))
        _m1, c1, t1 = _pool(st)
        _m2, c2, t2 = _pool(st, tp)
        start = jnp.zeros((2,), jnp.int32)
        clens = jnp.array([4, 3], jnp.int32)  # ragged tail row
        h1, _ = st.prefill_chunk_raw(w, x, c1, t1, start, clens,
                                     cos, sin)
        h2, _ = st.prefill_chunk_raw(w_tp, x, c2, t2, start, clens,
                                     cos, sin, tp=tp)
        # only the VALID rows are defined (pad rows are garbage)
        np.testing.assert_allclose(np.asarray(h1)[0], np.asarray(h2)[0],
                                   atol=1e-5)
        np.testing.assert_allclose(np.asarray(h1)[1, :3],
                                   np.asarray(h2)[1, :3], atol=1e-5)

    def test_weight_stacks_are_sharded_slices(self, virtual_devices):
        st = _stack()
        tp = TPContext.create(st.num_heads, st.num_kv_heads,
                              st.head_dim, mesh=_mesh2())
        w_tp = tp.shard_stack(st._stack())
        # column/row stacks are NOT replicated — each chip holds 1/mp
        for name in ("qkv_weight", "out_weight", "ffn1_weight",
                     "ffn2_weight"):
            assert not w_tp[name].sharding.is_fully_replicated, name
        # LN params and row-parallel biases are replicated
        for name in ("ln1_scale", "out_bias", "ffn2_bias"):
            assert w_tp[name].sharding.is_fully_replicated, name


class TestCollectiveCount:
    """PR-5-style trace pin: the decode program's once-traced layer
    body carries exactly one psum per column→row projection pair (2
    total: O-proj + FFN2) and no other collective primitive."""

    def _seq(self, fn, *args):
        from paddle_tpu.analysis import trace_census

        return trace_census(fn, *args)

    def test_decode_psums_per_layer(self, virtual_devices):
        st = _stack()
        cos, sin = rope_table(64, st.head_dim)
        tp = TPContext.create(st.num_heads, st.num_kv_heads,
                              st.head_dim, mesh=_mesh2())
        w_tp = tp.shard_stack(st._stack())
        _m, cache, tables = _pool(st, tp)
        lens = jnp.array([6, 6], jnp.int32)
        x = jnp.ones((2, st.embed_dim), jnp.float32)

        def fn(w, xb, ck, cv):
            h, c2 = st.decode_raw(w, xb, PagedKV(ck, cv), tables,
                                  lens, cos, sin, tp=tp)
            return h, c2.k, c2.v

        seq = self._seq(fn, w_tp, x, cache.k, cache.v)
        assert seq == [("psum", "('mp',)")] * 2, seq

    def test_chunk_prefill_psums_per_layer(self, virtual_devices):
        st = _stack()
        cos, sin = rope_table(64, st.head_dim)
        tp = TPContext.create(st.num_heads, st.num_kv_heads,
                              st.head_dim, mesh=_mesh2())
        w_tp = tp.shard_stack(st._stack())
        _m, cache, tables = _pool(st, tp)
        x = jnp.ones((2, 4, st.embed_dim), jnp.float32)
        start = jnp.zeros((2,), jnp.int32)
        clens = jnp.full((2,), 4, jnp.int32)

        def fn(w, xb, ck, cv):
            h, c2 = st.prefill_chunk_raw(
                w, xb, PagedKV(ck, cv), tables, start, clens, cos,
                sin, tp=tp)
            return h, c2.k, c2.v

        seq = self._seq(fn, w_tp, x, cache.k, cache.v)
        assert seq == [("psum", "('mp',)")] * 2, seq


class TestEngineTP:
    def _model(self):
        paddle.seed(7)
        return FusedCausalLM(vocab_size=64, embed_dim=32, num_heads=4,
                             dim_feedforward=64, num_layers=2,
                             max_position=128)

    def test_generate_token_parity_mp2(self, virtual_devices):
        rng = np.random.RandomState(3)
        ids = rng.randint(0, 64, (2, 6))
        out1 = GenerationEngine(self._model(), page_size=4,
                                max_length=64).generate(
            ids, max_new_tokens=8)
        out2 = GenerationEngine(self._model(), page_size=4,
                                max_length=64, mp_degree=2).generate(
            ids, max_new_tokens=8)
        np.testing.assert_array_equal(out1, out2)

    def test_rung_names_and_gauge(self, virtual_devices):
        eng = GenerationEngine(self._model(), page_size=4,
                               max_length=64, mp_degree=2)
        assert eng._decode_rung(8).endswith("[k=8,mp=2]")
        assert eng._mp_suffix() == "[mp=2]"
        assert stats.snapshot()["gauges"]["dist.mp_degree"] == 2.0
        eng1 = GenerationEngine(self._model(), page_size=4,
                                max_length=64)
        assert eng1._decode_rung(8).endswith("[k=8]")

    def test_mesh_kwarg_accepts_process_mesh(self, mesh2x4):
        # the conftest dp2 x mp4 ProcessMesh: engine resolves the mp
        # axis (extent 4) via .jax_mesh(); weights replicate over dp
        eng = GenerationEngine(self._model(), page_size=4,
                               max_length=64, mesh=mesh2x4)
        assert eng._tp is not None and eng._tp.mp == 4
        assert eng._tp.heads_per_shard == 1

    @pytest.mark.slow  # composition smoke, not a tier-1 invariant
    def test_a8w8_tp_runs_finite(self, virtual_devices):
        rng = np.random.RandomState(5)
        ids = rng.randint(0, 64, (2, 6))
        eng = GenerationEngine(self._model(), page_size=4,
                               max_length=64, mp_degree=2,
                               quant="a8w8")
        out = eng.generate(ids, max_new_tokens=4)
        assert out.shape == (2, 10)

    def test_indivisible_heads_raise_at_engine_init(self,
                                                    virtual_devices):
        paddle.seed(7)
        model = FusedCausalLM(vocab_size=64, embed_dim=30, num_heads=3,
                              dim_feedforward=64, num_layers=2,
                              max_position=128)
        with pytest.raises(ValueError, match="num_heads"):
            GenerationEngine(model, page_size=4, max_length=64,
                             mp_degree=2)


class TestServingEngineTP:
    def _model(self):
        paddle.seed(9)
        return FusedCausalLM(vocab_size=64, embed_dim=32, num_heads=4,
                             dim_feedforward=64, num_layers=2,
                             max_position=128)

    def _serve(self, mp, prompts, **kw):
        from paddle_tpu.serving import ServingEngine, SLOConfig

        eng = ServingEngine(
            self._model(), max_batch=2, page_size=4, max_length=64,
            decode_chunk=4, slo=SLOConfig(prefill_chunk=4),
            mp_degree=mp if mp > 1 else None, **kw)
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        eng.run()
        return eng

    @pytest.mark.slow  # tier-1 parity is pinned by the mesh2x4 e2e
    def test_end_to_end_parity_on_mesh(self, virtual_devices):
        rng = np.random.RandomState(11)
        sysp = list(rng.randint(0, 64, (8,)))
        prompts = [sysp + [1, 2, 3], sysp + [4, 5]]
        g1 = sorted(tuple(r.generated)
                    for r in self._serve(1, prompts).finished)
        g2 = sorted(tuple(r.generated)
                    for r in self._serve(2, prompts).finished)
        assert g1 == g2

    def test_serving_engine_on_mesh2x4_fixture(self, mesh2x4):
        # multi-axis mesh: the serving stack shards over its mp axis
        # (extent 4) and replicates over dp — end-to-end on the shared
        # conftest fixture, with token parity vs the mp1 engine
        from paddle_tpu.serving import ServingEngine, SLOConfig

        eng = ServingEngine(
            self._model(), max_batch=2, page_size=4, max_length=64,
            decode_chunk=4, slo=SLOConfig(prefill_chunk=4),
            mesh=mesh2x4)
        assert eng._gen._tp is not None and eng._gen._tp.mp == 4
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=5)
        done = eng.run()
        assert len(done) == 1 and len(done[0].generated) == 5
        ref = self._serve(1, [[1, 2, 3, 4, 5]])  # emits 6 tokens
        assert done[0].generated == ref.finished[0].generated[:5]

    def test_prefix_pages_saved_invariant_under_mp2(self,
                                                    virtual_devices):
        # PR 8's pages-saved pin, now under mp2: a 16-token shared
        # prefix at page_size 4 saves exactly 4 pages for the second
        # request (page TABLES are replicated host ints — sharding
        # the pool must not change page accounting)
        from paddle_tpu.serving import ServingEngine, SLOConfig

        rng = np.random.RandomState(13)
        sysp = list(rng.randint(0, 64, (16,)))
        base = int(stats.counter("serving.prefix_pages_saved").value)
        eng = ServingEngine(
            self._model(), max_batch=2, page_size=4, max_length=64,
            decode_chunk=4, slo=SLOConfig(prefill_chunk=4),
            mp_degree=2)
        for p in (sysp + [1, 2], sysp + [3, 4]):
            # sequential submit→run: the 2nd request hits the prefix
            # the 1st registered at prefill completion (the PR 8 pin)
            eng.submit(p, max_new_tokens=4)
            eng.run()
        saved = int(stats.counter("serving.prefix_pages_saved").value) \
            - base
        assert saved == 4
        assert len(eng.finished) == 2

    def test_chunk_rung_carries_mp_suffix(self, virtual_devices):
        from paddle_tpu.serving import ServingEngine, SLOConfig

        eng = ServingEngine(
            self._model(), max_batch=2, page_size=4, max_length=64,
            slo=SLOConfig(prefill_chunk=4), mp_degree=2)
        assert eng._chunk_rung(4) == "serve.prefill[c=4,mp=2]"


class TestToolsTP:
    def test_bench_gate_tp_directions(self):
        import tools.bench_gate as bg

        assert bg.DEFAULT_METRICS["decode_tp2_tokens_per_sec"] == "down"
        assert bg.DEFAULT_METRICS[
            "decode_tp2_pct_of_hbm_roofline"] == "down"
        assert bg.DEFAULT_METRICS["serve_tp2_tokens_per_sec"] == "down"
        assert bg.DEFAULT_METRICS["serve_tp2_p99_ttft_ms"] == "up"
        prev = {"decode_tp2_tokens_per_sec": 6000.0}
        bad, n = bg.gate(prev, {"decode_tp2_tokens_per_sec": 4000.0})
        assert n >= 1 and bad

    def test_serve_bench_has_mp_flag(self):
        import os

        src = open(os.path.join(os.path.dirname(__file__), "..",
                                "tools", "serve_bench.py")).read()
        assert '"--mp"' in src
        assert 'f"serve_tp{args.mp}_"' in src  # rung-key renaming

    def test_bench_has_decode_tp_rung(self):
        import os

        src = open(os.path.join(os.path.dirname(__file__), "..",
                                "bench.py")).read()
        assert "--decode-tp" in src
        assert 'f"decode_tp{mp}_tokens_per_sec"' in src


class TestSpmdSitesTP:
    def test_sites_registered(self):
        from paddle_tpu.analysis.spmd import SPMD_SITES

        names = {s.name for s in SPMD_SITES}
        assert {"tp.decode", "tp.prefill_chunk"} <= names
        for s in SPMD_SITES:
            if s.name.startswith("tp."):
                assert s.allowed == frozenset({"all-reduce"})
                assert s.expects_constraint

    def test_tp_decode_site_clean(self, virtual_devices):
        from paddle_tpu.analysis.spmd import (SPMD_SITES,
                                              check_spmd_site)

        site = next(s for s in SPMD_SITES if s.name == "tp.decode")
        assert check_spmd_site(site) == []

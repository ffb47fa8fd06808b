"""Comm/compute overlap (ISSUE 19): hide the collectives.

Tier-1 acceptance pins:

- **ring reduction** (``overlap="ring"`` / ``FLAGS_tp_overlap``): the
  mp2 decode path produces BITWISE-identical outputs to the blocking
  ``psum`` reference, and the traced census changes from exactly
  ``[psum, psum]`` per layer body to the exact ``mp*(mp-1)``-ppermute
  ladder (``ring_census``); an axis of extent 1 traces NO collective
  under either mode;
- **EP double buffering** (``FLAGS_ep_overlap``): ep2 greedy tokens
  stay identical through the engine while the per-layer census flips
  from the serialized dispatch/combine/gather triple to 4 all_to_alls
  + 1 all_gather;
- **async migration** (``FLAGS_migrate_async``): a fleet drain streams
  KV pages while the source keeps decoding — zero admitted requests
  lost, byte-identical continuation, decode progress DURING the
  stream, exact page accounting, and the ``fleet.migrate.stream``
  profiler span demonstrably overlapping ``fleet.replica.step`` spans
  in a captured trace;
- **S-OVERLAP** (``analysis/overlap.py``): the repo's overlap sites
  are census-clean, an injected blocking psum inside a ring site is
  caught, census drift is caught, and inline waivers silence;
- **tooling**: bench_gate directions, ``serve_bench --drain-async``,
  the bench overlap rungs are wired (and refuse off-chip).
"""
import importlib.util
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

import paddle_tpu as paddle
from paddle_tpu.analysis import trace_census
from paddle_tpu.analysis.overlap import (OVERLAP_SITES, OverlapSite,
                                         check_overlap_program,
                                         run_overlap_pass)
from paddle_tpu.analysis.spmd import (_build_moe_ep_decode,
                                      _tp_serving_setup)
from paddle_tpu.distributed.tp import (reduce_over_axis, resolve_overlap,
                                       ring_census, serving_mesh)
from paddle_tpu.incubate.nn.fused_transformer import PagedKV
from paddle_tpu.inference import FusedCausalLM, GenerationEngine
from paddle_tpu.profiler import (start_span_capture, stats,
                                 stop_span_capture)
from paddle_tpu.serving import FleetRouter, ServingEngine, SLOConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _flags:
    """Scoped flag override (flags are process-global)."""

    def __init__(self, **kw):
        self._new = {f"FLAGS_{k}": v for k, v in kw.items()}

    def __enter__(self):
        self._old = paddle.get_flags(list(self._new))
        paddle.set_flags(self._new)
        return self

    def __exit__(self, *exc):
        paddle.set_flags(self._old)


def _smap(body, mesh, in_specs, out_specs):
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs)


def _mp_mesh(n):
    return serving_mesh(n, devices=jax.devices("cpu")[:n])


# =====================================================================
# ring reduction: the collective seam itself
# =====================================================================

class TestRingReduce:
    def _mk(self, mode, n=2):
        mesh = _mp_mesh(n)

        def body(v):
            return reduce_over_axis(v, "mp", mode)

        return _smap(body, mesh, (P("mp", None),), P("mp", None))

    def test_ring_matches_psum_bitwise(self, virtual_devices):
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(2, 16).astype(np.float32))
        ref = np.asarray(self._mk("psum")(x))
        out = np.asarray(self._mk("ring")(x))
        # BITWISE, not allclose: the ring re-orders the collected
        # partials into global rank order before summing, so every
        # shard adds in the same order the psum does
        assert np.array_equal(ref, out)

    def test_census_psum_vs_ring(self, virtual_devices):
        x = jnp.ones((2, 16), jnp.float32)
        assert trace_census(self._mk("psum"), x) \
            == [("psum", "('mp',)")]
        assert trace_census(self._mk("ring"), x) \
            == ring_census("mp", 2)

    def test_axis_extent_one_traces_no_collective(self, virtual_devices):
        # the single-shard TP view: the reduction is the identity and
        # the census must stay EMPTY — no no-op psum in the program
        x = jnp.ones((2, 16), jnp.float32)
        for mode in ("psum", "ring"):
            assert trace_census(self._mk(mode, n=1), x) == [], mode

    def test_bad_mode_raises(self, virtual_devices):
        x = jnp.ones((2, 16), jnp.float32)
        with pytest.raises(ValueError, match="overlap"):
            self._mk("bogus")(x)

    def test_ring_census_helper_shape(self):
        seq = ring_census("mp", 4, reductions=2)
        assert len(seq) == 4 * 3 * 2
        assert set(seq) == {("ppermute", "('mp',)")}

    def test_resolve_overlap_knob_beats_flag(self):
        assert resolve_overlap("ring") == "ring"
        with _flags(tp_overlap="ring"):
            assert resolve_overlap(None) == "ring"
            assert resolve_overlap("psum") == "psum"
        assert resolve_overlap(None) == "psum"


# =====================================================================
# ring reduction through the mp2 decode path
# =====================================================================

class TestDecodeRing:
    def _decode_fns(self):
        st, tp, w_tp, cache, tables, cos, sin, lens = \
            _tp_serving_setup()
        x = jnp.ones((2, st.embed_dim), jnp.float32)

        def mk(mode):
            def fn(w, xb, ck, cv):
                h, c2 = st.decode_raw(w, xb, PagedKV(ck, cv), tables,
                                      lens, cos, sin, tp=tp,
                                      overlap=mode)
                return h, c2.k, c2.v

            return fn

        return mk, (w_tp, x, cache.k, cache.v)

    def test_bitwise_parity_and_exact_census_flip(self, virtual_devices):
        """THE tentpole pin: same bits out, and the program's census
        changes from exactly [psum, psum] (the once-traced layer
        body's O-proj + FFN2 pair) to the exact ppermute ladder."""
        mk, args = self._decode_fns()
        ref = mk("psum")(*args)
        out = mk("ring")(*args)
        for r, o in zip(ref, out):
            assert np.array_equal(np.asarray(r), np.asarray(o))
        assert trace_census(mk("psum"), *args) \
            == [("psum", "('mp',)")] * 2
        assert trace_census(mk("ring"), *args) \
            == ring_census("mp", 2, reductions=2)

    def test_engine_token_parity_under_ring_flag(self, virtual_devices):
        def model():
            paddle.seed(7)
            return FusedCausalLM(vocab_size=64, embed_dim=32,
                                 num_heads=4, dim_feedforward=64,
                                 num_layers=2, max_position=128)

        rng = np.random.RandomState(3)
        ids = rng.randint(0, 64, (2, 6))
        ref = GenerationEngine(model(), page_size=4,
                               max_length=64).generate(
                                   ids, max_new_tokens=8)
        stats.reset()
        with _flags(tp_overlap="ring"):
            out = GenerationEngine(model(), page_size=4, max_length=64,
                                   mp_degree=2).generate(
                                       ids, max_new_tokens=8)
        assert np.array_equal(ref, out)
        # the ring schedule accounted for itself
        assert stats.counter("dist.overlap_ring_reduces").value > 0
        assert stats.gauge("dist.overlap_ring_phases").value == 2.0


# =====================================================================
# EP double buffering
# =====================================================================

def _moe_model(seed=11):
    paddle.seed(seed)
    return FusedCausalLM(vocab_size=96, embed_dim=32, num_heads=4,
                         dim_feedforward=64, num_layers=2,
                         max_position=128, moe_num_experts=4,
                         moe_top_k=2)


class TestEPDoubleBuffer:
    def test_greedy_parity_through_engine(self, virtual_devices):
        rng = np.random.RandomState(5)
        ids = rng.randint(0, 96, (2, 10))
        ref = GenerationEngine(_moe_model(), page_size=4,
                               max_length=64).generate(
                                   ids, max_new_tokens=12)
        with _flags(ep_overlap=True):
            out = GenerationEngine(_moe_model(), page_size=4,
                                   max_length=64,
                                   ep_degree=2).generate(
                                       ids, max_new_tokens=12)
        assert np.array_equal(ref, out)

    def test_census_flips_to_double_buffer(self, virtual_devices):
        fn, args = _build_moe_ep_decode()
        base = trace_census(fn, *args)
        assert [p for p, _ in base] \
            == ["all_to_all", "all_to_all", "all_gather"], base
        with _flags(ep_overlap=True):
            # the flag resolves at trace time and jax caches traces
            # per closure instance, so the flipped census needs a
            # freshly built site
            fn2, args2 = _build_moe_ep_decode()
            seq = trace_census(fn2, *args2)
        # both half-buffer dispatches, combine0/combine1, then the
        # replicated-hidden gather — all_to_all carries a bare axis
        # name, all_gather the normalized tuple
        assert seq == [("all_to_all", "ep")] * 4 \
            + [("all_gather", str(("ep",)))], seq


# =====================================================================
# async migration: decode-concurrent fleet drain
# =====================================================================

def _serve_engine(seed=7):
    paddle.seed(seed)
    model = FusedCausalLM(vocab_size=64, embed_dim=32, num_heads=4,
                          dim_feedforward=64, num_layers=2,
                          max_position=256)
    return ServingEngine(model, max_batch=2, page_size=4,
                         max_length=96, decode_chunk=2,
                         slo=SLOConfig(prefill_chunk=8))


_PROMPT = np.random.RandomState(0).randint(0, 64, (10,))


def _ref_tokens(max_new=8):
    eng = _serve_engine()
    rid = eng.submit(_PROMPT, max_new_tokens=max_new)
    done = {r.id: r for r in eng.run()}
    assert done[rid].state == "ok"
    return list(done[rid].generated)


def _mid_decode_router(n_generated=2, max_new=8):
    """A 2-replica sync-driven fleet with one request mid-decode."""
    router = FleetRouter(engine_factory=lambda i: _serve_engine(),
                         n_replicas=2)
    rid = router.submit(_PROMPT, max_new_tokens=max_new)
    steps = 0
    while True:
        router.step()
        steps += 1
        assert steps < 500
        req = router.results()[rid]
        if len(req.generated) >= n_generated and not req.done:
            break
    src = next(r.idx for r in router.replicas if r.eng.num_active)
    return router, rid, src


class TestAsyncMigration:
    def test_zero_loss_parity_progress_and_accounting(self):
        """THE async-drain pin: pages stream while the source keeps
        decoding (token progress DURING the stream), the re-homed
        request finishes byte-identically, nothing recomputes, and
        page accounting closes exactly on both pools."""
        stats.reset()
        # enough remaining tokens that the source can't finish the
        # request mid-stream (which would legitimately skip the join)
        ref = _ref_tokens(max_new=24)
        with _flags(migrate_async=True):
            router, rid, src = _mid_decode_router(max_new=24)
            src_eng = router.replicas[src].eng
            dst_eng = router.replicas[1 - src].eng
            n_before = len(router.results()[rid].generated)
            router.drain(src)
            assert router.replicas[src].state == "drained"
            n_after = len(router.results()[rid].generated)
            # decode-concurrent: the drain drove source decode steps
            # BETWEEN page batches, so the stream saw tokens land
            assert n_after > n_before
            assert stats.counter("fleet.async_migrations").value == 1
            assert stats.counter("fleet.migrations").value == 1
            assert stats.counter("serving.preemptions").value == 0
            # source pool drained to empty (scratch page reserved)...
            assert src_eng._mgr.free_pages \
                == src_eng._mgr.num_pages - 1
            assert src_eng._mgr._owned == {}
            # ...and the destination owns the slot at refcount 1
            j = next(i for i in range(dst_eng.max_batch)
                     if dst_eng._slots[i] is not None)
            for p in dst_eng._mgr._owned[("slot", j)]:
                assert dst_eng._mgr.refcount(p) == 1
            # destination journal: an async-marked migrate event and
            # NO admitted event — the request never re-prefilled
            evs = dst_eng.journal.events(rid)
            mig = [e for e in evs if e["ev"] == "migrate"]
            assert mig and mig[0].get("async") is True
            assert not any(e["ev"] == "admitted" for e in evs)
            done = {r.id: r for r in router.run()}
        assert done[rid].state == "ok"
        assert list(done[rid].generated) == ref

    def test_flag_off_stays_on_blocking_path(self):
        stats.reset()
        ref = _ref_tokens()
        router, rid, src = _mid_decode_router()
        router.drain(src)
        assert router.replicas[src].state == "drained"
        assert stats.counter("fleet.async_migrations").value == 0
        assert stats.counter("fleet.migrations").value == 1
        done = {r.id: r for r in router.run()}
        assert done[rid].state == "ok"
        assert list(done[rid].generated) == ref

    def test_stream_span_overlaps_decode_spans(self):
        """The profiler sees the overlap: decode-step spans land
        INSIDE the fleet.migrate.stream span's wall window (the
        cross-thread span sink captures both)."""
        stats.reset()
        with _flags(migrate_async=True):
            router, rid, src = _mid_decode_router()
            sink = start_span_capture()
            try:
                router.drain(src)
            finally:
                stop_span_capture(sink)
        streams = [e for e in sink
                   if e["name"] == "fleet.migrate.stream"]
        assert len(streams) == 1, [e["name"] for e in sink]
        lo = streams[0]["ts"]
        hi = lo + streams[0]["dur"]
        inside = [e for e in sink if e["name"] == "fleet.replica.step"
                  and e["ts"] >= lo and e["ts"] + e["dur"] <= hi]
        assert inside, [e["name"] for e in sink]


# =====================================================================
# S-OVERLAP: the census lint pass
# =====================================================================

def _mod_from(tmp_path, name, source):
    p = tmp_path / f"{name}.py"
    p.write_text(source)
    spec = importlib.util.spec_from_file_location(name, str(p))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class TestOverlapPass:
    def test_sites_registered(self):
        assert {s.name for s in OVERLAP_SITES} \
            == {"overlap.tp_decode_ring", "overlap.moe_ep_double"}
        assert all("psum" in s.forbidden for s in OVERLAP_SITES)

    def test_repo_sites_clean(self, virtual_devices):
        assert run_overlap_pass() == []

    def _ring_site_build(self, reductions):
        mesh = _mp_mesh(2)

        def body(v):
            out = v
            for _ in range(reductions):
                out = reduce_over_axis(out, "mp", "ring")
            return out

        fn = _smap(body, mesh, (P("mp", None),), P("mp", None))
        return fn, (jnp.ones((2, 8), jnp.float32),)

    def test_clean_site_no_findings(self, virtual_devices):
        site = OverlapSite("t.ring_ok",
                           lambda: self._ring_site_build(1),
                           expected=lambda: ring_census("mp", 2))
        assert check_overlap_program(site) == []

    def test_injected_blocking_psum_caught(self, virtual_devices):
        """Acceptance criterion: collapse the ring back into one
        blocking psum — bitwise-correct on CPU, so only the census
        knows — and S-OVERLAP fires twice (stray forbidden collective
        + exact-sequence mismatch)."""
        mesh = _mp_mesh(2)

        def build():
            fn = _smap(lambda v: jax.lax.psum(v, "mp"), mesh,
                       (P("mp", None),), P("mp", None))
            return fn, (jnp.ones((2, 8), jnp.float32),)

        site = OverlapSite("t.ring_collapsed", build,
                           expected=lambda: ring_census("mp", 2))
        findings = check_overlap_program(site)
        assert [f.rule for f in findings] == ["S-OVERLAP"] * 2
        assert "psum" in findings[0].message
        assert "blocking" in findings[0].message

    def test_census_drift_caught(self, virtual_devices):
        # right primitives, wrong phase count: one reduction traced
        # where the site declares two
        site = OverlapSite("t.ring_drift",
                           lambda: self._ring_site_build(1),
                           expected=lambda: ring_census(
                               "mp", 2, reductions=2))
        findings = check_overlap_program(site)
        assert len(findings) == 1
        assert "expected exactly" in findings[0].message

    def test_waiver_silences_s_overlap(self, tmp_path, virtual_devices):
        mod = _mod_from(tmp_path, "overlap_waived", (
            "def build():"
            "  # tpu-lint: ok(S-OVERLAP) -- census change intended\n"
            "    import jax, jax.numpy as jnp\n"
            "    from jax.sharding import PartitionSpec as P\n"
            "    from paddle_tpu.distributed.tp import serving_mesh\n"
            "    mesh = serving_mesh(2,"
            " devices=jax.devices('cpu')[:2])\n"
            "    fn = jax.shard_map(lambda v: jax.lax.psum(v, 'mp'),"
            " mesh=mesh, in_specs=(P('mp', None),),"
            " out_specs=P('mp', None))\n"
            "    return fn, (jnp.ones((2, 8), jnp.float32),)\n"))
        from paddle_tpu.distributed.tp import ring_census as rc
        site = OverlapSite("t.waived_overlap", mod.build,
                           expected=lambda: rc("mp", 2))
        findings = run_overlap_pass(sites=[site])
        assert findings and all(f.waived for f in findings)


# =====================================================================
# tooling wiring
# =====================================================================

class TestToolingWired:
    def test_bench_gate_directions(self):
        import sys
        sys.path.insert(0, os.path.join(REPO, "tools"))
        try:
            import bench_gate
        finally:
            sys.path.pop(0)
        d = bench_gate.DEFAULT_METRICS
        assert d["decode_tp2_overlap_tokens_per_sec"] == "down"
        assert d["decode_tp2_overlap_pct_of_hbm_roofline"] == "down"
        assert d["moe_decode_ep2_overlap_tokens_per_sec"] == "down"
        assert d["fleet_async_migration_decode_tokens"] == "down"
        assert d["fleet_async_migration_stall_ms"] == "up"
        assert d["fleet_async_migration_lost"] == "up"
        # lost requests are strict: ONE regresses, no noise floor
        assert bench_gate._regressed("fleet_async_migration_lost",
                                     "up", 0.0, 1.0, 0.10)

    def test_serve_bench_drain_async_wired(self):
        with open(os.path.join(REPO, "tools", "serve_bench.py")) as f:
            src = f.read()
        for tok in ("--drain-async", "fleet_async_migrations",
                    "fleet_async_migration_decode_tokens",
                    "fleet_async_migration_lost"):
            assert tok in src, tok

    def test_bench_overlap_rungs_and_all_manifest(self):
        spec = importlib.util.spec_from_file_location(
            "bench_mod", os.path.join(REPO, "bench.py"))
        bench = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(bench)
        for kind in ("--decode-tp-overlap", "--moe-decode-ep-overlap",
                     "--fleet"):
            assert kind in bench.SECONDARY_KINDS, kind
        # no CPU manifest: every rung measures the chip, and a process
        # that finds none refuses instead of emitting toy numbers under
        # the device key names
        assert not hasattr(bench, "CPU_KINDS")
        with pytest.raises(SystemExit, match="needs the TPU"):
            bench._require_chip()

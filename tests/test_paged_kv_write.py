"""The chunked-prefill write into the paged pool, in place
(``write_prefill_kv_inplace`` / kernel ``pt_paged_kv_write``).

On the chip the kernel is the one prefill write of a bf16/f32 pool; off
it ``backend="auto"`` falls back to the scatter, so these tests drive
the kernel through the interpreter and hold it to
``write_prefill_kv_pages`` on the same inputs, bit for bit over the
whole pool. The two differ by design in what they do with rows that
have no home: the scatter sends padding rows to scratch page 0, the
kernel drops them (page 0 keeps its bytes).
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
import paddle_tpu.nn.functional.paged_attention as pa
from paddle_tpu.analysis.jaxpr_util import walk_eqns
from paddle_tpu.analysis.sites import _force_tpu_routing
from paddle_tpu.incubate.nn.fused_transformer import (
    FusedMultiTransformer, PagedKV, rope_table)
from paddle_tpu.inference.kv_cache import BlockKVCacheManager

PS, PP, POOL, N_KV, D = 16, 4, 32, 2, 128

#: name -> (chunk rows, start [b], valid_lens [b] or None)
CASES = {
    "aligned": (32, [16], None),
    "aligned_from_zero": (32, [0], [32]),
    "unaligned": (32, [5], None),
    "unaligned_last_slot": (32, [15], None),
    "padding_rows": (32, [16], [20]),
    "padding_rows_unaligned": (32, [5], [7]),
    "nothing_valid": (32, [16], [0]),
    "crosses_last_page": (32, [40], [20]),       # table holds 64 rows
    "crosses_last_page_aligned": (32, [48], [16]),
    "two_rows_distinct_tables": (32, [5, 32], [20, 32]),
    "three_rows_verify_window": (5, [13, 0, 59], None),
    "one_token": (1, [15, 16], None),
    "one_row_idle": (32, [0, 3], [0, 29]),
}


def _bits(x):
    x = np.asarray(x)
    return x.view(np.uint16 if x.dtype.itemsize == 2 else np.uint32)


def _inputs(c, b, dtype, seed=0):
    rng = np.random.RandomState(seed)

    def draw(*shape):
        return jnp.asarray(rng.randn(*shape), dtype)

    tables = rng.permutation(np.arange(1, POOL))[:b * PP] \
        .reshape(b, PP).astype(np.int32)
    return (draw(POOL, N_KV, PS, D), draw(POOL, N_KV, PS, D),
            draw(b, c, N_KV, D), draw(b, c, N_KV, D),
            jnp.asarray(tables))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_inplace_write_matches_the_scatter(case, dtype):
    c, start, vlens = CASES[case]
    b = len(start)
    kc, vc, k, v, tables = _inputs(c, b, dtype)
    start = jnp.asarray(start, jnp.int32)
    vl = None if vlens is None else jnp.asarray(vlens, jnp.int32)
    want = jax.jit(lambda *a: pa.write_prefill_kv_pages(
        *a[:5], start=a[5], valid_lens=vl))(kc, vc, k, v, tables, start)
    got = jax.jit(lambda *a: pa.write_prefill_kv_inplace(
        *a[:5], a[5], vl, backend="interpret"))(kc, vc, k, v, tables,
                                                start)
    for w, g, orig in zip(want, got, (kc, vc)):
        assert g.dtype == orig.dtype and g.shape == orig.shape
        # every page but the scratch page: the scatter's bytes
        assert np.array_equal(_bits(g)[1:], _bits(w)[1:])
        # the scratch page: untouched (the scatter parks padding there)
        assert np.array_equal(_bits(g)[0], _bits(orig)[0])
        if vlens is None:
            assert np.array_equal(_bits(g), _bits(w))
    # the live rows really moved (the comparison is not of two no-ops)
    moved = sum(min(n, c) for n in (vlens or [c] * b))
    changed = (_bits(got[0]) != _bits(kc)).any(axis=(1, 3)).sum()
    assert changed == moved


def test_rows_past_the_table_are_dropped_not_clamped():
    """Valid rows whose position lies past the table's coverage have no
    page: the scatter clamps them into the last page (and so corrupts
    it), the kernel leaves the pool alone there."""
    c = 32
    kc, vc, k, v, tables = _inputs(c, 1, jnp.float32)
    start = jnp.asarray([PP * PS - 8], jnp.int32)      # 8 rows fit
    got_k, _ = pa.write_prefill_kv_inplace(kc, vc, k, v, tables, start,
                                           None, backend="interpret")
    last = int(tables[0, -1])
    want = np.asarray(kc).copy()
    want[last, :, 8:] = np.swapaxes(np.asarray(k)[0, :8], 0, 1)
    assert np.array_equal(np.asarray(got_k), want)


def test_cast_into_the_pools_dtype():
    kc, vc, _, _, tables = _inputs(16, 1, jnp.bfloat16)
    _, _, k, v, _ = _inputs(16, 1, jnp.float32, seed=1)
    start = jnp.asarray([3], jnp.int32)
    want = pa.write_prefill_kv_pages(kc, vc, k, v, tables, start=start)
    got = pa.write_prefill_kv_inplace(kc, vc, k, v, tables, start,
                                      backend="interpret")
    for w, g in zip(want, got):
        assert g.dtype == jnp.bfloat16
        assert np.array_equal(_bits(g), _bits(w))


def test_auto_off_the_chip_is_the_scatter_and_names_are_checked():
    kc, vc, k, v, tables = _inputs(16, 1, jnp.float32)
    start = jnp.asarray([3], jnp.int32)
    vl = jnp.asarray([9], jnp.int32)
    want = pa.write_prefill_kv_pages(kc, vc, k, v, tables, start=start,
                                     valid_lens=vl)
    got = pa.write_prefill_kv_inplace(kc, vc, k, v, tables, start, vl)
    for w, g in zip(want, got):
        assert np.array_equal(_bits(g), _bits(w))
    with pytest.raises(ValueError, match="backend"):
        pa.write_prefill_kv_inplace(kc, vc, k, v, tables, start, vl,
                                    backend="xla")


# ---------------------------------------------------------------------
# through the layer loop
# ---------------------------------------------------------------------

def _tiny(ps=4, pp=8, pages=64, b=2, L=10):
    paddle.seed(13)
    st = FusedMultiTransformer(32, 4, 64, 2, max_position=128)
    cos, sin = rope_table(128, st.head_dim)
    mgr = BlockKVCacheManager(st.num_layers, st.num_kv_heads,
                              st.head_dim, ps, num_pages=pages,
                              reserve_scratch=True)
    for i in range(b):
        mgr.allocate(i, L + 12)
    tables = mgr.block_tables(range(b), pp)
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(b, L, 32).astype(np.float32))
    w = st._stack()
    _h, cache = st.prefill_raw(w, x, mgr.fresh_cache(), tables, cos, sin)
    return st, w, cos, sin, cache, tables, rng


@pytest.mark.parametrize("start,win,lens", [
    ([10, 10], 5, [5, 5]),        # the verify pass: start = seq_len
    ([8, 8], 8, [8, 3]),          # a scheduler chunk, one row padded
], ids=["verify_unaligned", "chunk_padded"])
def test_layer_loop_with_the_kernel_matches_the_scatter(
        monkeypatch, start, win, lens):
    st, w, cos, sin, cache, tables, rng = _tiny()
    x = jnp.asarray(rng.randn(2, win, 32).astype(np.float32))
    start = jnp.asarray(start, jnp.int32)
    lens = jnp.asarray(lens, jnp.int32)
    h0, c0 = st.prefill_chunk_raw(w, x, cache, tables, start, lens,
                                  cos, sin)
    monkeypatch.setattr(
        pa, "write_prefill_kv_inplace",
        functools.partial(pa.write_prefill_kv_inplace,
                          backend="interpret"))
    h1, c1 = st.prefill_chunk_raw(w, x, cache, tables, start, lens,
                                  cos, sin)
    live = np.asarray(jnp.arange(win)[None, :] < lens[:, None])
    np.testing.assert_allclose(np.asarray(h1)[live], np.asarray(h0)[live],
                               rtol=1e-6, atol=1e-6)
    npages = cache.k.shape[0] // st.num_layers
    scratch = [l * npages for l in range(st.num_layers)]
    keep = np.setdiff1d(np.arange(cache.k.shape[0]), scratch)
    for a, b_ in ((c1.k, c0.k), (c1.v, c0.v)):
        np.testing.assert_allclose(np.asarray(a)[keep],
                                   np.asarray(b_)[keep],
                                   rtol=1e-6, atol=1e-6)


def _loop_primitives(st, w, cos, sin, cache, tables):
    """(primitive, ``name=``) of what runs INSIDE the layer loop, traced
    as on the chip."""
    x = jax.ShapeDtypeStruct((2, 8, 32), jnp.float32)
    start = jnp.full((2,), 8, jnp.int32)
    with _force_tpu_routing():
        closed = jax.make_jaxpr(lambda x: st.prefill_chunk_raw(
            w, x, cache, tables, start, start, cos, sin)[0])(x)
    return [(eqn.primitive.name, eqn.params.get("name"))
            for eqn, in_loop in walk_eqns(closed.jaxpr) if in_loop]


def test_no_scatter_on_the_pool_inside_the_layer_loop():
    """The mechanism itself: as traced for the chip, the layer loop
    touches a bf16/f32 pool through the two Pallas calls only — a
    scatter there would pin the loop-carried pool to another layout and
    bring the whole-pool copies back."""
    st, w, cos, sin, cache, tables, _ = _tiny()
    prims = _loop_primitives(st, w, cos, sin, cache, tables)
    assert not [p for p, _ in prims if p.startswith("scatter")]
    kernels = [n for p, n in prims if p == "pallas_call"]
    assert kernels == ["pt_paged_kv_write", "pt_flash_varlen_paged"]


def test_quantized_pool_keeps_the_scatter():
    st, w, cos, sin, cache, tables, _ = _tiny()

    def side(pool):                      # [P, n_kv, ps, d] -> int8 + plane
        q, s = pa.quantize_kv_rows(jnp.swapaxes(pool, 1, 2))
        return (jnp.swapaxes(q, 1, 2),
                jnp.moveaxis(s, -1, 0).reshape(pool.shape[1], -1))

    prims = [p for p, _ in _loop_primitives(
        st, w, cos, sin, PagedKV(side(cache.k), side(cache.v)), tables)]
    assert "pallas_call" not in prims
    assert [p for p in prims if p.startswith("scatter")]


def test_kernel_inside_the_tp_shard_map(monkeypatch, virtual_devices):
    """``_tp_wrap`` runs the same loop inside ``shard_map`` with the pool
    sharded by kv head: the kernel sees per-shard ``n_kv`` pages."""
    from paddle_tpu.distributed.tp import TPContext, serving_mesh

    paddle.seed(21)
    st = FusedMultiTransformer(32, 4, 64, 2, num_kv_heads=2,
                               max_position=64)
    cos, sin = rope_table(64, st.head_dim)
    w = st._stack()
    tp = TPContext.create(st.num_heads, st.num_kv_heads, st.head_dim,
                          mesh=serving_mesh(2, devices=virtual_devices[:2]))

    def pool(tp=None):
        mgr = BlockKVCacheManager(
            st.num_layers, st.num_kv_heads, st.head_dim, 4, num_pages=16,
            reserve_scratch=True, mp_degree=tp.mp if tp else 1,
            mesh=tp.mesh if tp else None)
        for i in range(2):
            mgr.allocate(i, 12)
        return mgr.fresh_cache(), mgr.block_tables(range(2), 3)

    x = jnp.asarray(np.random.RandomState(4).randn(2, 6, 32)
                    .astype(np.float32))
    start = jnp.asarray([0, 3], jnp.int32)       # one row unaligned
    lens = jnp.asarray([6, 4], jnp.int32)
    c1, t1 = pool()
    h1, c1 = st.prefill_chunk_raw(w, x, c1, t1, start, lens, cos, sin)
    monkeypatch.setattr(
        pa, "write_prefill_kv_inplace",
        functools.partial(pa.write_prefill_kv_inplace,
                          backend="interpret"))
    c2, t2 = pool(tp)
    h2, c2 = st.prefill_chunk_raw(tp.shard_stack(w), x, c2, t2, start,
                                  lens, cos, sin, tp=tp)
    np.testing.assert_allclose(np.asarray(h1)[0], np.asarray(h2)[0],
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(h1)[1, :4],
                               np.asarray(h2)[1, :4], atol=1e-5)
    npages = c1.k.shape[0] // st.num_layers
    keep = np.setdiff1d(np.arange(c1.k.shape[0]),
                        [l * npages for l in range(st.num_layers)])
    for a, b_ in ((c1.k, c2.k), (c1.v, c2.v)):
        np.testing.assert_allclose(np.asarray(a)[keep],
                                   np.asarray(b_)[keep], atol=1e-5)

"""Decode paged attention: the one choice (``plan_decode_attention`` /
``decode_attend``), the XLA gather reference, the in-place kernels
against it (Pallas interpret mode off the chip), page-major layout
invariants and write-path round-trips.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.nn.functional.paged_attention import (
    decode_attend, paged_attention, plan_decode_attention, write_kv_pages)


def test_auto_backend_off_tpu_is_xla():
    # the public name IS the XLA gather path: it must compute correctly
    rng = np.random.RandomState(0)
    b, n, d, ps, pp = 2, 4, 8, 4, 3
    q = jnp.asarray(rng.randn(b, n, d).astype(np.float32))
    kc = jnp.asarray(rng.randn(b * pp, n, ps, d).astype(np.float32))
    vc = jnp.asarray(rng.randn(b * pp, n, ps, d).astype(np.float32))
    lens = jnp.asarray(np.array([5, 9], np.int32))
    tables = jnp.asarray(
        np.arange(b * pp, dtype=np.int32).reshape(b, pp))
    out = paged_attention(q, kc, vc, lens, tables)
    # independent dense reference
    max_len = pp * ps
    k_full = np.zeros((b, max_len, n, d), np.float32)
    v_full = np.zeros((b, max_len, n, d), np.float32)
    tb = np.asarray(tables)
    for i in range(b):
        for t in range(max_len):
            k_full[i, t] = np.asarray(kc)[tb[i, t // ps], :, t % ps]
            v_full[i, t] = np.asarray(vc)[tb[i, t // ps], :, t % ps]
    logits = np.einsum("bhd,blhd->bhl", np.asarray(q), k_full) \
        * (d ** -0.5)
    mask = np.arange(max_len)[None, :] < np.asarray(lens)[:, None]
    logits = np.where(mask[:, None, :], logits, -1e30)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    ref = np.einsum("bhl,blhd->bhd", w, v_full)
    np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5,
                               atol=2e-5)


def test_page_major_scatter_roundtrip_dtype_cast():
    """bf16 pool accepts fp32 writes (serving KV dtype decoupled from
    compute dtype)."""
    ck = jnp.zeros((4, 3, 2, 8), jnp.bfloat16)
    cv = jnp.zeros_like(ck)
    k = jnp.ones((2, 3, 8), jnp.float32)
    v = jnp.full((2, 3, 8), 2.0, jnp.float32)
    pos = jnp.asarray(np.array([0, 3], np.int32))
    tables = jnp.asarray(np.array([[0, 1], [2, 3]], np.int32))
    ck2, cv2 = write_kv_pages(ck, cv, k, v, pos, tables)
    assert ck2.dtype == jnp.bfloat16
    # seq 0 wrote page 0 slot 0; seq 1 wrote page 3 slot 1
    np.testing.assert_allclose(np.asarray(ck2[0, :, 0], np.float32), 1.0)
    np.testing.assert_allclose(np.asarray(cv2[3, :, 1], np.float32), 2.0)
    np.testing.assert_allclose(np.asarray(ck2[1], np.float32), 0.0)


def _dense_paged_ref(q, kc, vc, lens, tables, ps):
    """NumPy dense reference over gathered pages."""
    b, n_q, d = q.shape
    n_kv = kc.shape[2]
    g = n_q // n_kv
    pp = tables.shape[1]
    max_len = pp * ps
    k_full = np.zeros((b, max_len, n_kv, d), np.float32)
    v_full = np.zeros((b, max_len, n_kv, d), np.float32)
    for i in range(b):
        for t in range(max_len):
            k_full[i, t] = np.asarray(kc)[tables[i, t // ps], :, t % ps]
            v_full[i, t] = np.asarray(vc)[tables[i, t // ps], :, t % ps]
    qh = np.asarray(q, np.float32).reshape(b, n_kv, g, d)
    logits = np.einsum("bngd,blnd->bngl", qh, k_full) * (d ** -0.5)
    mask = np.arange(max_len)[None, :] < np.asarray(lens)[:, None]
    logits = np.where(mask[:, None, None, :], logits, -1e30)
    w = np.exp(logits - logits.max(-1, keepdims=True))
    w /= w.sum(-1, keepdims=True)
    return np.einsum("bngl,blnd->bngd", w, v_full).reshape(b, n_q, d)


def _ragged_case(rng, g, int8=False):
    """MHA (g=1) / GQA rows of ragged length incl. an idle slot (length
    0) over a two-layer folded pool; each row holds the pages its length
    + 1 needs. Returns (q, new k, new v, k pool, v pool, lens, tables,
    P, ps) as NumPy arrays."""
    b, n_kv, d, ps, pp = 4, 4, 128, 4, 6
    P, L = 24, 2
    q = rng.randn(b, n_kv * g, d).astype(np.float32)
    nk = rng.randn(b, n_kv, d).astype(np.float32)
    nv = rng.randn(b, n_kv, d).astype(np.float32)
    kpool = rng.randn(L * P, n_kv, ps, d).astype(np.float32)
    vpool = rng.randn(L * P, n_kv, ps, d).astype(np.float32)
    lens = np.array([5, 17, 0, 23], np.int32)
    return q, nk, nv, kpool, vpool, lens, \
        _alloc_tables(rng, lens, pp, ps, P), P, ps


@pytest.mark.parametrize("g", [1, 2])
def test_reference_decode_attend_parity(g):
    """The reference path of ``decode_attend`` (what the plan picks off
    the chip: scatter + XLA gather) vs the dense NumPy reference: MHA +
    GQA, ragged lens incl. a zero-length (idle slot) row — which attends
    to its own token alone — and the layer-folded base offset, through
    ``paged_attention(pool_base=)`` directly too."""
    rng = np.random.RandomState(1)
    q, nk, nv, kpool, vpool, lens_np, tables_np, P, ps = \
        _ragged_case(rng, g)
    j = jnp.asarray
    plan = plan_decode_attention(j(kpool), j(tables_np), j(lens_np), P)
    assert plan.kind == "xla" and plan.walk is None \
        and plan.ownership is None
    for layer, base in enumerate((0, P)):
        out, ck, cv = decode_attend(plan, j(q), j(nk), j(nv), j(kpool),
                                    j(vpool), layer)
        ck_np, cv_np = kpool.copy(), vpool.copy()
        for r, n in enumerate(lens_np):
            pg = base + tables_np[r, n // ps]
            ck_np[pg, :, n % ps] = nk[r]
            cv_np[pg, :, n % ps] = nv[r]
        np.testing.assert_array_equal(np.asarray(ck), ck_np)
        np.testing.assert_array_equal(np.asarray(cv), cv_np)
        ref = _dense_paged_ref(q, ck_np[base:base + P],
                               cv_np[base:base + P], lens_np + 1,
                               tables_np, ps)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)
        # the idle row saw its own token only
        np.testing.assert_allclose(
            np.asarray(out)[2], np.repeat(nv[2], g, axis=0), atol=2e-5)
        direct = paged_attention(j(q), ck, cv, j(lens_np + 1),
                                 j(tables_np), pool_base=base)
        np.testing.assert_allclose(np.asarray(direct), ref, atol=2e-5)


def _record_choice(pool_dtype, d, on_chip):
    """Names of the Pallas calls one plan + attend records when
    dry-traced (``jax.eval_shape``: no kernel runs), the probe answering
    ``on_chip``; and the plan's kind."""
    import contextlib

    from paddle_tpu.analysis.audit import record_pallas_calls
    from paddle_tpu.analysis.sites import _force_tpu_routing

    b, n_kv, ps, pp, P = 8, 2, 16, 8, 128
    sds = jax.ShapeDtypeStruct
    bf = jnp.bfloat16
    side = sds((2 * P, n_kv, ps, d), pool_dtype)
    if pool_dtype == jnp.int8:
        side = (side, sds((n_kv, 2 * P * ps), jnp.float32))
    kinds = []

    def fn(q, k, v, ck, cv, lens, tables):
        plan = plan_decode_attention(ck, tables, lens, P)
        kinds.append(plan.kind)
        return decode_attend(plan, q, k, v, ck, cv, 1)

    routed = _force_tpu_routing() if on_chip else contextlib.nullcontext()
    with routed, record_pallas_calls() as records:
        jax.eval_shape(fn, sds((b, n_kv, d), bf), sds((b, n_kv, d), bf),
                       sds((b, n_kv, d), bf), side, side,
                       sds((b,), jnp.int32), sds((b, pp), jnp.int32))
    return [r.name for r in records], kinds[0]


@pytest.mark.parametrize("case", ["bf16_head128", "bf16_head64",
                                  "int8_pool", "off_chip"])
def test_decode_attention_choice(case):
    """The choice ``plan_decode_attention`` makes from what it observes
    (pool form, the probe, head_dim), by the Pallas calls it records."""
    if case == "bf16_head128":
        assert _record_choice(jnp.bfloat16, 128, True) == (
            ["pt_paged_attention_decode_inplace"], "inplace")
    elif case == "bf16_head64":    # narrower than the lanes: no kernel
        assert _record_choice(jnp.bfloat16, 64, True) == ([], "xla")
    elif case == "int8_pool":      # the pair: its own kernel, anywhere
        for on_chip in (True, False):
            assert _record_choice(jnp.int8, 128, on_chip) == (
                ["pt_paged_attention_decode_inplace_q"], "inplace_q")
    else:
        assert _record_choice(jnp.bfloat16, 128, False) == ([], "xla")
        # and off the chip the same call computes the dense reference
        rng = np.random.RandomState(2)
        q, nk, nv, kpool, vpool, lens_np, tables_np, P, ps = \
            _ragged_case(rng, 2)
        j = jnp.asarray
        plan = plan_decode_attention(j(kpool), j(tables_np), j(lens_np),
                                     P)
        out, ck, cv = decode_attend(plan, j(q), j(nk), j(nv), j(kpool),
                                    j(vpool), 1)
        ref = _dense_paged_ref(q, np.asarray(ck)[P:], np.asarray(cv)[P:],
                               lens_np + 1, tables_np, ps)
        np.testing.assert_allclose(np.asarray(out), ref, atol=2e-5)


def _alloc_tables(rng, lens_np, pp, ps, P):
    """Each row gets the pages its length + 1 needs, drawn without
    replacement from a permutation of 1..P-1 (scattered over the
    region); the rest of its table is padded with page 0."""
    tables = np.zeros((len(lens_np), pp), np.int32)
    perm = rng.permutation(np.arange(1, P))
    i = 0
    for r, n_tok in enumerate(lens_np):
        n = min(-(-int(n_tok + 1) // ps), pp)
        tables[r, :n] = perm[i:i + n]
        i += n
    return tables


def _inplace_case(name, rng):
    """(ps, pp, P, lens, tables, fill free pages with NaN) of one case of
    the in-place parity test. Page 16 gives the walk chunks of 64
    entries; page 4 (the original geometry) one chunk of 256."""
    nan_free = False
    if name == "ragged":            # incl. an idle slot; one short chunk
        ps, pp, P = 4, 6, 16
        lens = np.array([5, 0, 13, 9], np.int32)
        tables = _alloc_tables(rng, lens, pp, ps, P)
    elif name in ("scattered", "free_pages_nan"):
        # 78 live entries of a 300-page region: two chunks, the second
        # short; every page no table names holds NaN in the second case
        ps, pp, P = 16, 48, 300
        lens = np.array([700, 17, 0, 500], np.int32)
        tables = _alloc_tables(rng, lens, pp, ps, P)
        nan_free = name == "free_pages_nan"
    elif name == "shared_page":
        # rows 0 and 2 name the SAME two physical pages as their first
        # two (a shared prefix); each must see them as its own
        ps, pp, P = 16, 8, 40
        lens = np.array([45, 20, 38, 33], np.int32)
        tables = _alloc_tables(rng, lens, pp, ps, P)
        tables[2, :2] = tables[0, :2]
    elif name == "all_empty":       # nothing to walk: every row length 0
        ps, pp, P = 16, 4, 24
        lens = np.zeros((4,), np.int32)
        tables = _alloc_tables(rng, lens, pp, ps, P)
    elif name == "full_region":     # every page of the region is live
        ps, pp, P = 16, 16, 65
        lens = np.full((4,), pp * ps - 1, np.int32)
        tables = _alloc_tables(rng, lens, pp, ps, P)
        assert sorted(tables.ravel()) == list(range(1, P))
    elif name == "partial_chunk":   # 65 entries: one whole chunk + one page
        ps, pp, P = 16, 24, 80
        lens = np.array([16 * 20, 16 * 20, 16 * 20, 16 * 4 + 1], np.int32)
        tables = _alloc_tables(rng, lens, pp, ps, P)
    else:
        raise AssertionError(name)
    return ps, pp, P, lens, tables, nan_free


@pytest.mark.parametrize("case,g", [
    ("ragged", 1), ("ragged", 2), ("ragged", 4), ("scattered", 2),
    ("free_pages_nan", 1), ("shared_page", 2), ("all_empty", 1),
    ("full_region", 1), ("partial_chunk", 4)])
def test_fused_inplace_kernel_parity(case, g):
    """paged_decode_attention_inplace (the default TPU serving path):
    append + attend in one kernel must equal scatter-write followed by
    the XLA gather attention with lens+1, AND must have patched exactly
    the written rows of the layer's pool region in place (other layers'
    regions untouched), for both regions of a two-layer pool. The
    kernel walks the pages the tables name and no others: a region
    whose free pages hold NaN gives the same finite result. Interpret
    mode off-TPU, compiled on the chip."""
    from paddle_tpu.nn.functional.paged_attention import (
        paged_decode_attention_inplace)

    rng = np.random.RandomState(5)
    ps, pp, P, lens_np, tables_np, nan_free = _inplace_case(case, rng)
    b, n_kv, d, L = len(lens_np), 2, 128, 2
    n_q = n_kv * g
    q = jnp.asarray(rng.randn(b, n_q, d).astype(np.float32))
    nk = jnp.asarray(rng.randn(b, n_kv, d).astype(np.float32))
    nv = jnp.asarray(rng.randn(b, n_kv, d).astype(np.float32))
    kpool = rng.randn(L * P, n_kv, ps, d).astype(np.float32)
    vpool = rng.randn(L * P, n_kv, ps, d).astype(np.float32)
    if nan_free:
        free = np.setdiff1d(np.arange(1, P), tables_np.ravel())
        assert len(free) > P // 2
        for base in (0, P):
            kpool[base + free] = np.nan
            vpool[base + free] = np.nan
    kpool, vpool = jnp.asarray(kpool), jnp.asarray(vpool)
    lens, tables = jnp.asarray(lens_np), jnp.asarray(tables_np)
    for base in (0, P):
        out, ck, cv = paged_decode_attention_inplace(
            q, nk, nv, kpool, vpool, lens, tables, pool_base=base)
        ck_ref, cv_ref = write_kv_pages(
            kpool[base:base + P], vpool[base:base + P], nk, nv, lens,
            tables)
        ref = paged_attention(q, ck_ref, cv_ref, lens + 1, tables)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=3e-2)
        # in-place page writes: layer region equals the scatter result,
        # the OTHER layer's region is bit-untouched
        np.testing.assert_array_equal(np.asarray(ck[base:base + P]),
                                      np.asarray(ck_ref))
        np.testing.assert_array_equal(np.asarray(cv[base:base + P]),
                                      np.asarray(cv_ref))
        other = slice(P, 2 * P) if base == 0 else slice(0, P)
        np.testing.assert_array_equal(np.asarray(ck[other]),
                                      np.asarray(kpool[other]))
        np.testing.assert_array_equal(np.asarray(cv[other]),
                                      np.asarray(vpool[other]))


def test_page_walk_builder_matches_a_numpy_loop():
    """build_page_walk alone: entries, length, owners, positions and the
    per-slot mask against the definition written as loops (entry j of
    row r is live while j * ps < seq_lens[r]); incl. an empty row, an
    overfull row (all its pp entries, no more) and a shared page."""
    from paddle_tpu.nn.functional.paged_attention import (
        build_page_walk, stream_chunk_pages)

    rng = np.random.RandomState(3)
    ps, b, pp = 16, 5, 30
    cp = stream_chunk_pages(ps)
    lens = np.array([0, 33, pp * ps + 7, 16, 250], np.int32)
    tables = rng.randint(1, 500, (b, pp)).astype(np.int32)
    tables[4, :2] = tables[1, :2]
    walk = build_page_walk(jnp.asarray(tables), jnp.asarray(lens), ps)

    pages, owner, pos = [], [], []
    for r in range(b):
        for j in range(pp):
            if j * ps < lens[r]:
                pages.append(tables[r, j])
                owner.append(r)
                pos.append(j * ps)
    n = len(pages)
    E = -(-(b * pp) // cp) * cp
    assert n == 0 + 3 + pp + 1 + 16 and n % cp
    tok = np.full((E, ps), -1, np.int32)
    for e in range(n):
        for t in range(ps):
            if pos[e] + t < lens[owner[e]]:
                tok[e, t] = owner[e]
    assert int(walk.length[0]) == n
    index = np.asarray(walk.index)
    assert index.shape == (E,)
    got_pages = tables.ravel()[index]
    np.testing.assert_array_equal(got_pages[:n], pages)
    # past the end: a live page again, never one no table names
    np.testing.assert_array_equal(got_pages[n:], pages[0])
    np.testing.assert_array_equal(np.asarray(walk.owner),
                                  owner + [-1] * (E - n))
    np.testing.assert_array_equal(np.asarray(walk.pos)[:n], pos)
    np.testing.assert_array_equal(
        np.asarray(walk.tok_owner), tok.reshape(E // cp, cp * ps))


def test_inplace_overfull_row_masked_noop_write():
    """seq_lens < pages_per_seq*page_size precondition guard (ADVICE
    r5): a row that is exactly full has no free slot — the in-place
    kernel must NOT overwrite slot lens%ps of its last allocated page
    (the clamped-index corruption), while other rows' appends still
    land. Covers both the bf16 and the int8 in-place kernels."""
    from paddle_tpu.nn.functional.paged_attention import (
        paged_decode_attention_inplace, paged_decode_attention_inplace_q,
        quantize_kv_rows)

    rng = np.random.RandomState(11)
    b, n_kv, d, ps, pp, P = 2, 2, 128, 4, 6, 32
    q = jnp.asarray(rng.randn(b, n_kv, d).astype(np.float32))
    nk = jnp.asarray(rng.randn(b, n_kv, d).astype(np.float32))
    nv = jnp.asarray(rng.randn(b, n_kv, d).astype(np.float32))
    kpool = jnp.asarray(rng.randn(P, n_kv, ps, d).astype(np.float32))
    vpool = jnp.asarray(rng.randn(P, n_kv, ps, d).astype(np.float32))
    lens_np = np.array([pp * ps, 5], np.int32)   # row 0 exactly full
    tables_np = np.zeros((b, pp), np.int32)
    tables_np[0] = np.arange(1, 1 + pp)
    tables_np[1, :2] = [7, 8]
    lens, tables = jnp.asarray(lens_np), jnp.asarray(tables_np)

    out, ck, cv = paged_decode_attention_inplace(
        q, nk, nv, kpool, vpool, lens, tables, pool_base=0)
    # expected: ONLY row 1's token written (page tables[1, 5//4]=8,
    # slot 1); row 0's pages — last one included — bit-identical
    exp_k = kpool.at[8, :, 1].set(nk[1])
    exp_v = vpool.at[8, :, 1].set(nv[1])
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(exp_k))
    np.testing.assert_array_equal(np.asarray(cv), np.asarray(exp_v))
    assert np.isfinite(np.asarray(out)).all()

    # int8 variant: quantized pools + scale planes equally untouched for
    # the overfull row
    kq, s_k = quantize_kv_rows(kpool)
    vq, s_v = quantize_kv_rows(vpool)
    # build planes positionally: scale of (page p, slot s) at col p*ps+s
    ks_np = np.zeros((n_kv, P * ps), np.float32)
    vs_np = np.zeros((n_kv, P * ps), np.float32)
    for p in range(P):
        for s in range(ps):
            ks_np[:, p * ps + s] = np.asarray(s_k)[p, :, s]
            vs_np[:, p * ps + s] = np.asarray(s_v)[p, :, s]
    out_q, kq2, ks2, vq2, vs2 = paged_decode_attention_inplace_q(
        q, nk, nv, kq, jnp.asarray(ks_np), vq, jnp.asarray(vs_np),
        lens, tables, pool_base=0, pool_pages=P)
    nkq, nks = quantize_kv_rows(nk)
    nvq, nvs = quantize_kv_rows(nv)
    exp_kq = kq.at[8, :, 1].set(nkq[1])
    exp_ks = jnp.asarray(ks_np).at[:, 8 * ps + 1].set(nks[1])
    np.testing.assert_array_equal(np.asarray(kq2), np.asarray(exp_kq))
    np.testing.assert_allclose(np.asarray(ks2), np.asarray(exp_ks),
                               rtol=1e-6)
    exp_vq = vq.at[8, :, 1].set(nvq[1])
    np.testing.assert_array_equal(np.asarray(vq2), np.asarray(exp_vq))
    assert np.isfinite(np.asarray(out_q)).all()


def test_int8_kv_fused_kernel_parity():
    """Cache-KV int8 mode: the quantized fused kernel must match the
    dequantized-pool XLA reference within int8 tolerance, patch the
    written int8 rows + scale-plane columns in place, and leave other
    layers' regions untouched."""
    from paddle_tpu.nn.functional.paged_attention import (
        paged_decode_attention_inplace_q, quantize_kv_rows)

    rng = np.random.RandomState(7)
    b, n_kv, d, ps = 4, 2, 128, 4
    pp, P, L = 6, 16, 2
    T = P * ps
    q = jnp.asarray(rng.randn(b, n_kv, d).astype(np.float32))
    nk = jnp.asarray(rng.randn(b, n_kv, d).astype(np.float32))
    nv = jnp.asarray(rng.randn(b, n_kv, d).astype(np.float32))
    kf = rng.randn(L * P, n_kv, ps, d).astype(np.float32)
    vf = rng.randn(L * P, n_kv, ps, d).astype(np.float32)
    s_k = np.maximum(np.abs(kf).max(-1) / 127.0, 1e-8)
    kq = np.clip(np.round(kf / s_k[..., None]), -127, 127) \
        .astype(np.int8)
    s_v = np.maximum(np.abs(vf).max(-1) / 127.0, 1e-8)
    vq = np.clip(np.round(vf / s_v[..., None]), -127, 127) \
        .astype(np.int8)
    ks_plane = np.zeros((n_kv, L * T), np.float32)
    vs_plane = np.zeros((n_kv, L * T), np.float32)
    for p in range(L * P):
        for s in range(ps):
            ks_plane[:, p * ps + s] = s_k[p, :, s]
            vs_plane[:, p * ps + s] = s_v[p, :, s]
    lens_np = np.array([5, 0, 13, 9], np.int32)
    tables_np = np.zeros((b, pp), np.int32)
    perm = rng.permutation(np.arange(1, P))
    i = 0
    for r in range(b):
        n = -(-int(lens_np[r] + 1) // ps)
        tables_np[r, :n] = perm[i:i + n]
        i += n
    lens, tables = jnp.asarray(lens_np), jnp.asarray(tables_np)
    for base in (0, P):
        out, kq2, ks2, vq2, vs2 = paged_decode_attention_inplace_q(
            q, nk, nv, jnp.asarray(kq), jnp.asarray(ks_plane),
            jnp.asarray(vq), jnp.asarray(vs_plane), lens, tables,
            pool_base=base, pool_pages=P)
        kd = kq[base:base + P].astype(np.float32) \
            * s_k[base:base + P][..., None]
        vd = vq[base:base + P].astype(np.float32) \
            * s_v[base:base + P][..., None]
        ck_ref, cv_ref = write_kv_pages(
            jnp.asarray(kd), jnp.asarray(vd), nk, nv, lens, tables)
        ref = paged_attention(q, ck_ref, cv_ref, lens + 1, tables)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=0.08)
        kq2n, ks2n = np.asarray(kq2), np.asarray(ks2)
        for r in range(b):
            pos = int(lens_np[r])
            pg = tables_np[r, pos // ps] + base
            sl = pos % ps
            want_q, want_s = quantize_kv_rows(nk[r][None])
            np.testing.assert_array_equal(kq2n[pg, :, sl],
                                          np.asarray(want_q)[0])
            np.testing.assert_allclose(
                ks2n[:, pg * ps + sl], np.asarray(want_s)[0],
                rtol=1e-5)
        other = slice(P, 2 * P) if base == 0 else slice(0, P)
        np.testing.assert_array_equal(np.asarray(kq2)[other], kq[other])


class TestTruncateRollback:
    """BlockKVCacheManager.truncate (ISSUE 12): the speculative-
    decoding rejection path is a PAGE-TABLE rollback with exact
    free-pool/refcount accounting — shared prefix pages must never be
    freed by a rejection while another holder is live."""

    def _mgr(self, ps=4, pages=32):
        from paddle_tpu.inference.kv_cache import BlockKVCacheManager

        return BlockKVCacheManager(2, 2, 8, ps, num_pages=pages,
                                   reserve_scratch=True)

    def test_exact_free_pool_accounting(self):
        mgr = self._mgr()
        free0 = mgr.free_pages
        mgr.allocate("s", 20)                      # 5 pages
        assert mgr.free_pages == free0 - 5
        released = mgr.truncate("s", 9)            # keep ceil(9/4) = 3
        assert len(released) == 2
        assert mgr.free_pages == free0 - 3
        assert len(mgr._owned["s"]) == 3
        for p in released:
            assert mgr.refcount(p) == 0
        # released pages are immediately reusable
        mgr.grow("s", 2)
        assert mgr.free_pages == free0 - 5
        mgr.free("s")
        assert mgr.free_pages == free0 and mgr._refs == {}

    def test_noop_when_already_covered(self):
        mgr = self._mgr()
        mgr.allocate("s", 8)                       # 2 pages
        assert mgr.truncate("s", 8) == []
        assert mgr.truncate("s", 12) == []         # larger than held
        assert len(mgr._owned["s"]) == 2
        assert mgr.truncate("missing", 0) == []    # unknown seq: no-op

    def test_shared_prefix_pages_survive_truncate(self):
        """A truncated tail page also held by the prefix cache (or any
        sharer) drops to its other holder instead of the free list."""
        mgr = self._mgr()
        free0 = mgr.free_pages
        pages = mgr.allocate("a", 16)              # 4 pages
        mgr.retain(pages[:2])                      # prefix-cache refs
        released = mgr.truncate("a", 0)            # drop everything
        assert released == pages
        # tail pages freed; the retained prefix pages stay live at rc 1
        assert mgr.refcount(pages[0]) == 1
        assert mgr.refcount(pages[1]) == 1
        assert mgr.refcount(pages[2]) == 0
        assert mgr.free_pages == free0 - 2
        mgr.release_pages(pages[:2])               # cache eviction
        assert mgr.free_pages == free0

    def test_truncate_sharer_keeps_prefix_alive_for_owner(self):
        mgr = self._mgr()
        pa = mgr.allocate("a", 8)                  # 2 full pages
        mgr.share("b", pa)                         # b maps a's prefix
        mgr.grow("b", 2)                           # b's private tail
        # b speculates past its tail and rolls all the way back into
        # the SHARED region: a's pages must survive at refcount 1
        mgr.truncate("b", 4)                       # keep 1 shared page
        assert mgr.refcount(pa[0]) == 2
        assert mgr.refcount(pa[1]) == 1            # b's ref dropped
        assert pa[1] not in mgr._free              # a still owns it
        mgr.free("b")
        mgr.free("a")
        assert mgr._refs == {}

    def test_property_randomized_refcount_model(self):
        """Property test: a random op sequence (allocate/grow/share/
        truncate/free) against a pure-python refcount model — the
        manager's free list and refcounts must match the model after
        EVERY op."""
        rng = np.random.RandomState(0xC0FFEE)
        mgr = self._mgr(ps=4, pages=64)
        model_refs = {}                            # page -> rc
        model_owned = {}                           # seq -> [pages]
        next_seq = 0

        def check():
            assert mgr._refs == model_refs
            live = set(model_refs)
            expect_free = (mgr.num_pages - 1) - len(live)  # -scratch
            assert mgr.free_pages == expect_free
            for s, pgs in model_owned.items():
                assert mgr._owned.get(s, []) == pgs

        for _step in range(300):
            ops = ["alloc"]
            if model_owned:
                ops += ["grow", "truncate", "free", "share"]
            op = ops[rng.randint(len(ops))]
            seqs = list(model_owned)
            if op == "alloc" and mgr.free_pages >= 4:
                sid = f"s{next_seq}"
                next_seq += 1
                n_tok = int(rng.randint(1, 17))
                got = mgr.allocate(sid, n_tok)
                model_owned[sid] = list(got)
                for p in got:
                    model_refs[p] = 1
            elif op == "grow" and seqs and mgr.free_pages >= 2:
                sid = seqs[rng.randint(len(seqs))]
                got = mgr.grow(sid, int(rng.randint(1, 3)))
                model_owned[sid].extend(got)
                for p in got:
                    model_refs[p] = 1
            elif op == "share" and seqs:
                src = seqs[rng.randint(len(seqs))]
                if not model_owned[src]:
                    continue
                sid = f"s{next_seq}"
                next_seq += 1
                shared = model_owned[src][:rng.randint(
                    1, len(model_owned[src]) + 1)]
                mgr.share(sid, shared)
                model_owned[sid] = list(shared)
                for p in shared:
                    model_refs[p] += 1
            elif op == "truncate" and seqs:
                sid = seqs[rng.randint(len(seqs))]
                new_len = int(rng.randint(
                    0, 4 * len(model_owned[sid]) + 1))
                keep = -(-new_len // 4)
                expect_rel = model_owned[sid][keep:]
                got = mgr.truncate(sid, new_len)
                assert got == expect_rel
                del model_owned[sid][keep:]
                for p in expect_rel:
                    model_refs[p] -= 1
                    if model_refs[p] == 0:
                        del model_refs[p]
            elif op == "free" and seqs:
                sid = seqs[rng.randint(len(seqs))]
                mgr.free(sid)
                for p in model_owned.pop(sid):
                    model_refs[p] -= 1
                    if model_refs[p] == 0:
                        del model_refs[p]
            check()
        # drain everything: the pool must return to pristine
        for sid in list(model_owned):
            mgr.free(sid)
        assert mgr._refs == {}
        assert mgr.free_pages == mgr.num_pages - 1


def test_int8_kv_engine_tokens():
    """GenerationEngine kv_dtype='int8' end-to-end vs full-precision KV:
    greedy tokens must agree on a small model."""
    import paddle_tpu as paddle
    from paddle_tpu.inference import FusedCausalLM, GenerationEngine

    paddle.seed(5)
    mk = dict(vocab_size=256, embed_dim=256, num_heads=2,
              dim_feedforward=512, num_layers=2, max_position=128)
    model = FusedCausalLM(**mk)
    ids = np.random.RandomState(2).randint(1, 256, (2, 12))
    out_a = GenerationEngine(model, page_size=4, max_length=48,
                             decode_chunk=4).generate(
                                 ids, max_new_tokens=8)
    out_b = GenerationEngine(model, page_size=4, max_length=48,
                             decode_chunk=4, kv_dtype="int8").generate(
                                 ids, max_new_tokens=8)
    agree = float((out_a[:, 12:] == out_b[:, 12:]).mean())
    assert agree >= 0.75, (out_a[:, 12:], out_b[:, 12:])

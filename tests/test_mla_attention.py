"""The two latent-attention kernels (``pt_mla_paged_prefill``,
``pt_mla_paged_decode``) through the interpreter against plain ``jax.numpy``
at toy widths: a dense softmax over the rows the block tables name. What a
chunk writes into the pool, what it leaves alone (padded rows, rows past the
table), a table that is full."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional import mla_attention as M

H, W, R, PS, P, PP = 4, 128, 32, 4, 64, 12


def _pool(rng):
    return jnp.asarray(rng.randn(P, PS, W).astype(np.float32))


def _dense(q, keys, n_keys):
    """q [H, W] over the first ``n_keys`` of keys [S, W] -> [H, R]."""
    s = np.einsum("hw,kw->hk", q, keys[:n_keys])
    p = np.exp(s - s.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return p @ keys[:n_keys, :R]


@pytest.mark.parametrize("backend", ["auto", "interpret"])
@pytest.mark.parametrize("c,start,vlen", [(8, 0, 8), (8, 5, 6), (16, 13, 16),
                                          (8, 40, 8), (8, 44, 8)])
def test_prefill_chunk_writes_and_attends(backend, c, start, vlen):
    rng = np.random.RandomState(c + start)
    pool = _pool(rng)
    q = rng.randn(c, H, W).astype(np.float32) * 0.3
    rows = rng.randn(c, W).astype(np.float32)
    tables = rng.permutation(np.arange(1, P))[:PP][None].astype(np.int32)
    out, new = M.mla_prefill_attend(
        jnp.asarray(q), jnp.asarray(rows), pool, jnp.asarray(tables),
        jnp.asarray([start]), jnp.asarray([vlen]), v_width=R,
        backend=backend)
    # the pool: the chunk's valid rows that fit the table, nothing else
    want = np.asarray(pool).copy()
    for r in range(vlen):
        pos = start + r
        if pos < PP * PS:
            want[tables[0, pos // PS], pos % PS] = rows[r]
    assert np.array_equal(np.asarray(new), want)
    keys = want[tables[0]].reshape(PP * PS, W)
    for r in range(vlen):
        if start + r < PP * PS:
            assert np.allclose(np.asarray(out[r]),
                               _dense(q[r], keys, start + r + 1), atol=2e-5)


@pytest.mark.parametrize("backend", ["auto", "interpret"])
@pytest.mark.parametrize("lens", [[0, 5, 47], [4, 48, 17], [3, 0, 12]])
def test_decode_step_appends_and_attends(backend, lens):
    rng = np.random.RandomState(sum(lens))
    pool = _pool(rng)
    big = jnp.concatenate([pool * 0.5, pool], 0)       # the layer fold
    S = len(lens)
    q = rng.randn(S, H, W).astype(np.float32) * 0.3
    nr = rng.randn(S, W).astype(np.float32)
    tables = rng.permutation(np.arange(1, P))[:S * PP].reshape(S, PP) \
        .astype(np.int32)
    out, new = M.mla_decode_attend(
        jnp.asarray(q), jnp.asarray(nr), big, jnp.asarray(tables),
        jnp.asarray(lens, jnp.int32), P, v_width=R, backend=backend)
    want = np.asarray(big).copy()
    for s, n in enumerate(lens):
        if n < PP * PS:                 # a full table: no slot, no write
            want[P + tables[s, n // PS], n % PS] = nr[s]
    assert np.array_equal(np.asarray(new), want)
    for s, n in enumerate(lens):
        keys = np.concatenate(
            [np.asarray(big)[P + tables[s]].reshape(PP * PS, W)[:n],
             nr[s: s + 1]])
        assert np.allclose(np.asarray(out[s]), _dense(q[s], keys, n + 1),
                           atol=2e-5)


def test_bf16_pool_keeps_its_dtype_and_rounds_the_rows():
    rng = np.random.RandomState(0)
    pool = _pool(rng).astype(jnp.bfloat16)
    rows = jnp.asarray(rng.randn(8, W).astype(np.float32))
    tables = jnp.asarray(np.arange(1, PP + 1)[None].astype(np.int32))
    q = jnp.asarray(rng.randn(8, H, W).astype(np.float32) * 0.3)
    for backend in ("auto", "interpret"):
        out, new = M.mla_prefill_attend(
            q.astype(jnp.bfloat16), rows, pool, tables, jnp.asarray([4]),
            jnp.asarray([8]), v_width=R, backend=backend)
        assert new.dtype == jnp.bfloat16 and out.dtype == jnp.float32
        assert np.array_equal(np.asarray(new[2, 1].astype(jnp.float32)),
                              np.asarray(rows[1].astype(jnp.bfloat16)
                                         .astype(jnp.float32)))


def test_unknown_backend_is_refused():
    z = jnp.zeros
    with pytest.raises(ValueError, match="backend"):
        M.mla_prefill_attend(z((4, H, W)), z((4, W)), z((P, PS, W)),
                             z((1, PP), jnp.int32), z((1,), jnp.int32),
                             z((1,), jnp.int32), v_width=R, backend="xla")
    with pytest.raises(ValueError, match="backend"):
        M.mla_decode_attend(z((2, H, W)), z((2, W)), z((P, PS, W)),
                            z((2, PP), jnp.int32), z((2,), jnp.int32),
                            v_width=R, backend="pallas")

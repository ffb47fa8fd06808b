"""The state-space pieces (``nn/functional/ssm.py``) against the recurrence
taken token by token: the chunked SSD scan and the one-token update with a
non-zero initial state, through plain XLA and through the Pallas
interpreter; rows that pad a bucketed chunk leave both states as they were;
the conv's carried tail is the last three VALID rows."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional import ssm

H, P, N = 8, 16, 32


def _inputs(T, seed=0, pad=0):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(T, H, P) * 0.5, jnp.float32)
    dt = jnp.asarray(np.log1p(np.exp(rng.randn(T, H) - 2)), jnp.float32)
    if pad:
        dt = dt.at[T - pad:].set(0.0)
    A = -jnp.asarray(rng.uniform(1, 16, H), jnp.float32)
    B = jnp.asarray(rng.randn(T, N) * 0.5, jnp.float32)
    C = jnp.asarray(rng.randn(T, N) * 0.5, jnp.float32)
    D = jnp.asarray(rng.uniform(0.5, 1.5, H), jnp.float32)
    s0 = jnp.asarray(rng.randn(N, H * P), jnp.float32)
    return x, dt, A, B, C, D, s0


@pytest.mark.parametrize("backend", ["xla", "interpret"])
@pytest.mark.parametrize("T,Q", [(64, 64), (128, 32), (24, 64)])
def test_ssd_scan_matches_token_by_token(T, Q, backend):
    x, dt, A, B, C, D, s0 = _inputs(T, seed=T + Q)
    y_ref, s_ref = ssm.ssm_scan_reference(x, dt, A, B, C, D, s0)
    y, s = ssm.ssd_chunk_scan(x, dt, A, B, C, D, s0, chunk_size=Q,
                              backend=backend)
    np.testing.assert_allclose(y, y_ref, atol=2e-5)
    np.testing.assert_allclose(s, s_ref, atol=2e-5)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_ssd_scan_in_pieces_equals_one_pass(backend):
    """A prompt prefilled in chunks: the final state of one piece is the
    initial state of the next."""
    x, dt, A, B, C, D, s0 = _inputs(96, seed=3)
    y_all, s_all = ssm.ssm_scan_reference(x, dt, A, B, C, D, s0)
    s, ys = s0, []
    for lo in (0, 32, 64):
        sl = slice(lo, lo + 32)
        y, s = ssm.ssd_chunk_scan(x[sl], dt[sl], A, B[sl], C[sl], D, s,
                                  chunk_size=32, backend=backend)
        ys.append(y)
    np.testing.assert_allclose(jnp.concatenate(ys), y_all, atol=2e-5)
    np.testing.assert_allclose(s, s_all, atol=2e-5)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_padded_rows_do_not_advance_the_state(backend):
    """dt = 0 on the padding of a bucketed chunk: the final state is the
    state after the valid rows, whatever the padded rows hold."""
    x, dt, A, B, C, D, s0 = _inputs(32, seed=5, pad=9)
    _, s_valid = ssm.ssm_scan_reference(x[:23], dt[:23], A, B[:23], C[:23],
                                        D, s0)
    _, s = ssm.ssd_chunk_scan(x, dt, A, B, C, D, s0, chunk_size=32,
                              backend=backend)
    np.testing.assert_allclose(s, s_valid, atol=2e-5)


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_decode_update_matches_one_scan_step_and_is_in_place(backend):
    rng = np.random.RandomState(1)
    L, S = 3, 4
    x, dt, A, B, C, D, _ = _inputs(S, seed=9)
    state = jnp.asarray(rng.randn(L, S, N, H * P), jnp.float32)
    dt = dt.at[1].set(0.0)                   # slot 1 is not decoding
    decay = ssm.expand_heads(jnp.exp(dt * A[None, :]), P)
    dtx = ssm.expand_heads(dt, P) * x.reshape(S, -1)
    new, y = ssm.ssm_decode_update(state, 1, decay, dtx, B, C,
                                   backend=backend)
    for b in range(S):
        y1, s1 = ssm.ssm_scan_reference(
            x[b: b + 1], dt[b: b + 1], A, B[b: b + 1], C[b: b + 1],
            jnp.zeros_like(D), state[1, b])
        np.testing.assert_allclose(new[1, b], s1, atol=1e-5)
        np.testing.assert_allclose(y[b], y1[0], atol=1e-5)
    # the other layers and the idle slot: bit for bit
    assert bool((new[0] == state[0]).all() and (new[2] == state[2]).all())
    assert bool((new[1, 1] == state[1, 1]).all())


def test_conv_tail_is_the_last_valid_rows():
    rng = np.random.RandomState(2)
    C_, k = 24, 4
    x = jnp.asarray(rng.randn(2, 16, C_), jnp.float32)
    tail = jnp.asarray(rng.randn(2, k - 1, C_), jnp.float32)
    w = jnp.asarray(rng.randn(k, C_), jnp.float32)
    b = jnp.asarray(rng.randn(C_), jnp.float32)
    valid = jnp.asarray([16, 2], jnp.int32)
    y, new = ssm.causal_conv1d_chunk(x, tail, w, b, valid)
    np.testing.assert_array_equal(new[0], x[0, 13:16])
    # two valid rows: the tail is one old row and the two new ones
    np.testing.assert_array_equal(new[1, 0], tail[1, 2])
    np.testing.assert_array_equal(new[1, 1:], x[1, :2])
    # and the chunk form equals the step form row by row
    t = tail
    for i in range(16):
        yi, t = ssm.causal_conv1d_step(x[:, i], t, w, b)
        np.testing.assert_allclose(yi, y[:, i], atol=1e-6)
    # an inactive row keeps its tail
    _, kept = ssm.causal_conv1d_step(x[:, 0], tail, w, b,
                                     jnp.asarray([True, False]))
    np.testing.assert_array_equal(kept[1], tail[1])
    assert not bool((kept[0] == tail[0]).all())


@pytest.mark.parametrize("backend", ["xla", "interpret"])
def test_a_piece_that_is_no_multiple_of_the_chunk(backend):
    x, dt, A, B, C, D, s0 = _inputs(48, seed=11)
    y_ref, s_ref = ssm.ssm_scan_reference(x, dt, A, B, C, D, s0)
    y, s = ssm.ssd_chunk_scan(x, dt, A, B, C, D, s0, chunk_size=32,
                              backend=backend)
    assert y.shape == (48, H * P)
    np.testing.assert_allclose(y, y_ref, atol=2e-5)
    np.testing.assert_allclose(s, s_ref, atol=2e-5)

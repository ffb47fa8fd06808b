"""The attention of a window / full pattern (``nn/functional/
mixed_attention.py``): the window kernels with a sink over the slot ring,
the full kernels over the pool of packed K|V rows, K heads of 192 and V
heads of 128, each through the interpreter and through its XLA path
against a plain numpy attention."""
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional.mixed_attention import (
    WindowRing, gqa_decode_attend, gqa_prefill_attend, pack_rows,
    swa_decode_attend, swa_prefill_attend, window_pairs)

DK, DV, G, H = 192, 128, 2, 4
KL = 256


def bf(a):
    return np.asarray(jnp.asarray(a, jnp.float32).astype(jnp.bfloat16)
                      .astype(jnp.float32), np.float64)


def attend(q, k, v, qpos, kpos, window=None, sink=None):
    """q [n, H, dk], k [m, G, dk], v [m, G, dv] (positions kpos) -> [n, H,
    dv]: query i sees keys of positions in (qpos - window, qpos], >= 0."""
    n, nh, _ = q.shape
    hpg = nh // k.shape[1]
    out = np.zeros((n, nh, v.shape[2]))
    for i in range(n):
        vis = (kpos <= qpos[i]) & (kpos >= 0)
        if window:
            vis &= kpos > qpos[i] - window
        for h in range(nh):
            s = k[vis, h // hpg] @ q[i, h]
            m = s.max() if sink is None else max(s.max(), sink[h])
            e = np.exp(s - m)
            den = e.sum() + (0.0 if sink is None else np.exp(sink[h] - m))
            out[i, h] = e @ v[vis, h // hpg] / den
    return out


def test_window_pairs_by_a_hand_count():
    for start, n, w in [(0, 3, 128), (200, 10, 128), (120, 20, 128),
                        (0, 300, 128), (5, 1, 8), (127, 1, 128)]:
        assert window_pairs(start, n, w) == sum(
            min(start + i + 1, w) for i in range(n))


# ------------------------------------------------------------ window

def _ring_with_history(rng, start, Wr, slot, layer, slots=3, layers=2):
    """A ring of random rows whose ``slot`` / ``layer`` rows hold the
    sequence's positions ``start - Wr .. start - 1`` (row p % Wr) and the
    sequence's history of K / V (positions 0 .. start - 1)."""
    hk = bf(rng.randn(max(start, 1), G, DK))
    hv = bf(rng.randn(max(start, 1), G, DV))
    rk = bf(rng.randn(layers, slots, G, Wr, KL))
    rv = bf(rng.randn(layers, slots, G, Wr, DV))
    for p in range(max(0, start - Wr), start):
        rk[layer, slot, :, p % Wr, :DK] = hk[p]
        rk[layer, slot, :, p % Wr, DK:] = 0.0
        rv[layer, slot, :, p % Wr] = hv[p]
    ring = WindowRing(jnp.asarray(rk, jnp.bfloat16),
                      jnp.asarray(rv, jnp.bfloat16))
    return ring, hk, hv


@pytest.mark.parametrize("backend", ["auto", "interpret"])
@pytest.mark.parametrize("start,c,n,sink", [
    (200, 256, 250, True),       # crosses the window's edge, bucketed tail
    (0, 64, 64, True),           # the first chunk: nothing before it
    (60, 128, 128, False),       # part of the window before, no sink
])
def test_window_prefill_against_numpy(backend, start, c, n, sink):
    rng = np.random.RandomState(start + c)
    Wr, slot, layer = 128, 1, 1
    ring, hk, hv = _ring_with_history(rng, start, Wr, slot, layer)
    q = bf(rng.randn(c, H, DK) * 0.2)
    k, v = bf(rng.randn(c, G, DK)), bf(rng.randn(c, G, DV))
    sk = rng.randn(H).astype(np.float32) if sink else None
    out, ring2 = swa_prefill_attend(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16), ring, layer, jnp.asarray([slot]),
        jnp.asarray([start]), jnp.asarray([n]),
        None if sk is None else jnp.asarray(sk), backend=backend)
    allk = np.concatenate([hk[:start], k])
    allv = np.concatenate([hv[:start], v])
    want = attend(q[:n], allk, allv, start + np.arange(n),
                  np.arange(start + c), Wr, sk)
    got = np.asarray(out)[:n]
    assert np.abs(got - want).max() < 2e-2 * np.abs(want).max()
    # the slot's ring holds the last Wr positions up to the last real row;
    # every other slot and layer is as it was
    last = start + n - 1
    rk2, rk = np.asarray(ring2.k, np.float64), np.asarray(ring.k, np.float64)
    for p in range(max(0, last - Wr + 1), last + 1):
        assert np.array_equal(rk2[layer, slot, :, p % Wr, :DK], allk[p])
        assert np.array_equal(np.asarray(ring2.v, np.float64)[
            layer, slot, :, p % Wr], allv[p])
    keep = np.ones(rk.shape[:2], bool)
    keep[layer, slot] = False
    assert np.array_equal(rk2[keep], rk[keep])


@pytest.mark.parametrize("backend", ["auto", "interpret"])
def test_window_decode_against_numpy(backend):
    """Three slots: 6 tokens cached (the ring half empty), 127 (the token
    makes exactly 128 keys with itself), 300 (the ring wrapped); the
    third is not active and keeps its ring."""
    rng = np.random.RandomState(5)
    Wr, layer, S = 128, 0, 3
    lens = np.asarray([6, 127, 300])
    active = np.asarray([True, True, False])
    rings, hist = [], []
    rk = np.zeros((2, S, G, Wr, KL))
    rv = np.zeros((2, S, G, Wr, DV))
    for s in range(S):
        r, hk, hv = _ring_with_history(rng, int(lens[s]), Wr, s, layer,
                                       slots=S)
        rk[:, s] = np.asarray(r.k, np.float64)[:, s]
        rv[:, s] = np.asarray(r.v, np.float64)[:, s]
        hist.append((hk, hv))
    ring = WindowRing(jnp.asarray(rk, jnp.bfloat16),
                      jnp.asarray(rv, jnp.bfloat16))
    q = bf(rng.randn(S, H, DK) * 0.2)
    k, v = bf(rng.randn(S, G, DK)), bf(rng.randn(S, G, DV))
    sk = rng.randn(H).astype(np.float32)
    out, ring2 = swa_decode_attend(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16), ring, layer, jnp.asarray(lens),
        jnp.asarray(active), jnp.asarray(sk), backend=backend)
    got = np.asarray(out)
    for s in (0, 1):
        n = int(lens[s])
        hk, hv = hist[s]
        allk = np.concatenate([hk[:n], k[s][None]])
        allv = np.concatenate([hv[:n], v[s][None]])
        want = attend(q[s][None], allk, allv, np.asarray([n]),
                      np.arange(n + 1), Wr, sk)[0]
        assert np.abs(got[s] - want).max() < 2e-2 * np.abs(want).max()
        assert np.array_equal(np.asarray(ring2.k, np.float64)[
            layer, s, :, n % Wr, :DK], k[s])
    assert np.array_equal(np.asarray(ring2.k)[:, 2], np.asarray(ring.k)[:, 2])
    assert np.array_equal(np.asarray(ring2.k)[1], np.asarray(ring.k)[1])


# -------------------------------------------------------------- full

RW = G * (DK + DV)             # 640: two kv heads, no pad


@pytest.mark.parametrize("backend", ["auto", "interpret"])
@pytest.mark.parametrize("start,c,n", [(0, 48, 48), (37, 64, 50)])
def test_full_prefill_against_numpy(backend, start, c, n):
    """A chunk written into its pages at a start inside a page and
    attended over the cached prefix, K 192 / V 128 a head."""
    rng = np.random.RandomState(c + start)
    ps, pp, P = 16, 8, 40
    pool = bf(rng.randn(P, ps, RW))
    table = rng.permutation(np.arange(1, P))[:pp]
    hk, hv = bf(rng.randn(start + c, G, DK)), bf(rng.randn(start + c, G, DV))
    rows = np.asarray(pack_rows(jnp.asarray(hk), jnp.asarray(hv), RW))
    for p in range(start):
        pool[table[p // ps], p % ps] = rows[p]
    q = bf(rng.randn(c, H, DK) * 0.2)
    out, pool2 = gqa_prefill_attend(
        jnp.asarray(q, jnp.bfloat16),
        jnp.asarray(rows[start:], jnp.bfloat16),
        jnp.asarray(pool, jnp.bfloat16), jnp.asarray(table[None]),
        jnp.asarray([start]), jnp.asarray([n]), n_kv=G, v_dim=DV,
        backend=backend)
    want = attend(q[:n], hk[:start + n], hv[:start + n],
                  start + np.arange(n), np.arange(start + n))
    got = np.asarray(out)[:n]
    assert np.abs(got - want).max() < 2e-2 * np.abs(want).max()
    p2 = np.asarray(pool2, np.float64)
    for p in range(start, start + n):
        assert np.array_equal(p2[table[p // ps], p % ps], rows[p])
    # padded rows never land in the pool
    for p in range(start + n, min(start + c, pp * ps)):
        assert np.array_equal(p2[table[p // ps], p % ps],
                              pool[table[p // ps], p % ps])


@pytest.mark.parametrize("backend", ["auto", "interpret"])
def test_full_decode_against_numpy(backend):
    rng = np.random.RandomState(11)
    ps, pp, P, S, base = 16, 4, 20, 2, 10
    lens = np.asarray([7, 40])
    pool = bf(rng.randn(P, ps, RW))
    tables = np.stack([rng.permutation(np.arange(1, 10))[:pp]
                       for _ in range(S)])
    q = bf(rng.randn(S, H, DK) * 0.2)
    k, v = bf(rng.randn(S, G, DK)), bf(rng.randn(S, G, DV))
    hist = []
    for s in range(S):
        hk, hv = bf(rng.randn(lens[s], G, DK)), bf(rng.randn(lens[s], G, DV))
        rows = np.asarray(pack_rows(jnp.asarray(hk), jnp.asarray(hv), RW))
        for p in range(lens[s]):
            pool[base + tables[s, p // ps], p % ps] = rows[p]
        hist.append((hk, hv))
    out, pool2 = gqa_decode_attend(
        jnp.asarray(q, jnp.bfloat16), jnp.asarray(k, jnp.bfloat16),
        jnp.asarray(v, jnp.bfloat16), jnp.asarray(pool, jnp.bfloat16),
        jnp.asarray(tables), jnp.asarray(lens), base, backend=backend)
    for s in range(S):
        hk, hv = hist[s]
        n = int(lens[s])
        want = attend(q[s][None], np.concatenate([hk, k[s][None]]),
                      np.concatenate([hv, v[s][None]]), np.asarray([n]),
                      np.arange(n + 1))[0]
        assert np.abs(np.asarray(out)[s] - want).max() \
            < 2e-2 * np.abs(want).max()
        row = np.asarray(pack_rows(jnp.asarray(k[s][None]),
                                   jnp.asarray(v[s][None]), RW))[0]
        assert np.array_equal(np.asarray(pool2, np.float64)[
            base + tables[s, n // ps], n % ps], row)

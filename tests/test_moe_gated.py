"""The gated routed-expert layer (``nn/functional/moe_gated.py``): routing
(softmax over the chosen logits), the grouped-GEMM and the streamed path
against a plain loop over experts, ``experts_held``, the pick counters, and
THE SHARE TEST: the two halves of the bank, each computed by a layer that
was told its half, with the shared MLP counted once, add up to the uncut
layer; and, at the rag cell's prefill routing shape, the work units the
grouped path reports."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.nn.functional import moe_gated as mg

T, D, E, K, F = 12, 32, 8, 3, 16


def _bank(seed=0, layers=2):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(T, D), jnp.float32)
    router = jnp.asarray(rng.randn(D, E), jnp.float32)
    w1 = jnp.asarray(rng.randn(layers, E, D, 2 * F) * 0.2, jnp.float32)
    w2 = jnp.asarray(rng.randn(layers, E, F, D) * 0.2, jnp.float32)
    return x, router, w1, w2


def _plain(x, gates, idx, w1, w2, experts):
    """Sum over the picks that name an expert in ``experts``."""
    out = np.zeros((T, D), np.float32)
    for t in range(T):
        for g, e in zip(np.asarray(gates[t]), np.asarray(idx[t])):
            if int(e) in experts:
                ab = np.asarray(x[t] @ w1[e])
                a, b = ab[:F], ab[F:]
                out[t] += g * ((a / (1 + np.exp(-a))) * b) @ np.asarray(w2[e])
    return out


def test_gates_are_the_softmax_over_the_chosen_logits():
    x, router, _, _ = _bank()
    gates, idx = mg.route_topk_softmax(x, router, K)
    logits = np.asarray(x @ router)
    for t in range(T):
        top = np.argsort(-logits[t])[:K]
        assert set(top) == set(np.asarray(idx[t]))
        chosen = logits[t][np.asarray(idx[t])]
        want = np.exp(chosen - chosen.max())
        np.testing.assert_allclose(gates[t], want / want.sum(), rtol=1e-5)
    # NOT the softmax over all E restricted to the chosen (it sums to 1)
    full = jax.nn.softmax(jnp.asarray(logits), -1)
    picked = jnp.take_along_axis(full, idx, axis=1)
    assert float(jnp.abs(gates - picked).max()) > 1e-3
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("held", [(0, E), (0, E // 2), (E // 2, E // 2)])
@pytest.mark.parametrize("path", ["grouped", "stream", "stream-interpret"])
def test_paths_compute_only_the_held_experts(held, path):
    x, router, w1, w2 = _bank(seed=3)
    gates, idx = mg.route_topk_softmax(x, router, K)
    first, count = held
    layer = 1
    bw1, bw2 = w1[:, first: first + count], w2[:, first: first + count]
    if path == "grouped":
        got, _ = mg.moe_gated_grouped(x, gates, idx, bw1, bw2, layer, held,
                                      backend="xla")
    else:
        got = mg.moe_gated_stream(
            x, gates, idx, bw1, bw2, layer, held,
            backend="interpret" if path.endswith("interpret") else "xla")
    want = _plain(x, gates, idx, w1[layer], w2[layer],
                  set(range(first, first + count)))
    np.testing.assert_allclose(got, want, atol=2e-4)


def test_the_shares_add_up_to_the_uncut_layer():
    """Experts 0..E/2-1 on one chip, E/2..E-1 on the other, the shared MLP
    (computed alike on both) counted once == the whole layer."""
    x, router, w1, w2 = _bank(seed=7, layers=1)
    rng = np.random.RandomState(8)
    s1 = jnp.asarray(rng.randn(D, 2 * 24) * 0.2, jnp.float32)
    s2 = jnp.asarray(rng.randn(24, D) * 0.2, jnp.float32)
    gates, idx = mg.route_topk_softmax(x, router, K)
    half = E // 2
    parts = [mg.moe_gated_grouped(x, gates, idx, w1[:, lo: lo + half],
                                  w2[:, lo: lo + half], 0, (lo, half),
                                  backend="xla")[0]
             for lo in (0, half)]
    shared = mg.gated_mlp(x, s1, s2)
    whole = _plain(x, gates, idx, w1[0], w2[0], set(range(E))) \
        + np.asarray(shared)
    np.testing.assert_allclose(parts[0] + parts[1] + shared, whole,
                               atol=2e-4)
    # and a half alone is NOT the whole: the cut is visible
    assert float(jnp.abs(parts[0] + shared - whole).max()) > 1e-2


def test_pick_counts():
    idx = jnp.asarray([[0, 5, 2], [1, 6, 7], [3, 3, 4]], jnp.int32)
    rows = jnp.asarray([True, True, False])
    got = mg.pick_counts(idx, rows, (0, 4))
    # 2 live rows x 3 picks; here: 0, 2 and 1; experts hit: 0, 1, 2
    np.testing.assert_array_equal(got, [6, 3, 3])
    assert got.dtype == jnp.int32
    np.testing.assert_array_equal(
        mg.pick_counts(idx, jnp.ones((3,), bool), (4, 4)), [9, 4, 4])


def test_dense_gates_drop_absent_picks():
    gates = jnp.asarray([[0.5, 0.3, 0.2]], jnp.float32)
    idx = jnp.asarray([[1, 6, 2]], jnp.int32)
    np.testing.assert_allclose(mg.dense_gates(gates, idx, (0, 4)),
                               [[0, 0.5, 0.2, 0]])
    np.testing.assert_allclose(mg.dense_gates(gates, idx, (4, 4)),
                               [[0, 0, 0.3, 0]])


class TestPrefillChunkShape:
    """``moe_gated_grouped`` at the rag cell's prefill shape scaled down
    in d and f only: 256 tokens, top-10 of 72, experts 0..35 held, so
    2,560 sorted rows of which about half name a held expert."""

    T, D, E, K, F, HELD = 256, 128, 72, 10, 64, (0, 36)

    def _layer(self, seed):
        rng = np.random.RandomState(seed)
        x = jnp.asarray(rng.randn(self.T, self.D), jnp.float32)
        router = jnp.asarray(rng.randn(self.D, self.E), jnp.float32)
        count = self.HELD[1]
        w1 = jnp.asarray(rng.randn(2, count, self.D, 2 * self.F) * 0.2,
                         jnp.float32)
        w2 = jnp.asarray(rng.randn(2, count, self.F, self.D) * 0.2,
                         jnp.float32)
        gates, idx = mg.route_topk_softmax(x, router, self.K)
        return x, gates, idx, w1, w2

    @pytest.mark.parametrize("seed", [0, 1])
    def test_equals_the_dense_per_expert_reference(self, seed):
        x, gates, idx, w1, w2 = self._layer(seed)
        got, _ = mg.moe_gated_grouped(x, gates, idx, w1, w2, 1, self.HELD,
                                      backend="xla")
        xs, g, ix = (np.asarray(a) for a in (x, gates, idx))
        want = np.zeros((self.T, self.D), np.float32)
        for e in range(self.HELD[1]):
            t, slot = np.nonzero(ix == e)
            ab = xs[t] @ np.asarray(w1[1, e])
            a, b = ab[:, :self.F], ab[:, self.F:]
            np.add.at(want, t, g[t, slot][:, None]
                      * (((a / (1 + np.exp(-a))) * b) @ np.asarray(w2[1, e])))
        np.testing.assert_allclose(got, want, atol=2e-4)

    @pytest.mark.parametrize("seed", [0, 1])
    def test_reported_units_equal_a_recount_from_the_picks(self, seed):
        """Both GEMMs walk the static 93 units (20 row tiles of 128 + 2
        x 36 + 1) over one column block each at these widths; a unit is
        live where an expert's interval of sorted rows touches a tile.
        Under 55% are."""
        x, gates, idx, w1, w2 = self._layer(seed)
        _, units = mg.moe_gated_grouped(x, gates, idx, w1, w2, 0,
                                        self.HELD, backend="xla")
        ix = np.asarray(idx)
        counts = np.bincount(ix[ix < self.HELD[1]],
                             minlength=self.HELD[1])
        ends = np.cumsum(counts)
        live = sum(-(-int(b) // 128) - int(a) // 128
                   for a, b in zip(ends - counts, ends) if b > a)
        np.testing.assert_array_equal(units, [2 * 93, 2 * live])
        assert units.dtype == jnp.int32
        assert 0.40 * 93 < live < 0.55 * 93

    def test_the_counts_are_not_scattered(self):
        """The per-expert counts come from compares and a column sum: a
        scatter-add of the 2,560 sort keys runs serially on the chip
        (``jnp.bincount``: 0.15 ms a layer, PR 34)."""
        x, gates, idx, w1, w2 = self._layer(0)
        closed = jax.make_jaxpr(lambda *a: mg.moe_gated_grouped(
            *a, 0, self.HELD, backend="xla"))(x, gates, idx, w1, w2)

        def names(jaxpr):
            for eqn in jaxpr.eqns:
                yield eqn.primitive.name
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    yield from names(sub)

        found = set(names(closed.jaxpr))
        assert "sort" in found and "scatter-add" not in found

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

    def test_the_interpreted_kernels_equal_the_dense_per_expert_reference(
            self):
        """The chip's path, interpreted: both grouped GEMMs and the combine
        (``pt_moe_combine``) as Pallas kernels."""
        x, gates, idx, w1, w2 = self._layer(2)
        got, _ = mg.moe_gated_grouped(x, gates, idx, w1, w2, 1, self.HELD,
                                      backend="interpret")
        want, _ = mg.moe_gated_grouped(x, gates, idx, w1, w2, 1, self.HELD,
                                       backend="xla")
        xs, g, ix = (np.asarray(a) for a in (x, gates, idx))
        dense = np.zeros((self.T, self.D), np.float32)
        for e in range(self.HELD[1]):
            t, slot = np.nonzero(ix == e)
            ab = xs[t] @ np.asarray(w1[1, e])
            a, b = ab[:, :self.F], ab[:, self.F:]
            np.add.at(dense, t, g[t, slot][:, None]
                      * (((a / (1 + np.exp(-a))) * b) @ np.asarray(w2[1, e])))
        np.testing.assert_allclose(got, dense, atol=2e-4)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def _sorted_picks(idx, gates, held):
    """What ``moe_gated_grouped`` hands its combine: the picks sorted by
    held expert (absent ones last), their gates, their tokens and the
    number of live rows, ``offsets[E]``."""
    first, count = held
    k = idx.shape[1]
    local = idx - first
    here = (local >= 0) & (local < count)
    key = jnp.where(here, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    g = jnp.where(here, gates, 0.0).reshape(-1)[order]
    live = jnp.sum(here, dtype=jnp.int32).reshape(1)
    return order, g, order // k, live


def _xla_combine(out, g, order, T, k):
    """Today's XLA combine, as ``backend="xla"`` keeps it."""
    y = jnp.zeros((T * k, out.shape[1]), jnp.float32).at[order].set(
        out * g[:, None])
    return jnp.sum(y.reshape(T, k, -1), axis=1)


class TestCombine:
    """``pt_moe_combine`` through the interpreter against today's XLA
    combine, at the three hybrid cells' prefill routing shapes (their T x
    k, top-k and share of held experts; d cut to 256)."""

    CELLS = {"mixed": (1024, 8, 256, (0, 16)),
             "longdoc": (1024, 4, 128, (0, 32)),
             "rag": (256, 10, 72, (0, 36))}
    D = 256

    def _picks(self, T, k, E, seed):
        rng = np.random.RandomState(seed)
        idx = jnp.asarray(np.argsort(rng.rand(T, E), axis=1)[:, :k],
                          jnp.int32)
        gates = jnp.asarray(rng.rand(T, k), jnp.float32)
        return idx, gates

    def _out(self, T, k, live, seed):
        """The second GEMM's rows: rows past ``live`` are exact zeros, as
        the grouped GEMM leaves them."""
        rng = np.random.RandomState(seed)
        out = rng.randn(T * k, self.D).astype(np.float32)
        out[live:] = 0.0
        return jnp.asarray(out)

    def _check(self, idx, gates, held, seed=0):
        T, k = idx.shape
        order, g, tok, live = _sorted_picks(idx, gates, held)
        out = self._out(T, k, int(live[0]), seed)
        got = mg.moe_combine(out, g, tok, live, T, interpret=True)
        want = _xla_combine(out, g, order, T, k)
        np.testing.assert_allclose(
            got, want, rtol=1e-6, atol=1e-6 * float(jnp.abs(out).max()))
        return got, int(live[0])

    @pytest.mark.parametrize("cell", sorted(CELLS))
    def test_equals_the_xla_combine_at_the_cells_shapes(self, cell):
        T, k, E, held = self.CELLS[cell]
        idx, gates = self._picks(T, k, E, seed=5)
        _, live = self._check(idx, gates, held)
        share = held[1] / E
        assert 0.7 * share < live / (T * k) < 1.3 * share

    def test_a_tail_chunk_whose_picks_do_not_fill_a_tile(self):
        """A 16-token tail (the engine's default bucket) at top-10: 160
        sorted rows, padded to two tiles of 128 for the walk."""
        idx, gates = self._picks(16, 10, 72, seed=9)
        self._check(idx, gates, (0, 36))

    def test_no_pick_held_reads_exact_zeros(self):
        idx, gates = self._picks(256, 10, 72, seed=1)
        got, live = self._check(idx + 72, gates, (0, 36))
        assert live == 0
        np.testing.assert_array_equal(got, np.zeros((256, self.D)))

    def test_every_pick_held(self):
        idx, gates = self._picks(256, 10, 72, seed=2)
        _, live = self._check(idx, gates, (0, 72))
        assert live == 256 * 10

    def test_one_expert_takes_every_live_pick(self):
        idx, gates = self._picks(1024, 8, 256, seed=3)
        # expert 5 in slot 0 of every token; no other held expert picked
        idx = jnp.where(idx < 16, idx + 16, idx).at[:, 0].set(5)
        _, live = self._check(idx, gates, (0, 16))
        assert live == 1024

    def test_live_rows_end_on_a_tile_boundary(self):
        idx, gates = self._picks(1024, 4, 128, seed=4)
        # exactly 256 picks held: slot 0 of tokens 0..255, none elsewhere
        idx = jnp.where(idx < 32, idx + 32, idx)
        idx = idx.at[:256, 0].set(jnp.arange(256, dtype=jnp.int32) % 32)
        _, live = self._check(idx, gates, (0, 32))
        assert live == 256 and live % 128 == 0

    @pytest.mark.parametrize("cell", ["mixed", "rag"])
    def test_rows_past_the_live_ones_are_never_read(self, cell):
        """``out``'s rows at and past ``offsets[E]``, and whole tiles past
        the last live one, hold NaN: the kernel's result is the clean
        run's, bit for bit. Today's combine multiplies them by a zero gate
        and returns NaN."""
        T, k, E, held = self.CELLS[cell]
        idx, gates = self._picks(T, k, E, seed=6)
        order, g, tok, live = _sorted_picks(idx, gates, held)
        clean = self._out(T, k, int(live[0]), seed=7)
        dirty = clean.at[int(live[0]):].set(jnp.nan)
        assert (T * k) // 128 > -(-int(live[0]) // 128)  # dead tiles exist
        np.testing.assert_array_equal(
            mg.moe_combine(dirty, g, tok, live, T, interpret=True),
            mg.moe_combine(clean, g, tok, live, T, interpret=True))
        assert bool(jnp.isnan(_xla_combine(dirty, g, order, T, k)).any())

    @pytest.mark.parametrize("cell", sorted(CELLS))
    def test_rows_accumulated_equal_the_picks_here_counter(self, cell):
        """With every row and gate 1.0 the result counts, row by row, the
        rows the kernel added: in all, exactly the picks held here that
        ``pick_counts`` reports (``serving.moe.picks_here``)."""
        T, k, E, held = self.CELLS[cell]
        idx, gates = self._picks(T, k, E, seed=8)
        _, _, tok, live = _sorted_picks(idx, gates, held)
        ones = jnp.ones((T * k, self.D), jnp.float32)
        got = mg.moe_combine(ones, jnp.ones((T * k,), jnp.float32), tok,
                             live, T, interpret=True)
        counts = mg.pick_counts(idx, jnp.ones((T,), bool), held)
        assert float(jnp.sum(got[:, 0])) == int(counts[1]) == int(live[0])
        np.testing.assert_array_equal(got, jnp.broadcast_to(
            got[:, :1], got.shape))

"""Ragged grouped-GEMM kernel (ISSUE 15 tentpole,
nn/functional/grouped_gemm.py).

Pinned here: the work-unit schedule's invariants, forward parity
against a dense per-row reference, BITWISE equality between the
interpreter-run Pallas kernel and the tiled XLA fallback (fwd and
grads — the off-TPU path must be the exact serving numerics), gradient
parity against jax autodiff of the dense reference, and the ragged
edge cases (empty experts, total skew, pad rows past offsets[E]), and
what a work unit that owns no row costs: no dot, in either walk.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.nn.functional.grouped_gemm import (
    DEFAULT_BLOCK_ROWS, grouped_gemm, grouped_gemm_banked,
    grouped_work_map, moe_route)


def _mk(T=200, K=256, N=384, E=4, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    x = jnp.asarray(rng.randn(T, K).astype(dtype))
    w = jnp.asarray((rng.randn(E, K, N) * 0.05).astype(dtype))
    b = jnp.asarray((rng.randn(E, N) * 0.1).astype(np.float32))
    eids = np.sort(rng.randint(0, E, T))
    offsets = jnp.asarray(
        np.concatenate([[0], np.cumsum(np.bincount(eids, minlength=E))])
        .astype(np.int32))
    return x, w, b, eids, offsets


def _dense_ref(x, w, b, eids, activation=None):
    rows = jnp.take(w, jnp.asarray(eids), axis=0)
    bb = jnp.take(b, jnp.asarray(eids), axis=0)
    y = jnp.einsum("tk,tkn->tn", x, rows) + bb
    if activation == "gelu":
        y = jax.nn.gelu(y)
    return y


class TestWorkMap:
    def test_invariants(self):
        """tids non-decreasing, units expert-sorted, every tile and
        every expert covered — the accumulation-correctness contract
        the kernel's zero-init logic rests on."""
        bm = 8
        offsets = jnp.asarray([0, 3, 3, 17, 20], jnp.int32)  # E=4, T=20
        t_pad = 24
        gids, tids, lo, hi = (np.asarray(a) for a in grouped_work_map(
            offsets, t_pad, bm))
        assert (np.diff(tids) >= 0).all()
        assert (np.diff(gids) >= 0).all()
        assert set(range(t_pad // bm)) <= set(tids.tolist())
        assert set(range(4)) <= set(gids.tolist())
        # masks partition [0, 20): each real row in exactly one unit
        covered = np.zeros(24, np.int32)
        for u in range(len(gids)):
            covered[lo[u]:hi[u]] += 1
        # a row straddling a tile boundary appears in the mask of each
        # of its units, but is in-range of exactly ONE tile per unit —
        # count (row in [lo,hi)) AND (row in unit's tile)
        covered[:] = 0
        for u in range(len(gids)):
            t0, t1 = tids[u] * bm, (tids[u] + 1) * bm
            a, z = max(int(lo[u]), t0), min(int(hi[u]), t1)
            if z > a:
                covered[a:z] += 1
        assert (covered[:20] == 1).all()
        assert (covered[20:] == 0).all()

    def test_static_shape(self):
        offsets = jnp.asarray([0, 5, 9], jnp.int32)
        gids, tids, lo, hi = grouped_work_map(offsets, 16, 8)
        nwu = 16 // 8 + 2 * 2 + 1
        assert gids.shape == tids.shape == lo.shape == hi.shape == (nwu,)


class TestGroupedGemm:
    def test_fwd_matches_dense_reference(self):
        x, w, b, eids, offsets = _mk()
        y = grouped_gemm(x, w, offsets, bias=b, activation="gelu",
                         backend="xla")
        ref = _dense_ref(x, w, b, eids, "gelu")
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=2e-6)

    def test_interpret_bitwise_equals_xla(self):
        """The off-TPU contract: the interpreter-run Pallas kernel and
        the tiled XLA walk produce IDENTICAL bits (same unit order,
        same fp32 accumulation from zero)."""
        x, w, b, eids, offsets = _mk()
        yx = grouped_gemm(x, w, offsets, bias=b, activation="gelu",
                          backend="xla")
        yi = grouped_gemm(x, w, offsets, bias=b, activation="gelu",
                          backend="interpret")
        assert np.array_equal(np.asarray(yx), np.asarray(yi))

    def test_grads_match_dense_autodiff(self):
        x, w, b, eids, offsets = _mk()

        def loss(x, w, b):
            y = grouped_gemm(x, w, offsets, bias=b, activation="gelu",
                             backend="xla")
            return jnp.sum(y ** 2)

        gx, gw, gb = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)

        def loss_ref(x, w, b):
            return jnp.sum(_dense_ref(x, w, b, eids, "gelu") ** 2)

        rx, rw, rb = jax.grad(loss_ref, argnums=(0, 1, 2))(x, w, b)
        np.testing.assert_allclose(np.asarray(gx), np.asarray(rx),
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(gw), np.asarray(rw),
                                   atol=2e-4)
        np.testing.assert_allclose(np.asarray(gb), np.asarray(rb),
                                   atol=2e-4)

    def test_grads_interpret_bitwise_equals_xla(self):
        x, w, b, eids, offsets = _mk()

        def mk_loss(backend):
            def loss(x, w):
                y = grouped_gemm(x, w, offsets, bias=b,
                                 activation="gelu", backend=backend)
                return jnp.sum(y ** 2)
            return loss

        gx, gw = jax.grad(mk_loss("xla"), argnums=(0, 1))(x, w)
        hx, hw = jax.grad(mk_loss("interpret"), argnums=(0, 1))(x, w)
        assert np.array_equal(np.asarray(gx), np.asarray(hx))
        assert np.array_equal(np.asarray(gw), np.asarray(hw))

    def test_total_skew_and_empty_experts(self):
        """Every token routed to ONE expert: the other experts are
        empty segments (forced min-1 units keep their dw blocks
        initialized) and the output is a plain dense GEMM."""
        x, w, b, _eids, _ = _mk()
        T, E = x.shape[0], w.shape[0]
        eids = np.full(T, 2)
        offsets = jnp.asarray(
            np.concatenate([[0],
                            np.cumsum(np.bincount(eids, minlength=E))])
            .astype(np.int32))
        y = grouped_gemm(x, w, offsets, bias=b, backend="xla")
        np.testing.assert_allclose(np.asarray(y),
                                   np.asarray(x @ w[2] + b[2]),
                                   atol=2e-6)
        gw = jax.grad(lambda w: jnp.sum(grouped_gemm(
            x, w, offsets, bias=b, backend="xla") ** 2))(w)
        # empty experts: exactly-zero weight grads (not garbage)
        for e in (0, 1, 3):
            assert float(jnp.abs(gw[e]).max()) == 0.0

    def test_rows_past_offsets_end_are_zero(self):
        """offsets[E] < T: trailing rows belong to no expert and must
        come out exactly zero (the phantom unit zero-fills pad tiles)."""
        x, w, b, eids, _ = _mk()
        T, E = x.shape[0], w.shape[0]
        live = T - 37
        eids = np.sort(np.random.RandomState(3).randint(0, E, live))
        offsets = jnp.asarray(
            np.concatenate([[0],
                            np.cumsum(np.bincount(eids, minlength=E))])
            .astype(np.int32))
        y = np.asarray(grouped_gemm(x, w, offsets, bias=b,
                                    backend="xla"))
        assert (y[live:] == 0).all()
        ref = _dense_ref(x[:live], w, b, eids)
        np.testing.assert_allclose(y[:live], np.asarray(ref), atol=2e-6)

    def test_no_bias_no_activation(self):
        x, w, _b, eids, offsets = _mk()
        y = grouped_gemm(x, w, offsets, backend="xla")
        zb = jnp.zeros((w.shape[0], w.shape[-1]), jnp.float32)
        ref = _dense_ref(x, w, zb, eids)
        np.testing.assert_allclose(np.asarray(y), np.asarray(ref),
                                   atol=2e-6)

    def test_offsets_shape_validated(self):
        x, w, b, _eids, _ = _mk()
        with pytest.raises(ValueError, match="E\\+1"):
            grouped_gemm(x, w, jnp.zeros((3,), jnp.int32))

    def test_tile_aligned_shapes_take_kernel_geometry(self):
        """128-aligned shapes run the kernel path (interpret off-TPU)
        and still match the fallback bitwise — the geometry the chip
        runs."""
        x, w, b, eids, offsets = _mk(T=DEFAULT_BLOCK_ROWS * 2, K=128,
                                     N=256, E=4, seed=5)
        yi = grouped_gemm(x, w, offsets, bias=b, backend="interpret")
        yx = grouped_gemm(x, w, offsets, bias=b, backend="xla")
        assert np.array_equal(np.asarray(yi), np.asarray(yx))


def _prefill_offsets(seed, T=256, k=10, experts=72, held=36):
    """Offsets of a prefill chunk at the rag cell's routing shape: T
    tokens pick k of ``experts`` at random, ``held`` are here, the
    absent picks sort behind ``offsets[held]``."""
    rng = np.random.RandomState(seed)
    idx = np.stack([rng.permutation(experts)[:k] for _ in range(T)])
    counts = np.bincount(idx[idx < held], minlength=held)
    return np.concatenate([[0], np.cumsum(counts)]).astype(np.int32)


def _recount_units(offsets, t_pad, bm):
    """(walked, live) by hand: the static schedule's length, and one
    unit for every row tile an expert's interval touches."""
    E = len(offsets) - 1
    live = sum(-(-int(b) // bm) - int(a) // bm
               for a, b in zip(offsets[:-1], offsets[1:]) if b > a)
    return t_pad // bm + 2 * E + 1, live


def _dots(jaxpr, guarded=False):
    """(equation, under a ``cond``?) of every dot_general in ``jaxpr``,
    kernel bodies and loops included."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            yield eqn, guarded
        inner = guarded or eqn.primitive.name == "cond"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _dots(sub, inner)


class TestDeadUnits:
    """The rag cell's prefill chunk, scaled down in K and N only: 2,560
    sorted rows, 36 held experts of 72, top-10. Half the static
    schedule owns no row; such a unit must cost no matmul and still
    leave its tile zero."""

    T_ROWS, E, K, N = 2560, 36, 128, 256

    def _operands(self, seed, groups=None):
        rng = np.random.RandomState(seed)
        G = groups or self.E
        x = rng.randn(self.T_ROWS, self.K).astype(np.float32)
        bank = (rng.randn(G, self.K, self.N) * 0.05).astype(np.float32)
        return x, bank, _prefill_offsets(seed)

    @pytest.mark.parametrize("seed", [0, 1, 2])
    def test_reported_units_equal_a_recount(self, seed):
        x, bank, offsets = self._operands(seed)
        _, units = grouped_gemm_banked(
            jnp.asarray(x), jnp.asarray(bank), jnp.asarray(offsets), 0,
            backend="xla")
        walked, live = _recount_units(offsets, self.T_ROWS,
                                      DEFAULT_BLOCK_ROWS)
        # un-tileable or not, N = 256 is one column block here
        assert [int(u) for u in units] == [walked, live]
        assert units.dtype == jnp.int32
        assert walked == 93 and live < 0.55 * walked

    @pytest.mark.parametrize("backend", ["xla", "interpret"])
    def test_a_dead_unit_does_no_matmul(self, backend):
        """Every dot of the walk sits under a ``cond`` (``pl.when`` in
        the kernel body) on the unit's ``hi > lo``."""
        x, bank, offsets = self._operands(0)

        def fn(x, bank, offsets):
            return grouped_gemm_banked(x, bank, offsets, 0,
                                       backend=backend)

        dots = list(_dots(jax.make_jaxpr(fn)(x, bank, offsets).jaxpr))
        assert dots, "no dot traced: the walk was not taken"
        assert all(guarded for _, guarded in dots)

    @pytest.mark.parametrize("backend", ["xla", "interpret"])
    def test_rows_past_the_live_picks_stay_exact_zeros(self, backend):
        """NaN where a dead unit would read: the absent picks' rows of
        x, and the weight block the phantom and trailing units alias
        (the last group's, here made empty). Masked today, skipped now:
        either way nothing of it may reach the output."""
        x, bank, offsets = self._operands(3)
        offsets[-1] = offsets[-2]               # the last expert: no row
        live = int(offsets[-1])
        x[live:] = np.nan
        bank[-1] = np.nan
        y, units = grouped_gemm_banked(
            jnp.asarray(x), jnp.asarray(bank), jnp.asarray(offsets), 0,
            backend=backend)
        y = np.asarray(y)
        assert (y[live:] == 0).all()
        eids = np.repeat(np.arange(self.E), np.diff(offsets))
        ref = np.einsum("tk,tkn->tn", x[:live], bank[eids])
        np.testing.assert_allclose(y[:live], ref, atol=2e-4)
        assert int(units[1]) == _recount_units(
            offsets, self.T_ROWS, DEFAULT_BLOCK_ROWS)[1]

    def test_banked_groups_are_the_banks_rows_in_place(self):
        """``first_group`` shifts the weight block index: layer 1's 36
        experts of a two-layer bank, bitwise the unshifted call on
        that layer's slice."""
        x, bank, offsets = self._operands(4, groups=2 * self.E)
        x, bank, offsets = map(jnp.asarray, (x, bank, offsets))
        y, _ = grouped_gemm_banked(x, bank, offsets, self.E, backend="xla")
        z, _ = grouped_gemm_banked(x, bank[self.E:], offsets, 0,
                                   backend="xla")
        assert np.array_equal(np.asarray(y), np.asarray(z))


class TestRouter:
    def test_fp32_routing_under_bf16_inputs(self):
        """The fp32-router satellite: logits whose top-2 margin is
        below bf16 resolution must still route by the TRUE ordering.
        A bf16 router collapses the pair into a tie (top_k then picks
        the lower index) — the exact instability the fp32 rule fixes."""
        # gate crafted so expert 1's logit exceeds expert 0's by 2^-10
        # (bf16 has 8 mantissa bits: both round to 1.0)
        d = 4
        x = jnp.ones((1, d), jnp.bfloat16)
        wg = np.zeros((d, 3), np.float32)
        wg[:, 0] = 1.0 / d
        wg[:, 1] = (1.0 + 2.0 ** -10) / d
        wg[:, 2] = -1.0
        wg = jnp.asarray(wg)

        _, _, idx = moe_route(x, wg, 1)
        assert int(idx[0, 0]) == 1  # true max, not the bf16 tie pick

        # the bf16 formulation demonstrably picks the WRONG expert
        bf_logits = (x @ wg.astype(jnp.bfloat16)).astype(jnp.bfloat16)
        _, bf_idx = jax.lax.top_k(jax.nn.softmax(bf_logits, -1), 1)
        assert int(bf_idx[0, 0]) == 0

    def test_bf16_and_fp32_inputs_route_identically(self):
        rng = np.random.RandomState(0)
        x32 = jnp.asarray(rng.randn(64, 16).astype(np.float32))
        xbf = x32.astype(jnp.bfloat16)
        wg = jnp.asarray(rng.randn(16, 8).astype(np.float32) * 0.3)
        _, _, i32 = moe_route(xbf.astype(jnp.float32), wg, 2)
        _, _, ibf = moe_route(xbf, wg, 2)
        # same VALUES in (the bf16 tensor) -> identical fp32 routing
        assert np.array_equal(np.asarray(i32), np.asarray(ibf))

"""Sequential masked LU oracle: correctness + properties."""

import numpy as np
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.lu.sequential import (
    lu_masked_sequential,
    masked_lup,
    permutation_sign,
    reconstruct,
    unpack_factors,
)
from repro.api import SolverConfig, factor
from repro.core.solve import lu_solve


RNG = np.random.default_rng(0)


def _rand(n):
    return RNG.standard_normal((n, n)).astype(np.float32)


class TestMaskedLU:
    @pytest.mark.parametrize("n,v", [(32, 8), (64, 16), (128, 32), (96, 12)])
    def test_reconstruction(self, n, v):
        A = _rand(n)
        F, rows = lu_masked_sequential(jnp.asarray(A), v=v)
        rec = np.asarray(reconstruct(F, rows))
        assert np.abs(rec - A).max() / np.abs(A).max() < 5e-5

    def test_pivot_order_is_permutation(self):
        A = _rand(64)
        _, rows = lu_masked_sequential(jnp.asarray(A), v=16)
        assert sorted(np.asarray(rows).tolist()) == list(range(64))

    def test_multipliers_bounded_like_partial_pivoting(self):
        A = _rand(64)
        F, rows = lu_masked_sequential(jnp.asarray(A), v=8)
        _, L, _ = unpack_factors(F, rows)
        assert np.abs(np.asarray(L)).max() <= 1.0 + 1e-6

    def test_rows_stay_in_place(self):
        """Row masking: the packed factor matrix keeps original row positions."""
        A = _rand(32)
        F, rows = lu_masked_sequential(jnp.asarray(A), v=8)
        # first pivot row holds U[0, :] = its original values in row `rows[0]`
        r0 = int(np.asarray(rows)[0])
        assert np.allclose(np.asarray(F)[r0], A[r0], atol=1e-6)

    def test_pallas_window_crosses_column_tiles(self):
        """The Pallas update visits only the trailing column window: at
        N=2048 its column tiles are narrower than the matrix, so the window
        start crosses a tile boundary mid-factorization.  The factors must
        rebuild A as closely as the ref backend's."""
        from repro.kernels import ops

        n, v = 2048, 128
        assert ops.schur_tiles(n, n, v, jnp.float32)[1] < n
        A = np.random.default_rng(1).uniform(-0.5, 0.5, (n, n)).astype(np.float32)
        err = {}
        for backend in ("ref", "pallas"):
            F, rows = lu_masked_sequential(jnp.asarray(A), v=v, backend=backend)
            err[backend] = np.abs(np.asarray(reconstruct(F, rows)) - A).max()
        assert err["pallas"] < 2e-4 and err["pallas"] < 4 * err["ref"], err

    @settings(max_examples=8, deadline=None)
    @given(st.integers(min_value=2, max_value=6), st.integers(min_value=0, max_value=2**31 - 1))
    def test_property_reconstruction_random(self, nv, seed):
        n = nv * 8
        A = np.random.default_rng(seed).standard_normal((n, n)).astype(np.float32)
        F, rows = lu_masked_sequential(jnp.asarray(A), v=8)
        rec = np.asarray(reconstruct(F, rows))
        assert np.abs(rec - A).max() / max(np.abs(A).max(), 1e-6) < 1e-4


class TestMaskedLUP:
    def test_inactive_rows_untouched(self):
        panel = _rand(16)[:, :4].copy()
        w = np.ones(16, np.float32)
        w[[3, 7]] = 0
        F, order, ok = masked_lup(jnp.asarray(panel), jnp.asarray(w), 4)
        assert np.allclose(np.asarray(F)[[3, 7]], panel[[3, 7]])
        assert bool(ok.all())
        assert 3 not in np.asarray(order) and 7 not in np.asarray(order)

    def test_exhausted_panel_reports_not_ok(self):
        panel = np.zeros((4, 4), np.float32)
        panel[0, 0] = 1.0
        w = np.zeros(4, np.float32)
        w[0] = 1.0
        _, _, ok = masked_lup(jnp.asarray(panel), jnp.asarray(w), 4)
        assert bool(np.asarray(ok)[0]) and not bool(np.asarray(ok)[1:].any())


class TestSolveAPI:
    def test_lu_solve(self):
        A, b = _rand(64), RNG.standard_normal(64).astype(np.float32)
        x = np.asarray(factor(A, SolverConfig(strategy="sequential")).solve(b))
        assert np.abs(A @ x - b).max() < 5e-4

    def test_lu_solve_matrix_rhs(self):
        A, B = _rand(32), RNG.standard_normal((32, 4)).astype(np.float32)
        F, rows = lu_masked_sequential(jnp.asarray(A), v=8)
        X = np.asarray(lu_solve(F, rows, jnp.asarray(B)))
        assert np.abs(A @ X - B).max() < 5e-4

    def test_slogdet_matches_numpy(self):
        A = _rand(48)
        s, ld = factor(A, SolverConfig(strategy="sequential")).slogdet()
        s_np, ld_np = np.linalg.slogdet(A.astype(np.float64))
        assert float(s) == pytest.approx(s_np)
        assert float(ld) == pytest.approx(ld_np, rel=1e-3)


class TestPermutationSign:
    def test_matches_cycle_decomposition(self):
        """Vectorized pointer-doubling sign == the O(N) cycle-loop oracle."""

        def slow_sign(rows):
            n = len(rows)
            seen = np.zeros(n, bool)
            sign = 1.0
            for i in range(n):
                if seen[i]:
                    continue
                j, clen = i, 0
                while not seen[j]:
                    seen[j] = True
                    j = int(rows[j])
                    clen += 1
                if clen % 2 == 0:
                    sign = -sign
            return sign

        rng = np.random.default_rng(5)
        for n in (1, 2, 3, 7, 64, 257, 1000):
            p = rng.permutation(n)
            assert permutation_sign(p) == slow_sign(p), n

    def test_sign_verified_against_numpy_slogdet(self):
        """Satellite acceptance: sign verified against numpy.linalg.slogdet
        of the permutation matrix itself."""
        rng = np.random.default_rng(6)
        for n in (2, 5, 16, 33):
            p = rng.permutation(n)
            s_np, _ = np.linalg.slogdet(np.eye(n)[p])
            assert permutation_sign(p) == s_np

    def test_identity_and_swap(self):
        assert permutation_sign(np.arange(10)) == 1.0
        assert permutation_sign(np.array([1, 0])) == -1.0
        assert permutation_sign(np.array([], dtype=int)) == 1.0

"""Windowed hot loop: parity vs the flat step body, fused kernel oracle.

The tentpole contract: the shrinking-window + fused-TRSM->Schur step bodies
of `conflux` and `cholesky25d` must produce *identical* pivot orders and
the same factor matrices as the historical flat full-block loop — the
windows only skip compute on retired rows/columns the masks already zeroed.
The two are different XLA programs, which XLA may fuse and order
differently, so the factors agree to a few ulps of the largest entry
rather than bit for bit (`assert_factors_match`).
Multi-device coverage (collectives inside the `lax.switch` bucket bodies)
lives in tests/multidev/run_backend_parity.py.
"""

import warnings

import numpy as np
import pytest

from repro.api import GridConfig, SolverConfig, plan
from repro.core.windows import window_bucket_index, window_buckets
from repro.kernels.backend import get_backend

RNG = np.random.default_rng(11)


def assert_factors_match(got, want, N):
    """Same factors up to rounding: N ulps of the largest entry."""
    want = np.asarray(want)
    tol = N * np.finfo(want.dtype).eps * np.abs(want).max()
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=tol)


def _inputs(N, dtype):
    A = RNG.standard_normal((N, N)).astype(dtype)
    G = RNG.standard_normal((N, N)).astype(dtype)
    A_spd = G @ G.T / N + np.eye(N, dtype=dtype)
    return A, A_spd


class TestWindowBuckets:
    def test_buckets_cover_every_step(self):
        for nb in (1, 2, 3, 4, 7, 8, 16, 33):
            caps = window_buckets(nb)
            assert caps[-1] >= nb
            for t in range(nb):
                idx = int(window_bucket_index(t, nb))
                assert 0 <= idx < len(caps)
                assert caps[idx] >= nb - t, (nb, t)  # bucket covers the window
                if idx:  # and is the *smallest* covering bucket
                    assert caps[idx - 1] < nb - t

    def test_bucket_count_is_logarithmic(self):
        assert len(window_buckets(1)) == 1
        assert len(window_buckets(16)) == 5
        assert len(window_buckets(1024)) == 11


class TestWindowedFlatParity:
    """Acceptance: windowed+fused hot loop == flat loop: same pivots, and
    the same factors up to rounding."""

    @pytest.mark.parametrize("strategy", ["conflux", "cholesky25d"])
    @pytest.mark.parametrize("dtype", ["float32", "float64"])
    @pytest.mark.parametrize("v", [8, 32])
    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_identical_pivots_and_factors(self, strategy, dtype, v, backend):
        N = 64
        A, A_spd = _inputs(N, dtype)
        Ain = A_spd if strategy == "cholesky25d" else A
        pivot = "none" if strategy == "cholesky25d" else "tournament"
        grid = GridConfig(Px=1, Py=1, c=1, v=v, N=N)
        facts = {}
        with warnings.catch_warnings():
            # float64 x pallas auto-falls back to ref (covered elsewhere);
            # here only the windowed-vs-flat contract is under test.
            warnings.simplefilter("ignore", UserWarning)
            for hl in ("windowed", "flat"):
                cfg = SolverConfig(strategy=strategy, pivot=pivot, grid=grid,
                                   dtype=dtype, backend=backend, hotloop=hl)
                facts[hl] = plan(N, cfg).execute(Ain)
        w, f = facts["windowed"], facts["flat"]
        np.testing.assert_array_equal(w.rows, f.rows)
        assert_factors_match(w.F, f.F, N)
        # and the result is a valid factorization, not merely self-consistent
        err = np.abs(np.asarray(w.reconstruct()) - Ain).max()
        assert err < 1e-4

    @pytest.mark.parametrize("pivot", ["tournament", "partial"])
    def test_both_pivot_schemes(self, pivot):
        N, v = 96, 8  # non-power-of-two tile count: 12 tiles, 5 buckets
        A, _ = _inputs(N, "float32")
        grid = GridConfig(Px=1, Py=1, c=1, v=v, N=N)
        facts = {
            hl: plan(N, SolverConfig(strategy="conflux", pivot=pivot, grid=grid,
                                     hotloop=hl)).execute(A)
            for hl in ("windowed", "flat")
        }
        np.testing.assert_array_equal(facts["windowed"].rows, facts["flat"].rows)
        assert_factors_match(facts["windowed"].F, facts["flat"].F, N)

    def test_hotloop_lands_in_cache_key(self):
        N = 32
        cfgs = [SolverConfig(strategy="sequential", v=8, hotloop=hl)
                for hl in ("windowed", "flat")]
        assert plan(N, cfgs[0]) is not plan(N, cfgs[1])

    def test_unknown_hotloop_rejected(self):
        with pytest.raises(ValueError, match="hotloop"):
            SolverConfig(hotloop="spiral")


class TestFusedTrsmSchur:
    """fused_trsm_schur == trsm_left_lower -> schur_update, both backends."""

    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    @pytest.mark.parametrize("unit", [True, False])
    @pytest.mark.parametrize("shape", [(64, 96, 16), (32, 32, 8), (128, 256, 32)])
    def test_matches_unfused_composition(self, backend, unit, shape):
        import jax.numpy as jnp

        M, C, v = shape
        bk = get_backend(backend)
        A = jnp.asarray(RNG.standard_normal((M, C)).astype(np.float32))
        L00 = jnp.tril(
            jnp.asarray(RNG.standard_normal((v, v)).astype(np.float32)), -1
        ) + (1.0 if unit else 2.0) * jnp.eye(v, dtype=jnp.float32)
        R01 = jnp.asarray(RNG.standard_normal((v, C)).astype(np.float32))
        L10 = jnp.asarray(RNG.standard_normal((M, v)).astype(np.float32))
        A2, U01 = bk.fused_trsm_schur(A, L00, R01, L10, unit=unit)
        U_ref = bk.trsm_left_lower(L00, R01, unit=unit)
        A_ref = bk.schur_update(A, L10, U_ref)
        np.testing.assert_array_equal(np.asarray(U01), np.asarray(U_ref))
        np.testing.assert_array_equal(np.asarray(A2), np.asarray(A_ref))

    def test_pallas_matches_ref_backend(self):
        import jax.numpy as jnp

        M, C, v = 64, 64, 16
        A = jnp.asarray(RNG.standard_normal((M, C)).astype(np.float32))
        L00 = jnp.tril(
            jnp.asarray(RNG.standard_normal((v, v)).astype(np.float32)), -1
        ) + jnp.eye(v, dtype=jnp.float32)
        R01 = jnp.asarray(RNG.standard_normal((v, C)).astype(np.float32))
        L10 = jnp.asarray(RNG.standard_normal((M, v)).astype(np.float32))
        outs = {
            name: get_backend(name).fused_trsm_schur(A, L00, R01, L10)
            for name in ("ref", "pallas")
        }
        np.testing.assert_allclose(np.asarray(outs["ref"][0]),
                                   np.asarray(outs["pallas"][0]),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(outs["ref"][1]),
                                   np.asarray(outs["pallas"][1]),
                                   rtol=1e-4, atol=1e-4)

    def test_masked_columns_stay_clean(self):
        """Pre-masking R01 columns zeroes the corresponding U01 columns and
        leaves those columns of A untouched — the property the windowed loop
        relies on for the (at most one) retired tile inside the bucket."""
        import jax.numpy as jnp

        M, C, v = 32, 64, 8
        bk = get_backend("ref")
        A = jnp.asarray(RNG.standard_normal((M, C)).astype(np.float32))
        L00 = jnp.eye(v, dtype=jnp.float32)
        R01 = jnp.asarray(RNG.standard_normal((v, C)).astype(np.float32))
        L10 = jnp.asarray(RNG.standard_normal((M, v)).astype(np.float32))
        mask = (jnp.arange(C) >= v).astype(jnp.float32)
        A2, U01 = bk.fused_trsm_schur(A, L00, R01 * mask[None, :], L10)
        np.testing.assert_array_equal(np.asarray(U01[:, :v]), 0.0)
        np.testing.assert_array_equal(np.asarray(A2[:, :v]), np.asarray(A[:, :v]))


class TestOpsAutoClamp:
    """Direct ops.* calls on matrices smaller than (or not multiples of)
    the 128/256 default tiles must auto-fit instead of erroring."""

    def test_schur_update_small(self):
        import jax.numpy as jnp
        from repro.kernels import ops, ref

        A = jnp.asarray(RNG.standard_normal((48, 48)).astype(np.float32))
        L = jnp.asarray(RNG.standard_normal((48, 8)).astype(np.float32))
        U = jnp.asarray(RNG.standard_normal((8, 48)).astype(np.float32))
        np.testing.assert_allclose(np.asarray(ops.schur_update(A, L, U)),
                                   np.asarray(ref.schur_update(A, L, U)),
                                   rtol=2e-4, atol=2e-4)

    def test_schur_update_non_multiple_of_default(self):
        import jax.numpy as jnp
        from repro.kernels import ops, ref

        # 192 % 128 != 0: the old min()-clamp would trip the exact-cover
        # assertion; the divisor fit drops to 96.
        A = jnp.asarray(RNG.standard_normal((192, 192)).astype(np.float32))
        L = jnp.asarray(RNG.standard_normal((192, 24)).astype(np.float32))
        U = jnp.asarray(RNG.standard_normal((24, 192)).astype(np.float32))
        np.testing.assert_allclose(np.asarray(ops.schur_update(A, L, U)),
                                   np.asarray(ref.schur_update(A, L, U)),
                                   rtol=2e-3, atol=2e-3)

    def test_trsm_small_and_odd(self):
        import jax.numpy as jnp
        from repro.kernels import ops, ref

        U = jnp.triu(jnp.asarray(RNG.standard_normal((8, 8)).astype(np.float32))) \
            + 3.0 * jnp.eye(8, dtype=jnp.float32)
        B = jnp.asarray(RNG.standard_normal((40, 8)).astype(np.float32))  # 40 < 256
        np.testing.assert_allclose(np.asarray(ops.trsm_right_upper(B, U)),
                                   np.asarray(ref.trsm_right_upper(B, U)),
                                   rtol=2e-4, atol=2e-4)
        L = jnp.tril(jnp.asarray(RNG.standard_normal((8, 8)).astype(np.float32)), -1) \
            + jnp.eye(8, dtype=jnp.float32)
        C = jnp.asarray(RNG.standard_normal((8, 72)).astype(np.float32))  # 72 < 256
        np.testing.assert_allclose(np.asarray(ops.trsm_left_lower(L, C)),
                                   np.asarray(ref.trsm_left_lower(L, C)),
                                   rtol=2e-4, atol=2e-4)

    def test_fused_small(self):
        import jax.numpy as jnp
        from repro.kernels import ops

        A = jnp.asarray(RNG.standard_normal((24, 40)).astype(np.float32))
        L00 = jnp.eye(8, dtype=jnp.float32)
        R01 = jnp.asarray(RNG.standard_normal((8, 40)).astype(np.float32))
        L10 = jnp.asarray(RNG.standard_normal((24, 8)).astype(np.float32))
        A2, U01 = ops.fused_trsm_schur(A, L00, R01, L10)
        np.testing.assert_allclose(np.asarray(A2), np.asarray(A - L10 @ R01),
                                   rtol=2e-4, atol=2e-4)
        np.testing.assert_array_equal(np.asarray(U01), np.asarray(R01))


class TestHotloopProfile:
    def test_profile_populates_plan_and_result(self):
        N = 64
        grid = GridConfig(Px=1, Py=1, c=1, v=16, N=N)
        p = plan(N, SolverConfig(strategy="conflux", grid=grid))
        prof = p.profile_hotloop(repeats=1)
        for key in ("panel_us", "trsm_us", "schur_us", "gather_us",
                    "gather_dense_us", "fused_us"):
            assert key in prof and prof[key] > 0.0, key
        A, _ = _inputs(N, "float32")
        fact = p.execute(A)
        assert fact.hotloop == prof
        assert "hot-loop primitives" in fact.comm_report()

    def test_sequential_plan_profiles_too(self):
        p = plan(64, SolverConfig(strategy="sequential", v=16))
        prof = p.profile_hotloop(repeats=1)
        assert prof["shapes"]["R"] == 64

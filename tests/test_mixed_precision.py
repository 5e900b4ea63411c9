"""Mixed-precision factorization + iterative refinement.

Covers the SolverConfig(compute_dtype=...) contract end to end: config
validation and cache-key isolation, the pallas backend staying engaged for
low-precision plans (and the actionable fallback hint when it can't), the
conditioning envelope of f32/bf16 refinement (SVD-shaped spectra, iteration
counts monotone in cond(A)), clean non-convergence on numerically broken
factorizations, bit-exactness of the default-dtype paths, the batched and
serving refinement plumbing, and the byte-accurate comm report.
"""

import warnings

import jax
import numpy as np
import pytest

from repro.api import (
    GridConfig,
    SolverConfig,
    clear_plan_cache,
    plan,
)
from repro.api.config import resolve_dtype
from repro.api.result import RefinedSolve
from repro.serving import AsyncSolveEngine
from repro.serving.solve_engine import SolveEngine

RNG = np.random.default_rng(0)


def _conditioned(n: int, cond: float, rng=None) -> np.ndarray:
    """A dense f64 matrix with the exact spectrum logspace(1 .. 1/cond),
    rotated by random orthogonal factors (SVD construction, so cond(A) is
    `cond` by design rather than by luck)."""
    rng = rng or np.random.default_rng(int(cond) % 2**31)
    u, _ = np.linalg.qr(rng.standard_normal((n, n)))
    v, _ = np.linalg.qr(rng.standard_normal((n, n)))
    s = np.logspace(0.0, -np.log10(cond), n)
    return (u * s) @ v.T


def _relres(A, x, b) -> float:
    x = np.asarray(x, np.float64)
    return float(np.abs(A @ x - np.asarray(b, np.float64)).max()
                 / max(np.abs(b).max(), 1e-300))


class TestConfigValidation:
    def test_unknown_compute_dtype_rejected(self):
        with pytest.raises(ValueError, match="compute_dtype"):
            SolverConfig(compute_dtype="float8")

    def test_wider_compute_than_working_rejected(self):
        with pytest.raises(ValueError, match="compute_dtype"):
            SolverConfig(dtype="float32", compute_dtype="float64")

    def test_equal_compute_dtype_normalizes_to_none(self):
        cfg = SolverConfig(dtype="float32", compute_dtype="float32")
        assert cfg.compute_dtype is None
        assert cfg.effective_compute_dtype == "float32"

    def test_effective_compute_dtype(self):
        cfg = SolverConfig(dtype="float64", compute_dtype="bfloat16")
        assert cfg.effective_compute_dtype == "bfloat16"
        assert SolverConfig(dtype="float64").effective_compute_dtype == "float64"

    @pytest.mark.parametrize("compute, ok", [(None, False), ("float32", True)])
    def test_tpu_rejects_float64_factorization(self, monkeypatch, compute, ok):
        """On a TPU an f64 factorization fails loudly; f32 factors of an f64
        working matrix (the refinement route) still resolve."""
        from repro.api import resolve

        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        cfg = SolverConfig(strategy="sequential", dtype="float64",
                           compute_dtype=compute)
        if ok:
            assert resolve(64, cfg).effective_compute_dtype == "float32"
        else:
            with pytest.raises(ValueError, match="no float64"):
                resolve(64, cfg)

    def test_resolve_dtype_knows_bfloat16(self):
        dt = resolve_dtype("bfloat16")
        assert dt.itemsize == 2


class TestPlanCacheKeys:
    def test_mixed_plan_does_not_collide_with_plain(self):
        clear_plan_cache()
        p_plain = plan(16, SolverConfig(strategy="sequential", dtype="float64",
                                        backend="ref", v=8))
        p_mixed = plan(16, SolverConfig(strategy="sequential", dtype="float64",
                                        backend="ref", compute_dtype="float32",
                                        v=8))
        assert p_plain is not p_mixed
        assert p_plain.config.cache_key != p_mixed.config.cache_key

    def test_normalized_compute_dtype_shares_plan(self):
        clear_plan_cache()
        p1 = plan(16, SolverConfig(strategy="sequential", dtype="float32", v=8))
        p2 = plan(16, SolverConfig(strategy="sequential", dtype="float32",
                                   compute_dtype="float32", v=8))
        assert p1 is p2


class TestPallasBackendRetention:
    def test_f64_working_with_f32_compute_keeps_pallas(self):
        """The tentpole claim: a float64 *working* dtype no longer forces the
        ref fallback when the compute dtype is MXU-native."""
        clear_plan_cache()
        with warnings.catch_warnings():
            warnings.simplefilter("error")  # any fallback warning -> failure
            p = plan(32, SolverConfig(strategy="sequential", backend="pallas",
                                      dtype="float64", compute_dtype="float32",
                                      v=8))
            A = RNG.standard_normal((32, 32))
            fact = p.execute(A)
        assert fact.F.dtype == np.float32
        assert np.asarray(fact.A_ref).dtype == np.float64

    def test_f64_fallback_warning_names_compute_dtype_fix(self):
        clear_plan_cache()
        with pytest.warns(UserWarning, match="compute_dtype") as rec:
            plan(32, SolverConfig(strategy="sequential", backend="pallas",
                                  dtype="float64", v=8))
        assert any("falling back to 'ref'" in str(w.message) for w in rec)
        assert any("refine_tol" in str(w.message) for w in rec)

    def test_fallback_warning_deduplicated(self):
        clear_plan_cache()
        cfg = SolverConfig(strategy="sequential", backend="pallas",
                           dtype="float64", v=8)
        with pytest.warns(UserWarning):
            plan(32, cfg)
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            plan(32, cfg)  # cached plan + deduped warning: silent


class TestRefinementConditioning:
    def test_f32_compute_iters_monotone_in_cond(self):
        """Refinement works across an SVD-shaped conditioning sweep and the
        iteration count grows (weakly) with cond(A) — the contraction factor
        per iteration is ~ cond(A) * u_f32."""
        n, tol = 96, 1e-12
        b = np.random.default_rng(3).standard_normal((n,))
        cfg = SolverConfig(strategy="sequential", dtype="float64",
                           compute_dtype="float32", backend="ref", v=8)
        iters = []
        for cond in (1e1, 1e3, 1e5):
            A = _conditioned(n, cond)
            rs = plan(n, cfg).execute(A).solve(b, refine_tol=tol,
                                               max_refine_iters=25)
            assert bool(rs.converged), f"cond={cond:g} did not converge"
            assert float(rs.final_residual) <= tol
            assert _relres(A, rs, b) <= 10 * tol
            iters.append(int(rs.refinement_iters))
        assert iters == sorted(iters), f"iters not monotone in cond: {iters}"
        assert iters[-1] > iters[0], f"cond sweep should cost extra iters: {iters}"

    def test_bf16_compute_converges_for_modest_cond(self):
        n, tol = 64, 1e-11
        b = np.random.default_rng(4).standard_normal((n,))
        cfg = SolverConfig(strategy="sequential", dtype="float64",
                           compute_dtype="bfloat16", backend="ref", v=8)
        A = _conditioned(n, 30.0)
        rs = plan(n, cfg).execute(A).solve(b, refine_tol=tol,
                                           max_refine_iters=40)
        assert bool(rs.converged)
        assert _relres(A, rs, b) <= 10 * tol
        # bf16's ~8 mantissa bits need visibly more iterations than f32 did
        assert int(rs.refinement_iters) >= 2

    def test_same_dtype_refinement_works(self):
        """refine_tol is honored even without a lower compute dtype: residuals
        are still recomputed in the working dtype against A_ref."""
        n = 48
        A = _conditioned(n, 10.0).astype(np.float32)
        b = np.random.default_rng(5).standard_normal((n,)).astype(np.float32)
        cfg = SolverConfig(strategy="sequential", dtype="float32", v=8)
        rs = plan(n, cfg).execute(A).solve(b, refine_tol=1e-5,
                                           max_refine_iters=10)
        assert bool(rs.converged)
        assert np.asarray(rs).dtype == np.float32

    def test_refined_x_comes_back_in_working_dtype(self):
        n = 32
        A = _conditioned(n, 10.0)
        b = np.random.default_rng(6).standard_normal((n,))
        cfg = SolverConfig(strategy="sequential", dtype="float64",
                           compute_dtype="float32", v=8)
        rs = plan(n, cfg).execute(A).solve(b, refine_tol=1e-12)
        assert isinstance(rs, RefinedSolve)
        x = np.asarray(rs)
        assert x.dtype == np.float64
        assert x.shape == (n,)
        assert np.isfinite(x).all()


class TestCleanNonConvergence:
    def test_hopeless_cond_reports_unconverged_without_nans(self):
        """cond(A) beyond the compute dtype's reach: the refine loop must hit
        its cap with finite state, never NaN/Inf or a silent 'converged'."""
        n, cap = 64, 5
        A = _conditioned(n, 1e14)  # far past f32's 1/u ~ 1.7e7
        b = np.random.default_rng(7).standard_normal((n,))
        cfg = SolverConfig(strategy="sequential", dtype="float64",
                           compute_dtype="float32", backend="ref", v=8)
        rs = plan(n, cfg).execute(A).solve(b, refine_tol=1e-14,
                                           max_refine_iters=cap)
        assert not bool(rs.converged)
        assert int(rs.refinement_iters) == cap
        assert np.isfinite(float(rs.final_residual))
        assert np.isfinite(np.asarray(rs)).all()

    def test_zero_iteration_cap_returns_initial_solve(self):
        n = 32
        A = _conditioned(n, 10.0)
        b = np.random.default_rng(8).standard_normal((n,))
        cfg = SolverConfig(strategy="sequential", dtype="float64",
                           compute_dtype="float32", v=8)
        rs = plan(n, cfg).execute(A).solve(b, refine_tol=1e-30,
                                           max_refine_iters=0)
        assert int(rs.refinement_iters) == 0
        assert not bool(rs.converged)
        assert np.isfinite(np.asarray(rs)).all()

    def test_refinement_requires_retained_matrix(self):
        n = 16
        cfg = SolverConfig(strategy="sequential", dtype="float32", v=8)
        fact = plan(n, cfg).execute(
            RNG.standard_normal((n, n)).astype(np.float32))
        fact = type(fact)(**{**fact.__dict__, "A_ref": None})
        with pytest.raises(ValueError, match="A_ref"):
            fact.solve(np.zeros(n), refine_tol=1e-6)


class TestDefaultPathBitExactness:
    """The regression oracle: dtype == compute_dtype paths must be untouched
    by the mixed-precision plumbing."""

    @pytest.mark.parametrize("backend", ["ref", "pallas"])
    def test_factors_identical_with_explicit_equal_compute(self, backend):
        clear_plan_cache()
        n = 32
        A = RNG.standard_normal((n, n)).astype(np.float32)
        f1 = plan(n, SolverConfig(strategy="sequential", backend=backend,
                                  v=8)).execute(A)
        clear_plan_cache()
        f2 = plan(n, SolverConfig(strategy="sequential", backend=backend,
                                  compute_dtype="float32", v=8)).execute(A)
        assert np.array_equal(np.asarray(f1.F), np.asarray(f2.F))
        assert np.array_equal(np.asarray(f1.rows), np.asarray(f2.rows))

    def test_plain_solve_unchanged_by_mixed_machinery(self):
        n = 32
        A = RNG.standard_normal((n, n)).astype(np.float32)
        b = RNG.standard_normal((n,)).astype(np.float32)
        fact = plan(n, SolverConfig(strategy="sequential", v=8)).execute(A)
        x_plain = np.asarray(fact.solve(b))
        x_again = np.asarray(fact.solve(b))
        assert np.array_equal(x_plain, x_again)
        assert x_plain.dtype == np.float32


class TestBatchedRefinement:
    def test_per_lane_iters_and_residuals(self):
        B, n = 3, 32
        rng = np.random.default_rng(9)
        A = np.stack([_conditioned(n, c, rng) for c in (1e1, 1e3, 1e5)])
        b = rng.standard_normal((B, n))
        cfg = SolverConfig(strategy="sequential", dtype="float64",
                           compute_dtype="float32", backend="ref", v=8)
        rs = plan((B, n), cfg).execute(A).solve(b, refine_tol=1e-12,
                                                max_refine_iters=25)
        assert np.asarray(rs).shape == (B, n)
        assert np.asarray(rs.refinement_iters).shape == (B,)
        assert np.asarray(rs.converged).all()
        for i in range(B):
            assert _relres(A[i], np.asarray(rs)[i], b[i]) <= 1e-11

    def test_per_lane_tolerances(self):
        B, n = 2, 32
        rng = np.random.default_rng(10)
        A = np.stack([_conditioned(n, 1e3, rng) for _ in range(B)])
        b = rng.standard_normal((B, n))
        cfg = SolverConfig(strategy="sequential", dtype="float64",
                           compute_dtype="float32", backend="ref", v=8)
        tols = np.array([1e-4, 1e-12])
        rs = plan((B, n), cfg).execute(A).solve(b, refine_tol=tols,
                                                max_refine_iters=25)
        iters = np.asarray(rs.refinement_iters)
        assert np.asarray(rs.converged).all()
        assert iters[0] <= iters[1]  # the loose lane must stop no later


class TestServingRefinement:
    def test_engine_refines_requesting_lanes_only(self):
        n = 32
        rng = np.random.default_rng(11)
        cfg = SolverConfig(strategy="sequential", dtype="float64",
                           compute_dtype="float32", backend="ref", v=8)
        eng = SolveEngine(n, cfg)
        systems = []
        for _ in range(3):
            A = _conditioned(n, 1e3, rng)
            b = rng.standard_normal(n)
            systems.append((A, b))
        eng.submit_system(*systems[0], refine_tol=1e-12)
        eng.submit_system(*systems[1])  # plain lane: factor-precision only
        eng.submit_system(*systems[2], refine_tol=1e-12)
        xs = eng.flush_systems()
        assert _relres(*systems[0][:1], xs[0], systems[0][1]) <= 1e-11
        assert _relres(*systems[2][:1], xs[2], systems[2][1]) <= 1e-11
        # the plain lane got the f32-factor solve: orders of magnitude looser
        assert _relres(*systems[1][:1], xs[1], systems[1][1]) > 1e-9
        st = eng.stats()
        assert st["refined_systems"] == 2
        assert st["refine_nonconverged"] == 0
        assert st["refine_iters_total"] >= 2

    def test_async_submit_passes_refine_tol(self):
        n = 32
        rng = np.random.default_rng(12)
        A = _conditioned(n, 1e3, rng)
        b = rng.standard_normal(n)
        cfg = SolverConfig(strategy="sequential", dtype="float64",
                           compute_dtype="float32", backend="ref", v=8)
        with AsyncSolveEngine(n, cfg, max_batch=4, max_delay_ms=1.0) as eng:
            x = eng.submit(A, b, refine_tol=1e-12).result(timeout=120)
        assert _relres(A, x, b) <= 1e-11

    def test_warm_slots_pretraces_partial_batches(self):
        cfg = SolverConfig(strategy="sequential", v=8)
        eng = SolveEngine(32, cfg)
        # sizes 20 and 32 share the N=32 slot; batch slots {1, 2, 4}
        assert eng.warm_slots(sizes=(20, 32), max_batch=4) == 3
        st = eng.stats()
        assert st["batched_factorizations"] == 0  # warming is not traffic
        A = RNG.standard_normal((32, 32)).astype(np.float32)
        A += 32 * np.eye(32, dtype=np.float32)
        b = RNG.standard_normal(32).astype(np.float32)
        eng.submit_system(A, b)
        (x,) = eng.flush_systems()
        assert float(np.abs(A @ x - b).max()) < 5e-2

    def test_async_warm_slots_delegates(self):
        cfg = SolverConfig(strategy="sequential", v=8)
        with AsyncSolveEngine(32, cfg, max_batch=2, max_delay_ms=1.0) as eng:
            assert eng.warm_slots(sizes=(32,)) == 2  # slots {1, 2}


class TestCommReportBytes:
    @staticmethod
    def _total_row(report: str) -> tuple[float, float]:
        for ln in report.splitlines():
            if ln.strip().startswith("total"):
                parts = [p.replace(",", "") for p in ln.split()]
                return float(parts[-2]), float(parts[-1])
        raise AssertionError("no total row in comm_report")

    def test_bytes_column_scales_with_compute_dtype(self):
        n = 32
        grid = GridConfig(Px=1, Py=1, c=1, v=8, N=n)
        A = RNG.standard_normal((n, n)).astype(np.float64)
        rep32 = plan(n, SolverConfig(strategy="conflux", grid=grid,
                                     dtype="float64", compute_dtype="float32",
                                     backend="ref")).execute(A).comm_report()
        assert "bytes" in rep32
        assert "working float64" in rep32
        elems, nbytes = self._total_row(rep32)
        assert nbytes == pytest.approx(4 * elems)  # f32 over the wire

        with jax.enable_x64(True):  # a genuine f64 plan (demoted to f32 otherwise)
            rep64 = plan(n, SolverConfig(strategy="conflux", grid=grid,
                                         dtype="float64",
                                         backend="ref")).execute(A).comm_report()
        assert "working" not in rep64  # no mixed-precision annotation
        elems64, nbytes64 = self._total_row(rep64)
        assert elems64 == pytest.approx(elems)  # same schedule, same elements
        assert nbytes64 == pytest.approx(8 * elems64)

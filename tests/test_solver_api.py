"""Unified plan/execute solver API: registry, plan cache, Factorization."""

import jax
import numpy as np
import pytest

from repro.api import (
    Factorization,
    GridConfig,
    SolverConfig,
    available_strategies,
    clear_plan_cache,
    factor,
    plan,
    plan_cache_stats,
    register_strategy,
    resolve,
    set_plan_cache_capacity,
)
from repro.serving.solve_engine import SolveEngine

RNG = np.random.default_rng(0)


def _rand(n, k=None):
    shape = (n, n) if k is None else (n, k)
    return RNG.standard_normal(shape).astype(np.float32)


# Single-device configs exercising every registered strategy, including the
# shard_map path (1x1x1 grid collapses the collectives to self-reductions).
def _all_strategy_configs(N):
    return [
        SolverConfig(strategy="sequential"),
        SolverConfig(strategy="conflux", grid=GridConfig(Px=1, Py=1, c=1, v=8, N=N)),
        SolverConfig(strategy="baseline2d", P_target=1, v=8),
        SolverConfig(strategy="auto"),
    ]


class TestPlanCache:
    def test_same_key_traces_exactly_once(self):
        """Acceptance: same (N, dtype, strategy, pivot, grid) twice =>
        one trace/compile, the second plan() is a cache hit."""
        clear_plan_cache()
        N = 32
        cfg = SolverConfig(strategy="sequential")
        p1 = plan(N, cfg)
        p1.execute(_rand(N))
        p2 = plan(N, cfg)
        p2.execute(_rand(N))
        assert p1 is p2
        assert p1.trace_count == 1
        assert p1.execute_count == 2
        stats = plan_cache_stats()
        assert stats["hits"] == 1 and stats["misses"] == 1

    def test_shardmap_plan_traces_exactly_once(self):
        clear_plan_cache()
        N = 32
        cfg = SolverConfig(strategy="conflux", grid=GridConfig(Px=1, Py=1, c=1, v=8, N=N))
        plan(N, cfg).execute(_rand(N))
        p = plan(N, cfg)
        p.execute(_rand(N))
        assert p.trace_count == 1 and p.execute_count == 2
        assert plan_cache_stats()["hits"] == 1

    def test_different_keys_get_different_plans(self):
        clear_plan_cache()
        N = 32
        p8 = plan(N, SolverConfig(strategy="sequential", v=8))
        p16 = plan(N, SolverConfig(strategy="sequential", v=16))
        p64 = plan(64, SolverConfig(strategy="sequential", v=8))
        assert p8 is not p16 and p8 is not p64
        assert plan_cache_stats()["misses"] == 3

    def test_auto_resolves_to_cached_concrete_plan(self):
        clear_plan_cache()
        N = 32
        pa = plan(N, SolverConfig(strategy="auto"))
        assert pa.config.strategy in ("sequential", "conflux")
        assert plan(N, SolverConfig(strategy="auto")) is pa
        assert plan_cache_stats()["hits"] == 1

    def test_plan_kwarg_overrides(self):
        p = plan(32, strategy="sequential", v=16)
        assert p.config.v == 16


class TestPlanCacheLRU:
    """Bounded plan cache: LRU eviction + counters (multi-tenant serving)."""

    @pytest.fixture(autouse=True)
    def _restore_capacity(self):
        prev = plan_cache_stats()["capacity"]
        yield
        set_plan_cache_capacity(prev)

    def test_eviction_at_capacity(self):
        clear_plan_cache()
        set_plan_cache_capacity(2)
        for v in (4, 8, 16):  # third insert evicts the LRU entry (v=4)
            plan(32, SolverConfig(strategy="sequential", v=v))
        stats = plan_cache_stats()
        assert stats["size"] == 2 and stats["evictions"] == 1 and stats["capacity"] == 2
        plan(32, SolverConfig(strategy="sequential", v=4))  # must rebuild
        assert plan_cache_stats()["misses"] == 4

    def test_hit_refreshes_recency(self):
        clear_plan_cache()
        set_plan_cache_capacity(2)
        p4 = plan(32, SolverConfig(strategy="sequential", v=4))
        plan(32, SolverConfig(strategy="sequential", v=8))
        assert plan(32, SolverConfig(strategy="sequential", v=4)) is p4  # touch v=4
        plan(32, SolverConfig(strategy="sequential", v=16))  # evicts v=8, not v=4
        assert plan(32, SolverConfig(strategy="sequential", v=4)) is p4
        assert plan_cache_stats()["evictions"] == 1

    def test_shrinking_capacity_evicts_immediately(self):
        clear_plan_cache()
        set_plan_cache_capacity(8)
        for v in (4, 8, 16):
            plan(32, SolverConfig(strategy="sequential", v=v))
        set_plan_cache_capacity(1)
        stats = plan_cache_stats()
        assert stats["size"] == 1 and stats["evictions"] == 2

    def test_evicted_plan_keeps_working(self):
        clear_plan_cache()
        set_plan_cache_capacity(1)
        held = plan(32, SolverConfig(strategy="sequential", v=8))
        plan(32, SolverConfig(strategy="sequential", v=16))  # evicts `held`
        A = _rand(32)
        fact = held.execute(A)  # outstanding reference still executes
        assert np.abs(np.asarray(fact.reconstruct()) - A).max() < 5e-5

    def test_capacity_must_be_nonnegative(self):
        with pytest.raises(ValueError, match="capacity"):
            set_plan_cache_capacity(-1)

    def test_engine_stats_surface_evictions(self):
        from repro.serving.solve_engine import SolveEngine

        clear_plan_cache()
        set_plan_cache_capacity(1)
        eng = SolveEngine(32, SolverConfig(strategy="sequential", v=8))
        plan(32, SolverConfig(strategy="sequential", v=16))  # evict engine's key
        st = eng.stats()
        assert st["plan_cache"]["evictions"] == 1
        assert st["plan_cache"]["capacity"] == 1
        assert st["backend"] == "ref"


class TestFactorizationCorrectness:
    @pytest.mark.parametrize("idx", range(4))
    def test_multirhs_solve_matches_numpy(self, idx):
        """Acceptance: Factorization.solve on a multi-RHS batch matches
        numpy.linalg.solve to fp32 tolerance for all registered strategies."""
        N, k = 64, 7
        cfg = _all_strategy_configs(N)[idx]
        A, B = _rand(N), _rand(N, k)
        fact = factor(A, cfg)
        X = np.asarray(fact.solve(B))
        X_np = np.linalg.solve(A.astype(np.float64), B.astype(np.float64))
        assert np.abs(X - X_np).max() < 5e-3
        assert np.abs(A @ X - B).max() < 5e-4

    def test_all_builtin_strategies_registered(self):
        assert {"auto", "conflux", "baseline2d", "sequential"} <= set(available_strategies())

    def test_single_rhs_and_det(self):
        N = 48
        A, b = _rand(N), RNG.standard_normal(N).astype(np.float32)
        fact = factor(A, SolverConfig(strategy="sequential"))
        x = np.asarray(fact.solve(b))
        assert np.abs(A @ x - b).max() < 5e-4
        assert fact.devices == (str(jax.devices()[0]),)
        s, ld = fact.slogdet()
        s_np, ld_np = np.linalg.slogdet(A.astype(np.float64))
        assert float(s) == pytest.approx(s_np)
        assert float(ld) == pytest.approx(ld_np, rel=1e-3)
        assert float(fact.det()) == pytest.approx(s_np * np.exp(ld_np), rel=1e-2)

    def test_reconstruct_and_comm_report(self):
        N = 32
        A = _rand(N)
        fact = factor(A, SolverConfig(strategy="conflux",
                                      grid=GridConfig(Px=1, Py=1, c=1, v=8, N=N)))
        assert isinstance(fact, Factorization)
        assert np.abs(np.asarray(fact.reconstruct()) - A).max() < 5e-5
        report = fact.comm_report()
        assert "conflux" in report and "total" in report

    def test_solve_rejects_bad_rhs_shape(self):
        fact = factor(_rand(32), SolverConfig(strategy="sequential"))
        with pytest.raises(ValueError, match="N=32"):
            fact.solve(np.zeros(16, np.float32))


class TestValidation:
    def test_layout_violation_rejected_at_plan_time(self):
        N = 64
        with pytest.raises(ValueError, match=r"divisible by v\*Px"):
            plan(N, SolverConfig(strategy="conflux",
                                 grid=GridConfig(Px=2, Py=1, c=1, v=24, N=N)))

    def test_nonpow2_px_rejected_for_tournament(self):
        N = 96
        with pytest.raises(ValueError, match="power of two"):
            plan(N, SolverConfig(strategy="conflux",
                                 grid=GridConfig(Px=3, Py=1, c=1, v=8, N=N)))

    def test_grid_built_for_other_N_rejected(self):
        with pytest.raises(ValueError, match="N=64"):
            plan(64, SolverConfig(strategy="conflux",
                                  grid=GridConfig(Px=1, Py=1, c=1, v=8, N=32)))

    def test_no_feasible_grid_names_the_way_out(self):
        # the local share of N=512 on 4 ranks outgrows the default M=2**14
        with pytest.raises(ValueError, match=r"raise M or P\); or pin the grid yourself: "
                                             r"SolverConfig\(grid=GridConfig\("):
            resolve(512, SolverConfig(strategy="conflux", P_target=4))

    def test_grid_given_as_a_mapping(self):
        fields = {"Px": 2, "Py": 2, "c": 1, "v": 32, "N": 256}
        cfg = SolverConfig(strategy="conflux", grid=fields)
        assert cfg == SolverConfig(strategy="conflux", grid=GridConfig(**fields))
        assert cfg.cache_key(256) == cfg.with_(v=None).cache_key(256)
        with pytest.raises(TypeError):
            SolverConfig(grid={"Px": 2, "Py": 2})

    def test_unknown_strategy_lists_available(self):
        with pytest.raises(KeyError, match="conflux"):
            plan(32, SolverConfig(strategy="does-not-exist"))

    def test_unknown_pivot_rejected(self):
        with pytest.raises(ValueError, match="pivot"):
            SolverConfig(pivot="rook")

    def test_wrong_matrix_shape_rejected(self):
        p = plan(32, SolverConfig(strategy="sequential"))
        with pytest.raises(ValueError, match="N=32"):
            p.execute(_rand(16))

    def test_sequential_v_must_divide(self):
        with pytest.raises(ValueError, match="panel width"):
            resolve(64, SolverConfig(strategy="sequential", v=24))


class TestResolutionGuards:
    def test_auto_with_oversized_grid_raises(self):
        import jax

        n_dev = len(jax.devices())
        big = GridConfig(Px=8, Py=8, c=4, v=8, N=2048)
        if n_dev >= big.P_used:
            pytest.skip("host has enough devices")
        with pytest.raises(ValueError, match="devices"):
            plan(2048, SolverConfig(strategy="auto", grid=big))


class TestDtypeHandling:
    """Regressions for the dtype-handling bugs: silent RHS downcasts,
    integer dtypes crashing deep in tracing, and complex input."""

    def test_solverconfig_rejects_noninexact_dtype(self):
        """Before: SolverConfig('int64') passed validation and the plan died
        inside fori_loop with 'carry input and carry output must have equal
        types'.  Now: a clear ValueError at config construction."""
        for bad in ("int64", "int32", "bool"):
            with pytest.raises(ValueError, match="inexact"):
                SolverConfig(dtype=bad)

    def test_solverconfig_rejects_complex_dtype(self):
        with pytest.raises(ValueError, match="complex"):
            SolverConfig(dtype="complex64")

    def test_factor_normalizes_integer_matrix(self):
        """An int matrix computes in the default float dtype — an integer
        dtype would otherwise crash deep in tracing with a carry-type
        TypeError (factor() only forwards *float* input dtypes)."""
        A = RNG.integers(-4, 5, (32, 32))
        fact = factor(
            A, SolverConfig(strategy="conflux",
                            grid=GridConfig(Px=1, Py=1, c=1, v=8, N=32))
        )
        assert fact.dtype == np.float32
        assert np.abs(np.asarray(fact.reconstruct()) - A).max() < 1e-4

    def test_factor_normalizes_bool_matrix(self):
        A = np.eye(32, dtype=bool)
        fact = factor(A, SolverConfig(strategy="baseline2d", P_target=1, v=8))
        assert fact.dtype == np.float32

    def test_solve_warns_on_rhs_downcast(self):
        """Before: Factorization.solve silently demoted a float64 RHS to the
        factor dtype (jnp.asarray eats the precision without jax x64)."""
        fact = factor(_rand(32), SolverConfig(strategy="sequential"))
        with pytest.warns(UserWarning, match="downcast"):
            fact.solve(np.zeros(32, np.float64))

    def test_solve_same_dtype_is_silent(self):
        import warnings

        fact = factor(_rand(32), SolverConfig(strategy="sequential"))
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            fact.solve(np.zeros(32, np.float32))

    def test_solve_rejects_complex_rhs(self):
        fact = factor(_rand(32), SolverConfig(strategy="sequential"))
        with pytest.raises(ValueError, match="complex"):
            fact.solve(np.zeros(32, np.complex64))

    def test_solve_accepts_python_list_rhs_silently(self):
        """Plain sequences carry no dtype intent: no crash (np.result_type
        would choke on a list) and no spurious float64-downcast warning."""
        import warnings

        fact = factor(_rand(32), SolverConfig(strategy="sequential"))
        with warnings.catch_warnings():
            warnings.simplefilter("error")
            x = fact.solve([0.0] * 32)
        assert np.asarray(x).shape == (32,)

    def test_execute_rejects_complex_matrix(self):
        """plan.execute only warned for wide floats; complex fell through to
        an astype that silently discarded the imaginary parts."""
        p = plan(32, SolverConfig(strategy="sequential", v=8))
        with pytest.raises(ValueError, match="complex"):
            p.execute(np.zeros((32, 32), np.complex64))

    def test_execute_still_warns_on_matrix_downcast(self):
        p = plan(32, SolverConfig(strategy="sequential", v=8))
        with pytest.warns(UserWarning, match="downcast"):
            p.execute(np.zeros((32, 32), np.float64))


class TestRegistry:
    def test_register_and_duplicate_rejected(self):
        calls = []

        @register_strategy("_test_strategy")
        def build(N, config, mesh=None):
            calls.append(N)
            return None

        assert "_test_strategy" in available_strategies()
        with pytest.raises(ValueError, match="already registered"):
            register_strategy("_test_strategy")(lambda N, c, mesh=None: None)
        register_strategy("_test_strategy", overwrite=True)(build)  # explicit ok


class TestSolveEngine:
    def test_engine_reuses_one_plan(self):
        clear_plan_cache()
        N = 32
        eng = SolveEngine(N, SolverConfig(strategy="sequential"))
        eng2 = SolveEngine(N, SolverConfig(strategy="sequential"))
        assert eng.plan is eng2.plan  # same cached plan across engines
        A, b = _rand(N), RNG.standard_normal(N).astype(np.float32)
        x = np.asarray(eng.solve(A, b))
        assert np.abs(A @ x - b).max() < 5e-4
        x2 = np.asarray(eng.resolve(b * 2))
        assert np.abs(A @ x2 - 2 * b).max() < 1e-3
        st = eng.stats()
        assert st["factorizations"] == 1 and st["solves"] == 2
        assert st["trace_count"] == 1

    def test_engine_solve_many(self):
        N = 16
        eng = SolveEngine(N, strategy="sequential")
        systems = [(_rand(N), RNG.standard_normal(N).astype(np.float32)) for _ in range(3)]
        xs = eng.solve_many(systems)
        for (A, b), x in zip(systems, xs):
            assert np.abs(A @ x - b).max() < 5e-4
        assert eng.plan.trace_count == 1  # one compile for the whole batch

    def test_engine_resolve_requires_factor(self):
        eng = SolveEngine(16, strategy="sequential")
        with pytest.raises(RuntimeError, match="no factorization"):
            eng.resolve(np.zeros(16, np.float32))

    def test_batched_multi_rhs_flush(self):
        """submit() queues RHS, flush() solves them as ONE [N, k] dispatch;
        results match the per-request solve path and the stats counters
        record the batching win."""
        N, k = 32, 5
        eng = SolveEngine(N, strategy="sequential")
        A = _rand(N)
        eng.factor(A)
        bs = [RNG.standard_normal(N).astype(np.float32) for _ in range(k)]
        tickets = [eng.submit(b) for b in bs]
        assert tickets == list(range(k))
        assert eng.stats()["pending"] == k
        xs = eng.flush()
        assert len(xs) == k and eng.stats()["pending"] == 0
        for b, x in zip(bs, xs):
            assert np.abs(A @ x - b).max() < 5e-4
            np.testing.assert_allclose(x, np.asarray(eng.resolve(b)),
                                       rtol=1e-6, atol=1e-6)
        st = eng.stats()
        assert st["batched_solves"] == 1  # one dispatch for the whole batch
        assert st["batched_rhs"] == k
        assert st["solves"] == 2 * k  # k batched + k resolve checks above

    def test_batched_flush_empty_and_validation(self):
        eng = SolveEngine(16, strategy="sequential")
        with pytest.raises(RuntimeError, match="no factorization"):
            eng.flush()
        eng.factor(_rand(16))
        assert eng.flush() == []  # nothing pending: no dispatch, no error
        assert eng.stats()["batched_solves"] == 0
        with pytest.raises(ValueError, match="single \\[N\\] RHS"):
            eng.submit(np.zeros((16, 2), np.float32))
        with pytest.raises(ValueError, match="single \\[N\\] RHS"):
            eng.submit(np.zeros(8, np.float32))
        # malformed dtypes fail at submit time, never inside a batch that
        # holds other requests hostage
        with pytest.raises(ValueError, match="real RHS"):
            eng.submit(np.zeros(16, np.complex64))
        assert eng.stats()["pending"] == 0

    def test_flush_failure_keeps_queue(self):
        """A failing batched solve must leave the queue intact for retry,
        not silently drop every pending request."""
        eng = SolveEngine(16, strategy="sequential")
        eng.factor(_rand(16))
        eng.submit(RNG.standard_normal(16).astype(np.float32))
        eng._last = None  # simulate the dispatch failing mid-flush
        with pytest.raises(RuntimeError):
            eng.flush()
        assert eng.stats()["pending"] == 1  # request survived
        eng.factor(_rand(16))
        assert len(eng.flush()) == 1

    def test_solve_timings_measure_blocked_compute(self):
        """Regression: the timed regions in solve()/resolve() used to stop
        the clock on an unblocked jax array — `solve_s_total` reported async
        dispatch latency (~constant in N) instead of compute.  With
        block_until_ready the counter must (a) cover the externally measured
        blocked wall time and (b) grow with N."""
        import time

        import jax

        reps, k = 3, 32
        deltas = {}
        for N in (64, 1024):
            eng = SolveEngine(N, strategy="sequential")
            A = _rand(N)
            b = RNG.standard_normal((N, k)).astype(np.float32)
            fact = eng.factor(A)
            eng.resolve(b)  # warm: compile the solve for this RHS shape
            t0 = time.perf_counter()
            for _ in range(reps):
                jax.block_until_ready(fact.solve(b))
            wall = time.perf_counter() - t0
            s0 = eng.stats()["solve_s_total"]
            for _ in range(reps):
                eng.resolve(b)
            deltas[N] = eng.stats()["solve_s_total"] - s0
            # engine-attributed time covers the real blocked compute (the
            # unblocked version reports a small constant fraction of it)
            assert deltas[N] > 0.3 * wall, (N, deltas[N], wall)
        assert deltas[1024] > deltas[64], deltas

"""plan/execute core: compiled FactorizationPlans and their registry cache.

`plan(N, config)` resolves a `SolverConfig` to a concrete strategy + grid +
kernel backend, then returns the cached `FactorizationPlan` for that key —
building (and therefore tracing/jitting) one only on a cache miss.  The plan
owns the mesh, the block-cyclic layout, and the jitted shard_map executable;
`plan.execute(A)` runs without re-tracing.  Executing the same
(N, dtype, compute_dtype, strategy, pivot, grid, v, backend) twice compiles
exactly once — assert it with `plan.trace_count` or `plan_cache_stats()`.

The cache is LRU-bounded (`set_plan_cache_capacity`, default
REPRO_PLAN_CACHE_CAPACITY or 64): multi-tenant serving traffic with many
shapes evicts the least-recently-planned executable instead of holding every
compiled program forever.  Evictions only drop the cache's reference —
plans already held (e.g. by a `SolveEngine`) keep working.
"""

from __future__ import annotations

import os
import threading
import time
import warnings
from collections import OrderedDict

import numpy as np

from repro import obs
from repro.api.config import SolverConfig, resolve_dtype
from repro.api.registry import get_strategy
from repro.api.result import Factorization
from repro.core.lu.grid import GridConfig


class FactorizationPlan:
    """A compiled, reusable factorization program for one (N, config).

    Attributes:
        N, config:     the resolved problem/strategy this plan was built for.
        grid, mesh:    processor grid + jax Mesh (None on single device).
        comm:          instrumented per-processor schedule volume (elements).
        trace_count:   times the underlying program was traced/compiled.
        execute_count: times `execute` ran (re-trace win = execute_count -
                       trace_count extra runs at zero compile cost).
    """

    def __init__(self, N: int, config: SolverConfig, *, grid: GridConfig | None = None,
                 mesh=None, comm: dict | None = None, run=None, kind: str = "lu"):
        self.N = N
        self.config = config
        self.B = config.B  # batch size, or None for a single-system plan
        self.grid = grid
        self.mesh = mesh
        self.comm = dict(comm or {})
        self.kind = kind  # "lu" or "cholesky" — flows into the Factorization
        self.hotloop: dict = {}  # per-primitive timings; see profile_hotloop
        # Trace-calibrated auto decision that produced this plan (tuple,
        # predicted wall, calibration version) — None for explicit configs.
        self.autotune: dict | None = None
        self.trace_count = 0
        self.execute_count = 0
        # Cached plans are shared across threads (SolveEngine callers, the
        # async tier's executor), so the counter bumps are locked: a bare
        # `+= 1` is a read-modify-write that can drop increments under
        # concurrent executes and skew the re-trace accounting.
        self._count_lock = threading.Lock()
        # (A: np.ndarray [N, N]) -> (F, rows, devices); set by build_*
        self._run = run
        # Set by the builders for static analysis (repro.analysis.audit):
        # the jitted callable and the abstract input it is traced with.
        self._fn = None
        self._in_avals: tuple | None = None
        self._lowered_obj = None
        self._lowered_cache: dict[str, str] = {}

    def lowered_text(self, stage: str = "stablehlo") -> str:
        """The plan's program text, without executing it.

        stage="stablehlo": the pre-optimization StableHLO module (cheap — one
        trace, no XLA compile).  stage="hlo": the optimized *per-device* HLO
        after SPMD partitioning (compiles the program; still never runs it) —
        the input `repro.analysis.hlo.analyze_hlo` expects.

        Lowering traces the program (bumping `trace_count` once); the trace
        is shared with the execute path's jit cache, so auditing a plan never
        adds a second trace.  Results are cached per stage on the plan.
        """
        if stage not in ("stablehlo", "hlo"):
            raise ValueError(f"stage must be 'stablehlo' or 'hlo', got {stage!r}")
        if self._fn is None or self._in_avals is None:
            raise RuntimeError(
                f"plan for strategy {self.config.strategy!r} does not expose its "
                f"traced program (builder did not set _fn/_in_avals)"
            )
        cached = self._lowered_cache.get(stage)
        if cached is None:
            lowered = self._lowered_obj
            if lowered is None:
                lowered = self._fn.lower(*self._in_avals)
                self._lowered_obj = lowered  # one trace serves both stages
            cached = (
                lowered.as_text() if stage == "stablehlo"
                else lowered.compile().as_text()
            )
            self._lowered_cache[stage] = cached
        return cached

    def _note_trace(self):
        """Called from inside the traced program: fires once per compile."""
        with self._count_lock:
            self.trace_count += 1

    def profile_hotloop(self, repeats: int = 3) -> dict:
        """Measure per-primitive hot-loop wall times on this plan's shapes.

        Times the backend's panel / TRSM / Schur / gather / fused primitives
        standalone (see `repro.api.hotloop`) and caches the result on the
        plan; every later `execute` carries it into
        `Factorization.hotloop` / `comm_report()`.
        """
        from repro.api.hotloop import profile_primitives

        self.hotloop = profile_primitives(
            self.N, self.config, grid=self.grid, repeats=repeats
        )
        return self.hotloop

    def execute(self, A) -> Factorization:
        """Factorize A with the compiled program (no re-trace).

        A is [N, N], or [B, N, N] for a batched plan (`plan((B, N))`)."""
        with obs.span(obs.EXECUTE):
            return self._execute(A)

    def _execute(self, A) -> Factorization:
        A = obs.to_host(A)
        if A.dtype.kind == "c":
            raise ValueError(
                f"complex matrices are not supported (plan computes in "
                f"{self.config.dtype}); factorize the real and imaginary parts "
                f"separately or use a real 2N x 2N embedding"
            )
        if A.dtype.kind == "f" and A.dtype.itemsize > np.dtype(self.config.dtype).itemsize:
            warnings.warn(
                f"plan computes in {self.config.dtype}; input {A.dtype} will be "
                f"downcast (set SolverConfig.dtype to keep precision)",
                stacklevel=3,
            )
        A = A.astype(self.config.dtype, copy=False)
        want = (self.N, self.N) if self.B is None else (self.B, self.N, self.N)
        if A.shape != want:
            what = f"N={self.N}" if self.B is None else f"B={self.B}, N={self.N}"
            raise ValueError(
                f"plan was built for {what} (expects shape {want}), "
                f"got A of shape {A.shape}"
            )
        # Mixed precision: the kernels run in the (lower) compute dtype while
        # A_ref keeps the working-precision matrix for refinement residuals.
        compute = self.config.compute_dtype
        A_lo = A if compute is None else A.astype(resolve_dtype(compute))
        t0 = time.perf_counter()
        F, rows, devices = self._run(A_lo)
        wall_us = (time.perf_counter() - t0) * 1e6
        with self._count_lock:
            self.execute_count += 1
        # Close the autotuner's feedback loop: stamp the measured wall next
        # to the cost model's prediction so comm_report() shows the residual.
        autotune = None
        if self.autotune is not None:
            autotune = {k: v for k, v in self.autotune.items() if k != "grid"}
            autotune["grid"] = str(self.autotune.get("grid"))
            autotune["measured_wall_us"] = wall_us
            pred = self.autotune.get("predicted_wall_us")
            if pred:
                autotune["wall_residual"] = (wall_us - pred) / pred
        return Factorization(
            F=F, rows=rows, grid=self.grid, comm=dict(self.comm),
            strategy=self.config.strategy, backend=self.config.backend,
            kind=self.kind, hotloop=dict(self.hotloop),
            A_ref=A, work_dtype=np.dtype(self.config.dtype),
            autotune=autotune, devices=devices,
        )

    def __repr__(self):
        return (f"FactorizationPlan(N={self.N}, strategy={self.config.strategy!r}, "
                f"pivot={self.config.pivot!r}, backend={self.config.backend!r}, "
                f"grid={self.grid}, "
                f"traces={self.trace_count}, executes={self.execute_count})")


def _capacity_from_env(default: int = 64) -> int:
    """Parse REPRO_PLAN_CACHE_CAPACITY without letting a bad value break
    `import repro.api`: non-integer or negative falls back to the default
    with a warning (0 = unbounded, matching set_plan_cache_capacity)."""
    raw = os.environ.get("REPRO_PLAN_CACHE_CAPACITY")
    if raw is None:
        return default
    try:
        cap = int(raw)
        if cap < 0:
            raise ValueError
        return cap
    except ValueError:
        warnings.warn(
            f"ignoring REPRO_PLAN_CACHE_CAPACITY={raw!r} (want an integer >= 0, "
            f"0 = unbounded); using {default}",
            stacklevel=2,
        )
        return default


_PLAN_CACHE: OrderedDict[tuple, FactorizationPlan] = OrderedDict()
_BUILDING: dict[tuple, threading.Event] = {}
_STATS = {"hits": 0, "misses": 0, "evictions": 0}
_CAPACITY = _capacity_from_env()
_LOCK = threading.Lock()
# Pallas->ref fallbacks already warned about, keyed per resolved plan shape:
# re-resolving the same config (every serving request hits resolve) must not
# re-emit the same warning.  Cleared with the plan cache.
_FALLBACK_WARNED: set[tuple] = set()


def _resolve_backend(N: int, config: SolverConfig) -> SolverConfig:
    """Validate the kernel backend and apply the pallas -> ref auto-fallback.

    Runs after strategy resolution, so the panel width is concrete (config.v
    or grid.v) and the fallback decision lands in the cache key — a config
    that *requested* pallas but cannot run it resolves to (and shares) the
    ref plan.  The constraint check runs on the *effective compute dtype*:
    `dtype='float64'` with `compute_dtype='float32'` keeps the pallas
    kernels (factor low, refine back up) instead of falling back.
    """
    from repro.kernels.backend import available_backends, pallas_constraint_violation

    if config.backend not in available_backends():
        raise ValueError(
            f"unknown kernel backend {config.backend!r}; "
            f"available: {available_backends()}"
        )
    if config.backend == "pallas":
        v = config.grid.v if config.grid is not None else config.v
        reason = pallas_constraint_violation(config.effective_compute_dtype, v)
        if reason:
            if reason.startswith("dtype"):
                fix = (
                    "set SolverConfig(compute_dtype='float32') (or 'bfloat16') "
                    "to factor in an MXU-native dtype and recover working "
                    "precision with solve(refine_tol=...)"
                )
            else:
                fix = "choose a panel width v that is a multiple of the tile"
            key = (N, config.dtype, config.compute_dtype, v,
                   config.strategy, config.B)
            with _LOCK:
                seen = key in _FALLBACK_WARNED
                _FALLBACK_WARNED.add(key)
            if not seen:
                warnings.warn(
                    f"backend 'pallas' cannot run this plan (N={N}: {reason}); "
                    f"falling back to 'ref' — {fix}",
                    stacklevel=4,
                )
            return config.with_(backend="ref")
    return config


def _reject_tpu_float64(config: SolverConfig) -> None:
    """A float64 factorization cannot run as such on a TPU: without jax x64
    it would silently run in float32, and the chip has no float64 units.
    Fail loudly instead, naming the mixed-precision route."""
    import jax

    if (resolve_dtype(config.effective_compute_dtype).itemsize > 4
            and jax.default_backend() == "tpu"):
        raise ValueError(
            "the TPU has no float64 arithmetic, so a float64 factorization "
            "would run demoted; factor in float32 and refine instead: "
            "SolverConfig(dtype='float64', compute_dtype='float32') and "
            "Factorization.solve(b, refine_tol=...)"
        )


def resolve(N: int, config: SolverConfig) -> SolverConfig:
    """Resolve "auto"/missing-grid/backend configs to concrete choices."""
    _reject_tpu_float64(config)
    for _ in range(3):
        builder = get_strategy(config.strategy)
        resolver = getattr(builder, "resolve", None)
        resolved = resolver(N, config) if resolver else config
        if resolved.strategy == config.strategy:
            return _resolve_backend(N, resolved)
        config = resolved
    raise RuntimeError(f"strategy resolution did not converge for {config}")


def plan(N: int | tuple[int, int], config: SolverConfig | None = None, *,
         mesh=None, **overrides) -> FactorizationPlan:
    """Get (or build) the compiled plan for factorizing N x N matrices.

    `N` may be a `(B, N)` tuple, which builds a *batched* plan: one traced
    program factorizing a [B, N, N] stack of independent systems (the
    many-small-systems path; equivalent to `plan(N, B=B)`).  `overrides` are
    SolverConfig fields, so `plan(256, strategy="conflux")` works without
    constructing a config.  Passing an explicit `mesh` bypasses the cache
    (meshes are caller-owned and unhashable).
    """
    config = config or SolverConfig()
    if overrides:
        config = config.with_(**overrides)
    if isinstance(N, tuple):
        if len(N) != 2:
            raise ValueError(
                f"plan() shape must be N or (B, N), got tuple of length {len(N)}"
            )
        B, N = N
        if config.B is not None and config.B != B:
            raise ValueError(
                f"plan((B={B}, N)) conflicts with SolverConfig.B={config.B}"
            )
        config = config.with_(B=int(B))
    resolved = resolve(N, config)
    builder = get_strategy(resolved.strategy)
    if mesh is not None:
        return _attach_autotune(builder(N, resolved, mesh=mesh),
                                resolved.cache_key(N))
    key = resolved.cache_key(N)
    while True:
        with _LOCK:
            cached = _PLAN_CACHE.get(key)
            if cached is not None:
                _STATS["hits"] += 1
                _PLAN_CACHE.move_to_end(key)  # LRU touch
                return _attach_autotune(cached, key)
            pending = _BUILDING.get(key)
            if pending is None:
                # We own the build: others with the same key wait instead of
                # paying a duplicate trace+compile.
                _BUILDING[key] = pending = threading.Event()
                _STATS["misses"] += 1
                break
        pending.wait()  # owner finished (or failed) — re-check the cache
    try:
        built = builder(N, resolved)
        with _LOCK:
            _PLAN_CACHE[key] = built
            _evict_lru_locked()
        return _attach_autotune(built, key)
    finally:
        with _LOCK:
            _BUILDING.pop(key, None)
        pending.set()


def _attach_autotune(p: FactorizationPlan, key: tuple) -> FactorizationPlan:
    """Copy the calibrated-auto decision (tuple + predicted wall) onto the
    plan so execute() can report the measured-vs-predicted residual.  Plans
    from explicit configs (calibration is None) never carry one."""
    if p.autotune is None and p.config.calibration is not None:
        from repro.analysis import costmodel

        p.autotune = costmodel.get_decision(key)
    return p


def factor(A, config: SolverConfig | None = None, **overrides) -> Factorization:
    """One-shot convenience: plan (cached) + execute.

    A 2-D A factorizes one system; a 3-D [B, N, N] stack gets a batched
    plan (`plan((B, N))`) factorizing all B systems in one program.  With
    no explicit config/dtype, the computation dtype follows A (an explicit
    SolverConfig states the contract and wins).
    """
    A = np.asarray(A)
    if config is None and "dtype" not in overrides and A.dtype.kind == "f":
        overrides["dtype"] = A.dtype.name
    if A.ndim == 3:
        return plan((A.shape[0], A.shape[1]), config, **overrides).execute(A)
    return plan(A.shape[0], config, **overrides).execute(A)


def _evict_lru_locked() -> None:
    """Drop least-recently-used plans until within capacity (lock held)."""
    if _CAPACITY <= 0:  # 0 = unbounded
        return
    while len(_PLAN_CACHE) > _CAPACITY:
        _PLAN_CACHE.popitem(last=False)
        _STATS["evictions"] += 1


def set_plan_cache_capacity(capacity: int) -> int:
    """Set the LRU bound (number of cached plans; 0 = unbounded).

    Shrinks the cache immediately if it already exceeds the new bound.
    Returns the previous capacity so callers can restore it.
    """
    global _CAPACITY
    if capacity < 0:
        raise ValueError(f"capacity must be >= 0 (0 = unbounded), got {capacity}")
    with _LOCK:
        prev, _CAPACITY = _CAPACITY, capacity
        _evict_lru_locked()
    return prev


def plan_cache_stats() -> dict:
    with _LOCK:
        return {**_STATS, "size": len(_PLAN_CACHE), "capacity": _CAPACITY}


def clear_plan_cache() -> None:
    with _LOCK:
        _PLAN_CACHE.clear()
        _STATS.update(hits=0, misses=0, evictions=0)
        _FALLBACK_WARNED.clear()

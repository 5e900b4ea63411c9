"""SolveEngine — serving-scale repeated dense solves on one cached plan.

The serving story for linear algebra mirrors the LM engine next door:
traffic is many requests of the *same shape* (covariance solves, KKT
systems, Gaussian-process updates ...), so the expensive parts — grid
optimization, mesh construction, shard_map tracing, XLA compilation — are
paid once at engine construction and every request runs the compiled plan.

    eng = SolveEngine(N=4096, config=SolverConfig(strategy="auto"))
    x = eng.solve(A, b)            # factorize + solve
    x2 = eng.resolve(b2)           # new RHS, reuse the last factorization
    print(eng.stats())

Batched multi-RHS: `submit` queues RHS vectors against the current
factorization and `flush` stacks all same-shape pending RHS into a single
[N, k] jitted solve — one dispatch instead of k:

    eng.factor(A)
    t1, t2 = eng.submit(b1), eng.submit(b2)
    xs = eng.flush()               # one [N, 2] solve; xs[t1], xs[t2]

Batch slots (the many-small-systems path): `submit_system` queues whole
(A, b) systems and `flush_systems` factorizes each *size bucket* as ONE
batched plan execution (`plan((B, N))` — a single traced program,
batch-grid Pallas kernels on the pallas backend) instead of a Python loop
of B small factorizations that each leave the MXU idle.  Requests are
**ragged in N**: any n x n system with n <= the engine's N is accepted and
padded (identity diagonal, zero RHS tail) into the nearest power-of-two N
slot, then each slot's queue is padded to a power-of-two batch size — so
one cached plan serves a whole size range and the plan cache holds one
batched plan per (B-slot, N-slot) rather than one per request shape.  The
padding overhead is visible as `batch_pad_waste` in `stats()`:

    t1, t2, t3 = (eng.submit_system(A_i, b_i) for ...)   # mixed sizes OK
    xs = eng.flush_systems()       # one plan((B, Nslot)) execute per bucket

The engine is **thread-safe**: every queue mutation and counter increment
happens under one internal lock, so concurrent submitters (or a background
flusher — see `repro.serving.async_engine`) never lose requests, double-use
tickets, or tear the stats.  `flush`/`flush_systems` hold the lock through
the solve: a submit landing mid-flush simply waits and joins the *next*
batch, which is exactly the backpressure a serving loop wants.
"""

from __future__ import annotations

import threading
import time
from typing import NamedTuple

import jax
import numpy as np

from repro import obs
from repro.api import Factorization, SolverConfig, plan, plan_cache_stats

# Floor for the ragged-N power-of-two slot: below this the per-request
# padding waste is trivial anyway and smaller slots would only multiply
# cached batched plans (and collide with panel-width minimums).
MIN_N_SLOT = 8


def _next_pow2(k: int) -> int:
    """Smallest power of two >= k (k >= 1)."""
    return 1 << max(k - 1, 0).bit_length()


class _PreparedSystem(NamedTuple):
    """A validated, slot-padded (A, b) system awaiting a batched flush.

    A is [slotN, slotN] with the real n x n system in the leading block and
    an identity diagonal on the padded tail (trivially factorizable, exact:
    the trailing Schur updates of the zero off-diagonal blocks vanish, so
    padding never perturbs the leading block's factors or pivots); b is
    [slotN] with a zero tail, so the padded solution's tail is zero and
    `x[:n]` is the exact solution of the original system.

    refine_tol is the per-request iterative-refinement tolerance (None =
    plain factor-precision solve); the identity tail keeps refinement exact
    too — the padded lanes' residuals are identically zero.
    """

    A: np.ndarray
    b: np.ndarray
    n: int
    slotN: int
    refine_tol: float | None = None
    max_refine_iters: int = 25


class SolveEngine:
    """Repeated same-shape factorize/solve traffic over one compiled plan."""

    def __init__(self, N: int, config: SolverConfig | None = None, **overrides):
        self.config = (config or SolverConfig()).with_(**overrides)
        self.plan = plan(N, self.config)
        self.N = N
        # One lock covers queues + counters: cheap (micro-ops) next to the
        # solves it guards, and it makes every stats() snapshot consistent.
        self._lock = threading.RLock()
        self._last: Factorization | None = None
        self._pending: list[np.ndarray] = []  # queued RHS awaiting flush()
        # queued prepared systems awaiting flush_systems()
        self._pending_systems: list[_PreparedSystem] = []
        self._n_factor = 0
        self._n_solve = 0
        self._n_batched = 0  # batched solve dispatches (flush groups)
        self._n_batched_rhs = 0  # RHS vectors that rode a batched dispatch
        self._n_batched_factor = 0  # batched factorizations (bucket flushes)
        self._n_batched_systems = 0  # systems that rode a batched factorization
        self._n_batch_pad = 0  # identity systems added to fill batch slots
        self._n_refined = 0  # systems served with iterative refinement
        self._n_refine_iters = 0  # refinement iterations across those
        self._n_refine_nonconverged = 0  # refined systems that hit the cap
        self._cells_useful = 0  # sum of n^2 over real flushed systems
        self._cells_batched = 0  # sum of slotB * slotN^2 over bucket flushes
        self._t_factor = 0.0
        self._t_solve = 0.0
        self._t_batch = 0.0

    def factor(self, A) -> Factorization:
        """Factorize one N x N system on the compiled plan."""
        t0 = time.perf_counter()
        fact = self.plan.execute(A)
        dt = time.perf_counter() - t0
        with self._lock:
            self._t_factor += dt
            self._n_factor += 1
            self._last = fact
        return fact

    def solve(self, A, b):
        """Factorize A and solve A x = b (b: [N] or [N, k] multi-RHS)."""
        fact = self.factor(A)
        t0 = time.perf_counter()
        # block_until_ready: jax dispatch is async — without it the timer
        # measures enqueue latency, not the solve (`stats()` would report
        # near-zero `solve_s_total` regardless of N).
        x = jax.block_until_ready(fact.solve(b))
        dt = time.perf_counter() - t0
        with self._lock:
            self._t_solve += dt
            self._n_solve += 1
        return x

    def resolve(self, b):
        """Solve against the most recent factorization (no re-factorize)."""
        with self._lock:
            last = self._last
        if last is None:
            raise RuntimeError("no factorization yet; call factor() or solve() first")
        t0 = time.perf_counter()
        x = jax.block_until_ready(last.solve(b))
        dt = time.perf_counter() - t0
        with self._lock:
            self._t_solve += dt
            self._n_solve += 1
        return x

    def solve_many(self, systems):
        """[(A, b), ...] -> [x, ...] — a request batch on one plan."""
        return [np.asarray(self.solve(A, b)) for A, b in systems]

    def _prepare_rhs(self, b) -> np.ndarray:
        """Validate a single RHS vector for the stacked-solve queue.

        Raises ValueError on malformed input (the eager-failure contract of
        `submit`); returns the array so the async tier's tenant queues can
        hold validated RHS-only requests without enqueueing them here yet.
        """
        b = np.asarray(b)
        if b.shape != (self.N,):
            raise ValueError(f"submit takes a single [N] RHS with N={self.N}, "
                             f"got shape {b.shape}")
        if b.dtype.kind not in "fiub":
            raise ValueError(
                f"submit takes a real RHS (factors are real); got dtype "
                f"{b.dtype.name} — solve b.real and b.imag separately"
            )
        return b

    def submit(self, b) -> int:
        """Queue a single-RHS solve against the current factorization.

        Returns the ticket index into the list `flush()` returns.  The RHS
        is validated eagerly (shape [N]) so a malformed request fails at
        submit time, not inside a batch holding other requests hostage.
        """
        b = self._prepare_rhs(b)
        with self._lock:
            self._pending.append(b)
            return len(self._pending) - 1

    def flush(self):
        """Solve every pending RHS as one stacked [N, k] dispatch.

        All queued RHS share the engine's N, so one `jnp.stack` -> one jitted
        triangular-solve pair covers the whole batch; results come back in
        submit order.  Counts one batched solve (plus k RHS) in `stats()`.
        The lock is held through the solve, and the queue is cleared only
        after it succeeds: a failing batch (e.g. a numerically broken
        factorization) leaves every request queued for a retry instead of
        silently dropping it, and a submit racing the flush waits and lands
        in the next batch with a fresh ticket.
        """
        with self._lock:
            if self._last is None:
                raise RuntimeError(
                    "no factorization yet; call factor() or solve() first")
            if not self._pending:
                return []
            pending = self._pending
            B = np.stack(pending, axis=1)  # [N, k]
            t0 = time.perf_counter()
            X = jax.block_until_ready(self._last.solve(B))
            self._pending = []
            self._t_solve += time.perf_counter() - t0
            self._n_solve += len(pending)
            self._n_batched += 1
            self._n_batched_rhs += len(pending)
        X = obs.to_host(X)
        return [X[:, j] for j in range(X.shape[1])]

    def _prepare_system(self, A, b, refine_tol: float | None = None,
                        max_refine_iters: int = 25) -> _PreparedSystem:
        """Validate an (A, b) request and pad it into its power-of-two N slot.

        Raises ValueError on malformed input (the eager-failure contract of
        `submit_system`); returns the padded arrays plus the real size n, so
        both the engine queue and the async tier's tenant queues hold
        ready-to-stack requests.
        """
        if refine_tol is not None:
            refine_tol = float(refine_tol)
            if not refine_tol > 0:
                raise ValueError(
                    f"refine_tol must be a positive relative-residual "
                    f"tolerance, got {refine_tol!r}"
                )
            if not isinstance(max_refine_iters, int) or max_refine_iters < 0:
                raise ValueError(
                    f"max_refine_iters must be a non-negative int, got "
                    f"{max_refine_iters!r}"
                )
        A = np.asarray(A)
        b = np.asarray(b)
        n = A.shape[0] if A.ndim == 2 else 0
        if A.ndim != 2 or A.shape != (n, n) or not 1 <= n <= self.N:
            raise ValueError(
                f"submit_system takes a square [N, N] matrix with "
                f"N <= {self.N} (the engine's size), got shape {A.shape}"
            )
        if b.shape != (n,):
            raise ValueError(
                f"submit_system takes a single [N] RHS matching its matrix "
                f"(N={n}), got shape {b.shape}"
            )
        for name, arr in (("matrix", A), ("RHS", b)):
            if arr.dtype.kind not in "fiub":
                raise ValueError(
                    f"submit_system takes a real {name} (plan computes in "
                    f"{self.config.dtype}); got dtype {arr.dtype.name}"
                )
        # Exact-size requests keep the engine's N as their slot even when it
        # is not a power of two (the pre-ragged behavior); smaller systems
        # bucket to the nearest power-of-two >= max(MIN_N_SLOT, panel width).
        if n == self.N:
            slotN = self.N
        else:
            slotN = max(_next_pow2(n), MIN_N_SLOT, _next_pow2(self.config.v or 1))
            slotN = min(slotN, self.N)  # never exceed the engine's own size
        dtype = np.dtype(self.config.dtype)
        if slotN == n:
            Ap = A.astype(dtype, copy=True)
            bp = b.astype(dtype, copy=True)
        else:
            Ap = np.zeros((slotN, slotN), dtype)
            Ap[:n, :n] = A
            idx = np.arange(n, slotN)
            Ap[idx, idx] = 1.0  # identity tail: trivially factorizable
            bp = np.zeros(slotN, dtype)
            bp[:n] = b
        return _PreparedSystem(Ap, bp, n, slotN, refine_tol, max_refine_iters)

    def submit_system(self, A, b, *, refine_tol: float | None = None,
                      max_refine_iters: int = 25) -> int:
        """Queue a whole (A, b) system for a batched factorize+solve.

        Accepts any square n x n system with n <= the engine's N (ragged-N
        batching: the request is padded into the nearest power-of-two N
        slot, see `_prepare_system`).  Returns the ticket index into the
        list `flush_systems()` returns.  Both the matrix and the RHS are
        validated eagerly so a malformed request fails at submit time, not
        inside a batch holding other requests hostage.

        `refine_tol` requests per-request iterative refinement: the bucket
        still factorizes and solves as one batch, then the refine-requesting
        lanes run a second (batched) refinement pass against their retained
        working-precision systems — lanes without it get the bit-identical
        plain solve they always got.
        """
        return self._enqueue_prepared(
            self._prepare_system(A, b, refine_tol, max_refine_iters)
        )

    def _enqueue_prepared(self, prep: _PreparedSystem) -> int:
        """Queue an already-validated system (async tier fast path)."""
        with self._lock:
            self._pending_systems.append(prep)
            return len(self._pending_systems) - 1

    @staticmethod
    def _slot(k: int) -> int:
        """Next power-of-two batch slot >= k (bounds plan-cache pollution:
        one batched plan per slot size instead of one per request count)."""
        return _next_pow2(k)

    def _batched_plan(self, slot: int, N: int | None = None):
        """The cached batched plan matching this engine's config at size slot.

        Batched plans are sequential-only, so a distributed engine strategy
        maps to its sequential sibling of the same kind (the plan cache makes
        repeat slot sizes free).  N overrides the system size for ragged-N
        buckets (default: the engine's N).
        """
        strategy = "sequential_chol" if self.plan.kind == "cholesky" else "sequential"
        return plan(
            (slot, self.N if N is None else N),
            self.config.with_(strategy=strategy, grid=None, B=None),
        )

    def warm_slots(self, sizes=(None,), max_batch: int = 1) -> int:
        """Pre-trace the batched slot programs cold-start traffic would hit.

        `flush_systems` compiles one program per (batch slot, N slot) pair
        on first use — a ~100ms jit trace charged to whichever requests sit
        in that first batch.  Sparse arrival patterns are the worst case:
        every drain lands a *different* partial-batch slot, so early traffic
        keeps hitting fresh compiles.  This executes one identity batch plus
        solve through the same cached plans for each request size in `sizes`
        (None = the engine's own N) crossed with every power-of-two batch
        slot up to `max_batch`, and returns the number of programs warmed.
        Stats counters are untouched: warming is not traffic.
        """
        slotNs = set()
        for n in sizes:
            n = self.N if n is None else int(n)
            prep = self._prepare_system(np.eye(n), np.zeros(n))
            slotNs.add(prep.slotN)
        slots = []
        k = 1
        while k < max(1, int(max_batch)):
            slots.append(k)
            k *= 2
        slots.append(k)  # _next_pow2(max_batch): the full-drain slot
        dtype = np.dtype(self.config.dtype)
        warmed = 0
        for slotN in sorted(slotNs):
            for slotB in slots:
                bplan = self._batched_plan(slotB, slotN)
                A = np.zeros((slotB, slotN, slotN), dtype)
                A[:] = np.eye(slotN, dtype=dtype)
                fact = bplan.execute(A)
                rhs = np.zeros((slotB, slotN), dtype)
                if (fact.work_dtype is not None
                        and np.dtype(fact.work_dtype) != fact.dtype):
                    rhs = rhs.astype(np.float32 if fact.dtype.itemsize < 4
                                     else fact.dtype, copy=False)
                jax.block_until_ready(fact.solve(rhs))
                warmed += 1
        return warmed

    def flush_systems(self):
        """Factorize and solve every pending system, one batch per N slot.

        Groups the queue by its power-of-two N slot, stacks each group into
        a [slotB, slotN, slotN] block (padded to the next power-of-two batch
        slot with identity systems and zero RHS), runs ONE batched plan
        execution plus ONE batched solve per group, and returns the
        solutions (trimmed back to each request's real n) in submit order.
        The lock is held throughout and the queue is cleared only after
        every bucket succeeds, so a failing dispatch leaves all requests
        queued for a retry instead of silently dropping them.
        """
        with self._lock:
            if not self._pending_systems:
                return []
            pending = self._pending_systems
            results: list[np.ndarray | None] = [None] * len(pending)
            buckets: dict[int, list[tuple[int, _PreparedSystem]]] = {}
            for i, prep in enumerate(pending):
                buckets.setdefault(prep.slotN, []).append((i, prep))
            dtype = np.dtype(self.config.dtype)
            t0 = time.perf_counter()
            flushed = []  # (k, slotB, slotN) per bucket, applied on success
            refined = []  # (systems, iters, nonconverged) per refining bucket
            for slotN, items in sorted(buckets.items()):
                k = len(items)
                slotB = self._slot(k)
                with obs.span(obs.SERVE_STAGE):
                    A = np.empty((slotB, slotN, slotN), dtype)
                    rhs = np.zeros((slotB, slotN), dtype)
                    for j, (_, prep) in enumerate(items):
                        A[j] = prep.A
                        rhs[j] = prep.b
                    A[k:] = np.eye(slotN, dtype=dtype)  # identity pad systems
                bplan = self._batched_plan(slotB, slotN)
                fact = bplan.execute(A)
                # Pre-cast the RHS to the plain solve's arithmetic dtype on
                # mixed-precision engines: the downcast is the engine's own
                # contract (refine_tol is the per-request escape hatch), so
                # the intent-mismatch warning Factorization.solve raises for
                # interactive callers would only be flush-loop noise here.
                if (fact.work_dtype is not None
                        and np.dtype(fact.work_dtype) != fact.dtype):
                    sdt = (np.float32 if fact.dtype.itemsize < 4
                           else fact.dtype)
                    rhs_in = rhs.astype(sdt, copy=False)
                else:
                    rhs_in = rhs
                X = obs.to_host(fact.solve(rhs_in))
                for j, (i, prep) in enumerate(items):
                    results[i] = X[j, :prep.n]
                # Second pass: refinement on the lanes that asked for it.
                # Lanes without refine_tol already hold the bit-identical
                # plain solve; the refining lanes are index-selected into a
                # sub-Factorization and run ONE batched refine program with
                # per-lane tolerances.
                ridx = [j for j, (_, prep) in enumerate(items)
                        if prep.refine_tol is not None]
                if ridx:
                    sub = Factorization(
                        F=np.asarray(fact.F)[ridx],
                        rows=np.asarray(fact.rows)[ridx],
                        strategy=fact.strategy, backend=fact.backend,
                        kind=fact.kind,
                        A_ref=np.asarray(fact.A_ref)[ridx],
                        work_dtype=fact.work_dtype,
                    )
                    tols = np.asarray(
                        [items[j][1].refine_tol for j in ridx], np.float64
                    )
                    cap = max(items[j][1].max_refine_iters for j in ridx)
                    rs = sub.solve(rhs[ridx], refine_tol=tols,
                                   max_refine_iters=cap)
                    Xr = np.asarray(rs.x)
                    iters = np.atleast_1d(rs.refinement_iters)
                    conv = np.atleast_1d(rs.converged)
                    for pos, j in enumerate(ridx):
                        i, prep = items[j]
                        results[i] = Xr[pos, :prep.n]
                    refined.append((len(ridx), int(iters.sum()),
                                    int(len(conv) - conv.sum())))
                flushed.append((k, slotB, slotN))
            self._t_batch += time.perf_counter() - t0
            self._pending_systems = []
            for k, slotB, slotN in flushed:
                self._n_batched_factor += 1
                self._n_batched_systems += k
                self._n_batch_pad += slotB - k
                self._cells_batched += slotB * slotN * slotN
            for systems, iters, nonconv in refined:
                self._n_refined += systems
                self._n_refine_iters += iters
                self._n_refine_nonconverged += nonconv
            self._cells_useful += sum(p.n * p.n for p in pending)
        return results

    def _abort_pending_rhs(self) -> int:
        """Drop the queued RHS vectors (async-tier flush-failure twin of
        `_abort_pending_systems`: the futures already carry the exception).
        Returns the number of dropped requests."""
        with self._lock:
            dropped = len(self._pending)
            self._pending = []
            return dropped

    def _abort_pending_systems(self) -> int:
        """Drop the queued systems (async tier: after a flush failure has
        already propagated the exception to every request's future, retrying
        the same batch would only fail the *next* batch's tickets too).
        Returns the number of dropped requests."""
        with self._lock:
            dropped = len(self._pending_systems)
            self._pending_systems = []
            return dropped

    def stats(self) -> dict:
        """Engine counters + the global plan-cache hit/miss trajectory."""
        with self._lock:
            waste = (1.0 - self._cells_useful / self._cells_batched
                     if self._cells_batched else 0.0)
            return {
                "N": self.N,
                "strategy": self.plan.config.strategy,
                "backend": self.plan.config.backend,
                "grid": str(self.plan.grid),
                "factorizations": self._n_factor,
                "solves": self._n_solve,
                "batched_solves": self._n_batched,
                "batched_rhs": self._n_batched_rhs,
                "batched_factorizations": self._n_batched_factor,
                "batched_systems": self._n_batched_systems,
                "batch_pad_systems": self._n_batch_pad,
                "refined_systems": self._n_refined,
                "refine_iters_total": self._n_refine_iters,
                "refine_nonconverged": self._n_refine_nonconverged,
                # fraction of batched compute cells spent on padding (both
                # the identity fill systems and the ragged-N identity tails)
                "batch_pad_waste": round(waste, 6),
                "pending": len(self._pending),
                "pending_systems": len(self._pending_systems),
                "trace_count": self.plan.trace_count,
                "factor_s_total": round(self._t_factor, 6),
                "solve_s_total": round(self._t_solve, 6),
                "batch_s_total": round(self._t_batch, 6),
                # includes the LRU hit/miss/eviction + size/capacity counters
                "plan_cache": plan_cache_stats(),
            }

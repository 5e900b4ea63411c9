"""Factorization — the one result type every strategy returns.

Subsumes the old `LUResult` dataclass and the raw `(F, rows)` tuples: packed
masked factors (rows never move, paper §7.3), the pivot order, the grid the
factorization ran on, and the instrumented per-processor communication
volume of the schedule.  Solves, determinants, and reconstruction are
methods, each backed by a single module-level jitted program shared across
instances (no per-result re-tracing).

Two factorization kinds share the type: `kind="lu"` (packed masked LU,
PA = LU) and `kind="cholesky"` (F holds the lower factor L with A = L L^T,
rows is the identity).  The methods branch on `kind`, so serving code and
the benchmarks consume both families through one interface.

Mixed precision: a plan built with `SolverConfig(compute_dtype=...)` factors
in a low MXU-native dtype and stamps the working-precision input onto the
result as `A_ref`.  `solve(b, refine_tol=...)` then runs jitted iterative
refinement — residual `r = b - A x` in the working dtype, correction solves
on the cached low-precision factors — returning a `RefinedSolve` carrying
the refined solution plus `refinement_iters` / `final_residual` /
`converged`.  A float64 working dtype is honored by wrapping the refine
program in `jax.enable_x64(True)` (the rest of the library runs
without x64, where jax silently demotes f64).
"""

from __future__ import annotations

import contextlib
import functools
import warnings
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core.cholesky.sequential import chol_reconstruct, chol_solve
from repro.core.lu.grid import GridConfig
from repro.core.lu.sequential import permutation_sign, unpack_factors
from repro.core.precision import full_precision


def _psolve(F, rows, b):
    """x = U^-1 L^-1 P b from packed masked factors (PA = LU)."""
    _, L, U = unpack_factors(F, rows)
    pb = b[rows]
    y = jax.scipy.linalg.solve_triangular(L, pb, lower=True, unit_diagonal=True)
    return jax.scipy.linalg.solve_triangular(U, y, lower=False)


def _preconstruct(F, rows):
    P, L, U = unpack_factors(F, rows)
    return P.T @ (L @ U)


def _pudiag(F, rows):
    n = F.shape[0]
    return F[rows, jnp.arange(n)]


def _program(fn):
    """A jitted program whose dots run at full float32 precision."""
    return jax.jit(full_precision(fn))


# Single-system jitted programs, shared across instances — plus their vmapped
# siblings for batched factorizations ([B, N, N] factors, [B, N] pivots).
_packed_solve = _program(_psolve)
_packed_solve_batched = _program(jax.vmap(_psolve))
_packed_reconstruct = _program(_preconstruct)
_packed_reconstruct_batched = _program(jax.vmap(_preconstruct))
_packed_u_diag = _program(_pudiag)
_packed_u_diag_batched = _program(jax.vmap(_pudiag))

# jitted wrappers over the one implementation in core.cholesky.sequential
_chol_solve = _program(chol_solve)
_chol_solve_batched = _program(jax.vmap(chol_solve))
_chol_reconstruct = _program(chol_reconstruct)
_chol_reconstruct_batched = _program(jax.vmap(chol_reconstruct))


# ---------------------------------------------------------------------------
# iterative refinement: low-precision correction solves, working-precision
# residuals (classic LP-factor IR; converges while cond(A) * eps_factor < 1)
# ---------------------------------------------------------------------------


def _refine_core(F, rows, A, b, tol, max_iters, *, chol: bool):
    """One system's refine loop.  A/b in the working dtype, F low precision.

    Returns (x [working dtype], iters int32, final relative residual,
    converged bool).  The relative residual is max over RHS columns of
    ||b_j - A x_j||_2 / ||b_j||_2.  Non-finite correction steps are rejected
    (the carry keeps the last finite iterate), so a singular or catastrophic
    low-precision factorization reports `converged=False` with a finite
    residual instead of propagating NaN into the solution.
    """
    wd = A.dtype
    # Correction solves run in fp32 when the factors are narrower (bf16/f16
    # triangular arithmetic would waste refinement iterations on solve noise;
    # the upcast is free next to the O(N^2) substitutions).
    sd = jnp.float32 if jnp.dtype(F.dtype).itemsize < 4 else F.dtype
    Fs = F.astype(sd)
    vec = b.ndim == 1
    bm = b[:, None] if vec else b  # [N, k]

    def lowsolve(r):
        rs = r.astype(sd)
        y = chol_solve(Fs, rs) if chol else _psolve(Fs, rows, rs)
        return y.astype(wd)

    den = jnp.maximum(
        jnp.linalg.norm(bm, axis=0), jnp.asarray(jnp.finfo(wd).tiny, wd)
    )

    def residual(x):
        r = bm - A @ x
        return r, jnp.max(jnp.linalg.norm(r, axis=0) / den)

    x0 = lowsolve(bm)
    finite0 = jnp.all(jnp.isfinite(x0))
    # A broken factorization (singular pivot -> inf/nan solve) restarts from
    # x = 0: residual b, relative residual exactly 1 — finite, reportable.
    x0 = jnp.where(finite0, x0, jnp.zeros_like(x0))
    r0, res0 = residual(x0)

    def cond(carry):
        _, _, res, it = carry
        return (res > tol) & (it < max_iters)

    def body(carry):
        x, r, res, it = carry
        # Under vmap the while_loop condition becomes "any lane active" and
        # the body runs on every lane, so the update must re-check this
        # lane's own state: converged lanes keep their x and iter count.
        active = (res > tol) & (it < max_iters)
        d = lowsolve(r)
        xn = x + d
        rn, resn = residual(xn)
        take = active & jnp.isfinite(resn)
        x = jnp.where(take, xn, x)
        r = jnp.where(take, rn, r)
        res = jnp.where(take, resn, res)
        return x, r, res, it + active.astype(it.dtype)

    x, _, res, it = jax.lax.while_loop(
        cond, body, (x0, r0, res0, jnp.zeros((), jnp.int32))
    )
    return x[:, 0] if vec else x, it, res, res <= tol


def _make_refine(chol: bool, batched: bool):
    core = functools.partial(_refine_core, chol=chol)
    if not batched:
        return _program(core)

    def fn(F, rows, A, b, tol, max_iters):
        # per-lane tol (the serving tier carries one tolerance per request);
        # max_iters is shared across the batch.
        return jax.vmap(
            lambda F_, rows_, A_, b_, tol_: core(F_, rows_, A_, b_, tol_, max_iters)
        )(F, rows, A, b, tol)

    return _program(fn)


_refine_lu = _make_refine(chol=False, batched=False)
_refine_lu_batched = _make_refine(chol=False, batched=True)
_refine_chol = _make_refine(chol=True, batched=False)
_refine_chol_batched = _make_refine(chol=True, batched=True)


@dataclass
class RefinedSolve:
    """A refined solve: working-precision solution + convergence report.

    x:                the refined solution in the working dtype ([N]/[N, k],
                      leading B axis on batched factorizations).
    refinement_iters: correction iterations taken (int; [B] array batched).
    final_residual:   max-over-columns relative residual ||b - A x|| / ||b||
                      at exit (float; [B] array batched).
    converged:        final_residual <= refine_tol (bool; [B] array batched).
                      False means the iteration cap was hit — the solution is
                      still the best (finite) iterate, never NaN.
    """

    x: np.ndarray
    refinement_iters: int | np.ndarray
    final_residual: float | np.ndarray
    converged: bool | np.ndarray

    def __array__(self, dtype=None):
        return np.asarray(self.x, dtype=dtype)

    @property
    def shape(self):
        return np.asarray(self.x).shape

    @property
    def dtype(self):
        return np.asarray(self.x).dtype


@dataclass
class Factorization:
    """Packed masked LU factors plus everything needed to consume them."""

    F: np.ndarray  # packed factors, original row positions [N, N] ([B, N, N] batched)
    rows: np.ndarray  # pivot order (global row ids) [N] ([B, N] batched)
    grid: GridConfig | None = None
    comm: dict = field(default_factory=dict)
    strategy: str = ""
    backend: str = ""  # KernelBackend that ran the local compute ("ref"/"pallas")
    kind: str = "lu"  # "lu" (F = packed masked LU) or "cholesky" (F = lower L)
    # per-primitive hot-loop wall times (us), populated when the plan was
    # profiled via FactorizationPlan.profile_hotloop()
    hotloop: dict = field(default_factory=dict)
    # the working-precision input matrix, retained by plan.execute for
    # refinement residuals (None on hand-built results: refined solves then
    # raise instead of silently skipping the residual check)
    A_ref: np.ndarray | None = None
    # the working dtype the caller asked for (SolverConfig.dtype); None
    # (hand-built results) means "same as the factor dtype"
    work_dtype: np.dtype | None = None
    # trace-calibrated auto decision + this execute's measured wall time
    # (predicted_wall_us / measured_wall_us / wall_residual); None unless the
    # plan came from the calibrated `strategy="auto"` path
    autotune: dict | None = None
    # the devices that held the factorization program's output shards (one
    # per shard, replicas included); empty on hand-built results
    devices: tuple[str, ...] = ()

    @property
    def N(self) -> int:
        return int(np.asarray(self.F).shape[-1])

    @property
    def batched(self) -> bool:
        """True when this holds B independent factorizations ([B, N, N])."""
        return np.asarray(self.F).ndim == 3

    @property
    def B(self) -> int | None:
        """Batch size, or None for a single-system factorization."""
        return int(np.asarray(self.F).shape[0]) if self.batched else None

    @property
    def dtype(self):
        return np.asarray(self.F).dtype

    def solve(self, b, *, refine_tol=None, max_refine_iters: int = 25):
        """Solve A x = b.  b: [N] single RHS or [N, k] multi-RHS batch.

        On a batched factorization b is [B, N] (one RHS per system) or
        [B, N, k], and each system solves against its own factors.

        With `refine_tol=None` (default) this is the plain factor-precision
        solve: one jitted triangular-solve pair shared by all Factorization
        instances (a new RHS *shape* compiles once, then reuses).  On a
        mixed-precision factorization the plain solve runs with fp32
        arithmetic over the low-precision factors and returns that compute
        precision — accuracy is factor-limited either way.

        With `refine_tol=<float>` the solve runs iterative refinement:
        working-precision residuals against the retained `A_ref`, correction
        solves on the cached low-precision factors, looping until the
        relative residual passes `refine_tol` or `max_refine_iters`.
        Returns a `RefinedSolve` (duck-types as the solution array via
        `__array__`, plus `refinement_iters`/`final_residual`/`converged`).
        On a batched factorization `refine_tol` may be a [B] array (one
        tolerance per system).
        """
        with obs.span(obs.SOLVE):
            return self._solve(b, refine_tol, max_refine_iters)

    def _solve(self, b, refine_tol, max_refine_iters: int):
        # Inspect the incoming dtype before jnp.asarray: without jax x64 the
        # conversion itself silently demotes float64, which is exactly the
        # downcast we must surface.  Only arrays carry dtype intent — a plain
        # Python list defaults to float64 in numpy without meaning it, so the
        # downcast warning fires for explicit dtypes only.
        has_dtype = hasattr(b, "dtype")
        in_dt = np.dtype(b.dtype) if has_dtype else np.asarray(b).dtype
        if in_dt.kind == "c":
            raise ValueError(
                f"complex RHS dtype {in_dt.name} is not supported (factors are "
                f"{self.dtype}); solve against b.real and b.imag separately"
            )
        if refine_tol is not None:
            return self._solve_refined(b, refine_tol, max_refine_iters)
        wd = np.dtype(self.work_dtype) if self.work_dtype is not None else self.dtype
        if has_dtype and in_dt.kind == "f" and in_dt.itemsize > self.dtype.itemsize:
            hint = (
                "pass solve(..., refine_tol=...) to recover working precision"
                if wd.itemsize >= in_dt.itemsize
                else "set SolverConfig.dtype to keep precision"
            )
            warnings.warn(
                f"factors are {self.dtype}; RHS {in_dt.name} will be downcast "
                f"({hint})",
                stacklevel=3,
            )
        if wd != self.dtype and self.dtype.itemsize < 4:
            # mixed-precision factors narrower than fp32: solve with fp32
            # arithmetic (bf16 triangular substitutions would add solve noise
            # on top of the factor error for no win)
            solve_dt = np.dtype(np.float32)
            F = obs.to_device(self.F).astype(solve_dt)
        else:
            solve_dt = self.dtype
            F = obs.to_device(self.F)
        b = obs.to_device(b, dtype=solve_dt)
        if self.batched:
            if b.ndim not in (2, 3) or b.shape[:2] != (self.B, self.N):
                raise ValueError(
                    f"batched factorization: b must be [B, N] or [B, N, k] "
                    f"with B={self.B}, N={self.N}, got shape {b.shape}"
                )
            if self.kind == "cholesky":
                return _chol_solve_batched(F, b)
            return _packed_solve_batched(F, obs.to_device(self.rows), b)
        if b.ndim not in (1, 2) or b.shape[0] != self.N:
            raise ValueError(
                f"b must be [N] or [N, k] with N={self.N}, got shape {b.shape}"
            )
        if self.kind == "cholesky":
            return _chol_solve(F, b)
        return _packed_solve(F, obs.to_device(self.rows), b)

    def _solve_refined(self, b, tol, max_iters: int) -> RefinedSolve:
        """Iterative refinement against the retained working-precision A_ref."""
        if self.A_ref is None:
            raise ValueError(
                "refined solve needs the original matrix for residuals, but "
                "this Factorization carries no A_ref; execute through "
                "repro.api.plan (which retains it) or set fact.A_ref"
            )
        if not isinstance(max_iters, (int, np.integer)) or max_iters < 0:
            raise ValueError(
                f"max_refine_iters must be a non-negative int, got {max_iters!r}"
            )
        wd = np.dtype(self.work_dtype) if self.work_dtype is not None else self.dtype
        if wd.itemsize < 4:
            wd = np.dtype(np.float32)  # residual accumulation floor
        b = np.asarray(b)
        if self.batched:
            if b.ndim not in (2, 3) or b.shape[:2] != (self.B, self.N):
                raise ValueError(
                    f"batched factorization: b must be [B, N] or [B, N, k] "
                    f"with B={self.B}, N={self.N}, got shape {b.shape}"
                )
        elif b.ndim not in (1, 2) or b.shape[0] != self.N:
            raise ValueError(
                f"b must be [N] or [N, k] with N={self.N}, got shape {b.shape}"
            )
        chol = self.kind == "cholesky"
        # A float64 working dtype needs x64 enabled around conversion AND the
        # jitted program — without it jax silently demotes to f32 and the
        # "refined to f64 quality" contract would be a lie.
        ctx = jax.enable_x64(True) if wd == np.float64 else contextlib.nullcontext()
        with ctx:
            A = obs.to_device(self.A_ref, dtype=wd)
            bj = obs.to_device(b, dtype=wd)
            F = obs.to_device(self.F)
            rows = obs.to_device(self.rows)
            mi = jnp.asarray(int(max_iters), jnp.int32)
            if self.batched:
                tol_arr = jnp.broadcast_to(
                    jnp.asarray(tol, dtype=wd), (self.B,)
                )
                fn = _refine_chol_batched if chol else _refine_lu_batched
            else:
                tol_arr = jnp.asarray(float(tol), dtype=wd)
                fn = _refine_chol if chol else _refine_lu
            x, it, res, conv = fn(F, rows, A, bj, tol_arr, mi)
            x, it, res, conv = (obs.to_host(v) for v in (x, it, res, conv))
        if self.batched:
            return RefinedSolve(
                x=x, refinement_iters=it, final_residual=res, converged=conv
            )
        return RefinedSolve(
            x=x, refinement_iters=int(it), final_residual=float(res),
            converged=bool(conv),
        )

    def slogdet(self):
        """(sign, log|det|) — overflow-safe; vectorized permutation sign.

        Batched factorizations return [B]-shaped signs and log-dets."""
        if self.kind == "cholesky":
            # det(A) = prod(diag(L))^2 > 0
            d = jnp.diagonal(jnp.asarray(self.F), axis1=-2, axis2=-1)
            return jnp.ones(d.shape[:-1], d.dtype), 2.0 * jnp.sum(jnp.log(d), axis=-1)
        if self.batched:
            d = _packed_u_diag_batched(jnp.asarray(self.F), jnp.asarray(self.rows))
            sign = jnp.asarray(
                [permutation_sign(r) for r in np.asarray(self.rows)], d.dtype
            )
        else:
            d = _packed_u_diag(jnp.asarray(self.F), jnp.asarray(self.rows))
            sign = permutation_sign(self.rows)
        return sign * jnp.prod(jnp.sign(d), axis=-1), jnp.sum(
            jnp.log(jnp.abs(d)), axis=-1
        )

    def det(self):
        s, ld = self.slogdet()
        return s * jnp.exp(ld)

    def reconstruct(self):
        """Rebuild A (original row order) from the factors."""
        if self.kind == "cholesky":
            if self.batched:
                return _chol_reconstruct_batched(jnp.asarray(self.F))
            return _chol_reconstruct(jnp.asarray(self.F))
        if self.batched:
            return _packed_reconstruct_batched(
                jnp.asarray(self.F), jnp.asarray(self.rows)
            )
        return _packed_reconstruct(jnp.asarray(self.F), jnp.asarray(self.rows))

    def unpack(self):
        """LU: (P, L, U) with P @ A = L @ U.  Cholesky: the lower factor L.

        Batched factorizations unpack per system (leading B axis)."""
        if self.kind == "cholesky":
            return jnp.asarray(self.F)
        if self.batched:
            return jax.vmap(unpack_factors)(
                jnp.asarray(self.F), jnp.asarray(self.rows)
            )
        return unpack_factors(jnp.asarray(self.F), jnp.asarray(self.rows))

    def comm_report(self) -> str:
        """Instrumented communication volume, elements AND bytes per proc.

        Every communicated element travels at the *compute* dtype's width,
        so a bf16 plan moves a quarter of the bytes of the f64 model row at
        identical element counts — the mixed-precision comm win, made
        visible."""
        wd = np.dtype(self.work_dtype) if self.work_dtype is not None else self.dtype
        prec = (f"dtype={self.dtype.name}"
                + (f" (working {wd.name})" if wd != self.dtype else ""))
        head = (f"strategy={self.strategy or '?'} backend={self.backend or '?'} "
                f"kind={self.kind} grid={self.grid} N={self.N} {prec}")
        itemsize = self.dtype.itemsize
        if not self.comm:
            lines = [f"{head}\n  single-device: no inter-processor communication"]
        else:
            lines = [head, f"  {'':20s} {'elements/proc':>14s} {'bytes/proc':>16s}"]
            for k, val in self.comm.items():
                if isinstance(val, (int, float)):
                    lines.append(
                        f"  {k:20s} {val:14,.0f} {val * itemsize:16,.0f}"
                    )
        if self.hotloop:
            lines.append("  hot-loop primitives (us, profiled local shapes):")
            for k, val in self.hotloop.items():
                if isinstance(val, (int, float)):
                    lines.append(f"    {k:18s} {val:12,.1f}")
        if self.autotune:
            pred = self.autotune.get("predicted_wall_us")
            meas = self.autotune.get("measured_wall_us")
            resid = self.autotune.get("wall_residual")
            lines.append(
                f"  autotune ({self.autotune.get('source', '?')}, calibration "
                f"{self.autotune.get('calibration_version', '?')}):"
            )
            if pred is not None and meas is not None:
                lines.append(
                    f"    predicted {pred:12,.1f} us   measured {meas:12,.1f} us"
                    f"   residual {resid:+.1%}" if resid is not None else
                    f"    predicted {pred:12,.1f} us   measured {meas:12,.1f} us"
                )
        return "\n".join(lines)

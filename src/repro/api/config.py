"""SolverConfig — the single declarative input to `repro.api.plan`.

Everything that used to be scattered across call sites (a `distributed`
bool, a `pivot` string, direct imports of a concrete factorization) is one
frozen, hashable record.  `plan()` resolves it against the problem size and
the available devices into a concrete `FactorizationPlan`.
"""

from __future__ import annotations

from collections.abc import Mapping
from dataclasses import dataclass, replace

import numpy as np

from repro.core.lu.grid import GridConfig

PIVOTS = ("tournament", "partial", "none")
HOTLOOPS = ("windowed", "flat")

# The computation dtype used when a caller gives none (and what the legacy
# shims normalize integer/bool matrices to).
DEFAULT_DTYPE = "float32"

# Dtypes the factorization may *compute* in (SolverConfig.compute_dtype).
# bfloat16/float16 are the MXU-native low-precision inputs; the kernels
# accumulate in fp32 regardless, and iterative refinement
# (`Factorization.solve(refine_tol=...)`) recovers working-precision solves.
COMPUTE_DTYPES = ("bfloat16", "float16", "float32", "float64")


def resolve_dtype(name) -> np.dtype:
    """np.dtype resolution that also understands the ml_dtypes names.

    Plain numpy only knows 'bfloat16' once ml_dtypes has registered it;
    jax always ships ml_dtypes, so importing it on demand keeps this module
    import-light while making `np.dtype('bfloat16')` work.
    """
    try:
        return np.dtype(name)
    except TypeError:
        import ml_dtypes  # noqa: F401 — registers bfloat16 et al. with numpy

        return np.dtype(name)


@dataclass(frozen=True)
class SolverConfig:
    """Declarative solver selection.

    strategy: a registered strategy name ("auto", "conflux", "baseline2d",
        "sequential", "cholesky25d", "sequential_chol", ...).  "auto" runs
        Processor Grid Optimization over the available devices and falls
        back to "sequential" on one device.
    pivot:    "tournament" (COnfLUX butterfly) or "partial" (ScaLAPACK-style).
              "none" is Cholesky-only — pivoting is meaningless for SPD
              matrices, so the Cholesky strategies normalize any requested
              pivot to "none" at resolve time and the LU strategies reject it.
    grid:     explicit GridConfig, or a mapping of its fields (as a JSON
              file gives it); None lets the strategy choose one.
    dtype:    *working* dtype (normalized to its numpy name, so configs hash):
              the precision of the input matrix, the retained `A_ref`, the
              refinement residual, and the refined solution.
    compute_dtype: the dtype the factorization kernels actually run in, or
              None to compute in `dtype` (the default; `compute_dtype ==
              dtype` normalizes to None so default-path plans cache-share).
              Pick an MXU-native low precision ("bfloat16"/"float16"/
              "float32") to keep the pallas kernels on dtypes the hardware
              has a fast path for — e.g. `dtype="float64",
              compute_dtype="float32"` factors in f32 (no pallas -> ref
              fallback) and `Factorization.solve(b, refine_tol=...)`
              recovers f64-quality solutions via iterative refinement on
              the cached low-precision factors.  Must not be wider than
              `dtype`.
    M:        fast-memory budget per processor, in elements (drives the
              replication factor c <= P*M/N^2 during grid optimization).
    P_target: processor budget for grid selection; None = all local devices.
    v:        panel width override; None lets the strategy/optimizer choose.
    backend:  registered KernelBackend name supplying the local compute
              primitives — "ref" (pure jnp) or "pallas" (MXU-tiled kernels;
              interpret mode on CPU).  Validated at plan resolution, which
              auto-falls back pallas -> ref (with a warning) when the plan
              violates the kernels' tiling constraints (float64, v not a
              multiple of 8).
    hotloop:  step-body variant of the 2.5D schedules — "windowed" (default:
              shrinking power-of-two trailing windows, indexed pivot-row
              gathers, fused TRSM->Schur) or "flat" (the full-block body,
              kept as the bit-parity oracle and benchmark baseline).
    B:        batch size for the many-small-systems path, or None for a
              single system.  `plan((B, N))` sets it; a batched plan
              factorizes a [B, N, N] stack in one traced program (sequential
              strategies only — the distributed schedules shard one large
              matrix and reject B).
    calibration: version tag of the cost-model calibration that resolved
              this config, stamped by the trace-calibrated `strategy="auto"`
              path (see `repro.analysis.costmodel`).  Callers leave it None;
              it enters the cache key so plans chosen under one calibration
              never alias plans chosen under another (re-fitting on new
              hardware invalidates stale auto picks instead of silently
              reusing them).
    """

    strategy: str = "auto"
    pivot: str = "tournament"
    grid: GridConfig | None = None
    dtype: str = DEFAULT_DTYPE
    M: float = 2.0**14
    P_target: int | None = None
    v: int | None = None
    backend: str = "ref"
    hotloop: str = "windowed"
    B: int | None = None
    compute_dtype: str | None = None
    calibration: str | None = None

    def __post_init__(self):
        if isinstance(self.grid, Mapping):
            object.__setattr__(self, "grid", GridConfig(**self.grid))
        dt = np.dtype(self.dtype)
        if dt.kind == "c":
            raise ValueError(
                f"complex dtype {dt.name!r} is not supported; factorize the real "
                f"and imaginary parts separately or use a real 2N x 2N embedding"
            )
        if dt.kind != "f":
            raise ValueError(
                f"SolverConfig.dtype must be an inexact (floating) dtype — the "
                f"factorizations divide by pivots inside jitted loops, so "
                f"{dt.name!r} would fail deep in tracing with a carry-type "
                f"error; cast the matrix or pass dtype='float32'/'float64'"
            )
        object.__setattr__(self, "dtype", dt.name)
        if self.compute_dtype is not None:
            try:
                cdt = resolve_dtype(self.compute_dtype)
            except TypeError:
                raise ValueError(
                    f"compute_dtype {self.compute_dtype!r} is not a known "
                    f"dtype; choose from {COMPUTE_DTYPES}"
                ) from None
            if cdt.name not in COMPUTE_DTYPES:
                raise ValueError(
                    f"compute_dtype {cdt.name!r} is not a supported kernel "
                    f"dtype; choose from {COMPUTE_DTYPES}"
                )
            if cdt.itemsize > dt.itemsize:
                raise ValueError(
                    f"compute_dtype {cdt.name!r} is wider than the working "
                    f"dtype {dt.name!r}; low-precision compute + iterative "
                    f"refinement only makes sense with compute_dtype <= dtype"
                )
            # compute == working is the default path; normalizing to None
            # keeps those configs sharing one cache key (and keeps the
            # bit-exactness oracle trivial).
            object.__setattr__(
                self, "compute_dtype", None if cdt.name == dt.name else cdt.name
            )
        if self.pivot not in PIVOTS:
            raise ValueError(f"unknown pivot {self.pivot!r}; choose from {PIVOTS}")
        if not isinstance(self.backend, str) or not self.backend:
            raise ValueError(
                f"backend must be a registered KernelBackend name, got {self.backend!r}"
            )
        if self.hotloop not in HOTLOOPS:
            raise ValueError(
                f"unknown hotloop {self.hotloop!r}; choose from {HOTLOOPS}"
            )
        if self.B is not None and (not isinstance(self.B, int) or self.B < 1):
            raise ValueError(
                f"B must be a positive int batch size or None, got {self.B!r}"
            )
        if self.calibration is not None and not isinstance(self.calibration, str):
            raise ValueError(
                f"calibration must be a version string or None, got "
                f"{self.calibration!r}"
            )

    def with_(self, **changes) -> "SolverConfig":
        """Functional update (dataclasses.replace with validation rerun)."""
        return replace(self, **changes)

    @property
    def effective_compute_dtype(self) -> str:
        """The dtype the kernels actually run in (compute_dtype or dtype)."""
        return self.compute_dtype or self.dtype

    def cache_key(self, N: int) -> tuple:
        """Key identifying the compiled plan this config resolves to.

        Only meaningful on a *resolved* config (concrete strategy + grid +
        backend); `plan()` resolves before keying, so a pallas plan and a ref
        plan of the same problem never share a cache entry.  B is part of
        the key, so `plan((B, N))` and `plan(N)` never collide, and
        compute_dtype is part of the key, so a low-precision plan never
        collides with the full-precision plan of the same working dtype.
        The calibration version participates so an auto pick made under one
        fitted cost table never serves a process running under another.
        """
        return (N, self.dtype, self.strategy, self.pivot, self.grid, self.v,
                self.backend, self.hotloop, self.B, self.compute_dtype,
                self.calibration)

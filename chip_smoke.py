#!/usr/bin/env python3
"""Smoke run of the solver on a TPU: its main path once, at a real size.

    python chip_smoke.py              # phases 1-5 on one chip
    python chip_smoke.py --chips 4    # the distributed phase (four chips)

One process drives the public entry points — `repro.api.plan(...).execute`,
`Factorization.solve` and `AsyncSolveEngine.submit` — and checks every
answer:

  1. device    the platform must be "tpu"; nothing runs on the CPU.
  2. LU        N=16384 f32, `strategy="sequential"` on the pallas and ref
               backends: HPL's scaled residual
               ||Ax-b||_inf / (eps (||A||_inf ||x||_inf + ||b||_inf) N) < 16,
               computed on the host in f64 with eps the f32 epsilon.
  3. Cholesky  `sequential_chol` at the same N, same residual check.
  4. bf16      bfloat16 factors of an f32 system, refined to f32 accuracy.
  5. serving   an AsyncSolveEngine answers ragged requests, n in [16, 256];
               every answer is compared with numpy.linalg.solve.
  6. --chips 4 only: `conflux` and `cholesky25d` on the P=4 grids [2x2x1]
               and [2x1x2], compared with the one-chip `sequential` solve.

Every matrix is generated from --seed.  A failed check raises, so the
script exits non-zero; only a run whose phases all passed prints the last
line, {"ok": true, "device": {...}}.  The times printed on the way are
smoke readings of one run each (first calls include compilation), not
benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
import warnings

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

N = 16384  # in-core system size: the matrix alone is 1 GiB in f32
HPL_THRESHOLD = 16.0
EPS32 = float(np.finfo(np.float32).eps)


class SmokeFailure(AssertionError):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(msg, flush=True)


# -- matrices (host, f32, from the seed) ------------------------------------


def hpl_matrix(rng, n: int) -> np.ndarray:
    """HPL's test matrix: entries uniform on [-0.5, 0.5)."""
    return rng.random((n, n), dtype=np.float32) - np.float32(0.5)


def spd_matrix(rng, n: int) -> np.ndarray:
    """Symmetrised uniform matrix plus n*I: SPD by diagonal dominance."""
    G = hpl_matrix(rng, n)
    A = G + G.T
    A *= np.float32(0.5)
    A[np.diag_indices(n)] += np.float32(n)
    return A


def mxp_matrix(rng, n: int) -> np.ndarray:
    """HPL-MxP's test matrix: uniform entries, each diagonal entry set to
    the sum of the absolute values of its row's other entries."""
    A = hpl_matrix(rng, n)
    A[np.diag_indices(n)] = 0.0
    A[np.diag_indices(n)] = np.abs(A).sum(axis=1)
    return A


def permuted_matrix(rng, n: int) -> tuple[np.ndarray, np.ndarray]:
    """Rows of a well-conditioned matrix G + sqrt(n) I, shuffled.

    Each column holds one entry of size sqrt(n) and the rest below 0.5, so
    partial and tournament pivoting must both pick, for column k, the row
    that holds that entry.  Returns (A, expected pivot order)."""
    A = hpl_matrix(rng, n)
    A[np.diag_indices(n)] += np.float32(np.sqrt(n))
    perm = rng.permutation(n)
    return A[perm], np.argsort(perm)


def hpl_scaled_residual(A: np.ndarray, x, b: np.ndarray) -> float:
    """HPL's scaled residual, on the host in f64 (row chunks bound memory)."""
    n = A.shape[0]
    x64 = np.asarray(x, np.float64)
    b64 = b.astype(np.float64)
    r_inf = a_inf = 0.0
    for i in range(0, n, 2048):
        rows = A[i:i + 2048].astype(np.float64)
        r_inf = max(r_inf, float(np.abs(rows @ x64 - b64[i:i + 2048]).max()))
        a_inf = max(a_inf, float(np.abs(rows).sum(axis=1).max()))
    den = EPS32 * (a_inf * float(np.abs(x64).max()) + float(np.abs(b64).max())) * n
    return r_inf / den


# -- phases -----------------------------------------------------------------


def device_phase(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, but JAX found platform "
                 f"{d.platform!r} ({d.device_kind}); nothing was run")
    check(len(devs) >= chips, f"--chips {chips} needs {chips} devices, "
          f"JAX found {len(devs)}")
    log(f"[device] {d.platform} {d.device_kind} x{len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def peak_gib() -> float:
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2**30


def factor_and_solve(name, n, config, A, b, *, refine_tol=None):
    """plan -> execute -> solve through the public API, timed and checked."""
    from repro.api import plan

    p = plan(n, config)
    want = config.backend
    check(p.config.backend == want,
          f"{name}: resolved backend {p.config.backend!r}, requested {want!r}")
    t0 = time.perf_counter()
    fact = p.execute(A)
    t1 = time.perf_counter()
    if refine_tol is None:
        x = np.asarray(fact.solve(b))
    else:
        x = fact.solve(b, refine_tol=refine_tol, max_refine_iters=50)
    t2 = time.perf_counter()
    res = hpl_scaled_residual(A, np.asarray(x), b)
    layout = f"grid={p.config.grid}" if p.config.grid else f"v={p.config.v}"
    log(f"[{name}] strategy={p.config.strategy} backend={p.config.backend} "
        f"{layout} dtype={p.config.dtype} "
        f"compute={p.config.effective_compute_dtype} "
        f"N={n}: execute {t1 - t0:.2f}s (first call, with compile), "
        f"solve {t2 - t1:.2f}s, HPL scaled residual {res:.4g}, "
        f"peak {peak_gib():.2f} GiB (smoke reading)")
    check(np.all(np.isfinite(np.asarray(x))), f"{name}: non-finite solution")
    check(res < HPL_THRESHOLD,
          f"{name}: HPL scaled residual {res:.4g} >= {HPL_THRESHOLD}")
    return fact, x, res


def lu_phase(rng, n: int) -> None:
    from repro.api import SolverConfig

    A = hpl_matrix(rng, n)
    b = rng.random(n, dtype=np.float32) - np.float32(0.5)
    for backend in ("pallas", "ref"):
        factor_and_solve(f"lu/{backend}", n,
                         SolverConfig(strategy="sequential", backend=backend),
                         A, b)


def cholesky_phase(rng, n: int) -> None:
    from repro.api import SolverConfig

    A = spd_matrix(rng, n)
    b = rng.random(n, dtype=np.float32) - np.float32(0.5)
    for backend in ("pallas", "ref"):
        factor_and_solve(f"cholesky/{backend}", n,
                         SolverConfig(strategy="sequential_chol",
                                      backend=backend), A, b)


def bf16_phase(rng, n: int) -> None:
    from repro.api import SolverConfig

    A = mxp_matrix(rng, n)
    b = rng.random(n, dtype=np.float32) - np.float32(0.5)
    cfg = SolverConfig(strategy="sequential", backend="pallas",
                       compute_dtype="bfloat16")
    fact, x, _ = factor_and_solve("bf16+refine", n, cfg, A, b,
                                  refine_tol=1e-5)
    with warnings.catch_warnings():  # the plain solve rounds b to bf16
        warnings.simplefilter("ignore", UserWarning)
        plain = hpl_scaled_residual(A, np.asarray(fact.solve(b)), b)
    log(f"[bf16+refine] {x.refinement_iters} refinement iterations, "
        f"relative residual {x.final_residual:.3g} (converged={x.converged}); "
        f"unrefined bf16 solve: HPL scaled residual {plain:.4g}")
    check(x.converged, "bf16+refine: refinement did not converge")
    check(x.refinement_iters >= 1, "bf16+refine: refinement never ran")


def serving_phase(rng, requests: int = 300, nmax: int = 256) -> None:
    from repro.serving import AsyncSolveEngine

    sizes = rng.integers(16, nmax + 1, size=requests)
    systems = []
    for n in sizes:
        A = rng.standard_normal((n, n)).astype(np.float32)
        A[np.diag_indices(n)] += np.float32(2 * np.sqrt(n))  # well-posed
        systems.append((A, rng.standard_normal(n).astype(np.float32)))
    t0 = time.perf_counter()
    with AsyncSolveEngine(N=nmax, strategy="sequential", backend="pallas",
                          max_batch=32, max_delay_ms=5.0, max_queue=requests) as eng:
        check(eng.engine.plan.config.backend == "pallas",
              "serving: engine plan did not resolve to pallas")
        futs = [eng.submit(A, b, tenant=f"tenant-{i % 3}")
                for i, (A, b) in enumerate(systems)]
        worst = 0.0
        for (A, b), fut in zip(systems, futs):
            x = np.asarray(fut.result(timeout=900), np.float64)
            want = np.linalg.solve(A.astype(np.float64), b.astype(np.float64))
            err = float(np.abs(x - want).max() / np.abs(want).max())
            check(err < 1e-4, f"serving: n={A.shape[0]} answer off by {err:.3g}")
            worst = max(worst, err)
        stats = eng.stats()
    a = stats["async"]
    log(f"[serving] {requests} requests, n in [16, {nmax}]: all match "
        f"numpy.linalg.solve (worst relative error {worst:.3g}); "
        f"{time.perf_counter() - t0:.2f}s with compiles (smoke reading)")
    log(f"[serving] stats: flushes={a['flushes']} served={a['served']} "
        f"failed={a['failed']} shed={a['shed']} spilled={a['spilled']} "
        f"batch_fill={a['batch_fill']} latency_ms={a['latency_ms']}")
    check(a["served"] == requests and a["failed"] == 0,
          f"serving: served {a['served']} of {requests}, failed {a['failed']}")


def distributed_phase(rng, n: int) -> None:
    """conflux and cholesky25d on P=4 grids vs the one-chip sequential solve."""
    from repro.api import GridConfig, SolverConfig

    A, pivots = permuted_matrix(rng, n)
    S = spd_matrix(rng, n)
    b = rng.random(n, dtype=np.float32) - np.float32(0.5)
    cases = (("conflux", "sequential", A), ("cholesky25d", "sequential_chol", S))
    for dist, seq, M in cases:
        one, x1, _ = factor_and_solve(f"{seq}/1-chip", n,
                                      SolverConfig(strategy=seq, backend="pallas"),
                                      M, b)
        for Px, Py, c in ((2, 2, 1), (2, 1, 2)):
            grid = GridConfig(Px=Px, Py=Py, c=c, v=32, N=n)
            fact, x, _ = factor_and_solve(
                f"{dist}/[{Px}x{Py}x{c}]", n,
                SolverConfig(strategy=dist, backend="pallas", grid=grid), M, b)
            log(f"[{dist}/[{Px}x{Py}x{c}]] output shards on {list(fact.devices)}")
            check(len(set(fact.devices)) == Px * Py * c,
                  f"{dist}: output shards on {len(set(fact.devices))} devices, "
                  f"want {Px * Py * c}")
            diff = float(np.abs(np.asarray(x, np.float64) - x1).max()
                         / np.abs(x1).max())
            log(f"[{dist}/[{Px}x{Py}x{c}]] vs 1-chip {seq}: max relative "
                f"difference {diff:.3g}")
            check(diff < 1e-4, f"{dist}: differs from the 1-chip solve by {diff:.3g}")
            if dist == "conflux":
                check(np.array_equal(fact.rows, pivots),
                      "conflux: pivot order misses the permuted rows")
        if seq == "sequential":
            check(np.array_equal(one.rows, pivots),
                  "sequential: pivot order misses the permuted rows")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the distributed phase, on four chips")
    args = ap.parse_args()

    device = device_phase(args.chips)
    from repro.compile_cache import enable_compile_cache

    cache = enable_compile_cache(ROOT)
    held = len(os.listdir(cache)) if os.path.isdir(cache) else 0
    log(f"[setup] compile cache at {cache}, {held} entries at start")
    # A plan that silently fell back from pallas to ref would pass the
    # residual checks on the wrong path: make the fallback an error.
    warnings.filterwarnings("error", message="backend 'pallas' cannot run")
    rng = np.random.default_rng(args.seed)
    t0 = time.perf_counter()
    if args.chips == 4:
        distributed_phase(rng, N)
    else:
        lu_phase(rng, N)
        cholesky_phase(rng, N)
        bf16_phase(rng, N)
        serving_phase(rng)
    log(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()

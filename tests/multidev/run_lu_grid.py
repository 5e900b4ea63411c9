"""Subprocess worker: distributed LU correctness on 8 host devices.

Run by tests/test_lu_distributed.py (device count must be pinned before jax
initializes, so this cannot live in the main pytest process).
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import sys

import numpy as np
import jax.numpy as jnp

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..", "src"))
sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

from repro.api import SolverConfig, factor
from repro.core.lu.grid import GridConfig
from repro.core.lu.sequential import reconstruct
from test_hotloop import assert_factors_match


def check(res, A, tag, tol=5e-5):
    N = A.shape[0]
    rec = np.asarray(reconstruct(jnp.asarray(res.F), jnp.asarray(res.rows)))
    err = np.abs(rec - A).max() / np.abs(A).max()
    assert err < tol, f"{tag}: reconstruction err {err}"
    assert sorted(res.rows.tolist()) == list(range(N)), f"{tag}: bad permutation"
    print(f"PASS {tag} err={err:.2e} comm/proc={res.comm['total']:.0f}")


def check_windowed_flat(A, g, pivot):
    """Windowed == flat step body on a grid with Py > 1 or c > 1, where the
    window buckets with c_start > 0 write pivot rows back across devices."""
    facts = {hl: factor(A, SolverConfig(strategy="conflux", grid=g, pivot=pivot,
                                        hotloop=hl))
             for hl in ("windowed", "flat")}
    w, f = facts["windowed"], facts["flat"]
    np.testing.assert_array_equal(w.rows, f.rows)
    assert_factors_match(w.F, f.F, g.N)
    check(w, A, f"windowed==flat {pivot} {g}")


def main():
    rng = np.random.default_rng(7)
    grids = [
        GridConfig(Px=2, Py=2, c=2, v=8, N=64),
        GridConfig(Px=2, Py=2, c=2, v=16, N=128),
        GridConfig(Px=4, Py=2, c=1, v=8, N=64),
        GridConfig(Px=2, Py=1, c=4, v=8, N=96),
        GridConfig(Px=1, Py=2, c=4, v=8, N=64),
        GridConfig(Px=8, Py=1, c=1, v=8, N=64),
    ]
    for g in grids:
        A = rng.standard_normal((g.N, g.N)).astype(np.float32)
        check(factor(A, SolverConfig(strategy="conflux", grid=g)), A, f"conflux {g}")
    A = rng.standard_normal((128, 128)).astype(np.float32)
    check(factor(A, SolverConfig(strategy="baseline2d", P_target=8, v=16)),
          A, "scalapack2d [2x4]")
    A = rng.standard_normal((256, 256)).astype(np.float32)
    for g in (GridConfig(Px=2, Py=2, c=1, v=8, N=256),
              GridConfig(Px=2, Py=1, c=2, v=8, N=256)):
        for pivot in ("tournament", "partial"):
            check_windowed_flat(A, g, pivot)
    # auto grid selection end-to-end
    A = rng.standard_normal((128, 128)).astype(np.float32)
    res = factor(A, SolverConfig(strategy="auto", M=2048.0))
    check(res, A, f"auto-grid {res.grid}")

    # plan/execute API on the full device count: cached plan, single trace,
    # multi-RHS solve vs numpy.
    from repro.api import GridConfig as GC, plan, plan_cache_stats

    N = 128
    cfg = SolverConfig(strategy="conflux", grid=GC(Px=2, Py=2, c=2, v=16, N=N))
    A = rng.standard_normal((N, N)).astype(np.float32)
    B = rng.standard_normal((N, 5)).astype(np.float32)
    p = plan(N, cfg)
    f1 = p.execute(A)
    hits0 = plan_cache_stats()["hits"]
    p2 = plan(N, cfg)  # same key: must be a pure cache hit
    f2 = p2.execute(A)
    assert p is p2 and p.trace_count == 1, (p.trace_count, p is p2)
    assert plan_cache_stats()["hits"] == hits0 + 1
    X = np.asarray(f2.solve(B))
    X_np = np.linalg.solve(A.astype(np.float64), B.astype(np.float64))
    assert np.abs(X - X_np).max() < 5e-3, np.abs(X - X_np).max()
    print(f"PASS api-plan {p.grid} traces={p.trace_count} "
          f"solve_err={np.abs(A @ X - B).max():.2e}")
    print("ALL-OK")


if __name__ == "__main__":
    main()

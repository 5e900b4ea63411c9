"""ref-vs-pallas backend parity on a 2x1x1 grid (non-square local tiles).

With Px=2, Py=1 every device holds an [N/2, N] local block, so the kernel
primitives see genuinely rectangular shapes (R != C) under shard_map — the
case the single-device 1x1x1 parity sweep cannot reach.  Run as a
subprocess: the host device count must be pinned before jax initializes.
"""

import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"

import numpy as np  # noqa: E402

from repro.api import GridConfig, SolverConfig, plan  # noqa: E402

rng = np.random.default_rng(3)
N, v = 32, 8
A = rng.standard_normal((N, N)).astype(np.float32)
grid = GridConfig(Px=2, Py=1, c=1, v=v, N=N)

facts = {}
for backend in ("ref", "pallas"):
    cfg = SolverConfig(strategy="conflux", backend=backend, grid=grid)
    p = plan(N, cfg)
    assert p.config.backend == backend, (backend, p.config.backend)
    facts[backend] = p.execute(A)

ref, pal = facts["ref"], facts["pallas"]
assert np.array_equal(ref.rows, pal.rows), "pivot orders diverged across backends"
np.testing.assert_allclose(ref.F, pal.F, rtol=1e-4, atol=1e-4)
for backend, fact in facts.items():
    err = np.abs(np.asarray(fact.reconstruct()) - A).max()
    assert err < 1e-4, (backend, err)
    assert sorted(fact.rows.tolist()) == list(range(N)), backend
    # one output shard per device of the 2x1x1 mesh
    assert len(set(fact.devices)) == 2, (backend, fact.devices)

# Windowed-vs-flat parity with *real* collectives inside the lax.switch
# bucket bodies: Px=2 exercises the tournament ppermute and the (px, pz)
# gather psums across genuinely distinct devices per branch — the case the
# single-device sweep in tests/test_hotloop.py cannot reach.  Pivots match
# exactly; the factors of the two programs agree to N ulps of the largest
# entry (XLA may fuse and order their reductions differently).
G = rng.standard_normal((N, N)).astype(np.float32)
A_spd = (G @ G.T / N + np.eye(N, dtype=np.float32))
for strategy, Ain, pivot in [("conflux", A, "tournament"),
                             ("conflux", A, "partial"),
                             ("cholesky25d", A_spd, "none")]:
    hl_facts = {}
    for hl in ("windowed", "flat"):
        cfg = SolverConfig(strategy=strategy, pivot=pivot, grid=grid, hotloop=hl)
        hl_facts[hl] = plan(N, cfg).execute(Ain)
    w, f = hl_facts["windowed"], hl_facts["flat"]
    assert np.array_equal(w.rows, f.rows), (strategy, pivot, "pivot order diverged")
    tol = N * np.finfo(f.F.dtype).eps * np.abs(f.F).max()
    assert np.abs(w.F - f.F).max() <= tol, (
        strategy, pivot, "factors diverged", np.abs(w.F - f.F).max(), tol)
    err = np.abs(np.asarray(w.reconstruct()) - Ain).max()
    assert err < 1e-4, (strategy, pivot, err)
print("ALL-OK")

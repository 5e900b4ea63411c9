"""Executed distributed LU at container scale via the plan/execute API:
correctness + wall time + instrumented comm volume + plan-cache/trace
counters on 8 host devices (subprocess because the device count must be
pinned before jax initializes).

Each (strategy, backend) pair executes the same plan twice: the second run
demonstrates the re-trace win (trace_count stays 1, the plan cache reports a
hit).  The conflux/sequential LU strategies and the cholesky25d/
sequential_chol SPD strategies run on both kernel backends — "ref" (pure
jnp) and "pallas" (the MXU-tiled kernels, interpret mode on this CPU
container) — so BENCH_lu.json carries the ref-vs-pallas wall-time delta and
the conflux-vs-cholesky comm-volume ratio (~2x fewer elements/proc for the
symmetric schedule) per PR; on real TPUs the same dispatch compiles to
Mosaic.

The ``batched`` rows (schema v5) time the many-small-systems path: one
``plan((B, N))`` execute over a [B, N, N] stack against the Python loop of
B single-system executes, interleaved best-of-7, on both backends — the
``loop_over_batched`` throughput ratio is the acceptance metric (and the
smoke perf gate compares it against the committed baseline).

The ``mixed_precision`` rows (schema v7) time the end-to-end cost of an
f64-quality solve three ways: genuine-f64 direct factor+solve (under
``enable_x64``) against the f32 and bf16 factor + iterative-refinement
pipelines (``SolverConfig(compute_dtype=...)`` + ``solve(refine_tol=...)``)
— ``refined_over_direct`` is the wall ratio the full-run validator floors
at < 1.0 for f32 and the smoke gate tracks PR-over-PR.  Measured rows also
carry ``comm_per_proc_bytes`` (elements x compute-dtype itemsize — the
wire-accurate volume) alongside the element counts.

The ``hotloop`` rows A/B the shrinking-window + fused step body
against the flat full-block baseline — full-run wall time for conflux and
cholesky25d on both backends, plus the per-primitive breakdown (panel /
trsm / schur / gather, fused vs unfused, indexed vs dense gather) from
`FactorizationPlan.profile_hotloop` — the PR-over-PR perf trajectory of the
hot loop itself.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_WORKER = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"  # host devices only, never the chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, time, json
sys.path.insert(0, %(src)r)
import numpy as np, jax, jax.numpy as jnp
from repro.api import SolverConfig, plan, plan_cache_stats, GridConfig
from repro.api.config import resolve_dtype
from repro.core.lu.cost_models import chol_model, conflux_model, scalapack2d_model

SMOKE = %(smoke)r
rng = np.random.default_rng(0)
records = []
print("impl,backend,N,grid,us_per_call,err,comm_per_proc,traces,cache_hits")
for N in ((64,) if SMOKE else (128, 256)):
    A = rng.standard_normal((N, N)).astype(np.float32)
    G = rng.standard_normal((N, N)).astype(np.float32)
    A_spd = G @ G.T / N + np.eye(N, dtype=np.float32)  # the SPD/serving input
    b = rng.standard_normal((N, 4)).astype(np.float32)
    v = 16
    grid25 = GridConfig(Px=2, Py=2, c=2, v=v, N=N)
    configs = []
    for backend in ("ref", "pallas"):
        configs.append(("conflux", SolverConfig(
            strategy="conflux", backend=backend, grid=grid25)))
        configs.append(("sequential", SolverConfig(strategy="sequential",
                                                   backend=backend)))
        configs.append(("cholesky25d", SolverConfig(
            strategy="cholesky25d", backend=backend, grid=grid25)))
        configs.append(("sequential_chol", SolverConfig(strategy="sequential_chol",
                                                        backend=backend)))
    configs.append(("baseline2d", SolverConfig(strategy="baseline2d",
                                               P_target=8, v=v)))
    for name, cfg in configs:
        spd = name in ("cholesky25d", "sequential_chol")
        Ain = A_spd if spd else A
        hits0 = plan_cache_stats()["hits"]
        p = plan(N, cfg)
        res = p.execute(Ain)          # warm compile
        p2 = plan(N, cfg)             # must be a cache hit, no re-trace
        dts = []
        for _ in range(3):            # best-of-3: the shared container is noisy
            t0 = time.perf_counter(); res = p2.execute(Ain)
            dts.append(time.perf_counter() - t0)
        dt = min(dts)
        hits = plan_cache_stats()["hits"] - hits0
        rec = np.asarray(res.reconstruct())
        err = float(np.abs(rec - Ain).max() / np.abs(Ain).max())
        x = np.asarray(res.solve(b))
        solve_err = float(np.abs(Ain @ x - b).max())
        comm = res.comm.get("total", 0.0)
        P_used = res.grid.P_used if res.grid else 1
        if res.grid is None:
            model = 0.0
        elif name == "baseline2d":
            model = scalapack2d_model(N, P_used)
        elif spd:
            model = chol_model(N, P_used, M=max(N * N * res.grid.c / P_used, 4.0))
        else:
            model = conflux_model(N, P_used, M=max(N * N * res.grid.c / P_used, 4.0))
        backend = p.config.backend
        print(f"{name},{backend},{N},{res.grid},{dt*1e6:.0f},{err:.2e},{comm:.0f},"
              f"{p.trace_count},{hits}")
        # the factors move over the wire in the *compute* dtype, so the
        # byte-accurate volume is elements x its itemsize, not the working
        # dtype's (schema v7; matches Factorization.comm_report)
        itemsize = resolve_dtype(cfg.effective_compute_dtype).itemsize
        records.append({
            "strategy": name, "backend": backend, "N": N, "grid": str(res.grid),
            "wall_us_per_call": dt * 1e6, "reconstruction_err": err,
            "solve_err": solve_err, "comm_per_proc_elements": comm,
            "comm_per_proc_bytes": comm * itemsize,
            "compute_dtype": cfg.effective_compute_dtype,
            "model_per_proc_elements": model,
            "trace_count": p.trace_count, "plan_cache_hits": hits,
            "plan_is_shared": p is p2,
        })
assert all(r["trace_count"] == 1 for r in records), "a plan re-traced!"

# ref-vs-pallas wall-time delta per (strategy, N) — the perf trajectory rows.
by_key = {(r["strategy"], r["N"], r["backend"]): r for r in records}
deltas = []
for (name, N, backend), r in sorted(by_key.items()):
    if backend != "pallas":
        continue
    ref = by_key.get((name, N, "ref"))
    if ref:
        deltas.append({
            "strategy": name, "N": N,
            "ref_us": ref["wall_us_per_call"], "pallas_us": r["wall_us_per_call"],
            "pallas_over_ref": r["wall_us_per_call"] / max(ref["wall_us_per_call"], 1e-9),
        })
for d in deltas:
    print(f"# delta {d['strategy']} N={d['N']}: pallas/ref = {d['pallas_over_ref']:.2f}x")

# hotloop rows: windowed-vs-flat full-run wall time per (strategy, backend)
# plus the per-primitive breakdown (panel / trsm / schur / gather and the
# fused-vs-unfused / indexed-vs-dense deltas) profiled on the plan's shapes.
# Measured on a 1x1x1 grid: the windowed/fused tentpole changes the *local*
# step body, and on the in-process multi-device mesh the per-collective
# rendezvous (~ms per psum on XLA:CPU, see the SPMD note in core.lu.conflux)
# swamps the local-compute delta the rows are meant to track.
hotloop_rows = []
N_hot = 64 if SMOKE else 256
A_hot = rng.standard_normal((N_hot, N_hot)).astype(np.float32)
G_hot = rng.standard_normal((N_hot, N_hot)).astype(np.float32)
Aspd_hot = G_hot @ G_hot.T / N_hot + np.eye(N_hot, dtype=np.float32)
grid_hot = GridConfig(Px=1, Py=1, c=1, v=16, N=N_hot)
for name in ("conflux", "cholesky25d"):
    Ain = Aspd_hot if name == "cholesky25d" else A_hot
    for backend in ("ref", "pallas"):
        plans = {hl: plan(N_hot, SolverConfig(strategy=name, backend=backend,
                                              grid=grid_hot, hotloop=hl))
                 for hl in ("windowed", "flat")}
        for p in plans.values():
            p.execute(Ain)  # warm compile
        # Interleaved best-of-7: these rows feed the CI perf gate via the
        # windowed/flat ratio, and the shared container drifts through slow
        # phases lasting whole seconds — alternating the two bodies sample
        # by sample puts any phase on both sides of the ratio instead of
        # poisoning one.
        dts = {hl: [] for hl in plans}
        for _ in range(7):
            for hl, p in plans.items():
                t0 = time.perf_counter(); p.execute(Ain)
                dts[hl].append(time.perf_counter() - t0)
        walls = {hl: min(ts) * 1e6 for hl, ts in dts.items()}
        prims = {k: val for k, val in plans["windowed"].profile_hotloop().items()
                 if isinstance(val, (int, float))}
        hotloop_rows.append({
            "strategy": name, "backend": backend, "N": N_hot,
            "grid": str(grid_hot), "windowed_us": walls["windowed"],
            "flat_us": walls["flat"],
            "windowed_over_flat": walls["windowed"] / max(walls["flat"], 1e-9),
            "primitives": prims,
        })
for d in hotloop_rows:
    print(f"# hotloop {d['strategy']}/{d['backend']} N={d['N']}: "
          f"windowed/flat = {d['windowed_over_flat']:.2f}x "
          f"(schur {d['primitives'].get('schur_us', 0):.0f}us, "
          f"fused {d['primitives'].get('fused_us', 0):.0f}us)")

# batched many-small-systems rows (schema v5): ONE plan((B, N)) execute — a
# single traced program over the [B, N, N] stack — against the Python loop
# of B single-system executes on the (cached, pre-warmed) single plan.  The
# interleaved best-of-7 puts the container's slow phases on both sides of
# the ratio, same reasoning as the hotloop rows above.  The pallas row runs
# at a smaller B: interpret mode executes grid points in Python, so the
# batch-grid win there is kernel-launch amortization, not wall time.
batched_rows = []
for backend, Bb in (("ref", 128), ("pallas", 8)):
    Nb, vb = 32, 8
    Ab = rng.standard_normal((Bb, Nb, Nb)).astype(np.float32)
    cfgb = SolverConfig(strategy="sequential", backend=backend, v=vb)
    pb = plan((Bb, Nb), cfgb)
    ps = plan(Nb, cfgb)
    pb.execute(Ab)        # warm compile (batched program)
    ps.execute(Ab[0])     # warm compile (single program, reused by the loop)
    dts_b, dts_l = [], []
    for _ in range(7):
        t0 = time.perf_counter(); pb.execute(Ab)
        dts_b.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        for i in range(Bb):
            ps.execute(Ab[i])
        dts_l.append(time.perf_counter() - t0)
    batched_us = min(dts_b) * 1e6
    loop_us = min(dts_l) * 1e6
    batched_rows.append({
        "B": Bb, "N": Nb, "backend": backend, "dtype": "float32",
        "batched_us": batched_us, "loop_us": loop_us,
        "loop_over_batched": loop_us / max(batched_us, 1e-9),
    })
for d in batched_rows:
    print(f"# batched {d['backend']} B={d['B']} N={d['N']}: "
          f"loop/batched = {d['loop_over_batched']:.1f}x "
          f"({d['loop_us']:.0f}us -> {d['batched_us']:.0f}us)")

# conflux-vs-cholesky comm volume at equal (N, grid) — the symmetric schedule
# should move roughly half the elements per processor (~2x fewer).
chol_vs_lu = []
for (name, N, backend), r in sorted(by_key.items()):
    if name != "cholesky25d" or backend != "ref":
        continue
    lu = by_key.get(("conflux", N, "ref"))
    if lu and r["comm_per_proc_elements"]:
        chol_vs_lu.append({
            "N": N, "grid": r["grid"],
            "lu_per_proc_elements": lu["comm_per_proc_elements"],
            "chol_per_proc_elements": r["comm_per_proc_elements"],
            "lu_over_chol": lu["comm_per_proc_elements"] / r["comm_per_proc_elements"],
        })
for d in chol_vs_lu:
    print(f"# comm {d['grid']} N={d['N']}: lu/cholesky = {d['lu_over_chol']:.2f}x")

# mixed-precision rows (schema v7): the end-to-end cost of an f64-quality
# solve.  f64_ref_direct factors and solves in genuine f64 (enable_x64 —
# jax on this container silently demotes otherwise); the refined rows
# factor in the MXU-native compute dtype and recover working precision via
# solve(refine_tol=...).  Wall time is factor + solve for both, interleaved
# best-of so container drift lands on every config; residuals are measured
# externally in numpy f64 against the same matrix.  N chosen where the
# f64/f32 factorization ratio has opened up (~1.9x at N=512 on this
# container vs 1.05x at N=256) so the full-run wall floor in run.py is a
# real claim, not noise.  Diagonally dominant input: the bf16 pipeline
# (~8 mantissa bits) only contracts for modest condition numbers — the
# conditioning sweep lives in tests/test_mixed_precision.py.
N_mp, v_mp = (128, 16) if SMOKE else (512, 32)
rng_mp = np.random.default_rng(7)
A_mp = rng_mp.standard_normal((N_mp, N_mp))
A_mp += N_mp * np.eye(N_mp)
b_mp = rng_mp.standard_normal((N_mp, 1))
bden = float(np.abs(b_mp).max())
mp_cases = [("f64_ref_direct", None), ("f32_refined", "float32"),
            ("bf16_refined", "bfloat16")]
mp_plans, mp_walls, mp_meta = {}, {}, {}

def mp_run(cname, cdt):
    cfg = SolverConfig(strategy="sequential", backend="ref", dtype="float64",
                       compute_dtype=cdt, v=v_mp)
    p = mp_plans.setdefault(cname, plan(N_mp, cfg))
    t0 = time.perf_counter()
    fact = p.execute(A_mp)
    if cdt is None:
        x = np.asarray(jax.block_until_ready(fact.solve(b_mp)))
        iters, conv = 0, True
    else:
        # tol at ~10x f64 machine epsilon: the validator floors the refined
        # residual at 10x the f64 direct row's, so refinement must iterate
        # all the way down to working-precision level, not just "good enough"
        rs = fact.solve(b_mp, refine_tol=2e-15, max_refine_iters=40)
        x, iters, conv = np.asarray(rs), int(rs.refinement_iters), bool(rs.converged)
    wall = time.perf_counter() - t0
    res = float(np.abs(A_mp @ x.astype(np.float64) - b_mp).max() / bden)
    return wall, res, iters, conv

with jax.enable_x64(True):  # the direct rows need genuine f64; refined rows manage
    for cname, cdt in mp_cases:  # their own x64 scope but are no-ops under it
        mp_run(cname, cdt)  # warm compile, untimed
        mp_walls[cname] = []
    for _ in range(5):  # interleaved best-of-5
        for cname, cdt in mp_cases:
            wall, res, iters, conv = mp_run(cname, cdt)
            mp_walls[cname].append(wall)
            mp_meta[cname] = (res, iters, conv, cdt)
direct_wall = min(mp_walls["f64_ref_direct"]) * 1e6
mixed_rows = []
for cname, _ in mp_cases:
    res, iters, conv, cdt = mp_meta[cname]
    wall = min(mp_walls[cname]) * 1e6
    mixed_rows.append({
        "config": cname, "N": N_mp, "v": v_mp, "dtype": "float64",
        "compute_dtype": cdt or "float64", "backend": "ref",
        "wall_us": wall, "residual": res, "refinement_iters": iters,
        "converged": conv,
        "refined_over_direct": wall / max(direct_wall, 1e-9),
    })
for d in mixed_rows:
    print(f"# mixed {d['config']} N={d['N']}: {d['wall_us']:.0f}us "
          f"({d['refined_over_direct']:.2f}x of direct), residual "
          f"{d['residual']:.2e}, {d['refinement_iters']} refine iters")
print("BENCH_JSON:" + json.dumps({"measured": records,
                                  "backend_delta": deltas,
                                  "chol_vs_lu": chol_vs_lu,
                                  "hotloop": hotloop_rows,
                                  "batched": batched_rows,
                                  "mixed_precision": mixed_rows,
                                  "plan_cache": plan_cache_stats()}))
"""


def main(csv: bool = True, smoke: bool = False):
    src = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run(
        [sys.executable, "-c", _WORKER % {"src": src, "smoke": smoke}],
        capture_output=True, text=True, timeout=1200,
    )
    if proc.returncode != 0:
        raise RuntimeError(proc.stderr[-2000:])
    payload = None
    for line in proc.stdout.splitlines():
        if line.startswith("BENCH_JSON:"):
            payload = json.loads(line[len("BENCH_JSON:"):])
        else:
            print(line)
    return payload


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv)

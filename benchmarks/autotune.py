"""Calibrate the trace-driven cost model and demonstrate the autotuner.

``python -m benchmarks.autotune [--smoke] [--write-default]``

Three stages, all from *measured* traces on this container:

1. **Primitive sweep** — `profile_primitives` (interleaved best-of-k with
   per-primitive spread) across a grid of (N, v) shapes per (backend,
   compute dtype), fitted into per-primitive `t = alpha + beta * work`
   constants by `repro.analysis.costmodel.fit_calibration`.
2. **Loop-overhead correction** — the standalone primitive timings carry
   per-dispatch overhead that the single-dispatch `fori_loop` hot loop does
   not pay, so the fitted alphas overprice many-step configs.  A few full
   `plan().execute()` probes at different v regress a global alpha scale
   `s >= 0` (measured wall = beta terms + s * alpha terms) that prices the
   *in-loop* per-step overhead instead.
3. **Collective alpha-beta fit** — distributed conflux executes on the 8
   pinned host devices (subprocess, same pattern as `lu_measured`) at
   several grids; the wall time in excess of the predicted compute is
   regressed against (collective op count, wire bytes from the audit's
   exact extraction) for the per-op latency and per-byte cost.

The result is saved as ``calibration.json`` at the repo root (the artifact
CI uploads; `strategy="auto"` uses it only when ``$REPRO_CALIBRATION``
names it) and ``--write-default`` refreshes the committed hermetic
cold-start table in ``src/repro/analysis/calibration_default.json``.

The ``autotune`` bench section (schema v9) then demonstrates the acceptance
criterion: resolve ``strategy="auto"`` under the fresh calibration, measure
its pick's full-run wall against the analytic (comm-argmin) pick's —
interleaved best-of-k, same process, so container load swings cancel — and
report predicted vs measured for both plus the auto/analytic ratio that
``benchmarks.run --validate`` floors at <= 1 + AUTOTUNE_TOLERANCE.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
_SRC = os.path.join(_ROOT, "src")
CALIBRATION_JSON = os.path.join(_ROOT, "calibration.json")
DEFAULT_TABLE = os.path.join(_SRC, "repro", "analysis",
                             "calibration_default.json")

# The measured auto pick may be slower than the analytic pick by at most
# this fraction before --validate / the smoke gate fails the run.  The two
# walls are interleaved same-process measurements (load swings cancel), but
# nearby (v, backend) tuples on this container legitimately sit within ~25%
# of each other, so the floor fires on real mispicks, not jitter.
AUTOTUNE_TOLERANCE = 0.25


def _sweep_shapes(smoke: bool) -> list[tuple[int, int]]:
    shapes = [(64, 8), (64, 16), (96, 32), (128, 16), (128, 32)]
    if not smoke:
        shapes += [(192, 32), (256, 32), (256, 64)]
    return shapes


def collect_samples(smoke: bool, repeats: int = 5) -> dict:
    """Primitive samples per (backend, compute dtype) across the shape sweep."""
    import contextlib

    import jax

    from repro.api.config import SolverConfig
    from repro.api.hotloop import profile_primitives
    from repro.analysis.costmodel import profile_sample_points

    combos = [("ref", "float32"), ("pallas", "float32"), ("ref", "float64"),
              ("ref", "bfloat16")]
    samples: dict = {}
    for backend, dtype in combos:
        per_prim: dict = {}
        # bfloat16 is a compute dtype, not a working dtype; f64 needs x64 on.
        if dtype == "bfloat16":
            cfg_kw = dict(dtype="float32", compute_dtype="bfloat16")
        else:
            cfg_kw = dict(dtype=dtype)
        ctx = jax.enable_x64(True) if dtype == "float64" else contextlib.nullcontext()
        with ctx:
            for N, v in _sweep_shapes(smoke):
                if backend == "pallas" and v % 8:
                    continue
                cfg = SolverConfig(strategy="sequential", backend=backend,
                                   v=v, **cfg_kw)
                t = profile_primitives(N, cfg, grid=None, repeats=repeats)
                for prim, pt in profile_sample_points(t, "lu").items():
                    per_prim.setdefault(prim, []).append(pt)
        samples[(backend, dtype)] = per_prim
    return samples


def _measure_execute(p, A, rounds: int = 5) -> float:
    """Best-of-N wall (us) of a pre-warmed plan's execute."""
    p.execute(A)
    best = float("inf")
    for _ in range(rounds):
        t0 = time.perf_counter()
        p.execute(A)
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def fit_alpha_scale(calib, smoke: bool) -> float:
    """Regress the global in-loop alpha scale from full-run probes.

    predict_wall with the raw (standalone-dispatch) alphas decomposes into
    a beta part and an alpha part per probe config; least-squares s >= 0 on
    `wall_i = beta_i + s * alpha_i` reprices the per-step overhead at what
    the single-dispatch loop actually pays.
    """
    import numpy as np

    from repro.analysis.costmodel import Calibration, PrimitiveFit, predict_wall
    from repro.api import SolverConfig
    from repro.api.plan import plan

    zero_alpha = Calibration(
        version=calib.version + "-beta-only", device_kind=calib.device_kind,
        tables={k: {p: PrimitiveFit(0.0, f.beta_us, f.n_samples, f.spread)
                    for p, f in fits.items()}
                for k, fits in calib.tables.items()},
        collective=None)
    probes = [(96, 8), (96, 32)] if smoke else [(128, 8), (128, 32), (256, 64)]
    rng = np.random.default_rng(3)
    num = den = 0.0
    for N, v in probes:
        cfg = SolverConfig(strategy="sequential", backend="ref", v=v)
        full = predict_wall(N, cfg, v=v, calibration=calib)
        beta_only = predict_wall(N, cfg, v=v, calibration=zero_alpha)
        if full is None or beta_only is None:
            continue
        alpha_part = full["wall_us"] - beta_only["wall_us"]
        if alpha_part <= 0:
            continue
        A = rng.standard_normal((N, N)).astype(np.float32)
        wall = _measure_execute(plan(N, cfg), A)
        num += max(wall - beta_only["wall_us"], 0.0) * alpha_part
        den += alpha_part * alpha_part
    return num / den if den > 0 else 1.0


def _scale_alphas(calib, scale: float):
    from repro.analysis.costmodel import (
        Calibration, PrimitiveFit, content_version,
    )

    tables = {k: {p: PrimitiveFit(f.alpha_us * scale, f.beta_us,
                                  f.n_samples, f.spread)
                  for p, f in fits.items()}
              for k, fits in calib.tables.items()}
    tag = calib.version.rsplit("-", 1)[0]
    return Calibration(
        version=content_version(tables, calib.collective, tag=tag),
        device_kind=calib.device_kind, tables=tables,
        collective=calib.collective,
        meta={**calib.meta, "alpha_scale": scale})


_COLLECTIVE_WORKER = r"""
import os
os.environ["JAX_PLATFORMS"] = "cpu"  # host devices only, never the chip
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys, time, json
sys.path.insert(0, %(src)r)
import numpy as np
from repro.api import SolverConfig, plan, GridConfig

rng = np.random.default_rng(0)
walls = []
for Px, Py, c in %(grids)r:
    N, v = %(n)d, 8
    grid = GridConfig(Px=Px, Py=Py, c=c, v=v, N=N)
    cfg = SolverConfig(strategy="conflux", backend="ref", grid=grid)
    p = plan(N, cfg)
    A = rng.standard_normal((N, N)).astype(np.float32)
    p.execute(A)
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter(); p.execute(A)
        best = min(best, time.perf_counter() - t0)
    walls.append({"Px": Px, "Py": Py, "c": c, "N": N, "v": v,
                  "wall_us": best * 1e6})
print("COLLECTIVE_JSON:" + json.dumps(walls))
"""


def fit_collective(calib, smoke: bool, timeout: int = 900):
    """Fit the collective (us/op, us/wire-byte) pair from distributed runs.

    Measures conflux executes on the 8 pinned host devices at several
    grids, subtracts the calibrated compute prediction, and regresses the
    excess against (op count, wire bytes).  Returns a PrimitiveFit (alpha =
    per-op rendezvous latency, beta = per-byte cost) or None when the
    subprocess fails (the calibration then ships compute-only and
    distributed candidates score without a collective term).
    """
    import numpy as np

    from repro.analysis.costmodel import (
        PrimitiveFit, collective_op_count, predict_wall,
    )
    from repro.analysis.audit import executed_comm_bytes
    from repro.api import GridConfig, SolverConfig

    grids = [(2, 2, 1), (2, 2, 2), (4, 2, 1)]
    N = 64 if smoke else 128
    code = _COLLECTIVE_WORKER % {"src": _SRC, "grids": grids, "n": N}
    try:
        proc = subprocess.run([sys.executable, "-c", code],
                              capture_output=True, text=True, timeout=timeout)
    except subprocess.TimeoutExpired:
        return None
    if proc.returncode != 0:
        print(f"# collective fit subprocess failed:\n{proc.stderr[-800:]}",
              file=sys.stderr)
        return None
    rows = None
    for line in proc.stdout.splitlines():
        if line.startswith("COLLECTIVE_JSON:"):
            rows = json.loads(line[len("COLLECTIVE_JSON:"):])
    if not rows:
        return None
    X, y = [], []
    for r in rows:
        grid = GridConfig(Px=r["Px"], Py=r["Py"], c=r["c"], v=r["v"], N=r["N"])
        cfg = SolverConfig(strategy="conflux", backend="ref", grid=grid)
        compute = predict_wall(r["N"], cfg, grid=grid, calibration=calib)
        if compute is None:
            return None
        n_ops = collective_op_count("lu", r["N"], grid, "tournament")
        wire = executed_comm_bytes("lu", r["N"], grid, "tournament",
                                   "windowed", 4)["total"]
        excess = max(r["wall_us"] - compute["wall_us"], 0.0)
        X.append([n_ops, wire])
        y.append(excess)
    sol, *_ = np.linalg.lstsq(np.asarray(X), np.asarray(y), rcond=None)
    alpha, beta = max(float(sol[0]), 0.0), max(float(sol[1]), 0.0)
    if alpha == 0.0 and beta == 0.0:
        # degenerate regression: charge everything to the op latency
        ops = np.asarray([x[0] for x in X])
        alpha = float(np.asarray(y) @ ops / (ops @ ops)) if ops.any() else 0.0
    return PrimitiveFit(alpha_us=alpha, beta_us=beta, n_samples=len(rows))


def calibrate(smoke: bool = True, out_path: str | None = None,
              skip_collective: bool = False):
    """Full calibration pipeline: sweep -> fit -> alpha rescale -> collective
    fit -> save.  Returns the fitted Calibration."""
    import jax

    from repro.analysis.costmodel import fit_calibration

    device_kind = jax.devices()[0].platform
    tag = "smoke" if smoke else "full"
    t0 = time.perf_counter()
    samples = collect_samples(smoke)
    calib = fit_calibration(samples, device_kind, tag=tag,
                            meta={"sweep": _sweep_shapes(smoke)})
    print(f"# calibrate: fitted {len(calib.tables)} (backend, dtype) tables "
          f"in {time.perf_counter()-t0:.1f}s")
    scale = fit_alpha_scale(calib, smoke)
    calib = _scale_alphas(calib, scale)
    print(f"# calibrate: in-loop alpha scale {scale:.3f}")
    if not skip_collective:
        coll = fit_collective(calib, smoke)
        if coll is not None:
            calib = _scale_alphas(  # rebuild with the collective term folded in
                type(calib)(version=calib.version, device_kind=calib.device_kind,
                            tables=calib.tables, collective=coll,
                            meta=calib.meta), 1.0)
            print(f"# calibrate: collective alpha={coll.alpha_us:.1f}us/op "
                  f"beta={coll.beta_us*1e3:.3f}ns/byte over {coll.n_samples} grids")
        else:
            print("# calibrate: collective fit unavailable (compute-only table)")
    path = out_path or CALIBRATION_JSON
    calib.save(path)
    print(f"# calibrate: wrote {path} (version {calib.version})")
    return calib


def autotune_rows(calib, smoke: bool = True) -> dict:
    """The schema-v9 ``autotune`` section: auto-vs-analytic measured walls.

    Resolves ``strategy="auto"`` under `calib`, measures its pick against
    the analytic comm-argmin pick (interleaved best-of-k, same process),
    and reports predicted vs measured for both.
    """
    import numpy as np

    from repro.analysis import costmodel
    from repro.api import SolverConfig
    from repro.api.plan import plan, resolve
    from repro.api.strategies import _resolve_auto_analytic

    N = 128 if smoke else 256
    base = SolverConfig(strategy="auto")
    prev = costmodel.set_calibration(calib)
    try:
        auto_cfg = resolve(N, base)
        decision = costmodel.get_decision(auto_cfg.cache_key(N)) or {}
        import jax

        analytic_cfg = _resolve_auto_analytic(N, base, n_dev=len(jax.devices()))
        plans = {
            "auto": plan(N, auto_cfg),
            "analytic": plan(N, analytic_cfg),
        }
        rng = np.random.default_rng(11)
        A = rng.standard_normal((N, N)).astype(np.float32)
        for p in plans.values():
            p.execute(A)  # warm compile
        walls = {k: [] for k in plans}
        for _ in range(7):  # interleaved: load spikes land on both picks
            for k, p in plans.items():
                t0 = time.perf_counter()
                p.execute(A)
                walls[k].append(time.perf_counter() - t0)
        meas = {k: min(ts) * 1e6 for k, ts in walls.items()}
        rows = []
        for pick, cfg in (("auto", auto_cfg), ("analytic", analytic_cfg)):
            pred = costmodel.predict_wall(
                N, cfg, grid=cfg.grid, v=cfg.v, backend=cfg.backend,
                hotloop=cfg.hotloop, calibration=calib)
            pred_us = pred["wall_us"] if pred else None
            rows.append({
                "pick": pick, "strategy": cfg.strategy, "backend": cfg.backend,
                "hotloop": cfg.hotloop, "v": cfg.v, "grid": str(cfg.grid),
                "N": N, "predicted_wall_us": pred_us,
                "measured_wall_us": meas[pick],
                "wall_residual": ((meas[pick] - pred_us) / pred_us
                                  if pred_us else None),
            })
        ratio = meas["auto"] / max(meas["analytic"], 1e-9)
        for r in rows:
            resid = r["wall_residual"]
            print(f"# autotune {r['pick']}: {r['strategy']}/{r['backend']} "
                  f"v={r['v']} -> measured {r['measured_wall_us']:.0f}us"
                  + (f" (predicted {r['predicted_wall_us']:.0f}us, "
                     f"residual {resid:+.0%})" if resid is not None else ""))
        print(f"# autotune: auto/analytic wall ratio {ratio:.2f} "
              f"(floor {1 + AUTOTUNE_TOLERANCE:.2f})")
        return {
            "rows": rows,
            "auto_over_analytic": ratio,
            "tolerance": AUTOTUNE_TOLERANCE,
            "calibration_version": calib.version,
            "n_candidates": decision.get("n_candidates"),
        }
    finally:
        costmodel.set_calibration(prev)


def main(smoke: bool = True, write_default: bool = False) -> dict:
    calib = calibrate(smoke=smoke)
    if write_default:
        calib.save(DEFAULT_TABLE)
        print(f"# wrote hermetic default table {DEFAULT_TABLE}")
    section = autotune_rows(calib, smoke=smoke)
    return {"autotune": section}


if __name__ == "__main__":
    main(smoke="--smoke" in sys.argv,
         write_default="--write-default" in sys.argv)

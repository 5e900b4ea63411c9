"""Benchmark harness entry: one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--skip-measured] [--smoke]
    PYTHONPATH=src python -m benchmarks.run --validate
    PYTHONPATH=src python -m benchmarks.run --calibrate [--smoke]

``--calibrate`` is the standalone cost-model refit: it re-runs the
``benchmarks.autotune`` pipeline (primitive sweep -> affine fits -> in-loop
alpha rescale -> collective alpha-beta fit), writes ``calibration.json`` at
the repo root, and merges the fresh ``autotune`` section into the existing
bench artifact without re-running the other sections.

Prints ``name,us_per_call,derived``-style CSV blocks per section and writes
a machine-readable ``BENCH_lu.json`` next to the repo root (per-strategy
*and per-kernel-backend* wall time, instrumented comm volume, model
prediction, plan-cache hit/miss + trace counts) so successive PRs accumulate
a perf trajectory.

``--smoke`` runs the CI-sized subset (model tables + a small-N executed
sweep over both kernel backends) and writes the full-schema JSON to
``BENCH_lu.smoke.json`` — a separate path so a local smoke run never
clobbers the tracked full-run trajectory file.  It then gates on perf: the
freshly measured hotloop windowed/flat wall-time ratios are compared
against the *committed* smoke baseline's and the run fails when any row
regresses past ``SMOKE_GATE_TOLERANCE`` (2x; ratios rather than absolute
times so the shared CI container's load swings cancel — the in-run flat
body is the control).  The gate also covers the ``batched`` rows
(batched-vs-Python-loop throughput per backend), the schema-v7
``mixed_precision`` rows (the refined-low-precision vs f64-direct
end-to-end wall ratio), and the ``serving`` section (async-vs-sync serving
throughput and batch-fill from ``benchmarks.serve_load``): the serving /
batched ratios regress when they *drop* past tolerance.  ``--validate``
checks the full-run JSON (``--validate --smoke`` the smoke one) against
schema v9 — requiring the ``audit`` section (static comm-conformance rows
from ``repro.analysis.audit``: HLO-extracted vs model-predicted vs
X-partitioning-lower-bound bytes per strategy x backend, zero
error-severity findings, every row within the stated tolerance) and the
``autotune`` section (``benchmarks.autotune``: the calibrated auto pick's
measured wall vs the analytic comm-argmin pick's, floored at
auto/analytic <= 1 + AUTOTUNE_TOLERANCE with a finite predicted-vs-measured
residual on the auto row) — and
including the acceptance floors that the ref B=128, N=32
batched execute beats a Python loop of single executes by >= 3x, that the
async serving tier beats the per-request sync baseline by >= 2x at
saturating load, that refined mixed-precision solves converge to within
10x of the f64 direct residual, and (full runs) that the f32 factor +
refine pipeline beats the f64 direct factor + solve on wall time and the
serving section carries Poisson open-loop rows — and exits non-zero on
violations; CI runs smoke (with the gates) + validate and uploads the
artifact.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
BENCH_JSON = os.path.join(_ROOT, "BENCH_lu.json")
BENCH_SMOKE_JSON = os.path.join(_ROOT, "BENCH_lu.smoke.json")

from benchmarks.autotune import AUTOTUNE_TOLERANCE
from benchmarks.serve_load import SERVING_MIN_SPEEDUP

SCHEMA = "BENCH_lu.v9"
_MEASURED_KEYS = {
    "strategy", "backend", "N", "grid", "wall_us_per_call", "reconstruction_err",
    "solve_err", "comm_per_proc_elements", "comm_per_proc_bytes",
    "compute_dtype", "model_per_proc_elements",
    "trace_count", "plan_cache_hits",
}
_DELTA_KEYS = {"strategy", "N", "ref_us", "pallas_us", "pallas_over_ref"}
_CHOL_KEYS = {"N", "grid", "lu_per_proc_elements", "chol_per_proc_elements",
              "lu_over_chol"}
_HOTLOOP_KEYS = {"strategy", "backend", "N", "grid", "windowed_us", "flat_us",
                 "windowed_over_flat", "primitives"}
_PRIMITIVE_KEYS = {"panel_us", "trsm_us", "schur_us", "gather_us"}
_BATCHED_KEYS = {"B", "N", "backend", "dtype", "batched_us", "loop_us",
                 "loop_over_batched"}
# The batched ref row must beat a Python loop of single-system executes by at
# least this factor (acceptance floor at B=128, N=32, f32).
BATCHED_MIN_SPEEDUP = 3.0
_SERVING_ROW_KEYS = {"engine", "tenants", "requests", "wall_s",
                     "throughput_rps", "p50_ms", "p95_ms", "p99_ms",
                     "batch_fill", "shed_rate", "spill_rate"}
_OPEN_LOOP_ROW_KEYS = {"engine", "arrival_rate_rps", "offered_rps",
                       "achieved_rps", "p50_ms", "p95_ms", "p99_ms"}
_CACHE_KEYS = {"hits", "misses", "evictions", "size", "capacity"}
_MIXED_KEYS = {"config", "N", "v", "dtype", "compute_dtype", "backend",
               "wall_us", "residual", "refinement_iters", "converged",
               "refined_over_direct"}
_MIXED_CONFIGS = {"f64_ref_direct", "f32_refined", "bf16_refined"}
# Schema v8: the static audit's comm-conformance rows (repro.analysis.audit)
# — HLO-extracted vs model-predicted vs X-partitioning-lower-bound bytes per
# strategy x backend, plus the audit's own finding counts.
_AUDIT_ROW_KEYS = {"strategy", "backend", "hotloop", "pivot", "compute_dtype",
                   "N", "grid", "extracted_bytes", "predicted_bytes",
                   "schedule_bytes", "lower_bound_bytes"}
_AUDIT_STRATEGIES = ("conflux", "baseline2d", "cholesky25d")
# Schema v9: the calibrated-autotuner demonstration rows (benchmarks.autotune)
# — the measured wall of auto's calibrated pick vs the analytic comm-argmin
# pick, with predicted-vs-measured residuals for both.
_AUTOTUNE_ROW_KEYS = {"pick", "strategy", "backend", "hotloop", "v", "grid",
                      "N", "predicted_wall_us", "measured_wall_us",
                      "wall_residual"}
# Full-run acceptance floors for the mixed_precision section: the refined
# low-precision pipelines must land within this factor of the f64 direct
# solve's residual (working-precision quality recovered by refinement) ...
MIXED_MAX_RESIDUAL_BLOWUP = 10.0
# ... and the f32 factor + refine end-to-end wall time must actually beat
# the f64 direct factor + solve (the whole point of computing in the
# MXU-native dtype).  bf16 carries the same residual floor but no wall
# floor: XLA:CPU emulates bf16 arithmetic, so its wall time on this
# container says nothing about MXU behavior.
MIXED_WALL_FLOOR_CONFIGS = {"f32_refined"}

# Perf-regression gate: a freshly measured windowed/flat hotloop ratio may
# exceed the committed baseline's by at most this factor.  The gate compares
# *ratios*, not absolute wall times: windowed and flat run back-to-back in
# the same process, so the shared CI container's 5-10x run-to-run load swings
# cancel, and what remains is exactly what the gate protects — the windowed
# step body regressing relative to the frozen flat oracle.  2x is generous;
# it fires on step-function regressions, not jitter.
SMOKE_GATE_TOLERANCE = 2.0


def _section(title):
    print(f"\n### {title}")


def _audit_section(timeout: int = 900) -> dict:
    """`bench_audit_rows()` in a subprocess: the distributed combos need the
    8 host devices pinned before jax initializes (same pattern as
    lu_measured's worker).  The child stays on the CPU, so it never
    contends for an accelerator with its parent."""
    src = os.path.abspath(os.path.join(_ROOT, "src"))
    code = (
        "import os, sys, json\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'\n"
        f"sys.path.insert(0, {src!r})\n"
        "from repro.analysis.audit import bench_audit_rows\n"
        "print('AUDIT_JSON:' + json.dumps(bench_audit_rows()))\n"
    )
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=timeout)
    if proc.returncode != 0:
        raise RuntimeError(f"audit subprocess failed:\n{proc.stderr[-2000:]}")
    for line in proc.stdout.splitlines():
        if line.startswith("AUDIT_JSON:"):
            return json.loads(line[len("AUDIT_JSON:"):])
    raise RuntimeError("audit subprocess produced no AUDIT_JSON line")


def validate_bench(path: str = BENCH_JSON, mode: str = "full") -> list[str]:
    """Schema check for a BENCH_lu json; returns a list of violations."""
    errors: list[str] = []
    if not os.path.exists(path):
        return [f"{path} does not exist"]
    with open(path) as f:
        bench = json.load(f)
    if bench.get("schema") != SCHEMA:
        errors.append(f"schema is {bench.get('schema')!r}, expected {SCHEMA!r}")
    if bench.get("mode") != mode:
        errors.append(f"mode is {bench.get('mode')!r}, expected {mode!r} for {path}")
    if "table2" not in bench:
        errors.append("missing section: table2")
    measured = bench.get("measured")
    if not isinstance(measured, list) or not measured:
        errors.append("measured must be a non-empty list of records")
        measured = []
    for i, rec in enumerate(measured):
        missing = _MEASURED_KEYS - set(rec)
        if missing:
            errors.append(f"measured[{i}] missing keys: {sorted(missing)}")
    backends = {r.get("backend") for r in measured}
    if measured and not {"ref", "pallas"} <= backends:
        errors.append(f"measured must cover both kernel backends, saw {sorted(map(str, backends))}")
    chol_backends = {r.get("backend") for r in measured
                     if r.get("strategy") == "cholesky25d"}
    if measured and not {"ref", "pallas"} <= chol_backends:
        errors.append(
            f"measured must carry cholesky25d rows on both kernel backends, "
            f"saw {sorted(map(str, chol_backends))}"
        )
    for i, d in enumerate(bench.get("backend_delta", [])):
        missing = _DELTA_KEYS - set(d)
        if missing:
            errors.append(f"backend_delta[{i}] missing keys: {sorted(missing)}")
    if measured and not bench.get("backend_delta"):
        errors.append("missing section: backend_delta (ref-vs-pallas wall-time rows)")
    chol_vs_lu = bench.get("chol_vs_lu")
    if measured and not chol_vs_lu:
        errors.append("missing section: chol_vs_lu (conflux-vs-cholesky comm rows)")
    for i, d in enumerate(chol_vs_lu or []):
        missing = _CHOL_KEYS - set(d)
        if missing:
            errors.append(f"chol_vs_lu[{i}] missing keys: {sorted(missing)}")
        elif not d["lu_over_chol"] > 1.0:
            errors.append(
                f"chol_vs_lu[{i}]: expected the symmetric schedule to move "
                f"fewer elements than LU, got ratio {d['lu_over_chol']}"
            )
    hotloop = bench.get("hotloop")
    if measured and not hotloop:
        errors.append("missing section: hotloop (windowed-vs-flat wall-time rows)")
    for i, d in enumerate(hotloop or []):
        missing = _HOTLOOP_KEYS - set(d)
        if missing:
            errors.append(f"hotloop[{i}] missing keys: {sorted(missing)}")
            continue
        pmissing = _PRIMITIVE_KEYS - set(d["primitives"])
        if pmissing:
            errors.append(f"hotloop[{i}] primitives missing: {sorted(pmissing)}")
        if mode == "full" and d["backend"] == "ref" and not d["windowed_over_flat"] < 1.0:
            errors.append(
                f"hotloop[{i}] ({d['strategy']}/ref): windowed step body must "
                f"beat the flat baseline, got ratio {d['windowed_over_flat']:.2f}"
            )
    if hotloop:
        combos = {(d.get("strategy"), d.get("backend")) for d in hotloop}
        want = {(s, b) for s in ("conflux", "cholesky25d") for b in ("ref", "pallas")}
        if not want <= combos:
            errors.append(
                f"hotloop must cover conflux+cholesky25d on both backends, "
                f"missing {sorted(want - combos)}"
            )
    batched = bench.get("batched")
    if measured and not batched:
        errors.append("missing section: batched (batched-vs-loop throughput rows)")
    seen_ref_accept = False
    for i, d in enumerate(batched or []):
        missing = _BATCHED_KEYS - set(d)
        if missing:
            errors.append(f"batched[{i}] missing keys: {sorted(missing)}")
            continue
        if d["backend"] == "ref" and d["B"] == 128 and d["N"] == 32:
            seen_ref_accept = True
            if not d["loop_over_batched"] >= BATCHED_MIN_SPEEDUP:
                errors.append(
                    f"batched[{i}] (ref B=128 N=32): batched execute must beat "
                    f"the Python loop by >= {BATCHED_MIN_SPEEDUP:.1f}x, got "
                    f"{d['loop_over_batched']:.2f}x"
                )
    if batched:
        b_backends = {d.get("backend") for d in batched}
        if not {"ref", "pallas"} <= b_backends:
            errors.append(
                f"batched must cover both kernel backends, saw "
                f"{sorted(map(str, b_backends))}"
            )
        if not seen_ref_accept:
            errors.append("batched must carry the ref B=128 N=32 acceptance row")
    mixed = bench.get("mixed_precision")
    if measured and not mixed:
        errors.append("missing section: mixed_precision (f64-direct vs "
                      "refined low-precision solve rows)")
    direct = next((d for d in mixed or []
                   if d.get("config") == "f64_ref_direct"), None)
    for i, d in enumerate(mixed or []):
        missing = _MIXED_KEYS - set(d)
        if missing:
            errors.append(f"mixed_precision[{i}] missing keys: {sorted(missing)}")
            continue
        if d["config"] == "f64_ref_direct":
            continue
        if not d["converged"]:
            errors.append(
                f"mixed_precision[{i}] ({d['config']}): refinement did not "
                f"converge (residual {d['residual']:.2e} after "
                f"{d['refinement_iters']} iters)"
            )
        if direct and not (
                d["residual"] <= direct["residual"] * MIXED_MAX_RESIDUAL_BLOWUP):
            errors.append(
                f"mixed_precision[{i}] ({d['config']}): refined residual "
                f"{d['residual']:.2e} exceeds the f64 direct baseline "
                f"{direct['residual']:.2e} by more than "
                f"{MIXED_MAX_RESIDUAL_BLOWUP:.0f}x"
            )
        if (mode == "full" and d["config"] in MIXED_WALL_FLOOR_CONFIGS
                and not d["refined_over_direct"] < 1.0):
            errors.append(
                f"mixed_precision[{i}] ({d['config']}): factor+refine must "
                f"beat the f64 direct factor+solve on wall time, got "
                f"{d['refined_over_direct']:.2f}x"
            )
    if mixed:
        configs = {d.get("config") for d in mixed}
        if not _MIXED_CONFIGS <= configs:
            errors.append(
                f"mixed_precision must carry {sorted(_MIXED_CONFIGS)}, "
                f"saw {sorted(map(str, configs))}"
            )
    serving = bench.get("serving")
    if measured and serving is None:
        errors.append("missing section: serving (sync-vs-async load rows "
                      "from benchmarks.serve_load)")
    elif serving is not None:
        errors.extend(validate_serving(serving, mode=mode))
    audit = bench.get("audit")
    if measured and not audit:
        errors.append("missing section: audit (static comm-conformance rows "
                      "from repro.analysis.audit)")
    elif audit is not None:
        errors.extend(validate_audit(audit))
    autotune = bench.get("autotune")
    if measured and not autotune:
        errors.append("missing section: autotune (calibrated-vs-analytic "
                      "pick rows from benchmarks.autotune)")
    elif autotune is not None:
        errors.extend(validate_autotune(autotune))
    cache = bench.get("plan_cache")
    if not isinstance(cache, dict) or not _CACHE_KEYS <= set(cache):
        errors.append(f"plan_cache must carry {sorted(_CACHE_KEYS)}, got {cache}")
    return errors


def validate_audit(audit) -> list[str]:
    """Schema check for the v8 `audit` section: distributed rows must cover
    every strategy x backend, carry the predicted/extracted/lower-bound byte
    triple, conform to the stated tolerance, and the audit itself must have
    run error-free."""
    errors: list[str] = []
    if not isinstance(audit, dict):
        return [f"audit must be a dict section, got {type(audit).__name__}"]
    rows = audit.get("rows")
    if not isinstance(rows, list) or not rows:
        return ["audit.rows must be a non-empty list of records"]
    tolerance = audit.get("tolerance")
    if not isinstance(tolerance, (int, float)):
        errors.append(f"audit.tolerance must be a number, got {tolerance!r}")
    combos = set()
    for i, r in enumerate(rows):
        missing = _AUDIT_ROW_KEYS - set(r)
        if missing:
            errors.append(f"audit.rows[{i}] missing keys: {sorted(missing)}")
            continue
        if not r["grid"]:
            continue  # in-core rows: collective-free by construction
        combos.add((r["strategy"], r["backend"]))
        if not r["lower_bound_bytes"] > 0:
            errors.append(
                f"audit.rows[{i}] ({r['strategy']}/{r['backend']}): "
                f"lower_bound_bytes must be positive, got {r['lower_bound_bytes']}")
        if isinstance(tolerance, (int, float)) and not (
                r.get("rel_err", 0.0) <= tolerance):
            errors.append(
                f"audit.rows[{i}] ({r['strategy']}/{r['backend']}): extracted "
                f"{r['extracted_bytes']} vs predicted {r['predicted_bytes']} "
                f"bytes (rel_err {r.get('rel_err')} > tolerance {tolerance})")
    want = {(s, b) for s in _AUDIT_STRATEGIES for b in ("ref", "pallas")}
    if not want <= combos:
        errors.append(
            f"audit.rows must cover {sorted(_AUDIT_STRATEGIES)} on both "
            f"kernel backends, missing {sorted(want - combos)}")
    if audit.get("errors"):
        errors.append(
            f"audit section reports {audit['errors']} error-severity "
            f"finding(s); the static audit must pass clean")
    return errors


def validate_autotune(autotune) -> list[str]:
    """Schema check for the v9 `autotune` section: both the calibrated
    ("auto") and analytic picks must be present with measured walls, the
    auto pick must carry a prediction and a finite residual (the feedback
    loop the calibrated path exists for), and auto's measured wall must sit
    within AUTOTUNE_TOLERANCE of the analytic pick's — the acceptance
    criterion that fitted constants rank at least as well as element counts.
    """
    import math

    errors: list[str] = []
    if not isinstance(autotune, dict):
        return [f"autotune must be a dict section, got {type(autotune).__name__}"]
    rows = autotune.get("rows")
    if not isinstance(rows, list) or not rows:
        return ["autotune.rows must be a non-empty list of records"]
    picks = {}
    for i, r in enumerate(rows):
        missing = _AUTOTUNE_ROW_KEYS - set(r)
        if missing:
            errors.append(f"autotune.rows[{i}] missing keys: {sorted(missing)}")
            continue
        picks[r["pick"]] = r
        if not (isinstance(r["measured_wall_us"], (int, float))
                and r["measured_wall_us"] > 0):
            errors.append(f"autotune.rows[{i}] ({r['pick']}): measured_wall_us "
                          f"must be positive, got {r['measured_wall_us']!r}")
    if not {"auto", "analytic"} <= set(picks):
        errors.append(f"autotune.rows must carry both the 'auto' and "
                      f"'analytic' picks, saw {sorted(picks)}")
    auto = picks.get("auto")
    if auto is not None:
        pred, resid = auto.get("predicted_wall_us"), auto.get("wall_residual")
        if not (isinstance(pred, (int, float)) and pred > 0):
            errors.append(f"autotune auto pick must carry a positive "
                          f"predicted_wall_us, got {pred!r}")
        if not (isinstance(resid, (int, float)) and math.isfinite(resid)):
            errors.append(f"autotune auto pick must carry a finite "
                          f"wall_residual, got {resid!r}")
    if not isinstance(autotune.get("calibration_version"), str):
        errors.append(f"autotune.calibration_version must be a string, got "
                      f"{autotune.get('calibration_version')!r}")
    tol = autotune.get("tolerance")
    if not isinstance(tol, (int, float)):
        errors.append(f"autotune.tolerance must be a number, got {tol!r}")
        tol = AUTOTUNE_TOLERANCE
    ratio = autotune.get("auto_over_analytic")
    if not isinstance(ratio, (int, float)):
        errors.append(f"autotune.auto_over_analytic must be a number, "
                      f"got {ratio!r}")
    elif not ratio <= 1.0 + tol:
        errors.append(
            f"autotune: the calibrated auto pick's measured wall must be "
            f"within {tol:.0%} of the analytic pick's, got ratio {ratio:.2f} "
            f"(> {1 + tol:.2f})"
        )
    return errors


def validate_serving(serving, mode: str = "full") -> list[str]:
    """Schema check for the v7 `serving` section (shared with serve_load)."""
    errors: list[str] = []
    if not isinstance(serving, dict):
        return [f"serving must be a dict section, got {type(serving).__name__}"]
    rows = serving.get("rows")
    if not isinstance(rows, list) or not rows:
        return ["serving.rows must be a non-empty list of records"]
    engines = set()
    for i, row in enumerate(rows):
        missing = _SERVING_ROW_KEYS - set(row)
        if missing:
            errors.append(f"serving.rows[{i}] missing keys: {sorted(missing)}")
        engines.add(row.get("engine"))
    if not {"sync", "async"} <= engines:
        errors.append(f"serving.rows must cover both the 'sync' and 'async' "
                      f"disciplines, saw {sorted(map(str, engines))}")
    ratio = serving.get("async_over_sync")
    if not isinstance(ratio, (int, float)):
        errors.append(f"serving.async_over_sync must be a number, got {ratio!r}")
    elif mode == "full" and not ratio >= SERVING_MIN_SPEEDUP:
        errors.append(
            f"serving: deadline-batched async throughput must beat the "
            f"per-request sync baseline by >= {SERVING_MIN_SPEEDUP:.1f}x at "
            f"saturating load, got {ratio:.2f}x"
        )
    for i, row in enumerate(rows):
        if row.get("engine") == "async" and isinstance(row.get("batch_fill"), float):
            if not 0.0 < row["batch_fill"] <= 1.0:
                errors.append(
                    f"serving.rows[{i}]: async batch_fill must be in (0, 1], "
                    f"got {row['batch_fill']}"
                )
    open_loop = serving.get("open_loop")
    if mode == "full" and open_loop is None:
        errors.append("serving.open_loop missing: full runs must carry the "
                      "Poisson open-loop rows (serve_load --arrival-rate)")
    if open_loop is not None:
        orows = open_loop.get("rows")
        if not isinstance(orows, list) or not orows:
            errors.append("serving.open_loop.rows must be a non-empty list")
        else:
            oengines = set()
            for i, row in enumerate(orows):
                missing = _OPEN_LOOP_ROW_KEYS - set(row)
                if missing:
                    errors.append(
                        f"serving.open_loop.rows[{i}] missing keys: "
                        f"{sorted(missing)}")
                oengines.add(row.get("engine"))
            if not {"sync", "async"} <= oengines:
                errors.append(
                    f"serving.open_loop.rows must cover both disciplines, "
                    f"saw {sorted(map(str, oengines))}")
    return errors


def serving_gate(bench: dict, baseline: dict | None,
                 tol: float = SMOKE_GATE_TOLERANCE) -> tuple[list[str], int]:
    """Gate the fresh serving section against the committed baseline's.

    Two ratios, both of two same-process measurements (load swings cancel):
    async/sync throughput must not *drop* below baseline/tol, and the async
    batch-fill ratio must not drop below baseline/tol (a fill collapse means
    the deadline trigger is firing on near-empty batches — the batching win
    is gone even if throughput noise hides it).  No baseline serving rows ->
    gates nothing; callers report compared == 0 as "gate did not run".
    """
    fresh = bench.get("serving") or {}
    base = (baseline or {}).get("serving") or {}
    regressions, compared = [], 0
    fr, br = fresh.get("async_over_sync"), base.get("async_over_sync")
    if isinstance(fr, (int, float)) and isinstance(br, (int, float)):
        compared += 1
        if fr < br / tol:
            regressions.append(
                f"serving: async/sync throughput ratio {fr:.2f} vs baseline "
                f"{br:.2f} (< 1/{tol:.1f}x tolerance)"
            )
    def _async_fill(section):
        for row in section.get("rows", []):
            if isinstance(row, dict) and row.get("engine") == "async":
                fill = row.get("batch_fill")
                if isinstance(fill, (int, float)) and fill > 0:
                    return fill
        return None
    ff, bf = _async_fill(fresh), _async_fill(base)
    if ff is not None and bf is not None:
        compared += 1
        if ff < bf / tol:
            regressions.append(
                f"serving: async batch-fill {ff:.2f} vs baseline {bf:.2f} "
                f"(< 1/{tol:.1f}x tolerance)"
            )
    return regressions, compared


def smoke_gate(bench: dict, baseline: dict | None,
               tol: float = SMOKE_GATE_TOLERANCE) -> tuple[list[str], int]:
    """Compare freshly measured hotloop rows against the committed smoke
    baseline; returns (regression messages, rows compared).

    Keyed by (strategy, backend), comparing the windowed/flat wall-time
    *ratio* (see SMOKE_GATE_TOLERANCE for why ratios: the in-run flat body
    is the load-invariant control).  Batched rows gate the same way, keyed
    by (backend, B, N) on the loop/batched throughput ratio — here a
    regression is the ratio *dropping* below baseline/tol, i.e. the batched
    execute losing its edge over the in-run Python loop (again a ratio of
    two same-process timings, so load swings cancel).  A baseline without
    comparable rows (older schema) or a missing row gates nothing — callers
    must report a compared-count of 0 as "gate did not run", never as a pass.
    """
    base = {(d["strategy"], d["backend"]): d
            for d in (baseline or {}).get("hotloop", [])
            if isinstance(d, dict) and _HOTLOOP_KEYS <= set(d)}
    regressions, compared = [], 0
    for d in bench.get("hotloop", []):
        ref = base.get((d["strategy"], d["backend"]))
        if ref is None or ref.get("N") != d.get("N"):
            continue
        compared += 1
        if d["windowed_over_flat"] > tol * ref["windowed_over_flat"]:
            regressions.append(
                f"{d['strategy']}/{d['backend']} N={d['N']}: windowed/flat "
                f"ratio {d['windowed_over_flat']:.2f} vs baseline "
                f"{ref['windowed_over_flat']:.2f} (> {tol:.1f}x tolerance)"
            )
    bbase = {(d["backend"], d["B"], d["N"]): d
             for d in (baseline or {}).get("batched", [])
             if isinstance(d, dict) and _BATCHED_KEYS <= set(d)}
    for d in bench.get("batched", []):
        if not _BATCHED_KEYS <= set(d):
            continue
        ref = bbase.get((d["backend"], d["B"], d["N"]))
        if ref is None:
            continue
        compared += 1
        if d["loop_over_batched"] < ref["loop_over_batched"] / tol:
            regressions.append(
                f"batched {d['backend']} B={d['B']} N={d['N']}: loop/batched "
                f"ratio {d['loop_over_batched']:.2f} vs baseline "
                f"{ref['loop_over_batched']:.2f} (< 1/{tol:.1f}x tolerance)"
            )
    mbase = {d["config"]: d for d in (baseline or {}).get("mixed_precision", [])
             if isinstance(d, dict) and _MIXED_KEYS <= set(d)}
    for d in bench.get("mixed_precision", []):
        if not _MIXED_KEYS <= set(d) or d["config"] == "f64_ref_direct":
            continue
        ref = mbase.get(d["config"])
        if ref is None or ref.get("N") != d.get("N"):
            continue
        compared += 1
        # refined/direct is again a ratio of two same-process timings, so the
        # container's load swings cancel; a blow-up means the refine loop or
        # the low-precision factorization itself regressed.
        if d["refined_over_direct"] > tol * ref["refined_over_direct"]:
            regressions.append(
                f"mixed_precision {d['config']} N={d['N']}: refined/direct "
                f"ratio {d['refined_over_direct']:.2f} vs baseline "
                f"{ref['refined_over_direct']:.2f} (> {tol:.1f}x tolerance)"
            )
    # auto/analytic is once more a ratio of two interleaved same-process
    # walls; it rising past tol x baseline means the calibrated pick lost
    # ground to the analytic one — stale or mis-fitted constants.
    afresh = (bench.get("autotune") or {}).get("auto_over_analytic")
    abase = ((baseline or {}).get("autotune") or {}).get("auto_over_analytic")
    if isinstance(afresh, (int, float)) and isinstance(abase, (int, float)):
        compared += 1
        if afresh > tol * abase:
            regressions.append(
                f"autotune: auto/analytic wall ratio {afresh:.2f} vs baseline "
                f"{abase:.2f} (> {tol:.1f}x tolerance)"
            )
    sregs, scompared = serving_gate(bench, baseline, tol)
    return regressions + sregs, compared + scompared


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache(_ROOT)
    smoke = "--smoke" in sys.argv
    if "--validate" in sys.argv:
        path = BENCH_SMOKE_JSON if smoke else BENCH_JSON
        errors = validate_bench(path, mode="smoke" if smoke else "full")
        for e in errors:
            print(f"SCHEMA-ERROR: {e}")
        if errors:
            sys.exit(1)
        print(f"# {path} conforms to {SCHEMA}")
        return

    if "--calibrate" in sys.argv:
        # Standalone calibrate mode: refit calibration.json from fresh traces
        # and merge the resulting autotune section into the existing bench
        # artifact (CI runs this in bench-smoke and uploads calibration.json).
        from benchmarks import autotune

        section = autotune.main(smoke=smoke)["autotune"]
        path = BENCH_SMOKE_JSON if smoke else BENCH_JSON
        if os.path.exists(path):
            with open(path) as f:
                bench = json.load(f)
            bench["autotune"] = section
            with open(path, "w") as f:
                json.dump(bench, f, indent=1, default=str)
            print(f"# merged autotune section into {path}")
        return

    skip_measured = "--skip-measured" in sys.argv
    bench: dict = {"schema": SCHEMA, "mode": "smoke" if smoke else "full"}

    # Load the committed smoke baseline *before* overwriting it: the perf
    # gate below compares this run's hotloop rows against it.
    baseline = None
    if smoke and os.path.exists(BENCH_SMOKE_JSON):
        with open(BENCH_SMOKE_JSON) as f:
            baseline = json.load(f)

    _section("Table 2: communication volume models vs paper (GB)")
    t0 = time.perf_counter()
    from benchmarks import table2

    bench["table2"] = table2.main()
    print(f"# table2 done in {time.perf_counter()-t0:.1f}s")

    if not smoke:
        _section("Fig 6a/6b/7: scaling + exascale extrapolation")
        from benchmarks import scaling

        bench["scaling"] = scaling.main()

        _section("Section 6: I/O lower bounds (solver vs closed form)")
        from benchmarks import lower_bounds

        lower_bounds.main()

    if not skip_measured:
        title = "smoke (N=64)" if smoke else "8 host devices"
        _section(f"Executed distributed LU + Cholesky via plan/execute, "
                 f"ref + pallas backends ({title})")
        from benchmarks import lu_measured

        measured = lu_measured.main(smoke=smoke)
        if measured:
            bench.update(measured)

        _section("Serving load: per-request sync vs async deadline batching")
        from benchmarks import serve_load

        bench.update(serve_load.main(smoke=smoke))

        # Static comm-conformance (schema v8): lowers every registered combo
        # without executing and compares HLO-extracted collective bytes with
        # the executed-schedule model + X-partitioning lower bound.
        _section("Static audit: comm-conformance of lowered HLO (v8)")
        t0 = time.perf_counter()
        bench["audit"] = _audit_section()
        print(f"# audit: {len(bench['audit']['rows'])} rows, "
              f"{bench['audit']['errors']} error(s) in "
              f"{time.perf_counter()-t0:.1f}s")

        # Calibrated autotuner demonstration (schema v9): fit the cost model
        # from fresh traces, then race auto's calibrated pick against the
        # analytic comm-argmin pick, interleaved in one process.
        _section("Autotune: calibrated auto pick vs analytic pick (v9)")
        from benchmarks import autotune

        bench["autotune"] = autotune.main(smoke=smoke)["autotune"]

    if not smoke:
        _section("Roofline table (from dry-run results, single pod)")
        from benchmarks import roofline_table

        roofline_table.main()

    out_path = BENCH_SMOKE_JSON if smoke else BENCH_JSON
    with open(out_path, "w") as f:
        json.dump(bench, f, indent=1, default=str)
    print(f"\n# wrote {out_path}")

    if smoke:
        regressions, compared = smoke_gate(bench, baseline)
        for r in regressions:
            print(f"PERF-REGRESSION: {r}")
        if regressions:
            sys.exit(1)
        if compared:
            print(f"# perf gate: {compared} hotloop/batched/serving ratios "
                  f"within {SMOKE_GATE_TOLERANCE:.1f}x of the committed baseline")
        else:
            print("# perf gate: SKIPPED — no committed baseline hotloop rows "
                  "to compare against (commit BENCH_lu.smoke.json to arm it)")


if __name__ == "__main__":
    main()

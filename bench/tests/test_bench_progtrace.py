"""CPU tests of the readers of the solver's own spans and scopes
(`bench/progtrace.py` and the metrics built on it), on a small trace
recorded on a TPU v5e, and on the older trace that has none of them.

The trace `lu1024_serve64_spans.xplane.pb` was recorded by this file on one
v5e chip (`python bench/tests/test_bench_progtrace.py --record <out>`): one
N=1024 pallas solution inside bench.solution/execute/solve spans, then 40
requests to an AsyncSolveEngine(N=64) inside a bench.window span.
"""

from __future__ import annotations

import math
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import devtrace, harness, progtrace, stats  # noqa: E402

DATA = os.path.join(ROOT, "bench", "tests", "data")
SPANS = os.path.join(DATA, "lu1024_serve64_spans.xplane.pb")
OLD = os.path.join(DATA, "lu1024_serve64.xplane.pb")
DEV = "/device:TPU:0"
N, SERVE_N, REQUESTS = 1024, 64, 40
SOLVE_METRICS = ("lu_layout_s", "lu_pivot_s", "lu_update_s", "solution_copy_s",
                 "solution_copy_gb")
SERVE_METRICS = ("serve_queue_wait_ms", "serve_flush_host_ms", "serve_transfer_ms")


def _rec(path):
    return {"trace": devtrace.load(path), "devices": [DEV], "xplane": path,
            "counters": {}, "work": {"N": N, "solutions": 1, "itemsize": 4}}


@pytest.fixture(scope="module")
def rec():
    return _rec(SPANS)


def _read(name, rec):
    return harness.load_module("metrics", name).read(rec)


def test_names_are_the_programs():
    from repro import obs

    assert progtrace.LU_SCOPES == obs.LU_SCOPES
    assert progtrace.COPY_PREFIX == obs.COPY_PREFIX
    assert (progtrace.EXECUTE, progtrace.SERVE_FLUSH) == (obs.EXECUTE, obs.SERVE_FLUSH)
    assert all(s.startswith(progtrace.SPAN_PREFIX) for s in obs.SPANS)


@pytest.mark.parametrize("name", SOLVE_METRICS + SERVE_METRICS)
def test_reader_is_finite_on_recorded_trace(rec, name):
    v = _read(name, rec)
    assert v is not None and math.isfinite(v) and v > 0, (name, v)


@pytest.mark.parametrize("name", SOLVE_METRICS + SERVE_METRICS)
def test_reader_reads_nothing_without_program_spans(name):
    assert _read(name, _rec(OLD)) is None


def test_scopes_cover_the_factor_program(rec):
    execute = rec["trace"].spans["bench.execute"]
    busy = sum(stats.covered(rec["trace"].busy(DEV), s, e) for s, e in execute)
    by = progtrace.scope_seconds(progtrace.for_record(rec), DEV, execute)
    assert set(by) - {None} == set(progtrace.LU_SCOPES)
    scoped = sum(by[s] for s in progtrace.LU_SCOPES)
    assert 0.9 * busy <= scoped <= busy * (1 + 1e-9)
    assert sum(_read(m, rec) for m in ("lu_layout_s", "lu_pivot_s", "lu_update_s")) \
        == pytest.approx(scoped - by["lu.panel"])


def test_copy_bytes_match_the_shapes(rec):
    # execute takes A to the host, but jax keeps the host copy of A from
    # the warm-up solution, so nothing moves there; A goes up, F comes down
    # and goes back up for the solve, and rows make the trip once each way
    # (b is already on the device)
    want = (3 * N * N * 4 + 2 * N * 4) / 1e9
    assert _read("solution_copy_gb", rec) == pytest.approx(want, rel=0.01)
    (s, e), = rec["trace"].spans["bench.solution"]
    assert _read("solution_copy_s", rec) < e - s


def test_flushes_answer_every_request(rec):
    pt, flushes = progtrace.flushes(rec)
    assert sum(f.args["systems"] for f in flushes) == REQUESTS
    assert sorted(f.args["flush"] for f in flushes) == list(range(len(flushes)))
    assert all(f.args["wait_s_max"] * f.args["systems"] >= f.args["wait_s_sum"] >= 0
               for f in flushes)
    per_flush = sum(f.end - f.start for f in flushes) / len(flushes) * 1e3
    assert _read("serve_transfer_ms", rec) <= _read("serve_flush_host_ms", rec) <= per_flush


def test_scope_of_takes_the_innermost():
    assert progtrace.scope_of("jit(f)/while/body/lu.panel/jit(lu_panel)/lu.layout/"
                              "transpose:") == "lu.layout"
    assert progtrace.scope_of("jit(f)/while/body/lu.update/pallas_call") == "lu.update"
    assert progtrace.scope_of("jit(_psolve)/triangular_solve") is None
    assert progtrace.scope_of("") is None


def test_unnamed_ops_take_their_readers_scope():
    ops = [(0.0, 10.0, "%while.1 = (f32[]) while(%t), body=%b"),
           (1.0, 2.0, "%copy.6 = f32[8,8]{0,1} copy(f32[8,8] %gte.1)"),
           (2.0, 3.0, "%fusion.2 = f32[8,1] fusion(f32[8,8] %copy.6), calls=%fc"),
           (3.0, 4.0, "%copy.9 = f32[8,8] copy(f32[8,8] %gte.1)"),
           (4.0, 5.0, "%dot.3 = f32[8,8] dot(f32[8,8] %copy.9, f32[8,8] %fusion.2)"),
           (5.0, 6.0, "%add.4 = f32[8,8] add(f32[8,8] %copy.9, f32[8,8] %dot.3)")]
    names = {("7", "fusion.2"): "jit(f)/lu.panel/lu.layout/transpose",
             ("7", "dot.3"): "jit(f)/lu.update/dot_general",
             ("7", "add.4"): "jit(f)/lu.pivot_writeback/add"}
    scopes = progtrace._scopes(ops, [(0.0, 10.0, "7")], names)
    # the copy read only by a layout op is a layout op; the one read by two
    # scopes stays unscoped, as does the loop
    assert scopes == [None, "lu.layout", "lu.layout", None, "lu.update",
                      "lu.pivot_writeback"]
    assert progtrace._self_seconds(ops) == [5.0, 1.0, 1.0, 1.0, 1.0, 1.0]


def record(out: str) -> None:
    """Record the spans trace on a TPU into `out`."""
    import glob
    import shutil
    import tempfile
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    from repro.api import SolverConfig, plan
    from repro.serving import AsyncSolveEngine

    assert jax.devices()[0].platform == "tpu", "records a TPU trace"
    rng = np.random.default_rng(1024)
    A = jnp.asarray(rng.uniform(-0.5, 0.5, (N, N)).astype(np.float32))
    b = jnp.asarray(rng.uniform(-0.5, 0.5, N).astype(np.float32))
    p = plan(N, SolverConfig(strategy="sequential", backend="pallas"))
    jax.block_until_ready(p.execute(A).solve(b))
    eng = AsyncSolveEngine(N=SERVE_N, strategy="sequential", backend="pallas",
                           max_batch=32, max_delay_ms=5.0)
    sizes = [int(n) for n in rng.integers(16, SERVE_N + 1, REQUESTS)]
    eng.warm_slots(sizes=sorted(set(sizes)))
    systems = [(rng.standard_normal((n, n)) + 2 * math.sqrt(n) * np.eye(n),
                rng.standard_normal(n)) for n in sizes]
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        with TraceAnnotation("bench.solution"):
            with TraceAnnotation("bench.execute"):
                fact = p.execute(A)
            with TraceAnnotation("bench.solve"):
                jax.block_until_ready(fact.solve(b))
        with TraceAnnotation("bench.window"):
            futs = []
            for Ai, bi in systems:
                futs.append(eng.submit(Ai.astype(np.float32), bi.astype(np.float32)))
                time.sleep(0.001)
            for f in futs:
                f.result(timeout=60)
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        shutil.copy(path, out)
    eng.close()


if __name__ == "__main__":
    if sys.argv[1:2] != ["--record"] or len(sys.argv) != 3:
        sys.exit(f"usage: python {sys.argv[0]} --record <out.xplane.pb>")
    record(sys.argv[2])

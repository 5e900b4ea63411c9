"""The solver's trace spans and device scopes (`repro.obs`).

A CPU profile around one small plan/execute/solve and one AsyncSolveEngine
flush must hold every `repro.*` span, nested as `repro.obs` documents and
carrying its arguments; the compiled LU programs must carry every `lu.*`
scope in their `op_name` metadata; and with no profiler running the span
helpers are plain context managers.
"""

from __future__ import annotations

import glob
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.api import SolverConfig, plan
from repro.serving import AsyncSolveEngine

N = 256
F32 = 4


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _system(n, rng):
    A = rng.standard_normal((n, n)).astype(np.float32) + n * np.eye(n, dtype=np.float32)
    return A, rng.standard_normal(n).astype(np.float32)


@pytest.fixture(scope="module")
def profile(tmp_path_factory):
    """Spans of one profiled solution and one profiled flush:
    name -> [(start_ns, end_ns, args, thread)]."""
    rng = np.random.default_rng(3)
    A, b = _system(N, rng)
    A_dev = jnp.asarray(A)
    p = plan(N, SolverConfig(strategy="sequential", v=32))
    jax.block_until_ready(p.execute(A_dev).solve(b))  # compile outside the trace
    clock = FakeClock()
    eng = AsyncSolveEngine(64, strategy="sequential", v=8, start=False,
                           clock=clock, max_batch=4)
    eng.warm_slots(sizes=[64, 20], max_batch=4)
    systems = [_system(n, rng) for n in (64, 20, 64)]
    out = tmp_path_factory.mktemp("profile")
    with jax.profiler.trace(str(out)):
        x = np.asarray(jax.block_until_ready(p.execute(A_dev).solve(b)))
        futs = []
        for i, (Ai, bi) in enumerate(systems):
            clock.t = 0.5 * i
            futs.append(eng.submit(Ai, bi))
        clock.t = 2.0
        assert eng.pump(force=True) == 3
    xs = [f.result() for f in futs]
    eng.close()
    assert np.abs(A @ x - b).max() < 1e-3 * np.abs(b).max() * N
    for (Ai, bi), xi in zip(systems, xs):
        assert np.abs(Ai @ xi - bi).max() < 1e-3 * N

    return _host_spans(out)


def _host_spans(out):
    """name -> [(start_ns, end_ns, args, thread)] of the `repro.*` spans
    in the profile written under `out`."""
    from jax.profiler import ProfileData

    path, = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    spans: dict[str, list] = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    spans.setdefault(e.name, []).append(
                        (e.start_ns, e.end_ns, dict(e.stats), line.name))
    return spans


def _inside(inner, outer):
    return (outer[0] <= inner[0] and inner[1] <= outer[1]) and inner[3] == outer[3]


def _within(spans, outer):
    return [s for s in spans if _inside(s, outer)]


def test_every_span_recorded(profile):
    # the block-cyclic layout is the distributed plans' own: see
    # test_conflux_layout_spans_each_way
    assert set(obs.SPANS) - {obs.LAYOUT} <= set(profile)
    for name, found in profile.items():
        assert name in obs.SPANS, name
        if name.startswith(obs.COPY_PREFIX):
            assert all(s[2]["bytes"] > 0 for s in found), name


def test_solution_spans_nest_with_their_bytes(profile):
    solution = [s for s in profile[obs.EXECUTE]
                if not any(_inside(s, f) for f in profile[obs.SERVE_FLUSH])]
    execute, = solution
    factor, = _within(profile[obs.FACTOR], execute)
    down = _within(profile[obs.COPY_D2H], execute)
    up = _within(profile[obs.COPY_H2D], execute)
    # A comes off the device, goes back up, and F and rows come down
    assert [s[2]["bytes"] for s in up] == [N * N * F32]
    assert sorted(s[2]["bytes"] for s in down) == [N * F32, N * N * F32, N * N * F32]
    assert all(s[1] <= factor[0] for s in up)
    assert max(s[0] for s in down) >= factor[1]
    solve, = [s for s in profile[obs.SOLVE] if s[0] >= execute[1]
              and not any(_inside(s, f) for f in profile[obs.SERVE_FLUSH])]
    # F, b and rows go up again for the solve
    assert sorted(s[2]["bytes"] for s in _within(profile[obs.COPY_H2D], solve)) == [
        N * F32, N * F32, N * N * F32]


def test_flush_span_args_and_children(profile):
    flush, = profile[obs.SERVE_FLUSH]
    args = flush[2]
    assert set(obs.FLUSH_ARGS) <= set(args)
    assert args["systems"] == 3
    # submitted at 0.0, 0.5, 1.0 on the engine's clock, drained at 2.0
    assert args["wait_s_sum"] == pytest.approx(4.5)
    assert args["wait_s_max"] == pytest.approx(2.0)
    stages = _within(profile[obs.SERVE_STAGE], flush)
    executes = _within(profile[obs.EXECUTE], flush)
    solves = _within(profile[obs.SOLVE], flush)
    assert len(stages) == len(executes) == len(solves) == 2  # slots 32 and 64
    for stage, execute in zip(sorted(stages), sorted(executes)):
        assert stage[1] <= execute[0]
    assert _within(profile[obs.FACTOR], flush)
    assert len(_within(profile[obs.COPY_D2H], flush)) == 2 * 3  # F, rows, X per slot
    assert len(_within(profile[obs.COPY_H2D], flush)) == 2 * 4  # A; F, b, rows
    complete, = _within(profile[obs.SERVE_COMPLETE], flush)
    assert complete[0] >= max(s[1] for s in solves)


def test_factor_span_carries_schur_tile_share(profile, tmp_path):
    from repro.kernels import ops
    from repro.kernels.fused_schur import schur_tile_share

    # the ref plan and the serving tier's batched plans update every column
    for s in profile[obs.FACTOR]:
        assert set(obs.FACTOR_ARGS) <= set(s[2])
        assert s[2]["schur_tile_share"] == 1.0
    # a Pallas plan whose trailing window crosses its column tiles
    n, v = 2048, 128
    p = plan(n, SolverConfig(strategy="sequential", backend="pallas", v=v))
    A = np.random.default_rng(4).uniform(-0.5, 0.5, (n, n)).astype(np.float32)
    with jax.profiler.trace(str(tmp_path)):
        p.execute(A)
    factor, = _host_spans(tmp_path)[obs.FACTOR]
    share = schur_tile_share(n, v, ops.schur_tiles(n, n, v, jnp.float32)[1])
    assert share < 1.0
    assert factor[2]["schur_tile_share"] == pytest.approx(share)


@pytest.mark.parametrize("batched", [False, True])
def test_lu_program_carries_every_scope(batched):
    from repro.core.lu.sequential import (
        lu_masked_sequential,
        lu_masked_sequential_batched,
    )

    fn = lu_masked_sequential_batched if batched else lu_masked_sequential
    shape = (2, 64, 64) if batched else (64, 64)
    hlo = fn.lower(jax.ShapeDtypeStruct(shape, jnp.float32), v=32,
                   backend="pallas").compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', hlo))
    for scope in obs.LU_SCOPES:
        assert any(f"/{scope}/" in n for n in names), scope
    # the layout ops sit inside the panel step
    layout = [n for n in names if f"/{obs.LU_LAYOUT}/" in n]
    assert all(n.index(obs.LU_PANEL) < n.index(obs.LU_LAYOUT) for n in layout)


@pytest.fixture(scope="module")
def conflux4():
    """Scopes and spans of a conflux [2x2x1] plan on four host devices."""
    here = os.path.dirname(os.path.abspath(__file__))
    proc = subprocess.run(
        [sys.executable, os.path.join(here, "multidev", "run_conflux_obs.py"), str(N)],
        capture_output=True, text=True, timeout=600,
        env={**os.environ, "PYTHONPATH": os.path.join(here, "..", "src")})
    assert proc.returncode == 0, proc.stderr[-3000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    execute, = [s for s in got["spans"] if s[0] == obs.EXECUTE]
    got["in_execute"] = [s for s in got["spans"] if execute[1] <= s[1] and s[2] <= execute[2]]
    return got


def test_conflux_program_carries_every_scope(conflux4):
    for scope in obs.LU_SCOPES + obs.CONFLUX_SCOPES:
        assert any(f"/{scope}/" in n for n in conflux4["op_names"]), scope


def test_conflux_layout_spans_each_way(conflux4):
    inside = conflux4["in_execute"]
    layout = [s for s in inside if s[0] == obs.LAYOUT]
    assert [s[3]["bytes"] for s in layout] == [N * N * F32] * 2
    factor, = [s for s in inside if s[0] == obs.FACTOR]
    # A is scattered before the factor program runs, F gathered after it
    assert layout[0][2] <= factor[1] and layout[1][1] >= factor[2]


def test_conflux_blocks_go_up_in_one_sharded_copy(conflux4):
    inside = conflux4["in_execute"]
    factor, = [s for s in inside if s[0] == obs.FACTOR]
    up = [s for s in inside if s[0] == obs.COPY_H2D]
    assert [s[3]["bytes"] for s in up] == [N * N * F32]
    assert up[0][2] <= factor[1]
    # the factor blocks and the pivot order come down
    down = [s[3]["bytes"] for s in inside if s[0] == obs.COPY_D2H]
    assert sorted(down) == [N * F32, N * N * F32]


def test_span_helpers_are_plain_context_managers():
    assert not jax.profiler.TraceAnnotation.is_enabled()
    names = obs.SPANS + obs.LU_SCOPES + obs.CONFLUX_SCOPES
    assert len(set(names)) == len(names)
    assert all(n.startswith("repro.") for n in obs.SPANS)
    assert all(n.startswith("lu.") for n in obs.LU_SCOPES + obs.CONFLUX_SCOPES)
    with obs.span(obs.SERVE_FLUSH, flush=0, systems=1) as s:
        s.set_metadata(wait_s_sum=0.0)
    with obs.scope(obs.LU_PANEL):
        assert float(jnp.ones(3).sum()) == 3.0
    host = np.arange(6, dtype=np.float32)
    dev = obs.to_device(host)
    assert isinstance(dev, jax.Array) and obs.to_device(dev) is dev
    assert obs.to_device(host, dtype=jnp.int32).dtype == jnp.int32
    back = obs.to_host(dev)
    assert isinstance(back, np.ndarray) and (back == host).all()
    assert obs.to_host(back) is back
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    put = obs.to_devices(host, one)
    assert put.sharding == one and (np.asarray(put) == host).all()

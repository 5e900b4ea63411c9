"""The `conflux_p4` cell on the CPU, and its trace readers.

The comparison: a subprocess with four host devices drives the whole cell
(set-up, window, reference check) through the harness with the chip check
skipped, at N=256 on the configuration's own grid shape and limits.  The
program passes; the lower-precision control and each planted fault of the
timed path fail; the plan ran the stated grid on four devices.

The readers: `conflux4_n2048.xplane.pb.gz` was recorded by this file on a
four-chip TPU v5e host (`python bench/tests/test_bench_conflux.py --record
<out.xplane.pb.gz>`): one N=2048 conflux solution on a [2x2x1] grid inside
bench.solution/execute/solve spans, gzipped to keep the tree small.  Every
reader the cell lists reads a value there; on the one-chip trace of
`test_bench_progtrace.py` the readers written for the cell read nothing.
"""

from __future__ import annotations

import gzip
import json
import math
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import devtrace, harness, peaks, progtrace, stats  # noqa: E402

DATA = os.path.join(ROOT, "bench", "tests", "data")
TRACE = os.path.join(DATA, "conflux4_n2048.xplane.pb.gz")
ONE_CHIP = os.path.join(DATA, "lu1024_serve64_spans.xplane.pb")
CHIPS = [f"/device:TPU:{i}" for i in range(4)]
TRACE_N, N = 2048, 256
METRICS = ("collective_s", "conflux_roofline", "conflux_layout_s", "pivot_writeback_s")
# accepted readers of the one-chip cell that the four-chip cell lists too
SHARED = ("execute_overhead_s", "solve_s", "pallas_s", "device_idle_share.solve",
          "lu_layout_s", "lu_pivot_s", "lu_update_s", "solution_copy_s", "solution_copy_gb")


def _config(n: int) -> dict:
    cfg = harness.read_json("configs", "conflux_p4")
    cfg["N"] = n
    cfg["solver"]["grid"]["N"] = n
    return cfg


def _drive(cfg) -> "harness.Context":
    ctx = harness.Context(workload="conflux_p4.test", config=cfg,
                          traffic=harness.read_json("traffic", "back_to_back"),
                          seed=2**31 + 15, seconds=0.3, chips=4, trace=False)
    ctx.record["device_kind"] = "TPU v5 lite"
    return harness.drive(ctx)


def cases() -> dict:
    """Each case of the comparison, driven in this process (which must see
    four devices); what the tests assert on."""
    from repro.api.plan import FactorizationPlan
    from repro.api.result import Factorization

    out = {}
    execute, solve = FactorizationPlan.execute, Factorization.solve
    facts = []

    def watched(self, A):
        facts.append(execute(self, A))
        return facts[-1]

    FactorizationPlan.execute = watched
    ctx = _drive(_config(N))
    FactorizationPlan.execute = execute
    out["program"] = {"correct": ctx.correct, "checks": ctx.checks,
                      "attempted": ctx.attempted,
                      "grids": sorted({str(f.grid) for f in facts}),
                      "devices": sorted({d for f in facts for d in f.devices})}

    cfg = _config(N)
    cfg["solver"].update(cfg["control"])
    ctx = _drive(cfg)
    out["control"] = {"correct": ctx.correct, "checks": ctx.checks}

    def altered(self, b, **kw):
        x = solve(self, b, **kw)
        return x.at[0].add(1e-2 * abs(x).max())

    Factorization.solve = altered
    ctx = _drive(_config(N))
    Factorization.solve = solve
    out["altered"] = {"correct": ctx.correct, "checks": ctx.checks}

    first = []

    def stale(self, A):
        if not first:
            first.append(execute(self, A))
        return first[0]

    FactorizationPlan.execute = stale
    ctx = _drive(_config(N))
    FactorizationPlan.execute = execute
    out["stale"] = {"correct": ctx.correct, "checks": ctx.checks}
    return out


@pytest.fixture(scope="module")
def driven():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, __file__, "--cases"], env=env, cwd=ROOT,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_program_is_correct(driven):
    got = driven["program"]
    assert got["attempted"] >= 2
    assert got["correct"], got["checks"]


def test_program_ran_the_stated_grid_on_four_devices(driven):
    from repro.api import GridConfig

    got = driven["program"]
    assert got["grids"] == [str(GridConfig(2, 2, 1, v=32, N=N))]
    assert len(got["devices"]) == 4


@pytest.mark.parametrize("case", ["control", "altered", "stale"])
def test_control_and_planted_faults_fail(driven, case):
    got = driven[case]
    assert not got["correct"], got["checks"]
    assert all(math.isfinite(v) for _, v, _ in got["checks"])


def test_configuration_states_its_grid():
    from repro.api import GridConfig, SolverConfig

    cfg = harness.read_json("configs", "conflux_p4")
    got = SolverConfig(**cfg["solver"]).grid
    assert got == GridConfig(Px=2, Py=2, c=1, v=32, N=cfg["N"])


# -- the readers, on a recorded four-chip trace ---------------------------------


def _rec(path, devices, n):
    tr = devtrace.load(path)
    return {"trace": tr, "devices": devices, "xplane": path,
            "device_kind": "TPU v5 lite", "peaks": peaks,
            "work": {"N": n, "solutions": 1, "itemsize": 4},
            "spans": {"solve": tr.spans.get("bench.solve", [])}}


@pytest.fixture(scope="module")
def trace(tmp_path_factory):
    """The recorded trace, unpacked: the profiler's reader takes a file."""
    path = tmp_path_factory.mktemp("conflux4") / "conflux4_n2048.xplane.pb"
    with gzip.open(TRACE, "rb") as src, open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


@pytest.fixture(scope="module")
def rec(trace):
    return _rec(trace, CHIPS, TRACE_N)


def _read(name, rec):
    return harness.load_module("metrics", name).read(rec)


def _busy_in_execute(rec, device):
    tr = rec["trace"]
    return sum(stats.covered(tr.busy(device), s, e) for s, e in tr.spans["bench.execute"])


@pytest.mark.parametrize("name", METRICS + SHARED)
def test_reader_is_finite_on_four_chip_trace(rec, name):
    v = _read(name, rec)
    assert v is not None and math.isfinite(v) and v > 0, (name, v)


@pytest.mark.parametrize("name", METRICS)
def test_reader_reads_nothing_on_one_chip(name):
    assert _read(name, _rec(ONE_CHIP, CHIPS[:1], 1024)) is None


def test_collectives_are_part_of_the_factor_program(rec):
    assert 0 < _read("collective_s", rec) < max(_busy_in_execute(rec, d) for d in CHIPS)
    assert _read("pivot_writeback_s", rec) < max(_busy_in_execute(rec, d) for d in CHIPS)


def test_roofline_share_is_a_share(rec):
    assert 0 < _read("conflux_roofline", rec) < 100


def test_layout_and_copies_lie_inside_the_solution(rec):
    (s, e), = rec["trace"].spans["bench.solution"]
    assert 0 < _read("conflux_layout_s", rec) < e - s
    assert 0 < _read("solution_copy_s", rec) < e - s


def scope_cover(path, devices) -> dict[str, dict]:
    """Per chip, the share of its device-busy time inside `bench.execute`
    that ran in a named step of the schedule (`repro.obs` LU and conflux
    scopes, innermost first; an op of a loop XLA expanded takes its loop's
    scope), and the seconds in each scope (None: ops that map to no
    scope)."""
    from repro import obs

    reader = harness.load_module("metrics", "pivot_writeback_s")
    saved = progtrace.LU_SCOPES
    progtrace.LU_SCOPES = obs.LU_SCOPES + obs.CONFLUX_SCOPES
    try:
        pt = progtrace.load(path)
        rec = {"trace": devtrace.load(path)}
        execute = rec["trace"].spans["bench.execute"]
        loops = reader.loop_op_names(path)
        by_chip = {d: reader.scope_seconds(rec["trace"], pt, d, execute, loops)
                   for d in devices}
    finally:
        progtrace.LU_SCOPES = saved
    out = {}
    for d, by in by_chip.items():
        out[d] = {"cover": sum(v for k, v in by.items() if k) / _busy_in_execute(rec, d),
                  "seconds": {str(k): v for k, v in by.items()}}
    return out


def test_every_step_is_scoped_on_every_chip(trace):
    # At this small N the rest is the step's `conditional` between its
    # short ops.  Every step the source names shows on every chip.
    from repro import obs

    for got in scope_cover(trace, CHIPS).values():
        assert 0.9 <= got["cover"] <= 1 + 1e-9, got
        for name in obs.LU_SCOPES + obs.CONFLUX_SCOPES:
            assert got["seconds"].get(name, 0) > 0, (name, got)
    assert progtrace.COPY_PREFIX == obs.COPY_PREFIX
    assert harness.load_module("metrics", "conflux_layout_s").LAYOUT == obs.LAYOUT
    assert harness.load_module("metrics", "pivot_writeback_s").SCOPE == obs.LU_PIVOT_WRITEBACK


def test_write_back_loop_takes_its_scope_from_the_embedded_hlo(rec):
    # xprof's op table gives the expanded loop's `while` no op_name; the
    # HLO in the trace does, and the loop's copies count as the write-back.
    reader = harness.load_module("metrics", "pivot_writeback_s")
    loops = reader.loop_op_names(rec["xplane"])
    writeback = {k for k, v in loops.items() if progtrace.scope_of(v) == reader.SCOPE}
    assert writeback and all(k.startswith("while.") for k in writeback)
    own = progtrace.lu_scope_per_solution(rec, (reader.SCOPE,))
    assert _read("pivot_writeback_s", rec) > 5 * own > 0


def test_protobuf_fields_are_read_by_wire_type():
    fields = harness.load_module("metrics", "pivot_writeback_s")._fields
    # field 1 varint 300, field 2 "hi", field 3 fixed64, field 4 fixed32
    buf = bytes([0x08, 0xAC, 0x02, 0x12, 0x02]) + b"hi" + bytes([0x19]) + bytes(8) \
        + bytes([0x25]) + bytes(4)
    assert list(fields(buf)) == [(1, 300), (2, (5, 7)), (3, None), (4, None)]


def test_collective_ops_by_opcode():
    is_coll = harness.load_module("metrics", "collective_s").is_collective
    assert is_coll("%psum.241 = s32[32]{0} all-reduce(%f.10), channel_id=1, to_apply=%r")
    assert is_coll("%collective-permute-start.19 = (s32[32]{0}, u32[]) "
                   "collective-permute-start(%fusion.129), channel_id=1")
    assert is_coll("%collective-permute-done.19 = s32[32]{0} "
                   "collective-permute-done(%collective-permute-start.19)")
    assert is_coll("%all-gather-start = (f32[8], f32[32]) async-start(%x), calls=%c")
    assert not is_coll("%add.5 = f32[] add(f32[] %all-reduce.3, f32[] %x)")
    assert not is_coll('%lu_panel.8 = (f32[32,1024]) custom-call(%x), '
                       'custom_call_target="tpu_custom_call"')


def record(out: str) -> None:
    """Record the four-chip trace on a TPU host, gzipped into `out`."""
    import glob
    import tempfile

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.profiler import TraceAnnotation

    from repro.api import SolverConfig, plan

    assert jax.devices()[0].platform == "tpu" and len(jax.devices()) >= 4, \
        "records a four-chip TPU trace"
    rng = np.random.default_rng(2048)
    A = jnp.asarray(rng.uniform(-0.5, 0.5, (TRACE_N, TRACE_N)).astype(np.float32))
    b = jnp.asarray(rng.uniform(-0.5, 0.5, TRACE_N).astype(np.float32))
    p = plan(TRACE_N, SolverConfig(**_config(TRACE_N)["solver"]))
    jax.block_until_ready(p.execute(A).solve(b))
    with tempfile.TemporaryDirectory() as d:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0  # the readers need no Python frames
        jax.profiler.start_trace(d, profiler_options=options)
        with TraceAnnotation("bench.solution"):
            with TraceAnnotation("bench.execute"):
                fact = p.execute(A)
            with TraceAnnotation("bench.solve"):
                jax.block_until_ready(fact.solve(b))
        jax.profiler.stop_trace()
        path, = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
        with open(path, "rb") as src, gzip.open(out, "wb") as dst:
            shutil.copyfileobj(src, dst)


if __name__ == "__main__":
    if sys.argv[1:] == ["--cases"]:
        print(json.dumps(cases()))
    elif sys.argv[1:2] == ["--record"] and len(sys.argv) == 3:
        record(sys.argv[2])
    elif sys.argv[1:2] == ["--cover"] and len(sys.argv) == 3:
        print(json.dumps(scope_cover(sys.argv[2], CHIPS)))
    else:
        sys.exit(f"usage: python {sys.argv[0]} --cases | --record <out.xplane.pb.gz> "
                 f"| --cover <xplane.pb>")

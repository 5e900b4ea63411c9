"""CPU tests of the benchmark's yardstick: the trace reduction (on a small
trace recorded on a TPU v5e), interval arithmetic, percentiles, the peak
table, the generator, and that BENCHMARK.json resolves every part by name."""

from __future__ import annotations

import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import devtrace, harness, peaks, stats  # noqa: E402

# One LU solution at N=1024 (pallas, f32) inside bench.solution/execute/solve
# spans, then 40 requests to an AsyncSolveEngine(N=64) inside bench.serve.
TRACE = os.path.join(ROOT, "bench", "tests", "data", "lu1024_serve64.xplane.pb")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def trace():
    return devtrace.load(TRACE)


def test_trace_planes_and_spans(trace):
    assert trace.devices() == ["/device:TPU:0"]
    for span in ("bench.solution", "bench.execute", "bench.solve", "bench.serve"):
        assert len(trace.spans[span]) == 1
    (s0, e0), = trace.spans["bench.solution"]
    (s1, e1), = trace.spans["bench.execute"]
    assert s0 <= s1 < e1 <= e0
    busy = trace.busy("/device:TPU:0")
    assert all(a < b for a, b in busy)
    assert all(b1 <= a2 for (_, b1), (a2, _) in zip(busy, busy[1:]))


def test_trace_kernels_and_breakdown(trace):
    dev = "/device:TPU:0"
    spans = trace.spans["bench.execute"]
    busy = sum(stats.covered(trace.busy(dev), s, e) for s, e in spans)
    mosaic = devtrace.mosaic_seconds(trace, dev, spans)
    assert 0 < mosaic <= busy
    ops = dict(devtrace.top_ops(trace, dev, trace.spans["bench.solution"]))
    assert {"lu_panel", "_fused_trsm_schur"} <= set(ops)
    assert sum(ops.values()) <= busy + 1e-3
    gaps = devtrace.idle_gaps(trace, dev, trace.spans["bench.solution"])
    assert gaps and all(isinstance(k, str) and v > 0 for k, v in gaps)
    (s, e), = trace.spans["bench.solution"]
    assert sum(v for _, v in gaps) <= (e - s) - 0 + 1e-9


def test_short_name_and_self_times():
    assert devtrace.short_name('%lu_panel.8 = (f32[32,1024]) custom-call(x)') == "lu_panel"
    assert devtrace.short_name("%while.14 = (s32[]) while(x)") == "while"
    evs = [(0.0, 10.0, "%while.1 = w"), (1.0, 3.0, "%a.2 = x"), (4.0, 5.0, "%b = y")]
    assert devtrace.self_times(evs) == {"while": 7.0, "a": 2.0, "b": 1.0}


def test_readers_on_recorded_trace(trace):
    rec = {"trace": trace, "devices": ["/device:TPU:0"], "device_kind": "TPU v5 lite",
           "peaks": peaks, "work": {"N": 1024, "solutions": 1, "itemsize": 4},
           "spans": {"solve": [(0.0, 0.25)]}}
    read = {m: harness.load_module("metrics", m).read(rec) for m in (
        "execute_overhead_s", "factor_roofline", "pallas_s", "device_idle_share.solve",
        "solve_s")}
    assert 0 < read["execute_overhead_s"] < 0.01
    assert 0 < read["factor_roofline"] < 100
    assert 0 < read["pallas_s"] < 0.01
    assert 0 < read["device_idle_share.solve"] < 100
    assert read["solve_s"] == 0.25
    # a serving record without its spans or counters reads nothing
    assert harness.load_module("metrics", "device_idle_share.serve").read(rec) is None
    assert harness.load_module("metrics", "serve_batch_fill").read(rec) is None
    tail = harness.load_module("metrics", "serve_tail_p99_ms")
    assert tail.read(rec) is None
    assert tail.read({"latency_p99_ms": 57.5}) == 57.5
    assert tail.read({"latency_p99_ms": math.inf}) is None  # lost requests: no tail


def test_union_gaps_and_idle_share():
    merged = stats.union([(5, 6), (0, 2), (1, 3), (3, 4), (7, 7)])
    assert merged == [(0, 4), (5, 6)]
    assert stats.covered(merged, 1, 5.5) == 3.5
    assert stats.gaps(merged, -1, 8) == [(-1, 0), (4, 5), (6, 8)]
    assert stats.idle_share([(0, 1), (2, 3)], [(0, 4)]) == 50.0
    assert stats.idle_share([(0, 1), (0.5, 2)], [(0, 2), (1, 4)]) == 50.0
    with pytest.raises(ValueError):
        stats.idle_share([(0, 1)], [])


def test_nearest_rank_percentile():
    vals = list(range(1, 101))
    assert stats.nearest_rank(vals, 99) == 99
    assert stats.nearest_rank(vals, 100) == 100
    assert stats.nearest_rank([3.0], 99) == 3.0
    # refused or lost requests are beyond every limit
    assert stats.nearest_rank([1.0] * 98 + [math.inf] * 2, 99) == math.inf
    assert stats.nearest_rank([1.0] * 99 + [math.inf], 99) == 1.0


def test_roofline_share_and_unknown_device():
    # 197 TFLOP in 2 s on a v5e: half the compute roofline
    assert peaks.roofline_share(197e12, 0, 2.0, "TPU v5 lite") == pytest.approx(50.0)
    # bandwidth-bound work: 819 GB in 4 s
    assert peaks.roofline_share(1.0, 819e9, 4.0, "TPU v5 lite") == pytest.approx(25.0)
    with pytest.raises(ValueError, match="no published peaks"):
        peaks.roofline_share(1.0, 1.0, 1.0, "TPU v9 imaginary")


def test_generator_is_seeded_and_same_work_per_seed():
    traffic = {"kind": "open_loop", "rate_per_s": 50, "tenants": 8, "zipf_s": 1.1,
               "n_min": 16, "n_max": 256, "pool": 32}
    generator = harness.load_module("arrivals", traffic["kind"])
    a = generator.schedule(traffic, 2**31 + 11, 4.0)
    b = generator.schedule(traffic, 2**31 + 11, 4.0)
    c = generator.schedule(traffic, 7, 4.0)
    for k in a:
        assert (a[k] == b[k]).all()
    assert len(a["t_due"]) == 200
    assert (a["t_due"] != c["t_due"]).any()
    assert sorted(a["tenant"]) == sorted(c["tenant"])
    assert sorted(a["item"]) == sorted(c["item"])
    assert (a["t_due"][:-1] <= a["t_due"][1:]).all() and a["t_due"][-1] <= 4.0
    counts = generator.zipf_counts(200, 8, 1.1)
    assert counts.sum() == 200 and (counts[:-1] >= counts[1:]).all()
    sizes = generator.pool_sizes(traffic)
    assert sizes.min() >= 16 and sizes.max() <= 256 and len(sizes) == 32


def test_benchmark_json_resolves_by_name():
    bench = harness.spec()
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"] and bench["command"][1] == "bench/run.py"
    configs = {c["name"]: c for c in bench["configs"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    cells = {w["name"]: w for w in bench["workloads"]}
    assert "setup_s" in e2e
    for item in list(configs) + list(cells) + list(e2e) + [m["name"] for m in bench["per_layer"]]:
        assert NAME.match(item), item
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        for w in m.get("workloads", []):
            assert w in cells
    for c in bench["configs"]:
        cfg = json.load(open(os.path.join(ROOT, c["file"])))
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert hasattr(harness.load_module("runners", cfg["runner"]), "run")
        assert hasattr(harness.load_module("problems", cfg["problem"]), "make_pool")
        assert cfg["control"] and cfg["limits"]
        assert set(c["reduced"]) <= set(cfg.get("reduced", {}))
    for w in cells.values():
        assert w["config"] in configs and w["chips"] in (1, 4)
        kind = harness.read_json("traffic", w["traffic"])["kind"]
        runner = json.load(open(os.path.join(ROOT, configs[w["config"]]["file"])))["runner"]
        if runner == "solve":
            assert kind == "back_to_back"
        else:
            assert callable(harness.load_module("arrivals", kind).schedule)
        mine_e2e, mine_layer = harness.metrics_for(bench, w["name"])
        assert "setup_s" in {m["name"] for m in mine_e2e} and len(mine_e2e) >= 2
        assert mine_layer
    for m in bench["per_layer"]:
        assert callable(harness.load_module("metrics", m["name"]).read)
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert m["moves"] in {x["name"] for x in harness.metrics_for(bench, w)[0]}


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "hpl_lu.n16384", "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _has_result(stdout: str) -> bool:
    lines = stdout.strip().splitlines()
    return bool(lines) and lines[-1].startswith("{")


def test_no_tpu_exits_nonzero_without_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert not _has_result(p.stdout)
    assert "needs a TPU" in p.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0
    assert not _has_result(p.stdout)

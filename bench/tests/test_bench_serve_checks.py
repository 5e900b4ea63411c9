"""The `batched_serve` comparison on the CPU at a small size: the program
passes, the lower-precision control and each planted fault of the timed
path fail.

Each test drives the whole cell (set-up, open-loop window, reference check)
through the harness with the chip check skipped, with an engine of N=32 and
max_batch=4 under the configuration's own limits."""

from __future__ import annotations

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def _drive(control: bool = False):
    cfg = harness.read_json("configs", "batched_serve")
    cfg["N"] = 32
    cfg["engine"]["max_batch"] = 4
    if control:
        cfg["solver"].update(cfg["control"])
    traffic = harness.read_json("traffic", "poisson")
    traffic.update(rate_per_s=100, n_min=16, n_max=32, pool=32)
    ctx = harness.Context(workload="batched_serve.test", config=cfg, traffic=traffic,
                          seed=2**31 + 9, seconds=0.5, chips=1, trace=False,
                          grace_s=5.0)
    ctx.record["device_kind"] = "TPU v5 lite"
    return harness.drive(ctx)


def test_program_is_correct():
    ctx = _drive()
    assert ctx.attempted == 50 and ctx.failed == 0
    assert ctx.correct, ctx.checks
    assert ctx.e2e["served_per_s"] > 0 and ctx.e2e["serve_p50_ms"] > 0
    assert ctx.record["latency_p99_ms"] >= ctx.e2e["serve_p50_ms"]


def test_lower_precision_control_fails():
    ctx = _drive(control=True)
    assert not ctx.correct, ctx.checks


def test_altered_answer_fails(monkeypatch):
    from repro.serving.solve_engine import SolveEngine

    orig = SolveEngine.flush_systems

    def altered(self):
        xs = orig(self)
        xs[0] = xs[0] * (1 + 1e-3)
        return xs

    monkeypatch.setattr(SolveEngine, "flush_systems", altered)
    ctx = _drive()
    assert not ctx.correct, ctx.checks


def test_half_of_batch_left_out_fails(monkeypatch):
    from repro.serving.async_engine import AsyncSolveEngine

    orig = AsyncSolveEngine._serve_group

    def half(self, group, enqueue, flush, abort):
        return orig(self, group[:(len(group) + 1) // 2], enqueue, flush, abort)

    monkeypatch.setattr(AsyncSolveEngine, "_serve_group", half)
    ctx = _drive()
    assert not ctx.correct, ctx.checks
    assert dict((n, v) for n, v, _ in ctx.checks)["unanswered"] > 0

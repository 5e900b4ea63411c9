"""The `hpl_lu` comparison on the CPU at a small N: the program passes, the
lower-precision control and each planted fault of the timed path fail.

Each test drives the whole cell (set-up, window, reference check) through
the harness with the chip check skipped, at N=256 with the configuration's
own limits."""

from __future__ import annotations

import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402


def _drive(control: bool = False):
    cfg = harness.read_json("configs", "hpl_lu")
    cfg["N"] = 256
    if control:
        cfg["solver"].update(cfg["control"])
    ctx = harness.Context(workload="hpl_lu.test", config=cfg,
                          traffic=harness.read_json("traffic", "back_to_back"),
                          seed=2**31 + 5, seconds=0.3, chips=1, trace=False)
    ctx.record["device_kind"] = "TPU v5 lite"
    return harness.drive(ctx)


def test_program_is_correct():
    ctx = _drive()
    assert ctx.attempted >= 2
    assert ctx.correct, ctx.checks


def test_lower_precision_control_fails():
    ctx = _drive(control=True)
    assert not ctx.correct, ctx.checks


def test_altered_answer_fails(monkeypatch):
    from repro.api.result import Factorization

    orig = Factorization.solve

    def altered(self, b, **kw):
        x = orig(self, b, **kw)
        return x.at[0].add(1e-2 * abs(x).max())

    monkeypatch.setattr(Factorization, "solve", altered)
    ctx = _drive()
    assert not ctx.correct, ctx.checks


def test_stale_factorization_fails(monkeypatch):
    """A factor step that hands back its first result unchanged."""
    from repro.api.plan import FactorizationPlan

    orig = FactorizationPlan.execute
    first = {}

    def stale(self, A):
        if "f" not in first:
            first["f"] = orig(self, A)
        return first["f"]

    monkeypatch.setattr(FactorizationPlan, "execute", stale)
    ctx = _drive()
    assert not ctx.correct, ctx.checks
    assert np.isfinite(ctx.checks[0][1])


@pytest.mark.parametrize("value", [float("nan"), float("inf")])
def test_nonfinite_reading_is_not_correct(value):
    ctx = harness.Context(workload="x", config={}, traffic={}, seed=0, seconds=0,
                          chips=1, trace=False)
    ctx.check("hpl_scaled_residual", value, 1.0)
    assert not ctx.correct

"""The harness behind `bench/run.py`: it finds a cell's parts by name, checks
the device, times set-up, drives the cell's runner, reduces the trace and
reports.

A cell (`workloads` entry of BENCHMARK.json) names a configuration and a
traffic mix; everything else is found by name, so a new cell, mix,
configuration or metric is new files and entries, not an edit:

    bench/configs/<config>.json    the deployment; names its runner and problem
    bench/traffic/<mix>.json       parameters of a mix; its `kind` names its arrivals
    bench/arrivals/<kind>.py       the schedule of an open-loop kind of mix
    bench/runners/<runner>.py      drives the system under test: run(ctx)
    bench/problems/<problem>.py    data from the seed and the plain reference
    bench/metrics/<metric>.py      a per-layer reader: read(rec) -> float | None
"""

from __future__ import annotations

import importlib.util
import json
import math
import os
import shutil
import sys
import time
import warnings
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE = os.path.join(ROOT, ".bench_cache")


class NoDevice(RuntimeError):
    """The run cannot measure: no accelerator, or too few chips."""


def spec() -> dict:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def read_json(kind: str, name: str) -> dict:
    with open(os.path.join(BENCH, kind, f"{name}.json")) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """`bench/<kind>/<name>.py` as a module (names may hold dots)."""
    path = os.path.join(BENCH, kind, f"{name}.py")
    mod_name = f"bench_{kind}_{name}".replace(".", "_")
    if mod_name in sys.modules:
        return sys.modules[mod_name]
    s = importlib.util.spec_from_file_location(mod_name, path)
    if s is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(s)
    sys.modules[mod_name] = mod
    s.loader.exec_module(mod)
    return mod


def cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def metrics_for(bench: dict, workload: str) -> tuple[list[dict], list[dict]]:
    """The cell's end-to-end and per-layer metrics.

    A metric with a `workloads` key belongs to those cells; a per-layer
    metric without it belongs to every cell reporting the metric it moves."""
    def mine(m):
        return "workloads" not in m or workload in m["workloads"]

    e2e = [m for m in bench["end_to_end"] if mine(m)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def device_info(chips: int) -> dict:
    """Platform, kind and count of the chips; raises NoDevice without a TPU."""
    import jax

    from bench import peaks

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"needs a TPU, but JAX found platform {d.platform!r} "
                       f"({d.device_kind}); nothing was measured")
    if len(devs) < chips:
        raise NoDevice(f"the cell needs {chips} chips, JAX found {len(devs)}")
    peaks.peak(d.device_kind)  # an unknown kind is an error, not a default
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the cell's chips."""
    import jax

    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in jax.devices()[:chips])


def enable_compile_cache() -> str:
    """JAX's persistent cache at a fixed path inside the checkout; every
    program is cached, so a second run of a cell compiles nothing."""
    import jax

    path = os.path.join(CACHE, "jax")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts JAX traces and XLA compiles while `armed` is set."""

    EVENTS = ("/jax/core/compile/backend_compile_duration",
              "/jax/core/compile/jaxpr_trace_duration")

    def __init__(self):
        from jax._src import monitoring

        self.armed = False
        self.counts = {e: 0 for e in self.EVENTS}
        monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and event in self.counts:
            self.counts[event] += 1


@dataclass
class Context:
    """What a runner gets, and what it hands back to the harness."""

    workload: str
    config: dict
    traffic: dict
    seed: int
    seconds: float
    chips: int
    trace: bool
    t_start: float = field(default_factory=time.perf_counter)
    grace_s: float = 60.0  # how long past the window an answer may come
    # filled by the runner
    t_window: float | None = None
    e2e: dict = field(default_factory=dict)
    record: dict = field(default_factory=dict)
    checks: list = field(default_factory=list)  # (name, value, limit)
    attempted: int = 0
    failed: int = 0
    memory_peak_bytes: int = 0
    notes: list = field(default_factory=list)
    _tracing: bool = False
    _compiles: CompileCounter | None = None

    @property
    def trace_dir(self) -> str:
        return os.path.join(CACHE, "trace", self.workload)

    def window_open(self) -> None:
        """Set-up ends here; nothing may compile from now on."""
        self.t_window = time.perf_counter()
        self.e2e["setup_s"] = self.t_window - self.t_start
        if self._compiles is not None:
            self._compiles.armed = True

    def window_closed(self) -> None:
        if self._compiles is not None:
            self._compiles.armed = False

    def trace_start(self) -> None:
        if not self.trace:
            return
        import jax

        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self._tracing = True

    def trace_stop(self) -> None:
        """Stops the profiler if this run started it."""
        if not self._tracing:
            return
        import jax

        jax.profiler.stop_trace()
        self._tracing = False

    def memory_read(self) -> None:
        self.memory_peak_bytes = memory_peak_bytes(self.chips)

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append((name, float(value), float(limit)))

    def note(self, msg: str) -> None:
        self.notes.append(msg)
        print(f"[bench] {msg}", file=sys.stderr, flush=True)

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            math.isfinite(v) and v <= lim for _, v, lim in self.checks)


def drive(ctx: Context) -> Context:
    """Run the cell's runner on an already checked device."""
    # A plan that silently fell back from pallas to ref would measure the
    # wrong path: make the fallback an error.
    warnings.filterwarnings("error", message="backend 'pallas' cannot run")
    load_module("runners", ctx.config["runner"]).run(ctx)
    ctx.window_closed()
    return ctx


def reduce_trace(ctx: Context, layer: list[dict]) -> tuple[dict, dict, dict]:
    """Per-layer metrics, the device's busy/window seconds and the breakdown."""
    from bench import devtrace, peaks, stats

    path = devtrace.find_xplane(ctx.trace_dir)
    tr = devtrace.load(path)
    devices = [f"/device:TPU:{i}" for i in range(ctx.chips)]
    missing = [d for d in devices if d not in tr.ops]
    if missing:
        raise ValueError(f"trace has no ops for {missing}")
    windows = tr.spans.get("bench.window", [])
    if not windows:
        raise ValueError("trace holds no bench.window span")
    window_s = sum(e - s for s, e in stats.union(windows))
    busy_s = sum(sum(stats.covered(tr.busy(d), s, e) for s, e in stats.union(windows))
                 for d in devices) / len(devices)
    rec = dict(ctx.record, trace=tr, devices=devices,
               device_kind=ctx.record["device_kind"], peaks=peaks)
    values = {}
    for m in layer:
        v = load_module("metrics", m["name"]).read(rec)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    breakdown = {"device_ops": devtrace.top_ops(tr, devices[0], windows),
                 "idle_gaps": devtrace.idle_gaps(tr, devices[0], windows)}
    return values, {"busy_s": busy_s, "window_s": window_s}, breakdown


def report(ctx: Context, device: dict, metrics: dict, extra_device: dict | None = None,
           breakdown: dict | None = None) -> str:
    """Print the compared numbers last on stderr; return the result line."""
    for name, v, lim in ctx.checks:
        ok = "ok" if math.isfinite(v) and v <= lim else "FAIL"
        print(f"check {name} {v!r} limit {lim!r} {ok}", file=sys.stderr, flush=True)
    out = {
        "correct": ctx.correct,
        "attempted": ctx.attempted,
        "failed": ctx.failed,
        "metrics": metrics,
        "device": dict(device, memory_peak_bytes=ctx.memory_peak_bytes,
                       **(extra_device or {})),
    }
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in ctx.checks}
    return json.dumps(out)

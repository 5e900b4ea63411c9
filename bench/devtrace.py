"""Reduction of a JAX profiler trace (`.xplane.pb`) to what the metric
readers need.

What a TPU trace holds (looked at by hand on a v5e): one plane per chip,
named `/device:TPU:<i>`, whose line `XLA Ops` has one event per executed HLO
instruction, nested (a `while` holds its body's ops).  The event name is the
instruction's HLO text, so a Pallas (Mosaic) kernel reads
`%lu_panel.8 = (...) custom-call(...), custom_call_target="tpu_custom_call"`.
The plane `/host:CPU` has one line per host thread; the Python tracer's
events are named `$file.py:line function`, and the benchmark's own spans
(`jax.profiler.TraceAnnotation`) carry names that start with `bench.`.  All
events share one clock, in nanoseconds from the start of the trace.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

from bench import stats

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MOSAIC = 'custom_call_target="tpu_custom_call"'
SPAN_PREFIX = "bench."
# Python frames that only wait; a gap is attributed to what else ran then.
_WAITS = re.compile(r"\b(wait|acquire|sleep|result|join|_wait_for_tstate_lock)$")


@dataclass
class Trace:
    """Events of one trace, times in seconds from the trace's start."""

    ops: dict[str, list[tuple[float, float, str]]] = field(default_factory=dict)
    spans: dict[str, list[tuple[float, float]]] = field(default_factory=dict)
    host: list[list[tuple[float, float, str]]] = field(default_factory=list)

    def busy(self, device: str) -> list[tuple[float, float]]:
        """Disjoint intervals in which an operation ran on `device`."""
        return stats.union((s, e) for s, e, _ in self.ops[device])

    def devices(self) -> list[str]:
        return sorted(self.ops)


def find_xplane(log_dir: str) -> str:
    """The newest `.xplane.pb` that `jax.profiler` wrote under `log_dir`."""
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(found, key=os.path.getmtime)


def load(path: str) -> Trace:
    """Read the device ops, the benchmark's spans and the host threads."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    tr = Trace()
    spans = defaultdict(list)
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    tr.ops[plane.name] = [(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                                          for e in line.events]
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                evs = []
                for e in line.events:
                    ev = (e.start_ns * 1e-9, e.end_ns * 1e-9, e.name)
                    if e.name.startswith(SPAN_PREFIX):
                        spans[e.name].append(ev[:2])
                    else:
                        evs.append(ev)
                if evs:
                    tr.host.append(evs)
    if not tr.ops:
        raise ValueError(f"{path}: no device plane with an '{OPS_LINE}' line")
    tr.spans = {k: sorted(v) for k, v in spans.items()}
    return tr


def short_name(hlo_text: str) -> str:
    """`%lu_panel.8 = (...) custom-call(...)` -> `lu_panel`."""
    head = hlo_text.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def mosaic_seconds(tr: Trace, device: str, windows) -> float:
    """Device time of Pallas (Mosaic) kernels that started inside `windows`."""
    wins = stats.union(windows)
    tot = 0.0
    for s, e, name in tr.ops[device]:
        if MOSAIC in name and stats.covered(wins, s, s + 1e-12) > 0:
            tot += e - s
    return tot


def self_times(events) -> dict[str, float]:
    """Seconds per op name with nested children's time taken out.

    Events of one line nest (a `while` holds its body), so a stack walk
    gives each op the time in which it, and none of its children, ran."""
    out: dict[str, float] = defaultdict(float)
    stack: list[list] = []  # [end, name, child_time]

    def close(item):
        end, name, child, start = item
        out[name] += (end - start) - child
        if stack:
            stack[-1][2] += end - start

    for s, e, name in sorted(events, key=lambda t: (t[0], -t[1])):
        while stack and stack[-1][0] <= s:
            close(stack.pop())
        stack.append([e, short_name(name), 0.0, s])
    while stack:
        close(stack.pop())
    return dict(out)


def _innermost_at(events, points):
    """For each sorted point, the innermost event of one thread covering it."""
    evs = sorted(events, key=lambda t: (t[0], -t[1]))
    out = []
    stack: list[tuple[float, float, str]] = []
    i = 0
    for p in points:
        while i < len(evs) and evs[i][0] <= p:
            while stack and stack[-1][1] <= evs[i][0]:
                stack.pop()
            stack.append(evs[i])
            i += 1
        while stack and stack[-1][1] <= p:
            stack.pop()
        out.append(stack[-1] if stack else None)
    return out


def _label(name: str) -> str:
    return re.sub(r":\d+ ", " ", name.lstrip("$"))


def idle_gaps(tr: Trace, device: str, windows, top: int = 10) -> list[list]:
    """Idle device time inside `windows`, summed by what the host was doing.

    Each gap is attributed at its midpoint to the innermost host event that
    is not a wait, taking the deepest such event over all threads; the
    `top` labels with the most idle time are returned as [label, seconds]."""
    busy = tr.busy(device)
    gs = []
    for s, e in stats.union(windows):
        gs.extend(stats.gaps(busy, s, e))
    if not gs:
        return []
    gs.sort(key=lambda g: (g[0] + g[1]) / 2)
    mids = [(s + e) / 2 for s, e in gs]
    per_thread = [_innermost_at(evs, mids) for evs in tr.host]
    totals: dict[str, float] = defaultdict(float)
    for k, (s, e) in enumerate(gs):
        best = None
        for found in per_thread:
            ev = found[k]
            if ev is None or _WAITS.search(ev[2]):
                continue
            if best is None or ev[1] - ev[0] < best[1] - best[0]:
                best = ev
        totals[_label(best[2]) if best else "(host waiting)"] += e - s
    ranked = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    return [[k, v] for k, v in ranked]


def top_ops(tr: Trace, device: str, windows, top: int = 10) -> list[list]:
    """The device ops with the most self time inside `windows`."""
    wins = stats.union(windows)
    inside = [ev for ev in tr.ops[device] if stats.covered(wins, ev[0], ev[0] + 1e-12) > 0]
    ranked = sorted(self_times(inside).items(), key=lambda kv: -kv[1])[:top]
    return [[k, v] for k, v in ranked]

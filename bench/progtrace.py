"""The solver's own spans and device scopes, read from a profiler trace.

`devtrace.load` keeps the device ops (by HLO text) and the benchmark's
`bench.*` spans.  The solver's spans (`repro.*`, with their arguments) and
the scope of each device op need the `.xplane.pb` itself:

- Host spans are TraceMe events on the `/host:CPU` plane; their arguments
  (`bytes`, `flush`, `systems`, `wait_s_sum`, `wait_s_max`) are event stats.
- A device op's `jax.named_scope`s are in the `op_name` metadata of its HLO
  instruction.  A TPU trace's op events carry no `op_name`, but the trace
  embeds each program's HLO; xprof's `hlo_stats` tool maps every (program
  id, instruction) to its `op_name`.  An op's program is the `XLA Modules`
  event around it, named `<module>(<program id>)`.  An instruction with no
  `op_name` of its own (a copy that XLA's layout assignment inserted) takes
  the scope of the instructions that read it, where they all agree.

Where the trace holds no solver spans or no scoped op (a program without
them), the readers get None, never a guess.  The names are the yardstick
and are written out here; `bench/tests` checks them against `repro.obs`.
"""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
import sys
import tempfile
from collections import defaultdict
from dataclasses import dataclass, field

from bench import devtrace, stats

SPAN_PREFIX = "repro."
COPY_PREFIX = "repro.copy."
EXECUTE = "repro.execute"
SERVE_FLUSH = "repro.serve.flush"
LU_SCOPES = ("lu.panel", "lu.layout", "lu.pivot_gather", "lu.update",
             "lu.pivot_writeback")
_PROGRAM = re.compile(r"\((\d+)\)$")
_OPERAND = re.compile(r"%([A-Za-z0-9_.\-]+)")


@dataclass
class Span:
    start: float
    end: float
    args: dict


@dataclass
class ProgTrace:
    """Solver spans by name, and per device (start, end, scope, self seconds)
    of every op, scope None where the op ran in no `lu.*` scope."""

    spans: dict[str, list[Span]] = field(default_factory=dict)
    ops: dict[str, list[tuple[float, float, str | None, float]]] = field(
        default_factory=dict)

    def scoped(self) -> bool:
        return any(op[2] for ops in self.ops.values() for op in ops)

    def named(self, prefix: str) -> list[Span]:
        return sorted((s for k, v in self.spans.items() if k.startswith(prefix)
                       for s in v), key=lambda s: s.start)


def _instr(hlo_text: str) -> str:
    """`%copy.6 = f32[...] copy(...)` -> `copy.6`."""
    return hlo_text.split(" = ", 1)[0].strip().removeprefix("ROOT ").lstrip("%")


def scope_of(op_name: str) -> str | None:
    """The innermost `lu.*` scope in an op_name path, or None."""
    found = None
    for seg in op_name.split("/"):
        if seg.rstrip(":") in LU_SCOPES:
            found = seg.rstrip(":")
    return found


def op_names(path: str) -> dict[tuple[str, str], str] | None:
    """(program id, instruction) -> op_name from the HLO the trace embeds,
    through xprof's `hlo_stats` tool; None where xprof cannot read it.

    The tool writes a cache file beside the trace it reads, so it reads a
    link to the trace in a temporary directory."""
    try:
        from xprof.convert import raw_to_tool_data
    except ImportError as e:
        print(f"[bench] progtrace: no xprof ({e}); no op is scoped", file=sys.stderr)
        return None
    with tempfile.TemporaryDirectory() as d:
        link = os.path.join(d, os.path.basename(path))
        os.symlink(os.path.abspath(path), link)
        try:
            raw, _ = raw_to_tool_data.xspace_to_tool_data(
                [link], "hlo_stats", {"use_saved_result": False})
        except Exception as e:  # noqa: BLE001 - the reader reads nothing, and says why
            print(f"[bench] progtrace: xprof could not read {path}: {e!r}", file=sys.stderr)
            return None
    if not raw:
        return None
    table = json.loads(raw)
    cols = [c["id"] for c in table["cols"]]
    try:
        ip, ih, it = (cols.index(k) for k in ("program_id", "hlo_op_name", "tf_op_name"))
    except ValueError:
        return None
    out = {}
    for row in table["rows"]:
        c = [cell.get("v") for cell in row["c"]]
        out[(str(c[ip]), str(c[ih]))] = c[it] or ""
    return out


def _self_seconds(events) -> list[float]:
    """Each event's time with its nested children's time taken out."""
    order = sorted(range(len(events)), key=lambda i: (events[i][0], -events[i][1]))
    own = [e - s for s, e, *_ in events]
    stack: list[int] = []
    for i in order:
        s = events[i][0]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= events[i][1] - s
        stack.append(i)
    return own


def _scopes(ops, modules, names) -> list[str | None]:
    """The innermost `lu.*` scope of each op; an op with no op_name takes
    the scope of the ops of its program that read it, where those agree."""
    starts = [m[0] for m in modules]
    keys, texts = [], {}
    for s, _, text in ops:
        k = bisect.bisect_right(starts, s) - 1
        pid = modules[k][2] if k >= 0 and s < modules[k][1] else None
        key = (pid, _instr(text))
        keys.append(key)
        texts.setdefault(key, text)
    op_name = {key: names.get(key, "") if key[0] else "" for key in texts}
    scope = {key: scope_of(n) for key, n in op_name.items()}
    readers: dict[tuple, set] = defaultdict(set)
    for (pid, name), text in texts.items():
        for ref in set(_OPERAND.findall(text.split(" = ", 1)[-1])):
            if (pid, ref) in texts:
                readers[(pid, ref)].add((pid, name))
    changed = True
    while changed:
        changed = False
        for key in texts:
            if op_name[key] or scope[key] is not None:
                continue
            got = {scope[r] for r in readers[key]}
            if len(got) == 1 and None not in got:
                scope[key] = got.pop()
                changed = True
    return [scope[key] for key in keys]


def _read(path: str):
    """Solver spans by name, and per device its ops and program events."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans = defaultdict(list)
    device = {}
    for plane in pd.planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            ops, modules = [], []
            for line in plane.lines:
                if line.name == devtrace.OPS_LINE:
                    ops = [(e.start_ns * 1e-9, e.end_ns * 1e-9, e.name) for e in line.events]
                elif line.name == "XLA Modules":
                    for e in line.events:
                        m = _PROGRAM.search(e.name)
                        modules.append((e.start_ns * 1e-9, e.end_ns * 1e-9,
                                        m.group(1) if m else None))
            device[plane.name] = (ops, sorted(modules))
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans[e.name].append(Span(e.start_ns * 1e-9, e.end_ns * 1e-9,
                                                  dict(e.stats)))
    return {k: sorted(v, key=lambda s: s.start) for k, v in spans.items()}, device


def load(path: str, expect: tuple[str, int] | None = None) -> ProgTrace | None:
    """Solver spans and scoped device ops of one trace; None where xprof
    cannot map the ops to their op_names, or where `expect` (device, op
    count) does not match the trace."""
    spans, device = _read(path)
    if expect is not None and len(device.get(expect[0], ((),))[0]) != expect[1]:
        return None
    names = op_names(path)
    if names is None:
        return None
    pt = ProgTrace(spans=spans)
    for dev, (ops, modules) in device.items():
        scopes = _scopes(ops, modules, names)
        own = _self_seconds(ops)
        pt.ops[dev] = [(s, e, sc, o) for (s, e, _), sc, o in zip(ops, scopes, own)]
    return pt


def for_record(rec) -> ProgTrace | None:
    """The ProgTrace of the run a metric reader was handed, kept in `rec`
    for the next reader.

    `rec["xplane"]` names the file where given; otherwise it is the newest
    trace under the benchmark's trace directory whose device op count
    matches `rec["trace"]`.
    """
    from bench import harness

    if "progtrace" in rec:
        return rec["progtrace"]
    if rec.get("xplane"):
        pt = load(rec["xplane"])
    else:
        dev = rec["devices"][0]
        want = len(rec["trace"].ops[dev])
        found = glob.glob(os.path.join(harness.CACHE, "trace", "**", "*.xplane.pb"),
                          recursive=True)
        pt = None
        for path in sorted(found, key=os.path.getmtime, reverse=True):
            pt = load(path, expect=(dev, want))
            if pt is not None:
                break
    rec["progtrace"] = pt
    return pt


def _starts_in(wins, t: float) -> bool:
    return stats.covered(wins, t, t + 1e-12) > 0


def scope_seconds(pt: ProgTrace, device: str, windows) -> dict[str, float]:
    """Device self time per innermost `lu.*` scope (None: no scope) of the
    ops that started inside `windows`."""
    wins = stats.union(windows)
    out: dict = defaultdict(float)
    for s, _, sc, own in pt.ops.get(device, ()):
        if _starts_in(wins, s):
            out[sc] += own
    return dict(out)


def spans_in(pt: ProgTrace, prefix: str, windows) -> list[Span]:
    """Solver spans named `prefix`... that started inside `windows`."""
    wins = stats.union(windows)
    return [s for s in pt.named(prefix) if _starts_in(wins, s.start)]


def wall(spans, windows=None) -> float:
    """Seconds covered by the union of `spans`, inside `windows` if given."""
    merged = stats.union((s.start, s.end) for s in spans)
    if windows is None:
        return sum(e - s for s, e in merged)
    return sum(stats.covered(merged, s, e) for s, e in stats.union(windows))


def lu_scope_per_solution(rec, scopes) -> float | None:
    """Device self time in `scopes` inside `bench.execute`, per solution."""
    spans = rec["trace"].spans
    execute, solutions = spans.get("bench.execute"), spans.get("bench.solution")
    if not execute or not solutions:
        return None
    pt = for_record(rec)
    if pt is None or not pt.scoped():
        return None
    by = scope_seconds(pt, rec["devices"][0], execute)
    return sum(by.get(s, 0.0) for s in scopes) / len(solutions)


def solution_copies(rec) -> tuple[list[Span], list] | None:
    """The copy spans inside `bench.solution`, and those spans; None where
    the solver ran no `repro.execute` span there."""
    solutions = rec["trace"].spans.get("bench.solution")
    if not solutions:
        return None
    pt = for_record(rec)
    if pt is None or not spans_in(pt, EXECUTE, solutions):
        return None
    return spans_in(pt, COPY_PREFIX, solutions), solutions


def flushes(rec) -> tuple[ProgTrace, list[Span]] | None:
    """The `repro.serve.flush` spans that started inside `bench.window`."""
    if "counters" not in rec:
        return None
    window = rec["trace"].spans.get("bench.window")
    if not window:
        return None
    pt = for_record(rec)
    if pt is None:
        return None
    found = spans_in(pt, SERVE_FLUSH, window)
    return (pt, found) if found else None

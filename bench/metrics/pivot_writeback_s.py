"""Device self time of the pivot-row write-back (innermost scope
`lu.pivot_writeback`) inside `plan.execute`, per solution, on the chip where
it is largest, counting the ops of the loop XLA expands it into.

On a TPU, XLA expands the write-back's row scatter-add into a `while` over
the pivot rows.  The ops of its body carry no op_name, and the trace's own
op table (xprof's `hlo_stats`) gives a `while` none either, so
`progtrace` leaves that time unscoped.  The HLO that the trace embeds (the
`Hlo Proto` stats of its `/host:metadata` plane) keeps the `while`'s
op_name; here an op with no scope of its own takes the scope of the
innermost loop around it that has one.  A one-chip trace reads nothing.
"""

from __future__ import annotations

import os

from bench import devtrace, harness, progtrace, stats

SCOPE = "lu.pivot_writeback"
LOOP = "while"
_METADATA_PLANE = b"/host:metadata"


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, span: tuple[int, int] | None = None):
    """(field number, value) of one protobuf message: an int for a varint,
    the (start, end) slice of a length-delimited field, None otherwise."""
    i, end = span or (0, len(buf))
    while i < end:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(buf, i)
        elif kind == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif kind in (1, 5):
            v, i = None, i + (8 if kind == 1 else 4)
        else:
            raise ValueError(f"protobuf wire type {kind} at byte {i}")
        yield key >> 3, v


def _sub(buf, span, number):
    return [v for f, v in _fields(buf, span) if f == number]


def _text(buf, span) -> str:
    return bytes(buf[span[0]:span[1]]).decode()


def loop_op_names(path: str) -> dict[str, str]:
    """Instruction name -> op_name of every `while` in the HLO the trace
    embeds; a name that two programs give different op_names is left out.

    XSpace.planes (1) -> XPlane name (2), event_metadata (4) -> map value
    (2) -> XEventMetadata.stats (5) -> XStat.bytes_value (6) = HloProto ->
    hlo_module (1) -> computations (3) -> instructions (2) -> name (1),
    opcode (2), metadata (7) -> op_name (2)."""
    with open(path, "rb") as f:
        buf = memoryview(f.read())
    out: dict[str, str] = {}
    clash = set()
    for plane in _sub(buf, None, 1):
        names = _sub(buf, plane, 2)
        if not names or bytes(buf[names[0][0]:names[0][1]]) != _METADATA_PLANE:
            continue
        for entry in _sub(buf, plane, 4):
            for meta in _sub(buf, entry, 2):
                for stat in _sub(buf, meta, 5):
                    for proto in _sub(buf, stat, 6):
                        for module in _sub(buf, proto, 1):
                            for comp in _sub(buf, module, 3):
                                for ins in _sub(buf, comp, 2):
                                    f = dict(_fields(buf, ins))
                                    if 2 not in f or _text(buf, f[2]) != LOOP:
                                        continue
                                    name = _text(buf, f[1])
                                    ops = _sub(buf, f[7], 2) if 7 in f else []
                                    op = _text(buf, ops[0]) if ops else ""
                                    if out.setdefault(name, op) != op:
                                        clash.add(name)
    for name in clash:
        del out[name]
    return out


def loop_scoped(tr, pt, device: str, loops: dict[str, str]):
    """(start, scope, self seconds) of each op of `device`, where an op
    with no scope takes that of the innermost scoped loop around it."""
    ops = tr.ops[device]
    mine = pt.ops.get(device, [])
    if len(mine) != len(ops):
        return None
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    stack: list[tuple[float, str | None]] = []  # (end, scope of the loop around)
    out = []
    for i in order:
        s, e, text = ops[i]
        scope, own = mine[i][2], mine[i][3]
        while stack and stack[-1][0] <= s:
            stack.pop()
        around = stack[-1][1] if stack else None
        name = devtrace.short_name(text)
        if name == LOOP:
            head = text.split(" = ", 1)[0].strip().removeprefix("ROOT ").lstrip("%")
            scope = scope or progtrace.scope_of(loops.get(head, ""))
        scope = scope or around
        out.append((s, scope, own))
        stack.append((e, scope if name == LOOP else around))
    return out


def scope_seconds(tr, pt, device, windows, loops) -> dict[str | None, float] | None:
    """Device self time per scope, loops credited, of the ops that started
    inside `windows`."""
    scoped = loop_scoped(tr, pt, device, loops)
    if scoped is None:
        return None
    wins = stats.union(windows)
    out: dict = {}
    for s, scope, own in scoped:
        if stats.covered(wins, s, s + 1e-12) > 0:
            out[scope] = out.get(scope, 0.0) + own
    return out


def _path(rec) -> str:
    return rec.get("xplane") or devtrace.find_xplane(os.path.join(harness.CACHE, "trace"))


def read(rec):
    execute = rec["trace"].spans.get("bench.execute")
    if len(rec["devices"]) < 2 or not execute:
        return None
    pt = progtrace.for_record(rec)
    if pt is None:
        return None
    loops = loop_op_names(_path(rec))
    worst = 0.0
    for d in rec["devices"]:
        by = scope_seconds(rec["trace"], pt, d, execute, loops)
        if by is None:
            return None
        worst = max(worst, by.get(SCOPE, 0.0))
    return worst / len(execute) if worst > 0 else None

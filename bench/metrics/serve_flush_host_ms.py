"""Time inside a flush (`repro.serve.flush` span) in which the device ran
nothing, per flush of the traced stretch, in ms."""

from bench import progtrace, stats


def read(rec):
    found = progtrace.flushes(rec)
    if found is None:
        return None
    _, spans = found
    busy = rec["trace"].busy(rec["devices"][0])
    idle = sum((s.end - s.start) - stats.covered(busy, s.start, s.end) for s in spans)
    return 1e3 * idle / len(spans)

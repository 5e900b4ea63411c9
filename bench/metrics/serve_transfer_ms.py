"""Host time of the host<->device copies (`repro.copy.*` spans) inside the
flushes (`repro.serve.flush` spans) of the traced stretch, per flush, in
ms."""

from bench import progtrace


def read(rec):
    found = progtrace.flushes(rec)
    if found is None:
        return None
    pt, spans = found
    windows = [(s.start, s.end) for s in spans]
    copies = progtrace.spans_in(pt, progtrace.COPY_PREFIX, windows)
    return 1e3 * progtrace.wall(copies, windows) / len(spans)

"""Host time of the block-cyclic layout (`repro.layout` spans: A scattered
into its blocks before the upload, the factor blocks gathered into F after
the download) inside `bench.solution`, per solution.  A one-chip trace
reads nothing."""

from bench import progtrace

LAYOUT = "repro.layout"


def read(rec):
    solutions = rec["trace"].spans.get("bench.solution")
    if len(rec["devices"]) < 2 or not solutions:
        return None
    pt = progtrace.for_record(rec)
    if pt is None:
        return None
    found = progtrace.spans_in(pt, LAYOUT, solutions)
    if not found:
        return None
    return progtrace.wall(found, solutions) / len(solutions)

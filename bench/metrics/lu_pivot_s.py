"""Device time of the pivot-row steps (innermost scopes `lu.pivot_gather`:
one-hot, U00, L00, R01; and `lu.pivot_writeback`: U01 into the pivot rows)
inside `plan.execute`, per solution."""

from bench import progtrace


def read(rec):
    return progtrace.lu_scope_per_solution(rec, ("lu.pivot_gather", "lu.pivot_writeback"))

"""Device time of the trailing update (innermost scope `lu.update`: the
fused TRSM -> Schur kernel and the XLA glue around it) inside
`plan.execute`, per solution."""

from bench import progtrace


def read(rec):
    return progtrace.lu_scope_per_solution(rec, ("lu.update",))

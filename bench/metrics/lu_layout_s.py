"""Device time of the panel kernel's layout ops (innermost scope
`lu.layout`: the transposes around `lu_panel`, and the copy of F that XLA's
layout assignment inserts for them) inside `plan.execute`, per solution."""

from bench import progtrace


def read(rec):
    return progtrace.lu_scope_per_solution(rec, ("lu.layout",))

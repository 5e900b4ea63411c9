"""Host time of the solver's host<->device copies (`repro.copy.*` spans)
inside `bench.solution`, per solution."""

from bench import progtrace


def read(rec):
    found = progtrace.solution_copies(rec)
    if found is None:
        return None
    copies, solutions = found
    return progtrace.wall(copies, solutions) / len(solutions)

"""Bytes the solver copies between host and device (the `bytes` argument
of its `repro.copy.*` spans) inside `bench.solution`, per solution, in GB."""

from bench import progtrace


def read(rec):
    found = progtrace.solution_copies(rec)
    if found is None:
        return None
    copies, solutions = found
    return sum(s.args.get("bytes", 0) for s in copies) / len(solutions) / 1e9

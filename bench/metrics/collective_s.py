"""Device self time of the collective ops (all-reduce, collective-permute,
all-gather, reduce-scatter, all-to-all, and their -start/-done halves) that
started inside `plan.execute` (the `bench.execute` spans), per solution, on
the chip where it is largest: the step waits for the slowest chip.

An op is a collective by its HLO opcode, or by its instruction name where
an async wrapper hides the opcode.  A one-chip trace reads
nothing."""

import re

from bench import devtrace, stats

KINDS = ("all-reduce", "collective-permute", "all-gather", "reduce-scatter", "all-to-all")
_KIND = "(?:" + "|".join(KINDS) + ")(?:-start|-done)?"
_OPCODE = re.compile(r"(?<![\w.%-])" + _KIND + r"\(")
_NAME = re.compile("^" + _KIND + "$")


def is_collective(hlo_text: str) -> bool:
    return bool(_NAME.match(devtrace.short_name(hlo_text))
                or _OPCODE.search(hlo_text.split(" = ", 1)[-1]))


def per_chip(tr, device, windows) -> float:
    """Self time of the collectives that started inside `windows`."""
    wins = stats.union(windows)
    inside = [(s, e, "%collective = " if is_collective(name) else "%other = ")
              for s, e, name in tr.ops[device] if stats.covered(wins, s, s + 1e-12) > 0]
    return devtrace.self_times(inside).get("collective", 0.0)


def read(rec):
    execute = rec["trace"].spans.get("bench.execute")
    if len(rec["devices"]) < 2 or not execute:
        return None
    worst = max(per_chip(rec["trace"], d, execute) for d in rec["devices"])
    return worst / len(execute) if worst > 0 else None

"""Share of the traced solutions' wall time in which the device ran no
operation, in %."""

from bench import stats


def read(rec):
    spans = rec["trace"].spans.get("bench.solution")
    if not spans:
        return None
    shares = [stats.idle_share(rec["trace"].busy(d), spans) for d in rec["devices"]]
    return sum(shares) / len(shares)

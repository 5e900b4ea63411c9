"""Share of the traced stretch of the serving window in which the device
ran no operation, in %."""

from bench import stats


def read(rec):
    if "counters" not in rec:
        return None
    spans = rec["trace"].spans.get("bench.window")
    if not spans:
        return None
    shares = [stats.idle_share(rec["trace"].busy(d), spans) for d in rec["devices"]]
    return sum(shares) / len(shares)

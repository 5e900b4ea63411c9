"""The distributed factorization's share of its roofline, in %.

The work is `factor_roofline`'s, counted from N whatever implements it,
(2/3) N^3 flops and 2 N^2 words (A in, F out), divided over the cell's
chips.  The time is the longest per-chip device-busy time inside the
`bench.execute` spans, per solution: the slowest chip sets the pace.  Some
chip does at least its share of the work, so no split between the chips
can read over 100%.  A one-chip trace reads nothing."""

from bench import stats


def read(rec):
    spans = rec["trace"].spans.get("bench.execute")
    if len(rec["devices"]) < 2 or not spans:
        return None
    chips = len(rec["devices"])
    t = max(sum(stats.covered(rec["trace"].busy(d), s, e) for s, e in spans)
            for d in rec["devices"]) / len(spans)
    if t <= 0:
        return None
    n = rec["work"]["N"]
    flops = 2.0 / 3.0 * n ** 3 / chips
    nbytes = 2.0 * rec["work"]["itemsize"] * n ** 2 / chips
    return rec["peaks"].roofline_share(flops, nbytes, t, rec["device_kind"])

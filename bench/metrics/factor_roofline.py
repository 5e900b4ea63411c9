"""The factorization's share of the chip's roofline, in %.

Work is counted from N, whatever implements it: (2/3) N^3 flops and
2 N^2 words (A in, F out).  The time is the device-busy time inside the
`bench.execute` spans.  The compute bound is the published bf16 peak; an
f32 program runs as several bf16 passes at HIGHEST precision, so the
share is conservative and no implementation can read over 100%."""

from bench import stats


def read(rec):
    spans = rec["trace"].spans.get("bench.execute")
    if not spans:
        return None
    n = rec["work"]["N"]
    busy = rec["trace"].busy(rec["devices"][0])
    t = sum(stats.covered(busy, s, e) for s, e in spans) / len(spans)
    if t <= 0:
        return None
    flops = 2.0 / 3.0 * n ** 3
    nbytes = 2.0 * rec["work"]["itemsize"] * n ** 2
    return rec["peaks"].roofline_share(flops, nbytes, t, rec["device_kind"])

"""Nearest-rank p99 of the latency of every request due in the window, from
its scheduled arrival to its answer, in ms.  In a traced run the profiler
covers the window's last seconds and slows the host there, so this reads
higher than an untraced run would.  A window that lost requests has no
finite tail: the result line's `failed` counts them, and this reads nothing."""

import math


def read(rec):
    v = rec.get("latency_p99_ms")
    if v is None or not math.isfinite(v):
        return None
    return v

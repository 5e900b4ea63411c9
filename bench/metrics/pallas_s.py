"""Device time of the Pallas (Mosaic) kernels inside `plan.execute`, per
solution."""

from bench import devtrace


def read(rec):
    spans = rec["trace"].spans.get("bench.execute")
    if not spans:
        return None
    t = devtrace.mosaic_seconds(rec["trace"], rec["devices"][0], spans)
    return t / len(spans) if t > 0 else None

"""Host-clock time of `Factorization.solve` up to a ready x, per solution."""


def read(rec):
    spans = rec.get("spans", {}).get("solve")
    if not spans:
        return None
    return sum(e - s for s, e in spans) / len(spans)

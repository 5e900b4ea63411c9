"""Host time of `plan.execute` per solution that is not device work: the
benchmark's `bench.execute` spans minus the device-busy time inside them
(host-device copies, dtype casts, `np.asarray`)."""

from bench import stats


def read(rec):
    spans = rec["trace"].spans.get("bench.execute")
    if not spans:
        return None
    busy = rec["trace"].busy(rec["devices"][0])
    wall = sum(e - s for s, e in spans)
    inside = sum(stats.covered(busy, s, e) for s, e in spans)
    return (wall - inside) / len(spans)

"""Systems served over flushes times `max_batch`, in %, from the async
engine's cumulative counters differenced over the run."""


def read(rec):
    c = rec.get("counters")
    if not c or not c["flushes"]:
        return None
    return 100.0 * c["served"] / (c["flushes"] * c["max_batch"])

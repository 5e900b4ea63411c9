"""Padded matrix cells over cells computed by the batched slots, in %: the
engine's cumulative `batch_pad_waste`.  Set-up's `warm_slots` leaves the
counters as they were, so this covers the window's requests alone."""


def read(rec):
    c = rec.get("counters")
    if not c or not c["flushes"]:
        return None
    return 100.0 * c["batch_pad_waste"]

"""Mean wait of a request from its submit to the drain of its flush, on
the engine's clock: the `wait_s_sum` over the `systems` of the
`repro.serve.flush` spans in the traced stretch, in ms."""

from bench import progtrace


def read(rec):
    found = progtrace.flushes(rec)
    if found is None:
        return None
    _, spans = found
    systems = sum(s.args.get("systems", 0) for s in spans)
    if not systems:
        return None
    return 1e3 * sum(s.args.get("wait_s_sum", 0.0) for s in spans) / systems

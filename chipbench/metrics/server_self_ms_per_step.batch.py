"""Server tick self time: the program's ``server.step`` spans less the
``engine.step`` and ``engine.admit`` spans inside them (what is left is
expiry, queue-wait bookkeeping, event polling and resolution), per tick
of the window."""
import program_spans as ps


def read(rd):
    recs = ps.records(rd)
    ticks = ps.named(recs, "server.step")
    if not ticks:
        return None
    inner = ps.named(recs, "engine.step") + ps.named(recs, "engine.admit")
    return 1e3 * (ps.seconds(ticks) - ps.nested_seconds(ticks, inner)) \
        / len(ticks)

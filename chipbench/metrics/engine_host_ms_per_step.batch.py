"""Engine step host work: the program's ``engine.step`` spans less the
``engine.readback`` (waiting on the decode program) and ``vote`` spans
inside them, per step of the window."""
import program_spans as ps


def read(rd):
    recs = ps.records(rd)
    steps = ps.named(recs, "engine.step")
    if not steps:
        return None
    wait = ps.nested_seconds(steps, ps.named(recs, "engine.readback"))
    votes = ps.nested_seconds(steps, ps.named(recs, "vote"))
    return 1e3 * (ps.seconds(steps) - wait - votes) / len(steps)

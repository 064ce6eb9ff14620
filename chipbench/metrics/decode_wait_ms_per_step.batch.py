"""Decode wait: the program's ``engine.readback`` spans (the host blocked
on the decode program's results) inside ``engine.step``, per step of the
window."""
import program_spans as ps


def read(rd):
    recs = ps.records(rd)
    steps = ps.named(recs, "engine.step")
    if not steps:
        return None
    return 1e3 * ps.nested_seconds(
        steps, ps.named(recs, "engine.readback")) / len(steps)

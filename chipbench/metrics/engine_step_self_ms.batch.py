"""Engine step self time: the harness span around ``BasecallEngine.step``
less the stitch/vote spans inside it, mean over the window's steps."""


def read(rd):
    steps = rd.span_list("engine_step")
    if not steps:
        return None
    total = sum(b - a for a, b in steps)
    return 1e3 * (total - rd.nested_seconds("engine_step", "vote")) \
        / len(steps)

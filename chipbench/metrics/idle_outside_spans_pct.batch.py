"""Device idle time the program's spans do not explain: the share of the
first device's idle-gap time (gaps cut as ``xplane.reduce`` cuts them)
during which no ``helix/`` span is open on the host, both on the
profiler's clock."""
import program_spans as ps


def read(rd):
    layout = ps.trace_layout(rd)
    if layout is None:
        return None
    gaps, spans = layout
    if not gaps or not spans:
        return None
    return ps.idle_outside(gaps, spans)

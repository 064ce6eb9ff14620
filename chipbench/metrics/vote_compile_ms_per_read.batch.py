"""Stitch/vote compiling per read: the compile seconds (JAX tracing,
lowering and backend compiles) the program charges to its ``vote`` spans
in the window, mean over the window's votes."""
import program_spans as ps


def read(rd):
    votes = ps.named(ps.records(rd), "vote")
    if not votes:
        return None
    return 1e3 * sum(r.compile_s for r in votes) / len(votes)

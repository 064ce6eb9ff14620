"""Stitch/vote running per read: each ``vote`` span of the window less
the compile seconds charged to it, mean over the window's votes."""
import program_spans as ps


def read(rd):
    votes = ps.named(ps.records(rd), "vote")
    if not votes:
        return None
    return 1e3 * sum(r.t1 - r.t0 - r.compile_s for r in votes) / len(votes)

"""Stitch/vote per read: the harness span around each
``BasecallResult.from_window_reads`` call in the window, compiles
included, mean."""


def read(rd):
    votes = rd.span_list("vote")
    if not votes:
        return None
    return 1e3 * sum(b - a for a, b in votes) / len(votes)

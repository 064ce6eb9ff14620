"""Admission per read: the program's ``admit.read`` spans in the window
(chunking one read's signal into windows and its frame lengths), mean."""
import program_spans as ps


def read(rd):
    admits = ps.named(ps.records(rd), "admit.read")
    if not admits:
        return None
    return 1e3 * ps.seconds(admits) / len(admits)

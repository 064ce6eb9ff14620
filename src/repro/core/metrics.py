"""Base-calling metrics: edit distance (paper §2.2), read/vote error rates."""
from __future__ import annotations

import numpy as np


def edit_distance(a, b) -> int:
    """Levenshtein distance — the paper's base-calling error count.

    One numpy pass per symbol of the longer sequence: substitutions and
    deletions come from the previous row, and the chain of insertions
    along the row is a running minimum, ``cur[j] = j + min_{k<=j}
    (cur[k] - k)``."""
    a, b = np.asarray(list(a)), np.asarray(list(b))
    if len(a) < len(b):
        a, b = b, a
    j = np.arange(len(b) + 1)
    prev = j
    for i, ca in enumerate(a, 1):
        cur = np.empty_like(prev)
        cur[0] = i
        cur[1:] = np.minimum(prev[1:] + 1, prev[:-1] + (b != ca))
        prev = np.minimum.accumulate(cur - j) + j
    return int(prev[-1])


def error_rate(pred, pred_len, truth, truth_len) -> float:
    """Mean edit distance / truth length over a batch (numpy arrays)."""
    total_err = 0
    total_len = 0
    for p, pl, t, tl in zip(pred, pred_len, truth, truth_len):
        total_err += edit_distance(p[: int(pl)], t[: int(tl)])
        total_len += int(tl)
    return total_err / max(total_len, 1)


def accuracy(pred, pred_len, truth, truth_len) -> float:
    return 1.0 - error_rate(pred, pred_len, truth, truth_len)

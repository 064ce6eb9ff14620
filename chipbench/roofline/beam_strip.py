"""Operations and bytes of one ``beam_merge_multiframe`` call: the hash
beam decoder's state advanced through ``F`` frames for ``B`` rows.

Operations: per frame and row, the merge compares every pair of the
``C = W x A`` candidates' prefix keys (``C * C``); sorting the merged
masses and the log-sum-exp arithmetic are not counted.

Bytes: the strip's log-probs ``(F, B, A)`` float32 and activity flags
``(F, B)`` int32 in, the winner indices ``(F, B, W)`` int32 out, and the
five ``(B, W)`` state arrays (key, two log-masses, last symbol, length)
in and out.
"""
import re



def cost(F: int, B: int, W: int, A: int) -> tuple:
    C = W * A
    ops = F * B * C * C
    nbytes = 4 * (F * B * A + F * B + F * B * W + 2 * 5 * B * W)
    return ops, nbytes


_OP = re.compile(r"= \(s32\[(\d+),(\d+),(\d+)\]\S*, .*?\) custom-call\("
                 r"f32\[(\d+),(\d+),(\d+)\]")


def parse(op: str):
    """(F, B, W, A) of a ``beam_merge_multiframe`` launch in a TPU trace,
    else None: a ``tpu_custom_call`` from ``(F, B, A)`` float32 log-probs
    whose results start with the ``(F, B, W)`` int32 winner indices."""
    m = _OP.search(op)
    if m is None or "tpu_custom_call" not in op:
        return None
    F, B, W, f, b, A = map(int, m.groups())
    return (F, B, W, A) if (f, b) == (F, B) else None

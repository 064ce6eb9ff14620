"""Operations and bytes of one ``gru_seq`` call: one GRU layer's whole
recurrent walk over ``T`` frames for ``B`` rows.

Operations: the hidden products the GRU needs per frame and row, three
gates of ``H x H`` multiply-accumulates (two operations each).  The
input projection runs outside the kernel and is not counted; nor are
the gate nonlinearities.

Bytes: what the call must move through HBM once: the input projection
``(T, B, 3H)`` in, the hidden sequence ``(T, B, H)`` out, the recurrent
weights ``(H, 3H)``, the bias ``3H`` and the initial state ``(B, H)``,
all float32.
"""
import re



def cost(T: int, B: int, H: int) -> tuple:
    ops = 2 * T * B * 3 * H * H
    nbytes = 4 * (T * B * 3 * H + T * B * H + H * 3 * H + 3 * H + B * H)
    return ops, nbytes


_OP = re.compile(r"= f32\[(\d+),(\d+),(\d+)\]\S* custom-call\("
                 r"f32\[(\d+),(\d+),(\d+)\]")


def parse(op: str):
    """(T, B, H) of a ``gru_seq`` launch in a TPU trace, else None: a
    ``tpu_custom_call`` from ``(T, B, 3H)`` float32 to ``(T, B, H)``."""
    m = _OP.search(op)
    if m is None or "tpu_custom_call" not in op:
        return None
    T, B, H, t, b, h3 = map(int, m.groups())
    return (T, B, H) if (t, b, h3) == (T, B, 3 * H) else None

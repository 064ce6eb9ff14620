"""``beam_merge_multiframe`` kernel time in the trace against its
roofline (``roofline/beam_strip.py``), as for ``gru_seq``.  Launches are
found by their shapes (``beam_strip.parse``)."""
from roofline import beam_strip


def read(rd):
    least, secs = 0.0, 0.0
    for op, s in (rd.trace or {}).get("op_s", {}).items():
        shape = beam_strip.parse(op)
        if shape is None:
            continue
        ops, nbytes = beam_strip.cost(*shape)
        least += rd.trace["op_n"][op] * max(
            ops / rd.peaks["bf16_flops"], nbytes / rd.peaks["hbm_bytes_per_s"])
        secs += s
    return 100.0 * least / secs if secs > 0 else None

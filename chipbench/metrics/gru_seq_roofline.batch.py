"""``gru_seq`` kernel time in the trace against its roofline
(``roofline/gru_seq.py``): per launch, the larger of its operations over
the peak of float32 products at ``HIGHEST`` (as the kernel runs them)
and its bytes over HBM bandwidth, summed over the launches, over the
launches' summed device time.  Launches are found by their shapes
(``gru_seq.parse``)."""
from roofline import gru_seq


def read(rd):
    least, secs = 0.0, 0.0
    for op, s in (rd.trace or {}).get("op_s", {}).items():
        shape = gru_seq.parse(op)
        if shape is None:
            continue
        ops, nbytes = gru_seq.cost(*shape)
        least += rd.trace["op_n"][op] * max(
            ops / rd.peaks["f32_highest_flops"],
            nbytes / rd.peaks["hbm_bytes_per_s"])
        secs += s
    return 100.0 * least / secs if secs > 0 else None

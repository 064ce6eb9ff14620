"""The whole step's share of the chip's peak: the DNN products of the
windows decoded in the measured window, each at the peak of the
precision it runs at (``roofline/basecaller_dnn.py``), over the chips'
seconds of the window on the host clock.  It takes nothing from the
trace, so every traced run reports it, and it reads at most
``decode_mfu.batch``: the decode program's device time lies inside the
window."""
from roofline import basecaller_dnn


def read(rd):
    windows = rd.run.get("windows", 0)
    if not windows or rd.run["window_s"] <= 0:
        return None
    return 100.0 * windows * basecaller_dnn.seconds_at_peak(rd.cfg, rd.peaks) \
        / (rd.run["window_s"] * rd.chips)

"""The decode step's share of the chip's peak: the DNN products of the
windows decoded in the window, each at the peak of the precision it runs
at (``roofline/basecaller_dnn.py``), over the device time of the decode
step's program in the trace.

The decode step's program is the XLA module that runs once in every
engine step of the window; of such modules, the one with the most
device time.  Both are per device: a device's share of the windows over
its mean module time."""
from roofline import basecaller_dnn


def read(rd):
    t = rd.trace or {}
    steps, n = t.get("steps", 0), rd.run.get("windows", 0)
    every_step = [m for m, k in t.get("module_n", {}).items()
                  if steps and round(k) == steps]
    if not every_step or not n:
        return None
    secs = max(t["module_s"][m] for m in every_step)
    ideal = n / rd.chips * basecaller_dnn.seconds_at_peak(rd.cfg, rd.peaks)
    return 100.0 * ideal / secs if secs > 0 else None

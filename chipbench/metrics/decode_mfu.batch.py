"""The decode step's share of the chip's peak, per execution of the
decode program: the DNN products of the windows one engine step decodes
on one device, each at the peak of the precision it runs at
(``roofline/basecaller_dnn.py``), over the mean device time of one
execution of the decode program in the trace.

The decode program is the XLA module the program names
``jit_decode_windows`` (every module whose name starts so, summed).  A
step's windows are the run's mean, ``windows / steps``, shared by the
chips; an execution's time is the module's traced seconds over its
traced executions.  So the share reads the same however many of the
run's executions the trace holds, and when it holds them all it equals
the run's windows at peak over the module's whole device time."""
from roofline import basecaller_dnn

MODULE = "jit_decode_windows"


def read(rd):
    t = rd.trace or {}
    mods = [m for m in t.get("module_n", {}) if m.startswith(MODULE)]
    windows, steps = rd.run.get("windows", 0), rd.run.get("steps", 0)
    if not mods or not windows or not steps:
        return None
    secs = sum(t["module_s"][m] for m in mods)
    execs = sum(t["module_n"][m] for m in mods)
    if secs <= 0:
        return None
    ideal = windows / steps / rd.chips \
        * basecaller_dnn.seconds_at_peak(rd.cfg, rd.peaks)
    return 100.0 * ideal / (secs / execs)

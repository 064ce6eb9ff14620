"""Share of the traced window in which no operation ran on the device:
``100 * (1 - busy_s / window_s)``, busy the union of op intervals."""


def read(rd):
    t = rd.trace
    if not t or not t.get("devices") or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])

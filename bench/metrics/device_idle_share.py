"""Share of the traced window in which no operation ran on the device
(dispatch layer, ``core/executor.py``): 100 * (1 - busy / window), with
busy the union of the device's operation intervals, averaged over chips."""


def read(summary):
    window, busy = summary.get("window_s"), summary.get("busy_s")
    if not window or busy is None:
        return None
    return 100.0 * (1.0 - busy / window)

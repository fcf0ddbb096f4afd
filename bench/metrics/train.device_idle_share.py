"""Share of the traced train window in which no op ran on the device
(1 - busy / window, busy the union of device op intervals)."""


def read(r):
    if r.trace is None or not r.trace.devices or r.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - r.trace.busy_s / r.trace.window_s)

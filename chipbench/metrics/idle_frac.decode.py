"""Share of the traced decode window in which no operation ran on the
device (1 - union of the device-op intervals over the window)."""
from chipbench import trace as tr


def read(r, trace):
    return 100.0 * (1.0 - tr.busy_ns(trace) / tr.window_ns(trace))

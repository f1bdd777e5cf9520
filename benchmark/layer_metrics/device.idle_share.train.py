"""Share of the traced window in which no operation ran on the device:
1 - (union of the device-op intervals) / window, in percent, averaged over
the chips used."""

from benchmark import trace_reduce


def read(m):
    busy_s, window_s = trace_reduce.busy_and_window(m.trace)
    return 100.0 * (1.0 - busy_s / window_s) if window_s > 0 else None

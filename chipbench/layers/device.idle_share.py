"""Share of the traced window in which no operation ran on the device:
1 - union of the device-op intervals / window, from the profiler trace."""


def read(before, after, trace, cell):
    busy_s, window_s = trace.get("busy_s"), trace.get("window_s")
    if busy_s is None or not window_s:
        return None
    return 100.0 * (1.0 - busy_s / window_s)

"""Mean time of one evaluated batch inside the client (the
``checks.dispatch`` timer around engine selection, lowering, device
dispatch, D2H and the host filter), over the window."""

from _timers import window_mean_ms


def read(before, after, trace, cell):
    return window_mean_ms(before, after, "checks.dispatch")

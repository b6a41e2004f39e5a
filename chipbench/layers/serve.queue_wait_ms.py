"""Mean wait of a submission between submit and batch formation over the
window, from the ``serve.queue_wait_s`` timer's count and total (the
sample ring holds the last 2,048 only, so it cannot be cut to a window)."""

from _timers import window_mean_ms


def read(before, after, trace, cell):
    return window_mean_ms(before, after, "serve.queue_wait_s")

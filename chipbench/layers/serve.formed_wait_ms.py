"""Mean time a formed batch waited for the one dispatcher thread
(formation start to dispatch start): the ``serve.formed_wait`` stage's
timer, one sample a batch."""

from _timers import window_mean_ms


def read(before, after, trace, cell):
    return window_mean_ms(before, after, "serve.formed_wait_s")

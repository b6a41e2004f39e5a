"""Mean time from a submission's future being resolved by the dispatcher
to the answer being in its caller's hand (the woken caller waiting to
run): the ``serve.wake`` stage's timer, one sample a request."""

from _timers import window_mean_ms


def read(before, after, trace, cell):
    return window_mean_ms(before, after, "serve.wake_s")

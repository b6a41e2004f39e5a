"""Mean wait for the device plus the device-to-host copy of one batch's
three result planes on the batch path (``jax.device_get`` in
``check_batch``): the ``engine.fetch`` stage's timer."""

from _timers import window_mean_ms


def read(before, after, trace, cell):
    return window_mean_ms(before, after, "engine.fetch_s")

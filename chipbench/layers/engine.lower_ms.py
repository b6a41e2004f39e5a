"""Mean host lowering of one batch (Relationship objects to interned
int32 query columns, ``DeviceEngine._lower_queries``) over the window:
the ``engine.lower`` stage's timer."""

from _timers import window_mean_ms


def read(before, after, trace, cell):
    return window_mean_ms(before, after, "engine.lower_s")

"""Device time a lookup: the union of the device-op intervals of the traced
window (``busy_s``, as ``device.idle_share`` reads it) over the lookups the
window answered, in ms.  What the candidate expansion, the exact filter's
check dispatches and anything the programs do besides cost the chip, per
operation a caller asked for."""

from _counters import ratio


def read(before, after, trace, cell):
    busy_s = trace.get("busy_s")
    if busy_s is None:
        return None
    return ratio(busy_s, cell["window"].get("lookups", 0), 1000.0)

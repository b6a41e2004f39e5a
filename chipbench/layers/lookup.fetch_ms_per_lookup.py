"""Time a lookup waits for the device and its copies back, in ms: the
seconds of the fused program's, the looped hops' and the exact filter's
fetch stages over the window (``_lookup_stages.FETCH``) by the lookups the
window answered.  Beside ``lookup.device_ms_per_lookup``, what is over it
is the wait behind other callers' device work."""

from _lookup_stages import FETCH, ms_per_lookup


def read(before, after, trace, cell):
    return ms_per_lookup(before, after, cell, FETCH)

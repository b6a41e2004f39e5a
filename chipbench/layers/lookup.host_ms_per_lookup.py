"""Host time a lookup, in ms: the seconds of the lookup path's host stages
over the window (``_lookup_stages.HOST``: resolve, device arguments, the
enqueues, candidate expansion, the exact filter's lowering, host
re-checks, id decode, sort) by the lookups the window answered."""

from _lookup_stages import HOST, ms_per_lookup


def read(before, after, trace, cell):
    return ms_per_lookup(before, after, cell, HOST)

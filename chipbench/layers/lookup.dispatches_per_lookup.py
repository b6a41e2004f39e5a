"""Device dispatches of the candidate expansion a lookup answered in the
window: (``lookup.dispatches`` + ``spmm.dispatches``) / window lookups.  The
exact filter's check dispatches are not in it."""

from _counters import gained, ratio


def read(before, after, trace, cell):
    return ratio(gained(before, after, "lookup.dispatches", "spmm.dispatches"),
                 cell["window"].get("lookups", 0))

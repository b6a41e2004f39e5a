"""Candidates the exact forward filter was asked to check for every id the
window's lookups returned: ``lookup.candidates`` / ids returned."""

from _counters import gained, ratio


def read(before, after, trace, cell):
    return ratio(gained(before, after, "lookup.candidates"),
                 cell["window"].get("ids", 0))

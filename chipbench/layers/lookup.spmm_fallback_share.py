"""Share of the fused lookups that overflowed a capacity of the one-dispatch
program (frontier, emission, candidates, rounds) and ran again on the looped
per-hop SpMV: ``spmm.fallbacks`` / ``lookups.fused``."""

from _counters import gained, ratio


def read(before, after, trace, cell):
    return ratio(gained(before, after, "spmm.fallbacks"),
                 gained(before, after, "lookups.fused"), 100.0)

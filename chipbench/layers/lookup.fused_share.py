"""Share of the window's lookup streams that the fused K-hop SpMM program
(engine/spmm.py) was asked first: ``lookups.fused`` / (``lookups.frontier`` +
``lookups.walker``), one count a stream.  100 % on a snapshot with the
reverse-CSR index and ``spmm`` on; the host walker's share is the rest."""

from _counters import gained, ratio


def read(before, after, trace, cell):
    return ratio(gained(before, after, "lookups.fused"),
                 gained(before, after, "lookups.frontier", "lookups.walker"), 100.0)

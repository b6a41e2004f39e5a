"""Distinct request contexts a lowered batch carries, the rows of the
request-context table the CEL VM reads: ``engine.query_contexts`` over
``engine.context_batches``, counters the program moves once a batch on a
schema with caveats."""

from _counters import gained, ratio


def read(before, after, trace, cell):
    return ratio(gained(before, after, "engine.query_contexts"),
                 gained(before, after, "engine.context_batches"))

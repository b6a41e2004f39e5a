"""Share of the lowering spent on the request contexts (the per-row dedup of
each check's ``caveat_context`` and ``_encode_query_contexts``): the
``engine.context_s`` timer, which the program observes once a batch on a
schema with caveats, over the ``engine.lower`` stage's."""

from _stages import share, window_total_s


def read(before, after, trace, cell):
    return share(window_total_s(before, after, "engine.context_s"),
                 window_total_s(before, after, "engine.lower_s"))

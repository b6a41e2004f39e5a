"""Share of the lowering spent inside ``interner.lookup`` (string key to
node id, for the keys the engine's memo missed): the ``engine.intern_s``
timer, which the program observes once a batch while a profiler session
is live, over the ``engine.lower`` stage's."""

from _stages import share, window_total_s


def read(before, after, trace, cell):
    return share(window_total_s(before, after, "engine.intern_s"),
                 window_total_s(before, after, "engine.lower_s"))

"""Share of the window's lowered check batches that went to a program
reading expiries (a view's ``exp`` gate lane, or the fold's until planes):
the program's ``engine.expiry_batches`` counter over ``intern.batch_calls``,
which it moves once a lowered batch.  None where the program has no such
counter."""

from _counters import gained, ratio


def read(before, after, trace, cell):
    if "engine.expiry_batches" not in after:
        return None
    return ratio(gained(before, after, "engine.expiry_batches"),
                 gained(before, after, "intern.batch_calls"), 100.0)

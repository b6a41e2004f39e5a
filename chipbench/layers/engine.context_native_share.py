"""Share of the window's lowered batches on a schema with caveats whose
request contexts the native pass grouped (``native/lower.cpp``
``gl_contexts``): the program's ``engine.context_native_batches`` counter
over ``engine.context_batches``, which it moves once such a batch.  None
where the program has no such counter."""

from _counters import gained, ratio


def read(before, after, trace, cell):
    if "engine.context_native_batches" not in after:
        return None
    return ratio(gained(before, after, "engine.context_native_batches"),
                 gained(before, after, "engine.context_batches"), 100.0)

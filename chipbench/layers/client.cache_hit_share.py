"""Share of the window's cache look-ups that the verdict cache answered:
the program's ``cache.hits`` counter over ``cache.hits`` + ``cache.misses``
(engine/vcache.py; rows that bypass the cache count in neither).  None
where the program made no look-up: no cache on the client."""


def read(before, after, trace, cell):
    hits = after.get("cache.hits", 0.0) - before.get("cache.hits", 0.0)
    misses = after.get("cache.misses", 0.0) - before.get("cache.misses", 0.0)
    if hits + misses <= 0:
        return None
    return 100.0 * hits / (hits + misses)

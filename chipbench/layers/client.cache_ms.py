"""Time of the client's cache layer a formed batch, on the dispatcher
thread: the seconds of the ``client.cache_read`` and ``client.cache_write``
stages over the window, by the batches that entered the layer (the count
of ``client.cache_read``)."""

from _cache_stages import cache_layer_s


def read(before, after, trace, cell):
    total_s = cache_layer_s(before, after)
    if total_s is None:
        return None
    batches = (after["client.cache_read_s.count"]
               - before.get("client.cache_read_s.count", 0))
    return 1000.0 * total_s / batches

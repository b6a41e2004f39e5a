"""Shared by the readers of the client's cache layer: the stages
``client.cache_read`` (keys, look-up, pending list, dedup map) and
``client.cache_write`` (fan-out of the verdicts, insert and whatever
eviction it triggers) around the direct evaluation of a formed batch."""

from _stages import window_total_s


def cache_layer_s(before, after):
    """Seconds both stages gained over the window; None where the program
    has no ``client.cache_read`` stage or it gained no sample.  A batch
    that the cache answered whole has no write stage."""
    read_s = window_total_s(before, after, "client.cache_read_s")
    if read_s is None:
        return None
    return read_s + (window_total_s(before, after, "client.cache_write_s") or 0.0)

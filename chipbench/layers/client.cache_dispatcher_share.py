"""Share of the one dispatcher thread's running time that the client's
cache layer takes: the seconds of the ``client.cache_read`` and
``client.cache_write`` stages over the seconds of the ``serve.dispatch_s``
timer, which encloses both."""

from _cache_stages import cache_layer_s
from _stages import share, window_total_s


def read(before, after, trace, cell):
    return share(cache_layer_s(before, after),
                 window_total_s(before, after, "serve.dispatch_s"))

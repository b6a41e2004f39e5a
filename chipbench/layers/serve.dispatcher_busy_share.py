"""Share of the window in which the one dispatcher thread was running a
batch: seconds of the ``serve.dispatch_s`` timer over the window's
seconds."""

from _stages import share, window_total_s


def read(before, after, trace, cell):
    return share(window_total_s(before, after, "serve.dispatch_s"),
                 cell["window"]["seconds"])

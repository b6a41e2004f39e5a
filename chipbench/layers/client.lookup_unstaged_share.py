"""Share of a lookup's time in no stage, in %: 100 * (1 - the seconds of
the lookup path's host and fetch stages / the seconds of ``client.lookup``,
the first ``next`` of each id generator to its end), over the window.
What is left is the client's generator loop over the ids and the Python
between stages."""

from _lookup_stages import FETCH, HOST, lookup_s, stages_s


def read(before, after, trace, cell):
    whole = lookup_s(before, after)
    if not whole:
        return None
    return 100.0 * (1.0 - stages_s(before, after, HOST + FETCH) / whole)

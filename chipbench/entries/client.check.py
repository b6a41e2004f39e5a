"""Entry ``client.check``: ``Client.check(ctx, cs, *rels)``, one call a
request — the bulk path.  Requests, operations and judging: ``_checks.py``."""

from _checks import (RATES, flipped, judge, reference, requests, short,  # noqa: F401
                     tally, warm_requests)


def bind(program):
    """The one call that is timed."""
    return lambda ctx, req: program.client.check(ctx, program.cs, *req.rels)

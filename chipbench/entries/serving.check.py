"""Entry ``serving.check``: ``ServingHandle.check(ctx, *rels)`` on one
``with_serving`` handle pinned to the mix's consistency — the served path
(batcher, tiers, one dispatcher).  Requests, operations and judging:
``_checks.py``."""

from _checks import (RATES, flipped, judge, reference, requests, short,  # noqa: F401
                     tally, warm_requests)


def bind(program):
    """Opens the handle (``program.close`` closes it) and returns the one
    call that is timed."""
    handle = program.handle = program.client.with_serving(cs=program.cs)
    return lambda ctx, req: handle.check(ctx, *req.rels)

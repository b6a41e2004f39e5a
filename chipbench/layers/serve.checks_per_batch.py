"""Checks in one coalesced batch the serving former dispatched, over the
window: ``serve.checks`` / ``serve.batches``."""


def read(before, after, trace, cell):
    batches = after.get("serve.batches", 0) - before.get("serve.batches", 0)
    if batches <= 0:
        return None
    return (after.get("serve.checks", 0) - before.get("serve.checks", 0)) / batches

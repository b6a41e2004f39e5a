"""Median latency of the window's lookups on the harness's clock (send to
last id drained).  Per layer, not end to end: a 30 s window holds some 200
lookups, and which of the heaviest fall into it swings the tail."""


def read(before, after, trace, cell):
    return cell["window"].get("request_p50_ms")

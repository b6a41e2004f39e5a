"""95th percentile latency of the window's lookups on the harness's clock:
the tail of some 200 requests, set by the few answers of tens of thousands
of ids that fell into the window — too wide a spread for a bound (PERF.md §2),
so it stands here."""


def read(before, after, trace, cell):
    return cell["window"].get("request_p95_ms")

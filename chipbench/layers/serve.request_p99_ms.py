"""99th percentile of one client call in the traced window, submit to
answer in hand, by the harness's own clock: the tail beyond the end-to-end
``request_p95_ms``.  It stands here and not among the end-to-end metrics
because its run-to-run spread on a shared host asks for a bound over the
contract's limit (PERF.md §2)."""


def read(before, after, trace, cell):
    return cell["window"]["request_p99_ms"]

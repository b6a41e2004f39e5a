"""Share of the window's checks that the host answered (the oracle served
the request, or resolved an overflowed or conditional item) instead of
the device.  Counters of utils/metrics.default."""

HOST = ("checks.oracle", "checks.fallback_overflow",
        "checks.fallback_conditional")


def read(before, after, trace, cell):
    checks = cell["window"]["checks"]
    if not checks:
        return None
    on_host = sum(after.get(k, 0.0) - before.get(k, 0.0) for k in HOST)
    return 100.0 * on_host / checks

"""Shared by the readers of the program's counters (utils/metrics.default),
over the window."""


def gained(before, after, *counters) -> float:
    """What the counters gained over the window, added up."""
    return sum(after.get(k, 0.0) - before.get(k, 0.0) for k in counters)


def ratio(part, whole, scale: float = 1.0):
    """scale * part / whole; None where the whole counted nothing."""
    return scale * part / whole if whole > 0 else None

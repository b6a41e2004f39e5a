"""Shared by the readers of the program's timers."""


def window_mean_ms(before, after, timer: str):
    """Mean of a utils/metrics timer over the window, in ms, from the
    registry's cumulative ``.count`` / ``.total_s``; None with no sample."""
    n = after.get(f"{timer}.count", 0) - before.get(f"{timer}.count", 0)
    if n <= 0:
        return None
    total = after.get(f"{timer}.total_s", 0.0) - before.get(f"{timer}.total_s", 0.0)
    return 1000.0 * total / n

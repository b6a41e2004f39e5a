"""Shared by the readers of the program's stage timers (utils/trace.py
``stage``: ``<name>_s`` wall, ``<name>_cpu_s`` thread CPU), over the
window, from the registry's cumulative ``.count`` / ``.total_s``."""


def window_total_s(before, after, timer: str):
    """Seconds the timer gained over the window; None where the program
    has no such timer or it gained no sample."""
    n = after.get(f"{timer}.count", 0) - before.get(f"{timer}.count", 0)
    if n <= 0:
        return None
    return after.get(f"{timer}.total_s", 0.0) - before.get(f"{timer}.total_s", 0.0)


def share(part_s, whole_s):
    """100 * part / whole, None where either was not read."""
    if part_s is None or not whole_s:
        return None
    return 100.0 * part_s / whole_s

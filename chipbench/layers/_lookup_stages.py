"""Shared by the readers of the lookup path's stages (utils/trace.py
``stage``, one ``<stage>_s`` timer each), over the window: which stages are
the host's own work and which wait for the device.  Only stage timers are
read, never a timer nested inside a stage (``engine.intern_s``,
``engine.context_s``, ``*_cpu_s``), so no second counts the same time
twice."""

from _stages import window_total_s

#: the host's work in a lookup: the query resolved and its stream opened,
#: host values made device arguments, the fused program, a looped hop and
#: the exact filter's check enqueued, host candidate work between
#: dispatches, the filter's lowering, host re-checks, the id decode and
#: the final sort
HOST = ("lookup.resolve", "lookup.args", "lookup.fused.enqueue",
        "lookup.hop.enqueue", "lookup.expand", "engine.lower",
        "engine.enqueue", "lookup.oracle", "lookup.decode", "lookup.sort")

#: the waits for the device and the copies back: the fused program's, a
#: looped hop's and the exact filter's
FETCH = ("lookup.fused.fetch", "lookup.hop.fetch", "engine.fetch")


def lookup_s(before, after):
    """Seconds of the timer-only stage ``client.lookup`` (first ``next`` of
    a lookup's id generator to its end) over the window; None where the
    program times no lookup, as one without the lookup stages does not."""
    return window_total_s(before, after, "client.lookup_s")


def stages_s(before, after, stages) -> float:
    """Seconds the stages gained over the window, added up (a stage the
    window never entered adds 0)."""
    return sum(window_total_s(before, after, f"{s}_s") or 0.0 for s in stages)


def ms_per_lookup(before, after, cell, stages):
    """1000 * the stages' seconds / the window's lookups; None where the
    program has no lookup stages or the window answered none."""
    lookups = cell["window"].get("lookups", 0)
    if lookup_s(before, after) is None or lookups <= 0:
        return None
    return 1000.0 * stages_s(before, after, stages) / lookups

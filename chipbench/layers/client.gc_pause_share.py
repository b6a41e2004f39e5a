"""Share of the window spent in full (generation 2) collections of the
Python collector: seconds of the ``host.gc_s`` timer, fed by the hook the
client installs, over the window's seconds.  0 where the hook is there
and no full collection ran; left out where the program has no hook."""


def read(before, after, trace, cell):
    if "host.gc_s.count" not in after or not cell["window"]["seconds"]:
        return None
    paused = after["host.gc_s.total_s"] - before.get("host.gc_s.total_s", 0.0)
    return 100.0 * paused / cell["window"]["seconds"]

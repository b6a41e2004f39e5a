"""``engine.window_compiles`` for the cells whose throughput is
``lookups_per_s``: backend compiles JAX reported inside the window.  Every
lookup has hop, emission and filter shapes of its own, which is why the mix
warms the whole pool once; a warm window has none."""


def read(before, after, trace, cell):
    return float(cell["window"]["compile_requests"])

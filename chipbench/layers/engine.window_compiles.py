"""Backend compiles JAX reported inside the window
(``/jax/core/compile/backend_compile_duration`` events): a warm window
has none."""


def read(before, after, trace, cell):
    return float(cell["window"]["compile_requests"])

"""Share of the lowering's wall time its thread was not on a CPU
(waiting for the GIL, a lock or the scheduler): 1 - thread CPU seconds /
wall seconds of the ``engine.lower`` stage, both read at the same two
points of every batch while a profiler session is live (the CPU clock
is a system call, so the program reads it only then)."""

from _stages import share, window_total_s


def read(before, after, trace, cell):
    wall = window_total_s(before, after, "engine.lower_s")
    cpu = window_total_s(before, after, "engine.lower_cpu_s")
    on_cpu = share(cpu, wall)
    return None if on_cpu is None else 100.0 - on_cpu

"""Device milliseconds per outer iteration of the window under the device
scope `direction-solve` (the PCG solve, its ELL products included; the
union of the intervals, so the `while` op and the kernels nested in it
count once), bench/program_trace.py."""
from bench import program_trace as pt


def read(ctx):
    return pt.per_iter_ms(ctx, "direction-solve")

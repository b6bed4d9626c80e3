"""Device milliseconds per outer iteration of the window under the device
scope `objective`: every energy and energy-and-gradient evaluation
(the gradient and the line search's trials), bench/program_trace.py."""
from bench import program_trace as pt


def read(ctx):
    return pt.per_iter_ms(ctx, "objective")

"""Device milliseconds per outer iteration of the window under the device
scope `laplacian/reverse`: the ELL products over the reverse graph (the
transpose half of the symmetric Laplacian, in every PCG iteration),
bench/program_trace.py."""
from bench import program_trace as pt


def read(ctx):
    return pt.per_iter_ms(ctx, "laplacian/reverse")

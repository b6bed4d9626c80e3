"""Share of the traced window in which the device sat idle before a fit's
SD loop, in %: for every `bench/fit` annotation, the idle seconds from
its start to its first `solve-iter` span (affinities, spectral
initialisation, the Cholesky factor, the first energy and gradient, and
the host work around them), summed over the window's fits, over the
window (bench/program_trace.py)."""
from bench import program_trace as pt


def read(ctx):
    sp = pt.load(ctx)
    if sp is None:
        return None
    phases = pt.fit_phases(sp)
    if not phases:
        return None
    idle = sum(pt.idle_s(sp, start, loop) for start, loop, _ in phases)
    return 100.0 * idle / pt.window_s(sp)

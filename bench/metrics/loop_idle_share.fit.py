"""Share of the traced window in which the device sat idle inside a fit's
SD loop, in %: for every `bench/fit` annotation, the idle seconds from
its first `solve-iter` span to the end of its last `iter-host` span
(the step, the scalar fetch and the engine's host bookkeeping), summed
over the window's fits, over the window (bench/program_trace.py).  None
where the program has no `iter-host` span."""
from bench import program_trace as pt


def read(ctx):
    sp = pt.load(ctx)
    if sp is None:
        return None
    phases = [p for p in pt.fit_phases(sp) if p[2] is not None]
    if not phases:
        return None
    idle = sum(pt.idle_s(sp, loop, end) for _, loop, end in phases)
    return 100.0 * idle / pt.window_s(sp)

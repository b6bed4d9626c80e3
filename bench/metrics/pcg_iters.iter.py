"""PCG iterations per outer iteration in the direction solve, mean over
the window's iterations (the program's `pcg_iters` diagnostic)."""


def read(ctx):
    it = ctx.counters.get("pcg_iters")
    return sum(it) / len(it) if it else None

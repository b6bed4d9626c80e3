"""Milliseconds of a fit before its first SD iteration, mean over the
window's fits: the fit's wall time less the engine's loop time (both ends
block), so affinities, spectral initialisation, the Cholesky factor and
the first energy and gradient."""


def read(ctx):
    pre = ctx.counters.get("preloop_s")
    return 1e3 * sum(pre) / len(pre) if pre else None

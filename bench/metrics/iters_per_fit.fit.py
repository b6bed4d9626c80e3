"""Outer SD iterations per fit to the target, mean over the window's fits
(`EngineResult.n_iters` as the fit's callback sees it): the paper's lever."""


def read(ctx):
    iters = ctx.counters.get("iters")
    return sum(iters) / len(iters) if iters else None

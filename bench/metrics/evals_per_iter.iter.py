"""Energy evaluations per outer iteration (the gradient evaluation and
the line search's trials), mean over the window's iterations."""


def read(ctx):
    ev = ctx.counters.get("n_evals")
    return sum(ev) / len(ev) if ev else None

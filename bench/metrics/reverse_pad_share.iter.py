"""Share of the reverse ELL graph's slots that are zero-weight padding:
slots every matvec reads and that contribute nothing (a count from the
graph's arrays)."""


def read(ctx):
    s = ctx.counters.get("rev_zero_share")
    return None if s is None else 100.0 * s

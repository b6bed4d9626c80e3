"""The whole fit's least time on the chip over the window, in %: per fit
the pairwise passes (bench/work/pairwise_terms.py) and the rest of the
dense SD fit (bench/work/dense_sd_fit.py), each bounded by the larger of
operations over peak FLOP/s and bytes over peak bandwidth, summed over
the window's fits.  It does not depend on which kernel does the work."""
from bench import metrics_common as mc


def read(ctx):
    c = ctx.counters
    if not c.get("iters") or ctx.reduction is None:
        return None
    total = c["pairwise_calls"] * mc.least("pairwise_terms", ctx, n=c["n"],
                                           d=c["d"])
    total += sum(mc.least("dense_sd_fit", ctx, n=c["n"], D=c["dim"],
                          d=c["d"], iters=it) for it in c["iters"])
    return 100.0 * total / ctx.reduction.window_s

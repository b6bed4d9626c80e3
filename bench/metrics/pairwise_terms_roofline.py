"""The fused pairwise kernel's least time over its device time, in %.

Least time: every pairwise pass the window's fits made (one before each
fit's loop, then one per line-search trial and one per new gradient),
its operations and HBM bytes from `bench/work/pairwise_terms.py`,
bounded by the larger of operations over peak FLOP/s and bytes over peak
bandwidth (memory bounds it at N = 720).  Device time: the trace's events
of the kernel, by the names in `bench/metrics_common.KERNEL_NAMES`."""
from bench import metrics_common as mc


def read(ctx):
    c = ctx.counters
    if not c.get("pairwise_calls") or ctx.reduction is None:
        return None
    least = c["pairwise_calls"] * mc.least("pairwise_terms", ctx, n=c["n"],
                                           d=c["d"])
    return mc.share(least, mc.kernel_s(ctx, "pairwise_terms"))

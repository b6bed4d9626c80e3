"""The ELL Laplacian products' least time over their device time, in %.

Least time: every product the window's direction solves made (one per
PCG iteration plus the initial residual, each over the forward graph and
the reverse graph), its operations and HBM bytes from
`bench/work/ell_lap_matvec.py`, each bounded by the larger of operations
over peak FLOP/s and bytes over peak bandwidth (memory bounds it).
Device time: the trace's events of the kernel, by the names in
`bench/metrics_common.KERNEL_NAMES`."""
from bench import metrics_common as mc


def read(ctx):
    c = ctx.counters
    if not c.get("pcg_iters") or ctx.reduction is None:
        return None
    calls = sum(p + 1 for p in c["pcg_iters"])
    least = calls * (mc.least("ell_lap_matvec", ctx, n=c["n"], k=c["k"],
                              d=c["d"])
                     + mc.least("ell_lap_matvec", ctx, n=c["n"],
                                k=c["k_rev"], d=c["d"]))
    return mc.share(least, mc.kernel_s(ctx, "ell_lap_matvec"))

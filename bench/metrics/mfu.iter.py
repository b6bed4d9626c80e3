"""The whole outer iteration's least time on the chip over the window, in
%: per iteration the direction solve's products (PCG iterations plus the
initial residual, each over both graphs, bench/work/ell_lap_matvec.py)
and the objective's evaluations (one with the gradient, the rest of the
line search's trials without, bench/work/sparse_tsne_eval.py), each
bounded by the larger of operations over peak FLOP/s and bytes over peak
bandwidth, summed over the window's iterations.  It does not depend on
which kernel does the work."""
from bench import metrics_common as mc


def read(ctx):
    c = ctx.counters
    if not c.get("pcg_iters") or ctx.reduction is None:
        return None
    n, k, kr, m, d = c["n"], c["k"], c["k_rev"], c["m"], c["d"]
    matvec = (mc.least("ell_lap_matvec", ctx, n=n, k=k, d=d)
              + mc.least("ell_lap_matvec", ctx, n=n, k=kr, d=d))
    e_g = mc.least("sparse_tsne_eval", ctx, n=n, k=k, k_rev=kr, m=m, d=d,
                   grad=True)
    e_only = mc.least("sparse_tsne_eval", ctx, n=n, k=k, k_rev=kr, m=m, d=d,
                      grad=False)
    total = sum((p + 1) * matvec + e_g + (ev - 1) * e_only
                for p, ev in zip(c["pcg_iters"], c["n_evals"]))
    return 100.0 * total / ctx.reduction.window_s

"""Helpers the per-layer readers share: the kernels' names in a trace, a
kernel's device seconds, an operation's least time, and shares."""
from __future__ import annotations

import os

from bench import trace
from bench.harness import BENCH, load_module
from bench.peaks import least_seconds

#: substrings of a kernel's device events in a TPU trace: the HLO
#: instruction of a `pallas_call` takes the name of the jitted function
#: around it in `repro.kernels.ops` (`_pairwise_pallas.1`), and its
#: scope names the kernel
KERNEL_NAMES = {
    "pairwise_terms": ["_pairwise_pallas"],
    "ell_lap_matvec": ["_ell_pallas"],
}


def work(op: str, **shapes) -> tuple[float, float]:
    mod = load_module(os.path.join(BENCH, "work", op + ".py"))
    return mod.work(**shapes)


def least(op: str, ctx, **shapes) -> float:
    flops, nbytes = work(op, **shapes)
    return least_seconds(flops, nbytes, ctx.device_kind)[0]


def kernel_s(ctx, kernel: str) -> float:
    return trace.kernel_seconds(ctx.reduction, KERNEL_NAMES[kernel])


def share(least_s: float, device_s: float):
    """least / measured in %, or None where the trace saw nothing."""
    if device_s <= 0:
        return None
    return 100.0 * least_s / device_s


def idle_share(ctx):
    red = ctx.reduction
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)

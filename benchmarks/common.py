"""Shared benchmark machinery: datasets, the method lineup, timing helpers.

Methods run through the public `repro.api.Embedding` estimator.
`method_by_name` hands out raw strategy objects for drivers that need
them (fig3's homotopy path, `repro.embed.homotopy_path`).

Scale note: the container is a single CPU core; Ns default to reduced
versions of the paper's datasets (COIL-20: N=720 exact; MNIST: N=2000 vs
the paper's 20000).  Every benchmark takes --n/--budget flags so the full
paper scale can be run on real hardware.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.api import Embedding, EmbedSpec
from repro.api.registries import strategy_entry
from repro.core import laplacian_eigenmaps, make_affinities
from repro.data import coil_like, mnist_like

# the paper's lineup (Fig. 1/2/4), as (display name, registry strategy,
# LSConfig.init_step).  SD uses the adaptive initial step the paper
# describes; quasi-Newton methods start at the natural alpha = 1 — these
# are exactly the strategy registry's defaults, asserted in method_by_name.
METHODS = [
    ("GD", "gd", "one"),
    ("FP", "fp", "one"),
    ("DiagH", "diag", "one"),
    ("CG", "cg", "one"),
    ("L-BFGS", "lbfgs", "one"),
    ("SD-", "sd-", "adaptive_grow"),
    ("SD", "sd", "adaptive_grow"),
]


def _parse(name: str):
    """(registry strategy, strategy_opts) from a lineup/display name;
    supports the 'SD(k7)' sparsified-kappa spelling."""
    if name.startswith("SD(k"):
        return "sd", {"kappa": int(name[4:-1])}
    for disp, strategy, ls in METHODS:
        if disp == name:
            assert strategy_entry(strategy).default_ls_init == ls
            return strategy, {}
    # fall through: accept registry names directly ("sd", "fp", ...)
    return name, {}


def method_by_name(name: str, **kw):
    """(strategy object, init_step) — the raw-strategy surface for drivers
    that bypass the estimator (e.g. homotopy over lambda)."""
    strategy, opts = _parse(name)
    entry = strategy_entry(strategy)
    return entry.dense_factory(EmbedSpec(strategy=strategy), **opts, **kw), \
        entry.default_ls_init


def coil_problem(n_per=72, loops=10, dim=256, perplexity=20.0, model="ee"):
    Y = jnp.asarray(coil_like(n_per=n_per, loops=loops, dim=dim))
    aff = make_affinities(Y, perplexity, model=model)
    X0 = laplacian_eigenmaps(aff.Wp, 2) * 0.1
    return Y, aff, X0


def mnist_problem(n=2000, perplexity=30.0, model="ee"):
    Y, labels = mnist_like(n=n)
    Y = jnp.asarray(Y)
    aff = make_affinities(Y, perplexity, model=model)
    X0 = laplacian_eigenmaps(aff.Wp, 2) * 0.1
    return Y, aff, X0, labels


def run_method(name, aff, X0, kind, lam, max_iters=200, tol=0.0,
               max_seconds=None, kappa=None):
    """One method on a prebuilt problem, through the public estimator;
    returns the EngineResult (energies/times/setup_time/n_fevals...)."""
    strategy, opts = _parse(name)
    if kappa is not None and strategy == "sd":
        opts = {**opts, "kappa": kappa}
    spec = EmbedSpec(kind=kind, lam=lam, strategy=strategy, backend="dense",
                     max_iters=max_iters, tol=tol, max_seconds=max_seconds,
                     strategy_opts=opts)
    return Embedding(spec).fit(None, X0=X0, aff=aff).result_


def time_to_target(res, target_e):
    """Wall-clock seconds (incl. setup) to first reach target_e, or inf."""
    below = np.nonzero(res.energies <= target_e)[0]
    if len(below) == 0:
        return float("inf")
    return float(res.times[below[0]] + res.setup_time)


def csv_row(*fields):
    print(",".join(str(f) for f in fields), flush=True)

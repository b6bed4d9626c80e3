"""Fits to a target energy, back to back, each from the input rows.

Every fit runs `repro.api.Embedding.fit` from scratch on host rows:
affinities, spectral initialisation, direction-solver set-up and SD
iterations until the energy the program reports falls to the cell's
target `target_ratio * e_init`, or `max_iters` is reached (a failed
fit).  The rows are `datasets` fixed orderings of the configuration's
rows (from `dataset_seed`, the same for every run): an EE fit is chaotic,
so each ordering takes its own number of iterations to the target, and
every run must do the same work.  The run's seed sets the order in which
a round visits them.  Set-up runs `warm_fits` fits, which compile every
program the window uses.  The window runs whole rounds until `--seconds`
have passed; the round in progress at the deadline is finished and
counted.

    fit_s = window seconds / fits completed

The check, after the window: the plain float64 reference
(bench/reference/embedding.py) builds its own affinities from the same
rows and evaluates, at every fit's final embedding, the energy and the
gradient norm the program reported for it, and the energy against the
target.
"""
from __future__ import annotations

import sys
import time

import numpy as np

from bench import data


class _Reached(Exception):
    pass


def _spec(cell):
    from bench.harness import program_spec

    return program_spec(cell, max_iters=int(cell.traffic["max_iters"]),
                        tol=0.0)


def target_energy(cell) -> float:
    return float(cell.traffic["target_ratio"]) * float(cell.traffic["e_init"])


def _fit(cell, Y, spec, target: float, telemetry=None) -> dict:
    from repro.api import Embedding

    last: dict = {}

    def callback(it, X, e, diag):
        last.update(it=it, X=X, e=e, g=diag["grad_norm"], t_loop=diag["t"])
        last["evals"] = last.get("evals", 0) + int(diag["n_evals"])
        if e <= target:
            raise _Reached

    t0 = time.perf_counter()
    reached = False
    with cell.annotate("bench/fit"):
        try:
            Embedding(spec).fit(Y, callback=callback, telemetry=telemetry)
        except _Reached:
            reached = True
    wall = time.perf_counter() - t0
    return {"wall": wall, "reached": reached, "iters": last["it"],
            "evals": last["evals"], "e": float(last["e"]),
            "g": float(last["g"]), "X": last["X"],
            "preloop": wall - float(last["t_loop"])}


def datasets(cell) -> list[np.ndarray]:
    """The round's host rows, in the order the run's seed gives."""
    t = cell.traffic
    k = int(t["datasets"])
    order = np.random.default_rng([cell.seed, 6]).permutation(k)
    return [data.rows(cell.config["data"], [int(t["dataset_seed"]), int(i)])[0]
            for i in order], [int(i) for i in order]


def run(cell) -> dict:
    Ys, order = datasets(cell)
    spec = _spec(cell)
    target = target_energy(cell)
    from repro.obs import Telemetry

    for i in range(int(cell.traffic["warm_fits"])):
        tel = Telemetry(record_memory=False)
        f = _fit(cell, Ys[0], spec, target, telemetry=tel)
        spans = {}
        for e in tel.tracer.to_chrome_trace()["traceEvents"]:
            if e["name"] != "solve-iter":
                spans[e["name"]] = spans.get(e["name"], 0.0) + e["dur"] * 1e-6
        cell.note(f"warm-up fit {i}: {f['wall']:.3f} s, {f['iters']} "
                  f"iterations, reached {f['reached']}, E {f['e']!r}; host "
                  "spans " + ", ".join(f"{k} {v:.3f} s"
                                       for k, v in spans.items()))
    tel = None
    if cell.trace:
        from repro.obs import Telemetry

        tel = Telemetry(jax_annotations=True, record_memory=False)
    fits = []
    cell.begin_window()
    while True:
        for j, Y in enumerate(Ys):
            fits.append(_fit(cell, Y, spec, target, telemetry=tel))
            fits[-1]["dataset"] = order[j]
        if time.perf_counter() >= cell.deadline:
            break
    cell.end_window()
    iters = {}
    for f in fits:
        iters.setdefault(f["dataset"], f["iters"])
    cell.note("iterations to the target by data set: " + ", ".join(
        f"{k} {v}" for k, v in sorted(iters.items())))
    return {"fits": fits, "attempted": len(fits),
            "failed": sum(not f["reached"] for f in fits)}


def end_to_end(cell, state) -> dict:
    return {"fit_s": cell.window_s / len(state["fits"])}


def counters(cell, state) -> dict:
    fits = state["fits"]
    cfg = cell.config
    return {"fits": len(fits),
            "iters": [f["iters"] for f in fits],
            # one pairwise evaluation before the loop, then each
            # iteration's line-search trials and its new gradient
            "pairwise_calls": sum(1 + f["evals"] for f in fits),
            "preloop_s": [f["preloop"] for f in fits],
            "n": int(cfg["n_points"]), "dim": int(cfg["input_dim"]),
            "d": int(cfg["spec"].get("dim", 2)),
            "window_s": cell.window_s}


def release(cell, state) -> None:
    for f in state["fits"]:
        f["X"] = np.asarray(f["X"], np.float64)


def check(cell, state) -> dict:
    from bench.reference import embedding as ref

    spec = cell.config["spec"]
    if spec["kind"] != "ee":
        raise NotImplementedError("the dense reference covers EE only")
    target = target_energy(cell)
    t = cell.traffic
    # the reference's affinities are computed once, on the rows in their
    # generated order, and permuted for each data set
    Y0, _ = data.generate(cell.config["data"])
    Wp0, Wm0 = ref.dense_affinities(Y0, float(spec["perplexity"]))
    W = {}
    for k in sorted({f["dataset"] for f in state["fits"]}):
        p = data.permutation(Y0.shape[0], [int(t["dataset_seed"]), k])
        W[k] = (Wp0[np.ix_(p, p)], Wm0[np.ix_(p, p)])
    e_gap = g_gap = t_gap = 0.0
    for f in state["fits"]:
        Wp, Wm = W[f["dataset"]]
        E, G = ref.ee_energy_grad(f["X"], Wp, Wm, float(spec["lam"]))
        gn = float(np.linalg.norm(G))
        e_gap = max(e_gap, abs(f["e"] - E) / abs(E))
        g_gap = max(g_gap, abs(f["g"] - gn) / gn)
        t_gap = max(t_gap, E / target - 1.0)
    lim = cell.traffic["limits"]
    return {"energy_gap": (e_gap, float(lim["energy_gap"])),
            "grad_gap": (g_gap, float(lim["grad_gap"])),
            "above_target": (t_gap, float(lim["above_target"]))}


def control(cell) -> dict:
    from bench.harness import program_control

    return program_control(cell, sys.modules[__name__])

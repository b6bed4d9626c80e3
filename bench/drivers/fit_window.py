"""The outer iterations of one long fit, after its first few.

Set-up takes the configuration's rows as generated (the same in every
run, so the objective's compiled programs, which close over the graph,
come from the persistent cache), builds the calibrated k-NN graph with the
program's `repro.sparse.graph.sparse_affinities` (timed and printed),
draws the initial embedding from the mix's `start_seed` (`init_scale`
times a standard normal, t-SNE's usual start; the same seed keys the
negative draws) and starts ONE
`repro.api.Embedding.fit` on them.  Its first `warm_iters` iterations,
which compile the objective and the direction solve, are set-up too; the
window is the iterations that follow, until `--seconds` have passed, and
the iteration in progress at the deadline is finished and counted.

    iter_s = window seconds / iterations completed in it

The check, after the window: the plain float64 reference builds its own
k-NN graph and conditionals from the same rows (their row-wise L1 gap to
the program's graph is printed), then follows the fit's first
`ref_steps` iterations from the same initial embedding, with the same
negative draws, and compares each step's energy, the norm of the
embedding's change over those steps, and the first iterate itself.  Those steps run through the same
fit call and the same compiled programs as the window.  Inside the
window, the energy the program reported at its last iterate is set
beside the reference's energy of that iterate with the same negatives
(printed as `window_energy_gap`).
"""
from __future__ import annotations

import sys
import time
import warnings

import numpy as np

from bench import data


class _Done(Exception):
    pass


def _graph(cell, Y):
    import jax
    import jax.numpy as jnp

    from repro.sparse.graph import sparse_affinities

    s = cell.config["spec"]
    t0 = time.perf_counter()
    saff = sparse_affinities(jnp.asarray(Y), k=int(s["n_neighbors"]),
                             perplexity=float(s["perplexity"]),
                             model=s["kind"])
    jax.block_until_ready(saff)
    cell.note(f"graph build {time.perf_counter() - t0:.3f} s, reverse "
              f"width {saff.rev.k}")
    return saff


def start_seed(cell) -> int:
    """The seed of the start and of the negative draws: the mix's, not the
    run's.  On the chip the start moved `iter_s` by up to 15 % from seed
    to seed (its PCG counts) while one seed repeated within 0.06 %, so
    every run fits from the same start."""
    return int(cell.traffic["start_seed"]) % (2 ** 31 - 2)


def initial_embedding(cell, n: int) -> np.ndarray:
    d = int(cell.config["spec"].get("dim", 2))
    rng = np.random.default_rng([start_seed(cell), 1])
    return (float(cell.traffic["init_scale"])
            * rng.standard_normal((n, d))).astype(np.float32)


def run(cell) -> dict:
    import jax.numpy as jnp

    from repro.api import Embedding

    from bench.harness import program_spec

    Y, _ = data.generate(cell.config["data"])
    saff = _graph(cell, Y)
    X0 = initial_embedding(cell, Y.shape[0])
    spec = program_spec(cell, max_iters=10 ** 9, tol=0.0,
                        seed=start_seed(cell))
    warm = int(cell.traffic["warm_iters"])
    n_ref = int(cell.traffic["ref_steps"])
    st = {"e": {}, "X": {}, "t": {}, "diag": [], "attempted": 0,
          "failed": 0}
    t_fit = time.perf_counter()

    def record(it, X, e, diag=None):
        now = time.perf_counter()
        if it <= n_ref:
            st["e"][it] = float(e)
            st["X"][it] = X
        if it == warm:
            cell.note(f"{warm} warm-up iterations "
                      f"{now - t_fit:.3f} s after the fit call")
            cell.begin_window()
        elif it > warm:
            st["t"][it] = now
            st["last"] = (it, X, float(e))
            if diag is not None:
                st["diag"].append(diag)
            if now >= cell.deadline:
                cell.end_window()
                raise _Done

    if cell.trace:
        from repro.obs import Telemetry

        tel = Telemetry(jax_annotations=True, record_memory=False)

        def callback(it, X, e, diag):       # diagnostics: PCG counts
            record(it, X, e, diag)
    else:
        tel = None

        def callback(it, X, e):             # no per-iteration transfer
            record(it, X, e)

    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        try:
            Embedding(spec).fit(Y, X0=jnp.asarray(X0), saff=saff,
                                callback=callback, telemetry=tel)
        except _Done:
            pass
    iters = sorted(st["t"])
    st.update(Y=Y, X0=X0, saff=saff, iters=len(iters),
              attempted=len(iters))
    return st


def end_to_end(cell, state) -> dict:
    return {"iter_s": cell.window_s / state["iters"]}


def counters(cell, state) -> dict:
    g, rev = state["saff"].graph, state["saff"].rev
    diag = state["diag"]
    return {"iters": state["iters"], "window_s": cell.window_s,
            "n": int(g.n), "k": int(g.k), "k_rev": int(rev.k),
            "d": int(cell.config["spec"].get("dim", 2)),
            "m": int(cell.config["spec"]["n_negatives"]),
            "rev_zero_share": float(np.mean(np.asarray(rev.weights) == 0)),
            "pcg_iters": [int(d["pcg_iters"]) for d in diag],
            "n_evals": [int(d["n_evals"]) for d in diag]}


def release(cell, state) -> None:
    saff = state.pop("saff")
    n = saff.graph.n
    state["graph"] = (np.asarray(saff.graph.indices),
                      np.asarray(saff.graph.weights, np.float64)
                      * (n if cell.config["spec"]["kind"] in ("tsne", "ssne")
                         else 1.0))
    state["X"] = {k: np.asarray(v, np.float64) for k, v in state["X"].items()}
    it, X, e = state["last"]
    state["last"] = (it, np.asarray(X, np.float64), e)
    del saff


def check(cell, state) -> dict:
    from bench.reference import embedding as ref

    s = cell.config["spec"]
    if s["kind"] != "tsne":
        raise NotImplementedError("the reference follows sampled t-SNE only")
    idx_r, p_r = ref.knn_conditionals(state["Y"], int(s["n_neighbors"]),
                                      float(s["perplexity"]))
    idx_p, p_p = state["graph"]
    # printed, not compared: no lower precision the control can switch
    # on moves it, and a wrong graph moves the energies compared below
    graph_gap = float(np.max(ref.row_l1_gap(idx_p, p_p, idx_r, p_r)))
    cell.note(f"graph gap (row-wise L1 of the conditionals) {graph_gap!r}")
    obj = ref.SparseTSNE(idx_r, p_r, float(s["lam"]), int(s["n_negatives"]),
                         float(s.get("z_ema_decay", 0.9)),
                         float(s.get("mu_scale", 1e-5)))
    ls = cell.config["line_search"]
    n_ref = int(cell.traffic["ref_steps"])
    e_ref, X_ref = ref.tsne_sd_steps(
        obj, state["X0"], start_seed(cell), n_ref, ls,
        float(s.get("cg_tol", 1e-3)), int(s.get("cg_maxiter", 100)))
    loss_gap = max(abs(state["e"][k + 1] - e_ref[k]) / abs(e_ref[k])
                   for k in range(n_ref))
    X0 = np.asarray(state["X0"], np.float64)
    move_p = np.linalg.norm(state["X"][n_ref] - X0)
    move_r = np.linalg.norm(X_ref[-1] - X0)
    move_gap = abs(move_p - move_r) / move_r
    # the gap of norms cannot see a direction turned by the solve (the
    # line search rescales the step): the first iterate's distance from
    # the reference's, over the reference's first move, can
    step_gaps = [float(np.linalg.norm(state["X"][k + 1] - X_ref[k])
                       / np.linalg.norm(X_ref[k] - X0)) for k in range(n_ref)]
    first_step_gap = step_gaps[0]
    cell.note("per-step energy gaps " + ", ".join(
        repr(float(abs(state["e"][k + 1] - e_ref[k]) / abs(e_ref[k])))
        for k in range(n_ref)) + "; per-step iterate gaps "
        + ", ".join(repr(g) for g in step_gaps))
    it, X_last, e_last = state["last"]
    e_win = obj.energy(X_last, obj.shifts(ref.step_key(start_seed(cell), it)))
    cell.note(f"window_energy_gap (iteration {it}) "
              f"{abs(e_last - e_win) / abs(e_win)!r}")
    lim = cell.traffic["limits"]
    return {"loss_gap": (float(loss_gap), float(lim["loss_gap"])),
            "move_gap": (float(move_gap), float(lim["move_gap"])),
            "first_step_gap": (first_step_gap, float(lim["first_step_gap"]))}


def control(cell) -> dict:
    from bench.harness import program_control

    return program_control(cell, sys.modules[__name__])

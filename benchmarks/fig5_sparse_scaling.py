"""Fig. 5 (beyond-paper): dense vs sparse pipeline scaling in N.

Sweeps N over {2k, 10k, 50k} (container default) and, per model in
`--model` (comma-separated; normalized kinds route through the sampled
ratio-estimator repulsion, unnormalized through absolute negative
sampling), reports per N:

  * graph/affinity build time (dense perplexity calibration vs k-NN + ELL
    calibration),
  * per-iteration wall-clock of the optimization step (energy + gradient +
    spectral-direction solve), dense (O(N^2 d), Cholesky backsolves) vs
    sparse (O(N (k + m) d), Jacobi-CG) vs tree (deterministic Barnes-Hut
    grid repulsion, O(N log N), sparse/farfield.py),
  * final (surrogate) energy after `iters` steps.

The dense path is SKIPPED above `dense_cutoff` (default 5k: the dense
pipeline holds several f32 (N, N) arrays — affinities, B, its Cholesky
factor — ~1.6 GB at N=10k, plus an O(N^3) factorization on one CPU core)
— exactly the wall the sparse subsystem removes.  The sparse
per-iteration time should scale ~linearly in N (acceptance: the measured
scaling exponent over the sweep stays near 1, far from quadratic).

`--devices 1,2,4,8` adds a device-count column: per count, the
row-sharded backend (sparse/sharding.py) on a (devices, 1) mesh.  A count
this process can see runs in process over its first devices (on a TPU
host, where the process holds the chips); a larger count on the CPU
backend runs in a child with that many forced host devices, since the
CPU device count is fixed before jax initializes.  On one CPU core the
emulated devices share the core, so this measures sharding OVERHEAD
(psum + padding), not speedup.  A count that fails prints a FAILED row
and the run exits non-zero.

The JSON output is keyed {model: {n: columns}} and MERGES into an
existing `--out` file at the model level, so successive runs (e.g. an ee
smoke sweep, then `--model tsne --ns 20000`) accumulate columns in one
results/fig5.json — the file the CI bench-regression job diffs
per-iteration timings against (benchmarks/check_regression.py).

    PYTHONPATH=src python -m benchmarks.fig5_sparse_scaling [--ns 2000,10000,50000] [--model ee,tsne]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Embedding, EmbedSpec
from repro.core import (energy_and_grad_sparse, is_normalized,
                        make_affinities)
from repro.data import mnist_like
from repro.sparse import (energy_and_grad_tree, make_grid_plan,
                          make_sd_operator, make_sharded_energy_grad,
                          make_sharded_sd_operator, pcg,
                          shard_sparse_affinities, sparse_affinities)

from .common import csv_row

Array = jnp.ndarray

# normalized models weight the LOG of the repulsive sum; lam ~ 1 is the
# t-SNE/s-SNE convention, against lam ~ 100 for the EE family
_DEFAULT_LAM = {"ssne": 1.0, "tsne": 1.0}


def _model_lam(kind: str, lam: float | None) -> float:
    return _DEFAULT_LAM.get(kind, 100.0) if lam is None else lam


def dense_point(Y: Array, kind: str, lam: float, iters: int,
                perplexity: float) -> dict:
    t0 = time.perf_counter()
    aff = jax.block_until_ready(make_affinities(Y, perplexity, model=kind))
    t_build = time.perf_counter() - t0
    n = Y.shape[0]
    X0 = 1e-2 * jax.random.normal(jax.random.PRNGKey(0), (n, 2))
    res = Embedding(EmbedSpec(kind=kind, lam=lam, strategy="sd",
                              backend="dense", max_iters=iters, tol=0.0)
                    ).fit(None, X0=X0, aff=aff).result_
    # steady-state per-iteration time: drop the compile-heavy first step
    t_iter = float(np.diff(res.times[1:]).mean()) if iters > 2 else \
        float(res.times[-1] / max(res.n_iters, 1))
    return {"build_s": t_build, "setup_s": res.setup_time,
            "iter_s": t_iter, "energy": float(res.energies[-1])}


def _time_sparse_iters(eg, matvec, inv_diag, n: int, iters: int,
                       t_build: float, normalized: bool = False) -> dict:
    """Shared timing loop for the sparse/sharded columns: the jitted step
    (eg -> warm-started PCG -> fixed small move) and the warmup/steady
    timing must be IDENTICAL for the two columns' energies and iter times
    to be comparable.  `eg(X, key) -> (E, G)`; normalized models thread
    the streaming partition-function estimate, `eg(X, key, z) ->
    (E, G, z)`."""

    @jax.jit
    def step(X, P, z, key):
        if normalized:
            E, G, z = eg(X, key, z)
        else:
            E, G = eg(X, key)
        P = pcg(matvec, -G, P, inv_diag=inv_diag, tol=1e-3, maxiter=50).x
        # fixed small step for timing purposes (the trainer line-searches)
        xc = X - jnp.mean(X, axis=0, keepdims=True)
        scale = jnp.sqrt(jnp.mean(xc * xc)) + 1e-3
        alpha = jnp.minimum(
            1.0, scale / (jnp.sqrt(jnp.mean(P * P)) + 1e-30))
        return X + alpha * P, P, z, E

    X = 1e-2 * jax.random.normal(jax.random.PRNGKey(0), (n, 2))
    P = jnp.zeros_like(X)
    z = jnp.zeros((), X.dtype)          # <= 0: uninitialized estimator
    key0 = jax.random.PRNGKey(1)
    X, P, z, E = jax.block_until_ready(step(X, P, z, key0))  # compile+iter 1
    t0 = time.perf_counter()
    for it in range(1, iters):
        X, P, z, E = step(X, P, z, jax.random.fold_in(key0, it))
    jax.block_until_ready(X)
    t_iter = (time.perf_counter() - t0) / max(iters - 1, 1)
    return {"build_s": t_build, "setup_s": 0.0,
            "iter_s": t_iter, "energy": float(E)}


def sparse_point(Y: Array, kind: str, lam: float, iters: int,
                 perplexity: float, k: int, m: int) -> dict:
    t0 = time.perf_counter()
    saff = jax.block_until_ready(sparse_affinities(
        Y, k=k, perplexity=perplexity, model=kind))
    t_build = time.perf_counter() - t0

    matvec, inv_diag, _ = make_sd_operator(saff.graph, saff.rev)
    lam_ = jnp.asarray(lam, jnp.float32)
    if is_normalized(kind):
        eg = lambda X, key, z: energy_and_grad_sparse(
            X, saff, kind, lam_, n_negatives=m, key=key, z_prev=z,
            return_state=True)
    else:
        eg = lambda X, key: energy_and_grad_sparse(X, saff, kind, lam_,
                                                   n_negatives=m, key=key)
    return _time_sparse_iters(eg, matvec, inv_diag, Y.shape[0], iters,
                              t_build, normalized=is_normalized(kind))


def tree_point(Y: Array, kind: str, lam: float, iters: int,
               perplexity: float, k: int) -> dict:
    """Deterministic Barnes-Hut column: same ELL attractive graph as the
    sparse column, grid far-field repulsion instead of sampling — so the
    iter_s delta is exactly the tree's price/win, and the energy column is
    the true (unsampled) objective value."""
    t0 = time.perf_counter()
    saff = jax.block_until_ready(sparse_affinities(
        Y, k=k, perplexity=perplexity, model=kind))
    t_build = time.perf_counter() - t0

    matvec, inv_diag, _ = make_sd_operator(saff.graph, saff.rev)
    plan = make_grid_plan(Y.shape[0])
    lam_ = jnp.asarray(lam, jnp.float32)
    # no z state and no PRNG: the tree repulsion is exact under the grid,
    # so normalized kinds use log(s) directly (normalized=False here just
    # means "no streaming-Z threading" in the shared timing loop)
    eg = lambda X, key: energy_and_grad_tree(X, saff, lam_, kind, plan)
    return _time_sparse_iters(eg, matvec, inv_diag, Y.shape[0], iters,
                              t_build, normalized=False)


def sharded_point(Y: Array, mesh, kind: str, lam: float, iters: int,
                  perplexity: float, k: int, m: int) -> dict:
    """Row-sharded sparse per-iteration time on an existing mesh."""
    t0 = time.perf_counter()
    saff = jax.block_until_ready(sparse_affinities(
        Y, k=k, perplexity=perplexity, model=kind))
    sg = shard_sparse_affinities(mesh, ("data",), saff)
    t_build = time.perf_counter() - t0

    eg_l, _ = make_sharded_energy_grad(mesh, ("data",), sg, kind,
                                       n_negatives=m)
    matvec, inv_diag, _ = make_sharded_sd_operator(mesh, ("data",), sg, saff)
    lam_ = jnp.asarray(lam, jnp.float32)
    if is_normalized(kind):
        eg = lambda X, key, z: eg_l(X, lam_, key, z)
    else:
        eg = lambda X, key: eg_l(X, lam_, key)
    return _time_sparse_iters(eg, matvec, inv_diag, Y.shape[0], iters,
                              t_build, normalized=is_normalized(kind))


_WORKER_MARK = "FIG5_WORKER_JSON "


def _sharded_rows(n_devices: int, ns, kind, lam, iters, perplexity, k, m,
                  dim) -> dict:
    """sharded_point rows over a (n_devices, 1) mesh of this process's
    first `n_devices` devices: {n: row}."""
    from repro.launch.mesh import axis_types_kwargs

    assert len(jax.devices()) >= n_devices, (len(jax.devices()), n_devices)
    mesh = jax.make_mesh((n_devices, 1), ("data", "model"),
                         devices=jax.devices()[:n_devices],
                         **axis_types_kwargs(2))
    out = {}
    for n in ns:
        Y, _ = mnist_like(n=n, dim=dim)
        out[n] = sharded_point(jnp.asarray(Y), mesh, kind, lam, iters,
                               perplexity, k, m)
    return out


def _sharded_worker(n_devices: int, ns, kind, lam, iters, perplexity, k, m,
                    dim) -> None:
    """Child-process entry: jax was initialized with `n_devices` forced
    host devices (XLA_FLAGS set by the parent before spawn)."""
    rows = _sharded_rows(n_devices, ns, kind, lam, iters, perplexity, k, m,
                         dim)
    print(_WORKER_MARK + json.dumps(rows), flush=True)


def _sharded_child(dev, ns, kind, lam, iters, perplexity, k, m, dim):
    """sharded_point rows from a child process with `dev` forced host
    devices (the CPU backend fixes its device count at start-up), or the
    child's last error line when it failed."""
    env = dict(os.environ)
    # keep the parent's other XLA flags (identical configs for the
    # sparse vs sharded columns), replacing only the device count
    inherited = [f for f in env.get("XLA_FLAGS", "").split()
                 if not f.startswith("--xla_force_host_platform_device_count")]
    env["XLA_FLAGS"] = " ".join(
        inherited + [f"--xla_force_host_platform_device_count={dev}"])
    argv = [sys.executable, "-m", "benchmarks.fig5_sparse_scaling",
            "--worker-devices", str(dev),
            "--ns", ",".join(str(n) for n in ns), "--model", kind,
            "--lam", str(lam), "--iters", str(iters), "--k", str(k),
            "--perplexity", str(perplexity), "--m", str(m),
            "--dim", str(dim)]
    proc = subprocess.run(argv, capture_output=True, text=True, env=env)
    if proc.returncode != 0:
        return None, (proc.stderr.strip().splitlines()[-1] if proc.stderr
                      else f"rc={proc.returncode}")
    payload = [ln for ln in proc.stdout.splitlines()
               if ln.startswith(_WORKER_MARK)]
    return {int(n): row for n, row in
            json.loads(payload[-1][len(_WORKER_MARK):]).items()}, None


def _run_sharded_sweep(devices, ns, kind, lam, iters, perplexity, k, m,
                       dim) -> dict:
    """Per device count, the sharded_point rows: {n: {n_devices: row}}.

    A count this process can see runs in process over its first `dev`
    devices — the only way on an accelerator, where the parent already
    holds the chips.  On the CPU backend a larger count needs a child
    with that many forced host devices.  Any failed count prints a
    FAILED row and the sweep raises once every count has run."""
    out: dict = {n: {} for n in ns}
    failed = []
    for dev in devices:
        args = (dev, ns, kind, lam, iters, perplexity, k, m, dim)
        if dev <= jax.device_count():
            rows, err = _sharded_rows(*args), None
        elif jax.default_backend() == "cpu":
            rows, err = _sharded_child(*args)
        else:
            rows, err = None, (f"{dev} devices requested, "
                               f"{jax.device_count()} visible")
        if rows is None:
            csv_row("fig5", kind, f"sharded@{dev}dev", "FAILED", err)
            failed.append(f"{dev}: {err}")
            continue
        for n, row in rows.items():
            out[n][dev] = row
            csv_row("fig5", kind, f"sharded@{dev}dev", n,
                    f"{row['build_s']:.2f}", f"{row['iter_s']:.4f}",
                    f"{row['energy']:.6g}")
    if failed:
        raise RuntimeError(f"sharded sweep failed for device counts "
                           f"{failed}")
    return out


def _run_one_model(ns, kind, lam, iters, perplexity, k, m, dense_cutoff,
                   dim, devices) -> dict:
    lam = _model_lam(kind, lam)
    results = {}
    for n in ns:
        Y, _ = mnist_like(n=n, dim=dim)
        Y = jnp.asarray(Y)
        row = {}
        if n <= dense_cutoff:
            row["dense"] = dense_point(Y, kind, lam, iters, perplexity)
            csv_row("fig5", kind, "dense", n,
                    f"{row['dense']['build_s']:.2f}",
                    f"{row['dense']['iter_s']:.4f}",
                    f"{row['dense']['energy']:.6g}")
        else:
            csv_row("fig5", kind, "dense", n, "skipped", "oom-cutoff", "")
        row["sparse"] = sparse_point(Y, kind, lam, iters, perplexity, k, m)
        csv_row("fig5", kind, "sparse", n,
                f"{row['sparse']['build_s']:.2f}",
                f"{row['sparse']['iter_s']:.4f}",
                f"{row['sparse']['energy']:.6g}")
        row["tree"] = tree_point(Y, kind, lam, iters, perplexity, k)
        csv_row("fig5", kind, "tree", n,
                f"{row['tree']['build_s']:.2f}",
                f"{row['tree']['iter_s']:.4f}",
                f"{row['tree']['energy']:.6g}")
        results[n] = row
    if devices:
        sharded = _run_sharded_sweep(devices, ns, kind, lam, iters,
                                     perplexity, k, m, dim)
        for n in ns:
            results[n]["sharded"] = sharded[n]
    # linear-scaling figure of merit over the sparse sweep
    ns_run = sorted(results)
    if len(ns_run) >= 2:
        n0, n1 = ns_run[0], ns_run[-1]
        for col in ("sparse", "tree"):
            t0, t1 = results[n0][col]["iter_s"], results[n1][col]["iter_s"]
            csv_row("fig5", kind, f"{col}-scaling-exponent", f"{n0}->{n1}",
                    f"{np.log(max(t1, 1e-9) / max(t0, 1e-9)) / np.log(n1 / n0):.2f}")
    return results


def run(ns=(2000, 10_000, 50_000), models=("ee",), lam=None, iters=10,
        perplexity=10.0, k=30, m=5, dense_cutoff=5000, dim=64,
        devices=(), out_json=None):
    """Returns {model: {n: columns}}.  `lam=None` picks the per-model
    default (1 for the normalized kinds, 100 for the EE family).  The JSON
    output MERGES at the model level into an existing `out_json`."""
    # keep k >= 3 * perplexity: with fewer candidates the entropy target
    # log(perplexity) is unreachable and the sparse calibration degenerates
    # to uniform, making the dense/sparse energy columns incomparable
    assert k >= perplexity, (k, perplexity)
    results = {kind: _run_one_model(ns, kind, lam, iters, perplexity, k, m,
                                    dense_cutoff, dim, devices)
               for kind in models}
    if out_json:
        if os.path.dirname(out_json):
            os.makedirs(os.path.dirname(out_json), exist_ok=True)
        merged = {}
        if os.path.exists(out_json):
            try:
                with open(out_json) as f:
                    merged = json.load(f)
            except (json.JSONDecodeError, OSError):
                merged = {}
            if merged and not any(
                    isinstance(v, dict) and
                    any(c in v for c in ("dense", "sparse", "sharded",
                                         "tree"))
                    for row in merged.values() if isinstance(row, dict)
                    for v in row.values()):
                merged = {}     # pre-model-column schema: start fresh
        for kind, rows in results.items():
            # merge at the (model, n) level so e.g. a later
            # `--model tsne --ns 20000` run extends the smoke sweep's tsne
            # column instead of replacing it
            model_rows = merged.setdefault(kind, {})
            model_rows.update({str(n): row for n, row in rows.items()})
        with open(out_json, "w") as f:
            json.dump(merged, f)
    return results


def _ns_list(s: str) -> tuple[int, ...]:
    try:
        return tuple(int(x) for x in s.split(","))
    except ValueError:
        raise argparse.ArgumentTypeError(
            f"--ns wants a comma-separated list of ints, got {s!r}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ns", type=_ns_list, default=(2000, 10_000, 50_000))
    ap.add_argument("--model", default="ee",
                    help="comma-separated model kinds, e.g. ee,tsne — each "
                         "gets its own column in the JSON output")
    ap.add_argument("--lam", type=float, default=None,
                    help="override the per-model default lambda "
                         "(100 EE-family, 1 normalized)")
    ap.add_argument("--iters", type=int, default=10)
    ap.add_argument("--k", type=int, default=30)
    ap.add_argument("--perplexity", type=float, default=10.0)
    ap.add_argument("--m", type=int, default=5)
    ap.add_argument("--dense-cutoff", type=int, default=5000)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--devices", type=_ns_list, default=(),
                    help="emulated device counts for the row-sharded "
                         "column, e.g. 1,2,4,8 (one subprocess per count)")
    ap.add_argument("--worker-devices", type=int, default=None,
                    help=argparse.SUPPRESS)   # internal: sharded-sweep child
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    models = tuple(a.model.split(","))
    if a.worker_devices is not None:
        _sharded_worker(a.worker_devices, a.ns, models[0],
                        _model_lam(models[0], a.lam), a.iters,
                        a.perplexity, a.k, a.m, a.dim)
        return
    run(ns=a.ns, models=models, lam=a.lam, iters=a.iters, k=a.k, m=a.m,
        perplexity=a.perplexity, dense_cutoff=a.dense_cutoff, dim=a.dim,
        devices=a.devices, out_json=a.out)


if __name__ == "__main__":
    main()

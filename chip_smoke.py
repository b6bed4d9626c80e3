"""Smoke run of the fit and serve path on one TPU chip.

    python3 chip_smoke.py              # phases (a)-(d), one chip
    python3 chip_smoke.py --chips 4    # the sparse-sharded phase only

Drives the public entry points, `repro.api.Embedding` and
`repro.serve.EmbeddingServer`, at the paper's configurations
(`src/repro/configs/embedding_paper.py`) on data from the `repro.data`
generators (seed 0), with every backend named explicitly:

  (a) embedding-coil20 on `dense`: ee, sd, perplexity 20 — the fused
      pairwise kernel;
  (b) embedding-mnist20k on `sparse`: tsne and ee, sd, perplexity 50
      (k = 150) — the ELL gather kernel inside the PCG solve and the
      sampled ratio estimator;
  (c) the tsne problem of (b) on `tree` — the Barnes-Hut interaction
      kernel;
  (b') the ELL matvec on the mnist20k graph (k = 150) and its reverse,
      forced into the HBM layout and the VMEM layout, each against a
      float64 host oracle to k * eps(f32) of the term scale — no fit at
      N = 20000 reaches the HBM layout;
  (d) an `EmbeddingServer` over the tsne fit of (b): `warmup()`, then 32
      single-row requests from 8 concurrent clients, micro-batched up to
      8 rows, whose answers must match one direct `Embedding.transform`
      call over the same 32 rows to 1e-5.

`--chips 4` runs only the tsne problem of (b) on `sparse-sharded` over a
(4, 1) ("data", "model") mesh and, in the same process, on one-chip
`sparse`; the per-iteration energies must agree to rtol 5e-3.

Each fit prints the device, its first and last energy, smoke timings
(first iteration with compilation, then the mean steady iteration — not
benchmark results) and the kernel dispatch decisions.  The run fails
(non-zero exit) when JAX finds no TPU, when a main-path kernel ran
interpreted or fell back to jnp for any reason but the size-dependent
"vmem-cap", when an energy did not decrease, when a layout's matvec
leaves its bound, or when server parity fails.  The last line of a
passing run is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SERVE_ROWS = 32
SERVE_BATCH = 8
SMOKE_REPS = 5
SERVE_TOL = 1e-5
SHARDED_RTOL = 5e-3       # tests/test_api.py::test_sharded_backend_parity


class SmokeFailure(Exception):
    pass


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def _kernel_decisions(before: dict, after: dict, expect: tuple) -> dict:
    """The dispatch records this phase wrote, checked: each expected
    kernel dispatched, compiled Pallas or a printed vmem-cap fallback."""
    mine = {k: v for k, v in after.items() if before.get(k) is not v}
    for kernel in expect:
        check(kernel in mine, f"{kernel} was never dispatched")
    for kernel, d in mine.items():
        check(d.get("reason") != "no-tpu", f"{kernel}: dispatch saw no TPU")
        check(not d.get("interpret"), f"{kernel} ran in interpret mode")
        check(d["path"] == "pallas" or d.get("reason") == "vmem-cap",
              f"{kernel} fell back to jnp ({d.get('reason')})")
    return mine


def fit_phase(label: str, spec, Y, device, expect, mesh=None):
    from repro.api import Embedding
    from repro.kernels import ops

    before = ops.last_dispatch()
    t0 = time.perf_counter()
    est = Embedding(spec, mesh=mesh).fit(Y)
    res = est.result_
    wall = time.perf_counter() - t0
    decisions = _kernel_decisions(before, ops.last_dispatch(), expect)
    e = [float(v) for v in res.energies]
    n = res.n_iters
    steady = (float(res.times[-1] - res.times[1]) / (n - 1)
              if n > 1 else float("nan"))
    print(f"[{label}] platform={device.platform} kind={device.device_kind} "
          f"backend={est.backend_} model={spec.kind} iters={n} "
          f"E_first={e[0]:.6g} E_last={e[-1]:.6g}", flush=True)
    print(f"[{label}] smoke timing (not a benchmark): fit_wall_s={wall:.2f} "
          f"setup_s={wall - float(res.times[-1]):.2f} "
          f"first_iter_with_compile_s={float(res.times[1]):.3f} "
          f"steady_iter_s={steady:.4f}", flush=True)
    print(f"[{label}] dispatch {json.dumps(decisions, sort_keys=True)}",
          flush=True)
    check(all(map(_finite, e)), f"{label}: non-finite energy {e}")
    check(e[-1] < e[0], f"{label}: energy did not decrease "
                        f"({e[0]:.6g} -> {e[-1]:.6g})")
    return est


def _finite(x: float) -> bool:
    return x == x and abs(x) != float("inf")


def serve_phase(est, Y, device) -> None:
    import threading

    import numpy as np

    from repro.api import TransformSpec
    from repro.serve import EmbeddingServer

    rng = np.random.default_rng(1)
    rows = rng.choice(Y.shape[0], size=SERVE_ROWS, replace=False)
    Yq = (Y[rows] + rng.normal(scale=0.01, size=(SERVE_ROWS, Y.shape[1]))
          ).astype(np.float32)
    # exhaustive repulsion (every training anchor, every iteration): the
    # deterministic transform, whose server-vs-direct parity
    # benchmarks/serve_bench.py also pins
    tspec = TransformSpec(solver="rowwise", exhaustive=True)
    direct = np.asarray(est.transform(Yq, spec=tspec))
    served = np.full_like(direct, np.nan)
    errors = []
    with EmbeddingServer(est, tspec, max_batch=SERVE_BATCH,
                         max_delay_s=0.05) as srv:
        t0 = time.perf_counter()
        srv.warmup()
        t_warm = time.perf_counter() - t0

        def client(idxs):
            try:
                for i in idxs:
                    served[i] = np.asarray(srv.transform(Yq[i],
                                                         timeout=600.0))
            except Exception as e:          # re-raised after the join
                errors.append(e)

        # one client per batch slot, so the micro-batcher forms batches
        # of up to SERVE_BATCH rows
        threads = [threading.Thread(target=client,
                                    args=(range(c, SERVE_ROWS, SERVE_BATCH),))
                   for c in range(SERVE_BATCH)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        t_all = time.perf_counter() - t0
    if errors:
        raise errors[0]
    stats = srv.stats()
    err = float(np.max(np.abs(served - direct)))
    print(f"[d serve] platform={device.platform} kind={device.device_kind} "
          f"requests={stats['n_requests']} batches={stats['n_batches']} "
          f"mean_batch={stats.get('mean_batch', 0.0):.2f} "
          f"max_abs_dev_vs_direct={err:.3e} (limit {SERVE_TOL:g})",
          flush=True)
    print(f"[d serve] smoke timing (not a benchmark): warmup_s={t_warm:.2f} "
          f"requests_wall_s={t_all:.3f}", flush=True)
    check(bool(np.all(np.isfinite(served))), "served a non-finite row")
    check(stats["n_batches"] < SERVE_ROWS, "no micro-batch held two rows")
    check(err <= SERVE_TOL, f"server parity {err:.3e} > {SERVE_TOL:g}")


def layout_phase(est, Y, device) -> None:
    """The ELL matvec on the mnist20k graph and on its reverse, in the
    HBM layout and in the VMEM layout, against a float64 host oracle.
    No fit here reaches the HBM layout (X at N = 20000 fits the VMEM
    budget), but every fit above about 24k rows runs on it."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.embedding_paper import MNIST20K
    from repro.kernels import ops
    from repro.sparse.graph import sparse_affinities

    saff = sparse_affinities(jnp.asarray(Y), k=int(3 * MNIST20K.perplexity),
                             perplexity=MNIST20K.perplexity, model="tsne")
    X = jnp.asarray(est.embedding_, jnp.float32)
    Xh = np.asarray(X, np.float64)
    for name, g in (("forward", saff.graph), ("reverse", saff.rev)):
        idx, w = np.asarray(g.indices), np.asarray(g.weights, np.float64)
        deg = w.sum(axis=1, keepdims=True)
        ref = deg * Xh - np.einsum("nk,nkd->nd", w, Xh[idx])
        # f32 accumulation over k terms: k * eps(f32) of the term scale
        k = idx.shape[1]
        limit = k * float(np.finfo(np.float32).eps) * float(
            np.max(np.abs(deg * Xh)))
        out, times, tiles = {}, {}, {}
        for layout in ("hbm", "vmem"):
            def run():
                return jax.block_until_ready(ops.ell_lap_matvec(
                    X, g.indices, g.weights, impl="pallas", layout=layout))
            out[layout] = np.asarray(run(), np.float64)
            d = ops.last_dispatch("ell_lap_matvec")
            check(d["layout"] == layout and d["path"] == "pallas"
                  and not d["interpret"],
                  f"{name} graph: asked for the {layout} layout, got {d}")
            tiles[layout] = (d["block_rows"], d["chunk"])
            t0 = time.perf_counter()
            for _ in range(SMOKE_REPS):
                run()
            times[layout] = (time.perf_counter() - t0) / SMOKE_REPS
        err = {lay: float(np.max(np.abs(o - ref))) for lay, o in out.items()}
        gap = float(np.max(np.abs(out["hbm"] - out["vmem"])))
        print(f"[b ell layouts] platform={device.platform} "
              f"kind={device.device_kind} graph={name} n={idx.shape[0]} "
              f"k={k} max_abs_err_hbm={err['hbm']:.3e} "
              f"max_abs_err_vmem={err['vmem']:.3e} hbm_vs_vmem={gap:.3e} "
              f"(limit {limit:.3e}) tiles(block_rows,chunk)={tiles}",
              flush=True)
        print(f"[b ell layouts] smoke timing (not a benchmark): graph={name} "
              f"hbm_call_s={times['hbm']:.5f} "
              f"vmem_call_s={times['vmem']:.5f}", flush=True)
        for lay in ("hbm", "vmem"):
            check(err[lay] <= limit, f"{name} graph, {lay} layout: error "
                                     f"{err[lay]:.3e} > {limit:.3e}")


def one_chip(device) -> None:
    from repro.api import EmbedSpec
    from repro.configs.embedding_paper import COIL20, MNIST20K
    from repro.data import coil_like, mnist_like

    Yc = coil_like(n_per=COIL20.n_points // 10, loops=10,
                   dim=COIL20.input_dim, seed=0)
    check(Yc.shape == (COIL20.n_points, COIL20.input_dim), str(Yc.shape))
    fit_phase("a coil20/dense", EmbedSpec(
        kind="ee", strategy="sd", backend="dense", lam=COIL20.lam,
        perplexity=COIL20.perplexity, max_iters=10, tol=0.0), Yc, device,
        ("pairwise_terms",))

    Ym, _ = mnist_like(n=MNIST20K.n_points, dim=MNIST20K.input_dim, seed=0)
    tsne = EmbedSpec(kind="tsne", strategy="sd", backend="sparse", lam=1.0,
                     perplexity=MNIST20K.perplexity, max_iters=10, tol=0.0)
    est = fit_phase("b mnist20k/sparse", tsne, Ym, device,
                    ("ell_lap_matvec",))
    fit_phase("b mnist20k/sparse", tsne.replace(kind="ee", lam=MNIST20K.lam),
              Ym, device, ("ell_lap_matvec",))
    fit_phase("c mnist20k/tree", tsne.replace(backend="tree"), Ym, device,
              ("bh_interaction", "ell_lap_matvec"))
    layout_phase(est, Ym, device)
    serve_phase(est, Ym, device)


def four_chips(device) -> None:
    import jax
    import numpy as np

    from repro.api import EmbedSpec
    from repro.configs.embedding_paper import MNIST20K
    from repro.data import mnist_like
    from repro.launch.mesh import make_host_mesh

    check(len(jax.devices()) == 4,
          f"--chips 4 needs 4 devices, JAX sees {len(jax.devices())}")
    mesh = make_host_mesh()
    check(dict(mesh.shape) == {"data": 4, "model": 1}, str(mesh.shape))
    Ym, _ = mnist_like(n=MNIST20K.n_points, dim=MNIST20K.input_dim, seed=0)
    spec = EmbedSpec(kind="tsne", strategy="sd", backend="sparse-sharded",
                     lam=1.0, perplexity=MNIST20K.perplexity, max_iters=10,
                     tol=0.0)
    sharded = fit_phase("4chip mnist20k/sparse-sharded", spec, Ym, device,
                        ("ell_lap_matvec_local",), mesh=mesh)
    single = fit_phase("4chip mnist20k/sparse", spec.replace(backend="sparse"),
                       Ym, device, ("ell_lap_matvec",))
    e_sh = np.asarray(sharded.result_.energies)
    e_1 = np.asarray(single.result_.energies)
    check(e_sh.shape == e_1.shape, f"iteration counts differ {e_sh.shape} "
                                   f"vs {e_1.shape}")
    rel = np.abs(e_sh - e_1) / np.abs(e_1)
    print(f"[4chip parity] max_rel_energy_dev={float(np.max(rel)):.3e} "
          f"(limit {SHARDED_RTOL:g}) "
          f"per_iter={np.array2string(rel, precision=3)}", flush=True)
    check(bool(np.all(rel <= SHARDED_RTOL)),
          f"sharded energies leave rtol {SHARDED_RTOL:g}: {rel}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    src = os.path.join(HERE, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        print("chip_smoke: no src/repro beside this script — run it from a "
              "checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, src)
    os.environ.setdefault("REPRO_AUTOTUNE_CACHE",
                          os.path.join(HERE, ".autotune_cache.json"))

    import jax

    from repro.compile_cache import enable_compile_cache

    print(f"chip_smoke: compile cache at {enable_compile_cache()}",
          flush=True)
    device = jax.devices()[0]
    if device.platform != "tpu":
        print(f"chip_smoke: JAX found no TPU (platform {device.platform!r})",
              file=sys.stderr)
        return 1
    try:
        (four_chips if args.chips == 4 else one_chip)(device)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

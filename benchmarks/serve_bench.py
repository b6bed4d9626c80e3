"""Serving benchmark: artifact round-trip + transform server under load.

Exercises the whole `repro.serve` story end to end and produces the
numbers the CI serve gate compares against the committed
`results/serve.json` baseline:

  * fit a small embedding, `save()` the artifact, `load()` it back and
    assert the training embedding survived BIT-EXACTLY
    (`roundtrip_bitexact`);
  * run an `EmbeddingServer` over the LOADED estimator with concurrent
    client threads firing single-row requests, report p50/p99 latency and
    sustained requests/s;
  * compare every served response against one direct
    `Embedding.transform` over the same queries — `max_abs_err` must be
    <= 1e-5 (the rowwise solver is batch-invariant, so this is exact on
    one device; the budget only absorbs XLA reduction-order tiling).

`--http-smoke` instead drives the wire path: a first child process fits,
saves the artifact and computes the direct-transform reference; then
`python -m repro.serve.http` runs as a second child, concurrent HTTP
clients hit it, response parity and p99 are checked, and a SIGTERM must
drain it gracefully (exit code 0).  The parent never computes with JAX,
so on an accelerator the server child can hold the chip.  The CI
serve-smoke job runs exactly this.

    PYTHONPATH=src python -m benchmarks.serve_bench [--http-smoke]
"""
from __future__ import annotations

import argparse
import json
import os
import tempfile
import threading
import time

import numpy as np

from repro.api import Embedding, EmbedSpec, TransformSpec
from repro.data import mnist_like
from repro.serve import EmbeddingServer
from repro.serve.metrics import percentiles

from .common import csv_row


def _problem(n: int, kind: str, iters: int, perplexity: float, dim: int):
    Y, _ = mnist_like(n=n, dim=dim)
    Y = np.asarray(Y, dtype=np.float32)
    spec = EmbedSpec(kind=kind, perplexity=perplexity,
                     n_neighbors=int(3 * perplexity), max_iters=iters,
                     tol=0.0, seed=0)
    return Y, Embedding(spec).fit(Y)


def run(n=512, n_queries=64, kind="ee", iters=30, perplexity=8.0,
        transform_iters=20, n_clients=8, max_batch=16,
        out_json="results/serve.json") -> dict:
    """Returns the bench's "serve" section:
    {p50_ms, p99_ms, rps, max_abs_err, roundtrip_bitexact, n_requests,
    mean_batch}; also writes it to `out_json` (the committed baseline
    shape)."""
    Y, est = _problem(n, kind, iters, perplexity, dim=16)
    rng = np.random.default_rng(1)
    Yq = Y[rng.choice(n, size=n_queries, replace=False)] \
        + rng.normal(scale=0.01, size=(n_queries, Y.shape[1])) \
        .astype(np.float32)

    # artifact round trip: the served estimator is the LOADED one, so the
    # parity number below also covers save/load
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "model.npz")
        est.save(path)
        loaded = Embedding.load(path)
    bitexact = bool(np.array_equal(np.asarray(est.embedding_),
                                   np.asarray(loaded.embedding_)))

    tspec = TransformSpec(solver="rowwise", exhaustive=True,
                          max_iters=transform_iters)
    direct = np.asarray(est.transform(Yq, spec=tspec))

    latencies: list[float] = []
    responses = np.zeros_like(direct)
    lock = threading.Lock()

    with EmbeddingServer(loaded, tspec, max_batch=max_batch,
                         max_delay_s=0.002) as srv:
        srv.warmup()              # all pow2 buckets up to max_batch

        def client(idxs):
            for i in idxs:
                t0 = time.perf_counter()
                x = srv.transform(Yq[i], timeout=120.0)
                dt = time.perf_counter() - t0
                with lock:
                    latencies.append(dt)
                    responses[i] = np.asarray(x)

        shards = [range(c, n_queries, n_clients) for c in range(n_clients)]
        threads = [threading.Thread(target=client, args=(s,))
                   for s in shards]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - t0
        stats = srv.stats()

    pct = percentiles([s * 1e3 for s in latencies], qs=(50, 99))
    out = {
        "p50_ms": pct["p50"],
        "p99_ms": pct["p99"],
        "rps": n_queries / wall,
        "max_abs_err": float(np.max(np.abs(responses - direct))),
        "roundtrip_bitexact": bitexact,
        "n_requests": stats["n_requests"],
        "mean_batch": stats.get("mean_batch", 0.0),
    }
    csv_row("serve", kind, n, n_queries, f"{out['p50_ms']:.1f}",
            f"{out['p99_ms']:.1f}", f"{out['rps']:.1f}",
            f"{out['max_abs_err']:.2e}", int(bitexact))
    if out_json:
        if os.path.dirname(out_json):
            os.makedirs(os.path.dirname(out_json), exist_ok=True)
        with open(out_json, "w") as f:
            json.dump(out, f, indent=1)
    return out


def http_prepare(out_dir: str, n=300, n_queries=12, kind="ee", iters=20,
                 perplexity=8.0) -> None:
    """First child of `http_smoke`: fit, save `model.npz`, and write the
    queries with their direct-transform answers to `reference.npz`."""
    Y, est = _problem(n, kind, iters, perplexity, dim=8)
    rng = np.random.default_rng(2)
    Yq = (Y[rng.choice(n, size=n_queries, replace=False)]
          + rng.normal(scale=0.01, size=(n_queries, Y.shape[1]))
          .astype(np.float32))
    # the HTTP CLI serves the DEFAULT rowwise spec; the parity reference
    # must resolve the same way (same iters/negatives/tol from est.spec)
    tspec = TransformSpec(solver="rowwise")
    direct = np.asarray(est.transform(Yq, spec=tspec))
    est.save(os.path.join(out_dir, "model.npz"))
    np.savez(os.path.join(out_dir, "reference.npz"), queries=Yq,
             direct=direct)


def http_smoke(n=300, n_queries=12, kind="ee", iters=20, perplexity=8.0,
               n_clients=4, p99_budget_ms=None) -> dict:
    """End-to-end wire check for CI: subprocess HTTP server from a saved
    artifact, concurrent clients, parity <= 1e-5, p99 under budget,
    graceful SIGTERM drain.  Raises on any failure."""
    import signal
    import subprocess
    import sys
    import urllib.request

    if p99_budget_ms is None:
        p99_budget_ms = float(os.environ.get("SERVE_P99_BUDGET_MS", 30000))

    import socket
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [os.path.join(root, "src"), root, env.get("PYTHONPATH", "")])
    with tempfile.TemporaryDirectory() as d:
        # one process per chip: the fit and the reference run in a child
        # that exits before the server child starts
        subprocess.run(
            [sys.executable, "-m", "benchmarks.serve_bench",
             "--http-prepare", d, "--n", str(n), "--queries",
             str(n_queries), "--kind", kind, "--iters", str(iters)],
            env=env, check=True)
        path = os.path.join(d, "model.npz")
        with np.load(os.path.join(d, "reference.npz")) as ref:
            Yq, direct = ref["queries"], ref["direct"]
        proc = subprocess.Popen(
            [sys.executable, "-m", "repro.serve.http", "--artifact", path,
             "--port", str(port), "--max-batch", "8",
             "--max-delay-ms", "2"],
            env=env)
        base = f"http://127.0.0.1:{port}"
        try:
            deadline = time.time() + 120
            while True:
                try:
                    urllib.request.urlopen(f"{base}/healthz", timeout=2)
                    break
                except Exception:
                    if proc.poll() is not None:
                        raise RuntimeError(
                            f"http server died (rc={proc.returncode})")
                    if time.time() > deadline:
                        raise TimeoutError("http server never came up")
                    time.sleep(0.2)

            latencies, results, errs = [], {}, []
            lock = threading.Lock()

            def client(idxs):
                try:
                    for i in idxs:
                        body = json.dumps(
                            {"rows": [Yq[i].tolist()]}).encode()
                        req = urllib.request.Request(
                            f"{base}/transform", data=body,
                            headers={"Content-Type": "application/json"})
                        t0 = time.perf_counter()
                        with urllib.request.urlopen(req, timeout=120) as r:
                            obj = json.loads(r.read())
                        dt = time.perf_counter() - t0
                        with lock:
                            latencies.append(dt * 1e3)
                            results[i] = np.asarray(obj["embedding"][0])
                except Exception as e:       # surfaced after join
                    with lock:
                        errs.append(e)

            shards = [range(c, n_queries, n_clients)
                      for c in range(n_clients)]
            threads = [threading.Thread(target=client, args=(sh,))
                       for sh in shards]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            if errs:
                raise errs[0]

            served = np.stack([results[i] for i in range(n_queries)])
            err = float(np.max(np.abs(served - direct)))
            pct = percentiles(latencies, qs=(50, 99))
            csv_row("serve-http", kind, n, n_queries,
                    f"{pct['p50']:.1f}", f"{pct['p99']:.1f}",
                    f"{err:.2e}")
            if err > 1e-5:
                raise AssertionError(
                    f"http responses diverge from direct transform: "
                    f"max abs err {err:.3e} > 1e-5")
            if pct["p99"] > p99_budget_ms:
                raise AssertionError(
                    f"http p99 {pct['p99']:.0f}ms over the "
                    f"{p99_budget_ms:.0f}ms budget")

            proc.send_signal(signal.SIGTERM)
            rc = proc.wait(timeout=60)
            if rc != 0:
                raise AssertionError(
                    f"server did not drain cleanly on SIGTERM (rc={rc})")
        finally:
            if proc.poll() is None:
                proc.kill()
                proc.wait(timeout=30)
    print("serve-http: OK — parity, p99 and graceful drain all pass",
          flush=True)
    return {"p50_ms": pct["p50"], "p99_ms": pct["p99"], "max_abs_err": err}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--queries", type=int, default=64)
    ap.add_argument("--kind", default="ee")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--transform-iters", type=int, default=20)
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=16)
    ap.add_argument("--out", default="results/serve.json")
    ap.add_argument("--http-smoke", action="store_true",
                    help="run the subprocess HTTP end-to-end check "
                         "instead of the in-process load benchmark")
    ap.add_argument("--http-prepare", default=None, help=argparse.SUPPRESS)
    a = ap.parse_args()
    if a.http_prepare is not None:      # internal: http_smoke's first child
        http_prepare(a.http_prepare, n=a.n, n_queries=a.queries,
                     kind=a.kind, iters=a.iters)
        return
    if a.http_smoke:
        http_smoke(kind=a.kind)
        return
    run(n=a.n, n_queries=a.queries, kind=a.kind, iters=a.iters,
        transform_iters=a.transform_iters, n_clients=a.clients,
        max_batch=a.max_batch, out_json=a.out)


if __name__ == "__main__":
    main()

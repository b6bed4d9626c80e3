"""Where the paper meets the LM zoo: visualize an LM's token-embedding
table with SD-optimized t-SNE/EE (the paper's technique applied to learned
representations).

Trains a small LM briefly, takes its (vocab, d_model) embedding table,
builds SNE affinities over the most-frequent tokens, and minimizes t-SNE
with the cached-Cholesky spectral direction.

    PYTHONPATH=src python examples/token_embedding_viz.py
"""
import argparse
import os

import jax
import jax.numpy as jnp
import numpy as np

from repro.api import Embedding, EmbedSpec
from repro.configs import RunConfig, get_smoke_config
from repro.configs.base import ShapeConfig
from repro.core import LSConfig, laplacian_eigenmaps, make_affinities
from repro.data import batch_for
from repro.models import build_model, init_train_state, make_train_step
from repro.optim.adamw import AdamWConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--train-steps", type=int, default=10)
    ap.add_argument("--n-tokens", type=int, default=400)
    ap.add_argument("--kind", default="tsne")
    a = ap.parse_args()

    cfg = get_smoke_config(a.arch)
    model = build_model(cfg, RunConfig(remat="none"))
    state, _ = init_train_state(model, jax.random.PRNGKey(0))
    step_fn = jax.jit(make_train_step(
        model, AdamWConfig(warmup_steps=2, total_steps=a.train_steps)),
        donate_argnums=(0,))
    shape = ShapeConfig("t", "train", 64, 4)
    for step in range(a.train_steps):
        state, m = step_fn(state, batch_for(cfg, shape, step=step))
    print(f"trained {a.train_steps} steps, loss {float(m['loss']):.3f}")

    table = np.asarray(state["params"]["embed"]["table"], np.float32)
    if table.ndim == 3:
        table = table[0]
    Y = jnp.asarray(table[: a.n_tokens])
    print(f"embedding table slice: {Y.shape}")

    aff = make_affinities(Y, perplexity=25.0, model=a.kind)
    X0 = laplacian_eigenmaps(aff.Wp, 2) * 0.1
    spec = EmbedSpec(kind=a.kind, strategy="sd", backend="dense",
                     lam=1.0 if a.kind in ("ssne", "tsne") else 100.0,
                     max_iters=150, tol=1e-8,
                     ls=LSConfig(init_step="adaptive_grow"))
    res = Embedding(spec).fit(None, X0=X0, aff=aff).result_
    print(f"{a.kind}+SD: E {res.energies[0]:.4f} -> {res.energies[-1]:.4f} "
          f"in {res.n_iters} iters")
    os.makedirs("results", exist_ok=True)
    np.save("results/token_embedding_2d.npy", np.asarray(res.X))
    print("2-D token map saved to results/token_embedding_2d.npy")


if __name__ == "__main__":
    main()

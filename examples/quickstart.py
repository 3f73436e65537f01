"""Quickstart — the paper's own experiment (§7.3, Table 2) in ~40 lines.

Trains the supervised autoencoder on synthetic classification data under the
bi-level ℓ1,∞ constraint with double descent, and prints accuracy + column
sparsity against the unconstrained baseline.

    PYTHONPATH=src python examples/quickstart.py [--epochs 120] [--radius 1.0]
"""

import argparse
import sys

sys.path.insert(0, "src")

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.configs.types import ProjectionSpec
from repro.core.masks import sparsity
from repro.data import classification_synthetic
from repro.models import params as PM, sae
from repro.runtime.double_descent import double_descent
from repro.training.sae_tables import _accuracy, _train_fn


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--epochs", type=int, default=120)
    ap.add_argument("--radius", type=float, default=1.0)
    ap.add_argument("--samples", type=int, default=600)
    ap.add_argument("--features", type=int, default=800)
    args = ap.parse_args()

    x, y, informative = classification_synthetic(
        n_samples=args.samples, n_features=args.features,
        n_informative=64, class_sep=0.8)
    import dataclasses
    cfg = dataclasses.replace(registry.get_arch("sae-paper"),
                              d_model=args.features)
    ntr = int(0.8 * len(x))
    xtr, ytr, xte, yte = x[:ntr], y[:ntr], x[ntr:], y[ntr:]

    init = PM.init_params(sae.template(cfg), jax.random.PRNGKey(0))

    # --- baseline: no constraint
    base = _train_fn(cfg, xtr, ytr, epochs=args.epochs, lr=3e-3)(init, None)
    print(f"baseline        acc={_accuracy(base, cfg, xte, yte):5.1f}%  "
          f"sparsity=0.0%")

    # --- the paper: bi-level l1,inf constraint + double descent
    spec = ProjectionSpec(pattern=r"enc1/w", levels=(("inf", 1), (1, 1)),
                          radius=args.radius, transpose=True)
    fn = _train_fn(cfg, xtr, ytr, epochs=args.epochs, lr=3e-3, spec=spec)
    final, mask, stats = double_descent(init, fn, spec)
    acc = _accuracy(final, cfg, xte, yte)
    sp = float(sparsity(final["enc1"]["w"], axis=1))
    print(f"bilevel_l1inf   acc={acc:5.1f}%  sparsity={sp:.1f}%  "
          f"(eta={args.radius})")
    kept = int((jnp.max(jnp.abs(final['enc1']['w']), axis=1) > 0).sum())
    print(f"features kept: {kept}/{args.features} "
          f"(dataset has {len(informative)} informative)")


if __name__ == "__main__":
    main()

"""Paper §7.3 SAE experiments (Tables 2–5): accuracy vs structured sparsity
under different projections, with double descent.

Synthetic = make_classification clone (1000×2000, 64 informative, sep 0.8);
Lung-like = log-normal heteroscedastic generator (DESIGN.md §7 — the real
LUNG csv is not redistributable/offline). 80/20 split, 5 methods:
baseline (no projection), exact ℓ1,∞, bi-level ℓ1,∞, bi-level ℓ1,1,
bi-level ℓ1,2. Reported: test accuracy %, column-sparsity % of the first
encoder layer (the paper's metric).
"""

from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.configs.types import ProjectionSpec
from repro.core import project_l1inf_exact
from repro.core.masks import sparsity
from repro.data import classification_synthetic, lung_like
from repro.models import params as PM, sae
from repro.optim import adamw
from repro.optim.projection_hook import project_tree
from repro.runtime.double_descent import double_descent
from repro.configs.types import TrainConfig


def _train_fn(cfg, xtr, ytr, *, epochs, lr, spec=None, exact_radius=None,
              seed=0, alpha=0.1, constrain=False):
    """Returns train_epochs_fn(params, mask) for double_descent."""
    tcfg = TrainConfig(lr=lr, weight_decay=0.0, grad_clip=0.0, warmup=1,
                       total_steps=epochs, master_dtype="")
    batch = {"x": jnp.asarray(xtr), "y": jnp.asarray(ytr)}

    @jax.jit
    def step(params, opt, mask):
        (loss, _), g = jax.value_and_grad(sae.loss_fn, has_aux=True)(
            params, batch, cfg, alpha=alpha, act="silu")
        if mask is not None:
            g = jax.tree_util.tree_map(lambda a, m_: a * m_, g, mask)
        params, opt, _ = adamw.update(g, opt, params, tcfg)
        if mask is not None:
            params = jax.tree_util.tree_map(lambda p, m_: p * m_, params, mask)
        if constrain and spec is not None:
            params = project_tree(params, spec)
        elif constrain and exact_radius is not None:
            params = dict(params, enc1=dict(
                params["enc1"],
                w=project_l1inf_exact(params["enc1"]["w"].T, exact_radius).T))
        return params, opt, loss

    def train_epochs(params, mask):
        opt = adamw.init(params, tcfg)
        for _ in range(epochs):
            params, opt, loss = step(params, opt, mask)
        return params

    return train_epochs


def _accuracy(params, cfg, x, y):
    z, _ = sae.forward(params, jnp.asarray(x), cfg)
    return float(jnp.mean((jnp.argmax(z, -1) == jnp.asarray(y)).astype(jnp.float32)) * 100)


def run_dataset(name, x, y, *, radius, epochs=150, lr=3e-3, seed=0,
                prefix="sae", rewind=True, only=None):
    """5-method sweep on one dataset; rows ``(prefix_name_method, µs, derived)``.

    ``rewind=False`` runs the no-rewind double-descent ablation (descent #2
    fine-tunes the projected weights); ``only`` restricts to a subset of
    method names.
    """
    cfg_base = registry.get_arch("sae-paper")
    import dataclasses
    cfg = dataclasses.replace(cfg_base, d_model=x.shape[1])
    ntr = int(0.8 * len(x))
    rng = np.random.default_rng(seed)
    order = rng.permutation(len(x))
    tr, te = order[:ntr], order[ntr:]
    xtr, ytr, xte, yte = x[tr], y[tr], x[te], y[te]

    methods = {
        "baseline": dict(spec=None),
        "exact_l1inf": dict(exact_radius=radius),
        "bilevel_l1inf": dict(spec=ProjectionSpec(
            pattern=r"enc1/w", levels=(("inf", 1), (1, 1)), radius=radius,
            transpose=True)),
        "bilevel_l11": dict(spec=ProjectionSpec(
            pattern=r"enc1/w", levels=((1, 1), (1, 1)), radius=100 * radius,
            transpose=True)),
        "bilevel_l12": dict(spec=ProjectionSpec(
            pattern=r"enc1/w", levels=((2, 1), (1, 1)), radius=10 * radius,
            transpose=True)),
    }
    rows = []
    for mname, kw in methods.items():
        if only is not None and mname not in only:
            continue
        key = jax.random.PRNGKey(seed)
        init = PM.init_params(sae.template(cfg), key)
        fn = _train_fn(cfg, xtr, ytr, epochs=epochs, lr=lr, **kw)
        t0 = time.perf_counter()
        if mname == "baseline":
            final = fn(init, None)
        else:
            spec = kw.get("spec") or ProjectionSpec(pattern=r"enc1/w",
                                                    radius=radius)
            projector = None
            if "exact_radius" in kw:
                projector = lambda p: dict(p, enc1=dict(
                    p["enc1"],
                    w=project_l1inf_exact(p["enc1"]["w"].T, kw["exact_radius"]).T))
            final, _, _ = double_descent(init, fn, spec, projector=projector,
                                         rewind=rewind)
        dt = time.perf_counter() - t0
        acc = _accuracy(final, cfg, xte, yte)
        sp = float(sparsity(final["enc1"]["w"], axis=1))
        rows.append((f"{prefix}_{name}_{mname}", dt * 1e6,
                     f"acc={acc:.1f}%_colsparsity={sp:.1f}%"))
    return rows


def tables(full=False):
    out = []
    n = 1000 if full else 400
    m = 2000 if full else 600
    x, y, _ = classification_synthetic(n_samples=n, n_features=m,
                                       n_informative=64, class_sep=0.8)
    out += run_dataset("synthetic", x, y, radius=1.0,
                       epochs=150 if full else 80)
    if full:
        xl, yl, _ = lung_like()
        out += run_dataset("lung_like", xl, yl, radius=1.0, epochs=150)
    else:
        xl, yl, _ = lung_like(n_samples=400, n_features=600)
        out += run_dataset("lung_like", xl, yl, radius=1.0, epochs=80)
    return out

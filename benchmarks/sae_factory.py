"""``--only sae_factory``: the sparse-SAE training factory, end to end.

Four row groups into ``BENCH_sae_factory.json``:

1. Paper §7.3 accuracy-vs-column-sparsity tables (5 methods × synthetic +
   lung-like) at factory-bench sizes — ``run_dataset`` from ``sae_tables``
   with the ``sae_factory_`` prefix so the artifact is self-contained.
2. The no-rewind double-descent ablation (descent #2 fine-tunes projected
   weights instead of rewinding to init) on the bi-level ℓ1,∞ method.
3. The factory pipeline itself at miniature scale: harvest a smoke LM's
   residual stream, train one projected dictionary SAE per seed, report the
   cross-seed MMCS (dictionary-consistency headline) and reconstruction MSE.
4. GSP whole-network sparsification on a forced 8-device host mesh
   (subprocess, like ``projections.sharded_sweep``): every LM weight
   projected per step through the mesh executor; derived carries projected
   leaf count, feasibility, and mean column sparsity.
"""

from __future__ import annotations

import tempfile
import time

from repro.data import classification_synthetic, lung_like

from .devices import run_cpu_mesh_child
from .sae_tables import run_dataset

_GSP_CHILD = r"""
import sys, time
import jax
from repro.launch.mesh import make_host_mesh
from repro.training import sae_factory as F

assert jax.device_count() == 8, jax.device_count()
mesh = make_host_mesh(1, 8)
t0 = time.perf_counter()
g = F.gsp_whole_network(mesh=mesh, steps=int(sys.argv[1]))
dt = time.perf_counter() - t0
rows = [[
    "sae_factory_gsp_8dev", dt * 1e6 / int(sys.argv[1]),
    f"nproj={g['n_projected']}_feasible={int(g['feasible'])}"
    f"_colsparsity={g['mean_col_sparsity']:.1f}%_ndev={g['n_devices']}",
]]
"""


def _tables_rows(full):
    n = 1000 if full else 240
    m = 2000 if full else 300
    epochs = 150 if full else 40
    rows = []
    x, y, _ = classification_synthetic(n_samples=n, n_features=m,
                                       n_informative=64, class_sep=0.8)
    rows += run_dataset("synthetic", x, y, radius=1.0, epochs=epochs,
                        prefix="sae_factory")
    xl, yl, _ = lung_like(n_samples=n, n_features=m) if not full else lung_like()
    rows += run_dataset("lung_like", xl, yl, radius=1.0, epochs=epochs,
                        prefix="sae_factory")
    # no-rewind ablation: descent #2 fine-tunes the projected weights
    rows += run_dataset("synthetic_norewind", x, y, radius=1.0, epochs=epochs,
                        prefix="sae_factory", rewind=False,
                        only=("bilevel_l1inf",))
    return rows


def _factory_rows(full):
    from repro.training import sae_factory as F

    fcfg = F.SAEFactoryConfig(
        layers=(0,), harvest_steps=4 if full else 2,
        train_steps=60 if full else 12, sae_batch=64, microbatch=32,
        expansion=4 if full else 2, radius=0.5)
    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter()
        out = F.run_factory(fcfg, d, seeds=(0, 1))
        dt = time.perf_counter() - t0
    rec = out["layers"][0]
    mmcs = rec["mmcs"]["seed0_vs_seed1"]
    mse = rec["metrics"][0]["mse"]
    rows = [("sae_factory_pipeline_layer0", dt * 1e6,
             f"mmcs={mmcs:.3f}_mse={mse:.4f}")]
    # head-structured variant (§6): 3-D encoder, tri-level l1,inf,inf ball
    hcfg = F.SAEFactoryConfig(
        layers=(0,), harvest_steps=4 if full else 2,
        train_steps=60 if full else 12, sae_batch=64, microbatch=32,
        expansion=4 if full else 2, radius=0.5, heads=2)
    with tempfile.TemporaryDirectory() as d:
        t0 = time.perf_counter()
        out = F.run_factory(hcfg, d, seeds=(0, 1))
        dt = time.perf_counter() - t0
    rec = out["layers"][0]
    mmcs = rec["mmcs"]["seed0_vs_seed1"]
    mse = rec["metrics"][0]["mse"]
    rows.append(("sae_factory_pipeline_heads2_layer0", dt * 1e6,
                 f"mmcs={mmcs:.3f}_mse={mse:.4f}"
                 f"_levels={len(F.effective_levels(hcfg))}"))
    return rows


def _gsp_row(full):
    return run_cpu_mesh_child(_GSP_CHILD, str(4 if full else 2), timeout=1200)


def factory_sweep(full=False):
    return _tables_rows(full) + _factory_rows(full) + _gsp_row(full)

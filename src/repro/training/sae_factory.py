"""Sparse-SAE training factory — the paper's headline application, end to end.

Three stages, each reusing the stack the previous PRs built:

1. **Harvest** (``data/activations.py``): run a configured LM from
   ``configs/`` over the deterministic token stream and shard per-layer
   residual/MLP activations to disk.
2. **Projected SAE training**: stream the shards back through
   ``DataPipeline`` into ``make_train_step(fused="auto")`` with the
   dictionary SAE (``models/sae.py``) — the encoder weight is projected onto
   the bi-/tri-level ball every optimizer step by the fused AdamW+project
   epilogue (single-device) or the §3 mesh executor (sharded params project
   in place). Learned dictionaries are compared across runs with MMCS
   (``training/mmcs.py``).
3. **GSP-style whole-network sparsification**: a training run whose
   projection spec matches *every* weight of the LM — each step projects
   every layer, with sharded leaves routed through the mesh executor
   (forced 8-device CPU mesh in CI; 1B–671B configs on real meshes).

``training/sae_tables.py`` runs the paper's §7.3 accuracy-vs-sparsity
tables; ``launch/sae_factory.py`` is the CLI. Everything here is deterministic given
(arch, seeds): the data cursor is the step index, inits are PRNGKey-seeded.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro import models
from repro.configs import registry
from repro.configs.types import ProjectionSpec, TrainConfig
from repro.core import multilevel_norm
from repro.data import DataConfig, DataPipeline
from repro.data.activations import HarvestConfig, harvest, read_meta
from repro.models import params as PM, sae
from repro.optim import adamw
from repro.optim.projection_hook import matched_names, tree_sparsity
from repro.training import step as TS


# ------------------------------------------------------------------ stage 1/2
@dataclasses.dataclass(frozen=True)
class SAEFactoryConfig:
    """One factory run: which model, what to harvest, how to train the SAE."""
    arch: str = "stablelm-1.6b"
    smoke: bool = True               # reduced arch (CPU tests); False = full
    site: str = "resid"              # harvest site
    layers: Optional[Sequence[int]] = None   # None -> all layers
    harvest_steps: int = 4           # shards per layer
    seq_len: int = 16
    lm_batch: int = 4                # sequences per harvest step
    expansion: int = 4               # d_dict = expansion * d_model
    train_steps: int = 40
    sae_batch: int = 64              # rows per SAE optimizer step
    microbatch: int = 32
    lr: float = 1e-2
    radius: float = 1.0
    levels: tuple = (("inf", 1), (1, 1))     # bi-level l1,inf by default
    heads: int = 1                   # >1: head-structured dictionary (§6) —
                                     # 3-D encoder + tri-level projection
    method: str = "bisect"
    seed: int = 0


def effective_levels(fcfg: SAEFactoryConfig) -> tuple:
    """The norm design actually projected: a head-structured factory
    (``heads > 1``) upgrades the default bi-level design to the paper's §6
    tri-level ℓ1,∞,∞ (one ∞ level per head axis of the 3-D encoder); an
    explicit 3-axis ``fcfg.levels`` wins."""
    if fcfg.heads == 1 or sum(k for _, k in fcfg.levels) != 2:
        return tuple(fcfg.levels)
    return (("inf", 1),) + tuple(fcfg.levels)


def lm_for(fcfg: SAEFactoryConfig):
    """(cfg, api, params) for the harvest model, seeded by ``fcfg.seed``."""
    cfg = (registry.smoke_config(fcfg.arch) if fcfg.smoke
           else registry.get_arch(fcfg.arch))
    api = models.get(cfg)
    params = PM.init_params(api.template(cfg), jax.random.PRNGKey(fcfg.seed))
    return cfg, api, params


def harvest_activations(fcfg: SAEFactoryConfig, out_dir, params=None) -> dict:
    """Stage 1: run the LM, shard activations. Returns the manifest."""
    cfg, api, init = lm_for(fcfg)
    pipe = DataPipeline(DataConfig(
        vocab=cfg.vocab, seq_len=fcfg.seq_len, global_batch=fcfg.lm_batch,
        microbatch=fcfg.lm_batch, seed=fcfg.seed))
    hcfg = HarvestConfig(site=fcfg.site, layers=fcfg.layers,
                         n_steps=fcfg.harvest_steps)
    return harvest(params if params is not None else init, cfg, pipe, out_dir,
                   hcfg=hcfg, forward=api.forward)


def sae_projection_spec(fcfg: SAEFactoryConfig) -> ProjectionSpec:
    """The per-step constraint: encoder columns (features) live on the ball.

    ``transpose=True`` groups by dictionary feature (paper §7.3 — the SAE's
    feature-selection orientation), exactly like the table experiments. With
    ``heads > 1`` the encoder is 3-D and the transposed view is
    (d_per_head, heads, d_in): the tri-level design aggregates ∞ over the
    per-head slots, ∞ over heads, then solves ℓ1 over d_in — zeroing whole
    heads, not just whole features.
    """
    return ProjectionSpec(pattern=r"enc/w", levels=effective_levels(fcfg),
                          radius=fcfg.radius, every=1, method=fcfg.method,
                          transpose=True)


def sae_train_config(fcfg: SAEFactoryConfig) -> TrainConfig:
    return TrainConfig(
        microbatch=fcfg.microbatch, lr=fcfg.lr, weight_decay=0.0,
        grad_clip=1.0, warmup=2, total_steps=max(fcfg.train_steps, 2),
        master_dtype="", compute_dtype="float32", remat=False,
        projection=sae_projection_spec(fcfg), seed=fcfg.seed)


def init_sae_state(d_in: int, d_dict: int, tcfg: TrainConfig, key, *,
                   heads: int = 1):
    params = PM.init_params(sae.dict_template(d_in, d_dict, heads=heads), key,
                            jnp.dtype(tcfg.param_dtype))
    return {"params": params, "opt": adamw.init(params, tcfg)}


def make_sae_train_step(tcfg: TrainConfig, *, l1: float = 0.0,
                        fused="auto", mesh=None, param_specs=None):
    """The projected dictionary-SAE step: ``make_train_step`` with the
    reconstruction loss — fused AdamW+project epilogue on the single-device
    path, mesh-native in-place projection when ``mesh``/``param_specs`` are
    given."""
    return TS.make_train_step(
        None, tcfg, None, fused=fused, mesh=mesh, param_specs=param_specs,
        loss_fn=lambda p, xb: sae.dict_loss(p, xb.astype(jnp.float32), l1=l1))


def train_sae(harvest_dir, layer: int, fcfg: SAEFactoryConfig, *,
              seed: Optional[int] = None) -> dict:
    """Stage 2 for one layer: stream shards into projected SAE training.

    Returns ``{"params", "metrics", "dictionary", "sparsity"}`` — the
    dictionary is the decoder weight transposed to (d_model, d_dict), ready
    for ``mmcs``.
    """
    meta = read_meta(harvest_dir)
    d_in = meta["d_model"]
    d_dict = fcfg.expansion * d_in
    seed = fcfg.seed if seed is None else seed
    tcfg = sae_train_config(fcfg)
    pipe = DataPipeline(DataConfig(
        vocab=1, seq_len=0, global_batch=fcfg.sae_batch,
        microbatch=fcfg.microbatch, activation_dir=str(harvest_dir),
        activation_layer=layer))
    state = init_sae_state(d_in, d_dict, tcfg, jax.random.PRNGKey(seed),
                           heads=fcfg.heads)
    step = jax.jit(make_sae_train_step(tcfg))
    last = {}
    for i in range(fcfg.train_steps):
        state, m = step(state, {"tokens": jnp.asarray(pipe.batch(i))})
    last = {k: float(v) for k, v in m.items()}
    params = state["params"]
    eval_rows = jnp.asarray(pipe.batch(0)).reshape(-1, d_in).astype(jnp.float32)
    diag = {k: float(v) for k, v in sae.dict_metrics(params, eval_rows).items()}
    spec = sae_projection_spec(fcfg)
    return {
        "params": params,
        "metrics": dict(last, **diag),
        # head-structured dec/w is (heads, d_dict//heads, d_in): flatten the
        # head axes back to d_dict before the (d_model, d_dict) orientation
        "dictionary": np.asarray(params["dec"]["w"]).reshape(-1, d_in).T,
        "sparsity": {k: float(v)
                     for k, v in tree_sparsity(params, spec).items()},
    }


def run_factory(fcfg: SAEFactoryConfig, workdir, *, seeds=(0, 1),
                lm_params=None) -> dict:
    """Harvest once, train one SAE per (layer, seed), cross-compare with MMCS.

    The per-layer MMCS across seeds is the factory's headline consistency
    number (dictionaries learned from the same activations should agree up to
    permutation/sign — exactly MMCS's invariances). ``lm_params`` harvests
    from a trained checkpoint's weights instead of the seeded init (the CLI's
    ``--checkpoint``).
    """
    from repro.training.mmcs import mmcs_sym

    meta = harvest_activations(fcfg, workdir, params=lm_params)
    out = {"meta": meta, "layers": {}}
    for layer in meta["layers"]:
        runs = {s: train_sae(workdir, layer, fcfg, seed=s) for s in seeds}
        pairs = {}
        slist = list(seeds)
        for i, a in enumerate(slist):
            for b in slist[i + 1:]:
                pairs[f"seed{a}_vs_seed{b}"] = float(mmcs_sym(
                    runs[a]["dictionary"], runs[b]["dictionary"]))
        out["layers"][layer] = {
            "mmcs": pairs,
            "metrics": {s: runs[s]["metrics"] for s in seeds},
            "sparsity": {s: runs[s]["sparsity"] for s in seeds},
        }
    return out


# ------------------------------------------------------------------- stage 3
def constraint_report(params, spec: ProjectionSpec) -> dict:
    """Max multilevel-norm violation over matched leaves (0 == feasible).

    Leading (stacked) axes are enumerated exactly like the hook's vmap, so a
    single infeasible layer of a scanned stack can't hide in an aggregate.
    """
    pat = re.compile(spec.pattern)
    need = sum(k for _, k in spec.levels)
    levels = list(spec.levels)
    report = {}

    def norm_of(w):
        if spec.transpose:
            w = jnp.swapaxes(w, -1, -2) if need == 2 else jnp.transpose(
                w, tuple(range(w.ndim - need)) + tuple(
                    reversed(range(w.ndim - need, w.ndim))))
        fn = lambda x: multilevel_norm(x, levels)
        for _ in range(w.ndim - need):
            fn = jax.vmap(fn)
        return jnp.max(jnp.atleast_1d(fn(w)))

    def one(path, w):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        if hasattr(w, "ndim") and w.ndim >= need and pat.search(name):
            report[name] = float(norm_of(jnp.asarray(w, jnp.float32)))
        return w

    jax.tree_util.tree_map_with_path(one, params)
    viol = max((v - spec.radius for v in report.values()), default=0.0)
    return {"norms": report, "max_violation": max(viol, 0.0),
            "feasible": viol <= spec.radius * 1e-3 + 1e-5}


def gsp_whole_network(arch: str = "stablelm-1.6b", *, mesh=None,
                      steps: int = 2, radius: float = 3.0,
                      pattern: str = r".*", microbatch: int = 2,
                      seq_len: int = 17, seed: int = 0) -> dict:
    """GSP-style whole-network sparsification: project EVERY weight per step.

    ``pattern=r".*"`` matches every >=2-D parameter of the LM — embeddings,
    attention projections (trailing (heads, head_dim) axes: the paper's §6
    head-structured sparsity), and MLP weights alike. With ``mesh`` given,
    leaves whose trailing axes are sharded project in place through the §3
    schedule executor under shard_map (no gather); the rest take the vmapped
    single-device path. Returns per-leaf column sparsity and a feasibility
    report — the CI ``sae`` job runs this on a forced 8-device CPU mesh.
    """
    from repro.parallel import sharding as SH

    cfg = registry.smoke_config(arch)
    api = models.get(cfg)
    proj = ProjectionSpec(pattern=pattern, radius=radius, every=1,
                          method="bisect")
    tcfg = TrainConfig(microbatch=microbatch, lr=1e-3, warmup=2,
                       total_steps=max(steps, 2), master_dtype="",
                       remat=False, projection=proj, seed=seed)
    state = TS.init_state(cfg, tcfg, api, jax.random.PRNGKey(seed))
    pspecs = None
    if mesh is not None:
        tpl = api.template(cfg)
        pspecs = PM.param_specs(tpl, SH.param_rules(mesh, fsdp=True),
                                SH.mesh_shape_dict(mesh))
        ospecs = adamw.state_specs(pspecs, tpl, tcfg)
        state = jax.device_put(state, SH.named(
            mesh, {"params": pspecs, "opt": ospecs}))
    step = jax.jit(TS.make_train_step(cfg, tcfg, api, impl="naive",
                                      mesh=mesh, param_specs=pspecs))
    pipe = DataPipeline(DataConfig(vocab=cfg.vocab, seq_len=seq_len,
                                   global_batch=2 * microbatch,
                                   microbatch=microbatch, seed=seed))
    for i in range(steps):
        state, metrics = step(state, {"tokens": jnp.asarray(pipe.batch(i))})
    params = jax.tree_util.tree_map(np.asarray, state["params"])
    names = matched_names(params, proj)
    rep = constraint_report(params, proj)
    sp = tree_sparsity(params, proj)
    return {
        "n_projected": len(names),
        "n_devices": int(mesh.devices.size) if mesh is not None else 1,
        "feasible": rep["feasible"],
        "max_violation": rep["max_violation"],
        "mean_col_sparsity": float(np.mean([float(v) for v in sp.values()])),
        "per_leaf_sparsity": {k: float(v) for k, v in sp.items()},
        "loss": float(metrics["loss"]),
    }

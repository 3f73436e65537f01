"""The jitted training step: microbatch gradient accumulation (lax.scan),
per-layer remat (inside the models), AdamW, and the paper's projection hook.

``make_train_step(cfg, tcfg, api, n_groups)`` returns

    train_step(state, batch) -> (state, metrics)

  state = {"params", "opt", } ; batch = {"tokens": (n_micro, mb, S)}

Loss is next-token CE computed with ``take_along_axis`` (vocab-sharding
friendly: the logsumexp partial-reduces over the sharded vocab axis and GSPMD
lowers the target-logit gather to a masked local gather + all-reduce — see
``xent``; no (B,S,V) one-hot is ever materialized).

When projection is enabled and the step is not mesh-native, the optimizer
epilogue runs FUSED (``optim/fused_step.py``): AdamW update, multi-level
projection, and the param/master casts execute in one pass per matched leaf
instead of three separate sweeps (``fused="auto"`` — force with
``fused=True/False``).
"""

from __future__ import annotations

import functools
import re
from typing import Callable

import jax
import jax.numpy as jnp

from repro.configs.types import ArchConfig, TrainConfig
from repro.core import multilevel
from repro.obs import jax_bridge
from repro.optim import adamw, fused_step
from repro.optim.projection_hook import _path_str, make_projection_hook


def xent(logits, targets):
    """logits (B,S,V) any float dtype; targets (B,S) int32. Mean nll in f32.

    take_along_axis (not a one-hot einsum): GSPMD lowers the vocab-axis gather
    on a model-sharded logits tensor to a masked local gather + all-reduce —
    O(B·S) bytes instead of materializing a (B,S,V) one-hot."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    tgt = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def make_loss_fn(cfg: ArchConfig, api, *, impl: str, n_groups: int,
                 remat: bool, compute_dtype, act_spec=None, logits_spec=None):
    def loss_fn(params, tokens):
        cparams = jax.tree_util.tree_map(
            lambda p: p.astype(compute_dtype)
            if p.dtype in (jnp.float32, jnp.bfloat16) else p, params)
        kw = {"remat": remat, "act_spec": act_spec}
        if cfg.family not in ("ssm", "hybrid"):
            kw["impl"] = impl
        if cfg.family in ("dense", "moe", "vlm"):
            kw["n_groups"] = n_groups
        logits, aux = api.forward(cparams, tokens[:, :-1], cfg, **kw)
        if logits_spec is not None:
            logits = jax.lax.with_sharding_constraint(logits, logits_spec)
        loss = xent(logits, tokens[:, 1:])
        if isinstance(aux, jax.Array) or (isinstance(aux, float) and aux):
            loss = loss + 0.01 * aux
        return loss

    return loss_fn


def make_train_step(cfg: ArchConfig, tcfg: TrainConfig, api, *,
                    impl: str = "chunked", n_groups: int = 1,
                    act_spec=None, logits_spec=None,
                    mesh=None, param_specs=None,
                    fused: bool | str = "auto",
                    telemetry_every: int = 0,
                    telemetry_marks: bool = False,
                    loss_fn: Callable = None) -> Callable:
    """Build the jitted projected train step (see module docstring).

    ``loss_fn(params, microbatch) -> scalar`` overrides the default LM
    next-token CE — the SAE factory passes the dictionary reconstruction loss
    and streams (n_micro, mb, d_model) activation batches through the same
    grad-accumulation scan, fused AdamW+project epilogue included
    (``batch["tokens"]`` is the per-step data leaf whatever its dtype/rank).

    ``telemetry_every > 0`` ships in-step telemetry to the obs registry
    through the host-callback bridge every that many steps (loss, grad norm,
    and — when projecting — per-leaf zero fraction and feasibility gap),
    batched in one ``lax.cond`` so off-cadence steps pay nothing.
    ``telemetry_marks=True`` additionally brackets the optimizer/projection
    epilogue with an *ordered* mark pair (``train_epilogue_seconds`` /
    ``train_projection_seconds`` histograms — the projection-time share of a
    step). Ordered callbacks serialize with the computation on EVERY step
    (they cannot ride the cadence cond), so marks are an opt-in deep-dive
    tool, priced separately by ``benchmarks/obs_overhead.py``. All of it
    rides :mod:`repro.obs.jax_bridge`, whose gate is trace-time static:
    with the bridge disabled the lowered step is bit-identical to
    ``telemetry_every=0`` (the overhead-off gate pins this).
    """
    compute_dtype = jnp.dtype(tcfg.compute_dtype)
    if loss_fn is None:
        loss_fn = make_loss_fn(cfg, api, impl=impl, n_groups=n_groups,
                               remat=tcfg.remat, compute_dtype=compute_dtype,
                               act_spec=act_spec, logits_spec=logits_spec)
    # single-pass epilogue: AdamW-update → project → cast fused per leaf
    # (optim/fused_step.py). "auto" = fused whenever projection is on and we
    # are not mesh-native (the sharded executor path keeps the hook, whose
    # shard_map placement the fused loop does not replicate yet).
    projecting = tcfg.projection is not None and tcfg.projection.enabled
    if fused == "auto":
        use_fused = projecting and mesh is None
    else:
        use_fused = bool(fused)
        if use_fused and mesh is not None:
            raise ValueError("fused=True is single-device/GSPMD only — the "
                             "mesh-native projection path needs fused='auto' "
                             "or fused=False")
    # plan the projection ONCE at step-build time (regex + backend resolution,
    # incl. method="auto" autotuning) — the per-step call is just the math.
    # mesh + param_specs make it mesh-native: sharded leaves project in place
    # under shard_map instead of relying on GSPMD (DESIGN.md §3)
    project = None if use_fused else make_projection_hook(
        tcfg.projection, mesh=mesh, param_specs=param_specs)

    emit_leaves = None
    if telemetry_every and projecting:
        # trace-time-static leaf matching (same rule as the hook); values
        # compute INSIDE the cond branch, so off-cadence steps pay nothing
        spec = tcfg.projection
        pat = re.compile(spec.pattern)
        need = sum(k for _, k in spec.levels)

        def _leaf_stats(w):
            x = w.astype(jnp.float32)
            if spec.transpose:
                x = jnp.swapaxes(x, -1, x.ndim - need) if need == 2 else \
                    jnp.transpose(x, tuple(range(x.ndim - need)) + tuple(
                        reversed(range(x.ndim - need, x.ndim))))
            fn = lambda v: multilevel.multilevel_norm(v, list(spec.levels))
            for _ in range(x.ndim - need):
                fn = jax.vmap(fn)
            worst = jnp.max(fn(x))
            return jnp.mean(w == 0), worst / spec.radius - 1.0

        def emit_leaves(params):
            def one(path, w):
                name = _path_str(path)
                if w.ndim >= need and pat.search(name):
                    zero_frac, gap = _leaf_stats(w)
                    jax_bridge.report("train_param_zero_frac", zero_frac,
                                      labels={"leaf": name})
                    jax_bridge.report("train_feasibility_gap", gap,
                                      labels={"leaf": name})
                return w

            jax.tree_util.tree_map_with_path(one, params)

    def train_step(state, batch):
        params = state["params"]
        tokens = batch["tokens"]              # (n_micro, mb, S)
        n_micro = tokens.shape[0]

        acc_dtype = (jnp.bfloat16 if tcfg.grad_allreduce_dtype == "bfloat16"
                     else jnp.float32)
        g0 = jax.tree_util.tree_map(
            lambda p: jnp.zeros(p.shape, acc_dtype), params)

        def micro(carry, toks):
            gsum, lsum = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, toks)
            gsum = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(acc_dtype), gsum, grads)
            return (gsum, lsum + loss), None

        (grads, loss_sum), _ = jax.lax.scan(micro, (g0, jnp.zeros((), jnp.float32)),
                                            tokens)
        grads = jax.tree_util.tree_map(lambda g: g / n_micro, grads)
        loss = loss_sum / n_micro

        if use_fused:
            # one pass per leaf: update → project (f32) → cast param/master
            if telemetry_marks:
                jax_bridge.mark("train_epilogue_start")
            new_params, new_opt, metrics = fused_step.fused_update(
                grads, state["opt"], params, tcfg)
            if telemetry_marks:
                jax_bridge.mark("train_epilogue_end")
        else:
            new_params, new_opt, metrics = adamw.update(grads, state["opt"],
                                                        params, tcfg)
            # the paper's constraint: project back onto the norm ball
            if telemetry_marks:
                jax_bridge.mark("train_projection_start")
            new_params = project(new_params, new_opt["step"])
            if telemetry_marks:
                jax_bridge.mark("train_projection_end")
            # keep the master copy consistent with the projected params
            if "master" in new_opt and projecting:
                new_opt = dict(new_opt)
                new_opt["master"] = jax.tree_util.tree_map(
                    lambda p, m: p.astype(m.dtype), new_params,
                    new_opt["master"])
        metrics = dict(metrics, loss=loss)
        if telemetry_every and jax_bridge.enabled():
            def _emit(op):
                loss_v, gnorm_v, ps = op
                jax_bridge.report("train_loss", loss_v)
                jax_bridge.report("train_grad_norm", gnorm_v)
                if emit_leaves is not None:
                    emit_leaves(ps)
                return jnp.zeros((), jnp.int32)

            jax.lax.cond(
                new_opt["step"] % telemetry_every == 0, _emit,
                lambda op: jnp.zeros((), jnp.int32),
                (loss, metrics["grad_norm"], new_params))
        return {"params": new_params, "opt": new_opt}, metrics

    return train_step


def init_state(cfg: ArchConfig, tcfg: TrainConfig, api, key, *,
               shardings=None):
    """The train state ``{"params", "opt"}`` from ``key``.

    ``shardings`` (a ``NamedSharding`` tree of the same structure, e.g.
    ``launch.train.state_shardings``) makes the state born sharded: one
    jitted init whose ``out_shardings`` place every parameter and both
    AdamW moments on their devices, so no device ever holds the whole
    state. Without it the state is built eagerly on the default device."""
    from repro.models import params as PM
    tpl = api.template(cfg)

    def make(key):
        params = PM.init_params(tpl, key, jnp.dtype(tcfg.param_dtype))
        return {"params": params, "opt": adamw.init(params, tcfg)}

    if shardings is None:
        return make(key)
    return jax.jit(make, out_shardings=shardings)(key)

"""AdamW from scratch, with optionally int8 block-quantized moments.

The quantized-moment mode is the distributed-optimization trick that makes
Adam states for the 671B/1T MoEs fit a v5e pod: m and v are stored as int8
with a float32 scale per 256-element block of the trailing axis (linear
symmetric for m, linear positive for v). Dequant → f32 update → requant every
step.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.types import TrainConfig
from repro.obs import metrics as obs_metrics

_BLOCK = 256


# ------------------------------------------------------------- int8 moments
def _pad_to_block(n: int) -> int:
    return -(-n // _BLOCK) * _BLOCK


def quantize_blockwise(x: jax.Array, signed: bool = True):
    """x (...) f32 -> {'q': int8, 's': f32 scales}; trailing axis blocked."""
    shape = x.shape
    n = shape[-1]
    npad = _pad_to_block(n)
    xp = jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, npad - n)])
    xb = xp.reshape(shape[:-1] + (npad // _BLOCK, _BLOCK))
    if signed:
        s = jnp.max(jnp.abs(xb), axis=-1, keepdims=True) / 127.0
    else:
        s = jnp.max(xb, axis=-1, keepdims=True) / 127.0
    s = jnp.maximum(s, 1e-12)
    q = jnp.clip(jnp.round(xb / s), -127, 127).astype(jnp.int8)
    return {"q": q.reshape(shape[:-1] + (npad,)),
            "s": s[..., 0].astype(jnp.float32)}


def dequantize_blockwise(qs: Dict[str, jax.Array], n: int) -> jax.Array:
    q, s = qs["q"], qs["s"]
    shape = q.shape
    xb = q.reshape(shape[:-1] + (shape[-1] // _BLOCK, _BLOCK)).astype(jnp.float32)
    x = (xb * s[..., None]).reshape(shape)
    return x[..., :n]


# ------------------------------------------------------------------- schedule
def lr_schedule(step, cfg: TrainConfig):
    warm = jnp.minimum(step / jnp.maximum(cfg.warmup, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup) /
                    jnp.maximum(cfg.total_steps - cfg.warmup, 1), 0.0, 1.0)
    cos = 0.5 * (1.0 + jnp.cos(jnp.pi * prog))
    return cfg.lr * warm * (0.1 + 0.9 * cos)


# ---------------------------------------------------------------------- state
def init(params, cfg: TrainConfig):
    """Optimizer state tree mirroring params."""
    def mom(p):
        if cfg.moment_dtype == "int8":
            z = jnp.zeros(p.shape, jnp.float32)
            return quantize_blockwise(z)
        return jnp.zeros(p.shape, jnp.dtype(cfg.moment_dtype))

    state = {
        "step": jnp.zeros((), jnp.int32),
        "m": jax.tree_util.tree_map(mom, params),
        "v": jax.tree_util.tree_map(lambda p: mom(p), params),
    }
    if cfg.master_dtype and cfg.master_dtype != cfg.param_dtype:
        state["master"] = jax.tree_util.tree_map(
            lambda p: p.astype(jnp.dtype(cfg.master_dtype)), params)
    return state


def _moment_spec(sp, quantized: bool):
    """A moment's spec: its param's, or for int8 blocks the param's for the
    codes and the scales' trailing axis (n_blocks, rarely divisible)
    replicated."""
    from jax.sharding import PartitionSpec as P
    if not quantized:
        return sp
    s_spec = P(*(tuple(sp)[:-1] + (None,))) if len(sp) else sp
    return {"q": sp, "s": s_spec}


def state_specs(param_specs_tree, params_template, cfg: TrainConfig):
    """Specs tree matching init()'s structure."""
    from jax.sharding import PartitionSpec as P
    q = cfg.moment_dtype == "int8"
    mom = jax.tree_util.tree_map(
        lambda sp: _moment_spec(sp, q), param_specs_tree,
        is_leaf=lambda x: isinstance(x, P))
    out = {"step": P(), "m": mom, "v": mom}
    if cfg.master_dtype and cfg.master_dtype != cfg.param_dtype:
        out["master"] = param_specs_tree
    return out


def specs_of(state, param_specs_tree):
    """Specs of an existing optimizer ``state`` (as :func:`init` built it),
    read off its own structure: each moment and the master copy follow
    their param's spec, the step counter is replicated."""
    from jax.sharding import PartitionSpec as P
    is_spec = lambda x: isinstance(x, P)  # noqa: E731
    out = {"step": P()}
    for k in ("m", "v"):
        out[k] = jax.tree_util.tree_map(
            lambda sp, m: _moment_spec(sp, isinstance(m, dict)),
            param_specs_tree, state[k], is_leaf=is_spec)
    if "master" in state:
        out["master"] = param_specs_tree
    return out


# --------------------------------------------------------------------- update
def global_norm(tree):
    leaves = [jnp.sum(jnp.square(x.astype(jnp.float32)))
              for x in jax.tree_util.tree_leaves(tree)]
    return jnp.sqrt(jnp.sum(jnp.stack(leaves)))


def grad_clip_factor(grads, cfg: TrainConfig):
    """(gnorm, clip): the global-norm clip multiplier shared by both steps."""
    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-12)) \
        if cfg.grad_clip else 1.0
    return gnorm, clip


def make_leaf_update(cfg: TrainConfig, step, clip=1.0):
    """Build the per-leaf AdamW update ``one_leaf(g, m, v, p) -> (pnew, m', v')``
    shared by :func:`update` and the fused projected step
    (``optim/fused_step.py``). ``pnew`` comes back in f32 — casting to the
    param/master dtype is the CALLER's epilogue, which is exactly what lets
    the fused step slot the projection in *before* the cast.

    Each leaf is counted once per trace in the obs registry's
    ``adamw_leaves{path="fused"|"per_layer"}``: ``per_layer`` is the
    layer-by-layer map that only int8 moments take."""
    lr = lr_schedule(step, cfg)
    b1, b2, eps = cfg.beta1, cfg.beta2, cfg.eps
    bc1 = 1.0 - b1 ** step.astype(jnp.float32)
    bc2 = 1.0 - b2 ** step.astype(jnp.float32)
    quant = cfg.moment_dtype == "int8"
    counted = obs_metrics.get_registry().counter(
        "adamw_leaves", "AdamW-updated leaves by the path they update on",
        labels=("path",))

    def one(g, m, v, p):
        gf = g.astype(jnp.float32) * clip
        pf = p.astype(jnp.float32)
        # v is stored int8 in the SQRT domain: linear int8 underflows small
        # second moments inside a block and m/sqrt(v) then explodes.
        mf = dequantize_blockwise(m, p.shape[-1]) if quant else m.astype(jnp.float32)
        vf = dequantize_blockwise(v, p.shape[-1]) ** 2 if quant else v.astype(jnp.float32)
        mf = b1 * mf + (1 - b1) * gf
        vf = b2 * vf + (1 - b2) * gf * gf
        upd = (mf / bc1) / (jnp.sqrt(vf / bc2) + eps)
        # decay true matrices only (stacked norm scales (L, d) are exempt)
        if p.ndim >= 2 and min(p.shape[-2:]) >= 64 and cfg.weight_decay:
            upd = upd + cfg.weight_decay * pf
        pnew = pf - lr * upd
        mq = quantize_blockwise(mf) if quant else mf.astype(m.dtype)
        vq = quantize_blockwise(jnp.sqrt(vf), signed=False) if quant \
            else vf.astype(v.dtype)
        return pnew, mq, vq

    def one_leaf(g, m, v, p):
        # int8 moments: layer-stacked tensors update one layer slice at a
        # time (lax.map), which bounds the f32 dequant/update working set to
        # a single layer — without it the 671B/1T updates need ~70 GB of f32
        # temporaries. Float moments have no such working set: the whole
        # leaf is one elementwise pass that XLA aliases onto the donated
        # state, where a map would add a loop and full-leaf copies.
        if quant and p.ndim >= 3 and p.shape[0] > 1:
            counted.labels(path="per_layer").inc()
            return jax.lax.map(lambda a: one(*a), (g, m, v, p))
        counted.labels(path="fused").inc()
        return one(g, m, v, p)

    return one_leaf


def update(grads, state, params, cfg: TrainConfig):
    """One AdamW step. Returns (new_params, new_state, metrics)."""
    step = state["step"] + 1
    gnorm, clip = grad_clip_factor(grads, cfg)
    one_leaf = make_leaf_update(cfg, step, clip)

    quant = cfg.moment_dtype == "int8"
    master = state.get("master")
    src = master if master is not None else params

    flat_g, treedef = jax.tree_util.tree_flatten(grads)
    flat_m = treedef.flatten_up_to(state["m"]) if quant else jax.tree_util.tree_leaves(state["m"])
    flat_v = treedef.flatten_up_to(state["v"]) if quant else jax.tree_util.tree_leaves(state["v"])
    flat_p = jax.tree_util.tree_leaves(src)
    outs = [one_leaf(g, m, v, p) for g, m, v, p in zip(flat_g, flat_m, flat_v, flat_p)]
    new_src = treedef.unflatten([o[0] for o in outs])
    new_m = treedef.unflatten([o[1] for o in outs])
    new_v = treedef.unflatten([o[2] for o in outs])

    new_state = {"step": step, "m": new_m, "v": new_v}
    if master is not None:
        new_state["master"] = jax.tree_util.tree_map(
            lambda x: x.astype(jnp.dtype(cfg.master_dtype)), new_src)
        new_params = jax.tree_util.tree_map(
            lambda x, p: x.astype(p.dtype), new_src, params)
    else:
        new_params = jax.tree_util.tree_map(
            lambda x, p: x.astype(p.dtype), new_src, params)
    metrics = {"grad_norm": gnorm, "lr": lr_schedule(step, cfg)}
    return new_params, new_state, metrics

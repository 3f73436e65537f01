"""Everything an AOT lowering needs per (arch × shape × mesh): abstract state,
input ShapeDtypeStructs, shardings, and the step function to lower.

``input_specs(cfg, shape, mesh, tuning)`` follows the assignment contract:
weak-type-correct ShapeDtypeStruct stand-ins, shardable, no allocation.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import models
from repro.configs.types import ArchConfig, ProjectionSpec, ShapeConfig, TrainConfig
from repro.models import params as PM
from repro.optim import adamw
from repro.parallel import sharding as SH
from repro.training import step as TS


# --------------------------------------------------- per-arch training tuning
@dataclasses.dataclass(frozen=True)
class Tuning:
    param_dtype: str = "bfloat16"
    master_dtype: str = "float32"
    moment_dtype: str = "float32"
    grad_allreduce_dtype: str = ""
    microbatch: int = 32          # global microbatch size for train_4k
    fsdp: bool = True
    attn_impl: str = "chunked"
    projection_pattern: str = r"(w_up|w_gate)"
    # ---- per-arch tuning knobs ----
    ep_2d: bool = False           # experts sharded over (data, model) — no FSDP
                                  # re-gather of expert weights per microbatch
    moe_dispatch: str = ""        # "" -> cfg default; "scatter" kills the
                                  # O(T²) one-hot dispatch einsum
    attn_chunk: int = 0           # 0 -> default 1024
    attn_probs_bf16: bool = False # store softmax probs bf16 (f32 accum)
    xlstm_chunk: int = 0          # mLSTM chunk length (state traffic ∝ 1/c)
    xlstm_shard_r: bool = False   # TP-shard sLSTM recurrent weights


TUNINGS: Dict[str, Tuning] = {
    # the trillion-scale MoEs: no fp32 master, int8 moments, bf16 grad accum
    "deepseek-v3-671b": Tuning(master_dtype="", moment_dtype="int8",
                               grad_allreduce_dtype="bfloat16", microbatch=16),
    "kimi-k2-1t-a32b": Tuning(master_dtype="", moment_dtype="int8",
                              grad_allreduce_dtype="bfloat16", microbatch=16),
    "qwen3-32b": Tuning(microbatch=16),
    "chameleon-34b": Tuning(microbatch=16),
}


def tuning_for(cfg: ArchConfig) -> Tuning:
    return TUNINGS.get(cfg.name, Tuning())


def apply_tuning(cfg: ArchConfig, tune: Tuning) -> ArchConfig:
    """Fold tuning knobs into the arch config + layers.ATTN_TUNE."""
    from repro.models import layers as L
    import jax.numpy as jnp
    L.ATTN_TUNE["chunk"] = tune.attn_chunk or 1024
    L.ATTN_TUNE["probs_dtype"] = jnp.bfloat16 if tune.attn_probs_bf16 else None
    if tune.moe_dispatch and cfg.moe is not None:
        cfg = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch=tune.moe_dispatch))
    if cfg.xlstm is not None and (tune.xlstm_chunk or tune.xlstm_shard_r):
        cfg = dataclasses.replace(
            cfg, xlstm=dataclasses.replace(
                cfg.xlstm, chunk=tune.xlstm_chunk or cfg.xlstm.chunk,
                shard_r=tune.xlstm_shard_r or cfg.xlstm.shard_r))
    return cfg


def train_config(cfg: ArchConfig, shape: ShapeConfig, tune: Tuning) -> TrainConfig:
    return TrainConfig(
        microbatch=tune.microbatch,
        param_dtype=tune.param_dtype,
        master_dtype=tune.master_dtype,
        moment_dtype=tune.moment_dtype,
        grad_allreduce_dtype=tune.grad_allreduce_dtype,
        remat=True,
        projection=ProjectionSpec(pattern=tune.projection_pattern,
                                  radius=100.0, every=1),
    )


# ------------------------------------------------------------ abstract state
def abstract_train_state(cfg: ArchConfig, tcfg: TrainConfig, api):
    """ShapeDtypeStruct tree matching training.init_state (no allocation)."""
    tpl = api.template(cfg)
    pdt = jnp.dtype(tcfg.param_dtype)
    params = PM.abstract_params(tpl, pdt)

    def mom(p):
        if tcfg.moment_dtype == "int8":
            npad = -(-p.shape[-1] // 256) * 256
            return {"q": jax.ShapeDtypeStruct(p.shape[:-1] + (npad,), jnp.int8),
                    "s": jax.ShapeDtypeStruct(p.shape[:-1] + (npad // 256,),
                                              jnp.float32)}
        return jax.ShapeDtypeStruct(p.shape, jnp.dtype(tcfg.moment_dtype))

    opt = {
        "step": jax.ShapeDtypeStruct((), jnp.int32),
        "m": jax.tree_util.tree_map(mom, params),
        "v": jax.tree_util.tree_map(mom, params),
    }
    if tcfg.master_dtype and tcfg.master_dtype != tcfg.param_dtype:
        opt["master"] = jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(p.shape, jnp.dtype(tcfg.master_dtype)),
            params)
    return {"params": params, "opt": opt}


def state_shardings(cfg: ArchConfig, tcfg: TrainConfig, api, mesh, *,
                    fsdp: bool = True, ep_2d: bool = False):
    tpl = api.template(cfg)
    rules = SH.param_rules(mesh, fsdp=fsdp)
    if "pod" in mesh.axis_names and cfg.name.startswith(("kimi", "deepseek")):
        rules = dict(rules, embed=("pod", "data"))  # cross-pod ZeRO for the giants
    if ep_2d:
        rules = dict(rules, experts=("data", "model"))
    shp = SH.mesh_shape_dict(mesh)
    pspecs = PM.param_specs(tpl, rules, shp)
    ospecs = adamw.state_specs(pspecs, tpl, tcfg)
    specs = {"params": pspecs, "opt": ospecs}
    return SH.named(mesh, specs), specs


# ------------------------------------------------------------------ the cells
def train_cell(cfg: ArchConfig, shape: ShapeConfig, mesh, tune=None):
    """(step_fn, abstract_args, in_shardings, out_shardings, static info)."""
    tune = tune or tuning_for(cfg)
    cfg = apply_tuning(cfg, tune)
    tcfg = train_config(cfg, shape, tune)
    api = models.get(cfg)
    n_micro = shape.global_batch // tcfg.microbatch
    n_groups = SH.dp_shards(mesh)

    b_ax = SH.tokens_spec(mesh, shape, tcfg.microbatch)[1]
    act_spec = P(b_ax, None, None)
    v_ok = cfg.vocab % SH.mesh_shape_dict(mesh)["model"] == 0
    logits_spec = P(b_ax, None, "model" if v_ok else None)
    state = abstract_train_state(cfg, tcfg, api)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (n_micro, tcfg.microbatch, shape.seq_len + 1), jnp.int32)}
    state_sh, state_specs_tree = state_shardings(cfg, tcfg, api, mesh,
                                                 fsdp=tune.fsdp,
                                                 ep_2d=tune.ep_2d)
    # the projection hook gets the params' PartitionSpecs so matched sharded
    # leaves run the schedule executor in place (no gather)
    step_fn = TS.make_train_step(cfg, tcfg, api, impl=tune.attn_impl,
                                 n_groups=n_groups, act_spec=act_spec,
                                 logits_spec=logits_spec, mesh=mesh,
                                 param_specs=state_specs_tree["params"])
    batch_sh = SH.named(mesh, {"tokens": SH.tokens_spec(mesh, shape,
                                                        tcfg.microbatch)})
    metrics_sh = SH.named(mesh, {"loss": P(), "grad_norm": P(), "lr": P()})
    return dict(
        fn=step_fn,
        args=(state, batch),
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, metrics_sh),
        tcfg=tcfg,
        donate=(0,),
    )


def decode_cell(cfg: ArchConfig, shape: ShapeConfig, mesh):
    """serve_step: one new token, cache of shape.seq_len."""
    from repro.serving import engine
    api = models.get(cfg)
    b = shape.global_batch
    n_groups = max(1, min(SH.dp_shards(mesh), b))
    step_fn = engine.make_decode_step(cfg, api, n_groups=n_groups)
    cache_ab = jax.eval_shape(
        lambda: api.make_cache(cfg, b, shape.seq_len, dtype=jnp.bfloat16))
    cache_specs = SH.cache_spec_tree(cfg, mesh, cache_ab, shape)

    tune = tuning_for(cfg)
    tpl = api.template(cfg)
    params_ab = PM.abstract_params(tpl, jnp.bfloat16)
    rules = SH.param_rules(mesh, fsdp=tune.fsdp)
    pspecs = PM.param_specs(tpl, rules, SH.mesh_shape_dict(mesh))

    tokens_ab = jax.ShapeDtypeStruct((b,), jnp.int32)
    pos_ab = jax.ShapeDtypeStruct((), jnp.int32)
    tok_spec = SH.batch_spec(mesh, b, extra_dims=0)

    b_ax = tok_spec[0] if len(tok_spec) else None
    logits_spec = P(b_ax, "model" if cfg.vocab % SH.mesh_shape_dict(mesh)["model"] == 0 else None)
    in_sh = (SH.named(mesh, pspecs), SH.named(mesh, tok_spec),
             SH.named(mesh, cache_specs), SH.named(mesh, P()))
    out_sh = (SH.named(mesh, tok_spec), SH.named(mesh, logits_spec),
              SH.named(mesh, cache_specs))
    return dict(
        fn=step_fn,
        args=(params_ab, tokens_ab, cache_ab, pos_ab),
        in_shardings=in_sh,
        out_shardings=out_sh,
        tcfg=None,
        donate=(2,),
    )


def prefill_cell(cfg: ArchConfig, shape: ShapeConfig, mesh):
    """Full-sequence forward (logits at the last position)."""
    from repro.serving import engine
    api = models.get(cfg)
    tune = tuning_for(cfg)
    tok_spec0 = SH.batch_spec(mesh, shape.global_batch, extra_dims=1)
    act_spec = P(tok_spec0[0] if len(tok_spec0) else None, None, None)
    step_fn = engine.make_prefill(cfg, api, impl=tune.attn_impl,
                                  act_spec=act_spec)

    tpl = api.template(cfg)
    params_ab = PM.abstract_params(tpl, jnp.bfloat16)
    pspecs = PM.param_specs(tpl, SH.param_rules(mesh, fsdp=tune.fsdp),
                            SH.mesh_shape_dict(mesh))
    tokens_ab = jax.ShapeDtypeStruct((shape.global_batch, shape.seq_len),
                                     jnp.int32)
    tok_spec = SH.batch_spec(mesh, shape.global_batch, extra_dims=1)
    in_sh = (SH.named(mesh, pspecs), SH.named(mesh, tok_spec))
    b_ax = tok_spec[0] if len(tok_spec) else None
    v_ok = cfg.vocab % SH.mesh_shape_dict(mesh)["model"] == 0
    out_sh = SH.named(mesh, P(b_ax, "model" if v_ok else None))
    return dict(fn=step_fn, args=(params_ab, tokens_ab), in_shardings=in_sh,
                out_shardings=out_sh, tcfg=None, donate=())


def sae_factory_cell(d_model: int, mesh, *, expansion: int = 8,
                     batch: int = 4096, microbatch: int = 512,
                     radius: float = 1.0, heads: int = 1):
    """The factory's projected dictionary-SAE train step as a lowerable cell.

    Activation rows stream in (n_micro, mb, d_model); the encoder weight
    ((d_model, expansion*d_model), 'ffn'-sharded over 'model') is projected
    onto the bi-level ball every step — through the §3 mesh executor when its
    trailing axis is sharded, so the lowered cell carries the factory's real
    collectives at production batch sizes. ``heads > 1`` is the §6
    head-structured variant: a 3-D encoder (d_model, heads, d_dict//heads)
    projected onto the tri-level ℓ1,∞,∞ ball.
    """
    from repro.models import sae
    from repro.training import sae_factory as F

    d_dict = expansion * d_model
    fcfg = F.SAEFactoryConfig(expansion=expansion, radius=radius,
                              microbatch=microbatch, sae_batch=batch,
                              heads=heads)
    tcfg = F.sae_train_config(fcfg)
    tpl = sae.dict_template(d_model, d_dict, heads=heads)
    pspecs = PM.param_specs(tpl, SH.param_rules(mesh, fsdp=True),
                            SH.mesh_shape_dict(mesh))
    params = PM.abstract_params(tpl, jnp.dtype(tcfg.param_dtype))
    ospecs = adamw.state_specs(pspecs, tpl, tcfg)
    state = {"params": params, "opt": {
        "step": jax.ShapeDtypeStruct((), jnp.int32),
        "m": jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params),
        "v": jax.tree_util.tree_map(
            lambda p: jax.ShapeDtypeStruct(p.shape, jnp.float32), params),
    }}
    n_micro = batch // microbatch
    b_ax = SH.batch_spec(mesh, microbatch, extra_dims=0)
    rows_spec = P(None, b_ax[0] if len(b_ax) else None, None)
    batch_ab = {"tokens": jax.ShapeDtypeStruct((n_micro, microbatch, d_model),
                                               jnp.float32)}
    step_fn = F.make_sae_train_step(tcfg, mesh=mesh, param_specs=pspecs)
    state_sh = SH.named(mesh, {"params": pspecs, "opt": ospecs})
    metrics_sh = SH.named(mesh, {"loss": P(), "grad_norm": P(), "lr": P()})
    return dict(
        fn=step_fn,
        args=(state, batch_ab),
        in_shardings=(state_sh, SH.named(mesh, {"tokens": rows_spec})),
        out_shardings=(state_sh, metrics_sh),
        tcfg=tcfg,
        donate=(0,),
    )


def build_cell(cfg: ArchConfig, shape: ShapeConfig, mesh, tune=None):
    if shape.kind == "train":
        return train_cell(cfg, shape, mesh, tune=tune)
    if shape.kind == "prefill":
        return prefill_cell(cfg, shape, mesh)
    return decode_cell(cfg, shape, mesh)


# cells that are skipped by assignment rule (full attention at 500k)
FULL_ATTENTION_500K_SKIP = {
    "stablelm-1.6b", "granite-3-2b", "qwen3-32b", "whisper-large-v3",
    "deepseek-v3-671b", "kimi-k2-1t-a32b", "chameleon-34b",
}


def cell_skipped(cfg: ArchConfig, shape: ShapeConfig):
    if shape.name == "long_500k" and cfg.name in FULL_ATTENTION_500K_SKIP:
        return ("skip: pure full-attention arch at 524k decode "
                "(sub-quadratic required; see DESIGN.md §5)")
    return None

"""Ahead-of-time compiles of the main path's Pallas kernels for a TPU v5e.

Interpret mode checks none of what the chip's compiler refuses: block shapes
off the (8, 128) tiling, scalars narrower than 32 bits in SMEM, vector ops
the chip has no bf16 form of. These tests lower the generated kernels at the
widths the system runs (the stablelm-1.6b ``w_up`` leaf, the head-structured
tri-level design, the paper's fig-1 size) and compile them against a
described ``v5e:2x2`` topology: no chip is needed, and nothing runs.

The topology is described inside a module fixture, never at import: only one
process may load the TPU runtime at a time, and every test worker imports
this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from repro.configs.types import TrainConfig
from repro.core import multilevel_project_sharded
from repro.core.schedule import compile_schedule
from repro.kernels import codegen
from repro.kernels.codegen.tiling import candidate_tile_plans
from repro.kernels.l1ball import project_l1_pallas_batched
from repro.optim import adamw

BI = (("inf", 1), ("1", 1))
TRI = (("inf", 1), ("inf", 1), ("1", 1))
W_UP = (2048, 5632)        # stablelm-1.6b MLP up-projection (d_model, d_ff)
HEADS = (32, 64, 2048)     # 32 heads × head_dim 64 × d_model 2048
FIG1 = (1000, 10000)       # the paper's fig-1 problem size


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache out of these compiles
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype), sharding=sharding)


def _compiled_text(fn, *args) -> str:
    return fn.lower(*args).compile().as_text()


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape,levels", [(W_UP, BI), (HEADS, TRI),
                                          (FIG1, BI)],
                         ids=["w_up_l1inf", "heads_l1infinf", "fig1_l1inf"])
def test_generated_forward_compiles(one_chip, shape, levels, dtype):
    fn = codegen.build(shape, levels, dtype, jit=True)
    text = _compiled_text(fn, _spec(shape, dtype, one_chip),
                          _spec((), dtype, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_every_candidate_tile_plan_compiles(one_chip, dtype):
    sched = compile_schedule(W_UP, BI)
    plans = candidate_tile_plans(sched, dtype)
    assert len(plans) > 1
    for tp in plans:
        fn = jax.jit(codegen.lowering.generate(sched, dtype, tile_plan=tp))
        text = _compiled_text(fn, _spec(W_UP, dtype, one_chip),
                              _spec((), dtype, one_chip))
        assert "tpu_custom_call" in text, tp


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape,levels", [(W_UP, BI), (HEADS, TRI)],
                         ids=["w_up_l1inf", "heads_l1infinf"])
def test_codegen_batch_compiles_at_bucket_8(one_chip, shape, levels, dtype):
    fn = codegen.build_batched(shape, levels, dtype, jit=True)
    text = _compiled_text(fn, _spec((8,) + shape, dtype, one_chip),
                          _spec((8,), dtype, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("method", ["bisect", "filter"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_batched_l1_kernel_compiles(one_chip, method, dtype):
    fn = jax.jit(lambda v, r: project_l1_pallas_batched(v, r, method=method))
    text = _compiled_text(fn, _spec((8, W_UP[1]), dtype, one_chip),
                          _spec((8,), dtype, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape,levels", [(W_UP, BI), (HEADS, TRI)],
                         ids=["w_up_l1inf", "heads_l1infinf"])
def test_generated_backward_compiles(one_chip, shape, levels, dtype):
    fwd = codegen.build(shape, levels, dtype)

    def loss(y, r):
        return jnp.sum(fwd(y, r).astype(jnp.float32) ** 2)

    text = _compiled_text(jax.jit(jax.grad(loss)),
                          _spec(shape, dtype, one_chip),
                          _spec((), dtype, one_chip))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("shape,levels,spec", [
    (W_UP, BI, P(None, "model")),
    (W_UP, BI, P("model", None)),
    (HEADS, TRI, P(None, None, "model")),
    # the final l1 level's groups span the mesh: distributed bisection, then
    # the epilogue resumes one level down (the partial-apply kernel)
    (HEADS, (("inf", 1), ("1", 1), ("1", 1)), P(None, "model", None)),
], ids=["w_up_cols", "w_up_rows", "heads_last", "heads_partial_apply"])
def test_sharded_codegen_body_compiles_on_4_chips(topo, shape, levels, spec):
    mesh = Mesh(np.asarray(topo.devices), ("model",))

    def fn(y, r):
        return multilevel_project_sharded(y, list(levels), r, mesh=mesh,
                                          spec=spec, method="bisect",
                                          backend="codegen")

    text = _compiled_text(jax.jit(fn),
                          _spec(shape, "float32", NamedSharding(mesh, spec)),
                          _spec((), "float32", NamedSharding(mesh, P())))
    assert "tpu_custom_call" in text


def test_stacked_leaf_codegen_body_compiles_on_2x2(topo):
    # the train step's mesh-native projection of stablelm-1.6b's stacked
    # w_up on a 2x2 host: 24 layers as the batch axis, d_model over "data"
    # (the final reduce's combine), d_ff over "model" (the gathered solve);
    # each chip's shard is (24, 1024, 2816)
    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "model"))
    spec = P(None, "data", "model")

    def fn(y, r):
        return multilevel_project_sharded(y, list(BI), r, mesh=mesh,
                                          spec=spec, method="bisect",
                                          batch_dims=1, backend="codegen")

    text = _compiled_text(jax.jit(fn),
                          _spec((24,) + W_UP, "float32",
                                NamedSharding(mesh, spec)),
                          _spec((), "float32", NamedSharding(mesh, P())))
    assert text.count("tpu_custom_call") >= 3     # reduce, solve, apply


def test_adamw_updates_stacked_f32_leaves_in_place(one_chip):
    # stablelm-1.6b's stacked wq and w_up at 4 layers, float32 moments, the
    # state and params donated as the train step donates them: the update is
    # one elementwise pass per leaf aliased onto its inputs — no per-layer
    # loop, no leaf-sized copy, no leaf-sized temporary
    shapes = {"wq": (4, 2048, 32, 64), "w_up": (4,) + W_UP}
    tcfg = TrainConfig(master_dtype="")
    params = {k: _spec(s, "float32", one_chip) for k, s in shapes.items()}
    state = {"step": _spec((), "int32", one_chip),
             "m": dict(params), "v": dict(params)}
    fn = jax.jit(lambda g, o, p: adamw.update(g, o, p, tcfg),
                 donate_argnums=(1, 2))
    compiled = fn.lower(params, state, params).compile()
    text = compiled.as_text()
    assert " while(" not in text
    leaf = "|".join(",".join(map(str, s)) for s in shapes.values())
    assert not re.findall(rf"f32\[(?:{leaf})\]\S* copy\(", text)
    slice_bytes = min(np.prod(s[1:]) for s in shapes.values()) * 4
    assert compiled.memory_analysis().temp_size_in_bytes < slice_bytes

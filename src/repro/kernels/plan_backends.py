"""Generated fused kernels as a projection-planner backend (DESIGN.md §2/§4).

Importing this module registers the ``codegen`` and ``codegen_batch``
backends with ``repro.core.plan`` (the planner imports it lazily on first
``make_plan``, so ``core`` never imports ``kernels`` at module load): the
kernel code generator (``kernels/codegen``) lowers ANY unsharded norm design
the tiler accepts to a fused reduce → θ-solve → apply kernel pipeline —
eligible on TPU, or anywhere under ``interpret=True`` (correctness tests
only; interpret mode is orders of magnitude slower than the jnp path, so
``method="auto"`` will never pick it off-TPU, by measurement).
``codegen_batch`` is the serving-bucket variant: batch-native (the stacked
batch axis joins the Pallas grid, per-item radii in SMEM), competing only on
``radius_kind="batch"`` plan keys.

The hand-written fused kernels (``bilevel_l1inf.py``/``trilevel_l1infinf.py``)
are no longer registered as backends: they are the *golden references* the
codegen equality tests pin against (``tests/test_codegen.py``).
"""

from __future__ import annotations

from repro.core import plan as planmod

from . import codegen

# the outer θ-solve of generated kernels: "bisect" has a VMEM kernel and no
# data-dependent sweep count (stable latency for a served plan)
_OUTER_METHOD = "bisect"


def _codegen_available(key: planmod.PlanKey) -> bool:
    # single-device workloads only: a mesh-sharded key routes to the sharded
    # schedule executor, not to a fused single-chip kernel.  Training keys
    # (key.grad) are eligible too: the generated kernels carry a generated
    # residual-VJP backward (kernels/codegen/backward.py) — no sort-oracle
    # recompute — so for grad keys the autotuner times them under
    # value_and_grad like any other candidate.
    if key.sharding is not None or not (key.device == "tpu" or key.interpret):
        return False
    return codegen.supported(key.shape, key.levels, key.dtype)


def _build_codegen(key: planmod.PlanKey):
    # build_tuned wires the measured block-size autotuner into make_plan:
    # the small candidate grid around the heuristic TilePlan is shot out the
    # same way method="auto" shoots out backends, and the winner is cached
    # per (canonical shape, dtype, device, interpret). In interpret mode the
    # measurement is skipped (block sizes change no machine behaviour there)
    # and the heuristic default is used.
    return codegen.build_tuned(key.shape, key.levels, key.dtype,
                               method=_OUTER_METHOD, interpret=key.interpret)


planmod.register_plan_backend(planmod.PlanBackend(
    name="codegen",
    available=_codegen_available,
    build=_build_codegen,
    description="generated fused Pallas kernels: one streaming reduce pass "
                "-> VMEM theta-solve -> fused apply epilogue (kernels/codegen)",
))


def _build_codegen_batch(key: planmod.PlanKey):
    return codegen.build_batched(key.shape, key.levels, key.dtype,
                                 method=_OUTER_METHOD, interpret=key.interpret)


planmod.register_plan_backend(planmod.PlanBackend(
    name="codegen_batch",
    # same eligibility as `codegen`; batch_native=True restricts it to
    # radius_kind="batch" keys (the planner enforces the gate)
    available=_codegen_available,
    build=_build_codegen_batch,
    description="batched-grid generated kernels for serving buckets: the "
                "stacked batch axis joins the Pallas grid (per-item radii in "
                "SMEM) instead of vmap-lifting the per-item kernel — one "
                "dispatch per pipeline stage for the whole bucket",
    batch_native=True,
))


def _sharded_codegen_available(key: planmod.PlanKey) -> bool:
    # the mesh executor's gates (scalar radius, forward key, live mesh) plus
    # the codegen ones (TPU or interpret; the shard-local schedule must have
    # a splice-compatible sharding and a VMEM tiling — distributed.shardable)
    if (key.sharding is None or key.radius_kind != "scalar" or key.grad
            or not (key.device == "tpu" or key.interpret)):
        return False
    mk = (key.sharding.mesh_axes, key.sharding.devices)
    if mk not in planmod._MESHES:
        return False
    from .codegen import distributed as dist

    return dist.shardable(key.shape, key.levels, key.sharding.spec,
                          planmod._MESHES[mk], key.dtype)


def _build_sharded_codegen(key: planmod.PlanKey):
    from repro.core import sharded as shmod

    mesh = planmod._MESHES[key.sharding.mesh_axes, key.sharding.devices]
    spec = key.sharding.spec
    levels = list(key.levels)
    interpret = key.interpret

    def fn(y, radius):
        return shmod.multilevel_project_sharded(
            y, levels, radius, mesh=mesh, spec=spec, method="auto",
            backend="codegen", interpret=interpret)

    return fn


planmod.register_plan_backend(planmod.PlanBackend(
    name="sharded_codegen",
    available=_sharded_codegen_available,
    build=_build_sharded_codegen,
    description="schedule executor under shard_map with the shard-local "
                "stages lowered through the fused codegen kernels: same "
                "collective plan as 'sharded', one streaming Pallas reduce "
                "and one fused apply epilogue per shard "
                "(kernels/codegen/distributed.py)",
))

"""Distributed fusion: generated Pallas kernels inside shard_map.

The ``backend="codegen"`` schedule body (kernels/codegen/distributed.py)
must be bit-for-bit interchangeable with the reference jnp body: same
collective plan (one psum/pmax per sharded ReduceLevel, replicated outer
solve, local applies), same results, same collective byte count. Coverage
mirrors test_sharded_equality.py:

* ``TestShardedCodegen*`` — in-process on an 8-device CPU mesh (the ``mesh``
  CI job; skipped on single-device hosts).
* ``TestShardedCodegenSubprocess`` — the equality matrix consolidated into
  one subprocess that forces the 8-device mesh, so tier-1 exercises the
  fused bodies on every run.

Also here: unit tests for the measured block-size autotuner
(``candidate_tile_plans`` / ``autotune_tiles``) and the ``exact_l1inf``
planner backend (satellites of the same PR).
"""

import json
import subprocess
import sys
import textwrap
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

# meshes with GSPMD (Auto) axes, as the launchers build them: JAX 0.9's
# jax.make_mesh makes Explicit axes, which the jnp executors do not type
from repro.launch.mesh import auto_mesh

multi_device = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

BILEVEL = [("inf", 1), ("1", 1)]
TRILEVEL = [("inf", 1), ("inf", 1), ("1", 1)]

# same registry as test_sharded_equality.DESIGNS: >=3 distinct norm designs,
# trailing AND non-trailing sharded axes, even and uneven shards
DESIGNS = [
    ("l1inf_cols",     (32, 64), BILEVEL, (None, "model")),
    ("l1inf_rows",     (32, 64), BILEVEL, ("model", None)),
    ("l1infinf_last",  (4, 16, 64), TRILEVEL, (None, None, "model")),
    ("l1infinf_mid",   (4, 16, 64), TRILEVEL, (None, "model", None)),
    ("l12_rows",       (32, 48), [("2", 1), ("1", 1)], ("model", None)),
    ("l11_rows",       (32, 48), [("1", 1), ("1", 1)], ("model", None)),
    ("flat_l1",        (16, 24), [("1", 2)], ("model", None)),
    ("l1inf_uneven",   (32, 60), BILEVEL, (None, "model")),
    ("l11_uneven",     (30, 48), [("1", 1), ("1", 1)], ("model", None)),
]

# resumes the apply chain at level L-2 after the mesh-spanning final-l1
# (the _partial_apply_call path): final reduce level is l1 AND sharded
PARTIAL_APPLY = ("l1l1inf_partial", (4, 16, 64),
                 [("inf", 1), ("1", 1), ("1", 1)], (None, "model", None))


def _rand(shape, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape) * 2, jnp.float32)


def _collective_counts(fn, *args):
    """Recursively count collective primitives in fn's jaxpr."""
    names = ("psum", "pmax", "pmin", "all_gather", "all_to_all",
             "reduce_scatter")
    counts = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            pname = eqn.primitive.name
            if any(n in pname for n in names):
                counts[pname] = counts.get(pname, 0) + 1
            for v in eqn.params.values():
                if hasattr(v, "jaxpr"):
                    walk(v.jaxpr)
                elif isinstance(v, (tuple, list)):
                    for w in v:
                        if hasattr(w, "jaxpr"):
                            walk(w.jaxpr)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return counts


@multi_device
class TestShardedCodegenEquality:
    @pytest.fixture(scope="class")
    def mesh(self):
        return auto_mesh((8,), ("model",))

    @pytest.mark.parametrize("name,shape,levels,spec", DESIGNS + [PARTIAL_APPLY])
    def test_matches_jnp_body_and_unsharded(self, mesh, name, shape, levels,
                                            spec):
        # vs the jnp shard body the fused kernels are exact (same collective
        # plan, same arithmetic order — measured 0.0 across the matrix); vs
        # the unsharded sort oracle both sharded bodies carry the 64-iter
        # distributed bisect's convergence residual (≤4e-6 f32 here)
        from repro.core import multilevel_project, multilevel_project_sharded
        y = _rand(shape, seed=zlib.crc32(name.encode()))
        want = multilevel_project(y, levels, 2.5, method="sort")
        ref = multilevel_project_sharded(y, levels, 2.5, mesh=mesh,
                                         spec=P(*spec))
        got = multilevel_project_sharded(y, levels, 2.5, mesh=mesh,
                                         spec=P(*spec), backend="codegen",
                                         interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-5)
        np.testing.assert_allclose(got, ref, atol=1e-6)

    @pytest.mark.parametrize("spec,shape", [
        ((None, None, "model"), (3, 16, 64)),    # sharded solve axis
        ((None, "model", None), (3, 16, 60)),    # sharded final reduce, uneven
        (("model", None, None), (8, 16, 40)),    # sharded batch axis
    ])
    def test_batch_dims(self, mesh, spec, shape):
        from repro.core import multilevel_project, multilevel_project_sharded
        yb = _rand(shape, seed=3)
        want = jax.vmap(lambda w: multilevel_project(w, BILEVEL, 1.5))(yb)
        got = multilevel_project_sharded(yb, BILEVEL, 1.5, mesh=mesh,
                                         spec=P(*spec), batch_dims=1,
                                         backend="codegen", interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_two_batch_dims(self, mesh):
        from repro.core import multilevel_project, multilevel_project_sharded
        yb = _rand((2, 3, 16, 64), seed=9)
        want = jax.vmap(jax.vmap(
            lambda w: multilevel_project(w, BILEVEL, 1.5)))(yb)
        got = multilevel_project_sharded(yb, BILEVEL, 1.5, mesh=mesh,
                                         spec=P(None, None, None, "model"),
                                         batch_dims=2, backend="codegen",
                                         interpret=True)
        np.testing.assert_allclose(got, want, atol=1e-6)

    def test_collective_plan_identical(self, mesh):
        # the fused body must splice in EXACTLY the jnp body's collective
        # sequence — counted from the traced jaxprs, plus the static
        # byte-count model (which is a function of schedule+spec only)
        from repro.core import multilevel_project_sharded
        from repro.core.sharded import sharded_collective_bytes
        for name, shape, levels, spec in (DESIGNS[0], DESIGNS[3], DESIGNS[5]):
            y = _rand(shape, seed=11)
            jnp_counts = _collective_counts(
                lambda w: multilevel_project_sharded(
                    w, levels, 2.5, mesh=mesh, spec=P(*spec)), y)
            cg_counts = _collective_counts(
                lambda w: multilevel_project_sharded(
                    w, levels, 2.5, mesh=mesh, spec=P(*spec),
                    backend="codegen", interpret=True), y)
            assert jnp_counts == cg_counts, (name, jnp_counts, cg_counts)
            # the static byte model takes no backend argument at all: it is
            # a function of (schedule, spec) only, so it is identical for
            # both bodies by construction — pin that it stays well-defined
            bytes_model = sharded_collective_bytes(shape, levels, P(*spec),
                                                   mesh)
            assert bytes_model["schedule_bytes"] >= 0

    def test_ineligible_design_gates(self, mesh):
        # an intermediate (level < L-2) reduce axis sharded: the in-tile fold
        # cannot be split by a collective -> shardable False, explicit
        # backend="codegen" refuses rather than silently falling back
        from repro.core import multilevel_project_sharded
        from repro.kernels.codegen import distributed as dist
        shape, levels, spec = (4, 16, 64), TRILEVEL, ("model", None, None)
        assert not dist.shardable(shape, levels, spec, mesh, jnp.float32)
        with pytest.raises(ValueError, match="codegen"):
            multilevel_project_sharded(_rand(shape, 1), levels, 1.0,
                                       mesh=mesh, spec=P(*spec),
                                       backend="codegen", interpret=True)
        # ...while the eligible orientation passes the gate
        assert dist.shardable(shape, levels, (None, None, "model"), mesh,
                              jnp.float32)

    def test_projection_hook_codegen_backend(self, mesh):
        # the training hook's mesh-native leaf path accepts backend= and
        # produces the same weights with the fused body; "auto" off-TPU
        # keeps the jnp body, so all three agree
        from repro.configs.types import ProjectionSpec
        from repro.optim import projection_hook as ph
        params = {"blk": {"w_up": _rand((4, 16, 64), seed=21)}}
        pspecs = {"blk": {"w_up": P(None, None, "model")}}
        spec = ProjectionSpec(pattern="w_up", levels=(("inf", 1), ("1", 1)),
                              radius=1.5, method="bisect")
        base = ph.make_projection_hook(spec, mesh=mesh, param_specs=pspecs,
                                       backend="jnp")(params, 0)
        fused = ph.make_projection_hook(spec, mesh=mesh, param_specs=pspecs,
                                        backend="codegen")(params, 0)
        auto = ph.make_projection_hook(spec, mesh=mesh,
                                       param_specs=pspecs)(params, 0)
        np.testing.assert_allclose(fused["blk"]["w_up"], base["blk"]["w_up"],
                                   atol=1e-6)
        np.testing.assert_allclose(auto["blk"]["w_up"], base["blk"]["w_up"],
                                   atol=1e-6)

    def test_plan_backend_competes_under_auto(self, mesh):
        from jax.sharding import NamedSharding
        from repro.core import multilevel_project, plan
        plan.clear_cache()
        y = _rand((32, 64), seed=12)
        ys = jax.device_put(y, NamedSharding(mesh, P(None, "model")))
        p = plan.make_plan((32, 64), jnp.float32, BILEVEL,
                           sharding=ys.sharding, interpret=True)
        assert "sharded_codegen" in p.timings_us  # it was a candidate
        want = multilevel_project(y, BILEVEL, 2.0)
        np.testing.assert_allclose(p(ys, 2.0), want, atol=1e-4)
        forced = plan.make_plan((32, 64), jnp.float32, BILEVEL,
                                sharding=ys.sharding, interpret=True,
                                method="sharded_codegen")
        np.testing.assert_allclose(forced(ys, 2.0), want, atol=1e-6)


class TestShardedCodegenSubprocess:
    """Tier-1 coverage on single-device hosts: one subprocess forces the
    8-device mesh and replays the fused-body equality matrix."""

    def test_equality_matrix(self):
        designs = [(n, s, lv, sp) for n, s, lv, sp in DESIGNS + [PARTIAL_APPLY]]
        prog = f"""
import os, zlib
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import multilevel_project, multilevel_project_sharded, plan

from repro.launch.mesh import auto_mesh
mesh = auto_mesh((8,), ("model",))
designs = {designs!r}
out = {{}}
jnp_body = {{}}
for name, shape, levels, spec in designs:
    rng = np.random.default_rng(zlib.crc32(name.encode()))
    y = jnp.asarray(rng.normal(size=shape) * 2, jnp.float32)
    want = multilevel_project(y, levels, 2.5, method="sort")
    ref = multilevel_project_sharded(y, levels, 2.5, mesh=mesh, spec=P(*spec))
    got = multilevel_project_sharded(y, levels, 2.5, mesh=mesh, spec=P(*spec),
                                     backend="codegen", interpret=True)
    out[name] = float(jnp.abs(got - want).max())
    jnp_body[name] = float(jnp.abs(got - ref).max())

# batch_dims through the codegen body: uneven shards + sharded batch axis
rng = np.random.default_rng(3)
levels = {BILEVEL!r}
for tag, shape, spec, bd in (
        ("batch_solve_ax", (3, 16, 64), (None, None, "model"), 1),
        ("batch_fin_uneven", (3, 16, 60), (None, "model", None), 1),
        ("batch_sharded_batch", (8, 16, 40), ("model", None, None), 1)):
    yb = jnp.asarray(rng.normal(size=shape) * 2, jnp.float32)
    want = jax.vmap(lambda w: multilevel_project(w, levels, 1.5))(yb)
    got = multilevel_project_sharded(yb, levels, 1.5, mesh=mesh, spec=P(*spec),
                                     batch_dims=bd, backend="codegen",
                                     interpret=True)
    out[tag] = float(jnp.abs(got - want).max())

# gating: intermediate reduce axis sharded must refuse, not fall back
from repro.kernels.codegen import distributed as dist
out["gate_shardable"] = not dist.shardable(
    (4, 16, 64), {TRILEVEL!r}, ("model", None, None), mesh, jnp.float32)
try:
    multilevel_project_sharded(jnp.zeros((4, 16, 64)), {TRILEVEL!r}, 1.0,
                               mesh=mesh, spec=P("model", None, None),
                               backend="codegen", interpret=True)
    out["gate_raises"] = False
except ValueError:
    out["gate_raises"] = True

# planner: sharded_codegen competes under auto on the sharded interpret key
plan.clear_cache()
y = jnp.asarray(np.random.default_rng(12).normal(size=(32, 64)) * 2,
                jnp.float32)
ys = jax.device_put(y, NamedSharding(mesh, P(None, "model")))
p = plan.make_plan((32, 64), jnp.float32, levels, sharding=ys.sharding,
                   interpret=True)
out["plan_candidate"] = "sharded_codegen" in p.timings_us
forced = plan.make_plan((32, 64), jnp.float32, levels, sharding=ys.sharding,
                        interpret=True, method="sharded_codegen")
out["plan_forced_diff"] = float(jnp.abs(
    forced(ys, 2.0) - multilevel_project(y, levels, 2.0)).max())
print("RESULT" + json.dumps({{"solver": out, "jnp_body": jnp_body}}))
"""
        res = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(prog)],
            capture_output=True, text=True, timeout=600,
            env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu"})
        assert res.returncode == 0, res.stderr[-3000:]
        payload = json.loads(res.stdout.split("RESULT", 1)[1])
        out = payload["solver"]
        assert out.pop("gate_shardable") is True
        assert out.pop("gate_raises") is True
        assert out.pop("plan_candidate") is True
        # fused body vs the jnp shard body: exact (same collective plan)
        for name, diff in payload["jnp_body"].items():
            assert diff < 1e-6, (name, diff)
        # vs the unsharded sort oracle: 64-iter bisect convergence residual
        for name, diff in out.items():
            assert diff < 1e-5, (name, diff)


class TestBlockAutotuner:
    """The measured block-size autotuner (kernels/codegen): candidate grid,
    caching, and the tuned-build entry point."""

    def test_candidate_grid_contains_default_first(self):
        from repro.core.schedule import compile_schedule
        from repro.kernels.codegen.tiling import (candidate_tile_plans,
                                                  plan_tiles)
        sched = compile_schedule((64, 256), BILEVEL)
        cands = candidate_tile_plans(sched, jnp.float32)
        assert len(cands) >= 1
        assert cands[0] == plan_tiles(sched, jnp.float32)
        # all candidates plan the same canonical shape, deduped
        assert len(set(cands)) == len(cands)
        for c in cands:
            assert c.canon_shape == cands[0].canon_shape

    def test_l1_resident_pins_block_n(self):
        # the l1 fold needs the whole group resident: only block_m may vary
        from repro.core.schedule import compile_schedule
        from repro.kernels.codegen.tiling import candidate_tile_plans
        sched = compile_schedule((64, 256), [("1", 1), ("1", 1)])
        cands = candidate_tile_plans(sched, jnp.float32)
        assert len({c.block_n for c in cands}) == 1

    def test_autotune_caches_and_builds(self):
        from repro.core import multilevel_project
        from repro.kernels import codegen
        codegen.clear_tile_cache()
        tp = codegen.autotune_tiles((16, 64), BILEVEL, jnp.float32,
                                    interpret=True)
        tp2 = codegen.autotune_tiles((16, 64), BILEVEL, jnp.float32,
                                     interpret=True)
        assert tp is tp2  # cached
        fn = codegen.build_tuned((16, 64), BILEVEL, jnp.float32,
                                 interpret=True)
        y = _rand((16, 64), seed=21)
        np.testing.assert_allclose(fn(y, 2.0),
                                   multilevel_project(y, BILEVEL, 2.0),
                                   atol=1e-5)

    def test_measured_autotune_picks_a_candidate(self):
        # force measurement even in interpret mode: the winner must come from
        # the candidate grid and produce correct results
        from repro.core import multilevel_project
        from repro.core.schedule import compile_schedule
        from repro.kernels import codegen
        from repro.kernels.codegen.tiling import candidate_tile_plans
        codegen.clear_tile_cache()
        tp = codegen.autotune_tiles((16, 48), BILEVEL, jnp.float32,
                                    interpret=True, measure=True)
        sched = compile_schedule((16, 48), BILEVEL)
        assert tp in candidate_tile_plans(sched, jnp.float32)
        fn = codegen.build((16, 48), BILEVEL, jnp.float32, interpret=True,
                           tile_plan=tp)
        y = _rand((16, 48), seed=22)
        np.testing.assert_allclose(fn(y, 1.5),
                                   multilevel_project(y, BILEVEL, 1.5),
                                   atol=1e-5)

    def test_explicit_tile_plan_equality(self):
        # every candidate block size computes the same projection
        from repro.core import multilevel_project
        from repro.core.schedule import compile_schedule
        from repro.kernels import codegen
        from repro.kernels.codegen.tiling import candidate_tile_plans
        sched = compile_schedule((32, 96), BILEVEL)
        y = _rand((32, 96), seed=23)
        want = multilevel_project(y, BILEVEL, 2.0)
        for tp in candidate_tile_plans(sched, jnp.float32):
            fn = codegen.build((32, 96), BILEVEL, jnp.float32,
                               interpret=True, tile_plan=tp)
            np.testing.assert_allclose(fn(y, 2.0), want, atol=1e-5,
                                       err_msg=str(tp))


class TestExactL1InfBackend:
    """core/exact_l1inf registered as a planner backend: the EXACT l1,inf
    projection (Chu et al.) competing under method="auto" on bi-level keys."""

    def test_registered_and_available(self):
        from repro.core import plan
        plan.clear_cache()
        key = plan.PlanKey(shape=(6, 10), dtype="float32",
                           levels=(("inf", 1), ("1", 1)),
                           radius_kind="scalar", device="cpu")
        assert "exact_l1inf" in plan._candidates(key)
        # tri-level and non-2D keys are out of scope for the exact solver
        key3 = plan.PlanKey(shape=(2, 6, 10), dtype="float32",
                            levels=(("inf", 1), ("inf", 1), ("1", 1)),
                            radius_kind="scalar", device="cpu")
        assert "exact_l1inf" not in plan._candidates(key3)

    def test_explicit_plan_close_to_bilevel(self):
        # the exact projection is a DIFFERENT operator from the bi-level
        # relaxation, but both land on the same l1,inf ball: compare at the
        # loose tolerance of the operator gap, and check exact feasibility
        from repro.core import multilevel_project, plan
        from repro.core.exact_l1inf import l1inf_norm
        plan.clear_cache()
        y = _rand((6, 10), seed=31)
        p = plan.make_plan((6, 10), jnp.float32,
                           [("inf", 1), ("1", 1)], method="exact_l1inf")
        got = p(y, 2.0)
        assert float(l1inf_norm(got)) <= 2.0 * (1 + 1e-5)
        ref = multilevel_project(y, [("inf", 1), ("1", 1)], 2.0)
        np.testing.assert_allclose(got, ref, atol=0.5)

    def test_auto_still_picks_a_generic_method(self):
        # regression guard: the exact solver is 3-30x slower than the generic
        # solvers on CPU — auto must keep choosing a ball method (the
        # assertion test_plan.py::test_auto_matches_fixed relies on)
        from repro.core import plan
        plan.clear_cache()
        p = plan.make_plan((64, 512), jnp.float32, [("inf", 1), ("1", 1)])
        assert p.method != "exact_l1inf"

"""Sharded-vs-single-device equality for the schedule executor.

Two layers of coverage:

* ``TestShardedEqualsSingleDevice`` — in-process property tests on an
  8-device CPU mesh. They run when the interpreter was started with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (the ``mesh`` CI
  job) and skip on a single-device host, where the flag can no longer be
  injected.
* ``TestShardedEqualitySubprocess`` — the same checks consolidated into one
  subprocess that forces the 8-device mesh itself, so the default (tier-1)
  suite exercises the executor on every run.
"""

import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

# meshes with GSPMD (Auto) axes, as the launchers build them: JAX 0.9's
# jax.make_mesh makes Explicit axes, which the jnp executors do not type
from repro.launch.mesh import auto_mesh

# legitimately environment-gated: XLA device count is fixed at interpreter
# start, so a 1-device tier-1 host CANNOT run these in-process (the subprocess
# class below covers the same checks there); the `mesh` CI job runs them.
multi_device = pytest.mark.skipif(
    jax.device_count() < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")

BILEVEL = [("inf", 1), ("1", 1)]
TRILEVEL = [("inf", 1), ("inf", 1), ("1", 1)]

# (name, shape, levels, spec entries) — >=3 distinct norm designs, trailing
# AND non-trailing sharded axes, even and uneven shards
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


def _rand(shape, seed):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape) * 2, jnp.float32)


@multi_device
class TestShardedEqualsSingleDevice:
    @pytest.fixture(scope="class")
    def mesh(self):
        return auto_mesh((8,), ("model",))

    @pytest.mark.parametrize("name,shape,levels,spec", DESIGNS)
    def test_matches_single_device(self, mesh, name, shape, levels, spec):
        from repro.core import multilevel_project, multilevel_project_sharded
        y = _rand(shape, seed=abs(hash(name)) % 2**31)
        want = multilevel_project(y, levels, 2.5, method="sort")
        got = multilevel_project_sharded(y, levels, 2.5, mesh=mesh,
                                         spec=P(*spec))
        np.testing.assert_allclose(got, want, atol=1e-4)

    @pytest.mark.parametrize("method", ["sort", "bisect", "filter"])
    @pytest.mark.parametrize("spec", [(None, "model"), ("model", None)])
    def test_every_theta_solver_works_sharded(self, mesh, method, spec):
        # regression: filter's while_loop / bisect's fori_loop must survive
        # shard_map (replication-checker rejections) for BOTH sharded-axis
        # positions — ProjectionSpec defaults to bisect, auto may pick filter
        from repro.core import multilevel_project, multilevel_project_sharded
        y = _rand((32, 64), seed=6)
        want = multilevel_project(y, BILEVEL, 2.0, method=method)
        got = multilevel_project_sharded(y, BILEVEL, 2.0, mesh=mesh,
                                         spec=P(*spec), method=method)
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_hook_projects_sharded_leaf_in_place(self, mesh):
        from jax.sharding import NamedSharding
        from repro.configs.types import ProjectionSpec
        from repro.optim.projection_hook import make_projection_hook
        W = _rand((3, 16, 64), seed=7)  # (layers, d, f): stacked batch axis
        pspec = P(None, None, "model")
        spec = ProjectionSpec(pattern=r"w_up", levels=(("inf", 1), ("1", 1)),
                              radius=1.0)  # method defaults to bisect
        plain = make_projection_hook(spec)
        meshy = make_projection_hook(spec, mesh=mesh,
                                     param_specs={"w_up": pspec})
        want = jax.jit(lambda p: plain(p, jnp.int32(0)))({"w_up": W})
        got = jax.jit(lambda p: meshy(p, jnp.int32(0)))(
            {"w_up": jax.device_put(W, NamedSharding(mesh, pspec))})
        np.testing.assert_allclose(jnp.asarray(got["w_up"]), want["w_up"],
                                   atol=1e-4)
        assert got["w_up"].sharding.spec == pspec  # projected in place

    def test_wrappers_and_auto(self, mesh):
        from repro.core import (make_sharded_bilevel, make_sharded_trilevel,
                                multilevel_project)
        y = _rand((32, 64), seed=1)
        got = make_sharded_bilevel(mesh, "model", method="auto")(y, 3.0)
        np.testing.assert_allclose(
            got, multilevel_project(y, BILEVEL, 3.0), atol=1e-4)
        y3 = _rand((4, 16, 64), seed=2)
        got3 = make_sharded_trilevel(mesh, "model", method="auto")(y3, 2.0)
        np.testing.assert_allclose(
            got3, multilevel_project(y3, TRILEVEL, 2.0), atol=1e-4)

    def test_uneven_shards_raise_in_specials(self, mesh):
        from repro.core import make_sharded_bilevel, make_sharded_trilevel
        with pytest.raises(ValueError, match="not divisible"):
            make_sharded_bilevel(mesh, "model")(jnp.zeros((4, 60)), 1.0)
        with pytest.raises(ValueError, match="not divisible"):
            make_sharded_trilevel(mesh, "model")(jnp.zeros((2, 4, 60)), 1.0)

    def test_batch_dims_with_sharded_batch_axis(self, mesh):
        from repro.core import multilevel_project, multilevel_project_sharded
        yb = _rand((8, 16, 40), seed=3)
        want = jax.vmap(lambda w: multilevel_project(w, BILEVEL, 1.5))(yb)
        got = multilevel_project_sharded(yb, BILEVEL, 1.5, mesh=mesh,
                                         spec=P("model", None, None),
                                         batch_dims=1)
        np.testing.assert_allclose(got, want, atol=1e-4)

    def test_planner_routes_committed_sharded_arrays(self, mesh):
        from jax.sharding import NamedSharding
        from repro.core import multilevel_project, plan
        plan.clear_cache()
        y = _rand((64, 96), seed=4)
        ys = jax.device_put(y, NamedSharding(mesh, P(None, "model")))
        p = plan.make_plan((64, 96), jnp.float32, BILEVEL, sharding=ys.sharding)
        assert p.key.sharding is not None
        assert "sharded" in p.timings_us
        want = multilevel_project(y, BILEVEL, 2.0)
        np.testing.assert_allclose(p(ys, 2.0), want, atol=1e-4)
        # method="auto" on the committed array takes the same mesh-aware plan
        np.testing.assert_allclose(
            multilevel_project(ys, BILEVEL, 2.0, method="auto"), want,
            atol=1e-4)

    def test_engine_groups_by_sharding(self, mesh):
        from jax.sharding import NamedSharding
        from repro.serving import ProjectionEngine
        from repro.core import multilevel_project
        y = _rand((32, 64), seed=5)
        ys = jax.device_put(y, NamedSharding(mesh, P(None, "model")))
        want = multilevel_project(y, BILEVEL, 1.0)
        eng = ProjectionEngine(method="sort", start=False)
        t1 = eng.submit(y, BILEVEL, radius=1.0)
        t2 = eng.submit(ys, BILEVEL, radius=1.0)  # same shape, own plan key
        eng.drain()
        assert eng.stats_snapshot()["dispatches"] == 2
        np.testing.assert_allclose(eng.result(t1), want, atol=1e-5)
        np.testing.assert_allclose(eng.result(t2), want, atol=1e-4)
        eng.stop()


class TestShardedEqualitySubprocess:
    """Tier-1 coverage on single-device hosts: one subprocess forces the
    8-device mesh and replays the equality matrix (compiles are sub-second
    at these sizes, unlike the full train-step meshes in test_parallel)."""

    def test_equality_matrix(self):
        designs = [(n, s, lv, sp) for n, s, lv, sp in DESIGNS]
        prog = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ.setdefault("JAX_PLATFORMS", "cpu")  # keep libtpu out of the child
import json
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import (make_sharded_bilevel, make_sharded_trilevel,
                        multilevel_project, multilevel_project_sharded, plan)

from repro.launch.mesh import auto_mesh
mesh = auto_mesh((8,), ("model",))
designs = {designs!r}
out = {{}}
for name, shape, levels, spec in designs:
    rng = np.random.default_rng(abs(hash(name)) % 2**31)
    y = jnp.asarray(rng.normal(size=shape) * 2, jnp.float32)
    want = multilevel_project(y, levels, 2.5, method="sort")
    got = multilevel_project_sharded(y, levels, 2.5, mesh=mesh, spec=P(*spec))
    out[name] = float(jnp.abs(got - want).max())

rng = np.random.default_rng(0)
y = jnp.asarray(rng.normal(size=(32, 64)) * 2, jnp.float32)
got = make_sharded_bilevel(mesh, "model", method="auto")(y, 3.0)
out["make_bilevel_auto"] = float(jnp.abs(
    got - multilevel_project(y, {BILEVEL!r}, 3.0)).max())
y3 = jnp.asarray(rng.normal(size=(4, 16, 64)) * 2, jnp.float32)
got3 = make_sharded_trilevel(mesh, "model", method="auto")(y3, 2.0)
out["make_trilevel_auto"] = float(jnp.abs(
    got3 - multilevel_project(y3, {TRILEVEL!r}, 2.0)).max())

try:
    make_sharded_bilevel(mesh, "model")(jnp.zeros((4, 60)), 1.0)
    out["uneven_error"] = "MISSING"
except ValueError as e:
    out["uneven_error"] = "not divisible" in str(e)

ys = jax.device_put(y, NamedSharding(mesh, P(None, "model")))
p = plan.make_plan((32, 64), jnp.float32, {BILEVEL!r}, sharding=ys.sharding)
out["plan_sharded_key"] = p.key.sharding is not None
out["plan_diff"] = float(jnp.abs(
    p(ys, 2.0) - multilevel_project(y, {BILEVEL!r}, 2.0)).max())

# every registered theta-solver must survive shard_map, both axis positions
for method in ("sort", "bisect", "filter"):
    for spec in ((None, "model"), ("model", None)):
        want = multilevel_project(y, {BILEVEL!r}, 2.0, method=method)
        got = multilevel_project_sharded(y, {BILEVEL!r}, 2.0, mesh=mesh,
                                         spec=P(*spec), method=method)
        out[f"method_{{method}}_ax{{spec.index('model')}}"] = float(
            jnp.abs(got - want).max())

# the mesh-native hook path with ProjectionSpec's default method (bisect)
from repro.configs.types import ProjectionSpec
from repro.optim.projection_hook import make_projection_hook
W = jnp.asarray(rng.normal(size=(3, 16, 64)) * 2, jnp.float32)
pspec = P(None, None, "model")
hspec = ProjectionSpec(pattern=r"w_up", levels=(("inf", 1), ("1", 1)),
                       radius=1.0)
plain = make_projection_hook(hspec)
meshy = make_projection_hook(hspec, mesh=mesh, param_specs={{"w_up": pspec}})
want = jax.jit(lambda pr: plain(pr, jnp.int32(0)))({{"w_up": W}})["w_up"]
got = jax.jit(lambda pr: meshy(pr, jnp.int32(0)))(
    {{"w_up": jax.device_put(W, NamedSharding(mesh, pspec))}})["w_up"]
out["hook_sharded_leaf"] = float(jnp.abs(jnp.asarray(got) - want).max())
print("RESULT" + json.dumps(out))
"""
        res = subprocess.run(
            [sys.executable, "-c", textwrap.dedent(prog)],
            capture_output=True, text=True, timeout=420,
            env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                 "JAX_PLATFORMS": "cpu"})
        assert res.returncode == 0, res.stderr[-3000:]
        out = json.loads(res.stdout.split("RESULT", 1)[1])
        assert out.pop("uneven_error") is True
        assert out.pop("plan_sharded_key") is True
        for name, diff in out.items():
            assert diff < 1e-4, (name, diff)

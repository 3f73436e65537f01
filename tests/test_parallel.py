"""Mesh-parallel behaviour — runs in subprocesses with
XLA_FLAGS=--xla_force_host_platform_device_count=8 so the main test process
keeps its single device (the device-count flag must NOT be set globally)."""

import json
import subprocess
import sys
import textwrap

import pytest


def _run(code: str, devices: int = 8, timeout=420):
    prog = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={devices}"
os.environ.setdefault("JAX_PLATFORMS", "cpu")
{textwrap.dedent(code)}
"""
    # JAX_PLATFORMS=cpu also in the env: with it unset, a host that has
    # libtpu installed stalls for minutes probing TPU instance metadata
    res = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                         text=True, timeout=timeout,
                         env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
                              "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


@pytest.mark.slow
class TestShardedProjection:
    """Subprocess mesh tests: minutes each on a single-core host (8 fake
    devices force full shard_map compiles). Nightly CI runs them; the default
    suite deselects via the ``slow`` marker."""

    def test_sharded_bilevel_matches_single_device(self):
        out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import bilevel_l1inf
        from repro.core.sharded import make_sharded_bilevel
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(0)
        y = jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)
        fn = make_sharded_bilevel(mesh, "model")
        got = jax.jit(fn)(y, 3.0)
        want = bilevel_l1inf(y, 3.0, method="sort")
        print("MAXDIFF", float(jnp.abs(got - want).max()))
        """)
        assert float(out.split("MAXDIFF")[1]) < 1e-4

    def test_sharded_trilevel_feasible(self):
        out = _run("""
        import jax, jax.numpy as jnp, numpy as np, functools
        from jax.sharding import PartitionSpec as P
        from repro.core.sharded import trilevel_project_sharded
        from repro.core import multilevel_norm
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((8,), ("model",))
        rng = np.random.default_rng(1)
        y = jnp.asarray(rng.normal(size=(4, 16, 64)), jnp.float32)
        body = functools.partial(trilevel_project_sharded, axis_name="model")
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P(None, None, "model"), P()),
                           out_specs=P(None, None, "model"), check_vma=False)
        got = jax.jit(fn)(y, jnp.float32(2.0))
        n = multilevel_norm(got, [("inf", 1), ("inf", 1), (1, 1)])
        print("NORM", float(n))
        """)
        assert float(out.split("NORM")[1]) <= 2.0 * (1 + 1e-3)

    def test_train_step_under_mesh_matches_single(self):
        out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import models
        from repro.configs import registry
        from repro.configs.types import TrainConfig, ProjectionSpec
        from repro.training import init_state, make_train_step
        from repro.models import params as PM
        from repro.parallel import sharding as SH
        from repro.data import DataPipeline, DataConfig

        cfg = registry.smoke_config("granite-3-2b")
        api = models.get(cfg)
        tcfg = TrainConfig(microbatch=4, total_steps=10, lr=1e-3, remat=False,
                           warmup=2)
        pipe = DataPipeline(DataConfig(vocab=cfg.vocab, seq_len=17,
                                       global_batch=8, microbatch=4))
        batch = {"tokens": jnp.asarray(pipe.batch(0))}

        # single device
        state1 = init_state(cfg, tcfg, api, jax.random.PRNGKey(0))
        step1 = jax.jit(make_train_step(cfg, tcfg, api, impl="naive"))
        s1, m1 = step1(state1, batch)

        # 2x4 mesh with full sharding rules
        from repro.launch.mesh import auto_mesh
        mesh = auto_mesh((2, 4), ("data", "model"))
        rules = SH.param_rules(mesh)
        specs = PM.param_specs(api.template(cfg), rules,
                               SH.mesh_shape_dict(mesh))
        state2 = init_state(cfg, tcfg, api, jax.random.PRNGKey(0))
        with mesh:
            state2 = {"params": jax.device_put(
                          state2["params"], SH.named(mesh, specs)),
                      "opt": state2["opt"]}
            step2 = jax.jit(make_train_step(cfg, tcfg, api, impl="naive",
                                            act_spec=P("data", None, None)))
            s2, m2 = step2(state2, batch)
        print("LOSSDIFF", abs(float(m1["loss"]) - float(m2["loss"])))
        w1 = s1["params"]["blocks"]["mlp"]["w_up"]
        w2 = s2["params"]["blocks"]["mlp"]["w_up"]
        print("WDIFF", float(jnp.abs(w1 - jnp.asarray(w2)).max()))
        """)
        assert float(out.split("LOSSDIFF")[1].split()[0]) < 5e-3
        assert float(out.split("WDIFF")[1]) < 5e-3

    def test_elastic_restore_to_smaller_mesh(self, tmp_path):
        out = _run(f"""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.runtime import CheckpointManager

        mgr = CheckpointManager("{tmp_path}", keep=2)
        from repro.launch.mesh import auto_mesh
        mesh8 = auto_mesh((8,), ("data",))
        x = jax.device_put(jnp.arange(64.0).reshape(8, 8),
                           NamedSharding(mesh8, P("data", None)))
        mgr.save(1, {{"x": x}})

        # restore onto a SHRUNK mesh (8 -> 4 data shards: elastic scale-down)
        from repro.launch.mesh import auto_mesh
        mesh4 = auto_mesh((4,), ("data",))
        sh = {{"x": NamedSharding(mesh4, P("data", None))}}
        tree, _ = mgr.restore(shardings=sh)
        ok = np.allclose(np.asarray(tree["x"]), np.arange(64.0).reshape(8, 8))
        print("ELASTIC_OK", ok, len(tree["x"].sharding.device_set))
        """)
        assert "ELASTIC_OK True 4" in out


class TestRooflineParser:
    def test_cell_skip_rules(self):
        from repro.configs import registry
        from repro.configs.types import SHAPES
        from repro.launch import specs as SP
        assert SP.cell_skipped(registry.get_arch("qwen3-32b"),
                               SHAPES["long_500k"])
        assert not SP.cell_skipped(registry.get_arch("zamba2-7b"),
                                   SHAPES["long_500k"])
        assert not SP.cell_skipped(registry.get_arch("qwen3-32b"),
                                   SHAPES["train_4k"])

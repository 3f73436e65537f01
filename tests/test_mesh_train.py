"""The launcher's train state born sharded and its projected step on a 2×2
mesh — on four forced host devices, in a subprocess (the main test process
keeps its single device).

One subprocess computes every reading below, at a tiny stablelm width with
the bi-level constraint binding on every projected layer slice; each test
checks one part of it.
"""

import json
import pathlib
import subprocess
import sys
import textwrap

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]

PROG = """
import dataclasses, functools, json, os, sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
os.environ["JAX_PLATFORMS"] = "cpu"
sys.path[:0] = [{src!r}, {bench!r}]
import jax, jax.numpy as jnp, numpy as np
import reference
from repro.configs import registry
from repro.configs.types import TrainConfig
from repro.launch import train as launch
from repro.obs import metrics as obs_metrics
from repro.training import init_state

out = {{}}
SIZES = dict(hidden_size=64, intermediate_size=128, num_attention_heads=4,
             num_key_value_heads=4, num_hidden_layers=2, vocab_size=256,
             partial_rotary_factor=0.25, rope_theta=10000, layer_norm_eps=1e-5)
cfg = dataclasses.replace(
    registry.get_arch("stablelm-1.6b"), n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=4, d_ff=128, vocab=256, head_dim=0, qk_norm=False, window=None)
RADIUS, BATCH, SEQ, STEPS = 4.0, 8, 16, 3
OPT = dict(lr=3e-4, warmup_steps=20, total_steps=100, beta1=0.9, beta2=0.95,
           eps=1e-8, weight_decay=0.1, decay_min_dim=64, grad_clip=1.0)

def leaves():
    fam = obs_metrics.get_registry().snapshot().get("projection_leaves", {{}})
    return {{v["labels"]["path"]: v["value"] for v in fam.get("values", [])}}

mesh = launch.parse_mesh("2x2")
run = launch.make_run(cfg, mesh, steps=OPT["total_steps"], seq=SEQ,
                      batch=BATCH, lr=OPT["lr"], radius=RADIUS)
sh = launch.state_shardings(mesh, run)

# --- born sharded vs the one-device init
key = jax.random.PRNGKey(7)
born = init_state(cfg, run.tcfg, run.api, key, shardings=sh)
one_dev = jax.jit(lambda k: init_state(cfg, run.tcfg, run.api, k),
                  device=jax.devices()[0])(key)
eager = init_state(cfg, run.tcfg, run.api, key)
flat_b = jax.tree_util.tree_flatten_with_path(born)[0]
flat_o = jax.tree_util.tree_leaves(one_dev)
flat_e = jax.tree_util.tree_leaves(eager)
out["init_equal"] = all(np.array_equal(np.asarray(b), np.asarray(o))
                        for (_, b), o in zip(flat_b, flat_o))
out["init_eager_diff"] = max(float(np.abs(np.asarray(b) - np.asarray(e)).max())
                             for (_, b), e in zip(flat_b, flat_e))
out["init_leaves"] = len(flat_b)
spread, whole = [], []
for path, x in flat_b:
    name = jax.tree_util.keystr(path)
    shard = x.sharding.shard_shape(x.shape)
    if x.ndim and shard != x.shape and len(x.sharding.device_set) == 4:
        spread.append(name)
    elif x.size > 1:
        whole.append(name)
out["not_spread"] = whole
per_dev = {{}}
for _, x in flat_b:
    for s in x.addressable_shards:
        per_dev[s.device.id] = per_dev.get(s.device.id, 0) + s.data.nbytes
total = sum(x.nbytes for _, x in flat_b)
out["largest_device_share"] = max(per_dev.values()) / total
placed = launch.place_state(eager, mesh, run.specs)
out["place_matches_born"] = all(
    a.sharding.is_equivalent_to(b.sharding, a.ndim) for a, b in zip(
        jax.tree_util.tree_leaves(placed), jax.tree_util.tree_leaves(born)))
del born, one_dev, eager, placed

# --- three projected steps of the launcher's 2x2 step, computing in float32
# (the test steers the launcher's TrainConfig; the program itself computes in
# bfloat16), against the plain float32 reference from the same weights
launch.TrainConfig = functools.partial(TrainConfig, compute_dtype="float32")
before = leaves()
run = launch.make_run(cfg, mesh, steps=OPT["total_steps"], seq=SEQ,
                      batch=BATCH, lr=OPT["lr"], radius=RADIUS)
assert run.tcfg.compute_dtype == "float32" and run.tcfg.warmup == 20
m = dict(SIZES)
params = reference.init_lm(m, 11, out_shardings=launch.state_shardings(
    mesh, run)["params"])
out["smallest_norm"] = reference.min_norm(params)
state = {{"params": params, "opt": launch.init_opt(params, mesh, run)}}
del params
batches = [np.asarray(run.pipe.batch(i)) for i in range(STEPS)]
losses = []
with mesh:
    state = launch.place_state(state, mesh, run.specs)
    jaxpr = str(jax.make_jaxpr(run.step_fn)(state, {{"tokens": batches[0]}}))
    out["shard_maps_in_step"] = jaxpr.count("shard_map")
    for i, b in enumerate(batches):
        state, met = run.step_fn(state, {{"tokens": jnp.asarray(b)}})
        losses.append(float(met["loss"]))
        if i == 0:
            first = {{p: float(jnp.linalg.norm(x / (1 - OPT["beta1"])))
                     for p, x in reference.flatten(state["opt"]["m"]).items()}}
init = reference.flatten(reference.init_lm(m, 11))
delta = {{p: float(jnp.linalg.norm(x - init[p]))
         for p, x in reference.flatten(state["params"]).items()}}
after = leaves()
out["hook_paths"] = {{k: v - before.get(k, 0) for k, v in after.items()
                     if v - before.get(k, 0)}}
ref = reference.train_reference(m, OPT, 11, [b[0] for b in batches], RADIUS)
out["loss_gap"] = max(abs(a - b) / abs(b) for a, b in zip(losses, ref["losses"]))
out["grad_gap"] = reference.gap(first, ref["grad_norms"])[0]
out["delta_gap"] = reference.gap(delta, ref["delta_norms"])[0]

# --- the same hook on a one-device mesh runs every leaf vmapped
before = leaves()
mesh1 = launch.parse_mesh("1x1", jax.devices()[:1])
one = launch.make_run(cfg, mesh1, steps=OPT["total_steps"], seq=SEQ,
                      batch=BATCH, lr=OPT["lr"], radius=RADIUS)
with mesh1:
    jax.eval_shape(one.step_fn, init_state(cfg, one.tcfg, one.api, key),
                   {{"tokens": batches[0]}})
after = leaves()
out["hook_paths_1x1"] = {{k: v - before.get(k, 0) for k, v in after.items()
                         if v - before.get(k, 0)}}
print("RESULT" + json.dumps(out))
"""


@pytest.fixture(scope="module")
def readings():
    code = PROG.format(src=str(ROOT / "src"), bench=str(ROOT / "bench"))
    res = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=600,
                         env={"PATH": "/usr/bin:/bin", "JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.split("RESULT", 1)[1])


def test_sharded_init_equals_the_one_device_init(readings):
    # the same key and the same init program on one device give the same
    # values, bit for bit, leaf for leaf: params and both moments
    assert readings["init_equal"] and readings["init_leaves"] >= 3 * 10
    # the eager init rounds each leaf's N(0,1) draw and its scaling apart,
    # where the jitted one fuses them: a last-bit difference (2**-25 is one
    # ulp of the largest weights here, under 0.5)
    assert readings["init_eager_diff"] <= 2 ** -25


def test_sharded_init_spreads_every_leaf_over_the_mesh(readings):
    # every weight and its moments are split over the 4 devices; the
    # program's rules replicate only the norm scales (a vector a layer)
    whole = readings["not_spread"]
    assert whole and all(n.endswith(("['ln1']", "['ln2']", "['final_norm']"))
                         for n in whole), whole
    # no device holds the whole state: each holds about a quarter
    assert readings["largest_device_share"] < 0.3


def test_place_state_places_params_and_moments_alike(readings):
    # placing an eagerly built state gives the born-sharded layout
    assert readings["place_matches_born"]


def test_radius_binds(readings):
    assert readings["smallest_norm"] > 4.0


def test_mesh_step_matches_the_float32_reference(readings):
    # the program computes in float32 here, so the gaps are rounding alone:
    # the sums run in another order over the shards, and the program's
    # θ-solve is a bisection where the reference sorts. Read on the CPU:
    # loss 1.6e-7 (relative, worst step), first-gradient norms 2.9e-6 and
    # the change over three steps 1.4e-6 (each leaf's gap over the larger of
    # its and the median leaf's reference norm); each limit leaves more than
    # ten times that. A wrong shard slice or a skipped projection moves them
    # by whole percents.
    assert readings["loss_gap"] < 2e-6, readings
    assert readings["grad_gap"] < 5e-5, readings
    assert readings["delta_gap"] < 5e-5, readings


def test_hook_counts_the_path_of_every_leaf(readings):
    # off the TPU "auto" runs the jnp body under shard_map: both w_up and
    # w_gate are sharded on the 2x2 mesh; both are vmapped on one device
    assert readings["hook_paths"] == {"shard_map_jnp": 2}
    assert readings["shard_maps_in_step"] >= 2
    assert readings["hook_paths_1x1"] == {"vmapped": 2}

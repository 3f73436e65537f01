"""Driver of the training cells on a mesh of chips: the launcher's projected
train step with its state born sharded, against the sharded float32
reference.

``train.py`` does the same on one chip, and its pieces are used here as
they are (loaded through ``harness.driver``): the program check, the feed,
the window and the gaps. What differs is where the state is made and where
the reference runs, since no one chip holds either at the cell's size:

* the weights come from ``reference.init_lm`` with ``out_shardings`` on the
  program's own specs, and the AdamW moments from the program's sharded
  init (``launch.init_opt``): no chip ever holds the whole state;
* the projection hook's decisions are read from the obs registry's
  ``projection_leaves`` counter into ``counters`` (one count a projected
  leaf, labelled with the path it runs: ``shard_map_codegen``,
  ``shard_map_jnp`` or ``vmapped``);
* once the window has closed, the state is freed and
  ``reference_mesh.train_reference`` runs the same three steps from the same
  weights and tokens, jitted over the same chips.
"""

from __future__ import annotations

import sys
import time

import harness
import load
import reference
import reference_mesh

base = harness.driver("train")

LEAVES = "projection_leaves"


def _leaf_paths() -> dict:
    """{path: leaves} of the obs registry's hook counter so far."""
    from repro.obs import metrics as obs_metrics

    fam = obs_metrics.get_registry().snapshot().get(LEAVES, {})
    return {v["labels"]["path"]: v["value"] for v in fam.get("values", [])}


def setup(config: dict, traffic: dict, seed: int, devices):
    """Build the step and its state, born sharded, and drive it through its
    first steps. Returns (run, state, mesh, readings, radius, leaf paths)."""
    import jax
    from repro.launch import train as launch

    init_opt, shardings = launch.init_opt, launch.state_shardings
    cfg = base.arch_config(config)
    mesh = launch.parse_mesh(config["program"]["mesh"], devices)
    o = config["optimizer"]
    radius = config["projection"]["radius"]
    before = _leaf_paths()
    with harness.phase("make_run"):
        run = launch.make_run(cfg, mesh, steps=o["total_steps"],
                              seq=traffic["seq"], batch=traffic["batch"],
                              lr=o["lr"], radius=radius)
    base.check_program(run, config)
    with harness.phase("init weights"):
        params = reference.init_lm(config, seed,
                                   out_shardings=shardings(mesh,
                                                           run)["params"])
        smallest = reference.min_norm(params)
    if not radius < smallest:
        raise ValueError(f"radius {radius} does not bind: the smallest "
                         f"initial norm is {smallest}")
    with harness.phase("init optimizer"):
        state = {"params": params, "opt": init_opt(params, mesh, run)}
        del params
    readings = {"losses": []}
    with mesh:
        state = launch.place_state(state, mesh, run.specs)
        for i in range(traffic["setup_steps"]):
            with harness.phase(f"set-up step {i + 1}"):
                state, met = run.step_fn(state,
                                         base.feed(traffic, seed, i, config))
                readings["losses"].append(float(met["loss"]))
            if i == 0:
                with harness.phase("first gradient norms"):
                    readings["grad_norms"] = base._first_grad_norms(
                        state["opt"], o["beta1"])
        with harness.phase("change norms"):
            readings["delta_norms"] = base._delta_norms(state["params"],
                                                        config, seed)
        jax.block_until_ready(state)
    after = _leaf_paths()
    paths = {k: v - before.get(k, 0) for k, v in after.items()
             if v - before.get(k, 0)}
    return run, state, mesh, readings, radius, paths


def reference_run(config, traffic, seed, radius, devices, **kw) -> dict:
    batches = [load.train_tokens(traffic, seed, i, config["vocab_size"])[0]
               for i in range(traffic["setup_steps"])]
    return reference_mesh.train_reference(
        config, config["optimizer"], seed, batches, radius, devices=devices,
        mesh=config["program"]["mesh"], **kw)


def run(config: dict, traffic: dict, seed: int, seconds: float, *, t0: float,
        trace_dir=None, devices=None, clock=time.perf_counter) -> dict:
    import jax

    devices = devices or jax.devices()[:config["chips"]]
    prog, state, mesh, readings, radius, paths = setup(config, traffic,
                                                       seed, devices)
    setup_s = clock() - t0
    first = traffic["setup_steps"]
    cache_before = base._cache_size(prog.step_fn)
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(trace_dir, profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench/window"):
                state, steps, elapsed = base.window(
                    prog, state, mesh, traffic, seed, config,
                    traffic["trace_seconds"], first_step=first, tracing=True)
    else:
        state, steps, elapsed = base.window(prog, state, mesh, traffic, seed,
                                            config, seconds, first_step=first,
                                            tracing=False)
    compiles = base._cache_size(prog.step_fn) - cache_before
    device = harness.device_record(devices)
    del state
    with harness.phase("reference"):
        ref = reference_run(config, traffic, seed, radius, devices)
    gaps = base._gaps(readings, ref)
    limits = config.get("limits", {})
    checks = {}
    for k in ("loss1_gap", "loss_gap", "grad_gap", "delta_gap"):
        if k in limits:
            checks[k] = {"value": gaps[k], "limit": limits[k]}
        else:                     # read, but no limit has been set for it
            print(f"reading {k}: {gaps[k]!r} (not compared)",
                  file=sys.stderr)
    if compiles:
        checks["compiles_in_window"] = {"value": compiles, "limit": 0}
    return {
        "e2e": {"setup_s": setup_s, "train_step_ms": elapsed / steps * 1e3},
        "attempted": steps, "failed": 0, "checks": checks, "device": device,
        "counters": {"steps": steps, "elapsed_s": elapsed,
                     "tokens_per_step": traffic["batch"] * traffic["seq"],
                     "chips": len(devices), "radius": radius,
                     LEAVES: paths},
        "readings": {"program": readings, "reference": ref, "gaps": gaps},
    }

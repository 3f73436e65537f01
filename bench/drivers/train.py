"""Driver of the training cells: the launcher's projected train step.

The program is driven only through ``repro.launch.train``: ``make_run``
builds the jitted step, ``place_state`` puts the state on the mesh, and the
window calls ``step_fn``. The benchmark makes the initial weights from the
seed (``reference.init_lm``, one jitted call on the device) and the token
batches (``load.train_tokens``); the optimizer moments start at zero.

Set-up compiles the step and drives the same state through its first
``setup_steps`` steps, through the window's own call and feed, and keeps
three readings of them: each step's loss, each leaf's norm of the first
gradient as the optimizer got it (its first moment over 1 − β1), and each
leaf's norm of the parameters' change over those steps. The window then
runs the same object on. Once it has closed, the state is freed and the
float32 reference runs the same steps from the same weights and tokens;
``checks`` holds each gap the configuration sets a limit for (its
``limits``); the others are printed as readings.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import sys
import time

import load
import reference


def arch_config(config: dict):
    """The program's ArchConfig with every size taken from the file."""
    from repro.configs import registry

    base = registry.get_arch(config["program"]["arch"])
    return dataclasses.replace(
        base, n_layers=config["num_hidden_layers"],
        d_model=config["hidden_size"], n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab=config["vocab_size"],
        head_dim=0, rope_pct=config["partial_rotary_factor"],
        rope_theta=config["rope_theta"], norm_eps=config["layer_norm_eps"],
        tie_embeddings=config["tie_word_embeddings"],
        act=config["hidden_act"], qk_norm=False, window=None)


def check_program(run, config: dict) -> None:
    """Refuse a program that would run something else than the file
    states: other parameter shapes, optimizer or precision."""
    import jax
    from repro.models import params as PM

    got = {PM._path_str(p): tuple(d.shape) for p, d in
           jax.tree_util.tree_flatten_with_path(
               run.api.template(arch_config(config)),
               is_leaf=PM.is_def)[0]}
    want = {p: tuple(s) for p, s in reference.lm_shapes(config).items()}
    if got != want:
        raise ValueError(f"parameter layout differs from the file: "
                         f"{sorted(set(got.items()) ^ set(want.items()))}")
    t, o, prog = run.tcfg, config["optimizer"], config["program"]
    stated = {"lr": o["lr"], "warmup": o["warmup_steps"],
              "total_steps": o["total_steps"], "beta1": o["beta1"],
              "beta2": o["beta2"], "eps": o["eps"],
              "weight_decay": o["weight_decay"], "grad_clip": o["grad_clip"],
              "param_dtype": prog["param_dtype"],
              "compute_dtype": prog["compute_dtype"],
              "moment_dtype": prog["moment_dtype"], "master_dtype": "",
              "remat": prog["remat"]}
    differs = {k: (getattr(t, k), v) for k, v in stated.items()
               if getattr(t, k) != v}
    norm = lambda lvs: tuple((str(q), int(k)) for q, k in lvs)  # noqa: E731
    levels = norm(config["projection"]["levels"])
    if t.projection is None or norm(t.projection.levels) != levels:
        differs["projection"] = (t.projection, levels)
    if differs:
        raise ValueError(f"the program departs from the file: {differs}")


def _first_grad_norms(opt_state, beta1):
    import jax

    fn = jax.jit(lambda mom: reference.leaf_norms(
        {p: x / (1 - beta1) for p, x in reference.flatten(mom).items()}))
    return {p: float(v) for p, v in fn(opt_state["m"]).items()}


def _delta_norms(params, config, seed):
    import jax
    import jax.numpy as jnp

    shapes = reference.lm_shapes(config)

    def fn(flat, key):
        return {p: jnp.sqrt(jnp.sum(jnp.square(
            x - reference.init_leaf(key, p, shapes[p]))))
            for p, x in flat.items()}

    return {p: float(v) for p, v in jax.jit(fn)(
        reference.flatten(params), reference.seed_key(seed)).items()}


def _gaps(prog: dict, ref: dict) -> dict:
    """``loss1_gap``: the first step's relative loss gap, a forward pass
    from the same weights; ``loss_gap``: the worst over every step."""
    loss = max(abs(a - b) / abs(b)
               for a, b in zip(prog["losses"], ref["losses"]))
    loss1 = abs(prog["losses"][0] - ref["losses"][0]) / abs(ref["losses"][0])
    grad, grad_leaf = reference.gap(prog["grad_norms"], ref["grad_norms"])
    delta, delta_leaf = reference.gap(prog["delta_norms"],
                                      ref["delta_norms"])
    return {"loss1_gap": loss1, "loss_gap": loss, "grad_gap": grad,
            "delta_gap": delta,
            "grad_leaf": grad_leaf, "delta_leaf": delta_leaf}


def _cache_size(fn) -> int:
    """Programs the jitted ``fn`` has compiled so far (0 where JAX does
    not say)."""
    size = getattr(fn, "_cache_size", None)
    return size() if size else 0


def setup(config: dict, traffic: dict, seed: int, devices):
    """Build the step and its state and drive it through its first steps.
    Returns (run, state, mesh, readings, radius)."""
    import jax
    from repro.launch import train as launch
    from repro.optim import adamw

    import harness

    cfg = arch_config(config)
    mesh = launch.parse_mesh(config["program"]["mesh"], devices)
    with harness.phase("init weights"):
        params = reference.init_lm(config, seed)
        radius = config["projection"]["radius"]
        smallest = reference.min_norm(params)
    if not radius < smallest:
        raise ValueError(f"radius {radius} does not bind: the smallest "
                         f"initial norm is {smallest}")
    o = config["optimizer"]
    with harness.phase("make_run"):
        run = launch.make_run(cfg, mesh, steps=o["total_steps"],
                              seq=traffic["seq"], batch=traffic["batch"],
                              lr=o["lr"], radius=radius)
    check_program(run, config)
    with harness.phase("init optimizer"):
        state = {"params": params, "opt": adamw.init(params, run.tcfg)}
        del params
    readings = {"losses": []}
    with mesh:
        state = launch.place_state(state, mesh, run.specs)
        for i in range(traffic["setup_steps"]):
            with harness.phase(f"set-up step {i + 1}"):
                state, met = run.step_fn(state,
                                         feed(traffic, seed, i, config))
                readings["losses"].append(float(met["loss"]))
            if i == 0:
                with harness.phase("first gradient norms"):
                    readings["grad_norms"] = _first_grad_norms(
                        state["opt"], o["beta1"])
        with harness.phase("change norms"):
            readings["delta_norms"] = _delta_norms(state["params"], config,
                                                   seed)
        jax.block_until_ready(state)
    return run, state, mesh, readings, radius


def feed(traffic, seed, step, config):
    import jax.numpy as jnp

    return {"tokens": jnp.asarray(
        load.train_tokens(traffic, seed, step, config["vocab_size"]))}


def window(run, state, mesh, traffic, seed, config, seconds, *,
           first_step: int, tracing: bool, clock=time.perf_counter):
    """Run steps back to back for ``seconds``; the host stays at most
    ``inflight_steps`` steps ahead of the device. Returns (state, steps,
    elapsed_s)."""
    import jax

    span = (jax.profiler.TraceAnnotation if tracing
            else lambda _name: contextlib.nullcontext())
    ahead = collections.deque()
    n = 0
    with mesh:
        start = clock()
        while True:
            with span("bench/step"):
                state, met = run.step_fn(
                    state, feed(traffic, seed, first_step + n, config))
            n += 1
            ahead.append(met["loss"])
            if len(ahead) > traffic["inflight_steps"]:
                with span("bench/wait"):
                    jax.block_until_ready(ahead.popleft())
            if clock() - start >= seconds:
                break
        jax.block_until_ready((state, ahead[-1]))
        elapsed = clock() - start
    return state, n, elapsed


def run(config: dict, traffic: dict, seed: int, seconds: float, *, t0: float,
        trace_dir=None, devices=None, clock=time.perf_counter) -> dict:
    import jax

    import harness

    devices = devices or jax.devices()[:config["chips"]]
    prog, state, mesh, readings, radius = setup(config, traffic, seed,
                                                devices)
    setup_s = clock() - t0
    first = traffic["setup_steps"]
    cache_before = _cache_size(prog.step_fn)
    if trace_dir:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        with jax.profiler.trace(trace_dir, profiler_options=opts):
            with jax.profiler.TraceAnnotation("bench/window"):
                state, steps, elapsed = window(
                    prog, state, mesh, traffic, seed, config,
                    traffic["trace_seconds"], first_step=first, tracing=True)
    else:
        state, steps, elapsed = window(prog, state, mesh, traffic, seed,
                                       config, seconds, first_step=first,
                                       tracing=False)
    compiles = _cache_size(prog.step_fn) - cache_before
    device = harness.device_record(devices)
    del state
    with harness.phase("reference"):
        batches = [load.train_tokens(traffic, seed, i, config["vocab_size"])[0]
                   for i in range(traffic["setup_steps"])]
        ref = reference.train_reference(config, config["optimizer"], seed,
                                        batches, radius)
    gaps = _gaps(readings, ref)
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
                     "chips": len(devices), "radius": radius},
        "readings": {"program": readings, "reference": ref, "gaps": gaps},
    }

"""Bring-up smoke test: the main path on a TPU, checked against f32 references.

    python chip_smoke.py            # one chip: devices, plan, serve, train
    python chip_smoke.py --chips 4  # four chips: the mesh path only

One chip (the default) drives, through the entry points a user calls:

* plan  — ``core.plan.make_plan(..., method="auto")`` on the bi-level ℓ1,∞
  ``w_up`` leaf of stablelm-1.6b (2048×5632, f32 and bf16), the
  head-structured tri-level ℓ1,∞,∞ design on (32, 64, 2048) and the paper's
  fig-1 size (1000×10000). Every candidate backend runs on the chip; each
  one's output is compared with ``multilevel_project(..., method="sort")``
  in float32, and the ``codegen`` / ``codegen_batch`` executables must
  contain a Mosaic kernel (``tpu_custom_call``);
* serve — a ``serving.ProjectionEngine`` answers 60 requests over the same
  keys in buckets of 1, 2, 4 and 8 through ``codegen_batch``;
* train — 5 projected train steps of stablelm-1.6b at its published widths,
  depth cut to 4 layers, built by ``repro.launch.train.make_run`` exactly as
  ``python -m repro.launch.train`` builds them.

``--chips 4`` runs the mesh path alone: ``multilevel_project_sharded`` with
both shard-local bodies, and 3 train steps on the 2×2 ("data", "model")
mesh, each against the single-device f32 reference computed in this process.

Each phase prints one line of results; any failed check raises, so the exit
code is non-zero. The last line is ``{"ok": true, "device": {...}}``. The
script exits non-zero without a result when JAX finds no TPU, when
``REPRO_FORCE_INTERPRET`` is set, or when it is run outside the checkout.
All phases run in this one process: one process owns the chip.
"""

from __future__ import annotations

import argparse
import dataclasses
import glob
import json
import os
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent

BI = (("inf", 1), ("1", 1))
TRI = (("inf", 1), ("inf", 1), ("1", 1))
# (name, shape, dtype, levels): the plan and serve keys
KEYS = (
    ("w_up_f32", (2048, 5632), "float32", BI),
    ("w_up_bf16", (2048, 5632), "bfloat16", BI),
    ("heads_tri_f32", (32, 64, 2048), "float32", TRI),
    ("fig1_f32", (1000, 10000), "float32", BI),
)
ARCH = "stablelm-1.6b"
TRAIN_LAYERS = 4          # one chip's share of the 24-layer model
RADIUS_FRACTION = 0.1     # radius = 0.1 × the input's norm: always active
BUCKETS = (1, 2, 4, 8)


class SmokeFailure(RuntimeError):
    """A check of the smoke run failed."""


def check(ok, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def emit(phase: str, result: dict) -> None:
    print(f"[{phase}] " + json.dumps(result, sort_keys=True), flush=True)


def tolerance(dtype) -> float:
    """Allowed max |x - ref| relative to max |ref|: 1e-5 in f32; four bf16
    epsilons for bf16 (the output itself rounds to half an epsilon)."""
    import jax.numpy as jnp

    return 1e-5 if jnp.dtype(dtype) == jnp.float32 \
        else 4 * float(jnp.finfo(jnp.bfloat16).eps)


def has_kernel(fn, *args) -> bool:
    """Does the compiled executable of ``fn(*args)`` hold a Mosaic kernel?"""
    return "tpu_custom_call" in fn.lower(*args).compile().as_text()


def sample(seed: int, shape, dtype):
    import jax
    import jax.numpy as jnp

    y = jax.random.normal(jax.random.PRNGKey(seed), shape, jnp.float32)
    return y.astype(dtype)


def reference(y, levels, radius):
    """The plain f32 reference: the sort-based schedule executor."""
    import jax.numpy as jnp
    from repro.core import multilevel_project

    return multilevel_project(y.astype(jnp.float32), list(levels), radius,
                              method="sort")


def active_radius(y, levels) -> float:
    import jax.numpy as jnp
    from repro.core import multilevel_norm

    return RADIUS_FRACTION * float(
        multilevel_norm(y.astype(jnp.float32), list(levels)))


def rel_err(x, ref) -> float:
    import jax.numpy as jnp

    diff = jnp.max(jnp.abs(x.astype(jnp.float32) - ref))
    return float(diff / jnp.maximum(jnp.max(jnp.abs(ref)), 1e-30))


# --------------------------------------------------------------------------- #
# phases
# --------------------------------------------------------------------------- #


def devices_phase(chips: int) -> dict:
    import jax

    from repro.runtime import compile_cache

    cache = compile_cache.configure()
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    emit("devices", {**dev, "compile_cache": cache})
    check(dev["platform"] == "tpu", f"JAX found no TPU: {dev}")
    check(dev["count"] >= chips, f"{chips} chips asked, {dev['count']} found")
    return dev


def plan_phase(keys=KEYS) -> None:
    """method="auto" on every key: all candidates run, each is checked."""
    import jax
    import jax.numpy as jnp
    from repro.core import multilevel_norm
    from repro.core import plan as planmod

    out = {}
    for i, (name, shape, dtype, levels) in enumerate(keys):
        dtype = jnp.dtype(dtype)
        y = sample(i, shape, dtype)
        r = active_radius(y, levels)
        ref = reference(y, levels, r)
        tol = tolerance(dtype)
        t0 = time.perf_counter()
        auto = planmod.make_plan(shape, dtype, levels, method="auto")
        setup_s = time.perf_counter() - t0
        check("codegen" in auto.timings_us,
              f"{name}: codegen was not a candidate: {auto.timings_us}")
        errs = {}
        for cand in auto.timings_us:
            x = planmod.make_plan(shape, dtype, levels, method=cand)(y, r)
            if cand == "exact_l1inf":
                # the exact ℓ1,∞ projection is another operator on the same
                # ball: held to feasibility, not to the bi-level output
                gap = float(multilevel_norm(x.astype(jnp.float32),
                                            list(levels))) / r - 1.0
                errs[cand] = {"norm_over_radius_minus_1": gap}
                check(gap <= tol, f"{name}/{cand}: infeasible by {gap}")
                continue
            errs[cand] = rel_err(x, ref)
            check(errs[cand] <= tol,
                  f"{name}/{cand}: rel err {errs[cand]} > {tol}")
        cg = planmod.make_plan(shape, dtype, levels, method="codegen")
        r_arr = jnp.asarray(r, dtype)
        cg_kernel = has_kernel(cg._exec.fn, y, r_arr)
        check(cg_kernel, f"{name}: codegen compiled without a Mosaic kernel")
        # the serving-bucket backend at B = 4 (per-item radii in SMEM)
        ys = jnp.stack([sample(100 * i + b, shape, dtype) for b in range(4)])
        radii = jnp.asarray([active_radius(v, levels) for v in ys], dtype)
        bp = planmod.make_plan(shape, dtype, levels, radius_kind="batch",
                               method="codegen_batch")
        xs = bp(ys, radii)
        batch_err = max(rel_err(xs[b], reference(ys[b], levels,
                                                 float(radii[b])))
                        for b in range(4))
        check(batch_err <= tol,
              f"{name}/codegen_batch: rel err {batch_err} > {tol}")
        cb_kernel = has_kernel(bp._exec.fn, ys, radii)
        check(cb_kernel,
              f"{name}: codegen_batch compiled without a Mosaic kernel")
        jax.block_until_ready(xs)
        out[name] = {
            "shape": list(shape), "dtype": dtype.name, "radius": r,
            "winner": auto.method,
            "timings_us": {k: round(v, 1) for k, v in auto.timings_us.items()},
            "setup_s": round(setup_s, 2), "tol": tol,
            "rel_err_vs_f32_sort": errs,
            "codegen_batch_b4_rel_err": batch_err,
            "codegen_tpu_custom_call": cg_kernel,
            "codegen_batch_tpu_custom_call": cb_kernel,
        }
    emit("plan", out)


def serve_phase(keys=KEYS) -> None:
    """ProjectionEngine over every key, groups filling buckets 1, 2, 4, 8."""
    from repro.serving import ProjectionEngine

    worst, n_req = {}, 0
    with ProjectionEngine(method="codegen_batch", max_batch=max(BUCKETS),
                          start=False) as eng:
        for k, (name, shape, dtype, levels) in enumerate(keys):
            worst[name] = 0.0
            for b in BUCKETS:
                reqs = []
                for j in range(b):
                    y = sample(1000 * k + 10 * b + j, shape, dtype)
                    r = active_radius(y, levels)
                    ref = reference(y, levels, r)
                    # the engine consumes (donates) the payload it is given
                    reqs.append((eng.submit(y, list(levels), r), ref))
                eng.drain()
                for ticket, ref in reqs:
                    err = rel_err(eng.result(ticket), ref)
                    worst[name] = max(worst[name], err)
                    check(err <= tolerance(dtype),
                          f"serve {name} bucket {b}: rel err {err}")
                n_req += b
        snap = eng.stats()
    total = sum(snap[k] for k in ("completed", "failed", "discarded",
                                  "queued", "inflight"))
    check(total == snap["submitted"] == n_req,
          f"engine accounting broken: {total} vs {snap['submitted']}")
    check(snap["failed"] == 0, f"{snap['failed']} requests failed")
    check(snap["max_group"] == max(BUCKETS),
          f"largest group {snap['max_group']} != {max(BUCKETS)}")
    emit("serve", {
        "requests": n_req, "buckets": list(BUCKETS),
        "backend": "codegen_batch", "max_rel_err_vs_f32_sort": worst,
        **{k: snap[k] for k in ("submitted", "completed", "failed",
                                "discarded", "queued", "inflight",
                                "dispatches", "max_group")},
    })


def _projected_leaves(params, spec):
    """{path: leaf} of every parameter the projection hook matches."""
    import jax
    from repro.optim.projection_hook import _path_str, matched_names

    names = set(matched_names(params, spec))
    flat = jax.tree_util.tree_flatten_with_path(params)[0]
    return {_path_str(p): w for p, w in flat if _path_str(p) in names}


def _leaf_checks(leaves, spec) -> dict:
    """Feasibility and idempotence (re-projection by the f32 sort reference)
    of each projected leaf; leading stacked axes are per-layer instances."""
    import jax
    import jax.numpy as jnp
    from repro.core import multilevel_norm

    levels = list(spec.levels)
    worst_norm, worst_move = 0.0, 0.0
    for name, w in leaves.items():
        w = w.astype(jnp.float32)
        norm = lambda v: multilevel_norm(v, levels)  # noqa: E731
        proj = lambda v: reference(v, levels, spec.radius)  # noqa: E731
        for _ in range(w.ndim - 2):
            norm, proj = jax.vmap(norm), jax.vmap(proj)
        ratio = float(jnp.max(norm(w))) / spec.radius
        move = rel_err(proj(w), w)
        check(ratio <= 1 + 1e-4, f"{name}: norm/radius {ratio} > 1 + 1e-4")
        check(move <= tolerance(jnp.float32),
              f"{name}: re-projection moved it by {move} (not idempotent)")
        worst_norm, worst_move = max(worst_norm, ratio), max(worst_move, move)
    return {"leaves": sorted(leaves), "max_norm_over_radius": worst_norm,
            "max_reprojection_rel_change": worst_move}


def _train_cfg(n_layers: int):
    from repro.configs import registry

    return dataclasses.replace(registry.get_arch(ARCH), n_layers=n_layers)


def _train_radius(cfg, run) -> float:
    """RADIUS_FRACTION × the smallest initial norm over the matched leaves
    and their layers, so the constraint is active on every one of them."""
    import jax
    from repro.core import multilevel_norm
    from repro.training import init_state

    spec = run.tcfg.projection
    params = init_state(cfg, run.tcfg, run.api,
                        jax.random.PRNGKey(run.tcfg.seed))["params"]
    norm = lambda w: multilevel_norm(w, list(spec.levels))  # noqa: E731
    smallest = min(float(jax.vmap(norm)(w).min())
                   for w in _projected_leaves(params, spec).values())
    return RADIUS_FRACTION * smallest


def _run_steps(cfg, mesh, *, steps, batch, seq, radius):
    """``steps`` projected train steps as ``repro.launch.train`` runs them;
    returns (run, state, losses, compile_s, compiled)."""
    import jax
    import jax.numpy as jnp
    from repro.launch import train as launch
    from repro.training import init_state

    run = launch.make_run(cfg, mesh, steps=steps, seq=seq, batch=batch,
                          radius=radius)
    state = init_state(cfg, run.tcfg, run.api,
                       jax.random.PRNGKey(run.tcfg.seed))
    losses, compile_s, compiled = [], None, None
    with mesh:
        state = launch.place_state(state, mesh, run.specs)
        for step in range(steps):
            tokens = {"tokens": jnp.asarray(run.pipe.batch(step))}
            if compiled is None:
                # compiled ahead of the first step for its compile time and
                # memory analysis; the step call then finds the program in
                # the persistent compilation cache
                t0 = time.perf_counter()
                compiled = run.step_fn.lower(state, tokens).compile()
                compile_s = time.perf_counter() - t0
            state, metrics = run.step_fn(state, tokens)
            losses.append(float(metrics["loss"]))
    return run, state, losses, compile_s, compiled


def train_phase(n_layers=TRAIN_LAYERS, batch=8, seq=256, steps=5) -> None:
    import math

    import jax
    from repro.configs import registry
    from repro.launch import train as launch

    cfg = _train_cfg(n_layers)
    mesh = launch.parse_mesh("1x1", jax.devices()[:1])
    probe = launch.make_run(cfg, mesh, steps=steps, seq=seq, batch=batch,
                            radius=1.0)
    radius = _train_radius(cfg, probe)
    run, state, losses, compile_s, compiled = _run_steps(
        cfg, mesh, steps=steps, batch=batch, seq=seq, radius=radius)
    check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    leaves = _leaf_checks(_projected_leaves(state["params"],
                                            run.tcfg.projection),
                          run.tcfg.projection)
    mem = compiled.memory_analysis()
    need = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    n_params = sum(int(x.size)
                   for x in jax.tree_util.tree_leaves(state["params"]))
    emit("train", {
        "arch": ARCH, "d_model": cfg.d_model, "d_ff": cfg.d_ff,
        "n_heads": cfg.n_heads, "vocab": cfg.vocab,
        "depth_cut": f"{registry.get_arch(ARCH).n_layers} -> "
                     f"{cfg.n_layers} layers (one chip's share)",
        "params": n_params, "batch": batch, "seq": seq, "steps": steps,
        "radius": radius, "losses": losses,
        "compile_s": round(compile_s, 2),
        "step_program_gb": round(need / 1e9, 3), **leaves,
    })


# ----------------------------- four chips ---------------------------------- #


def _spread(x) -> dict:
    """How ``x`` lies on the devices: every device must hold a shard that is
    smaller than the whole array (nothing gathered onto one device)."""
    shards = x.addressable_shards
    devices = {s.device.id for s in shards}
    partial = all(s.data.shape != x.shape for s in shards)
    return {"devices": sorted(devices), "sharded": partial}


def sharded_phase(chips: int, designs=None) -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import multilevel_project_sharded
    from repro.launch.mesh import auto_mesh

    mesh = auto_mesh((chips,), ("model",))
    designs = designs or (
        ("bilevel_cols", (2048, 5632), BI, P(None, "model")),
        ("bilevel_rows", (2048, 5632), BI, P("model", None)),
        ("trilevel_heads", (32, 64, 2048), TRI, P(None, None, "model")),
    )
    out = {}
    for i, (name, shape, levels, spec) in enumerate(designs):
        y = sample(i, shape, "float32")
        r = active_radius(y, levels)
        ref = reference(y, levels, r)          # single device, f32
        ys = jax.device_put(y, NamedSharding(mesh, spec))
        for body in ("jnp", "codegen"):
            fn = jax.jit(lambda v, rr, levels=levels, spec=spec, body=body:
                         multilevel_project_sharded(
                             v, list(levels), rr, mesh=mesh, spec=spec,
                             method="auto", backend=body))
            x = fn(ys, r)
            err = rel_err(x, ref)
            spread = _spread(x)
            check(err <= tolerance("float32"),
                  f"sharded {name}/{body}: rel err {err}")
            check(len(spread["devices"]) == chips and spread["sharded"],
                  f"sharded {name}/{body}: output not spread: {spread}")
            kernel = has_kernel(fn, ys, r) if body == "codegen" else None
            check(body == "jnp" or kernel,
                  f"sharded {name}/codegen: no Mosaic kernel compiled")
            out[f"{name}/{body}"] = {"rel_err_vs_1dev_f32_sort": err,
                                     "spec": str(spec), **spread,
                                     "tpu_custom_call": kernel}
    emit("sharded", out)


def mesh_train_phase(chips: int, n_layers=TRAIN_LAYERS, batch=8, seq=256,
                     steps=3) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.launch import train as launch

    cfg = _train_cfg(n_layers)
    one = launch.parse_mesh("1x1", jax.devices()[:1])
    probe = launch.make_run(cfg, one, steps=steps, seq=seq, batch=batch,
                            radius=1.0)
    radius = _train_radius(cfg, probe)
    # the single-device reference first, pulled to the host and freed
    run1, state1, losses1, _, _ = _run_steps(cfg, one, steps=steps,
                                             batch=batch, seq=seq,
                                             radius=radius)
    ref = {k: np.asarray(v) for k, v in _projected_leaves(
        state1["params"], run1.tcfg.projection).items()}
    del state1
    side = int(round(chips ** 0.5))
    mesh = launch.parse_mesh(f"{side}x{chips // side}")
    run, state, losses, compile_s, _ = _run_steps(
        cfg, mesh, steps=steps, batch=batch, seq=seq, radius=radius)
    leaves = _projected_leaves(state["params"], run.tcfg.projection)
    loss_diff = max(abs(a - b) for a, b in zip(losses, losses1))
    w_diff = max(float(jnp.max(jnp.abs(w - ref[k]))) for k, w in
                 leaves.items())
    spread = {k: _spread(w) for k, w in leaves.items()}
    # the repo's own mesh-vs-single-device step check allows 5e-3 on both
    check(loss_diff < 5e-3, f"mesh loss differs by {loss_diff}")
    check(w_diff < 5e-3, f"mesh projected weights differ by {w_diff}")
    check(all(len(s["devices"]) == chips and s["sharded"]
              for s in spread.values()), f"leaves not spread: {spread}")
    emit("mesh_train", {
        "mesh": dict(mesh.shape), "arch": ARCH, "n_layers": cfg.n_layers,
        "d_model": cfg.d_model, "d_ff": cfg.d_ff, "steps": steps,
        "radius": radius, "losses": losses, "losses_1dev": losses1,
        "max_loss_diff": loss_diff, "max_projected_weight_diff": w_diff,
        "compile_s": round(compile_s, 2), "spread": spread,
        **_leaf_checks(leaves, run.tcfg.projection),
    })


# --------------------------------------------------------------------------- #


def _tpu_present() -> bool:
    """A TPU VM exposes its chips as device nodes; without one, importing
    the TPU runtime would only probe for hardware that is not there."""
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the mesh path, on four chips")
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_FORCE_INTERPRET"):
        print("REPRO_FORCE_INTERPRET is set: kernels would run in interpret "
              "mode, not on the chip", file=sys.stderr)
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no repro package under {ROOT}: run chip_smoke.py from the "
              "repository checkout", file=sys.stderr)
        return 2
    if not _tpu_present():
        print("no TPU device node (/dev/accel*, /dev/vfio) on this machine",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    dev = devices_phase(args.chips)
    if args.chips == 1:
        plan_phase()
        serve_phase()
        train_phase()
    else:
        sharded_phase(args.chips)
        mesh_train_phase(args.chips)
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

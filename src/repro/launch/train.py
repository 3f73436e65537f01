"""Production training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch granite-3-2b \
        --smoke --steps 50 --mesh 1x1 --ckpt /tmp/run1

On a real TPU slice run without --smoke and with the pod mesh (e.g.
--mesh 16x16). The launcher owns: mesh construction, sharded state init (or
elastic restore from the latest checkpoint), the data pipeline, async
checkpointing, straggler monitoring hooks, and the projection constraint.
"""

from __future__ import annotations

import argparse
import time
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import models
from repro.configs import registry
from repro.configs.types import ProjectionSpec, TrainConfig
from repro.data import DataConfig, DataPipeline
from repro.launch.mesh import auto_mesh
from repro.models import params as PM
from repro.obs import jax_bridge
from repro.obs import metrics as obs_metrics
from repro.obs import profile as obs_profile
from repro.optim import adamw
from repro.parallel import sharding as SH
from repro.runtime import CheckpointManager, StragglerMonitor, compile_cache
from repro.training import init_state, make_train_step
from repro.optim.projection_hook import tree_sparsity


def parse_mesh(spec: str, devices=None):
    """``"DxM"`` → a ``("data", "model")`` mesh (``"PxDxM"`` adds ``"pod"``)
    over ``devices`` (default: all of this process's devices)."""
    dims = tuple(int(x) for x in spec.split("x"))
    names = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return auto_mesh(dims, names, devices)


class Run(NamedTuple):
    """Everything one launcher run is built from (see :func:`make_run`)."""

    api: object
    tcfg: TrainConfig
    pipe: DataPipeline
    specs: object          # the params' PartitionSpec tree on the mesh
    step_fn: object        # jitted (state, batch) -> (state, metrics)


def make_run(cfg, mesh, *, steps: int, seq: int, batch: int,
             microbatch: int = 0, lr: float = 3e-4, radius: float = 0.0,
             smoke: bool = False, ckpt_every: int = 50,
             telemetry_every: int = 0, telemetry_marks: bool = False) -> Run:
    """The launcher's training setup: TrainConfig, data pipeline, parameter
    specs and the jitted projected train step. ``radius > 0`` turns on the
    bi-level ℓ1,∞ constraint on every ``w_up``/``w_gate`` leaf.

    The step donates its state argument: the caller rebinds ``state`` to the
    step's output, so parameters and optimizer moments exist once on the
    device, not twice."""
    api = models.get(cfg)
    micro = microbatch or batch
    proj = None
    if radius > 0:
        proj = ProjectionSpec(pattern=r"(w_up|w_gate)", radius=radius)
    tcfg = TrainConfig(microbatch=micro, lr=lr, total_steps=steps,
                       warmup=min(20, steps // 5 + 1), remat=not smoke,
                       master_dtype="", projection=proj,
                       checkpoint_every=ckpt_every)
    pipe = DataPipeline(DataConfig(vocab=cfg.vocab, seq_len=seq + 1,
                                   global_batch=batch, microbatch=micro))
    rules = SH.param_rules(mesh)
    specs = PM.param_specs(api.template(cfg), rules, SH.mesh_shape_dict(mesh))
    b_ax = SH.batch_axes(mesh)
    act_spec = P(b_ax if len(b_ax) > 1 else b_ax[0], None, None)
    step_fn = jax.jit(make_train_step(
        cfg, tcfg, api, impl="naive" if smoke else "chunked",
        n_groups=SH.dp_shards(mesh), act_spec=act_spec,
        mesh=mesh, param_specs=specs,
        telemetry_every=telemetry_every,
        telemetry_marks=telemetry_marks), donate_argnums=(0,))
    return Run(api, tcfg, pipe, specs, step_fn)


def state_shardings(mesh, run: Run):
    """``NamedSharding``s of the whole train state ``{"params", "opt"}``:
    the params' specs, both AdamW moments (and a master copy) alike, the
    step counter replicated. ``init_state(..., shardings=...)`` builds the
    state born sharded on them."""
    return SH.named(mesh, {"params": run.specs,
                           "opt": adamw.state_specs(run.specs, None,
                                                    run.tcfg)})


def init_opt(params, mesh, run: Run):
    """AdamW's state for ``params`` (already on the mesh), born sharded
    like them: one jitted init, no moment ever whole on one device."""
    return jax.jit(lambda p: adamw.init(p, run.tcfg),
                   out_shardings=state_shardings(mesh, run)["opt"])(params)


def place_state(state, mesh, specs):
    """Shard the whole state onto the mesh: the parameters by their specs,
    the optimizer state like them (moments and master copy as their param,
    the step counter replicated). A state born sharded is already in place
    and stays there."""
    specs = {"params": specs, "opt": adamw.specs_of(state["opt"], specs)}
    return jax.device_put(state, SH.named(mesh, specs))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-3-2b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-friendly)")
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--radius", type=float, default=0.0,
                    help=">0 enables the bi-level l1,inf constraint")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax.profiler trace of the run here "
                         "(schedule stages show up as proj/* named scopes)")
    ap.add_argument("--telemetry-every", type=int, default=0,
                    help=">0 enables the host-callback telemetry bridge and "
                         "ships loss/grad-norm/sparsity/feasibility every "
                         "that many steps")
    ap.add_argument("--telemetry-marks", action="store_true",
                    help="also bracket the optimizer/projection epilogue "
                         "with ordered timing marks (costly: serializes a "
                         "host callback pair into every step)")
    ap.add_argument("--metrics-out", default="",
                    help="write the final obs-registry snapshot (JSON lines) "
                         "to this path")
    args = ap.parse_args()
    compile_cache.configure()
    if args.telemetry_every > 0 or args.telemetry_marks:
        jax_bridge.enable()

    cfg = (registry.smoke_config(args.arch) if args.smoke
           else registry.get_arch(args.arch))
    mesh = parse_mesh(args.mesh)
    run = make_run(cfg, mesh, steps=args.steps, seq=args.seq,
                   batch=args.batch, microbatch=args.microbatch, lr=args.lr,
                   radius=args.radius, smoke=args.smoke,
                   ckpt_every=args.ckpt_every,
                   telemetry_every=args.telemetry_every,
                   telemetry_marks=args.telemetry_marks)
    tcfg, pipe, step_fn = run.tcfg, run.pipe, run.step_fn
    proj = tcfg.projection
    mgr = CheckpointManager(args.ckpt, keep=3) if args.ckpt else None
    mon = StragglerMonitor(n_hosts=jax.process_count())

    state, start = None, 0
    if mgr:
        state, manifest = mgr.restore(shardings=None)
        if state is not None:
            start = manifest["step"]
            print(f"[elastic restart] resuming from step {start}")
    if state is None:
        # on a mesh the state is born sharded: no device ever holds it
        # whole; on one device it is built eagerly, as it always was (the
        # jitted init fuses each leaf's scaling, a last-bit difference)
        state = init_state(cfg, tcfg, run.api, jax.random.PRNGKey(tcfg.seed),
                           shardings=state_shardings(mesh, run)
                           if mesh.size > 1 else None)
    step_hist = obs_metrics.get_registry().histogram(
        "train_step_seconds", "end-to-end wall time of one training step")
    with mesh, obs_profile.capture(args.profile_dir):
        state = place_state(state, mesh, run.specs)
        for step in range(start, args.steps):
            t0 = time.perf_counter()
            batch = {"tokens": jnp.asarray(pipe.batch(step))}
            state, metrics = step_fn(state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.perf_counter() - t0
            step_hist.observe(dt)
            rep = mon.record({jax.process_index(): dt})
            if mgr and (step + 1) % tcfg.checkpoint_every == 0:
                mgr.save_async(step + 1, state)
            if (step + 1) % 10 == 0 or step + 1 == args.steps:
                msg = (f"step {step + 1:5d} loss {float(metrics['loss']):.4f} "
                       f"gnorm {float(metrics['grad_norm']):.2f}")
                if rep.action != "none":
                    msg += f"  [straggler watch: {rep.stragglers}]"
                print(msg)
    if mgr:
        mgr.save(args.steps, state)
        mgr.wait()
    if proj:
        for name, sp in tree_sparsity(state["params"], proj).items():
            print(f"column sparsity {name}: {float(sp):.1f}%")
    if args.metrics_out:
        obs_metrics.get_registry().write_jsonl(args.metrics_out)
        print(f"metrics snapshot -> {args.metrics_out}")
    if args.profile_dir:
        print(f"profiler trace -> {args.profile_dir} "
              f"({len(obs_profile.trace_files(args.profile_dir))} files)")


if __name__ == "__main__":
    main()

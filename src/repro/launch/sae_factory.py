"""CLI for the sparse-SAE training factory (training/sae_factory.py).

    PYTHONPATH=src python -m repro.launch.sae_factory \
        --arch stablelm-1.6b --out /tmp/sae_run --layers 0,2 \
        --train-steps 200 --expansion 8

Runs harvest → projected SAE training (one per layer × seed) → MMCS
cross-comparison and writes ``summary.json`` into ``--out``. Add ``--gsp``
to also run the whole-network GSP sparsification pass (every weight of the
LM projected per step; give it a multi-device host via
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` to exercise the mesh
executor path).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b")
    ap.add_argument("--out", required=True)
    ap.add_argument("--site", default="resid", choices=["resid", "mlp"])
    ap.add_argument("--layers", default="",
                    help="comma list of layer indices; empty = all")
    ap.add_argument("--harvest-steps", type=int, default=4)
    ap.add_argument("--train-steps", type=int, default=40)
    ap.add_argument("--expansion", type=int, default=4)
    ap.add_argument("--radius", type=float, default=1.0)
    ap.add_argument("--heads", type=int, default=1,
                    help=">1: head-structured dictionary — 3-D encoder "
                         "projected onto the tri-level l1,inf,inf ball")
    ap.add_argument("--checkpoint", default="",
                    help="checkpoint dir to harvest from (runtime/checkpoint "
                         "layout); default: seeded init weights")
    ap.add_argument("--seeds", default="0,1")
    ap.add_argument("--full", action="store_true",
                    help="full-size arch (default: smoke config)")
    ap.add_argument("--gsp", action="store_true",
                    help="also run whole-network GSP sparsification")
    ap.add_argument("--profile-dir", default="",
                    help="capture a jax.profiler trace of the factory run "
                         "(projection stages appear as proj/* named scopes)")
    args = ap.parse_args(argv)

    from repro.launch.mesh import make_host_mesh
    from repro.obs import metrics as obs_metrics
    from repro.obs import profile as obs_profile
    from repro.runtime import compile_cache
    from repro.training import sae_factory as F

    import jax

    compile_cache.configure()

    fcfg = F.SAEFactoryConfig(
        arch=args.arch, smoke=not args.full, site=args.site,
        layers=tuple(int(x) for x in args.layers.split(",") if x) or None,
        harvest_steps=args.harvest_steps, train_steps=args.train_steps,
        expansion=args.expansion, radius=args.radius, heads=args.heads)
    out = pathlib.Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    seeds = tuple(int(s) for s in args.seeds.split(","))
    lm_params = None
    if args.checkpoint:
        from repro.runtime.checkpoint import CheckpointManager

        tree, manifest = CheckpointManager(args.checkpoint).restore()
        if tree is None:
            print(f"no checkpoint found under {args.checkpoint}",
                  file=sys.stderr)
            return 1
        # training states store {"params", "opt"}; bare param trees pass as-is
        lm_params = tree["params"] if (isinstance(tree, dict)
                                       and "params" in tree) else tree
        print(f"harvesting from checkpoint step "
              f"{manifest.get('step', '?')} at {args.checkpoint}")
    with obs_profile.capture(args.profile_dir):
        summary = F.run_factory(fcfg, out, seeds=seeds, lm_params=lm_params)
        if args.gsp:
            n_dev = jax.device_count()
            mesh = make_host_mesh(1, n_dev) if n_dev > 1 else None
            summary["gsp"] = F.gsp_whole_network(args.arch, mesh=mesh)
    obs_metrics.get_registry().write_jsonl(out / "metrics.jsonl")
    # json keys must be strings; layers come out as ints
    summary["layers"] = {str(k): v for k, v in summary["layers"].items()}
    (out / "summary.json").write_text(json.dumps(summary, indent=1,
                                                 default=str) + "\n")
    for layer, rec in summary["layers"].items():
        print(f"layer {layer}: mmcs={rec['mmcs']}")
    if args.gsp:
        g = summary["gsp"]
        print(f"gsp: n_projected={g['n_projected']} feasible={g['feasible']} "
              f"mean_col_sparsity={g['mean_col_sparsity']:.1f}%")
    return 0


if __name__ == "__main__":
    sys.exit(main())

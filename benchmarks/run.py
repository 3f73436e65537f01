"""Benchmark driver — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only fig1,fig2,...]
                                            [--json-dir DIR] [--no-json]

Prints ``name,us_per_call,derived`` CSV rows (µs medians, steady-state) and,
unless ``--no-json``, writes one machine-readable ``BENCH_<section>.json`` per
section into ``--json-dir`` (default: CWD) — the bench-trajectory artifacts CI
uploads. Default sizes are scaled for the single-core container; --full uses
the paper's sizes. Each row records the device it ran on (``platform``,
``device_kind``, count); the mesh sections run in a forced-CPU child process
and record the child's device. Roofline/dry-run numbers live in experiments/
(they come from the AOT pipeline, not this script).
"""

from __future__ import annotations

import argparse
import json
import pathlib
import platform
import sys

from repro.runtime import compile_cache

from .devices import device_record

# artifact file names per section (the methods sweep seeds the trajectory)
_JSON_NAMES = {
    "fig1": "BENCH_fig1_radius.json",
    "fig2": "BENCH_fig2_size.json",
    "fig3": "BENCH_fig3_trilevel.json",
    "fig4": "BENCH_fig4_parallel.json",
    "table1": "BENCH_table1_scaling.json",
    "methods": "BENCH_projection_methods.json",
    "plan": "BENCH_projection_plan.json",
    "sharded": "BENCH_sharded_multilevel.json",
    "codegen": "BENCH_codegen_kernels.json",
    "sharded_codegen": "BENCH_sharded_codegen.json",
    "serving": "BENCH_serving_latency.json",
    "train": "BENCH_train_step.json",
    "sae": "BENCH_sae_tables.json",
    "sae_factory": "BENCH_sae_factory.json",
    "obs": "BENCH_obs_overhead.json",
}


def _write_json(json_dir: pathlib.Path, section: str, rows, full: bool) -> None:
    # a row measured in a CPU-mesh child carries the child's own device
    # record as a 4th element; every other row ran on this process's device
    device = device_record()
    payload = {
        "section": section,
        "full": full,
        "device": device,
        "machine": platform.machine(),
        "rows": [
            {"name": name, "us_per_call": round(us, 1), "derived": derived,
             "device": dev[0] if dev else device}
            for name, us, derived, *dev in rows
        ],
    }
    path = json_dir / _JSON_NAMES[section]
    path.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"# wrote {path}", file=sys.stderr)
    # the obs-registry state the section's run left behind (engine/planner
    # counters, latency histograms, ...) — one JSON-lines snapshot per
    # section, next to its BENCH artifact, uploaded by CI with it
    from repro.obs import metrics as obs_metrics

    mpath = json_dir / f"METRICS_{section}.jsonl"
    obs_metrics.get_registry().write_jsonl(mpath)
    print(f"# wrote {mpath}", file=sys.stderr)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true", help="paper-scale sizes")
    ap.add_argument("--only", default="",
                    help="comma list: fig1,fig2,fig3,fig4,table1,methods,plan,"
                         "sharded,codegen,sharded_codegen,serving,train,sae,"
                         "sae_factory,obs")
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_<section>.json artifacts")
    ap.add_argument("--no-json", action="store_true",
                    help="CSV to stdout only, no artifact files")
    args = ap.parse_args(argv)
    only = set(filter(None, args.only.split(",")))
    compile_cache.configure()

    from . import (obs_overhead, projections, sae_factory, sae_tables,
                   serving_trace, train_step)

    sections = {
        "fig1": lambda: projections.fig1_radius(full=args.full),
        "fig2": lambda: projections.fig2_size(full=args.full),
        "fig3": lambda: projections.fig3_trilevel(full=args.full),
        "table1": lambda: projections.table1_scaling(full=args.full),
        "methods": lambda: projections.methods_sweep(full=args.full),
        "plan": lambda: projections.plan_sweep(full=args.full),
        "sharded": lambda: projections.sharded_sweep(full=args.full),
        "codegen": lambda: projections.codegen_sweep(full=args.full),
        "sharded_codegen":
            lambda: projections.sharded_codegen_sweep(full=args.full),
        "serving": lambda: serving_trace.serving_sweep(full=args.full),
        "train": lambda: train_step.train_sweep(full=args.full),
        "fig4": projections.fig4_parallel,
        "sae": lambda: sae_tables.tables(full=args.full),
        "sae_factory": lambda: sae_factory.factory_sweep(full=args.full),
        "obs": lambda: obs_overhead.obs_sweep(full=args.full),
    }
    unknown = only - set(sections)
    if unknown:
        ap.error(f"unknown section(s) {sorted(unknown)}; pick from {sorted(sections)}")
    json_dir = pathlib.Path(args.json_dir)
    if not args.no_json:
        json_dir.mkdir(parents=True, exist_ok=True)
    print("name,us_per_call,derived")
    for key, fn in sections.items():
        if only and key not in only:
            continue
        rows = fn()
        for name, us, derived, *_ in rows:
            print(f"{name},{us:.1f},{derived}")
            sys.stdout.flush()
        if not args.no_json:
            _write_json(json_dir, key, rows, args.full)


if __name__ == "__main__":
    main()

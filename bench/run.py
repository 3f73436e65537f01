"""Run one benchmark cell once, on the chip, and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration and a
traffic mix; the configuration's ``driver`` loads the program, makes its
weights and inputs from the seed, warms up every shape the mix uses (that is
``setup_s``), measures for ``--seconds``, and compares what the timed path
produced with the plain reference. ``--trace 0`` reports the cell's
end-to-end metrics; ``--trace 1`` traces the window and reports its
per-layer metrics and a breakdown.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown`` with
``--trace 1``), then ``checks``, each number compared beside its limit,
which also close standard error.

Exits 2, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for, when ``REPRO_FORCE_INTERPRET`` is set, or when the program
(``src/repro``) is not beside the benchmark. JAX's compilation cache lives in
``<checkout>/.jax_cache``.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def _refuse(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if os.environ.get("REPRO_FORCE_INTERPRET"):
        return _refuse("REPRO_FORCE_INTERPRET is set: kernels would run in "
                       "interpret mode, not on the chip")
    if not (ROOT / "src" / "repro").is_dir():
        return _refuse(f"no program under {ROOT / 'src'}")
    sys.path.insert(0, str(BENCH))
    import harness

    manifest = harness.load_manifest()
    cell = harness.workload(manifest, args.workload)
    cfg = harness.config(manifest, cell["config"])
    mix = harness.traffic(cell["traffic"])

    cache = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _refuse(f"JAX found no TPU (platform {devices[0].platform})")
    if len(devices) < cell["chips"]:
        return _refuse(f"{cell['chips']} chips asked, {len(devices)} found")
    devices = devices[:cell["chips"]]
    sys.path.insert(0, str(ROOT / "src"))
    harness.phase_line("start-up", time.perf_counter() - T0)
    result, checks = measure(manifest, cell, cfg, mix, args.seed, args.seconds,
                             trace=bool(args.trace), devices=devices, t0=T0)
    harness.emit(result, checks)
    return 0


def measure(manifest, cell, cfg, mix, seed, seconds, *, trace, devices, t0,
            device_pattern=r"^/device:TPU:\d+$", **driver_kw):
    """One run of ``cell`` on ``devices``: the driver's set-up, window and
    comparison, then the metrics. Returns (result line, checks)."""
    import harness

    drv = harness.driver(cfg["driver"])
    with tempfile.TemporaryDirectory() as tmp:
        rec = drv.run(cfg, mix, seed, seconds, t0=t0,
                      trace_dir=tmp if trace else None, devices=devices,
                      **driver_kw)
        metrics, breakdown = {}, None
        if trace:
            import counts
            import trace as trace_mod

            tr = trace_mod.reduce(tmp, device_pattern=device_pattern)
            ctx = {"record": rec, "trace": tr, "config": cfg, "traffic": mix,
                   "peaks": counts.peaks(devices[0].device_kind)}
            for m in harness.per_layer(manifest, cell["name"]):
                v = harness.metric_reader(m["name"]).read(ctx)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            rec["device"]["busy_s"] = tr["busy_s"]
            rec["device"]["window_s"] = tr["window_s"]
            breakdown = {"device_ops": tr["device_ops"],
                         "idle_gaps": tr["idle_gaps"]}
        else:
            for m in harness.end_to_end(manifest, cell["name"]):
                metrics[m["name"]] = {"value": rec["e2e"][m["name"]],
                                      "unit": m["unit"]}
    result = {"correct": harness.judge(rec["checks"]),
              "attempted": rec["attempted"], "failed": rec["failed"],
              "metrics": metrics, "device": rec["device"]}
    if breakdown is not None:
        result["breakdown"] = breakdown
    return result, rec["checks"]


if __name__ == "__main__":
    sys.exit(main())

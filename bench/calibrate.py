"""Readings that the limits and the traffic rates are set from, on the chip.

    python3 bench/calibrate.py train <cell> --seeds 1,2,3 [--controls 3]
    python3 bench/calibrate.py serve <cell> --seeds 1,2,3 [--controls 3] [--seconds 3]
    python3 bench/calibrate.py knee <cell> --rates 100,200,400 [--seconds 6] [--max-pending 64]

Everything runs in this one process (one process owns the chip), and each
line printed is one JSON record.

* ``train``: for each seed, the program's three set-up steps and the
  float32 reference, and the gaps the cell compares (its lower readings);
  on the first ``--controls`` seeds the control (the reference with every
  activation, matmul operand and backward cotangent rounded to float8 e4m3
  under a per-tensor scale, one step below the bfloat16 compute the
  configuration states), the same reference rounded to bfloat16 (what the
  stated precision alone reads) and the fault of half the batch left out,
  each in the program's place (its upper readings). A step that
  leaves its state unchanged reads 1 on the change gap by construction.
* ``serve``: for each seed, a short run of the cell (the program's errors),
  and on the first ``--controls`` seeds the control: the sampled payloads
  projected by the reference with payload and answer held in bfloat16
  (float32 keys) or float8 e4m3 (bfloat16 keys), against the float32
  reference; beside it, the reference computed wholly in that precision
  (``computed_in_lower``; float8 sums can overflow and give no number).
* ``knee``: the cell's open-loop mix at each rate in turn, in rising order:
  offered and completed rates, latency quantiles, and whether the backlog
  grew (the median latency of the last fifth of requests over the first
  fifth's). The queue bound is cut to ``--max-pending`` so that a backlog
  above capacity cannot fill the device's memory, and the sweep stops after
  ``--past`` rates that do not keep up.
"""

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent


def _setup_jax():
    cache = str(ROOT / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    sys.path.insert(0, str(BENCH))
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("calibrate.py: JAX found no TPU")
    return jax


def _out(rec):
    print(json.dumps(rec), flush=True)


def _readings(drv, run, ref):
    """The gaps the train driver reads, each leaf's ratio of first-gradient
    norms, and the median leaf's gradient gap."""
    import numpy as np

    ratio = {p: run["grad_norms"][p] / r - 1
             for p, r in ref["grad_norms"].items()}
    med = float(np.median(list(ref["grad_norms"].values())))
    leaf_gaps = [abs(run["grad_norms"][p] - r) / max(r, med)
                 for p, r in ref["grad_norms"].items()]
    return {**drv._gaps(run, ref), "losses": run["losses"],
            "grad_ratio": ratio,
            "grad_gap_median": float(np.median(leaf_gaps))}


def train(cell, seeds, n_controls):
    jax = _setup_jax()
    import jax.numpy as jnp

    import harness
    import load
    import reference

    m = harness.load_manifest()
    w = harness.workload(m, cell)
    cfg = harness.config(m, w["config"])
    mix = harness.traffic(w["traffic"])
    drv = harness.driver(cfg["driver"])
    devices = jax.devices()[:w["chips"]]
    for k, seed in enumerate(seeds):
        t = time.perf_counter()
        _, state, _, prog, radius = drv.setup(cfg, mix, seed, devices)
        del state
        batches = [load.train_tokens(mix, seed, i, cfg["vocab_size"])[0]
                   for i in range(mix["setup_steps"])]
        ref = reference.train_reference(cfg, cfg["optimizer"], seed, batches,
                                        radius)
        _out({"seed": seed, "who": "program", **_readings(drv, prog, ref),
              "ref_losses": ref["losses"], "s": time.perf_counter() - t})
        if k >= n_controls:
            continue
        for who, kw in (("control_fp8", {"compute": jnp.float8_e4m3fn}),
                        ("reference_bf16", {"compute": jnp.bfloat16}),
                        ("fault_half_batch", {"rows": mix["batch"] // 2})):
            alt = reference.train_reference(cfg, cfg["optimizer"], seed,
                                            batches, radius, **kw)
            _out({"seed": seed, "who": who, **_readings(drv, alt, ref)})
        if k == 0:
            _out({"seed": seed, "who": "ref_norms",
                  "grad_norms": ref["grad_norms"],
                  "delta_norms": ref["delta_norms"],
                  "prog_grad_norms": prog["grad_norms"],
                  "prog_delta_norms": prog["delta_norms"]})


def serve(cell, seeds, n_controls, seconds):
    jax = _setup_jax()
    import jax.numpy as jnp

    import harness
    import load
    import reference
    import run as bench_run

    m = harness.load_manifest()
    w = harness.workload(m, cell)
    cfg = harness.config(m, w["config"])
    mix = harness.traffic(w["traffic"])
    drv = harness.driver(cfg["driver"])
    devices = jax.devices()[:w["chips"]]
    keys = {k["name"]: k for k in cfg["keys"]}
    lower = {"float32": jnp.bfloat16, "bfloat16": jnp.float8_e4m3fn}
    for k, seed in enumerate(seeds):
        t = time.perf_counter()
        result, checks = bench_run.measure(m, w, cfg, mix, seed, seconds,
                                           trace=False, devices=devices,
                                           t0=time.perf_counter())
        _out({"seed": seed, "who": "program",
              "checks": {n: c["value"] for n, c in checks.items()},
              "metrics": {n: v["value"] for n, v in result["metrics"].items()},
              "attempted": result["attempted"], "failed": result["failed"],
              "s": time.perf_counter() - t})
        if k >= n_controls:
            continue
        if mix["kind"] == "open":
            specs = load.open_schedule(mix, seed, seconds)
            picks = [specs[i][1:] for i in
                     sorted(load.sample(seed, len(specs), mix["sample"]))]
        else:
            pool = load.closed_pool(mix, seed)
            picks = [pool[i % len(pool)] for i in sorted(
                load.sample(seed, mix["sample_range"], mix["sample"]))]
        used = list(dict.fromkeys(mix["keys"]))
        maker = drv._maker()
        bases = {n: drv._base(seed, i, tuple(keys[n]["shape"]),
                              keys[n]["dtype"]) for i, n in enumerate(used)}
        worst, computed = {}, {}
        for name, factor, frac in picks:
            key = keys[name]
            levels = tuple(tuple(lv) for lv in key["levels"])
            y = maker(bases[name], factor)
            norm = drv._norm(bases[name], levels)
            r = drv.round_radius(frac * factor * norm, key["dtype"])
            ref = reference.project(y, levels, r)
            ctl = reference.project_stored(y, levels, r, lower[key["dtype"]])
            n = f"err_{key['dtype']}"
            worst[n] = max(worst.get(n, 0.0), reference.rel_err(ctl, ref))
            low = reference.project(y, levels, r, dtype=lower[key["dtype"]])
            computed[n] = max(computed.get(n, 0.0),
                              reference.rel_err(low, ref))
        _out({"seed": seed, "who": "control_lower_precision", "checks": worst,
              "computed_in_lower": computed})


def knee(cell, rates, seconds, max_pending, past):
    jax = _setup_jax()
    import numpy as np

    import harness

    m = harness.load_manifest()
    w = harness.workload(m, cell)
    cfg = harness.config(m, w["config"])
    mix = harness.traffic(w["traffic"])
    drv = harness.driver(cfg["driver"])
    devices = jax.devices()[:w["chips"]]
    cfg = dict(cfg, engine=dict(cfg["engine"], max_pending=max_pending))
    over = 0
    for rate in sorted(rates):
        mix = dict(mix, rate_per_s=rate)
        rec = drv.run(cfg, mix, 1000 + int(rate), seconds,
                      t0=time.perf_counter(), devices=devices)
        c = rec["counters"]
        lat = [x for x in c["latency_s"] if x is not None]
        if len(lat) < 10:
            _out({"rate": rate, "keeps_up": False, "failed": rec["failed"]})
            over += 1
            if over >= past:
                return
            continue
        fifth = max(1, len(lat) // 5)
        growth = float(np.median(lat[-fifth:]) / np.median(lat[:fifth]))
        done = len(lat) / c["window_s"]
        keeps_up = (rec["failed"] == 0 and growth < 1.5
                    and done >= 0.95 * len(c["latency_s"]) / seconds)
        _out({"rate": rate, "offered": len(c["latency_s"]) / seconds,
              "completed_per_s": done, "keeps_up": keeps_up,
              "window_s": c["window_s"], "failed": rec["failed"],
              "p50_ms": float(np.percentile(lat, 50)) * 1e3,
              "p95_ms": float(np.percentile(lat, 95)) * 1e3,
              "p99_ms": float(np.percentile(lat, 99)) * 1e3,
              "backlog_growth": growth,
              "batch": c["completed"] / max(c["dispatches"], 1),
              "gen_late_p95_ms": float(np.percentile(
                  [x for x in c["gen_late_s"] if x is not None], 95)) * 1e3,
              "checks": {n: v["value"] for n, v in rec["checks"].items()}})
        over += not keeps_up
        if over >= past:
            return


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("train", "serve", "knee"))
    ap.add_argument("cell")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--controls", type=int, default=3)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--max-pending", type=int, default=64)
    ap.add_argument("--past", type=int, default=2)
    a = ap.parse_args()
    seeds = [int(s) for s in a.seeds.split(",") if s]
    if a.mode == "train":
        train(a.cell, seeds, a.controls)
    elif a.mode == "serve":
        serve(a.cell, seeds, a.controls, a.seconds)
    else:
        knee(a.cell, [float(r) for r in a.rates.split(",")], a.seconds,
             a.max_pending, a.past)


if __name__ == "__main__":
    main()

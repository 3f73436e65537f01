"""What every cell shares: the manifest, the files found by name, the
device record and the result line.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric lives in a file of its own, found by the name that
``BENCHMARK.json`` gives it:

* ``bench/configs/<config>.json`` (the manifest's ``file``), whose
  ``driver`` names ``bench/drivers/<driver>.py``;
* ``bench/traffic/<traffic>.json``;
* ``bench/metrics/<metric>.py``, whose ``read(ctx)`` returns the metric or
  None where it finds nothing to read.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parent
ROOT = BENCH.parent
MANIFEST = ROOT / "BENCHMARK.json"


def load_manifest(path=MANIFEST) -> dict:
    return json.loads(pathlib.Path(path).read_text())


def workload(manifest: dict, name: str) -> dict:
    for w in manifest["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(manifest: dict, name: str) -> dict:
    for c in manifest["configs"]:
        if c["name"] == name:
            return json.loads((ROOT / c["file"]).read_text())
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def traffic(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def _module(path: pathlib.Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(name: str):
    return _module(BENCH / "drivers" / f"{name}.py", f"bench_driver_{name}")


def metric_reader(name: str):
    return _module(BENCH / "metrics" / f"{name}.py",
                   "bench_metric_" + name.replace(".", "_"))


def end_to_end(manifest: dict, cell: str) -> list:
    return [m for m in manifest["end_to_end"]
            if cell in m.get("workloads", [cell])]


def per_layer(manifest: dict, cell: str) -> list:
    return [m for m in manifest["per_layer"]
            if cell in m.get("workloads", [cell])]


def device_record(devices) -> dict:
    """The devices as JAX reports them, and the peak memory of the fullest
    one."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def phase_line(name: str, seconds: float) -> None:
    print(f"phase {name}: {seconds:.3f} s", file=sys.stderr, flush=True)


@contextlib.contextmanager
def phase(name: str):
    """Print how long a phase of the run took, on standard error."""
    t = time.perf_counter()
    yield
    phase_line(name, time.perf_counter() - t)


def judge(checks: dict) -> bool:
    """Every number compared is at or under its limit (a missing limit or
    a number that could not be read fails)."""
    return bool(checks) and all(
        isinstance(v["value"], (int, float)) and v["limit"] is not None
        and v["value"] == v["value"] and v["value"] <= v["limit"]
        for v in checks.values())


def emit(result: dict, checks: dict) -> None:
    """Standard error ends with the numbers compared; standard output ends
    with the result line, its ``checks`` key last."""
    for k, v in checks.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr)
    out = dict(result)
    out["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(out), flush=True)

"""Which device a benchmark row ran on, and the forced-CPU mesh children.

Every row names the device it ran on. Rows measured in the parent process
carry the section's device (:func:`device_record`); the mesh sections
(``sharded``, ``sharded_codegen``, the GSP row of ``sae_factory``) run in a
child process on a forced 8-device CPU host mesh, and their rows carry the
record the child reports about itself — never the parent's. A child is
refused once the parent holds a TPU backend: one process owns the chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

_N_DEVICES = 8

_EPILOGUE = """
import json as _json
import jax as _jax
_d = _jax.devices()
print("ROWS" + _json.dumps({"device": {"platform": _d[0].platform,
                                       "device_kind": _d[0].device_kind,
                                       "count": len(_d)},
                            "rows": rows}))
"""


def device_record() -> dict:
    """``platform``, ``device_kind`` and device count of this process."""
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind, "count": len(devs)}


def _parent_holds_tpu() -> bool:
    from jax._src import xla_bridge

    return (xla_bridge.backends_are_initialized()
            and "tpu" in xla_bridge.backends())


def run_cpu_mesh_child(code: str, *argv: str, timeout: float):
    """Run ``code`` (which leaves its result rows in ``rows``) in a child
    process on 8 forced CPU devices; returns its rows as
    ``(name, us, derived, device)`` with the child's own device record."""
    if _parent_holds_tpu():
        raise RuntimeError(
            "this section runs in a CPU-mesh child process, which must not "
            "start once this process holds the TPU; run it in a process of "
            "its own (benchmarks.run --only <section>) before any TPU work")
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={_N_DEVICES}"
    env["JAX_PLATFORMS"] = "cpu"
    env.setdefault("PYTHONPATH", "src")
    prog = code + _EPILOGUE
    res = subprocess.run([sys.executable, "-c", prog, *argv],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    if res.returncode != 0:
        raise RuntimeError(f"benchmark child failed:\n{res.stderr[-3000:]}")
    payload = json.loads(res.stdout.split("ROWS", 1)[1])
    return [(name, us, derived, payload["device"])
            for name, us, derived in payload["rows"]]

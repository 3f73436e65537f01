"""Host-callback bridge: metrics computed *inside* jit flow into the registry.

A projected train step knows things worth observing that only exist on the
device — the feasibility gap after projection, the support size of the
projected weights, the loss — but reading them back with ``float(x)`` forces
a device sync on the hot path. This bridge ships them out through
``jax.debug.callback`` instead: the callback is enqueued behind the step's
real work (``ordered=False``) and the host thread folds the value into the
process-global registry whenever it lands.

The bridge is **gated off by default** and the gate is *trace-time static*:
``report(...)`` inside a function traced while the bridge is disabled
lowers to nothing at all — the jitted program is bit-identical to the
un-instrumented one (``tests/test_obs.py`` pins this at HLO level). Enabling the bridge and
re-tracing (new shapes, or an explicit cache clear) is what turns the
telemetry on; the ``REPRO_OBS_BRIDGE=1`` env var enables it from launch.

    from repro.obs import jax_bridge

    jax_bridge.enable()

    @jax.jit
    def step(w):
        x = project(w)
        jax_bridge.report("feasibility_gap", gap(x), kind="gauge")
        return x
"""

from __future__ import annotations

import contextlib
import os
import time
from typing import Dict, Optional

import jax
import numpy as np

from . import metrics

_ENABLED = os.environ.get("REPRO_OBS_BRIDGE", "") == "1"

_HELP = "bridged from inside jit (obs.jax_bridge)"


def enabled() -> bool:
    """Whether ``report()`` emits callbacks for traces made *now*."""
    return _ENABLED


def enable() -> None:
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


@contextlib.contextmanager
def enabled_scope(on: bool = True):
    """Temporarily flip the gate (tests): traces made inside see ``on``."""
    global _ENABLED
    prev, _ENABLED = _ENABLED, bool(on)
    try:
        yield
    finally:
        _ENABLED = prev


def _record(name: str, kind: str, labels: Optional[Dict[str, str]], value):
    reg = metrics.get_registry()
    v = float(np.asarray(value))
    if kind == "counter":
        fam = reg.counter(name, _HELP, labels=tuple(labels or ()))
    elif kind == "hist":
        fam = reg.histogram(name, _HELP, labels=tuple(labels or ()))
    else:
        fam = reg.gauge(name, _HELP, labels=tuple(labels or ()))
    child = fam.labels(**labels) if labels else fam
    if kind == "counter":
        child.inc(v)
    elif kind == "hist":
        child.observe(v)
    else:
        child.set(v)


def report(name: str, value, *, kind: str = "gauge",
           labels: Optional[Dict[str, str]] = None) -> None:
    """Emit one scalar from traced code into the registry (async, no sync).

    ``kind`` is ``"gauge"`` (set), ``"counter"`` (inc by value), or
    ``"hist"`` (observe). ``labels`` must be static strings (they become
    part of the lowered program). No-op — literally absent from the jitted
    program — when the bridge is disabled at trace time.
    """
    if not _ENABLED:
        return
    if kind not in ("gauge", "counter", "hist"):
        raise ValueError(f"unknown bridge kind {kind!r}")
    labels = dict(labels) if labels else None
    jax.debug.callback(
        lambda v, _name=name, _kind=kind, _labels=labels:
            _record(_name, _kind, _labels, v),
        value)


def mark(name: str, *, labels: Optional[Dict[str, str]] = None) -> None:
    """Drop an *ordered* host-arrival timestamp marker from traced code.

    A ``mark("x_start")`` / ``mark("x_end")`` pair brackets a traced region;
    the host records ``perf_counter()`` when each callback arrives and folds
    the pair's difference into the ``<x>_seconds`` histogram. Because the
    callbacks are ordered they serialize with the surrounding computation —
    on CPU (and in interpret mode) the difference is a faithful stage
    timing; on an accelerator it measures the dispatch stream, which is
    still the ordering the trace viewer shows. Costlier than ``report``
    (ordering forces sequencing): keep it on an ``every``-step cadence.
    No-op when the bridge is disabled at trace time.
    """
    if not _ENABLED:
        return
    if not (name.endswith("_start") or name.endswith("_end")):
        raise ValueError(
            f"mark name must end in _start or _end, got {name!r}")
    labels = dict(labels) if labels else None
    jax.debug.callback(
        lambda _name=name, _labels=labels: _mark_record(_name, _labels),
        ordered=True)


_pending_marks: Dict[str, float] = {}


def _mark_record(name: str, labels: Optional[Dict[str, str]]) -> None:
    now = time.perf_counter()
    stem, _, edge = name.rpartition("_")
    key = stem + "|" + "|".join(
        f"{k}={v}" for k, v in sorted((labels or {}).items()))
    if edge == "start":
        _pending_marks[key] = now
        return
    t0 = _pending_marks.pop(key, None)
    if t0 is None:
        return  # unmatched end (e.g. bridge enabled mid-stream): drop it
    fam = metrics.get_registry().histogram(
        f"{stem}_seconds", _HELP, labels=tuple(labels or ()))
    child = fam.labels(**labels) if labels else fam
    child.observe(now - t0)

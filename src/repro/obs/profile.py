"""Profiler plumbing: trace capture, the schedule-stage named scopes and
the host spans.

Three consumers:

* launchers wrap a region in :func:`capture` — a thin,
  None-tolerant wrapper over ``jax.profiler.trace`` (pass the launcher's
  ``--profile-dir`` straight through; empty/None disables cleanly);
* the schedule executors (``core/schedule.py`` jnp path,
  ``core/sharded.py`` shard_map body, ``kernels/codegen`` lowering
  boundaries) wrap each ReduceLevel/OuterSolve/ApplyGroup stage in
  :func:`stage_scope` — a ``jax.named_scope`` whose name is derived from
  the :class:`~repro.core.schedule.Schedule` step metadata, so a captured
  trace attributes device time to the stages the paper's Θ(n+m)
  complexity argument is actually about;
* the serving engine wraps its host work in :func:`span`.

Named scopes cost nothing at runtime (they are lowered-metadata only).
:func:`span` is the host-side counterpart for work that never enters a
traced computation (the serving engine's submit, dispatch and launch).
While a profiler session records, a span lands in the trace as a
``TraceAnnotation`` on the profiler's own clock, and its wall seconds are
tallied into the ``trace_span_seconds{span=<name>}`` histogram, so the
tallies cover exactly what one capture covers. Outside a capture a span is
a shared no-op: one ``is_enabled()`` call and no registry work.
"""

from __future__ import annotations

import contextlib
import os
import pathlib
import time

import jax

from repro.obs import metrics as obs_metrics

# every projection stage scope shares this prefix — what trace tooling (and
# tests/test_obs.py) greps a captured .xplane.pb for
SCOPE_PREFIX = "proj"

# the per-capture span tallies: one histogram family, one child per span
SPAN_METRIC = "trace_span_seconds"
# 10µs .. 3s: host spans are tens of µs to a few ms; the mean (sum/count)
# is what readers use, the buckets only shape the quantile estimate
SPAN_BUCKETS = (1e-5, 3e-5, 1e-4, 3e-4, 1e-3, 3e-3, 1e-2, 3e-2, 0.1, 0.3,
                1.0, 3.0)

_recording = jax.profiler.TraceAnnotation.is_enabled
_OFF = contextlib.nullcontext()
# span name -> (registry, histogram child): resolved once per name, and
# again only when the process-global registry is swapped
_tallies: dict = {}


def stage_name(step, index: int | None = None) -> str:
    """Scope name for one schedule step (``ReduceLevel``/``OuterSolve``/
    ``ApplyGroup``): ``proj/reduce0_inf``, ``proj/solve_1``,
    ``proj/apply0_inf`` — stable across executors so jnp, shard_map, and
    codegen runs of one design line up in the trace viewer."""
    kind = type(step).__name__
    if kind == "ReduceLevel":
        return f"{SCOPE_PREFIX}/reduce{index}_{step.norm}"
    if kind == "OuterSolve":
        return f"{SCOPE_PREFIX}/solve_{step.norm}"
    if kind == "ApplyGroup":
        return f"{SCOPE_PREFIX}/apply{index}_{step.norm}"
    raise TypeError(f"not a schedule step: {step!r}")


def stage_scope(step, index: int | None = None):
    """``jax.named_scope`` for one schedule step (trace-time metadata only)."""
    return jax.named_scope(stage_name(step, index))


def scope(name: str):
    """A raw ``proj/``-prefixed named scope (codegen lowering boundaries)."""
    return jax.named_scope(f"{SCOPE_PREFIX}/{name}")


def _tally(name: str):
    reg = obs_metrics.get_registry()
    hit = _tallies.get(name)
    if hit is None or hit[0] is not reg:
        fam = reg.histogram(SPAN_METRIC, "host span wall time inside a "
                            "profiler capture", labels=("span",),
                            buckets=SPAN_BUCKETS)
        hit = (reg, fam.labels(span=name))
        _tallies[name] = hit
    return hit[1]


class _Span:
    """One recording span: a ``TraceAnnotation`` plus its tally."""

    __slots__ = ("_annotation", "_child", "_t0")

    def __init__(self, name: str, meta: dict):
        self._annotation = jax.profiler.TraceAnnotation(name, **meta)
        self._child = _tally(name)

    def __enter__(self):
        self._annotation.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        dt = time.perf_counter() - self._t0
        self._annotation.__exit__(*exc)
        self._child.observe(dt)
        return False


def span(name: str, **meta):
    """Host span ``name`` around a block of host work: ``with span(n): ...``.

    While a profiler session records, the block is a
    ``jax.profiler.TraceAnnotation(name, **meta)`` in the trace, and its
    wall seconds are observed into ``trace_span_seconds{span=name}`` (also
    when the block raises). Otherwise it is a shared no-op context and the
    registry is not touched. ``meta`` values land as the event's stats.
    """
    if not _recording():
        return _OFF
    return _Span(name, meta)


@contextlib.contextmanager
def capture(path):
    """Capture a profiler trace of the block into ``path``.

    ``path`` falsy (None/"") disables capture — launchers pass their
    ``--profile-dir`` flag through unconditionally. The directory is
    created; afterwards it holds the ``.xplane.pb`` (plus a Perfetto
    ``.trace.json.gz``) that ``jax.profiler`` tooling / TensorBoard read.
    """
    if not path:
        yield None
        return
    path = os.fspath(path)
    pathlib.Path(path).mkdir(parents=True, exist_ok=True)
    with jax.profiler.trace(path):
        yield path


def trace_files(path):
    """The capture artifacts under ``path`` (recursive; files only)."""
    root = pathlib.Path(path)
    return sorted(p for p in root.rglob("*") if p.is_file())

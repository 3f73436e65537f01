"""Projection planner/autotuner — pick, compile, and cache the fastest
executable for a projection workload (DESIGN.md §2).

``multilevel_project`` is correct for every (shape, ν, backend) combination,
but per-call dispatch re-resolves the method and re-traces on every new
enclosing jit. The planner hoists all of that to *build time*:

    build    — validate the norm design against the shape ONCE
    autotune — ``method="auto"``: micro-benchmark every available backend on
               synthetic data of the exact (shape, dtype) and keep the winner
    cache    — the winner AND the jitted executable are memoised keyed on
               ``(shape, dtype, levels, radius_kind, device)``; a second
               ``make_plan`` (or a second call of the plan) never re-traces
    execute  — ``plan(y, radius)`` runs the reused jitted executable

Backends are (a) every ℓ1 θ-solver in the ``core.ball`` registry, applied
through ``multilevel_project``, and (b) *specialized* executables registered
via ``register_plan_backend`` — the ``codegen`` generated fused kernels
(``repro.kernels.plan_backends`` / ``repro.kernels.codegen``: any unsharded
norm design the tiler accepts), offered on TPU (or under ``interpret=True``
for tests), and the ``sharded`` schedule executor for mesh-committed keys.

Example (fixed backend; ``method="auto"`` benchmarks first):

>>> import jax.numpy as jnp
>>> from repro.core import plan
>>> p = plan.make_plan((4, 8), "float32", [("inf", 1), ("1", 1)],
...                    method="filter")
>>> p.method
'filter'
>>> X = p(jnp.ones((4, 8)), 2.0)
>>> float(jnp.sum(jnp.max(jnp.abs(X), axis=0)))   # inside the l1,inf ball
2.0
>>> plan.make_plan((4, 8), "float32", [("inf", 1), ("1", 1)],
...                method="filter") is p           # plan cache hit
True
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import ball, multilevel, schedule

AUTO = "auto"

_AUTOTUNE_BATCH = 4     # representative batch size for radius_kind="batch"
_AUTOTUNE_REPS = 7      # interleaved timing rounds (min per candidate kept)

_RADIUS_KINDS = ("scalar", "batch")


class ShardingKey(NamedTuple):
    """Canonical, hashable description of a mesh sharding (PlanKey component).

    ``mesh_axes`` is ``((axis_name, size), ...)`` in mesh order; ``devices``
    the flat device-id assignment (two meshes with equal axis signatures but
    different device sets/orders must not alias one plan); ``spec`` maps each
    tensor axis to a mesh axis name (or None). The live Mesh object is kept
    in a side registry keyed on ``(mesh_axes, devices)`` — registered
    whenever a plan is built from a real mesh, looked up when the sharded
    backend builds.
    """

    mesh_axes: Tuple[Tuple[str, int], ...]
    devices: Tuple[int, ...]
    spec: Tuple[Optional[str], ...]


class PlanKey(NamedTuple):
    """The cache key a plan is specialized on."""

    shape: Tuple[int, ...]
    dtype: str
    levels: Tuple[Tuple[str, int], ...]   # canonical ('1'|'2'|'inf', n_axes)
    radius_kind: str                      # 'scalar' | 'batch'
    device: str                           # jax platform ('cpu' | 'tpu' | ...)
    interpret: bool = False               # Pallas interpret mode (tests)
    sharding: Optional[ShardingKey] = None  # None = single-device workload
    grad: bool = False                    # training key: autotuned under vjp


class PlanBackend(NamedTuple):
    """A specialized planner backend (e.g. a fused Pallas kernel).

    ``available(key)`` gates shape/levels/device eligibility; ``build(key)``
    returns the raw ``(y, radius) -> x`` callable (the planner jits it).
    ``batch_native=True`` marks a backend whose built callable already takes
    the stacked ``(ys, radii)`` serving-bucket shape (the batch axis lives in
    its Pallas grid): the planner jits it as-is for ``radius_kind="batch"``
    keys instead of vmap-lifting a per-item callable, and never offers it for
    scalar-radius keys.
    """

    name: str
    available: Callable[[PlanKey], bool]
    build: Callable[[PlanKey], Callable]
    description: str = ""
    batch_native: bool = False


class _Executable(NamedTuple):
    fn: Callable        # jitted (y, radius) -> x
    traces: List[int]   # [trace count] — bumped by the traced body


_SPECIALIZED: Dict[str, PlanBackend] = {}
_EXECS: Dict[Tuple[PlanKey, str], _Executable] = {}
_PLANS: Dict[Tuple[PlanKey, str], "ProjectionPlan"] = {}
_AUTO_WINNERS: Dict[PlanKey, Tuple[str, Dict[str, float]]] = {}
_GENERIC_WINNERS: Dict[PlanKey, Tuple[str, Dict[str, float]]] = {}  # best_l1_method
_MESHES: Dict[Tuple[Tuple[str, int], ...], object] = {}  # ShardingKey.mesh_axes -> Mesh
_KERNEL_BACKENDS_LOADED = False

# cache-lifecycle counters (observability): hits/misses describe the current
# cache generation (reset together with the caches by clear_cache, so sizes
# and counters always refer to the same lifetime); "evictions" is cumulative
# over the process (Prometheus counter semantics — a clear IS an eviction
# event, so it must survive the clear that caused it)
_COUNTER_KEYS = ("plan_hits", "plan_misses", "exec_hits", "exec_misses",
                 "retraces", "autotune_runs", "autotune_hits")
_COUNTERS: Dict[str, int] = dict.fromkeys(_COUNTER_KEYS, 0)
_EVICTIONS = [0]


def _count(event: str, n: int = 1) -> None:
    _COUNTERS[event] += n


def register_plan_backend(backend: PlanBackend) -> None:
    """Register (or replace) a specialized planner backend by name."""
    _SPECIALIZED[backend.name] = backend


def clear_cache() -> None:
    """Drop every cached plan, executable, and autotune verdict
    (tests), and reset the generation counters with them.

    Dropped entries count into the cumulative ``evictions`` counter; the
    hit/miss/retrace/autotune counters restart at zero so ``cache_info()``
    sizes and counters always describe the same cache generation.
    """
    _EVICTIONS[0] += (len(_PLANS) + len(_EXECS) + len(_AUTO_WINNERS)
                      + len(_GENERIC_WINNERS))
    _EXECS.clear()
    _PLANS.clear()
    _AUTO_WINNERS.clear()
    _GENERIC_WINNERS.clear()
    _COUNTERS.update(dict.fromkeys(_COUNTER_KEYS, 0))


def cache_info() -> Dict[str, int]:
    """Sizes AND lifecycle counters of the planner caches.

    Sizes: ``plans`` / ``executables`` / ``auto_winners``. Counters (since
    the last :func:`clear_cache`): ``plan_hits``/``plan_misses`` (the
    ``make_plan`` memo), ``exec_hits``/``exec_misses`` (jitted executables),
    ``retraces`` (executable body re-traces beyond the first — a nonzero
    value means some call pattern defeats the jit cache),
    ``autotune_runs``/``autotune_hits`` (micro-benchmark shoot-outs vs
    cached verdicts). ``evictions`` is cumulative over the process. The
    same numbers are mirrored into the obs registry as
    ``plan_cache_<name>`` gauges on every call.
    """
    info = {"plans": len(_PLANS), "executables": len(_EXECS),
            "auto_winners": len(_AUTO_WINNERS) + len(_GENERIC_WINNERS),
            **_COUNTERS,
            "evictions": _EVICTIONS[0]}
    try:
        from repro.obs import metrics as _obs_metrics

        gauge = _obs_metrics.get_registry().gauge(
            "plan_cache", "planner cache sizes and lifecycle counters "
            "(core.plan.cache_info)", labels=("stat",))
        for name, v in info.items():
            gauge.labels(stat=name).set(v)
    except Exception:  # pragma: no cover - obs must never break the planner
        pass
    return info


# the single home of norm-design canonicalization is the schedule IR;
# re-exported here because every planner consumer keys on it
canonical_levels = schedule.canonical_levels


def canonical_sharding(sharding, ndim: int) -> Optional[ShardingKey]:
    """Fold a sharding description into a hashable :class:`ShardingKey`.

    Accepts ``None``, an already-canonical ``ShardingKey``, a committed
    ``jax.sharding.NamedSharding``, or a ``(mesh, partition_spec)`` pair.
    Returns ``None`` for shardings the mesh executor does not handle (fully
    replicated, single-device, or >1 mesh axis on one tensor axis) — those
    route to the ordinary single-device backends. Registers the live mesh in
    the side registry so the sharded backend can rebuild from the key alone.
    """
    if sharding is None or isinstance(sharding, ShardingKey):
        return sharding
    if isinstance(sharding, jax.sharding.NamedSharding):
        mesh, spec = sharding.mesh, sharding.spec
    else:
        mesh, spec = sharding
    if np.prod(list(mesh.shape.values())) <= 1:
        return None
    from . import sharded as shmod

    names = shmod.parse_spec(spec, ndim, mesh)  # the one spec parser
    if names is None:
        return None  # >1 mesh axis on a tensor axis: executor can't run it
    if not any(names):
        return None  # fully replicated: a plain single-device workload
    mesh_axes = tuple((str(n), int(s)) for n, s in mesh.shape.items())
    devices = tuple(int(d.id) for d in mesh.devices.flat)
    _MESHES[mesh_axes, devices] = mesh
    return ShardingKey(mesh_axes, devices, tuple(names))


def _sharded_available(key: PlanKey) -> bool:
    # scalar-radius only: a batch plan vmaps its executable, and shard_map
    # bodies don't batch — sharded serving groups run per-request instead.
    # Training (grad) keys are excluded too: differentiating through the
    # shard_map body is untested; mesh-native training keeps the hook path.
    return (key.sharding is not None and key.radius_kind == "scalar"
            and not key.grad
            and (key.sharding.mesh_axes, key.sharding.devices) in _MESHES)


def _build_sharded(key: PlanKey):
    from . import sharded as shmod

    mesh = _MESHES[key.sharding.mesh_axes, key.sharding.devices]
    spec = key.sharding.spec
    levels = list(key.levels)

    def fn(y, radius):
        return shmod.multilevel_project_sharded(
            y, levels, radius, mesh=mesh, spec=spec, method="auto")

    return fn


register_plan_backend(PlanBackend(
    name="sharded",
    available=_sharded_available,
    build=_build_sharded,
    description="schedule executor under shard_map: collective reduces, "
                "gathered tiny outer solve, local applies (DESIGN.md §3)",
))


_L1INF_LEVELS = (("inf", 1), ("1", 1))


def _exact_l1inf_available(key: PlanKey) -> bool:
    # The EXACT ℓ1,∞ projection (Chu et al. semismooth Newton) targets the
    # same ball as the bi-level design but is a different operator — offering
    # it under method="auto" deliberately trades bi-level's O(1/n) looseness
    # for measured speed (the equality matrix pins it at loose tolerance).
    # Unsharded 2-D scalar-radius forward keys only: the Newton fori_loop and
    # the per-column sort make its vjp cost pathological for training keys.
    return (key.levels == _L1INF_LEVELS and len(key.shape) == 2
            and key.sharding is None and key.radius_kind == "scalar"
            and not key.grad)


def _build_exact_l1inf(key: PlanKey):
    from .exact_l1inf import project_l1inf_exact

    def fn(y, radius):
        return project_l1inf_exact(y, radius)

    return fn


register_plan_backend(PlanBackend(
    name="exact_l1inf",
    available=_exact_l1inf_available,
    build=_build_exact_l1inf,
    description="EXACT l1,inf projection (Chu et al. semismooth Newton on "
                "the dual): same ball as the bi-level design, exact optimum "
                "— method='auto' trades exactness for speed by measurement",
))


def _maybe_register_kernel_backends() -> None:
    """Lazily pull in the fused-kernel backends (kernels imports core, so core
    cannot import kernels at module load — first make_plan does it instead)."""
    global _KERNEL_BACKENDS_LOADED
    if _KERNEL_BACKENDS_LOADED:
        return
    from repro.kernels import plan_backends  # noqa: F401  (registers on import)
    _KERNEL_BACKENDS_LOADED = True


def _backend_available(backend: PlanBackend, key: PlanKey) -> bool:
    """Availability incl. the batch-native gate (batch-native backends take
    the stacked bucket shape, so they only fit ``radius_kind="batch"`` keys)."""
    if backend.batch_native and key.radius_kind != "batch":
        return False
    return backend.available(key)


def is_batch_native(name: str) -> bool:
    """True when ``name`` is a registered batch-native specialized backend
    (its executables take stacked ``(ys, radii)`` buckets only — a serving
    group routed to it must dispatch through a batch plan even for size 1)."""
    backend = _SPECIALIZED.get(name)
    return backend is not None and backend.batch_native


def _build_backend_fn(key: PlanKey, name: str) -> Callable:
    """Raw (y, radius) -> x callable for one backend on one key."""
    if name in _SPECIALIZED:
        backend = _SPECIALIZED[name]
        if not _backend_available(backend, key):
            raise ValueError(
                f"backend {name!r} is not available for plan key {key}")
        return backend.build(key)
    method = ball.resolve_method(name)
    levels = list(key.levels)

    def fn(y, radius):
        return multilevel.multilevel_project(y, levels, radius, method=method)

    return fn


def _get_executable(key: PlanKey, name: str, donate: bool = False) -> _Executable:
    ek = (key, name, donate)
    if ek in _EXECS:
        _count("exec_hits")
        return _EXECS[ek]
    _count("exec_misses")
    base = _build_backend_fn(key, name)
    traces = [0]

    def counted(y, radius):
        traces[0] += 1  # python side effect: runs at trace time only
        if traces[0] > 1:
            _count("retraces")
        return base(y, radius)

    # a batch-native backend already takes the stacked (ys, radii) bucket —
    # jit it as-is; everything else vmap-lifts the per-item callable
    if key.radius_kind == "batch" and not is_batch_native(name):
        body = jax.vmap(counted, in_axes=(0, 0))
    else:
        body = counted
    # donate=True consumes the payload buffer in place (serving: the request
    # tensor — or the stacked bucket — is dead after projection anyway)
    fn = jax.jit(body, donate_argnums=(0,) if donate else ())
    ex = _Executable(fn, traces)
    _EXECS[ek] = ex
    return ex


def _candidates(key: PlanKey) -> List[str]:
    """Backends worth benchmarking for this key.

    For a sharded key the generic θ-solvers still compete: jitted on the
    committed sharded input they become the GSPMD gather-and-project
    baseline, so autotune decides schedule-vs-gather by measurement."""
    if any(q == "1" for q, _ in key.levels):
        names = list(ball.available_methods())
    else:
        # no l1 level anywhere -> the θ-solver is never invoked; one generic
        # executable is enough
        names = [ball.DEFAULT_METHOD]
    names += [b.name for b in _SPECIALIZED.values()
              if _backend_available(b, key)]
    return names


def _bench_args(key: PlanKey):
    rng = np.random.default_rng(0)
    shape = key.shape if key.radius_kind == "scalar" \
        else (_AUTOTUNE_BATCH,) + key.shape
    y = jnp.asarray(rng.uniform(0.0, 1.0, shape), key.dtype)
    if key.sharding is not None:
        mesh = _MESHES[key.sharding.mesh_axes, key.sharding.devices]
        spec = key.sharding.spec
        if key.radius_kind == "batch":
            spec = (None,) + spec
        y = jax.device_put(y, jax.sharding.NamedSharding(
            mesh, jax.sharding.PartitionSpec(*spec)))
    if key.radius_kind == "scalar":
        radius = jnp.asarray(1.0, key.dtype)
    else:
        radius = jnp.ones((_AUTOTUNE_BATCH,), key.dtype)
    return y, radius


def _grad_fn(key: PlanKey, name: str) -> Callable:
    """value_and_grad of a scalarized loss through one backend — what a
    training step actually executes for a ``grad`` key, so that is what the
    autotuner must time (a backend that wins the forward shoot-out can lose
    it under vjp: residual stashes and backward structure differ)."""
    base = _build_backend_fn(key, name)
    if key.radius_kind == "batch" and not is_batch_native(name):
        base = jax.vmap(base, in_axes=(0, 0))

    def loss(y, radius):
        return jnp.sum(base(y, radius) ** 2)

    return jax.jit(jax.value_and_grad(loss))


def _autotune(key: PlanKey, names: Optional[List[str]] = None
              ) -> Tuple[str, Dict[str, float]]:
    """Interleaved min-of-rounds shoot-out over ``names`` (default: every
    candidate backend of the key).

    Candidates are timed round-robin (not each in its own block) and the
    minimum per candidate is kept: the fastest rep is the least contaminated
    by scheduler noise, interleaving keeps machine drift from favouring
    whichever candidate ran in a calm window, and a wrong verdict is
    permanent for the process.

    ``grad`` keys time forward+backward (``value_and_grad`` of a scalarized
    loss) instead of the plain call — the verdict that matters for a
    projection differentiated through by training.
    """
    y, radius = _bench_args(key)
    if names is None:
        names = _candidates(key)
    if key.grad:
        fns = {name: _grad_fn(key, name) for name in names}
    else:
        fns = {name: _get_executable(key, name).fn for name in names}
    for fn in fns.values():
        for _ in range(2):
            jax.block_until_ready(fn(y, radius))  # compile + warm
    timings: Dict[str, float] = dict.fromkeys(fns, float("inf"))
    for _ in range(_AUTOTUNE_REPS):
        for name, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(y, radius))
            timings[name] = min(timings[name],
                                (time.perf_counter() - t0) * 1e6)
    winner = min(timings, key=timings.get)
    return winner, timings


def _canonical_backend_name(key: PlanKey, method: str) -> str:
    if method in _SPECIALIZED:
        if not _backend_available(_SPECIALIZED[method], key):
            raise ValueError(
                f"backend {method!r} is not available for shape={key.shape} "
                f"levels={key.levels} radius_kind={key.radius_kind!r} on "
                f"device={key.device!r} (interpret={key.interpret})")
        return method
    try:
        return ball.resolve_method(method)
    except ValueError:
        raise ValueError(
            f"unknown projection backend {method!r}; generic: "
            f"{sorted(ball.available_methods())}, specialized: "
            f"{sorted(_SPECIALIZED)} (or 'auto')") from None


@dataclasses.dataclass(frozen=True, eq=False)
class ProjectionPlan:
    """A shape/dtype-specialized, pre-compiled multi-level projection.

    Call it like a function: ``plan(y, radius)``. ``method`` is the backend
    the planner chose (the autotune winner under ``method="auto"``);
    ``timings_us`` holds the per-candidate micro-benchmark when autotuned.
    """

    key: PlanKey
    method: str                              # chosen backend
    requested: str                           # what the caller asked for
    timings_us: Optional[Dict[str, float]]   # autotune results (auto only)
    _exec: _Executable
    donate: bool = False                     # executable consumes the payload

    def __call__(self, y, radius=1.0):
        y = jnp.asarray(y)
        if self.key.radius_kind == "scalar":
            expected = self.key.shape
        else:
            expected = y.shape[:1] + self.key.shape
        if y.shape != expected:
            raise ValueError(
                f"plan built for shape {self.key.shape} "
                f"(radius_kind={self.key.radius_kind!r}) got {y.shape}")
        if y.dtype.name != self.key.dtype:
            raise ValueError(
                f"plan built for dtype {self.key.dtype} got {y.dtype.name}")
        radius = jnp.asarray(radius, y.dtype)
        if self.key.radius_kind == "batch" and radius.ndim == 0:
            radius = jnp.full((y.shape[0],), radius)
        return self._exec.fn(y, radius)

    @property
    def trace_count(self) -> int:
        """How many times the executable's body has been traced (tests)."""
        return self._exec.traces[0]


def make_plan(shape, dtype, levels, radius_kind: str = "scalar",
              method: str = AUTO, *, interpret: bool = False,
              device: str | None = None, sharding=None,
              donate: bool = False, grad: bool = False) -> ProjectionPlan:
    """Build (or fetch from cache) the projection plan for one workload.

    ``shape``/``dtype`` describe one tensor to project (for
    ``radius_kind="batch"`` the plan executes over a leading batch axis and a
    per-item radius vector, vmap'd; the batch axis is dynamic, so each NEW
    batch size traces once — batch callers should pad to bucket sizes, as the
    serving service does). ``levels`` is the norm design ν of
    ``multilevel_project``. ``method`` is a backend name, or ``"auto"`` to
    micro-benchmark every available backend on first call and cache the
    winner. ``interpret=True`` makes the fused Pallas backends eligible off
    TPU (interpret mode — tests only; never use it for performance).

    ``sharding`` (a committed ``NamedSharding`` or a ``(mesh, spec)`` pair)
    makes the plan mesh-aware: the schedule executor joins the candidate set
    as the ``"sharded"`` backend and the generic candidates are timed on the
    committed sharded input (i.e. as GSPMD gather-and-project), so the
    autotune verdict is schedule-vs-gather by measurement.

    ``donate=True`` jits the executable with ``donate_argnums=(0,)``: the
    payload buffer (the tensor, or the stacked bucket for
    ``radius_kind="batch"``) is consumed in place — the serving engine's
    no-copy path. Donating and non-donating plans share the autotune verdict
    but hold separate executables; callers must not reuse a donated input.

    ``grad=True`` marks a TRAINING key: the workload will be differentiated
    through (the projection sits inside a loss), so under ``method="auto"``
    the autotuner times ``value_and_grad`` of each candidate instead of the
    forward call. Forward and grad keys cache separate verdicts — a backend
    with a cheap forward but an expensive (or recomputing) backward loses
    only the grad shoot-out. The plan's executable is the forward either way
    (it is differentiable; the chosen backend's custom VJP is what the
    enclosing ``jax.grad`` picks up).
    """
    _maybe_register_kernel_backends()
    shape = tuple(int(s) for s in shape)
    dtype = np.dtype(dtype)
    lv = canonical_levels(levels)
    multilevel._check_levels(shape, lv)   # validate the norm design ONCE
    if radius_kind not in _RADIUS_KINDS:
        raise ValueError(
            f"radius_kind must be one of {_RADIUS_KINDS}, got {radius_kind!r}")
    if device is None:
        device = jax.devices()[0].platform
    key = PlanKey(shape, dtype.name, lv, radius_kind, device, bool(interpret),
                  canonical_sharding(sharding, len(shape)), bool(grad))
    cache_key = (key, method, donate)
    if cache_key in _PLANS:
        _count("plan_hits")
        return _PLANS[cache_key]
    _count("plan_misses")
    timings: Optional[Dict[str, float]] = None
    if method == AUTO:
        if key in _AUTO_WINNERS:
            _count("autotune_hits")
            chosen, timings = _AUTO_WINNERS[key]
        else:
            _count("autotune_runs")
            chosen, timings = _autotune(key)
            _AUTO_WINNERS[key] = (chosen, timings)
    else:
        chosen = _canonical_backend_name(key, method)
    plan = ProjectionPlan(key=key, method=chosen, requested=method,
                          timings_us=timings,
                          _exec=_get_executable(key, chosen, donate),
                          donate=donate)
    _PLANS[cache_key] = plan
    return plan


def validate_backend(shape, dtype, levels, method: str, *,
                     device: str | None = None, interpret: bool = False,
                     sharding=None, radius_kind: str = "scalar",
                     grad: bool = False) -> str:
    """Canonicalize + validate a backend name for a workload, without
    building (or autotuning) a plan.

    Returns the canonical name (aliases fold, ``"auto"`` passes through);
    raises ``ValueError`` for an unknown backend or a specialized backend
    that is not available for this (shape, levels, device, radius_kind).
    Cheap enough for a request-admission path — the serving tier calls it
    per submit (with ``radius_kind="batch"`` for unsharded traffic, since
    groups execute as stacked buckets).
    """
    _maybe_register_kernel_backends()
    if method == AUTO:
        return AUTO
    if device is None:
        device = jax.devices()[0].platform
    key = PlanKey(tuple(int(s) for s in shape), np.dtype(dtype).name,
                  canonical_levels(levels), radius_kind, device,
                  bool(interpret), canonical_sharding(sharding, len(shape)),
                  bool(grad))
    return _canonical_backend_name(key, method)


def best_l1_method(n: int, dtype=jnp.float32, *, device: str | None = None,
                   grad: bool = False) -> str:
    """Autotuned θ-solver name for flat length-``n`` ℓ1 projections.

    Build-time helper for call sites that need a *generic* backend name (the
    sharded projection, the training hook): only ``core.ball`` registry
    methods compete, so the winner is always embeddable under an enclosing
    jit/vmap/shard_map. ``grad=True`` makes it a training key — the shoot-out
    times each θ-solver under ``value_and_grad`` (solvers differ much more in
    backward cost than forward: sort-based ones backprop through the sort).
    """
    _maybe_register_kernel_backends()
    if device is None:
        device = jax.devices()[0].platform
    key = PlanKey((int(n),), np.dtype(dtype).name, canonical_levels([("1", 1)]),
                  "scalar", device, False, None, bool(grad))
    if key in _GENERIC_WINNERS:
        _count("autotune_hits")
    else:
        # the specialized backends (the codegen kernels on TPU) are not
        # θ-solvers a caller can embed: keep them out of this shoot-out
        _count("autotune_runs")
        _GENERIC_WINNERS[key] = _autotune(key, list(ball.available_methods()))
    return _GENERIC_WINNERS[key][0]


def maybe_plan_call(y, levels, radius):
    """Eager ``method="auto"`` dispatch for the core entry points.

    Returns the projected array when ``y`` is concrete (plan built/cached and
    executed), or ``None`` when ``y`` is a tracer — the caller then falls back
    to :func:`best_l1_method` on the (always static) shape. A committed
    mesh-sharded array routes to a mesh-aware plan (the sharded schedule
    executor competes against GSPMD gather-and-project in its autotune).
    """
    if isinstance(y, jax.core.Tracer):
        return None
    sharding = getattr(y, "sharding", None)
    if not isinstance(sharding, jax.sharding.NamedSharding):
        sharding = None
    plan = make_plan(jnp.shape(y), jnp.asarray(y).dtype, levels, method=AUTO,
                     sharding=sharding)
    return plan(y, radius)

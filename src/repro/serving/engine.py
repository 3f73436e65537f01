"""Continuous-batching projection engine: async submit/poll with latency SLOs.

The engine is the production serving tier over the projection planner
(DESIGN.md §5). It serves by **continuous batching**: a background
dispatcher pops *every* request pending for one plan key the moment that
key's plan is ready, so a request joins the next in-flight dispatch for its
key instead of waiting for a bucket to fill or a caller to flush.

Four mechanisms make the latency profile (DESIGN.md §5 derives the model):

* **continuous batching** — one dispatch serves everything that arrived for
  a key since its last dispatch (popped group capped at ``max_batch``,
  padded to the next power of two so varying traffic re-traces the batch
  executable only O(log max_batch) times);
* **buffer donation** — the engine takes ownership of every submitted
  payload: each dispatch is one fused jitted call (stack → project →
  unstack) that donates the request buffers at its boundary, so projections
  run in place and the stacked bucket never exists outside the executable;
* **plan-cache warm pool** — plans build on a thread pool, and the
  dispatcher skips keys whose plan is still building: a cold shape never
  stalls the hot path. ``prewarm()`` schedules builds ahead of traffic;
* **admission control** — the queue is bounded (``max_pending``); overload
  is shed at ``submit()`` with a typed :class:`QueueFullError`, and
  per-request deadlines double as dispatch hints (the dispatcher serves the
  earliest-deadline key first; requests past their deadline complete with
  :class:`DeadlineExceededError` instead of burning compute).

Mesh-sharded submissions keep their own plan key and execute per request
through the sharded schedule executor — they are never gather-stacked with
single-device traffic of the same shape (DESIGN.md §5).

Typical use (see docs/serving.md for a runnable tour)::

    with ProjectionEngine() as eng:
        t1 = eng.submit(w1, [("inf", 1), ("1", 1)], radius=1.0)
        t2 = eng.submit(w2, [("inf", 1), ("1", 1)], radius=2.0)  # joins t1's dispatch
        x1 = eng.result(t1, timeout=5.0)
        x2 = eng.result(t2, timeout=5.0)

Failure semantics: a dispatch that raises re-queues its group (at the front,
order preserved) and retries up to ``max_attempts`` times; after that every
ticket in the group completes exceptionally. ``result()`` re-raises the
stored error; an unknown, already-claimed, or discarded ticket raises
:class:`UnknownTicketError`.
"""

from __future__ import annotations

import contextlib
import threading
import time
from concurrent.futures import Future, ThreadPoolExecutor
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import multilevel
from repro.core import plan as planmod
from repro.obs import metrics as obs_metrics
from repro.obs.metrics import timed
from repro.obs.profile import span

# THE clock for everything time-shaped in this module — deadlines, queue
# ages, latency accounting. A single *monotonic* source: wall-clock
# (time.time) jumps — NTP steps, suspend/resume — must never expire a
# deadline or corrupt a latency histogram (regression-pinned in
# tests/test_serving.py). Tests monkeypatch this one name to fake time.
_now = time.monotonic

# (shape, dtype name, canonical levels, canonical method, sharding key):
# requests share a dispatch iff they share a planner executable
GroupKey = Tuple[Tuple[int, ...], str, Tuple[Tuple[str, int], ...], str,
                 object]

# batch-size distribution buckets: the pow-2 dispatch buckets themselves
_BATCH_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256)


def _key_label(key: GroupKey) -> str:
    """Compact per-plan-key metric label: ``6x10/float32/inf1-11/sort``."""
    shape, dtype, levels, method, shard = key
    lv = "-".join(f"{q}{k}" for q, k in levels)
    base = f"{'x'.join(map(str, shape))}/{dtype}/{lv}/{method}"
    return base + "/sharded" if shard is not None else base


class ServingError(RuntimeError):
    """Base class for engine failures surfaced through tickets."""


class QueueFullError(ServingError):
    """Admission control: the bounded queue is full — shed load upstream."""


class DeadlineExceededError(ServingError):
    """The request's deadline passed before its dispatch executed."""


class UnknownTicketError(ServingError, KeyError):
    """The ticket is not pending here: foreign, already claimed, or
    discarded."""


def _bucket(n: int) -> int:
    """Next power of two ≥ n (bucketed padding: O(log max_batch) traces)."""
    return 1 << (n - 1).bit_length()


class Ticket:
    """Handle for one submitted projection. Opaque: hand it back to
    :meth:`ProjectionEngine.poll` / :meth:`ProjectionEngine.result`."""

    __slots__ = ("id", "key", "_engine", "_event", "_state", "_value",
                 "_error")

    def __init__(self, tid: int, key: GroupKey, engine: "ProjectionEngine"):
        self.id = tid
        self.key = key
        self._engine = engine
        self._event = threading.Event()
        self._state = "pending"          # -> done | failed -> claimed
        self._value: Optional[jax.Array] = None
        self._error: Optional[BaseException] = None

    def __repr__(self):  # pragma: no cover - debug aid
        return f"Ticket(id={self.id}, state={self._state})"


class _Request:
    __slots__ = ("ticket", "y", "radius", "deadline", "attempts", "enqueued")

    def __init__(self, ticket: Ticket, y, radius, deadline: Optional[float]):
        self.ticket = ticket
        self.y = y
        self.radius = radius
        self.deadline = deadline          # absolute _now() time, or None
        self.attempts = 0
        self.enqueued = _now()


class EngineStats(dict):
    """The engine's operational counters — a plain dict (back-compat:
    ``eng.stats["dispatches"]``) that is ALSO callable: ``eng.stats()``
    returns the full structured snapshot (counters, queue state, per-key
    latency summaries, planner cache info). See
    :meth:`ProjectionEngine.stats_snapshot`."""

    def __init__(self, engine: "ProjectionEngine", *args, **kw):
        super().__init__(*args, **kw)
        self._engine = engine

    def __call__(self) -> dict:
        return self._engine.stats_snapshot()


class _EngineMetrics:
    """The engine's registry handles, built once per engine.

    All series live in the process-global obs registry (labelled by plan
    key where it matters), so one scrape sees every engine in the process.
    ``instrument=False`` engines skip this object entirely — the bare hot
    path performs zero registry operations.
    """

    def __init__(self):
        reg = obs_metrics.get_registry()
        self.queue_depth = reg.gauge(
            "serving_queue_depth", "queued (undispatched) requests")
        self.inflight = reg.gauge(
            "serving_inflight_requests", "popped but not yet completed")
        self.events = reg.counter(
            "serving_events_total", "engine lifecycle events",
            labels=("event",))
        self.queue_s = reg.histogram(
            "serving_queue_seconds", "submit -> dispatch-pop wait",
            labels=("key",))
        # ends when the results are handed to the tickets: the device may
        # still be computing them (the launch is asynchronous)
        self.e2e_s = reg.histogram(
            "serving_e2e_seconds", "submit -> results handed to the tickets",
            labels=("key",))
        self.batch_size = reg.histogram(
            "serving_batch_size", "requests per dispatch",
            buckets=_BATCH_BUCKETS)
        self.plan_build_s = reg.histogram(
            "serving_plan_build_seconds", "plan build on the warm pool")
        self.warm_s = reg.histogram(
            "serving_warm_seconds", "warm-bucket pre-trace on the warm pool")
        # hot-path handle caches: resolving a labelled child costs a label
        # check + tuple build + lock per call — done ONCE per key/event
        # here, so the per-request cost is a dict hit (GIL-atomic)
        self._by_key: Dict[GroupKey, tuple] = {}
        self.ev = {name: self.events.labels(event=name)
                   for name in ("submitted", "rejected", "expired",
                                "requeue", "failure", "dispatch",
                                "completed", "failed", "discarded")}

    def for_key(self, key: GroupKey) -> tuple:
        """(queue_s, e2e_s) histogram children for one key."""
        h = self._by_key.get(key)
        if h is None:
            lbl = _key_label(key)
            h = (self.queue_s.labels(key=lbl), self.e2e_s.labels(key=lbl))
            self._by_key[key] = h
        return h


class ProjectionEngine:
    """Async continuous-batching projection server over the planner.

    Parameters
    ----------
    method:       default backend request for every submit (``"auto"``
                  autotunes per workload); per-submit ``method=`` overrides.
    max_batch:    cap on one dispatch's group size (the pow-2 padding bucket
                  never exceeds it).
    max_pending:  admission-control bound on queued (undispatched) requests;
                  ``submit()`` past it raises :class:`QueueFullError`.
    donate:       donate payload buffers to the executable (in-place
                  projection). The engine takes ownership of submitted
                  buffers: a singleton dispatch *consumes* the caller's
                  array (donation invariant, DESIGN.md §5).
    max_attempts: dispatch attempts per request before its group's failure
                  is surfaced through the tickets.
    warm_workers: threads in the plan warm pool.
    warm_buckets: pow-2 bucket sizes per key to pre-trace on the warm pool
                  (e.g. 3 traces buckets 1, 2, 4). Tracing a bucket size at
                  build time moves its one-time trace/compile cost off the
                  first dispatch that reaches it — under open-loop traffic
                  one mid-replay compile delays the whole backlog. 0 (the
                  default) builds plans only.
    interpret:    run Pallas-backed plans in interpreter mode (tests/CPU).
    instrument:   record queue/latency/batch/deadline metrics into the
                  process-global obs registry (``repro.obs``). ``False`` is
                  the bare hot path — zero registry operations per request
                  (the counter dict ``stats`` is always maintained either
                  way; only histograms/gauges/labelled series are gated).
                  Independently of it, the host spans ``serving/submit``,
                  ``serving/dispatch`` and ``serving/launch``
                  (:func:`repro.obs.profile.span`) record only while a
                  profiler capture is on.
    start:        launch the background dispatcher thread. With
                  ``start=False`` the engine is synchronous: nothing runs
                  until :meth:`drain` dispatches inline (deterministic mode
                  for tests).
    """

    def __init__(self, *, method: str = planmod.AUTO, max_batch: int = 64,
                 max_pending: int = 1024, donate: bool = True,
                 max_attempts: int = 2, warm_workers: int = 2,
                 warm_buckets: int = 0, interpret: bool = False,
                 instrument: bool = True, start: bool = True):
        if max_batch < 1 or max_pending < 1 or max_attempts < 1:
            raise ValueError(
                "max_batch, max_pending, max_attempts must be >= 1")
        self.warm_buckets = int(warm_buckets)
        self.default_method = method
        self.max_batch = int(max_batch)
        self.max_pending = int(max_pending)
        self.donate = bool(donate)
        self.max_attempts = int(max_attempts)
        self.interpret = bool(interpret)
        self._cv = threading.Condition()
        self._queues: Dict[GroupKey, List[_Request]] = {}
        self._plans: Dict[GroupKey, Future] = {}
        self._fused: Dict[Tuple[GroupKey, int], object] = {}
        self._pending_count = 0
        self._inflight = 0
        self._inflight_reqs = 0
        self._next_ticket = 0
        self._stopping = False
        self.stats = EngineStats(
            self, {"submitted": 0, "dispatches": 0, "batched_requests": 0,
                   "rejected": 0, "expired": 0, "requeues": 0,
                   "failures": 0, "max_group": 0, "completed": 0,
                   "failed": 0, "discarded": 0})
        self._metrics = _EngineMetrics() if instrument else None
        self._warm = ThreadPoolExecutor(max_workers=int(warm_workers),
                                        thread_name_prefix="plan-warm")
        self._thread: Optional[threading.Thread] = None
        if start:
            self._thread = threading.Thread(target=self._loop,
                                            name="projection-dispatch",
                                            daemon=True)
            self._thread.start()

    # ------------------------------------------------------------- submit

    def submit(self, y, levels, radius=1.0, *, method: Optional[str] = None,
               deadline: Optional[float] = None) -> Ticket:
        """Queue one projection; returns a :class:`Ticket`.

        ``deadline`` is seconds from now: a request still queued past it
        completes with :class:`DeadlineExceededError` instead of executing,
        and pending deadlines prioritise which key dispatches next.

        ``radius`` is rounded to the payload's dtype here, on the host, and
        stays there: the radii of a dispatched group cross to the device
        together, as one vector, when the group's program is called (a
        mesh-sharded request runs alone, its radius in its own call). A
        radius given as a ``jax.Array`` is fetched to the host (a blocking
        read; its value is unchanged).

        Raises :class:`QueueFullError` when ``max_pending`` requests are
        already queued, and ``ValueError`` for an invalid design/backend or
        a radius that is not a scalar — bad requests are rejected here,
        where the caller can handle it.
        """
        with span("serving/submit"):
            with self._cv:
                if self._stopping:
                    raise ServingError("engine is stopped")
            y = jnp.asarray(y)
            levels = planmod.canonical_levels(levels)
            multilevel._check_levels(y.shape, levels)
            # committed mesh-sharded tensors get their own plan key: they
            # run through the sharded schedule executor per request, never
            # gather-stacked with single-device traffic of the same shape
            sharding = getattr(y, "sharding", None)
            if not isinstance(sharding, jax.sharding.NamedSharding):
                sharding = None
            shard_key = planmod.canonical_sharding(sharding, y.ndim)
            requested = self.default_method if method is None else method
            requested = planmod.validate_backend(
                y.shape, y.dtype, levels, requested, sharding=shard_key,
                interpret=self.interpret,
                radius_kind="scalar" if shard_key is not None else "batch")
            radius = np.asarray(radius, dtype=y.dtype)
            if radius.ndim != 0:
                raise ValueError(
                    f"radius must be a scalar (one per request), got shape "
                    f"{radius.shape}")
            key: GroupKey = (y.shape, y.dtype.name, levels, requested,
                             shard_key)
            abs_deadline = (None if deadline is None
                            else _now() + float(deadline))
            m = self._metrics
            with self._cv:
                if self._stopping:
                    raise ServingError("engine is stopped")
                if self._pending_count >= self.max_pending:
                    self.stats["rejected"] += 1
                    if m:
                        m.ev["rejected"].inc()
                    raise QueueFullError(
                        f"{self._pending_count} requests queued "
                        f"(max_pending={self.max_pending})")
                ticket = Ticket(self._next_ticket, key, self)
                self._next_ticket += 1
                self._queues.setdefault(key, []).append(
                    _Request(ticket, y, radius, abs_deadline))
                self._pending_count += 1
                self.stats["submitted"] += 1
                if m:
                    m.ev["submitted"].inc()
                    m.queue_depth.set(self._pending_count)
                self._ensure_plan_locked(key)
                self._cv.notify_all()
            return ticket

    def prewarm(self, shape, dtype, levels, *, method: Optional[str] = None,
                sharding=None) -> None:
        """Schedule the plan build for a workload ahead of traffic, on the
        warm pool. Returns immediately; the first submit for this key then
        dispatches without a cold-build stall."""
        shape = tuple(int(s) for s in shape)
        levels = planmod.canonical_levels(levels)
        multilevel._check_levels(shape, levels)
        shard_key = planmod.canonical_sharding(sharding, len(shape))
        requested = self.default_method if method is None else method
        requested = planmod.validate_backend(
            shape, dtype, levels, requested, sharding=shard_key,
            interpret=self.interpret,
            radius_kind="scalar" if shard_key is not None else "batch")
        key: GroupKey = (shape, jnp.dtype(dtype).name, levels, requested,
                         shard_key)
        with self._cv:
            self._ensure_plan_locked(key)

    def wait_warm(self, timeout: Optional[float] = None) -> None:
        """Block until every scheduled plan build (and its warm-bucket
        traces) has finished. Re-raises the first build failure."""
        with self._cv:
            futs = list(self._plans.values())
        for fut in futs:
            fut.result(timeout)

    # --------------------------------------------------------- plan cache

    def _ensure_plan_locked(self, key: GroupKey) -> None:
        if key not in self._plans:
            fut = self._warm.submit(self._build_plans, key)
            fut.add_done_callback(self._on_plan_ready)
            self._plans[key] = fut

    def _on_plan_ready(self, _fut: Future) -> None:
        with self._cv:
            self._cv.notify_all()

    def _build_plans(self, key: GroupKey) -> Dict[str, planmod.ProjectionPlan]:
        """Build every plan flavour one key dispatches through (runs on the
        warm pool, so a cold key never stalls the dispatcher)."""
        if self._metrics:
            with timed(self._metrics.plan_build_s):
                return self._build_plans_inner(key)
        return self._build_plans_inner(key)

    def _build_plans_inner(self, key: GroupKey
                           ) -> Dict[str, planmod.ProjectionPlan]:
        shape, dtype, levels, method, shard_key = key
        if shard_key is not None:
            # sharded: per-request scalar plan, no donation (the sharded
            # executor manages its own per-shard buffers)
            return {"scalar": planmod.make_plan(shape, dtype, levels,
                                                method=method,
                                                sharding=shard_key)}
        # the batch plan itself is NOT donated: the fused dispatch wrapper
        # (see _fused_dispatch) donates the per-request payloads at its own
        # boundary and the stacked bucket is internal to the jit
        plans = {"batch": planmod.make_plan(
            shape, dtype, levels, radius_kind="batch", method=method,
            interpret=self.interpret)}
        if not planmod.is_batch_native(method):
            # singleton fast path: donate the caller's own buffer (true
            # in-place projection, zero copies). Batch-native backends take
            # stacked buckets only, so they route size-1 groups through the
            # batch plan instead.
            plans["scalar"] = planmod.make_plan(
                shape, dtype, levels, method=method,
                interpret=self.interpret, donate=self.donate)
        self._warm_dispatch_paths(key, plans)
        return plans

    def _warm_dispatch_paths(self, key: GroupKey, plans) -> None:
        """Trace the first ``warm_buckets`` pow-2 dispatch paths (stack +
        executable + unstack) with dummy payloads, still on the warm pool.
        A failure here fails the key's plan build: :meth:`wait_warm`
        re-raises it, and the key's requests complete with
        :class:`ServingError` (a later submit retries the build)."""
        shape, dtype_name, _levels, _method, shard_key = key
        if shard_key is not None or self.warm_buckets <= 0:
            return
        dtype = jnp.dtype(dtype_name)
        # built as submit() builds a request (host radius), so each warmed
        # bucket is the very executable that live groups call
        dummy = lambda: _Request(None, jnp.zeros(shape, dtype),
                                 np.asarray(0.5, dtype), None)
        ctx = timed(self._metrics.warm_s) if self._metrics \
            else contextlib.nullcontext()
        with ctx:
            if "scalar" in plans:
                r = dummy()
                jax.block_until_ready(plans["scalar"](r.y, r.radius))
            b, done = 1, 0
            while b <= self.max_batch and done < self.warm_buckets:
                jax.block_until_ready(self._run_group(
                    key, plans, [dummy() for _ in range(b)]))
                b, done = b * 2, done + 1

    # --------------------------------------------------------- dispatcher

    def _loop(self) -> None:
        while True:
            with self._cv:
                if self._stopping and self._pending_count == 0:
                    break
            self._dispatch_once()

    def _dispatch_once(self, wait_s: float = 0.02) -> bool:
        """Pop and execute one group; returns whether anything ran."""
        m = self._metrics
        with self._cv:
            key = self._select_key_locked()
            if key is None:
                self._cv.wait(wait_s)
                return False
            reqs = self._queues.pop(key)
            take, rest = reqs[:self.max_batch], reqs[self.max_batch:]
            if rest:
                self._queues[key] = rest
            self._pending_count -= len(take)
            self._inflight += 1
            self._inflight_reqs += len(take)
            if m:
                m.queue_depth.set(self._pending_count)
                m.inflight.set(self._inflight_reqs)
        try:
            # from the pop to the last ticket's completion; the idle wait
            # above stays outside
            with span("serving/dispatch"):
                if m:
                    popped, (queue_h, _) = _now(), m.for_key(key)
                    for r in take:
                        queue_h.observe(popped - r.enqueued)
                self._execute(key, take)
        finally:
            with self._cv:
                self._inflight -= 1
                self._inflight_reqs -= len(take)
                if m:
                    m.inflight.set(self._inflight_reqs)
                self._cv.notify_all()
        return True

    def _select_key_locked(self) -> Optional[GroupKey]:
        """Earliest-deadline dispatchable key (deadline hints), FIFO on the
        longest-waiting head request among deadline-free keys — a hot key
        cannot starve the others. Keys whose plan is still building are
        skipped — cold never stalls hot."""
        best, best_pri = None, (float("inf"), float("inf"))
        for key, q in self._queues.items():
            if not q:
                continue
            fut = self._plans.get(key)
            if fut is None:
                self._ensure_plan_locked(key)
                continue
            if not fut.done():
                continue
            dl = min((r.deadline for r in q if r.deadline is not None),
                     default=float("inf"))
            pri = (dl, q[0].enqueued)
            if best is None or pri < best_pri:
                best, best_pri = key, pri
        return best

    def _execute(self, key: GroupKey, reqs: List[_Request]) -> None:
        m = self._metrics
        e2e_h = m.for_key(key)[1] if m else None
        try:
            plans = self._plans[key].result()
        except Exception as exc:
            with self._cv:
                # drop the failed build so a later submit retries it
                self._plans.pop(key, None)
            err = ServingError(f"plan build failed for {key[:4]}: {exc!r}")
            err.__cause__ = exc
            for r in reqs:
                self._fail(r.ticket, err)
            return
        now = _now()
        live = []
        for r in reqs:
            if r.ticket._state != "pending":      # discarded before dispatch
                continue
            if r.deadline is not None and now > r.deadline:
                self.stats["expired"] += 1
                if m:
                    m.ev["expired"].inc()
                self._fail(r.ticket, DeadlineExceededError(
                    f"ticket {r.ticket.id} expired "
                    f"{now - r.deadline:.3f}s before dispatch"))
                continue
            live.append(r)
        if not live:
            return
        try:
            # host time to launch the group (asynchronous: the device may
            # still be running it when this returns). Here and not in
            # _run_group, so the warm pool's warm-up groups do not count
            with span("serving/launch"):
                outs = self._run_group(key, plans, live)
        except Exception as exc:
            for r in live:
                r.attempts += 1
            retry = [r for r in live if r.attempts < self.max_attempts]
            spent = [r for r in live if r.attempts >= self.max_attempts]
            for r in spent:
                self.stats["failures"] += 1
                if m:
                    m.ev["failure"].inc()
                err = ServingError(
                    f"dispatch failed after {r.attempts} attempt(s): {exc!r}")
                err.__cause__ = exc
                self._fail(r.ticket, err)
            if retry:
                self.stats["requeues"] += 1
                if m:
                    m.ev["requeue"].inc()
                with self._cv:
                    # re-queue at the front, order preserved
                    self._queues.setdefault(key, [])[0:0] = retry
                    self._pending_count += len(retry)
                    self._cv.notify_all()
            return
        self.stats["dispatches"] += 1
        self.stats["max_group"] = max(self.stats["max_group"], len(live))
        if len(live) > 1:
            self.stats["batched_requests"] += len(live)
        if m:
            m.ev["dispatch"].inc()
            m.batch_size.observe(len(live))
        done = _now()
        for r, out in zip(live, outs):
            self._complete(r.ticket, out)
            if m:
                e2e_h.observe(done - r.enqueued)

    def _fused_dispatch(self, key: GroupKey, plans, b: int):
        """One jitted executable per (key, bucket): stack → project →
        unstack fused into a single dispatch, each request's payload
        donated individually, the group's radii one ``(b,)`` vector.
        Without the fusion every dispatch pays O(bucket) op-by-op
        stack/slice calls — which is exactly the per-request overhead
        continuous batching exists to amortize."""
        fn = self._fused.get((key, b))
        if fn is None:
            batch_plan = plans["batch"]

            def dispatch(*args):               # b payloads, then the radii
                out = batch_plan(jnp.stack(args[:b]), args[b])
                return tuple(out[i] for i in range(b))

            donate = tuple(range(b)) if self.donate else ()
            fn = jax.jit(dispatch, donate_argnums=donate)
            self._fused[(key, b)] = fn
        return fn

    def _run_group(self, key: GroupKey, plans, live) -> List[jax.Array]:
        """The raw compute for one popped group (the retry boundary)."""
        shape, dtype_name, _levels, _method, shard_key = key
        if shard_key is not None:
            p = plans["scalar"]
            return [p(r.y, r.radius) for r in live]
        if len(live) == 1 and "scalar" in plans:
            r = live[0]
            return [plans["scalar"](r.y, r.radius)]
        b = min(_bucket(len(live)), self.max_batch)
        pad = b - len(live)
        dtype = jnp.dtype(dtype_name)
        # pad slots get fresh zero buffers — donation forbids handing the
        # executable the same buffer twice
        ys = [r.y for r in live] + [jnp.zeros(shape, dtype)
                                    for _ in range(pad)]
        # the host radii as one vector, pads 0: the call makes the group's
        # one radius transfer
        radii = np.array([r.radius for r in live] + [0] * pad, dtype)
        out = self._fused_dispatch(key, plans, b)(*ys, radii)
        return list(out[: len(live)])

    # --------------------------------------------------------- completion

    def _complete(self, ticket: Ticket, value) -> None:
        with self._cv:
            if ticket._state != "pending":        # discarded mid-dispatch
                return
            ticket._state = "done"
            ticket._value = value
            self.stats["completed"] += 1
        if self._metrics:
            self._metrics.ev["completed"].inc()
        ticket._event.set()

    def _fail(self, ticket: Ticket, error: BaseException) -> None:
        with self._cv:
            if ticket._state != "pending":
                return
            ticket._state = "failed"
            ticket._error = error
            self.stats["failed"] += 1
        if self._metrics:
            self._metrics.ev["failed"].inc()
        ticket._event.set()

    # ------------------------------------------------------------ results

    def poll(self, ticket: Ticket) -> bool:
        """True once the ticket completed (result ready or failed)."""
        self._check_ticket(ticket)
        return ticket._event.is_set()

    def result(self, ticket: Ticket, timeout: Optional[float] = None):
        """Projected tensor for a completed ticket — single read (the value
        is released on return). Blocks up to ``timeout`` seconds
        (``TimeoutError`` past it); re-raises the dispatch error for a
        failed ticket; :class:`UnknownTicketError` for a foreign, claimed,
        or discarded ticket."""
        self._check_ticket(ticket)
        if self._thread is None and not ticket._event.is_set():
            self.drain()                   # synchronous mode: dispatch inline
        if not ticket._event.wait(timeout):
            raise TimeoutError(
                f"ticket {ticket.id} incomplete after {timeout}s")
        with self._cv:
            state = ticket._state
            if state == "done":
                ticket._state = "claimed"
                value, ticket._value = ticket._value, None
                return value
            if state == "failed":
                ticket._state = "claimed"
                error, ticket._error = ticket._error, None
            else:
                error = UnknownTicketError(
                    f"ticket {ticket.id} already {state}")
        raise error

    def discard(self, ticket: Ticket) -> None:
        """Drop a ticket that will never be claimed (no-op if already
        claimed). A discarded pending request is skipped at dispatch; a
        discarded completed result is released immediately."""
        self._check_ticket(ticket)
        with self._cv:
            if ticket._state == "claimed":
                return
            if ticket._state == "pending":
                # terminal accounting: a request discarded before its
                # dispatch is neither completed nor failed. If it is still
                # queued it leaves the queue NOW (so queued+discarded never
                # double-count it and its slot frees immediately); a request
                # already popped into a dispatch is skipped at completion.
                q = self._queues.get(ticket.key)
                if q is not None:
                    for i, r in enumerate(q):
                        if r.ticket is ticket:
                            del q[i]
                            if not q:
                                del self._queues[ticket.key]
                            self._pending_count -= 1
                            break
                self.stats["discarded"] += 1
                if self._metrics:
                    self._metrics.ev["discarded"].inc()
                    self._metrics.queue_depth.set(self._pending_count)
            ticket._state = "discarded"
            ticket._value = None
            ticket._error = None
        ticket._event.set()

    def _check_ticket(self, ticket) -> None:
        if not isinstance(ticket, Ticket) or ticket._engine is not self:
            raise UnknownTicketError(
                f"not a ticket of this engine: {ticket!r}")

    # ---------------------------------------------------------- lifecycle

    def pending(self) -> int:
        """Queued (undispatched) requests."""
        with self._cv:
            return self._pending_count

    # ------------------------------------------------------- observability

    def stats_snapshot(self) -> dict:
        """Structured operational snapshot (what ``eng.stats()`` returns).

        Counters plus live queue state plus — on instrumented engines —
        per-plan-key latency summaries (p50/p99 seconds, bucket-estimated)
        and the planner's cache counters. Accounting invariant (pinned in
        tests/test_serving.py)::

            completed + failed + discarded + queued + inflight == submitted
        """
        with self._cv:
            snap: dict = dict(self.stats)
            snap["queued"] = self._pending_count
            snap["inflight"] = self._inflight_reqs
        m = self._metrics
        if m is not None:
            lat = {}
            for fam, field in ((m.queue_s, "queue"), (m.e2e_s, "e2e")):
                for child in fam.children():
                    key = child.labelvalues[0]
                    d = lat.setdefault(key, {})
                    d[f"{field}_count"] = child.count
                    d[f"{field}_p50_s"] = child.quantile(0.5)
                    d[f"{field}_p99_s"] = child.quantile(0.99)
            snap["latency"] = lat
            snap["batch_p50"] = m.batch_size.quantile(0.5)
        snap["plan_cache"] = planmod.cache_info()
        return snap

    def drain(self, timeout: Optional[float] = None) -> None:
        """Block until every submitted request has completed. With
        ``start=False`` this IS the dispatcher: groups execute inline, on
        this thread, until the queue is empty."""
        deadline = None if timeout is None else _now() + timeout
        if self._thread is None:
            while True:
                with self._cv:
                    if not self._pending_count and not self._inflight:
                        return
                if deadline is not None and _now() > deadline:
                    raise TimeoutError("drain timed out")
                self._dispatch_once(wait_s=0.005)
        with self._cv:
            while self._pending_count or self._inflight:
                left = None if deadline is None else deadline - _now()
                if left is not None and left <= 0:
                    raise TimeoutError("drain timed out")
                self._cv.wait(left if left is not None else 0.1)

    def stop(self, drain: bool = True) -> None:
        """Shut the engine down. ``drain=True`` (default) finishes queued
        work first; ``drain=False`` fails still-queued tickets with
        :class:`ServingError`. Idempotent; ``submit()`` raises afterwards."""
        with self._cv:
            self._stopping = True
            if not drain:
                for q in self._queues.values():
                    for r in q:
                        self._fail(r.ticket, ServingError("engine stopped"))
                self._queues.clear()
                self._pending_count = 0
            self._cv.notify_all()
        if self._thread is not None:
            if drain:
                self.drain()
            self._thread.join(timeout=10.0)
            self._thread = None
        elif drain:
            self.drain()
        self._warm.shutdown(wait=True)

    def __enter__(self) -> "ProjectionEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.stop(drain=exc[0] is None)

    # -------------------------------------------------------- convenience

    def project(self, y, levels, radius=1.0, *,
                method: Optional[str] = None):
        """submit + result in one call (single-request convenience)."""
        return self.result(self.submit(y, levels, radius, method=method))

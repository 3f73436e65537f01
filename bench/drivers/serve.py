"""Driver of the serving cells: ``repro.serving.ProjectionEngine``.

The engine is built with the configuration's settings and driven only
through ``prewarm``/``wait_warm``, ``submit`` and ``result``. Set-up makes
each key's base tensor on the device from the seed, builds the plans of the
keys the mix uses and warms every dispatch bucket the mix can reach (the
engine's ``warm_buckets``, from :func:`reachable_buckets`), and the payload
maker. A request's payload is its key's
base scaled by the request's factor, made when the request is due, so its
making counts in its latency; its radius is the request's fraction of the
payload's own multi-level norm.

* ``open`` mixes: a generator thread submits each request at its due time
  (refused requests count as failed); a client thread claims results in
  submission order. Latency runs from the due time to the claim, over every
  request due in the window, each waited for up to a minute past its close.
* ``closed`` mixes: one client keeps ``inflight`` requests outstanding and
  submits the next as each is claimed; the rate counts the payload bytes
  claimed inside the window.

A sample of requests, drawn from the seed, keeps its answers. Once the
window has closed and the engine is stopped, the float32 reference projects
each sampled payload again and ``checks`` holds the worst relative error
per payload precision beside its limit, and ``compiles_in_window`` where a
program was lowered inside the window.
"""

from __future__ import annotations

import collections
import contextlib
import queue
import sys
import threading
import time

import numpy as np

import counts
import load
import reference

CLAIM_GRACE_S = 60.0


def reachable_buckets(traffic: dict, max_batch: int) -> int:
    """How many power-of-two dispatch buckets (1, 2, 4, ...) the mix can
    reach: up to the bucket of the most requests of one key that can be
    outstanding at once (a closed loop's ``inflight``; an open loop's
    bursts are unbounded), capped at ``max_batch``. A bucket left cold
    would compile inside the window the first time a group reaches it."""
    reach = max_batch
    if traffic["kind"] == "closed":
        reach = min(reach, traffic["inflight"])
    return (max(1, reach) - 1).bit_length() + 1


def round_radius(r: float, dtype) -> float:
    """``r`` as the engine holds it, in the payload's dtype, rounded on the
    host."""
    import jax.numpy as jnp

    return float(np.asarray(r, dtype=jnp.dtype(dtype)))


class _Lowerings:
    """Counts the programs JAX lowers while it is open."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.events = []            # appended from any thread

    @property
    def n(self) -> int:
        return len(self.events)

    def _seen(self, event, _secs, **_kw):
        if event == self.EVENT:
            self.events.append(event)

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._seen)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._seen)


def _maker():
    import jax
    import jax.numpy as jnp

    def bench_payload(base, factor):
        return (base.astype(jnp.float32) * factor).astype(base.dtype)

    return jax.jit(bench_payload)


def _base(seed, index, shape, dtype):
    import jax
    import jax.numpy as jnp

    def bench_base(key):
        return jax.random.normal(jax.random.fold_in(key, index), shape,
                                 jnp.float32).astype(dtype)

    return jax.jit(bench_base)(reference.seed_key(seed))


def _norm(y, levels) -> float:
    import jax
    import jax.numpy as jnp

    return float(jax.jit(lambda a: reference.multilevel_norm(
        a.astype(jnp.float32), levels))(y))


def _queue_totals():
    """(sum, count) of the engine's submit → dispatch-pop waits."""
    from repro.obs import metrics as obs_metrics

    fam = obs_metrics.get_registry().histogram("serving_queue_seconds",
                                               labels=("key",))
    kids = list(fam.children())
    return sum(c.sum for c in kids), sum(c.count for c in kids)


class Client:
    """The load generator and client of one run."""

    def __init__(self, engine, keys, bases, norms, maker, clock):
        self.eng, self.keys, self.bases = engine, keys, bases
        self.norms, self.maker, self.clock = norms, maker, clock
        self.span = lambda _name: contextlib.nullcontext()
        self.errors = []            # what stopped requests being submitted

    def radius(self, key, factor, frac) -> float:
        return round_radius(frac * factor * self.norms[key],
                            self.keys[key]["dtype"])

    def submit(self, key, factor, frac):
        """(ticket, or None if the request was refused or could not be
        made, and the submit time)."""
        from repro.serving import QueueFullError

        t = self.clock()
        try:
            with self.span("bench/make"):
                y = self.maker(self.bases[key], factor)
            with self.span("bench/submit"):
                t = self.clock()
                ticket = self.eng.submit(y, self.keys[key]["levels"],
                                         self.radius(key, factor, frac))
        except Exception as e:          # noqa: BLE001 - counted as failed
            if not isinstance(e, QueueFullError):
                self.errors.append(repr(e))
            ticket = None
        return ticket, t

    def open_loop(self, schedule, sample, start):
        """Every request at its due time; returns per-request records."""
        n = len(schedule)
        late, lat, failed = [None] * n, [None] * n, []
        kept = {}
        handoff = queue.Queue()
        deadline = [None]

        def generate():
            for i, (due, key, factor, frac) in enumerate(schedule):
                target = start + due
                wait = target - self.clock()
                if wait > 0:
                    time.sleep(wait)
                ticket, t = self.submit(key, factor, frac)
                late[i] = t - target
                handoff.put((i, ticket, target))
            deadline[0] = self.clock() + CLAIM_GRACE_S
            handoff.put(None)

        def claim():
            while True:
                item = handoff.get()
                if item is None:
                    return
                i, ticket, target = item
                if ticket is None:
                    failed.append(i)
                    continue
                try:
                    with self.span("bench/claim"):
                        left = (deadline[0] - self.clock() if deadline[0]
                                else CLAIM_GRACE_S + schedule[-1][0])
                        out = self.eng.result(ticket, timeout=max(left, 0.0))
                except Exception:       # noqa: BLE001 - counted as failed
                    failed.append(i)
                    continue
                lat[i] = self.clock() - target
                if i in sample:
                    kept[i] = out
                del out

        threads = [threading.Thread(target=generate, name="bench-generator"),
                   threading.Thread(target=claim, name="bench-client")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return {"late_s": late, "latency_s": lat, "failed": failed,
                "kept": kept}

    def closed_loop(self, pool, inflight, seconds, sample, start):
        """``inflight`` requests outstanding until the window closes."""
        outstanding = collections.deque()
        kept, failed = {}, []
        done, done_bytes, i = 0, 0, 0
        spec_of = {}

        def one():
            nonlocal i
            spec = pool[i % len(pool)]
            ticket, _ = self.submit(*spec)
            outstanding.append((i, ticket))
            spec_of[i] = spec
            i += 1

        for _ in range(inflight):
            one()
        end = start + seconds
        closed = False
        while outstanding:
            j, ticket = outstanding.popleft()
            try:
                with self.span("bench/claim"):
                    out = self.eng.result(ticket, timeout=CLAIM_GRACE_S)
            except Exception:           # noqa: BLE001 - counted as failed
                failed.append(j)
                continue
            now = self.clock()
            if now <= end:
                done += 1
                k = self.keys[spec_of[j][0]]
                done_bytes += counts.payload_bytes(k["shape"], k["dtype"])
            else:
                closed = True
            if j in sample:
                kept[j] = out
            del out
            if not closed:
                one()
        return {"completed": done, "completed_bytes": done_bytes,
                "submitted": i, "failed": failed, "kept": kept,
                "specs": {j: spec_of[j] for j in kept}}


def _checks(client, kept_specs, kept, config) -> dict:
    """Worst relative error of the sampled answers, per payload dtype."""
    import jax

    project = jax.jit(reference.project, static_argnums=(1,))
    worst = {}
    for j, out in kept.items():
        key, factor, frac = kept_specs[j]
        k = client.keys[key]
        y = client.maker(client.bases[key], factor)
        ref = project(y, tuple(tuple(lv) for lv in k["levels"]),
                      client.radius(key, factor, frac))
        name = f"err_{k['dtype']}"
        worst[name] = max(worst.get(name, 0.0), reference.rel_err(out, ref))
    limits = config.get("limits", {})
    return {k: {"value": v, "limit": limits.get(k)}
            for k, v in sorted(worst.items())}


def _pct(xs, q) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(xs, float), q))


def run(config: dict, traffic: dict, seed: int, seconds: float, *, t0: float,
        trace_dir=None, devices=None, interpret: bool = False,
        clock=time.perf_counter) -> dict:
    import jax
    import jax.numpy as jnp
    from repro.serving import ProjectionEngine

    import harness

    devices = devices or jax.devices()[:config["chips"]]
    keys = {k["name"]: k for k in config["keys"]}
    used = list(dict.fromkeys(traffic["keys"]))
    e = config["engine"]
    eng = ProjectionEngine(method=e["method"], max_batch=e["max_batch"],
                           max_pending=e["max_pending"],
                           warm_buckets=reachable_buckets(
                               traffic, e["max_batch"]),
                           interpret=interpret)
    try:
        for name in used:
            k = keys[name]
            eng.prewarm(k["shape"], k["dtype"], k["levels"])
        t_warm = clock()
        maker = _maker()
        bases, norms = {}, {}
        for idx, name in enumerate(used):
            k = keys[name]
            bases[name] = _base(seed, idx, tuple(k["shape"]), k["dtype"])
            norms[name] = _norm(bases[name], tuple(tuple(lv)
                                                    for lv in k["levels"]))
            jax.block_until_ready(maker(bases[name], 1.0))
        harness.phase_line("bases and payload maker", clock() - t_warm)
        eng.wait_warm()
        for name in used:
            # the engine pads a group to its bucket with fresh zeros, and
            # its warm-up groups are full: make those zeros once here
            k = keys[name]
            jax.block_until_ready((jnp.zeros(k["shape"], k["dtype"]),
                                   jnp.zeros((), k["dtype"])))
        harness.phase_line("plans and dispatch buckets", clock() - t_warm)
        client = Client(eng, keys, bases, norms, maker, clock)
        for name in used:                 # one request per key end to end
            ticket, _ = client.submit(name, 1.0, 0.1)
            eng.result(ticket, timeout=CLAIM_GRACE_S)
        setup_s = clock() - t0
        length = traffic["trace_seconds"] if trace_dir else seconds
        stats0, queue0 = eng.stats(), _queue_totals()
        ctx = contextlib.nullcontext()
        if trace_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            ctx = jax.profiler.trace(trace_dir, profiler_options=opts)
            client.span = jax.profiler.TraceAnnotation
        with ctx, client.span("bench/window"), _Lowerings() as lowered:
            start = clock()
            if traffic["kind"] == "open":
                sched = load.open_schedule(traffic, seed, length)
                sample = load.sample(seed, len(sched), traffic["sample"])
                res = client.open_loop(sched, sample, start)
                specs = {i: sched[i][1:] for i in res["kept"]}
            else:
                pool = load.closed_pool(traffic, seed)
                sample = load.sample(seed, traffic["sample_range"],
                                     traffic["sample"])
                res = client.closed_loop(pool, traffic["inflight"], length,
                                         sample, start)
                specs = res["specs"]
            window_s = clock() - start
        stats1, queue1 = eng.stats(), _queue_totals()
        device = harness.device_record(devices)
    finally:
        eng.stop(drain=True)
    if client.errors:
        print(f"client: {len(client.errors)} requests could not be made or "
              f"submitted; the first: {client.errors[0]}", file=sys.stderr)
    kept = res.pop("kept")
    with harness.phase("reference"):
        checks = _checks(client, specs, kept, config)
    if not kept:
        checks["answers_compared"] = {"value": 0, "limit": None}
    if lowered.n:
        checks["compiles_in_window"] = {"value": lowered.n, "limit": 0}
    del kept
    counters = {
        "completed": stats1["completed"] - stats0["completed"],
        "dispatches": stats1["dispatches"] - stats0["dispatches"],
        "queue_wait_s": queue1[0] - queue0[0],
        "queue_waits": queue1[1] - queue0[1],
        "window_s": window_s, "length_s": length,
    }
    e2e = {"setup_s": setup_s}
    if traffic["kind"] == "open":
        lat = [x for x in res["latency_s"] if x is not None]
        e2e["serve_p50_ms"] = _pct(lat, 50) * 1e3 if lat else float("nan")
        counters["gen_late_s"] = res["late_s"]
        counters["latency_s"] = res["latency_s"]
        attempted = len(res["latency_s"])
        counters["payload_bytes"] = sum(
            counts.payload_bytes(keys[k]["shape"], keys[k]["dtype"])
            for (_, k, _, _), x in zip(sched, res["latency_s"])
            if x is not None)
    else:
        e2e["serve_gb_per_s"] = res["completed_bytes"] / length / 1e9
        attempted = res["submitted"]
        counters["payload_bytes"] = res["completed_bytes"]
    return {"e2e": e2e, "attempted": attempted, "failed": len(res["failed"]),
            "checks": checks, "device": device, "counters": counters}

"""Observability layer (repro.obs): metrics core semantics, exactness under
thread concurrency, exporter round-trips, the host-callback bridge's
trace-time-static gate, and profiler capture with the schedule-stage named
scopes actually present in the trace bytes."""

import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.obs import jax_bridge, metrics
from repro.obs import profile as obs_profile


@pytest.fixture()
def reg():
    """A fresh registry installed as the process-global one (the bridge and
    the planner mirror always write to the global)."""
    fresh = metrics.Registry()
    prev = metrics.set_registry(fresh)
    yield fresh
    metrics.set_registry(prev)


# ---------------------------------------------------------------- core model


class TestMetricsCore:
    def test_counter_inc_and_value(self, reg):
        c = reg.counter("c_total", "help")
        c.inc()
        c.inc(4)
        assert c.value == 5

    def test_counter_rejects_negative(self, reg):
        c = reg.counter("c_total")
        with pytest.raises(ValueError, match=">= 0"):
            c.inc(-1)

    def test_gauge_set_add(self, reg):
        g = reg.gauge("g")
        g.set(2.5)
        g.add(-1.0)
        assert g.value == 1.5

    def test_labeled_children_are_cached(self, reg):
        c = reg.counter("req_total", labels=("route",))
        assert c.labels(route="a") is c.labels(route="a")
        assert c.labels(route="a") is not c.labels(route="b")

    def test_label_names_enforced(self, reg):
        c = reg.counter("req_total", labels=("route",))
        with pytest.raises(ValueError, match="expected labels"):
            c.labels(wrong="a")
        # a labeled family is not its own child
        with pytest.raises(ValueError, match="use .labels"):
            c.inc()

    def test_reregistration_same_signature_is_same_family(self, reg):
        a = reg.counter("x_total", "first", labels=("k",))
        b = reg.counter("x_total", "again", labels=("k",))
        assert a is b

    def test_reregistration_kind_mismatch_raises(self, reg):
        reg.counter("x_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.gauge("x_total")
        with pytest.raises(ValueError, match="already registered"):
            reg.counter("x_total", labels=("k",))

    def test_histogram_counts_sum_and_overflow(self, reg):
        h = reg.histogram("h_seconds", buckets=(0.1, 1.0))
        for v in (0.05, 0.5, 0.5, 5.0):
            h.observe(v)
        assert h.count == 4
        assert h.sum == pytest.approx(6.05)
        snap = reg.snapshot()["h_seconds"]["values"][0]
        assert snap["counts"] == [1, 2, 1]   # per-bucket + the +Inf overflow

    def test_quantile_empty_and_interpolation(self, reg):
        h = reg.histogram("h_seconds", buckets=(1.0, 2.0, 4.0))
        assert h.quantile(0.5) == 0.0        # empty histogram
        for v in (0.5, 1.5, 3.0, 3.5):
            h.observe(v)
        # p50: rank 2 lands at the end of the (1,2] bucket
        assert 1.0 <= h.quantile(0.5) <= 2.0
        # values past the last bucket clamp to the last finite bound
        h.observe(100.0)
        assert h.quantile(1.0) == 4.0

    def test_timed_observes_on_exception(self, reg):
        h = reg.histogram("op_seconds", labels=("op",))
        with pytest.raises(RuntimeError):
            with metrics.timed(h, op="boom"):
                raise RuntimeError("boom")
        assert h.labels(op="boom").count == 1

    def test_clear_drops_families(self, reg):
        reg.counter("c_total").inc()
        reg.clear()
        assert reg.snapshot() == {}


# ------------------------------------------------------------- concurrency


class TestConcurrency:
    def test_eight_threads_exact(self, reg):
        """8 threads hammer one counter family and one histogram; counters
        are exact and the histogram conserves its total (the registry's
        single-lock design pins this)."""
        n_threads, n_iter = 8, 2000
        c = reg.counter("hits_total", labels=("t",))
        h = reg.histogram("lat_seconds", buckets=(1e-3, 1e-2, 1e-1))
        barrier = threading.Barrier(n_threads)

        def worker(tid):
            child = c.labels(t=str(tid % 4))     # contended label children
            barrier.wait()
            for i in range(n_iter):
                child.inc()
                h.observe((i % 7) * 1e-3)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(n_threads)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        total = sum(ch.value for ch in c.children())
        assert total == n_threads * n_iter
        assert h.count == n_threads * n_iter
        snap = reg.snapshot()["lat_seconds"]["values"][0]
        assert sum(snap["counts"]) == snap["count"] == n_threads * n_iter
        expected_sum = n_threads * sum((i % 7) * 1e-3 for i in range(n_iter))
        assert snap["sum"] == pytest.approx(expected_sum, rel=1e-9)


# ---------------------------------------------------------------- exporters


class TestExporters:
    def _populate(self, reg):
        reg.counter("req_total", "requests", labels=("route",)) \
            .labels(route="submit").inc(3)
        reg.gauge("depth").set(7)
        h = reg.histogram("lat_seconds", buckets=(0.1, 1.0))
        h.observe(0.05)
        h.observe(0.5)

    def test_jsonl_round_trip(self, reg, tmp_path):
        self._populate(reg)
        path = tmp_path / "metrics.jsonl"
        reg.write_jsonl(path)
        rows = [json.loads(line) for line in
                path.read_text().strip().splitlines()]
        by_name = {r["name"]: r for r in rows}
        assert by_name["req_total"]["value"] == 3
        assert by_name["req_total"]["labels"] == {"route": "submit"}
        assert by_name["depth"]["value"] == 7
        hist = by_name["lat_seconds"]
        assert hist["kind"] == "histogram"
        assert hist["count"] == 2 and sum(hist["counts"]) == 2
        assert hist["buckets"] == [0.1, 1.0]

    def test_prometheus_format(self, reg):
        self._populate(reg)
        text = reg.to_prometheus()
        assert "# TYPE req_total counter" in text
        assert '# HELP req_total requests' in text
        assert 'req_total{route="submit"} 3' in text
        assert "depth 7.0" in text
        # cumulative bucket counts, +Inf last, then sum/count
        assert 'lat_seconds_bucket{le="0.1"} 1' in text
        assert 'lat_seconds_bucket{le="1.0"} 2' in text
        assert 'lat_seconds_bucket{le="+Inf"} 2' in text
        assert "lat_seconds_count 2" in text

    def test_prometheus_label_escaping(self, reg):
        reg.counter("c_total", labels=("k",)).labels(k='a"b\\c\nd').inc()
        text = reg.to_prometheus()
        assert r'c_total{k="a\"b\\c\nd"} 1' in text

    def test_empty_registry_exports(self, reg):
        assert reg.to_jsonl() == ""
        assert reg.to_prometheus() == ""


# ------------------------------------------------------------------- bridge


class TestBridge:
    def test_gate_scope_restores(self):
        before = jax_bridge.enabled()
        with jax_bridge.enabled_scope(True):
            assert jax_bridge.enabled()
            with jax_bridge.enabled_scope(False):
                assert not jax_bridge.enabled()
            assert jax_bridge.enabled()
        assert jax_bridge.enabled() == before

    def test_disabled_gate_is_trace_time_static(self, reg):
        """With the bridge off at trace time the lowered program is
        bit-identical to one with no report() at all — the overhead-off
        claim in benchmarks/obs_overhead.py, pinned at HLO level."""

        def plain(x):
            return x * 2.0

        def instrumented(x):
            y = x * 2.0
            jax_bridge.report("bridge_gauge", jnp.sum(y))
            return y

        # same jit name so the lowered modules differ only in body
        instrumented.__name__ = plain.__name__
        x = jnp.arange(4.0)
        with jax_bridge.enabled_scope(False):
            a = jax.jit(plain).lower(x).as_text()
            b = jax.jit(instrumented).lower(x).as_text()
        assert a == b
        assert "bridge_gauge" not in reg.snapshot()

    def test_report_kinds_land_in_registry(self, reg):
        with jax_bridge.enabled_scope(True):
            @jax.jit
            def step(x):
                jax_bridge.report("b_gauge", jnp.max(x))
                jax_bridge.report("b_count", jnp.asarray(2.0),
                                  kind="counter")
                jax_bridge.report("b_hist", jnp.min(x), kind="hist",
                                  labels={"leaf": "w"})
                return x + 1

            jax.block_until_ready(step(jnp.arange(3.0)))
            jax.block_until_ready(step(jnp.arange(3.0)))
        jax.effects_barrier()
        assert reg.gauge("b_gauge").value == 2.0
        assert reg.counter("b_count").value == 4.0        # inc'd per call
        h = reg.histogram("b_hist", labels=("leaf",)).labels(leaf="w")
        assert h.count == 2 and h.sum == 0.0

    def test_report_bad_kind(self):
        with jax_bridge.enabled_scope(True):
            with pytest.raises(ValueError, match="unknown bridge kind"):
                jax_bridge.report("x", 1.0, kind="summary")

    def test_mark_pairs_into_histogram(self, reg):
        with jax_bridge.enabled_scope(True):
            @jax.jit
            def step(x):
                jax_bridge.mark("span_start")
                y = x @ x
                jax_bridge.mark("span_end")
                return y

            for _ in range(3):
                jax.block_until_ready(step(jnp.eye(8)))
        jax.effects_barrier()
        h = reg.histogram("span_seconds")
        assert h.count == 3
        assert h.sum >= 0.0

    def test_mark_name_validated(self):
        with jax_bridge.enabled_scope(True):
            with pytest.raises(ValueError, match="_start or _end"):
                jax_bridge.mark("span")

    def test_unmatched_end_dropped(self, reg):
        jax_bridge._mark_record("orphan_end", None)
        assert "orphan_seconds" not in reg.snapshot()


# ------------------------------------------------------------------ profile


class TestProfile:
    def test_stage_names(self):
        from repro.core import schedule as S

        sched = S.compile_schedule((4, 6), [("inf", 1), ("1", 1)])
        names = [obs_profile.stage_name(step, i)
                 for i, step in enumerate(sched.steps)]
        assert all(n.startswith("proj/") for n in names)
        assert any(n.startswith("proj/reduce") for n in names)
        assert any(n.startswith("proj/solve_") for n in names)
        assert any(n.startswith("proj/apply") for n in names)

    def test_stage_name_rejects_non_steps(self):
        with pytest.raises(TypeError, match="not a schedule step"):
            obs_profile.stage_name(object(), 0)

    def test_capture_disabled_is_noop(self, tmp_path):
        with obs_profile.capture("") as p:
            assert p is None
        with obs_profile.capture(None) as p:
            assert p is None

    def test_capture_trace_contains_stage_scopes(self, tmp_path):
        """End-to-end: run a jitted multilevel projection under capture();
        the .xplane.pb must contain the proj/* stage-scope names (named
        scopes survive into the lowered metadata and the trace bytes)."""
        from repro.core import multilevel

        x = jnp.asarray(np.random.default_rng(0).normal(size=(6, 10)),
                        jnp.float32)
        levels = [("inf", 1), ("1", 1)]
        fn = jax.jit(lambda v: multilevel.multilevel_project(
            v, levels, radius=1.0))
        jax.block_until_ready(fn(x))             # compile outside the trace
        trace_dir = tmp_path / "trace"
        with obs_profile.capture(trace_dir):
            jax.block_until_ready(fn(x))
        files = obs_profile.trace_files(trace_dir)
        assert files, "capture produced no artifacts"
        xplanes = [f for f in files if f.name.endswith(".xplane.pb")]
        assert xplanes, f"no .xplane.pb among {[f.name for f in files]}"
        blob = b"".join(f.read_bytes() for f in xplanes)
        assert b"proj/" in blob, "stage scopes missing from captured trace"


class TestSpans:
    """``profile.span``: a TraceAnnotation plus a per-name tally while a
    profiler session records, a shared no-op otherwise."""

    def test_no_capture_records_nothing(self, reg):
        assert not jax.profiler.TraceAnnotation.is_enabled()
        with obs_profile.span("test/outside", step=1) as sp:
            with obs_profile.span("test/inner"):
                pass
        assert sp is None
        assert obs_profile.span("a") is obs_profile.span("b")   # shared
        assert obs_profile.SPAN_METRIC not in reg.snapshot()

    def test_nested_spans_on_two_threads_tally_their_own(self, reg,
                                                         tmp_path):
        def work(prefix, n):
            for _ in range(n):
                with obs_profile.span(f"{prefix}/outer"):
                    with obs_profile.span(f"{prefix}/inner"):
                        pass

        with jax.profiler.trace(str(tmp_path)):
            threads = [threading.Thread(target=work, args=(p, n))
                       for p, n in (("t0", 5), ("t1", 3))]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        fam = reg.histogram(obs_profile.SPAN_METRIC, labels=("span",))
        counts = {c.labelvalues[0]: c.count for c in fam.children()}
        assert counts == {"t0/outer": 5, "t0/inner": 5,
                          "t1/outer": 3, "t1/inner": 3}
        outer = fam.labels(span="t0/outer")
        assert outer.sum >= fam.labels(span="t0/inner").sum > 0.0

    def test_span_tally_follows_a_swapped_registry(self, reg, tmp_path):
        with jax.profiler.trace(str(tmp_path)):
            with obs_profile.span("test/swap"):
                pass
            other = metrics.Registry()
            prev = metrics.set_registry(other)
            try:
                with obs_profile.span("test/swap"):
                    pass
            finally:
                metrics.set_registry(prev)
        for r in (reg, other):
            fam = r.histogram(obs_profile.SPAN_METRIC, labels=("span",))
            assert fam.labels(span="test/swap").count == 1

    def test_span_names_in_the_host_plane(self, reg, tmp_path):
        from jax.profiler import ProfileData

        with jax.profiler.trace(str(tmp_path)):
            with obs_profile.span("test/host_plane", size=7):
                pass
        xplane, = [f for f in obs_profile.trace_files(tmp_path)
                   if f.name.endswith(".xplane.pb")]
        pd = ProfileData.from_file(str(xplane))
        names = {ev.name for plane in pd.planes
                 if plane.name.startswith("/host:")
                 for line in plane.lines for ev in line.events}
        assert "test/host_plane" in names

    def test_span_records_when_the_block_raises(self, reg, tmp_path):
        with jax.profiler.trace(str(tmp_path)):
            with pytest.raises(RuntimeError):
                with obs_profile.span("test/raises"):
                    raise RuntimeError("boom")
        fam = reg.histogram(obs_profile.SPAN_METRIC, labels=("span",))
        assert fam.labels(span="test/raises").count == 1


# ----------------------------------------------------- global registry wiring


def test_global_registry_swap_restores(reg):
    assert metrics.get_registry() is reg
    reg.counter("only_here_total").inc()
    assert "only_here_total" in metrics.get_registry().snapshot()

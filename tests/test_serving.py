"""Serving tier: LM generation and the continuous-batching
ProjectionEngine (typed failures, donation, batching)."""

import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import models
from repro.configs import registry
from repro.models import params as PM
from repro.serving import lm


def _setup(name, seed=0):
    cfg = registry.smoke_config(name)
    api = models.get(cfg)
    params = PM.init_params(api.template(cfg), jax.random.PRNGKey(seed))
    return cfg, api, params


class TestGenerate:
    def test_greedy_deterministic(self):
        cfg, api, params = _setup("granite-3-2b")
        prompt = jnp.asarray(
            np.random.default_rng(0).integers(0, cfg.vocab, (2, 8)), jnp.int32)
        a = lm.generate(params, cfg, prompt, max_new=6)
        b = lm.generate(params, cfg, prompt, max_new=6)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert a.shape == (2, 6)

    def test_batch_independence(self):
        # each request decodes as if alone in the batch
        cfg, api, params = _setup("granite-3-2b")
        rng = np.random.default_rng(1)
        p1 = jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)), jnp.int32)
        p2 = jnp.asarray(rng.integers(0, cfg.vocab, (1, 8)), jnp.int32)
        both = jnp.concatenate([p1, p2], axis=0)
        o_both = lm.generate(params, cfg, both, max_new=5)
        o_1 = lm.generate(params, cfg, p1, max_new=5)
        np.testing.assert_array_equal(np.asarray(o_both[0]), np.asarray(o_1[0]))

    def test_swa_ring_cache_generation(self):
        # windowed arch with prompt longer than the ring: must not crash and
        # must agree with teacher-forced forward on the final logits
        cfg, api, params = _setup("h2o-danube-1.8b")
        assert cfg.window == 16
        prompt = jnp.asarray(
            np.random.default_rng(2).integers(0, cfg.vocab, (1, 24)), jnp.int32)
        cache = api.make_cache(cfg, 1, max_len=40, dtype=jnp.float32)
        step = lm.make_decode_step(cfg, api)
        logits = None
        for i in range(prompt.shape[1]):
            _, logits, cache = step(params, prompt[:, i], cache, jnp.int32(i))
        full, _ = api.forward(params, prompt, cfg, impl="naive", remat=False)
        np.testing.assert_allclose(np.asarray(logits), np.asarray(full[:, -1]),
                                   rtol=5e-3, atol=5e-3)

    def test_recurrent_arch_generation(self):
        cfg, api, params = _setup("xlstm-1.3b")
        prompt = jnp.asarray(
            np.random.default_rng(3).integers(0, cfg.vocab, (2, 6)), jnp.int32)
        out = lm.generate(params, cfg, prompt, max_new=4)
        assert out.shape == (2, 4)
        assert bool(jnp.all(out >= 0)) and bool(jnp.all(out < cfg.vocab))

    def test_prefill_last_logits_match_decode(self):
        cfg, api, params = _setup("granite-3-2b")
        prompt = jnp.asarray(
            np.random.default_rng(4).integers(0, cfg.vocab, (2, 10)), jnp.int32)
        pre = lm.make_prefill(cfg, api, impl="naive")
        last = pre(params, prompt)
        cache = api.make_cache(cfg, 2, max_len=16, dtype=jnp.float32)
        step = lm.make_decode_step(cfg, api)
        logits = None
        for i in range(10):
            _, logits, cache = step(params, prompt[:, i], cache, jnp.int32(i))
        np.testing.assert_allclose(np.asarray(logits), np.asarray(last),
                                   rtol=5e-3, atol=5e-3)


# ------------------------------------------------------- projection engine
class TestProjectionEngine:
    """Continuous-batching async engine (serving/engine): typed failure
    paths, donation invariants, dispatch-join behaviour."""

    def _eng(self, **kw):
        from repro.core import plan
        from repro.serving import ProjectionEngine
        plan.clear_cache()
        kw.setdefault("method", "sort")
        kw.setdefault("start", False)  # deterministic: drain() dispatches
        return ProjectionEngine(**kw)

    def test_pending_requests_join_one_dispatch(self):
        # continuous batching: every request queued for a key joins the
        # next dispatch for that key — one executable call for all five
        from repro.core import multilevel
        eng = self._eng()
        rng = np.random.default_rng(0)
        lv = [("inf", 1), ("1", 1)]
        ys = [jnp.asarray(rng.normal(size=(6, 10)), jnp.float32)
              for _ in range(5)]
        wants = [multilevel.multilevel_project(y, lv, 0.5 + 0.25 * i,
                                               method="sort")
                 for i, y in enumerate(ys)]  # before submit: ys get donated
        ts = [eng.submit(y, lv, radius=0.5 + 0.25 * i)
              for i, y in enumerate(ys)]
        eng.drain()
        assert eng.stats["dispatches"] == 1
        assert eng.stats["batched_requests"] == 5
        for t, want in zip(ts, wants):
            np.testing.assert_allclose(eng.result(t), want, atol=1e-5)
        eng.stop()

    @pytest.mark.parametrize("engine_method,requests,dispatches", [
        # three same-key matrices batch into one dispatch, the ℓ1 vector
        # (another plan key) into a second
        ("sort", [((6, 10), [("inf", 1), ("1", 1)], None, 0.5),
                  ((6, 10), [("inf", 1), ("1", 1)], None, 1.0),
                  ((6, 10), [("inf", 1), ("1", 1)], None, 2.0),
                  ((40,), [("1", 1)], None, 1.0)], 2),
        # michelot is an alias of filter: both requests fold to one group
        ("filter", [((3, 7), [("inf", 1), ("1", 1)], None, 1.0),
                    ((3, 7), [("inf", 1), ("1", 1)], "michelot", 1.0)], 1),
    ], ids=["heterogeneous_keys", "method_aliases"])
    def test_dispatches_follow_plan_keys(self, engine_method, requests,
                                         dispatches):
        from repro.core import multilevel
        eng = self._eng(method=engine_method)
        rng = np.random.default_rng(0)
        ys = [jnp.asarray(rng.normal(size=shape), jnp.float32)
              for shape, *_ in requests]
        wants = [multilevel.multilevel_project(y, lv, r, method="sort")
                 for y, (_, lv, _, r) in zip(ys, requests)]
        ts = [eng.submit(y, lv, radius=r, method=m)
              for y, (_, lv, m, r) in zip(ys, requests)]
        assert eng.pending() == len(requests)
        eng.drain()
        assert eng.pending() == 0
        assert eng.stats_snapshot()["dispatches"] == dispatches
        for t, want in zip(ts, wants):
            np.testing.assert_allclose(eng.result(t), want, atol=1e-5)
        eng.stop()

    def test_results_keyed_by_ticket_not_order(self):
        from repro.core import ball
        eng = self._eng()
        rng = np.random.default_rng(1)
        a, b = (jnp.asarray(rng.normal(size=(8,)), jnp.float32)
                for _ in range(2))
        want_a, want_b = ball.project_l1(a, 1.0), ball.project_l1(b, 1.0)
        ta = eng.submit(a, [("1", 1)], 1.0)
        tb = eng.submit(b, [("1", 1)], 1.0)
        eng.drain()
        np.testing.assert_allclose(eng.result(tb), want_b, atol=1e-6)
        np.testing.assert_allclose(eng.result(ta), want_a, atol=1e-6)
        eng.stop()

    @pytest.mark.parametrize("method", ["auto", "filter"])
    def test_project_matches_sort_reference(self, method):
        from repro.core import multilevel
        eng = self._eng(method=method)
        y = jnp.asarray(np.random.default_rng(3).normal(size=(5, 9)),
                        jnp.float32)
        lv = [("inf", 1), ("1", 1)]
        want = multilevel.multilevel_project(y, lv, 1.5, method="sort")
        np.testing.assert_allclose(eng.project(y, lv, 1.5), want, atol=1e-5)
        eng.stop()

    def test_group_sizes_share_a_bucket(self):
        # group sizes 3 and 4 pad to the same pow-2 bucket: the second
        # group calls the executable the first one lowered
        eng = self._eng()
        rng = np.random.default_rng(6)
        lv = [("1", 1)]
        for size in (3, 4):
            ys = [jnp.asarray(rng.normal(size=(16,)), jnp.float32)
                  for _ in range(size)]
            jax.block_until_ready(ys)
            with _Lowerings() as lowered:
                ts = [eng.submit(y, lv, 1.0) for y in ys]
                eng.drain()
                jax.block_until_ready([eng.result(t) for t in ts])
        assert lowered.n == 0
        assert eng.stats_snapshot()["dispatches"] == 2
        eng.stop()

    def test_threaded_submit_poll_result(self):
        from repro.core import ball
        eng = self._eng(start=True)
        y = jnp.asarray(np.random.default_rng(1).normal(size=(16,)),
                        jnp.float32)
        want = ball.project_l1(y, 1.0)  # before submit: y gets donated
        t = eng.submit(y, [("1", 1)], radius=1.0)
        out = eng.result(t, timeout=60.0)
        assert eng.poll(t)
        np.testing.assert_allclose(out, want, atol=1e-6)
        eng.stop()

    def test_singleton_donates_callers_buffer(self):
        # donation invariant: a singleton dispatch consumes the submitted
        # buffer (in-place projection, no payload copy)
        eng = self._eng(donate=True)
        y = jnp.asarray(np.random.default_rng(2).normal(size=(6, 10)),
                        jnp.float32)
        t = eng.submit(y, [("inf", 1), ("1", 1)], radius=1.0)
        out = eng.result(t)
        assert y.is_deleted()
        assert not out.is_deleted()
        eng.stop()

    def test_donate_false_preserves_buffers(self):
        eng = self._eng(donate=False)
        y = jnp.asarray(np.random.default_rng(3).normal(size=(6, 10)),
                        jnp.float32)
        eng.result(eng.submit(y, [("inf", 1), ("1", 1)], radius=1.0))
        assert not y.is_deleted()
        eng.stop()

    def test_queue_full_typed_rejection(self):
        from repro.serving import QueueFullError, ServingError
        eng = self._eng(max_pending=2)
        eng.submit(jnp.ones((4,)), [("1", 1)])
        eng.submit(jnp.ones((4,)), [("1", 1)])
        with pytest.raises(QueueFullError) as ei:
            eng.submit(jnp.ones((4,)), [("1", 1)])
        assert isinstance(ei.value, ServingError)  # typed, catchable family
        assert eng.stats["rejected"] == 1
        eng.stop()

    def test_deadline_expired_before_dispatch(self):
        from repro.serving import DeadlineExceededError
        eng = self._eng()
        t = eng.submit(jnp.ones((8,)), [("1", 1)], deadline=0.0)
        time.sleep(0.01)
        eng.drain()
        assert eng.stats["expired"] == 1
        with pytest.raises(DeadlineExceededError):
            eng.result(t)
        eng.stop()

    def test_failed_group_requeues_then_fails_typed(self):
        # a dispatch that raises re-queues its group; after max_attempts
        # the tickets complete exceptionally with the stored error
        from repro.serving import ServingError
        eng = self._eng(max_attempts=2)
        calls = []

        def flaky(key, plans, live):
            calls.append(len(live))
            raise RuntimeError("injected dispatch failure")

        eng._run_group = flaky
        t = eng.submit(jnp.ones((8,)), [("1", 1)])
        eng.drain()
        assert calls == [1, 1]  # original attempt + one re-queue
        assert eng.stats["requeues"] == 1 and eng.stats["failures"] == 1
        with pytest.raises(ServingError, match="injected"):
            eng.result(t)
        eng.stop()

    def test_unknown_and_discarded_ticket_raise_typed(self):
        from repro.serving import UnknownTicketError
        eng = self._eng()
        with pytest.raises(UnknownTicketError):
            eng.result(object())  # foreign handle
        t = eng.submit(jnp.ones((8,)), [("1", 1)])
        eng.discard(t)
        eng.drain()
        with pytest.raises(UnknownTicketError):
            eng.result(t)
        t2 = eng.submit(jnp.ones((8,)), [("1", 1)])
        eng.result(t2)
        with pytest.raises(UnknownTicketError):
            eng.result(t2)  # single read: second claim is unknown
        eng.stop()

    def test_batch_native_backend_routes_singleton_via_batch_plan(self):
        # codegen_batch executables take stacked buckets only: a size-1
        # group must still dispatch through the batch plan, and the
        # answer must match the reference
        from repro.core import multilevel
        eng = self._eng(method="codegen_batch", interpret=True)
        y = jnp.asarray(np.random.default_rng(5).normal(size=(6, 10)),
                        jnp.float32)
        lv = [("inf", 1), ("1", 1)]
        want = multilevel.multilevel_project(y, lv, 0.7, method="sort")
        out = eng.project(y, lv, radius=0.7)
        np.testing.assert_allclose(out, want, atol=1e-5)
        eng.stop()

    def test_bad_request_rejected_at_submit(self):
        eng = self._eng()
        with pytest.raises(ValueError):
            eng.submit(jnp.ones((4, 6, 2)), [("inf", 1), ("1", 1)])
        with pytest.raises(ValueError):
            eng.submit(jnp.ones((4,)), [("1", 1)], method="nope")
        with pytest.raises(ValueError):
            eng.submit(jnp.ones((4,)), [("1", 1)], jnp.ones((3,)))
        assert eng.pending() == 0
        eng.stop()

    def test_stop_then_submit_raises(self):
        from repro.serving import ServingError
        eng = self._eng()
        eng.stop()
        with pytest.raises(ServingError):
            eng.submit(jnp.ones((4,)), [("1", 1)])

    def test_context_manager_drains(self):
        from repro.core import ball
        from repro.serving import ProjectionEngine
        y = jnp.asarray(np.random.default_rng(6).normal(size=(16,)),
                        jnp.float32)
        want = ball.project_l1(y, 1.0)  # before submit: y gets donated
        with ProjectionEngine(method="sort") as eng:
            t = eng.submit(y, [("1", 1)], radius=1.0)
            out = eng.result(t, timeout=60.0)
        np.testing.assert_allclose(out, want, atol=1e-6)


class _Lowerings:
    """Counts the programs JAX lowers while it is open (the listener the
    serve benchmark's compiles-in-window check uses)."""

    EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"

    def __init__(self):
        self.n = 0

    def _seen(self, event, _secs, **_kw):
        if event == self.EVENT:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._seen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._seen)


class TestEngineHostRadius:
    """The radius stays on the host from submit() to dispatch: a group's
    radii cross to the device as one vector, with answers bit-identical to
    a radius placed on the device per request."""

    LV = [("inf", 1), ("1", 1)]
    # 1 + 2**-8 + 2**-30 rounds to bfloat16 through float32 (a tie, to
    # even: 1.0), not straight from float64 (1 + 2**-7)
    RADII = (0.3, 1 / 3, 1 + 2 ** -8 + 2 ** -30, 2.7)

    def _eng(self, **kw):
        from repro.core import plan
        from repro.serving import ProjectionEngine
        plan.clear_cache()
        kw.setdefault("method", "sort")
        kw.setdefault("start", False)
        return ProjectionEngine(**kw)

    @staticmethod
    def _payloads(n, seed):
        rng = np.random.default_rng(seed)
        return [rng.normal(size=(6, 10)).astype(np.float32) for _ in range(n)]

    @staticmethod
    def _bits(x):
        x = np.asarray(x)
        return x.view(np.uint16 if x.dtype.itemsize == 2 else np.uint32)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("method", ["sort", "codegen_batch"])
    def test_answers_bit_identical_to_device_radius(self, dtype, method):
        # singletons (sort: the scalar plan; codegen_batch: bucket 1) and a
        # group of three padded to bucket 4, against the same plans given
        # each radius as a device scalar of the payload's dtype
        from repro.core import plan as planmod
        interpret = method == "codegen_batch"
        eng = self._eng(method=method, interpret=interpret)
        hosts = self._payloads(len(self.RADII) + 3, seed=11)
        dev = lambda h: jnp.asarray(h, dtype)
        singles = [eng.project(dev(h), self.LV, r)
                   for h, r in zip(hosts, self.RADII)]
        group_h, group_r = hosts[len(self.RADII):], self.RADII[:3]
        ts = [eng.submit(dev(h), self.LV, r)
              for h, r in zip(group_h, group_r)]
        eng.drain()
        group = [eng.result(t) for t in ts]
        eng.stop()

        bplan = planmod.make_plan((6, 10), dtype, self.LV,
                                  radius_kind="batch", method=method,
                                  interpret=interpret)

        def fused(*args):                    # 4 payloads, then 4 radii
            out = bplan(jnp.stack(args[:4]), jnp.stack(args[4:]))
            return tuple(out[i] for i in range(4))

        zero = jnp.zeros((6, 10), dtype)
        want = jax.jit(fused)(*[dev(h) for h in group_h], zero,
                              *[jnp.asarray(r, dtype) for r in group_r],
                              jnp.zeros((), dtype))
        if method == "sort":
            splan = planmod.make_plan((6, 10), dtype, self.LV, method=method)
            want_singles = [splan(dev(h), jnp.asarray(r, dtype))
                            for h, r in zip(hosts, self.RADII)]
        else:
            one = jax.jit(lambda y, r: bplan(y[None], r[None])[0])
            want_singles = [one(dev(h), jnp.asarray(r, dtype))
                            for h, r in zip(hosts, self.RADII)]
        for got, w in zip(singles + group, want_singles + list(want)):
            assert got.dtype == dtype
            np.testing.assert_array_equal(self._bits(got), self._bits(w))

    def test_radius_checks_at_submit(self):
        eng = self._eng()
        y = jnp.asarray(self._payloads(1, seed=12)[0])
        for bad in (jnp.ones((3,)), np.ones((2,)), [0.5, 1.0]):
            with pytest.raises(ValueError, match="radius must be a scalar"):
                eng.submit(y, self.LV, bad)
        assert eng.pending() == 0
        # a 0-d jax.Array radius is fetched to the host: same answer
        h = self._payloads(1, seed=13)[0]
        a = eng.project(jnp.asarray(h), self.LV, 0.7)
        b = eng.project(jnp.asarray(h), self.LV, jnp.asarray(0.7))
        np.testing.assert_array_equal(self._bits(a), self._bits(b))
        eng.stop()

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_submit_queues_a_host_radius(self, dtype):
        eng = self._eng()
        y = jnp.asarray(self._payloads(1, seed=14)[0], dtype)
        t = eng.submit(y, self.LV, 0.3)
        (r,), = eng._queues.values()
        assert isinstance(r.radius, (np.ndarray, np.generic))
        assert not isinstance(r.radius, jax.Array)
        assert r.radius.ndim == 0 and r.radius.dtype == jnp.dtype(dtype)
        assert r.radius == np.asarray(jnp.asarray(0.3, dtype))
        eng.discard(t)
        eng.stop()

    def test_padded_group_gets_zero_pad_radii(self):
        # 3 live requests in bucket 4: one host vector of the key's dtype,
        # the live radii first, 0 in the pad slot; live answers as alone
        from repro.core import multilevel
        eng = self._eng()
        hosts = self._payloads(3, seed=15)
        radii = self.RADII[:3]
        calls = []
        fused = eng._fused_dispatch

        def spy(key, plans, b):
            fn = fused(key, plans, b)

            def call(*args):
                calls.append((b, args[b:]))
                return fn(*args)
            return call

        eng._fused_dispatch = spy
        ts = [eng.submit(jnp.asarray(h), self.LV, r)
              for h, r in zip(hosts, radii)]
        eng.drain()
        (b, (vec,)), = calls
        assert b == 4 and isinstance(vec, np.ndarray)
        assert vec.dtype == np.float32 and vec.shape == (4,)
        np.testing.assert_array_equal(
            vec, np.asarray([*radii, 0.0], np.float32))
        for t, h, r in zip(ts, hosts, radii):
            want = multilevel.multilevel_project(jnp.asarray(h), self.LV, r,
                                                 method="sort")
            np.testing.assert_allclose(eng.result(t), want, atol=1e-5)
        eng.stop()

    @pytest.mark.parametrize("method", ["sort", "codegen_batch"])
    def test_no_lowering_after_warm_up(self, method):
        # warm-up builds its dummies as submit() builds requests, so a
        # size-1 and a size-2 live group call the warmed executables
        interpret = method == "codegen_batch"
        eng = self._eng(method=method, interpret=interpret, warm_buckets=2)
        eng.prewarm((6, 10), jnp.float32, self.LV)
        eng.wait_warm(timeout=120)
        hosts = [jnp.asarray(h) for h in self._payloads(3, seed=16)]
        jax.block_until_ready(hosts)
        with _Lowerings() as lowered:
            eng.result(eng.submit(hosts[0], self.LV, 0.5))
            ts = [eng.submit(h, self.LV, 0.25) for h in hosts[1:]]
            eng.drain()
            outs = [eng.result(t) for t in ts]
        assert eng.stats["dispatches"] == 2 and eng.stats["max_group"] == 2
        assert lowered.n == 0
        jax.block_until_ready(outs)
        eng.stop()


class TestEngineObservability:
    """PR-10 serving telemetry: the stats() snapshot and its accounting
    invariant, the single monotonic clock behind every deadline, and the
    instrument=False bare path."""

    def _eng(self, **kw):
        from repro.core import plan
        from repro.serving import ProjectionEngine
        plan.clear_cache()
        kw.setdefault("method", "sort")
        kw.setdefault("start", False)
        return ProjectionEngine(**kw)

    @staticmethod
    def _accounted(s):
        return (s["completed"] + s["failed"] + s["discarded"]
                + s["queued"] + s["inflight"])

    def test_stats_dict_and_callable(self):
        # back-compat: eng.stats is the counters dict; eng.stats() is the
        # structured snapshot
        eng = self._eng()
        eng.result(eng.submit(jnp.ones((8,)), [("1", 1)]))
        assert eng.stats["dispatches"] == 1
        snap = eng.stats()
        assert snap["dispatches"] == 1 and snap["queued"] == 0
        eng.stop()

    def test_lifecycle_invariant(self):
        # pinned by stats_snapshot's docstring:
        #   completed + failed + discarded + queued + inflight == submitted
        eng = self._eng()
        lv = [("1", 1)]
        ts = [eng.submit(jnp.ones((8,)), lv) for _ in range(5)]
        s = eng.stats()
        assert s["submitted"] == 5 and s["queued"] == 5
        assert self._accounted(s) == 5
        eng.discard(ts[0])
        s = eng.stats()
        assert s["discarded"] == 1 and self._accounted(s) == 5
        eng.drain()
        s = eng.stats()
        assert s["completed"] == 4 and self._accounted(s) == 5
        # failed leg: every dispatch attempt raises -> tickets end failed
        def boom(key, plans, live):
            raise RuntimeError("injected")
        eng._run_group = boom
        eng.submit(jnp.ones((8,)), lv)
        eng.drain()
        s = eng.stats()
        assert s["failed"] == 1 and self._accounted(s) == s["submitted"] == 6
        eng.stop()

    def test_rejected_not_counted_as_submitted(self):
        from repro.serving import QueueFullError
        eng = self._eng(max_pending=1)
        eng.submit(jnp.ones((8,)), [("1", 1)])
        with pytest.raises(QueueFullError):
            eng.submit(jnp.ones((8,)), [("1", 1)])
        s = eng.stats()
        assert s["rejected"] == 1 and s["submitted"] == 1
        assert self._accounted(s) == 1
        eng.stop()

    def test_snapshot_latency_and_plan_cache(self):
        from repro.obs import metrics as obs_metrics
        reg = obs_metrics.Registry()
        prev = obs_metrics.set_registry(reg)
        try:
            eng = self._eng()
            for i in range(3):
                eng.result(eng.submit(
                    jnp.full((6, 10), float(i + 1)),
                    [("inf", 1), ("1", 1)], radius=1.0))
            snap = eng.stats()
            assert snap["latency"], "instrumented engine reports latency"
            (key, lat), = snap["latency"].items()
            assert "6x10" in key and lat["e2e_count"] == 3
            assert lat["e2e_p99_s"] >= lat["e2e_p50_s"] >= 0.0
            # bucket-interpolated: all-singleton batches estimate inside
            # the (0, 1] bucket
            assert 0.0 < snap["batch_p50"] <= 1.0
            assert snap["plan_cache"]["plans"] >= 1
            # the same series back the Prometheus export
            text = reg.to_prometheus()
            assert "serving_e2e_seconds_bucket" in text
            assert 'serving_events_total{event="completed"} 3' in text
            eng.stop()
        finally:
            obs_metrics.set_registry(prev)

    def test_instrument_false_bare_path(self):
        from repro.obs import metrics as obs_metrics
        reg = obs_metrics.Registry()
        prev = obs_metrics.set_registry(reg)
        try:
            eng = self._eng(instrument=False)
            eng.result(eng.submit(jnp.ones((8,)), [("1", 1)]))
            snap = eng.stats()
            assert snap["completed"] == 1
            assert "latency" not in snap and "batch_p50" not in snap
            assert self._accounted(snap) == 1
            # nothing was recorded into the registry by this engine
            assert not any(n.startswith("serving_")
                           for n in reg.snapshot())
            eng.stop()
        finally:
            obs_metrics.set_registry(prev)

    @staticmethod
    def _span_counts(reg):
        from repro.obs import profile as obs_profile
        fam = reg.snapshot().get(obs_profile.SPAN_METRIC)
        return {v["labels"]["span"]: v["count"]
                for v in (fam or {}).get("values", [])}

    @pytest.mark.parametrize("start", [False, True])
    def test_spans_tally_a_captured_run(self, tmp_path, start):
        from repro.obs import metrics as obs_metrics
        reg = obs_metrics.Registry()
        prev = obs_metrics.set_registry(reg)
        try:
            eng = self._eng(start=start)
            lv = [("inf", 1), ("1", 1)]
            n = 6
            eng.result(eng.submit(jnp.ones((6, 10)), lv))   # plan built
            with jax.profiler.trace(str(tmp_path)):
                ts = [eng.submit(jnp.full((6, 10), float(i + 1)), lv)
                      for i in range(n)]
                for t in ts:
                    eng.result(t, timeout=60)
            counts = self._span_counts(reg)
            assert counts["serving/submit"] == n
            assert 1 <= counts["serving/dispatch"] <= n
            assert 1 <= counts["serving/launch"] <= counts["serving/dispatch"]
            eng.stop()
        finally:
            obs_metrics.set_registry(prev)

    @pytest.mark.parametrize("instrument", [True, False])
    def test_uncaptured_run_tallies_no_span(self, instrument):
        from repro.obs import metrics as obs_metrics
        from repro.obs import profile as obs_profile
        reg = obs_metrics.Registry()
        prev = obs_metrics.set_registry(reg)
        try:
            eng = self._eng(instrument=instrument, start=True)
            ts = [eng.submit(jnp.ones((8,)), [("1", 1)]) for _ in range(4)]
            for t in ts:
                eng.result(t, timeout=60)
            eng.stop()
            assert obs_profile.SPAN_METRIC not in reg.snapshot()
            assert "serving_dispatch_seconds" not in reg.snapshot()
        finally:
            obs_metrics.set_registry(prev)

    def test_engine_source_never_reads_wall_clock(self):
        # the single-clock satellite: every engine timestamp goes through
        # the module-level ``_now`` (monotonic); wall clock is forbidden
        import inspect

        from repro.serving import engine as engmod
        src = inspect.getsource(engmod)
        assert "time.time(" not in src
        assert engmod._now is time.monotonic

    def test_wall_clock_jump_does_not_expire_deadlines(self, monkeypatch):
        # regression: an NTP step / wall-clock jump mid-flight must not
        # expire deadlines — they live on the fake-able monotonic ``_now``
        from repro.serving import DeadlineExceededError
        from repro.serving import engine as engmod
        fake = {"t": 1000.0}
        monkeypatch.setattr(engmod, "_now", lambda: fake["t"])
        eng = self._eng()
        t1 = eng.submit(jnp.ones((8,)), [("1", 1)], deadline=5.0)
        with monkeypatch.context() as mp:
            # wall clock leaps a year; monotonic advanced only 1s
            mp.setattr(time, "time", lambda: time.monotonic() + 3.2e7)
            fake["t"] += 1.0
            eng.drain()
        assert jnp.asarray(eng.result(t1)).shape == (8,)
        assert eng.stats["expired"] == 0
        # the monotonic clock alone drives expiry
        t2 = eng.submit(jnp.ones((8,)), [("1", 1)], deadline=5.0)
        fake["t"] += 10.0
        eng.drain()
        assert eng.stats["expired"] == 1
        with pytest.raises(DeadlineExceededError):
            eng.result(t2)
        eng.stop()

"""Projection planner (core.plan): correctness vs the unplanned recursion,
the multilevel edge cases the planner must validate, autotune behavior, and
plan/executable cache semantics (second call does not re-trace)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ball, bilevel, multilevel, plan

BILEVEL = [("inf", 1), ("1", 1)]
TRILEVEL = [("inf", 1), ("inf", 1), ("1", 1)]


def _rand(shape, seed=0, scale=2.0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.normal(size=shape) * scale, jnp.float32)


@pytest.fixture(autouse=True)
def _fresh_planner():
    plan.clear_cache()
    yield
    plan.clear_cache()


class TestMakePlan:
    @pytest.mark.parametrize("method", ["sort", "bisect", "filter"])
    @pytest.mark.parametrize("shape,levels", [
        ((6, 10), BILEVEL),
        ((3, 6, 10), TRILEVEL),
        ((4, 5), [("2", 1), ("1", 1)]),
    ])
    def test_matches_multilevel(self, shape, levels, method):
        y = _rand(shape, seed=hash((shape, method)) % 2**31)
        p = plan.make_plan(shape, jnp.float32, levels, method=method)
        want = multilevel.multilevel_project(y, levels, 1.5, method=method)
        np.testing.assert_allclose(p(y, 1.5), want, atol=1e-5)

    def test_auto_matches_fixed(self):
        y = _rand((6, 10), seed=1)
        p = plan.make_plan((6, 10), jnp.float32, BILEVEL, method="auto")
        assert p.method in ball.available_methods()
        assert set(p.timings_us) >= set(ball.available_methods())
        want = multilevel.multilevel_project(y, BILEVEL, 1.0, method=p.method)
        np.testing.assert_allclose(p(y, 1.0), want, atol=1e-5)

    def test_degenerate_single_level(self):
        # |ν| = 1: the plan is the classical flat projection (Prop 6.3)
        y = _rand((4, 8), seed=2)
        p = plan.make_plan((4, 8), jnp.float32, [("1", 2)], method="sort")
        want = ball.project_l1(y.reshape(-1), 1.0).reshape(4, 8)
        np.testing.assert_allclose(p(y, 1.0), want, atol=1e-6)

    @pytest.mark.parametrize("method", ["sort", "bisect", "filter"])
    def test_radius_zero_projects_to_origin(self, method):
        y = _rand((5, 7), seed=3)
        p = plan.make_plan((5, 7), jnp.float32, BILEVEL, method=method)
        np.testing.assert_allclose(p(y, 0.0), jnp.zeros((5, 7)), atol=1e-6)

    @pytest.mark.parametrize("method", ["sort", "bisect", "filter"])
    def test_ties_at_the_max(self, method):
        # a level whose ∞-reduce sees exact ties must stay exact + feasible
        y = jnp.asarray([[2.0, 2.0, -2.0], [2.0, -2.0, 2.0]], jnp.float32)
        p = plan.make_plan((2, 3), jnp.float32, BILEVEL, method=method)
        got = p(y, 1.0)
        want = multilevel.multilevel_project(y, BILEVEL, 1.0, method="sort")
        np.testing.assert_allclose(got, want, atol=1e-5)
        assert float(multilevel.multilevel_norm(got, BILEVEL)) <= 1.0 + 1e-5

    def test_batch_radius_kind(self):
        ys = jnp.stack([_rand((4, 6), seed=s) for s in range(3)])
        radii = jnp.asarray([0.5, 1.0, 2.0], jnp.float32)
        p = plan.make_plan((4, 6), jnp.float32, BILEVEL,
                           radius_kind="batch", method="sort")
        out = p(ys, radii)
        for i in range(3):
            want = multilevel.multilevel_project(ys[i], BILEVEL, radii[i],
                                                 method="sort")
            np.testing.assert_allclose(out[i], want, atol=1e-6)

    def test_validation_errors(self):
        with pytest.raises(ValueError, match="covers"):
            plan.make_plan((4, 6, 2), jnp.float32, BILEVEL)
        with pytest.raises(ValueError, match="unknown projection backend"):
            plan.make_plan((4, 6), jnp.float32, BILEVEL, method="nope")
        with pytest.raises(ValueError, match="radius_kind"):
            plan.make_plan((4, 6), jnp.float32, BILEVEL, radius_kind="maybe")
        with pytest.raises(ValueError, match="not available"):
            # generated kernel ineligible off-TPU without interpret
            plan.make_plan((4, 6), jnp.float32, BILEVEL, method="codegen")
        p = plan.make_plan((4, 6), jnp.float32, BILEVEL, method="sort")
        with pytest.raises(ValueError, match="built for shape"):
            p(jnp.zeros((4, 7)), 1.0)
        with pytest.raises(ValueError, match="built for dtype"):
            p(jnp.zeros((4, 6), jnp.bfloat16), 1.0)


class TestPlanCache:
    def test_plan_cache_hit_returns_same_object(self):
        p1 = plan.make_plan((4, 6), jnp.float32, BILEVEL, method="sort")
        p2 = plan.make_plan((4, 6), jnp.float32, BILEVEL, method="sort")
        assert p1 is p2

    def test_second_call_does_not_retrace(self):
        y = _rand((4, 6), seed=4)
        p = plan.make_plan((4, 6), jnp.float32, BILEVEL, method="sort")
        p(y, 1.0)
        assert p.trace_count == 1
        p(y, 2.0)
        p(y + 1.0, 0.5)
        assert p.trace_count == 1  # same shape/dtype: cached lowering reused

    def test_auto_shares_winner_executable(self):
        y = _rand((4, 6), seed=5)
        pa = plan.make_plan((4, 6), jnp.float32, BILEVEL, method="auto")
        traces_after_autotune = pa.trace_count
        assert traces_after_autotune == 1  # autotune itself traced it once
        pf = plan.make_plan((4, 6), jnp.float32, BILEVEL, method=pa.method)
        pa(y, 1.0)
        pf(y, 1.0)
        assert pa.trace_count == traces_after_autotune  # shared, no re-trace
        assert pf.trace_count == pa.trace_count

    def test_auto_winner_cached(self):
        pa = plan.make_plan((4, 6), jnp.float32, BILEVEL, method="auto")
        info = plan.cache_info()
        assert info["auto_winners"] == 1
        pb = plan.make_plan((4, 6), jnp.float32, BILEVEL, method="auto")
        assert pa is pb
        assert plan.cache_info()["auto_winners"] == 1

    def test_clear_cache(self):
        plan.make_plan((4, 6), jnp.float32, BILEVEL, method="sort")
        assert plan.cache_info()["plans"] == 1
        plan.clear_cache()
        info = plan.cache_info()
        assert info["plans"] == info["executables"] == 0
        assert info["auto_winners"] == 0

    def test_cache_info_hit_miss_counters(self):
        # generation counters: first build is a miss, the repeat is a hit
        plan.make_plan((4, 6), jnp.float32, BILEVEL, method="sort")
        info = plan.cache_info()
        assert info["plan_misses"] == 1 and info["plan_hits"] == 0
        plan.make_plan((4, 6), jnp.float32, BILEVEL, method="sort")
        info = plan.cache_info()
        assert info["plan_misses"] == 1 and info["plan_hits"] == 1
        # a different key is another miss, not a hit
        plan.make_plan((4, 8), jnp.float32, BILEVEL, method="sort")
        assert plan.cache_info()["plan_misses"] == 2

    def test_cache_info_retrace_counter(self):
        y = _rand((4, 6), seed=40)
        p = plan.make_plan((4, 6), jnp.float32, BILEVEL, method="sort")
        p(y, 1.0)
        p(y, 2.0)
        assert plan.cache_info()["retraces"] == 0  # jit cache held

    def test_cache_info_autotune_counters(self):
        plan.make_plan((4, 6), jnp.float32, BILEVEL, method="auto")
        info = plan.cache_info()
        assert info["autotune_runs"] == 1 and info["autotune_hits"] == 0
        # an identical repeat hits the plan memo BEFORE the winner lookup
        plan.make_plan((4, 6), jnp.float32, BILEVEL, method="auto")
        info = plan.cache_info()
        assert info["autotune_runs"] == 1 and info["autotune_hits"] == 0
        assert info["plan_hits"] == 1
        # a plan-memo miss for the same PlanKey (different donate flag)
        # reuses the cached verdict instead of re-running the shoot-out
        plan.make_plan((4, 6), jnp.float32, BILEVEL, method="auto",
                       donate=True)
        info = plan.cache_info()
        assert info["autotune_runs"] == 1 and info["autotune_hits"] == 1

    def test_evictions_cumulative_across_clear(self):
        # hit/miss counters reset with the generation; evictions are
        # Prometheus-counter cumulative (the clear IS the eviction event)
        plan.clear_cache()
        base = plan.cache_info()["evictions"]
        plan.make_plan((4, 6), jnp.float32, BILEVEL, method="sort")
        plan.make_plan((4, 6), jnp.float32, BILEVEL, method="sort")
        n_cached = (plan.cache_info()["plans"]
                    + plan.cache_info()["executables"]
                    + plan.cache_info()["auto_winners"])
        plan.clear_cache()
        info = plan.cache_info()
        assert info["evictions"] == base + n_cached
        assert info["plan_hits"] == info["plan_misses"] == 0

    def test_cache_info_mirrors_to_obs_gauge(self):
        from repro.obs import metrics as obs_metrics
        reg = obs_metrics.Registry()
        prev = obs_metrics.set_registry(reg)
        try:
            plan.make_plan((4, 6), jnp.float32, BILEVEL, method="sort")
            info = plan.cache_info()
            gauge = reg.gauge("plan_cache", labels=("stat",))
            for name, v in info.items():
                assert gauge.labels(stat=name).value == v
        finally:
            obs_metrics.set_registry(prev)


class TestAutoThreading:
    def test_multilevel_auto_eager(self):
        y = _rand((3, 6, 10), seed=6)
        got = multilevel.multilevel_project(y, TRILEVEL, 1.0, method="auto")
        want = multilevel.multilevel_project(y, TRILEVEL, 1.0, method="sort")
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_multilevel_auto_under_jit(self):
        y = _rand((6, 10), seed=7)
        fn = jax.jit(lambda y: multilevel.multilevel_project(
            y, BILEVEL, 1.0, method="auto"))
        want = multilevel.multilevel_project(y, BILEVEL, 1.0, method="sort")
        np.testing.assert_allclose(fn(y), want, atol=1e-5)

    def test_bilevel_auto(self):
        y = _rand((6, 10), seed=8)
        got = bilevel.bilevel_l1inf(y, 1.0, method="auto")
        want = bilevel.bilevel_l1inf(y, 1.0, method="sort")
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_bilevel_axes_auto(self):
        y = _rand((5, 4, 6), seed=9)
        got = bilevel.bilevel_project_axes(y, 1.0, inner_axes=(1,),
                                           method="auto")
        want = bilevel.bilevel_project_axes(y, 1.0, inner_axes=(1,),
                                            method="sort")
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_best_l1_method_is_generic(self):
        assert plan.best_l1_method(512) in ball.available_methods()

    def test_best_l1_method_excludes_specialized_backends(self):
        # a specialized backend eligible for the flat l1 key (as `codegen`
        # is on a TPU) may win make_plan's shoot-out but never
        # best_l1_method's: its callers embed the name as a theta-solver
        plan.register_plan_backend(plan.PlanBackend(
            name="fake_specialized",
            available=lambda key: key.shape == (512,),
            build=lambda key: (lambda y, radius: y)))
        try:
            plan.clear_cache()
            p = plan.make_plan((512,), jnp.float32, [("1", 1)],
                               method="auto")
            assert "fake_specialized" in p.timings_us
            assert plan.best_l1_method(512) in ball.available_methods()
        finally:
            del plan._SPECIALIZED["fake_specialized"]
            plan.clear_cache()


class TestCodegenBackendPlans:
    """The generated-kernel backend through the planner; the full equality
    matrix lives in tests/test_codegen.py."""

    def test_codegen_trilevel_via_plan(self):
        y = _rand((3, 17, 130), seed=10)
        p = plan.make_plan((3, 17, 130), jnp.float32, TRILEVEL,
                           method="codegen", interpret=True)
        want = multilevel.trilevel_l1infinf(y, 1.0, method="bisect")
        np.testing.assert_allclose(p(y, 1.0), want, atol=1e-5)

    def test_codegen_bilevel_via_plan(self):
        y = _rand((16, 130), seed=11)
        p = plan.make_plan((16, 130), jnp.float32, BILEVEL,
                           method="codegen", interpret=True)
        want = bilevel.bilevel_l1inf(y, 1.0, method="bisect")
        np.testing.assert_allclose(p(y, 1.0), want, atol=1e-5)

    def test_hand_written_backends_demoted(self):
        # the golden kernels no longer compete as planner backends
        with pytest.raises(ValueError, match="unknown projection backend"):
            plan.make_plan((16, 130), jnp.float32, BILEVEL,
                           method="fused_bilevel", interpret=True)


class TestDonationAndBatchNative:
    """Serving-facing planner features: donated executables (in-place
    projection for the engine) and batch-native backend gating."""

    def test_donating_plan_consumes_input(self):
        p = plan.make_plan((6, 10), jnp.float32, BILEVEL, method="sort",
                           donate=True)
        y = _rand((6, 10), seed=20)
        want = multilevel.multilevel_project(y, BILEVEL, 1.0, method="sort")
        out = p(y, 1.0)
        np.testing.assert_allclose(out, want, atol=1e-6)
        assert y.is_deleted()          # buffer was donated to the executable

    def test_plain_plan_preserves_input(self):
        p = plan.make_plan((6, 10), jnp.float32, BILEVEL, method="sort")
        y = _rand((6, 10), seed=21)
        p(y, 1.0)
        assert not y.is_deleted()

    def test_donating_and_plain_plans_are_distinct(self):
        a = plan.make_plan((6, 10), jnp.float32, BILEVEL, method="sort")
        b = plan.make_plan((6, 10), jnp.float32, BILEVEL, method="sort",
                           donate=True)
        assert a is not b
        assert plan.make_plan((6, 10), jnp.float32, BILEVEL,
                              method="sort", donate=True) is b

    def test_donating_batch_plan(self):
        p = plan.make_plan((6, 10), jnp.float32, BILEVEL,
                           radius_kind="batch", method="sort", donate=True)
        ys = jnp.stack([_rand((6, 10), seed=s) for s in range(3)])
        radii = jnp.asarray([0.5, 1.0, 2.0], jnp.float32)
        refs = [multilevel.multilevel_project(ys[i], BILEVEL, radii[i],
                                              method="sort")
                for i in range(3)]
        out = p(ys, radii)
        assert ys.is_deleted()
        for i in range(3):
            np.testing.assert_allclose(out[i], refs[i], atol=1e-6)

    def test_is_batch_native_registry(self):
        assert plan.is_batch_native("codegen_batch")
        assert not plan.is_batch_native("codegen")
        assert not plan.is_batch_native("sort")
        assert not plan.is_batch_native("auto")

    def test_validate_backend_radius_kind_gate(self):
        # codegen_batch validates only for batch keys
        assert plan.validate_backend((8, 16), jnp.float32, BILEVEL,
                                     "codegen_batch", interpret=True,
                                     radius_kind="batch") == "codegen_batch"
        with pytest.raises(ValueError, match="not available"):
            plan.validate_backend((8, 16), jnp.float32, BILEVEL,
                                  "codegen_batch", interpret=True,
                                  radius_kind="scalar")


class TestTrainingGradKeys:
    """grad=True plan keys: the autotuner times value_and_grad, verdicts are
    cached separately from forward keys, and the generated-kernel backend
    (which now carries its own backward) is eligible for them."""

    def test_grad_key_is_distinct_and_differentiable(self):
        fwd = plan.make_plan((6, 10), jnp.float32, BILEVEL, method="auto")
        trn = plan.make_plan((6, 10), jnp.float32, BILEVEL, method="auto",
                             grad=True)
        assert fwd is not trn
        assert fwd.key.grad is False and trn.key.grad is True
        assert trn.method in ball.available_methods()
        assert set(trn.timings_us) >= set(ball.available_methods())
        # the plan executable stays differentiable (it IS the forward)
        y = _rand((6, 10), seed=30)
        g = jax.grad(lambda v: jnp.sum(trn._exec.fn(v, jnp.float32(1.5)) ** 2))(y)
        assert np.all(np.isfinite(g))

    def test_grad_verdict_cached_per_key(self):
        a = plan.make_plan((6, 10), jnp.float32, BILEVEL, method="auto",
                           grad=True)
        b = plan.make_plan((6, 10), jnp.float32, BILEVEL, method="auto",
                           grad=True)
        assert a is b
        info = plan.cache_info()
        assert info["auto_winners"] >= 1

    def test_codegen_eligible_for_grad_keys(self):
        # fixed-backend grad key: codegen builds, and differentiating through
        # the plan matches the sort oracle (the generated backward)
        p = plan.make_plan((16, 130), jnp.float32, BILEVEL, method="codegen",
                           interpret=True, grad=True)
        y = _rand((16, 130), seed=31)
        got = jax.grad(lambda v: jnp.sum(p._exec.fn(v, jnp.float32(1.0)) ** 2))(y)
        want = jax.grad(lambda v: jnp.sum(multilevel.multilevel_project(
            v, BILEVEL, 1.0, method="sort") ** 2))(y)
        np.testing.assert_allclose(got, want, atol=1e-5)

    def test_plankey_positional_backcompat(self):
        # the grad field is trailing + defaulted: pre-existing positional
        # constructions (tests, serving) must keep meaning grad=False
        key = plan.PlanKey((16, 32), "float32", (("inf", 1), ("1", 1)),
                           "scalar", "cpu")
        assert key.grad is False and key.interpret is False

    def test_best_l1_method_grad(self):
        m = plan.best_l1_method(64, jnp.float32, grad=True)
        assert m in ball.available_methods()

    def test_sharded_backend_excluded_from_grad_keys(self):
        # _sharded_available gates grad keys out (mesh training keeps the hook)
        key = plan.PlanKey((8, 16), "float32", (("inf", 1), ("1", 1)),
                           "scalar", "cpu", False,
                           plan.ShardingKey((("d", 2),), (0, 1), (None, "d")),
                           True)
        assert not plan._SPECIALIZED["sharded"].available(key)

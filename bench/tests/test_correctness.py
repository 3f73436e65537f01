"""The comparison that decides ``correct`` fails where it must: each fault a
cell can have, planted under the timed path, and the control, the reference
one precision step below the configuration's in the program's place. Tiny
sizes on the CPU, with the limits the configuration files hold; the same
readings at the cells' own sizes come from ``calibrate.py`` on the chip."""

import time

import jax
import jax.numpy as jnp
import pytest

import harness
import load
import reference
import run
import tiny

SEED = 2 ** 31 + 77


def _measure(name, **kw):
    manifest, w, cfg, mix = tiny.cell(name)
    return run.measure(manifest, w, cfg, mix, SEED, 0.3, trace=False,
                       devices=jax.devices()[:1], t0=time.perf_counter(),
                       **kw)


def _break_step(monkeypatch, fault):
    from repro.launch import train as launch

    real = launch.make_run

    def make_run(*a, **kw):
        r = real(*a, **kw)
        step = r.step_fn

        def broken(state, batch):
            if fault == "state_unchanged":
                _, met = step(jax.tree_util.tree_map(jnp.copy, state), batch)
                return state, met
            tokens = batch["tokens"]           # half the rows, mean over them
            return step(state, {"tokens": tokens[:, : tokens.shape[1] // 2]})

        return r._replace(step_fn=broken)

    monkeypatch.setattr(launch, "make_run", make_run)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_train_fault_is_not_correct(monkeypatch, fault):
    _break_step(monkeypatch, fault)
    result, checks = _measure("train_stablelm4l_b8s256")
    assert result["correct"] is False, checks


def test_train_control_is_not_correct():
    _, w, cfg, mix = tiny.cell("train_stablelm4l_b8s256")
    batches = [load.train_tokens(mix, SEED, i, cfg["vocab_size"])[0]
               for i in range(mix["setup_steps"])]
    radius = cfg["projection"]["radius"]
    ref = reference.train_reference(cfg, cfg["optimizer"], SEED, batches,
                                    radius)
    ctl = reference.train_reference(cfg, cfg["optimizer"], SEED, batches,
                                    radius, compute=jnp.float8_e4m3fn)
    gaps = harness.driver("train")._gaps(ctl, ref)
    checks = {k: {"value": gaps[k], "limit": cfg["limits"][k]}
              for k in cfg["limits"]}
    assert not harness.judge(checks), checks


def _break_answers(monkeypatch, fault):
    from repro.serving.engine import ProjectionEngine

    real = ProjectionEngine._run_group

    def broken(self, key, plans, live):
        if fault == "unprojected":
            return [jnp.copy(r.y) for r in live]
        outs = real(self, key, plans, live)
        return [o.at[(0,) * o.ndim].add(jnp.max(jnp.abs(o)) / 4)
                for o in outs]

    monkeypatch.setattr(ProjectionEngine, "_run_group", broken)


def test_serve_compile_in_window_is_not_correct(monkeypatch):
    from repro.serving.engine import ProjectionEngine

    real = ProjectionEngine._run_group
    calls = [0]

    def recompiling(self, key, plans, live):
        calls[0] += 1                # a fresh program for every dispatch
        bump = jax.jit(lambda o, k=calls[0]: o * (1.0 + 0 * k))
        return [bump(o) for o in real(self, key, plans, live)]

    monkeypatch.setattr(ProjectionEngine, "_run_group", recompiling)
    result, checks = _measure("serve_fig1_closed64", interpret=True)
    assert checks["compiles_in_window"]["value"] > 0
    assert result["correct"] is False, checks


@pytest.mark.parametrize("fault", ["answer_altered", "unprojected"])
@pytest.mark.parametrize("name", ["serve_zipf4_steady", "serve_fig1_closed64"])
def test_serve_fault_is_not_correct(monkeypatch, fault, name):
    _break_answers(monkeypatch, fault)
    result, checks = _measure(name, interpret=True)
    assert result["correct"] is False, checks


@pytest.mark.parametrize("dtype,lower", [("float32", jnp.bfloat16),
                                         ("bfloat16", jnp.float8_e4m3fn)])
def test_serve_control_is_not_correct(dtype, lower):
    _, _, cfg, _ = tiny.cell("serve_zipf4_steady")
    bi = (("inf", 1), ("1", 1))
    y = jax.random.normal(reference.seed_key(SEED), (16, 40)).astype(dtype)
    r = float(jnp.asarray(0.1 * reference.multilevel_norm(
        y.astype(jnp.float32), bi), dtype))
    err = reference.rel_err(reference.project_stored(y, bi, r, lower),
                            reference.project(y, bi, r))
    name = f"err_{dtype}"
    assert not harness.judge(
        {name: {"value": err, "limit": cfg["limits"].get(name)}})


def test_control_rounds_forward_and_backward():
    """The control's rounding keeps a tensor far outside float8's range
    finite, and rounds the cotangent the backward pass carries too."""
    x = jnp.asarray([3.0e4, -1.7e3, 37.0, 5.0], jnp.float32)
    y = reference._round(x, jnp.float8_e4m3fn)
    assert bool(jnp.all(jnp.isfinite(y))) and not bool(jnp.all(y == x))
    assert float(jnp.max(jnp.abs(y - x) / jnp.abs(x))) < 0.07
    g = jax.grad(lambda a: jnp.sum(
        reference._round(a, jnp.float8_e4m3fn) * x))(jnp.ones(4))
    assert not bool(jnp.all(g == x))
    assert bool(jnp.all(reference._round(x, None) == x))

"""Observability overhead benchmark: what the telemetry costs, measured.

The ``obs`` section prices the PR-10 observability layer on its two hot
paths and pins the price in ``BENCH_obs_overhead.json``:

* **serving** — one ``ProjectionEngine`` request (submit → inline drain →
  claim), ``instrument=True`` vs ``instrument=False``. The instrumented
  engine performs a handful of registry operations per request (queue-depth
  gauge, queue/e2e histograms, event counters); the bare engine
  performs none. Gate: ``overhead_on`` ≤ 1.10.
* **training** — a cadence window of projected train steps (``_CADENCE``
  consecutive steps — what one telemetry period costs per step,
  steady-state), four builds of the SAME workload:

  - ``bare``            — ``telemetry_every=0`` (no telemetry code at all);
  - ``compiled_out``    — telemetry requested but traced with the bridge
    DISABLED. ``obs.jax_bridge``'s gate is trace-time static, so this
    lowers to a bit-identical program — the measured overhead is pure
    noise. Gate: ``overhead_off`` ≤ 1.02;
  - ``on``              — ``telemetry_every=_CADENCE`` traced with the
    bridge ENABLED: loss/grad-norm/sparsity/feasibility callbacks fire
    once per window inside the cadence ``lax.cond``. Gate:
    ``overhead_on`` ≤ 1.10;
  - ``marks``           — ``telemetry_marks=True`` on top: the ordered
    epilogue mark pair serializes a host round-trip into EVERY step.
    Priced, NOT gated — marks are the documented opt-in deep-dive tool
    (``host callbacks on CPU cost O(100µs) each; ordering forbids riding
    the cadence cond``), not part of the default telemetry configuration.

Timing is interleaved min-of-rounds (the repo's standard estimator:
container CPU contention only ever inflates a round, so the min is stable,
and interleaving decorrelates slow spells across the compared sides); each
round ends with ``jax.effects_barrier()`` so one side's in-flight
callbacks never bleed into the next side's measurement.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.types import ProjectionSpec, TrainConfig
from repro.obs import jax_bridge
from repro.training import make_train_step

BILEVEL = (("inf", 1), ("1", 1))

_ROUNDS = 9
_CADENCE = 10   # the telemetry period the "on" rows amortize over


def _interleaved_min(named_fns, rounds=_ROUNDS, warmup=2):
    """min-of-rounds µs per side, sides interleaved within every round."""
    for _, fn in named_fns:
        for _ in range(warmup):
            fn()
        jax.effects_barrier()
    best = {name: float("inf") for name, _ in named_fns}
    for _ in range(rounds):
        for name, fn in named_fns:
            t0 = time.perf_counter()
            fn()
            jax.effects_barrier()
            best[name] = min(best[name], (time.perf_counter() - t0) * 1e6)
    return best


# ----------------------------------------------------------------- serving

def _engine_round(eng, payloads, levels):
    tks = [eng.submit(y, levels, radius=1.0) for y in payloads]
    eng.drain()
    for tk in tks:
        jax.block_until_ready(eng.result(tk))


def engine_overhead(shape=(32, 64), k=8):
    """Per-request µs, instrumented vs bare engine, same plans/payloads."""
    from repro.serving import ProjectionEngine

    rng = np.random.default_rng(3)
    payload = lambda: jnp.asarray(rng.normal(size=shape), jnp.float32)
    levels = list(BILEVEL)
    engines = {
        "bare": ProjectionEngine(method="sort", instrument=False,
                                 start=False),
        "instrumented": ProjectionEngine(method="sort", start=False),
    }
    try:
        for eng in engines.values():
            eng.prewarm(shape, jnp.float32, levels)
            eng.wait_warm(timeout=300.0)
        best = _interleaved_min([
            (name, lambda e=eng: _engine_round(
                e, [payload() for _ in range(k)], levels))
            for name, eng in engines.items()])
    finally:
        for eng in engines.values():
            eng.stop()
    return best["bare"] / k, best["instrumented"] / k


# ---------------------------------------------------------------- training

def _train_setup():
    """A projected training workload (fused epilogue path), sized so one
    bare step takes tens of ms on the container — the scale where the
    telemetry's fixed per-step cost (effectful jits dispatch through the
    slow Python path: ~2 ms/call on CPU) is priced against a step that is
    at least the size of any real training step, not a toy."""
    rng = np.random.default_rng(11)
    shapes = {"w_up": (16, 256, 512), "w_gate": (1024, 512),
              "w_skip": (256, 64)}
    params = {name: jnp.asarray(rng.normal(size=s) * 0.5, jnp.float32)
              for name, s in shapes.items()}
    spec = ProjectionSpec(pattern=r"w_up|w_gate", levels=list(BILEVEL),
                          radius=1.0, method="bisect")
    tcfg = TrainConfig(lr=1e-3, warmup=1, total_steps=100, microbatch=4,
                       master_dtype="", projection=spec)

    def loss_fn(p, x):
        acts = sum(jnp.sum(w.astype(jnp.float32) ** 2) for w in
                   jax.tree_util.tree_leaves(p))
        return acts * jnp.mean(x.astype(jnp.float32) ** 2)

    from repro.optim import adamw

    state = {"params": params, "opt": adamw.init(params, tcfg)}
    batch = {"tokens": jnp.asarray(rng.normal(size=(2, 4, 8)), jnp.float32)}
    return tcfg, loss_fn, state, batch


def train_overhead():
    """Per-step µs over one telemetry period, the four builds."""
    tcfg, loss_fn, state, batch = _train_setup()

    def build(telemetry_every, bridge_on, marks=False):
        with jax_bridge.enabled_scope(bridge_on):
            fn = jax.jit(make_train_step(
                None, tcfg, None, telemetry_every=telemetry_every,
                telemetry_marks=marks, loss_fn=loss_fn))
            jax.block_until_ready(fn(state, batch))   # trace under the gate
        return fn

    steps = {
        "bare": build(0, False),
        "compiled_out": build(_CADENCE, False, marks=True),
        "on": build(_CADENCE, True),
        "marks": build(_CADENCE, True, marks=True),
    }
    # the rigorous form of the overhead-off claim: a bridge-disabled trace
    # lowers to the very same program, so the measured ratio is pure noise
    with jax_bridge.enabled_scope(False):
        hlo_identical = (
            steps["bare"].lower(state, batch).as_text()
            == steps["compiled_out"].lower(state, batch).as_text())

    def window(fn):
        # one full telemetry period, threading the state so the step
        # counter advances through the cadence cond's firing step
        s = state
        for _ in range(_CADENCE):
            s, _m = fn(s, batch)
        jax.block_until_ready(s["opt"]["step"])

    # callbacks must run under an enabled bridge so the host side actually
    # records (measuring the full cost, not a dropped payload)
    with jax_bridge.enabled_scope(True):
        best = _interleaved_min(
            [(name, lambda f=fn: window(f)) for name, fn in steps.items()],
            warmup=1)
    out = {name: us / _CADENCE for name, us in best.items()}
    out["hlo_identical"] = hlo_identical
    return out


def obs_sweep(full=False):
    """The ``obs`` benchmark section (BENCH_obs_overhead.json)."""
    del full  # one scale: the gated quantities are ratios, machine cancels
    bare_rq, instr_rq = engine_overhead()
    t = train_overhead()
    r_engine = instr_rq / bare_rq
    r_off = t["compiled_out"] / t["bare"]
    r_on = t["on"] / t["bare"]
    r_marks = t["marks"] / t["bare"]
    return [
        ("obs_engine_request_bare", bare_rq, "instrument=False"),
        ("obs_engine_request_instrumented", instr_rq,
         f"bare_us={bare_rq:.1f},overhead_on={r_engine:.3f}"),
        ("obs_train_step_bare", t["bare"], "telemetry_every=0"),
        ("obs_train_step_telemetry_compiled_out", t["compiled_out"],
         f"bare_us={t['bare']:.1f},overhead_off={r_off:.3f},"
         f"hlo_identical={'yes' if t['hlo_identical'] else 'no'}"),
        ("obs_train_step_telemetry_on", t["on"],
         f"bare_us={t['bare']:.1f},cadence={_CADENCE},"
         f"overhead_on={r_on:.3f}"),
        ("obs_train_step_telemetry_marks", t["marks"],
         f"bare_us={t['bare']:.1f},marks_overhead={r_marks:.3f},"
         f"gated=no"),
    ]

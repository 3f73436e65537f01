"""Timing benchmarks for the paper's figures (1, 2, 3) and Table 1.

All candidates are jitted; we time steady-state (post-compile) medians on this
container's single CPU core. The paper's absolute numbers are C++/i9 — what
must reproduce is the *ordering and scaling*: bi-level ≥2.5× faster than the
exact (Chu-style semismooth Newton) projection, flat in the radius, linear in
nm; tri-level linear in m.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (available_methods, bilevel_l1inf, project_l1,
                        project_l1inf_exact, multilevel_project,
                        trilevel_l111, trilevel_l1infinf)

from .devices import run_cpu_mesh_child


def _time(fn, *args, reps=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6  # µs


def fig1_radius(rows=(), full=False):
    """Paper Fig 1: time vs radius, matrix 1000×10000 (scaled down unless full)."""
    n, m = (1000, 10000) if full else (500, 2000)
    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.uniform(0, 1, (n, m)), jnp.float32)
    bl = jax.jit(lambda y, r: bilevel_l1inf(y, r))
    ex = jax.jit(lambda y, r: project_l1inf_exact(y, r))
    out = []
    for radius in (0.25, 0.5, 1.0, 2.0, 4.0):
        r = jnp.float32(radius)
        t_bl = _time(bl, y, r)
        t_ex = _time(ex, y, r)
        out.append((f"fig1_bilevel_l1inf_eta{radius}", t_bl,
                    f"speedup_vs_exact={t_ex / t_bl:.2f}"))
        out.append((f"fig1_exact_chu_eta{radius}", t_ex, f"n={n},m={m}"))
    return out


def fig2_size(full=False):
    """Paper Fig 2: time vs matrix size (m=1000, η=1 fixed)."""
    ns = (1000, 2000, 5000, 10000) if full else (250, 500, 1000, 2000)
    m = 1000 if full else 500
    rng = np.random.default_rng(1)
    bl = jax.jit(lambda y: bilevel_l1inf(y, 1.0))
    ex = jax.jit(lambda y: project_l1inf_exact(y, 1.0))
    out = []
    for n in ns:
        y = jnp.asarray(rng.uniform(0, 1, (n, m)), jnp.float32)
        t_bl = _time(bl, y)
        t_ex = _time(ex, y)
        out.append((f"fig2_bilevel_n{n}", t_bl,
                    f"speedup_vs_exact={t_ex / t_bl:.2f}"))
        out.append((f"fig2_exact_n{n}", t_ex, f"m={m}"))
    return out


def fig3_trilevel(full=False):
    """Paper Fig 3: tri-level time vs m (d=32, n=1000 fixed)."""
    d, n = (32, 1000) if full else (8, 250)
    ms = (250, 500, 1000, 2000) if full else (64, 128, 256, 512)
    rng = np.random.default_rng(2)
    t_inf = jax.jit(lambda y: trilevel_l1infinf(y, 1.0))
    t_111 = jax.jit(lambda y: trilevel_l111(y, 1.0))
    out = []
    for m in ms:
        y = jnp.asarray(rng.uniform(0, 1, (d, n, m)), jnp.float32)
        out.append((f"fig3_tri_l1infinf_m{m}", _time(t_inf, y, reps=3), f"d={d},n={n}"))
        out.append((f"fig3_tri_l111_m{m}", _time(t_111, y, reps=3), f"d={d},n={n}"))
    return out


def methods_sweep(full=False):
    """ℓ1 backend shoot-out: sort vs bisect vs filter over the fig2 size sweep.

    Two workload shapes per (n, m):

    * ``flat``  — one vector of n·m entries (the outer-step / Prop 6.3 shape);
      the largest default size already has n·m = 1e6, where the linear-time
      filter backend must beat sort by >= 1.5x on CPU (CI asserts the artifact).
    * ``batch`` — m vectors of length n with per-vector radii (the q = 1 inner
      step of the bi-/multi-level projections).
    """
    ns = (1000, 2000, 5000, 10000) if full else (250, 500, 1000, 2000)
    m = 1000 if full else 500
    rng = np.random.default_rng(4)
    methods = available_methods()
    out = []
    for n in ns:
        flat = jnp.asarray(rng.uniform(0, 1, (n * m,)), jnp.float32)
        batch = jnp.asarray(rng.uniform(0, 1, (m, n)), jnp.float32)
        radii = jnp.full((m,), 1.0, jnp.float32)
        for kind, y, r in (("flat", flat, 1.0), ("batch", batch, radii)):
            times = {}
            for method in methods:
                fn = jax.jit(lambda v, method=method, r=r:
                             project_l1(v, r, method=method))
                times[method] = _time(fn, y, reps=3)
            for method in methods:
                out.append((
                    f"methods_{kind}_{method}_n{n}", times[method],
                    f"nm={n * m},speedup_vs_sort={times['sort'] / times[method]:.2f}",
                ))
    return out


def plan_sweep(full=False):
    """Planner autotune sweep: auto vs every fixed backend, cold vs warm cache.

    Per workload (bi-level matrix, tri-level tensor, flat vector):

    * ``plan_cold_*``    — wall time of the FIRST ``make_plan`` + call with
      ``method="auto"`` (includes micro-benchmarking every candidate and
      jitting the winner) — the one-time cost a served workload amortizes.
    * ``plan_auto_*``    — steady-state of the autotuned plan. The acceptance
      bar: ``auto_vs_best`` ≤ 1.05 (auto is never >5% slower than the best
      fixed backend — it shares the winner's cached executable, so any gap is
      timer noise).
    * ``plan_fixed_*``   — steady-state of each fixed-method plan.
    * ``plan_warm_*``    — wall time of a repeat ``make_plan`` (cache hit:
      no autotune, no re-trace; microseconds).
    """
    from repro.core import plan as planmod

    n, m = (1000, 4000) if full else (400, 1000)
    d = 8
    workloads = [
        ("bilevel_l1inf", (n, m), [("inf", 1), ("1", 1)]),
        ("trilevel_l1infinf", (d, n // 4, m), [("inf", 1), ("inf", 1), ("1", 1)]),
        ("flat_l1", (n * m,), [("1", 1)]),
    ]
    rng = np.random.default_rng(5)
    out = []
    for wname, shape, levels in workloads:
        y = jnp.asarray(rng.uniform(0, 1, shape), jnp.float32)
        planmod.clear_cache()
        t0 = time.perf_counter()
        p = planmod.make_plan(shape, jnp.float32, levels)
        jax.block_until_ready(p(y, 1.0))
        cold = (time.perf_counter() - t0) * 1e6
        # Time each *backend executable* once, interleaved min-of-rounds.
        # Plans with the same resolved ``.method`` share one cached jitted
        # executable (that is the planner's cache contract), so they must get
        # the same number — timing the auto plan and the same-method fixed
        # plan in separate blocks folds scheduler noise and machine drift
        # into the auto_vs_best ratio instead of backend choice.
        for attempt in range(2):
            plans = {"auto": p}
            for meth in available_methods():
                plans[meth] = planmod.make_plan(shape, jnp.float32, levels,
                                                method=meth)
            backends = {fp.method: fp for fp in plans.values()}
            for fp in backends.values():
                for _ in range(2):
                    jax.block_until_ready(fp(y, 1.0))
            bt = dict.fromkeys(backends, float("inf"))
            for _ in range(25):
                for bname, fp in backends.items():
                    t0 = time.perf_counter()
                    jax.block_until_ready(fp(y, 1.0))
                    bt[bname] = min(bt[bname],
                                    (time.perf_counter() - t0) * 1e6)
            times = {name: bt[fp.method] for name, fp in plans.items()}
            t_auto = times.pop("auto")
            best_name = min(times, key=times.get)
            best = times[best_name]
            if t_auto <= 1.05 * best or attempt:
                break
            # the autotune verdict is process-permanent and was taken in the
            # (noisy) cold window; one bounded re-tune before reporting, so a
            # shared CI runner's load spike cannot fail the gate alone
            planmod.clear_cache()
            p = planmod.make_plan(shape, jnp.float32, levels)
        # cold row emitted AFTER the attempt loop so its winner always agrees
        # with the plan_auto_* row (a re-tune may change it)
        out.append((f"plan_cold_{wname}", cold,
                    f"winner={p.method},candidates={len(p.timings_us)}"))
        out.append((f"plan_auto_{wname}", t_auto,
                    f"winner={p.method},best_fixed={best_name},"
                    f"auto_vs_best={t_auto / best:.3f}"))
        for meth, t in times.items():
            out.append((f"plan_fixed_{meth}_{wname}", t,
                        f"vs_auto={t / t_auto:.2f}"))
        t0 = time.perf_counter()
        planmod.make_plan(shape, jnp.float32, levels)
        warm = (time.perf_counter() - t0) * 1e6
        out.append((f"plan_warm_{wname}", warm, "plan_cache=hit"))
    return out


_SHARDED_CHILD = r"""
import json, sys, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import (multilevel_project, multilevel_project_sharded,
                        sharded_collective_bytes)

FULL = json.loads(sys.argv[1])
def _time(fn, *args, reps=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts)) * 1e6

from repro.launch.mesh import auto_mesh
mesh = auto_mesh((8,), ("model",))
n, m = (1000, 10000) if FULL else (256, 2048)
d = 32 if FULL else 8
designs = [
    ("bilevel_l1inf",    (n, m),      [("inf",1),("1",1)],          P(None, "model")),
    ("trilevel_l1infinf",(d, n//4, m),[("inf",1),("inf",1),("1",1)],P(None, None, "model")),
    ("bilevel_l12_axis0",(m, n),      [("2",1),("1",1)],            P("model", None)),
]
rows = []
rng = np.random.default_rng(7)
for name, shape, levels, spec in designs:
    y = jnp.asarray(rng.uniform(0, 1, shape), jnp.float32)
    ys = jax.device_put(y, NamedSharding(mesh, spec))
    sched_fn = jax.jit(lambda v, r, levels=levels, spec=spec:
                       multilevel_project_sharded(v, levels, r, mesh=mesh,
                                                  spec=spec, method="sort"))
    gather_fn = jax.jit(lambda v, r, levels=levels:
                        multilevel_project(v, levels, r, method="sort"),
                        out_shardings=NamedSharding(mesh, spec))
    r = jnp.float32(2.0)
    diff = float(jnp.abs(sched_fn(ys, r) - gather_fn(ys, r)).max())
    assert diff < 1e-4, (name, diff)
    t_sched = _time(sched_fn, ys, r)
    t_gather = _time(gather_fn, ys, r)
    cb = sharded_collective_bytes(shape, levels, spec, mesh)
    rows.append([f"sharded_schedule_{name}", t_sched,
                 f"coll_bytes={cb['schedule_bytes']},"
                 f"bytes_ratio={cb['ratio']:.0f}x,"
                 f"speedup_vs_gather={t_gather / t_sched:.2f}"])
    rows.append([f"sharded_gather_{name}", t_gather,
                 f"coll_bytes={cb['gather_bytes']},shape={shape}"])
    per = ";".join(f"{s['step']}:{s['bytes']}" for s in cb["per_step"])
    rows.append([f"sharded_bytes_{name}", float(cb["schedule_bytes"]), per])
"""


def sharded_sweep(full=False):
    """``--only sharded``: the generalized DESIGN.md §3 argument, measured.

    Runs in a subprocess with a forced 8-device host mesh (the parent process
    must keep its single device). Per norm design: steady-state wall-clock of
    the schedule executor vs. jitted gather-and-project (GSPMD) on the same
    committed sharded input, plus the analytic per-level collective payload
    of both — the ``bytes_ratio`` is the aggregated-extent factor of
    Proposition 6.4. The ``sharded_bytes_*`` rows carry the per-step payload
    breakdown in ``derived``.
    """
    import json as _json

    return run_cpu_mesh_child(_SHARDED_CHILD, _json.dumps(bool(full)),
                              timeout=1200)


_SHARDED_CODEGEN_CHILD = r"""
import json, sys, time
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core import multilevel_project_sharded, plan as planmod

FULL = json.loads(sys.argv[1])
from repro.launch.mesh import auto_mesh
mesh = auto_mesh((8,), ("model",))
n, m = (1000, 10000) if FULL else (128, 1024)
d = 8
designs = [
    ("bilevel_l1inf",     (n, m),       [("inf",1),("1",1)],
     P(None, "model")),
    ("trilevel_l1infinf", (d, n//8, m), [("inf",1),("inf",1),("1",1)],
     P(None, None, "model")),
    ("bilevel_l11_fin",   (n, m//2),    [("1",1),("1",1)],
     P("model", None)),
]
rows = []
rng = np.random.default_rng(13)
for name, shape, levels, spec in designs:
    y = jnp.asarray(rng.uniform(0, 1, shape), jnp.float32)
    ys = jax.device_put(y, NamedSharding(mesh, spec))
    r = jnp.float32(2.0)
    fns = {
        "fused": jax.jit(lambda v, rr, levels=levels, spec=spec:
                         multilevel_project_sharded(
                             v, levels, rr, mesh=mesh, spec=spec,
                             backend="codegen", interpret=True)),
        "jnp": jax.jit(lambda v, rr, levels=levels, spec=spec:
                       multilevel_project_sharded(v, levels, rr, mesh=mesh,
                                                  spec=spec)),
    }
    diff = float(jnp.abs(fns["fused"](ys, r) - fns["jnp"](ys, r)).max())
    assert diff < 1e-5, (name, diff)
    for fn in fns.values():
        for _ in range(2):
            jax.block_until_ready(fn(ys, r))
    best = dict.fromkeys(fns, float("inf"))
    for _ in range(10):
        for key, fn in fns.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fn(ys, r))
            best[key] = min(best[key], (time.perf_counter() - t0) * 1e6)
    rows.append([f"sharded_codegen_fused_{name}", best["fused"],
                 f"vs_jnp={best['fused'] / best['jnp']:.3f},interpret=True"])
    rows.append([f"sharded_codegen_jnpbody_{name}", best["jnp"],
                 f"shape={shape}"])

# method="auto" on the sharded key: the fused backend competes, and the auto
# plan must sit within 5% of the best fixed backend (bounded re-tune, like
# plan_sweep: the verdict is process-permanent and the cold window is noisy)
name, shape, levels, spec = designs[0]
y = jnp.asarray(rng.uniform(0, 1, shape), jnp.float32)
ys = jax.device_put(y, NamedSharding(mesh, spec))
sharding = ys.sharding
for attempt in range(2):
    planmod.clear_cache()
    p = planmod.make_plan(shape, jnp.float32, levels, sharding=sharding,
                          interpret=True)
    fixed = {}
    for meth in ("sharded", "sharded_codegen", "sort", "bisect"):
        fixed[meth] = planmod.make_plan(shape, jnp.float32, levels,
                                        sharding=sharding, interpret=True,
                                        method=meth)
    cands = dict(fixed, auto=p)
    execs = {fp.method: fp for fp in cands.values()}
    for fp in execs.values():
        for _ in range(2):
            jax.block_until_ready(fp(ys, 2.0))
    bt = dict.fromkeys(execs, float("inf"))
    for _ in range(15):
        for bname, fp in execs.items():
            t0 = time.perf_counter()
            jax.block_until_ready(fp(ys, 2.0))
            bt[bname] = min(bt[bname], (time.perf_counter() - t0) * 1e6)
    t_auto = bt[p.method]
    best_name = min(fixed, key=lambda k: bt[fixed[k].method])
    t_best = bt[fixed[best_name].method]
    if t_auto <= 1.05 * t_best or attempt:
        break
rows.append([f"sharded_codegen_plan_auto_{name}", t_auto,
             f"winner={p.method},best_fixed={best_name},"
             f"auto_vs_best={t_auto / t_best:.3f}"])
"""


def sharded_codegen_sweep(full=False):
    """``--only sharded_codegen``: the fused shard-local stages, measured.

    Subprocess with a forced 8-device CPU mesh (interpret-mode kernels, like
    ``codegen_sweep`` off-TPU — absolute µs are meaningless, the artifact
    asserts structural ratios that CI gates against the committed copy):

    * ``sharded_codegen_fused_*`` — the ``backend="codegen"`` schedule body
      vs the reference jnp body on the same committed sharded input; the
      ``vs_jnp`` ratio is the fusion overhead/gain and must stay within
      1.25x of the committed artifact's ratio.
    * ``sharded_codegen_plan_auto_*`` — ``method="auto"`` on the sharded key
      with the fused backend competing: auto within 5% of the best fixed
      backend (bounded re-tune, plan_sweep protocol).
    * ``sharded_codegen_blocktune_*`` (parent process, single device) — the
      measured block-size autotuner: the tuned plan within 5% of the best
      fixed block of the candidate grid.
    """
    import json as _json

    rows = run_cpu_mesh_child(_SHARDED_CODEGEN_CHILD, _json.dumps(bool(full)),
                              timeout=1800)
    return rows + blocktune_rows(full)


def blocktune_rows(full=False):
    """Measured block-size autotuner rows (single device, interpret mode)."""
    from repro.core.schedule import compile_schedule
    from repro.kernels import codegen
    from repro.kernels.codegen.tiling import candidate_tile_plans

    n, m = (1000, 10000) if full else (256, 2048)
    workloads = [
        ("bilevel_l1inf", (n, m), [("inf", 1), ("1", 1)]),
        ("trilevel_l1infinf", (8, n // 8, m),
         [("inf", 1), ("inf", 1), ("1", 1)]),
    ]
    rng = np.random.default_rng(17)
    out = []
    for name, shape, levels in workloads:
        y = jnp.asarray(rng.uniform(0, 1, shape), jnp.float32)
        r = jnp.float32(2.0)
        sched = compile_schedule(shape, levels)
        cands = candidate_tile_plans(sched, jnp.float32)
        fns = {tp: jax.jit(codegen.build(shape, levels, jnp.float32,
                                         interpret=True, tile_plan=tp))
               for tp in cands}
        for fn in fns.values():
            for _ in range(2):
                jax.block_until_ready(fn(y, r))
        for attempt in range(2):
            best = dict.fromkeys(fns, float("inf"))
            for _ in range(8):
                for tp, fn in fns.items():
                    t0 = time.perf_counter()
                    jax.block_until_ready(fn(y, r))
                    best[tp] = min(best[tp], (time.perf_counter() - t0) * 1e6)
            codegen.clear_tile_cache()
            tuned = codegen.autotune_tiles(shape, levels, jnp.float32,
                                           interpret=True, measure=True)
            t_tuned, t_best = best[tuned], min(best.values())
            if t_tuned <= 1.05 * t_best or attempt:
                break
        out.append((
            f"sharded_codegen_blocktune_{name}", t_tuned,
            f"tuned_vs_best={t_tuned / t_best:.3f},"
            f"n_candidates={len(cands)},"
            f"block={tuned.block_n}x{tuned.block_m}"))
    return out


def codegen_sweep(full=False):
    """``--only codegen``: generated fused kernels vs the hand-written golden
    kernels vs the jnp schedule path, on the golden kernels' home workloads.

    Off-TPU both kernel paths run in Pallas interpret mode, so the absolute
    µs are meaningless there — what the artifact asserts is the *structural
    parity* ``vs_hand`` ratio (generated and golden kernels lower to the same
    reduce → θ-solve → apply pipeline, so the generated one must sit within
    10% of the hand-written on its home design). On TPU the same rows measure
    real kernels and ``vs_jnp`` becomes the fusion speedup. Candidates are
    timed interleaved min-of-rounds (the autotuner's protocol) so machine
    drift lands on all three equally instead of inside the ratio.
    """
    import functools

    from repro.kernels import codegen
    from repro.kernels.bilevel_l1inf import bilevel_l1inf_pallas
    from repro.kernels.trilevel_l1infinf import trilevel_l1infinf_pallas

    interpret = jax.devices()[0].platform != "tpu"
    n, m = (1000, 10000) if full else (256, 1024)
    d = 8
    workloads = [
        ("bilevel_l1inf", (n, m), [("inf", 1), ("1", 1)],
         bilevel_l1inf_pallas),
        ("trilevel_l1infinf", (d, n // 4, m),
         [("inf", 1), ("inf", 1), ("1", 1)], trilevel_l1infinf_pallas),
    ]
    rng = np.random.default_rng(9)
    out = []
    for name, shape, levels, hand in workloads:
        y = jnp.asarray(rng.uniform(0, 1, shape), jnp.float32)
        r = jnp.float32(2.0)
        fns = {
            "generated": jax.jit(codegen.build(
                shape, levels, jnp.float32, method="bisect",
                interpret=interpret)),
            "hand": jax.jit(functools.partial(hand, method="bisect",
                                              interpret=interpret)),
            "jnp": jax.jit(lambda v, rr, levels=levels: multilevel_project(
                v, levels, rr, method="bisect")),
        }
        diff = float(jnp.abs(fns["generated"](y, r) - fns["hand"](y, r)).max())
        assert diff < 1e-5, (name, diff)
        for fn in fns.values():
            for _ in range(2):
                jax.block_until_ready(fn(y, r))
        best = dict.fromkeys(fns, float("inf"))
        for _ in range(20):
            for key, fn in fns.items():
                t0 = time.perf_counter()
                jax.block_until_ready(fn(y, r))
                best[key] = min(best[key], (time.perf_counter() - t0) * 1e6)
        out.append((f"codegen_generated_{name}", best["generated"],
                    f"vs_hand={best['generated'] / best['hand']:.3f},"
                    f"vs_jnp={best['generated'] / best['jnp']:.2f},"
                    f"interpret={interpret}"))
        out.append((f"codegen_hand_{name}", best["hand"], f"shape={shape}"))
        out.append((f"codegen_jnp_{name}", best["jnp"], f"shape={shape}"))
    return out


def table1_scaling(full=False):
    """Empirical complexity fit (Table 1): log-log slope of time vs nm."""
    sizes = ((200, 200), (400, 400), (800, 800), (1600, 1600)) if not full \
        else ((500, 500), (1000, 1000), (2000, 2000), (4000, 4000))
    rng = np.random.default_rng(3)
    bl = jax.jit(lambda y: bilevel_l1inf(y, 1.0))
    ex = jax.jit(lambda y: project_l1inf_exact(y, 1.0))
    t_bl, t_ex, nm = [], [], []
    for n, m in sizes:
        y = jnp.asarray(rng.uniform(0, 1, (n, m)), jnp.float32)
        t_bl.append(_time(bl, y, reps=3))
        t_ex.append(_time(ex, y, reps=3))
        nm.append(n * m)
    s_bl = np.polyfit(np.log(nm), np.log(t_bl), 1)[0]
    s_ex = np.polyfit(np.log(nm), np.log(t_ex), 1)[0]
    return [
        ("table1_bilevel_scaling_exponent", t_bl[-1],
         f"loglog_slope={s_bl:.2f}_theory=1.0"),
        ("table1_exact_scaling_exponent", t_ex[-1],
         f"loglog_slope={s_ex:.2f}_theory>=1.0"),
    ]


def fig4_parallel():
    """Paper Fig 4 analogue — the parallel decomposition on a mesh.

    No multi-core wall-clock exists in this container; we report the paper's
    own complexity model (work/depth from Prop 6.4) and the collective-bytes
    ratio of the sharded bi-level projection vs a gathered exact projection
    (the factor-n traffic reduction of DESIGN.md §3).
    """
    from repro.core.multilevel import work_depth
    out = []
    n, m = 1000, 10000
    work, depth = work_depth((n, m), [(jnp.inf, 1), (1, 1)])
    for workers in (1, 2, 4, 8, 12, 64, 256):
        t_par = work / workers + depth
        out.append((f"fig4_modelled_gain_w{workers}", t_par,
                    f"gain={work / t_par:.1f}x_ideal={workers}"))
    # collective traffic: sharded bi-level moves m floats; gathered exact n*m
    out.append(("fig4_coll_bytes_bilevel_sharded", m * 4, "all_gather_of_colnorms"))
    out.append(("fig4_coll_bytes_exact_gathered", n * m * 4,
                f"ratio={n}x_prop6.4"))
    return out
